"""ISSUE 16 acceptance: continuous-batching autoregressive decode —
incremental KV-cache parity with full-sequence greedy, bitwise stability
across batch compositions, per-token join/leave with slot recycling,
compile-once per (batch_bucket, len_bucket) with a plan-cache-hit steady
state, the ``decode-incompatible-op`` lint, decode trace spans/flows,
and tp-sharded decode through a searched ParallelPlan.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from hetu_tpu import metrics, obs                         # noqa: E402
from hetu_tpu.models import GPT2Config, gpt2_decode_graph  # noqa: E402
from hetu_tpu.models.gpt2 import gpt2_lm_graph             # noqa: E402
from hetu_tpu.profiler import HetuProfiler                 # noqa: E402
from hetu_tpu.serving import (DecodeEngine, DecodeRouter,  # noqa: E402
                              InferenceExecutor, ServeRejected)

_CFG = GPT2Config.tiny(n_positions=64, batch_size=1, seq_len=16)
_MAX_LEN = 16


@pytest.fixture(scope="module")
def decode_graph():
    """One tiny decode graph shared by the module (weight init is
    seed-deterministic, so every engine over it serves identical
    weights)."""
    return gpt2_decode_graph(_CFG, max_len=_MAX_LEN)


def _engine(decode_graph, **kw):
    feeds, logits, caches, _layers = decode_graph
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", _MAX_LEN)
    return DecodeEngine(feeds, logits, caches, seed=0, **kw)


# ----------------------------------------------------- correctness / parity

def test_decode_matches_full_sequence_greedy(decode_graph):
    """The tentpole correctness claim: one-token-at-a-time decode over
    the incremental KV cache produces EXACTLY the token stream of greedy
    re-prefill with the full-sequence training graph (same weights BY
    NAME)."""
    eng = _engine(decode_graph, max_slots=2)
    w = {eng.iex.var_names[n]: np.asarray(eng.iex.params[eng.iex._k(n)])
         for n in eng.iex.var_nodes}
    f2, _loss, logits2 = gpt2_lm_graph(_CFG)
    iex_full = InferenceExecutor([logits2], weights=w, buckets=(1,),
                                 seed=0, validate="off")
    fn_full = iex_full.compiled(1)
    prompt, max_new = [5, 9, 13], 8
    seq, ref = list(prompt), []
    for _ in range(max_new):
        ids = np.zeros((1, _CFG.seq_len), np.int32)
        ids[0, :len(seq)] = seq
        outs = fn_full(iex_full.params,
                       {iex_full._k(f2["input_ids"]): ids})
        row = np.asarray(outs[0]).reshape(
            _CFG.seq_len, _CFG.vocab_size)[len(seq) - 1]
        ref.append(int(np.argmax(row)))
        seq.append(ref[-1])
    with DecodeRouter(eng) as router:
        got = router.submit(prompt, max_new_tokens=max_new).result(
            timeout=120)
    assert got == ref


def test_decode_bitwise_stable_across_batch_mates(decode_graph):
    """The same prompt decodes to the identical token stream whatever
    else shares the in-flight batch: each slot attends only to its own
    cache rows, and greedy argmax is deterministic."""
    eng = _engine(decode_graph)
    prompt = [7, 3, 11]
    with DecodeRouter(eng) as router:
        solo = router.submit(prompt, max_new_tokens=6).result(timeout=120)
        streams = [router.submit(p, max_new_tokens=6)
                   for p in (prompt, [2], [9, 4, 1, 8], [1, 1])]
        crowded = [s.result(timeout=120) for s in streams]
    assert crowded[0] == solo
    assert len(solo) == 6


# ------------------------------------------------ KV slab format (ISSUE 26)
# head_dim 64 packs two key rows into a 128-lane slab row; head_dim 128
# keeps plain (B, H, L, D) rows.  Both must append and attend exactly as
# a (B, H, L, D) cache would.

def _slab_case(head_dim, length=32, batch=4, heads=2, seed=0):
    from hetu_tpu.ops.attention import kv_slab_from_rows, kv_slab_shape
    rng = np.random.RandomState(seed)
    rows = rng.standard_normal(
        (batch, heads, length, head_dim)).astype(np.float32)
    shape = kv_slab_shape(batch, heads, length, head_dim)
    slab = np.asarray(kv_slab_from_rows(rows, shape[-1]))
    assert slab.shape == shape and shape[-1] % 128 == 0
    return rng, rows, slab


def _cut(monkeypatch, block_rows, heads, lanes, itemsize, slabs=2):
    """Key blocks of ``block_rows`` slab rows of all ``heads``."""
    from hetu_tpu.ops.pallas import decode_attention as da
    monkeypatch.setattr(da, "BLOCK_BYTES",
                        block_rows * heads * lanes * itemsize * slabs)
    monkeypatch.setattr(da, "MIN_BLOCK_ROWS", 8)


@pytest.mark.parametrize("masked", [False, True], ids=["all", "valid"])
@pytest.mark.parametrize("chunk", [1, 4])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_kv_append_is_bitwise_the_row_append(head_dim, chunk, masked):
    """The appended slab, read back as rows, is bit for bit what a
    (B, H, L, D) append leaves: rows at and past ``valid`` keep their
    bytes, an idle slot (valid 0) is untouched, and so is every lane the
    chunk does not own — at position 0, at an odd position (the second
    half of a slab row) and with the chunk ending on the last row."""
    from hetu_tpu.ops.attention import _kv_cache_append, kv_slab_to_rows
    rng, rows, slab = _slab_case(head_dim)
    new = rng.standard_normal(
        (4, 2, chunk, head_dim)).astype(np.float32)
    positions = np.array([0, 5, 32 - chunk, 17], np.int32)
    valid = np.array([chunk, 0, chunk, max(1, chunk - 1)], np.int32)
    want = rows.copy()
    for b in range(4):
        n = int(valid[b]) if masked else chunk
        want[b, :, positions[b]:positions[b] + n] = new[b, :, :n]
    got = _kv_cache_append(None, slab, new, positions,
                           valid if masked else None)
    assert got.shape == slab.shape
    assert np.array_equal(np.asarray(kv_slab_to_rows(got, head_dim)), want)


@pytest.mark.parametrize("path", ["kernel", "kernel_blocks",
                                  "kernel_head_groups", "kernel_plain_rows",
                                  "jnp", "jnp_chunk"])
@pytest.mark.parametrize("head_dim", [64, 128])
def test_attention_over_slabs_matches_row_reference(head_dim, path,
                                                    monkeypatch):
    """The one-token attention over the stored slabs — the Pallas body in
    interpret mode (whole cache in one key block; cut into blocks so
    the length-clamped block index is exercised; four heads in two
    programs' worth, so the copy ahead crosses a head group's end) and
    the jnp path the CPU serves — agrees with ``sdpa_reference`` over the
    unpacked rows for ragged positions, position 0 and an odd position
    included; so does the chunked steps' attention, and so does the
    kernel over the UNPACKED (B, H, L, D) rows (64 lanes wide: widened
    with zeros, a copy cannot cut a padded lane row)."""
    from hetu_tpu.ops import attention as att
    from hetu_tpu.ops.pallas import decode_attention as da
    heads = 4 if path == "kernel_head_groups" else 2
    rng, k_rows, k_slab = _slab_case(head_dim, heads=heads, seed=1)
    _, v_rows, v_slab = _slab_case(head_dim, heads=heads, seed=2)
    chunk = 4 if path == "jnp_chunk" else 1
    q = rng.standard_normal((4, heads, chunk, head_dim)).astype(np.float32)
    positions = np.array([0, 5, 32 - chunk, 17], np.int32)
    seen = positions[:, None] + 1 + np.arange(chunk)[None, :]   # (B, C)
    mask = np.arange(32)[None, None, None, :] < seen[:, None, :, None]
    want = np.asarray(att.sdpa_reference(q, k_rows, v_rows, mask=mask))
    if path == "jnp":
        got = att.dispatch_sdpa_decode(q, k_slab, v_slab, positions)
    elif path == "jnp_chunk":
        got = att.dispatch_sdpa_prefill(q, k_slab, v_slab, positions)
    elif path == "kernel_plain_rows":
        got = da.decode_attention(
            q[:, :, :1] * head_dim ** -0.5, k_rows, v_rows,
            positions + 1, interpret=True)
    else:
        pack = 128 // head_dim
        if path != "kernel":
            # 8 slab rows of two heads a key block
            _cut(monkeypatch, 8, 2, 128, 4)
            assert da.geometry(heads, 32 // pack, 128, 4) == (2, 8)
        rows = att.kv_slab_queries(q[:, :, 0] * head_dim ** -0.5, pack)
        got = da.decode_attention(rows, k_slab, v_slab, positions + 1,
                                  pack=pack, interpret=True)
    assert got.shape == q.shape
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-6)


# the one-token kernel reads only what a sequence has written (ISSUE 30):
# 256 key rows in key blocks of 64 keys (one block: the whole slab)
_Q1_LENGTHS = {
    "edges": [1, 63, 64, 65, 256],          # below / at / above a block edge
    "ragged": [200, 1, 129, 17, 128],       # an idle slot (length 1) inside
    "full": [256] * 5,
}


def _garbage_past(slab, lengths, pack, fill):
    """``slab`` with every key row at or past its sequence's length set to
    ``fill``: a dead block that is read, or a masked key that counts,
    shows."""
    rows = slab.shape[2]
    key = (np.arange(rows)[:, None] * pack
           + np.arange(slab.shape[3])[None, :] // (slab.shape[3] // pack))
    dead = key[None] >= np.asarray(lengths)[:, None, None]    # (B, rows, l)
    return np.where(dead[:, None], np.float32(fill), slab)


def _packed_read(lengths, keys, block_rows, monkeypatch):
    """GPT-2's caller (float32, two keys a slab row, the two score rows
    of a query sharing one softmax) against ``sdpa_slab_reference``, rows
    past every length filled with large finite garbage: ``(got, want)``."""
    from hetu_tpu.ops import attention as att
    from hetu_tpu.ops.pallas import decode_attention as da
    b = len(lengths)
    rng, _, k_slab = _slab_case(64, length=keys, batch=b, seed=3)
    _, _, v_slab = _slab_case(64, length=keys, batch=b, seed=4)
    k_slab = _garbage_past(k_slab, lengths, 2, 3.0e4)
    v_slab = _garbage_past(v_slab, lengths, 2, -3.0e4)
    q = rng.standard_normal((b, 2, 1, 64)).astype(np.float32)
    want = np.asarray(att.sdpa_slab_reference(q, k_slab, v_slab,
                                              lengths[:, None]))
    if block_rows:
        _cut(monkeypatch, block_rows, 2, 128, 4)
    assert da.geometry(2, keys // 2, 128, 4) == (
        2, block_rows or keys // 2)
    rows = att.kv_slab_queries(q[:, :, 0] * 0.125, 2)
    got = da.decode_attention(rows, k_slab, v_slab, lengths, pack=2,
                              interpret=True)
    return np.asarray(got), want[:, :, 0:1]


def _paired_read(lengths, keys, block_rows, monkeypatch):
    """The shared-KV readers' caller: ``_diff_attention_kv`` at ``C = 1``
    through the kernel (bfloat16 paired rows as stored, ``r = 1``, two
    query pairs a key pair: four score rows with a softmax each) against
    its own ``jnp`` path over the same bfloat16 values.  The ``jnp`` side
    reads them from float32 slabs (this CPU has no bfloat16 product at
    these sizes), so its weights meet ``V`` unrounded, as the kernel's do
    (``_pv``)."""
    import functools

    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops import ssm
    from hetu_tpu.ops.pallas import decode_attention as da
    b = len(lengths)
    rng, _, k_slab = _slab_case(128, length=keys, batch=b, seed=5)
    _, _, v_slab = _slab_case(128, length=keys, batch=b, seed=6)
    k16 = jnp.asarray(_garbage_past(k_slab, lengths, 1, 3.0e4), jnp.bfloat16)
    v16 = jnp.asarray(_garbage_past(v_slab, lengths, 1, -3.0e4),
                      jnp.bfloat16)
    # queries that are bfloat16 values, so that 1/8 of them are too
    q = jnp.asarray(rng.standard_normal((b, 4 * 128)), jnp.bfloat16).astype(
        jnp.float32)
    lams = [jnp.asarray(0.1 * rng.standard_normal(64), jnp.float32)
            for _ in range(4)]
    norm_w = jnp.asarray(1.0 + 0.1 * rng.standard_normal(128), jnp.float32)
    ids = jnp.zeros((b, 1), jnp.int32)
    call = functools.partial(ssm._diff_attention_kv, None, q)
    want = call(k16.astype(jnp.float32), v16.astype(jnp.float32),
                lengths - 1, ids, *lams, norm_w)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, interpret=True))
    if block_rows:
        _cut(monkeypatch, block_rows, 2, 128, 2)
    metrics.reset_all()
    got = call(k16, v16, lengths - 1, ids, *lams, norm_w)
    assert metrics.decode_attn_call_counts() == {
        "2x%d" % (block_rows or keys): 1}
    assert got.shape == want.shape == (b, 4 * 128)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    return np.asarray(got), np.asarray(want)


def _latent_read(lengths, keys, block_rows, monkeypatch):
    """The latent mode as ``ops/mla.py`` calls it — 20 score rows over ONE
    slab of bfloat16 rows whose first lanes (128 of 256) are the value
    too — against attention written out in float64 over the same
    bfloat16 values."""
    import jax.numpy as jnp
    from hetu_tpu.ops.pallas import decode_attention as da
    rng = np.random.default_rng(6)
    b, n, lanes, v_lanes = len(lengths), 20, 256, 128
    dead = np.arange(keys)[None, :] >= lengths[:, None]
    slab = jnp.asarray(np.where(
        dead[:, None, :, None], 3.0e4,
        rng.standard_normal((b, 1, keys, lanes))), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((b, 1, n, lanes)) * 0.2,
                    jnp.bfloat16)
    if block_rows:
        _cut(monkeypatch, block_rows, 1, lanes, 2, slabs=1)
    metrics.reset_all()
    got = np.asarray(da.decode_attention(q, slab, None, lengths,
                                         v_lanes=v_lanes, interpret=True))
    assert metrics.decode_attn_call_counts() == {
        "1x%d" % (block_rows or keys): 1}
    assert got.shape == (b, 1, n, v_lanes)
    want = np.zeros(got.shape)
    for i in range(b):
        live = np.asarray(slab, np.float64)[i, 0, :lengths[i]]
        s = np.asarray(q, np.float64)[i, 0] @ live.T
        p = np.exp(s - s.max(-1, keepdims=True))
        want[i, 0] = (p / p.sum(-1, keepdims=True)) @ live[:, :v_lanes]
    return got, want


@pytest.mark.parametrize("blocks", ["one_block", "blocks"])
@pytest.mark.parametrize("mix", sorted(_Q1_LENGTHS))
def test_one_token_kernel_over_packed_slabs(mix, blocks, monkeypatch):
    """GPT-2's caller against ``sdpa_slab_reference``: lengths 1, one
    below / at / one above a block edge and the full slab, a ragged batch
    with an idle slot, rows past every length filled with large finite
    garbage."""
    got, want = _packed_read(np.array(_Q1_LENGTHS[mix], np.int32), 256,
                             32 if blocks == "blocks" else None, monkeypatch)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("blocks", ["one_block", "blocks"])
@pytest.mark.parametrize("mix", sorted(_Q1_LENGTHS))
def test_one_token_kernel_over_paired_rows(mix, blocks, monkeypatch):
    """The shared-KV readers' caller against its own ``jnp`` path."""
    got, want = _paired_read(np.array(_Q1_LENGTHS[mix], np.int32), 256,
                             64 if blocks == "blocks" else None, monkeypatch)
    # read 2.0e-6 to 2.3e-6 over the cases, of outputs up to 0.74: the
    # weights meet V as hi + lo bfloat16 rows and lose nothing (one
    # rounding of each row's weights reads 1.3e-3 to 1.7e-3 here)
    np.testing.assert_allclose(got, want, atol=2e-5)


# a sequence's last key block is copied and multiplied only as far as the
# sequence reaches (ISSUE 41).  Slab rows a slot holds, of 1024 in key
# blocks of 512 rows, a product's sub-block 256 rows, a copy's tile 8
# (float32) or 16 (bfloat16) rows: on both sides of each edge
_TAIL_ROWS = {
    "tile": [1, 7, 8, 9, 15, 16],
    "sub_block": [17, 255, 256, 257, 767, 769],
    "block": [511, 512, 513, 768, 1023, 2],
    "full": [1024] * 6,
    "ragged": [800, 1, 257, 17, 600, 1],       # idle slots inside
    # a one-block slot behind a many-block slot: its block was on its way
    # while the slots before it were multiplied
    "after_many": [1024, 5, 600, 1, 520, 3],
}
_TAIL_READS = {                  # mode -> (the read, keys a slab row, atol)
    "packed_f32": (_packed_read, 2, 2e-6),
    "paired_bf16": (_paired_read, 1, 2e-5),
    "latent_bf16": (_latent_read, 1, 2e-5),
}


@pytest.mark.parametrize("mix", sorted(_TAIL_ROWS))
@pytest.mark.parametrize("mode", sorted(_TAIL_READS))
def test_one_token_kernel_stops_where_the_sequence_does(mode, mix,
                                                        monkeypatch):
    """All three modes of the kernel over slabs of 1024 rows: every length
    of ``_TAIL_ROWS`` reads what the reference reads, with the slabs' rows
    past each length garbage and the VMEM the kernel copies into NaN
    wherever nothing was copied (the TPU interpreter's
    ``uninitialized_memory="nan"``): a product that took a row past the
    copy would show as NaN."""
    read, pack, atol = _TAIL_READS[mode]
    rows = np.array(_TAIL_ROWS[mix], np.int32)
    # the odd slots stop on the FIRST key of their last slab row
    lengths = rows * pack - (np.arange(len(rows)) % 2) * (pack - 1)
    got, want = read(lengths, 1024 * pack, 512, monkeypatch)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=atol)


def test_the_poison_would_show(monkeypatch):
    """What the test above rests on: with the rows past a copy left as
    they were, the same read IS NaN — the buffers do start poisoned."""
    import jax
    from hetu_tpu.ops.pallas import decode_attention as da
    monkeypatch.setattr(da, "_zero_past", lambda *a: None)
    jax.clear_caches()              # the kernel's call is jitted by shape
    got, _ = _paired_read(np.array([1, 7, 256], np.int32), 512, 256,
                          monkeypatch)
    assert np.isnan(got[:2]).all() and np.isfinite(got[2]).all()
    jax.clear_caches()


def test_a_chunk_and_the_cpu_keep_the_jnp_read(monkeypatch):
    """``C > 1`` never enters the kernel, whatever the backend says, and
    off the chip nothing does: no ``decode_attn_calls``."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops import ssm
    rng, _, slab = _slab_case(128, length=256, batch=2, seed=7)
    lams = [jnp.zeros(64)] * 4
    args = (jnp.asarray(slab), jnp.asarray(slab), np.array([5, 9]))
    metrics.reset_all()
    one = ssm._diff_attention_kv(
        None, jnp.ones((2, 256)), *args, jnp.zeros((2, 1), jnp.int32),
        *lams, jnp.ones(128))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    two = ssm._diff_attention_kv(
        None, jnp.ones((4, 256)), *args, jnp.zeros((2, 2), jnp.int32),
        *lams, jnp.ones(128))
    assert one.shape == (2, 256) and two.shape == (4, 256)
    assert metrics.decode_attn_call_counts() == {}


# the kernel's chunk form (ISSUE 46): C positions a sequence, C x pack
# score rows a head, each with its own causal limit.  256 keys in key
# blocks of 32 slab rows; where the chunk's FIRST position stands (0, in
# the middle of a block, on a block's last row, as far as the slab lets a
# chunk start, in an idle slot) and how many positions the step took of it
_CHUNK_STARTS = ("first", "mid_block", "block_end", "last", "idle", "one")


def _chunk_case(pack, chunk, dtype, seed=11):
    """``(q, k_slab, v_slab, positions, count)`` of six slots over slabs
    of 256 keys in ``dtype`` (the queries scaled before they are rounded
    to it), the key rows at and past ``positions + max(count, 1)`` large
    finite garbage."""
    import jax.numpy as jnp
    d = 128 // pack
    rng, _, k_slab = _slab_case(d, length=256, batch=6, seed=seed)
    _, _, v_slab = _slab_case(d, length=256, batch=6, seed=seed + 1)
    block = 32 * pack                                   # keys a key block
    positions = np.array([0, block + 5, 2 * block - 1, 256 - chunk, 17, 70],
                         np.int32)
    count = np.array([chunk, chunk, chunk, chunk, 0, 1], np.int32)
    ends = positions + np.maximum(count, 1)
    k_slab = _garbage_past(k_slab, ends, pack, 3.0e4)
    v_slab = _garbage_past(v_slab, ends, pack, -3.0e4)
    q = rng.standard_normal((6, 2, chunk, d)).astype(np.float32) * d ** -0.5
    return (jnp.asarray(q, dtype), jnp.asarray(k_slab, dtype),
            jnp.asarray(v_slab, dtype), positions, count)


def _chunk_read(q, k_slab, v_slab, positions, count, pack):
    """The chunk form as ``dispatch_sdpa_prefill`` calls it, of scaled
    queries."""
    from hetu_tpu.ops import attention as att
    from hetu_tpu.ops.pallas import decode_attention as da
    rows = att.kv_slab_chunk_rows(q, pack)
    assert np.array_equal(rows, att.kv_slab_queries(q, pack).reshape(
        rows.shape))
    return np.asarray(da.decode_attention(
        rows, k_slab, v_slab, positions + 1, pack=pack, interpret=True,
        chunk=q.shape[2], count=count))


@pytest.mark.parametrize("chunk", [2, 4, 32])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("pack", [1, 2])
def test_chunk_form_matches_the_slab_reference(pack, dtype, chunk,
                                               monkeypatch):
    """Every row the step took reads what ``sdpa_slab_reference`` reads
    (the same values in float32: a bfloat16 slab's weights meet ``V`` as
    hi + lo rows), over eight key blocks, VMEM NaN wherever nothing was
    copied; a row past ``count`` — a don't-care — is a finite number."""
    import jax.numpy as jnp
    from hetu_tpu.ops import attention as att
    from hetu_tpu.ops.pallas import decode_attention as da
    q, k_slab, v_slab, positions, count = _chunk_case(pack, chunk, dtype)
    itemsize = k_slab.dtype.itemsize
    _cut(monkeypatch, 32, 2, 128, itemsize)
    assert da.geometry(2, 256 // pack, 128, itemsize, 2, chunk * pack) \
        == (2, 32)
    metrics.reset_all()
    got = _chunk_read(q, k_slab, v_slab, positions, count, pack)
    assert metrics.decode_attn_call_counts() == {f"2x32:c{chunk}": 1}
    assert got.shape == q.shape and np.isfinite(got).all()
    lengths = positions[:, None] + 1 + np.arange(chunk)[None, :]
    want = np.asarray(att.sdpa_slab_reference(
        *(jnp.asarray(x, jnp.float32) for x in (q, k_slab, v_slab)),
        jnp.asarray(lengths), scale=1.0))
    for b, n in enumerate(np.maximum(count, 1)):
        np.testing.assert_allclose(
            got[b, :, :n], want[b, :, :n], rtol=2e-5,
            atol=2e-6 if dtype == "float32" else 2e-5,
            err_msg=_CHUNK_STARTS[b])


@pytest.mark.parametrize("pack", [1, 2])
def test_one_position_through_the_chunk_form_is_the_one_token_call(
        pack, monkeypatch):
    """``chunk=1`` IS today's call (the same program: no fourth scalar,
    the trace's ``flash_fwd_q1``), and the first position of a chunk of
    which the step took one reads the one-token call's result to the
    last bit: the same keys, blocks and sums."""
    from hetu_tpu.ops.pallas import decode_attention as da
    q, k_slab, v_slab, positions, _ = _chunk_case(pack, 2, "float32")
    _cut(monkeypatch, 32, 2, 128, 4)
    ones = np.ones(6, np.int32)
    metrics.reset_all()
    want = _chunk_read(q[:, :, :1], k_slab, v_slab, positions, None, pack)
    assert np.array_equal(want, _chunk_read(
        q[:, :, :1], k_slab, v_slab, positions, ones, pack))
    assert metrics.decode_attn_call_counts() == {"2x32": 2}
    got = _chunk_read(q, k_slab, v_slab, positions, ones, pack)
    assert np.array_equal(got[:, :, :1], want)
    assert np.isfinite(got).all()
    with pytest.raises(ValueError, match="score rows"):
        da.decode_attention(np.zeros((1, 1, 3 * pack, 128), np.float32),
                            k_slab[:1, :1], v_slab[:1, :1], ones[:1],
                            pack=pack, chunk=2)


def test_chunk_schedule_ends_where_the_steps_rows_do(monkeypatch):
    """The schedule, and the last block's copy, run to ``positions +
    count``: with every slab row past the copy tile that holds it NaN,
    a block or a row fetched past it would show (a weight of zero times
    a NaN); and ``kv_rows_fetched(chunk=)`` counts exactly those rows."""
    import jax
    from hetu_tpu.ops import attention as att
    q, k_slab, v_slab, positions, count = _chunk_case(2, 4, "float32")
    _cut(monkeypatch, 32, 2, 128, 4)
    ends = positions + np.maximum(count, 1)
    tile = 8 * 2                           # keys a copy's tile holds
    copied = -(-ends // tile) * tile
    k_nan = _garbage_past(np.asarray(k_slab), copied, 2, np.nan)
    v_nan = _garbage_past(np.asarray(v_slab), copied, 2, np.nan)
    assert np.isnan(k_nan).any()
    got = _chunk_read(q, k_nan, v_nan, positions, count, 2)
    assert np.isfinite(got).all()
    assert np.array_equal(got, _chunk_read(q, k_slab, v_slab, positions,
                                           count, 2))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    slab = (6, 2, 256, 128)                # on the gate: 512 keys
    assert att.kv_rows_fetched(ends, slab, 2, 4, chunk=4) == copied.sum()
    assert att.kv_rows_fetched(ends, slab, 2, 4, chunk=4) \
        == att.kv_rows_fetched(ends, slab, 2, 4)
    # off the gate (under 256 keys), and a chunk too long for one head's
    # program: the slab whole
    assert att.kv_rows_fetched(ends, (6, 2, 64, 128), 2, 4, chunk=4) \
        == 6 * 128
    assert att.kv_rows_fetched(ends, slab, 2, 4, chunk=4096) == 6 * 512


@pytest.mark.parametrize("chunk", [4, 32, 128])
def test_prefill_dispatch_enters_the_chunk_form_on_the_chip_alone(
        chunk, monkeypatch):
    """``dispatch_sdpa_prefill`` over a slab on the one-token read's gate
    (512 keys): off the chip the jnp read and no ``decode_attn_calls``;
    behind a backend that says tpu the kernel's chunk form, counted with
    the chunk behind its geometry, reading what the jnp read reads — a
    chunk of whole 128-row tiles keeps the flash kernel's path."""
    import functools
    import importlib

    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops import attention as att
    from hetu_tpu.ops.pallas import decode_attention as da
    fa = importlib.import_module("hetu_tpu.ops.pallas.flash_attention")
    rng, _, k_slab = _slab_case(64, length=512, batch=3, seed=21)
    _, _, v_slab = _slab_case(64, length=512, batch=3, seed=22)
    q = rng.standard_normal((3, 2, chunk, 64)).astype(np.float32)
    positions = np.array([0, 200, 512 - chunk], np.int32)
    valid = np.array([chunk, 1, chunk], np.int32)
    metrics.reset_all()
    want = np.asarray(att.dispatch_sdpa_prefill(q, k_slab, v_slab,
                                                positions, valid))
    assert metrics.decode_attn_call_counts() == {}
    assert metrics.flash_fallback_counts() == {"backend:cpu": 1}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, interpret=True))
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    metrics.reset_all()
    got = np.asarray(att.dispatch_sdpa_prefill(
        jnp.asarray(q), jnp.asarray(k_slab), jnp.asarray(v_slab),
        jnp.asarray(positions), jnp.asarray(valid)))
    assert metrics.flash_fallback_counts() == {}
    assert metrics.decode_attn_call_counts() == (
        {} if chunk == 128 else {f"2x256:c{chunk}": 1})
    assert bool(metrics.flash_call_counts()) == (chunk == 128)
    for b, n in enumerate(valid):
        np.testing.assert_allclose(got[b, :, :n], want[b, :, :n],
                                   rtol=2e-5, atol=2e-6)
    # a slab under the gate keeps the jnp read, counted with its reason
    att.dispatch_sdpa_prefill(q[:, :, :4], k_slab[:, :, :64],
                              v_slab[:, :, :64], positions % 100)
    assert metrics.flash_fallback_counts() == {
        "decode_below_gate:kv128<256": 1}


def test_geometry_follows_the_calls_shape():
    """All the heads of a slot in one program, key blocks sized to
    ``BLOCK_BYTES`` over the call's slabs together: the four cells'
    shapes (the latent read's ONE slab takes rows twice as long), a slab
    too short to cut, heads too many for one program, and rows with no
    aligned divisor."""
    from hetu_tpu.ops.pallas.decode_attention import geometry
    assert geometry(1, 4096, 640, 2, 1) == (1, 2048)     # the glm cell
    assert geometry(10, 4608, 128, 2) == (10, 512)       # the phi4 cell
    assert geometry(16, 384, 128, 4) == (16, 128)        # the chat cell
    assert geometry(1, 4096, 128, 2) == (1, 4096)        # the solar cell
    assert geometry(2, 16, 128, 4) == (2, 16)
    assert geometry(64, 1024, 128, 4) == (32, 64)
    assert geometry(25, 512, 128, 4) == (25, 64)
    assert geometry(4, 100, 128, 4) == (4, 100)
    assert geometry(128, 1024, 128, 4) == (32, 64)


# the four serving cells' calls: slab, keys a slab row, bytes an element
_CELL_CALLS = {
    "glm": ((128, 1, 4096, 640), 1, 2),
    "phi4": ((64, 10, 4608, 128), 1, 2),
    "chat": ((16, 16, 384, 128), 2, 4),
    "solar": ((128, 1, 4096, 128), 1, 2),
}


@pytest.mark.parametrize("cell", sorted(_CELL_CALLS))
def test_rows_fetched_end_a_tile_past_the_rows_live(cell, monkeypatch):
    """``kv_rows_fetched`` at each cell's call: every live row, under one
    copy tile (8 float32 or 16 bfloat16 slab rows) more a slot, never more
    than the whole blocks the grid walks (``kv_rows_read``, multiples of
    the geometry's block); off the kernel's gate, the slab whole."""
    import jax
    from hetu_tpu.ops import attention as att
    from hetu_tpu.ops.pallas.decode_attention import _tail, geometry
    slab, pack, itemsize = _CELL_CALLS[cell]
    b, heads, slab_rows, lanes = slab
    block = geometry(heads, slab_rows, lanes, itemsize)[1]
    tile = 32 // itemsize
    assert _tail(block, itemsize)[0] == tile and slab_rows % block == 0
    call = (slab, pack, itemsize)
    whole = b * slab_rows * pack
    assert att.kv_rows_fetched(np.ones(b, np.int64), *call) == whole
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rng = np.random.default_rng(41)
    for lengths in (rng.integers(1, slab_rows * pack + 1, b),
                    np.ones(b, np.int64),                  # idle slots
                    np.full(b, slab_rows * pack)):
        live = int(lengths.sum())
        fetched = att.kv_rows_fetched(lengths, *call)
        walked = att.kv_rows_read(lengths, *call)
        assert live <= fetched <= walked <= whole
        assert fetched - live < b * tile * pack
        assert walked % (block * pack) == 0 and fetched % (tile * pack) == 0
    assert att.kv_rows_fetched(np.ones(b, np.int64), *call) \
        == b * tile * pack
    assert att.kv_rows_fetched(np.full(b, slab_rows * pack), *call) == whole


_ROWS_CFG = GPT2Config.tiny(n_positions=256, batch_size=1, seq_len=16)


def _serve_and_count():
    """Two prompts (37 and 2 tokens, 4 new tokens each) through a
    2 x 256 engine: the streams, the decode counters, the kernel's
    geometries."""
    feeds, logits, caches, _ = gpt2_decode_graph(_ROWS_CFG, max_len=256)
    eng = DecodeEngine(feeds, logits, caches, seed=0, max_slots=2,
                       max_len=256)
    eng.reserve(2, 256)
    metrics.reset_all()
    with DecodeRouter(eng, start=False) as router:
        streams = [router.submit(p, max_new_tokens=4)
                   for p in (list(range(3, 40)), [5, 6])]
        router.start()
        tokens = [s.result(timeout=300) for s in streams]
    return tokens, metrics.decode_counts(), HetuProfiler.decode_attn_calls()


@pytest.fixture(scope="module")
def jnp_rows_run():
    return _serve_and_count()


def test_engine_reads_its_slabs_whole_on_the_jnp_path(jnp_rows_run):
    """Off the chip every step's attention is the jnp path:
    ``decode_kv_rows_read`` equals ``decode_kv_rows_held`` (batch bucket x
    slab rows, per step) and no ``decode_attn_calls`` is recorded."""
    _, c, calls = jnp_rows_run
    assert c["decode_steps"] == 37 + 3
    assert c["decode_kv_rows_held"] == c["decode_steps"] * 2 * 256
    assert c["decode_kv_rows_read"] == c["decode_kv_rows_held"]
    assert calls == {}


def test_engine_counts_the_kv_rows_the_kernel_fetches(jnp_rows_run,
                                                      monkeypatch):
    """``decode_kv_rows_read`` counts, per step and from the positions
    alone, what the compiled geometry fetches — of every slot of the
    batch bucket the rows its sequence reaches, rounded up to a copy's
    tile: ``kv_rows_fetched`` summed over the steps — and
    ``decode_attn_calls`` names the geometry once per layer per trace:
    the kernel in interpret mode behind a backend that says tpu, emitting
    the jnp path's tokens.  The rows were appended by the aliased kernel
    (``kv_append_calls``: K and V of every layer, one tile of 8 float32
    slab rows a program)."""
    import functools

    import jax
    from hetu_tpu.ops import attention as att
    from hetu_tpu.ops.pallas import decode_attention as da
    from hetu_tpu.ops.pallas import kv_append as ka
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, interpret=True))
    monkeypatch.setattr(ka, "kv_append", functools.partial(
        ka.kv_append, interpret=True))
    # 16 slab rows (32 keys) of every head a key block
    _cut(monkeypatch, 16, _ROWS_CFG.n_head, 128, 4)
    tokens, c, calls = _serve_and_count()
    assert tokens == jnp_rows_run[0]
    assert c["decode_kv_rows_held"] == jnp_rows_run[1]["decode_kv_rows_held"]
    # slot 0 holds 1..40 keys, slot 1 1..5 and then stays at its last
    # position: a tile of 8 slab rows (16 keys) each, and another of
    # slot 0 for every 16 keys more — where the blocks the grid WALKS
    # (``kv_rows_read``) are 32 keys each
    slab = (2, _ROWS_CFG.n_head, 128, 128)
    steps = [np.array([n, min(n, 5)]) for n in range(1, 41)]
    assert c["decode_kv_rows_read"] == sum(
        att.kv_rows_fetched(n, slab, 2, 4) for n in steps) == sum(
        16 * (-(-n // 16) + 1) for n in range(1, 41))
    assert sum(att.kv_rows_read(n, slab, 2, 4) for n in steps) == sum(
        32 * (-(-n // 32) + 1) for n in range(1, 41))
    assert calls == {f"{_ROWS_CFG.n_head}x16": _ROWS_CFG.n_layer}
    assert HetuProfiler.all_counters()["decode_attn_calls"] == calls
    assert HetuProfiler.all_counters()["kv_append_calls"] == {
        "8x128:kernel": 2 * _ROWS_CFG.n_layer}


def _chunked_engine():
    from hetu_tpu.models import gpt2_decode_chunked_graph
    cfg = GPT2Config.tiny(n_positions=512, batch_size=1, seq_len=16)
    feeds, logits, caches, _ = gpt2_decode_graph(cfg, max_len=512)
    cf, cl, cc, _ = gpt2_decode_chunked_graph(cfg, max_len=512)
    eng = DecodeEngine(feeds, logits, caches, seed=0, max_slots=2,
                       max_len=512, chunked=(cf, cl, cc), max_chunk=32)
    eng.reserve(2, 512)
    assert eng._chunk_live
    return eng


def _serve_chunked_and_count(steps):
    """One prompt of 300 tokens (3 new tokens) beside one of 2 through a
    2 x 512 engine with GPT-2's chunked entry, chunks up to 32:
    ``(tokens, counters, geometries)``; ``steps`` collects ``(chunk,
    positions + consumed)`` of every launch."""
    eng = _chunked_engine()
    kv_rows = eng._kv_rows

    def noted(chunk, consume=1):
        steps.append((chunk, eng.positions + np.maximum(consume, 1)))
        return kv_rows(chunk, consume)
    eng._kv_rows = noted
    metrics.reset_all()
    with DecodeRouter(eng, start=False) as router:
        streams = [router.submit(p, max_new_tokens=3)
                   for p in (list(range(3, 303)), [5, 6])]
        router.start()
        tokens = [s.result(timeout=300) for s in streams]
    return tokens, metrics.decode_counts(), HetuProfiler.decode_attn_calls()


def test_engine_counts_a_chunked_step_by_what_it_fetches(monkeypatch):
    """A chunked step of a graph whose ``kv`` placeholder says
    ``chunk_read`` (GPT-2's) counts ``kv_rows_fetched(positions +
    consumed)`` where the kernel's gate passes, and every row the slabs
    hold off it (here: the CPU) — emitting the same tokens through the
    kernel's chunk form in interpret mode behind a backend that says tpu;
    a graph without the mark (phi4's, solar's, glm's, sala's, granite's)
    keeps counting a chunked step whole."""
    import functools

    import jax
    from hetu_tpu.ops import attention as att
    from hetu_tpu.ops.pallas import decode_attention as da
    from hetu_tpu.ops.pallas import kv_append as ka
    steps = []
    tokens, c, calls = _serve_chunked_and_count(steps)
    assert calls == {} and c["decode_prefill_steps"] >= 300 // 32
    assert c["decode_kv_rows_held"] == c["decode_steps"] * 2 * 512
    assert c["decode_kv_rows_read"] == c["decode_kv_rows_held"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, interpret=True))
    monkeypatch.setattr(ka, "kv_append", functools.partial(
        ka.kv_append, interpret=True))
    steps = []
    got, k, calls = _serve_chunked_and_count(steps)
    assert got == tokens
    assert k["decode_steps"] == c["decode_steps"] == len(steps)
    assert k["decode_kv_rows_held"] == c["decode_kv_rows_held"]
    slab = (2, 2, 256, 128)
    assert k["decode_kv_rows_read"] == sum(
        att.kv_rows_fetched(ends, slab, 2, 4, chunk)
        for chunk, ends in steps) < k["decode_kv_rows_held"] // 2
    wide = [chunk for chunk, _ in steps if chunk > 1]
    assert wide and max(wide) == 32
    # once a layer a traced program: the one-token key as it was, a
    # chunked program's with its chunk behind it
    layers = _ROWS_CFG.n_layer
    assert calls == {"2x256": layers, **{
        f"2x256:c{chunk}": layers for chunk in set(wide)}}
    # a chunked graph that does not say so reads whole, whatever the gate
    eng = _chunked_engine()
    consume = np.array([32, 1])
    assert eng._kv_rows(32, consume) == (32 + 16, 1024)
    eng._chunk_live = False
    assert eng._kv_rows(32, consume) == (1024, 1024)
    assert eng._kv_rows(1) == (2 * 16, 1024)


def test_slab_format_follows_head_dim_alone(decode_graph):
    """The rule: a head that is a proper divisor of the 128 lanes shares
    a lane row, any other keeps plain rows; the engine reads the format
    off the graph's placeholders and reports it."""
    from hetu_tpu.ops.attention import kv_slab_pack, kv_slab_shape
    assert [kv_slab_pack(d) for d in (8, 32, 64, 96, 128, 256)] == \
        [16, 4, 2, 1, 1, 1]
    assert kv_slab_shape(16, 16, 768, 64) == (16, 16, 384, 128)
    assert kv_slab_shape(16, 16, 768, 128) == (16, 16, 768, 128)
    assert kv_slab_shape(2, 2, 5, 64) == (2, 2, 3, 128)
    metrics.reset_decode_counts()
    eng = _engine(decode_graph)
    assert (eng._heads, eng._head_dim, eng._pack) == (2, 64, 2)
    assert all(c.shape[-1] == 128 for c in eng.caches.values())
    assert metrics.decode_counts()["decode_kv_slab_format_hw"] == 2
    # the slabs hold what (B, H, L, D) slabs held: not a byte more
    assert eng.kv_bytes == (len(eng.caches) * eng.bb * 2 * 64 * 4
                            * -(-eng.lb // 2) * 2)


# ---------------------------------------------- continuous batching plane

def test_continuous_join_leave_slot_recycle(decode_graph):
    """Sequences join and leave the in-flight batch per token; freed
    KV-cache slots are recycled by later joiners; counters account for
    every row."""
    metrics.reset_decode_counts()
    eng = _engine(decode_graph, max_slots=2)
    prompts = [([3], 2), ([5, 6], 4), ([7, 8, 9], 3), ([11], 5)]
    with DecodeRouter(eng, queue_limit=8) as router:
        streams = [router.submit(p, max_new_tokens=n) for p, n in prompts]
        outs = [s.result(timeout=120) for s in streams]
    for (p, n), toks in zip(prompts, outs):
        assert len(toks) == n
    c = HetuProfiler.decode_counters()
    assert c["decode_joins"] == 4 and c["decode_leaves"] == 4
    # 4 sequences through <= 2 slots: at least two slots were reused
    assert c["decode_slot_recycles"] >= 2
    assert c["decode_tokens"] == sum(n for _, n in prompts)
    # every prompt token past the first is a prefill row
    assert c["decode_prefill_rows"] == sum(len(p) - 1 for p, _ in prompts)
    assert c["decode_kv_bytes_hw"] > 0
    assert eng.idle and eng.capacity() == 2


def test_backpressure_and_too_long_rejection(decode_graph):
    eng = _engine(decode_graph, max_slots=2)
    router = DecodeRouter(eng, queue_limit=1, start=False)
    try:
        router.submit([1], max_new_tokens=2)
        with pytest.raises(ServeRejected) as ei:
            router.submit([2], max_new_tokens=2)
        assert ei.value.reason == "queue_full"      # structured vocabulary
        with pytest.raises(ServeRejected) as ei:
            router.submit(list(range(10)), max_new_tokens=_MAX_LEN)
        assert ei.value.reason == "over_max_len"
    finally:
        router.close()
    with pytest.raises(ServeRejected) as ei:
        router.submit([1], max_new_tokens=2)
    assert ei.value.reason == "draining"


def test_stream_token_futures_and_iteration(decode_graph):
    """Per-token futures resolve in emission order; iteration yields the
    whole stream; past-the-end futures fail with IndexError."""
    eng = _engine(decode_graph, max_slots=2)
    with DecodeRouter(eng) as router:
        s = router.submit([5, 2], max_new_tokens=3)
        first = s.token(0).result(timeout=120)
        rest = s.result(timeout=120)
        assert rest[0] == first and len(rest) == 3
        assert list(s) == rest
        with pytest.raises(IndexError):
            s.token(10).result(timeout=5)
        assert s.n_tokens == 3 and s.done


def test_router_close_fails_inflight_and_queued(decode_graph):
    eng = _engine(decode_graph, max_slots=1)
    router = DecodeRouter(eng, queue_limit=8, start=False)
    queued = router.submit([1, 2], max_new_tokens=4)
    router.close()
    with pytest.raises(ServeRejected):
        queued.result(timeout=5)


# ---------------------------------------------- per-request deadlines (ISSUE 17)

def test_decode_deadline_expired_in_queue_fails_fast(decode_graph):
    """A queued request whose deadline passes before it gets a slot is
    failed with the structured ``deadline`` reason WHEN the loop next
    looks at the queue — it never occupies a slot, and the requests
    behind it still run."""
    metrics.reset_decode_counts()
    eng = _engine(decode_graph, max_slots=1)
    router = DecodeRouter(eng, queue_limit=8, start=False)
    try:
        doomed = router.submit([1, 2], max_new_tokens=2, deadline_ms=0.01)
        live = router.submit([3, 2], max_new_tokens=2)
        import time as _t
        _t.sleep(0.05)                  # deadline long gone before start
        router.start()
        with pytest.raises(ServeRejected) as ei:
            doomed.result(timeout=30)
        assert ei.value.reason == "deadline"
        assert live.result(timeout=60)  # the non-deadlined mate finishes
        c = metrics.decode_counts()
        assert c.get("decode_deadline_evictions", 0) == 1
    finally:
        router.close()


def test_decode_deadline_mid_generation_evicts_and_frees_slot(decode_graph):
    """A deadline that lands MID-generation evicts the seated sequence at
    the next step boundary: its stream fails with reason ``deadline``,
    the slot is recycled (a follow-up sequence runs through the same
    1-slot engine), and the eviction is counted.  Driven through
    ``evict_expired``'s explicit clock so the test is deterministic
    regardless of compile-cache warmth."""
    import time as _t

    from hetu_tpu.serving.decode import _DecodeRequest
    metrics.reset_decode_counts()
    eng = _engine(decode_graph, max_slots=1)
    req = _DecodeRequest(np.asarray([1, 2], np.int32), _MAX_LEN - 2,
                         None, None, deadline=_t.monotonic() + 1000.0)
    eng.join(req)
    eng.step()
    eng.step()                          # genuinely mid-generation
    assert eng.evict_expired(now=req.deadline - 1.0) == 0   # not yet due
    assert eng.evict_expired(now=req.deadline + 1.0) == 1   # due: evicts
    with pytest.raises(ServeRejected) as ei:
        req.stream.result(timeout=5)
    assert ei.value.reason == "deadline"
    assert eng.idle and eng.capacity() == 1
    c = metrics.decode_counts()
    assert c.get("decode_deadline_evictions", 0) == 1
    # the freed slot seats new work through a live router
    with DecodeRouter(eng, queue_limit=8) as router:
        assert router.submit([3, 2], max_new_tokens=2).result(timeout=60)


# --------------------------------------- compile-once / plan-cache steady state

def test_compile_once_per_bucket_pair_over_stream():
    """Over a stream of requests, the engine compiles AT MOST once per
    (batch_bucket, len_bucket) pair — every other step dispatches
    through a plan-cache hit (the steady-state claim)."""
    feeds, logits, caches, _ = gpt2_decode_graph(_CFG, max_len=_MAX_LEN)
    metrics.reset_all()
    eng = DecodeEngine(feeds, logits, caches, max_slots=4,
                       max_len=_MAX_LEN, seed=0)
    rng = np.random.RandomState(0)
    with DecodeRouter(eng, queue_limit=64) as router:
        streams = []
        for _ in range(24):
            plen = int(rng.zipf(1.8)) % 4 + 1
            prompt = rng.randint(1, _CFG.vocab_size, plen)
            streams.append(router.submit(prompt, max_new_tokens=3))
        for s in streams:
            s.result(timeout=300)
    decode = metrics.decode_counts()
    serve = metrics.serve_counts()
    rp = metrics.run_plan_counts()
    steps = decode["decode_steps"]
    pairs = rp.get("plan_cache_miss", 0)
    assert steps > pairs, "stream too short to show a steady state"
    # one dispatch-plan miss per distinct (batch, len) bucket pair, and
    # one real compile per miss — everything else is a hit
    assert serve["serve_bucket_compiles"] + \
        metrics.step_cache_counts().get("step_cache_serve_hit", 0) == pairs
    assert rp["plan_cache_hit"] == steps - pairs
    # the ladders bound the pairs: batch in {1,2,4}, len in buckets(16)
    assert pairs <= len(eng.batch_ladder) * len(eng.len_ladder)


# ------------------------------------------------------------ lint gate

def test_decode_incompatible_op_lint_at_construction():
    """A full-sequence attention op in a decode-plane executor is a
    construction-time error naming the offending op's creation site."""
    import hetu_tpu as ht
    q = ht.placeholder_op("q", shape=(2, 2, 8, 4))
    k = ht.placeholder_op("k", shape=(2, 2, 8, 4))
    v = ht.placeholder_op("v", shape=(2, 2, 8, 4))
    att = ht.ops.sdpa_op(q, k, v, causal=True)   # the flagged line
    with pytest.raises(ValueError) as ei:
        InferenceExecutor([att], decode=True, validate="error",
                          buckets=(2,))
    msg = str(ei.value)
    assert "decode-incompatible-op" in msg
    assert "sdpa_decode_op" in msg          # the fix is named
    assert "test_decode.py" in msg          # creation-site provenance


def test_decode_lint_passes_decode_graph(decode_graph):
    """The real decode graph is clean under the decode plane lint (the
    fixture engine already constructed with validate='error', but assert
    explicitly against the rule registry)."""
    from hetu_tpu.analysis.lint import lint
    feeds, logits, caches, _ = decode_graph
    report = lint([logits] + list(caches), serving=True, decode=True)
    assert not [d for d in report.diagnostics
                if d.rule == "decode-incompatible-op"]


# ------------------------------------------------------------ observability

def test_decode_trace_spans_and_flows(decode_graph):
    """Every token batch is one ``decode.step`` span; request→join→emit
    is stitched with flow arrows, and the join→emit flow terminator is
    timestamp-contained in a decode.step span (machine-checked)."""
    obs.enable(False)
    obs.clear_trace()
    eng = _engine(decode_graph, max_slots=2)
    obs.enable(True)
    try:
        with DecodeRouter(eng) as router:
            s1 = router.submit([5, 9], max_new_tokens=3)
            s2 = router.submit([7], max_new_tokens=2)
            s1.result(timeout=120)
            s2.result(timeout=120)
    finally:
        obs.enable(False)
    evs = obs.trace_events()
    obs.clear_trace()
    steps = [e for e in evs if e.get("ph") == "X"
             and e["name"] == "decode.step"]
    assert steps, "no decode.step spans traced"
    for e in steps:
        assert {"batch", "len", "rows", "emitted"} <= set(e["args"])
    # flows pair by id: one request flow and one join flow per sequence
    for flow in ("decode.request", "decode.join"):
        starts = {e["id"] for e in evs
                  if e.get("ph") == "s" and e["name"] == flow}
        ends = {e["id"] for e in evs
                if e.get("ph") == "f" and e["name"] == flow}
        assert starts and starts == ends, flow
    # ts containment: every join->emit terminator lands inside a step
    spans = [(e["ts"], e["ts"] + e["dur"]) for e in steps]
    for e in evs:
        if e.get("ph") == "f" and e["name"] == "decode.join":
            assert any(t0 <= e["ts"] <= t1 for t0, t1 in spans), \
                "decode.join emit flow outside every decode.step span"


def test_decode_counters_accessor_registered():
    """The decode family rides the one-registry profiler view (the
    counter-coverage gate)."""
    metrics.reset_decode_counts()
    assert HetuProfiler.decode_counters() == {}
    metrics.record_decode("decode_tokens", 3)
    assert HetuProfiler.decode_counters() == {"decode_tokens": 3}
    assert HetuProfiler.all_counters()["decode"] == {"decode_tokens": 3}
    metrics.reset_decode_counts()


# ------------------------------------------------------------ tp-sharded decode

def _tp_plan(layers=None):
    from hetu_tpu.autoparallel import transformer_layer_spec
    from hetu_tpu.autoparallel.cost_model import Strategy
    from hetu_tpu.autoparallel.plan import ParallelPlan
    spec = transformer_layer_spec(_CFG.n_embd, 1, _CFG.n_head,
                                  name="blk", count=_CFG.n_layer)
    plan = ParallelPlan([spec], [Strategy(pp=1, tp=2, dp=1)], 2,
                        est_time=1e-3)
    if layers is not None:
        plan.bind(layers)
    return plan


def test_decode_with_tp_plan_matches_unsharded():
    """A searched tp=2 plan bound to the decode blocks shards the step
    over the mesh and still produces the unsharded token stream."""
    feeds, logits, caches, layers = gpt2_decode_graph(_CFG,
                                                      max_len=_MAX_LEN)
    eng0 = DecodeEngine(feeds, logits, caches, max_slots=2,
                        max_len=_MAX_LEN, seed=0)
    with DecodeRouter(eng0) as router:
        want = router.submit([5, 9, 13], max_new_tokens=4).result(
            timeout=120)
    feeds, logits, caches, layers = gpt2_decode_graph(_CFG,
                                                      max_len=_MAX_LEN)
    eng = DecodeEngine(feeds, logits, caches, max_slots=2,
                       max_len=_MAX_LEN, seed=0,
                       plan=_tp_plan(layers))
    assert eng.iex.mesh is not None and "tp" in eng.iex.mesh.axis_names
    assert eng.iex._plan_fingerprint is not None
    with DecodeRouter(eng) as router:
        got = router.submit([5, 9, 13], max_new_tokens=4).result(
            timeout=120)
    assert got == want


def test_decode_unbound_tp_plan_fails_plan_coverage():
    """A tp plan that never bound the decode layers annotates nothing —
    the plan-coverage lint rejects the executor at construction instead
    of silently serving an unsharded program."""
    feeds, logits, caches, _layers = gpt2_decode_graph(_CFG,
                                                       max_len=_MAX_LEN)
    with pytest.raises(ValueError, match="plan-coverage"):
        DecodeEngine(feeds, logits, caches, max_slots=2,
                     max_len=_MAX_LEN, seed=0, plan=_tp_plan(None))


# ---------------------------------------------------------- the scenario

@pytest.mark.timeout(300)
def test_decode_scenario():
    """``scenarios.decode_scenario`` on its 16-request stream: every
    verdict the decode plane was accepted on, as counts."""
    import scenarios
    res = scenarios.decode_scenario()
    # scheduling AND ingestion mode must not change results
    assert res["streams_bitwise_equal"] is True
    # the compile-once steady state: real builds + serve-cache reuses
    # account for EVERY distinct bucket key — (batch, len) pairs and
    # (batch, chunk, len) triples — and every other step dispatches
    # through a plan_cache_hit
    co = res["compile_once"]
    assert co["holds"] is True
    assert (co["serve_bucket_compiles"] + co["step_cache_serve_hits"]
            == co["bucket_keys"] > 0)
    assert co["plan_cache_hits"] == co["decode_steps"] - co["bucket_keys"]
    # the chunked stream actually saved prefill steps
    assert res["prefill"]["steps_saved_vs_token_by_token"] > 0
    # repeated-prefix requests hit the store, skip prefill rows, and
    # still match the cold run bitwise
    assert res["prefix_cache"]["holds"] is True
    assert res["prefix_cache"]["hits"] > 0
    assert (res["prefix_cache"]["prefill_rows_warm"]
            < res["prefix_cache"]["prefill_rows_cold"])
    # one ttft histogram observation per stream
    assert res["ttft_counted_per_stream"] is True
    assert set(res["rejections"].values()) == {0}
    # ISSUE 19: the mid-generation replica kill recovered every
    # in-flight stream bitwise-equal with zero failures and zero
    # restarts; the zero-survivor kill failed loudly with partials
    rec = res["recovery"]
    assert rec["holds"] is True
    assert rec["failed_streams"] == 0 and rec["restarts"] == 0
    assert rec["streams_bitwise_equal_to_unkilled"] is True
    assert rec["counters"]["decode_recovery_reseated"] >= 1
    assert rec["protocol_conformance"]["ok"] is True
    assert rec["zero_survivor"]["holds"] is True
    assert rec["zero_survivor"]["recovery_exhausted"] >= 1
    assert res["total_tokens"] > 0
    assert res["ok"] is True


def _flops(compiled):
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def _wide_cfg(seq_len):
    return GPT2Config.tiny(n_positions=256, batch_size=1, seq_len=seq_len,
                           n_embd=384, n_layer=4, n_head=4)


@pytest.fixture(scope="module")
def wide_engine():
    """A wider model than ``_CFG`` (the O(1)-vs-O(len) claim is about the
    program's arithmetic), its cache well above the largest length."""
    feeds, logits, caches, _ = gpt2_decode_graph(_wide_cfg(128), max_len=128)
    return DecodeEngine(feeds, logits, caches, max_slots=1, max_len=128,
                        seed=0)


@pytest.mark.parametrize("length", [8, 16, 32, 64])
def test_one_token_step_is_cheaper_than_a_reprefill(wide_engine, length):
    """The incremental KV step against the naive alternative — one FULL
    forward over the ``length``-token prefix for every generated token —
    by what the compiler counts for each program: the one-token step at
    the length bucket that holds ``length`` cached rows and the new one
    costs fewer FLOPs than the full forward, and the gap grows with the
    length."""
    import jax
    import jax.numpy as jnp
    eng = wide_engine
    lb = next(b for b in eng.len_ladder if b >= length + 1)

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    slabs = tuple(
        jax.ShapeDtypeStruct(
            (1, eng._tails[n][0][0], eng._slab_rows(lb),
             eng._tails[n][0][2]), eng._tails[n][1])
        for n in eng.cache_names)
    fed = ({eng._fk["input_ids"]: jax.ShapeDtypeStruct((1, 1), jnp.int32),
            eng._fk["positions"]: jax.ShapeDtypeStruct((1,), jnp.int32)},
           slabs)
    step = jax.jit(eng._program(eng.iex, eng._fk)).lower(
        jax.tree_util.tree_map(sds, eng.iex.params), fed,
        jax.ShapeDtypeStruct((1,), jnp.int32)).compile()

    f2, _loss, logits2 = gpt2_lm_graph(_wide_cfg(length))
    full = InferenceExecutor([logits2], buckets=(1,), seed=0,
                             validate="off", donate=False)
    forward = full.compiled(1).lower(
        jax.tree_util.tree_map(sds, full.params),
        {full._k(f2["input_ids"]):
         jax.ShapeDtypeStruct((1, length), jnp.int32)}).compile()
    incremental, reprefill = _flops(step), _flops(forward)
    assert 0 < incremental < reprefill, (length, incremental, reprefill)
    # the full forward grows with the length, the step (nearly) does not
    assert reprefill / incremental > length / 4, (incremental, reprefill)


@pytest.mark.parametrize("prompt_len", [4, 8, 16, 24])
def test_chunked_prefill_reaches_the_first_token_in_fewer_steps(prompt_len):
    """Time to first token as what it is made of: engine steps from join
    to the first emitted token.  Token-by-token ingestion pays one step a
    prompt token, the chunked entry ``ceil(P / chunk)`` — and the first
    tokens are bitwise equal."""
    from hetu_tpu.models import gpt2_decode_chunked_graph
    from hetu_tpu.serving.decode import _DecodeRequest
    cfg = GPT2Config.tiny(n_positions=64, batch_size=1, seq_len=32)

    def first_token(chunked):
        feeds, logits, caches, _ = gpt2_decode_graph(cfg, max_len=32)
        kw = {}
        if chunked:
            cf, cl, cc, _ = gpt2_decode_chunked_graph(cfg, max_len=32)
            kw = {"chunked": (cf, cl, cc), "max_chunk": 8}
        eng = DecodeEngine(feeds, logits, caches, max_slots=4, max_len=32,
                           seed=0, **kw)
        metrics.reset_all()
        req = _DecodeRequest(np.full(prompt_len, 3, np.int32), max_new=1,
                             eos_id=None, fid=None)
        eng.join(req)
        steps = 0
        while eng.active:
            eng.step()
            steps += 1
        counts = metrics.decode_counts()
        assert counts["decode_steps"] == steps
        return req.stream.result(timeout=60), steps, counts

    tok_t, steps_t, _ = first_token(chunked=False)
    tok_c, steps_c, counts_c = first_token(chunked=True)
    assert tok_c == tok_t and len(tok_c) == 1
    assert steps_t == prompt_len
    assert steps_c == -(-prompt_len // 8) < steps_t
    assert counts_c["decode_prefill_steps_saved"] == steps_t - steps_c


# ------------------------------------------------ state kinds (ISSUE 27)
def test_an_all_kv_graph_is_allocated_grown_and_donated_as_before():
    """GPT-2's graph is the all-``kv`` case of the engine's state kinds:
    the same slabs, the same growth along both ladders, one program per
    bucket pair used, every slab donated to its own update — and both
    entries over one set of weight buffers."""
    import jax
    from hetu_tpu import metrics as ht_metrics
    from hetu_tpu.graph import step_cache
    from hetu_tpu.models import gpt2_decode_chunked_graph
    from hetu_tpu.serving.decode import _DecodeRequest
    step_cache.clear()
    ht_metrics.reset_all()
    cfg = GPT2Config.tiny(n_positions=64, batch_size=1, seq_len=32)
    feeds, logits, caches, _ = gpt2_decode_graph(cfg, max_len=32)
    cf, cl, cc, _ = gpt2_decode_chunked_graph(cfg, max_len=32)
    eng = DecodeEngine(feeds, logits, caches, max_slots=4, max_len=32,
                       seed=0, chunked=(cf, cl, cc), max_chunk=8)
    assert set(eng._kinds.values()) == {"kv"} and eng._head == 2
    heads, lanes = eng._heads, eng._lanes
    n = len(eng.cache_names)

    def slab_bytes(bb, lb):
        return n * bb * heads * eng._slab_rows(lb) * lanes * 4
    assert eng.state_bytes() == {"kv": slab_bytes(1, 1)} \
        and eng.kv_bytes == slab_bytes(1, 1)
    reqs = [_DecodeRequest(np.full(p, 3, np.int32), 6, None, None)
            for p in (9, 2, 5)]
    for r in reqs:
        eng.join(r)
    assert (eng.bb, eng.lb) == (4, 1)           # 1 -> 2 -> 4 seats
    while not eng.idle:
        eng.step()
    c = ht_metrics.decode_counts()
    assert c["decode_batch_grows"] == 2 and eng.lb == 16
    assert c["decode_len_grows"] == 4           # 1 -> 8 at once, then 16
    assert c["decode_kv_bytes_hw"] == c["decode_state_bytes_kv_hw"] \
        == eng.kv_bytes == slab_bytes(4, 16)
    assert "decode_state_clears" not in c
    assert "decode_state_bytes_ring_hw" not in c
    # one chunked program and one one-token program: a bucket pair is
    # compiled when a step first needs it and never again
    assert ht_metrics.serve_counts()["serve_bucket_compiles"] == 2
    # every slab is donated to its own update: the step's aliases pair
    # input i of the slab tuple with output 1 + i
    fed = ({eng._fk["input_ids"]: np.zeros((4, 1), np.int32),
            eng._fk["positions"]: np.zeros(4, np.int32)},
           tuple(eng.caches[k] for k in eng.cache_names))
    text = jax.jit(eng._program(eng.iex, eng._fk),
                   donate_argnums=(1,)).lower(
                       eng.iex.params, fed, np.zeros(4, np.int32)).as_text()
    assert text.count("tf.aliasing_output") >= n
    # one set of weight buffers under both entries
    one = {eng.iex.var_names[v]: eng.iex.params[eng.iex._k(v)]
           for v in eng.iex.var_nodes}
    two = {eng.ciex.var_names[v]: eng.ciex.params[eng.ciex._k(v)]
           for v in eng.ciex.var_nodes}
    assert one.keys() == two.keys() and all(one[k] is two[k] for k in one)


# ------------------------------------------- one step ahead (ISSUE 32)
# The router launches step n+1 before it collects step n; a loop of
# ``engine.step()`` collects each step before the next.  Same programs,
# same tokens.

_AHEAD_SPECS = [([5, 9, 13], 8, None), ([2], 3, None),
                ([7, 3, 11, 4, 1, 8, 6, 2, 9], 6, None), ([1, 1], 1, None),
                ([9, 4, 1, 8], 10, None), ([3, 3, 3, 3, 3], 7, None)]


def _ahead_engine(entry, decode_graph, **kw):
    from hetu_tpu.models import gpt2_decode_chunked_graph
    if entry == "chunked":
        cf, cl, cc, _ = gpt2_decode_chunked_graph(_CFG, max_len=_MAX_LEN)
        kw.update(chunked=(cf, cl, cc), max_chunk=4)
    return _engine(decode_graph, **kw)


@pytest.mark.parametrize("entry", ["one_token", "chunked"])
def test_router_one_step_ahead_emits_the_serial_loops_streams(
        decode_graph, entry):
    """GPT-2's graphs fetch no token: the ids are the engine's own argmax
    on the device.  Six requests through two slots (slot reuse, a
    ``max_new`` of one, a batch bucket that grows under a step in flight):
    the router's streams are the serial loop's, bit for bit."""
    from decode_ahead import assert_same_streams
    serial, ahead = assert_same_streams(
        lambda: _ahead_engine(entry, decode_graph, max_slots=2),
        _AHEAD_SPECS)
    assert (ahead.get("decode_prefill_steps", 0) > 0) == (entry == "chunked")
    assert ahead["decode_slot_recycles"] == serial["decode_slot_recycles"] \
        == len(_AHEAD_SPECS) - 2


def test_the_engines_own_ids_are_the_first_maximum_of_the_logits(
        decode_graph):
    """What replaced the host's ``np.argmax``: the program's ids are the
    argmax of the logits it leaves on the device, first maximum on a tie,
    and a row fed ``-1`` takes the previous step's id."""
    import jax.numpy as jnp
    eng = _engine(decode_graph, max_slots=2)
    eng.reserve(2, 4)
    fed = ({eng._fk["input_ids"]: np.array([[7], [-1]], np.int32),
            eng._fk["positions"]: np.zeros(2, np.int32)},
           tuple(eng.caches[n] for n in eng.cache_names))
    outs = eng._program(eng.iex, eng._fk)(
        eng.iex.params, fed, jnp.asarray([3, 7], jnp.int32))
    ids, logits = np.asarray(outs[0]), np.asarray(outs[1])
    assert ids.dtype == np.int32 and ids.shape == (2,)
    assert np.array_equal(ids, np.argmax(logits, -1))
    # row 1 was fed -1 and took id 7 from ``prev``: the same row as row 0
    assert np.array_equal(logits[0], logits[1])
    tie = jnp.asarray([[0.0, 2.0, 2.0, 1.0], [5.0, 5.0, 5.0, 5.0]])
    assert jnp.argmax(tie, -1).tolist() == np.argmax(tie, -1).tolist() \
        == [1, 0]


def test_eos_hit_with_a_step_in_flight_drops_the_stray_token(decode_graph):
    """``eos_id`` matches at the collect of step n with step n+1 launched
    and carrying the row: that answer is dropped (one step more than the
    serial loop, not one token more), and the slot's next occupant decodes
    as if alone."""
    from decode_ahead import serve_router, serve_serial
    a, b = [5, 9, 13], [9, 4, 1, 8]
    (free, after), _ = serve_serial(
        _engine(decode_graph, max_slots=1), [(a, 10, None), (b, 6, None)])
    toks = free.result(0)
    k = max(i for i in range(len(toks) - 1) if toks[i] not in toks[:i])
    specs = [(a, 10, toks[k]), (b, 6, None)]
    serial, c_serial = serve_serial(_engine(decode_graph, max_slots=1), specs)
    ahead, c_ahead = serve_router(_engine(decode_graph, max_slots=1), specs)
    for s in (serial, ahead):
        assert s[0].result(0) == toks[:k + 1]
        assert s[1].result(0) == after.result(0)
    assert c_ahead["decode_tokens"] == c_serial["decode_tokens"] == k + 1 + 6
    assert c_ahead["decode_steps"] == c_serial["decode_steps"] + 1
    assert c_ahead["decode_leaves"] == c_serial["decode_leaves"] == 2


def test_no_launch_carries_a_row_done_by_max_new(decode_graph):
    """``max_new`` is known when a step is launched: the row's last step
    is its last launch, so the router makes exactly the serial loop's
    steps, every one but the first launched ahead."""
    from decode_ahead import serve_router, serve_serial
    specs = [([5, 9, 13], 8, None)]
    _, c_serial = serve_serial(_engine(decode_graph, max_slots=1), specs)
    _, c_ahead = serve_router(_engine(decode_graph, max_slots=1), specs)
    assert c_ahead["decode_steps"] == c_serial["decode_steps"] == 3 + 8 - 1
    assert c_ahead["decode_padded_row_tokens"] \
        == c_serial["decode_padded_row_tokens"]
    assert c_ahead["decode_launches_ahead"] == c_ahead["decode_steps"] - 1


def test_device_error_at_collect_with_a_later_step_launched(decode_graph):
    """A step's error surfaces where its answer is waited for, with the
    next step already launched: ``abort`` fails every seated stream and
    drops both steps, and the router serves the next request as ever."""
    eng = _engine(decode_graph, max_slots=2)
    collect, fired = eng.collect, []

    def failing(fl, ph):
        if fl is not None and eng.in_flight is not fl and not fired:
            fired.append(fl)
            raise RuntimeError("device lost")
        return collect(fl, ph)

    eng.collect = failing
    with DecodeRouter(eng) as router:
        doomed = [router.submit(p, max_new_tokens=8)
                  for p in ([5, 9, 13], [2, 4])]
        for s in doomed:
            with pytest.raises(RuntimeError, match="device lost"):
                s.result(timeout=120)
        assert fired and eng.in_flight is None
        got = router.submit([5, 9, 13], max_new_tokens=8).result(timeout=120)
    fresh = _engine(decode_graph, max_slots=2)
    with DecodeRouter(fresh) as router:
        want = router.submit([5, 9, 13], max_new_tokens=8).result(timeout=120)
    assert got == want
