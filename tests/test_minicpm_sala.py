"""MiniCPM-SALA (InfLLM-v2 block-sparse attention 1:3 with Lightning linear
attention) through ``DecodeEngine``, against the plain full-sequence
reference of ``benchmarks/reference/minicpm_sala_lm.py`` in float32 on the
CPU; its ops one by one against what the equations say."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu import metrics
from hetu_tpu.models import (MiniCPMSALAConfig,
                             minicpm_sala_decode_chunked_graph,
                             minicpm_sala_decode_graph, minicpm_sala_lm_graph)
from hetu_tpu.models.minicpm_sala import param_names
from hetu_tpu.ops import lightning, sparse_attention as sparse
from hetu_tpu.profiler import HetuProfiler
from hetu_tpu.serving import DecodeEngine, DecodeRouter, InferenceExecutor
from hetu_tpu.serving.decode import _DecodeRequest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import sys  # noqa: E402
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.reference import minicpm_sala_lm as ref  # noqa: E402

with open(os.path.join(ROOT, "tests", "bench_harness", "data",
                       "tiny-sala.json")) as _f:
    #: the tiny preset as the reference reads a configuration
    TINY = json.load(_f)
MAX_LEN = 128
BLOCKS = "sparse_blocks"
#: float32 sums in another order (a chunk's products, the pooled keys): a
#: logit of size ~1 to 2e-6
TOL = 2e-6


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def draw(cfg, seed=0):
    """Seeded weights with the matrices twice the spec's spread, so that
    the mixers move the logits and a wrong one shows."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, (shape, mean, std) in ref.param_spec(cfg).items():
        wide = 2 if name.endswith(".weight") else 1
        out[name] = (rng.standard_normal(shape) * std * wide
                     + mean).astype(np.float32)
    return out


def program_weights(weights):
    return {"sala" + k[k.index("."):]: v for k, v in weights.items()}


@pytest.fixture(scope="module")
def weights():
    return draw(TINY)


@pytest.fixture(scope="module")
def ref_logits(weights):
    """``(ids, blocks, start) -> (logits, info)`` of the reference over a
    sequence padded to ``MAX_LEN`` (one program), following ``blocks`` —
    (positions, sparse layers, G, topk) from position ``start`` — or
    choosing for itself (None)."""
    w = {k: jnp.asarray(v) for k, v in weights.items()}
    fn = jax.jit(lambda ids, blocks: ref.logits(w, ids, TINY, blocks=blocks))

    def run(ids, blocks=None, start=0):
        full = np.full((MAX_LEN, 2, 2, 2), -1, np.int32)
        if blocks is not None:
            full[start:start + len(blocks)] = blocks
        logits, info = fn(jnp.asarray(np.pad(ids, (0, MAX_LEN - len(ids))),
                                      jnp.int32), jnp.asarray(full))
        return np.asarray(logits)[:len(ids)], info
    return run


def engine(weights, max_chunk=8, slots=4, **kw):
    cfg = MiniCPMSALAConfig.tiny()
    f, lg, st, tok, bl = minicpm_sala_decode_graph(cfg, MAX_LEN)
    chunked = None
    if max_chunk:
        cf, cl, cs, ctok, cbl = minicpm_sala_decode_chunked_graph(cfg, MAX_LEN)
        chunked = (cf, cl, cs, ctok, {BLOCKS: cbl})
    eng = DecodeEngine(f, lg, st, weights=program_weights(weights),
                       tokens=tok, aux={BLOCKS: bl},
                       aux_fold={BLOCKS: cfg.block_counters()},
                       max_slots=slots, max_len=MAX_LEN, chunked=chunked,
                       max_chunk=max_chunk or None, **kw)
    eng.reserve(slots, MAX_LEN)
    return eng


def serve(eng, prompts, new, ref_logits=None, **req):
    """Drive ``prompts`` through ``eng`` to the end; returns the token
    streams, every served row's logits per request, the streams, and the
    worst gap between a served row's logits and the reference's at that
    position (the reference following the blocks the program chose: the
    margin of every choice, in the reference's own scores, is 0)."""
    reqs = [_DecodeRequest(np.asarray(p, np.int32), new, None, None, **req)
            for p in prompts]
    slot = {id(r): eng.join(r) for r in reqs}
    rows = {id(r): [] for r in reqs}
    while not eng.idle:
        before = {id(r): r.stream.n_tokens for r in reqs}
        eng.step()
        for r in reqs:
            if r.stream.n_tokens != before[id(r)]:
                rows[id(r)].append(eng.last_logits[slot[id(r)]].copy())
    worst = 0.0
    for r in reqs:
        if ref_logits is None:
            continue
        tokens = r.stream.result(0)
        seq = np.concatenate([r.prompt, np.asarray(tokens[:-1], np.int32)])
        stream = r.stream
        chosen = stream.aux(BLOCKS)
        assert len(chosen) == len(seq) - stream.aux_from
        want, info = ref_logits(seq, chosen, stream.aux_from)
        want = want[len(r.prompt) - 1:]
        worst = max(worst, float(np.abs(np.stack(rows[id(r)]) - want).max()))
        assert tokens == list(want.argmax(-1))
        assert float(info["select_margin"]) < 1e-6
    return ([r.stream.result(0) for r in reqs], [np.stack(rows[id(r)])
                                                 for r in reqs],
            [r.stream for r in reqs], worst)


def prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], n) for n in lengths]


# ------------------------------------------------------------- the model

def test_the_cut_counts_what_the_configuration_file_says():
    """Every key of the catalog's ``config`` as published but the depth and
    the layer list, which are published layers 9-16; the sizes the config
    lacks under ``assumed`` with their sources; 2.82 B parameters here, 9.48
    B uncut — from the reference's spec, from the program's variables and
    in the file."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "minicpm-sala.json")) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "max_position_embeddings": 524288, "model_type": "minicpm_sala",
        "num_attention_heads": 32, "num_key_value_heads": 2,
        "qk_norm": True, "rand_init": False, "rms_norm_eps": 1e-06,
        "vocab_size": 73448, "rope_theta": 10000, "scale_emb": 12,
        "scale_depth": 1.4, "mup_denominator": 32, "dim_model_base": 256,
        "tie_word_embeddings": False, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "mixer_types"]
    whole = cfg["published"]
    assert (cfg["num_hidden_layers"], whole["num_hidden_layers"]) == (8, 32)
    assert cfg["mixer_types"] == whole["mixer_types"][9:17] \
        == ["minicpm4"] + ["lightning-attn"] * 6 + ["minicpm4"]
    assert whole["mixer_types"].count("minicpm4") == 8
    assert cfg["assumed"]["sparse"]["value"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "window_size": 2048, "topk": 64, "init_blocks": 1, "dense_len": 8192}
    for key in ("sparse", "pooling", "decay", "gates", "norms"):
        assert cfg["assumed"][key]
    assert "pipeline stages" in cfg["deployment"]
    spec = ref.param_spec(cfg)
    count = sum(int(np.prod(shape)) for shape, _, _ in spec.values())
    assert count == cfg["parameters"] == 2820545280
    assert ref.parameters_published(cfg) \
        == cfg["parameters_published_check"] == 9477110784     # 9.48 B
    mcfg = MiniCPMSALAConfig(num_hidden_layers=8,
                             mixer_types=cfg["mixer_types"])
    names = param_names(mcfg)
    assert {"sala" + k[k.index("."):]: tuple(v[0])
            for k, v in spec.items()} == names
    assert [mcfg.layer_kind(i) for i in range(8)] \
        == [ref.layer_kind(cfg, i) for i in range(8)]
    assert mcfg.sparse == cfg["assumed"]["sparse"]["value"]
    assert abs(mcfg.residual_scale - 1.4 / np.sqrt(32)) < 1e-12
    assert mcfg.logit_scale == 1 / 16


def test_full_sequence_graph_is_the_reference(weights, ref_logits):
    cfg = MiniCPMSALAConfig.tiny()
    ids = prompts(1, [77])[0].astype(np.int32)
    feeds, logits, blocks = minicpm_sala_lm_graph(cfg, len(ids))
    iex = InferenceExecutor([logits, blocks],
                            weights=program_weights(weights), buckets=(1,))
    got, chosen = iex.infer({feeds["input_ids"]: ids[None]})
    want, info = ref_logits(ids)
    assert np.abs(got - want).max() < TOL
    assert np.array_equal(chosen[0], np.asarray(info["blocks"])[:len(ids)])
    assert (chosen[0][:31] == -1).all() and (chosen[0][31:, :, :, 0] == 0).all()


# ------------------------------------------------------------- the engine

@pytest.mark.parametrize("max_chunk", [0, 8, 32],
                         ids=["one_token", "chunk8", "chunk32"])
def test_engine_serves_the_reference_at_every_position(weights, ref_logits,
                                                       max_chunk):
    """Prompts of 1 to 70 tokens (the indexer selects from 32 keys on)
    prefilled by chunks up to ``max_chunk`` (0: token by token), 20 tokens
    generated in a mixed batch, then the slots seated AGAIN: at every served
    position the engine's logits are the plain forward's."""
    eng = engine(weights, max_chunk)
    *_, worst = serve(eng, prompts(2, [3, 45, 70, 1]), 20, ref_logits)
    assert worst < TOL
    *_, worst = serve(eng, prompts(3, [37, 2]), 12, ref_logits)
    assert worst < TOL
    assert metrics.decode_counts()["decode_state_clears"] >= 6


def test_one_token_path_and_chunked_path_serve_the_same(weights):
    ps = prompts(4, [11, 50, 5])
    slow = serve(engine(weights, 0), ps, 10)[0]
    fast = serve(engine(weights, 16), ps, 10)[0]
    assert slow == fast


def test_router_serves_it_and_hands_on_the_chosen_blocks(weights, ref_logits):
    eng = engine(weights, 8)
    prompt = prompts(8, [40])[0].astype(np.int32)
    with DecodeRouter(eng) as router:
        stream = router.submit(prompt, max_new_tokens=9)
        tokens = stream.result(timeout=120)
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    got = stream.aux(BLOCKS)
    assert got.shape == (len(seq), 2, 2, 2) and got.dtype == np.int16
    want, info = ref_logits(seq)
    assert tokens == list(want[len(prompt) - 1:].argmax(-1))
    assert np.array_equal(got, np.asarray(info["blocks"])[:len(seq)])


def test_state_kinds_and_counters(weights):
    """Four kinds of state side by side: the compressed keys an ``index``
    slab of a row per 2 positions, gauged on its own; the folded block
    counters; the rows a selective read fetches."""
    metrics.reset_all()            # the call families too
    eng = engine(weights, 0, slots=2)
    kinds = eng._kinds
    assert sorted(set(kinds.values())) == ["index", "kv", "recurrent"]
    assert [sum(k == kind for k in kinds.values())
            for kind in ("index", "kv", "recurrent")] == [2, 4, 8]
    assert eng.caches["index_0"].shape == (2, 2, MAX_LEN // 2 // 8, 128)
    assert eng.caches["k_cache_0"].shape == (2, 2, MAX_LEN // 8, 128)
    by_kind = eng.state_bytes()
    assert by_kind["index"] * 2 * 2 == by_kind["kv"]     # K and V, stride 2
    serve(eng, prompts(9, [40]), 6)
    c = metrics.decode_counts()
    assert c["decode_state_bytes_index_hw"] == by_kind["index"]
    # 40 + 5 steps, the one row; dense below 32 keys: 14 reads x 2 layers
    # x 2 heads select, 2 far + 2 window blocks each
    assert c["sparse_reads"] == 14 * 4
    assert c["sparse_blocks_chosen"] == 14 * 4 * 4
    assert c["sparse_blocks_far"] == 14 * 4            # block 0 is the init
    assert 2 * 14 * 4 <= c["sparse_block_runs"] <= 3 * 14 * 4
    assert c["decode_index_rows_live"] == sum(n // 2 for n in range(1, 46))
    assert c["decode_kv_rows_read"] == c["decode_kv_rows_held"]   # the CPU
    assert set(HetuProfiler.sparse_attn_calls()) == {"4x8:jnp"}


def test_selected_rows_are_counted_by_what_the_kernel_fetches(weights,
                                                              monkeypatch):
    eng = engine(weights, 0, slots=2)
    # where the decode gate lets the kernel in (the chip, a slab of 128 x n)
    monkeypatch.setattr("hetu_tpu.ops.attention._decode_gate_reason",
                        lambda rows: None)
    eng.positions[:] = [9, 99]
    # 10 keys: 2 live blocks of 8; 100 keys: the 4 chosen blocks
    assert eng._selected == (8, 4, 32)
    assert eng._kv_rows(1) == (16 + 32, 2 * MAX_LEN)
    assert eng._kv_rows(8) == (2 * MAX_LEN, 2 * MAX_LEN)


# -------------------------------------------------------------- Lightning

def _lightning_explicit(q, k, v, rate):
    """``o_t = Σ_{s<=t} λ^{t−s} (q_t·k_s) v_s`` and the state it leaves."""
    t = q.shape[0]
    o = np.zeros_like(v)
    for i in range(t):
        for s in range(i + 1):
            o[i] += np.exp(-rate * (i - s))[:, None] * np.sum(
                q[i] * k[s], -1, keepdims=True) * v[s]
    state = sum(np.exp(-rate * (t - 1 - s))[:, None, None]
                * k[s][:, :, None] * v[s][:, None, :] for s in range(t))
    return o, state


def test_lightning_one_token_is_the_chunk_is_the_explicit_sum():
    rng = np.random.default_rng(0)
    h, d, t = 3, 4, 11
    q, k, v = (0.4 * rng.standard_normal((t, h, d)).astype(np.float32)
               for _ in range(3))
    rate = np.asarray(lightning.decay_rates(h))
    assert np.allclose(rate, 2.0 ** (-8 * (np.arange(h) + 1) / h))
    want, want_state = _lightning_explicit(q, k, v, rate)
    zero = jnp.zeros((1, h, d, d))
    o, state = lightning._chunk(*(jnp.asarray(x[None]) for x in (q, k, v)),
                                jnp.asarray(rate), zero, jnp.asarray([t]))
    assert np.abs(np.asarray(o[0]) - want).max() < 2e-6
    assert np.abs(np.asarray(state[0]) - want_state).max() < 2e-6
    s, outs = zero, []
    for i in range(t):
        step, s = lightning._one_token(
            *(jnp.asarray(x[None, i]) for x in (q, k, v)), jnp.asarray(rate),
            s, jnp.asarray([True]))
        outs.append(np.asarray(step[0]))
    assert np.abs(np.stack(outs) - want).max() < 2e-6
    assert np.abs(np.asarray(s[0]) - want_state).max() < 2e-6
    # two chunks carry the state; columns past ``valid`` leave it alone
    o1, s1 = lightning._chunk(*(jnp.asarray(x[None, :8]) for x in (q, k, v)),
                              jnp.asarray(rate), zero, jnp.asarray([5]))
    _, want5 = _lightning_explicit(q[:5], k[:5], v[:5], rate)
    assert np.abs(np.asarray(s1[0]) - want5).max() < 2e-6
    assert np.abs(np.asarray(o1[0, :5]) - want[:5]).max() < 2e-6
    o2, s2 = lightning._chunk(*(jnp.asarray(x[None, 5:]) for x in (q, k, v)),
                              jnp.asarray(rate), s1, jnp.asarray([t - 5]))
    assert np.abs(np.asarray(o2[0]) - want[5:]).max() < 2e-6
    assert np.abs(np.asarray(s2[0]) - want_state).max() < 2e-6
    idle, kept = lightning._one_token(
        *(jnp.asarray(x[None, 0]) for x in (q, k, v)), jnp.asarray(rate), s2,
        jnp.asarray([False]))
    assert np.array_equal(np.asarray(kept), np.asarray(s2))


# ------------------------------------------------------------ the indexer

@pytest.mark.parametrize("chunks", [[1] * 23, [5, 1, 1, 8, 3, 5], [23],
                                    [3, 0, 4, 16]])
def test_compressed_rows_appear_exactly_when_a_kernel_completes(chunks):
    """Row ``s`` is the mean of keys ``2 s .. 2 s + 3`` and is handed out
    by the step that consumes key ``2 s + 3``, however the sequence is cut
    into chunks (a 0: an idle row's step)."""
    rng = np.random.default_rng(0)
    g, d, stride = 2, 4, 2
    total = sum(chunks)
    k = rng.standard_normal((1, g, total, d)).astype(np.float32)
    pool, at, out = jnp.zeros((1, g, 2, d)), 0, {}
    for c in chunks:
        width = max(c, 1)
        rows, first, count, pool = sparse._pool_rows(
            None, jnp.asarray(np.pad(k[:, :, at:at + c],
                                     ((0, 0),) * 2 + ((0, width - c),
                                                      (0, 0)))),
            pool, jnp.asarray([at]), np.zeros((1, width), np.int32),
            jnp.asarray([c]), stride=stride)
        first, count = int(first[0]), int(count[0])
        # exactly the kernels that end inside this chunk
        assert [first + r for r in range(count)] == [
            s for s in range(total) if at < 2 * s + 4 <= at + c]
        for r in range(count):
            out[first + r] = np.asarray(rows[0, :, r])
        at += c
    assert sorted(out) == list(range((total - 4) // 2 + 1))
    for s, row in out.items():
        assert np.abs(row - k[0, :, 2 * s:2 * s + 4].mean(1)).max() < 1e-6


def _own_selection(a, t, z):
    """The reference's rule in plain numpy: block scores from the kernels
    that overlap a block, then a stable ranking."""
    n = t + 1
    done = max((n - z.kernel) // z.stride + 1, 0)
    blocks = -(-len(a) * z.stride // z.block)
    score = np.full(max(blocks, z.topk), -np.inf)
    for j in range(blocks):
        rows = [s for s in range(done)
                if s * z.stride + z.kernel - 1 >= j * z.block
                and s * z.stride <= j * z.block + z.block - 1]
        if rows:
            score[j] = max(a[s] for s in rows)
    edge = t // z.block - (z.near - 1)
    ranked = np.where(np.arange(len(score)) < z.init, np.inf, score)
    ranked = np.where(np.arange(len(score)) < edge, ranked, -np.inf)
    return sorted(np.argsort(-ranked, kind="stable")[:z.topk].tolist())


def test_selection_is_the_references_on_random_scores_and_on_ties():
    z = sparse.SparseSizes(kernel_size=4, kernel_stride=2, block_size=8,
                           window_size=16, topk=3, init_blocks=1,
                           dense_len=48)
    rng = np.random.default_rng(0)
    rows = 64                                  # 128 positions, 16 blocks
    for trial in range(60):
        a = rng.random(rows).astype(np.float32)
        if trial % 3 == 0:
            # ties everywhere, the window's edge included
            a = np.round(a * 3) / 3
        t = int(rng.integers(47, 128))
        scores = sparse.block_scores(
            jnp.asarray(a), jnp.asarray(z.done(t + 1)), z.block // z.stride,
            z.kernel // z.stride - 1)
        got = sparse.select_blocks(scores, jnp.asarray(t), z)
        assert np.asarray(got).tolist() == _own_selection(a, t, z), (trial, t)
    short = sparse.select_blocks(scores, jnp.asarray(46), z)
    assert np.asarray(short).tolist() == [-1, -1, -1]
    with pytest.raises(ValueError, match="fewer than topk"):
        sparse.SparseSizes(kernel_size=4, kernel_stride=2, block_size=8,
                           window_size=16, topk=3, dense_len=32)


def _slabs(rng, b, g, length, d, stride):
    k, v = (rng.standard_normal((b, g, length, d)).astype(np.float32)
            for _ in range(2))
    index = np.stack([k[:, :, s * stride:s * stride + 2 * stride].mean(2)
                      for s in range(length // stride)], 2)
    return k, v, index


def test_sparse_read_is_dense_below_dense_len_and_masked_above():
    z = sparse.SparseSizes(kernel_size=4, kernel_stride=2, block_size=8,
                           window_size=16, topk=2, init_blocks=1,
                           dense_len=32)
    rng = np.random.default_rng(1)
    b, g, r, d, length, chunk = 3, 2, 2, 8, 64, 4
    k, v, index = _slabs(rng, b, g, length, d, z.stride)
    q = rng.standard_normal((b, chunk, g, r, d)).astype(np.float32)
    t = np.asarray([[5, 6, 7, 8], [27, 28, 29, 30], [50, 51, 52, 53]])
    out, ids = sparse._read_masked(*(jnp.asarray(x) for x in (
        q, k, v, index, t, np.asarray([4, 4, 4]))), z)
    out, ids = np.asarray(out), np.asarray(ids)

    def dense(bi, c, keys):
        s = np.einsum("grd,gmd->grm", q[bi, c], k[bi][:, keys])
        p = np.exp(s - s.max(-1, keepdims=True))
        return np.einsum("grm,gmd->grd", p / p.sum(-1, keepdims=True),
                         v[bi][:, keys])

    for bi in range(2):                        # every key up to the query's
        for c in range(chunk):
            assert (ids[bi, c] == -1).all()
            assert np.abs(out[bi, c] - dense(bi, c, np.arange(
                t[bi, c] + 1))).max() < 1e-5
    for c in range(chunk):                     # the chosen blocks' keys only
        assert (ids[2, c] >= 0).all() and (ids[2, c, :, 0] == 0).all()
        for gi in range(g):
            first = t[2, c] // 8 - 1
            blocks = sorted(set(ids[2, c, gi].tolist())
                            | set(range(first, t[2, c] // 8 + 1)))
            keys = np.concatenate([np.arange(8 * j, 8 * j + 8)
                                   for j in blocks])
            keys = keys[keys <= t[2, c]]
            assert len(keys) < t[2, c] + 1
            assert np.abs(out[2, c, gi] - dense(2, c, keys)[gi]).max() < 1e-5
    # slot groups, an idle one skipped: the same rows
    sparse_bytes = sparse._SCORE_BYTES
    try:
        sparse._SCORE_BYTES = chunk * g * r * length * 4
        grouped, gids = sparse._read_masked(*(jnp.asarray(x) for x in (
            q, k, v, index, t, np.asarray([4, 0, 4]))), z)
    finally:
        sparse._SCORE_BYTES = sparse_bytes
    assert np.array_equal(np.asarray(gids)[[0, 2]], ids[[0, 2]])
    assert np.abs(np.asarray(grouped)[[0, 2]] - out[[0, 2]]).max() < 1e-6
    assert not np.asarray(grouped)[1].any()
    assert (np.asarray(gids)[1] == -1).all()       # nothing read, none chosen


def test_selected_block_kernel_is_the_masked_read():
    """The one-token kernel in interpret mode (the TPU interpreter, VMEM
    that reads NaN until written) over the schedule ``_schedule`` makes of
    the chosen blocks, against the masked read of the whole slab: a row that
    reads everything, one past ``dense_len``, one whose last block is
    partly filled."""
    from hetu_tpu.ops.pallas.decode_attention import decode_attention_blocks
    z = sparse.SparseSizes(kernel_size=32, kernel_stride=16, block_size=64,
                           window_size=256, topk=5, init_blocks=1,
                           dense_len=1024)
    rng = np.random.default_rng(2)
    b, g, r, d, length = 3, 2, 16, 128, 2048
    k, v, index = _slabs(rng, b, g, length, d, z.stride)
    q = (rng.standard_normal((b, 1, g, r, d)) * 0.3).astype(np.float32)
    t = np.asarray([700, 1500, 2047])
    want, ids = sparse._read_masked(*(jnp.asarray(x) for x in (
        q, k, v, index, t[:, None], np.ones(3, np.int32))), z)
    far = jnp.asarray(ids)[:, 0]
    blocks, counts = sparse._schedule(far, jnp.asarray(t), length, z)
    assert blocks.shape == (b, g, 16)
    assert np.asarray(counts).tolist() == [[11, 11], [9, 9], [9, 9]]
    got = decode_attention_blocks(
        jnp.asarray(q[:, 0]), jnp.asarray(k), jnp.asarray(v), t + 1, blocks,
        counts, block_rows=z.block, interpret=True)
    assert np.abs(np.asarray(got) - np.asarray(want)[:, 0]).max() < 2e-5
    assert HetuProfiler.decode_attn_calls().get("1x16x64", 0) >= 1
