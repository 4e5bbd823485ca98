"""Pallas kernel parity tests (interpret mode, so CPU CI exercises the
exact kernel code that compiles on TPU — closes the round-1 gap where the
TPU-only branch was dead under CPU tests)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.ops.attention import sdpa_reference
from hetu_tpu.ops.pallas.flash_attention import flash_attention


#: block shapes the parity tests run under: the module's rule (s <= 512
#: non-causal: the WHOLE key range in one block — straight softmax, the
#: one-pass backward) and 128 x 128 (two or three key blocks at s = 256 /
#: 384: the online-softmax forward, the dq + dkv backward)
BLOCKS = pytest.mark.parametrize(
    "blocks", [{}, {"block_q": 128, "block_k": 128}],
    ids=["rule", "128x128"])


def _rand_qkv(b, h, s, d, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, s, d).astype(np.float32) * 0.3,
                             dtype)
    return mk(), mk(), mk()


@BLOCKS
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("s", [256, 384])
def test_flash_forward_parity(causal, s, blocks):
    q, k, v = _rand_qkv(2, 3, s, 64)
    out = flash_attention(q, k, v, causal=causal, interpret=True, **blocks)
    ref = sdpa_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@BLOCKS
@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_parity(causal, blocks):
    q, k, v = _rand_qkv(1, 2, 256, 64, seed=1)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True, **blocks) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(sdpa_reference(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_cross_attention_shapes(causal):
    # s_q != s_kv (decoder incremental attention); causal must match the
    # reference's bottom-right-aligned diagonal (tril offset s_kv - s_q)
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(1, 2, 256, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, 512, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, 512, 64).astype(np.float32))
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = sdpa_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       interpret=True) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(sdpa_reference(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


def test_flash_bf16():
    q, k, v = _rand_qkv(1, 2, 256, 64, seed=3, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, interpret=True)
    ref = sdpa_reference(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_flash_ragged_bucketing_parity():
    """Ragged (non-128-multiple) lengths bucket: pad to the next
    flash-legal length, mask the pad keys through the lengths strip
    path, unpad — fwd AND grad parity vs the reference at seq=200
    (bucket 256), the regime the old hard gate silently excluded."""
    s = 200
    q, k, v = _rand_qkv(2, 2, s, 32, seed=21)
    for causal in (False, True):
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        assert out.shape == q.shape
        ref = sdpa_reference(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)
    _grad_parity(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, interpret=True) ** 2),
        lambda q, k, v: jnp.sum(sdpa_reference(
            q, k, v, causal=True) ** 2),
        (q, k, v), "qkv")


def test_flash_ragged_roundtrip_matches_manual_pad():
    """pad → kernel → unpad is EXACT: the bucketed ragged call equals
    hand-padding to the bucket with an explicit lengths mask and slicing
    the result (same kernel, same blocks — bitwise)."""
    from hetu_tpu.ops.pallas.flash_attention import flash_bucket
    s = 200
    sp = flash_bucket(s)
    assert sp == 256
    q, k, v = _rand_qkv(2, 2, s, 32, seed=22)
    out = flash_attention(q, k, v, interpret=True)
    pad = [(0, 0), (0, 0), (0, sp - s), (0, 0)]
    qp, kp, vp = (jnp.pad(x, pad) for x in (q, k, v))
    manual = flash_attention(qp, kp, vp,
                             lengths=jnp.full((2,), s, jnp.int32),
                             interpret=True)[:, :, :s]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(manual))


def test_flash_ragged_with_bias_and_mask():
    """seq=384+r with additive bias (and a key mask) stays on the kernel
    path: parity incl. dbias through the pad/unpad wrapper."""
    s = 421                          # buckets to 512
    q, k, v = _rand_qkv(1, 2, s, 16, seed=23)
    rng = np.random.RandomState(23)
    bias = jnp.asarray(rng.randn(1, 2, s, s).astype(np.float32) * .5)
    km = jnp.asarray(rng.rand(1, s) > 0.3)
    out = flash_attention(q, k, v, bias=bias, key_mask=km, interpret=True)
    ref = sdpa_reference(q, k, v, bias=bias, mask=km[:, None, None, :])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    _grad_parity(
        lambda q, k, v, b: jnp.sum(flash_attention(
            q, k, v, bias=b, key_mask=km, interpret=True) ** 2),
        lambda q, k, v, b: jnp.sum(sdpa_reference(
            q, k, v, bias=b, mask=km[:, None, None, :]) ** 2),
        (q, k, v, bias), ["q", "k", "v", "bias"])


def test_flash_causal_ragged_cross_attention_raises():
    # the ONE unbucketable case: causal cross-attention whose lengths
    # differ mod 128 (padding would shift the aligned diagonal)
    q, k, v = _rand_qkv(1, 1, 256, 64)
    with pytest.raises(ValueError, match="diagonal"):
        flash_attention(q[:, :, :100], k, v, causal=True, interpret=True)


# ----------------------------------------------------- masked/biased paths
# (round-2 verdict: masked/bias attention always fell back to the XLA
# composed reference, so padded pretraining never reached the kernel)

def _grad_parity(f_flash, f_ref, args, names, rtol=2e-4, atol=2e-4):
    gf = jax.grad(f_flash, argnums=tuple(range(len(args))))(*args)
    gr = jax.grad(f_ref, argnums=tuple(range(len(args))))(*args)
    for a, b, n in zip(gf, gr, names):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=rtol, atol=atol, err_msg=n)


@BLOCKS
@pytest.mark.parametrize("causal", [False, True])
def test_flash_key_mask(causal, blocks):
    # non-prefix key masks (the general padded-batch form: BERT attention
    # masks that are NOT sorted-by-length prefixes)
    q, k, v = _rand_qkv(2, 3, 256, 64, seed=5)
    rng = np.random.RandomState(5)
    km = jnp.asarray(rng.rand(2, 256) > 0.3)
    out = flash_attention(q, k, v, causal=causal, key_mask=km,
                          interpret=True, **blocks)
    ref = sdpa_reference(q, k, v, causal=causal, mask=km[:, None, None, :])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    _grad_parity(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=causal, key_mask=km, interpret=True,
            **blocks) ** 2),
        lambda q, k, v: jnp.sum(sdpa_reference(
            q, k, v, causal=causal, mask=km[:, None, None, :]) ** 2),
        (q, k, v), "qkv")


@pytest.mark.parametrize("gshape", [(2, 3), (1, 3), (2, 1), (1, 1)])
def test_flash_full_mask_broadcast_groups(gshape):
    # every broadcast group layout of a full mask, incl. fully-masked rows
    # (which must yield ZERO output, not a uniform-softmax value leak)
    q, k, v = _rand_qkv(2, 3, 256, 64, seed=6)
    rng = np.random.RandomState(6)
    fm = rng.rand(*gshape, 256, 256) > 0.3
    fm[..., 5, :] = False                       # a fully-masked query row
    fm = jnp.asarray(fm)
    out = flash_attention(q, k, v, mask=fm, interpret=True)
    ref = sdpa_reference(q, k, v, mask=fm)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert float(jnp.abs(out[0, 0, 5]).max()) == 0.0
    _grad_parity(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, mask=fm, interpret=True) ** 2),
        lambda q, k, v: jnp.sum(sdpa_reference(q, k, v, mask=fm) ** 2),
        (q, k, v), "qkv")


@BLOCKS
@pytest.mark.parametrize("gshape", [(1, 3), (2, 3), (1, 1)])
def test_flash_bias_grad(gshape, blocks):
    # differentiable additive bias (T5 relative position bias): dbias is
    # emitted per-block and broadcast-reduced to the stored bias shape
    q, k, v = _rand_qkv(2, 3, 256, 64, seed=7)
    rng = np.random.RandomState(7)
    bias = jnp.asarray(rng.randn(*gshape, 256, 256).astype(np.float32) * .5)
    out = flash_attention(q, k, v, bias=bias, interpret=True, **blocks)
    ref = sdpa_reference(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    _grad_parity(
        lambda q, k, v, b: jnp.sum(flash_attention(
            q, k, v, bias=b, interpret=True, **blocks) ** 2),
        lambda q, k, v, b: jnp.sum(sdpa_reference(q, k, v, bias=b) ** 2),
        (q, k, v, bias), ["q", "k", "v", "bias"])


@pytest.mark.parametrize(
    "blocks", [{}, {"block_q": 128, "block_k": 128},
               {"block_q": 128, "block_k": 256}],
    ids=["rule", "128x128", "128x256"])
def test_flash_mask_bias_causal_combo(blocks):
    # XLNet-style: permutation mask + positional bias + causal, with grads.
    # The rule takes the 256 keys whole in one 256 x 256 program; 128 x 128
    # prunes the block above the diagonal; 128 x 256 puts the diagonal,
    # the full mask and the dbias tiles through the whole-range kernels
    # with dk/dv summed in scratch over two query blocks
    q, k, v = _rand_qkv(2, 2, 256, 64, seed=8)
    rng = np.random.RandomState(8)
    fm = jnp.asarray(rng.rand(2, 2, 256, 256) > 0.2)
    bias = jnp.asarray(rng.randn(1, 2, 256, 256).astype(np.float32) * .5)
    out = flash_attention(q, k, v, causal=True, mask=fm, bias=bias,
                          interpret=True, **blocks)
    ref = sdpa_reference(q, k, v, causal=True, mask=fm, bias=bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    _grad_parity(
        lambda q, k, v, b: jnp.sum(flash_attention(
            q, k, v, causal=True, mask=fm, bias=b, interpret=True,
            **blocks) ** 2),
        lambda q, k, v, b: jnp.sum(sdpa_reference(
            q, k, v, causal=True, mask=fm, bias=b) ** 2),
        (q, k, v, bias), ["q", "k", "v", "bias"])


def test_sdpa_masked_op_dispatches_to_flash(monkeypatch):
    # the graph-level op must reach the kernel (not the XLA fallback) for
    # key-padding masks when the backend/gate allow it
    from hetu_tpu.ops import attention as att

    calls = {}

    def fake_flash(q, k, v, **kw):
        calls.update(kw)
        return sdpa_reference(
            q, k, v, causal=kw.get("causal", False),
            mask=None if kw.get("key_mask") is None
            else kw["key_mask"][:, None, None, :])

    monkeypatch.setattr(att, "_use_flash", lambda q, k: True)
    import sys
    fa = sys.modules["hetu_tpu.ops.pallas.flash_attention"]
    monkeypatch.setattr(fa, "flash_attention", fake_flash)
    q, k, v = _rand_qkv(2, 2, 256, 64, seed=9)
    km = jnp.asarray(np.random.RandomState(9).rand(2, 1, 1, 256) > 0.3)
    out = att._sdpa_masked(None, q, k, v, km)
    assert calls.get("key_mask") is not None
    assert calls.get("mask") is None
    ref = sdpa_reference(q, k, v, mask=km)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------- MoE sparse
from hetu_tpu.ops.moe import (_top1_gating, _top2_gating,  # noqa: E402
                              _topk_sparse_indices)
from hetu_tpu.ops.pallas.moe_dispatch import (row_gather,  # noqa: E402
                                              sparse_dispatch, sparse_combine)


def test_row_gather_basic():
    rng = np.random.RandomState(0)
    src = jnp.asarray(rng.randn(10, 16).astype(np.float32))
    idx = jnp.asarray([3, -1, 0, 9, 9], jnp.int32)
    out = row_gather(src, idx, interpret=True)
    expect = np.where((np.asarray(idx) >= 0)[:, None],
                      np.asarray(src)[np.maximum(np.asarray(idx), 0)], 0.0)
    np.testing.assert_allclose(np.asarray(out), expect)


@pytest.mark.parametrize("k", [1, pytest.param(2, marks=pytest.mark.slow)])
def test_sparse_dispatch_matches_dense(k):
    s, e, d = 64, 8, 32
    cap = 16
    rng = np.random.RandomState(4)
    logits = jnp.asarray(rng.randn(s, e).astype(np.float32))
    tokens = jnp.asarray(rng.randn(s, d).astype(np.float32))

    dense_fn = _top1_gating if k == 1 else _top2_gating
    dispatch, combine, aux_d = dense_fn(logits, cap)
    buf_dense = jnp.einsum("sec,sm->ecm", dispatch, tokens).reshape(
        e * cap, d)

    tos, sot, kos, gate_w, aux_s = _topk_sparse_indices(logits, k, cap)
    buf_sparse = sparse_dispatch(tokens, tos, sot, True)
    np.testing.assert_allclose(np.asarray(buf_sparse), np.asarray(buf_dense),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(aux_s), float(aux_d), rtol=1e-6)

    # combine parity: expert output = buffers (identity experts)
    out_dense = jnp.einsum("sec,ecm->sm", combine,
                           buf_dense.reshape(e, cap, d))
    out_sparse = sparse_combine(buf_sparse, gate_w, sot, tos, kos, True)
    np.testing.assert_allclose(np.asarray(out_sparse), np.asarray(out_dense),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.slow
@pytest.mark.parametrize("k", [1, 2])
def test_sparse_moe_grads_match_dense(k):
    s, e, d = 32, 4, 16
    cap = 12
    rng = np.random.RandomState(5)
    logits_np = rng.randn(s, e).astype(np.float32)
    tokens_np = rng.randn(s, d).astype(np.float32)
    w_np = rng.randn(d, d).astype(np.float32) * 0.3

    def dense_loss(tokens, w):
        fn = _top1_gating if k == 1 else _top2_gating
        dispatch, combine, aux = fn(jnp.asarray(logits_np), cap)
        buf = jnp.einsum("sec,sm->ecm", dispatch, tokens)
        eo = jnp.tanh(buf @ w)
        out = jnp.einsum("sec,ecm->sm", combine, eo)
        return jnp.sum(out ** 2)

    def sparse_loss(tokens, w):
        tos, sot, kos, gate_w, aux = _topk_sparse_indices(
            jnp.asarray(logits_np), k, cap)
        buf = sparse_dispatch(tokens, tos, sot, True).reshape(e, cap, d)
        eo = jnp.tanh(buf @ w).reshape(e * cap, d)
        out = sparse_combine(eo, gate_w, sot, tos, kos, True)
        return jnp.sum(out ** 2)

    t, w = jnp.asarray(tokens_np), jnp.asarray(w_np)
    ld, gd = jax.value_and_grad(dense_loss, argnums=(0, 1))(t, w)
    ls, gs = jax.value_and_grad(sparse_loss, argnums=(0, 1))(t, w)
    np.testing.assert_allclose(float(ls), float(ld), rtol=1e-5)
    for a, b, name in zip(gs, gd, ["tokens", "w"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


# Tier-1 siblings of ``test_sparse_moe_grads_match_dense``: the full
# dispatch+combine grad chain at k=1/k=2 runs in the slow tier (each
# interpret-mode kernel under grad costs seconds of fixed tracing
# overhead regardless of shape), so tier-1 covers each kernel's VJP
# separately against its dense einsum counterpart.

def _moe_lean_inputs():
    s, e, d, cap = 8, 2, 8, 4
    rng = np.random.RandomState(5)
    return (s, e, d, cap, rng.randn(s, e).astype(np.float32),
            rng.randn(s, d).astype(np.float32),
            rng.randn(d, d).astype(np.float32) * 0.3)


def _assert_grads_match(dense_loss, sparse_loss, tokens_np, w_np):
    t, w = jnp.asarray(tokens_np), jnp.asarray(w_np)
    ld, gd = jax.value_and_grad(dense_loss, argnums=(0, 1))(t, w)
    ls, gs = jax.value_and_grad(sparse_loss, argnums=(0, 1))(t, w)
    np.testing.assert_allclose(float(ls), float(ld), rtol=1e-5)
    for a, b, name in zip(gs, gd, ["tokens", "w"]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_sparse_dispatch_grad_matches_dense_lean():
    s, e, d, cap, logits_np, tokens_np, w_np = _moe_lean_inputs()

    def dense_loss(tokens, w):
        dispatch, _combine, _aux = _top1_gating(jnp.asarray(logits_np),
                                                cap)
        buf = jnp.einsum("sec,sm->ecm", dispatch, tokens)
        return jnp.sum(jnp.tanh(buf @ w) ** 2)

    def sparse_loss(tokens, w):
        tos, sot, _kos, _gate_w, _aux = _topk_sparse_indices(
            jnp.asarray(logits_np), 1, cap)
        buf = sparse_dispatch(tokens, tos, sot, True).reshape(e, cap, d)
        return jnp.sum(jnp.tanh(buf @ w) ** 2)

    _assert_grads_match(dense_loss, sparse_loss, tokens_np, w_np)


def test_sparse_combine_grad_matches_dense_lean():
    s, e, d, cap, logits_np, tokens_np, w_np = _moe_lean_inputs()

    def dense_loss(tokens, w):
        dispatch, combine, _aux = _top1_gating(jnp.asarray(logits_np),
                                               cap)
        buf = jnp.einsum("sec,sm->ecm", dispatch, tokens)
        eo = jnp.tanh(buf @ w)
        out = jnp.einsum("sec,ecm->sm", combine, eo)
        return jnp.sum(out ** 2)

    def sparse_loss(tokens, w):
        tos, sot, kos, gate_w, _aux = _topk_sparse_indices(
            jnp.asarray(logits_np), 1, cap)
        dispatch, _combine, _aux2 = _top1_gating(jnp.asarray(logits_np),
                                                 cap)
        buf = jnp.einsum("sec,sm->ecm", dispatch, tokens)
        eo = jnp.tanh(buf @ w).reshape(e * cap, d)
        out = sparse_combine(eo, gate_w, sot, tos, kos, True)
        return jnp.sum(out ** 2)

    _assert_grads_match(dense_loss, sparse_loss, tokens_np, w_np)


def test_sorted_segment_sum():
    from hetu_tpu.ops.pallas.segment_sum import sorted_segment_sum
    rng = np.random.RandomState(6)
    n, d = 300, 24
    seg_np = np.sort(rng.randint(0, 40, n)).astype(np.int32)
    # make contiguous 0..k
    _, seg_np = np.unique(seg_np, return_inverse=True)
    rows_np = rng.randn(n, d).astype(np.float32)
    nseg = int(seg_np.max()) + 1
    out = sorted_segment_sum(jnp.asarray(rows_np),
                             jnp.asarray(seg_np, jnp.int32), nseg,
                             block=64, interpret=True)
    expect = np.zeros((nseg, d), np.float32)
    np.add.at(expect, seg_np, rows_np)
    np.testing.assert_allclose(np.asarray(out), expect, rtol=1e-5, atol=1e-5)


def test_sorted_segment_sum_single_run():
    """One segment spanning every block (worst-case carry chain)."""
    from hetu_tpu.ops.pallas.segment_sum import sorted_segment_sum
    rng = np.random.RandomState(7)
    rows_np = rng.randn(256, 8).astype(np.float32)
    out = sorted_segment_sum(jnp.asarray(rows_np),
                             jnp.zeros((256,), jnp.int32), 1,
                             block=64, interpret=True)
    np.testing.assert_allclose(np.asarray(out[0]), rows_np.sum(0),
                               rtol=1e-5, atol=1e-5)


def test_dedup_rows():
    from hetu_tpu.ops.pallas.segment_sum import dedup_rows
    ids_np = np.array([5, 3, 5, 7, 3, 3], np.int32)
    rows_np = np.arange(12, dtype=np.float32).reshape(6, 2)
    uniq, summed, n_u = dedup_rows(jnp.asarray(ids_np), jnp.asarray(rows_np),
                                   interpret=True)
    assert int(n_u) == 3
    uniq, summed = np.asarray(uniq)[:3], np.asarray(summed)[:3]
    assert list(uniq) == [3, 5, 7]
    np.testing.assert_allclose(summed[0], rows_np[[1, 4, 5]].sum(0))
    np.testing.assert_allclose(summed[1], rows_np[[0, 2]].sum(0))
    np.testing.assert_allclose(summed[2], rows_np[3])


@pytest.mark.slow
def test_sparse_moe_layer_trains():
    """SparseMoELayer end-to-end through the graph executor."""
    import hetu_tpu as ht
    s, d, e = 64, 16, 4
    x = ht.placeholder_op("x", shape=(s, d))
    gate = ht.layers.TopKGateSparse(d, s, e, k=2)
    experts = ht.layers.Expert(e, d, hidden_dim=32)
    moe = ht.layers.SparseMoELayer(gate, experts, d)
    y, aux = moe(x)
    loss = ht.ops.reduce_mean_op(ht.ops.mul_op(y, y), [0, 1]) + 0.01 * aux
    opt = ht.optim.AdamOptimizer(1e-2)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0)
    rng = np.random.RandomState(0)
    xv = rng.randn(s, d).astype(np.float32)
    losses = [float(np.asarray(ex.run("train", feed_dict={x: xv})[0].jax()))
              for _ in range(8)]
    assert losses[-1] < losses[0]


@BLOCKS
@pytest.mark.parametrize("causal", [False, True])
def test_flash_varlen_padding_mask(causal, blocks):
    """lengths argument == reference column mask, fwd and grads."""
    b, h, s, d = 3, 2, 256, 32
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32) * 0.3)
    lengths = jnp.asarray([256, 100, 17], jnp.int32)
    cols = np.arange(s)[None, None, None, :]
    mask = (cols < np.asarray(lengths)[:, None, None, None])

    out = flash_attention(q, k, v, causal=causal, lengths=lengths,
                          interpret=True, **blocks)
    ref = sdpa_reference(q, k, v, causal=causal,
                         mask=jnp.asarray(mask, jnp.float32))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def f_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal,
                                       lengths=lengths,
                                       interpret=True, **blocks) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(sdpa_reference(
            q, k, v, causal=causal,
            mask=jnp.asarray(mask, jnp.float32)) ** 2)

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_, name in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4, err_msg=name)
    # grads w.r.t. fully-padded keys must be zero
    dk = np.asarray(gf[1])
    assert np.abs(dk[2, :, 17:]).max() == 0.0


def test_sdpa_varlen_op_graph():
    import hetu_tpu as ht
    b, h, s, d = 2, 2, 32, 16
    rng = np.random.RandomState(12)
    q = ht.placeholder_op("q", shape=(b, h, s, d))
    lens = ht.placeholder_op("lens", shape=(b,), dtype=np.int32)
    out = ht.ops.sdpa_varlen_op(q, q, q, lens, causal=False)
    ex = ht.Executor({"fwd": [out]})
    qv = rng.randn(b, h, s, d).astype(np.float32)
    lv = np.asarray([32, 9], np.int32)
    got = np.asarray(ex.run("fwd", feed_dict={q: qv, lens: lv})[0].asnumpy())
    cols = np.arange(s)[None, None, None, :]
    ref = sdpa_reference(jnp.asarray(qv), jnp.asarray(qv), jnp.asarray(qv),
                         mask=jnp.asarray(cols < lv[:, None, None, None],
                                          jnp.float32))
    np.testing.assert_allclose(got, np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_flash_gate_is_the_module_constant(monkeypatch):
    # the dispatcher's gate is one constant: the reason a call stays on
    # XLA's path names it, and moving the constant moves the dispatch
    import jax
    from hetu_tpu.ops import attention as att

    assert att._FLASH_MIN_LEN == 256
    q = jax.ShapeDtypeStruct((1, 2, 255, 64), jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert att._gate_reason(q, q) == "below_gate:seq255<256"
    assert not att._use_flash(q, q)
    monkeypatch.setattr(att, "_FLASH_MIN_LEN", 128)
    assert att._gate_reason(q, q) is None and att._use_flash(q, q)
    # a table of blocks left in an old artifact overrides nothing
    assert not hasattr(att, "_FLASH_BLOCKS")
    assert not hasattr(att, "_clipped_blocks")


@pytest.mark.parametrize("s_q,s_kv,d,itemsize,causal,dense", [
    (512, 512, 64, 2, False, 0),      # bert-base, key mask: strips only
    (512, 512, 64, 2, False, 2),      # T5: bias tile + dbias tile
    (512, 512, 64, 2, False, 3),      # XLNet: full mask + bias + dbias
    (1024, 1024, 64, 2, True, 0),     # gpt2 causal
    (512, 512, 64, 2, True, 0),
    (4096, 4096, 64, 2, True, 0),     # causal, past one block
    (8192, 8192, 128, 2, True, 3),
    (1024, 1024, 64, 2, False, 0),
    (2048, 2048, 128, 2, False, 0),   # llama-width head, long
    (4096, 4096, 64, 2, False, 0),
    (256, 512, 64, 4, False, 0),      # cross-attention, f32
    (512, 512, 128, 2, False, 0),     # bert-base packed: two heads a block
    (2048, 2048, 128, 2, False, 0),
    (128, 768, 64, 4, False, 1),      # chunked prefill: full mask
    (384, 384, 64, 4, True, 0),       # 384 has no 256 divisor
    (128, 128, 64, 4, True, 0),
])
def test_flash_block_rule(s_q, s_kv, d, itemsize, causal, dense):
    """``_pick_blocks``: multiples of 128 that divide both lengths, within
    the stated VMEM budget, and nothing else decides."""
    import importlib
    fa = importlib.import_module("hetu_tpu.ops.pallas.flash_attention")
    bq, bk = fa._pick_blocks(s_q, s_kv, d, itemsize, causal, dense)
    assert bq % 128 == 0 and bk % 128 == 0
    assert s_q % bq == 0 and s_kv % bk == 0
    assert fa._block_bytes(bq, bk, d, itemsize, dense, s_q // bq,
                           s_kv // bk) <= fa._VMEM_BUDGET
    if causal and bk < s_kv:
        # several key blocks: pruning-friendly, at least two blocks a side
        assert bq <= max(128, s_q // 2) and bk <= max(128, s_kv // 2)
    # the same call is answered the same way: no state, no environment
    assert (bq, bk) == fa._pick_blocks(s_q, s_kv, d, itemsize, causal,
                                       dense)


def test_flash_block_rule_choices(monkeypatch):
    """What the rule gives the shapes the models run, and what moves it."""
    import importlib
    fa = importlib.import_module("hetu_tpu.ops.pallas.flash_attention")
    pick = fa._pick_blocks
    # BERT's 512 dense keys: one program per (b, h) takes the whole
    # sequence (6,144 programs a kernel at 128 x 128 -> 384)
    assert pick(512, 512, 64, 2) == (512, 512)
    # a dense bias and its dbias tile cost block_q x block_k x 4 B each,
    # double-buffered: the query block shrinks, the key range stays whole
    assert pick(512, 512, 64, 2, False, 2) == (256, 512)
    assert pick(512, 512, 64, 2, False, 3) == (256, 512)
    small = pick(512, 512, 64, 2, False, 2)
    assert small[0] * small[1] < 512 * 512
    # causal: a key range that fits is still taken whole (one pass over
    # the masked half beat pruned tiles on the chip, PERF.md PR 28) ...
    assert pick(512, 512, 64, 2, True) == (512, 512)
    assert pick(1024, 1024, 64, 2, True) == (256, 1024)
    # ... and past that, blocks above the diagonal must exist to be pruned
    bq, bk = pick(4096, 4096, 64, 2, True)
    assert bq <= 2048 and bk <= 2048
    assert pick(256, 256, 64, 2, True, 100) == (128, 128)  # nothing fits
    # long rows fall back to what fits: square tiles, several key blocks
    assert pick(4096, 4096, 64, 2) == (512, 512)
    assert pick(2048, 2048, 64, 2)[1] < 2048
    # cross-attention takes each side's own length
    assert pick(256, 512, 64, 4) == (256, 512)
    # the dispatch gate does not reach the rule
    from hetu_tpu.ops import attention as att
    monkeypatch.setattr(att, "_FLASH_MIN_LEN", 4096)
    assert pick(512, 512, 64, 2) == (512, 512)


def test_flash_explicit_blocks_win_and_geometry_is_counted():
    """Callers' ``block_q=`` / ``block_k=`` are used as given (tests and
    the ring choose their own), and every traced call records the
    geometry it compiled with — except under an abstract shape trace."""
    from hetu_tpu import metrics
    from hetu_tpu.profiler import HetuProfiler
    q, k, v = _rand_qkv(1, 2, 256, 64, seed=31)
    metrics.reset_all()
    flash_attention(q, k, v, interpret=True)
    flash_attention(q, k, v, causal=True, interpret=True)
    flash_attention(q, k, v, block_q=128, block_k=128, interpret=True)
    jax.grad(lambda q: jnp.sum(flash_attention(
        q, k, v, block_q=128, block_k=256, interpret=True)))(q)
    assert HetuProfiler.flash_calls() == {
        "256x256:one_pass": 2,          # the rule: whole key range
        "128x128:two_pass": 1,          # explicit
        "128x256:one_pass": 1}
    assert HetuProfiler.all_counters()["flash_calls"] \
        == metrics.flash_call_counts()
    with metrics.suppress_perf_counters():
        jax.eval_shape(lambda q: flash_attention(q, k, v, interpret=True),
                       q)
    assert sum(metrics.flash_call_counts().values()) == 4
    with pytest.raises(ValueError, match="divisible by block"):
        flash_attention(q, k, v, block_q=96, block_k=128, interpret=True)
    metrics.reset_all()


def test_flash_whole_range_backward_sums_over_query_blocks():
    """block_k = S_kv with several query blocks: K/V stay resident, dk /
    dv / the key-bias gradient are summed in scratch across the query
    blocks and written at the last — against the reference, with a key
    mask, a per-key bias and cross-attention lengths."""
    rng = np.random.RandomState(41)
    q = jnp.asarray(rng.randn(2, 2, 384, 32).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(2, 2, 256, 32).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(2, 2, 256, 32).astype(np.float32) * 0.3)
    km = jnp.asarray(rng.rand(2, 256) > 0.3)
    kb = jnp.asarray(rng.randn(2, 1, 1, 256).astype(np.float32))
    mask = km[:, None, None, :]
    _grad_parity(
        lambda q, k, v, b: jnp.sum(flash_attention(
            q, k, v, key_mask=km, bias=b, block_q=128, block_k=256,
            interpret=True) ** 2),
        lambda q, k, v, b: jnp.sum(sdpa_reference(
            q, k, v, mask=mask, bias=b) ** 2),
        (q, k, v, kb), ["q", "k", "v", "key_bias"])


@pytest.mark.parametrize("seq,with_bias", [(384, True), (421, True),
                                           (421, False)])
def test_tpu_lowering_contains_pallas_custom_call(seq, with_bias):
    """Cross-platform TPU LOWERING of biased / ragged-length attention
    contains the Pallas (Mosaic) custom-call: the dispatch reaches the
    kernel.  ``jax.export`` serialises the kernel and never runs the
    chip's compiler, so this says nothing about whether Mosaic accepts
    it — tests/test_tpu_compile.py compiles for a described v5e."""
    import jax.export

    def f(q, k, v, bias):
        return flash_attention(q, k, v, bias=bias)

    def f_nobias(q, k, v):
        return flash_attention(q, k, v)

    q = jnp.zeros((1, 2, seq, 64), jnp.float32)
    if with_bias:
        bias = jnp.zeros((1, 2, seq, seq), jnp.float32)
        exp = jax.export.export(jax.jit(f), platforms=["tpu"])(q, q, q,
                                                               bias)
    else:
        exp = jax.export.export(jax.jit(f_nobias), platforms=["tpu"])(
            q, q, q)
    assert "tpu_custom_call" in exp.mlir_module()


def test_flash_fallback_reasons_recorded(monkeypatch):
    """Dispatch fallbacks are COUNTED, never silent: the reason lands in
    the metrics registry, and HETU_REQUIRE_FLASH=1 escalates to a hard
    failure."""
    from hetu_tpu import metrics
    from hetu_tpu.ops import attention as att

    metrics.reset_flash_fallbacks()
    q, k, v = _rand_qkv(1, 1, 256, 16, seed=30)
    att.dispatch_sdpa(q, k, v)              # cpu backend → einsum path
    counts = metrics.flash_fallback_counts()
    assert counts.get("backend:cpu", 0) >= 1

    # gate forced open on a "tpu" backend: the remaining blocker (causal
    # ragged q/kv mod-128 mismatch) gets its own reason — the reason
    # vocabulary is ordered backend → gate → shape
    metrics.reset_flash_fallbacks()
    monkeypatch.setattr(att, "_use_flash", lambda q, k: True)
    monkeypatch.setattr(att.jax, "default_backend", lambda: "tpu")
    q2, k2, v2 = _rand_qkv(1, 1, 384, 16, seed=30)
    att.dispatch_sdpa(q2[:, :, :300], k2, v2, causal=True)
    assert any(r.startswith("causal_ragged_mismatch")
               for r in metrics.flash_fallback_counts())

    monkeypatch.setenv("HETU_REQUIRE_FLASH", "1")
    with pytest.raises(RuntimeError, match="HETU_REQUIRE_FLASH"):
        att.dispatch_sdpa(q2[:, :, :300], k2, v2, causal=True)
    metrics.reset_flash_fallbacks()


def test_swin_window_mask_small_constant_tiles_to_old_layout():
    """The swin shifted-window mask is stored (nW, 1, w², w²) — B× smaller
    than the old baked (B·nW, 1, w², w²) constant — and the on-graph
    Repeat reproduces EXACTLY the old layout (tile maps flat window index
    t = b·nW + w to mask[w], swin's batch-major flattening)."""
    from hetu_tpu.models.swin import SwinConfig, _WindowBlock, _shift_mask
    cfg = SwinConfig.tiny(batch_size=2)
    blk = _WindowBlock(cfg, cfg.embed_dim, 2, 8, shift=2, name="swb",
                       consts={})
    w = blk.w
    nW = (8 // w) ** 2
    assert blk.mask._value.shape == (nW, 1, w * w, w * w)
    # the old (pre-PR) baked constant, reproduced from the same source
    m = _shift_mask(8, 8, w, blk.shift)
    old = np.broadcast_to(m[None, :, None],
                          (2, nW, 1, w * w, w * w)).reshape(
        2 * nW, 1, w * w, w * w)
    tiled = np.tile(blk.mask._value, (2, 1, 1, 1))   # what repeat_op lowers to
    np.testing.assert_array_equal(tiled, old)


@pytest.mark.parametrize("bias_shape,causal", [
    ((1, 1, 1, 128), False),    # shared per-key bias (ALiBi-slope-free form)
    ((2, 1, 1, 128), False),    # per-batch key bias
    ((2, 4, 1, 128), True),     # full (b, h) group + causal
])
def test_flash_key_bias_strip_path(bias_shape, causal):
    """(·, ·, 1, S_kv) biases ride O(S) column strips (never materialised
    to (S_q, S_kv)) — fwd and dbias parity vs the jnp reference."""
    import jax
    from hetu_tpu.ops.attention import sdpa_reference
    rng = np.random.RandomState(11)
    b, h, s, d = 2, 4, 128, 16
    q, k, v = [jnp.asarray(rng.randn(b, h, s, d), jnp.float32)
               for _ in range(3)]
    bias = jnp.asarray(rng.randn(*bias_shape), jnp.float32)

    def f(q, k, v, bias):
        return flash_attention(q, k, v, bias=bias, causal=causal,
                               block_q=64, block_k=64, interpret=True).sum()

    def fr(q, k, v, bias):
        return sdpa_reference(q, k, v, bias=bias, causal=causal).sum()

    out = flash_attention(q, k, v, bias=bias, causal=causal,
                          block_q=64, block_k=64, interpret=True)
    ref = sdpa_reference(q, k, v, bias=bias, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    g = jax.grad(f, argnums=(0, 1, 2, 3))(q, k, v, bias)
    gr = jax.grad(fr, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, e in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   rtol=3e-5, atol=3e-6)


# ------------------------------------------- packed (B, S, H·D) flash entry
from hetu_tpu.ops.attention import _merge_heads as _pack  # noqa: E402


def _both_layouts(q, k, v, h, **kw):
    """(out, dq, dk, dv) of the head-major entry and of the packed entry
    over the same heads, the packed side's in the packed layout."""
    def run(fn, args):
        loss = lambda *a: jnp.sum(fn(*a) ** 2)           # noqa: E731
        return (fn(*args),) + jax.grad(loss, argnums=(0, 1, 2))(*args)
    head_major = run(lambda q, k, v: flash_attention(
        q, k, v, interpret=True, **kw), (q, k, v))
    packed = run(lambda q, k, v: flash_attention(
        q, k, v, heads=h, interpret=True, **kw),
        tuple(_pack(x) for x in (q, k, v)))
    return [_pack(x) for x in head_major], packed


def _layout_extras(extra, b, s_kv, seed):
    rng = np.random.RandomState(seed)
    return {"plain": {}, "causal": {"causal": True},
            "lengths": {"lengths": jnp.asarray(
                rng.randint(1, s_kv + 1, b), jnp.int32)},
            "key_mask": {"key_mask": jnp.asarray(
                rng.rand(b, s_kv) > 0.3)}}[extra]


@pytest.mark.parametrize("blocks", [{}, {"block_q": 128, "block_k": 128}],
                         ids=["one_pass", "two_pass"])
@pytest.mark.parametrize("extra", ["plain", "causal", "lengths", "key_mask"])
@pytest.mark.parametrize("h,d", [(4, 64), (2, 128), (4, 32)],
                         ids=["d64x2", "d128x1", "d32x4"])
def test_flash_packed_matches_head_major(h, d, extra, blocks):
    """The packed entry — (B, S, H·D) in, (B, S, H·D) out and back — is the
    head-major kernel's arithmetic: forward and all three gradients, two
    heads a column block (d = 64), one (d = 128), four (d = 32), every
    extra the packed entry takes, the one-pass kernels (the rule's whole
    key range) and the online-softmax forward with the dq + dkv backward
    (two key blocks).  To the last bit wherever the interpreter's matrix
    products block alike in both layouts (a head per column block, or
    128 x 128 blocks at d = 64: XLA's CPU product picks its contraction
    blocking by operand shape, the MXU does not); elsewhere to a few
    f32 ulps of a sum."""
    b, s = 2, 256
    q, k, v = _rand_qkv(b, h, s, d, seed=3 + d)
    head_major, packed = _both_layouts(
        q, k, v, h, **_layout_extras(extra, b, s, seed=d), **blocks)
    exact = d == 128 or (d == 64 and blocks)
    for name, want, got in zip(("out", "dq", "dk", "dv"), head_major,
                               packed):
        assert got.shape == (b, s, h * d)
        if exact:
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want), err_msg=name)
        else:
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=0, atol=3e-6, err_msg=name)


@pytest.mark.parametrize("s_q,s_kv,extra", [
    (256, 384, "key_mask"),     # cross-attention, one pass
    (128, 384, "lengths"),
    (200, 300, "plain"),        # ragged: bucketed to 256 / 384, the pad
    (200, 300, "key_mask"),     # keys masked by lengths / the strip
    (300, 300, "causal"),
])
def test_flash_packed_cross_attention_and_ragged_lengths(s_q, s_kv, extra):
    """S_q != S_kv and lengths off the 128 grid go through the packed
    entry as through the head-major one (pad, mask, unpad along axis 1),
    and agree with the reference."""
    b, h, d = 2, 4, 64
    rng = np.random.RandomState(s_q + s_kv)
    q = jnp.asarray(rng.randn(b, h, s_q, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(b, h, s_kv, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(b, h, s_kv, d).astype(np.float32) * 0.3)
    kw = _layout_extras(extra, b, s_kv, seed=s_q)
    head_major, packed = _both_layouts(q, k, v, h, **kw)
    assert packed[0].shape == (b, s_q, h * d)
    for name, want, got in zip(("out", "dq", "dk", "dv"), head_major,
                               packed):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=3e-6, err_msg=name)
    cols = jnp.arange(s_kv)[None, None, None, :]
    mask = {"key_mask": lambda: kw["key_mask"][:, None, None, :],
            "lengths": lambda: cols < kw["lengths"][:, None, None, None],
            }.get(extra, lambda: None)()
    ref = sdpa_reference(q, k, v, causal=extra == "causal", mask=mask)
    np.testing.assert_allclose(np.asarray(packed[0]),
                               np.asarray(_pack(ref)), rtol=2e-5, atol=2e-5)


def test_flash_packed_entry_refuses_what_it_cannot_lay_out():
    from hetu_tpu.ops.pallas.flash_attention import (_block_bytes,
                                                     _pick_blocks,
                                                     packed_width)
    assert [packed_width(d) for d in (16, 32, 64, 128, 256, 80, 96)] \
        == [128, 128, 128, 128, 256, None, None]
    q = jnp.zeros((1, 256, 160), jnp.float32)
    with pytest.raises(ValueError, match="2 heads of 80"):
        flash_attention(q, q, q, heads=2, interpret=True)
    q = jnp.zeros((1, 256, 192), jnp.float32)      # 3 heads of 64: 1.5 blocks
    with pytest.raises(ValueError, match="3 heads of 64"):
        flash_attention(q, q, q, heads=3, interpret=True)
    q = jnp.zeros((1, 256, 128), jnp.float32)
    with pytest.raises(ValueError, match="causal, lengths and key_mask"):
        flash_attention(q, q, q, heads=2, interpret=True,
                        bias=jnp.zeros((1, 2, 256, 256)))
    # the rule counts a packed row block at its 128 lanes — twice the
    # bytes of a 64-lane head — and BERT's 512 x 512 still fits, to the
    # byte: one program holds both ranges whole, so no sum crosses a grid
    # step and no f32 accumulator is kept (same blocks in both layouts)
    assert _pick_blocks(512, 512, 64, 2) == (512, 512)
    assert _pick_blocks(512, 512, packed_width(64), 2) == (512, 512)
    assert _block_bytes(512, 512, 128, 2, 0, 1, 1) == 8 * 2 ** 20 \
        < _block_bytes(512, 512, 128, 2, 0)
    assert _block_bytes(512, 512, 64, 2, 0, 1, 1) \
        < _block_bytes(512, 512, 128, 2, 0, 1, 1)
    assert _pick_blocks(2048, 2048, packed_width(64), 2) == (256, 512)


# ------------------------------------------- the layer's choice of layout
def _mha_graph(case):
    """One MultiHeadAttention call of ``case`` → (output node, feeds)."""
    import hetu_tpu as ht
    b, s = 2, 16
    hid, heads, kw, layer_kw = {
        "plain": (256, 4, {}, {}),
        "causal_d128": (256, 2, {}, {"causal": True}),
        "d32": (128, 4, {}, {}),
        "key_mask": (256, 4, {"mask": (b, 1, 1, s)}, {}),
        "shared_key_mask": (256, 4, {"mask": (1, 1, 1, s)}, {}),
        "bias": (256, 4, {"bias": (1, 4, s, s)}, {}),
        "full_mask": (256, 4, {"mask": (b, 1, s, s)}, {}),
        "mask_of_unknown_shape": (256, 4, {"mask": None}, {}),
        "ring": (256, 4, {}, {"context_parallel": "ring"}),
        "ulysses": (256, 4, {}, {"context_parallel": "ulysses"}),
        "d80": (160, 2, {}, {}),
        "half_a_column_block": (64, 4, {}, {}),
    }[case]
    x = ht.placeholder_op("x", shape=(b * s, hid))
    feeds = {x: np.random.RandomState(5).randn(b * s, hid).astype(
        np.float32)}
    extras = {}
    for name, shape in kw.items():
        node = ht.placeholder_op(
            name, shape=shape,
            dtype=np.int32 if name == "mask" else np.float32)
        extras[name] = node
        shape = shape or (b, 1, s, s)
        feeds[node] = (np.random.RandomState(6).rand(*shape) > 0.3
                       ).astype(np.int32) if name == "mask" else \
            np.random.RandomState(6).randn(*shape).astype(np.float32)
    mha = ht.layers.MultiHeadAttention(hid, heads, name="lay", **layer_kw)
    return mha(x, b, s, **extras), feeds


@pytest.mark.parametrize("case,reason", [
    ("plain", None), ("causal_d128", None), ("d32", None),
    ("key_mask", None), ("shared_key_mask", None),
    ("bias", "bias"),
    ("full_mask", "mask_shape:(2, 1, 16, 16)"),
    ("mask_of_unknown_shape", "mask_shape:None"),
    ("ring", "context_parallel:ring"),
    ("ulysses", "context_parallel:ulysses"),
    ("d80", "head_dim:80"),
    ("half_a_column_block", "column_block:4x16%128"),
])
def test_mha_takes_the_packed_layout_by_what_it_can_observe(
        case, reason, monkeypatch):
    """Where the rule passes — head size, whole column blocks, no context
    parallelism, no bias, at most a key-padding mask — the layer builds NO
    transpose node: q, k, v go to ``sdpa_packed_op`` as the projections
    leave them.  Where it does not, today's graph, and the counter names
    the reason.  Both graphs give the same numbers."""
    import hetu_tpu as ht
    from hetu_tpu import metrics
    from hetu_tpu.graph.node import topo_sort
    from hetu_tpu.layers.attention import MultiHeadAttention
    from hetu_tpu.profiler import HetuProfiler

    metrics.reset_all()
    out, feeds = _mha_graph(case)
    kinds = [n.op_type for n in topo_sort([out])]
    attention = [t for t in kinds if "Attention" in t]
    if reason is None:
        assert kinds.count("Transpose") == 0
        assert attention == ["ScaledDotProductAttentionPacked"]
        assert HetuProfiler.flash_head_major() == {}
    else:
        assert kinds.count("Transpose") == 4
        assert len(attention) == 1 and "Packed" not in attention[0]
        assert HetuProfiler.flash_head_major() == {reason: 1}
        assert HetuProfiler.all_counters()["flash_head_major"] \
            == {reason: 1}
    if reason is None:
        got = ht.Executor({"f": [out]}, seed=0).run(
            "f", feed_dict=feeds)[0].asnumpy()
        monkeypatch.setattr(MultiHeadAttention, "_head_major_reason",
                            lambda self, mask, bias: "forced")
        old, old_feeds = _mha_graph(case)
        assert [n.op_type for n in topo_sort([old])].count("Transpose") == 4
        want = ht.Executor({"f": [old]}, seed=0).run(
            "f", feed_dict=old_feeds)[0].asnumpy()
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    metrics.reset_all()


def _interpreted_flash(monkeypatch):
    """The attention dispatch hears a TPU and reaches the flash kernels,
    which run under the interpreter."""
    import functools
    import sys
    from hetu_tpu.ops import attention as att
    fa = sys.modules["hetu_tpu.ops.pallas.flash_attention"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "flash_attention", functools.partial(
        fa.flash_attention, interpret=True))
    return att


@pytest.mark.parametrize("masked", [False, True], ids=["plain", "key_mask"])
def test_sdpa_packed_op_reaches_the_packed_kernel(masked, monkeypatch):
    from hetu_tpu import metrics
    att = _interpreted_flash(monkeypatch)
    metrics.reset_all()
    q, k, v = _rand_qkv(2, 4, 256, 64, seed=17)
    km = jnp.asarray(np.random.RandomState(17).rand(2, 1, 1, 256) > 0.3) \
        if masked else None
    out = att._sdpa_packed(None, _pack(q), _pack(k), _pack(v), km,
                           head_dim=64, causal=not masked)
    assert metrics.flash_call_counts() == {"256x256:one_pass:packed": 1}
    assert metrics.flash_fallback_counts() == {}
    ref = sdpa_reference(q, k, v, mask=km, causal=not masked)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_pack(ref)),
                               rtol=2e-5, atol=2e-5)
    # below the gate and with a full mask: the counted head-major dispatch
    # between two transposes, the same numbers
    short = [_pack(x[:, :, :128]) for x in (q, k, v)]
    att._sdpa_packed(None, *short, head_dim=64)
    assert metrics.flash_fallback_counts() == {"below_gate:seq128<256": 1}
    full = jnp.asarray(np.random.RandomState(18).rand(2, 1, 256, 256) > 0.3)
    out = att._sdpa_packed(None, _pack(q), _pack(k), _pack(v), full,
                           head_dim=64)
    assert metrics.flash_head_major_counts() \
        == {"mask_shape:(2, 1, 256, 256)": 1}
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_pack(sdpa_reference(q, k, v,
                                                         mask=full))),
        rtol=2e-5, atol=2e-5)
    metrics.reset_all()


@pytest.mark.parametrize("heads,reason", [
    (4, None),              # a shard keeps one whole block of two heads
    (2, "tp_splits_column_block:2x64/2"),   # a shard would keep half a one
])
def test_sdpa_packed_op_under_a_tp_mesh(heads, reason, monkeypatch):
    """On a dp x tp mesh the packed op shards batch rows over ``dp`` and
    the LAST axis over ``tp`` — only where each shard keeps whole column
    blocks of heads; where one would be cut it shards head-major as
    before and says so."""
    import types
    import hetu_tpu as ht
    from hetu_tpu import metrics
    att = _interpreted_flash(monkeypatch)
    metrics.reset_all()
    mesh = ht.make_mesh({"dp": 2, "tp": 2}, jax.devices()[:4])
    q, k, v = _rand_qkv(2, heads, 256, 64, seed=23)
    km = jnp.asarray(np.random.RandomState(23).rand(2, 1, 1, 256) > 0.3)
    out = att._sdpa_packed(types.SimpleNamespace(mesh=mesh), _pack(q),
                           _pack(k), _pack(v), km, head_dim=64)
    assert out.shape == (2, 256, heads * 64)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_pack(sdpa_reference(q, k, v, mask=km))),
        rtol=2e-5, atol=2e-5)
    if reason is None:
        assert metrics.flash_call_counts() == {"256x256:one_pass:packed": 1}
        assert metrics.flash_head_major_counts() == {}
    else:
        assert metrics.flash_call_counts() == {"256x256:one_pass": 1}
        assert metrics.flash_head_major_counts() == {reason: 1}
    metrics.reset_all()
