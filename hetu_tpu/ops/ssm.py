"""Decode-time ops of hybrid state-space / attention language models.

Three kinds of per-sequence state meet in one decode step of such a model
(``hetu_tpu/models/phi4flash.py``; the engine's ``kv`` / ``ring`` /
``recurrent`` kinds, ``serving/decode.py``):

* **recurrent** — a Mamba-1 selective scan's state ``(B, N, E)`` and the
  last ``K - 1`` inputs of its causal depthwise convolution ``(B, K-1,
  E)``, both float32 and both with the wide axis ``E`` minor (a ``(B, E,
  N)`` state would be stored with its 16 columns padded to 128 lanes);
* **ring** — the keys and values of a sliding-window attention layer, a
  fixed ``(B, G, W, 2D)`` buffer written at ``position mod W``;
* **kv** — the growable slabs of ``ops.attention`` (one full-attention
  layer's, which later cross-attention layers read without a cache of
  their own).

Every op takes a ``(B, C)`` chunk of tokens per call, ``C = 1`` being the
one-token step, with the residual stream flattened to ``(B*C, ·)`` as the
GPT-2 decode graphs do and ``(B, C)`` recovered from the ``ids`` feed.  An
optional trailing ``valid`` input ``(B,)`` says how many of a row's ``C``
columns are real: state advances by exactly that many tokens (a row that
consumes 3 of 8 columns moves its scan state 3 steps, writes 3 ring rows)
and the outputs of the columns past it are don't-cares.  All lowerings
are plain ``jax.numpy`` but one: the one-token read of a ``kv`` slab on
the chip is the Pallas kernel of ``ops/pallas/decode_attention.py``.
Sums, softmax, scan and norms run in float32 whatever the storage type.
"""
import jax
import jax.numpy as jnp

from .base import def_op, tuple_outputs


def _count(ids, valid):
    b, chunk = ids.shape
    if valid is None:
        return jnp.full((b,), chunk, jnp.int32)
    return jnp.minimum(jnp.asarray(valid, jnp.int32), chunk)


def _f32(x):
    return x.astype(jnp.float32)


# ------------------------------------------------------------ elementwise

swiglu_op = def_op(
    "SwiGLU",
    lambda c, gu: jax.nn.silu(gu[..., :gu.shape[-1] // 2])
    * gu[..., gu.shape[-1] // 2:])

silu_gate_op = def_op(
    "SiluGate", lambda c, gate, x: jax.nn.silu(_f32(gate)) * _f32(x))

greedy_token_op = def_op(
    "GreedyToken",
    lambda c, logits: jnp.argmax(logits, axis=-1).astype(jnp.int32))

# zero states for a graph that is fed none (the full-sequence graph): the
# batch is the feed's, known only when the step is traced, not the graph's
zeros_op = def_op(
    "BatchZeros", lambda c, ids, tail=(), dtype=jnp.float32:
    jnp.zeros((ids.shape[0],) + tuple(tail), dtype))


# ------------------------------------------------------- recurrent state

def _conv_state_shift(c, u, state, w, bias, ids, valid=None):
    """Causal depthwise convolution of a token chunk over a carried
    window.  ``u``: (B*C, E) inputs; ``state``: (B, K-1, E), the last
    ``K - 1`` inputs this sequence consumed (zeros for a fresh one);
    ``w``: (K, E), ``bias``: (E,).  Returns ``(silu(conv(u) + bias)``
    as (B*C, E), the shifted state)``: the new state holds the last
    ``K - 1`` of the inputs consumed so far, so a row with ``valid = 0``
    keeps its state."""
    b, chunk = ids.shape
    k = w.shape[0]
    seq = jnp.concatenate([_f32(state), _f32(u).reshape(b, chunk, -1)], 1)
    wf = _f32(w)
    out = sum(seq[:, i:i + chunk] * wf[i] for i in range(k)) + _f32(bias)
    at = _count(ids, valid)[:, None] + jnp.arange(k - 1, dtype=jnp.int32)
    new = jnp.take_along_axis(seq, at[:, :, None], axis=1)
    return (jax.nn.silu(out).reshape(b * chunk, -1),
            new.astype(state.dtype))


_conv_state_shift_node = def_op("ConvStateShift", _conv_state_shift)
_conv_state_shift_plain_node = def_op(
    "ConvStateShiftNoBias", lambda c, u, state, w, ids, valid=None:
    _conv_state_shift(c, u, state, w, jnp.zeros((), jnp.float32), ids, valid))


def conv_state_shift_op(*inputs, name=None, bias=True):
    """``(activated output, state')`` nodes of :func:`_conv_state_shift`;
    ``bias=False`` for a convolution without one (inputs ``u, state, w,
    ids[, valid]``)."""
    node = _conv_state_shift_node if bias else _conv_state_shift_plain_node
    return tuple_outputs(node(*inputs, name=name), 2)


def _ssm_scan(u, delta, a, bm, cm, d, state, count):
    """The selective scan over a chunk: ``S_t = exp(Δ_t A) ⊙ S_{t-1} +
    (Δ_t u_t) B_tᵀ``, ``y_t = S_t C_t + D ⊙ u_t``.  ``u``, ``delta``:
    (B, C, E); ``bm``, ``cm``: (B, C, N); ``a``: (N, E); ``state``:
    (B, N, E); ``count``: (B,) — columns at or past it leave the state
    alone."""
    def one(s, t):
        u_t, d_t, b_t, c_t, live = t
        nxt = jnp.exp(d_t[:, None, :] * a) * s \
            + (d_t * u_t)[:, None, :] * b_t[:, :, None]
        s = jnp.where(live[:, None, None], nxt, s)
        return s, jnp.sum(s * c_t[:, :, None], axis=1) + d * u_t

    chunk = u.shape[1]
    live = jnp.arange(chunk, dtype=jnp.int32)[:, None] < count[None, :]
    if chunk == 1:
        state, y = one(state, (u[:, 0], delta[:, 0], bm[:, 0], cm[:, 0],
                               live[0]))
        return y[:, None], state
    state, ys = jax.lax.scan(
        one, state, (u.swapaxes(0, 1), delta.swapaxes(0, 1),
                     bm.swapaxes(0, 1), cm.swapaxes(0, 1), live))
    return ys.swapaxes(0, 1), state


def _ssm_chunk_scan(c, u, dt, bc, a_log, d, state, ids, valid=None):
    """Mamba-1 state update of a (B, C) chunk.  ``u``: (B*C, E) the
    convolved, activated inputs; ``dt``: (B*C, E) the projected step
    sizes before softplus (bias added); ``bc``: (B*C, 2N) the input and
    output maps ``[B_t, C_t]``; ``a_log``: (N, E) with ``A = -exp(a_log)``;
    ``d``: (E,); ``state``: (B, N, E) float32.  Returns ``(y, state')``,
    ``y`` (B*C, E) BEFORE the output gate — what a later gated memory
    unit reads."""
    b, chunk = ids.shape
    n = a_log.shape[0]
    shape = (b, chunk, -1)
    bc = _f32(bc)
    y, new = _ssm_scan(
        _f32(u).reshape(shape), jax.nn.softplus(_f32(dt)).reshape(shape),
        -jnp.exp(_f32(a_log)), bc[:, :n].reshape(shape),
        bc[:, n:].reshape(shape), _f32(d), _f32(state), _count(ids, valid))
    return y.reshape(b * chunk, -1), new.astype(state.dtype)


_ssm_chunk_scan_node = def_op("SSMChunkScan", _ssm_chunk_scan)


def ssm_chunk_scan_op(*inputs, name=None):
    """``(y, state')`` nodes of :func:`_ssm_chunk_scan`."""
    return tuple_outputs(_ssm_chunk_scan_node(*inputs, name=name), 2)


def ssm_step_op(*inputs, name=None):
    """The one-token update: the chunk scan at ``C = 1`` (no loop is
    emitted; every row advances by its one token)."""
    return ssm_chunk_scan_op(*inputs, name=name)


# ------------------------------------------------- differential attention

def _diff_rows(q, g):
    """The score rows of query pairs: ``q`` (B, C, P, 2, D), ``P`` query
    pairs of two heads each, read by ``G`` key pairs (query pair ``p``
    reads key pair ``p // (P // G)``) -> (B, C, G, R, 2, 2D) float32,
    scaled by ``1 / sqrt(D)``.  Head 1 of a pair scores against ``k1``
    and head 2 against ``k2`` in ONE product over the 2D lanes of a
    paired key row ``[k1; k2]``: each query is laid in its own half with
    zeros in the other, the same products plus exact zeros, and the keys
    are read as stored."""
    b, chunk, pairs, _, d = q.shape
    q = _f32(q).reshape(b, chunk, g, pairs // g, 2, d) * (d ** -0.5)
    zero = jnp.zeros_like(q[..., 0, :])
    return jnp.stack([jnp.concatenate([q[..., 0, :], zero], -1),
                      jnp.concatenate([zero, q[..., 1, :]], -1)], axis=-2)


def _diff_scores(q, keys):
    """Scores of query pairs (:func:`_diff_rows`) against paired keys
    (B, G, M, 2D).  Returns (B, G, R, 2, C, M) float32."""
    rows = _diff_rows(q, keys.shape[1])
    return jnp.einsum("bcgrwl,bgml->bgrwcm", rows.astype(keys.dtype), keys,
                      preferred_element_type=jnp.float32)


def _lambda(lq1, lk1, lq2, lk2, lam_init):
    return (jnp.exp(jnp.sum(_f32(lq1) * _f32(lk1)))
            - jnp.exp(jnp.sum(_f32(lq2) * _f32(lk2))) + lam_init)


def _diff_combine(scores, seen, values, lam, norm_w, lam_init, eps):
    """``(A1 − λ A2) [v1; v2]``, RMS-normed over the 2D lanes and scaled
    by ``1 − λ_init``.  ``scores``: list of (B, G, R, 2, C, M_i) blocks
    whose softmax runs over all of them together; ``seen``: matching (B,
    C, M_i) masks; ``values``: matching (B, G, M_i, 2D).  Returns (B*C,
    P * 2D), pair-major."""
    s = jnp.concatenate(scores, axis=-1)
    mask = jnp.concatenate(seen, axis=-1)[:, None, None, None]
    probs = jax.nn.softmax(jnp.where(mask, s, -1e30), axis=-1)
    p = probs[:, :, :, 0] - lam * probs[:, :, :, 1]      # (B, G, R, C, M)
    out, at = 0.0, 0
    for v in values:
        m = v.shape[2]
        out = out + jnp.einsum(
            "bgrcm,bgml->bcgrl", p[..., at:at + m].astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        at += m
    return _diff_norm(out, norm_w, lam_init, eps)


def _diff_norm(out, norm_w, lam_init, eps):
    """(B, C, G, R, 2D) differences ``(A1 − λ A2) [v1; v2]`` RMS-normed
    over the 2D lanes and scaled by ``1 − λ_init`` -> (B*C, P * 2D)."""
    out = out * jax.lax.rsqrt(
        jnp.mean(jnp.square(out), axis=-1, keepdims=True) + eps)
    out = out * _f32(norm_w) * (1.0 - lam_init)
    b, chunk = out.shape[:2]
    return out.reshape(b * chunk, -1)


def _pairs(t, ids, width):
    """(B*C, n * width) -> (B, C, n, width)."""
    b, chunk = ids.shape
    return t.reshape(b, chunk, -1, width)


def _diff_attention_kv(c, q, k_slab, v_slab, positions, ids, lq1, lk1, lq2,
                       lk2, norm_w, head_dim=64, lam_init=0.8, eps=1e-5):
    """Differential attention (arXiv:2410.05258) of a (B, C) chunk's
    queries over growable KV slabs that already hold the chunk's own
    rows (``kv_cache_append_op``): query ``j`` of sequence ``b`` sees keys
    ``<= positions[b] + j``.  ``q``: (B*C, P * 2 * D), heads ``2p`` and
    ``2p + 1`` forming pair ``p``; slabs (B, G, L/r, r * 2D) of paired
    rows ``[k1; k2]`` / ``[v1; v2]`` (``r = 1`` when ``2D`` fills the 128
    lanes).  The cross-attention layers of a shared-KV decoder call this
    on ANOTHER layer's slabs.

    The one-token step on the chip (``C == 1``, no mesh, the decode gate
    of ``ops.attention``) hands the slabs AS STORED to the one-token
    kernel (:mod:`~hetu_tpu.ops.pallas.decode_attention`), which fetches
    only the key blocks below each sequence's length: the four heads that
    read a key pair are four score rows with a softmax each, and ``A1 −
    λ A2`` is taken of their normalised ``P @ V`` rows here.  A chunk, the
    CPU and the full-sequence graph read the slabs whole through
    ``jnp``."""
    from .attention import (_decode_gate_reason, kv_slab_queries,
                            kv_slab_to_rows)
    d = int(head_dim)
    b, chunk = ids.shape
    g, _, lanes = k_slab.shape[1:]
    pack = lanes // (2 * d)
    q = _pairs(q, ids, 2 * d).reshape(b, chunk, -1, 2, d)
    lam = _lambda(lq1, lk1, lq2, lk2, lam_init)
    if (chunk == 1 and getattr(c, "mesh", None) is None
            and _decode_gate_reason(k_slab.shape[2] * pack) is None):
        from .pallas.decode_attention import decode_attention
        rows = _diff_rows(q, g)[:, 0]                     # (B, G, R, 2, 2D)
        a = decode_attention(
            kv_slab_queries(rows, pack).reshape(b, g, -1, lanes).astype(
                k_slab.dtype), k_slab, v_slab,
            positions.astype(jnp.int32) + 1, pack=pack).reshape(rows.shape)
        return _diff_norm((a[..., 0, :] - lam * a[..., 1, :])[:, None],
                          norm_w, lam_init, eps)
    keys = kv_slab_to_rows(k_slab, 2 * d)
    vals = kv_slab_to_rows(v_slab, 2 * d)
    at = positions.astype(jnp.int32)[:, None] \
        + jnp.arange(chunk, dtype=jnp.int32)[None, :]            # (B, C)
    seen = jnp.arange(keys.shape[2], dtype=jnp.int32)[None, None, :] \
        <= at[:, :, None]
    return _diff_combine([_diff_scores(q, keys)], [seen], [vals], lam,
                         norm_w, lam_init, eps)


diff_attention_kv_op = def_op("DiffAttentionKV", _diff_attention_kv)


def _diff_attention_ring(c, q, k_new, v_new, k_ring, v_ring, positions, ids,
                         lq1, lk1, lq2, lk2, norm_w, valid=None, head_dim=64,
                         lam_init=0.8, eps=1e-5):
    """Sliding-window differential attention of a (B, C) chunk over ring
    buffers, and the rings with the chunk appended.  ``k_ring`` /
    ``v_ring``: (B, G, W, 2D), slot ``s`` holding the newest key whose
    position is ``s`` modulo ``W``; ``k_new`` / ``v_new``: (B*C, G * 2D),
    the chunk's own rows.  Query ``j`` (position ``p + j``) sees a key at
    position ``t`` iff ``t <= p + j`` and ``p + j − t < W``.  The chunk
    attends the ring AS IT WAS (a write first would overwrite keys its
    earlier queries still see) beside its own rows, then row ``j <
    valid`` lands in slot ``(p + j) mod W``.  A slot is read only when
    the sequence itself wrote it (its position is derived from ``p``), so
    a re-seated slot's ring needs no clearing.  Returns ``(out, k_ring',
    v_ring')``."""
    d = int(head_dim)
    b, chunk = ids.shape
    w = k_ring.shape[2]
    q = _pairs(q, ids, 2 * d).reshape(b, chunk, -1, 2, d)
    kn = _pairs(k_new, ids, 2 * d).transpose(0, 2, 1, 3).astype(k_ring.dtype)
    vn = _pairs(v_new, ids, 2 * d).transpose(0, 2, 1, 3).astype(v_ring.dtype)
    p = positions.astype(jnp.int32)
    cols = jnp.arange(chunk, dtype=jnp.int32)
    slots = jnp.arange(w, dtype=jnp.int32)
    # position of the key a slot holds: the newest one before p
    held = (p[:, None] - 1) - jnp.mod(p[:, None] - 1 - slots[None, :], w)
    at = p[:, None] + cols[None, :]                              # (B, C)
    seen_ring = jnp.logical_and(
        held[:, None, :] >= 0, at[:, :, None] - held[:, None, :] < w)
    seen_new = jnp.logical_and(cols[None, :] <= cols[:, None],
                               cols[:, None] - cols[None, :] < w)
    seen_new = jnp.broadcast_to(seen_new[None], (b, chunk, chunk))
    out = _diff_combine(
        [_diff_scores(q, k_ring), _diff_scores(q, kn)],
        [seen_ring, seen_new], [v_ring, vn],
        _lambda(lq1, lk1, lq2, lk2, lam_init), norm_w, lam_init, eps)
    count = _count(ids, valid)
    return (out, _ring_put(c, k_ring, kn, p, count),
            _ring_put(c, v_ring, vn, p, count))


def _ring_put(c, ring, new, p, count):
    """:func:`_ring_write` as a step runs it.  A one-token step's row —
    every step a window serves — is on the TPU the KV slabs' aliased
    write (:func:`~hetu_tpu.ops.pallas.kv_append.kv_append`: plain rows,
    key row ``p mod W``), one tile of the donated ring rewritten per
    slot; a chunk (set-up's prompts) and every other backend take the
    select, which is what the kernel is held bitwise equal to."""
    if new.shape[2] == 1 and jax.default_backend() == "tpu":
        from .attention import _partitioned
        from .pallas.kv_append import kv_append
        return _partitioned(c, kv_append, ring, new,
                            jnp.mod(p, ring.shape[2]), count)
    return _ring_write(ring, new, p, count)


def _ring_write(ring, new, p, count):
    """``ring`` (B, G, W, 2D) with rows ``j < count[b]`` of ``new`` (B, G,
    C, 2D) written at slots ``(p[b] + j) mod W``; where a chunk longer
    than the ring maps several rows to a slot, the last one stays.  One
    select over the whole ring (all of it read and rewritten): no loop
    over the batch, no scatter."""
    w, chunk = ring.shape[2], new.shape[2]
    slots = jnp.arange(w, dtype=jnp.int32)
    if chunk == 1:
        # the one row, broadcast along the ring: no gather to lay out
        write = jnp.logical_and(slots[None, :] == jnp.mod(p, w)[:, None],
                                count[:, None] > 0)
        return jnp.where(write[:, None, :, None], new, ring)
    first = jnp.mod(slots[None, :] - p[:, None], w)              # (B, W)
    last = first + w * jnp.floor_divide(count[:, None] - 1 - first, w)
    write = (first < count[:, None])[:, None, :, None]
    pick = jnp.clip(last, 0, chunk - 1)[:, None, :, None]
    return jnp.where(write, jnp.take_along_axis(new, pick, axis=2), ring)


def _ring_append(c, ring, new, positions, ids, valid=None):
    """The ring write alone (``new``: (B, G, C, 2D) rows, as
    ``pair_rows_op`` gives them)."""
    return _ring_put(c, ring, new.astype(ring.dtype),
                     positions.astype(jnp.int32), _count(ids, valid))


ring_append_op = def_op("RingAppend", _ring_append)


_diff_attention_ring_node = def_op("DiffAttentionRing", _diff_attention_ring)


def diff_attention_ring_op(*inputs, name=None, **attrs):
    """``(out, k_ring', v_ring')`` nodes of :func:`_diff_attention_ring`
    — attention and ``ring_append`` in one node, because the chunk has to
    read the ring before it writes it."""
    return tuple_outputs(
        _diff_attention_ring_node(*inputs, name=name, **attrs), 3)


def _pair_rows(c, t, ids, head_dim=64):
    """(B*C, G * 2D) projected keys or values -> (B, G, C, 2D) paired
    rows for ``kv_cache_append_op``."""
    return _pairs(t, ids, 2 * int(head_dim)).transpose(0, 2, 1, 3)


pair_rows_op = def_op("PairRows", _pair_rows)
