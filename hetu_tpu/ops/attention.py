"""Attention ops.

The reference has NO attention kernel — its transformer examples compose
batch_matmul + softmax ops (SURVEY.md §5.7).  Here scaled-dot-product
attention is a first-class fused op so the hot path can lower to the Pallas
flash-attention kernel (:mod:`hetu_tpu.ops.pallas.flash_attention`) on TPU,
with a reference jnp lowering for CPU tests; ring/blockwise variants live in
:mod:`hetu_tpu.parallel.ring_attention`.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from .base import def_op


#: sequences shorter than this take XLA's fused attention: below it the
#: flash kernel's per-program overhead is not paid back (every run the
#: ledger holds used 256; ``tools/flash_ab.py`` times both sides on the chip)
_FLASH_MIN_LEN = 256


@jax.custom_vjp
def _scores_f32(q, k):
    """q·kᵀ with an f32 RESULT from low-precision operands (softmax needs
    the f32 range) — but with a custom backward that casts the f32
    cotangent down to the operand dtype before the dq/dk dots, the same
    discipline flash backward kernels use.  Without this, the f32 primal
    output makes dscores f32 and both backward dots run f32×f32 at half
    MXU throughput (the matmul.py dtype-discipline note; found by
    tools/hlo_audit.py — 24 residual f32 dots, 2 per layer)."""
    return jnp.einsum("bhqd,bhkd->bhqk", q, k,
                      preferred_element_type=jnp.float32)


def _scores_f32_fwd(q, k):
    return _scores_f32(q, k), (q, k)


def _scores_f32_bwd(res, g):
    q, k = res
    g = g.astype(q.dtype)
    dq = jnp.einsum("bhqk,bhkd->bhqd", g, k)
    dk = jnp.einsum("bhqk,bhqd->bhkd", g, q)
    return dq, dk.astype(k.dtype)


_scores_f32.defvjp(_scores_f32_fwd, _scores_f32_bwd)


def sdpa_reference(q, k, v, causal=False, scale=None, mask=None, bias=None):
    """(B, H, S, D) reference attention in plain jnp."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    logits = _scores_f32(q, k) * scale
    if bias is not None:  # additive position bias (T5-style), broadcastable
        logits = logits + bias
    valid = None
    if causal:
        s_q, s_k = logits.shape[-2:]
        valid = jnp.tril(jnp.ones((s_q, s_k), bool), s_k - s_q)
    if mask is not None:
        m = mask.astype(bool)
        valid = m if valid is None else jnp.logical_and(valid, m)
    if valid is not None:
        logits = jnp.where(valid, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    if valid is not None:
        # a query row with NO valid key (under the COMBINED causal∧mask
        # validity) yields ZERO output, not the uniform softmax fallback
        # (which would leak every value vector — e.g. the XLNet query
        # stream's first-in-permutation position)
        row_any = jnp.any(valid, axis=-1, keepdims=True)
        probs = jnp.where(row_any, probs, 0.0)
    # result dtype follows the operands (bf16 in → bf16 out): forcing an
    # f32 result here would make the cotangent f32 and run the backward
    # dots as f32×f32 (the matmul.py dtype-discipline note); the scores
    # einsum above keeps its f32 RESULT because softmax needs the range
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(q.dtype), v)


def _note_flash_fallback(reason):
    """Record a dispatch that left the flash fast path — NEVER silent:
    the reason lands in the ``hetu_tpu.metrics`` counter registry
    (surfaced by ``HetuProfiler.flash_fallbacks()``), and
    ``HETU_REQUIRE_FLASH=1`` escalates it to a hard failure so a TPU run
    that silently compiled onto the einsum path cannot masquerade as a
    flash measurement."""
    from ..metrics import counters_suppressed, record_flash_fallback
    if counters_suppressed():
        return  # abstract shape trace (ht.lint), not a real dispatch
    record_flash_fallback(reason)
    if os.environ.get("HETU_REQUIRE_FLASH") == "1":
        raise RuntimeError(
            f"HETU_REQUIRE_FLASH=1: attention dispatch fell back off the "
            f"flash path ({reason})")


def _causal_bucketable(q, k, causal):
    """Ragged lengths bucket (pad+mask+unpad) EXCEPT under causal when
    q/kv lengths differ mod 128 — padding would shift the bottom-right-
    aligned diagonal (flash_attention raises for that combination)."""
    return not causal or (q.shape[-2] % 128) == (k.shape[-2] % 128)


def _gate_reason(q, k, causal=False):
    """Why the base gate refuses the flash path (None = it passes)."""
    be = jax.default_backend()
    if be != "tpu":
        return f"backend:{be}"
    s_q, s_kv = q.shape[-2], k.shape[-2]
    if s_q < _FLASH_MIN_LEN:
        return f"below_gate:seq{s_q}<{_FLASH_MIN_LEN}"
    if not _causal_bucketable(q, k, causal):
        return f"causal_ragged_mismatch:({s_q},{s_kv})"
    return None


def _use_flash(q, k):
    """One dispatch rule for every flash-capable op (keeps the varlen and
    dense paths from drifting apart).  Ragged (non-128-multiple) lengths
    no longer disqualify — the kernel entry buckets them."""
    s_q = q.shape[-2]
    return jax.default_backend() == "tpu" and s_q >= _FLASH_MIN_LEN


def dispatch_sdpa(q, k, v, causal=False, scale=None):
    """Backend-dispatched dense attention: the Pallas flash kernel when the
    empirical gate says it wins, XLA-composed otherwise.  The functional
    entry point for schedules that compose attention themselves (Ulysses'
    full-sequence local step, pipeline stages)."""
    if _use_flash(q, k) and _causal_bucketable(q, k, causal):
        from .pallas.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, scale=scale)
    _note_flash_fallback(_gate_reason(q, k, causal) or "dispatch_gate")
    return sdpa_reference(q, k, v, causal=causal, scale=scale)


def _partition_mesh(c):
    """The executor's mesh where an attention op has to partition itself
    (:func:`_partitioned`): on TPU, over several devices, not already
    inside a ``shard_map``; else None."""
    mesh = getattr(c, "mesh", None)
    if (mesh is None or mesh.size == 1 or jax.default_backend() != "tpu"
            or jax.sharding.get_abstract_mesh().manual_axes):
        return None
    return mesh


def _partitioned(c, fn, q, k, v, *extras, head_dim=None):
    """``fn(q, k, v, *extras)`` — a ``dispatch_*`` entry — under the
    executor's mesh.  XLA's SPMD partitioner refuses a Mosaic kernel it
    meets in a multi-device program ("cannot be automatically
    partitioned"), so on TPU the graph-level attention ops partition
    themselves: a ``shard_map`` with the batch dim over ``dp`` and the
    head dim over ``tp`` (where the mesh has them and they divide), every
    other axis replicated.  Attention is independent per (batch, head), so
    this is the partition GSPMD would have to pick anyway.  Masks, biases,
    lengths and positions follow their batch / head dims; broadcast (size
    1) dims stay replicated.  Inside an enclosing ``shard_map`` (ring /
    Ulysses steps, pipeline stages) the call is already manual and runs
    as is.  ``head_dim``: q, k, v and the result are PACKED (B, S, H·D)
    with heads of that size, and ``tp`` shards their last axis."""
    mesh = _partition_mesh(c)
    if mesh is None:
        return fn(q, k, v, *extras)
    from jax.sharding import PartitionSpec as P
    packed = head_dim is not None
    b = q.shape[0]
    h = q.shape[2] // head_dim if packed else q.shape[1]

    def axis(name, n):
        ok = name in mesh.axis_names and mesh.shape[name] > 1 \
            and n % mesh.shape[name] == 0
        return name if ok else None

    dp, tp = axis("dp", b), axis("tp", h)

    def spec(x):
        dims = [None] * x.ndim
        if x.shape[0] == b:
            dims[0] = dp
        if packed and x.ndim == 3:
            dims[2] = tp
        elif x.ndim == 4 and x.shape[1] == h:
            dims[1] = tp
        return P(*dims)

    args = (q, k, v) + extras
    return jax.shard_map(fn, mesh=mesh,
                         in_specs=tuple(spec(x) for x in args),
                         out_specs=P(dp, None, tp) if packed
                         else P(dp, tp, None, None),
                         check_vma=False)(*args)


def _sdpa(c, q, k, v, causal=False, scale=None):
    return _partitioned(
        c, lambda q, k, v: dispatch_sdpa(q, k, v, causal=causal,
                                         scale=scale), q, k, v)


sdpa_op = def_op("ScaledDotProductAttention", _sdpa)


def _split_mask_kinds(mask, q):
    """Route a broadcastable mask to the cheap kernel path.

    (B|1, 1, 1, S_kv) masks are pure key-padding masks — O(S) memory as the
    kernel's ``key_mask`` column strips; anything else rides the blockwise
    full-mask path.  Returns (key_mask, full_mask) with exactly one set.
    ``q``: head-major or packed — only its batch dim is read."""
    b = q.shape[0]
    if mask.ndim == 4 and mask.shape[1] == 1 and mask.shape[2] == 1:
        km = mask.reshape(mask.shape[0], mask.shape[-1])
        if km.shape[0] == 1:
            km = jnp.broadcast_to(km, (b, km.shape[-1]))
        return km, None
    return None, mask


def _broadcastable_extra(q, k, x):
    """Shape check for a mask/bias the kernel's broadcast-group loader
    supports: (1|B, 1|H, 1|S_q, S_kv)."""
    b, h = q.shape[:2]
    return x.ndim == 4 and x.shape[0] in (1, b) \
        and x.shape[1] in (1, h) \
        and x.shape[2] in (1, q.shape[2]) and x.shape[3] == k.shape[2]


def _flash_maskable(q, k, mask):
    """Mask shapes the kernel's broadcast-group loader supports."""
    if not _use_flash(q, k):
        return False
    if mask is None:
        return True
    return _broadcastable_extra(q, k, mask)


def _masked_reason(q, k, causal, mask, what="mask"):
    """Fallback reason for a masked/biased dispatch (None = flash-able)."""
    r = _gate_reason(q, k, causal)
    if r is not None:
        return r
    if mask is not None and not _broadcastable_extra(q, k, mask):
        return f"{what}_shape:{tuple(mask.shape)}"
    return None


def dispatch_sdpa_masked(q, k, v, mask, causal=False, scale=None):
    """Backend-dispatched masked attention (functional entry — Ulysses'
    full-sequence local step with a padding mask)."""
    if _flash_maskable(q, k, mask) and _causal_bucketable(q, k, causal):
        from .pallas.flash_attention import flash_attention
        km, fm = _split_mask_kinds(mask, q)
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               key_mask=km, mask=fm)
    _note_flash_fallback(_masked_reason(q, k, causal, mask)
                         or "dispatch_gate")
    return sdpa_reference(q, k, v, causal=causal, scale=scale, mask=mask)


def _sdpa_masked(c, q, k, v, mask, causal=False, scale=None):
    return _partitioned(
        c, lambda q, k, v, mask: dispatch_sdpa_masked(
            q, k, v, mask, causal=causal, scale=scale), q, k, v, mask)


sdpa_masked_op = def_op("ScaledDotProductAttentionMasked", _sdpa_masked)


def dispatch_sdpa_bias(q, k, v, bias, causal=False, scale=None):
    """Backend-dispatched attention with an additive logit bias — flash
    kernel when the gate and broadcast shape allow, XLA-composed otherwise
    (the functional entry for Ulysses' full-sequence local step)."""
    if _flash_maskable(q, k, bias) and _causal_bucketable(q, k, causal):
        from .pallas.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               bias=bias)
    _note_flash_fallback(_masked_reason(q, k, causal, bias, what="bias")
                         or "dispatch_gate")
    return sdpa_reference(q, k, v, causal=causal, scale=scale, bias=bias)


def _sdpa_bias(c, q, k, v, bias, causal=False, scale=None):
    """Attention with an additive logit bias (T5 relative position bias)."""
    return _partitioned(
        c, lambda q, k, v, bias: dispatch_sdpa_bias(
            q, k, v, bias, causal=causal, scale=scale), q, k, v, bias)


sdpa_bias_op = def_op("ScaledDotProductAttentionBias", _sdpa_bias)


def dispatch_sdpa_masked_bias(q, k, v, mask, bias, causal=False,
                              scale=None):
    """Backend-dispatched masked+biased attention (functional entry —
    the non-cp fallbacks of the masked CP ops and Ulysses' local step)."""
    if _flash_maskable(q, k, mask) and _flash_maskable(q, k, bias) \
            and _causal_bucketable(q, k, causal):
        from .pallas.flash_attention import flash_attention
        km, fm = _split_mask_kinds(mask, q)
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               key_mask=km, mask=fm, bias=bias)
    _note_flash_fallback(_masked_reason(q, k, causal, mask)
                         or _masked_reason(q, k, causal, bias, what="bias")
                         or "dispatch_gate")
    return sdpa_reference(q, k, v, causal=causal, scale=scale, mask=mask,
                          bias=bias)


def _sdpa_masked_bias(c, q, k, v, mask, bias, causal=False, scale=None):
    """Masked attention with an additive bias (XLNet two-stream layers)."""
    return _partitioned(
        c, lambda q, k, v, mask, bias: dispatch_sdpa_masked_bias(
            q, k, v, mask, bias, causal=causal, scale=scale),
        q, k, v, mask, bias)


sdpa_masked_bias_op = def_op("ScaledDotProductAttentionMaskedBias",
                             _sdpa_masked_bias)


def _sdpa_varlen(c, q, k, v, lengths, causal=False, scale=None):
    """Padding-masked attention: keys >= lengths[b] are invisible.

    TPU → the Pallas flash kernel's lengths path (no FLOPs spent on
    fully-masked key blocks; ragged shapes bucket); otherwise the jnp
    reference with a built column mask."""
    def local(q, k, v, lengths):
        if _use_flash(q, k) and _causal_bucketable(q, k, causal):
            from .pallas.flash_attention import flash_attention
            return flash_attention(q, k, v, causal=causal, scale=scale,
                                   lengths=lengths)
        _note_flash_fallback(_gate_reason(q, k, causal) or "dispatch_gate")
        s_kv = k.shape[-2]
        cols = jnp.arange(s_kv)[None, None, None, :]
        mask = cols < lengths.astype(jnp.int32)[:, None, None, None]
        return sdpa_reference(q, k, v, causal=causal, scale=scale,
                              mask=mask)
    return _partitioned(c, local, q, k, v, lengths)


sdpa_varlen_op = def_op("ScaledDotProductAttentionVarlen", _sdpa_varlen)


# ------------------------------------------------------------ packed layout
# q, k, v as (B, S, H·D): what ``x @ W`` leaves after a free reshape, and
# what the output projection wants back.  The training flash kernels read
# and write that layout in lane-aligned column blocks of heads
# (``flash_attention(..., heads=H)``), so a layer that can take it pays no
# transpose and no relayout around its attention — at BERT's shape 15
# whole-tensor copies a layer (PERF.md §6, PR 44).
def packed_layout_reason(heads, head_dim):
    """Why ``heads`` heads of ``head_dim`` cannot cross the flash kernels
    packed (None = they can): the head size has to divide the 128 lanes or
    be a multiple of them, and ``heads · head_dim`` to be whole column
    blocks."""
    from .pallas.flash_attention import packed_width
    width = packed_width(head_dim)
    if width is None:
        return f"head_dim:{head_dim}"
    if (heads * head_dim) % width:
        return f"column_block:{heads}x{head_dim}%{width}"
    return None


def _split_heads(x, head_dim):
    """(B, S, H·D) → (B, H, S, D)."""
    b, s, lanes = x.shape
    return x.reshape(b, s, lanes // head_dim, head_dim).transpose(0, 2, 1, 3)


def _merge_heads(x):
    """(B, H, S, D) → (B, S, H·D)."""
    b, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, s, h * d)


def _head_major(plain, masked, q, k, v, mask, head_dim, **kw):
    """A (B, H, S, D) entry — ``plain(q, k, v)``, or ``masked(q, k, v,
    mask)`` where there is one — around packed operands: the transposes
    the packed kernel spares, for every call it cannot serve."""
    q, k, v = (_split_heads(x, head_dim) for x in (q, k, v))
    return _merge_heads(plain(q, k, v, **kw) if mask is None
                        else masked(q, k, v, mask, **kw))


def dispatch_sdpa_packed(q, k, v, mask=None, head_dim=None, causal=False,
                         scale=None):
    """Backend-dispatched attention over PACKED (B, S, H·D) operands →
    (B, S_q, H·D).  Where the flash gate passes, the kernel's packed
    entry, with a (B|1, 1, 1, S_kv) ``mask`` as its key-mask strips.
    Everything else is the head-major dispatch between two transposes —
    the CPU's ``sdpa_reference`` (same numbers as a graph that transposes
    for itself), a call below the gate, a full per-query mask — each
    counted as that dispatch counts it."""
    km, full = (None, None) if mask is None else _split_mask_kinds(mask, q)
    if full is not None:
        from ..metrics import record_flash_head_major
        record_flash_head_major(f"mask_shape:{tuple(mask.shape)}")
    elif _use_flash(q, k) and _causal_bucketable(q, k, causal):
        from .pallas.flash_attention import flash_attention
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               key_mask=km, heads=q.shape[-1] // head_dim)
    return _head_major(dispatch_sdpa, dispatch_sdpa_masked, q, k, v, mask,
                       head_dim, causal=causal, scale=scale)


def _sdpa_packed(c, q, k, v, mask=None, head_dim=None, causal=False,
                 scale=None):
    """Attention of packed (B, S, H·D) q / k / v (optional 4th graph
    input: a (B|1, 1, 1, S_kv) key-padding mask) — what
    ``MultiHeadAttention`` builds where its rule passes.  Under a mesh
    the last axis shards over ``tp`` only where each shard keeps whole
    column blocks of heads; where it would cut one, the heads shard
    head-major as before, counted in ``flash_head_major``."""
    mesh = _partition_mesh(c)
    tp = mesh.shape["tp"] if mesh is not None \
        and "tp" in mesh.axis_names else 1
    heads = q.shape[-1] // head_dim
    if tp > 1 and heads % tp == 0 \
            and packed_layout_reason(heads // tp, head_dim) is not None:
        from ..metrics import record_flash_head_major
        record_flash_head_major(
            f"tp_splits_column_block:{heads}x{head_dim}/{tp}")
        return _head_major(
            functools.partial(_sdpa, c), functools.partial(_sdpa_masked, c),
            q, k, v, mask, head_dim, causal=causal, scale=scale)

    def local(q, k, v, *mask):
        return dispatch_sdpa_packed(q, k, v, *mask, head_dim=head_dim,
                                    causal=causal, scale=scale)
    return _partitioned(c, local, q, k, v,
                        *(() if mask is None else (mask,)),
                        head_dim=head_dim)


sdpa_packed_op = def_op("ScaledDotProductAttentionPacked", _sdpa_packed)


# ------------------------------------------------------------ KV slabs
# One format for the decode plane's KV caches, chosen by ``head_dim``
# alone.  The device stores an array whose minor dimension is under 128
# lanes with that dimension moved inward (a (B, H, L, 64) f32 array is
# stored length-minor), and every kernel or dot that wants (L, D) rows
# then pays a whole-slab transposing copy, per layer, per step.  So a
# head narrower than a lane row shares it: ``r = 128 // D`` consecutive
# key rows side by side, slab shape ``(B, H, ceil(L / r), r * D)``.  The
# append, the one-token kernel and the chunked steps' jnp attention all
# read and write that shape as it is stored; ``(H, m, D)`` rows exist
# only at the edges (prefix snapshots: ``kv_slab_from_rows`` /
# ``kv_slab_to_rows``).  The ops below derive ``r`` from the shapes they
# are handed (slab lanes over the query's / new rows' ``D``), so a plain
# ``(B, H, L, D)`` cache is simply the ``r = 1`` case.
_LANES = 128


def kv_slab_pack(head_dim):
    """Key rows per slab row: ``128 // head_dim`` when ``head_dim`` is a
    proper divisor of the 128 lanes, else 1 (a head that fills whole lane
    rows, or cannot share one evenly, keeps plain (B, H, L, D) rows)."""
    d = int(head_dim)
    return _LANES // d if d < _LANES and _LANES % d == 0 else 1


def kv_slab_shape(batch, heads, length, head_dim):
    """Stored shape of a KV slab holding ``length`` rows of ``head_dim``:
    ``(batch, heads, ceil(length / r), r * head_dim)``, key row ``p`` in
    slab row ``p // r``, lanes ``[(p % r) * D, (p % r + 1) * D)``."""
    r = kv_slab_pack(head_dim)
    return (batch, heads, -(-int(length) // r), r * int(head_dim))


def kv_slab_placeholder(name, batch, heads, length, head_dim,
                        dtype=np.float32):
    """The feed of one KV slab: a placeholder of :func:`kv_slab_shape`
    that also says which ``head_dim`` it was packed for (``attrs``), the
    one thing the shape alone cannot tell the decode engine."""
    from ..graph.node import placeholder_op
    node = placeholder_op(name, dtype=dtype,
                          shape=kv_slab_shape(batch, heads, length,
                                              head_dim))
    node.attrs["head_dim"] = int(head_dim)
    return node


#: what a decode graph's state placeholder can be (``state_placeholder``)
STATE_KINDS = ("kv", "index", "ring", "recurrent")


def state_placeholder(name, kind, shape=None, dtype=np.float32, **slab):
    """The feed of one piece of per-sequence decode state, which tells
    the decode engine its KIND (``attrs["state_kind"]``) — how to
    allocate, grow, seat and account it:

    * ``"kv"`` — a growable KV slab: give ``batch``, ``heads``, ``length``
      and ``head_dim`` in place of a shape
      (:func:`kv_slab_placeholder`); its rows axis walks the
      length ladder, and a row is read only below its sequence's
      position, so a re-seated slot needs no clearing.
    * ``"index"`` — a slab as ``"kv"``'s that grows at ONE ROW PER
      ``stride`` POSITIONS (give ``stride`` with the slab's sizes,
      ``length`` still in positions): the compressed keys of a sparse
      layer's indexer.  Read below what its sequence has completed, so
      never cleared; it walks the length ladder at ``1 / stride``.
    * ``"ring"`` — a fixed ``(B, heads, window, width)`` buffer written
      at ``position mod window`` and read by position, never grown along
      the window and never cleared.
    * ``"recurrent"`` — a fixed ``(B, ...)`` state that every step folds
      its token into: the engine ZEROES a slot's rows when it seats a
      sequence there.

    A ``kv_slab_placeholder`` without a kind is a ``kv`` state."""
    if kind not in STATE_KINDS:
        raise ValueError(f"state kind {kind!r}: expected one of "
                         f"{STATE_KINDS}")
    if kind == "kv":
        node = kv_slab_placeholder(name, dtype=dtype, **slab)
    elif kind == "index":
        stride = int(slab.pop("stride"))
        node = kv_slab_placeholder(name, dtype=dtype, **dict(
            slab, length=-(-int(slab["length"]) // stride)))
        node.attrs["stride"] = stride
    else:
        from ..graph.node import placeholder_op
        node = placeholder_op(name, dtype=dtype, shape=tuple(shape))
    node.attrs["state_kind"] = kind
    return node


def kv_slab_from_rows(rows, lanes):
    """(..., m, D) key rows -> (..., ceil(m / r), lanes) slab rows, the
    last one zero-filled past row ``m``."""
    m, d = rows.shape[-2:]
    r = lanes // d
    pad = -m % r
    if pad:
        rows = jnp.pad(rows, [(0, 0)] * (rows.ndim - 2) + [(0, pad), (0, 0)])
    return rows.reshape(*rows.shape[:-2], (m + pad) // r, lanes)


def kv_slab_to_rows(slab, head_dim):
    """(..., n, r * D) slab rows -> the (..., n * r, D) key rows they
    hold.  A relayout on the device where ``r > 1``: for snapshots, tests
    and the one step that amortises it (a prefill chunk of 128 rows, which
    tiles the flash kernel), never for a whole slab in a one-token step or
    a shorter chunk's — ``dispatch_sdpa_decode``, ``dispatch_sdpa_prefill``
    and ``ops/ssm.py``'s ``_diff_attention_kv`` hand their slabs to the
    kernel as stored."""
    n, lanes = slab.shape[-2:]
    return slab.reshape(*slab.shape[:-2], n * (lanes // head_dim), head_dim)


def kv_slab_queries(q, pack):
    """(..., D) query rows -> (..., r, r * D): copy ``j`` of a query sits
    in lanes ``[j * D, (j + 1) * D)`` of row ``j``, zeros elsewhere.  Its
    product with a slab scores ``r`` keys per slab row — row ``j``,
    column ``m`` is key ``m * r + j`` (the same products plus exact
    zeros) — with no slab-shaped slice or transpose."""
    eye = jnp.eye(pack, dtype=q.dtype)
    rows = eye[:, :, None] * q[..., None, None, :]       # (..., r, r, D)
    return rows.reshape(*q.shape[:-1], pack, pack * q.shape[-1])


def kv_slab_chunk_rows(q, pack):
    """(B, H, C, D) queries of a chunk -> the (B, H, C * r, r * D) score
    rows of the slab kernel's chunk form, a query's ``r`` rows together:
    :func:`kv_slab_queries` with its two row axes merged, made by ``r``
    pads and a stack.  In front of a kernel XLA lays the eye-product out
    in a ``(2, 128)``-tiled buffer and relays it out afterwards: at
    docqa-c16's shape the call with its rows made so took 0.131 ms where
    it takes 0.113 this way (PERF.md §6, PR 46)."""
    b, h, chunk, d = q.shape
    rows = jnp.stack(
        [jnp.pad(q, ((0, 0),) * 3 + ((j * d, (pack - 1 - j) * d),))
         for j in range(pack)], axis=3)                # (B, H, C, r, r * D)
    return rows.reshape(b, h, chunk * pack, pack * d)


def sdpa_slab_reference(q, k_slab, v_slab, lengths, scale=None):
    """Plain-jnp attention of (B, H, C, D) queries over KV slabs, read as
    stored.  ``lengths``: (B, C) int — query ``c`` of sequence ``b`` sees
    keys ``< lengths[b, c]`` (at least one).  The softmax runs over the
    ``r`` score rows of a query together; the output is the sum over
    ``j`` of lanes ``[j * D, (j + 1) * D)`` of row ``j`` of ``P @ V``."""
    b, h, chunk, d = q.shape
    slab_rows, lanes = k_slab.shape[2:]
    pack = lanes // d
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    qr = kv_slab_queries(q, pack)                  # (B, H, C, r, lanes)
    s = jnp.einsum("bhcjl,bhml->bhcjm", qr, k_slab,
                   preferred_element_type=jnp.float32) * scale
    key = (jnp.arange(slab_rows, dtype=jnp.int32)[None, :] * pack
           + jnp.arange(pack, dtype=jnp.int32)[:, None])     # (r, L/r)
    seen = key[None, None, None] < lengths.astype(
        jnp.int32)[:, None, :, None, None]         # (B, 1, C, r, L/r)
    s = jnp.where(seen, s, -1e30)
    probs = jax.nn.softmax(
        s.reshape(b, h, chunk, pack * slab_rows), axis=-1).reshape(s.shape)
    out = jnp.einsum("bhcjm,bhml->bhcjl", probs.astype(q.dtype), v_slab)
    return jnp.einsum("bhcjjd->bhcd",
                      out.reshape(b, h, chunk, pack, pack, d))


def _slab_len(q, k_cache):
    """Key rows a slab has room for: slab rows times rows per slab row."""
    return k_cache.shape[-2] * (k_cache.shape[-1] // q.shape[-1])


def _decode_gate_reason(s_kv):
    """Why a one-token step over a slab with room for ``s_kv`` key rows
    leaves the kernel path (None = kernel-able).  The decode gate keys on
    the KV-CACHE length — the axis the kernel tiles and the axis that
    grows as generation proceeds — not the base gate's q_len (always 1 in
    decode, where the base gate would refuse every step)."""
    be = jax.default_backend()
    if be != "tpu":
        return f"backend:{be}"
    if s_kv < _FLASH_MIN_LEN:
        return f"decode_below_gate:kv{s_kv}<{_FLASH_MIN_LEN}"
    if s_kv % 128:
        return f"decode_kv_ragged:kv{s_kv}"
    return None


def kv_rows_read(lengths, slab_shape, pack, itemsize):
    """Key rows of the key blocks a ONE-TOKEN step's attention WALKS of
    ``(B, H, L/r, lanes)`` slabs (``r = pack``) for sequences of
    ``lengths`` keys: the live key blocks of the geometry of a call over
    a K and a V slab, whole, where the decode gate lets the kernel in;
    every row the slabs hold on the jnp path.  No counter reads it: what
    the kernel copies of those blocks is :func:`kv_rows_fetched` (kept
    for ``tests/bench_harness``, PERF.md §7)."""
    return _kv_rows(lengths, slab_shape, pack, itemsize, 1, whole=True)


def kv_rows_fetched(lengths, slab_shape, pack, itemsize, chunk=1):
    """Key rows a step's attention FETCHES of those slabs through the
    kernel — a one-token step's, or a step of ``chunk`` positions a
    sequence through the chunk form (``dispatch_sdpa_prefill``),
    ``lengths`` then where the rows each sequence wrote end: of a
    sequence's last live block only the rows below its length, rounded up
    to what one copy moves (``decode_attention._tail``).  Host arithmetic
    over shapes — what ``DecodeEngine`` counts a step by
    (``decode_kv_rows_read``)."""
    return _kv_rows(lengths, slab_shape, pack, itemsize, chunk, whole=False)


def _kv_rows(lengths, slab_shape, pack, itemsize, chunk, whole):
    b, heads, slab_rows, lanes = slab_shape
    if _slab_gate_reason(slab_shape, pack, itemsize, chunk) is not None:
        return b * slab_rows * pack
    from .pallas.decode_attention import _tail, geometry
    block = geometry(heads, slab_rows, lanes, itemsize, 2, chunk * pack)[1]
    unit = block if whole else _tail(block, itemsize)[0] or block
    rows = -(-np.clip(lengths, 1, slab_rows * pack) // pack)
    return int((-(-rows // unit) * unit).sum()) * pack


def dispatch_sdpa_decode(q, k_cache, v_cache, positions, scale=None):
    """One autoregressive decode step against a bucketed KV cache.

    ``q``: the current token's query, (B, H, 1, D).  ``k_cache`` /
    ``v_cache``: KV slabs (:func:`kv_slab_shape`: (B, H, L/r, r*D), or
    plain (B, H, L, D) — ``r`` is read off the shapes) with the new token
    already appended at ``positions`` (see ``kv_cache_append_op``).
    ``positions``: (B,) int — the row each sequence just wrote; keys
    beyond it are invisible (so ``causal`` is implied: the query IS the
    last valid key).  On TPU a cache at a mod-128 bucket >= the flash
    gate goes to the one-token kernel (:mod:`~hetu_tpu.ops.pallas.
    decode_attention`: the slab read as stored, only the key blocks below
    a sequence's length fetched, computed or stepped over); anything else
    is the counted jnp reference over the same slabs."""
    lengths = positions.astype(jnp.int32) + 1
    reason = _decode_gate_reason(_slab_len(q, k_cache))
    if reason is None:
        from .pallas.decode_attention import decode_attention
        d = q.shape[-1]
        pack = k_cache.shape[-1] // d
        scale = scale if scale is not None else 1.0 / (d ** 0.5)
        rows = kv_slab_queries(q[:, :, 0, :] * scale, pack)
        return decode_attention(rows.astype(k_cache.dtype), k_cache, v_cache,
                                lengths, pack=pack).astype(q.dtype)
    _note_flash_fallback(reason)
    return sdpa_slab_reference(q, k_cache, v_cache, lengths[:, None],
                               scale=scale)


def _sdpa_decode(c, q, k_cache, v_cache, positions, scale=None):
    return _partitioned(
        c, lambda q, k, v, pos: dispatch_sdpa_decode(q, k, v, pos,
                                                     scale=scale),
        q, k_cache, v_cache, positions)


sdpa_decode_op = def_op("ScaledDotProductAttentionDecode", _sdpa_decode)


def _kv_cache_append(c, cache, new, positions, valid=None):
    """Append (B, H, C, D) token rows into a KV slab at key rows
    ``positions[b] .. positions[b]+C`` — the incremental write that makes
    a generation O(S) total attention work instead of re-prefill's
    O(S^2).  C=1 is the classic decode write; C>1 is a chunked-prefill
    write (ISSUE 18).

    ``cache`` is (B, H, L/r, r*D) (:func:`kv_slab_shape`; ``r`` is read
    off the shapes, 1 for a plain (B, H, L, D) cache): key row ``p``
    lives in slab row ``p // r`` at lanes ``[(p % r)*D, (p % r + 1)*D)``.
    The write is a read-modify-write of the few slab rows the chunk
    touches, so every lane it does not own keeps its bytes.  On the TPU
    it is ONE call of :func:`~hetu_tpu.ops.pallas.kv_append.kv_append`,
    whose grid walks the slots and rewrites one sublane tile of the
    donated slab each, aliased onto its output; every other backend
    (Pallas TPU kernels do not run there) takes a loop over the batch —
    per sequence a dynamic_slice, a select and a dynamic_update_slice —
    which is also what the kernel is held bitwise equal to.
    ``kv_append_calls`` counts either path per trace.

    ``valid`` (optional 4th graph input, (B,) int): rows ``>= valid[b]``
    of the chunk are NOT written — the old cache bytes are preserved by
    the same select, so a ragged chunk (a row consuming fewer than C
    prompt tokens, or an idle slot with valid=0) leaves the cache
    bitwise-identical to the token-by-token path.  That byte-level
    path-independence is what makes shared-prefix KV snapshots safe to
    reuse across ingestion modes.  The engine guarantees positions+C
    never exceeds the rows the slab holds (past it the window clamps
    under XLA dynamic-slice semantics and the write would shift)."""
    positions = jnp.asarray(positions, jnp.int32)
    chunk = new.shape[2]
    count = (jnp.full(positions.shape, chunk, jnp.int32) if valid is None
             else jnp.minimum(jnp.asarray(valid, jnp.int32), chunk))
    if jax.default_backend() == "tpu":
        from .pallas.kv_append import kv_append
        return _partitioned(c, kv_append, cache, new, positions, count)
    return _kv_append_loop(cache, new, positions, count)


def _kv_append_loop(cache, new, positions, count):
    """:func:`_kv_cache_append` as XLA ops, one sequence after another:
    the path of every backend but the TPU, and what the kernel's bytes
    are compared with.  ``count``: (B,) int32, at most C."""
    heads, chunk, d = new.shape[1:]
    slab_rows, lanes = cache.shape[2:]
    r = lanes // d
    from ..metrics import record_kv_append_call
    from .pallas.kv_append import geometry
    record_kv_append_call(
        geometry(chunk, slab_rows, lanes, d, cache.dtype.itemsize)[0],
        lanes, "loop")
    # slab rows a chunk can touch, wherever in a slab row it starts
    win = min((chunk + r - 2) // r + 1, slab_rows)
    # key row (counted from the window's first) of every window element
    at = (jnp.arange(win, dtype=jnp.int32)[:, None] * r
          + jnp.arange(lanes, dtype=jnp.int32)[None, :] // d)

    def write(b, slab):
        # one sequence: its window out, the chunk's rows selected in,
        # the window back — a loop over the batch, not a vmap, because a
        # batched dynamic_slice is a gather, for whose operand the
        # compiler relays out the whole slab
        p = positions[b]
        row0 = jnp.minimum(p // r, slab_rows - win)
        off = p - row0 * r
        old = jax.lax.dynamic_slice(slab, (b, 0, row0, 0),
                                    (1, heads, win, lanes))
        rows = jax.lax.dynamic_slice(new, (b, 0, 0, 0),
                                     (1, heads, chunk, d))
        fresh = jax.lax.dynamic_update_slice(
            jnp.zeros((1, heads, win * r, d), slab.dtype),
            rows.astype(slab.dtype), (0, 0, off, 0)).reshape(old.shape)
        keep = jnp.logical_and(at >= off, at < off + count[b])
        return jax.lax.dynamic_update_slice(
            slab, jnp.where(keep, fresh, old), (b, 0, row0, 0))
    return jax.lax.fori_loop(0, cache.shape[0], write, cache)


kv_cache_append_op = def_op("KVCacheAppend", _kv_cache_append)


def _slab_gate_reason(slab_shape, pack, itemsize, chunk):
    """Why the attention of ``chunk`` positions a sequence over K and V
    slabs of ``slab_shape`` (``pack`` key rows a slab row) leaves the
    slab kernel (None = kernel-able): the one-token read's gate, and the
    chunk's score rows beside a key block have to fit the kernel's VMEM
    (``decode_attention.fits``; every chunk of an engine's ladder does)."""
    _, heads, slab_rows, lanes = slab_shape
    reason = _decode_gate_reason(slab_rows * pack)
    if reason is None and chunk > 1:
        from .pallas.decode_attention import fits
        if not fits(heads, slab_rows, lanes, itemsize, 2, chunk * pack):
            reason = f"prefill_chunk_rows:q{chunk}"
    return reason


def dispatch_sdpa_prefill(q, k_cache, v_cache, positions, valid=None,
                          scale=None):
    """A chunked prefill step against a bucketed KV cache — the q_len=C
    generalization of ``dispatch_sdpa_decode`` (ISSUE 18).

    ``q``: this chunk's queries, (B, H, C, D).  ``k_cache`` /
    ``v_cache``: KV slabs (:func:`kv_slab_shape`) with the chunk's rows
    already appended at ``positions..positions+C`` (see
    ``kv_cache_append_op``).  ``positions``: (B,) int — the cache row of
    each sequence's FIRST chunk token; chunk-local query j may see keys
    ``< positions+j+1`` (causal-within-chunk, everything before the
    chunk visible).  ``valid``: (B,) int or None — the chunk rows each
    sequence really took, as the append was told.

    On the TPU, over a slab the one-token read's gate lets in
    (:func:`_decode_gate_reason`), a chunk is the one-token kernel's chunk
    form (:func:`~hetu_tpu.ops.pallas.decode_attention.decode_attention`,
    ``chunk=C``): the slabs read as stored, only the key blocks below
    where a sequence's ``valid`` rows end fetched, ``C * r`` score rows a
    head with a causal limit each.  A chunk of whole 128-row tiles keeps
    the flash kernel's full-mask path (kernel-causal can't shift its
    diagonal per batch row), which wants (L, D) rows: a packed slab is
    unpacked for it, a whole-slab relayout that a 128-row chunk amortises.
    Everything else — every other backend, a short length bucket — is the
    counted jnp reference, which reads the slabs whole as stored, and is
    what the kernel is held to.  Rows past a sequence's real prompt are
    masked by the CALLER's cache-write ``valid`` and sliced away by the
    emit gather — their outputs are don't-cares here."""
    chunk, d = q.shape[-2:]
    positions = positions.astype(jnp.int32)
    lengths = (positions[:, None]
               + 1 + jnp.arange(chunk, dtype=jnp.int32)[None, :])  # (B, C)
    pack = k_cache.shape[-1] // d
    reason = _slab_gate_reason(k_cache.shape, pack, k_cache.dtype.itemsize,
                               chunk)
    if reason is None and chunk % 128 == 0:
        from .pallas.flash_attention import flash_attention
        cols = jnp.arange(_slab_len(q, k_cache), dtype=jnp.int32)
        mask = cols[None, None, None, :] < lengths[:, None, :, None]
        return flash_attention(q, kv_slab_to_rows(k_cache, d),
                               kv_slab_to_rows(v_cache, d), causal=False,
                               scale=scale, mask=mask)
    if reason is None:
        from .pallas.decode_attention import decode_attention
        scale = scale if scale is not None else 1.0 / (d ** 0.5)
        rows = kv_slab_chunk_rows(q * scale, pack)
        return decode_attention(rows.astype(k_cache.dtype), k_cache, v_cache,
                                positions + 1, pack=pack, chunk=chunk,
                                count=valid).astype(q.dtype)
    _note_flash_fallback(reason)
    return sdpa_slab_reference(q, k_cache, v_cache, lengths, scale=scale)


def _sdpa_prefill(c, q, k_cache, v_cache, positions, valid=None, scale=None):
    extras = (positions,) if valid is None else (positions, valid)
    return _partitioned(
        c, lambda q, k, v, pos, *valid: dispatch_sdpa_prefill(
            q, k, v, pos, *valid, scale=scale),
        q, k_cache, v_cache, *extras)


sdpa_prefill_op = def_op("ScaledDotProductAttentionPrefill", _sdpa_prefill)


def _chunk_positions(c, positions, ids, limit=None):
    """Per-token cache positions for a (B, C) chunk:
    ``positions[b] + j`` for chunk-local token j, clamped to
    ``limit - 1`` so idle slots / ragged tails index a real (ignored)
    position-embedding row.  Shape-agnostic: one graph retraces per fed
    (B, C)."""
    chunk = ids.shape[-1]
    p = (positions.astype(jnp.int32)[:, None]
         + jnp.arange(chunk, dtype=jnp.int32)[None, :])
    if limit is not None:
        p = jnp.minimum(p, jnp.int32(limit - 1))
    return p


chunk_positions_op = def_op("ChunkPositions", _chunk_positions)


def _split_heads_chunk(c, t, ids, n_head=1):
    """(B*C, H*D) projected activations -> (B, H, C, D) heads, with the
    (B, C) shape recovered from the ``ids`` feed (shape-agnostic chunk
    twin of the decode graph's q_len=1 reshape)."""
    b, chunk = ids.shape
    return t.reshape(b, chunk, n_head, -1).transpose(0, 2, 1, 3)


split_heads_chunk_op = def_op("SplitHeadsChunk", _split_heads_chunk)


def _merge_heads_chunk(c, att):
    """(B, H, C, D) attention outputs -> (B*C, H*D) for the residual
    stream."""
    b, h, chunk, d = att.shape
    return att.transpose(0, 2, 1, 3).reshape(b * chunk, h * d)


merge_heads_chunk_op = def_op("MergeHeadsChunk", _merge_heads_chunk)


def _chunk_emit_gather(c, hidden, ids, valid):
    """Pick each sequence's LAST consumed chunk row out of the (B*C, E)
    hidden stream: row ``valid[b] - 1`` (clamped into the chunk) of
    batch b -> (B, E).  Sliced before ln_f/lm_head so a chunked step
    pays the vocab projection for B rows, not B*C."""
    b, chunk = ids.shape
    e = hidden.shape[-1]
    h3 = hidden.reshape(b, chunk, e)
    rows = jnp.clip(valid.astype(jnp.int32) - 1, 0, chunk - 1)
    return jnp.take_along_axis(h3, rows[:, None, None], axis=1)[:, 0, :]


chunk_emit_gather_op = def_op("ChunkEmitGather", _chunk_emit_gather)


def _has_cp(mesh):
    return mesh is not None and "cp" in mesh.axis_names \
        and mesh.shape["cp"] > 1


def _ring_attention(c, q, k, v, bias=None, causal=False, scale=None):
    """Ring attention over the 'cp' mesh axis; plain sdpa when no cp axis
    (identical numerics — parity-tested in tests/test_context_parallel.py).
    ``bias`` (optional 4th graph input): additive logit bias, ring-sliced
    per step (T5 relative position bias with context parallelism)."""
    if _has_cp(c.mesh):
        from ..parallel.ring_attention import ring_attention
        return ring_attention(q, k, v, c.mesh, bias=bias, causal=causal,
                              scale=scale)
    if bias is not None:
        return dispatch_sdpa_bias(q, k, v, bias, causal=causal, scale=scale)
    return _sdpa(c, q, k, v, causal=causal, scale=scale)


ring_attention_op = def_op("RingAttention", _ring_attention)


def _ulysses_attention(c, q, k, v, bias=None, causal=False, scale=None):
    """Ulysses head-sharded all-to-all attention over the 'cp' axis.
    ``bias`` (optional 4th graph input): head-sharded additive bias."""
    if _has_cp(c.mesh):
        from ..parallel.ring_attention import ulysses_attention
        return ulysses_attention(q, k, v, c.mesh, bias=bias, causal=causal,
                                 scale=scale)
    if bias is not None:
        return dispatch_sdpa_bias(q, k, v, bias, causal=causal, scale=scale)
    return _sdpa(c, q, k, v, causal=causal, scale=scale)


ulysses_attention_op = def_op("UlyssesAttention", _ulysses_attention)


def _cp_mask_kwargs(mask):
    """Route a 4-D attention mask onto the cheapest cp schedule input:
    KEY-padding masks ((B|1, 1, 1, S_kv) — validity does not vary per
    query) ride the ring as (B, S_kv) column flags; anything else is a
    FULL per-query mask, query-sharded like the bias (round-4 verdict
    item 5 made these shard over the ring instead of raising)."""
    if mask.ndim != 4:
        raise ValueError(f"attention mask must be 4-D, got {mask.shape}")
    if mask.shape[1] == 1 and mask.shape[2] == 1:
        return {"key_mask": mask}
    return {"mask": mask}


def _ring_attention_masked(c, q, k, v, mask, bias=None, causal=False,
                           scale=None):
    """Ring attention with a key-padding OR full per-query mask; optional
    additive bias rides the same ring slicing."""
    if _has_cp(c.mesh):
        from ..parallel.ring_attention import ring_attention
        return ring_attention(q, k, v, c.mesh, bias=bias, causal=causal,
                              scale=scale, **_cp_mask_kwargs(mask))
    if bias is not None:
        return dispatch_sdpa_masked_bias(q, k, v, mask, bias, causal=causal,
                                         scale=scale)
    return dispatch_sdpa_masked(q, k, v, mask, causal=causal, scale=scale)


ring_attention_masked_op = def_op("RingAttentionMasked",
                                  _ring_attention_masked)


def _ulysses_attention_masked(c, q, k, v, mask, bias=None, causal=False,
                              scale=None):
    """Ulysses attention with a key-padding OR full per-query mask."""
    if _has_cp(c.mesh):
        from ..parallel.ring_attention import ulysses_attention
        return ulysses_attention(q, k, v, c.mesh, bias=bias, causal=causal,
                                 scale=scale, **_cp_mask_kwargs(mask))
    if bias is not None:
        return dispatch_sdpa_masked_bias(q, k, v, mask, bias, causal=causal,
                                         scale=scale)
    return dispatch_sdpa_masked(q, k, v, mask, causal=causal, scale=scale)


ulysses_attention_masked_op = def_op("UlyssesAttentionMasked",
                                     _ulysses_attention_masked)
