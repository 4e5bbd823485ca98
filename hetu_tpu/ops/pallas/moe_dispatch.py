"""Sparse MoE dispatch/combine — Pallas row-gather kernel.

The dense GShard dispatch in :mod:`hetu_tpu.ops.moe` materialises (s, e, c)
one-hot tensors, whose memory/FLOPs grow with expert count × capacity —
fine for small expert pools, ruinous for large ones.  This module replaces
both layout transforms with index maps + a single Pallas primitive:

    row_gather(src, idx)[i] = src[idx[i]]   (zeros where idx < 0)

implemented as per-row async DMA from HBM (the rows of one block are all
in flight before the first wait) over a view of the source in which one
row is a whole number of HBM tiles (:func:`_to_lane_rows`).  Both directions of both transforms are
gathers given the forward (slot→token) and inverse (token→slot) maps, so
no scatter is ever emitted:

    dispatch fwd:  buffers[j]  = tokens[token_of_slot[j]]
    dispatch bwd:  d_tokens[t] = Σ_k d_buffers[slot_of_token[t, k]]
    combine  fwd:  out[t]      = Σ_k w[t,k] · buffers[slot_of_token[t, k]]
    combine  bwd:  d_buffers[j]= w_of_slot[j] · d_out[token_of_slot[j]]

Reference parity: LayoutTransform.cu / ReverseLayoutTransform.cu (Tutel
scatter kernels, SURVEY.md §2.6) — redesigned as gathers because TPU DMA
has no scatter engine but a sequential grid makes gather-by-index cheap.
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import dtypes as jdtypes
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_BLOCK = 32
#: the chip moves HBM in whole (sublane, 128-lane) tiles of 32-bit words
LANES = 128


def _to_lane_rows(src):
    """``(N, m)`` rows → ``(N, 1, W)`` 32-bit words, ``W % 128 == 0``.

    The chip's compiler refuses a DMA of one row out of a 2-D HBM array:
    a ``(N, m)`` array is tiled ``(8, 128)`` (``(16, 128)`` for 16-bit),
    so a single row is a fraction of a tile.  With a unit second-minor
    dimension the array is tiled ``(1, 128)`` instead and one logical
    row is a whole number of tiles.  Narrower dtypes are bit-packed into
    uint32 words first (a 16-bit row would still share its tile with its
    neighbour) and the row is zero-padded to the lane width.  For a
    32-bit source whose width is 128 the view is free; otherwise XLA
    emits one relayout pass over ``src``."""
    size = src.dtype.itemsize
    if size > 4:
        raise ValueError(
            f"row_gather: {src.dtype} rows are wider than the 32-bit words "
            f"the kernel moves")
    per = 4 // size
    pad = -src.shape[1] % (per * LANES)
    if pad:
        src = jnp.pad(src, ((0, 0), (0, pad)))
    if per > 1:
        src = jax.lax.bitcast_convert_type(
            src.reshape(src.shape[0], -1, per), jnp.uint32)
    return src[:, None, :]


def _from_lane_rows(out, dtype, m):
    """Inverse of :func:`_to_lane_rows` for the gathered rows."""
    out = out[:, 0, :]
    if out.dtype != dtype:
        out = jax.lax.bitcast_convert_type(out, dtype)
        out = out.reshape(out.shape[0], -1)
    return out[:, :m]


def _gather_kernel(idx_ref, src_ref, out_ref, sems, *, block):
    b = pl.program_id(0)
    for i in range(block):
        row = idx_ref[b * block + i]

        @pl.when(row >= 0)
        def _start(i=i, row=row):
            pltpu.make_async_copy(
                src_ref.at[row], out_ref.at[i], sems.at[i]).start()

        @pl.when(row < 0)
        def _zero(i=i):
            out_ref[i] = jnp.zeros(out_ref.shape[1:], out_ref.dtype)

    for i in range(block):
        row = idx_ref[b * block + i]

        @pl.when(row >= 0)
        def _wait(i=i, row=row):
            pltpu.make_async_copy(
                src_ref.at[row], out_ref.at[i], sems.at[i]).wait()


def row_gather(src, idx, block=ROW_BLOCK, interpret=False):
    """out[i] = src[idx[i]] (rows; idx < 0 → zeros).  Non-differentiable —
    callers wire their own VJP from the inverse index map."""
    n = idx.shape[0]
    m = src.shape[1]
    rows = _to_lane_rows(src)
    w = rows.shape[2]
    n_pad = -(-n // block) * block
    idx_p = jnp.full((n_pad,), -1, jnp.int32).at[:n].set(idx.astype(jnp.int32))
    out = pl.pallas_call(
        functools.partial(_gather_kernel, block=block),
        name="row_gather",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n_pad // block,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((block, 1, w), lambda g, *_: (g, 0, 0)),
            scratch_shapes=[pltpu.SemaphoreType.DMA((block,))],
        ),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1, w), rows.dtype),
        interpret=interpret,
    )(idx_p, rows)
    return _from_lane_rows(out[:n], src.dtype, m)


def _f0(x):
    """float0 cotangent for integer primals (custom_vjp requirement)."""
    return np.zeros(x.shape, jdtypes.float0)


# ------------------------------------------------------------- dispatch
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def sparse_dispatch(tokens, token_of_slot, slot_of_token, interpret=False):
    """tokens (s, m) → expert buffers (n_slots, m).

    ``token_of_slot``: (n_slots,) int32, -1 for empty slots.
    ``slot_of_token``: (s, k) int32, -1 where the token was dropped.
    """
    return row_gather(tokens, token_of_slot, interpret=interpret)


def _dispatch_fwd(tokens, token_of_slot, slot_of_token, interpret):
    return (row_gather(tokens, token_of_slot, interpret=interpret),
            (token_of_slot, slot_of_token))


def _dispatch_bwd(interpret, res, g):
    token_of_slot, slot_of_token = res
    k = slot_of_token.shape[1]
    d_tokens = row_gather(g, slot_of_token[:, 0], interpret=interpret)
    for j in range(1, k):
        d_tokens = d_tokens + row_gather(g, slot_of_token[:, j],
                                         interpret=interpret)
    return d_tokens, _f0(token_of_slot), _f0(slot_of_token)


sparse_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


# -------------------------------------------------------------- combine
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def sparse_combine(buffers, w, slot_of_token, token_of_slot, k_of_slot,
                   interpret=False):
    """buffers (n_slots, m), gate weights w (s, k) → tokens out (s, m).

    ``k_of_slot``: (n_slots,) which of the token's k routes this slot is.
    """
    out = 0.0
    for j in range(w.shape[1]):
        part = row_gather(buffers, slot_of_token[:, j], interpret=interpret)
        out = out + w[:, j:j + 1] * part
    return out


def _combine_fwd(buffers, w, slot_of_token, token_of_slot, k_of_slot,
                 interpret):
    out = sparse_combine(buffers, w, slot_of_token, token_of_slot, k_of_slot,
                         interpret)
    return out, (buffers, w, slot_of_token, token_of_slot, k_of_slot)


def _combine_bwd(interpret, res, g):
    buffers, w, slot_of_token, token_of_slot, k_of_slot = res
    k = w.shape[1]
    # d_w[t, j] = <g[t], buffers[slot_of_token[t, j]]>  (gather recompute)
    dw_cols = []
    for j in range(k):
        part = row_gather(buffers, slot_of_token[:, j], interpret=interpret)
        dw_cols.append(jnp.sum(g * part, axis=-1))
    d_w = jnp.stack(dw_cols, axis=1).astype(w.dtype)
    # d_buffers[slot] = w_of_slot · g[token_of_slot]
    valid = token_of_slot >= 0
    t_safe = jnp.maximum(token_of_slot, 0)
    w_of_slot = jnp.where(
        valid, w[t_safe, jnp.clip(k_of_slot, 0, k - 1)], 0.0)
    gm = row_gather(g, token_of_slot, interpret=interpret)
    d_buffers = (gm * w_of_slot[:, None]).astype(buffers.dtype)
    return (d_buffers, d_w, _f0(slot_of_token), _f0(token_of_slot),
            _f0(k_of_slot))


sparse_combine.defvjp(_combine_fwd, _combine_bwd)
