"""One-token decode attention over a KV slab — Pallas TPU kernel.

The decode step's attention has ONE query row per (sequence, head) and
reads that head's whole cache: it is bound by the slab's bytes, so the
kernel reads the slab exactly as it is stored (:func:`~hetu_tpu.ops.
attention.kv_slab_shape`): ``(B, H, L/r, r*D)`` with ``r`` consecutive
key rows side by side in one 128-lane row when ``D`` is a divisor of 128
(``r = 1`` — plain ``(B, H, L, D)`` rows — otherwise).  A slab whose
minor dimension is a whole number of lane rows is stored row-major and
unpadded by the device, which is the layout a ``pallas_call`` demands of
its operands: no relayout copy stands between the append and this call
(a 64-wide minor dimension is stored length-minor by the compiler and
was transposed, padded to 128 lanes, for every layer of every step).

The query row becomes ``r`` rows, copy ``j`` sitting in lanes
``[j*D, (j+1)*D)`` and zero elsewhere, so ``Q_r @ K_slab^T`` holds in
row ``j``, column ``m`` the score of key ``m*r + j`` — the same products
as the plain contraction plus exact zeros.  The online softmax runs over
all ``r`` rows together (one running max, one running sum), and the
output is the sum over ``j`` of lanes ``[j*D, (j+1)*D)`` of row ``j`` of
``P @ V_slab``.

Grid ``(B, H, key blocks)``; the valid-key counts ride as a scalar
prefetch, so a key block wholly past a sequence's length is neither
computed nor fetched (its index maps onto the last live block, which
the pipeline does not fetch twice).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import kv_slab_queries
from .flash_attention import NEG_INF

#: key blocks of at most this many slab rows (256 KiB of f32 at 128 lanes)
MAX_BLOCK_ROWS = 512


def _block_rows(slab_rows):
    """Slab rows per key block: the whole cache when it is short enough
    (one grid step per head: the call is bound by grid steps and bytes,
    not FLOPs), else its largest sublane-aligned divisor."""
    if slab_rows <= MAX_BLOCK_ROWS:
        return slab_rows
    for rows in range(MAX_BLOCK_ROWS, 7, -8):
        if slab_rows % rows == 0:
            return rows
    return slab_rows


def _q1_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
               *, scale, pack, head_dim, block_k, num_kv):
    ki = pl.program_id(2)
    length = len_ref[pl.program_id(0)]

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(ki * (block_k * pack) < length)
    def _block():
        q = q_ref[...]                                  # (rows, lanes)
        k = k_ref[...]                                  # (block_k, lanes)
        v = v_ref[...]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (rows, block_k)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # row j, column m of this block scores key (ki*block_k + m)*r + j;
        # rows >= r are the sublane padding of the query tile
        valid = jnp.logical_and(
            (col + ki * block_k) * pack + row < length, row < pack)
        s = jnp.where(valid, s, NEG_INF)
        # one running max and one running sum for all r rows: kept per
        # row (every row holds the same value), because a (1, 1) value
        # broadcasts along sublanes or lanes, not both at once
        m_prev = m_scr[:, :1]                           # (rows, 1)
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(
            jnp.max(s, axis=1, keepdims=True), axis=0, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new) * valid
        l_new = alpha * l_prev + jnp.sum(
            jnp.sum(p, axis=1, keepdims=True), axis=0, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # (rows, lanes)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_kv - 1)
    def _finish():
        l = l_scr[:, :1]
        acc = acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
        out = acc[0:1, 0:head_dim]
        for j in range(1, pack):
            out = out + acc[j:j + 1, j * head_dim:(j + 1) * head_dim]
        o_ref[...] = out.astype(o_ref.dtype)


def decode_attention(q, k_slab, v_slab, lengths, scale=None,
                     interpret=False):
    """Attention of one query row per (sequence, head) over KV slabs.

    ``q``: (B, H, 1, D).  ``k_slab`` / ``v_slab``: (B, H, L/r, r*D), key
    row ``p`` at slab row ``p // r``, lanes ``[(p % r)*D, (p % r + 1)*D)``
    (``r = 1``: plain (B, H, L, D)).  ``lengths``: (B,) int — keys at
    positions ``>= lengths[b]`` are invisible; at least 1.  Returns
    (B, H, 1, D).  ``interpret=True`` runs the Pallas interpreter (CPU
    tests exercise the same body)."""
    b, h, _, d = q.shape
    slab_rows, lanes = k_slab.shape[2:]
    pack = lanes // d
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    rows = -(-pack // 8) * 8                     # a whole f32 sublane tile
    qr = kv_slab_queries(q[:, :, 0, :], pack)       # (B, H, r, lanes)
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, rows - pack), (0, 0)))
    block_k = _block_rows(slab_rows)
    num_kv = slab_rows // block_k
    keys_per_block = block_k * pack

    def kv_index(bi, hi, ki, len_ref):
        last = jnp.maximum(len_ref[bi] - 1, 0) // keys_per_block
        return bi, hi, jnp.minimum(ki, last), 0

    kv_spec = pl.BlockSpec((None, None, block_k, lanes), kv_index)
    return pl.pallas_call(
        functools.partial(_q1_kernel, scale=scale, pack=pack, head_dim=d,
                          block_k=block_k, num_kv=num_kv),
        # the one-token call keeps the name the device trace knows it by
        name="flash_fwd_q1",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h, num_kv),
            in_specs=[
                pl.BlockSpec((None, None, rows, lanes),
                             lambda bi, hi, ki, len_ref: (bi, hi, 0, 0)),
                kv_spec, kv_spec],
            out_specs=pl.BlockSpec((None, None, 1, d),
                                   lambda bi, hi, ki, len_ref:
                                   (bi, hi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, 128), jnp.float32),    # running max
                pltpu.VMEM((rows, 128), jnp.float32),    # running sum
                pltpu.VMEM((rows, lanes), jnp.float32),  # output rows
            ]),
        out_shape=jax.ShapeDtypeStruct((b, h, 1, d), q.dtype),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32), qr, k_slab, v_slab)
