"""One-token attention over a KV slab — Pallas TPU kernel.

A decode step's attention has a handful of query rows per (sequence, KV
head) and reads that head's cache: it is bound by the slab's bytes, so the
kernel reads the slab exactly as it is stored (:func:`~hetu_tpu.ops.
attention.kv_slab_shape`: ``(B, H, L/r, r*D)``, ``r`` consecutive key rows
side by side in one 128-lane row, ``r = 1`` for plain rows) and fetches
ONLY the key blocks below each sequence's length.  No relayout copy stands
between the append and this call (a slab whose minor dimension is a whole
number of lane rows is stored row-major and unpadded, the layout a
``pallas_call`` demands of its operands).

Every one-token caller is this one call (a third, ``ops/kda.py``'s
grouped-query read, hands the ``R`` heads of a key head as ``R`` rows; the
fourth, ``ops/mla.py``'s latent read, is the latent mode below):

* GPT-2's packed heads (``dispatch_sdpa_decode``; ``r = 2``, float32): a
  query becomes ``r`` rows (:func:`~hetu_tpu.ops.attention.
  kv_slab_queries`), copy ``j`` in lanes ``[j*D, (j+1)*D)`` and zero
  elsewhere, so row ``j``, column ``m`` of ``rows @ K_slab^T`` is the score
  of key ``m*r + j`` — the plain products plus exact zeros;
* the shared-KV readers of a differential-attention decoder
  (``ops/ssm.py::_diff_attention_kv``; ``r = 1``, bfloat16 paired rows
  ``[k1; k2]``): the four heads that read a key pair are four rows, each
  laid in its own 64-lane half.

Every score row keeps its own online softmax (running max, sum and
``P @ V`` accumulator, float32); the ``r`` rows of a query are merged at
the end by their log-sum-exp, ``out = sum_j e^(m_j - m) acc_j[lanes j] /
sum_j e^(m_j - m) l_j``, which for ``r = 1`` is the row's own ``acc / l``.

Geometry (:func:`geometry`, from the call's shape and dtype alone): one
program holds ALL the heads of a slot over a block of key rows sized to
``BLOCK_BYTES`` — a grid step costs a third of a microsecond whatever it
moves (PERF.md §6, PR 30), so a program per (slot, head) spends its time
on steps — and the grid walks a schedule of the LIVE (slot, key block)
pairs only: the schedule and its length are computed from ``lengths`` in
front of the call and ride as scalar prefetch and as the grid's traced
bound, so a block past a sequence's length is neither fetched, computed
nor stepped over, and the pipeline prefetches the next slot's first block
behind the current slot's last.  Measured against a static ``(B, key
blocks)`` grid that skips dead steps (28 % slower at the phi4 cell's
lengths) and against a hand-written double-buffered copy loop per slot
(5 % faster, three times the code) before it was chosen.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF

#: bytes of one key block, all the heads of a program together (K and V,
#: double-buffered, hold four of them in VMEM).  Measured on the chip at
#: both cells' shapes (``tools/decode_attn_ab.py``, PERF.md §6 PR 30): half
#: of it costs more in grid steps than it saves in dead rows, twice of it
#: the reverse
BLOCK_BYTES = 640 * 1024
#: a program takes fewer heads before its key blocks get shorter than this
MIN_BLOCK_ROWS = 64


def geometry(heads, slab_rows, lanes, itemsize):
    """``(heads per program, slab rows per key block)`` of a call over
    ``(B, heads, slab_rows, lanes)`` slabs: every head of a slot in one
    program while a block of ``MIN_BLOCK_ROWS`` rows of them fits
    ``BLOCK_BYTES`` (else their largest divisor that does), and the
    largest sublane-aligned divisor of the slab's rows that keeps the
    block inside it."""
    row = lanes * itemsize
    floor = min(slab_rows, MIN_BLOCK_ROWS)
    hb = max((h for h in range(1, heads + 1)
              if heads % h == 0 and h * floor * row <= BLOCK_BYTES),
             default=1)
    tile = 32 // itemsize
    fit = BLOCK_BYTES // (hb * row)
    rows = max((r for r in range(tile, min(fit, slab_rows) + 1, tile)
                if slab_rows % r == 0), default=slab_rows)
    return hb, rows


def _init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _block(q, k, v, ki, length, m_scr, l_scr, acc_scr, pack):
    """One key block into the running softmax of every score row.  ``q``:
    (heads, rows, lanes); ``k`` / ``v``: (heads, block_k, lanes), slab
    rows ``ki * block_k ...``."""
    block_k = k.shape[1]
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)          # (heads, rows, block_k)
    row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    # row i, column m of this block scores key (ki*block_k + m)*r + i % r
    valid = (col + ki * block_k) * pack + row % pack < length
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[:, :, :1]                         # (heads, rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # a row with no key yet has m_new = NEG_INF: its exp(0) must not count
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l_scr[:, :, :1] + jnp.sum(p, axis=2, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + _pv(p, v)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _pv(p, v):
    """``P @ V`` in float32: (heads, rows, block_k) float32 weights over
    (heads, block_k, lanes) values.  Values narrower than float32 meet
    the weights as TWO rows of their own type, ``p = hi + lo`` (the
    rounding to that type and what it left), stacked so that V crosses
    the MXU once: each score row is rounded on its own here — before its
    sum is known and before ``A1 − λ A2`` is taken of a differential
    pair, where one rounding of bfloat16 weights reads up to three times
    the error of rounding the combined, normalised weights (PERF.md §6,
    PR 30) — and with the second row nothing of a weight is lost that
    float32 sums would keep."""
    dims = (((2,), (1,)), ((0,), (0,)))
    if v.dtype == jnp.float32:
        return jax.lax.dot_general(p, v, dims,
                                   preferred_element_type=jnp.float32)
    hi = p.astype(v.dtype)
    lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
    out = jax.lax.dot_general(jnp.concatenate([hi, lo], axis=1), v, dims,
                              preferred_element_type=jnp.float32)
    return out[:, :p.shape[1]] + out[:, p.shape[1]:]


def _finish(o_ref, m_scr, l_scr, acc_scr, pack):
    """The ``pack`` rows of each query merged by their log-sum-exp."""
    queries, d = o_ref.shape[-2:]
    for g in range(queries):
        at = [g * pack + j for j in range(pack)]
        m = [m_scr[:, i:i + 1, :1] for i in at]      # (heads, 1, 1) each
        top = functools.reduce(jnp.maximum, m)
        w = [jnp.exp(mj - top) for mj in m]
        den = sum(wj * l_scr[:, i:i + 1, :1] for wj, i in zip(w, at))
        num = sum(wj * acc_scr[:, i:i + 1, j * d:(j + 1) * d]
                  for j, (wj, i) in enumerate(zip(w, at)))
        o_ref[:, g:g + 1, :] = (
            num / jnp.where(den == 0.0, 1.0, den)).astype(o_ref.dtype)


def _kernel(len_ref, slot_ref, blk_ref, q_ref, k_ref, *rest, pack, v_lanes):
    """``rest``: the value block, then the output and the scratch — or, in
    the latent mode (``v_lanes``), no value block: the value is the first
    ``v_lanes`` lanes of the key block already in VMEM."""
    o_ref, m_scr, l_scr, acc_scr = rest[-4:]
    t = pl.program_id(1)
    ki = blk_ref[t]
    length = len_ref[slot_ref[t]]
    pl.when(ki == 0)(lambda: _init(m_scr, l_scr, acc_scr))
    k = k_ref[...]
    v = rest[0][...] if v_lanes is None else k[:, :, :v_lanes]
    _block(q_ref[...], k, v, ki, length, m_scr, l_scr, acc_scr, pack)
    # the slot's last live block
    pl.when(ki == (length - 1) // (k_ref.shape[1] * pack))(
        lambda: _finish(o_ref, m_scr, l_scr, acc_scr, pack))


def _schedule(lengths, keys_per_block, num_kv):
    """The live (slot, key block) pairs in slot order, padded to ``B *
    num_kv`` entries, and how many there are."""
    b = lengths.shape[0]
    live = (lengths - 1) // keys_per_block + 1                     # (B,)
    ends = jnp.cumsum(live)
    steps = jnp.arange(b * num_kv, dtype=jnp.int32)
    slot = jnp.minimum(
        jnp.searchsorted(ends, steps, side="right").astype(jnp.int32), b - 1)
    block = jnp.minimum(steps - (ends - live)[slot], live[slot] - 1)
    return slot, block, ends[-1]


def decode_attention(rows, k_slab, v_slab, lengths, pack=1,
                     interpret=False, v_lanes=None):
    """Attention of a few query rows per (sequence, KV head) over KV slabs.

    ``rows``: (B, H, n, lanes) score rows, scaled, in the slabs' dtype —
    ``n / pack`` queries of ``pack`` rows each; row ``i`` scores, at slab
    row ``m``, key ``m * pack + i % pack``.  ``k_slab`` / ``v_slab``: (B,
    H, L/r, lanes) with ``r = pack`` key rows a slab row.  ``lengths``:
    (B,) int — keys at positions ``>= lengths[b]`` are invisible; clamped
    to ``[1, L]``.  Returns (B, H, n / pack, lanes / pack) float32: per
    query the softmax over its ``pack`` rows together, lanes ``[j*D,
    (j+1)*D)`` of row ``j`` of ``P @ V`` summed over ``j``.
    ``interpret=True`` runs the Pallas interpreter (the CPU tests exercise
    the same body).

    **The latent mode** (``v_slab=None``, ``v_lanes``, ``pack == 1``;
    ``ops/mla.py``): a cache row is a key whose first ``v_lanes`` lanes are
    also its value, so the one slab is fetched once — a second operand of
    the same slab would read it twice — and the result is (B, H, n,
    ``v_lanes``).  The trace knows that call as ``mla_fwd_q1``."""
    b, h, n, lanes = rows.shape
    latent = v_slab is None
    if latent and (pack != 1 or not v_lanes):
        raise ValueError("the latent mode reads plain rows (pack 1) and "
                         "needs v_lanes")
    out_lanes = int(v_lanes) if latent else lanes
    slab_rows = k_slab.shape[2]
    hb, block_k = geometry(h, slab_rows, lanes, k_slab.dtype.itemsize)
    from ...metrics import record_decode_attn_call
    record_decode_attn_call(hb, block_k)
    keys = block_k * pack
    lengths = jnp.clip(jnp.asarray(lengths, jnp.int32), 1, slab_rows * pack)
    slot, block, steps = _schedule(lengths, keys, slab_rows // block_k)
    tile = 32 // rows.dtype.itemsize             # a whole sublane tile
    padded = -(-n // tile) * tile
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, padded - n), (0, 0)))

    def at_slot(hi, t, len_ref, slot_ref, blk_ref):
        return slot_ref[t], hi, 0, 0

    def at_block(hi, t, len_ref, slot_ref, blk_ref):
        return slot_ref[t], hi, blk_ref[t], 0

    kv_spec = pl.BlockSpec((None, hb, block_k, lanes), at_block)
    slabs = (k_slab,) if latent else (k_slab, v_slab)
    return pl.pallas_call(
        functools.partial(_kernel, pack=pack,
                          v_lanes=out_lanes if latent else None),
        # the one-token call keeps the name the device trace knows it by
        name="mla_fwd_q1" if latent else "flash_fwd_q1",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            # the second bound is the traced count of live blocks
            grid=(h // hb, steps),
            in_specs=[pl.BlockSpec((None, hb, padded, lanes), at_slot)]
            + [kv_spec] * len(slabs),
            out_specs=pl.BlockSpec((None, hb, n // pack, out_lanes // pack),
                                   at_slot),
            scratch_shapes=[
                pltpu.VMEM((hb, padded, 128), jnp.float32),    # running max
                pltpu.VMEM((hb, padded, 128), jnp.float32),    # running sum
                pltpu.VMEM((hb, padded, out_lanes), jnp.float32),  # P @ V
            ]),
        out_shape=jax.ShapeDtypeStruct(
            (b, h, n // pack, out_lanes // pack), jnp.float32),
        interpret=interpret,
    )(lengths, slot, block, rows, *slabs)
