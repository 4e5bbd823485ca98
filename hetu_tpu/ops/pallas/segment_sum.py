"""Sorted-segment row-sum — Pallas TPU kernel (embedding scatter-add).

TPU-native replacement for the reference's sparse-gradient machinery
(``IndexedSlices.cpu_deduplicate`` ndarray.py:507, ``OptimizersSparse.cu``):
duplicate embedding-row gradients are summed by (1) sorting rows by id in
XLA (fast bitonic sort on TPU) and (2) reducing each sorted run in this
kernel.  Per token block the reduction is ONE MXU matmul — a (bt × bt)
0/1 segment-indicator contracted with the (bt × d) row block — so the whole
scatter-add is matmul-shaped instead of serialized row updates.  A run that
spans block boundaries is carried forward in VMEM scratch (the sequential
TPU grid makes the carry exact), and each block DMA-writes its window of
completed segment sums to the output in HBM.

Used by the PS embedding push path (dedup before host transfer) and
available as ``sorted_segment_sum`` for any segment-reduce.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _seg_kernel(seg_ref, rows_ref, out_ref, partial, carry_row, carry_seg,
                sem, *, block, num_blocks):
    b = pl.program_id(0)
    seg = seg_ref[:]                                   # (bt, 1) int32
    seg_first = seg[0, 0]
    seg_last = seg[block - 1, 0]
    local = seg - seg_first                            # (bt, 1)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    onehot = (local == cols).astype(jnp.float32)       # (bt, W=bt)
    # HIGHEST: at default precision the MXU rounds the f32 rows to one
    # bf16 pass (1.5e-3 relative error per summed gradient, measured on a
    # v5e) — the indicator is exact in bf16, the rows are not
    partial[:] = jax.lax.dot_general(
        onehot, rows_ref[:].astype(jnp.float32), (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)            # (W, d)

    @pl.when((b > 0) & (seg_first == carry_seg[0]))
    def _merge_carry():
        partial[0, :] += carry_row[0, :]

    # stash the (possibly incomplete) last run for the next block
    local_open = seg_last - seg_first
    carry_row[0, :] = partial[pl.ds(local_open, 1), :][0, :]
    carry_seg[0] = seg_last

    # write this block's window; later blocks overwrite any rows whose run
    # continues past the boundary (sequential grid ⇒ last write wins)
    cp = pltpu.make_async_copy(partial, out_ref.at[pl.ds(seg_first, block)],
                               sem)
    cp.start()
    cp.wait()


#: the window DMA moves whole 128-lane tiles, and only an array exactly
#: one lane tile wide is laid out so that a window may start at any row
LANES = 128


def sorted_segment_sum(rows, seg_ids, num_segments, block=128,
                       interpret=False):
    """Sum ``rows`` (n, d) over sorted, contiguous ``seg_ids`` (n,) int32.

    ``seg_ids`` MUST be non-decreasing starting at 0 (sort upstream).
    Returns (num_segments, d) float32.  ``d`` is zero-padded to the lane
    width and reduced one 128-lane column panel per kernel call."""
    n, d = rows.shape
    n_pad = -(-n // block) * block
    d_pad = -(-d // LANES) * LANES
    if n_pad != n:
        last = seg_ids[-1]
        seg_ids = jnp.concatenate(
            [seg_ids, jnp.full((n_pad - n,), last, jnp.int32)])
    if (n_pad, d_pad) != (n, d):
        rows = jnp.pad(rows, ((0, n_pad - n), (0, d_pad - d)))
    num_blocks = n_pad // block
    call = pl.pallas_call(
        functools.partial(_seg_kernel, block=block, num_blocks=num_blocks),
        name="sorted_segment_sum",
        grid=(num_blocks,),
        in_specs=[
            pl.BlockSpec((block, 1), lambda b: (b, 0)),
            pl.BlockSpec((block, LANES), lambda b: (b, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((num_segments + block, LANES),
                                       jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((block, LANES), jnp.float32),    # window partials
            pltpu.VMEM((1, LANES), jnp.float32),        # carry row
            pltpu.SMEM((1,), jnp.int32),                # carry segment id
            pltpu.SemaphoreType.DMA,
        ],
        interpret=interpret,
    )
    seg_col = seg_ids.astype(jnp.int32)[:, None]
    panels = [call(seg_col, rows[:, c:c + LANES])
              for c in range(0, d_pad, LANES)]
    out = panels[0] if len(panels) == 1 else jnp.concatenate(panels, axis=1)
    # rows past the last actual segment are uninitialised HBM (blocks only
    # DMA their own windows) — zero them so the padding contract holds
    n_actual = seg_ids[-1] + 1
    valid = jnp.arange(num_segments)[:, None] < n_actual
    return jnp.where(valid, out[:num_segments, :d], 0.0)


def dedup_rows(ids, rows, interpret=False):
    """Sum rows sharing an id (reference ``cpu_deduplicate``).

    Returns (unique_ids (n,), summed (n, d), n_unique) — padded to the
    static input length with id -1 / zero rows (XLA static shapes).
    """
    n, d = rows.shape
    order = jnp.argsort(ids)
    sid = jnp.take(ids, order).astype(jnp.int32)
    r = jnp.take(rows, order, axis=0)
    starts = jnp.concatenate(
        [jnp.ones((1,), bool), sid[1:] != sid[:-1]])
    seg = jnp.cumsum(starts.astype(jnp.int32)) - 1          # (n,)
    summed = sorted_segment_sum(r, seg, n, interpret=interpret)
    n_unique = seg[-1] + 1
    uniq = jnp.full((n,), -1, jnp.int32).at[seg].set(sid)
    return uniq, summed, n_unique
