"""A decode step's new cache rows, written in place — Pallas TPU kernel.

A step appends ``C`` key rows per sequence to state it already owns: a KV
slab (:func:`~hetu_tpu.ops.attention.kv_slab_shape`: ``(B, H, L/r,
r*D)``, ``r`` consecutive key rows side by side in one lane row) or a
window layer's ring (``(B, G, W, 2D)``, ``r = 1``).  The bytes are a few
KB a buffer; as XLA ops the write was a serial loop over the batch (a
``dynamic_slice``, a select and a ``dynamic_update_slice`` a trip) or a
select over the whole ring.  Here the grid walks the slots, ``positions``
and the valid counts ride as scalar prefetch, the buffer's block is ONE
sublane tile of slab rows (all heads, all lanes) whose index is computed
from ``positions[b]``, and the body is the loop body's own select on that
one block.  ``input_output_aliases`` maps the buffer onto the output, so
blocks the grid never visits keep their bytes and no second buffer
exists: the engine donates every state buffer to its own update.

A chunk of ``C > 1`` rows can straddle a few tiles: the grid's second
axis walks them, clamped to the buffer's last tile (a clamped program
repeats its neighbour's block and computes the same bytes again).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def geometry(chunk, slab_rows, lanes, d, itemsize):
    """``(slab rows per block, blocks a chunk can straddle)`` of a call
    writing ``chunk`` key rows of width ``d`` into ``(B, H, slab_rows,
    lanes)`` buffers: one sublane tile of the buffer's type (the whole
    buffer where it is shorter or no whole number of tiles), and the
    tiles a window of the chunk's slab rows touches wherever it starts."""
    r = lanes // d
    tile = 32 // itemsize
    rows = tile if slab_rows % tile == 0 else slab_rows
    win = (chunk + r - 2) // r + 1
    return rows, min((win + rows - 2) // rows + 1, slab_rows // rows)


def _kernel(pos_ref, cnt_ref, new_ref, buf_ref, out_ref, *, pack, blocks):
    """One (slot, tile) program: ``buf_ref`` / ``out_ref`` (H, rows,
    lanes), the same block of the aliased buffer; ``new_ref`` (H, C,
    lanes), the chunk's rows repeated ``pack`` times along the lanes."""
    b, t = pl.program_id(0), pl.program_id(1)
    rows, lanes = buf_ref.shape[1:]
    chunk = new_ref.shape[1]
    p, n = pos_ref[b], cnt_ref[b]
    blk = jnp.minimum(p // pack // rows + t, blocks - 1)
    # the key row every element of the block belongs to
    at = ((blk * rows
           + jax.lax.broadcasted_iota(jnp.int32, (1, rows, lanes), 1)) * pack
          + jax.lax.broadcasted_iota(jnp.int32, (1, rows, lanes), 2)
          // (lanes // pack))
    out = buf_ref[...]
    for j in range(chunk):
        out = jnp.where(jnp.logical_and(at == p + j, j < n),
                        new_ref[:, j:j + 1, :], out)
    out_ref[...] = out


def kv_append(buffer, new, positions, count, interpret=False):
    """``buffer`` (B, H, S, lanes) with rows ``j < count[b]`` of ``new``
    (B, H, C, D) written at key rows ``positions[b] + j`` — key row ``p``
    in slab row ``p // r``, lanes ``[(p % r)*D, (p % r + 1)*D)``, ``r =
    lanes // D`` — and every other byte kept.  ``positions`` / ``count``:
    (B,) int32.  A row whose key row lies past the buffer is dropped.
    The result IS ``buffer`` where the caller donates it (the operand is
    aliased to the output).  ``interpret=True`` runs the Pallas
    interpreter (the CPU tests exercise the same body)."""
    slab_rows, lanes = buffer.shape[2:]
    chunk, d = new.shape[2:]
    from ...metrics import record_kv_append_call
    record_kv_append_call(
        geometry(chunk, slab_rows, lanes, d, buffer.dtype.itemsize)[0],
        lanes, "kernel")
    return _call(buffer, new.astype(buffer.dtype),
                 jnp.asarray(positions, jnp.int32),
                 jnp.asarray(count, jnp.int32), interpret=interpret)


# jitted so that the calls of one program that share a shape — K and V of
# every layer — are traced and lowered to ONE kernel: a decode program
# holds 13 to 48 of them, and lowered one by one they added half a minute
# to the chat cell's set-up (PERF.md section 6, PR 37)
@functools.partial(jax.jit, static_argnames="interpret")
def _call(buffer, new, positions, count, interpret):
    b, heads, slab_rows, lanes = buffer.shape
    chunk, d = new.shape[2:]
    pack = lanes // d
    rows, steps = geometry(chunk, slab_rows, lanes, d, buffer.dtype.itemsize)
    blocks = slab_rows // rows

    def at_slot(bi, t, pos_ref, cnt_ref):
        return bi, 0, 0, 0

    def at_tile(bi, t, pos_ref, cnt_ref):
        return bi, 0, jnp.minimum(pos_ref[bi] // pack // rows + t,
                                  blocks - 1), 0

    tile_spec = pl.BlockSpec((None, heads, rows, lanes), at_tile)
    return pl.pallas_call(
        functools.partial(_kernel, pack=pack, blocks=blocks),
        name="kv_append",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, steps),
            in_specs=[pl.BlockSpec((None, heads, chunk, lanes), at_slot),
                      tile_spec],
            out_specs=tile_spec),
        out_shape=jax.ShapeDtypeStruct(buffer.shape, buffer.dtype),
        # operands: positions, count, new, buffer
        input_output_aliases={3: 0},
        interpret=interpret,
    )(positions, count, jnp.tile(new, (1, 1, 1, pack)), buffer)
