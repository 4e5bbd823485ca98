"""ISSUE 37: a decode step's new cache rows written in place by one
aliased kernel (``ops/pallas/kv_append.py``) — the kernel in interpret
mode held BITWISE to the loop over the batch that every backend but the
TPU runs (``ops.attention._kv_append_loop``) and to the ring's select
(``ops.ssm._ring_write``), over buffers of arbitrary bit patterns so a
byte the write does not own shows if it moves.
"""
import functools
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax                                                 # noqa: E402
import jax.numpy as jnp                                    # noqa: E402

from hetu_tpu import metrics                               # noqa: E402
from hetu_tpu.ops import attention as att                  # noqa: E402
from hetu_tpu.ops import ssm                               # noqa: E402
from hetu_tpu.ops.pallas import kv_append as ka            # noqa: E402
from hetu_tpu.profiler import HetuProfiler                 # noqa: E402

#: (heads, slab rows, lanes, head width): plain rows, GPT-2's two keys a
#: lane row, the latent cache's one head of 640-lane rows
_LAYOUTS = {"r1_l128": (3, 64, 128, 128), "r2_l128": (3, 32, 128, 64),
            "r1_l640_h1": (1, 64, 640, 640)}


def _bits(dtype):
    return np.uint32 if jnp.dtype(dtype).itemsize == 4 else np.uint16


def _poison(rng, shape, dtype):
    """An array of arbitrary bit patterns, denormals and both zeros among
    them — but no NaN: XLA's CPU backend selects bfloat16 through float32
    and quiets a signalling one, on the loop's path as on the kernel's."""
    kind = _bits(dtype)
    raw = rng.integers(0, np.iinfo(kind).max, size=shape, dtype=kind)
    exponent = kind(0x7f800000 if kind is np.uint32 else 0x7f80)
    raw = np.where(raw & exponent == exponent, raw & ~exponent, raw)
    return jax.lax.bitcast_convert_type(jnp.asarray(raw), dtype)


def _raw(x):
    return np.asarray(jax.lax.bitcast_convert_type(
        x, jnp.dtype(_bits(x.dtype))))


def _positions(length, chunk, tile_keys):
    """Key rows that put a chunk at a tile's first row, ending on and
    starting on a tile's last row, straddling two tiles, at the slab's
    last rows, and at row 0."""
    at = [0, tile_keys, tile_keys - chunk, 2 * tile_keys - 1,
          3 * tile_keys - chunk // 2 - 1, length - chunk]
    return np.clip(np.array(at, np.int32), 0, length - chunk)


def _written(shape, d, positions, count):
    """Boolean (B, S, lanes): the elements rows ``j < count[b]`` at key
    rows ``positions[b] + j`` own."""
    slab_rows, lanes = shape[2:]
    r = lanes // d
    key = (np.arange(slab_rows)[:, None] * r
           + np.arange(lanes)[None, :] // d)                  # (S, lanes)
    lo = np.asarray(positions)[:, None, None]
    return np.logical_and(key[None] >= lo,
                          key[None] < lo + np.asarray(count)[:, None, None])


@pytest.mark.parametrize("valid", ["absent", "ragged", "zero"])
@pytest.mark.parametrize("chunk", [1, 4, 32])
@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_writes_the_loops_bytes(dtype, layout, chunk, valid):
    """Every byte of the buffer after the kernel is the byte the loop
    leaves: the chunk's rows where they belong (tile edges, a chunk over
    two tiles, the slab's last rows), and the poison everywhere else —
    rows at or past ``valid``, the other key rows' lanes of a shared slab
    row, the tiles and slots the grid never visits."""
    heads, slab_rows, lanes, d = _LAYOUTS[layout]
    r = lanes // d
    rows, steps = ka.geometry(chunk, slab_rows, lanes, d,
                              jnp.dtype(dtype).itemsize)
    assert rows == 32 // jnp.dtype(dtype).itemsize
    positions = _positions(slab_rows * r, chunk, rows * r)
    b = len(positions)
    rng = np.random.default_rng(chunk)
    buf = _poison(rng, (b, heads, slab_rows, lanes), dtype)
    new = _poison(rng, (b, heads, chunk, d), dtype)
    count = {"absent": np.full(b, chunk), "zero": np.zeros(b),
             "ragged": rng.integers(0, chunk + 1, b)}[valid].astype(np.int32)
    if valid == "ragged":
        count[0], count[-1] = chunk, 0            # an idle slot among them
    want = att._kv_append_loop(buf, new, jnp.asarray(positions),
                               jnp.asarray(count))
    got = ka.kv_append(buf, new, positions, count, interpret=True)
    assert got.dtype == buf.dtype and got.shape == buf.shape
    assert np.array_equal(_raw(got), _raw(want))
    # and, of the loop's say-so independently: what was not written is
    # what was there, what was written is the chunk's row
    mine = _written(buf.shape, d, positions, count)
    assert mine.sum() == count.sum() * d
    kept = np.broadcast_to(~mine[:, None], buf.shape)
    assert np.array_equal(_raw(got)[kept], _raw(buf)[kept])
    for s in range(b):
        for j in range(count[s]):
            p = positions[s] + j
            assert np.array_equal(
                _raw(got)[s, :, p // r, (p % r) * d:(p % r + 1) * d],
                _raw(new)[s, :, j])


def test_a_row_past_the_buffer_is_dropped():
    """A position at or past the last key row writes nothing (the loop's
    window clamps and its select keeps every byte)."""
    rng = np.random.default_rng(0)
    buf = _poison(rng, (2, 2, 16, 128), jnp.float32)
    new = _poison(rng, (2, 2, 1, 64), jnp.float32)
    positions, count = np.array([32, 40], np.int32), np.ones(2, np.int32)
    want = att._kv_append_loop(buf, new, jnp.asarray(positions),
                               jnp.asarray(count))
    got = ka.kv_append(buf, new, positions, count, interpret=True)
    assert np.array_equal(_raw(got), _raw(want))
    assert np.array_equal(_raw(got), _raw(buf))


def test_geometry_is_one_tile_and_the_tiles_a_chunk_straddles():
    """A block is one sublane tile of the buffer's type; a chunk's window
    of slab rows touches one tile more than it fills, and never more
    than the buffer has."""
    # the four callers at their cells' shapes, one-token and chunk 32
    assert ka.geometry(1, 384, 128, 64, 4) == (8, 1)        # chat, r = 2
    assert ka.geometry(32, 384, 128, 64, 4) == (8, 3)
    assert ka.geometry(1, 4608, 128, 128, 2) == (16, 1)     # phi4's slabs
    assert ka.geometry(32, 4608, 128, 128, 2) == (16, 3)
    assert ka.geometry(1, 512, 128, 128, 2) == (16, 1)      # phi4's rings
    assert ka.geometry(1, 4096, 640, 640, 2) == (16, 1)     # glm's latent
    assert ka.geometry(2, 4096, 640, 640, 2) == (16, 2)
    assert ka.geometry(16, 4096, 128, 128, 2) == (16, 2)    # solar
    # a buffer that is no whole number of tiles is one block
    assert ka.geometry(4, 12, 128, 128, 4) == (12, 1)
    assert ka.geometry(32, 16, 128, 64, 4) == (8, 2)


@pytest.fixture
def on_the_chip(monkeypatch):
    """A backend that says tpu, the kernel behind it in interpret mode."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ka, "kv_append", functools.partial(
        ka.kv_append, interpret=True))


@pytest.mark.parametrize("masked", [False, True], ids=["all", "valid"])
def test_the_op_takes_the_kernel_on_the_chip_and_the_loop_elsewhere(
        masked, monkeypatch):
    """``_kv_cache_append`` asks the backend alone: the same bytes either
    way, ``kv_append_calls`` naming the block and the path once per
    trace, and nothing under ``suppress_perf_counters()``."""
    rng = np.random.default_rng(1)
    buf = _poison(rng, (3, 2, 32, 128), jnp.float32)
    new = _poison(rng, (3, 2, 4, 64), jnp.float32)
    positions = np.array([0, 15, 60], np.int32)
    valid = (np.array([4, 0, 2], np.int32),) if masked else ()
    metrics.reset_all()
    want = att._kv_cache_append(None, buf, new, positions, *valid)
    assert metrics.kv_append_call_counts() == {"8x128:loop": 1}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ka, "kv_append", functools.partial(
        ka.kv_append, interpret=True))
    got = att._kv_cache_append(None, buf, new, positions, *valid)
    assert np.array_equal(_raw(got), _raw(want))
    assert HetuProfiler.all_counters()["kv_append_calls"] == {
        "8x128:loop": 1, "8x128:kernel": 1}
    with metrics.suppress_perf_counters():
        att._kv_cache_append(None, buf, new, positions, *valid)
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        att._kv_cache_append(None, buf, new, positions, *valid)
    assert metrics.kv_append_call_counts() == {
        "8x128:loop": 1, "8x128:kernel": 1}


def test_the_counter_counts_traces_not_steps(on_the_chip):
    """A jitted step run three times traced its append once."""
    buf = jnp.zeros((2, 1, 32, 128), jnp.bfloat16)
    new = jnp.ones((2, 1, 1, 128), jnp.bfloat16)
    step = jax.jit(lambda buf, p: att._kv_cache_append(None, buf, new, p))
    metrics.reset_all()
    for p in range(3):
        buf = step(buf, jnp.full((2,), p, jnp.int32))
    assert metrics.kv_append_call_counts() == {"16x128:kernel": 1}
    assert float(buf[:, :, :3].astype(jnp.float32).sum()) == 2 * 3 * 128
    assert float(buf.astype(jnp.float32).sum()) == 2 * 3 * 128


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_a_windows_one_row_write_is_the_selects_bytes(dtype, on_the_chip):
    """``_ring_put`` at ``chunk == 1`` behind a backend that says tpu —
    the kernel at row ``p mod W`` — against ``_ring_write``'s select over
    the whole ring: positions below, at and beyond the window, slots with
    ``count`` 0 keeping every byte."""
    rng = np.random.default_rng(2)
    ring = _poison(rng, (6, 2, 32, 128), dtype)
    new = _poison(rng, (6, 2, 1, 128), dtype)
    p = jnp.asarray([0, 31, 32, 47, 1000, 15], jnp.int32)
    count = jnp.asarray([1, 1, 1, 0, 1, 0], jnp.int32)
    metrics.reset_all()
    got = ssm._ring_put(None, ring, new, p, count)
    assert len(metrics.kv_append_call_counts()) == 1
    want = ssm._ring_write(ring, new, p, count)
    assert np.array_equal(_raw(got), _raw(want))
    assert not np.array_equal(_raw(got), _raw(ring))
    # a chunk keeps the select (last row wins around the ring)
    metrics.reset_all()
    wide = _poison(rng, (6, 2, 40, 128), dtype)
    many = jnp.asarray([40, 3, 0, 33, 40, 1], jnp.int32)
    assert np.array_equal(_raw(ssm._ring_put(None, ring, wide, p, many)),
                          _raw(ssm._ring_write(ring, wide, p, many)))
    assert metrics.kv_append_call_counts() == {}
