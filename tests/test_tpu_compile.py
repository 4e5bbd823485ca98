"""The chip's compiler, without the chip: every Pallas kernel of the main
paths compiled for a DESCRIBED TPU v5e at the widths its callers use.

Interpret-mode tests cannot see what Mosaic refuses (a DMA slice that is not
tile-aligned, a lane width below 128 — both passed every interpret test and
were refused on the first real compile, ISSUE 21); ``jax.export(...,
platforms=["tpu"])`` only serialises the kernel and never runs the compiler.
These do: ``jit(f).lower(shapes).compile()`` against
``topologies.get_topology_desc`` raises exactly what the chip would.  A
compile that passes here is NOT a chip run — nothing executes.

The topology is described inside a module-scoped fixture (only one process
may load the TPU library, and every xdist worker imports every test file —
so nothing here touches it at import, in ``skipif`` or in ``parametrize``),
and all of these tests live in this one file so one worker runs them all.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    # a compile for a described device is written to the persistent cache
    # but cannot be read back without a chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def chip(topo):
    """shape -> ShapeDtypeStruct placed on one described chip."""
    one = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)
    return shape


def _compiles_with_kernel(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _sum32(x):
    return jnp.sum(x.astype(jnp.float32))


# ------------------------------------------------------------------ flash
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("case", ["bert_key_mask", "gpt2_causal"])
def test_flash_attention_compiles_at_model_shapes(chip, case, grad):
    """bert-base b64 x s512 with a key-padding mask; gpt2-medium heads,
    causal, s1024 — bf16, forward and backward."""
    from hetu_tpu.ops.pallas.flash_attention import flash_attention
    if case == "bert_key_mask":
        qkv = chip((64, 12, 512, 64), jnp.bfloat16)
        extra = (chip((64, 512), jnp.bool_),)

        def f(q, k, v, km):
            return flash_attention(q, k, v, key_mask=km)
    else:
        qkv = chip((8, 16, 1024, 64), jnp.bfloat16)
        extra = ()

        def f(q, k, v):
            return flash_attention(q, k, v, causal=True)
    if grad:
        fn = jax.grad(lambda *a: _sum32(f(*a)), argnums=(0, 1, 2))
    else:
        fn = f
    _compiles_with_kernel(fn, qkv, qkv, qkv, *extra)


def test_flash_decode_q1_compiles(chip):
    """The decode engine's q_len=1 entry against a 256-row cache bucket."""
    from hetu_tpu.ops.pallas.flash_attention import flash_attention
    _compiles_with_kernel(
        lambda q, k, v, n: flash_attention(q, k, v, lengths=n),
        chip((8, 12, 1, 64), jnp.float32), chip((8, 12, 256, 64), jnp.float32),
        chip((8, 12, 256, 64), jnp.float32), chip((8,), jnp.int32))


# ------------------------------------------------------------ moe dispatch
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_row_gather_compiles_at_moe_size(chip, dtype):
    """(8192, 512): the moe config's own sizes.  The per-row DMA out of a
    2-D HBM array was refused here (slice of 1 row vs the 8-row tile)."""
    from hetu_tpu.ops.pallas.moe_dispatch import row_gather
    _compiles_with_kernel(row_gather, chip((8192, 512), dtype),
                          chip((8192,), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_sparse_dispatch_combine_grads_compile(chip, dtype):
    """SparseMoELayer's dispatch + combine, forward and backward, with the
    moe config's token / slot counts (top-2, 16 experts, cf 1.25)."""
    from hetu_tpu.ops.pallas.moe_dispatch import (sparse_combine,
                                                  sparse_dispatch)
    s, m, k, n_slots = 8192, 512, 2, 16 * 1280

    def loss(tokens, w, tos, sot, kos):
        buf = sparse_dispatch(tokens, tos, sot)
        return _sum32(sparse_combine(buf, w, sot, tos, kos))
    _compiles_with_kernel(
        jax.grad(loss, argnums=(0, 1)), chip((s, m), dtype),
        chip((s, k), dtype), chip((n_slots,), jnp.int32),
        chip((s, k), jnp.int32), chip((n_slots,), jnp.int32))


# --------------------------------------------------- embedding cache, segsum
@pytest.mark.parametrize("width", [16, 64, 128])
def test_emb_gather_rows_compiles(chip, width):
    """The HET cache's slab gather at WDL's width (16), the emb scale run's
    (64) and a lane-wide table (128), over the slab the cache allocates."""
    from hetu_tpu.ops.pallas import emb_cache
    _compiles_with_kernel(
        emb_cache.gather_rows,
        chip((65536 + 1024 + 1, emb_cache.slab_width(width)), jnp.float32),
        chip((4096,), jnp.int32))


def test_emb_gather_rows_pads_an_unaligned_slab(chip):
    """A caller's slab that is NOT lane-aligned still compiles (padded
    inside the entry point) — never a silent jnp.take."""
    from hetu_tpu.ops.pallas import emb_cache
    _compiles_with_kernel(emb_cache.gather_rows,
                          chip((4096, 64), jnp.float32),
                          chip((512,), jnp.int32))


@pytest.mark.parametrize("width", [16, 64, 128, 256])
def test_sorted_segment_sum_compiles(chip, width):
    """The window DMA was refused below 128 lanes, and above 128 lanes for
    a window starting at an arbitrary row; now one 128-lane panel per
    kernel call."""
    from hetu_tpu.ops.pallas.segment_sum import sorted_segment_sum
    text = _compiles_with_kernel(
        lambda r, s: sorted_segment_sum(r, s, 4096),
        chip((4096, width), jnp.float32), chip((4096,), jnp.int32))
    assert text.count("tpu_custom_call") == -(-width // 128)


@pytest.mark.parametrize("width", [16, 64])
def test_emb_scatter_add_grads_compiles(chip, width):
    from hetu_tpu.ops.pallas import emb_cache
    _compiles_with_kernel(emb_cache.scatter_add_grads,
                          chip((4096, width), jnp.float32),
                          chip((4096,), jnp.int32))
