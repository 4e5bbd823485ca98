"""The serving cell's programs, compiled for a DESCRIBED TPU v5e without
the chip: the one-token step and every chunk size of the engine's ladder at
the cell's own shape — batch 16, cache 768, GPT-2 medium's widths — so a
later PR that breaks these shapes fails here and not on chip time.  Depth is
cut to two layers (every layer has the same shapes; whether 24 fit the
chip's memory is what the chip run shows).  Nothing executes: a compile that
passes is not a chip run.

A rehearsal has to reach under the front door for the step function and its
feed keys (``_handles`` below, the one place).  Where a later PR renames
what it reads, these tests SKIP and say so, and do not stand in that PR's
way; the chip run still judges its shapes.

The topology is described inside a module-scoped fixture, never at import
(only one process may load the TPU library, and every xdist worker imports
every test file).
"""
import json
import os

import numpy as np
import pytest

import jax
from jax.sharding import SingleDeviceSharding

from conftest import BENCH

CELL = "gpt2-medium.chat-c16"


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one = SingleDeviceSharding(desc.devices[0])
    yield lambda dims, dtype: jax.ShapeDtypeStruct(dims, dtype, sharding=one)
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _handles(engine):
    """What a compile without the chip needs of the engine, or skip."""
    try:
        return {"one": (engine.iex, engine._fk), "chunked":
                (engine.ciex, engine._cfk), "cache_names": engine.cache_names,
                "heads": (engine._heads, engine._head_dim),
                "fn": (engine.iex._infer_fn, engine.ciex._infer_fn)}
    except AttributeError as e:
        pytest.skip(f"the engine no longer has what the rehearsal reads "
                    f"({e}): port the rehearsal in a benchmark PR")


@pytest.fixture(scope="module")
def cell():
    """The cell's system as the harness builds it, two layers deep, and
    the shape set-up brings it to: every client seated, the cache bucket
    of the table's longest request."""
    from hetu_tpu.serving.executor import default_buckets
    from benchmarks import weights
    from benchmarks.reference import gpt2_lm
    from benchmarks.systems import gpt2_decode
    with open(os.path.join(BENCH, "configs", "gpt2-medium.json")) as f:
        cfg = dict(json.load(f), n_layer=2)
    with open(os.path.join(BENCH, "traffic", "chat-c16.json")) as f:
        mix = json.load(f)
    system = gpt2_decode.System(
        cfg, mix, weights.make(gpt2_lm.param_spec(cfg), 0))
    last = max(p + o for p, o in mix["table"]) - 2
    shape = {"bb": next(b for b in default_buckets(mix["max_slots"])
                        if b >= mix["clients"]),
             "lb": next(b for b in default_buckets(mix["max_len"])
                        if b > last)}
    yield system, shape, mix
    system.close()


def _lower(iex, keys, feeds, chip, monkeypatch):
    """Compile ``iex``'s serving step for the described chip; the attention
    dispatch asks ``jax.default_backend()`` and has to hear 'tpu'."""
    params = {k: chip(v.shape, v.dtype) for k, v in iex.params.items()}
    shapes = {keys[name]: chip(dims, dtype)
              for name, (dims, dtype) in feeds.items()}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(iex._infer_fn(), donate_argnums=(1,)).lower(
        params, shapes).compile()
    return compiled.as_text()


def _cache_feeds(h, shape):
    dims = (shape["bb"], h["heads"][0], shape["lb"], h["heads"][1])
    return {name: (dims, np.float32) for name in h["cache_names"]}


def test_the_cell_stands_at_batch_16_cache_768(cell):
    _, shape, mix = cell
    assert shape == {"bb": 16, "lb": 768} and mix["max_chunk"] == 32


def test_one_token_step_compiles_at_the_cells_shape(cell, chip, monkeypatch):
    system, shape, _ = cell
    h = _handles(system.engine)
    feeds = dict(_cache_feeds(h, shape),
                 input_ids=((shape["bb"], 1), np.int32),
                 positions=((shape["bb"],), np.int32))
    text = _lower(*h["one"], feeds, chip, monkeypatch)
    assert "tpu_custom_call" in text        # the one-token Pallas kernel


@pytest.mark.parametrize("chunk", [2, 4, 8, 16, 32])
def test_chunked_step_compiles_at_the_cells_shape(cell, chip, monkeypatch,
                                                  chunk):
    system, shape, _ = cell
    h = _handles(system.engine)
    feeds = dict(_cache_feeds(h, shape),
                 input_ids=((shape["bb"], chunk), np.int32),
                 positions=((shape["bb"],), np.int32),
                 valid=((shape["bb"],), np.int32))
    _lower(*h["chunked"], feeds, chip, monkeypatch)
