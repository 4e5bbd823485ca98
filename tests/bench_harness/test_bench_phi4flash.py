"""The Phi-4-mini-flash cell: its data files, its table, and whole runs of
the harness at CPU size — sound, under the control, and with the timed
path broken three ways."""
import json
import math
import os
from statistics import NormalDist

import numpy as np
import pytest

from conftest import BENCH, ROOT
import phi4_root

from benchmarks import harness, traffic, weights_by_leaf

CELL = phi4_root.REAL


@pytest.fixture()
def root(tmp_path):
    from hetu_tpu.graph import step_cache
    step_cache.clear()      # a broken run must trace its own programs
    yield phi4_root.build(str(tmp_path))
    step_cache.clear()


@pytest.fixture(scope="module")
def mix():
    return harness.Files(ROOT).mix("reason-c64")


def _run(root, control=False, trace=False):
    return harness.run_cell(phi4_root.TINY, 3000000019, 1.0, trace,
                            files=harness.Files(root), require_tpu=False,
                            out_dir=os.path.join(root, "out"),
                            control=control)


def test_the_cell_is_found_by_the_names_in_its_files():
    files = harness.Files(ROOT)
    cell = files.cell(CELL)
    cfg = files.config(cell["config"])
    assert (cell["chips"], cfg["system"], cfg["reference"]) \
        == (1, "phi4flash_decode", "phi4flash_lm")
    assert files.mix(cell["traffic"])["driver"] == "closed_loop_decode_large"
    assert set(files.limits(CELL)) == {"logit_gap_max", "logit_gap_sq_mean"}
    ends = {m["name"] for m in files.metrics("end_to_end", CELL)}
    assert ends == {"serve_tokens_per_s", "itl_p90_ms", "setup_s"}
    layers = {m["name"] for m in files.metrics("per_layer", CELL)}
    assert {"mixer_share_pct.serve", "ssm_share_pct.serve",
            "cross_attn_share_pct.serve", "state_bytes_per_slot.serve",
            "engine_step_ms.serve", "device_idle_pct.serve"} <= layers
    for name in layers:
        assert callable(files.reader(name))
    # the copies' reader finds the compiler's own moves here too; what
    # reads GPT-2's kernel, or the width of chunks in a window that holds
    # none, has nothing to read in this cell
    assert "kv_copy_share_pct.serve" in layers
    assert not {"decode_attn_share_pct.serve",
                "chunk_width_mean.serve"} & layers


def test_table_is_what_the_mix_file_says_it_is(mix):
    nd, n = NormalDist(), len(mix["table"])
    lengths = mix["lengths"]

    def column(d):
        return [int(min(d["max"], max(d["min"], round(d["median"] * math.exp(
            d["sigma"] * nd.inv_cdf((i + 0.5) / n)))))) for i in range(n)]
    prompts, outputs = column(lengths["prompt"]), column(lengths["output"])
    assert (n, prompts[0], prompts[-1], outputs[0], outputs[-1]) \
        == (64, 32, 768, 256, 3840)
    assert sorted(p for p, _ in mix["table"]) == prompts
    cut = 0
    for i in range(8):
        for j in range(8):
            p, o = mix["table"][8 * i + j]
            assert p == prompts[8 * i + j]
            assert o == min(outputs[8 * j + i], lengths["sum_max"] - p)
            cut += o != outputs[8 * j + i]
    assert cut == 1 and mix["table"][-1] == [768, 3776]
    assert max(p + o for p, o in mix["table"]) == lengths["sum_max"] == 4544
    assert mix["blocks"] == [[8 * i + (i + b) % 8 for i in range(8)]
                             for b in range(8)]
    assert mix["clients"] == mix["max_slots"] == 64
    # the furthest row a step can ask for, a top chunk running beside the
    # longest request, is inside what the engine is reserved at
    assert lengths["sum_max"] - 2 + mix["max_chunk"] - 1 < mix["max_len"]
    s = traffic.Schedule(mix, 200064, 3000000019)
    ids, new = s.request(7)
    assert (len(ids), new) == s.lengths(7) and ids.max() < 200064


def test_weights_come_leaf_by_leaf_and_do_not_depend_on_their_company():
    spec = {"a": ((4, 8), 0.0, 0.02), "b": ((8,), 1.0, 0.02),
            "c": ((3, 5), -4.6, 1.0)}
    import jax.numpy as jnp
    whole = weights_by_leaf.make(spec, 3000000019, jnp.bfloat16)
    alone = weights_by_leaf.make(spec, 3000000019, jnp.bfloat16, only=["c"])
    other = weights_by_leaf.make(spec, 3000000019 - 2 ** 31, jnp.bfloat16)
    assert list(whole) == ["a", "b", "c"] and list(alone) == ["c"]
    assert np.array_equal(whole["c"], alone["c"])
    assert not np.array_equal(whole["a"], other["a"])
    assert whole["a"].dtype == jnp.bfloat16 and whole["a"].shape == (4, 8)
    assert abs(float(whole["b"].astype(np.float32).mean()) - 1) < 0.05


def test_the_cell_at_test_size_runs_and_is_correct(root):
    """Float32 on one backend: the engine serves exactly the tokens the
    plain reference, followed layer by layer, puts first."""
    out = _run(root)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "itl_p90_ms",
                                   "setup_s"}
    assert out["compared"]["logit_gap_max"]["value"] < 1e-4
    assert out["compared"]["logit_gap_sq_mean"]["value"] < 1e-9
    json.dumps(out)


def test_readers_read_what_is_there_and_return_nothing_otherwise():
    """The scope readers find a mixer's device time by the framework name
    the profiler keeps for each operation (checked on the recorded v5e
    trace: its one named fusion lies under ``while/body/closed_call``);
    without a traced run, or under a program that records no such
    counters, every reader returns None and the line leaves it out."""
    from benchmarks import trace_scopes
    files = harness.Files(ROOT)
    fixture = os.path.join(BENCH, "trace_fixture.xplane.pb")
    got = trace_scopes.scope_seconds(fixture, ["closed_call", "mix.ssm"])
    assert 0 < got["closed_call"] <= got["busy"] and got["mix.ssm"] == 0
    names = trace_scopes.framework_names(fixture)["/device:TPU:0"]
    assert any(op.startswith("jit(work)/while/body/closed_call/")
               for op in names.values())
    untraced = {"trace": None, "mix": {"name": "reason-c64"},
                "cfg": {"name": "phi4-mini-flash"},
                "window": {"counters": {}}}
    for name in ("mixer_share_pct.serve", "ssm_share_pct.serve",
                 "cross_attn_share_pct.serve", "state_bytes_per_slot.serve"):
        assert files.reader(name)(untraced) is None
    run = dict(untraced, window={"slots": 64, "state_bytes": {
        "kv": 1509949440, "ring": 1342177280, "recurrent": 224133120}})
    assert files.reader("state_bytes_per_slot.serve")(run) \
        == pytest.approx(48.06656)


def test_window_reports_the_state_gauges_by_kind(root):
    import importlib
    files = harness.Files(root)
    cell = files.cell(phi4_root.TINY)
    cfg, mix = files.config(cell["config"]), files.mix(cell["traffic"])
    drv = importlib.import_module("benchmarks.drivers." + mix["driver"])
    d = drv.Driver(cfg=cfg, mix=mix, seed=5, compiles=harness.CompileLog.get(),
                   system=importlib.import_module(
                       "benchmarks.systems." + cfg["system"]),
                   reference=importlib.import_module(
                       "benchmarks.reference." + cfg["reference"]),
                   log=harness.log)
    d.setup()
    run = d.window(0.5, None)
    d.free()
    assert set(run["window"]["state_bytes"]) == {"kv", "ring", "recurrent"}
    assert run["window"]["slots"] == 4
    assert run["window"]["counters"].get("decode_kv_bytes_hw", 0) == 0
    # the window's sample, then set-up's lone prompts: the top chunk and
    # one of each width, and one whose second chunk is partly valid
    assert [len(p) for p, _ in d.lone] == [10, 12, 16, 13]
    assert all(len(t) == mix["prime"]["lone_output"] for _, t in d.lone)
    assert 0 < len(d.sample) - len(d.lone) <= mix["check_requests"]
    assert all(a is b for (a, _), (b, _) in zip(d.sample[-4:], d.lone))


def _no_lambda(monkeypatch):
    from hetu_tpu.ops import ssm
    monkeypatch.setattr(ssm, "_lambda", lambda *a: 0.0)


def _no_clearing(monkeypatch):
    from hetu_tpu.serving import DecodeEngine
    monkeypatch.setattr(DecodeEngine, "_clear_recurrent",
                        lambda self, slot: None)


def _ring_one_row_off(monkeypatch):
    from hetu_tpu.ops import ssm
    real = ssm._ring_write
    monkeypatch.setattr(ssm, "_ring_write",
                        lambda ring, new, p, count: real(ring, new, p + 1,
                                                         count))


def _valid_ignored(monkeypatch):
    """Only a chunk that is partly valid can tell."""
    from hetu_tpu.ops import ssm
    monkeypatch.setattr(ssm, "_count", lambda ids, valid: ssm.jnp.full(
        (ids.shape[0],), ids.shape[1], ssm.jnp.int32))


@pytest.mark.parametrize("fault", [
    "control_precision", "lambda_term_dropped", "recurrent_state_not_cleared",
    "ring_written_at_the_wrong_row", "valid_ignored_in_a_chunk"])
def test_a_broken_run_is_not_correct(root, monkeypatch, fault):
    """The fp8 control in the program's place, and the timed path broken
    four ways, under the REAL cell's limits: ``correct`` comes out
    false."""
    breaks = {"lambda_term_dropped": _no_lambda,
              "recurrent_state_not_cleared": _no_clearing,
              "ring_written_at_the_wrong_row": _ring_one_row_off,
              "valid_ignored_in_a_chunk": _valid_ignored}
    if fault in breaks:
        breaks[fault](monkeypatch)
    out = _run(root, control=fault == "control_precision")
    assert out["correct"] is False, out["compared"]
