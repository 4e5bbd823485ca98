"""The Granite-4.0-H cell: its data files, its table, the readers that wait
on made runs, and whole runs of the harness at CPU size — sound, under the
control, and with the timed path broken."""
import json
import os

import pytest

from conftest import ROOT
import granite_root
from test_bench_cells import _driver

from benchmarks import harness, ssd_bytes, traffic

CELL = granite_root.REAL
NEW = tuple(m["name"] for m in granite_root.ENTRIES)    # the readers that wait
JOINED = ("ttft_mean_ms.serve", "ttft_p95_ms.serve",
          "prefill_tokens_per_step.serve", "kv_copy_share_pct.serve",
          "device_idle_pct.serve", "queue_wait_ms.serve",
          "row_token_fill_pct.serve", "kv_rows_read_pct.serve",
          "launch_ahead_pct.serve", "ssm_share_pct.serve",
          "gqa_attn_share_pct.serve")


@pytest.fixture()
def root(tmp_path):
    from hetu_tpu.graph import step_cache
    step_cache.clear()      # a broken run must trace its own programs
    yield granite_root.build(str(tmp_path))
    step_cache.clear()


def _run(root, control=False):
    return harness.run_cell(granite_root.TINY, 3000000019, 1.0, False,
                            files=harness.Files(root), require_tpu=False,
                            out_dir=os.path.join(root, "out"),
                            control=control)


def test_the_cell_is_found_by_the_names_in_its_files():
    files = harness.Files(ROOT)
    cell = files.cell(CELL)
    cfg = files.config(cell["config"])
    assert (cell["chips"], cell["config"], cell["traffic"], cfg["system"],
            cfg["reference"]) == (1, "granite4-h-micro", "chat-c64",
                                  "granite_hybrid_decode",
                                  "granite_hybrid_lm")
    assert files.mix(cell["traffic"])["driver"] == "closed_loop_decode_stem"
    assert set(files.limits(CELL)) == {"logit_gap_max", "logit_gap_sq_mean"}
    ends = {m["name"] for m in files.metrics("end_to_end", CELL)}
    assert ends == {"serve_tokens_per_s", "setup_s"}         # NOT itl_p90_ms
    layers = {m["name"] for m in files.metrics("per_layer", CELL)}
    assert layers == set(JOINED)
    moved = {m["name"]: m["moves"] for m in files.bench["per_layer"]}
    assert {moved[name] for name in layers} == {"serve_tokens_per_s"}
    for name in layers:
        assert callable(files.reader(name))
    entry = next(c for c in files.bench["configs"]
                 if c["name"] == "granite4-h-micro")
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["source"] == cfg["source"]
    assert (cfg["num_hidden_layers"], cfg["vocab_size"]) == (40, 100352)


def test_table_is_chat_c16s_at_four_times_the_clients():
    files = harness.Files(ROOT)
    mix, chat, reason = (files.mix(n) for n in ("chat-c64", "chat-c16",
                                                "reason-c64"))
    assert all(mix[k] == chat[k] for k in ("lengths", "table", "blocks",
                                           "blocks_how", "max_chunk",
                                           "trace_seconds"))
    assert (mix["name"], mix["clients"], mix["max_slots"], mix["max_len"],
            mix["warmup_requests"], mix["check_requests"]) \
        == ("chat-c64", 64, 64, 768, 64, 8)
    assert {k: mix["prime"][k] for k in ("prompt", "output", "lone_output")} \
        == {k: reason["prime"][k] for k in ("prompt", "output",
                                            "lone_output")}
    # the furthest row a step can ask for, a top chunk running beside the
    # longest request, is inside what the engine is reserved at
    longest = max(p + o for p, o in mix["table"])
    assert longest == 736 and longest - 2 + mix["max_chunk"] - 1 == 765 \
        < mix["max_len"]
    s = traffic.Schedule(mix, 100352, 3000000019)
    ids, new = s.request(7)
    assert (len(ids), new) == s.lengths(7) and ids.max() < 100352


def test_the_waiting_readers_are_ready_to_be_listed(root):
    """The cell's own two wait in a sub-directory of ``metrics_waiting/``:
    neither is listed yet, each is a reader as the contract wants one, the
    layers they name are layers the benchmark has, and the root a
    ``benchmark`` PR would make lists them for this cell alone, after every
    entry that was there."""
    real = harness.Files(ROOT).bench["per_layer"]
    assert not {m["name"] for m in real} & set(NEW)
    assert sorted(f[:-3] for f in os.listdir(granite_root.WAITING)
                  if f.endswith(".py")) == sorted(NEW) \
        == ["ssd_update_roofline", "ssd_update_share_pct.serve"]
    for m in granite_root.ENTRIES:
        mod = granite_root.waiting_reader(m["name"])
        assert callable(mod.read) and mod.MOVES == m["moves"] \
            == "serve_tokens_per_s"
        assert m["layer"] in {e["layer"] for e in real}
        assert m["workloads"] == [CELL]
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    roofline = granite_root.ENTRIES[1]
    assert (roofline["name"], roofline["unit"], roofline["source"],
            roofline["better"]) == ("ssd_update_roofline", "%",
                                    "device_trace", "higher")
    files = harness.Files(root)
    assert [m["name"] for m in files.bench["per_layer"]] \
        == [m["name"] for m in real] + list(NEW)
    assert {m["name"] for m in files.metrics("per_layer", granite_root.TINY)} \
        == set(NEW) | set(JOINED)
    for name in NEW:
        assert callable(files.reader(name))


def test_the_tool_lists_the_waiting_readers_for_the_real_cell(tmp_path):
    """``tools/waiting_metrics.py --waiting granite4-h-micro`` runs the real
    cell on a root that lists them: every file of ``benchmarks/``, the
    sub-directory's entries at the end."""
    from tools import waiting_metrics
    files = harness.Files(waiting_metrics.build_root(
        str(tmp_path / "r"), granite_root.SUB))
    real = harness.Files(ROOT)
    assert files.bench["per_layer"] == real.bench["per_layer"] \
        + granite_root.ENTRIES
    assert {m["name"] for m in files.metrics("per_layer", CELL)} \
        == set(NEW) | set(JOINED)
    other = "phi4-mini-flash.reason-c64"
    assert files.metrics("per_layer", other) \
        == real.metrics("per_layer", other)
    for name in NEW:
        assert callable(files.reader(name))
    assert files.config("granite4-h-micro") == real.config("granite4-h-micro")
    assert files.limits(CELL) == real.limits(CELL)


def test_byte_function_counts_the_published_state():
    cfg = harness.Files(ROOT).config("granite4-h-micro")
    # 64 heads x 64 x 128 float32 = 2 MiB a layer and slot, 36 Mamba layers
    assert ssd_bytes.state_bytes(cfg, 1) == 36 * 2 * 2 ** 20 == 75497472
    # the cell's 64 slots, read and written: the forecast's 9.66 GB a step
    assert ssd_bytes.update_bytes(cfg, 64) == 2 * 64 * 75497472 == 9663676416


def _made_run(scope_s=None, slots=64):
    files = harness.Files(ROOT)
    return {"trace": None if scope_s is None else {"op_s": {}},
            "peaks": {"hbm_bytes_per_s": 819e9}, "mix": files.mix("chat-c64"),
            "cfg": files.config("granite4-h-micro"),
            "window": {"counters": {"decode_steps": 2000}, "seconds": 51.0,
                       "slots": slots}}


def test_readers_return_nothing_where_there_is_nothing_to_read(monkeypatch):
    """The parent of this PR has no such scope: None, never 0, and nothing
    raises — without a trace, with a trace that names no such scope, and
    under another configuration."""
    from benchmarks import trace_scopes
    from benchmarks.metrics import moe_experts_roofline
    monkeypatch.setattr(moe_experts_roofline, "traced_steps", lambda run: 400)
    for name in NEW:
        read = granite_root.waiting_reader(name).read
        assert read(_made_run()) is None
        for got in (None, {"ssd.update": 0.0, "busy": 4.0}):
            monkeypatch.setattr(trace_scopes, "of_run",
                                lambda run, scopes, got=got: got)
            assert read(_made_run(0.0)) is None
    monkeypatch.setattr(trace_scopes, "of_run",
                        lambda run, scopes: {"ssd.update": 1.0, "busy": 4.0})
    other = dict(_made_run(1.0), cfg=harness.Files(ROOT).config(
        "phi4-mini-flash"))
    assert granite_root.waiting_reader("ssd_update_roofline").read(other) \
        is None


@pytest.mark.parametrize("pace,want", [(1.0, 100.0), (2.0, 50.0),
                                       (4.0, 25.0)])
def test_update_roofline_cannot_read_over_100(monkeypatch, pace, want):
    """The bytes counted are the states' alone, read once and written once a
    step; whatever updates them moves at least those.  At the HBM's peak
    over exactly those bytes the share reads 100."""
    from benchmarks import trace_scopes
    from benchmarks.metrics import moe_experts_roofline
    monkeypatch.setattr(moe_experts_roofline, "traced_steps", lambda run: 400)
    spent = pace * 400 * 9663676416 / 819e9
    monkeypatch.setattr(trace_scopes, "of_run", lambda run, scopes: {
        "ssd.update": spent, "busy": 2 * spent})
    run = _made_run(spent)
    assert granite_root.waiting_reader("ssd_update_roofline").read(run) \
        == pytest.approx(want)
    monkeypatch.setattr(trace_scopes, "share",
                        lambda run, scopes: 100.0 * spent / (2 * spent))
    assert granite_root.waiting_reader("ssd_update_share_pct.serve").read(
        run) == pytest.approx(50.0)


def test_the_cell_at_test_size_runs_and_is_correct(root):
    """Float32 on one backend: the engine serves exactly the tokens the
    plain reference — the recurrence token by token, no cache — puts
    first."""
    out = _run(root)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["compared"]["logit_gap_max"]["value"] < 1e-4
    assert out["compared"]["logit_gap_sq_mean"]["value"] < 1e-9
    json.dumps(out)


def test_a_window_reports_the_state_by_kind(root):
    """What the gauges' readers find in a tiny window on the CPU: ``kv`` and
    ``recurrent`` state only, every slot cleared at its join; the trace
    readers find no trace."""
    files = harness.Files(root)
    d, _ = _driver(root, granite_root.TINY, seed=5)
    try:
        run = d.window(0.5, None)
    finally:
        d.free()
    run.update(cfg=d.cfg, mix=d.mix, trace=None, peaks=None)
    c = run["window"]["counters"]
    assert run["window"]["state_bytes"].keys() == {"kv", "recurrent"}
    assert run["window"]["slots"] == 4
    assert c["decode_state_clears"] > 0
    assert abs(c["decode_state_clears"] - c["decode_joins"]) <= 1
    # the top chunk and one of each width, and one partly valid
    assert [len(p) for p, _ in d.lone] == [10, 12, 16, 13]
    for name in NEW + ("ssm_share_pct.serve", "gqa_attn_share_pct.serve"):
        assert files.reader(name)(run) is None


# ------------------------------------------------------ the planted faults

def _decay_of_another_head(monkeypatch):
    """The one-token update decays every head at its neighbour's rate."""
    from hetu_tpu.ops import ssd
    real = ssd._one_token
    monkeypatch.setattr(ssd, "_one_token", lambda x, delta, la, *rest: real(
        x, delta, ssd.jnp.roll(la, 1, axis=1), *rest))


def _no_clearing(monkeypatch):
    from hetu_tpu.serving import DecodeEngine
    monkeypatch.setattr(DecodeEngine, "_clear_recurrent",
                        lambda self, slot: None)


def _valid_ignored(monkeypatch):
    """Only a chunk that is partly valid can tell."""
    from hetu_tpu.ops import ssd, ssm
    for mod in (ssd, ssm):        # the state update and the conv window
        monkeypatch.setattr(mod, "_count", lambda ids, valid: ssm.jnp.full(
            (ids.shape[0],), ids.shape[1], ssm.jnp.int32))


def _softmax_scale_of_another_model(monkeypatch):
    """``1/√D`` in place of ``attention_multiplier``."""
    from hetu_tpu.models import granite_hybrid
    real = granite_hybrid.kda.gqa_attention_kv_op

    def unscaled(*inputs, scale=None, **attrs):
        return real(*inputs, **attrs)
    monkeypatch.setattr(granite_hybrid.kda, "gqa_attention_kv_op", unscaled)


BREAKS = {"a_head_decays_as_its_neighbour": _decay_of_another_head,
          "recurrent_state_not_cleared": _no_clearing,
          "valid_ignored_in_a_chunk": _valid_ignored,
          "the_softmax_scale_of_another_model":
              _softmax_scale_of_another_model}


@pytest.mark.parametrize("fault", ["control_precision"] + list(BREAKS))
def test_a_broken_run_is_not_correct(root, monkeypatch, fault):
    """The fp8 control in the program's place, and the timed path broken
    four ways, under the REAL cell's limits: ``correct`` comes out
    false."""
    if fault in BREAKS:
        BREAKS[fault](monkeypatch)
    out = _run(root, control=fault == "control_precision")
    assert out["correct"] is False, out["compared"]
