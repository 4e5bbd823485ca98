"""ISSUE 10 acceptance: unified telemetry — span tracing, the metrics
registry (histograms + gauges), Chrome-trace export.
"""
import json
import os
import sys
import threading
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import hetu_tpu as ht            # noqa: E402
from hetu_tpu import metrics, obs      # noqa: E402
from hetu_tpu.obs.registry import Histogram      # noqa: E402
from hetu_tpu.profiler import HetuProfiler       # noqa: E402


@pytest.fixture(autouse=True)
def _clean_tracer():
    """Every test starts and ends with tracing off and an empty ring
    (the tracer and registry are process-wide)."""
    obs.enable(False)
    obs.clear_trace()
    yield
    obs.enable(False)
    obs.clear_trace()
    metrics.enable_step_timing(False)


def _tiny_executor():
    x = ht.placeholder_op("x", shape=(8, 8))
    w = ht.init.zeros(shape=(8, 8), name="w")
    loss = ht.reduce_mean_op(ht.ops.matmul_op(x, w), [0, 1])
    opt = ht.optim.SGDOptimizer(0.1)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0)
    return ex, x, loss


# ------------------------------------------------------------- span tracing

def test_span_nesting_and_thread_tracks():
    """Nested spans nest by timestamp containment; spans from another
    thread land on a separate, named track."""
    obs.enable(True)
    with obs.span("outer", phase="demo"):
        with obs.span("inner"):
            obs.event("tick", n=1)

    def worker():
        obs.set_track_name("bg-worker")
        with obs.span("bg-span"):
            pass
    t = threading.Thread(target=worker)
    t.start()
    t.join()
    obs.enable(False)
    evs = obs.trace_events()
    by_name = {e["name"]: e for e in evs if e.get("ph") in ("X", "i")}
    outer, inner, tick = by_name["outer"], by_name["inner"], by_name["tick"]
    # containment: inner inside outer, tick inside inner
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]
    assert inner["ts"] <= tick["ts"] <= inner["ts"] + inner["dur"]
    assert outer["args"] == {"phase": "demo"}
    # thread separation + named track metadata
    bg = by_name["bg-span"]
    assert bg["tid"] != outer["tid"]
    tracks = {e["args"]["name"] for e in evs
              if e.get("ph") == "M" and e["name"] == "thread_name"}
    assert "bg-worker" in tracks


def test_tracing_off_records_nothing():
    obs.enable(False)
    with obs.span("ghost"):
        obs.event("ghost-event")
    assert [e for e in obs.trace_events()
            if e.get("ph") in ("X", "i")] == []


def test_ring_buffer_wraparound():
    """A ring of N slots keeps the NEWEST N events; the overwritten
    count is reported, and export survives the wrap."""
    obs.enable(True, buf=32)
    try:
        for i in range(100):
            obs.event(f"e{i}")
    finally:
        obs.enable(False)
    evs = [e for e in obs.trace_events() if e.get("ph") == "i"]
    assert len(evs) == 32
    # newest survive, in order
    assert [e["name"] for e in evs] == [f"e{i}" for i in range(68, 100)]
    assert list(obs.TRACER.dropped().values()) == [68]
    obs.enable(False, buf=65536)    # restore default capacity


def test_flow_events_pair():
    obs.enable(True)
    fid = obs.flow_begin("hand-off")
    obs.flow_end("hand-off", fid)
    obs.enable(False)
    flows = [e for e in obs.trace_events() if e.get("ph") in ("s", "f")]
    assert [e["ph"] for e in flows] == ["s", "f"]
    assert flows[0]["id"] == flows[1]["id"] == fid
    assert flows[1]["bp"] == "e"


def test_chrome_trace_json_valid(tmp_path):
    """export_chrome_trace writes loadable Chrome/Perfetto JSON with
    executor step spans from a real (traced) training run."""
    obs.enable(True)
    ex, x, _ = _tiny_executor()
    xv = np.ones((8, 8), np.float32)
    for _ in range(3):
        ex.run("train", feed_dict={x: xv})
    obs.enable(False)
    path = tmp_path / "trace.json"
    n = obs.export_chrome_trace(path)
    blob = json.loads(path.read_text())
    evs = blob["traceEvents"]
    assert blob["displayTimeUnit"] == "ms" and len(evs) == n
    for e in evs:
        assert e["ph"] in ("X", "i", "s", "f", "M")
        assert "name" in e and "pid" in e and "tid" in e
        if e["ph"] == "X":
            assert e["dur"] >= 0 and "ts" in e
        elif e["ph"] in ("s", "f"):
            assert "id" in e
    steps = [e for e in evs if e["name"] == "step"]
    assert len(steps) == 3
    # phase spans nest inside their step span
    for phase in ("run_plan.lookup", "feeds.place", "jit.dispatch"):
        sub = [e for e in evs if e["name"] == phase]
        assert len(sub) == 3, phase
        assert all(any(s["ts"] - 1 <= p["ts"] <= s["ts"] + s["dur"] + 1
                       for s in steps) for p in sub), phase


# --------------------------------------------------------------- histograms

def test_histogram_percentiles_vs_numpy():
    """The log-bucketed estimates track a numpy reference within the
    bucket's relative width (8 buckets/octave => ~9% + interpolation)."""
    rng = np.random.default_rng(7)
    data = rng.lognormal(mean=4.0, sigma=1.5, size=20000)
    h = Histogram("t_us", "test")
    for v in data:
        h.observe(v)
    for q in (50, 90, 99):
        ref = float(np.percentile(data, q))
        est = h.percentile(q)
        assert abs(est - ref) / ref < 0.1, (q, est, ref)
    snap = h.snapshot()[""]
    assert snap["count"] == data.size
    assert snap["min"] == pytest.approx(float(data.min()))
    assert snap["max"] == pytest.approx(float(data.max()))
    assert snap["sum"] == pytest.approx(float(data.sum()), rel=1e-9)


def test_histogram_labels_edges_and_reset():
    h = Histogram("lat", "test")
    h.observe(5.0, label="a")
    h.observe(0.0, label="a")       # non-positive: exact, sorts first
    h.observe(7.0, label="b")
    assert h.percentile(99, label="a") <= 5.0
    assert h.percentile(1, label="a") == 0.0
    assert sorted(h.labels()) == ["a", "b"]
    assert h.percentile(50, label="missing") is None
    h.reset()
    assert h.snapshot() == {}


# ------------------------------------------------------- registry round-trip

def test_metrics_dump_roundtrips_every_counter_family():
    """metrics_dump()'s counter view equals the legacy per-family
    accessors on the same run — one registry, two views."""
    metrics.reset_all()
    metrics.record_flash_fallback("test_reason")
    metrics.record_flash_call(512, 512, one_pass=True)
    metrics.record_flash_call(256, 512, one_pass=True, packed=True)
    metrics.record_flash_head_major("bias")
    metrics.record_decode_attn_call(10, 256)
    metrics.record_kv_append_call(16, 640, "kernel")
    metrics.record_mlm_head_call(80, 512, "gathered")
    metrics.record_moe_call(40, 320, 8)
    metrics.record_sparse_attn_call(96, 64, "kernel")
    metrics.record_ssd_call(1, 64, 64, 128)
    metrics.record_fault("test_fault", 2)
    metrics.record_elastic("elastic_shrink")
    metrics.record_concurrency("concurrency_preemptions")
    metrics.record_remat("remat_layers_rematted", 3)
    metrics.record_autoparallel("autoparallel_plans_searched")
    metrics.record_cache("emb_cache_hit_rows", 5)
    metrics.record_zero("zero_pad_bytes", 64)
    metrics.record_step_cache("step_cache_hit")
    metrics.record_compile({
        "owner": "serve", "trace_us": 10, "lower_us": 20, "backend_us": 300,
        "cache": "miss", "stored": False, "cache_read_us": 0})
    metrics.record_setup("setup.weights", 40, 4096)
    metrics.record_run_plan("plan_cache_hit", 3)
    metrics.record_run_plan("feed_pipeline_depth_hw", 2)
    metrics.record_serve("serve_requests", 4)
    metrics.record_serve("serve_queue_depth_hw", 9)
    metrics.record_decode("decode_tokens", 7)
    metrics.record_decode("decode_kv_bytes_hw", 4096)
    metrics.record_serve_rejection("shed:batch")
    metrics.record_fleet("fleet_admitted", 6)
    metrics.record_fleet("fleet_replicas_hw", 3)
    metrics.record_prefix_cache("prefix_cache_hits", 2)
    metrics.record_prefix_cache("prefix_cache_bytes_hw", 512)
    metrics.record_decode_recovery("decode_recovery_reseated", 2)
    metrics.record_protocol("protocol_states_explored", 1224)
    metrics.record_protocol("protocol_events", 3)
    metrics.record_rpc("OP_PULL", 100.0, 2048)
    dump = obs.metrics_dump()
    legacy = {
        "flash_fallbacks": metrics.flash_fallback_counts(),
        "flash_calls": metrics.flash_call_counts(),
        "flash_head_major": metrics.flash_head_major_counts(),
        "decode_attn_calls": metrics.decode_attn_call_counts(),
        "kv_append_calls": metrics.kv_append_call_counts(),
        "mlm_head_calls": metrics.mlm_head_call_counts(),
        "moe_calls": metrics.moe_call_counts(),
        "sparse_attn_calls": metrics.sparse_attn_call_counts(),
        "ssd_calls": metrics.ssd_call_counts(),
        "emb_pallas_fallbacks": metrics.emb_pallas_fallback_counts(),
        "faults": metrics.fault_counts(),
        "elastic": metrics.elastic_counts(),
        "concurrency": metrics.concurrency_counts(),
        "remat": metrics.remat_counts(),
        "autoparallel": metrics.autoparallel_counts(),
        "cache": metrics.cache_counts(),
        "zero": metrics.zero_counts(),
        "step_cache": metrics.step_cache_counts(),
        "compile": metrics.compile_counts(),
        "setup_us": metrics.setup_counts()["us"],
        "setup_bytes": metrics.setup_counts()["bytes"],
        "run_plan": metrics.run_plan_counts(),
        "serve": metrics.serve_counts(),
        "decode": metrics.decode_counts(),
        "serve_rejection_reason": metrics.serve_rejection_counts(),
        "fleet": metrics.fleet_counts(),
        "prefix_cache": metrics.prefix_cache_counts(),
        "decode_recovery": metrics.decode_recovery_counts(),
        "protocol": metrics.protocol_counts(),
    }
    for fam, want in legacy.items():
        assert dump["counters"][fam] == want, fam
    assert legacy["flash_calls"] == {"512x512:one_pass": 1,
                                     "256x512:one_pass:packed": 1}
    assert legacy["flash_head_major"] == {"bias": 1}
    assert legacy["decode_attn_calls"] == {"10x256": 1}
    assert legacy["kv_append_calls"] == {"16x640:kernel": 1}
    assert legacy["mlm_head_calls"] == {"80of512:gathered": 1}
    assert legacy["moe_calls"] == {"40of320:top8:ragged": 1}
    assert legacy["sparse_attn_calls"] == {"96x64:kernel": 1}
    assert legacy["ssd_calls"] == {"ssd_step_calls:64x64x128": 1}
    assert legacy["faults"] == {"test_fault": 2}
    assert legacy["compile"] == {
        "serve:programs": 1, "serve:trace_us": 10, "serve:lower_us": 20,
        "serve:backend_us": 300, "serve:cache_misses": 1,
        "serve:unstored": 1, "serve:unstored_us": 300}
    assert (legacy["setup_us"], legacy["setup_bytes"]) == (
        {"setup.weights": 40}, {"setup.weights": 4096})
    assert legacy["serve"]["serve_queue_depth_hw"] == 9
    assert legacy["decode"] == {"decode_tokens": 7,
                                "decode_kv_bytes_hw": 4096}
    assert legacy["serve_rejection_reason"] == {"shed:batch": 1}
    assert legacy["fleet"] == {"fleet_admitted": 6, "fleet_replicas_hw": 3}
    assert legacy["prefix_cache"] == {"prefix_cache_hits": 2,
                                      "prefix_cache_bytes_hw": 512}
    assert legacy["protocol"] == {"protocol_states_explored": 1224,
                                  "protocol_events": 3}
    assert dump["counters"]["ps_rpc_bytes"] == {"OP_PULL": 2048}
    assert dump["histograms"]["ps_rpc_us"]["OP_PULL"]["count"] == 1
    # the one-call profiler view is the same registry
    assert HetuProfiler.all_counters() == {
        **legacy, "ps_rpc_bytes": {"OP_PULL": 2048}}
    # reset_all replaces the seven copy-pasted reset bodies
    metrics.reset_all()
    assert HetuProfiler.all_counters() == {
        k: {} for k in HetuProfiler.all_counters()}
    assert obs.metrics_dump()["histograms"]["ps_rpc_us"] == {}


def test_prometheus_text_exposition():
    metrics.reset_all()
    metrics.record_fault("probe")
    metrics.record_serve_latency("queue_wait", 120.0)
    metrics.record_run_gauges("probe_run", 3.25)
    text = obs.prometheus_text()
    assert 'hetu_faults_total{kind="probe"} 1' in text
    assert "# TYPE hetu_serve_latency_us summary" in text
    assert 'hetu_serve_latency_us{label="queue_wait",quantile="0.5"}' \
        in text
    assert 'hetu_step_time_ms{label="probe_run"} 3.25' in text
    metrics.reset_all()


def test_metricsd_files_and_http(tmp_path):
    """tools/metricsd.py: file export + the tiny HTTP endpoint serve
    the same registry."""
    from tools.metricsd import start_http, write_json, write_prom
    metrics.reset_all()
    metrics.record_fault("served_fault")
    jp, pp = tmp_path / "m.json", tmp_path / "m.prom"
    write_json(jp)
    write_prom(pp)
    assert json.loads(jp.read_text())["counters"]["faults"] == \
        {"served_fault": 1}
    assert 'hetu_faults_total{kind="served_fault"} 1' in pp.read_text()
    srv, port = start_http(0)
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert b'hetu_faults_total{kind="served_fault"} 1' in r.read()
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics.json", timeout=10) as r:
            assert json.load(r)["counters"]["faults"] == \
                {"served_fault": 1}
    finally:
        srv.shutdown()
    metrics.reset_all()


# ------------------------------------------------- step time + graph FLOPs

def test_step_time_histogram_and_graph_flops_bert_tiny():
    """``metrics_dump()`` exposes step-time p50/p99 for a bert-tiny run,
    and the inferred-shape FLOP count a measured plan's MFU is priced
    with (``autoparallel.graph_flops``) agrees with the hand formula
    6N + 12·L·h·s."""
    from hetu_tpu.autoparallel import graph_flops
    from tools.audit_graphs import build_bert_graph
    cfg, ex, fd = build_bert_graph(batch_size=2, seq_len=64, size="tiny")
    metrics.reset_step_times()
    metrics.enable_step_timing(True)
    for _ in range(2):
        out = ex.run("train", feed_dict=fd)
    np.asarray(out[0].jax())
    metrics.enable_step_timing(False)

    n_params = int(sum(np.prod(v.shape) for n, v in ex.var_values.items()
                       if n.trainable))
    embed_params = (cfg.vocab_size + cfg.max_position_embeddings
                    + cfg.type_vocab_size) * cfg.hidden_size
    tokens = 2 * 64
    hand = (6 * (n_params - embed_params)
            + 12 * cfg.num_hidden_layers * cfg.hidden_size * 64) * tokens
    flops = graph_flops(list(ex.eval_node_dict["train"]), feeds=fd)
    assert flops > 0
    # 6N counts bias/layernorm params as matmul work, the inferred-shape
    # model prices the actual contractions — close, not identical
    assert abs(flops - hand) / hand < 0.2, (flops, hand)

    st = obs.metrics_dump()["histograms"]["step_time_us"]["train"]
    assert st["count"] == 2
    assert 0 < st["p50"] <= st["p99"]


# ------------------------------------------------------- the chaos trace

def test_trace_scenario():
    """``scenarios.trace_scenario``: step spans, per-opcode RPC spans,
    the failover promotion INSIDE the affected step's span,
    feed-pipeline + serve-router tracks, loss parity vs the untraced
    run, one step-time observation a step."""
    import scenarios
    res = scenarios.trace_scenario(steps=5)
    assert res["step_spans"] >= 5 and res["rpc_spans"] > 0
    assert res["failover_promotions"] >= 1
    assert res["promotion_inside_step_span"] and res["loss_parity"]
    assert res["feed_pipeline_track"] and res["serve_router_track"]
    assert res["serve_device_calls"] >= 1
    assert res["clean_run_counters_empty"]
    assert res["step_time_observations"] == 5
    assert res["ok"] is True, res


def test_committed_trace_artifact_schema():
    """artifacts/trace_step.json (``scenarios.trace_scenario(export_to=
    ...)`` wrote it) loads as
    valid Chrome trace JSON and carries the acceptance content: step
    spans, a PS-RPC track with the failover events, and the serving +
    feed-pipeline thread tracks."""
    path = os.path.join(ROOT, "artifacts", "trace_step.json")
    with open(path) as f:
        blob = json.load(f)
    evs = blob["traceEvents"]
    assert isinstance(evs, list) and evs
    for e in evs:
        assert e["ph"] in ("X", "i", "s", "f", "M")
        assert "name" in e and "tid" in e
        if e["ph"] != "M":
            assert "ts" in e
    names = [e["name"] for e in evs]
    steps = [e for e in evs if e["name"] == "step" and e["ph"] == "X"]
    assert len(steps) >= 5
    assert any(n.startswith("rpc:") for n in names)
    promos = [e for e in evs
              if e["name"] == "fault:ps_failover_promoted"]
    assert promos and any(
        s["ts"] <= p["ts"] <= s["ts"] + s["dur"]
        for p in promos for s in steps)
    tracks = {e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert any("hetu-serve-router" in t for t in tracks), tracks
    assert any("run-steps-feed" in t or "feed-pipeline" in t
               for t in tracks), tracks
    assert any("ps-serve" in t for t in tracks), tracks
