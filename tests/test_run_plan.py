"""Cached run plans, pipelined feeds and non-blocking stepping (ISSUE 9).

The dispatch-path contract: a steady feed schema resolves its per-step
Python ONCE (plan-cache hits prove it), schema changes transparently
re-plan, sustained churn warns with the offending placeholder's creation
site, traced-lr schedules match the host path, and async (``sync=False``)
stepping is BITWISE equal to synchronous stepping — including a
PS-backed graph where the push boundary forces the sync point.
"""
import os
import sys
import warnings

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.metrics import reset_run_plan_counts, run_plan_counts

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _dense_graph(shape=(8, 8), lr=0.1, optimizer=None):
    x = ht.placeholder_op("x", shape=shape)
    w = ht.init.random_normal(shape=(shape[1], 4), stddev=0.1, name="w")
    loss = ht.reduce_mean_op(ht.ops.matmul_op(x, w), [0, 1])
    opt = optimizer or ht.optim.SGDOptimizer(lr)
    return x, loss, opt.minimize(loss)


def _feed(shape=(8, 8), seed=0):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


# ------------------------------------------------------------ plan cache

def test_plan_cache_hits_on_steady_schema():
    x, loss, train = _dense_graph()
    ex = ht.Executor({"train": [loss, train]}, seed=0)
    xv = _feed()
    reset_run_plan_counts()
    for _ in range(6):
        out = ex.run("train", feed_dict={x: xv})
    c = run_plan_counts()
    assert c.get("plan_cache_miss", 0) == 1, c
    assert c.get("plan_cache_hit", 0) == 5, c
    assert np.isfinite(float(out[0].asnumpy()))


def test_plan_cache_replans_on_schema_change_and_reuses_both():
    # shape-less placeholder: feeding different batch sizes is legal
    x = ht.placeholder_op("x")
    w = ht.init.random_normal(shape=(8, 4), stddev=0.1, name="w")
    loss = ht.reduce_mean_op(ht.ops.matmul_op(x, w), [0, 1])
    ex = ht.Executor({"train": [loss,
                                ht.optim.SGDOptimizer(0.1).minimize(loss)]},
                     seed=0)
    a, b = _feed((4, 8)), _feed((6, 8), seed=1)
    reset_run_plan_counts()
    ex.run("train", feed_dict={x: a})
    ex.run("train", feed_dict={x: b})        # new shape: re-plan
    ex.run("train", feed_dict={x: a})        # both schemas stay cached
    ex.run("train", feed_dict={x: b})
    c = run_plan_counts()
    assert c.get("plan_cache_miss", 0) == 2, c
    assert c.get("plan_cache_hit", 0) == 2, c


def test_plan_results_identical_across_feed_containers():
    """numpy, device-committed and NDArray feeds hit different plan
    kinds but must produce identical math."""
    import jax
    losses = {}
    for kind in ("np", "jax", "ndarray"):
        x, loss, train = _dense_graph()
        ex = ht.Executor({"train": [loss, train]}, seed=0)
        xv = _feed()
        val = {"np": xv, "jax": jax.device_put(xv),
               "ndarray": ht.array(xv)}[kind]
        out = [np.asarray(ex.run("train", feed_dict={x: val})[0].jax())
               for _ in range(3)]
        losses[kind] = [v.tobytes() for v in out]
    assert losses["np"] == losses["jax"] == losses["ndarray"]


def test_feed_schema_churn_warns_with_creation_site():
    """Sustained churn = re-missing schemas the cache already planned
    (eviction cycling): a 2-plan cache fed 4 cycling shapes."""
    os.environ["HETU_RUN_PLAN_CACHE"] = "2"
    try:
        x = ht.placeholder_op("ragged_x")
        w = ht.init.random_normal(shape=(8, 4), stddev=0.1, name="w")
        loss = ht.reduce_mean_op(ht.ops.matmul_op(x, w), [0, 1])
        ex = ht.Executor(
            {"train": [loss,
                       ht.optim.SGDOptimizer(0.1).minimize(loss)]},
            seed=0)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            for i in range(8):                  # 2,3,5,7,2,3,5,7
                bs = (2, 3, 5, 7)[i % 4]
                ex.run("train", feed_dict={x: _feed((bs, 8), seed=i)})
        msgs = [str(r.message) for r in rec
                if "feed-schema-churn" in str(r.message)]
        assert msgs, [str(r.message) for r in rec]
        assert "ragged_x" in msgs[0]
        assert "created at" in msgs[0]          # PR 5 provenance style
        assert "bucket" in msgs[0].lower()      # points at the fix
    finally:
        os.environ.pop("HETU_RUN_PLAN_CACHE", None)


def test_fixed_bucket_set_warmup_does_not_warn_churn():
    """A correctly bucketed workload misses once per bucket while
    warming and then hits forever — that must NOT trip the churn
    warning that recommends exactly this bucketing."""
    x = ht.placeholder_op("bucketed_x")
    w = ht.init.random_normal(shape=(8, 4), stddev=0.1, name="w")
    loss = ht.reduce_mean_op(ht.ops.matmul_op(x, w), [0, 1])
    ex = ht.Executor({"train": [loss,
                                ht.optim.SGDOptimizer(0.1).minimize(loss)]},
                     seed=0)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        for i in range(12):                     # buckets cycle, all hit
            bs = (8, 16, 24, 32)[i % 4]         # after the warm-up pass
            ex.run("train", feed_dict={x: _feed((bs, 8), seed=i)})
    msgs = [str(r.message) for r in rec
            if "feed-schema-churn" in str(r.message)]
    assert not msgs, msgs


# ------------------------------------------------------------- traced lr

def test_traced_lr_matches_host_lr_for_step_schedules():
    """Every pure step-indexed schedule traced inside the step must match
    the host-computed path (HETU_TRACED_LR=0) to f32 accuracy."""
    scheds = [
        lambda: ht.optim.lr_scheduler.StepScheduler(0.5, step_size=2,
                                                    gamma=0.5),
        lambda: ht.optim.lr_scheduler.MultiStepScheduler(0.5, [2, 4], 0.5),
        lambda: ht.optim.lr_scheduler.ExponentialScheduler(0.5, 0.9),
        lambda: ht.optim.lr_scheduler.CosineScheduler(0.5, 2, 8),
        lambda: 0.25,
    ]
    for make in scheds:
        runs = {}
        for env in ("1", "0"):
            os.environ["HETU_TRACED_LR"] = env
            try:
                x, loss, train = _dense_graph(
                    optimizer=ht.optim.SGDOptimizer(make()))
                ex = ht.Executor({"train": [loss, train]}, seed=0)
                xv = _feed()
                runs[env] = [float(ex.run(
                    "train", feed_dict={x: xv})[0].asnumpy())
                    for _ in range(6)]
            finally:
                os.environ.pop("HETU_TRACED_LR", None)
        np.testing.assert_allclose(runs["1"], runs["0"], rtol=2e-6,
                                   err_msg=str(make()))


def test_mutated_constant_lr_rebuilds_and_is_honored():
    """A plain-float lr is baked into the traced step; assigning
    ``opt.lr = x`` mid-training must rebuild the step against the new
    constant (detected per run), not silently keep the stale one."""
    opt = ht.optim.SGDOptimizer(0.5)
    x, loss, train = _dense_graph(optimizer=opt)
    ex = ht.Executor({"train": [loss, train]}, seed=0)
    xv = _feed()
    ex.run("train", feed_dict={x: xv})
    opt.lr = 1e-6      # collapse the lr 500000x
    w_before = {k: np.asarray(v) for k, v in
                ex.return_tensor_values().items()}
    ex.run("train", feed_dict={x: xv})
    w_after = {k: np.asarray(v) for k, v in
               ex.return_tensor_values().items()}
    deltas = [np.abs(w_after[k] - w_before[k]).max() for k in w_before]
    assert max(deltas) < 1e-4, \
        "mutated constant lr was not honored (stale baked value used)"


def test_instance_assigned_on_step_hook_fires():
    """`opt.on_step = fn` (instance attribute, no subclass) must keep
    firing every training step — the pre-plan executor dispatched
    on_step unconditionally."""
    opt = ht.optim.SGDOptimizer(0.1)
    calls = []
    opt.on_step = calls.append
    x, loss, train = _dense_graph(optimizer=opt)
    ex = ht.Executor({"train": [loss, train]}, seed=0)
    xv = _feed()
    for _ in range(3):
        ex.run("train", feed_dict={x: xv})
    assert calls == [1, 2, 3], calls


def test_reassigned_scheduler_lr_rebuilds_and_is_honored():
    """Replacing a traced SCHEDULER (or swapping scheduler→float) mid-
    training must rebuild the step — the old schedule is baked into the
    compiled program."""
    opt = ht.optim.SGDOptimizer(
        ht.optim.lr_scheduler.StepScheduler(0.5, step_size=1000))
    x, loss, train = _dense_graph(optimizer=opt)
    ex = ht.Executor({"train": [loss, train]}, seed=0)
    xv = _feed()
    ex.run("train", feed_dict={x: xv})
    opt.lr = 1e-6      # freeze-like: swap the schedule for a tiny const
    w_before = {k: np.asarray(v) for k, v in
                ex.return_tensor_values().items()}
    ex.run("train", feed_dict={x: xv})
    w_after = {k: np.asarray(v) for k, v in
               ex.return_tensor_values().items()}
    deltas = [np.abs(w_after[k] - w_before[k]).max() for k in w_before]
    assert max(deltas) < 1e-4, \
        "reassigned scheduler lr was not honored (old schedule baked)"


def test_data_dependent_scheduler_stays_live_on_host_path():
    """ReduceOnPlateau mutates its lr from a monitored metric — it must
    stay a per-step host input, so mid-training mutations take effect."""
    sched = ht.optim.lr_scheduler.ReduceOnPlateauScheduler(
        0.5, patience=0, factor=0.01)
    opt = ht.optim.SGDOptimizer(sched)
    x, loss, train = _dense_graph(optimizer=opt)
    ex = ht.Executor({"train": [loss, train]}, seed=0)
    sub = ex.subexecutors["train"]
    assert sub._host_lr_ops, "data-dependent schedule must ride host lrs"
    xv = _feed()
    ex.run("train", feed_dict={x: xv})
    w_before = {k: np.asarray(v) for k, v in
                ex.return_tensor_values().items()}
    # plateau twice -> lr collapses by 100x; the next step must move
    # weights ~100x less than a fresh 0.5-lr step would
    sched.step(1.0)
    sched.step(1.0)
    assert sched.get(0) < 0.5
    ex.run("train", feed_dict={x: xv})
    w_after = {k: np.asarray(v) for k, v in
               ex.return_tensor_values().items()}
    deltas = [np.abs(w_after[k] - w_before[k]).max() for k in w_before]
    assert max(deltas) < 0.05, "mutated (collapsed) lr was not honored"


# --------------------------------------------------- async / sync parity

def _run_losses(ex, x, xv, n, sync):
    if sync:
        return [np.asarray(ex.run("train", feed_dict={x: xv})[0].jax(),
                           np.float32) for _ in range(n)]
    rs = ex.run_steps(lambda i: {x: xv}, n, name="train", sync=False)
    return [np.asarray(r[0].jax(), np.float32) for r in rs]


def test_async_sync_bitwise_parity_dense():
    results = {}
    for sync in (True, False):
        x, loss, train = _dense_graph(
            optimizer=ht.optim.AdamOptimizer(1e-2))
        ex = ht.Executor({"train": [loss, train]}, seed=0)
        losses = _run_losses(ex, x, _feed(), 12, sync)
        finals = {k: np.asarray(v) for k, v in
                  ex.return_tensor_values().items()}
        results[sync] = ([v.tobytes() for v in losses],
                         {k: v.tobytes() for k, v in finals.items()})
    assert results[True][0] == results[False][0], "losses diverged"
    assert results[True][1] == results[False][1], "final state diverged"


@pytest.mark.timeout(300)
def test_async_sync_bitwise_parity_wdl_ps():
    """PS-backed (wdl) graph: the per-step row-grad push is the forced
    sync point on the async path — losses and final weights must still
    be bitwise equal, and the sync points must be counted."""
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "ctr_models_rp", os.path.join(root, "examples", "ctr", "models.py"))
    ctr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ctr)
    B = 32
    dv, sv, yv = ctr.synthetic_criteo(B, vocab=1000)
    results = {}
    for sync in (True, False):
        dense = ht.placeholder_op("dense")
        sparse = ht.placeholder_op("sparse", dtype=np.int64)
        y_ = ht.placeholder_op("y")
        loss, _prob = ctr.wdl_criteo(dense, sparse, y_, B, vocab=1000,
                                     dim=8, embed_mode="ps", lr=0.01)[:2]
        ex = ht.Executor(
            {"train": [loss, ht.optim.SGDOptimizer(0.01).minimize(loss)]},
            seed=0)
        fd = {dense: dv, sparse: sv, y_: yv}
        reset_run_plan_counts()
        if sync:
            losses = [np.asarray(ex.run("train", feed_dict=fd)[0].jax(),
                                 np.float32) for _ in range(10)]
        else:
            rs = [ex.run("train", feed_dict=fd, sync=False)
                  for _ in range(10)]
            losses = [np.asarray(r[0].jax(), np.float32) for r in rs]
            assert run_plan_counts().get("async_sync_points", 0) >= 10, \
                "PS push boundary must be counted as a sync point"
        finals = {k: np.asarray(v) for k, v in
                  ex.return_tensor_values().items()}
        results[sync] = ([v.tobytes() for v in losses],
                         {k: v.tobytes() for k, v in finals.items()})
    assert results[True][0] == results[False][0], "wdl losses diverged"
    assert results[True][1] == results[False][1], "wdl weights diverged"


def test_convert_to_numpy_forces_sync_point():
    x, loss, train = _dense_graph()
    ex = ht.Executor({"train": [loss, train]}, seed=0)
    xv = _feed()
    reset_run_plan_counts()
    out = ex.run("train", feed_dict={x: xv}, sync=False,
                 convert_to_numpy_ret_vals=True)
    assert isinstance(out[0], np.ndarray)
    assert run_plan_counts().get("async_sync_points", 0) >= 1


def test_async_window_bounds_inflight():
    os.environ["HETU_ASYNC_WINDOW"] = "2"
    try:
        x, loss, train = _dense_graph()
        ex = ht.Executor({"train": [loss, train]}, seed=0)
        xv = _feed()
        reset_run_plan_counts()
        for _ in range(8):
            ex.run("train", feed_dict={x: xv}, sync=False)
        assert len(ex._async_pending) <= 2
        assert run_plan_counts().get("async_sync_points", 0) >= 6
        ex._drain_async()
        assert not ex._async_pending
    finally:
        os.environ.pop("HETU_ASYNC_WINDOW", None)


def test_save_drains_async_steps(tmp_path):
    x, loss, train = _dense_graph()
    ex = ht.Executor({"train": [loss, train]}, seed=0)
    xv = _feed()
    for _ in range(3):
        ex.run("train", feed_dict={x: xv}, sync=False)
    assert ex._async_pending
    ex.save(str(tmp_path / "ck"))
    assert not ex._async_pending


# ------------------------------------------------- run_steps + pipeline

def test_run_steps_matches_manual_loop():
    manual = {}
    for mode in ("loop", "steps"):
        x, loss, train = _dense_graph(
            optimizer=ht.optim.AdamOptimizer(1e-2))
        ex = ht.Executor({"train": [loss, train]}, seed=0)
        feeds = [_feed(seed=i) for i in range(8)]
        if mode == "loop":
            losses = [np.asarray(
                ex.run("train", feed_dict={x: feeds[i]})[0].jax(),
                np.float32) for i in range(8)]
        else:
            rs = ex.run_steps(lambda i: {x: feeds[i]}, 8, name="train")
            losses = [np.asarray(r[0].jax(), np.float32) for r in rs]
        manual[mode] = [v.tobytes() for v in losses]
    assert manual["loop"] == manual["steps"]


def test_dataloader_feed_pipeline_bitwise_and_counted():
    """Dataloader-fed graphs double-buffer next-step device_puts; the
    pipelined run must be bitwise-identical to the unpipelined one."""
    def build():
        xv = np.random.RandomState(0).randn(40, 8).astype(np.float32)
        x = ht.dataloader_op([ht.Dataloader(xv, 8, "train")])
        w = ht.init.random_normal(shape=(8, 4), stddev=0.1, name="w")
        loss = ht.reduce_mean_op(ht.ops.matmul_op(x, w), [0, 1])
        ex = ht.Executor(
            {"train": [loss, ht.optim.SGDOptimizer(0.1).minimize(loss)]},
            seed=0)
        return ex

    runs = {}
    for pipeline in ("1", "0"):
        os.environ["HETU_FEED_PIPELINE"] = pipeline
        # force the double-buffer on (the adaptive gate would keep a
        # tiny test batch inline)
        os.environ["HETU_FEED_PIPELINE_MIN_US"] = "0"
        try:
            reset_run_plan_counts()
            ex = build()
            losses = [np.asarray(ex.run("train")[0].jax(), np.float32)
                      for _ in range(10)]
            runs[pipeline] = [v.tobytes() for v in losses]
            if pipeline == "1":
                c = run_plan_counts()
                assert c.get("feeds_pipelined", 0) > 0, c
                assert c.get("feed_pipeline_depth_hw", 0) >= 1, c
        finally:
            os.environ.pop("HETU_FEED_PIPELINE", None)
            os.environ.pop("HETU_FEED_PIPELINE_MIN_US", None)
    assert runs["1"] == runs["0"], "pipelined feeds changed the math"


def test_fast_and_general_dispatch_paths_agree():
    runs = {}
    for fast in ("1", "0"):
        os.environ["HETU_RUN_PLAN_FAST"] = fast
        try:
            x, loss, train = _dense_graph(
                optimizer=ht.optim.AdamOptimizer(1e-2))
            ex = ht.Executor({"train": [loss, train]}, seed=0)
            xv = _feed()
            losses = [np.asarray(
                ex.run("train", feed_dict={x: xv})[0].jax(), np.float32)
                for _ in range(6)]
            runs[fast] = [v.tobytes() for v in losses]
        finally:
            os.environ.pop("HETU_RUN_PLAN_FAST", None)
    assert runs["1"] == runs["0"], \
        "fast-lane dispatch diverged from the general path"


# ----------------------------------------------------- timing + profiler

def test_timing_blocks_on_fetches():
    x, loss, train = _dense_graph()
    ex = ht.Executor({"train": [loss, train]}, seed=0, timing=True)
    xv = _feed()
    for _ in range(3):
        ex.run("train", feed_dict={x: xv})
    assert len(ex.timer_logs["train"]) == 3
    assert all(t > 0 for t in ex.timer_logs["train"])
    # timing under async stepping still records (and still blocks)
    ex.run("train", feed_dict={x: xv}, sync=False)
    assert len(ex.timer_logs["train"]) == 4


def test_run_plan_counters_surfaced_by_profiler():
    x, loss, train = _dense_graph()
    ex = ht.Executor({"train": [loss, train]}, seed=0)
    xv = _feed()
    reset_run_plan_counts()
    for _ in range(3):
        ex.run("train", feed_dict={x: xv})
    prof = ht.HetuProfiler(ex, "train")
    c = prof.run_plan_counters()
    assert c.get("plan_cache_hit", 0) >= 2
    assert c.get("plan_cache_miss", 0) == 1


def test_async_sync_point_is_a_span_the_flow_arrow_ends_in():
    """ISSUE 25: traced, materialising a ``run(sync=False)`` step is an
    ``executor.sync`` span, and each dispatch's ``async_step`` arrow ends
    inside one; untraced, the ring stays empty."""
    from hetu_tpu import obs
    x, loss, train = _dense_graph()
    ex = ht.Executor({"train": [loss, train]}, seed=0)
    xv = _feed()
    obs.enable(False)
    obs.clear_trace()
    for _ in range(3):
        ex.run("train", feed_dict={x: xv}, sync=False)
    ex._drain_async()
    assert obs.TRACER.records() == []
    obs.enable(True)
    try:
        for _ in range(3):
            ex.run("train", feed_dict={x: xv}, sync=False)
        ex._drain_async()
    finally:
        obs.enable(False)
    evs = obs.trace_events()
    obs.clear_trace()
    syncs = [e for e in evs if e.get("ph") == "X"
             and e["name"] == "executor.sync"]
    ends = [e for e in evs if e.get("ph") == "f"
            and e["name"] == "async_step"]
    assert len(syncs) == len(ends) == 3
    assert all(any(s["ts"] <= e["ts"] <= s["ts"] + s["dur"] for s in syncs)
               for e in ends)
