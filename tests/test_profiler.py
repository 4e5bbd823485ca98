"""Profiler tests (reference: tests/test_profiler.py — per-op replay + comm).

Runs on the 8-virtual-CPU-device mesh from conftest.
"""
import numpy as np

import hetu_tpu as ht


def _mlp_executor():
    x = ht.placeholder_op("x")
    y = ht.placeholder_op("y")
    w1 = ht.init.xavier_uniform((32, 64), name="w1")
    w2 = ht.init.xavier_uniform((64, 10), name="w2")
    h = ht.relu_op(ht.matmul_op(x, w1))
    logits = ht.matmul_op(h, w2)
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_sparse_op(logits, y), [0])
    opt = ht.optim.SGDOptimizer(0.1)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0)
    feeds = {x: np.random.randn(16, 32).astype(np.float32),
             y: np.random.randint(0, 10, (16,)).astype(np.int32)}
    return ex, feeds


def test_profile_ops_returns_per_op_times():
    ex, feeds = _mlp_executor()
    prof = ht.HetuProfiler(ex, "train", repeats=2, warmup=1)
    per_op = prof.profile_ops(feeds)
    assert per_op, "no ops profiled"
    assert any("MatrixMult" in k for k in per_op)
    assert all(v >= 0 for v in per_op.values())


def test_profile_step_and_hlo_cost():
    ex, feeds = _mlp_executor()
    prof = ht.HetuProfiler(ex, "train", repeats=2, warmup=1)
    ms = prof.profile_step(feeds)
    assert ms > 0
    cost = prof.hlo_cost(feeds)
    # XLA's cpu/tpu cost analysis reports flops for the matmuls
    assert cost.get("flops", 0) > 0


def test_collective_profiler_bandwidth_table():
    prof = ht.CollectiveProfiler(repeats=2)
    table = prof.bandwidth_table(sizes=(1 << 12,))
    assert set(table) == {"allreduce", "sendrecv", "alltoall"}
    for entry in table.values():
        for dt, gbps in entry.values():
            assert dt >= 0 and gbps >= 0


def test_profiler_handles_ps_embedding_graph():
    """_pack must pull PS rows like sub.run (regression: KeyError)."""
    rng = np.random.RandomState(0)
    vocab, dim, batch = 20, 8, 8
    store = ht.EmbeddingStore()
    table = store.init_table(vocab, dim, opt="sgd", lr=0.1, seed=0)
    store.set_data(table, rng.randn(vocab, dim).astype(np.float32))
    ids = ht.placeholder_op("ids")
    y_ = ht.placeholder_op("y")
    rows = ht.ps_embedding_lookup_op((store, table), ids, width=dim)
    w = ht.Variable("w", value=rng.randn(dim, 4).astype(np.float32),
                    trainable=True)
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(rows, w), y_), [0])
    ex = ht.Executor({"train": [loss,
                                ht.optim.SGDOptimizer(0.1).minimize(loss)]},
                     seed=0)
    feeds = {ids: rng.randint(0, vocab, batch),
             y_: np.eye(4, dtype=np.float32)[rng.randint(0, 4, batch)]}
    prof = ht.HetuProfiler(ex, "train", repeats=1, warmup=0)
    per_op = prof.profile_ops(feeds)
    assert per_op
    assert prof.hlo_cost(feeds).get("flops", 0) > 0


def test_memory_stats_shape():
    ex, feeds = _mlp_executor()
    prof = ht.HetuProfiler(ex, "train")
    stats = prof.memory_stats()  # may be empty on some backends
    assert isinstance(stats, dict)


def test_trace_writes_profile(tmp_path):
    """jax.profiler trace capture around real executor steps."""
    import os
    x = ht.placeholder_op("x", shape=(8, 4))
    w = ht.Variable("w", value=np.ones((4, 4), np.float32))
    loss = ht.ops.reduce_mean_op(ht.ops.matmul_op(x, w), [0, 1])
    ex = ht.Executor({"train": [loss]}, seed=0)
    prof = ht.HetuProfiler(ex, "train")
    rng = np.random.RandomState(0)
    out_dir = prof.trace({x: rng.randn(8, 4).astype(np.float32)},
                         tmp_path / "trace")
    found = [f for _, _, fs in os.walk(out_dir) for f in fs]
    assert found, "trace produced no files"


def test_trace_holds_the_executors_spans_on_the_profilers_clock(tmp_path):
    """ISSUE 25: ``HetuProfiler.trace()`` switches span tracing on for its
    capture (and puts it back), so the ``.xplane.pb`` it writes holds the
    executor's step phases beside whatever the device did — read back with
    the benchmark's own loader."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import trace_reduce
    from hetu_tpu import obs
    ex, feeds = _mlp_executor()
    prof = ht.HetuProfiler(ex, "train")
    assert not obs.enabled()
    obs.clear_trace()
    out_dir = prof.trace(feeds, tmp_path / "trace", steps=3)
    assert not obs.enabled()            # put back as it was
    planes = trace_reduce.load(trace_reduce.find_xplane(out_dir))
    host = [e for lines in planes.values() for evs in lines.values()
            for e in evs]
    steps = sorted((e for e in host if e[0] == "step"), key=lambda e: e[1])
    assert len(steps) == 3
    for name in ("run_plan.lookup", "feeds.place", "jit.dispatch"):
        kids = [e for e in host if e[0] == name]
        assert len(kids) == 3, name
        assert all(any(s[1] <= k[1] and k[1] + k[2] <= s[1] + s[2]
                       for s in steps) for k in kids), name
    assert sum(e[0] == "hetu_step" for e in host) == 3
    # the ring recorded the same steps; outside a capture with tracing on,
    # no annotation object is built (obs.trace.annotate is the one gate)
    ring = [e for e in obs.trace_events() if e.get("name") == "step"]
    obs.clear_trace()
    assert len(ring) == 3
    from hetu_tpu.obs.trace import annotate
    assert annotate("x") is None
