"""Online inference serving (ISSUE 7): compile-once InferenceExecutor,
adaptive micro-batching router, read-mostly embedding serving with
client-transparent failover.

Coverage map (the ISSUE's test satellite):
- batcher packs/pads/scatters correctly at ragged arrival patterns,
  including a single straggler shipping alone at the deadline
- compile-once: one executable per bucket across 100 requests, proven by
  serve + step-cache counters, and cross-rebuild executable reuse
- backpressure: queue-full submissions are EXPLICITLY rejected; close()
  rejects whatever is still queued
- train-only-op-in-serving lint rule: optimizer/gradient fetches are
  rejected at construction with creation-site provenance; dropout warns
  but serves
- failover mid-load: a replicated shard primary killed between waves is
  absorbed inside the batch's pull — responses bitwise equal to the
  unperturbed run, zero restarts
- the serve scenario (``scenarios.serve_scenario``)
"""
import socket as _socket
import time
import warnings

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import chaos as chaos_mod
from hetu_tpu import metrics as hmetrics
from hetu_tpu.graph import step_cache
from hetu_tpu.ps import EmbeddingStore
from hetu_tpu.ps.dist_store import DistCacheTable, DistributedStore
from hetu_tpu.serving import (InferenceExecutor, ServeRejected,
                              ServingRouter, default_buckets)

W0 = (np.arange(12, dtype=np.float32).reshape(3, 4) * 0.1) - 0.5


def _dense_graph():
    """y = x @ w — the minimal servable graph (w seeded by value)."""
    x = ht.placeholder_op("x")
    w = ht.Variable("w", value=W0.copy())
    return x, ht.matmul_op(x, w)


def _expect(xv):
    return np.asarray(xv, np.float32) @ W0


@pytest.fixture(autouse=True)
def _reset_serve_counters():
    hmetrics.reset_serve_counts()
    yield
    hmetrics.reset_serve_counts()


# ------------------------------------------------------------ batcher core

def test_batcher_packs_pads_and_scatters_ragged_arrivals():
    x, y = _dense_graph()
    iex = InferenceExecutor([y], buckets=(2, 4, 8))
    with ServingRouter(iex, max_batch=4, max_wait_ms=30.0) as r:
        futs = [r.submit({x: np.full((3,), i, np.float32)})
                for i in range(11)]
        res = [f.result(timeout=30) for f in futs]
    for i, row in enumerate(res):
        assert row[0].shape == (4,)
        np.testing.assert_allclose(row[0], _expect(np.full((3,), i)),
                                   rtol=1e-6)
    c = hmetrics.serve_counts()
    assert c["serve_requests"] == 11
    assert c["serve_responses"] == 11
    # 11 requests at max_batch=4 → at least ceil(11/4)=3 batches, and the
    # trailing partial batch(es) were padded up to a legal bucket
    assert c["serve_batches"] >= 3
    assert c["serve_batch_rows"] >= 11
    assert c["serve_batch_rows"] - 11 == c["serve_pad_rows"] > 0
    assert c["serve_queue_depth_hw"] >= 1


def test_single_straggler_ships_at_deadline():
    x, y = _dense_graph()
    iex = InferenceExecutor([y], buckets=(8,))
    with ServingRouter(iex, max_batch=8, max_wait_ms=40.0) as r:
        t0 = time.monotonic()
        fut = r.submit({x: np.ones((3,), np.float32)})
        row = fut.result(timeout=30)
        dt = time.monotonic() - t0
    np.testing.assert_allclose(row[0], _expect(np.ones((3,))), rtol=1e-6)
    # shipped alone: waited out the deadline window, padded 1 → 8
    assert dt >= 0.030, f"straggler shipped before its deadline ({dt}s)"
    c = hmetrics.serve_counts()
    assert c["serve_batches"] == 1
    assert c["serve_pad_rows"] == 7


def test_straggler_deadline_anchors_at_arrival_not_observation():
    """The max_wait_ms clock starts when the request ARRIVES, not when
    the batcher gets back around to the queue: a request that already
    waited out its window during a slow previous batch (failover pull,
    cold compile) ships immediately instead of waiting a second one."""
    x, y = _dense_graph()
    iex = InferenceExecutor([y], buckets=(4,))
    iex.warm({x: np.zeros((1, 3), np.float32)})   # compile outside timing
    r = ServingRouter(iex, max_batch=4, max_wait_ms=2000.0, start=False)
    try:
        fut = r.submit({x: np.ones((3,), np.float32)})
        time.sleep(2.2)                 # paused router = the slow batch
        t0 = time.monotonic()
        r.start()
        row = fut.result(timeout=30)
        dt = time.monotonic() - t0
    finally:
        r.close()
    np.testing.assert_allclose(row[0], _expect(np.ones((3,))), rtol=1e-6)
    assert dt < 1.5, (
        f"request older than max_wait_ms waited another {dt:.2f}s — the "
        f"deadline re-anchored at observation instead of arrival")


def test_full_batch_ships_without_waiting_out_deadline():
    x, y = _dense_graph()
    iex = InferenceExecutor([y], buckets=(4,))
    iex.warm({x: np.zeros((1, 3), np.float32)})   # compile outside timing
    with ServingRouter(iex, max_batch=4, max_wait_ms=5000.0) as r:
        t0 = time.monotonic()
        futs = [r.submit({x: np.zeros((3,), np.float32)})
                for _ in range(4)]
        for f in futs:
            f.result(timeout=30)
        dt = time.monotonic() - t0
    assert dt < 2.0, "a full batch must ship immediately, not at deadline"


def test_batch_aggregating_fetch_fails_loudly_under_padding():
    """A fetch that reduces over the batch dim (no per-row leading dim)
    would silently include the zero-padding rows — infer() must refuse
    to serve it for a padded batch instead of handing every request a
    padding-polluted value.  At an exact bucket fit it serves fine."""
    x, y = _dense_graph()
    mean = ht.reduce_mean_op(y, [0])
    iex = InferenceExecutor([y, mean], buckets=(4, 8))
    exact = np.arange(12, dtype=np.float32).reshape(4, 3)
    rows, m = iex.infer({x: exact})
    np.testing.assert_allclose(m, _expect(exact).mean(0), rtol=1e-5)
    np.testing.assert_allclose(rows, _expect(exact), rtol=1e-6)
    with pytest.raises(ValueError, match="zero-padding"):
        iex.infer({x: exact[:3]})       # 3 → bucket 4: padded, refused
    # through the router at an exact fit, every request receives the
    # WHOLE aggregate (shared value), each its own per-row slice of y
    with ServingRouter(iex, max_batch=4, max_wait_ms=2000.0) as r:
        futs = [r.submit({x: exact[i]}) for i in range(4)]
        res = [f.result(timeout=30) for f in futs]
    for i, (row, agg) in enumerate(res):
        np.testing.assert_allclose(row, _expect(exact)[i], rtol=1e-6)
        np.testing.assert_allclose(agg, _expect(exact).mean(0), rtol=1e-5)
    x, y = _dense_graph()
    iex = InferenceExecutor([y], buckets=(2,))
    with pytest.raises(ValueError, match="exceeds the largest"):
        iex.infer({x: np.zeros((5, 3), np.float32)})


def test_malformed_request_fails_only_itself():
    """Schema grouping: a request with a wrong shape (or alien feed key)
    co-arriving with valid ones must fail alone — the valid requests in
    the same take still get answers."""
    x, y = _dense_graph()
    iex = InferenceExecutor([y], buckets=(2, 4, 8))
    r = ServingRouter(iex, max_batch=8, max_wait_ms=20.0, start=False)
    try:
        good = [r.submit({x: np.full((3,), i, np.float32)})
                for i in range(3)]
        bad_shape = r.submit({x: np.zeros((5,), np.float32)})
        alien = ht.placeholder_op("alien")
        bad_key = r.submit({alien: np.zeros((3,), np.float32)})
        r.start()
        for i, f in enumerate(good):
            np.testing.assert_allclose(
                f.result(timeout=30)[0], _expect(np.full((3,), i)),
                rtol=1e-6)
        with pytest.raises(Exception):
            bad_shape.result(timeout=30)
        with pytest.raises(Exception):
            bad_key.result(timeout=30)
    finally:
        r.close()


def test_scatter_hands_each_request_its_own_k_rows():
    """A graph that flattens a per-sample dim into the batch dim
    (reshape(-1, d) of (batch, k, d)) returns k rows per request; the
    router must scatter i's OWN k rows, never a neighbour's."""
    ids = ht.placeholder_op("ids_k")             # (batch, 2, 2) per stack
    w = ht.Variable("w_k", value=np.eye(2, dtype=np.float32))
    flat = ht.array_reshape_op(ids, (-1, 2))     # (2*batch, 2): k = 2
    out = ht.matmul_op(flat, w)
    iex = InferenceExecutor([out], buckets=(4,))
    r = ServingRouter(iex, max_batch=4, max_wait_ms=20.0)
    try:
        futs = [r.submit({ids: np.full((2, 2), i, np.float32)})
                for i in range(4)]
        for i, f in enumerate(futs):
            got = f.result(timeout=30)[0]
            assert got.shape == (2, 2)
            np.testing.assert_allclose(got, np.full((2, 2), i), rtol=1e-6)
    finally:
        r.close()


# ---------------------------------------------------------- compile-once

def test_compile_once_per_bucket_across_100_requests():
    step_cache.clear()
    hmetrics.reset_step_cache_counts()
    x, y = _dense_graph()
    iex = InferenceExecutor([y], buckets=(2, 4, 8))
    rng = np.random.RandomState(0)
    with ServingRouter(iex, max_batch=8, max_wait_ms=10.0) as r:
        futs = [r.submit({x: rng.rand(3).astype(np.float32)})
                for _ in range(100)]
        for f in futs:
            f.result(timeout=60)
    c = hmetrics.serve_counts()
    sc = hmetrics.step_cache_counts()
    used = len(iex._compiled)
    assert c["serve_batches"] >= 100 // 8
    # THE compile-once claim: executable builds == distinct buckets used,
    # across 100 requests — and the process-wide serve cache agrees
    assert c["serve_bucket_compiles"] == used <= 3
    assert sc.get("step_cache_serve_miss", 0) == used
    assert sc.get("step_cache_serve_uncachable", 0) == 0


def test_rebuilt_executor_reuses_compiled_executables():
    step_cache.clear()
    hmetrics.reset_step_cache_counts()
    x, y = _dense_graph()
    iex1 = InferenceExecutor([y], buckets=(4,))
    out1 = iex1.infer({x: np.ones((4, 3), np.float32)})
    # a STRUCTURALLY IDENTICAL rebuild (fresh nodes, same graph): the
    # serve cache must hand back the same jitted step, no retrace
    x2, y2 = _dense_graph()
    iex2 = InferenceExecutor([y2], buckets=(4,))
    out2 = iex2.infer({x2: np.ones((4, 3), np.float32)})
    np.testing.assert_array_equal(out1[0], out2[0])
    sc = hmetrics.step_cache_counts()
    assert sc.get("step_cache_serve_miss", 0) == 1
    assert sc.get("step_cache_serve_hit", 0) == 1
    assert iex2._compiled[4] is iex1._compiled[4]
    # the compile-once counter counts BUILDS: the rebuild's cache hit
    # built nothing, so one bucket served by two executors reads 1
    assert hmetrics.serve_counts()["serve_bucket_compiles"] == 1


def test_default_buckets_are_flash_legal():
    assert default_buckets(128) == (1, 2, 4, 8, 16, 32, 64, 128)
    bs = default_buckets(512)
    assert 256 in bs and 384 in bs and bs[-1] == 512
    assert all(b % 128 == 0 for b in bs if b > 64)


# ---------------------------------------------------------- backpressure

def test_queue_full_is_explicit_rejection_not_growth():
    x, y = _dense_graph()
    iex = InferenceExecutor([y], buckets=(4,))
    r = ServingRouter(iex, max_batch=4, max_wait_ms=5.0, queue_limit=3,
                      start=False)      # paused: nothing drains the queue
    try:
        futs = [r.submit({x: np.zeros((3,), np.float32)})
                for _ in range(3)]
        with pytest.raises(ServeRejected) as ei:
            r.submit({x: np.zeros((3,), np.float32)})
        assert ei.value.reason == "queue_full"      # structured vocabulary
        assert hmetrics.serve_counts()["serve_rejections"] == 1
        assert hmetrics.serve_rejection_counts()["queue_full"] == 1
        assert r.queue_depth == 3
        r.start()                       # backpressure over: drain
        for f in futs:
            f.result(timeout=30)
    finally:
        r.close()
    with pytest.raises(ServeRejected) as ei:
        r.submit({x: np.zeros((3,), np.float32)})
    assert ei.value.reason == "draining"


def test_close_rejects_still_queued_requests():
    x, y = _dense_graph()
    iex = InferenceExecutor([y], buckets=(4,))
    r = ServingRouter(iex, queue_limit=8, start=False)
    fut = r.submit({x: np.zeros((3,), np.float32)})
    r.close()
    with pytest.raises(ServeRejected) as ei:
        fut.result(timeout=5)
    assert ei.value.reason == "draining"


def test_close_survives_cancelled_queued_request():
    """close() rejects the still-queued requests even when one of them
    was already cancelled by its caller — the cancelled future must not
    raise InvalidStateError and abort the rejection of the others."""
    x, y = _dense_graph()
    iex = InferenceExecutor([y], buckets=(4,))
    r = ServingRouter(iex, queue_limit=8, start=False)
    doomed = r.submit({x: np.zeros((3,), np.float32)})
    live = r.submit({x: np.ones((3,), np.float32)})
    assert doomed.cancel()              # still PENDING: cancel succeeds
    r.close()                           # must not raise
    assert doomed.cancelled()
    with pytest.raises(ServeRejected) as ei:
        live.result(timeout=5)
    assert ei.value.reason == "draining"


def test_cancelled_request_does_not_kill_the_batcher():
    """A caller cancelling its future (standard client-side timeout) must
    not wedge the router: the batcher claims futures before resolving
    them, drops the cancelled ones, and keeps serving everyone else."""
    x, y = _dense_graph()
    iex = InferenceExecutor([y], buckets=(4,))
    r = ServingRouter(iex, max_batch=4, max_wait_ms=10.0, queue_limit=16,
                      start=False)
    try:
        doomed = r.submit({x: np.zeros((3,), np.float32)})
        live = [r.submit({x: np.full((3,), i, np.float32)})
                for i in range(3)]
        assert doomed.cancel()       # still PENDING: cancel succeeds
        r.start()
        for i, f in enumerate(live):
            np.testing.assert_allclose(
                f.result(timeout=30)[0], _expect(np.full((3,), i)),
                rtol=1e-6)
        # the batcher survived; later traffic still flows
        again = r.submit({x: np.ones((3,), np.float32)})
        np.testing.assert_allclose(again.result(timeout=30)[0],
                                   _expect(np.ones((3,))), rtol=1e-6)
    finally:
        r.close()


# ------------------------------------------------- train-only lint rule

def _train_graph():
    x = ht.placeholder_op("xt", shape=(4, 3))
    y_ = ht.placeholder_op("yt", shape=(4, 4))
    w = ht.Variable("wt", value=np.ones((3, 4), np.float32))
    d = ht.matmul_op(x, w) - y_
    loss = ht.reduce_mean_op(ht.mul_op(d, d), [0, 1])
    return x, y_, loss


def test_serving_rejects_optimizer_and_gradient_fetches():
    x, y_, loss = _train_graph()
    opt = ht.optim.SGDOptimizer(0.1).minimize(loss)
    with pytest.raises(ht.GraphValidationError,
                       match="train-only-op-in-serving") as ei:
        InferenceExecutor([loss, opt], validate="error")
    # provenance: the diagnostic names this test file as the creation site
    assert "test_serving.py" in str(ei.value)
    # ht.lint(serving=True) reports BOTH the optimizer and its gradients
    rep = ht.lint([loss, opt], serving=True, training=False)
    kinds = [d.rule for d in rep.errors]
    assert kinds.count("train-only-op-in-serving") >= 2
    # the same fetch set is FINE for the training executor's linting
    rep_train = ht.lint([loss, opt])
    assert not [d for d in rep_train.diagnostics
                if d.rule == "train-only-op-in-serving"]


def test_serving_skips_train_nodes_when_not_validating():
    x, y_, loss = _train_graph()
    opt = ht.optim.SGDOptimizer(0.1).minimize(loss)
    iex = InferenceExecutor([loss, opt], validate="off")
    out = iex.infer({x: np.zeros((4, 3), np.float32),
                     y_: np.zeros((4, 4), np.float32)})
    assert out[0] is not None            # the loss still evaluates
    assert out[1] is None                # the optimizer was never lowered


def test_dropout_warns_but_serves_as_identity():
    x = ht.placeholder_op("xd", shape=(4, 3))
    w = ht.Variable("wd", value=W0.copy())
    h = ht.dropout_op(ht.matmul_op(x, w), keep_prob=0.5)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        iex = InferenceExecutor([h], validate="error", seed=1)
    assert any("train-only-op-in-serving" in str(w_.message)
               for w_ in rec), "dropout should warn, not reject"
    out = iex.infer({x: np.ones((4, 3), np.float32)})
    # identity under training=False: no rows zeroed, no 1/keep_prob scale
    np.testing.assert_allclose(out[0], _expect(np.ones((4, 3))), rtol=1e-6)


# ------------------------------------------- weights loading round trips

def test_weights_from_live_executor_and_checkpoint(tmp_path):
    x = ht.placeholder_op("x", shape=(4, 3))
    y_ = ht.placeholder_op("y", shape=(4, 2))
    w = ht.Variable("w", initializer=ht.init.GenXavierNormal(),
                    shape=(3, 2))
    d = ht.matmul_op(x, w) - y_
    loss = ht.reduce_mean_op(ht.mul_op(d, d), [0, 1])
    ex = ht.Executor({"train": [loss,
                                ht.optim.SGDOptimizer(0.1).minimize(loss)]},
                     seed=0, install_signal_handlers=False)
    rng = np.random.RandomState(0)
    for _ in range(3):
        ex.run("train", feed_dict={x: rng.rand(4, 3).astype(np.float32),
                                   y_: rng.rand(4, 2).astype(np.float32)})
    ck = str(tmp_path / "ck")
    ex.save(ck)
    prob = ht.matmul_op(x, w)            # serving head over the SAME vars
    xv = np.ones((2, 3), np.float32)
    trained_w = ex.return_tensor_values()["w"]
    want = xv @ trained_w
    for source in (ex, ck, {"w": trained_w}):
        iex = InferenceExecutor([prob], weights=source, buckets=(2, 4))
        np.testing.assert_allclose(iex.infer({x: xv})[0], want, rtol=1e-6)
    # an unknown-name source warns and serves initializer values
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        InferenceExecutor([prob], weights={"nope": trained_w},
                          buckets=(2,))
    assert any("INITIALIZER" in str(w_.message) for w_ in rec)


def test_checkpoint_ps_tables_restore_by_node_name(tmp_path):
    """Checkpoint PS files are named by the TRAINING graph's table
    ordinal; the serving loader must match them through meta's node-name
    mapping — a serving graph reaching a different/subset table must
    never load another table's rows."""
    vocab, dim = 24, 4
    st = EmbeddingStore()
    t = st.init_table(vocab, dim, opt="sgd", lr=0.1, seed=2,
                      init_scale=0.1)
    ids = ht.placeholder_op("ids_ck", dtype=np.int64)
    y_ = ht.placeholder_op("y_ck", shape=(4, 2))
    emb = ht.ps_embedding_lookup_op((st, t), ids, width=dim,
                                    name="user_emb")
    w = ht.Variable("w_ck", value=np.ones((dim, 2), np.float32))
    d = ht.matmul_op(ht.array_reshape_op(emb, (-1, dim)), w) - y_
    loss = ht.reduce_mean_op(ht.mul_op(d, d), [0, 1])
    ex = ht.Executor({"train": [loss, ht.optim.SGDOptimizer(0.1)
                                .minimize(loss)]},
                     seed=0, install_signal_handlers=False)
    ck = str(tmp_path / "ck")
    ex.save(ck)
    saved = np.asarray(st.get_data(t))
    # the live table drifts after the save
    st.push(t, np.arange(vocab, dtype=np.int64),
            np.ones((vocab, dim), np.float32), 1.0)
    # same node name -> the checkpoint rows come back
    s_ids = ht.placeholder_op("s_ids_ck", dtype=np.int64)
    s_emb = ht.ps_embedding_lookup_op((st, t), s_ids, width=dim,
                                      name="user_emb")
    InferenceExecutor([s_emb + 0.0], weights=ck, buckets=(4,))
    np.testing.assert_array_equal(np.asarray(st.get_data(t)), saved)
    # a DIFFERENT node name warns and leaves the live table alone
    st.push(t, np.arange(vocab, dtype=np.int64),
            np.ones((vocab, dim), np.float32), 1.0)
    drifted = np.asarray(st.get_data(t))
    o_ids = ht.placeholder_op("o_ids_ck", dtype=np.int64)
    o_emb = ht.ps_embedding_lookup_op((st, t), o_ids, width=dim,
                                      name="other_emb")
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        InferenceExecutor([o_emb + 0.0], weights=ck, buckets=(4,))
    assert any("no PS table for serving node 'other_emb'"
               in str(w_.message) for w_ in rec)
    np.testing.assert_array_equal(np.asarray(st.get_data(t)), drifted)


# --------------------------------------- read-mostly embedding serving

def test_ps_readonly_embedding_serving_end_to_end():
    vocab, dim = 40, 4
    st = EmbeddingStore()
    t = st.init_table(vocab, dim, opt="sgd", lr=0.1, seed=5,
                      init_scale=0.1)
    table = np.asarray(st.get_data(t))
    ids_node = ht.placeholder_op("ids", dtype=np.int64)
    cache = DistCacheTable(st, t, limit=16, read_only=True)
    emb = ht.ps_embedding_lookup_op(cache, ids_node, width=dim)
    wv = np.asarray(np.arange(dim * 2, dtype=np.float32).reshape(dim, 2))
    w = ht.Variable("w_ps", value=wv.copy())
    out_node = ht.matmul_op(ht.array_reshape_op(emb, (-1, dim)), w)
    iex = InferenceExecutor([out_node], buckets=(4, 8))
    with ServingRouter(iex, max_batch=8, max_wait_ms=20.0) as r:
        futs = [r.submit({ids_node: np.asarray([i % vocab], np.int64)})
                for i in range(20)]
        res = [f.result(timeout=30) for f in futs]
    for i, row in enumerate(res):
        np.testing.assert_allclose(
            row[0], (table[i % vocab][None, :] @ wv)[0], rtol=1e-5)
    # read-only invariants held through the serving path
    assert cache.stats["pushes"] == 0
    assert not cache._gcnt.any()


def test_warm_does_not_touch_the_embedding_cache():
    """warm() pre-compiles every bucket with ZERO store traffic: feeding
    the default all-zero example ids through the read-only cache would
    pull id 0 (bucket) times per field — an LFU frequency boost that
    could pin key 0 unevictable, plus skewed hit stats."""
    st = EmbeddingStore()
    t = st.init_table(16, 4, opt="sgd", lr=0.1, seed=3, init_scale=0.1)
    ids_node = ht.placeholder_op("ids", dtype=np.int64, shape=(1,))
    cache = DistCacheTable(st, t, limit=8, read_only=True, policy="lfu")
    emb = ht.ps_embedding_lookup_op(cache, ids_node, width=4)
    iex = InferenceExecutor([ht.array_reshape_op(emb, (-1, 4))],
                            buckets=(2, 4))
    assert iex.warm() == 2
    assert cache.stats["lookups"] == 0
    assert cache.stats["fetches"] == 0
    assert not cache._freq.any(), "warm() inflated LFU frequency clocks"
    c = hmetrics.serve_counts()
    assert c.get("serve_bucket_compiles", 0) >= 1  # it DID compile
    # warming runs serve no requests: batch counters stay clean
    assert c.get("serve_batches", 0) == 0
    assert c.get("serve_batch_rows", 0) == 0


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = _socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


@pytest.mark.timeout(120)
def test_failover_mid_load_bitwise_equal_responses():
    """A replicated shard primary killed mid-stream (request-count
    trigger) is absorbed INSIDE a batch's pull: zero restarts, every
    request answered, responses bitwise equal to the unperturbed run."""
    world, vocab, dim = 2, 48, 4
    rng = np.random.RandomState(3)
    stream = [rng.randint(0, vocab, 4).astype(np.int64)
              for _ in range(30)]

    table = np.random.RandomState(11).normal(
        0, 0.1, (vocab, dim)).astype(np.float32)

    def run(schedule):
        # the injector must be live BEFORE the stores start: each
        # StoreServer registers itself as a kill target at construction
        prev = None
        if schedule:
            prev = chaos_mod.install(
                chaos_mod.ChaosInjector.from_spec(schedule))
        ports = _free_ports(world)
        stores = [DistributedStore(
            r, world, [("127.0.0.1", p) for p in ports], port=ports[r],
            rpc_timeout=3.0, rpc_retries=2, connect_timeout=2.0,
            replication=2) for r in range(world)]
        try:
            tid = None
            for s in stores:
                tid = s.init_table(vocab, dim, opt="sgd", lr=0.1,
                                   init_scale=0.0)
            stores[0].set_data(tid, table)
            ids_node = ht.placeholder_op("ids", dtype=np.int64)
            cache = DistCacheTable(stores[0], tid, limit=16,
                                   read_only=True)
            emb = ht.ps_embedding_lookup_op(cache, ids_node, width=dim)
            w = ht.Variable("w_f", value=np.eye(dim, dtype=np.float32))
            out = ht.matmul_op(ht.array_reshape_op(emb, (-1, dim)), w)
            iex = InferenceExecutor([out], buckets=(4, 8))
            responses = []
            with ServingRouter(iex, max_batch=8, max_wait_ms=10.0) as r:
                for wave in range(0, len(stream), 5):
                    futs = [r.submit({ids_node: ids})
                            for ids in stream[wave:wave + 5]]
                    responses += [np.asarray(f.result(timeout=60)[0])
                                  for f in futs]
            return responses
        finally:
            if schedule:
                chaos_mod.install(prev)
            for s in stores:
                try:
                    s.close()
                except Exception:
                    pass

    hmetrics.reset_faults()
    base = run(None)
    assert hmetrics.fault_counts() == {}, "clean serve recorded faults"
    # ground truth, not just cross-run agreement: identity weights make
    # each response exactly its OWN request's 4 table rows — the k-rows-
    # per-request scatter must never hand request i a neighbour's rows
    for ids, resp in zip(stream, base):
        np.testing.assert_allclose(resp, table[ids], rtol=1e-6)
    hmetrics.reset_faults()
    chaos = run("11:kill:primary@shard1:req12")
    counters = hmetrics.fault_counts()
    assert counters.get("chaos_kill_primary", 0) == 1
    assert counters.get("ps_failover_promoted", 0) >= 1
    assert len(chaos) == len(base) == len(stream)
    for a, b in zip(chaos, base):
        np.testing.assert_array_equal(a, b)
    assert hmetrics.serve_counts().get("serve_failovers", 0) >= 1


# ------------------------------------------------------- chaos req specs

def test_chaos_req_spec_parsing_and_one_shot_fire():
    seed, faults = chaos_mod.parse_spec("9:kill:primary@shard2:req40")
    assert faults == [{"kind": "kill_primary", "shard": 2, "req": 40}]
    with pytest.raises(chaos_mod.ChaosSpecError):
        chaos_mod.parse_spec("9:kill:primary@shard2:reqx")
    inj = chaos_mod.ChaosInjector(seed, faults)

    class _Srv:
        stopped = False

        def serves(self, s):
            return s == 2

        def holds(self, s):
            return s == 2

        def stop(self):
            self.stopped = True

    srv = _Srv()
    inj.register_server(0, srv)
    assert inj.on_request(39) == []
    assert srv.stopped is False
    assert inj.on_request(40) == [0]
    assert srv.stopped is True
    srv.stopped = False
    assert inj.on_request(41) == [], "req kills fire at most once"
    assert srv.stopped is False
    # the step clock ignores req-scheduled faults entirely
    inj2 = chaos_mod.ChaosInjector(*chaos_mod.parse_spec(
        "9:kill:primary@shard2:req40"))
    inj2.register_server(0, _Srv())
    assert inj2.on_step(40) == []


# ---------------------------------------------------------- the scenario

@pytest.mark.timeout(300)
def test_serve_scenario():
    """A zipf(1.05) stream served clean and under a mid-load primary
    kill: bitwise-equal responses, every request answered, zero
    rejections, the kill absorbed by a counted failover."""
    import scenarios
    extra = scenarios.serve_scenario(n_requests=180)
    assert extra["responses_bitwise_equal"] is True
    assert extra["all_answered"] is True
    assert extra["rejections"] == 0
    assert extra["fault_counters"]["chaos_kill_primary"] == 1
    assert extra["fault_counters"]["ps_failover_promoted"] >= 1
    assert extra["clean_run_counters"] == {}
    assert extra["serve_counters"]["serve_failovers"] >= 1
    # executables build in the CLEAN run (one per bucket used); the chaos
    # run reuses them through the serve cache and builds none
    assert 0 < extra["clean_serve_counters"]["serve_bucket_compiles"] <= 4
    assert extra["serve_counters"].get("serve_bucket_compiles", 0) == 0
    # the obs registry's queue-wait and batch-latency histograms hold
    # one observation a request / a device call, per run
    for obs_n in (extra["latency_observations"],
                  extra["chaos_latency_observations"]):
        assert obs_n["queue_wait"] == 180 and obs_n["batch"] > 0
    assert extra["ok"] is True
