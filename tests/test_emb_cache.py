"""Vectorized HET embedding-cache tests (ISSUE 3).

Three layers of evidence:

1. **Parity suite** — the array-backed :class:`DistCacheTable` is replayed
   against the per-key reference model (:class:`PerKeyCacheTable`, the
   pre-PR semantics) on random + zipf traces over identically-seeded
   stores: every lookup output, the final server table, per-key versions,
   and the cache counters must agree exactly (staleness bounds, eviction
   pushes, flush ordering, exactly-once gradient application under
   dedup'd batched pushes).
2. **Wire level** — ``DistributedStore.pull/push`` dedup, the fused
   ``push_pull`` round trip, and ``versions`` through the RPC fanout, on
   in-process 2-rank stores.
3. **Scale** — a 10^5-row zipf stream through both caches, compared by
   their counters (rows pulled, hits, push RPC frames).
"""
import gc
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))          # repo root

import hetu_tpu as ht
from hetu_tpu import metrics as hmetrics
from hetu_tpu.ps import EmbeddingStore, CacheSparseTable
from hetu_tpu.ps.dist_store import DistCacheTable, DistributedStore
from hetu_tpu.ps.refcache import PerKeyCacheTable


def _mk_store(vocab, dim, opt="sgd", lr=0.5, seed=3):
    st = EmbeddingStore()
    t = st.init_table(vocab, dim, opt=opt, lr=lr, seed=seed, init_scale=0.1)
    return st, t


def _trace(rng, n_ops, vocab, dim, batch, zipf):
    """Mixed lookup/update/flush trace; zipf=True draws skewed ids."""
    if zipf:
        p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** 1.2
        cdf = np.cumsum(p / p.sum())

        def draw(n):
            return np.searchsorted(cdf, rng.rand(n)).astype(np.int64)
    else:
        def draw(n):
            return rng.randint(0, vocab, n).astype(np.int64)

    ops = []
    for _ in range(n_ops):
        r = rng.rand()
        n = rng.randint(1, batch + 1)
        if r < 0.45:
            ops.append(("lookup", draw(n)))
        elif r < 0.92:
            ops.append(("update", draw(n),
                        rng.randn(n, dim).astype(np.float32)))
        else:
            ops.append(("flush",))
    return ops


def _replay(cache, ops):
    outs = []
    for op in ops:
        if op[0] == "lookup":
            outs.append(cache.lookup(op[1]).copy())
        elif op[0] == "update":
            cache.update(op[1], op[2])
        else:
            cache.flush()
    cache.flush()
    return outs


_PARITY_STATS = ("lookups", "hits", "evictions", "pushes", "fetches",
                 "updates")


def _assert_parity(vocab=120, dim=4, limit=16, pull_bound=5, push_bound=3,
                   policy="lru", zipf=False, opt="sgd", seed=0, n_ops=70,
                   batch=14):
    """Replay one trace through both implementations.

    Row VALUES compare under a tight float32 tolerance: the vectorized
    grad accumulation (scipy CSR matmul) may associate a duplicate key's
    float32 sums differently from the reference's per-occurrence loop.
    Everything decision-bearing — versions (exactly-once application),
    counters (hits/evictions/pushes/fetches), cache membership — is
    value-independent and must match EXACTLY."""
    rng = np.random.RandomState(seed)
    ops = _trace(rng, n_ops, vocab, dim, batch, zipf)
    st_v, tv = _mk_store(vocab, dim, opt=opt)
    st_r, tr = _mk_store(vocab, dim, opt=opt)
    vec = DistCacheTable(st_v, tv, limit=limit, pull_bound=pull_bound,
                         push_bound=push_bound, policy=policy)
    ref = PerKeyCacheTable(st_r, tr, limit=limit, pull_bound=pull_bound,
                          push_bound=push_bound, policy=policy)
    out_v = _replay(vec, ops)
    out_r = _replay(ref, ops)
    for i, (a, b) in enumerate(zip(out_v, out_r)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6,
                                   err_msg=f"lookup #{i}")
    np.testing.assert_allclose(st_v.get_data(tv), st_r.get_data(tr),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_array_equal(st_v.versions(tv, np.arange(vocab)),
                                  st_r.versions(tr, np.arange(vocab)))
    for k in _PARITY_STATS:
        assert vec.stats[k] == ref.stats[k], \
            (k, vec.stats, ref.stats)
    assert len(vec) == len(ref)


@pytest.mark.parametrize("policy", ["lru", "lfu"])
@pytest.mark.parametrize("zipf", [False, True])
def test_cache_parity_random_and_zipf(policy, zipf):
    _assert_parity(policy=policy, zipf=zipf, seed=1)


@pytest.mark.parametrize("pull_bound,push_bound", [(0, 1), (1, 1), (5, 2),
                                                   (100, 100)])
def test_cache_parity_staleness_bounds(pull_bound, push_bound):
    _assert_parity(pull_bound=pull_bound, push_bound=push_bound, seed=2)


def test_cache_parity_eviction_storm():
    # limit far below the working set: every batch evicts
    _assert_parity(limit=4, vocab=200, batch=10, seed=3, n_ops=60)


def test_cache_parity_batch_overflows_capacity():
    # a single batch's unique keys exceed the whole cache: the sorted-first
    # keys get slots, the remainder are served (and their grads pushed)
    # uncached
    _assert_parity(limit=6, vocab=300, batch=40, seed=4, n_ops=50)


def test_cache_parity_stateful_optimizer():
    # adagrad's per-row state makes WHEN each grad lands observable — the
    # strongest exactly-once + flush-ordering check
    _assert_parity(opt="adagrad", seed=5, push_bound=2)


def test_cache_exactly_once_gradient_totals():
    """Independent of staleness/eviction order, SGD guarantees the final
    table = init - lr * (per-key sum of all update grads) once every
    pending grad is flushed — dedup'd batched pushes must apply each
    gradient exactly once."""
    vocab, dim, lr = 64, 4, 0.5
    st, t = _mk_store(vocab, dim, lr=lr)
    base = st.get_data(t)
    cache = DistCacheTable(st, t, limit=8, pull_bound=3, push_bound=2)
    rng = np.random.RandomState(7)
    total = np.zeros((vocab, dim), np.float32)
    for _ in range(25):
        keys = rng.randint(0, vocab, 12).astype(np.int64)
        grads = rng.randn(12, dim).astype(np.float32)
        cache.lookup(keys)
        cache.update(keys, grads)
        np.add.at(total, keys, grads)
    cache.flush()
    np.testing.assert_allclose(st.get_data(t), base - lr * total,
                               rtol=1e-5, atol=1e-5)


def test_cache_staleness_and_invalidate_on_push():
    """pull_bound serves a stale row exactly bound times; a push-bound
    overflow invalidates the local copy (next lookup refetches)."""
    vocab, dim = 16, 4
    st, t = _mk_store(vocab, dim, lr=1.0)
    cache = DistCacheTable(st, t, limit=8, pull_bound=3, push_bound=2)
    v0 = cache.lookup([7])[0].copy()            # miss: uses=1
    st.push(t, np.asarray([7]), np.full((1, dim), 4.0, np.float32))
    np.testing.assert_allclose(cache.lookup([7])[0], v0)   # uses=2
    np.testing.assert_allclose(cache.lookup([7])[0], v0)   # uses=3
    v_fresh = cache.lookup([7])[0]              # bound exhausted: refetch
    np.testing.assert_allclose(v_fresh, v0 - 4.0)
    cache.update([7], np.full((1, dim), 0.5, np.float32))  # gcnt=1
    np.testing.assert_allclose(st.pull(t, np.asarray([7]))[0], v_fresh)
    cache.update([7], np.full((1, dim), 0.5, np.float32))  # gcnt=2: push
    np.testing.assert_allclose(st.pull(t, np.asarray([7]))[0],
                               v_fresh - 1.0)
    # the pushed row is invalidated locally: the next lookup refetches
    fetched = cache.stats["fetches"]
    np.testing.assert_allclose(cache.lookup([7])[0], v_fresh - 1.0)
    assert cache.stats["fetches"] == fetched + 1


def test_cache_batched_pushes_not_per_key():
    """One flush of many dirty rows = ONE batched push round trip (the
    pre-PR path paid one RPC per key)."""
    vocab, dim = 256, 4
    st, t = _mk_store(vocab, dim)
    cache = DistCacheTable(st, t, limit=256, pull_bound=10, push_bound=100)
    keys = np.arange(64, dtype=np.int64)
    cache.update(keys, np.ones((64, dim), np.float32))
    cache.flush()
    assert cache.stats["pushes"] == 64
    assert cache.stats["push_rpcs"] == 1


class _FlakyStore:
    """Store proxy whose next N sparse ops raise (the shape of
    ``DistributedStore._rpc`` after retry exhaustion)."""

    def __init__(self, store, table):
        self._store, self._table = store, table
        self.fail_next = 0

    def width(self, table):
        return self._store.width(table)

    def _maybe_fail(self):
        if self.fail_next > 0:
            self.fail_next -= 1
            raise RuntimeError("PS peer unreachable (injected)")

    def pull(self, table, keys):
        self._maybe_fail()
        return self._store.pull(table, keys)

    def push(self, table, keys, grads, lr=-1.0):
        self._maybe_fail()
        return self._store.push(table, keys, grads, lr)

    def push_pull(self, table, push_keys, grads, pull_keys, lr=-1.0):
        self._maybe_fail()
        return self._store.push_pull(table, push_keys, grads, pull_keys,
                                     lr)


def test_cache_survives_transient_store_failure():
    """A failed store round trip must leave the cache untouched: no key
    registered for a never-filled row (a retried lookup would otherwise
    serve garbage as a hit), no pending grad lost, and a retried update
    applies exactly once."""
    vocab, dim, lr = 40, 4, 1.0
    st, t = _mk_store(vocab, dim, lr=lr)
    flaky = _FlakyStore(st, t)
    cache = DistCacheTable(flaky, t, limit=8, pull_bound=5, push_bound=2,
                           lr=lr)
    truth = st.get_data(t)
    keys = np.asarray([1, 2, 3], np.int64)
    flaky.fail_next = 1
    with pytest.raises(RuntimeError, match="unreachable"):
        cache.lookup(keys)
    # retry serves the TRUE rows (not zeros from a torn registration)
    np.testing.assert_array_equal(cache.lookup(keys), truth[keys])
    assert len(cache) == 3

    # pending grad survives a failed refresh-push and lands exactly once
    cache.update(keys, np.ones((3, dim), np.float32))    # gcnt=1, pending
    flaky.fail_next = 1
    with pytest.raises(RuntimeError, match="unreachable"):
        cache.flush()
    cache.flush()                                        # retry succeeds
    np.testing.assert_allclose(st.get_data(t)[keys], truth[keys] - lr)
    v = st.versions(t, keys)
    np.testing.assert_array_equal(v, [1, 1, 1])          # exactly once

    # a failed push-bound update leaves the whole update unapplied: the
    # caller's retry is exactly-once, not doubled
    cache.update(keys, np.ones((3, dim), np.float32))    # gcnt=1
    flaky.fail_next = 1
    with pytest.raises(RuntimeError, match="unreachable"):
        cache.update(keys, np.ones((3, dim), np.float32))  # would push
    cache.update(keys, np.ones((3, dim), np.float32))    # retry: pushes
    np.testing.assert_allclose(st.get_data(t)[keys], truth[keys] - 3 * lr)
    np.testing.assert_array_equal(st.versions(t, keys), [2, 2, 2])


# ------------------------------------------------------ wire level (dedup)

def test_dist_pull_push_dedup_counters_and_semantics():
    hmetrics.reset_cache_counts()
    store = DistributedStore(0, 1)
    try:
        t = store.init_table(32, 4, opt="sgd", lr=1.0, init_scale=0.0)
        dup = np.asarray([3, 3, 5, 3, 5, 9], np.int64)
        rows = store.pull(t, dup)
        assert rows.shape == (6, 4)
        np.testing.assert_allclose(rows, 0.0)
        # duplicate grads pre-accumulate client-side; the server applies
        # the identical per-key sum (versions bump once per unique key)
        store.push(t, dup, np.ones((6, 4), np.float32))
        np.testing.assert_allclose(store.pull(t, np.asarray([3]))[0], -3.0)
        np.testing.assert_allclose(store.pull(t, np.asarray([5]))[0], -2.0)
        np.testing.assert_allclose(store.pull(t, np.asarray([9]))[0], -1.0)
        v = store.versions(t, dup)
        np.testing.assert_array_equal(v, [1, 1, 1, 1, 1, 1])
        counts = hmetrics.cache_counts()
        assert counts["ps_dedup_pull_rows_saved"] >= 3
        assert counts["ps_dedup_push_rows_saved"] == 3
    finally:
        store.close()


def _two_rank_stores(rows=64, width=8, lr=1.0):
    import socket
    socks, ports = [], []
    for _ in range(2):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    endpoints = [("127.0.0.1", p) for p in ports]
    stores = [DistributedStore(r, 2, endpoints, port=ports[r],
                               rpc_timeout=10.0, rpc_retries=2,
                               connect_timeout=5.0) for r in range(2)]
    tid = None
    for s in stores:
        tid = s.init_table(rows, width, opt="sgd", lr=lr, init_scale=0.0)
    return stores, tid


def test_fused_push_pull_single_round_trip():
    """push_pull over a 2-rank store: the remote peer gets ONE fused
    OP_PUSH_PULL frame (counter), and the pulled rows already include the
    pushes that rode the same frame."""
    hmetrics.reset_cache_counts()
    stores, tid = _two_rank_stores()
    s0 = stores[0]
    try:
        push_keys = np.asarray([1, 3, 2], np.int64)   # 1,3 remote; 2 local
        grads = np.ones((3, 8), np.float32)
        pull_keys = np.asarray([1, 3, 2, 5], np.int64)
        rows = s0.push_pull(tid, push_keys, grads, pull_keys, lr=1.0)
        np.testing.assert_allclose(rows[0], -1.0)     # push visible
        np.testing.assert_allclose(rows[1], -1.0)
        np.testing.assert_allclose(rows[2], -1.0)
        np.testing.assert_allclose(rows[3], 0.0)
        assert hmetrics.cache_counts()["ps_push_pull_fused_rpcs"] == 1
        # parity with serial push-then-pull semantics
        s0.push(tid, push_keys, grads, lr=1.0)
        np.testing.assert_allclose(
            s0.pull(tid, push_keys),
            np.full((3, 8), -2.0, np.float32))
    finally:
        for s in stores:
            s.close()


def test_fused_push_pull_dup_frame_applies_push_once():
    """The chaos harness resends the same (client, seq) OP_PUSH_PULL
    frame: the server's dedup window must apply the non-idempotent push
    half exactly once while still answering the idempotent pull."""
    from hetu_tpu import chaos as chaos_mod
    stores, tid = _two_rank_stores()
    s0 = stores[0]
    prev = chaos_mod.install(chaos_mod.ChaosInjector.from_spec("7:dup=1.0"))
    try:
        rows = s0.push_pull(tid, np.asarray([1, 3], np.int64),
                            np.ones((2, 8), np.float32),
                            np.asarray([1, 3], np.int64), lr=1.0)
        np.testing.assert_allclose(rows, -1.0)     # once, not twice
        np.testing.assert_array_equal(
            s0.versions(tid, np.asarray([1, 3], np.int64)), [1, 1])
    finally:
        chaos_mod.install(prev)
        for s in stores:
            s.close()


def test_cstable_revives_pool_after_close():
    """A cache can outlive the executor that closed it (shared table /
    rebound executor): the next async op revives the worker instead of
    dying on a closed pool."""
    st, t = _mk_store(20, 4)
    cache = CacheSparseTable(limit=8, length=20, width=4, store=st, table=t,
                             bound=0)
    cache.close()
    assert cache._pool is None
    rows = cache.embedding_lookup(np.asarray([1, 2])).result()
    assert rows.shape == (2, 4)
    cache.close()


def test_versions_through_fanout_with_dups():
    stores, tid = _two_rank_stores()
    s0 = stores[0]
    try:
        s0.push(tid, np.asarray([1, 2], np.int64),
                np.ones((2, 8), np.float32))
        v = s0.versions(tid, np.asarray([1, 1, 2, 3, 2], np.int64))
        np.testing.assert_array_equal(v, [1, 1, 1, 0, 1])
    finally:
        for s in stores:
            s.close()


def test_dist_cache_over_two_ranks_batched():
    """The vectorized cache over a real 2-rank store: owner-grouped
    batched pushes land on both shards, and a flush makes every grad
    visible exactly once."""
    stores, tid = _two_rank_stores()
    s0 = stores[0]
    try:
        cache = DistCacheTable(s0, tid, limit=16, pull_bound=4,
                               push_bound=100, lr=1.0)
        keys = np.arange(10, dtype=np.int64)          # both owners
        rows = cache.lookup(keys)
        np.testing.assert_allclose(rows, 0.0)
        cache.update(keys, np.ones((10, 8), np.float32))
        cache.flush()
        assert cache.stats["push_rpcs"] == 1          # one batched flush
        np.testing.assert_allclose(s0.pull(tid, keys),
                                   np.full((10, 8), -1.0, np.float32))
    finally:
        for s in stores:
            s.close()


# ------------------------------------------- streamed save/load (numpy v3)

def _numpy_store(vocab, dim, opt="adam"):
    st = EmbeddingStore()
    st._lib, st._h = None, None      # force the numpy fallback table
    t = st.init_table(vocab, dim, opt=opt, lr=0.1, seed=1, init_scale=0.1)
    return st, t


def test_v3_chunked_save_load_roundtrip(tmp_path, monkeypatch):
    from hetu_tpu.ps import store as store_mod
    monkeypatch.setattr(store_mod, "_V3_CHUNK", 64)   # force many chunks
    st, t = _numpy_store(50, 6)
    rng = np.random.RandomState(0)
    for _ in range(3):
        st.push(t, rng.randint(0, 50, 8), rng.randn(8, 6).astype(np.float32))
    path = str(tmp_path / "emb.bin")
    st.save(t, path)
    with open(path, "rb") as f:
        assert f.read(8) == store_mod._V3_MAGIC
    st2, t2 = _numpy_store(50, 6)
    st2.load(t2, path)
    np.testing.assert_array_equal(st2.get_data(t2), st.get_data(t))
    np.testing.assert_array_equal(st2.versions(t2, np.arange(50)),
                                  st.versions(t, np.arange(50)))
    # adam moments restored: identical further pushes converge identically
    keys = rng.randint(0, 50, 8)
    grads = rng.randn(8, 6).astype(np.float32)
    st.push(t, keys, grads)
    st2.push(t2, keys, grads)
    np.testing.assert_array_equal(st2.get_data(t2), st.get_data(t))


def test_v3_load_rejects_shape_mismatch(tmp_path):
    st, t = _numpy_store(20, 4)
    path = str(tmp_path / "emb.bin")
    st.save(t, path)
    st2, t2 = _numpy_store(21, 4)
    with pytest.raises(IOError, match="v3 checkpoint"):
        st2.load(t2, path)


def test_v2_npz_backward_compat_load(tmp_path):
    st, t = _numpy_store(12, 4, opt="sgd")
    tbl = st._np_tables[t]
    st.push(t, np.asarray([2, 5]), np.ones((2, 4), np.float32))
    path = str(tmp_path / "v2.bin")
    with open(path, "wb") as f:                     # the pre-PR v2 format
        np.savez(f, data=tbl.data, version=tbl.version)
    st2, t2 = _numpy_store(12, 4, opt="sgd")
    st2.load(t2, path)
    np.testing.assert_array_equal(st2.get_data(t2), st.get_data(t))


# ------------------------------------------------- teardown + counters

def test_cstable_close_shuts_pool_and_executor_teardown():
    st, t = _mk_store(20, 4)
    cache = CacheSparseTable(limit=8, length=20, width=4, store=st, table=t,
                             bound=0)
    pool = cache._pool
    assert pool is not None
    cache.close()
    assert cache._pool is None
    assert pool._shutdown
    cache.close()                                   # idempotent

    # executor teardown path closes the caches its graphs own
    st2, t2 = _mk_store(20, 4)
    cache2 = CacheSparseTable(limit=8, length=20, width=4, store=st2,
                              table=t2, bound=0)
    ids = ht.placeholder_op("ids")
    y_ = ht.placeholder_op("y")
    h = ht.ps_embedding_lookup_op(cache2, ids)
    w = ht.Variable("w", value=np.full((4, 2), 0.3, np.float32))
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
        ht.matmul_op(h, w), y_), [0])
    ex = ht.Executor({"train": [loss,
                                ht.optim.SGDOptimizer(0.1).minimize(loss)]},
                     seed=0)
    ex.run("train", feed_dict={ids: np.arange(4),
                               y_: np.eye(2, dtype=np.float32)[[0, 1, 0, 1]]})
    del ex
    gc.collect()
    assert cache2._pool is None


def test_clean_dense_run_records_zero_cache_counters():
    """The acceptance invariant: a dense (non-PS) training step records
    NOTHING in the cache/dedup registry."""
    hmetrics.reset_cache_counts()
    x = ht.placeholder_op("x", shape=(8, 4))
    y_ = ht.placeholder_op("y", shape=(8, 2))
    w = ht.Variable("w", value=np.full((4, 2), 0.3, np.float32),
                    trainable=True)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
        ht.matmul_op(x, w), y_), [0])
    ex = ht.Executor({"train": [loss,
                                ht.optim.SGDOptimizer(0.1).minimize(loss)]},
                     seed=0)
    rng = np.random.RandomState(0)
    for _ in range(3):
        ex.run("train", feed_dict={
            x: rng.randn(8, 4).astype(np.float32),
            y_: np.eye(2, dtype=np.float32)[rng.randint(0, 2, 8)]})
    from hetu_tpu.profiler import HetuProfiler
    assert HetuProfiler.cache_counters() == {}


def test_executor_trains_through_vectorized_cache():
    """End-to-end: a PS embedding routed through the vectorized cache
    trains (prefetch path included) and the counters surface."""
    hmetrics.reset_cache_counts()
    rng = np.random.RandomState(0)
    vocab, dim, batch = 40, 4, 16
    st, t = _mk_store(vocab, dim, lr=0.3)
    cache = DistCacheTable(st, t, limit=16, pull_bound=5, push_bound=3,
                           policy="lru")
    ids = ht.placeholder_op("ids")
    y_ = ht.placeholder_op("y")
    h = ht.ps_embedding_lookup_op(cache, ids, width=dim)
    w = ht.Variable("w", value=rng.randn(dim, 3).astype(np.float32) * 0.3,
                    trainable=True)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
        ht.matmul_op(h, w), y_), [0])
    ex = ht.Executor({"train": [loss,
                                ht.optim.SGDOptimizer(0.3).minimize(loss)]},
                     seed=0)
    ids_v = rng.randint(0, vocab, batch)
    y_v = np.eye(3, dtype=np.float32)[rng.randint(0, 3, batch)]
    losses = [float(ex.run("train", feed_dict={ids: ids_v, y_: y_v}
                           )[0].asnumpy()) for _ in range(6)]
    cache.flush()
    assert losses[-1] < losses[0]
    assert cache.stats["hits"] > 0
    counts = hmetrics.cache_counts()
    assert counts.get("emb_cache_hit_rows", 0) > 0
    assert counts.get("emb_cache_push_rows", 0) > 0


def test_executor_save_flushes_cache_pending_grads(tmp_path):
    """Executor.save persists PS tables SERVER-side — grads still pending
    in a client cache (below push_bound) must be flushed first or the
    checkpoint silently misses them."""
    rng = np.random.RandomState(0)
    vocab, dim, batch = 30, 4, 8
    st, t = _mk_store(vocab, dim, lr=0.2)
    cache = DistCacheTable(st, t, limit=32, pull_bound=100,
                           push_bound=1000)    # nothing pushes on its own
    ids = ht.placeholder_op("ids")
    y_ = ht.placeholder_op("y")
    h = ht.ps_embedding_lookup_op(cache, ids, width=dim)
    w = ht.Variable("w", value=rng.randn(dim, 2).astype(np.float32) * 0.3,
                    trainable=True)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
        ht.matmul_op(h, w), y_), [0])
    ex = ht.Executor({"train": [loss,
                                ht.optim.SGDOptimizer(0.2).minimize(loss)]},
                     seed=0)
    ids_v = rng.randint(0, vocab, batch)
    y_v = np.eye(2, dtype=np.float32)[rng.randint(0, 2, batch)]
    for _ in range(3):
        ex.run("train", feed_dict={ids: ids_v, y_: y_v})
    assert int(cache._gcnt.sum()) > 0          # grads pending pre-save
    ex.save(str(tmp_path / "ckpt"))
    assert int(cache._gcnt.sum()) == 0         # flushed into the table
    assert (st.versions(t, np.unique(ids_v)) > 0).all()


def test_wdl_graph_builds_on_vectorized_cache_policy():
    """The --emb-policy wdl path: the CTR model's vlru embedding mode
    trains green end-to-end."""
    sys.path_hooks  # keep flake quiet
    import importlib.util
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "_test_ctr_models", os.path.join(root, "examples", "ctr",
                                         "models.py"))
    ctr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ctr)
    bs = 32
    dense = ht.placeholder_op("dense")
    sparse = ht.placeholder_op("sparse", dtype=np.int64)
    y_ = ht.placeholder_op("y")
    loss, prob = ctr.wdl_criteo(dense, sparse, y_, bs, vocab=2000, dim=8,
                                embed_mode="vlru", lr=0.05)
    ex = ht.Executor({"train": [loss,
                                ht.optim.SGDOptimizer(0.05).minimize(loss)]},
                     seed=0)
    d, s, y = ctr.synthetic_criteo(bs, vocab=2000)
    losses = [float(ex.run("train", feed_dict={dense: d, sparse: s, y_: y}
                           )[0].asnumpy()) for _ in range(4)]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


# ----------------------------------------------------------- scale proof

def test_vectorized_cache_counts_against_per_key_model_at_scale():
    """A 10^5-row zipf(1.05) stream through the vectorized cache and the
    per-key reference model (the pre-ISSUE-3 cost shape) over
    identically-seeded stores: the same rows are pulled and the same
    lookups hit, and the per-key model's one push RPC per dirty key
    becomes one batched RPC per flushing call.  Then the raw (uncached)
    pull/push path on the same dup-heavy batches: ``np.unique`` dedup
    removes rows before the shard fanout."""
    vocab, dim, limit, batch = 100_000, 16, 20_000, 4096
    rng = np.random.RandomState(0)
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** 1.05
    cdf = np.cumsum(p / p.sum())
    trace = [np.searchsorted(cdf, rng.rand(batch)).astype(np.int64)
             for _ in range(4)]
    # bounds are in USE counts and the zipf head key shows up hundreds
    # of times a batch, so they scale with the batch
    bounds = dict(limit=limit, pull_bound=batch // 2, push_bound=batch)
    st_v, tv = _mk_store(vocab, dim, lr=0.05)
    st_r, tr = _mk_store(vocab, dim, lr=0.05)
    vec = DistCacheTable(st_v, tv, **bounds)
    ref = PerKeyCacheTable(st_r, tr, **bounds)
    grng = np.random.RandomState(1)
    for ids in trace:
        g = grng.standard_normal((ids.size, dim)).astype(np.float32) * 0.01
        np.testing.assert_array_equal(vec.lookup(ids), ref.lookup(ids))
        vec.update(ids, g)
        ref.update(ids, g)
    vec.flush()
    ref.flush()
    perf = vec.perf()
    for k in ("lookups", "hits", "fetches", "updates", "pushes"):
        assert perf[k] == ref.stats[k], (k, perf[k], ref.stats[k])
    assert perf["lookups"] == 4 * batch and 0.0 < perf["hit_rate"] <= 1.0
    # rows pulled: only misses and refreshes, far fewer than looked up
    assert 0 < perf["fetches"] < perf["lookups"] // 2
    # RPC frames: one per dirty key before, at most one per call now
    assert ref.stats["push_rpcs"] == ref.stats["pushes"] > 100
    assert 0 < perf["push_rpcs"] <= 2 * len(trace) + 1

    hmetrics.reset_cache_counts()
    store = DistributedStore(0, 1)
    try:
        tid = store.init_table(vocab, dim, opt="sgd", lr=0.05,
                               init_scale=0.01)
        for ids in trace[:2]:
            store.pull(tid, ids)
            store.push(tid, ids, np.zeros((ids.size, dim), np.float32),
                       0.05)
    finally:
        store.close()
    dedup = hmetrics.cache_counts()
    assert dedup["ps_dedup_pull_rows_saved"] > 0
    assert dedup["ps_dedup_push_rows_saved"] > 0


# ------------------------------------------------- read-only serving mode

def test_readonly_lookup_parity_and_no_write_bookkeeping():
    """ISSUE 7 satellite: on an identical pure-lookup trace the read-only
    cache serves the SAME rows as the training-mode cache, but a pure
    lookup allocates no dirty-slab entry, never counts toward
    push_bound, and never burns pull_bound budget (no forced
    re-fetches)."""
    rng = np.random.RandomState(0)
    vocab, dim = 64, 4
    st_a, ta = _mk_store(vocab, dim)
    st_b, tb = _mk_store(vocab, dim)
    train = DistCacheTable(st_a, ta, limit=16, pull_bound=3, push_bound=2)
    ro = DistCacheTable(st_b, tb, limit=16, pull_bound=3, push_bound=2,
                        read_only=True)
    trace = [rng.randint(0, vocab, rng.randint(1, 12)).astype(np.int64)
             for _ in range(40)]
    for ids in trace:
        a = train.lookup(ids)
        b = ro.lookup(ids)
        assert np.array_equal(a, b)
    # no write-side bookkeeping anywhere in the read-only cache
    assert not ro._gcnt.any(), "pure lookup allocated a dirty slab entry"
    assert not ro._grad.any()
    assert ro.stats["pushes"] == 0 and ro.stats["push_rpcs"] == 0
    # pull_bound budget untouched: a hot key is re-fetched by the
    # TRAINING cache every pull_bound lookups, never by the read-only one
    hot = np.asarray([7], np.int64)
    f0_train, f0_ro = train.stats["fetches"], ro.stats["fetches"]
    for _ in range(10):
        train.lookup(hot)
        ro.lookup(hot)
    assert train.stats["fetches"] > f0_train, "oracle: training re-fetches"
    assert ro.stats["fetches"] - f0_ro <= 1, \
        "read-only lookup burned pull_bound budget"


def test_readonly_rejects_update_and_keeps_evicting():
    st, t = _mk_store(32, 4)
    ro = DistCacheTable(st, t, limit=8, pull_bound=100, push_bound=2,
                        read_only=True)
    with pytest.raises(RuntimeError, match="read_only"):
        ro.update(np.asarray([1], np.int64), np.ones((1, 4), np.float32))
    # capacity pressure still evicts (recency clocks advance on RO hits)
    for lo in range(0, 32, 4):
        ro.lookup(np.arange(lo, lo + 4, dtype=np.int64))
    assert ro.stats["evictions"] > 0
    assert len(ro) <= 8


def test_readonly_version_refresh_picks_up_writer():
    """Version-based staleness: a trainer pushing rows elsewhere advances
    the server version; refresh_stale() re-pulls EXACTLY the changed
    cached rows (batched), after which lookups serve the new value."""
    st, t = _mk_store(32, 4, lr=1.0)
    ro = DistCacheTable(st, t, limit=16, pull_bound=2, push_bound=2,
                        read_only=True)
    ids = np.arange(8, dtype=np.int64)
    before = ro.lookup(ids)
    # an external trainer updates rows 2 and 5 (sgd lr=1: row -= grad)
    g = np.ones((2, 4), np.float32)
    st.push(t, np.asarray([2, 5], np.int64), g, 1.0)
    # stale until refreshed (beyond pull_bound: RO mode never re-pulls)
    assert np.array_equal(ro.lookup(ids), before)
    assert np.array_equal(ro.lookup(ids), before)
    refreshed = ro.refresh_stale()
    assert refreshed == 2
    after = ro.lookup(ids)
    expect = before.copy()
    expect[[2, 5]] -= 1.0
    assert np.allclose(after, expect)
    # idempotent: nothing changed since, so nothing re-pulls
    assert ro.refresh_stale() == 0


def test_readonly_refresh_every_autorefresh():
    st, t = _mk_store(16, 4, lr=1.0)
    ro = DistCacheTable(st, t, limit=16, read_only=True, refresh_every=3)
    ids = np.arange(4, dtype=np.int64)
    before = ro.lookup(ids)
    st.push(t, np.asarray([1], np.int64), np.ones((1, 4), np.float32), 1.0)
    ro.lookup(ids)            # 2nd call since construction
    out = ro.lookup(ids)      # 3rd call: trips the async sweep AFTER serving
    assert np.array_equal(out, before)
    assert ro.refresh_join(timeout=10)   # drain the background sweep
    out = ro.lookup(ids)      # post-sweep: refreshed row visible
    assert not np.array_equal(out, before)
    assert out[1][0] == before[1][0] - 1.0


def test_readonly_fill_version_read_before_pull_survives_racing_writer():
    """A writer landing BETWEEN the miss path's two store RPCs must not
    create an invisible-stale row: versions are read BEFORE the rows, so
    the recorded version can only be OLDER than the data — refresh_stale
    then re-pulls (harmlessly) instead of never noticing."""
    st, t = _mk_store(16, 4, lr=1.0)

    class _RacingStore:
        """Injects one push between the versions() and pull() calls of a
        single read-only miss — the exact interleaving of the race."""

        def __init__(self, store, table):
            self._s, self._t = store, table
            self.armed = False

        def width(self, table):
            return self._s.width(table)

        def versions(self, table, keys):
            v = self._s.versions(table, keys)
            if self.armed:
                self.armed = False
                self._s.push(self._t, np.asarray([3], np.int64),
                             np.ones((1, 4), np.float32), 1.0)
            return v

        def pull(self, table, keys):
            return self._s.pull(table, keys)

    racing = _RacingStore(st, t)
    ro = DistCacheTable(racing, t, limit=16, read_only=True)
    racing.armed = True
    first = ro.lookup(np.asarray([3], np.int64))   # fill races the writer
    # the pull already observed the post-write row (versions came first)
    np.testing.assert_array_equal(
        first[0], np.asarray(st.pull(t, np.asarray([3], np.int64)))[0])
    # the conservative version makes the sweep re-pull once, then settle
    assert ro.refresh_stale() == 1
    assert ro.refresh_stale() == 0
    now = ro.lookup(np.asarray([3], np.int64))
    np.testing.assert_array_equal(
        now[0], np.asarray(st.pull(t, np.asarray([3], np.int64)))[0])
