"""Device-resident HET embedding cache (ISSUE 11).

Four layers of evidence, all CPU-runnable:

1. **Kernel parity** — the Pallas gather / scatter-add kernels run in
   interpret mode (the exact TPU kernel code) against numpy references,
   and the dispatchers' fallback counters + ``HETU_REQUIRE_PALLAS_EMB``
   hard-fail are exercised.
2. **Oracle parity** — ``DistCacheTable(device=True)`` replays mixed
   traces against the PR 3 per-key oracle (``refcache``): served values
   to float32-association tolerance, versions / counters / eviction
   decisions EXACT — the same contract the host-mode parity suite
   holds, now through begin→roundtrip→finish and the device slab.
3. **Executor end-to-end** — device-mode training is BITWISE equal to
   host-mode cache training (losses, final server table, versions,
   cache stats), sync and async, and the overlapped miss pull is
   visible in the trace (``ps.miss_pull`` on the feed-pipeline track,
   flow arrow into the consuming step).
4. **TPU-target lowering** — ``jax.export`` for platform "tpu" shows
   the Pallas custom-call in both kernels' modules (the dispatch reaches
   them; whether the chip's compiler accepts them is
   tests/test_tpu_compile.py's).

Sizes are deliberately tiny (tier-1 budget); the zipf scale proof is
marked ``slow``.
"""
import gc
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))          # repo root

import jax
import jax.numpy as jnp

import hetu_tpu as ht
from hetu_tpu import metrics as hmetrics
from hetu_tpu.ops.pallas import emb_cache as emb
from hetu_tpu.ps import EmbeddingStore
from hetu_tpu.ps.dist_store import DistCacheTable
from hetu_tpu.ps.refcache import PerKeyCacheTable


@pytest.fixture(autouse=True)
def _drain_dead_executors():
    """Run deferred ``Executor.__del__`` cache flushes at a SAFE point
    (between tests) — a gen-2 GC firing inside a later test's jax trace
    would otherwise re-enter the store push mid-trace (the PR 3
    teardown-segfault class)."""
    yield
    gc.collect()


def _mk_store(vocab, dim, opt="sgd", lr=0.5, seed=3):
    st = EmbeddingStore()
    t = st.init_table(vocab, dim, opt=opt, lr=lr, seed=seed,
                      init_scale=0.1)
    return st, t


# ------------------------------------------------------------ kernel layer

def test_gather_kernel_interpret_parity():
    rng = np.random.RandomState(0)
    slab = jnp.asarray(rng.randn(64, 8).astype(np.float32))
    slots = jnp.asarray(rng.randint(0, 64, 21).astype(np.int32))
    out = emb.gather_rows(slab, slots, interpret=True)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(slab)[np.asarray(slots)])


def test_scatter_add_kernel_interpret_parity():
    rng = np.random.RandomState(1)
    n, w = 37, 8
    ids = rng.randint(0, 9, n)
    uk, inv = np.unique(ids, return_inverse=True)
    g = rng.randn(n, w).astype(np.float32)
    out = np.asarray(emb.scatter_add_grads(jnp.asarray(g),
                                           jnp.asarray(inv),
                                           interpret=True))
    ref = np.zeros((n, w), np.float32)
    np.add.at(ref, inv, g)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=1e-6)
    # rows past the last segment are zero padding (U known host-side)
    assert not out[uk.size:].any()


def test_fill_rows_and_dump_padding():
    rng = np.random.RandomState(2)
    slab = jnp.asarray(rng.randn(16, 4).astype(np.float32))
    rows = jnp.asarray(rng.randn(3, 4).astype(np.float32))
    # two real targets + one padding entry on the dump row (15)
    tgt = jnp.asarray(np.array([3, 7, 15], np.int32))
    out = np.asarray(emb.fill_rows(slab, rows, tgt))
    np.testing.assert_array_equal(out[3], np.asarray(rows)[0])
    np.testing.assert_array_equal(out[7], np.asarray(rows)[1])
    # untouched rows survive
    np.testing.assert_array_equal(out[4], np.asarray(slab)[4])


def test_dispatch_fallback_counted_not_silent():
    if jax.default_backend() == "tpu":
        pytest.skip("fallback path is the off-TPU path")
    hmetrics.reset_emb_pallas_fallbacks()
    rng = np.random.RandomState(3)
    slab = jnp.asarray(rng.randn(32, 4).astype(np.float32))
    slots = jnp.asarray(rng.randint(0, 32, 9).astype(np.int32))
    out = emb.emb_gather(slab, slots)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(slab)[np.asarray(slots)])
    g = jnp.asarray(rng.randn(9, 4).astype(np.float32))
    inv = jnp.asarray(np.array([0, 0, 1, 2, 2, 2, 3, 4, 4], np.int32))
    ref = np.zeros((9, 4), np.float32)
    np.add.at(ref, np.asarray(inv), np.asarray(g))
    np.testing.assert_allclose(np.asarray(emb.emb_scatter_add(g, inv)),
                               ref, rtol=2e-5, atol=1e-6)
    counts = hmetrics.emb_pallas_fallback_counts()
    assert counts.get("gather:backend_cpu", 0) >= 1, counts
    assert counts.get("scatter_add:backend_cpu", 0) >= 1, counts


def test_require_pallas_emb_hard_fail(monkeypatch):
    if jax.default_backend() == "tpu":
        pytest.skip("fallback path is the off-TPU path")
    monkeypatch.setenv("HETU_REQUIRE_PALLAS_EMB", "1")
    slab = jnp.zeros((8, 4), jnp.float32)
    with pytest.raises(RuntimeError, match="HETU_REQUIRE_PALLAS_EMB"):
        emb.emb_gather(slab, jnp.zeros((4,), jnp.int32))


def test_tpu_lowering_contains_pallas_custom_call():
    """Cross-platform TPU LOWERING of the gather and the scatter-add
    contains the Mosaic custom-call: the device path reaches the kernels.
    ``jax.export`` never runs the chip's compiler (both kernels passed
    this while Mosaic refused them, ISSUE 21) — whether they COMPILE is
    tests/test_tpu_compile.py's, at the widths the callers use."""
    import jax.export
    slab = jnp.zeros((64, 8), jnp.float32)
    slots = jnp.zeros((16,), jnp.int32)
    exp = jax.export.export(
        jax.jit(lambda s, i: emb.gather_rows(s, i)),
        platforms=["tpu"])(slab, slots)
    assert "tpu_custom_call" in exp.mlir_module()
    g = jnp.zeros((32, 8), jnp.float32)
    inv = jnp.zeros((32,), jnp.int32)
    exp2 = jax.export.export(
        jax.jit(lambda g, i: emb.scatter_add_grads(g, i)),
        platforms=["tpu"])(g, inv)
    assert "tpu_custom_call" in exp2.mlir_module()


def test_segment_sum_scipy_absent_fallback(monkeypatch):
    """Satellite: the scipy-absent grad segment-sum runs ``np.add.at``
    and records ``emb_grad_host_fallback`` (counter-coverage gate)."""
    from hetu_tpu.ps.dist_store import _segment_sum
    rng = np.random.RandomState(4)
    inv = np.array([0, 1, 1, 2, 0, 2, 2], np.int64)
    cnt = np.array([2, 2, 3], np.int64)
    g = rng.randn(7, 4).astype(np.float32)
    want = _segment_sum(g, inv, cnt)             # scipy path
    monkeypatch.setitem(sys.modules, "scipy", None)
    monkeypatch.setitem(sys.modules, "scipy.sparse", None)
    before = hmetrics.cache_counts().get("emb_grad_host_fallback", 0)
    got = _segment_sum(g, inv, cnt)              # np.add.at path
    after = hmetrics.cache_counts().get("emb_grad_host_fallback", 0)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-6)
    assert after == before + 1


# ------------------------------------------------------------ oracle layer

def _trace(rng, n_ops, vocab, dim, batch):
    ops = []
    for _ in range(n_ops):
        r = rng.rand()
        n = rng.randint(1, batch + 1)
        ids = rng.randint(0, vocab, n).astype(np.int64)
        if r < 0.45:
            ops.append(("lookup", ids))
        elif r < 0.92:
            ops.append(("update", ids,
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


def _assert_device_parity(policy="lru", seed=0, vocab=120, dim=4,
                          limit=16, pull_bound=5, push_bound=3,
                          n_ops=35, batch=12, scratch=64,
                          interpret=None):
    rng = np.random.RandomState(seed)
    ops = _trace(rng, n_ops, vocab, dim, batch)
    st_d, td = _mk_store(vocab, dim)
    st_r, tr = _mk_store(vocab, dim)
    dev = DistCacheTable(st_d, td, limit=limit, pull_bound=pull_bound,
                         push_bound=push_bound, policy=policy,
                         device=True, device_scratch=scratch,
                         device_interpret=interpret)
    ref = PerKeyCacheTable(st_r, tr, limit=limit, pull_bound=pull_bound,
                           push_bound=push_bound, policy=policy)
    out_d, out_r = _replay(dev, ops), _replay(ref, ops)
    for i, (a, b) in enumerate(zip(out_d, out_r)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-6,
                                   err_msg=f"lookup #{i}")
    np.testing.assert_allclose(st_d.get_data(td), st_r.get_data(tr),
                               rtol=2e-5, atol=1e-6)
    np.testing.assert_array_equal(st_d.versions(td, np.arange(vocab)),
                                  st_r.versions(tr, np.arange(vocab)))
    for k in _PARITY_STATS:
        assert dev.stats[k] == ref.stats[k], (k, dev.stats, ref.stats)
    assert len(dev) == len(ref)


@pytest.mark.parametrize("policy", ["lru", "lfu"])
def test_device_cache_parity_vs_oracle(policy):
    """The PR 3 contract through begin→roundtrip→finish + device slab:
    values to float32-association tolerance; versions, counters and
    eviction decisions exact."""
    _assert_device_parity(policy=policy, seed=1)


def test_device_cache_parity_interpret_kernels():
    """Same oracle, with the REAL Pallas kernels (interpret mode)
    serving every value — the device gather and the scatter-add are the
    measured path, not the jnp fallbacks."""
    _assert_device_parity(seed=2, vocab=32, dim=4, limit=8, n_ops=7,
                          batch=5, scratch=16, interpret=True)


def test_device_capacity_overflow_served_via_scratch():
    """A batch whose unique keys exceed capacity serves the overflow
    through scratch rows — same values and decisions as the oracle's
    'served uncached' contract."""
    _assert_device_parity(seed=3, vocab=60, dim=4, limit=4,
                          batch=24, n_ops=15, scratch=64)


def test_device_scratch_exhausted_raises():
    st, t = _mk_store(64, 4)
    dev = DistCacheTable(st, t, limit=2, policy="lru", device=True,
                         device_scratch=2)
    with pytest.raises(RuntimeError, match="device_scratch"):
        dev.lookup(np.arange(16, dtype=np.int64))
    # the failed plan released the lock and left the cache consistent
    assert len(dev) == 0
    dev2 = DistCacheTable(st, t, limit=2, policy="lru", device=True,
                          device_scratch=32)
    out = dev2.lookup(np.arange(16, dtype=np.int64))
    assert out.shape == (16, 4)


def test_device_rejects_read_only():
    st, t = _mk_store(16, 4)
    with pytest.raises(NotImplementedError):
        DistCacheTable(st, t, device=True, read_only=True)


def test_apply_update_summed_matches_host_update():
    """The executor's pre-summed grad entry commits the same state as a
    host-mode occurrence-level update on the same batch."""
    ids = np.array([5, 7, 5, 9, 7, 5], np.int64)
    g = np.random.RandomState(5).randn(6, 4).astype(np.float32)
    st_a, ta = _mk_store(32, 4)
    st_b, tb = _mk_store(32, 4)
    host = DistCacheTable(st_a, ta, limit=8, push_bound=100)
    dev = DistCacheTable(st_b, tb, limit=8, push_bound=100, device=True)
    host.lookup(ids)
    dev.lookup(ids)
    host.update(ids, g)
    uk, inv, cnt = np.unique(ids, return_inverse=True,
                             return_counts=True)
    acc = np.zeros((uk.size, 4), np.float32)
    np.add.at(acc, inv, g)
    dev.apply_update_summed(uk, acc, cnt)
    np.testing.assert_array_equal(host._gcnt[host._find(uk)],
                                  dev._gcnt[dev._find(uk)])
    np.testing.assert_allclose(host._grad[host._find(uk)],
                               dev._grad[dev._find(uk)],
                               rtol=2e-5, atol=1e-6)
    assert host.stats["updates"] == dev.stats["updates"]


# --------------------------------------------------------- executor layer

def _build_exec(device, vocab=300, dim=8, batch=16, fields=4, seed=0,
                policy="lru"):
    store = EmbeddingStore()
    t = store.init_table(vocab, dim, opt="sgd", lr=0.05, seed=0,
                         init_scale=0.1)
    cache = DistCacheTable(store, t, limit=48, pull_bound=5,
                           push_bound=3, policy=policy, device=device,
                           device_scratch=vocab)
    ids = ht.placeholder_op("ids", dtype=np.int64)
    y_ = ht.placeholder_op("y")
    e = ht.ps_embedding_lookup_op(cache, ids, width=dim)
    flat = ht.array_reshape_op(e, (batch, fields * dim))
    w = ht.Variable("w", initializer=ht.init.GenXavierNormal(),
                    shape=(fields * dim, 1))
    prob = ht.sigmoid_op(ht.matmul_op(flat, w))
    loss = ht.reduce_mean_op(ht.binarycrossentropy_op(prob, y_), [0, 1])
    opt = ht.optim.SGDOptimizer(0.1)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)],
                      "eval": [prob]}, seed=seed)
    return ex, ids, y_, cache, store, t


def _batches(n, vocab=300, batch=16, fields=4, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, vocab, (batch, fields)).astype(np.int64),
             (rng.rand(batch, 1) > 0.5).astype(np.float32))
            for _ in range(n)]


def test_executor_device_vs_host_bitwise():
    """The acceptance core: training through the device-resident cache
    is BITWISE equal to the host cache — losses, final server table,
    versions, and every cache decision counter."""
    B = _batches(8)

    def run(device):
        ex, ids, y_, cache, store, t = _build_exec(device)
        losses = []
        for iv, yv in B:
            losses.append(float(ex.run(
                "train", feed_dict={ids: iv, y_: yv})[0].asnumpy()))
        cache.flush()
        return (losses, cache, store.get_data(t),
                store.versions(t, np.arange(300)))

    lh, ch, dh, vh = run(False)
    ld, cd, dd, vd = run(True)
    assert lh == ld
    np.testing.assert_array_equal(dh, dd)
    np.testing.assert_array_equal(vh, vd)
    for k in _PARITY_STATS:
        assert ch.stats[k] == cd.stats[k], (k, ch.stats, cd.stats)


def test_executor_device_async_bitwise():
    """run(sync=False) through the device cache: same losses, and the
    grad commit is a counted forced sync point."""
    B = _batches(5, seed=1)
    ex1, i1, y1, c1, _, _ = _build_exec(True, seed=1)
    ex2, i2, y2, c2, _, _ = _build_exec(True, seed=1)
    la = [float(ex1.run("train", feed_dict={i1: iv, y1: yv})[0]
                .asnumpy()) for iv, yv in B]
    before = hmetrics.run_plan_counts().get("async_sync_points", 0)
    lb = [float(ex2.run("train", feed_dict={i2: iv, y2: yv},
                        sync=False)[0].asnumpy()) for iv, yv in B]
    after = hmetrics.run_plan_counts().get("async_sync_points", 0)
    assert la == lb
    assert after >= before + len(B)     # PS grad commit forces the sync
    c1.flush()
    c2.flush()


def test_executor_device_eval_subgraph():
    B = _batches(3, seed=2)
    ex, ids, y_, cache, _, _ = _build_exec(True, seed=2)
    for iv, yv in B:
        ex.run("train", feed_dict={ids: iv, y_: yv})
    pv = ex.run("eval", feed_dict={ids: B[0][0]},
                convert_to_numpy_ret_vals=True)[0]
    assert pv.shape == (16, 1)
    assert np.isfinite(pv).all()
    cache.flush()


def test_device_miss_pull_overlap_trace():
    """Satellite: ``ps.miss_pull`` spans land on the feed-pipeline
    track, the flow arrow pairs into the consuming (main-thread) step,
    and the ``emb.gather`` / ``emb.scatter_add`` spans exist."""
    from hetu_tpu.obs.trace import TRACER
    B = _batches(4, seed=3)
    ex, ids, y_, cache, _, _ = _build_exec(True, seed=3)
    TRACER.enable(True)
    TRACER.clear()
    try:
        for iv, yv in B:
            ex.run("train", feed_dict={ids: iv, y_: yv})
    finally:
        TRACER.enable(False)
    tracks = dict(TRACER.tracks())
    by_name = {}
    for tid, r in TRACER.records():
        if r[0] in ("X", "s", "f"):
            by_name.setdefault(r[1], []).append((r[0], tracks.get(tid)))
    pulls = by_name.get("ps.miss_pull", [])
    assert any("feed-pipeline" in (t or "") for _, t in pulls), by_name
    flows = by_name.get("emb.miss_fill", [])
    starts = [t for k, t in flows if k == "s"]
    ends = [t for k, t in flows if k == "f"]
    assert len(starts) == len(ends) == len(B)
    assert all("feed-pipeline" in (t or "") for t in starts)
    assert all("feed-pipeline" not in (t or "") for t in ends)
    assert len(by_name.get("emb.gather", [])) == len(B)
    assert len(by_name.get("emb.scatter_add", [])) == len(B)
    cache.flush()


@pytest.mark.parametrize("dl_is_feed", [False, True])
def test_executor_device_dataloader_ids_consume_once(dl_is_feed):
    """Dataloader-fed ids advance the loader EXACTLY once per step in
    device mode — whether the loader is consumed only by the lookup
    (begin consumes) or also placed as a graph feed (begin PEEKS, the
    run plan consumes) — with host-mode loss parity on the same
    stream."""
    from hetu_tpu.data.dataloader import Dataloader, DataloaderOp
    vocab, dim, batch, steps = 200, 4, 8, 5
    rng = np.random.RandomState(7)
    ids_stream = rng.randint(0, vocab, (batch * (steps + 2), 1))
    yv = (rng.rand(batch, 1) > 0.5).astype(np.float32)

    def build(device):
        st = EmbeddingStore()
        t = st.init_table(vocab, dim, opt="sgd", lr=0.1, seed=0,
                          init_scale=0.1)
        dl = DataloaderOp([Dataloader(ids_stream, batch, "train")],
                          name="ids")
        y_ = ht.placeholder_op("y")
        cache = DistCacheTable(st, t, limit=64, pull_bound=5,
                               push_bound=3, device=device,
                               device_scratch=64)
        e = ht.ps_embedding_lookup_op(cache, dl, width=dim)
        flat = ht.array_reshape_op(e, (batch, dim))
        w = ht.Variable("w", initializer=ht.init.GenXavierNormal(),
                        shape=(dim, 1))
        loss = ht.reduce_mean_op(ht.binarycrossentropy_op(
            ht.sigmoid_op(ht.matmul_op(flat, w)), y_), [0, 1])
        opt = ht.optim.SGDOptimizer(0.1)
        fetches = [loss, opt.minimize(loss)]
        if dl_is_feed:
            fetches.append(dl)      # the run plan now places/consumes it
        ex = ht.Executor({"train": fetches}, seed=0)
        return ex, y_, dl, cache

    def run(device):
        ex, y_, dl, cache = build(device)
        losses = []
        for _ in range(steps):
            out = ex.run("train", feed_dict={y_: yv})
            losses.append(float(out[0].asnumpy()))
        cache.flush()
        return losses, dl.dataloaders["train"]._consumed

    lh, ch = run(False)
    ld, cd = run(True)
    assert cd == steps, (cd, steps)     # no double-consume
    assert ch == cd                     # host/device same position
    assert lh == ld                     # same batches -> bitwise losses


def test_executor_device_rejects_asp_and_ssp():
    B = _batches(1, seed=4)
    for bsp in (-1, 1):
        store = EmbeddingStore()
        t = store.init_table(64, 4, opt="sgd", lr=0.05, seed=0,
                             init_scale=0.1)
        cache = DistCacheTable(store, t, limit=16, device=True)
        ids = ht.placeholder_op("ids", dtype=np.int64)
        y_ = ht.placeholder_op("y")
        e = ht.ps_embedding_lookup_op(cache, ids, width=4)
        flat = ht.array_reshape_op(e, (16, 4 * 4))
        w = ht.Variable("w", initializer=ht.init.GenXavierNormal(),
                        shape=(16, 1))
        loss = ht.reduce_mean_op(ht.binarycrossentropy_op(
            ht.sigmoid_op(ht.matmul_op(flat, w)), y_), [0, 1])
        opt = ht.optim.SGDOptimizer(0.1)
        ex = ht.Executor({"train": [loss, opt.minimize(loss)]},
                         seed=0, bsp=bsp)
        with pytest.raises(NotImplementedError, match="BSP"):
            ex.run("train", feed_dict={ids: B[0][0] % 64, y_: B[0][1]})


def test_device_cache_moves_only_miss_rows_across_the_host_boundary():
    """What the device slab buys, as rows: host mode materialises and
    transfers every looked-up occurrence every step, device mode only the
    rows it PULLED (misses and refreshes) — fewer once the cache is warm,
    and never more.  The kernels' fallback reasons are counted."""
    hmetrics.reset_emb_pallas_fallbacks()
    ex, ids, y_, cache, _store, _t = _build_exec(True)
    B = _batches(6)
    host_rows = 0
    for iv, yv in B:
        ex.run("train", feed_dict={ids: iv, y_: yv})
        host_rows += iv.size
    cache.flush()
    perf = cache.perf()
    assert perf["lookups"] == host_rows
    assert 0 < perf["fetches"] < perf["lookups"]
    assert 0.0 < perf["hit_rate"] < 1.0
    # off the TPU the Pallas kernels are not the path; a dispatch that is
    # traced here counts that as its reason, and no other reason appears
    assert set(hmetrics.emb_pallas_fallback_counts()) <= {
        "gather:backend_cpu", "scatter_add:backend_cpu"}


@pytest.mark.slow
def test_device_cache_zipf_scale_slow():
    """Scale proof (slow): a 10^5-row zipf stream through the device
    cache — warm hit rate materializes, parity oracle holds on a
    sampled prefix, and the slab serves every value."""
    vocab, dim, limit = 100000, 16, 10000
    rng = np.random.RandomState(0)
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** 1.05
    cdf = np.cumsum(p / p.sum())
    st, t = _mk_store(vocab, dim)
    dev = DistCacheTable(st, t, limit=limit, pull_bound=100,
                         push_bound=10, policy="lfu", device=True,
                         device_scratch=vocab)
    n_rows = 0
    for i in range(50):
        ids = np.searchsorted(cdf, rng.rand(2000)).astype(np.int64)
        rows = dev.lookup(ids)
        assert rows.shape == (2000, dim)
        dev.update(ids, np.full((2000, dim), 1e-3, np.float32))
        n_rows += 2000
    perf = dev.perf()
    assert perf["lookups"] == n_rows
    # warm working set: a solid hit rate despite the occurrence-counted
    # pull_bound staleness clock (hot keys deliberately re-pull), and —
    # the device-mode point — the rows that CROSS the host boundary
    # (fetches) are a fraction of the rows served
    assert perf["hit_rate"] > 0.3, perf
    assert perf["fetches"] < 0.4 * perf["lookups"], perf
    dev.flush()
