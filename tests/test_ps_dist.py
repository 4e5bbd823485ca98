"""Multi-host PS tests: 2 real processes, TCP-routed key ownership
(reference ``tests/pstests/test_apis.py:22`` pattern — multiprocessing
spawn of server/worker roles, numeric push/pull checks)."""
import multiprocessing as mp
import traceback

import numpy as np
import pytest

from scenarios import free_ports as _free_ports


def _child(rank, ports, barrier, errq):
    try:
        import jax
        jax.config.update("jax_platforms", "cpu")
        from hetu_tpu.ps.dist_store import DistributedStore, DistCacheTable

        world = 2
        endpoints = [("127.0.0.1", p) for p in ports]
        store = DistributedStore(rank, world, endpoints,
                                 port=ports[rank])
        tid = store.init_table(10, 4, opt="sgd", lr=1.0, init_scale=0)
        barrier.wait()

        # --- cross-process push: rank0 pushes keys owned by rank1 ---------
        if rank == 0:
            g = np.ones((2, 4), np.float32) * np.asarray([[1.0], [3.0]])
            store.push(tid, np.asarray([1, 3]), g)   # 1,3 owned by rank1
        barrier.wait()
        if rank == 1:
            rows = store.pull(tid, np.asarray([1, 3]))   # local pull
            np.testing.assert_allclose(rows[0], -1.0 * np.ones(4))
            np.testing.assert_allclose(rows[1], -3.0 * np.ones(4))
        barrier.wait()

        # --- cross-process pull: rank1 pulls keys owned by rank0 ----------
        if rank == 1:
            rows = store.pull(tid, np.asarray([0, 2]))
            np.testing.assert_allclose(rows, 0.0)
            store.push(tid, np.asarray([0]), np.full((1, 4), 2.0, np.float32))
        barrier.wait()
        if rank == 0:
            row = store.pull(tid, np.asarray([0]))[0]
            np.testing.assert_allclose(row, -2.0 * np.ones(4))
            # versions: key 0 (local) updated once; key 1 (remote) once
            v = store.versions(tid, np.asarray([0, 1]))
            assert list(v) == [1, 1], v
        barrier.wait()

        # --- ASP async push with flush barrier ----------------------------
        if rank == 0:
            store.push_async(tid, np.asarray([5]),
                             np.full((1, 4), 1.0, np.float32))  # 5 -> rank1
            store.flush()
        barrier.wait()
        if rank == 1:
            row = store.pull(tid, np.asarray([5]))[0]
            np.testing.assert_allclose(row, -1.0 * np.ones(4))
        barrier.wait()

        # --- SSP clocks on rank 0 ------------------------------------------
        store.ssp_init(2) if rank == 0 else None
        barrier.wait()
        store.clock()
        assert store.ssp_sync(staleness=1, timeout_ms=5000)
        barrier.wait()

        # --- HET cache staleness across hosts ------------------------------
        cache = DistCacheTable(store, tid, pull_bound=3, push_bound=2)
        if rank == 0:
            v0 = cache.lookup([7])[0].copy()        # 7 owned by rank1
        barrier.wait()
        if rank == 1:
            store.push(tid, np.asarray([7]), np.full((1, 4), 4.0, np.float32))
        barrier.wait()
        if rank == 0:
            # within pull_bound: stale value served from cache
            v1 = cache.lookup([7])[0]
            np.testing.assert_allclose(v1, v0)
            assert cache.stats["hits"] >= 1
            cache.lookup([7])                        # use #3 exhausts bound
            v2 = cache.lookup([7])[0]                # forced refresh
            np.testing.assert_allclose(v2, v0 - 4.0)
            # push_bound: first update cached, second triggers the push
            cache.update([7], np.full((1, 4), 0.5, np.float32))
            before = store.pull(tid, np.asarray([7]))[0]
            np.testing.assert_allclose(before, v2)   # not pushed yet
            cache.update([7], np.full((1, 4), 0.5, np.float32))
            after = store.pull(tid, np.asarray([7]))[0]
            np.testing.assert_allclose(after, v2 - 1.0)
        barrier.wait()
        store.close()
    except Exception:
        errq.put(f"rank {rank}:\n{traceback.format_exc()}")
        try:
            barrier.abort()
        except Exception:
            pass


@pytest.mark.timeout(180)
def test_two_process_routing():
    ctx = mp.get_context("spawn")
    ports = _free_ports(2)
    barrier = ctx.Barrier(2)
    errq = ctx.Queue()
    procs = [ctx.Process(target=_child, args=(r, ports, barrier, errq))
             for r in range(2)]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=150)
    errors = []
    while not errq.empty():
        errors.append(errq.get())
    for p in procs:
        if p.is_alive():
            p.terminate()
            errors.append("child hung")
    assert not errors, "\n".join(errors)
    assert all(p.exitcode == 0 for p in procs)


# ------------------------------------------------- preduce over SSP clocks

def _preduce_child(rank, ports, barrier, errq):
    try:
        import time
        import jax
        jax.config.update("jax_platforms", "cpu")
        from hetu_tpu.ps.dist_store import DistributedStore
        from hetu_tpu.parallel.preduce import DistPartialReduce

        world = 2
        store = DistributedStore(rank, world,
                                 [("127.0.0.1", p) for p in ports],
                                 port=ports[rank])
        if rank == 0:
            store.ssp_init(world)
        barrier.wait()
        pr = DistPartialReduce(store, max_wait_ms=400.0, min_workers=1)

        # --- step 0: both workers arrive promptly -> full mask ------------
        pr.report_arrival(rank, 0)
        mask = pr.get_partner(rank, 0)
        np.testing.assert_allclose(mask, [1.0, 1.0])
        barrier.wait()

        # --- step 1: rank 1 straggles past rank 0's window ----------------
        if rank == 0:
            pr.report_arrival(rank, 1)
            mask = pr.get_partner(rank, 1)      # waits <=400ms, alone
            np.testing.assert_allclose(mask, [1.0, 0.0])
        else:
            time.sleep(0.9)                     # past the window
            pr.report_arrival(rank, 1)
            mask = pr.get_partner(rank, 1)      # rank0 already arrived
            np.testing.assert_allclose(mask, [1.0, 1.0])
        barrier.wait()
        store.close()
    except Exception:
        errq.put(f"rank {rank}:\n{traceback.format_exc()}")
        try:
            barrier.abort()
        except Exception:
            pass


@pytest.mark.timeout(180)
def test_preduce_partner_from_dist_clocks():
    """The docstring promise (preduce.py) as code: PartialReduce group
    formation fed by the distributed store's SSP clock arrivals across 2
    real processes (reference preduce_get_partner / preduce_handler.h)."""
    ctx = mp.get_context("spawn")
    ports = _free_ports(2)
    barrier = ctx.Barrier(2)
    errq = ctx.Queue()
    procs = [ctx.Process(target=_preduce_child,
                         args=(r, ports, barrier, errq))
             for r in range(2)]
    for p in procs:
        p.start()
    for p in procs:
        p.join(timeout=150)
    errors = []
    while not errq.empty():
        errors.append(errq.get())
    for p in procs:
        if p.is_alive():
            p.terminate()
            errors.append("child hung")
    assert not errors, "\n".join(errors)
    assert all(p.exitcode == 0 for p in procs)


# ------------------------------------------ transport failure diagnostics

def _victim_child(rank, ports, barrier):
    """Rank-1 server that dies (hard) mid-run after the first barrier."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from hetu_tpu.ps.dist_store import DistributedStore
    store = DistributedStore(rank, 2, [("127.0.0.1", p) for p in ports],
                             port=ports[rank])
    store.init_table(10, 4, opt="sgd", lr=1.0, init_scale=0)
    barrier.wait()      # parent does one healthy pull
    barrier.wait()      # parent says: time to die
    import os
    os._exit(1)         # hard death: no close(), sockets reset


@pytest.mark.timeout(120)
def test_dead_peer_raises_clean_diagnostic():
    """Kill one server mid-run: the next RPC to it must raise a RuntimeError
    naming the peer within the bounded retry budget — not a raw OSError and
    not a hang inside a blocking recv (round-3 verdict item 5; reference
    transport resilience ``ps-lite/src/resender.h``)."""
    import time as _time
    from hetu_tpu.ps.dist_store import DistributedStore

    ctx = mp.get_context("spawn")
    ports = _free_ports(2)
    barrier = ctx.Barrier(2)
    victim = ctx.Process(target=_victim_child, args=(1, ports, barrier))
    victim.start()
    store = DistributedStore(0, 2, [("127.0.0.1", p) for p in ports],
                             port=ports[0], rpc_timeout=3.0, rpc_retries=2,
                             connect_timeout=3.0)
    tid = store.init_table(10, 4, opt="sgd", lr=1.0, init_scale=0)
    try:
        barrier.wait(timeout=60)
        # healthy: key 1 lives on rank 1
        rows = store.pull(tid, np.asarray([1]))
        np.testing.assert_allclose(rows, 0.0)
        barrier.wait(timeout=60)     # victim exits hard now
        victim.join(timeout=30)
        t0 = _time.monotonic()
        with pytest.raises(RuntimeError, match="peer 1 .*unreachable"):
            for _ in range(3):       # first recv may see a clean reset
                store.pull(tid, np.asarray([1]))
        assert _time.monotonic() - t0 < 30, "diagnostic took too long"
        # healthy shard still answers
        np.testing.assert_allclose(store.pull(tid, np.asarray([0])), 0.0)
    finally:
        if victim.is_alive():
            victim.terminate()
        store.close()


# ------------------------------------------ replicated cross-process failover

def _repl_victim_child(rank, ports, barrier):
    """Replicated rank-1 server that dies HARD after seeding + serving."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from hetu_tpu.ps.dist_store import DistributedStore
    store = DistributedStore(rank, 2, [("127.0.0.1", p) for p in ports],
                             port=ports[rank], replication=2)
    barrier.wait()      # both servers bound: replica inits can land
    store.init_table(16, 4, opt="sgd", lr=1.0, init_scale=0)
    barrier.wait()      # parent seeds + pushes through us
    barrier.wait()      # parent says: time to die
    import os
    os._exit(1)         # hard death: no close(), sockets reset


@pytest.mark.timeout(120)
def test_replicated_failover_across_real_processes():
    """ISSUE 4 across REAL process boundaries: rank 1 (a replicated
    primary) dies hard mid-run; the surviving rank's next ops to that
    shard promote its own in-process backup and serve the SAME bytes —
    no restart, no checkpoint, no raised error."""
    from hetu_tpu.metrics import fault_counts, reset_faults
    from hetu_tpu.ps.dist_store import DistributedStore

    reset_faults()
    ctx = mp.get_context("spawn")
    ports = _free_ports(2)
    barrier = ctx.Barrier(2)
    victim = ctx.Process(target=_repl_victim_child, args=(1, ports, barrier))
    victim.start()
    store = DistributedStore(0, 2, [("127.0.0.1", p) for p in ports],
                             port=ports[0], rpc_timeout=3.0, rpc_retries=2,
                             connect_timeout=3.0, replication=2)
    try:
        barrier.wait(timeout=60)    # both servers bound
        tid = store.init_table(16, 4, opt="sgd", lr=1.0, init_scale=0)
        barrier.wait(timeout=60)    # both tables (and replicas) exist
        table = np.arange(64, dtype=np.float32).reshape(16, 4)
        store.set_data(tid, table)      # replicated seed, both processes
        # cross-process push onto rank 1's shard (forwarded to OUR backup)
        store.push(tid, np.asarray([1, 3]), np.ones((2, 4), np.float32))
        expected = store.pull(tid, np.arange(16))
        barrier.wait(timeout=60)        # victim exits hard now
        victim.join(timeout=30)
        got = store.pull(tid, np.arange(16))    # transparent failover
        np.testing.assert_array_equal(got, expected)
        # and shard-1 mutations keep applying on the promoted backup
        store.push(tid, np.asarray([1]), np.ones((1, 4), np.float32))
        np.testing.assert_allclose(store.pull(tid, np.asarray([1]))[0],
                                   expected[1] - 1.0)
        fc = fault_counts()
        assert fc.get("ps_failover_promoted", 0) >= 1
        assert store._route[1] == 0
    finally:
        if victim.is_alive():
            victim.terminate()
        store.close()


def test_clock_channels_are_independent():
    """The executor's SSP loop (channel 0) and preduce arrivals (channel 1)
    must not share a clock vector (round-3 advisor finding)."""
    from hetu_tpu.ps.dist_store import DistributedStore
    from hetu_tpu.parallel.preduce import DistPartialReduce

    store = DistributedStore(0, 1)
    try:
        store.ssp_init(1)                       # executor channel
        pr = DistPartialReduce(store, n_workers=1, max_wait_ms=50.0,
                               min_workers=1)
        for _ in range(5):
            store.clock()                       # executor ticks 5 steps
        np.testing.assert_array_equal(store.clocks(), [5])
        np.testing.assert_array_equal(store.clocks(channel=pr.CHANNEL), [0])
        pr.report_arrival(0, 0)
        mask = pr.get_partner(0, 0)             # step 0: clock 1 >= 1
        np.testing.assert_allclose(mask, [1.0])
        # executor's 5 ticks did NOT leak into preduce arrivals
        np.testing.assert_array_equal(store.clocks(channel=pr.CHANNEL), [1])
        # step 4 has NOT arrived on the preduce channel (would have under
        # the shared-vector bug, where clocks()==5 fakes arrival)
        assert (store.clocks(channel=pr.CHANNEL) >= 5).sum() == 0
    finally:
        store.close()
