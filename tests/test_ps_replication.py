"""Live PS shard replication (ISSUE 4): seq-ordered op-log forwarding
keeps primary/backup bitwise identical (optimizer moments included),
client-side failover promotes the backup transparently inside one RPC,
the promotion-window retry of an ack'd-then-died push stays exactly-once,
re-replication restores redundancy onto a relaunched standby so a SECOND
failure is survivable, heartbeat liveness survives rank-0 death, and
``tools/ps_fsck.py --verify`` detects real divergence on a live cluster.

Everything here is in-process multi-rank (2–3 server threads in one
pytest process) so the whole file stays tier-1 cheap; the real
two-process failover lives in test_ps_dist.py and the end-to-end
training acceptance in ``scenarios.failover_scenario`` (run here
too)."""
import os
import socket
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))          # repo root: tools import

from hetu_tpu import chaos
from hetu_tpu.metrics import fault_counts, reset_faults
from hetu_tpu.ps.dist_store import (DistributedStore, OP_PUSH,
                                    _next_backoff)


@pytest.fixture(autouse=True)
def _clean_chaos_and_counters():
    chaos.uninstall()
    reset_faults()
    yield
    chaos.uninstall()
    reset_faults()


def _free_ports(n):
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _cluster(world=3, rows=48, width=8, opt="sgd", lr=0.1, ports=None,
             **kw):
    """``world`` in-process replicated stores sharing one table seeded
    through the REPLICATED set_data path."""
    ports = ports or _free_ports(world)
    endpoints = [("127.0.0.1", p) for p in ports]
    kw.setdefault("rpc_timeout", 5.0)
    kw.setdefault("rpc_retries", 2)
    kw.setdefault("connect_timeout", 2.0)
    stores = [DistributedStore(r, world, endpoints, port=ports[r],
                               replication=2, **kw) for r in range(world)]
    tid = None
    for s in stores:
        tid = s.init_table(rows, width, opt=opt, lr=lr, init_scale=0.0)
    table = np.random.RandomState(42).normal(
        0, 0.01, (rows, width)).astype(np.float32)
    stores[0].set_data(tid, table)
    return stores, tid, ports


def _close_all(stores):
    for s in stores:
        try:
            s.close()
        except Exception:
            pass


def _assert_replicas_equal(client, tid, world, shards=None):
    for s in shards or range(world):
        a = client.table_checksum(tid, s, rank=s)
        b = client.table_checksum(tid, s, rank=(s + 1) % world)
        assert a == b, f"shard {s} diverged"


# ------------------------------------------------ replica bitwise parity

def test_replicated_init_and_set_data_parity():
    stores, tid, _ = _cluster()
    try:
        _assert_replicas_equal(stores[0], tid, 3)
    finally:
        _close_all(stores)


def test_oplog_forwarding_keeps_adam_moments_identical():
    """Pushes from every client (duplicate keys included) — both copies
    of every shard must agree bitwise, INCLUDING the adam moment slabs
    and step counters (a backup with zeroed moments would silently
    diverge after promotion)."""
    stores, tid, _ = _cluster(opt="adam", lr=0.01)
    try:
        rng = np.random.RandomState(0)
        for i in range(6):
            ids = rng.randint(0, 48, 32)
            g = rng.standard_normal((32, 8)).astype(np.float32) * 0.1
            stores[i % 3].push(tid, ids, g)
        _assert_replicas_equal(stores[0], tid, 3)
    finally:
        _close_all(stores)


def test_fused_push_pull_rides_the_oplog():
    stores, tid, _ = _cluster()
    try:
        rng = np.random.RandomState(1)
        for _ in range(4):
            keys = np.unique(rng.randint(0, 48, 16))
            g = rng.standard_normal((keys.size, 8)).astype(np.float32)
            stores[0].push_pull(tid, keys, g, np.arange(48))
        _assert_replicas_equal(stores[0], tid, 3)
    finally:
        _close_all(stores)


def test_replication1_is_unchanged_and_counter_free():
    """The default topology must behave exactly as before this PR: no
    replica stores, no forwarding, and a clean run records NO failover/
    replication counters (the acceptance criterion's empty-counter
    half)."""
    ports = _free_ports(2)
    endpoints = [("127.0.0.1", p) for p in ports]
    stores = [DistributedStore(r, 2, endpoints, port=ports[r],
                               rpc_timeout=5.0, rpc_retries=2,
                               connect_timeout=2.0) for r in range(2)]
    try:
        tid = None
        for s in stores:
            tid = s.init_table(16, 4, opt="sgd", lr=1.0, init_scale=0.0)
        assert stores[0].replication == 1
        assert len(stores[0].server._stores) == 1
        stores[0].push(tid, np.asarray([1, 2]), np.ones((2, 4), np.float32))
        np.testing.assert_allclose(
            stores[1].pull(tid, np.asarray([1]))[0], -1.0)
    finally:
        _close_all(stores)
    fc = fault_counts()
    for k in fc:
        assert "failover" not in k and "repl" not in k \
            and "promote" not in k, fc


def test_replication_env_knob(monkeypatch):
    monkeypatch.setenv("HETU_PS_REPLICATION", "2")
    ports = _free_ports(2)
    endpoints = [("127.0.0.1", p) for p in ports]
    stores = [DistributedStore(r, 2, endpoints, port=ports[r])
              for r in range(2)]
    try:
        assert all(s.replication == 2 for s in stores)
        assert all(len(s.server._stores) == 2 for s in stores)
    finally:
        _close_all(stores)
    with pytest.raises(ValueError, match="replication"):
        DistributedStore(0, 2, replication=3)
    # world=1 has nowhere to put a backup: degrade, don't crash
    s = DistributedStore(0, 1, replication=2)
    try:
        assert s.replication == 1
    finally:
        s.close()


# ----------------------------------------------------- transparent failover

def test_failover_transparent_pull_push_and_versions():
    """Kill shard 1's primary: the next op promotes the backup inside the
    failing call — same values, zero raised errors, counters prove what
    happened."""
    stores, tid, _ = _cluster()
    try:
        expected = stores[0].pull(tid, np.arange(48))
        vexpected = stores[0].versions(tid, np.arange(48))
        stores[1].server.stop()
        got = stores[0].pull(tid, np.arange(48))
        np.testing.assert_array_equal(got, expected)
        np.testing.assert_array_equal(
            stores[0].versions(tid, np.arange(48)), vexpected)
        # mutations keep flowing through the promoted replica
        stores[0].push(tid, np.asarray([1, 4]), np.ones((2, 8), np.float32))
        row = stores[0].pull(tid, np.asarray([1]))[0]
        np.testing.assert_allclose(row, expected[1] - 0.1)  # sgd lr=0.1
        fc = fault_counts()
        assert fc.get("ps_failover", 0) >= 1
        assert fc.get("ps_promoted", 0) >= 1
        assert fc.get("ps_failover_promoted", 0) >= 1
        assert stores[0]._route[1] == 2
        assert 1 in stores[0]._failed_over
    finally:
        _close_all(stores)


def test_failover_of_both_copies_raises_diagnosable():
    stores, tid, _ = _cluster()
    try:
        stores[1].server.stop()
        stores[2].server.stop()     # primary AND backup of shard 1 gone
        with pytest.raises(RuntimeError,
                           match="shard 1.*unreachable AND backup"):
            stores[0].pull(tid, np.asarray([1]))
        assert fault_counts().get("ps_failover_failed", 0) >= 1
    finally:
        _close_all(stores)


def test_promotion_refuses_half_initialised_standby():
    """A standby that never got the replica tables must NOT be promoted —
    serving a fresh-seeded empty copy would silently corrupt training."""
    stores, tid, ports = _cluster()
    try:
        stores[1].server.stop()
        stores[2].server.stop()
        standby = DistributedStore(2, 3,
                                   [("127.0.0.1", p) for p in ports],
                                   port=ports[2], rpc_timeout=5.0,
                                   rpc_retries=2, connect_timeout=2.0,
                                   replication=2, standby=True)
        stores.append(standby)
        with pytest.raises(RuntimeError, match="not promotable"):
            stores[0].pull(tid, np.asarray([1]))
    finally:
        _close_all(stores)


# --------------------------------------- promotion-window exactly-once

def test_promotion_window_retry_is_exactly_once():
    """THE replication correctness corner: a push the primary applied,
    forwarded, and ack'd — then died before the client saw the ack.  The
    client's retry lands on the promoted backup with the SAME (client,
    seq); the backup's dedup window (populated by the forwarded op-log
    frame) must skip the re-apply."""
    stores, tid, _ = _cluster()
    try:
        before = stores[0].pull(tid, np.asarray([1]))[0].copy()
        keys = np.asarray([1], np.int64)
        grads = np.ones((1, 8), np.float32)
        seq = next(stores[0]._seq)
        # the push: applied on primary rank 1, forwarded to backup rank 2,
        # ack'd (we receive it — the 'lost ack' is simulated by retrying
        # anyway, exactly what the transport does when the ack frame dies
        # on the wire)
        stores[0]._rpc(1, OP_PUSH, tid, keys, grads.tobytes(), 0.1, 8,
                       shard=1, seq=seq)
        stores[1].server.stop()                  # primary dies post-ack
        alt = stores[0]._failover(1)
        assert alt == 2
        # the retried frame: same seq, promoted backup, stamped with the
        # epoch the promotion ack taught the client (what _rpc_shard's
        # retry does — a stale-epoch retry would be fenced, not deduped)
        stores[0]._rpc(alt, OP_PUSH, tid, keys, grads.tobytes(), 0.1, 8,
                       shard=1, seq=seq, epoch=stores[0]._epoch[1])
        after = stores[0].pull(tid, np.asarray([1]))[0]
        np.testing.assert_allclose(after, before - 0.1)  # once, not twice
    finally:
        _close_all(stores)


def test_chaos_dup_frames_straddling_failover_stay_exactly_once():
    """dup=1.0 doubles every frame while a kill straddles the run: the
    grand total applied to the (surviving) replica must equal every push
    applied exactly once."""
    stores, tid, _ = _cluster()
    try:
        key = np.asarray([1], np.int64)          # shard 1
        start = stores[0].pull(tid, key)[0].copy()
        chaos.install(chaos.ChaosInjector.from_spec("5:dup=1.0"))
        n_pushes = 6
        for i in range(n_pushes):
            stores[0].push(tid, key, np.ones((1, 8), np.float32))
            if i == 2:
                stores[1].server.stop()          # mid-stream failover
        chaos.uninstall()
        after = stores[0].pull(tid, key)[0]
        # float32 sequential accumulation vs one float64 product: allow
        # rounding; a double-applied push would be off by a full 0.1
        np.testing.assert_allclose(after, start - 0.1 * n_pushes,
                                   atol=1e-5)
        assert fault_counts().get("chaos_dup", 0) >= n_pushes
        assert fault_counts().get("ps_failover_promoted", 0) == 1
    finally:
        chaos.uninstall()
        _close_all(stores)


def test_chaos_drop_retries_across_failover_stay_exactly_once():
    stores, tid, _ = _cluster(rpc_retries=8)
    try:
        key = np.asarray([4], np.int64)          # shard 1
        start = stores[0].pull(tid, key)[0].copy()
        chaos.install(chaos.ChaosInjector.from_spec("21:drop=0.35"))
        n_pushes = 6
        for i in range(n_pushes):
            stores[0].push(tid, key, np.ones((1, 8), np.float32))
            if i == 2:
                stores[1].server.stop()
        chaos.uninstall()
        after = stores[0].pull(tid, key)[0]
        np.testing.assert_allclose(after, start - 0.1 * n_pushes,
                                   atol=1e-5)
    finally:
        chaos.uninstall()
        _close_all(stores)


# ------------------------------------------------------- re-replication

def test_re_replication_restores_redundancy_for_second_failure():
    """Failover shard 1 → relaunch a standby at the dead rank →
    re_replicate (snapshot + op-log catch-up) → bitwise parity between
    the promoted server and the standby → kill the promoted server too:
    the SECOND failover serves the same bits.  PR 2 could only answer
    this with restart+resume; this is the tentpole's whole point."""
    stores, tid, ports = _cluster()
    standby = None
    try:
        rng = np.random.RandomState(3)
        stores[1].server.stop()
        # failover + post-failover traffic the standby must catch up on
        stores[0].push(tid, rng.randint(0, 48, 16),
                       rng.standard_normal((16, 8)).astype(np.float32))
        assert 1 in stores[0]._failed_over
        standby = DistributedStore(1, 3,
                                   [("127.0.0.1", p) for p in ports],
                                   port=ports[1], rpc_timeout=5.0,
                                   rpc_retries=2, connect_timeout=2.0,
                                   replication=2, standby=True)
        assert not standby.server.serves(1)      # standby serves nothing
        stores[0].re_replicate(1)
        assert 1 not in stores[0]._failed_over
        # promoted copy (rank 2) and the re-attached standby agree
        a = stores[0].table_checksum(tid, 1, rank=2)
        b = stores[0].table_checksum(tid, 1, rank=1)
        assert a == b
        # live forwarding resumed: new pushes land on BOTH
        stores[0].push(tid, np.asarray([7]), np.ones((1, 8), np.float32))
        assert stores[0].table_checksum(tid, 1, rank=2) \
            == stores[0].table_checksum(tid, 1, rank=1)
        # second failure: the promoted ex-backup dies; the standby serves
        expected = stores[0].pull(tid, np.arange(48))
        stores[2].server.stop()
        got = stores[0].pull(tid, np.arange(48))
        np.testing.assert_array_equal(got, expected)
        assert stores[0]._route[1] == 1
        assert fault_counts().get("ps_re_replicated", 0) >= 1
    finally:
        _close_all(stores + ([standby] if standby else []))


def test_maybe_re_replicate_defers_then_repairs():
    stores, tid, ports = _cluster()
    standby = None
    try:
        stores[1].server.stop()
        stores[0].pull(tid, np.asarray([1]))     # trigger failover
        assert stores[0].maybe_re_replicate() is False   # target dead
        assert fault_counts().get("ps_re_replicate_deferred", 0) >= 1
        standby = DistributedStore(1, 3,
                                   [("127.0.0.1", p) for p in ports],
                                   port=ports[1], rpc_timeout=5.0,
                                   rpc_retries=2, connect_timeout=2.0,
                                   replication=2, standby=True)
        assert stores[0].maybe_re_replicate() is True
        assert stores[0].table_checksum(tid, 1, rank=2) \
            == stores[0].table_checksum(tid, 1, rank=1)
    finally:
        _close_all(stores + ([standby] if standby else []))


def test_backup_loss_degrades_then_repairs():
    """Killing a BACKUP must not disturb serving: the primary's forward
    fails once (counter), traffic continues, and maybe_re_replicate
    re-attaches a standby at the backup slot."""
    stores, tid, ports = _cluster()
    standby = None
    try:
        # rank 1 holds shard 0's backup
        stores[1].server.stop()
        with pytest.warns(RuntimeWarning, match="UNREPLICATED"):
            stores[0].push(tid, np.asarray([0]),
                           np.ones((1, 8), np.float32))
        assert fault_counts().get("repl_forward_failed", 0) >= 1
        assert fault_counts().get("ps_failover", 0) == 0  # no failover!
        standby = DistributedStore(1, 3,
                                   [("127.0.0.1", p) for p in ports],
                                   port=ports[1], rpc_timeout=5.0,
                                   rpc_retries=2, connect_timeout=2.0,
                                   replication=2, standby=True)
        assert stores[0].maybe_re_replicate() is True
        assert stores[0].table_checksum(tid, 0, rank=0) \
            == stores[0].table_checksum(tid, 0, rank=1)
    finally:
        _close_all(stores + ([standby] if standby else []))


def test_standby_self_initialised_tables_are_not_promotable():
    """The table-count guard alone can't tell synced-from-primary from
    freshly-seed-initialized: a standby whose own training script calls
    init_table has the right COUNT but step-0 data.  Promoting it would
    silently reset the shard — it must refuse until an OP_SYNC snapshot
    actually lands."""
    stores, tid, ports = _cluster()
    standby = None
    try:
        stores[1].server.stop()
        stores[0].pull(tid, np.asarray([1]))     # failover to rank 2
        standby = DistributedStore(1, 3,
                                   [("127.0.0.1", p) for p in ports],
                                   port=ports[1], rpc_timeout=5.0,
                                   rpc_retries=2, connect_timeout=2.0,
                                   replication=2, standby=True)
        # the standby's own script re-creates the table locally: right
        # count, seed data (no sync has run)
        standby.init_table(48, 8, opt="sgd", lr=0.1, init_scale=0.0)
        stores[2].server.stop()                  # now BOTH copies die
        with pytest.raises(RuntimeError, match="never "):
            stores[0].pull(tid, np.asarray([1]))
    finally:
        _close_all(stores + ([standby] if standby else []))


def test_post_failover_save_covers_adopted_shard(tmp_path):
    """After a failover the promoted server must checkpoint the shard it
    adopted — shard files are named by SHARD and written for every
    SERVED shard, so a full-state save/restore round-trips through a
    failover (the supervisor fallback path stays consistent)."""
    stores, tid, ports = _cluster()
    restored = None
    try:
        stores[1].server.stop()
        expected = stores[2].pull(tid, np.arange(48))   # rank2 promotes s1
        base = str(tmp_path / "ps.bin")
        for r in (0, 2):
            stores[r].save(tid, base)
        # rank 2 now serves shards 1 AND 2: both files must exist
        for s in range(3):
            assert (tmp_path / f"ps.bin.shard{s}").exists(), s
        # restore into a FRESH replication=1 cluster: all three shards
        ports2 = _free_ports(3)
        eps2 = [("127.0.0.1", p) for p in ports2]
        restored = [DistributedStore(r, 3, eps2, port=ports2[r],
                                     rpc_timeout=5.0, rpc_retries=2,
                                     connect_timeout=2.0)
                    for r in range(3)]
        for s in restored:
            s.init_table(48, 8, opt="sgd", lr=0.1, init_scale=0.0)
            s.load(tid, base)
        np.testing.assert_array_equal(
            restored[0].pull(tid, np.arange(48)), expected)
    finally:
        _close_all(stores + (restored or []))


def test_ssp_clocks_survive_rank0_death():
    """The scheduler's OTHER state: SSP clock vectors ride shard 0's
    replication like the heartbeat table, so clock()/clocks()/ssp_sync()
    keep answering (with the pre-kill ticks intact) after rank 0 dies."""
    stores, tid, _ = _cluster()
    try:
        stores[0].ssp_init(3)
        stores[1].clock(worker=1)
        stores[1].clock(worker=1)
        stores[2].clock(worker=2)
        stores[0].server.stop()
        # rank 1's client fails over shard 0 and reads the MIRRORED vector
        np.testing.assert_array_equal(stores[1].clocks(), [0, 2, 1])
        stores[1].clock(worker=0)                # ticks keep landing
        np.testing.assert_array_equal(stores[1].clocks(), [1, 2, 1])
        assert stores[2].ssp_sync(worker=2, staleness=2, timeout_ms=5000)
    finally:
        _close_all(stores)


# ------------------------------------------- liveness survives rank 0

def test_heartbeat_mirror_survives_rank0_death():
    """Satellite: the failure detector must not be a single point of
    failure.  Heartbeats mirrored to shard 0's backup keep alive_mask
    answering (via failover) after rank 0 dies."""
    stores, tid, _ = _cluster()
    try:
        stores[1].heartbeat(rank=1, step=5)
        stores[2].heartbeat(rank=2, step=5)
        stores[0].server.stop()                  # the scheduler role dies
        # rank 2's client fails over shard 0 to rank 1 and reads the
        # MIRRORED liveness table: ranks 1 and 2 pinged recently
        mask = stores[2].alive_mask(5000)
        np.testing.assert_array_equal(mask[1:], [1, 1])
        assert fault_counts().get("ps_failover_promoted", 0) >= 1
        # and heartbeats keep landing on the promoted copy
        stores[2].heartbeat(rank=2, step=6)
        assert stores[2].alive_mask(5000)[2] == 1
    finally:
        _close_all(stores)


# ---------------------------------------------------------- ps_fsck

def test_ps_fsck_clean_and_divergence_detection():
    from tools.ps_fsck import fsck
    stores, tid, ports = _cluster(world=2, rows=16, width=4)
    endpoints = [("127.0.0.1", p) for p in ports]
    try:
        rep = fsck(endpoints, n_tables=1, replication=2)
        assert rep["ok"], rep
        # corrupt rank 1's BACKUP copy of shard 0 behind the op-log's back
        stores[1].server._stores[0].set_data(
            tid, np.zeros((8, 4), np.float32))
        rep = fsck(endpoints, n_tables=1, replication=2)
        assert not rep["ok"]
        assert any(m["shard"] == 0 for m in rep["mismatches"])
    finally:
        _close_all(stores)


def test_ps_fsck_cli_verify_exit_codes():
    from tools import ps_fsck
    stores, tid, ports = _cluster(world=2, rows=16, width=4)
    ep_arg = ",".join(f"127.0.0.1:{p}" for p in ports)
    try:
        assert ps_fsck.main(["--endpoints", ep_arg, "--tables", "1",
                             "--verify"]) == 0
        stores[0].server._stores[1].set_data(
            tid, np.zeros((8, 4), np.float32))
        assert ps_fsck.main(["--endpoints", ep_arg, "--tables", "1",
                             "--verify"]) == 1
    finally:
        _close_all(stores)


# --------------------------------------------------- backoff satellite

def test_backoff_is_decorrelated_jittered_and_env_tunable(monkeypatch):
    import random as _random
    rng = _random.Random(0)
    base, cap = 0.05, 1.0
    delays, prev = [], 0.0
    for _ in range(64):
        prev = _next_backoff(base, prev, cap, rng)
        delays.append(prev)
    assert all(base <= d <= cap for d in delays)
    assert len(set(round(d, 6) for d in delays)) > 10, "no jitter"
    # two streams decorrelate
    rng2 = _random.Random(1)
    d2, prev = [], 0.0
    for _ in range(64):
        prev = _next_backoff(base, prev, cap, rng2)
        d2.append(prev)
    assert delays != d2
    monkeypatch.setenv("HETU_RPC_BACKOFF_MS", "123")
    s = DistributedStore(0, 1)
    try:
        assert abs(s._backoff_base - 0.123) < 1e-9
    finally:
        s.close()


# ------------------------------------------- CI smoke of the acceptance

@pytest.mark.timeout(300)
def test_failover_scenario():
    """Double-kill a replicated primary under chaos with NO try/except
    and no resume around a step: bitwise loss parity, both kills absorbed
    by a promotion inside the step, fsck-verified re-replication, a
    conforming protocol trace and an empty clean-run counter set."""
    import scenarios
    extra = scenarios.failover_scenario(steps=10)
    assert extra["loss_parity"] is True
    assert len(extra["failover_steps"]) == 2
    assert extra["redundancy_restored"] is True
    assert extra["protocol_conformance"]["ok"] is True
    assert extra["clean_run_counters"] == {}
    assert extra["ok"] is True
    assert extra["fault_counters"]["chaos_kill_primary"] == 2
