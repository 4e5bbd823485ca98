"""Fault-tolerance tests: chaos-schedule determinism, transport fault
injection, heartbeat liveness, dead-rank exclusion, preemption-safe
auto-checkpoint/resume, and the acceptance scenario — kill a live PS
server mid-training under an injected fault schedule and finish the run
via retry + resume with losses matching the uninterrupted run (ISSUE 2).

Everything here is single-pytest-process (the two "ranks" of the
distributed store are two in-process server threads) so the whole file
stays tier-1 cheap; the multiprocess launcher-level recovery lives in
test_launcher.py."""
import glob
import os
import socket
import struct
import time

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import chaos
from scenarios import free_ports as _free_ports
from hetu_tpu.graph.executor import Executor
from hetu_tpu.metrics import fault_counts, reset_faults
from hetu_tpu.parallel.preduce import DistPartialReduce
from hetu_tpu.profiler import HetuProfiler
from hetu_tpu.ps.dist_store import (DistributedStore, FrameError,
                                    MAX_FRAME_BYTES, _recv_frame)


@pytest.fixture(autouse=True)
def _clean_chaos_and_counters():
    chaos.uninstall()
    reset_faults()
    yield
    chaos.uninstall()
    reset_faults()




# ------------------------------------------------------- schedule parsing

def test_chaos_schedule_determinism():
    """Same seed ⇒ the exact same injected fault sequence (the property
    that turns every failure mode into a reproducible test)."""
    spec = "123:drop=0.3,delay=0.2:15,dup=0.1,wedge=0.05:50"
    a = chaos.ChaosInjector.from_spec(spec)
    b = chaos.ChaosInjector.from_spec(spec)
    seq_a = [a.on_send(i % 4, 1) for i in range(300)]
    seq_b = [b.on_send(i % 4, 1) for i in range(300)]
    assert seq_a == seq_b
    assert any(x is not None for x in seq_a), "schedule injected nothing"
    assert any(x is None for x in seq_a), "schedule injected everything"
    c = chaos.ChaosInjector.from_spec(
        "124:drop=0.3,delay=0.2:15,dup=0.1,wedge=0.05:50")
    assert [c.on_send(i % 4, 1) for i in range(300)] != seq_a


def test_chaos_spec_errors_are_loud():
    for bad in ("drop=0.5",              # no seed
                "7:",                    # no faults
                "7:flip=0.5",            # unknown kind
                "7:drop=1.5",            # prob out of range
                "7:delay=0.5",           # delay without duration
                "7:kill:ps@rank1",       # kill without step
                "7:kill:primary@rank1:step3",   # role kill needs shard
                "7:kill:backup@shard1",         # role kill without step
                "x:drop=0.5"):           # non-int seed
        with pytest.raises(chaos.ChaosSpecError):
            chaos.parse_spec(bad)


def test_chaos_replica_role_kill_specs_parse():
    _, faults = chaos.parse_spec(
        "7:kill:primary@shard1:step3,kill:backup@shard0:step2")
    assert faults[0] == {"kind": "kill_primary", "shard": 1, "step": 3}
    assert faults[1] == {"kind": "kill_backup", "shard": 0, "step": 2}


def test_chaos_role_kills_resolve_serving_and_holding_servers():
    """kill:primary targets whoever SERVES the shard at fire time;
    kill:backup targets the non-serving holder — after a failover the
    same spec form therefore tracks the promoted server (the double-kill
    schedule of ``scenarios.failover_scenario`` relies on exactly this)."""
    from hetu_tpu.ps.dist_store import DistributedStore
    ports = _free_ports(2)
    endpoints = [("127.0.0.1", p) for p in ports]
    stores = [DistributedStore(r, 2, endpoints, port=ports[r],
                               rpc_timeout=5.0, rpc_retries=2,
                               connect_timeout=2.0, replication=2)
              for r in range(2)]
    inj = chaos.ChaosInjector.from_spec(
        "7:kill:backup@shard0:step1,kill:primary@shard0:step2")
    for r, s in enumerate(stores):
        inj.register_server(r, s.server)
    try:
        tid = None
        for s in stores:
            tid = s.init_table(8, 4, opt="sgd", lr=1.0, init_scale=0)
        # step 1: shard 0's BACKUP (held, unserved, on rank 1) dies
        assert inj.on_step(1) == [1]
        assert stores[1].server._stop and not stores[0].server._stop
        assert fault_counts().get("chaos_kill_backup", 0) == 1
        # step 2: shard 0's PRIMARY (serving, rank 0) dies
        assert inj.on_step(2) == [0]
        assert stores[0].server._stop
        assert fault_counts().get("chaos_kill_primary", 0) == 1
    finally:
        for s in stores:
            s.close()


def test_chaos_proc_step_kill_spec_parses():
    """``kill:proc@rank<r>:step<n>`` — the DETERMINISTIC step-clock
    worker kill the elastic tests schedule (ISSUE 12 satellite); the
    wall-clock ``after<ms>`` form keeps parsing unchanged."""
    _, faults = chaos.parse_spec(
        "7:kill:proc@rank2:step5,kill:proc@rank0:after250")
    assert faults[0] == {"kind": "kill_proc", "rank": 2, "step": 5}
    assert faults[1] == {"kind": "kill_proc", "rank": 0,
                         "after_ms": 250.0}
    with pytest.raises(chaos.ChaosSpecError):
        chaos.parse_spec("7:kill:proc@rank2:when5")


class _FakeProc:
    def __init__(self):
        self.stopped = 0

    def stop(self):
        self.stopped += 1


def test_chaos_proc_step_kill_fires_once_on_step_clock():
    """The step form fires a register_proc'd handle exactly once, at
    exactly its step, via on_step — and NEVER via due_proc_kills (that
    is the launcher's wall clock); the kill consumes no RNG draw, so a
    schedule mixing it with probabilistic faults stays deterministic."""
    reset_faults()
    spec = "11:drop=0.2,kill:proc@rank1:step3"
    inj = chaos.ChaosInjector.from_spec(spec)
    procs = {r: _FakeProc() for r in range(2)}
    for r, p in procs.items():
        inj.register_proc(r, p)
    # the wall clock never fires a step-form kill, at any elapsed time
    assert inj.due_proc_kills(1e9) == []
    assert inj.on_step(2) == []
    assert procs[1].stopped == 0
    assert inj.on_step(3) == [1]
    assert procs[1].stopped == 1 and procs[0].stopped == 0
    assert inj.on_step(3) == []         # one-shot
    assert procs[1].stopped == 1
    assert fault_counts().get("chaos_kill_proc") == 1
    # determinism: same seed + same event order ⇒ same transport stream,
    # kill present or not (kills draw nothing from the RNG)
    a = chaos.ChaosInjector.from_spec(spec)
    b = chaos.ChaosInjector.from_spec("11:drop=0.2")
    a.register_proc(1, _FakeProc())
    seq_a = []
    for i in range(100):
        if i == 50:
            a.on_step(3)
        seq_a.append(a.on_send(i % 3, 1))
    assert seq_a == [b.on_send(i % 3, 1) for i in range(100)]


def test_chaos_proc_step_kill_missing_handle_is_loud():
    """A step-form proc kill with NO registered handles warns + counts
    (quiet when OTHER ranks' handles are registered — the target lives
    in a different process, chaos.py's kill:ps convention)."""
    reset_faults()
    inj = chaos.ChaosInjector.from_spec("7:kill:proc@rank1:step2")
    with pytest.warns(RuntimeWarning, match="kill:proc@rank1:step2"):
        assert inj.on_step(2) == []
    assert fault_counts().get("chaos_kill_target_missing") == 1
    # registered handle for a DIFFERENT rank: quiet no-op
    reset_faults()
    inj2 = chaos.ChaosInjector.from_spec("7:kill:proc@rank1:step2")
    inj2.register_proc(0, _FakeProc())
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        assert inj2.on_step(2) == []
    assert fault_counts().get("chaos_kill_target_missing", 0) == 0


def test_chaos_replica_kill_spec_parses():
    """``kill:replica@<idx>:req<n>`` — the fleet-tier replica kill on
    the FRONT DOOR's admission clock (ISSUE 17 satellite)."""
    _, faults = chaos.parse_spec("7:kill:replica@1:req40")
    assert faults == [{"kind": "kill_replica", "idx": 1, "req": 40}]
    with pytest.raises(chaos.ChaosSpecError):
        chaos.parse_spec("7:kill:replica@1:step40")    # req clock only


def test_chaos_replica_kill_fires_once_on_admission_clock():
    """The replica kill fires its register_replica'd handle exactly once,
    at exactly its admission count, and draws nothing from the RNG — a
    schedule mixing it with probabilistic faults stays deterministic."""
    reset_faults()
    spec = "11:drop=0.2,kill:replica@1:req5"
    inj = chaos.ChaosInjector.from_spec(spec)
    reps = {i: _FakeProc() for i in range(2)}
    for i, h in reps.items():
        inj.register_replica(i, h)
    assert inj.on_request(4) == []
    assert reps[1].stopped == 0
    assert inj.on_request(5) == [1]
    assert reps[1].stopped == 1 and reps[0].stopped == 0
    assert inj.on_request(5) == []      # one-shot
    assert reps[1].stopped == 1
    assert fault_counts().get("chaos_kill_replica") == 1
    # determinism: same seed + same event order ⇒ same transport stream,
    # kill present or not (replica kills draw nothing from the RNG)
    a = chaos.ChaosInjector.from_spec(spec)
    b = chaos.ChaosInjector.from_spec("11:drop=0.2")
    a.register_replica(1, _FakeProc())
    seq_a = []
    for i in range(100):
        if i == 50:
            a.on_request(5)
        seq_a.append(a.on_send(i % 3, 1))
    assert seq_a == [b.on_send(i % 3, 1) for i in range(100)]


def test_chaos_replica_kill_missing_handle_is_loud():
    """A replica kill with NO registered replicas warns + counts; with
    OTHER replicas registered it is a quiet no-op (the target lives
    behind a different front door — chaos.py's kill:ps convention)."""
    reset_faults()
    inj = chaos.ChaosInjector.from_spec("7:kill:replica@1:req2")
    with pytest.warns(RuntimeWarning, match="kill:replica@1:req2"):
        assert inj.on_request(2) == []
    assert fault_counts().get("chaos_kill_target_missing") == 1
    reset_faults()
    inj2 = chaos.ChaosInjector.from_spec("7:kill:replica@1:req2")
    inj2.register_replica(0, _FakeProc())
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        assert inj2.on_request(2) == []
    assert fault_counts().get("chaos_kill_target_missing", 0) == 0


def test_chaos_replica_kill_token_clock_spec_parses():
    """``kill:replica@<idx>:tok<n>`` — the DECODE ENGINE's own emitted-
    token clock (ISSUE 19), for deterministic mid-generation kills; the
    rank-level ``:step<n>`` form stays invalid for replicas."""
    _, faults = chaos.parse_spec("7:kill:replica@0:tok16")
    assert faults == [{"kind": "kill_replica", "idx": 0, "tok": 16}]
    _, faults = chaos.parse_spec("7:kill:replica@1:req3,kill:replica@0:tok5")
    assert faults == [{"kind": "kill_replica", "idx": 1, "req": 3},
                      {"kind": "kill_replica", "idx": 0, "tok": 5}]
    with pytest.raises(chaos.ChaosSpecError):
        chaos.parse_spec("7:kill:replica@1:step40")    # req/tok clocks only


def test_chaos_replica_kill_fires_once_on_token_clock():
    """The token-clock kill fires its handle exactly once, at the first
    report where the replica's cumulative emitted tokens reach n, only
    for ITS replica index — and draws nothing from the RNG."""
    reset_faults()
    spec = "11:drop=0.2,kill:replica@1:tok5"
    inj = chaos.ChaosInjector.from_spec(spec)
    reps = {i: _FakeProc() for i in range(2)}
    for i, h in reps.items():
        inj.register_replica(i, h)
    assert inj.on_token(1, 4) == []
    assert inj.on_token(0, 5) == []     # replica 0's clock: not the target
    assert reps[0].stopped == 0 and reps[1].stopped == 0
    assert inj.on_token(1, 5) == [1]
    assert reps[1].stopped == 1 and reps[0].stopped == 0
    assert inj.on_token(1, 6) == []     # one-shot
    assert reps[1].stopped == 1
    assert fault_counts().get("chaos_kill_replica") == 1
    # determinism: the kill perturbs no transport fault decision
    a = chaos.ChaosInjector.from_spec(spec)
    b = chaos.ChaosInjector.from_spec("11:drop=0.2")
    a.register_replica(1, _FakeProc())
    seq_a = []
    for i in range(100):
        if i == 50:
            a.on_token(1, 7)
        seq_a.append(a.on_send(i % 3, 1))
    assert seq_a == [b.on_send(i % 3, 1) for i in range(100)]


def test_chaos_replica_kill_token_clock_missing_handle_is_loud():
    """Same quiet/loud split as the admission clock: no registered
    replicas at fire time warns + counts; other replicas registered
    means the target lives behind a different door — quiet no-op."""
    reset_faults()
    inj = chaos.ChaosInjector.from_spec("7:kill:replica@1:tok2")
    with pytest.warns(RuntimeWarning, match="kill:replica@1:tok2"):
        assert inj.on_token(1, 2) == []
    assert fault_counts().get("chaos_kill_target_missing") == 1
    reset_faults()
    inj2 = chaos.ChaosInjector.from_spec("7:kill:replica@1:tok2")
    inj2.register_replica(0, _FakeProc())
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error")
        assert inj2.on_token(1, 2) == []
    assert fault_counts().get("chaos_kill_target_missing", 0) == 0


def test_partition_spec_parses():
    _, faults = chaos.parse_spec("7:partition:rank0|rank1@step3:heal7")
    assert faults == [{"kind": "partition", "a": frozenset({0}),
                       "b": frozenset({1}), "step": 3, "heal": 7}]
    # multi-rank sides + no heal (a partition that never heals)
    _, faults = chaos.parse_spec(
        "7:partition:rank0+rank2|rank1+rank3@step5")
    assert faults[0]["a"] == frozenset({0, 2})
    assert faults[0]["b"] == frozenset({1, 3})
    assert faults[0]["heal"] is None
    # composes with other fault kinds on one schedule
    _, faults = chaos.parse_spec(
        "7:drop=0.1,partition:rank0|rank1@step2:heal4,kill:ps@rank1:step9")
    assert [f["kind"] for f in faults] == ["drop", "partition", "kill_ps"]


def test_partition_spec_errors_are_loud():
    for bad in ("7:partition:rank0|rank1",           # no @step trigger
                "7:partition:rank0@step3",           # only one side
                "7:partition:rank0|rank0@step3",     # overlapping sides
                "7:partition:rank0+rank1|rank1@step3",
                "7:partition:|rank1@step3",          # empty side
                "7:partition:rank0|rank1@step3:heal2",   # heal <= step
                "7:partition:rank0|rank1@step3:heal3",
                "7:partition:rankX|rank1@step3",     # bad rank
                "7:partition:rank0|rank1@stepX",     # bad step
                "7:partition:rank0|rank1@req3",      # wrong clock
                "7:partition:rank0|rank1@step3:cure7"):  # bad clause
        with pytest.raises(chaos.ChaosSpecError, match="partition"):
            chaos.parse_spec(bad)


def test_partition_same_seed_determinism_and_rng_isolation():
    """A partition consumes NO RNG draw: the probabilistic fault stream
    of a schedule with a partition is positionally identical to the same
    schedule without it — before, during, and after the window — so the
    same seed reproduces the same run either way."""
    with_p = chaos.ChaosInjector.from_spec(
        "123:drop=0.3,partition:rank0|rank1@step1:heal3")
    without = chaos.ChaosInjector.from_spec("123:drop=0.3")
    assert [with_p.on_send(1, 1, src=0) for _ in range(60)] \
        == [without.on_send(1, 1, src=0) for _ in range(60)]
    with_p.on_step(1)
    during = [with_p.on_send(1, 1, src=0) for _ in range(40)]
    assert all(a == ("drop", 0.0) for a in during), "cut not absolute"
    for _ in range(40):
        without.on_send(1, 1, src=0)     # advance the twin's stream
    with_p.on_step(3)                    # heal
    assert [with_p.on_send(1, 1, src=0) for _ in range(60)] \
        == [without.on_send(1, 1, src=0) for _ in range(60)]
    # and the whole thing replays bitwise from the same seed
    a = chaos.ChaosInjector.from_spec(
        "9:partition:rank0|rank1@step1:heal2")
    b = chaos.ChaosInjector.from_spec(
        "9:partition:rank0|rank1@step1:heal2")
    for inj in (a, b):
        inj.on_step(1)
    assert [a.on_send(p % 3, 1, src=0) for p in range(30)] \
        == [b.on_send(p % 3, 1, src=0) for p in range(30)]


def test_partition_heal_clock_isolated_from_kill_clock():
    """The partition window and the one-shot kill bookkeeping share
    on_step but nothing else: a kill firing at the cut step neither
    consumes nor is consumed by the window, healing closes the window
    without touching kills, and replaying an old step re-fires
    nothing."""
    inj = chaos.ChaosInjector.from_spec(
        "7:partition:rank0|rank1@step2:heal4,kill:ps@rank5:step2")
    assert inj.on_send(1, 1, src=0) is None      # window not open yet
    with pytest.warns(RuntimeWarning, match="no registered kill target"):
        inj.on_step(2)          # kill fires (loud: no target) + cut opens
    assert inj.on_send(1, 1, src=0) == ("drop", 0.0)
    assert inj.on_send(0, 1, src=1) == ("drop", 0.0)   # both directions
    assert inj.on_send(2, 1, src=0) is None            # outside the cut
    assert inj.on_send(1, 1) is None           # unknown src never drops
    inj.on_step(3)
    assert inj.on_send(1, 1, src=0) == ("drop", 0.0)   # still open
    inj.on_step(4)                                     # heal
    assert inj.on_send(1, 1, src=0) is None
    inj.on_step(2)       # replaying an old step: no re-fire, no re-open
    assert inj.on_send(1, 1, src=0) is None
    fc = fault_counts()
    assert fc.get("partition_frames_dropped", 0) == 3
    assert fc.get("chaos_kill_target_missing", 0) == 1


def test_partition_blocks_then_heals_real_transport():
    """End to end over the live dist-store transport: once the window
    opens, every rank0<->rank1 frame drops (the client sees bounded
    retries then a diagnosable unreachable), and the SAME store works
    again the moment the window heals — no reconnect ceremony."""
    s0, s1, tid = _store_pair(_free_ports(2))
    inj = chaos.ChaosInjector.from_spec(
        "9:partition:rank0|rank1@step1:heal2")
    chaos.install(inj)
    try:
        key = np.asarray([1], np.int64)              # owned by rank 1
        before = s0.pull(tid, key)                   # window closed: flows
        inj.on_step(1)
        with pytest.raises(RuntimeError, match="unreachable"):
            s0.pull(tid, key)
        assert fault_counts().get("partition_frames_dropped", 0) >= 2
        inj.on_step(2)                               # heal
        np.testing.assert_array_equal(s0.pull(tid, key), before)
    finally:
        chaos.uninstall()
        s0.close()
        s1.close()


def test_chaos_install_from_env(monkeypatch):
    monkeypatch.setenv("HETU_CHAOS", "9:drop=0.25")
    inj = chaos.install_from_env()
    assert inj is not None and chaos.active() is inj
    assert inj.seed == 9
    chaos.uninstall()
    monkeypatch.delenv("HETU_CHAOS")
    assert chaos.ChaosInjector.from_env() is None


# ------------------------------------------------- transport fault paths

def test_chaos_dup_is_absorbed_by_dedup():
    """dup=1.0 sends every frame twice; the server's (client, seq) dedup
    must apply non-idempotent ops exactly once."""
    chaos.install(chaos.ChaosInjector.from_spec("5:dup=1.0"))
    store = DistributedStore(0, 1)
    try:
        store.ssp_init(1)
        store.clock()
        np.testing.assert_array_equal(store.clocks(), [1])
        assert fault_counts().get("chaos_dup", 0) >= 1
    finally:
        chaos.uninstall()       # before close: a dup'd SHUTDOWN races the
        store.close()           # server-side connection teardown


def test_chaos_drop_exhausts_retries_with_counters():
    store = DistributedStore(0, 1, rpc_retries=2)
    store.ssp_init(1)
    chaos.install(chaos.ChaosInjector.from_spec("5:drop=1.0"))
    try:
        with pytest.raises(RuntimeError, match="unreachable"):
            store.clock()
        fc = fault_counts()
        assert fc.get("chaos_drop", 0) >= 2
        assert fc.get("ps_rpc_retry", 0) >= 1
        assert fc.get("ps_peer_unreachable", 0) == 1
    finally:
        chaos.uninstall()
        store.close()


def test_chaos_drop_half_recovers_via_retry():
    """p<1 drops: the at-least-once retry discipline still lands every op
    (the dedup window keeps retried ticks single-application)."""
    chaos.install(chaos.ChaosInjector.from_spec("21:drop=0.4"))
    store = DistributedStore(0, 1, rpc_retries=8)
    try:
        store.ssp_init(1)
        for _ in range(10):
            store.clock()
        chaos.uninstall()
        np.testing.assert_array_equal(store.clocks(), [10])
        assert fault_counts().get("chaos_drop", 0) >= 1
    finally:
        chaos.uninstall()
        store.close()


# ------------------------------------------------- frame-length validation

def test_recv_frame_rejects_corrupt_lengths():
    a, b = socket.socketpair()
    try:
        a.sendall(struct.pack("<q", -5))
        with pytest.raises(FrameError, match="outside"):
            _recv_frame(b)
        a.sendall(struct.pack("<q", MAX_FRAME_BYTES + 1))
        with pytest.raises(FrameError, match="outside"):
            _recv_frame(b)
        assert fault_counts().get("ps_bad_frame", 0) == 2
    finally:
        a.close()
        b.close()


def test_server_survives_hostile_frame():
    """A corrupt/hostile length prefix must cost one dropped connection —
    not a multi-GB allocation, not a dead server."""
    store = DistributedStore(0, 1)
    try:
        s = socket.create_connection(("127.0.0.1", store.server.port),
                                     timeout=5)
        s.sendall(struct.pack("<q", 1 << 60))   # ~1 exabyte frame
        s.settimeout(10)
        assert s.recv(1) == b"", "server should drop the connection"
        s.close()
        store.ssp_init(1)                       # server still healthy
        store.clock()
        np.testing.assert_array_equal(store.clocks(), [1])
    finally:
        store.close()


# ------------------------------------------------------ heartbeat liveness

def test_heartbeat_alive_mask_and_grace():
    store = DistributedStore(0, 1)
    try:
        # before any ping, liveness is vacuous: everyone counts alive
        np.testing.assert_array_equal(store.alive_mask(100, 3), [1, 1, 1])
        store.heartbeat(rank=0, step=7)
        store.heartbeat(rank=1, step=7)
        np.testing.assert_array_equal(store.alive_mask(5000, 3), [1, 1, 1])
        time.sleep(0.35)
        store.heartbeat(rank=0)
        # rank 1 went stale; rank 2 NEVER pinged and stays alive —
        # liveness only declares death for ranks it has seen alive
        # (startup stagger must not read as death)
        np.testing.assert_array_equal(store.alive_mask(300, 3), [1, 0, 1])
    finally:
        store.close()


def test_background_heartbeat_thread():
    store = DistributedStore(0, 1)
    try:
        store.start_heartbeat(interval_ms=50, step_fn=lambda: 11)
        time.sleep(0.3)
        assert store.alive_mask(200, 1)[0] == 1
    finally:
        store.close()


# ---------------------------------------------- in-process 2-rank fixture

def _store_pair(ports, **kw):
    """Two DistributedStores (two in-process TCP servers) sharing one
    32x8 table with deterministic content (key k lives on rank k%2 at
    local row k//2)."""
    endpoints = [("127.0.0.1", p) for p in ports]
    kw.setdefault("rpc_timeout", 5.0)
    kw.setdefault("rpc_retries", 2)
    kw.setdefault("connect_timeout", 2.0)
    stores = [DistributedStore(r, 2, endpoints, port=ports[r], **kw)
              for r in range(2)]
    table = np.random.RandomState(42).normal(
        0, 0.01, (32, 8)).astype(np.float32)
    tids = []
    for r, s in enumerate(stores):
        tids.append(s.init_table(32, 8, opt="sgd", lr=0.1, init_scale=0.0))
        s.local.set_data(tids[r], table[np.arange(16) * 2 + r])
    assert tids[0] == tids[1]
    return stores[0], stores[1], tids[0]


def _ps_executor(store, tid, **kw):
    rng = np.random.RandomState(1)
    ids = ht.placeholder_op("ids")
    y_ = ht.placeholder_op("y")
    h = ht.ps_embedding_lookup_op((store, tid), ids, width=8)
    w = ht.Variable("w", value=rng.randn(8, 2).astype(np.float32) * 0.3)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
        ht.matmul_op(h, w), y_), [0])
    ex = ht.Executor(
        {"train": [loss, ht.optim.AdamOptimizer(0.01).minimize(loss)]},
        seed=0, **kw)
    return ex, ids, y_


def _ps_feeds(n):
    rng = np.random.RandomState(0)
    return [(rng.randint(0, 32, 16),
             np.eye(2, dtype=np.float32)[rng.randint(0, 2, 16)])
            for _ in range(n)]


# --------------------------------------- preduce dead-rank exclusion

def test_preduce_excludes_dead_rank_within_one_window():
    s0, s1, _ = _store_pair(_free_ports(2))
    try:
        pr = DistPartialReduce(s0, max_wait_ms=3000.0, min_workers=1,
                               heartbeat_deadline_ms=250.0)
        s0.heartbeat(rank=0)
        s0.heartbeat(rank=1)        # rank 1 alive ... then silent
        time.sleep(0.4)
        s0.heartbeat(rank=0)        # rank 0 stays fresh
        pr.report_arrival(0, 0)     # rank 1 never arrives
        t0 = time.monotonic()
        mask = pr.get_partner(0, 0)
        took = time.monotonic() - t0
        np.testing.assert_allclose(mask, [1.0, 0.0])
        assert took < 1.5, f"waited {took:.2f}s for a dead rank " \
                           f"(window is 3s — exclusion failed)"
        assert fault_counts().get("preduce_dead_rank_excluded", 0) >= 1
    finally:
        s0.close()
        s1.close()


def test_preduce_alive_fn_in_process():
    """Liveness wiring on the in-process PartialReduce: dead ranks leave
    the mask and the min-workers fallback degrades to believed-alive,
    never to ranks known dead."""
    from hetu_tpu.parallel.preduce import PartialReduce
    pr = PartialReduce(4, min_workers=3,
                       alive_fn=lambda: [1.0, 1.0, 0.0, 1.0])
    pr.report_arrival(0, 0)
    pr.report_arrival(2, 0)         # arrived but heartbeat-dead
    mask = pr.get_partner(0, 0)
    np.testing.assert_allclose(mask, [1.0, 1.0, 0.0, 1.0])
    assert fault_counts().get("preduce_dead_rank_excluded", 0) >= 1


# ------------------------------------- auto-save / resume (dense graph)

def _dense_executor(**kw):
    rng = np.random.RandomState(3)
    x = ht.placeholder_op("x")
    y_ = ht.placeholder_op("y")
    w1 = ht.Variable("w1", value=rng.randn(16, 32).astype(np.float32) * .1)
    w2 = ht.Variable("w2", value=rng.randn(32, 4).astype(np.float32) * .1)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
        ht.matmul_op(ht.relu_op(ht.matmul_op(x, w1)), w2), y_), [0])
    ex = ht.Executor(
        {"train": [loss, ht.optim.AdamOptimizer(0.01).minimize(loss)]},
        seed=0, install_signal_handlers=False, **kw)
    return ex, x, y_


def _dense_feeds(n):
    rng = np.random.RandomState(0)
    return [(rng.randn(8, 16).astype(np.float32),
             np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)])
            for _ in range(n)]


def _run_steps(ex, x, y_, feeds):
    return [float(ex.run("train", feed_dict={x: f[0], y_: f[1]}
                         )[0].asnumpy()) for f in feeds]


def test_autosave_resume_exact_continuation(tmp_path):
    """Interrupt at step 3, resume from the step-2 auto-checkpoint in a
    FRESH executor, finish — the loss trajectory must be bitwise equal
    to the uninterrupted run (params + Adam moments + step restored)."""
    feeds = _dense_feeds(6)
    ex0, x0, y0 = _dense_executor()
    base = _run_steps(ex0, x0, y0, feeds)

    d = str(tmp_path / "autosave")
    ex1, x1, y1 = _dense_executor(auto_save_dir=d, auto_save_every=2)
    part = _run_steps(ex1, x1, y1, feeds[:3])   # dies after step 3
    np.testing.assert_array_equal(part, base[:3])
    assert fault_counts().get("auto_save", 0) == 1      # step 2

    ex2, x2, y2 = _dense_executor()
    assert ex2.resume(d) == 2
    rest = _run_steps(ex2, x2, y2, feeds[2:])
    np.testing.assert_array_equal(rest, base[2:])
    assert fault_counts().get("resume", 0) == 1


def test_autosave_retention_keeps_last_n(tmp_path):
    d = str(tmp_path / "keep")
    ex, x, y_ = _dense_executor(auto_save_dir=d, auto_save_every=1,
                                auto_save_keep=2)
    _run_steps(ex, x, y_, _dense_feeds(5))
    left = sorted(os.path.basename(p)
                  for p in glob.glob(os.path.join(d, "ckpt-*")))
    assert left == ["ckpt-00000004", "ckpt-00000005"], left


def test_truncated_checkpoint_rejected(tmp_path):
    """resume must pick the newest COMPLETE checkpoint: a truncated
    params file (manifest size mismatch) and a missing meta.json are
    both rejected."""
    d = str(tmp_path / "trunc")
    ex, x, y_ = _dense_executor(auto_save_dir=d, auto_save_every=1,
                                auto_save_keep=10)
    _run_steps(ex, x, y_, _dense_feeds(4))
    import json
    ck4 = os.path.join(d, "ckpt-00000004")
    with open(os.path.join(ck4, "meta.json")) as f:
        rel = sorted(json.load(f)["manifest"])[0]
    with open(os.path.join(ck4, rel), "r+b") as f:
        f.truncate(2)                               # preempted mid-write
    os.remove(os.path.join(d, "ckpt-00000003", "meta.json"))
    assert not Executor._checkpoint_complete(ck4)

    ex2, x2, y2 = _dense_executor()
    with pytest.warns(RuntimeWarning, match="incomplete"):
        assert ex2.resume(d) == 2
    assert fault_counts().get("ckpt_incomplete_skipped", 0) >= 2


def test_auto_resume_at_construction(tmp_path, monkeypatch):
    """Under the supervisor (HETU_AUTO_RESUME=1 + HETU_AUTO_SAVE_DIR), a
    plain training script's Executor restores the newest checkpoint at
    construction — a relaunch continues instead of retraining from 0."""
    feeds = _dense_feeds(6)
    ex0, x0, y0 = _dense_executor()
    base = _run_steps(ex0, x0, y0, feeds)

    d = str(tmp_path / "ar")
    ex1, x1, y1 = _dense_executor(auto_save_dir=d, auto_save_every=1)
    _run_steps(ex1, x1, y1, feeds[:4])
    monkeypatch.setenv("HETU_AUTO_RESUME", "1")
    monkeypatch.setenv("HETU_AUTO_SAVE_DIR", d)
    ex2, x2, y2 = _dense_executor()     # no explicit resume() call
    assert ex2.step_counter == 4
    rest = _run_steps(ex2, x2, y2, feeds[4:])
    np.testing.assert_array_equal(rest, base[4:])


def test_resume_recovers_stranded_rename_checkpoint(tmp_path):
    """A crash between the two renames of an overwriting save can leave
    the only complete copy of the newest step at <path>.replaced (or
    .saving); resume must probe those remnants — and a stranded NEWER
    step must beat an older published one."""
    d = str(tmp_path / "stranded")
    ex, x, y_ = _dense_executor(auto_save_dir=d, auto_save_every=1)
    _run_steps(ex, x, y_, _dense_feeds(2))
    ck2 = os.path.join(d, "ckpt-00000002")
    os.rename(ck2, ck2 + ".replaced")   # crash window mid-swap
    ex2, _, _ = _dense_executor()
    assert ex2.resume(d) == 2           # not 1: the remnant is newer


def test_resume_empty_dir_returns_none(tmp_path):
    ex, _, _ = _dense_executor()
    assert ex.resume(str(tmp_path)) is None
    assert ex.step_counter == 0


def test_sigterm_triggers_emergency_save(tmp_path):
    import signal
    d = str(tmp_path / "emerg")
    feeds = _dense_feeds(1)
    rng = np.random.RandomState(3)
    x = ht.placeholder_op("x")
    y_ = ht.placeholder_op("y")
    w1 = ht.Variable("w1", value=rng.randn(16, 32).astype(np.float32) * .1)
    w2 = ht.Variable("w2", value=rng.randn(32, 4).astype(np.float32) * .1)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
        ht.matmul_op(ht.relu_op(ht.matmul_op(x, w1)), w2), y_), [0])
    # auto_save_dir + default install_signal_handlers=True
    ex = ht.Executor(
        {"train": [loss, ht.optim.AdamOptimizer(0.01).minimize(loss)]},
        seed=0, auto_save_dir=d)
    try:
        ex.run("train", feed_dict={x: feeds[0][0], y_: feeds[0][1]})
        with pytest.raises(SystemExit) as ei:
            signal.raise_signal(signal.SIGTERM)
        assert ei.value.code == 143                 # 128 + SIGTERM
        ck = os.path.join(d, "ckpt-00000001")
        assert Executor._checkpoint_complete(ck)
        assert fault_counts().get("emergency_save", 0) == 1
    finally:
        for sig, prev in ex._prev_handlers.items():
            signal.signal(sig, prev)


# --------------------------------------------- THE acceptance scenario

@pytest.mark.timeout(180)
def test_kill_ps_server_mid_training_recovers_with_loss_parity(tmp_path):
    """ISSUE 2 acceptance: an injected schedule kills the live rank-1 PS
    server after step 3; the run detects it (bounded retry, clean
    diagnostic), restores a replacement server's shard and the executor
    state from the newest complete auto-checkpoint, and finishes — loss
    trajectory equal to the uninterrupted run.  Fault/retry counters are
    nonzero for the chaos run and zero for the clean run."""
    feeds = _ps_feeds(6)

    # --- clean run: zero fault counters --------------------------------
    s0, s1, tid = _store_pair(_free_ports(2))
    try:
        ex, ids, y_ = _ps_executor(s0, tid)
        base = [float(ex.run("train", feed_dict={ids: f[0], y_: f[1]}
                             )[0].asnumpy()) for f in feeds]
    finally:
        s0.close()
        s1.close()
    assert HetuProfiler.fault_counters() == {}, \
        "clean run must report zero fault/retry counters"

    # --- chaos run: kill rank-1's server after step 3 -------------------
    save_dir = str(tmp_path / "autosave")
    ports = _free_ports(2)
    chaos.install(chaos.ChaosInjector.from_spec("11:kill:ps@rank1:step3"))
    s0, s1, tid = _store_pair(ports)
    dead_s1 = s1
    try:
        ex, ids, y_ = _ps_executor(
            s0, tid, auto_save_dir=save_dir, auto_save_every=1,
            install_signal_handlers=False)
        losses = [None] * 6
        step, failures = 0, 0
        while step < 6:
            try:
                losses[step] = float(
                    ex.run("train", feed_dict={ids: feeds[step][0],
                                               y_: feeds[step][1]}
                           )[0].asnumpy())
                step += 1
                # in a real deployment EVERY rank's executor calls save,
                # each persisting its own PS shard; this in-process test
                # has only rank 0's executor, so rank 1's server-side
                # shard save is mirrored here after each step
                ck = os.path.join(save_dir, f"ckpt-{step:08d}")
                if os.path.isdir(ck):
                    s1.save(tid, os.path.join(ck, "ps0.bin"))
            except RuntimeError as e:
                assert "unreachable" in str(e), e
                failures += 1
                assert failures <= 1, "failed to recover after restart"
                # recovery: the dead server's RAM is gone — a REPLACEMENT
                # rank-1 store at the same endpoint loads its shard from
                # the newest complete checkpoint ...
                newest = next(c for c in sorted(
                    glob.glob(os.path.join(save_dir, "ckpt-*")),
                    reverse=True) if Executor._checkpoint_complete(c))
                endpoints = [("127.0.0.1", p) for p in ports]
                s1 = DistributedStore(1, 2, endpoints, port=ports[1],
                                      rpc_timeout=5.0, rpc_retries=2,
                                      connect_timeout=2.0)
                s1.init_table(32, 8, opt="sgd", lr=0.1, init_scale=0.0)
                s1.load(tid, os.path.join(newest, "ps0.bin"))
                # ... and a fresh executor resumes params/opt/step/shard-0
                ex, ids, y_ = _ps_executor(
                    s0, tid, auto_save_dir=save_dir, auto_save_every=1,
                    install_signal_handlers=False)
                restored = ex.resume(save_dir)
                assert restored == 3, restored
                step = restored
        assert failures == 1, "the schedule should have killed the server"
        np.testing.assert_array_equal(losses, base)
        fc = HetuProfiler.fault_counters()
        assert fc.get("chaos_kill_ps", 0) == 1
        assert fc.get("ps_rpc_retry", 0) >= 1
        assert fc.get("ps_peer_unreachable", 0) >= 1
        assert fc.get("auto_save", 0) >= 3
        assert fc.get("resume", 0) == 1
    finally:
        chaos.uninstall()
        for s in (s0, s1, dead_s1):
            try:
                s.close()
            except Exception:
                pass
