"""Partition tolerance for the replicated PS (ISSUE 8): fencing epochs
refuse old-lineage frames without mutating state, a healed stale
ex-primary demotes itself into re-replication instead of acking clients
(and the stale client re-routes off the refusal), liveness distinguishes
partitioned from dead, ``ps_fsck --retries`` keeps live-cluster verify
usable, fsck's lineage check makes an unconverged split brain visible,
and the 2-cell serving scenario + the whole acceptance rides
``scenarios.partition_scenario``.

Everything is in-process multi-rank like test_ps_replication.py so the
file stays tier-1 cheap."""
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))          # repo root: tools import

from scenarios import free_ports as _free_ports
from hetu_tpu import chaos
from hetu_tpu.metrics import fault_counts, reset_faults
from hetu_tpu.ps.dist_store import (DistributedStore, OP_PUSH,
                                    OP_PROMOTE, OP_REPLICATE, _HDR)


@pytest.fixture(autouse=True)
def _clean_chaos_and_counters():
    chaos.uninstall()
    reset_faults()
    yield
    chaos.uninstall()
    reset_faults()


def _cluster(world=2, rows=16, width=4, **kw):
    ports = _free_ports(world)
    endpoints = [("127.0.0.1", p) for p in ports]
    kw.setdefault("rpc_timeout", 5.0)
    kw.setdefault("rpc_retries", 2)
    kw.setdefault("connect_timeout", 2.0)
    kw.setdefault("replication", 2)
    stores = [DistributedStore(r, world, endpoints, port=ports[r], **kw)
              for r in range(world)]
    tid = None
    for s in stores:
        tid = s.init_table(rows, width, opt="sgd", lr=0.1, init_scale=0.0)
    stores[0].set_data(tid, np.random.RandomState(42).normal(
        0, 0.01, (rows, width)).astype(np.float32))
    return stores, tid, ports


def _close_all(stores):
    for s in stores:
        try:
            s.close()
        except Exception:
            pass


# --------------------------------------------------- epoch fencing unit

def test_old_epoch_push_refused_and_counted_without_mutation():
    """Satellite: an old-epoch OP_PUSH against a promoted (newer-epoch)
    copy is refused, counted, and applies NOTHING — and the refusal must
    not poison the dedup window: the same (client, seq) retried at the
    correct epoch still applies, exactly once."""
    stores, tid, _ = _cluster()
    try:
        # promote rank 1's copy of shard 0 (rank 0 is presumed dead but
        # actually lives on — the split-brain setup): epoch 0 -> 1
        assert stores[0]._failover(0) == 1
        assert stores[0]._epoch[0] == 1
        assert fault_counts().get("ps_epoch_bumps", 0) == 1
        key = np.asarray([0], np.int64)              # shard-0 key
        before = stores[0].pull(tid, key)[0].copy()  # from rank 1 now
        grads = np.ones((1, 4), np.float32)
        seq = next(stores[0]._seq)
        with pytest.raises(RuntimeError, match="epoch_fence cur=1"):
            stores[0]._rpc(1, OP_PUSH, tid, key, grads.tobytes(), 0.1, 4,
                           shard=0, seq=seq, epoch=0)
        np.testing.assert_array_equal(
            stores[0].pull(tid, key)[0], before), "stale frame mutated!"
        assert fault_counts().get("ps_epoch_refused", 0) == 1
        # same seq, correct epoch: NOT a duplicate — applies once
        stores[0]._rpc(1, OP_PUSH, tid, key, grads.tobytes(), 0.1, 4,
                       shard=0, seq=seq, epoch=1)
        np.testing.assert_allclose(stores[0].pull(tid, key)[0],
                                   before - 0.1)     # sgd lr=0.1, once
    finally:
        _close_all(stores)


def test_old_epoch_replicate_frame_refused_without_mutation():
    """Satellite: a stale lineage's op-log forward (OP_REPLICATE) into
    the promoted copy is refused + counted, and the inner push never
    lands."""
    stores, tid, _ = _cluster()
    try:
        stores[0]._failover(0)                       # rank 1: epoch 1
        key = np.asarray([0], np.int64)
        before = stores[0].pull(tid, key)[0].copy()
        inner = _HDR.pack(OP_PUSH, tid, 1, 0.1, 4, 99,
                          time.time_ns(), 0, 0) \
            + key.tobytes() + np.ones((1, 4), np.float32).tobytes()
        with pytest.raises(RuntimeError, match="epoch_fence cur=1"):
            stores[0]._rpc(1, OP_REPLICATE, 0, np.asarray([0], np.int64),
                           payload=inner, epoch=0)
        np.testing.assert_array_equal(stores[0].pull(tid, key)[0], before)
        assert fault_counts().get("ps_epoch_refused", 0) == 1
    finally:
        _close_all(stores)


def test_stale_ex_primary_demotes_and_stale_client_reroutes():
    """The tentpole's convergence story end to end (no wire partition
    needed — the lineages alone reproduce it): rank 1 is promoted for
    shard 0 while rank 0 still believes it serves.  A stale client
    (rank 1's store, route + epoch both old) pushes through rank 0:
    rank 0 applies locally, its forward is epoch-refused by rank 1,
    rank 0 DEMOTES itself instead of acking, the client learns the
    epoch from the refusal, re-routes, and the SAME op lands on the
    surviving lineage exactly once."""
    stores, tid, _ = _cluster()
    try:
        stores[0]._failover(0)          # rank 1 now serves shard 0 @ e1
        assert stores[1]._epoch[0] == 0 and stores[1]._route[0] == 0
        key = np.asarray([0], np.int64)
        before = stores[0].pull(tid, key)[0].copy()  # surviving lineage
        stores[1].push(tid, key, np.ones((1, 4), np.float32))
        # the write was acked — on the SURVIVING lineage, exactly once
        np.testing.assert_allclose(stores[0].pull(tid, key)[0],
                                   before - 0.1)
        fc = fault_counts()
        assert fc.get("ps_epoch_refused", 0) >= 1
        assert fc.get("ps_demotions", 0) == 1
        assert not stores[0].server.serves(0), "stale ex-primary serves!"
        assert stores[1]._route[0] == 1 and stores[1]._epoch[0] == 1
        # lineage introspection agrees: one serving copy, epoch 1
        assert stores[1].shard_epoch(0) == (1, True)       # rank 1
        assert stores[1].shard_epoch(0, rank=0) == (1, False)  # demoted
    finally:
        _close_all(stores)


def test_demoted_copy_needs_sync_before_promotion():
    """A demoted ex-primary's copy may hold writes the surviving lineage
    never saw — it must refuse promotion until an epoch-checked OP_SYNC
    lands, then serve again (epoch advances past every prior lineage)."""
    stores, tid, _ = _cluster()
    try:
        stores[0]._failover(0)                       # rank 1 @ epoch 1
        key = np.asarray([0], np.int64)
        stores[1].push(tid, key, np.ones((1, 4), np.float32))  # demotes 0
        assert not stores[0].server.serves(0)
        # without re-replication, promoting rank 0's copy must refuse
        with pytest.raises(RuntimeError, match="not promotable|never"):
            stores[1]._rpc(0, OP_PROMOTE, 0,
                           np.asarray([0, 1, 2], np.int64))
        # epoch-checked re-replication restores it as a valid backup
        stores[1].re_replicate(0)
        assert stores[1].table_checksum(tid, 0, rank=0) \
            == stores[1].table_checksum(tid, 0, rank=1)
        # now a second failover can promote it: epoch 1 -> 2
        expected = stores[1].pull(tid, key)[0].copy()
        stores[1].server.stop()
        got = stores[0].pull(tid, key)[0]            # fails over to rank 0
        np.testing.assert_array_equal(got, expected)
        assert stores[0]._route[0] == 0
        assert stores[0]._epoch[0] == 2
        assert stores[0].shard_epoch(0, rank=0) == (2, True)
    finally:
        _close_all(stores)


def test_broken_forward_primary_probes_lineage_and_demotes(monkeypatch):
    """A stale ex-primary whose forwarding broke with a TRANSPORT error
    (not a fence) has no op-log path left to learn it was deposed — the
    rate-limited broken-forward probe is that path: the next write after
    the cut heals finds the other holder at a newer epoch, demotes, and
    refuses instead of acking onto the losing lineage."""
    monkeypatch.setenv("HETU_PS_FENCE_PROBE_S", "0")
    stores, tid, _ = _cluster()
    try:
        # rank 0's forwarding for shard 0 broke during "the partition"
        # (simulated: transport failure already recorded, fwd disabled)
        stores[0].server._fwd_ok[0] = False
        stores[0]._failover(0)           # meanwhile rank 1 was promoted
        key = np.asarray([0], np.int64)
        surviving = stores[0].pull(tid, key)[0].copy()   # rank 1's copy
        # stale client writes through the still-serving stale ex-primary:
        # the forward path is dead, so the PROBE must do the fencing
        stores[1].push(tid, key, np.ones((1, 4), np.float32))
        np.testing.assert_allclose(stores[0].pull(tid, key)[0],
                                   surviving - 0.1)      # once, rank 1
        assert not stores[0].server.serves(0)
        assert fault_counts().get("ps_demotions", 0) == 1
        assert stores[1]._route[0] == 1 and stores[1]._epoch[0] == 1
    finally:
        _close_all(stores)


# ----------------------------------------------- liveness vs partition

def test_liveness_report_distinguishes_unreachable_from_dead():
    """Satellite: a rank that misses heartbeats while still answering a
    direct probe is UNREACHABLE (partition — counted ps_unreachable),
    one that answers nothing is DEAD."""
    stores, tid, _ = _cluster(replication=1)
    try:
        stores[0].heartbeat(rank=0)
        stores[0].heartbeat(rank=1)
        time.sleep(0.35)
        stores[0].heartbeat(rank=0)         # rank 1 goes heartbeat-silent
        rep = stores[0].liveness_report(250)
        assert rep == {"alive": [0], "dead": [], "unreachable": [1]}
        assert fault_counts().get("ps_unreachable", 0) == 1
        stores[1].server.stop()             # now it is REALLY dead
        rep = stores[0].liveness_report(250)
        assert rep == {"alive": [0], "dead": [1], "unreachable": []}
    finally:
        _close_all(stores)


# --------------------------------------------------- fsck: retries + lineage

def test_fsck_retries_clear_transient_but_keep_stable_divergence():
    """Satellite: an in-flight-frame false mismatch (simulated by a probe
    that lies once) clears under --retries; a REAL divergence survives
    every pass and still fails."""
    from tools import ps_fsck
    stores, tid, ports = _cluster()
    endpoints = [("127.0.0.1", p) for p in ports]
    try:
        lied = []

        def flaky(endpoint, shard, table, timeout=10.0):
            if not lied:                 # first probe lies: a frame "in
                lied.append(1)           # flight" between the two reads
                return "ok", "transient-bogus-digest"
            return ps_fsck.checksum(endpoint, shard, table,
                                    timeout=timeout)

        rep = ps_fsck.fsck(endpoints, n_tables=1, replication=2,
                           retries=2, retry_wait=0.01, probe=flaky)
        assert rep["ok"], rep
        assert rep["retries_used"] == 1
        assert rep["transient_cleared"] == 1
        # a REAL divergence: corrupt rank 1's backup behind the op-log
        stores[1].server._stores[0].set_data(
            tid, np.zeros((8, 4), np.float32))
        rep = ps_fsck.fsck(endpoints, n_tables=1, replication=2,
                           retries=2, retry_wait=0.01)
        assert not rep["ok"]
        assert rep["retries_used"] == 2
        assert any(m["shard"] == 0 for m in rep["mismatches"])
    finally:
        _close_all(stores)


def test_fsck_reports_epochs_and_flags_split_brain():
    """Satellite: fsck exposes per-shard fencing epochs + serving ranks,
    and a shard with TWO serving holders (unconverged split brain) is a
    lineage violation that fails --verify even when digests agree."""
    from tools import ps_fsck
    stores, tid, ports = _cluster()
    endpoints = [("127.0.0.1", p) for p in ports]
    try:
        rep = ps_fsck.fsck(endpoints, n_tables=1, replication=2)
        assert rep["ok"]
        assert rep["serving_ranks"] == {0: [0], 1: [1]}
        assert rep["epochs"][0][0] == {"status": "ok", "epoch": 0,
                                       "serving": True, "error": None}
        # force a split brain: promote rank 1's copy of shard 0 while
        # rank 0 still serves it (no writes — digests stay EQUAL, only
        # the lineage check can catch this)
        stores[1].server._promote(0, 1, want_epoch=1)
        rep = ps_fsck.fsck(endpoints, n_tables=1, replication=2)
        assert not rep["ok"]
        assert not rep["mismatches"], "digests should agree here"
        assert rep["serving_ranks"][0] == [0, 1]
        assert rep["lineage_violations"][0]["shard"] == 0
        # CLI --verify gates on it too
        ep_arg = ",".join(f"127.0.0.1:{p}" for p in ports)
        assert ps_fsck.main(["--endpoints", ep_arg, "--tables", "1",
                             "--verify"]) == 1
    finally:
        _close_all(stores)


# --------------------------------------------------------- cell tagging

def test_cellmap_tagging_and_partition_spec():
    from hetu_tpu.serving import CellMap
    cm = CellMap({"west": [0, 1], "east": [2, 3]})
    assert cm.world == 4
    assert cm.cell_of(1) == "west" and cm.cell_of(3) == "east"
    assert cm.ranks("east") == [2, 3]
    assert cm.is_local("west", 0) and not cm.is_local("west", 2)
    assert cm.partition_spec("west", "east", 3, 7) \
        == "partition:rank0+rank1|rank2+rank3@step3:heal7"
    spec = cm.partition_spec("west", "east", 3)
    assert spec.endswith("@step3")
    # the emitted spec round-trips through the chaos parser
    _, faults = chaos.parse_spec("7:" + cm.partition_spec(
        "west", "east", 3, 7))
    assert faults[0]["a"] == frozenset({0, 1})
    assert faults[0]["b"] == frozenset({2, 3})


def test_cellmap_validation_is_loud():
    from hetu_tpu.serving import CellMap
    with pytest.raises(ValueError, match="disjoint"):
        CellMap({"a": [0, 1], "b": [1, 2]})
    with pytest.raises(ValueError, match="exactly once"):
        CellMap({"a": [0], "b": [2]})        # rank 1 untagged
    with pytest.raises(ValueError, match="tags no ranks"):
        CellMap({"a": [], "b": [0]})


# ------------------------------------------- CI smoke of the acceptance

@pytest.mark.timeout(420)
def test_partition_scenario():
    """Partition shard 1's primary from its clients at step
    3, heal at step 7 — zero restarts, zero lost acked writes (bitwise
    loss parity in BOTH chaos variants), the healed stale ex-primary
    epoch-refused + demoted, post-heal fsck(retries=2) zero stable
    divergence + one serving epoch per shard, the unhealed run's split
    brain visible, and the 2-cell scenario serving local reads through
    the cut (rejections=0) and converging after heal."""
    import scenarios
    extra = scenarios.partition_scenario(steps=10)
    assert extra["loss_parity_heal"] is True
    assert extra["loss_parity_noheal"] is True
    assert extra["probe_acked"] is True
    assert extra["re_replication_deferred_in_partition"] is True
    fc = extra["fault_counters"]
    assert fc["partition_frames_dropped"] > 0
    assert fc["ps_epoch_refused"] > 0
    assert fc["ps_demotions"] > 0
    assert fc["ps_epoch_bumps"] > 0
    assert extra["fsck_ok"] is True
    assert extra["fsck_serving_ranks"][1] == [2]
    assert all(len(v) == 1 for v in extra["fsck_serving_ranks"].values())
    assert extra["noheal_split_brain_detected"] is True
    assert extra["clean_run_counters"] == {}
    two = extra["two_cell"]
    assert two["ok"] is True
    assert two["served_through_cut"] is True
    assert all(s["rejections"] == 0 for s in two["cell_stats"].values())
    assert two["fsck_ok"] is True
    for conf in ("protocol_conformance", "noheal_protocol_conformance",
                 "clean_protocol_conformance"):
        assert extra[conf]["ok"] is True, extra[conf]
    assert extra["ok"] is True
