"""ISSUE 19 acceptance: exactly-once recovery of in-flight generations.

A decode stream's emitted-token journal + replay epoch make replica
death survivable: the sweep detaches seated sequences as continuation
requests, the least-loaded survivor re-seats them through chunked
prefill (prefix store first), and the recovered stream is BITWISE equal
to an unkilled run — already-resolved ``token(i)`` futures never
re-fire.  Doomed streams (no survivor / retry budget / deadline) fail
fast with ``recovery_exhausted`` carrying the partial tokens, and the
wedge condition now sees seated-but-unqueued work (the pre-ISSUE-19
eject bug).
"""
import threading
import time
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from hetu_tpu import chaos as chaos_mod                    # noqa: E402
from hetu_tpu import metrics as hmetrics                   # noqa: E402
from hetu_tpu import race                                  # noqa: E402
from hetu_tpu.models import (GPT2Config,                   # noqa: E402
                             gpt2_decode_chunked_graph, gpt2_decode_graph)
from hetu_tpu.serving import (DecodeEngine, DecodeRouter,  # noqa: E402
                              FrontDoor, PrefixKVStore, ServeRejected)
from hetu_tpu.serving.decode import (_continuation,        # noqa: E402
                                     _DecodeRequest, DecodeStream)

_CFG = GPT2Config.tiny(n_positions=64, batch_size=1, seq_len=16)
_MAX_LEN = 16


@pytest.fixture(autouse=True)
def _reset_counters():
    hmetrics.reset_decode_counts()
    hmetrics.reset_decode_recovery_counts()
    hmetrics.reset_fleet_counts()
    hmetrics.reset_serve_rejection_counts()
    hmetrics.reset_prefix_cache_counts()
    yield


@pytest.fixture(scope="module")
def graphs():
    """One tiny one-token graph + one chunked graph shared by the
    module (weight init is seed-deterministic per graph, so every
    engine built from these produces identical token streams)."""
    return (gpt2_decode_graph(_CFG, max_len=_MAX_LEN),
            gpt2_decode_chunked_graph(_CFG, max_len=_MAX_LEN))


def _engine(graphs, chunked=True, **kw):
    (feeds, logits, caches, _), cg = graphs
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", _MAX_LEN)
    if chunked:
        kw.setdefault("chunked", (cg[0], cg[1], cg[2]))
    return DecodeEngine(feeds, logits, caches, seed=0, **kw)


_REF_CACHE = {}


@pytest.fixture(scope="module")
def ref(graphs):
    """Uninterrupted single-engine reference stream per (prompt,
    max_new) — what a never-killed run delivers (ISSUE 18 already
    proves chunked == incremental, so one incremental engine serves
    as the reference for every mode)."""
    eng = _engine(graphs, chunked=False, max_slots=2)

    def _ref(prompt, max_new):
        key = (tuple(int(t) for t in prompt), int(max_new))
        if key not in _REF_CACHE:
            req = _DecodeRequest(np.asarray(prompt, np.int32), max_new,
                                 None, None)
            eng.join(req)
            while eng.active:
                eng.step()
            _REF_CACHE[key] = req.stream.result(timeout=60)
        return _REF_CACHE[key]

    return _ref


def _fleet(graphs, n=2, *, chunked=True, shared_store=False, **door_kw):
    routers = {}
    store = PrefixKVStore() if shared_store else None

    def mk(idx):
        eng = _engine(graphs, chunked=chunked, prefix_store=store)
        routers[idx] = DecodeRouter(eng, queue_limit=16, name=f"rec{idx}")
        return routers[idx]

    door_kw.setdefault("health_every_ms", 1e9)
    # a first-encounter bucket compile inside engine.step can stall the
    # loop for seconds on CPU — far past the production wedge default —
    # and the seated mirror now makes that visible to the sweep, so
    # tests not about wedging push the threshold out of the way
    door_kw.setdefault("wedge_timeout_ms", 1e9)
    return FrontDoor(mk, n, **door_kw), routers


def _poll_until_done(door, streams, timeout=90.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        door.poll()
        if all(s.done for s in streams):
            return True
        time.sleep(0.01)
    return False


# ------------------------------------------------- journal + epoch unit

def test_stream_epoch_fencing_is_exactly_once():
    """The tentpole's core mechanism, no engine involved: ``_detach``
    bumps the epoch atomically with the journal snapshot, every stale-
    epoch mutation is a fenced no-op, and a future that resolved once
    never re-fires."""
    s = DecodeStream(prompt_len=2, max_new_tokens=4)
    fired = {i: 0 for i in range(4)}
    for i in range(4):
        s.token(i).add_done_callback(
            lambda f, i=i: fired.__setitem__(i, fired[i] + 1))
    assert s._emit(7, epoch=0) == 1
    assert s._emit(8, epoch=0) == 2
    epoch, journal = s._detach()
    assert (epoch, journal) == (1, [7, 8])
    # the dead replica wakes up: every mutation under epoch 0 is fenced
    assert s._emit(99, epoch=0) is False
    assert s._finish(epoch=0) is False
    assert s._fail(RuntimeError("stale"), epoch=0) is False
    assert s.partial() == [7, 8] and not s.done
    # the survivor continues at the NEXT index under the new epoch
    assert s._emit(9, epoch=1) == 3
    assert s._emit(10, epoch=1) == 4
    assert s._finish(epoch=1) is True
    assert s.result(timeout=5) == [7, 8, 9, 10]
    assert fired == {0: 1, 1: 1, 2: 1, 3: 1}


def test_continuation_carries_journal_deadline_and_retry():
    """A continuation replays prompt + journal with the remaining token
    budget, the SAME stream, the original arrival/deadline, and a
    bumped retry count — and building it counts the detach."""
    req = _DecodeRequest(np.asarray([3, 5, 11], np.int32), 6, None, None,
                         deadline=12345.0)
    req.stream._emit(7, epoch=0)
    req.stream._emit(8, epoch=0)
    cont = _continuation(req)
    assert cont.prompt.tolist() == [3, 5, 11, 7, 8]
    assert cont.max_new == 4 and cont.eos_id is None
    assert cont.stream is req.stream
    assert cont.t_arrival == req.t_arrival
    assert cont.deadline == 12345.0
    assert cont.epoch == req.stream.epoch == 1
    assert cont.retries == 1 and cont.detached_ts is not None
    c = hmetrics.decode_recovery_counts()
    assert c["decode_recovery_detached"] == 1
    assert c.get("decode_recovery_retries", 0) == 0   # first recovery
    cont2 = _continuation(cont)
    assert cont2.retries == 2 and cont2.prompt.tolist() == [3, 5, 11, 7, 8]
    assert hmetrics.decode_recovery_counts()["decode_recovery_retries"] == 1


# ------------------------------------------- bitwise continuation parity

def test_mid_generation_kill_bitwise_parity_solo(graphs, ref):
    """A mid-generation replica kill is invisible in the token stream:
    the rescued stream equals the unkilled reference bitwise, and every
    token future fires exactly once (no gap, no re-fire)."""
    prompt, max_new = [3, 5, 9], 10
    expect = ref(prompt, max_new)
    door, routers = _fleet(graphs, 2, chunked=False)
    try:
        s = door.submit(prompt, max_new_tokens=max_new)
        fired = [0] * max_new
        for i in range(max_new):
            s.token(i).add_done_callback(
                lambda f, i=i: fired.__setitem__(i, fired[i] + 1))
        s.token(1).result(timeout=60)      # mid-generation, journal >= 2
        routers[0].kill()
        assert _poll_until_done(door, [s])
        assert s.result(timeout=5) == expect
        assert fired == [1] * max_new
        c = hmetrics.decode_recovery_counts()
        assert c["decode_recovery_detached"] == 1
        assert c["decode_recovery_reseated"] == 1
        assert c["decode_recovery_replayed_rows"] > 0   # cold: no store
        assert hmetrics.fleet_counts().get("fleet_request_failures", 0) == 0
        assert door.stats()["failures"] == 0
    finally:
        door.close()


def test_crowded_kill_bitwise_parity_with_prefix_assist(graphs, ref):
    """A crowded batch over chunked engines + a SHARED prefix store:
    the dead replica's own prompt snapshot seats its continuations with
    rows pre-filled (``prefix_assisted``), batch mates on the survivor
    are undisturbed, and every stream matches its reference bitwise."""
    base = [5, 3, 9, 2]
    prompts = [base + [7], base + [11], [2, 4, 6, 8, 1], [13, 1, 5]]
    max_new = 8
    expect = [ref(p, max_new) for p in prompts]
    door, routers = _fleet(graphs, 2, chunked=True, shared_store=True)
    # pin replica 0 mid-generation: on a warm process (serve cache primed
    # by earlier test modules) steps run in ~1ms, so by the time four
    # token(1) waits resolve replica 0's streams may have FINISHED and a
    # kill would find nothing in flight — gate its engine loop once its
    # two streams (dispatch tiebreak (pending, cost, idx) seats streams
    # 0 and 2 there) each hold two tokens, so the kill always lands on
    # live in-flight work
    release = threading.Event()
    watch = []
    orig_collect = routers[0].engine.collect
    def gated_collect(fl, ph):
        if watch and all(s.n_tokens >= 2 for s in watch) \
                and not release.is_set():
            release.wait(timeout=60)
        return orig_collect(fl, ph)
    routers[0].engine.collect = gated_collect
    try:
        streams = [door.submit(p, max_new_tokens=max_new)
                   for p in prompts]
        watch.extend([streams[0], streams[2]])
        for s in streams:
            s.token(1).result(timeout=60)
        routers[0].kill()
        assert _poll_until_done(door, streams)
        for s, want in zip(streams, expect):
            assert s.result(timeout=5) == want
        c = hmetrics.decode_recovery_counts()
        assert c["decode_recovery_reseated"] >= 1
        # the shared store turns replay into a hit: the original-prompt
        # rows seat for free, only the journal suffix re-prefills
        assert c.get("decode_recovery_prefix_assisted", 0) >= 1
        assert hmetrics.fleet_counts().get("fleet_request_failures", 0) == 0
    finally:
        release.set()
        door.close()


def test_chaos_token_clock_kill_drives_same_path(graphs, ref):
    """``kill:replica@0:tok6`` on the ENGINE's deterministic token
    clock: the 6th cumulative emitted token on replica 0 fail-stops it
    mid-generation, the sweep resurrects its streams, and every stream
    still matches the unkilled reference."""
    hmetrics.reset_faults()
    prompts = [[3, 5, 9], [4, 1, 2], [6, 6, 1]]
    max_new = 8
    expect = [ref(p, max_new) for p in prompts]
    inj = chaos_mod.ChaosInjector.from_spec("7:kill:replica@0:tok6")
    prev = chaos_mod.install(inj)
    try:
        door, routers = _fleet(graphs, 2, chunked=False)
        try:
            streams = [door.submit(p, max_new_tokens=max_new)
                       for p in prompts]
            assert _poll_until_done(door, streams)
            for s, want in zip(streams, expect):
                assert s.result(timeout=5) == want
            assert hmetrics.fault_counts().get("chaos_kill_replica") == 1
            assert hmetrics.fleet_counts()["fleet_replica_ejected"] == 1
            c = hmetrics.decode_recovery_counts()
            assert c["decode_recovery_reseated"] >= 1
        finally:
            door.close()
    finally:
        chaos_mod.install(prev)


# ----------------------------------------------- gated failure surfaces

def test_recovery_budget_exhausted_fails_fast_with_partial(graphs):
    """``recovery_budget=0``: the FIRST recovery attempt already
    exceeds the budget — the stream fails fast with
    ``recovery_exhausted`` carrying the tokens it did deliver."""
    door, routers = _fleet(graphs, 2, chunked=False, recovery_budget=0)
    try:
        s = door.submit([3, 5, 9], max_new_tokens=10)
        s.token(1).result(timeout=60)
        routers[0].kill()
        door.poll()
        with pytest.raises(ServeRejected) as ei:
            s.result(timeout=30)
        exc = ei.value
        assert exc.reason == "recovery_exhausted"
        assert "retry budget" in str(exc)
        assert isinstance(exc.partial, list) and len(exc.partial) >= 2
        assert exc.partial == s.partial()
        c = hmetrics.decode_recovery_counts()
        assert c["decode_recovery_exhausted"] == 1
        assert c.get("decode_recovery_reseated", 0) == 0
        assert hmetrics.serve_rejection_counts()["recovery_exhausted"] >= 1
        assert door.stats()["failures"] == 1
    finally:
        door.close()


def test_recovery_deadline_estimator_refuses_doomed_resurrection(graphs):
    """The recovery gate reuses the door's deadline estimator: a
    survivor too slow to replay + finish before the stream's original
    deadline means fail fast, not a doomed reseat."""
    door, routers = _fleet(graphs, 2, chunked=False,
                           forward_deadline_ms=True)
    try:
        s = door.submit([3, 5, 9], max_new_tokens=10, deadline_ms=60000.0)
        s.token(1).result(timeout=60)
        for rep in door._replicas:          # survivor looks glacial
            rep.cost_ms = 1e9
        routers[0].kill()
        door.poll()
        with pytest.raises(ServeRejected) as ei:
            s.result(timeout=30)
        assert ei.value.reason == "recovery_exhausted"
        assert "deadline" in str(ei.value)
        assert len(ei.value.partial) >= 2
    finally:
        door.close()


def test_zero_survivor_kill_fails_loudly_with_partial(graphs):
    """Killing the only replica mid-generation: nothing can adopt the
    stream, so it fails LOUDLY — ``recovery_exhausted``, partial tokens
    attached, counted — never a silent hang."""
    door, routers = _fleet(graphs, 1, chunked=False)
    try:
        s = door.submit([3, 5, 9], max_new_tokens=10)
        s.token(1).result(timeout=60)
        routers[0].kill()
        door.poll()
        with pytest.raises(ServeRejected) as ei:
            s.result(timeout=30)
        assert ei.value.reason == "recovery_exhausted"
        assert "no survivor" in str(ei.value)
        assert len(ei.value.partial) >= 2
        assert hmetrics.decode_recovery_counts()[
            "decode_recovery_exhausted"] == 1
    finally:
        door.close()


# --------------------------------------------------- wedge-eject (bug)

def test_wedged_replica_with_only_seated_work_is_ejected(graphs, ref):
    """Regression for the pre-ISSUE-19 eject bug: a replica wedged
    mid-device-call with an EMPTY queue (its whole batch seated) used
    to report pending=0 and was never ejected.  The seated mirror now
    counts, the sweep ejects, the stream migrates — and the wedged
    loop's eventual late emission is fenced, not double-delivered."""
    prompt, max_new = [3, 5, 9], 12
    expect = ref(prompt, max_new)
    door, routers = _fleet(graphs, 2, chunked=False,
                           wedge_timeout_ms=75.0)
    release = threading.Event()
    orig_collect = routers[0].engine.collect
    holder = {}

    def wedge_collect(fl, ph):
        # wedge where a step's answer is waited for, once the stream has
        # a token out: the loop is "inside a device call" from the
        # router's view (a later step already launched), and the
        # post-release collect emits under the by-then-stale epoch
        s = holder.get("s")
        if s is not None and s.n_tokens >= 1 and not release.is_set():
            release.wait(timeout=60)
        return orig_collect(fl, ph)

    routers[0].engine.collect = wedge_collect
    try:
        s = holder["s"] = door.submit(prompt, max_new_tokens=max_new)
        s.token(0).result(timeout=60)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            snap = routers[0].health()
            if snap["queued"] == 0 and snap["pending"] >= 1:
                break
            time.sleep(0.005)
        # the regression: seated-but-unqueued work IS pending work
        snap = routers[0].health()
        assert snap["queued"] == 0 and snap["pending"] >= 1
        time.sleep(0.15)                # heartbeat goes stale mid-step
        door.poll()
        assert hmetrics.fleet_counts()["fleet_replica_ejected"] == 1
        assert _poll_until_done(door, [s])
        assert s.result(timeout=5) == expect
        assert hmetrics.decode_recovery_counts()[
            "decode_recovery_reseated"] == 1
    finally:
        release.set()
        door.close()
    # the wedged loop woke inside its stale step: whatever it emitted
    # after the detach was fenced by the epoch, never re-delivered
    assert hmetrics.decode_recovery_counts().get(
        "decode_recovery_fenced", 0) >= 1


# ---------------------------------- a step in flight (ISSUE 32)

def test_deadline_eviction_with_a_step_in_flight(graphs, ref):
    """The router evicts with step n+1 on the device: the evicted row's
    answer is dropped at that step's collect — what was delivered stays
    a prefix of the uninterrupted stream, nothing after the failure — and
    the slot's next occupant decodes as ever."""
    prompt, max_new = [3, 5, 9], 12
    expect = ref(prompt, max_new)
    hmetrics.reset_decode_counts()       # less the reference's own steps
    eng = _engine(graphs, chunked=False, max_slots=1)
    collect, held = eng.collect, []

    def slow_collect(fl, ph):
        # once two tokens are out, hold the loop past the deadline with a
        # later step launched: the next iteration's evict finds the row
        if held and held[0].n_tokens >= 2 and eng.in_flight is not fl:
            time.sleep(0.3)
        return collect(fl, ph)

    eng.collect = slow_collect
    with DecodeRouter(eng) as router:
        warm = router.submit(prompt, max_new_tokens=max_new)
        assert warm.result(timeout=60) == expect      # compiles first
        s = router.submit(prompt, max_new_tokens=max_new, deadline_ms=250)
        held.append(s)
        with pytest.raises(ServeRejected) as e:
            s.result(timeout=60)
        assert e.value.reason == "deadline"
        got = s.partial()
        assert 2 <= len(got) < max_new and got == expect[:len(got)]
        held.clear()
        again = router.submit(prompt, max_new_tokens=max_new)
        assert again.result(timeout=60) == expect
        assert router.drain(timeout=60) and eng.in_flight is None
    c = hmetrics.decode_counts()
    assert c["decode_deadline_evictions"] == 1
    assert c["decode_tokens"] == 2 * max_new + len(got)
    # the evicted row's step in flight was collected (and counted), empty
    assert s.n_tokens == len(got)


def test_kill_with_a_step_in_flight_loses_no_token_and_repeats_none(
        graphs, ref):
    """``kill`` lands between a launch and its collect: the un-collected
    step is lost with the replica and nothing else — the journal holds
    emitted tokens only, so the continuation on the survivor resumes at
    the next index and the stream is the uninterrupted one."""
    prompt, max_new = [3, 5, 9], 12
    expect = ref(prompt, max_new)
    door, routers = _fleet(graphs, 2, chunked=False)
    eng = routers[0].engine
    collect, seen = eng.collect, []

    def killing_collect(fl, ph):
        emitted = collect(fl, ph)
        if seen and seen[0].n_tokens >= 3 and eng.in_flight is not None \
                and not routers[0].health()["killed"]:
            routers[0].kill()            # a step launched, not collected
        return emitted

    eng.collect = killing_collect
    try:
        s = door.submit(prompt, max_new_tokens=max_new)
        seen.append(s)
        assert _poll_until_done(door, [s])
        assert s.result(timeout=5) == expect
        assert hmetrics.decode_recovery_counts()[
            "decode_recovery_reseated"] == 1
        assert eng.in_flight is not None     # dropped with the replica
    finally:
        door.close()


# -------------------------------------------------- recovery vs close

@pytest.mark.parametrize("first", ["recovery.adopt", "decode.close"])
def test_race_recovery_vs_survivor_close(graphs, first):
    """Forced interleavings of stream rescue against the survivor's own
    shutdown (both orders): whichever side wins, every stream
    TERMINATES — a completed result or a structured failure — and no
    future fires twice or hangs."""
    seed = next(s for s in range(64)
                if race.RaceSchedule("recovery.adopt", "decode.close",
                                     seed=s).order[0] == first)
    door, routers = _fleet(graphs, 2, chunked=False)
    s = door.submit([3, 5, 9], max_new_tokens=10)
    s.token(0).result(timeout=60)
    routers[0].kill()
    sched = race.RaceSchedule("recovery.adopt", "decode.close",
                              seed=seed, timeout_ms=5000.0)
    race.install(sched)
    try:
        t_poll = threading.Thread(target=door.poll)
        t_close = threading.Thread(target=routers[1].close)
        t_poll.start()
        t_close.start()
        t_poll.join(timeout=30)
        t_close.join(timeout=30)
        assert not t_poll.is_alive() and not t_close.is_alive()
    finally:
        race.uninstall()
        door.close()
    deadline = time.monotonic() + 10
    while not s.done and time.monotonic() < deadline:
        time.sleep(0.01)
    assert s.done, "stream neither completed nor failed"
    try:
        toks = s.result(timeout=5)
        assert len(toks) == 10          # adopt won and finished cleanly
    except ServeRejected as exc:
        assert exc.reason in ("recovery_exhausted", "draining")
    # exactly-once: every resolved token future fired, none pending
    for i in range(s.n_tokens):
        assert s.token(i).done()
