"""Fleet serving tier (ISSUE 17): replica sets behind a FrontDoor with
load-aware dispatch, class-based admission control, health ejection +
queue rescue, SLO autoscaling on the elastic plane's flap-damping
machinery, and graceful drain.

Coverage map (the ISSUE's acceptance):
- dispatch picks the least-loaded healthy replica, lowest index on ties
  (deterministic)
- overload sheds lowest class first as structured ``shed:<class>``
  rejections, counted per reason; interactive holds to the hard
  aggregate bound (``queue_full``); per-class deadlines reject at the
  door (``deadline``)
- a killed replica is ejected at the next sweep, its QUEUED requests
  rescued onto a survivor — every admitted request answered, zero
  restarts; a chaos ``kill:replica@<idx>:req<n>`` drives the same path
  on the door's admission clock
- a killed DECODE replica's seated in-flight streams are detached as
  continuation requests and resurrected on a survivor (ISSUE 19 —
  bitwise parity + gating live in tests/test_decode_recovery.py)
- a wedge-ejected replica whose heartbeat returns is re-admitted; the
  wedge condition sees seated-but-unqueued work, not just the queue
- scale-out builds no new executable: the new replica's bucket resolves
  through the serve arm of the step cache (``step_cache_serve_hit``)
- scale-in / close drain gracefully: queued work handed to a survivor,
  in-flight work finished, nothing dropped
- FlapDamper (extracted from ElasticController's rejoin bookkeeping)
  gates the autoscaler: grow/shrink only after N consecutive breaching
  polls, never past the bounds (refused grows counted)
- the ServeRejected reason vocabulary is validated at construction and
  counted in ``serve_rejection_reason``
- the same replica contract works over DecodeRouter replicas
"""
import time

import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import chaos as chaos_mod
from hetu_tpu import metrics as hmetrics
from hetu_tpu.parallel.elastic import FlapDamper
from hetu_tpu.serving import (FrontDoor, InferenceExecutor, ServeRejected,
                              ServingRouter, SLOAutoscaler)
from hetu_tpu.serving.fleet import CLASSES

W0 = (np.arange(12, dtype=np.float32).reshape(3, 4) * 0.1) - 0.5
X = ht.placeholder_op("x_fleet")
Y = ht.matmul_op(X, ht.Variable("w_fleet", value=W0.copy()))


def _mk(idx, *, start=True, queue_limit=16, max_wait_ms=1.0,
        max_batch=8):
    return ServingRouter(InferenceExecutor([Y], buckets=(8,)),
                         max_batch=max_batch, max_wait_ms=max_wait_ms,
                         queue_limit=queue_limit, start=start,
                         name=f"r{idx}")


def _feed(v=0.0):
    return {X: np.full((3,), v, np.float32)}


@pytest.fixture(autouse=True)
def _reset_counters():
    hmetrics.reset_fleet_counts()
    hmetrics.reset_serve_rejection_counts()
    yield
    hmetrics.reset_fleet_counts()
    hmetrics.reset_serve_rejection_counts()


# ------------------------------------------------------------- dispatch

def test_dispatch_least_loaded_lowest_idx_tiebreak():
    """Paused replicas make queue depths fully observable: admissions
    alternate by pending count, ties broken by the LOWER index."""
    routers = {}

    def mk(idx):
        routers[idx] = _mk(idx, start=False)
        return routers[idx]

    door = FrontDoor(mk, 2, health_every_ms=1e9)
    try:
        futs = [door.submit(_feed(i)) for i in range(4)]
        # tie at (0,0) -> r0; then (1,0) -> r1; tie at (1,1) -> r0 ...
        assert routers[0].pending == 2 and routers[1].pending == 2
        door.submit(_feed(9))
        assert routers[0].pending == 3      # tie again: lowest idx
        for r in routers.values():
            r.start()
        for f in futs:
            f.result(timeout=30)
        c = hmetrics.fleet_counts()
        assert c["fleet_admitted"] == c["fleet_dispatch"] == 5
    finally:
        door.close()


# ----------------------------------------------- admission control / shed

def test_shed_lowest_class_first_with_structured_reasons():
    """queue_limit=4 x2 replicas: at load 0.5 best_effort sheds, at
    0.875 batch sheds, interactive admits to the hard bound and then
    gets ``queue_full`` — each rejection a counted structured reason."""
    door = FrontDoor(lambda i: _mk(i, start=False, queue_limit=4), 2,
                     health_every_ms=1e9)
    try:
        for _ in range(4):                      # load 4/8 = 0.5
            door.submit(_feed(), klass="interactive")
        with pytest.raises(ServeRejected) as ei:
            door.submit(_feed(), klass="best_effort")
        assert ei.value.reason == "shed:best_effort"
        assert ei.value.klass == "best_effort"
        door.submit(_feed(), klass="batch")     # 0.5 < 0.85: batch rides
        for _ in range(2):                      # load 7/8 = 0.875
            door.submit(_feed(), klass="interactive")
        with pytest.raises(ServeRejected) as ei:
            door.submit(_feed(), klass="batch")
        assert ei.value.reason == "shed:batch"
        door.submit(_feed(), klass="interactive")   # 8/8: last seat
        with pytest.raises(ServeRejected) as ei:
            door.submit(_feed(), klass="interactive")
        assert ei.value.reason == "queue_full"
        rej = hmetrics.serve_rejection_counts()
        assert rej["shed:best_effort"] == 1
        assert rej["shed:batch"] == 1
        assert rej["queue_full"] == 1
        assert hmetrics.fleet_counts()["fleet_admitted"] == 8
        with pytest.raises(ValueError):
            door.submit(_feed(), klass="realtime")  # unknown class: loud
    finally:
        door.close(timeout=0.2)


def test_deadline_rejected_at_the_door():
    """A deadline the estimated wait cannot meet is rejected at
    admission (reason ``deadline``), not discovered by a timeout inside
    a batch; a roomy deadline admits."""
    door = FrontDoor(lambda i: _mk(i, start=False, queue_limit=16,
                                   max_batch=4), 1, health_every_ms=1e9)
    try:
        door.submit(_feed(), deadline_ms=1000.0)    # empty fleet: fits
        for _ in range(7):
            door.submit(_feed())
        # pending=8, max_batch=4, cost ~1ms -> ~3 batches ahead
        with pytest.raises(ServeRejected) as ei:
            door.submit(_feed(), deadline_ms=0.001)
        assert ei.value.reason == "deadline"
        assert hmetrics.serve_rejection_counts()["deadline"] == 1
    finally:
        door.close(timeout=0.2)


def test_class_default_deadlines_apply():
    door = FrontDoor(lambda i: _mk(i, start=False, max_batch=4), 1,
                     health_every_ms=1e9,
                     shed_at={"best_effort": None},     # isolate the gate
                     class_deadline_ms={"best_effort": 0.001})
    try:
        for _ in range(8):
            door.submit(_feed())
        with pytest.raises(ServeRejected) as ei:
            door.submit(_feed(), klass="best_effort")
        assert ei.value.reason == "deadline"
    finally:
        door.close(timeout=0.2)


# --------------------------------------------- health: eject / rescue

def test_killed_replica_ejected_queue_rescued_all_answered():
    """Replica 0 (paused, so its queue is captive) killed mid-load: the
    sweep ejects it and adopts its queued requests onto the survivor —
    every admitted request is answered, zero failures, zero restarts."""
    routers = {}

    def mk(idx):
        routers[idx] = _mk(idx, start=(idx != 0))
        return routers[idx]

    door = FrontDoor(mk, 2, health_every_ms=1e9)
    try:
        futs = [door.submit(_feed(i)) for i in range(6)]
        assert routers[0].pending > 0       # captive on the paused r0
        routers[0].kill()
        door.poll()
        res = [f.result(timeout=30) for f in futs]
        for i, row in enumerate(res):
            np.testing.assert_allclose(
                row[0], np.full((3,), i, np.float32) @ W0, rtol=1e-6)
        c = hmetrics.fleet_counts()
        assert c["fleet_replica_ejected"] == 1
        assert c["fleet_rescued"] >= 1
        assert c.get("fleet_request_failures", 0) == 0
        assert door.stats()["failures"] == 0
        assert door.n_replicas == 1
    finally:
        door.close()


def test_chaos_replica_kill_drives_same_path():
    """``kill:replica@0:req4`` on the door's admission clock: the door
    registers its replicas, the 4th admission kills r0, the sweep
    rescues — all admitted requests still answered."""
    from hetu_tpu.metrics import fault_counts, reset_faults
    reset_faults()
    routers = {}

    def mk(idx):
        routers[idx] = _mk(idx, start=(idx != 0))
        return routers[idx]

    inj = chaos_mod.ChaosInjector.from_spec("7:kill:replica@0:req4")
    prev = chaos_mod.install(inj)
    try:
        door = FrontDoor(mk, 2, health_every_ms=1e9)
        futs = [door.submit(_feed(i)) for i in range(6)]
        assert routers[0]._killed            # fired at admission #4
        door.poll()
        for f in futs:
            f.result(timeout=30)
        assert fault_counts().get("chaos_kill_replica") == 1
        assert hmetrics.fleet_counts()["fleet_replica_ejected"] == 1
        door.close()
    finally:
        chaos_mod.install(prev)


def test_wedged_replica_ejected_then_readmitted():
    """A paused replica with captive work and a stale heartbeat is a
    WEDGE: ejected (queue rescued); once its loop runs again the fresh
    heartbeat re-admits it."""
    routers = {}

    def mk(idx):
        routers[idx] = _mk(idx, start=(idx != 0))
        return routers[idx]

    # wedge threshold must sit ABOVE the router's 50ms idle-heartbeat
    # cadence (else a healthy idle loop reads as wedged) and below the
    # staleness we manufacture
    door = FrontDoor(mk, 2, health_every_ms=1e9, wedge_timeout_ms=75.0)
    try:
        futs = [door.submit(_feed(i)) for i in range(4)]
        time.sleep(0.15)                    # heartbeat goes stale
        door.poll()
        assert hmetrics.fleet_counts()["fleet_replica_ejected"] == 1
        assert door.n_replicas == 1
        for f in futs:                      # rescued work still answers
            f.result(timeout=30)
        routers[0].start()                  # loop runs: heartbeat back
        deadline = time.monotonic() + 10.0
        while door.n_replicas < 2 and time.monotonic() < deadline:
            door.poll()
            time.sleep(0.02)
        assert hmetrics.fleet_counts()["fleet_replica_readmitted"] == 1
        assert door.n_replicas == 2
    finally:
        door.close()


# --------------------------------------------------- scaling + drain

def test_scale_out_is_a_serve_cache_hit_not_a_compile():
    """The fleet's cheap-spin-up proof: replica N+1's bucket resolves
    through the serve arm of the step cache — ``step_cache_serve_hit``
    advances, ``serve_bucket_compiles`` does not."""
    door = FrontDoor(_mk, 1, health_every_ms=1e9)
    try:
        door.submit(_feed()).result(timeout=30)     # replica 0 compiles
        h0 = hmetrics.step_cache_counts().get("step_cache_serve_hit", 0)
        c0 = hmetrics.serve_counts().get("serve_bucket_compiles", 0)
        idx = door.scale_out()
        rep = door._by_idx(idx)
        rep.router.submit(_feed()).result(timeout=30)
        assert hmetrics.step_cache_counts()["step_cache_serve_hit"] \
            == h0 + 1
        assert hmetrics.serve_counts()["serve_bucket_compiles"] == c0
    finally:
        door.close()


def test_scale_in_drains_gracefully_and_never_to_zero():
    """scale_in retires the highest-index live replica: stops its
    admissions, hands its queue over, finishes in-flight work; the last
    replica is never retired."""
    routers = {}

    def mk(idx):
        routers[idx] = _mk(idx, start=False)
        return routers[idx]

    door = FrontDoor(mk, 2, health_every_ms=1e9)
    try:
        futs = [door.submit(_feed(i)) for i in range(6)]
        assert routers[1].pending > 0       # captive work on the victim
        routers[0].start()                  # only the survivor serves
        assert door.scale_in() == 1
        assert door.n_replicas == 1
        for f in futs:
            f.result(timeout=30)            # handed over, not dropped
        assert hmetrics.fleet_counts()["fleet_scale_in"] == 1
        assert door.scale_in() is None      # never drains itself to zero
        assert door.n_replicas == 1
    finally:
        door.close()


def test_close_answers_everything_then_rejects():
    door = FrontDoor(_mk, 2, health_every_ms=1e9)
    futs = [door.submit(_feed(i)) for i in range(8)]
    door.close()
    for f in futs:
        assert f.result(timeout=5) is not None      # already resolved
    with pytest.raises(ServeRejected) as ei:
        door.submit(_feed())
    assert ei.value.reason == "draining"


# ------------------------------------------------ autoscaler machinery

def test_flap_damper_consecutive_grace_gate():
    d = FlapDamper(3)
    assert not d.ready("k", True) and d.streak("k") == 1
    assert not d.ready("k", True)
    assert d.ready("k", True)               # 3rd consecutive: ready
    assert d.ready("k", True)               # stays ready while ok
    assert not d.ready("k", False)          # one miss resets the streak
    assert d.streak("k") == 0
    assert not d.ready("k", True)
    d.clear("k")
    assert d.streak("k") == 0
    d2 = FlapDamper(1)                      # grace floors at 1
    assert d2.ready("x", True)


class _FakeDoor:
    """Duck-typed FrontDoor for autoscaler unit tests: scripted p99 and
    load signals, counted resizes."""

    def __init__(self, n=1):
        self.n = n
        self.p99 = 0.0
        self.load = 0.0
        self.admitted = 0
        self.resets = 0

    def poll(self, now=None):
        pass

    def p99_ms(self):
        return self.p99

    def load_factor(self):
        return self.load

    @property
    def n_replicas(self):
        return self.n

    def scale_out(self):
        self.n += 1
        return self.n - 1

    def scale_in(self):
        if self.n <= 1:
            return None
        self.n -= 1
        return self.n

    def reset_window(self):
        self.resets += 1


def test_autoscaler_grows_after_grace_and_respects_max():
    door = _FakeDoor(1)
    sc = SLOAutoscaler(door, p99_target_ms=100.0, min_replicas=1,
                       max_replicas=2, grow_grace=2, shrink_grace=2)
    door.p99 = 500.0                        # hot
    assert sc.poll() is None                # 1st breach: damped
    ev = sc.poll()                          # 2nd consecutive: grow
    assert ev["kind"] == "scale_out"
    assert (ev["from_replicas"], ev["to_replicas"]) == (1, 2)
    assert door.n == 2 and door.resets == 1
    assert sc.poll() is None and sc.poll() is None  # at max: refused
    assert hmetrics.fleet_counts()["fleet_scale_refused"] >= 1
    assert door.n == 2
    assert [e["kind"] for e in sc.events] == ["scale_out"]


def test_autoscaler_grows_on_load_signal_alone():
    """Load crossing grow_load breaches even while p99 looks fine — the
    queue-pressure half of the grow condition."""
    door = _FakeDoor(1)
    sc = SLOAutoscaler(door, p99_target_ms=100.0, max_replicas=3,
                       grow_grace=1, grow_load=0.6)
    door.p99, door.load = 1.0, 0.9
    assert sc.poll()["kind"] == "scale_out"


def test_autoscaler_shrinks_after_grace_and_respects_min():
    door = _FakeDoor(3)
    sc = SLOAutoscaler(door, p99_target_ms=100.0, min_replicas=2,
                       max_replicas=4, grow_grace=2, shrink_grace=2,
                       shrink_load=0.2, low_p99_frac=0.3)
    door.p99, door.load = 5.0, 0.0          # cold
    assert sc.poll() is None
    ev = sc.poll()
    assert ev["kind"] == "scale_in" and door.n == 2
    assert sc.poll() is None and sc.poll() is None  # at min: holds
    assert door.n == 2
    # a hot poll mid-cold-streak resets the shrink damper
    door2 = _FakeDoor(3)
    sc2 = SLOAutoscaler(door2, p99_target_ms=100.0, min_replicas=1,
                        shrink_grace=2)
    door2.p99 = 5.0
    assert sc2.poll() is None
    door2.p99 = 500.0                       # flap: hot for one poll
    sc2.poll()
    door2.p99 = 5.0
    assert sc2.poll() is None               # streak restarted
    assert hmetrics.fleet_counts()["fleet_autoscaler_polls"] >= 7


# ------------------------------------------------- vocabulary validation

def test_serve_rejected_reason_vocabulary_is_validated_and_counted():
    before = dict(hmetrics.serve_rejection_counts())
    for reason in ("queue_full", "over_max_len", "deadline", "draining",
                   "shed:batch", "shed:best_effort"):
        exc = ServeRejected(reason, "detail", klass="batch")
        assert exc.reason == reason and exc.klass == "batch"
        assert str(exc) == f"{reason}: detail"
    after = hmetrics.serve_rejection_counts()
    for reason in ("queue_full", "over_max_len", "deadline", "draining",
                   "shed:batch", "shed:best_effort"):
        assert after.get(reason, 0) == before.get(reason, 0) + 1
    with pytest.raises(ValueError, match="vocabulary"):
        ServeRejected("bogus")
    with pytest.raises(ValueError):
        ServeRejected("queue full")         # old free-text form: dead
    assert set(CLASSES) == {"interactive", "batch", "best_effort"}


# --------------------------------------------------- decode-fleet rescue

def test_decode_fleet_kill_rescues_queued_streams():
    """The same replica contract over DecodeRouter: a killed decode
    replica's QUEUED streams are rescued onto the survivor and complete.
    (SEATED streams are resurrected too since ISSUE 19 — exactly-once
    migration is covered in tests/test_decode_recovery.py; this replica
    here never started, so everything is queued.)"""
    from hetu_tpu.models import GPT2Config, gpt2_decode_graph
    from hetu_tpu.serving import DecodeEngine, DecodeRouter
    cfg = GPT2Config.tiny(n_positions=32, batch_size=1)
    routers = {}

    def mk(idx):
        feeds, logits, caches, _ = gpt2_decode_graph(cfg, max_len=16)
        eng = DecodeEngine(feeds, logits, caches, max_slots=2,
                           max_len=16)
        routers[idx] = DecodeRouter(eng, queue_limit=8,
                                    start=(idx != 0), name=f"d{idx}")
        return routers[idx]

    door = FrontDoor(mk, 2, health_every_ms=1e9)
    try:
        streams = [door.submit([3 + i, 5], max_new_tokens=2)
                   for i in range(4)]
        assert routers[0].pending > 0       # captive on paused d0
        routers[0].kill()
        door.poll()
        for s in streams:
            assert len(s.result(timeout=120)) == 2      # max_new tokens
        assert hmetrics.fleet_counts()["fleet_rescued"] >= 1
    finally:
        door.close()


# ---------------------------------------------------------- the scenario

@pytest.mark.slow
def test_fleet_scenario():
    """Flash crowd absorbed by a recorded scale-out (each spin-up a
    serve-cache hit, one compile in all), per-class counted sheds lowest
    class first, zero interactive rejections, bounded queues, a mid-spike
    replica kill with bitwise response parity and no failed future."""
    import scenarios
    extra = scenarios.fleet_scenario()
    assert extra["scaling"]["events"], "no scale-out recorded"
    assert extra["rejections"].get("shed:best_effort", 0) > 0
    assert extra["rejections"].get("shed:interactive", 0) == 0
    assert extra["interactive_rejections"] == {"clean": 0, "chaos": 0}
    assert extra["bounded_queues"]["bounded"] is True
    assert extra["spin_up"]["cheap"] is True
    assert extra["chaos"]["kill_absorbed"] is True
    assert extra["chaos"]["failed_futures"] == 0
    assert extra["chaos"]["responses_bitwise_equal"] is True
    assert extra["ok"] is True, extra
