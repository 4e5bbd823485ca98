"""Elastic data-parallel training: resize the dp world without a restart
(ISSUE 12 tentpole).

Hetu's partial-reduce story (PAPER.md) lets a straggling or dead rank
drop out of a *single collective*; this module takes it to its
conclusion: a lost rank drops out of the *job*.  On a dead rank the
:class:`ElasticController` drives the resize dance —

1. **detect** — heartbeat liveness (``DistributedStore.liveness_report``,
   ISSUE 8) or a pluggable ``alive_fn`` mask; a rank is shrunk out only
   after it has been heartbeat-silent for one full wait window
   (``heartbeat_deadline_ms`` — the same window
   :class:`~hetu_tpu.parallel.preduce.DistPartialReduce` stops waiting
   on it);
2. **quiesce** — in-flight ``run(sync=False)`` steps drain
   (``Executor._drain_async``, ISSUE 9) so no dispatched program still
   references the old world's buffers;
3. **re-plan** — :meth:`hetu_tpu.graph.executor.Executor.resize_world`
   re-packs the ZeRO buckets for the new world (ISSUE 6's packing is
   dp-parameterized), redistributes the surviving ranks' param/moment
   slabs bitwise, and rebuilds the jitted step THROUGH the compiled-step
   cache — the dp−1 executable is a one-time compile, and any later
   revisit of a world size (the grow-back) is a ``step_cache_hit``, not
   a recompile;
4. **rescale** — gradient semantics are preserved by construction: the
   mean-loss psum over the dp−1 mesh equals the partial-reduce
   alive-mask mean ``psum(mask*g)/psum(mask)`` over the old world with
   the dead rank masked (:func:`alive_mask` + ``preduce_mean``; the
   parity test holds this BITWISE through an optimizer step);
5. **rejoin** — a standby coming back first has its PS shard state
   seeded by the ISSUE 4 re-replication machinery (OP_INIT / OP_SYNC
   snapshot / op-log catch-up via ``store.maybe_re_replicate``), then
   the controller grows the world back — hitting the original world
   size's cached executable.

Every resize is a first-class event: ``elastic_*`` counters in the
metrics registry, an ``elastic.resize`` span plus ``elastic:shrink`` /
``elastic:grow`` instant events on the Perfetto trace (ISSUE 10), and a
timeline entry (step, dp transition, recovery_ms) in
:attr:`ElasticController.events` for the bench artifact.

**Failure model (fail-stop, the ISSUE 4 convention).**  A rank is
either correct or silent: the controller shrinks over ranks that
stopped heartbeating AND fail a direct probe.  A rank that is
heartbeat-silent but still answers a probe is *partitioned*, not dead —
resizing over it would run two worlds against one PS lineage, so the
controller HOLDS (``elastic_unreachable_held``) and leaves fencing to
the epoch machinery (ISSUE 8).  Byzantine ranks (wrong answers) are out
of scope.  The resize itself is single-controller: one process owns the
mesh and the decision; multi-controller (jax.distributed) elasticity is
future work and ``resize_world`` refuses multiprocess meshes loudly.
"""
from __future__ import annotations

import threading
import time

import numpy as np

from ..analysis.protocol import PROTO as _PROTO
from ..metrics import record_elastic
from .. import obs
from .. import race as _race
from .preduce import preduce_mean  # noqa: F401  (re-export: the rescale half)


def alive_mask(world, dead=()):
    """Float32 liveness mask over ``world`` ranks with ``dead`` zeroed —
    the partial-reduce mask under which a masked mean over the full
    world equals the shrunk world's plain mean (the grad-rescale
    equivalence the elastic shrink relies on; bitwise-tested)."""
    mask = np.ones(int(world), np.float32)
    for r in dead:
        mask[int(r)] = 0.0
    return mask


class LogicalRank:
    """One in-process data-parallel worker identity — the unit the
    elastic harness kills and rejoins.

    On real clusters a "rank" is a process (killed by the launcher /
    preemption); the in-process simulation the tests run
    (``tests/scenarios.py::elastic_scenario``) makes it an object with the
    same two behaviours that matter to elasticity: it can **die**
    (``stop()`` — also the ``kill:proc@rank<r>:step<n>`` chaos target,
    via :func:`hetu_tpu.chaos.ChaosInjector.register_proc`) and it can
    **heartbeat** (``attach_heartbeat(store)`` pings the dist store's
    rank-0 heartbeat table on a daemon thread, so liveness flows
    through the REAL ISSUE 8 machinery instead of a test shim).
    ``rejoin()`` models the standby coming back."""

    def __init__(self, rank):
        self.rank = int(rank)
        self.alive = True
        self._hb_thread = None
        self._hb_stop = None

    def attach_heartbeat(self, store, interval_ms=50.0):
        """Ping ``store.heartbeat(rank)`` every ``interval_ms`` while
        alive (daemon thread, named for the trace track)."""
        self._hb_stop = threading.Event()

        def ping():
            while not self._hb_stop.is_set():
                if self.alive:
                    try:
                        store.heartbeat(self.rank)
                    except (RuntimeError, OSError, ConnectionError):
                        pass    # liveness will notice; death is the point
                self._hb_stop.wait(interval_ms / 1e3)

        self._hb_thread = threading.Thread(
            target=ping, daemon=True, name=f"elastic-hb-r{self.rank}")
        self._hb_thread.start()
        return self

    def stop(self):
        """Die (fail-stop): stop answering liveness.  Chaos's
        ``kill:proc`` step-clock kills call exactly this."""
        self.alive = False

    def rejoin(self):
        """The standby comes back: resume answering liveness."""
        self.alive = True

    def close(self):
        if self._hb_stop is not None:
            self._hb_stop.set()
            self._hb_thread.join(timeout=2.0)

    def __repr__(self):
        return (f"<LogicalRank {self.rank} "
                f"{'alive' if self.alive else 'dead'}>")


class FlapDamper:
    """Consecutive-poll grace gate — the flap-damping half of the
    elastic machinery, extracted (ISSUE 17) so the serving fleet's SLO
    autoscaler reuses it instead of reinventing it.

    A keyed condition must hold for ``grace`` CONSECUTIVE polls before
    :meth:`ready` returns True; a single False poll resets the streak.
    The elastic controller keys it by rejoining rank (a flapping rank
    must not thrash recompiles); the autoscaler keys it by resize
    direction (a noisy p99 must not thrash replica churn).  Poll-driven
    single-caller like its owners — no lock."""

    def __init__(self, grace):
        self.grace = max(1, int(grace))
        self._seen = {}

    def ready(self, key, ok):
        """Record one poll of ``key``'s condition; True once it has held
        ``grace`` consecutive polls (and keeps returning True until the
        condition breaks or :meth:`clear`)."""
        if not ok:
            self._seen.pop(key, None)
            return False
        n = self._seen.get(key, 0) + 1
        self._seen[key] = n
        return n >= self.grace

    def streak(self, key):
        """Current consecutive-ok count for ``key``."""
        return self._seen.get(key, 0)

    def clear(self, key=None):
        """Reset one key's streak (or every streak): the caller acted on
        the signal, the next decision starts from fresh evidence."""
        if key is None:
            self._seen.clear()
        else:
            self._seen.pop(key, None)


def handles_alive_fn(handles):
    """``alive_fn`` over a list of :class:`LogicalRank` handles —
    deterministic liveness for the step-clock chaos tests (a kill at
    step n is visible to the very next ``poll``, no wall-clock wait
    window)."""
    def fn():
        return np.asarray([1.0 if h.alive else 0.0 for h in handles],
                          np.float32)
    return fn


class ElasticController:
    """Drives elastic world resizes for one :class:`Executor`.

    The training loop calls :meth:`poll` once per step boundary (after
    ``executor.run``); the controller consults liveness and, when the
    world changed, drives the shrink/grow dance described in the module
    docstring.  ``executor.resize_world`` does the state
    redistribution; this class owns detection, the wait-window
    semantics, rejoin seeding, and the telemetry.

    Liveness source (exactly one):

    * ``alive_fn`` — callable returning a length-``world`` 0/1 mask
      (in-process harnesses: :func:`handles_alive_fn`);
    * ``store`` — a :class:`~hetu_tpu.ps.dist_store.DistributedStore`
      whose ``liveness_report(heartbeat_deadline_ms)`` classifies
      heartbeat-silent ranks as dead vs unreachable (ISSUE 8).  Dead
      ranks shrink; unreachable ranks HOLD (see the failure-model note).

    ``min_dp`` floors the shrink (below it the controller refuses and
    leaves recovery to the supervisor's restart budget — the two
    mechanisms compose, they don't compete).  ``rejoin_grace`` polls of
    consecutive liveness are required before a grow (a flapping rank
    must not thrash recompiles).
    """

    def __init__(self, executor, world=None, alive_fn=None, store=None,
                 heartbeat_deadline_ms=1000.0, min_dp=2, rejoin_grace=1,
                 re_replicate_on_rejoin=True):
        if (alive_fn is None) == (store is None):
            raise ValueError("ElasticController needs exactly one "
                             "liveness source: alive_fn= or store=")
        self.ex = executor
        if world is None:
            if executor.mesh is None:
                raise ValueError("no mesh: pass world= explicitly")
            world = int(np.prod(executor.mesh.devices.shape))
        self.world = int(world)
        self.alive_fn = alive_fn
        self.store = store
        self.heartbeat_deadline_ms = float(heartbeat_deadline_ms)
        self.min_dp = max(1, int(min_dp))
        self.rejoin_grace = max(1, int(rejoin_grace))
        self.re_replicate_on_rejoin = bool(re_replicate_on_rejoin)
        self.active = list(range(self.world))
        #: resize timeline for the bench artifact: dicts with step, kind,
        #: from_dp/to_dp, the ranks involved, and recovery_ms (detection
        #: poll -> resized executor ready to step)
        self.events = []
        self._rejoin = FlapDamper(self.rejoin_grace)

    @property
    def dp(self):
        return len(self.active)

    # -- liveness ----------------------------------------------------------

    def _liveness(self):
        """(mask over world, set of unreachable ranks)."""
        if self.alive_fn is not None:
            mask = np.asarray(self.alive_fn(),
                              np.float32)[:self.world]
            return mask, frozenset()
        rep = self.store.liveness_report(self.heartbeat_deadline_ms,
                                         n_workers=self.world)
        mask = np.zeros(self.world, np.float32)
        for r in rep["alive"]:
            if r < self.world:
                mask[r] = 1.0
        return mask, frozenset(rep["unreachable"])

    # -- the per-step hook -------------------------------------------------

    def poll(self, step=None):
        """Consult liveness; resize if the world changed.  Returns the
        timeline event dict of a resize that happened, else None.  Call
        at step boundaries only (mid-step the executor's state is being
        swapped)."""
        t0 = time.perf_counter()
        mask, unreachable = self._liveness()
        step = self.ex.step_counter if step is None else int(step)

        dead = [r for r in self.active if not mask[r]]
        held = [r for r in dead if r in unreachable]
        if held:
            # partitioned, not crashed: fencing's problem, not ours
            record_elastic("elastic_unreachable_held", len(held))
            obs.event("elastic:unreachable_held", cat="elastic",
                      ranks=list(held), step=step)
            if _PROTO.on:
                for r in held:
                    _PROTO.emit("elastic", "hold", rank=r, step=step)
            dead = [r for r in dead if r not in held]
        if dead:
            if _PROTO.on:
                for r in dead:
                    _PROTO.emit("elastic", "dead", rank=r, step=step)
            survivors = [r for r in self.active if r not in dead]
            if len(survivors) < self.min_dp:
                record_elastic("elastic_shrink_refused")
                obs.event("elastic:shrink_refused", cat="elastic",
                          step=step, survivors=len(survivors))
                if _PROTO.on:
                    _PROTO.emit("elastic", "refused", step=step,
                                survivors=len(survivors),
                                min_dp=self.min_dp)
            else:
                record_elastic("elastic_dead_rank", len(dead))
                return self._resize("shrink", survivors, dead, step, t0)

        backs = frozenset(r for r in range(self.world)
                          if r not in self.active and mask[r]
                          and r not in unreachable)
        ready = []
        for r in range(self.world):
            if r in self.active:
                continue
            # one damper poll per standby rank: a rank seen back for
            # rejoin_grace consecutive polls is ready; a rank that
            # flapped away restarts its grace (ok=False resets)
            if self._rejoin.ready(r, r in backs):
                ready.append(r)
        if ready:
            record_elastic("elastic_rejoin", len(ready))
            if self.store is not None and self.re_replicate_on_rejoin \
                    and getattr(self.store, "replication", 1) > 1:
                # seed the rejoiner's PS shard state through the ISSUE 4
                # re-replication machinery (OP_INIT / OP_SYNC snapshot /
                # op-log catch-up) BEFORE it carries training traffic
                try:
                    self.store.maybe_re_replicate()
                except (RuntimeError, OSError, ConnectionError):
                    pass    # deferred: the executor's tick retries
            grown = sorted(self.active + ready)
            return self._resize("grow", grown, ready, step, t0)
        return None

    # -- the resize dance --------------------------------------------------

    def _resize(self, kind, new_active, changed, step, t0):
        from_dp, to_dp = self.dp, len(new_active)
        if _race.ACTIVE is not None:   # ISSUE 14 preemption point
            _race.point("elastic.resize")
        obs.event(f"elastic:{kind}", cat="elastic", step=step,
                  ranks=list(changed), from_dp=from_dp, to_dp=to_dp)
        with obs.span("elastic.resize", cat="elastic", kind=kind,
                      step=step, from_dp=from_dp, to_dp=to_dp):
            self.ex.resize_world(new_active)
        self.active = list(new_active)
        for r in changed:
            self._rejoin.clear(r)
        ms = (time.perf_counter() - t0) * 1e3
        record_elastic(f"elastic_{kind}")
        record_elastic("elastic_resize_ms", max(1, int(round(ms))))
        ev = {"step": step, "kind": kind, "from_dp": from_dp,
              "to_dp": to_dp, "ranks": list(changed),
              "recovery_ms": round(ms, 3)}
        self.events.append(ev)
        if _PROTO.on:
            _PROTO.emit("elastic", "resize", way=kind, step=step,
                        removed=list(changed) if kind == "shrink" else [],
                        added=list(changed) if kind == "grow" else [],
                        active=list(self.active), min_dp=self.min_dp)
        return ev


__all__ = ["ElasticController", "FlapDamper", "LogicalRank", "alive_mask",
           "handles_alive_fn", "preduce_mean"]
