"""Explicit-state model checking of the repo's distributed protocols
(ISSUE 20 tentpole), plus the trace-conformance layer that ties the
models back to the real implementation.

Three executable protocol models, each a guarded-transition system over
canonical tuple states, exhaustively explored by :func:`check` (BFS with
deduped states and shortest counterexample traces):

* :class:`PSReplicationModel` — epoch-fenced PS replication/failover
  (ISSUEs 4/8): clients with (client, seq) dedup windows, per-shard
  primary/backup with apply+mirror-before-ack, promotion with the
  synced-copy gate and the ``max(cur+1, want)`` epoch bump, demotion,
  the healed-split-brain lineage probe, and environment kill /
  partition / heal / retry actions.
* :class:`DecodeRecoveryModel` — exactly-once in-flight decode stream
  migration (ISSUE 19): seat / emit / detach / adopt with the stream
  replay-epoch fence and the front door's retry budget.
* :class:`ElasticResizeModel` — elastic dp resize (ISSUE 12):
  step-boundary polls vs the async in-flight window, heartbeat
  wait-window liveness, unreachable-HOLD, and the ``min_dp`` floor.

Checked invariants are the claims the docs already make: exactly-once
apply per (client, seq) across promotion; no ack'd write lost by
failover (the single-fault claim k=2 replication actually makes); at
most one serving lineage per shard at quiescence with monotone epochs;
a demoted or unsynced copy never serves; every token index resolved
exactly once with no journal gaps; fenced zombies never mutate
post-detach; recovery terminates within its budget.

:data:`SEEDED_MUTATIONS` re-introduces three historical bug classes as
model mutations (promotion without the synced-copy gate, promotion
without the epoch bump, zombie emission without the stream-epoch
fence); the checker must produce a counterexample naming the violated
invariant for each — the verifier's synthetic-violation tests.

The model-vs-code gap is closed by the trace-conformance layer: the
:data:`PROTO` recorder collects ``protocol_event()`` records emitted at
the real transition sites (``ps/dist_store.py``, ``serving/decode.py``,
``serving/fleet.py``, ``parallel/elastic.py`` — flag-guarded, ISSUE 10
tracer discipline: one attribute load when off), and
:func:`check_conformance` replays a recorded run against the models'
transition relations.  The failover / partition / decode-recovery fault
scenarios (``tests/scenarios.py``) gate on it, so every such run is also
a machine-checked trace of the verified model.

Stdlib-only BY DESIGN (the `analysis.concurrency` convention):
``tools/hetu_lint.py`` and ``tools/verify_protocols.py`` load this
module by file path, so it must import without jax; the lazy
``..metrics`` import degrades to a no-op outside the package.
"""
from __future__ import annotations

import os
import threading
from collections import deque

# ---------------------------------------------------------------- recorder

_record_protocol = None


def _record(kind, n=1):
    """Lazy bridge to ``metrics.record_protocol`` — resolved on first
    use so this module stays importable by file path (lint, CLI)
    without pulling the package (and jax) in."""
    global _record_protocol
    if _record_protocol is None:
        try:
            from ..metrics import record_protocol
        except ImportError:
            record_protocol = None
        _record_protocol = record_protocol or (lambda kind, n=1: None)
    _record_protocol(kind, n)


def _env_on():
    return os.environ.get("HETU_PROTO_TRACE", "0").lower() not in (
        "", "0", "false", "off")


#: hard cap on buffered events — a runaway chaos loop must not OOM the
#: process through its own verifier
_REC_CAP = 200_000


class _ProtoRecorder:
    """Process-wide protocol-event recorder (module singleton
    :data:`PROTO`).  ``on`` is the ONE hot flag — instrumentation sites
    read it directly (``if _PROTO.on: _PROTO.emit(...)``), so a
    disabled recorder costs one attribute load per site (the ISSUE 10
    tracer discipline; default off, env ``HETU_PROTO_TRACE=1`` or
    :meth:`start` enables)."""

    __slots__ = ("on", "_lock", "_events", "dropped")

    def __init__(self):
        self.on = _env_on()
        self._lock = threading.Lock()
        self._events = []
        self.dropped = 0

    def start(self):
        """Begin a fresh recording (clears the buffer, flips ``on``)."""
        with self._lock:
            self._events = []
            self.dropped = 0
        self.on = True

    def stop(self):
        """Flip ``on`` off and return the recorded events (drained)."""
        self.on = False
        return self.drain()

    def drain(self):
        """Return and clear the buffered events (arrival order)."""
        with self._lock:
            ev, self._events = self._events, []
        return ev

    def emit(self, plane, kind, **fields):
        """Record one protocol transition event.  Callers gate on
        ``.on`` themselves (the whole point of the flag)."""
        ev = fields
        ev["plane"] = plane
        ev["kind"] = kind
        with self._lock:
            if len(self._events) >= _REC_CAP:
                self.dropped += 1
                _record("protocol_events_dropped")
                return
            ev["i"] = len(self._events)
            self._events.append(ev)
        _record("protocol_events")


PROTO = _ProtoRecorder()


def protocol_event(plane, kind, **fields):
    """Convenience wrapper for cold call sites (hot sites inline the
    ``PROTO.on`` guard instead)."""
    if PROTO.on:
        PROTO.emit(plane, kind, **fields)


# ------------------------------------------------------------------ engine

class Violation:
    """One invariant violation with its shortest counterexample trace
    (BFS guarantees minimality in transition count)."""

    __slots__ = ("invariant", "message", "trace", "state", "depth")

    def __init__(self, invariant, message, trace, state, depth):
        self.invariant = invariant
        self.message = message
        self.trace = trace          # list of rendered transition labels
        self.state = state          # rendered violating state
        self.depth = depth

    def render(self):
        lines = [f"invariant violated: {self.invariant}",
                 f"  {self.message}",
                 f"  counterexample ({len(self.trace)} steps):"]
        for i, lab in enumerate(self.trace):
            lines.append(f"    {i + 1:2d}. {lab}")
        lines.append(f"  state: {self.state}")
        return "\n".join(lines)

    def to_dict(self):
        return {"invariant": self.invariant, "message": self.message,
                "trace": list(self.trace), "state": self.state,
                "depth": self.depth}


class CheckResult:
    """Outcome of one :func:`check` run: state/transition counts, the
    exploration completeness flag, and (at most one) violation."""

    __slots__ = ("model", "states", "transitions", "depth", "complete",
                 "violations")

    def __init__(self, model, states, transitions, depth, complete,
                 violations):
        self.model = model
        self.states = states
        self.transitions = transitions
        self.depth = depth
        self.complete = complete
        self.violations = violations

    @property
    def ok(self):
        return not self.violations

    def to_dict(self):
        return {"model": self.model, "states": self.states,
                "transitions": self.transitions, "depth": self.depth,
                "complete": self.complete, "ok": self.ok,
                "violations": [v.to_dict() for v in self.violations]}


def check(model, max_states=500_000, max_depth=None):
    """Exhaustive BFS over ``model``'s reachable state space.

    The model contract (duck-typed, like the lint rule registry):
    ``init()`` -> canonical hashable state; ``actions(state)`` ->
    iterable of ``(label, next_state)``; ``invariants`` /
    ``edge_invariants`` / ``quiescent_invariants`` /
    ``terminal_invariants`` -> iterables of ``(name, fn)`` where ``fn``
    returns an error string (violated) or None; ``quiescent(state)`` ->
    bool.  Stops at the FIRST violation (BFS order ⇒ the returned trace
    is a shortest counterexample); ``complete`` is False when the
    ``max_states`` / ``max_depth`` budget truncated exploration."""
    init = model.init()
    seen = {init: (None, None, 0)}      # state -> (parent, label, depth)
    q = deque([init])
    states = transitions = maxd = 0
    complete = True

    def trace_to(state, extra=None):
        labels = []
        while True:
            parent, label, _ = seen[state]
            if parent is None:
                break
            labels.append(model.render_label(label))
            state = parent
        labels.reverse()
        if extra is not None:
            labels.append(model.render_label(extra))
        return labels

    def done(states, complete, violations):
        _record("protocol_states_explored", states)
        if violations:
            _record("protocol_violations", len(violations))
        return CheckResult(model.name, states, transitions, maxd,
                           complete, violations)

    while q:
        s = q.popleft()
        d = seen[s][2]
        maxd = max(maxd, d)
        states += 1
        for name, fn in model.invariants:
            err = fn(s)
            if err:
                return done(states, complete, [Violation(
                    name, err, trace_to(s), model.render_state(s), d)])
        acts = list(model.actions(s))
        if model.quiescent(s):
            for name, fn in model.quiescent_invariants:
                err = fn(s)
                if err:
                    return done(states, complete, [Violation(
                        name, err, trace_to(s), model.render_state(s),
                        d)])
        if not acts:
            for name, fn in model.terminal_invariants:
                err = fn(s)
                if err:
                    return done(states, complete, [Violation(
                        name, err, trace_to(s), model.render_state(s),
                        d)])
            continue
        for label, s2 in acts:
            transitions += 1
            for name, fn in model.edge_invariants:
                err = fn(s, label, s2)
                if err:
                    return done(states, complete, [Violation(
                        name, err, trace_to(s, extra=label),
                        model.render_state(s2), d + 1)])
            if s2 not in seen:
                if len(seen) >= max_states or \
                        (max_depth is not None and d + 1 > max_depth):
                    complete = False
                    continue
                seen[s2] = (s, label, d + 1)
                q.append(s2)
    return done(states, complete, [])


class _ModelBase:
    """Shared defaults for the model contract."""

    name = "model"
    invariants = ()
    edge_invariants = ()
    quiescent_invariants = ()
    terminal_invariants = ()

    def quiescent(self, state):
        return False

    def render_state(self, state):
        return repr(state)

    def render_label(self, label):
        if isinstance(label, tuple):
            return label[0] + "(" + ", ".join(str(x) for x in label[1:]) \
                + ")"
        return str(label)


# -------------------------------------------------- model: PS replication

# client-op statuses (one non-idempotent write per client, retried with
# a PINNED (client, seq) — the dedup window's whole point)
_IDLE, _WAIT, _RESEND, _CONN, _WPROM, _ACKED, _FAILED = (
    "idle", "wait", "resend", "conn", "wait_promote", "acked", "failed")


class PSReplicationModel(_ModelBase):
    """Epoch-fenced PS replication/failover as a guarded-transition
    system.

    Topology mirrors ``dist_store``'s k=2 ring: shard ``s`` is
    home-served by rank ``s`` with its backup on rank ``s+1`` (mod
    world); ``unsynced`` shards start with their backup MID-SYNC
    (copy exists, ``promotable`` False until the ``sync_done``
    transition — the OP_SYNC / OP_SYNC_PUT plane collapsed to its
    promotability effect).  One write op per client, client ``i`` ->
    shard ``shards[i]``; retries resend the SAME (client, seq).

    The apply+mirror-before-ack critical section (``_repl_lock``) is
    one atomic ``deliver_push`` transition: fence -> dedup -> local
    apply -> synchronous OP_REPLICATE forward (with the peer's
    ``_fence_or_adopt`` gate, ``refuse_equal_if_serving``) -> ack.
    Environment actions: one fault (kill OR partition episode — the
    single-fault claim k=2 replication makes), heal, the rate-limited
    lineage probe (``_probe_lineage`` — how a healed stale ex-primary
    learns it was deposed), and ``sync_done``.

    ``mutation`` re-introduces historical bugs: ``promote_unsynced``
    (PR 4 review: promotion skips the synced-copy gate) and
    ``promote_no_epoch_bump`` (PR 8 split-brain: promotion reuses the
    current epoch, so the deposed primary's frames stay unfenceable).
    """

    name = "ps_replication"

    def __init__(self, n_ranks=3, shards=(0, 1), unsynced=(1,),
                 max_sends=3, max_promotes=2, fault_budget=1,
                 mutation=None):
        assert mutation in (None, "promote_unsynced",
                            "promote_no_epoch_bump"), mutation
        self.world = int(n_ranks)
        self.shards = tuple(shards)
        self.unsynced = frozenset(unsynced)
        self.max_sends = int(max_sends)
        self.max_promotes = int(max_promotes)
        self.fault_budget = int(fault_budget)
        self.mutation = mutation
        self.n_ops = len(self.shards)        # op i = client i -> shards[i]
        self.slots = []                      # (rank, shard) copy slots
        for s in self.shards:
            self.slots.append((s % self.world, s))
            self.slots.append(((s + 1) % self.world, s))
        self.slot_ix = {rs: i for i, rs in enumerate(self.slots)}
        self.invariants = (
            ("exactly-once-apply", self._inv_exactly_once),
            ("demoted-or-unsynced-never-serves", self._inv_gate),
        )
        self.edge_invariants = (
            ("epoch-monotonicity", self._inv_epoch_monotone),
        )
        self.quiescent_invariants = (
            ("single-serving-lineage", self._inv_single_lineage),
            ("no-acked-write-lost", self._inv_no_lost_write),
        )
        self.terminal_invariants = (
            ("ops-terminate", self._inv_ops_terminate),
        )

    # copy tuple layout: (epoch, serving, promotable, fwd_ok, syncing,
    #                     applied: per-op counts, seen: per-op bools)

    def holders(self, s):
        return (s % self.world, (s + 1) % self.world)

    def other_holder(self, s, r):
        a, b = self.holders(s)
        return b if r == a else a

    def init(self):
        zeros = (0,) * self.n_ops
        falses = (False,) * self.n_ops
        copies = []
        for r, s in self.slots:
            if r == s % self.world:          # home primary: serving
                copies.append((1, True, True, True, False, zeros, falses))
            elif s in self.unsynced:         # backup mid-sync
                copies.append((1, False, False, False, True, zeros,
                               falses))
            else:                            # synced standby backup
                copies.append((1, False, True, True, False, zeros,
                               falses))
        ops = tuple((_IDLE, 0, 0, s % self.world, 1, 0)
                    for s in self.shards)
        # op tuple: (status, sends, promotes, route, epoch, flip_epoch)
        return (ops, tuple(copies), (True,) * self.world,
                (False,) * self.world, (), self.fault_budget)

    # -- tuple surgery helpers --------------------------------------------

    @staticmethod
    def _upd(tup, i, val):
        return tup[:i] + (val,) + tup[i + 1:]

    def _demoted(self, copy, epoch):
        """The ``_demote`` effect: adopt the newer epoch, stop serving,
        drop promotability, stop forwarding."""
        return (max(copy[0], epoch), False, False, False, copy[4],
                copy[5], copy[6])

    # -- transition relation ----------------------------------------------

    def actions(self, state):
        ops, copies, alive, parts, msgs, fault = state
        out = []

        def emit(label, nops=None, ncopies=None, nalive=None,
                 nparts=None, nmsgs=None, nfault=None):
            out.append((label, (
                ops if nops is None else nops,
                copies if ncopies is None else ncopies,
                alive if nalive is None else nalive,
                parts if nparts is None else nparts,
                msgs if nmsgs is None else tuple(sorted(nmsgs)),
                fault if nfault is None else nfault)))

        def unreachable(r):
            return not alive[r] or parts[r]

        # client actions --------------------------------------------------
        for i, op in enumerate(ops):
            st, sends, proms, route, epoch, flip = op
            s = self.shards[i]
            if st == _IDLE or st == _RESEND:
                if sends < self.max_sends:
                    nop = (_WAIT, sends + 1, proms, route, epoch, flip)
                    emit(("send", f"c{i}", f"r{route}"),
                         nops=self._upd(ops, i, nop),
                         nmsgs=msgs + (("PUSH", i, route, epoch),))
                elif st == _RESEND:
                    emit(("give_up", f"c{i}"), nops=self._upd(
                        ops, i, (_FAILED,) + op[1:]))
            elif st == _CONN:
                # conn-failed route: client-side failover — promote the
                # shard's other holder with want = our epoch + 1
                if proms < self.max_promotes:
                    alt = self.other_holder(s, route)
                    nop = (_WPROM, sends, proms + 1, route, epoch, flip)
                    emit(("failover", f"c{i}", f"r{alt}"),
                         nops=self._upd(ops, i, nop),
                         nmsgs=msgs + (("PROMOTE", i, alt, epoch + 1),))
                else:
                    emit(("give_up", f"c{i}"), nops=self._upd(
                        ops, i, (_FAILED,) + op[1:]))

        # message deliveries ----------------------------------------------
        for m in msgs:
            rest = tuple(x for x in msgs if x != m)
            i = m[1]
            op = ops[i]
            st, sends, proms, route, epoch, flip = op
            s = self.shards[i]
            if m[0] == "PUSH":
                _, _, dst, e = m
                label = ("deliver_push", f"c{i}", f"r{dst}")
                if unreachable(dst):
                    emit(label, nops=self._upd(
                        ops, i, (_CONN, sends, proms, route, epoch,
                                 flip)), nmsgs=rest)
                    continue
                ci = self.slot_ix.get((dst, s))
                copy = copies[ci] if ci is not None else None
                if copy is None or not copy[1]:
                    emit(label, nmsgs=rest + (("NSERV", i, dst),))
                    continue
                cur = copy[0]
                if e < cur:          # stale client: teach it our epoch
                    emit(label,
                         nmsgs=rest + (("FENCE", i, dst, cur, True),))
                    continue
                if e > cur:          # we missed a promotion: demote
                    emit(label, ncopies=self._upd(
                        copies, ci, self._demoted(copy, e)),
                        nmsgs=rest + (("FENCE", i, dst, e, False),))
                    continue
                if copy[6][i]:       # (client, seq) dedup window hit
                    emit(("dedup_ack", f"c{i}", f"r{dst}"),
                         nmsgs=rest + (("ACK", i, dst, cur),))
                    continue
                ncopies = list(copies)
                peer = self.other_holder(s, dst)
                pi = self.slot_ix.get((peer, s))
                pc = copies[pi] if pi is not None else None
                if not copy[3] and pc is not None and \
                        not unreachable(peer) and pc[0] > cur:
                    # degraded-serving deposed-check (_probe_lineage
                    # before the apply): refuse instead of acking onto
                    # the losing lineage
                    emit(("probe_fenced", f"c{i}", f"r{dst}"),
                         ncopies=self._upd(
                             copies, ci, self._demoted(copy, pc[0])),
                         nmsgs=rest + (("FENCE", i, dst, pc[0],
                                        False),))
                    continue
                applied = self._upd(copy[5], i, copy[5][i] + 1)
                seen = self._upd(copy[6], i, True)
                ncopy = (cur, True, copy[2], copy[3], copy[4], applied,
                         seen)
                fenced = False
                if pc is not None and not pc[4] and copy[3]:
                    # synchronous mirror (apply+mirror-before-ack): the
                    # peer's _fence_or_adopt gate runs refuse_equal_if_
                    # serving — an equal-epoch second primary is refused
                    if unreachable(peer):
                        ncopy = ncopy[:3] + (False,) + ncopy[4:]
                    elif pc[0] > cur or (pc[0] == cur and pc[1]):
                        ncopies[ci] = self._demoted(ncopy, pc[0])
                        emit(("fwd_fenced", f"c{i}", f"r{dst}"),
                             ncopies=tuple(ncopies),
                             nmsgs=rest + (("FENCE", i, dst, pc[0],
                                            False),))
                        fenced = True
                    else:
                        papp = pc[5] if pc[6][i] else \
                            self._upd(pc[5], i, pc[5][i] + 1)
                        ncopies[pi] = (max(pc[0], cur), pc[1], pc[2],
                                       pc[3], pc[4], papp,
                                       self._upd(pc[6], i, True))
                if not fenced:
                    ncopies[ci] = ncopy
                    emit(("apply_ack", f"c{i}", f"r{dst}"),
                         ncopies=tuple(ncopies),
                         nmsgs=rest + (("ACK", i, dst, cur),))
            elif m[0] == "ACK":
                _, _, src, e = m
                if unreachable(src):     # ack lost with the connection
                    nop = (_CONN, sends, proms, route, epoch, flip)
                else:
                    nop = (_ACKED, sends, proms, route, max(epoch, e),
                           flip)
                emit(("deliver_ack", f"c{i}"),
                     nops=self._upd(ops, i, nop), nmsgs=rest)
            elif m[0] == "FENCE":
                _, _, src, cur, serving = m
                if unreachable(src):
                    nop = (_CONN, sends, proms, route, epoch, flip)
                else:
                    # _note_fence: locked max-merge + at-most-one route
                    # flip per epoch, only on a refusal at least as new
                    # as what we know and only when the refuser no
                    # longer serves
                    ne = max(epoch, cur)
                    nroute, nflip = route, flip
                    if not serving and cur == ne and flip != cur:
                        nroute = self.other_holder(s, route)
                        nflip = cur
                    nop = (_RESEND, sends, proms, nroute, ne, nflip)
                emit(("deliver_fence", f"c{i}"),
                     nops=self._upd(ops, i, nop), nmsgs=rest)
            elif m[0] == "NSERV":
                # stale route hit a non-serving holder: failover-worthy
                emit(("deliver_nserv", f"c{i}"), nops=self._upd(
                    ops, i, (_CONN, sends, proms, route, epoch, flip)),
                    nmsgs=rest)
            elif m[0] == "PROMOTE":
                _, _, dst, want = m
                label = ("deliver_promote", f"c{i}", f"r{dst}")
                if unreachable(dst):
                    emit(label, nops=self._upd(
                        ops, i, (_FAILED,) + op[1:]), nmsgs=rest)
                    continue
                ci = self.slot_ix.get((dst, s))
                copy = copies[ci] if ci is not None else None
                if copy is None:
                    emit(label, nmsgs=rest + (("PFAIL", i, dst),))
                elif copy[1]:        # idempotent re-promote: adopt want
                    ep = max(copy[0], want)
                    emit(label, ncopies=self._upd(
                        copies, ci, (ep,) + copy[1:]),
                        nmsgs=rest + (("PROMOTED", i, dst, ep),))
                elif not copy[2] and self.mutation != "promote_unsynced":
                    # the synced-copy gate: a never-synced (or demoted)
                    # copy would resurrect stale state — refuse loudly
                    emit(label, nmsgs=rest + (("PFAIL", i, dst),))
                else:
                    if self.mutation == "promote_no_epoch_bump":
                        ep = copy[0]
                    else:
                        ep = max(copy[0] + 1, want)
                    ncopy = (ep, True, copy[2], False, False, copy[5],
                             copy[6])
                    emit(label, ncopies=self._upd(copies, ci, ncopy),
                         nmsgs=rest + (("PROMOTED", i, dst, ep),))
            elif m[0] == "PROMOTED":
                _, _, src, ep = m
                if unreachable(src):
                    nop = (_FAILED, sends, proms, route, epoch, flip)
                else:
                    # the promotion IS this epoch's route change
                    nop = (_RESEND, sends, proms, src, max(epoch, ep),
                           max(epoch, ep))
                emit(("deliver_promoted", f"c{i}"),
                     nops=self._upd(ops, i, nop), nmsgs=rest)
            elif m[0] == "PFAIL":
                emit(("deliver_pfail", f"c{i}"), nops=self._upd(
                    ops, i, (_FAILED,) + op[1:]), nmsgs=rest)

        # environment -----------------------------------------------------
        if fault > 0:
            for r in range(self.world):
                if alive[r]:
                    emit(("kill", f"r{r}"),
                         nalive=self._upd(alive, r, False),
                         nfault=fault - 1)
                    if not parts[r]:
                        emit(("partition", f"r{r}"),
                             nparts=self._upd(parts, r, True),
                             nfault=fault - 1)
        for r in range(self.world):
            if parts[r]:
                emit(("heal", f"r{r}"), nparts=self._upd(parts, r,
                                                         False))
        for label, ncopies in self._converge_actions(state):
            emit(label, ncopies=ncopies)
        return out

    def _converge_actions(self, state):
        """sync_done + lineage-probe transitions — separated so
        :meth:`quiescent` can ask "is any convergence step still
        enabled?" without re-deriving the guards."""
        ops, copies, alive, parts, msgs, fault = state
        out = []

        def reachable(r):
            return alive[r] and not parts[r]

        for ci, (r, s) in enumerate(self.slots):
            copy = copies[ci]
            if copy is None:
                continue
            if copy[4] and reachable(r):
                # sync completion: snapshot + op-log catch-up land, the
                # copy becomes promotable and live forwarding resumes
                src_ix = self.slot_ix[(self.other_holder(s, r), s)]
                src = copies[src_ix]
                if src is not None and src[1] and \
                        reachable(self.slots[src_ix][0]):
                    ncopies = self._upd(copies, ci, (
                        src[0], False, True, True, False, src[5],
                        src[6]))
                    ncopies = self._upd(ncopies, src_ix,
                                        src[:3] + (True,) + src[4:])
                    out.append((("sync_done", f"r{r}", f"s{s}"),
                                ncopies))
            if copy[1] and reachable(r):
                # lineage probe: any reachable peer copy with a newer
                # epoch means we were deposed — demote (OP_EPOCH probe
                # / refused forward / fenced traffic all teach this)
                peer = self.other_holder(s, r)
                pi = self.slot_ix.get((peer, s))
                pc = copies[pi] if pi is not None else None
                if pc is not None and reachable(peer) and \
                        pc[0] > copy[0]:
                    out.append((("probe_demote", f"r{r}", f"s{s}"),
                                self._upd(copies, ci, self._demoted(
                                    copy, pc[0]))))
        return out

    # -- invariants --------------------------------------------------------

    def _inv_exactly_once(self, state):
        ops, copies, alive, parts, msgs, fault = state
        for ci, copy in enumerate(copies):
            if copy is None:
                continue
            for i, n in enumerate(copy[5]):
                if n > 1:
                    r, s = self.slots[ci]
                    return (f"op c{i} applied {n}x on rank {r}'s copy "
                            f"of shard {s} (dedup window breached)")
        return None

    def _inv_gate(self, state):
        ops, copies, alive, parts, msgs, fault = state
        for ci, copy in enumerate(copies):
            if copy is not None and copy[1] and not copy[2]:
                r, s = self.slots[ci]
                return (f"rank {r} SERVES shard {s} from a copy that "
                        f"is not promotable (unsynced or demoted)")
        return None

    def _inv_epoch_monotone(self, s0, label, s1):
        for ci in range(len(self.slots)):
            c0, c1 = s0[1][ci], s1[1][ci]
            if c0 is not None and c1 is not None and c1[0] < c0[0]:
                r, sh = self.slots[ci]
                return (f"rank {r} shard {sh} epoch went backwards "
                        f"{c0[0]} -> {c1[0]}")
        for i in range(self.n_ops):
            if s1[0][i][4] < s0[0][i][4]:
                return (f"client c{i} epoch went backwards "
                        f"{s0[0][i][4]} -> {s1[0][i][4]}")
        return None

    def _inv_single_lineage(self, state):
        ops, copies, alive, parts, msgs, fault = state
        for s in self.shards:
            serving = [r for (r, sh), ci in self.slot_ix.items()
                       if sh == s and alive[r]
                       and copies[ci] is not None and copies[ci][1]]
            if len(serving) > 1:
                return (f"shard {s} has {len(serving)} live serving "
                        f"copies (ranks {sorted(serving)}) at "
                        f"quiescence — split brain")
        return None

    def _inv_no_lost_write(self, state):
        ops, copies, alive, parts, msgs, fault = state
        for i, op in enumerate(ops):
            if op[0] != _ACKED:
                continue
            s = self.shards[i]
            for (r, sh), ci in self.slot_ix.items():
                copy = copies[ci]
                if sh == s and alive[r] and copy is not None \
                        and copy[1] and copy[5][i] < 1:
                    return (f"acked op c{i} missing from the serving "
                            f"copy of shard {s} on rank {r} — failover "
                            f"lost an acknowledged write")
        return None

    def _inv_ops_terminate(self, state):
        for i, op in enumerate(state[0]):
            if op[0] not in (_ACKED, _FAILED):
                return (f"stuck state: op c{i} is '{op[0]}' with no "
                        f"enabled transition")
        return None

    def quiescent(self, state):
        ops, copies, alive, parts, msgs, fault = state
        return (not msgs and not any(parts)
                and all(op[0] in (_ACKED, _FAILED) for op in ops)
                and not self._converge_actions(state))

    def render_state(self, state):
        ops, copies, alive, parts, msgs, fault = state
        bits = []
        for i, op in enumerate(ops):
            bits.append(f"c{i}:{op[0]}@e{op[4]}->r{op[3]}")
        for ci, (r, s) in enumerate(self.slots):
            c = copies[ci]
            if c is None:
                continue
            flags = ("S" if c[1] else "-") + ("P" if c[2] else "-") + \
                ("F" if c[3] else "-") + ("y" if c[4] else "-")
            bits.append(f"r{r}s{s}:e{c[0]}{flags}{list(c[5])}")
        bits.append("alive=" + "".join("1" if a else "0" for a in alive))
        if any(parts):
            bits.append("cut=" + "".join(
                "1" if p else "0" for p in parts))
        if msgs:
            bits.append(f"msgs={list(msgs)}")
        return " ".join(bits)


# ------------------------------------------------ model: decode recovery

class DecodeRecoveryModel(_ModelBase):
    """Exactly-once in-flight decode stream migration (ISSUE 19) as a
    guarded-transition system.

    Streams carry a replay epoch, a journal prefix (per-index delivered
    counts), and a retry count; replicas are ok / dead / wedged.  The
    sweep detaches a stream seated on a non-ok replica (atomic epoch
    bump + journal snapshot — ``DecodeStream._detach``), the front door
    re-seats it on a survivor (``adopt`` + chunked-prefill
    continuation) or fails it fast once ``retries`` exceeds the budget
    or no survivor remains.  A WEDGED replica's engine keeps running:
    after detach its emissions arrive with the stale epoch and must be
    dropped by the stream fence (``zombie_emit`` — a no-op at HEAD).

    ``mutation='zombie_emit_unfenced'`` re-introduces the PR 19 bug
    class: the stale emission lands in the journal anyway.
    """

    name = "decode_recovery"

    def __init__(self, n_streams=2, n_replicas=2, max_tokens=2,
                 retry_budget=1, fault_budget=2, mutation=None):
        assert mutation in (None, "zombie_emit_unfenced"), mutation
        self.n_streams = int(n_streams)
        self.n_replicas = int(n_replicas)
        self.max_tokens = int(max_tokens)
        self.retry_budget = int(retry_budget)
        self.fault_budget = int(fault_budget)
        self.mutation = mutation
        self.invariants = (
            ("exactly-once-token", self._inv_exactly_once),
            ("no-journal-gaps", self._inv_gaps),
            ("retry-budget", self._inv_budget),
        )
        self.edge_invariants = (
            ("fenced-zombie-never-mutates", self._inv_zombie),
            ("stream-epoch-monotone", self._inv_epoch),
        )
        self.terminal_invariants = (
            ("recovery-terminates", self._inv_terminates),
        )

    # stream tuple: (phase, seat, epoch, nxt, counts, retries)
    # zombie tuple: (sid, replica, stale_epoch, frozen_next)

    def init(self):
        streams = tuple(("q", -1, 0, 0, (0,) * self.max_tokens, 0)
                        for _ in range(self.n_streams))
        return (streams, (), ("ok",) * self.n_replicas,
                self.fault_budget)

    @staticmethod
    def _upd(tup, i, val):
        return tup[:i] + (val,) + tup[i + 1:]

    def actions(self, state):
        streams, zombies, reps, fault = state
        out = []
        any_ok = any(st == "ok" for st in reps)
        for sid, stream in enumerate(streams):
            phase, seat, epoch, nxt, counts, retries = stream
            if phase == "q":
                for r, st in enumerate(reps):
                    if st == "ok":
                        out.append((("seat", f"s{sid}", f"r{r}"), (
                            self._upd(streams, sid,
                                      ("s", r, epoch, nxt, counts,
                                       retries)),
                            zombies, reps, fault)))
                if not any_ok:
                    # recovery gate: zero survivors — fail FAST with the
                    # partial journal instead of queueing forever
                    out.append((("fail_no_survivor", f"s{sid}"), (
                        self._upd(streams, sid,
                                  ("failed", -1, epoch, nxt, counts,
                                   retries)),
                        zombies, reps, fault)))
            elif phase == "s":
                if reps[seat] == "ok":
                    nc = self._upd(counts, nxt, counts[nxt] + 1)
                    nphase = "done" if nxt + 1 >= self.max_tokens \
                        else "s"
                    nseat = -1 if nphase == "done" else seat
                    out.append((("emit", f"s{sid}", f"t{nxt}"), (
                        self._upd(streams, sid,
                                  (nphase, nseat, epoch, nxt + 1, nc,
                                   retries)),
                        zombies, reps, fault)))
                else:
                    # sweep detach: atomic epoch bump + journal
                    # snapshot; a wedged replica's engine lives on as a
                    # fenced zombie
                    nz = zombies + ((sid, seat, epoch, nxt),) \
                        if reps[seat] == "wedged" else zombies
                    if retries >= self.retry_budget:
                        ns = ("failed", -1, epoch, nxt, counts, retries)
                        out.append((("detach_exhausted", f"s{sid}"), (
                            self._upd(streams, sid, ns),
                            tuple(sorted(nz)), reps, fault)))
                    else:
                        ns = ("q", -1, epoch + 1, nxt, counts,
                              retries + 1)
                        out.append((("detach", f"s{sid}"), (
                            self._upd(streams, sid, ns),
                            tuple(sorted(nz)), reps, fault)))
        for zi, (sid, r, ze, zn) in enumerate(zombies):
            if reps[r] == "wedged":
                rest = zombies[:zi] + zombies[zi + 1:]
                if self.mutation == "zombie_emit_unfenced" and \
                        zn < self.max_tokens:
                    st = streams[sid]
                    nc = self._upd(st[4], zn, st[4][zn] + 1)
                    nstreams = self._upd(
                        streams, sid, st[:4] + (nc, st[5]))
                else:
                    nstreams = streams   # fenced: journal untouched
                out.append((("zombie_emit", f"s{sid}", f"r{r}",
                             f"t{zn}"),
                            (nstreams, rest, reps, fault)))
        if fault > 0:
            for r, st in enumerate(reps):
                if st == "ok":
                    out.append((("kill", f"r{r}"), (
                        streams, zombies,
                        self._upd(reps, r, "dead"), fault - 1)))
                    out.append((("wedge", f"r{r}"), (
                        streams, zombies,
                        self._upd(reps, r, "wedged"), fault - 1)))
        return out

    def _inv_exactly_once(self, state):
        for sid, st in enumerate(state[0]):
            for idx, n in enumerate(st[4]):
                if n > 1:
                    return (f"stream s{sid} token index {idx} "
                            f"delivered {n}x")
        return None

    def _inv_gaps(self, state):
        for sid, st in enumerate(state[0]):
            nxt, counts = st[3], st[4]
            for idx, n in enumerate(counts):
                want = 1 if idx < nxt else 0
                if n != want:
                    return (f"stream s{sid} journal gap at index "
                            f"{idx}: delivered {n}, next={nxt}")
        return None

    def _inv_budget(self, state):
        for sid, st in enumerate(state[0]):
            if st[5] > self.retry_budget:
                return (f"stream s{sid} recovered {st[5]}x — past the "
                        f"retry budget {self.retry_budget}")
        return None

    def _inv_zombie(self, s0, label, s1):
        if label[0] == "zombie_emit" and s1[0] != s0[0]:
            return (f"stale-epoch emission {label} mutated a stream's "
                    f"journal — the replay-epoch fence did not hold")
        return None

    def _inv_epoch(self, s0, label, s1):
        for sid in range(self.n_streams):
            if s1[0][sid][2] < s0[0][sid][2]:
                return f"stream s{sid} replay epoch went backwards"
        return None

    def _inv_terminates(self, state):
        for sid, st in enumerate(state[0]):
            if st[0] not in ("done", "failed"):
                return (f"stuck state: stream s{sid} is '{st[0]}' with "
                        f"no enabled transition")
        return None

    def render_state(self, state):
        streams, zombies, reps, fault = state
        bits = [f"s{sid}:{st[0]}@e{st[2]}n{st[3]}{list(st[4])}"
                f"x{st[5]}" for sid, st in enumerate(streams)]
        bits.append("reps=" + ",".join(reps))
        if zombies:
            bits.append(f"zombies={list(zombies)}")
        return " ".join(bits)


# ------------------------------------------------- model: elastic resize

class ElasticResizeModel(_ModelBase):
    """Elastic dp resize (ISSUE 12) as a guarded-transition system.

    Ranks are (alive, reachable, hb_missed, held); ``poll`` runs only
    at a step boundary (async in-flight window drained to zero) and
    applies the controller's decision function: shrink ranks that are
    dead AND heartbeat-silent for the full wait window (unless the
    survivors would drop below ``min_dp`` — refused), HOLD ranks that
    are alive-but-unreachable (partition is fencing's problem, not a
    shrink), re-admit healed/rejoining ranks.  Environment: one kill,
    one partition episode, heartbeat misses, async launches/drains.
    """

    name = "elastic_resize"

    def __init__(self, n_ranks=3, min_dp=2, hb_threshold=2, window=2,
                 kill_budget=1, cut_budget=1):
        self.world = int(n_ranks)
        self.min_dp = int(min_dp)
        self.th = int(hb_threshold)
        self.window = int(window)
        self.kill_budget = int(kill_budget)
        self.cut_budget = int(cut_budget)
        self.invariants = (
            ("min-dp-floor", self._inv_floor),
        )
        self.edge_invariants = (
            ("resize-at-step-boundary", self._inv_boundary),
            ("held-unreachable-never-shrunk", self._inv_held),
        )
        self.quiescent_invariants = (
            ("heartbeat-wait-window-liveness", self._inv_liveness),
        )

    # rank tuple: (alive, reachable, missed, held)

    def init(self):
        ranks = tuple((True, True, 0, False)
                      for _ in range(self.world))
        return (ranks, tuple(range(self.world)), 0, self.kill_budget,
                self.cut_budget)

    @staticmethod
    def _upd(tup, i, val):
        return tup[:i] + (val,) + tup[i + 1:]

    def _poll_result(self, state):
        """The controller's deterministic decision at a boundary; None
        when poll would be a no-op."""
        ranks, active, inflight, kb, cb = state
        nranks = list(ranks)
        act = set(active)
        for r, (alv, reach, missed, held) in enumerate(ranks):
            if not alv and held:
                # the hold set tracks alive-but-unreachable ranks; a
                # held rank that dies graduates to the shrink path
                nranks[r] = (alv, reach, missed, False)
                held = False
            if r in act and missed >= self.th:
                if not alv:
                    if len(act) - 1 >= self.min_dp:
                        act.discard(r)           # shrink the dead rank
                elif not reach and not held:
                    nranks[r] = (alv, reach, missed, True)   # HOLD
            if alv and reach and r not in act:
                act.add(r)                       # rejoin / grow back
                nranks[r] = (alv, reach, 0, False)
            if alv and reach and held:
                nranks[r] = (alv, reach, 0, False)
        nstate = (tuple(nranks), tuple(sorted(act)), inflight, kb, cb)
        return None if nstate == state else nstate

    def actions(self, state):
        ranks, active, inflight, kb, cb = state
        out = []
        if inflight < self.window:
            out.append((("launch_async",),
                        (ranks, active, inflight + 1, kb, cb)))
        if inflight > 0:
            out.append((("drain_async",),
                        (ranks, active, inflight - 1, kb, cb)))
        for r, (alv, reach, missed, held) in enumerate(ranks):
            if alv and kb > 0:
                out.append((("kill", f"r{r}"), (
                    self._upd(ranks, r, (False, reach, missed, held)),
                    active, inflight, kb - 1, cb)))
            if alv and reach and cb > 0:
                out.append((("partition", f"r{r}"), (
                    self._upd(ranks, r, (alv, False, missed, held)),
                    active, inflight, kb, cb - 1)))
            if alv and not reach:
                out.append((("heal", f"r{r}"), (
                    self._upd(ranks, r, (alv, True, missed, held)),
                    active, inflight, kb, cb)))
            if (not alv or not reach) and missed < self.th:
                out.append((("hb_miss", f"r{r}"), (
                    self._upd(ranks, r, (alv, reach, missed + 1,
                                         held)),
                    active, inflight, kb, cb)))
        if inflight == 0:
            ns = self._poll_result(state)
            if ns is not None:
                out.append((("poll",), ns))
        return out

    def _inv_floor(self, state):
        if len(state[1]) < self.min_dp:
            return (f"active dp {len(state[1])} fell below the "
                    f"min_dp={self.min_dp} floor")
        return None

    def _inv_boundary(self, s0, label, s1):
        if s0[1] != s1[1]:
            if label[0] != "poll":
                return (f"active set changed on a non-poll transition "
                        f"{label}")
            if s0[2] != 0:
                return (f"resize ran with {s0[2]} async steps still "
                        f"in flight — not a step boundary")
        return None

    def _inv_held(self, s0, label, s1):
        removed = set(s0[1]) - set(s1[1])
        for r in removed:
            alv, reach, missed, held = s0[0][r]
            if alv:
                return (f"rank {r} was shrunk out while still ALIVE "
                        f"({'held ' if held else ''}unreachable ranks "
                        f"must be HELD, not shrunk)")
            if missed < self.th:
                return (f"rank {r} was shrunk out after only {missed} "
                        f"heartbeat misses (wait window is {self.th})")
        return None

    def quiescent(self, state):
        ranks, active, inflight, kb, cb = state
        if inflight != 0 or self._poll_result(state) is not None:
            return False
        return all(alv and reach or missed >= self.th
                   for alv, reach, missed, held in ranks)

    def _inv_liveness(self, state):
        ranks, active, inflight, kb, cb = state
        act = set(active)
        for r, (alv, reach, missed, held) in enumerate(ranks):
            if not alv and r in act:
                survivors = len(act) - sum(
                    1 for rr in act if not ranks[rr][0])
                if survivors >= self.min_dp:
                    return (f"dead rank {r} still active at quiescence "
                            f"though the shrink was admissible")
            if alv and reach and r not in act:
                return (f"rank {r} is alive+reachable but excluded at "
                        f"quiescence — grow-back never happened")
            if held and not (alv and r in act):
                return f"rank {r} held but not an active alive rank"
        return None

    def render_state(self, state):
        ranks, active, inflight, kb, cb = state
        bits = []
        for r, (alv, reach, missed, held) in enumerate(ranks):
            bits.append(f"r{r}:{'A' if alv else 'd'}"
                        f"{'R' if reach else 'u'}m{missed}"
                        f"{'H' if held else ''}")
        bits.append(f"active={list(active)} inflight={inflight}")
        return " ".join(bits)


# ------------------------------------------------- mutations + registry

#: the three historical bug classes, re-introduced as model mutations —
#: the checker must produce a counterexample naming each one's invariant
SEEDED_MUTATIONS = {
    "promote_unsynced": {
        "model": "ps_replication",
        "invariant": "demoted-or-unsynced-never-serves",
        "history": "PR 4 review: promotion without the synced-copy "
                   "gate silently serves seed-initialized state",
    },
    "promote_no_epoch_bump": {
        "model": "ps_replication",
        "invariant": "single-serving-lineage",
        "history": "PR 8 split-brain: a promotion that reuses the "
                   "current epoch leaves the deposed primary "
                   "unfenceable",
    },
    "zombie_emit_unfenced": {
        "model": "decode_recovery",
        "invariant": "fenced-zombie-never-mutates",
        "history": "PR 19: a migrated-away replica's stale emission "
                   "lands in the journal without the replay-epoch "
                   "fence",
    },
}


def build_model(name, mutation=None, deep=False):
    """Model factory for the CLI / tests.  ``deep`` widens the budgets
    (more sends, a second fault) for the slow exhaustive sweep."""
    if name == "ps_replication":
        if deep:
            return PSReplicationModel(n_ranks=4, shards=(0, 1, 2),
                                      unsynced=(1,), max_sends=4,
                                      mutation=mutation)
        return PSReplicationModel(mutation=mutation)
    if name == "decode_recovery":
        if deep:
            return DecodeRecoveryModel(n_streams=2, n_replicas=3,
                                       max_tokens=3, retry_budget=2,
                                       fault_budget=3,
                                       mutation=mutation)
        return DecodeRecoveryModel(mutation=mutation)
    if name == "elastic_resize":
        assert mutation is None, mutation
        if deep:
            return ElasticResizeModel(n_ranks=4, window=3,
                                      kill_budget=2)
        return ElasticResizeModel()
    raise ValueError(f"unknown protocol model {name!r}")


MODELS = ("ps_replication", "decode_recovery", "elastic_resize")


def verify_all(deep=False, max_states=500_000):
    """Check every model at HEAD (expect zero violations) and every
    seeded mutation (expect a counterexample naming its invariant).
    Returns a JSON-able report — the core of
    ``artifacts/protocol_verify.json``."""
    report = {"models": {}, "mutations": {}, "ok": True}
    for name in MODELS:
        res = check(build_model(name, deep=deep), max_states=max_states)
        report["models"][name] = res.to_dict()
        report["ok"] &= res.ok and res.complete
    for mname, spec in SEEDED_MUTATIONS.items():
        res = check(build_model(spec["model"], mutation=mname,
                                deep=False), max_states=max_states)
        got = res.violations[0].invariant if res.violations else None
        hit = got == spec["invariant"]
        report["mutations"][mname] = {
            "model": spec["model"], "expected": spec["invariant"],
            "violated": got, "ok": hit,
            "trace_len": len(res.violations[0].trace)
            if res.violations else 0,
            "history": spec["history"],
        }
        report["ok"] &= hit
    return report


# ------------------------------------------ opcode alphabet (drift gate)

#: PS wire opcodes the replication model gives semantics to — the
#: message alphabet the lint drift gate checks ``ps/opcodes``' registry
#: against (a new replication-relevant opcode must land here or in the
#: allowlist below, with a reason)
PS_MESSAGE_ALPHABET = {
    "OP_PUSH": "client write: the deliver_push transition "
               "(fence -> dedup -> apply+mirror-before-ack)",
    "OP_PUSH_PULL": "fused write+read: its push half is deliver_push; "
                    "the pull half is the unfenced read plane",
    "OP_SET_DATA": "whole-table write: same fence/dedup/mirror path as "
                   "OP_PUSH (deliver_push)",
    "OP_REPLICATE": "the synchronous mirror inside deliver_push, with "
                    "the peer's _fence_or_adopt gate "
                    "(refuse_equal_if_serving)",
    "OP_PROMOTE": "the deliver_promote transition: synced-copy gate + "
                  "max(cur+1, want) epoch bump",
    "OP_INIT": "replica table creation rides the replica-plane "
               "_fence_or_adopt gate; collapsed into the model's "
               "initial copy placement",
    "OP_SYNC": "re-replication source half; collapsed into the "
               "sync_done transition (promotability gate)",
    "OP_SYNC_PUT": "re-replication sink half; completion IS the "
                   "sync_done transition that earns promotability",
    "OP_EPOCH": "lineage introspection: the probe_demote transition "
                "(healed split-brain convergence)",
}

#: PS opcodes deliberately OUTSIDE the replication model, each with the
#: reason it does not carry replicated-state-mutation semantics
PS_OPCODE_ALLOWLIST = {
    "OP_PULL": "read plane: deliberately unfenced bounded-staleness "
               "reads; fencing guards the write plane only",
    "OP_VERSIONS": "read plane: per-row version introspection, no "
                   "mutation",
    "OP_CLOCK": "SSP clock tick: rides shard-0 replication with the "
                "SAME (client, seq) dedup + forward path the model "
                "checks for OP_PUSH — no separate protocol arm",
    "OP_CLOCKS": "read plane: SSP clock-vector snapshot",
    "OP_SSP_SYNC": "scheduler plane: bounded server-side wait, no "
                   "replicated-state mutation",
    "OP_SSP_INIT": "scheduler plane: idempotent channel init, mirrored "
                   "via the modeled forward path",
    "OP_HEARTBEAT": "liveness plane: modeled abstractly by the elastic "
                    "model's hb_miss/poll transitions",
    "OP_ALIVE": "liveness read: mask snapshot, no mutation",
    "OP_SHUTDOWN": "admin plane: connection teardown",
    "OP_CHECKSUM": "fsck read plane: state digest of a held copy, no "
                   "mutation",
}


# ------------------------------------------------------ trace conformance

#: divergence rules accepted with a documented reason (the ISSUE 20
#: triage outlet: a REAL divergence found on a committed chaos bench is
#: either fixed with a regression test or allowlisted here)
CONFORMANCE_ALLOWLIST = {}


class ConformanceReport:
    """Per-plane replay verdict: events checked, divergences (each a
    dict naming the violated rule + the event index), allowlisted
    divergences."""

    __slots__ = ("plane", "checked", "divergences", "allowlisted")

    def __init__(self, plane):
        self.plane = plane
        self.checked = 0
        self.divergences = []
        self.allowlisted = []

    @property
    def ok(self):
        return not self.divergences

    def to_dict(self):
        return {"plane": self.plane, "checked": self.checked,
                "ok": self.ok, "divergences": list(self.divergences),
                "allowlisted": list(self.allowlisted)}

    def flag(self, rule, ev, detail, allowlist):
        d = {"plane": self.plane, "rule": rule,
             "event": ev.get("i", -1), "detail": detail}
        if rule in allowlist:
            d["reason"] = allowlist[rule]
            self.allowlisted.append(d)
            _record("protocol_divergences_allowlisted")
        else:
            self.divergences.append(d)
            _record("protocol_divergences")


class _PSMonitor:
    """Replays recorded ``ps`` events against the replication model's
    transition relation: per-copy epoch monotonicity, promote-bumps-
    epoch, the fence gates' stale-only refusal discipline, demoted
    copies never serving another apply, and per-copy exactly-once
    (client, seq) application."""

    def __init__(self, report, allowlist):
        self.rep = report
        self.allow = allowlist
        self.epoch = {}          # (rank, shard) -> last seen epoch
        self.serving = {}        # (rank, shard) -> True/False/unknown
        self.applied = set()     # (rank, shard, client, seq)

    def _epoch_ok(self, key, epoch, ev):
        last = self.epoch.get(key)
        if last is not None and epoch < last:
            self.rep.flag("epoch-monotonicity", ev,
                          f"copy r{key[0]}/s{key[1]} epoch {last} -> "
                          f"{epoch}", self.allow)
        self.epoch[key] = max(epoch, last if last is not None else 0)

    def feed(self, ev):
        kind = ev["kind"]
        key = (ev.get("rank"), ev.get("shard"))
        if kind == "promote":
            old, new = ev["old"], ev["new"]
            if new <= old:
                self.rep.flag("promote-bumps-epoch", ev,
                              f"promotion of r{key[0]}/s{key[1]} kept "
                              f"epoch {old} -> {new}", self.allow)
            if new < ev.get("want", 0):
                self.rep.flag("promote-bumps-epoch", ev,
                              f"promotion epoch {new} below the "
                              f"client's want={ev['want']}", self.allow)
            self._epoch_ok(key, new, ev)
            self.serving[key] = True
        elif kind == "demote":
            self._epoch_ok(key, ev["epoch"], ev)
            self.serving[key] = False
        elif kind == "adopt":
            self._epoch_ok(key, ev["new"], ev)
        elif kind == "apply":
            self._epoch_ok(key, ev["epoch"], ev)
            if self.serving.get(key) is False:
                self.rep.flag("demoted-copy-served", ev,
                              f"serving-side apply on r{key[0]}/"
                              f"s{key[1]} after its demotion",
                              self.allow)
            self._once(key, ev)
        elif kind == "apply_replica":
            self._once(key, ev)
        elif kind == "fence_refused":
            cur, got = ev["cur"], ev["got"]
            if ev.get("gate") == "repl":
                if got > cur:
                    self.rep.flag("fence-refuses-stale-only", ev,
                                  f"replica gate refused a NEWER epoch "
                                  f"{got} > {cur}", self.allow)
            elif got == cur:
                self.rep.flag("fence-refuses-stale-only", ev,
                              f"serving gate refused an equal-epoch "
                              f"frame (epoch {cur})", self.allow)
        elif kind == "sync_done":
            self.serving.setdefault(key, False)
        # client-plane kinds (client_failover, client_promoted,
        # route_flip, dedup_hit) are counted, not constrained: the
        # server-side gates above are where the model's claims live

    def _once(self, key, ev):
        k = key + (ev.get("client"), ev.get("seq"))
        if None in k:
            return
        if k in self.applied:
            self.rep.flag("exactly-once-apply", ev,
                          f"(client={k[2]}, seq={k[3]}) applied twice "
                          f"on r{key[0]}/s{key[1]} — dedup window "
                          f"breached", self.allow)
        self.applied.add(k)


class _DecodeMonitor:
    """Replays recorded ``decode`` events: per-stream journal
    contiguity + exactly-once token indices, accepted emissions carry
    the CURRENT replay epoch (a stale accepted emission is the PR 19
    zombie bug), detach bumps the epoch by one, fences drop only stale
    epochs, retries stay within the budget."""

    def __init__(self, report, allowlist):
        self.rep = report
        self.allow = allowlist
        self.epoch = {}
        self.nxt = {}

    def feed(self, ev):
        kind, sid = ev["kind"], ev.get("sid")
        if kind == "seat":
            if sid not in self.epoch:
                self.epoch[sid] = ev["epoch"]
                self.nxt[sid] = ev.get("n", 0)
            else:
                if ev["epoch"] != self.epoch[sid]:
                    self.rep.flag("stream-epoch-monotone", ev,
                                  f"s{sid} seated at epoch "
                                  f"{ev['epoch']}, tracked "
                                  f"{self.epoch[sid]}", self.allow)
                n = ev.get("n")
                if n is not None and n != self.nxt[sid]:
                    self.rep.flag("no-journal-gaps", ev,
                                  f"s{sid} reseated with journal {n}, "
                                  f"expected {self.nxt[sid]}",
                                  self.allow)
        elif kind == "emit":
            cur = self.epoch.setdefault(sid, ev["epoch"])
            if ev["epoch"] != cur:
                self.rep.flag("fenced-zombie-never-mutates", ev,
                              f"s{sid} ACCEPTED an emission at stale "
                              f"epoch {ev['epoch']} (current {cur})",
                              self.allow)
            want = self.nxt.setdefault(sid, ev["idx"])
            if ev["idx"] != want:
                self.rep.flag("exactly-once-token", ev,
                              f"s{sid} emitted index {ev['idx']}, "
                              f"expected {want} — duplicate or gap",
                              self.allow)
            self.nxt[sid] = max(want, ev["idx"] + 1)
        elif kind == "fenced":
            cur = self.epoch.get(sid)
            if cur is not None and ev["got"] >= cur:
                self.rep.flag("fence-only-stale", ev,
                              f"s{sid} fenced a CURRENT-epoch emission "
                              f"({ev['got']} >= {cur})", self.allow)
        elif kind == "detach":
            old, new = ev["old"], ev["new"]
            cur = self.epoch.get(sid)
            if new != old + 1 or (cur is not None and old != cur):
                self.rep.flag("stream-epoch-monotone", ev,
                              f"s{sid} detach epoch {old} -> {new} "
                              f"(tracked {cur})", self.allow)
            self.epoch[sid] = new
            budget = ev.get("budget")
            if budget is not None and ev.get("retries", 0) > budget:
                self.rep.flag("retry-budget", ev,
                              f"s{sid} requeued with retries="
                              f"{ev['retries']} past budget {budget}",
                              self.allow)
        # finish / fail / exhausted are terminal markers: counted only


class _ElasticMonitor:
    """Replays recorded ``elastic`` events: shrinks remove only ranks
    reported dead (never held-unreachable ones), the active set stays
    at or above ``min_dp``, refusals happen only below the floor."""

    def __init__(self, report, allowlist):
        self.rep = report
        self.allow = allowlist
        self.dead = set()
        self.held = set()

    def feed(self, ev):
        kind = ev["kind"]
        if kind == "dead":
            self.dead.add(ev["rank"])
            self.held.discard(ev["rank"])
        elif kind == "hold":
            self.held.add(ev["rank"])
        elif kind == "resize":
            removed = set(ev.get("removed", ()))
            for r in removed & self.held:
                self.rep.flag("held-unreachable-never-shrunk", ev,
                              f"rank {r} was HELD (alive, unreachable) "
                              f"yet shrunk out", self.allow)
            for r in removed - self.dead:
                self.rep.flag("shrink-only-dead", ev,
                              f"rank {r} shrunk without a preceding "
                              f"dead verdict", self.allow)
            if len(ev.get("active", ())) < ev.get("min_dp", 0):
                self.rep.flag("min-dp-floor", ev,
                              f"resize left dp="
                              f"{len(ev['active'])} below min_dp="
                              f"{ev['min_dp']}", self.allow)
            for r in ev.get("added", ()):
                self.dead.discard(r)
                self.held.discard(r)
        elif kind == "refused":
            if ev.get("survivors", 0) >= ev.get("min_dp", 0):
                self.rep.flag("refuse-only-below-floor", ev,
                              f"shrink refused with survivors="
                              f"{ev['survivors']} >= min_dp="
                              f"{ev['min_dp']}", self.allow)


def check_conformance(events, allowlist=None):
    """Replay a recorded run (:data:`PROTO` events, arrival order)
    against the models' transition relations.  Returns a JSON-able
    report with per-plane verdicts; ``ok`` is False iff any
    non-allowlisted divergence was found."""
    allowlist = CONFORMANCE_ALLOWLIST if allowlist is None else allowlist
    reports = {p: ConformanceReport(p)
               for p in ("ps", "decode", "elastic")}
    monitors = {"ps": _PSMonitor(reports["ps"], allowlist),
                "decode": _DecodeMonitor(reports["decode"], allowlist),
                "elastic": _ElasticMonitor(reports["elastic"],
                                           allowlist)}
    for ev in events:
        mon = monitors.get(ev.get("plane"))
        if mon is None:
            continue
        reports[ev["plane"]].checked += 1
        mon.feed(ev)
    _record("protocol_conformance_checks", len(events))
    out = {p: r.to_dict() for p, r in reports.items()}
    out["events"] = len(events)
    out["ok"] = all(r.ok for r in reports.values())
    return out


__all__ = [
    "PROTO", "protocol_event", "Violation", "CheckResult", "check",
    "PSReplicationModel", "DecodeRecoveryModel", "ElasticResizeModel",
    "SEEDED_MUTATIONS", "build_model", "MODELS", "verify_all",
    "PS_MESSAGE_ALPHABET", "PS_OPCODE_ALLOWLIST",
    "CONFORMANCE_ALLOWLIST", "ConformanceReport", "check_conformance",
]
