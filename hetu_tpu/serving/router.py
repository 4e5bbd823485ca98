"""Async request router: bounded queue → adaptive micro-batcher.

Online traffic arrives one request at a time; TPU programs want full,
legal batches.  The router sits between them:

* **Bounded admission.**  ``submit`` enqueues a request and returns a
  ``concurrent.futures.Future``.  A full queue REJECTS loudly
  (:class:`ServeRejected`, counted as ``serve_rejections``) instead of
  growing without bound — backpressure is the caller's signal to shed
  load upstream; an unbounded queue just converts overload into
  unbounded latency and an OOM.

* **Adaptive micro-batching.**  The batcher thread takes the oldest
  waiting request and keeps collecting until either ``max_batch``
  requests are waiting or the OLDEST one has waited ``max_wait_ms`` —
  the deadline is per-batch head-of-line, so a single straggler request
  ships alone after one wait window instead of stalling forever.  The
  collected batch is stacked, padded to the smallest legal bucket
  (``InferenceExecutor.infer``), run as ONE jitted call on the bucket's
  pinned executable, and the per-row results are scattered back to each
  request's future.

* **Failure semantics.**  A PS failover inside the batch's pull is
  absorbed by the store (the batch just takes longer; counted as
  ``serve_failovers`` via the fault-counter delta).  A genuinely failed
  batch fails ONLY its own requests' futures — the router keeps serving.
  ``close()`` rejects whatever is still queued.

Chaos integration: every dispatched batch reports the router's admission
count to the active :class:`~hetu_tpu.chaos.ChaosInjector`
(``on_request``), so ``kill:primary@shard<s>:req<n>`` schedules a
primary kill mid-load — the serving analogue of the step-scheduled kills
training chaos uses.

Fleet integration (ISSUE 17): a router can serve as ONE REPLICA behind
:class:`~hetu_tpu.serving.fleet.FrontDoor`.  The replica contract is the
small surface the front door drives: ``pending``/``health()`` (load +
heartbeat snapshot under the router's own lock), ``stop_admitting()`` →
``drain()`` (graceful retirement: reject new work with reason
``draining``, finish the queue and the in-flight batch), ``kill()``
(chaos fail-stop: the batcher exits at the next batch boundary WITHOUT
touching the queue, so the front door can ``detach_queue()`` the
orphaned requests and ``adopt()`` them into a survivor), and a ``name``
that suffixes the ``serve_latency_us`` labels (``batch@r0``) so
per-replica health is scored from the shared histogram.
"""
from __future__ import annotations

import collections
import threading
import time
from concurrent.futures import Future

import numpy as np

from .. import race as _race
from ..metrics import (record_serve, record_serve_latency,
                       record_serve_rejection)
from ..obs.lock_witness import make_condition
from ..obs.trace import TRACER as _TR


class ServeRejected(RuntimeError):
    """Explicit backpressure: the request was NOT admitted — shed load
    upstream and retry later.

    Every instance carries a structured ``reason`` from the CLOSED
    vocabulary below (plus the parameterized ``shed:<class>`` form) and an
    optional admission ``klass``; construction counts the reason into
    the ``serve_rejection_reason`` metrics family, so artifacts and
    tests read ``exc.reason`` / the counter instead of string-matching
    exception text.
    """

    #: the closed reason vocabulary; ``shed:<class>`` is the one
    #: parameterized form (class-based admission shedding).
    #: ``recovery_exhausted`` (ISSUE 19) marks an in-flight decode
    #: stream the fleet could NOT resurrect after its replica died
    #: (retry budget, deadline estimator, or zero survivors) — the
    #: instance's ``partial`` carries the tokens generated so far.
    REASONS = ("queue_full", "over_max_len", "deadline", "draining",
               "recovery_exhausted")

    def __init__(self, reason, detail="", klass=None, partial=None):
        reason = str(reason)
        if reason not in self.REASONS and not reason.startswith("shed:"):
            raise ValueError(
                f"unknown ServeRejected reason {reason!r} — vocabulary is "
                f"{list(self.REASONS)} or 'shed:<class>'")
        self.reason = reason
        self.klass = klass
        #: tokens already delivered before recovery gave up (a list for
        #: ``recovery_exhausted`` failures, else None) — partial work is
        #: surfaced, never silently discarded
        self.partial = partial
        record_serve_rejection(reason)
        super().__init__(f"{reason}: {detail}" if detail else reason)


class _Request:
    __slots__ = ("feeds", "future", "t_arrival")

    def __init__(self, feeds):
        self.feeds = feeds
        self.future = Future()
        self.t_arrival = time.monotonic()


class ServingRouter:
    """Bounded-queue adaptive micro-batching front end for one
    :class:`~hetu_tpu.serving.InferenceExecutor` (see module docstring).

    ``max_batch``: largest batch the batcher packs (default: the
    executor's largest bucket).  ``max_wait_ms``: how long the oldest
    waiting request may sit before its batch ships part-full.
    ``queue_limit``: admission bound — beyond it ``submit`` raises
    :class:`ServeRejected`.  ``refresh_every_batches``: run the read-only
    embedding staleness sweep every N batches (0 = never — call
    ``iex.refresh_embeddings()`` yourself).  ``start=False`` builds the
    router paused (tests exercising the backpressure path); call
    :meth:`start`.  ``name``: replica label — suffixes the
    ``serve_latency_us`` histogram labels (``batch@<name>``) so a fleet
    scores each replica separately off the shared registry.
    """

    def __init__(self, iex, max_batch=None, max_wait_ms=2.0,
                 queue_limit=256, refresh_every_batches=0, start=True,
                 name=""):
        self.iex = iex
        self.name = str(name)
        self.max_batch = min(int(max_batch or iex.max_batch),
                             iex.max_batch)
        if self.max_batch < 1:
            raise ValueError("max_batch must be >= 1")
        self.max_wait_ms = float(max_wait_ms)
        self.queue_limit = int(queue_limit)
        self.refresh_every_batches = int(refresh_every_batches)
        # latency labels: suffixed per replica when named, so fleet
        # health scoring can read one replica's distribution
        self._lat_queue_wait = f"queue_wait@{self.name}" if self.name \
            else "queue_wait"
        self._lat_batch = f"batch@{self.name}" if self.name else "batch"
        self._q = collections.deque()
        self._cv = make_condition("ServingRouter._cv")
        self._stop = False
        self._draining = False
        self._killed = False
        self._inflight = 0
        now = time.monotonic()
        self.hb_ts = now          # batcher-loop heartbeat (under _cv)
        self.progress_ts = now    # last COMPLETED batch (under _cv)
        self._admitted = 0
        self._batches = 0
        self._thread = None
        if start:
            self.start()

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        """Start the batcher thread (idempotent)."""
        with self._cv:
            if self._thread is not None or self._stop:
                return self
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="hetu-serve-router")
            self._thread.start()
        return self

    def close(self, timeout=None):
        """Stop the batcher; requests still queued are REJECTED (their
        futures fail with :class:`ServeRejected`)."""
        with self._cv:
            self._stop = True
            pending = list(self._q)
            self._q.clear()
            self._cv.notify_all()
        if _race.ACTIVE is not None:   # ISSUE 14 preemption point
            _race.point("router.close")
        for req in pending:
            # claim first: a caller-cancelled future would otherwise
            # raise InvalidStateError out of set_exception and abort the
            # rejection of every later pending request
            if req.future.set_running_or_notify_cancel():
                req.future.set_exception(
                    ServeRejected("draining",
                                  "router closed with the request queued"))
        if self._thread is not None:
            self._thread.join(timeout)
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    @property
    def queue_depth(self):
        with self._cv:
            return len(self._q)

    # -- fleet replica contract (ISSUE 17) ---------------------------------

    @property
    def pending(self):
        """Queued + in-flight request count — the front door's per-
        replica load signal (least-loaded dispatch keys on this)."""
        with self._cv:
            return len(self._q) + self._inflight

    def health(self):
        """Point-in-time health snapshot for the front door's sweep:
        load, the batcher-loop heartbeat / last-progress timestamps
        (wedge = pending work but a stale heartbeat), and the lifecycle
        flags.  One lock hold, plain dict out."""
        with self._cv:
            return {"pending": len(self._q) + self._inflight,
                    "queued": len(self._q),
                    "inflight": self._inflight,
                    "hb_ts": self.hb_ts,
                    "progress_ts": self.progress_ts,
                    "killed": self._killed,
                    "draining": self._draining,
                    "stopped": self._stop}

    def stop_admitting(self):
        """Graceful-drain step 1: new ``submit`` calls are rejected with
        reason ``draining`` while the batcher keeps working the queue
        (step 2 is :meth:`drain`, step 3 :meth:`close`)."""
        with self._cv:
            self._draining = True
            self._cv.notify_all()

    def drain(self, timeout=10.0):
        """Block until the queue is empty and no batch is in flight
        (call :meth:`stop_admitting` first or this may never converge).
        Returns True when drained, False on timeout or a killed
        batcher."""
        deadline = time.monotonic() + float(timeout)
        with self._cv:
            while self._q or self._inflight:
                if self._killed or self._thread is None:
                    return False
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(min(left, 0.05))
            return True

    def detach_queue(self):
        """Remove and return every QUEUED (not yet batch-claimed)
        request — the front door hands them to a surviving replica via
        :meth:`adopt` instead of failing admitted work."""
        with self._cv:
            orphans = list(self._q)
            self._q.clear()
            self._cv.notify_all()
            return orphans

    def adopt(self, reqs):
        """Admit requests detached from another replica.  Arrival
        timestamps are preserved (head-of-line deadlines anchor at the
        ORIGINAL arrival, so rescued work ships promptly) and the
        ``queue_limit`` is deliberately bypassed: rescue must not
        re-reject already-admitted requests.  Returns the count."""
        reqs = list(reqs)
        if not reqs:
            return 0
        with self._cv:
            if self._stop or self._killed:
                raise ServeRejected(
                    "draining", "cannot adopt into a stopped router")
            self._q.extend(reqs)
            self._admitted += len(reqs)
            record_serve("serve_queue_depth_hw", len(self._q))
            self._cv.notify_all()
        return len(reqs)

    def kill(self):
        """Chaos fail-stop: the batcher exits at its NEXT batch boundary
        without touching the queue — queued requests stay put for the
        front door to rescue (``detach_queue`` → ``adopt``), and a batch
        already on the device completes normally.  The failure model is
        fail-stop-at-a-boundary: no partial batch is ever half-answered,
        which is what keeps the fleet's bitwise-response guarantee for
        admitted requests.  New submits are rejected (``draining``)."""
        with self._cv:
            self._killed = True
            self._cv.notify_all()

    # -- admission ---------------------------------------------------------

    def submit(self, feed_dict):
        """Admit one single-sample request (``{placeholder: array}``
        WITHOUT the batch dim — the batcher stacks).  Returns a Future
        resolving to one value per executor fetch (row ``i`` of
        batch-derived fetches; whole value otherwise).  Raises
        :class:`ServeRejected` when the queue is full (reason
        ``queue_full``) or the router is closed / draining / killed
        (reason ``draining``)."""
        req = _Request(feed_dict)
        with self._cv:
            if self._stop or self._killed:
                raise ServeRejected("draining", "router is closed")
            if self._draining:
                raise ServeRejected("draining",
                                    "router is draining — not admitting")
            if len(self._q) >= self.queue_limit:
                record_serve("serve_rejections")
                raise ServeRejected(
                    "queue_full",
                    f"request queue full ({self.queue_limit} waiting) — "
                    f"shed load upstream and retry")
            self._q.append(req)
            self._admitted += 1
            record_serve("serve_requests")
            record_serve("serve_queue_depth_hw", len(self._q))
            if _TR.on:
                _TR.instant("serve.enqueue", cat="serve",
                            args={"depth": len(self._q)})
            self._cv.notify()
        return req.future

    # -- batching ----------------------------------------------------------

    def _take_batch(self):
        """Block until work exists, then collect until ``max_batch``
        requests wait or the OLDEST has hit the ``max_wait_ms``
        deadline.  Returns (requests, admitted-count snapshot), or None
        at shutdown."""
        with self._cv:
            while not self._q:
                if self._stop or self._killed:
                    return None
                self.hb_ts = time.monotonic()   # idle loop still beats
                self._cv.wait(0.05)
            # the deadline anchors at the oldest request's ARRIVAL, not
            # at the moment the batcher got back around to the queue — a
            # request that already waited out a slow previous batch (a
            # failover pull, a cold compile) ships immediately instead
            # of waiting up to a second full window
            deadline = self._q[0].t_arrival + self.max_wait_ms / 1e3
            while len(self._q) < self.max_batch and not self._stop \
                    and not self._killed:
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                self._cv.wait(left)
            if self._killed:
                # fail-stop at the batch boundary: leave the queue
                # intact for the front door's rescue
                return None
            n = min(len(self._q), self.max_batch)
            reqs = [self._q.popleft() for _ in range(n)]
            self._inflight += n
            self.hb_ts = time.monotonic()
            return reqs, self._admitted

    def _loop(self):
        while True:
            taken = self._take_batch()
            if taken is None:
                return
            reqs, admitted = taken
            # one malformed request must fail ONLY itself: requests are
            # grouped by feed schema (keys + shapes + dtypes) and each
            # group runs as its own sub-batch, so a bad shape or a
            # missing/unknown key poisons nobody it merely co-arrived
            # with (heterogeneous-but-valid shapes also just work)
            groups = {}
            for r in reqs:
                groups.setdefault(self._schema(r), []).append(r)
            for group in groups.values():
                self._run_batch(group, admitted)
            with self._cv:
                self._inflight -= len(reqs)
                now = time.monotonic()
                self.hb_ts = now
                self.progress_ts = now
                self._cv.notify_all()   # drain() waits on this

    @staticmethod
    def _schema(req):
        try:
            return tuple(sorted(
                (n.id, tuple(np.shape(v)), str(np.asarray(v).dtype))
                for n, v in req.feeds.items()))
        except Exception:
            return ("unstackable", id(req))

    def _run_batch(self, reqs, admitted):
        from ..metrics import fault_counts
        from .. import chaos as chaos_mod
        # claim each future (RUNNING) so a caller's later cancel() cannot
        # race set_result into InvalidStateError and kill this thread;
        # already-cancelled requests drop out of the batch here
        reqs = [r for r in reqs
                if r.future.set_running_or_notify_cancel()]
        if not reqs:
            return
        inj = chaos_mod.active()
        if inj is not None:
            # request-count-scheduled kills fire BEFORE the batch runs,
            # so the kill lands mid-load and THIS batch's pull absorbs
            # the failover
            inj.on_request(admitted)
        n = len(reqs)
        nodes = list(reqs[0].feeds)
        # per-request queue wait: submit -> claimed into a batch (the
        # router's contribution to tail latency — a p99 spike here is a
        # batching/backpressure problem, not a model problem)
        now = time.monotonic()
        for r in reqs:
            record_serve_latency(self._lat_queue_wait,
                                 (now - r.t_arrival) * 1e6)
        tr = _TR if _TR.on else None
        if tr is not None:
            t_asm = time.perf_counter_ns()
        try:
            stacked = {node: np.stack(
                [np.asarray(r.feeds[node]) for r in reqs], 0)
                for node in nodes}
            before = fault_counts().get("ps_failover_promoted", 0)
            if tr is not None:
                t_dev = time.perf_counter_ns()
                tr.complete("serve.assemble", t_asm, t_dev, cat="serve",
                            args={"n": n})
            t_call = time.perf_counter_ns()
            # the executor's scatter plan is STATIC (abstract shapes at
            # two batch sizes — see _fetch_row_scaling): each request
            # gets its k per-sample rows of a row-scaled fetch, the
            # whole value of a batch-invariant (or exact-fit aggregate)
            # one; no runtime shape guessing to mis-scatter
            try:
                outs, rows_per_req = self.iex.infer_rows(stacked)
            except Exception:     # noqa: BLE001 — one COUNTED retry
                # (ISSUE 19): a transient dispatch fault (a PS failover
                # racing the pull, a replica mid-promotion) should not
                # fail an admitted batch; a second failure is real and
                # falls through to fail the futures
                record_serve("serve_batch_retries")
                outs, rows_per_req = self.iex.infer_rows(stacked)
            t_done = time.perf_counter_ns()
            record_serve_latency(self._lat_batch, (t_done - t_call) / 1e3)
            if tr is not None:
                tr.complete("serve.device_call", t_call, t_done,
                            cat="serve", args={"n": n})
            delta = fault_counts().get("ps_failover_promoted", 0) - before
            if delta:
                record_serve("serve_failovers", delta)
        except Exception as e:    # noqa: BLE001 — each request must learn
            for r in reqs:        # its fate; the router keeps serving
                if not r.future.done():
                    r.future.set_exception(e)
            return
        record_serve("serve_responses", n)
        if _race.ACTIVE is not None:   # ISSUE 14: the set_result/cancel
            _race.point("router.resolve")   # window
        if tr is not None:
            t_sc = time.perf_counter_ns()
        for i, r in enumerate(reqs):
            row = []
            for o, k in zip(outs, rows_per_req):
                if k is None:
                    row.append(o)
                elif k == 1:
                    row.append(o[i])
                else:
                    row.append(o[i * k:(i + 1) * k])
            r.future.set_result(row)
        if tr is not None:
            tr.complete("serve.scatter", t_sc, time.perf_counter_ns(),
                        cat="serve", args={"n": n})
        self._batches += 1
        if self.refresh_every_batches > 0 \
                and self._batches % self.refresh_every_batches == 0:
            try:
                self.iex.refresh_embeddings()
            except Exception:
                pass    # a refresh hiccup must not kill the router


__all__ = ["ServingRouter", "ServeRejected"]
