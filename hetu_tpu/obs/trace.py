"""Lock-cheap thread-aware span/event tracer (ISSUE 10 tentpole, part 1).

One process-wide :class:`Tracer` collects timing records into
PER-THREAD ring buffers: the hot path touches only thread-local state
(no lock, no allocation beyond the record tuple), so a span costs two
``perf_counter_ns`` reads plus one ring store (~0.3us) — cheap enough
to leave compiled into the executor's dispatch path behind a single
``TRACER.on`` flag read (the ``HETU_TRACE=0`` default pays one
attribute load per guarded site, nothing else).

Record shapes (plain tuples — a ring slot assignment, never a dict):

* complete span  ``("X", name, cat, t0_ns, dur_ns, args)``
* instant event  ``("i", name, cat, t_ns, args)``
* flow begin/end ``("s"/"f", name, cat, t_ns, flow_id)`` — ties a
  ``run(sync=False)`` dispatch to the sync point that materialized it
  across arbitrary span nesting (rendered as arrows in Perfetto).
* packed hot-path records, expanded by the exporter: ``("P", t_pl, t0,
  t1, t2)`` is the executor fast lane's whole phase set (run-plan
  lookup / feed placement / jit dispatch) in ONE tuple, and ``("S",
  sub, t0, t1, step)`` one step span — per-step telemetry allocates
  two GC-tracked objects instead of five (generation-0 collections
  were a measurable slice of the tracing tax at microsecond step
  rates).

Thread buffers register themselves on first emit, named after their
thread (``threading.current_thread().name`` — the feed-pipeline /
serve-router / PS-serve pools pass ``thread_name_prefix``, so the
background planes show up as named tracks for free);
:meth:`Tracer.set_track_name` overrides.  Each buffer is a ring of
``HETU_TRACE_BUF`` slots (default 65536): a long run keeps the newest
events per thread instead of growing without bound, and
:func:`hetu_tpu.obs.export_chrome_trace` merges whatever survived.

Timestamps are ``time.perf_counter_ns()`` everywhere — one monotonic
base shared by every thread, so cross-track ordering is meaningful.

The rings live on the host's clock alone.  To stand beside the device's
operations a span also has to be in the trace ``jax.profiler`` writes,
which a ``jax.profiler.TraceAnnotation`` (a ``TraceMe``) opened at the
same boundary does: :func:`span` and the executor's traced branches open
one while a profiler session captures (:func:`annotate`), and
:class:`Phases` — the serving loop's per-step accounting — always
does, so that any ``jax.profiler`` session shows the program's phases
over the device's idle gaps with no switch to flip first.
"""
from __future__ import annotations

import itertools
import os
import threading
import time

from jax.profiler import TraceAnnotation


def annotate(name, **meta):
    """An ENTERED ``TraceAnnotation``, or None unless a ``jax.profiler``
    session is capturing (one C++ flag read, ~60 ns) — for :func:`span`
    and the executor's traced branches, which stamp their boundaries
    inline: with ``HETU_TRACE=1`` alone they pay the flag read, not an
    object per phase.  Close it with :func:`annotate_end`."""
    if not TraceAnnotation.is_enabled():
        return None
    ann = TraceAnnotation(name, **meta)
    ann.__enter__()
    return ann


def annotate_end(ann):
    """Close what :func:`annotate` returned (None: nothing was open)."""
    if ann is not None:
        ann.__exit__(None, None, None)


def _env_on():
    return os.environ.get("HETU_TRACE", "0").lower() not in (
        "", "0", "false", "off")


def _env_cap():
    try:
        return max(16, int(os.environ.get("HETU_TRACE_BUF", "65536")))
    except ValueError:
        return 65536


class _Buf:
    """One thread's ring: ``items[i % cap]`` with a monotonically growing
    write index ``i`` (``i > cap`` means the ring wrapped and the oldest
    ``i - cap`` records were overwritten)."""

    __slots__ = ("items", "i", "cap", "tid", "name", "gen")

    def __init__(self, cap, tid, name, gen):
        self.items = [None] * cap
        self.i = 0
        self.cap = cap
        self.tid = tid
        self.name = name
        self.gen = gen


class Tracer:
    """Process-wide trace collector (module singleton :data:`TRACER`).

    ``on`` is the ONE hot flag: instrumentation sites read it directly
    (``if TRACER.on: ...``) so a disabled tracer costs an attribute
    load per site.  Everything else — buffers, capacity, the flow-id
    counter — hides behind it.
    """

    def __init__(self):
        self.on = _env_on()
        self.cap = _env_cap()
        self._lock = threading.Lock()
        self._bufs = []
        self._tl = threading.local()
        self._gen = 0           # bumped by clear()/set_capacity()
        self._flow_ids = itertools.count(1)     # thread-safe in CPython

    # -- buffer management -------------------------------------------------

    def _buf(self):
        b = getattr(self._tl, "buf", None)
        if b is None or b.gen != self._gen:
            t = threading.current_thread()
            with self._lock:
                b = _Buf(self.cap, threading.get_ident(), t.name,
                         self._gen)
                self._bufs.append(b)
            self._tl.buf = b
        return b

    def set_track_name(self, name):
        """Name this thread's track in the exported trace (defaults to
        the thread's own name)."""
        self._buf().name = str(name)

    # -- hot emitters ------------------------------------------------------

    def complete(self, name, t0_ns, t1_ns, cat="hetu", args=None):
        """One finished span: explicit timestamps, for hot paths that
        stamp ``perf_counter_ns`` inline instead of entering a context
        manager."""
        b = self._buf()
        i = b.i
        b.items[i % b.cap] = ("X", name, cat, t0_ns, t1_ns - t0_ns, args)
        b.i = i + 1

    def instant(self, name, cat="hetu", args=None):
        """One point event (a fault, a sync point, an injection)."""
        b = self._buf()
        i = b.i
        b.items[i % b.cap] = ("i", name, cat,
                              time.perf_counter_ns(), args)
        b.i = i + 1

    def flow_begin(self, name, cat="async"):
        """Open a flow arrow (returns the flow id to close it with)."""
        fid = next(self._flow_ids)
        b = self._buf()
        i = b.i
        b.items[i % b.cap] = ("s", name, cat, time.perf_counter_ns(), fid)
        b.i = i + 1
        return fid

    def flow_end(self, name, fid, cat="async"):
        """Close a flow arrow opened by :meth:`flow_begin` (any thread)."""
        b = self._buf()
        i = b.i
        b.items[i % b.cap] = ("f", name, cat, time.perf_counter_ns(), fid)
        b.i = i + 1

    # -- control -----------------------------------------------------------

    def enable(self, on=True):
        """Turn tracing on/off at runtime (the env knob sets the initial
        state; tests flip it live)."""
        self.on = bool(on)

    def set_capacity(self, cap):
        """Resize the per-thread rings.  Drops everything recorded so
        far (each thread re-registers a fresh ring on its next emit)."""
        with self._lock:
            self.cap = max(16, int(cap))
            self._gen += 1
            self._bufs = []

    def clear(self):
        """Drop all recorded events (capacity unchanged)."""
        with self._lock:
            self._gen += 1
            self._bufs = []

    # -- readout -----------------------------------------------------------

    def tracks(self):
        """[(tid, track name)] for every thread that recorded events."""
        with self._lock:
            bufs = list(self._bufs)
        return [(b.tid, b.name) for b in bufs if b.i]

    def records(self):
        """Merged [(tid, record)] over all live rings, oldest-first per
        ring (the export sorts globally by timestamp)."""
        with self._lock:
            bufs = list(self._bufs)
        out = []
        for b in bufs:
            i, cap = b.i, b.cap
            if i <= cap:
                ring = b.items[:i]
            else:       # wrapped: oldest surviving record first
                k = i % cap
                ring = b.items[k:] + b.items[:k]
            for rec in ring:
                if rec is not None:
                    out.append((b.tid, rec))
        return out

    def dropped(self):
        """{tid: overwritten-record count} for rings that wrapped."""
        with self._lock:
            bufs = list(self._bufs)
        return {b.tid: b.i - b.cap for b in bufs if b.i > b.cap}


#: the process-wide tracer — instrumentation sites read ``TRACER.on``
TRACER = Tracer()


class _SpanCtx:
    """Context-manager span for non-hot call sites (``obs.span(...)``):
    one ring record, and the same interval in the profiler's trace while
    one is being captured."""

    __slots__ = ("name", "cat", "args", "t0", "ann")

    def __init__(self, name, cat, args):
        self.name = name
        self.cat = cat
        self.args = args or None

    def __enter__(self):
        self.ann = annotate(self.name)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        TRACER.complete(self.name, self.t0, time.perf_counter_ns(),
                        self.cat, self.args)
        annotate_end(self.ann)
        return False


class _NullSpan:
    """Tracing-off singleton: enter/exit are no-ops."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def span(name, cat="hetu", **args):
    """``with obs.span("step", step=3): ...`` — a no-op singleton when
    tracing is off, a recorded complete event when on."""
    if not TRACER.on:
        return _NULL_SPAN
    return _SpanCtx(name, cat, args)


def event(name, cat="hetu", **args):
    """Record one instant event (no-op when tracing is off)."""
    if TRACER.on:
        TRACER.instant(name, cat, args or None)


class Phases:
    """One interval of a loop — a decode step, the router's time between
    two steps — cut into phases that touch and do not overlap.  Each
    boundary is stamped ONCE (``perf_counter_ns``) and the stamp feeds
    three records of the same interval:

    1. an always-on counter, integer microseconds: ``record(kind, us)``
       per phase (``kinds``: ``{phase: (annotation name, counter
       kind)}``, built once by the caller), and the whole interval under
       ``total`` if given.  The microseconds are
       differences of the truncated stamps, so the phases of one
       interval add up to its length exactly;
    2. a ``jax.profiler.TraceAnnotation`` per phase, nested in one named
       ``name`` (``**meta`` becomes its arguments), ALWAYS opened: with
       no profiler session a ``TraceMe`` is a flag check in C++, and
       with one — the benchmark's, an operator's — the program's phases
       are on the device trace's clock with nothing to switch on;
    3. the :data:`TRACER` ring when ``TRACER.on``: the phases as ``X``
       records inside the ``name`` span (``self.args`` its arguments).

    ``with Phases(...) as ph: ph.mark("plan"); ...; ph.mark("feed")``:
    :meth:`mark` ends the open phase and starts the next, returning the
    stamp; leaving the block (an exception too) ends the last phase and
    the interval, after which ``ph.t1`` is the closing stamp.  A phase
    marked twice in one interval counts twice."""

    __slots__ = ("name", "cat", "args", "t0", "t1", "_record", "_kinds",
                 "_total", "_outer", "_inner", "_phase", "_t")

    def __init__(self, name, record, kinds=None, total=None, cat="hetu",
                 **meta):
        self.name = name
        self.cat = cat
        self.args = None
        self._record = record
        self._kinds = kinds
        self._total = total
        self._inner = self._phase = self.t1 = None
        self.t0 = self._t = time.perf_counter_ns()
        self._outer = TraceAnnotation(name, **meta)
        self._outer.__enter__()

    def meta(self, **meta):
        """More arguments for the interval's annotation, known only once
        it has begun (a decode step's chunk size)."""
        self._outer.set_metadata(**meta)

    def _end_phase(self, now):
        self._inner.__exit__(None, None, None)
        span, kind = self._kinds[self._phase]
        self._record(kind, now // 1000 - self._t // 1000)
        if TRACER.on:
            TRACER.complete(span, self._t, now, self.cat)
        self._inner = None

    def mark(self, phase):
        """End the open phase, begin ``phase``; returns the stamp (ns)."""
        now = time.perf_counter_ns()
        if self._inner is not None:
            self._end_phase(now)
        self._phase = phase
        self._inner = TraceAnnotation(self._kinds[phase][0])
        self._inner.__enter__()
        self._t = now
        return now

    def close(self):
        """End the open phase and the interval (once; later calls do
        nothing)."""
        if self.t1 is not None:
            return
        now = self.t1 = time.perf_counter_ns()
        if self._inner is not None:
            self._end_phase(now)
        self._outer.__exit__(None, None, None)
        if self._total is not None:
            self._record(self._total, now // 1000 - self.t0 // 1000)
        if TRACER.on:
            TRACER.complete(self.name, self.t0, now, self.cat, self.args)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


__all__ = ["Tracer", "TRACER", "span", "event", "annotate", "annotate_end",
           "Phases"]
