"""What a process's start is made of: one record for every program jax
compiles, and the program's own set-up phases (ISSUE 39).

**Compiles.**  jax already says what it does to a program, through
``jax.monitoring``: how long it traced the Python function
(``/jax/core/compile/jaxpr_trace_duration``), lowered the jaxpr
(``.../jaxpr_to_mlir_module_duration``) and held the backend's compiler
(``.../backend_compile_duration``, which is ALSO the interval in which a
program is read back from the persistent cache), each with the
function's name; and, with no name but on the compiling thread inside
that last interval, whether the persistent cache answered
(``/jax/compilation_cache/cache_hits``, with ``cache_retrieval_time_sec``
and ``compile_time_saved_sec``) or was WRITTEN
(``/jax/compilation_cache/cache_misses`` — jax 0.9 fires it where it
stores the entry, after its own rule: process 0, no host callbacks, at
least ``jax_persistent_cache_min_compile_time_secs`` of compile, at
least ``jax_persistent_cache_min_entry_size_bytes``).  :func:`install`
registers ONE listener object for them, once a process
(``graph.executor.configure_compile_cache`` calls it), which folds them
into one record a program::

    {"owner": "decode", "program": "b16:c1:l768", "t_end": 1790894012.3,
     "trace_us": 1810000, "lower_us": 420000, "backend_us": 5400000,
     "cache": "miss", "cache_read_us": 0, "saved_us": 0, "stored": True}

``cache`` is ``hit`` (read back), ``miss`` (the cache is on, by
``jax.config`` at that moment: ``jax_enable_compilation_cache`` and a
``jax_compilation_cache_dir``; the program was compiled) or ``off``.
``stored`` is whether jax wrote the entry: **a miss that is not stored
is a program the next process compiles again.**

**Who asked.**  ``graph/step_cache.py`` is the only place where the
program's jitted steps are made, and it NAMES the function it jits
``<owner>:<program>`` (:func:`name_program`): ``train:<subgraph>``,
``serve:b<bucket>``, ``decode:b<batch>:c<chunk>:l<len>``.  jax hands
the name back with every event (and the profiler's trace says
``PjitFunction(decode:b16:c1:l768)`` where it said ``step``); any other
name is owner ``other`` — helpers, references, tests.  Nothing is added
to a dispatch: a step that compiles nothing runs what it ran.

**Where the records go.**  Always: the counter family
``compile_counts()`` keyed ``<owner>:<what>`` (``programs``,
``trace_us``, ``lower_us``, ``backend_us``, ``cache_hits``,
``cache_misses``, ``cache_read_us``, ``unstored``, ``unstored_us``),
``decode_step_compile_us`` in ``decode_counts()`` (a ``decode``
program's three parts: the share of ``decode_step_dispatch_us`` that
was no dispatch), and the newest :data:`KEEP` records
(:func:`records`, ``HetuProfiler.compile_log()``).  With
``HETU_TRACE=1`` each record is also four spans on the compiling
thread's track of the ``obs`` ring, written once the program is there:
``compile`` over ``compile.trace``, ``compile.lower`` and
``compile.backend``.

**Set-up phases.**  :class:`SetupPhase` times the program's own share
of a start that is no compilation — ``setup.graph`` (an executor's
construction), ``setup.weights`` (host arrays to the device),
``setup.state`` (a decode engine's slabs, rings and recurrent state) —
into the always-on counters ``setup_us{phase}`` / ``setup_bytes{phase}``
and, like every ``obs.span``, into the ring and a capturing profiler's
trace.  Once a process each, so no hot path.
"""
from __future__ import annotations

import collections
import threading
import time
import warnings

from .trace import TRACER, annotate, annotate_end

#: owners whose programs are the program's own (``step_cache`` names
#: them); everything else is ``other``
OWNERS = ("train", "serve", "decode")
#: records kept, newest last
KEEP = 256

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_WRITTEN = "/jax/compilation_cache/cache_misses"
_READ = "/jax/compilation_cache/cache_retrieval_time_sec"
_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
#: names a thread may hold an open trace / lower interval under before
#: the oldest is dropped (names come from code, so this is never reached
#: by a program that compiles what it traces)
_PENDING_MAX = 1024

_records = collections.deque(maxlen=KEEP)
_pending = threading.local()
_installed = False


def name_program(fn, owner, program):
    """Give the function ``step_cache`` is about to jit the name its
    records will carry; returns ``fn``."""
    fn.__name__ = fn.__qualname__ = f"{owner}:{program}"
    return fn


def _asked(fun_name):
    """``(owner, program)`` of a name as jax reports it (``jit(<name>)``
    from the lowering on, the bare name while tracing)."""
    name = fun_name[4:-1] if fun_name.startswith("jit(") \
        and fun_name.endswith(")") else fun_name
    owner, sep, program = name.partition(":")
    if sep and owner in OWNERS:
        return name, owner, program
    return name, "other", name


def _state():
    """This thread's open intervals, by the name jax reports them under,
    and the cache's word on the backend interval in progress."""
    st = _pending.__dict__
    if "trace" not in st:
        st.update(trace={}, lower={}, hit=False, written=False,
                  read_s=0.0, saved_s=0.0)
    return st


def _hold(spans, name, start, end):
    """Keep ``name``'s newest interval until its backend interval closes.
    By NAME, so that nothing else on the thread can take it away: a
    program's lowering traces hundreds of jitted helpers that are never
    compiled on their own (every ``jnp`` function inside a Pallas
    kernel's lowering) and compiles a few that are, all between the
    program's trace and its own backend interval — the chip's first
    readings had lost the one-token programs' trace to them.  A helper
    traced INSIDE a program's trace has another name and is part of the
    program's interval."""
    spans.pop(name, None)
    spans[name] = (start, end)
    if len(spans) > _PENDING_MAX:
        del spans[next(iter(spans))]


def _on_span(event, start, end, fun_name="", **_):
    if event == _BACKEND:
        try:
            _close(start, end, str(fun_name))
        except Exception as e:      # noqa: BLE001 — jax calls this from
            # inside its compile: a fault in the accounting must cost a
            # record, never the program
            warnings.warn(f"compile record of {fun_name!r} lost: "
                          f"{type(e).__name__}: {e}", RuntimeWarning)
    elif event == _TRACE:
        _hold(_state()["trace"], str(fun_name), start, end)
    elif event == _LOWER:
        _hold(_state()["lower"], str(fun_name), start, end)


def _on_event(event, **_):
    if event == _HIT:
        _state()["hit"] = True
    elif event == _WRITTEN:
        _state()["written"] = True


def _on_duration(event, seconds, **_):
    if event == _READ:
        _state()["read_s"] += seconds
    elif event == _SAVED:
        _state()["saved_s"] += seconds


def _cache_on():
    import jax
    return bool(jax.config.jax_enable_compilation_cache
                and jax.config.jax_compilation_cache_dir)


def _us(seconds):
    return max(0, int(round(seconds * 1e6)))


def _close(start, end, fun_name):
    """The backend interval of ``fun_name`` ended on this thread: fold
    what the thread holds into the program's record."""
    from .. import metrics
    st = _state()
    name, owner, program = _asked(fun_name)
    trace = st["trace"].pop(name, None)
    lower = st["lower"].pop(fun_name, None)
    hit, written = st["hit"], st["written"]
    read_s, saved_s = st["read_s"], st["saved_s"]
    st.update(hit=False, written=False, read_s=0.0, saved_s=0.0)
    cache = "hit" if hit else ("miss" if written or _cache_on() else "off")
    rec = {"owner": owner, "program": program, "t_end": end,
           "trace_us": _us(trace[1] - trace[0]) if trace else 0,
           "lower_us": _us(lower[1] - lower[0]) if lower else 0,
           "backend_us": _us(end - start), "cache": cache,
           "cache_read_us": _us(read_s), "saved_us": _us(saved_s),
           "stored": cache == "miss" and written}
    _records.append(rec)
    metrics.record_compile(rec)
    if TRACER.on:
        # jax stamps time.time(); the ring lives on perf_counter_ns
        off = time.perf_counter_ns() - time.time_ns()
        parts = [(span, *at) for span, at in (
            ("compile.trace", trace), ("compile.lower", lower),
            ("compile.backend", (start, end))) if at]
        t0 = min(s for _, s, _ in parts)
        TRACER.complete(
            "compile", int(t0 * 1e9) + off, int(end * 1e9) + off,
            "compile", {"owner": owner, "program": program,
                        "cache": cache, "stored": rec["stored"]})
        for span, s, e in parts:
            TRACER.complete(span, int(s * 1e9) + off, int(e * 1e9) + off,
                            "compile")


def install():
    """Register the listener with ``jax.monitoring`` (once a process;
    later calls do nothing)."""
    global _installed
    if _installed:
        return
    _installed = True
    from jax import monitoring
    monitoring.register_event_time_span_listener(_on_span)
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def records():
    """The newest :data:`KEEP` records, oldest first (copies)."""
    return [dict(r) for r in list(_records)]


def clear():
    """Drop the kept records (the counters are ``metrics``' to reset)."""
    _records.clear()


class SetupPhase:
    """``with SetupPhase("setup.weights") as ph: ...; ph.nbytes += n``
    (or ``ph = SetupPhase(...).start()`` ... ``ph.stop()`` around a
    constructor's body) — one of the program's set-up phases: always the
    counters ``setup_us{phase}`` / ``setup_bytes{phase}``; the ``obs``
    ring with ``HETU_TRACE=1`` and a ``TraceAnnotation`` while a profiler
    session captures, as :func:`hetu_tpu.obs.span`.  Phases do not nest:
    what one covers, no other counts.  The time is the HOST's: a
    ``device_put`` returns before its copy ends."""

    __slots__ = ("phase", "nbytes", "_t0", "_ann")

    def __init__(self, phase):
        self.phase = phase
        self.nbytes = 0

    def start(self):
        self._ann = annotate(self.phase) if TRACER.on else None
        self._t0 = time.perf_counter_ns()
        return self

    def stop(self):
        from .. import metrics
        t1 = time.perf_counter_ns()
        metrics.record_setup(self.phase, (t1 - self._t0) // 1000,
                             self.nbytes)
        if TRACER.on:
            TRACER.complete(self.phase, self._t0, t1, "setup",
                            {"bytes": int(self.nbytes)})
        annotate_end(self._ann)

    __enter__ = start

    def __exit__(self, *exc):
        self.stop()
        return False


__all__ = ["OWNERS", "KEEP", "install", "name_program", "records", "clear",
           "SetupPhase"]
