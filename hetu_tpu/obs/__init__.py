"""hetu_tpu.obs — unified telemetry: step-span tracing, a metrics
registry with latency histograms and gauges, Chrome/Perfetto export
(ISSUE 10).

The framework's behaviours worth reproducing — overlapped
communication, PS failover, pipelined execution — are exactly the ones
invisible to per-op timers and disconnected counters.  This subsystem
makes them visible from one place:

* **Spans/events** (:mod:`~hetu_tpu.obs.trace`): ``obs.span("x")`` /
  ``obs.event("x")`` write into lock-free per-thread ring buffers.
  Compiled to a no-op when ``HETU_TRACE=0`` (the default — guarded
  sites pay one flag read); ``HETU_TRACE=1`` (or ``obs.enable(True)``)
  records the executor's step phases (run-plan lookup, feed placement,
  jit dispatch, the PS push boundary), every PS client RPC per opcode
  (latency + payload bytes, with retry/failover/promotion/epoch-refusal
  as point events via the fault counters), the serving router
  lifecycle, chaos injections, the background feed-pipeline /
  replication / read-only-refresh threads as named tracks, and
  ``run(sync=False)`` in-flight windows as flow arrows.
  ``HETU_TRACE_BUF`` sizes the per-thread rings (default 65536; the
  ring keeps the newest events when it wraps).

* **Export** (:mod:`~hetu_tpu.obs.export`):
  ``obs.export_chrome_trace(path)`` writes Chrome trace JSON — load it
  at https://ui.perfetto.dev.  That file is the host's clock alone.

* **The program's spans and the device's ops in ONE trace**: capture
  with ``jax.profiler`` (``jax.profiler.trace(dir)``, or
  ``HetuProfiler.trace()``, which also switches span tracing on for the
  capture).  While a session captures, every ``obs.span`` and the
  executor's ``step`` / ``run_plan.lookup`` / ``feeds.place`` /
  ``jit.dispatch`` / ``executor.sync`` boundaries (with ``HETU_TRACE=1``)
  open a ``jax.profiler.TraceAnnotation`` too, and the decode plane's
  ``decode.step`` with its phases and ``decode.between``
  (:class:`~hetu_tpu.obs.trace.Phases`) do so ALWAYS — so the
  ``.xplane.pb`` holds the program's phases on the device's clock and an
  idle gap of the chip has an owner.  ``benchmarks/trace_reduce.py``
  reduces such a file; TensorBoard/XProf and Perfetto show it.

* **Metrics registry** (:mod:`~hetu_tpu.obs.registry`): every counter
  family, latency histogram and gauge registers against
  ``obs.registry``; :func:`metrics_dump` snapshots all of it as one
  JSON-able dict and ``tools/metricsd.py`` exposes the same registry as
  Prometheus text (file export or a tiny HTTP endpoint).  The
  histograms are log-bucketed (8 buckets/octave) with p50/p90/p99
  accessors; the ``step_time_ms`` gauge holds each measured plan's
  step time (``autoparallel.measure``).

Diagnostic-style conventions follow PR 5/PR 8: every exported name
says WHERE the number comes from and what a surprising value means.
"""
from __future__ import annotations

from .trace import TRACER, span, event
from .export import trace_events, export_chrome_trace
from .registry import REGISTRY as registry
from . import lock_witness  # noqa: F401  (ISSUE 14 runtime lock-witness)


def enabled():
    """True iff span/event tracing is currently recording."""
    return TRACER.on


def enable(on=True, buf=None):
    """Turn tracing on/off at runtime; ``buf`` resizes the per-thread
    rings first (dropping prior records)."""
    if buf is not None:
        TRACER.set_capacity(buf)
    TRACER.enable(on)


def set_track_name(name):
    """Name the calling thread's track in the exported trace."""
    TRACER.set_track_name(name)


def clear_trace():
    """Drop every recorded span/event (ring capacity unchanged)."""
    TRACER.clear()


def flow_begin(name, cat="async"):
    """Open a flow arrow; returns the id ``flow_end`` closes it with
    (no-op returning None when tracing is off)."""
    if not TRACER.on:
        return None
    return TRACER.flow_begin(name, cat)


def flow_end(name, fid, cat="async"):
    """Close a flow arrow opened by :func:`flow_begin` (from any
    thread); a ``None`` id (tracing was off at begin) is ignored."""
    if fid is not None and TRACER.on:
        TRACER.flow_end(name, fid, cat)


def metrics_dump():
    """One JSON-able snapshot of EVERY registered instrument:
    ``{"counters": {family: {kind: n}}, "histograms": {name: {label:
    {count/sum/min/max/mean/p50/p90/p99}}}, "gauges": {name: {label:
    value}}}``.  The counter values are the same numbers the legacy
    per-family accessors (``HetuProfiler.fault_counters()`` & co)
    report — one registry, two views."""
    return registry.dump()


def prometheus_text():
    """The registry as Prometheus text exposition (see
    ``tools/metricsd.py`` for the file/HTTP wrappers)."""
    return registry.prometheus_text()


def reset_all_metrics():
    """Zero every registered counter family, histogram and gauge
    (alias of ``hetu_tpu.metrics.reset_all``)."""
    registry.reset_all()


__all__ = ["TRACER", "span", "event", "enabled", "enable",
           "set_track_name", "clear_trace", "flow_begin", "flow_end",
           "trace_events", "export_chrome_trace", "registry",
           "metrics_dump", "prometheus_text", "reset_all_metrics"]
