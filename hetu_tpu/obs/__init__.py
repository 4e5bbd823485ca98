"""hetu_tpu.obs — unified telemetry: step-span tracing, a metrics
registry with latency histograms and MFU gauges, Chrome/Perfetto export
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
  accessors; the ``mfu``/``step_time_ms`` gauges are computed per run
  from the PR 5 inferred-shape FLOP model over measured step time
  (:func:`graph_flops` / :func:`record_mfu`).

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


# -- MFU / step-time gauges --------------------------------------------------

def graph_flops(fetches, feeds=None, train=True):
    """Per-step FLOPs of a fetch subgraph from the PR 5 inferred-shape
    cost model (``autoparallel.graph_layer_spec``: every matmul-family
    and attention contraction priced off the abstract-interpreter
    shapes — no hand-derived approximation).  ``train=True`` applies
    the standard 3x forward multiplier (forward + ~2x backward matmul
    work); pass ``train=False`` for inference-only graphs."""
    from ..autoparallel.cost_model import graph_layer_spec
    spec = graph_layer_spec(fetches, feeds=feeds)
    return (3.0 if train else 1.0) * float(spec.fwd_flops)


#: bf16 peak FLOP/s per chip by device_kind prefix, most-specific prefix
#: first.  Source: Google Cloud TPU documentation, the "System
#: architecture" page of each generation (v5e: 197 TFLOP/s bf16, 16 GB HBM
#: at 819 GB/s; v5p: 459; v4: 275; v3: 123; v2: 46; v6e/Trillium: 918).  THE one table — ``bench.py``
#: and ``autoparallel.measure`` both resolve through
#: :func:`device_peak_flops`, so a new device kind lands here once.
TPU_PEAK_BY_KIND = (
    ("TPU v6 lite", 918e12), ("TPU v6", 918e12),     # Trillium
    ("TPU v5 lite", 197e12), ("TPU v5p", 459e12), ("TPU v5", 459e12),
    ("TPU v4", 275e12), ("TPU v3", 123e12), ("TPU v2", 46e12),
)


def device_peak_flops():
    """(peak_flops_per_chip, device_kind).  A TPU ``device_kind`` that is
    not in :data:`TPU_PEAK_BY_KIND` is an error — a utilisation against a
    guessed peak is not a measurement; add the kind and its source to the
    table.  Non-TPU backends get a nominal 50 TF placeholder: their MFU
    is a relative CPU-side gauge, and nothing that reports a device
    metric reaches this branch (the accelerator bench configs and
    ``chip_smoke.py`` refuse a non-TPU backend first)."""
    import jax
    kind = jax.devices()[0].device_kind
    if jax.default_backend() != "tpu":
        return 50e12, kind
    for prefix, peak in TPU_PEAK_BY_KIND:
        if str(kind).startswith(prefix):
            return peak, kind
    raise ValueError(
        f"no peak FLOP/s known for TPU device_kind {kind!r}: add it to "
        f"hetu_tpu.obs.TPU_PEAK_BY_KIND with its source")


def record_mfu(label, flops_per_step, step_time_s, peak_flops):
    """Compute and publish the per-run ``mfu`` + ``step_time_ms``
    gauges: ``flops_per_step`` (see :func:`graph_flops`) over measured
    ``step_time_s``, against the hardware peak (``bench.py``'s
    per-device-kind table).  Returns the MFU value; ``metrics_dump()``
    exposes both gauges under ``label``."""
    from .. import metrics
    mfu = float(flops_per_step) / max(float(step_time_s), 1e-12) \
        / max(float(peak_flops), 1e-12)
    metrics.record_run_gauges(label, step_time_s * 1e3, mfu)
    return mfu


__all__ = ["TRACER", "span", "event", "enabled", "enable",
           "set_track_name", "clear_trace", "flow_begin", "flow_end",
           "trace_events", "export_chrome_trace", "registry",
           "metrics_dump", "prometheus_text", "reset_all_metrics",
           "graph_flops", "record_mfu", "device_peak_flops",
           "TPU_PEAK_BY_KIND"]
