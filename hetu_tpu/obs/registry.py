"""Metrics registry: counters, log-bucketed latency histograms, gauges
(ISSUE 10 tentpole, part 3).

One process-wide :class:`Registry` (module singleton :data:`REGISTRY`)
owns every instrument, so ``metrics_dump()`` and the Prometheus
exposition (``tools/metricsd.py``) read ONE source of truth instead of
eight disconnected counter-family modules.  ``hetu_tpu.metrics``
registers every instrument at import — the thin ``record_*`` wrappers
there are the recording API; this module is the storage + readout.

* :class:`CounterFamily` — the pre-existing ``{kind: count}`` family
  shape (plain adds plus ``*_hw`` high-water max-gauges), with the same
  Counter-under-a-Lock hot path the old module-level families had: the
  migration must not tax ``record_run_plan`` (called once per training
  step on the dispatch path).
* :class:`Histogram` — log-bucketed latency distributions.  Buckets are
  8 per octave via ``math.frexp`` (no ``log`` call on the observe
  path): relative bucket width <= 12.5%, so p50/p90/p99 estimates (log-
  linear interpolation inside the hit bucket, clamped to the observed
  min/max) land within a few percent of a numpy reference — a p99
  PS-RPC spike or serving queue-wait is now distinguishable from its
  mean.  Optionally labeled (one sub-histogram per label, e.g. per
  opcode).
* :class:`Gauge` — last-written values per label (the per-run
  ``step_time_ms`` gauge).
"""
from __future__ import annotations

import math
import threading
from collections import Counter


class CounterFamily:
    """One named ``{kind: count}`` family (see module docstring)."""

    kind = "counter"
    __slots__ = ("name", "doc", "_c", "_lock")

    def __init__(self, name, doc):
        self.name = name
        self.doc = doc
        self._c = Counter()
        self._lock = threading.Lock()

    def inc(self, key, n=1):
        with self._lock:
            self._c[key] += n

    def max_gauge(self, key, v):
        """High-water semantics (``*_hw`` kinds): keep the max seen."""
        with self._lock:
            if v > self._c[key]:
                self._c[key] = v

    def counts(self):
        with self._lock:
            return dict(self._c)

    def reset(self):
        with self._lock:
            self._c.clear()

    def snapshot(self):
        return {k: int(v) for k, v in self.counts().items()}


def _bucket_of(v):
    """Log bucket index of a positive value: 8 sub-buckets per octave
    (``frexp``-based — no transcendental call on the observe path)."""
    m, e = math.frexp(v)        # v = m * 2**e, m in [0.5, 1)
    return (e << 3) | int((m - 0.5) * 16.0)


def _bucket_bounds(idx):
    """(lo, hi) value bounds of bucket ``idx`` (inverse of _bucket_of)."""
    e, sub = idx >> 3, idx & 7
    lo = math.ldexp(0.5 + sub / 16.0, e)
    hi = math.ldexp(0.5 + (sub + 1) / 16.0, e)
    return lo, hi


class _Hist:
    """One label's histogram state (caller holds the family lock)."""

    __slots__ = ("buckets", "n", "sum", "min", "max", "zeros")

    def __init__(self):
        self.buckets = Counter()    # bucket idx -> count
        self.n = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf
        self.zeros = 0              # v <= 0 observations (kept exact)

    def observe(self, v):
        self.n += 1
        self.sum += v
        if v < self.min:
            self.min = v
        if v > self.max:
            self.max = v
        if v > 0.0:
            self.buckets[_bucket_of(v)] += 1
        else:
            self.zeros += 1

    def percentile(self, q):
        """Estimate the q-th percentile (log-linear interpolation inside
        the hit bucket, clamped to the exact observed min/max)."""
        if self.n == 0:
            return None
        rank = q / 100.0 * self.n
        cum = self.zeros
        if rank <= cum:     # non-positive observations sort first
            return min(self.min, 0.0)
        est = self.max
        for idx in sorted(self.buckets):
            cnt = self.buckets[idx]
            if cum + cnt >= rank:
                lo, hi = _bucket_bounds(idx)
                frac = (rank - cum) / cnt
                est = lo * (hi / lo) ** frac
                break
            cum += cnt
        return float(min(max(est, self.min), self.max))

    def snapshot(self):
        out = {"count": int(self.n),
               "sum": float(self.sum),
               "min": None if self.n == 0 else float(self.min),
               "max": None if self.n == 0 else float(self.max),
               "mean": float(self.sum / self.n) if self.n else None}
        for q in (50, 90, 99):
            out[f"p{q}"] = self.percentile(q)
        return out


class Histogram:
    """A (possibly labeled) log-bucketed distribution (module docstring)."""

    kind = "histogram"
    __slots__ = ("name", "doc", "unit", "_h", "_lock")

    def __init__(self, name, doc, unit="us"):
        self.name = name
        self.doc = doc
        self.unit = unit
        self._h = {}            # label -> _Hist
        self._lock = threading.Lock()

    def observe(self, v, label=""):
        with self._lock:
            h = self._h.get(label)
            if h is None:
                h = self._h[label] = _Hist()
            h.observe(float(v))

    def percentile(self, q, label=""):
        with self._lock:
            h = self._h.get(label)
            return h.percentile(q) if h is not None else None

    def labels(self):
        with self._lock:
            return list(self._h)

    def snapshot(self):
        with self._lock:
            return {label: h.snapshot() for label, h in self._h.items()}

    def reset(self):
        with self._lock:
            self._h.clear()


class Gauge:
    """Last-written values per label (``step_time_ms``)."""

    kind = "gauge"
    __slots__ = ("name", "doc", "_v", "_lock")

    def __init__(self, name, doc):
        self.name = name
        self.doc = doc
        self._v = {}
        self._lock = threading.Lock()

    def set(self, v, label=""):
        with self._lock:
            self._v[label] = float(v)

    def values(self):
        with self._lock:
            return dict(self._v)

    def reset(self):
        with self._lock:
            self._v.clear()

    def snapshot(self):
        return self.values()


class Registry:
    """Name -> instrument map with one dump/reset/exposition surface."""

    def __init__(self):
        self._lock = threading.Lock()
        self._insts = {}

    def _register(self, cls, name, *args):
        with self._lock:
            inst = self._insts.get(name)
            if inst is not None:
                if type(inst) is not cls:
                    raise ValueError(
                        f"instrument {name!r} already registered as "
                        f"{inst.kind}, cannot re-register as a "
                        f"different kind")
                return inst     # idempotent re-registration (reimports)
            inst = cls(name, *args)
            self._insts[name] = inst
            return inst

    def counter_family(self, name, doc):
        return self._register(CounterFamily, name, doc)

    def histogram(self, name, doc, unit="us"):
        return self._register(Histogram, name, doc, unit)

    def gauge(self, name, doc):
        return self._register(Gauge, name, doc)

    def instruments(self):
        with self._lock:
            return dict(self._insts)

    def get(self, name):
        with self._lock:
            return self._insts.get(name)

    def dump(self):
        """One JSON-able snapshot of every instrument, grouped by kind:
        ``{"counters": {family: {kind: n}}, "histograms": {name: {label:
        {count/sum/min/max/mean/p50/p90/p99}}}, "gauges": {name: {label:
        value}}}`` — the single source of truth ``metrics_dump()``,
        ``HetuProfiler`` and ``tools/metricsd.py`` all read."""
        out = {"counters": {}, "histograms": {}, "gauges": {}}
        for name, inst in sorted(self.instruments().items()):
            out[inst.kind + "s"][name] = inst.snapshot()
        return out

    def reset_all(self):
        """Zero every registered instrument (replaces the per-family
        copy-pasted ``reset_*`` bodies)."""
        for inst in self.instruments().values():
            inst.reset()

    # -- Prometheus text exposition ---------------------------------------

    @staticmethod
    def _san(s):
        return "".join(c if c.isalnum() or c == "_" else "_" for c in s)

    def prometheus_text(self, prefix="hetu"):
        """Prometheus text-format exposition: counter families as
        ``<name>_total{kind=...}``, histograms as summaries (quantile
        series + ``_sum``/``_count``), gauges as plain gauges."""
        lines = []
        for name, inst in sorted(self.instruments().items()):
            mname = f"{prefix}_{self._san(name)}"
            doc = " ".join((inst.doc or "").split()) or name
            if inst.kind == "counter":
                lines.append(f"# HELP {mname}_total {doc}")
                lines.append(f"# TYPE {mname}_total counter")
                for k, v in sorted(inst.counts().items()):
                    lines.append(
                        f'{mname}_total{{kind="{self._san(str(k))}"}} '
                        f'{int(v)}')
            elif inst.kind == "histogram":
                lines.append(f"# HELP {mname} {doc}")
                lines.append(f"# TYPE {mname} summary")
                for label, snap in sorted(inst.snapshot().items()):
                    sel = f'label="{self._san(label)}",' if label else ""
                    for q in (50, 90, 99):
                        p = snap[f"p{q}"]
                        if p is not None:
                            lines.append(
                                f'{mname}{{{sel}quantile='
                                f'"{q / 100}"}} {p}')
                    lab = f'{{label="{self._san(label)}"}}' if label else ""
                    lines.append(f'{mname}_sum{lab} {snap["sum"]}')
                    lines.append(f'{mname}_count{lab} {snap["count"]}')
            else:
                lines.append(f"# HELP {mname} {doc}")
                lines.append(f"# TYPE {mname} gauge")
                for label, v in sorted(inst.values().items()):
                    lab = f'{{label="{self._san(label)}"}}' if label else ""
                    lines.append(f"{mname}{lab} {v}")
        return "\n".join(lines) + "\n"


#: the process-wide registry every instrument registers against
REGISTRY = Registry()


__all__ = ["CounterFamily", "Histogram", "Gauge", "Registry", "REGISTRY"]
