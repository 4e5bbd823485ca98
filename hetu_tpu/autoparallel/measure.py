"""Measurement feedback for searched plans — the loop-closing leg.

The search (``search.py``) predicts; this module RUNS the top-k candidate
plans for a few steps each and feeds the measurements back:

* :func:`measure_plans` — one ``Executor(plan=candidate)`` per candidate
  through the process-wide compiled-step cache (one compile per distinct
  candidate, reused thereafter — re-measuring a plan hits the cache,
  counted as ``autoparallel_candidate_cache_hits``), per-step wall times
  forced honest by a scalar host read (the only reliable sync — the
  calibration probes' discipline), published into the registry as
  per-plan ``step_time_us`` histogram observations; on a TPU each
  measurement also carries its MFU (:func:`graph_flops` over the step
  time and :func:`device_peak_flops`);
* :func:`plan_diff` — per-layer predicted-vs-measured cost table for one
  measured plan (the cost model's end-to-end error, attributed per layer);
* :meth:`ParallelPlan.rerank <hetu_tpu.autoparallel.ParallelPlan.rerank>`
  consumes the measurement list and re-orders candidates by measured step
  time, so a mispriced cost model cannot pin the deployment to a slow
  plan.

The per-plan step time is the MIN over this run's measured steps (PR 9
convention: shared-host contention only ever inflates a step, so min is
the least-noise estimator).  The same per-step observations are
published to the registry histogram under ``label:plan.tag()`` — what
``metrics_dump()``/Prometheus expose — but the measurement itself never
reads back through the process-wide registry, so an earlier run under
the same tag (a different build, different feeds) cannot masquerade as
this one's min.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field


@dataclass
class PlanMeasurement:
    """One candidate plan's measured run."""
    plan: object
    label: str
    #: histogram-min step wall time, microseconds (PR 9 discipline)
    step_time_us: float
    #: every measured step's wall, microseconds (the distribution behind
    #: the min)
    walls_us: list = field(default_factory=list)
    #: the search's predicted step time, microseconds (None when the plan
    #: was constructed by hand without an estimate)
    predicted_us: float = None
    #: model-FLOPs utilization of this plan (None off the TPU, or when
    #: graph FLOPs could not be inferred)
    mfu: float = None
    #: True when this candidate's executable was built fresh (a step-cache
    #: miss); False = reused a previously compiled candidate
    compiled: bool = True

    @property
    def seconds(self):
        return self.step_time_us / 1e6


def graph_flops(fetches, feeds=None, train=True):
    """Per-step FLOPs of a fetch subgraph from the inferred-shape cost
    model (:func:`~.cost_model.graph_layer_spec`: every matmul-family
    and attention contraction priced off the abstract-interpreter
    shapes — no hand-derived approximation).  ``train=True`` applies
    the standard 3x forward multiplier (forward + ~2x backward matmul
    work); pass ``train=False`` for inference-only graphs."""
    from .cost_model import graph_layer_spec
    spec = graph_layer_spec(fetches, feeds=feeds)
    return (3.0 if train else 1.0) * float(spec.fwd_flops)


#: bf16 peak FLOP/s per chip by device_kind prefix, most-specific prefix
#: first.  Source: Google Cloud TPU documentation, the "System
#: architecture" page of each generation (v5e: 197 TFLOP/s bf16, 16 GB HBM
#: at 819 GB/s; v5p: 459; v4: 275; v3: 123; v2: 46; v6e/Trillium: 918).
#: This table prices a searched plan's MFU; the benchmark's own peaks are
#: ``benchmarks/peaks.json``.
TPU_PEAK_BY_KIND = (
    ("TPU v6 lite", 918e12), ("TPU v6", 918e12),     # Trillium
    ("TPU v5 lite", 197e12), ("TPU v5p", 459e12), ("TPU v5", 459e12),
    ("TPU v4", 275e12), ("TPU v3", 123e12), ("TPU v2", 46e12),
)


def device_peak_flops():
    """(peak_flops_per_chip, device_kind).  A TPU ``device_kind`` that is
    not in :data:`TPU_PEAK_BY_KIND` is an error — a utilisation against a
    guessed peak is not a measurement; add the kind and its source to the
    table.  Off the TPU there is no peak (``None``): a plan measured there
    has a step time and no MFU."""
    import jax
    kind = jax.devices()[0].device_kind
    if jax.default_backend() != "tpu":
        return None, kind
    for prefix, peak in TPU_PEAK_BY_KIND:
        if str(kind).startswith(prefix):
            return peak, kind
    raise ValueError(
        f"no peak FLOP/s known for TPU device_kind {kind!r}: add it to "
        f"hetu_tpu.autoparallel.measure.TPU_PEAK_BY_KIND with its source")


class _CandidateRun:
    """One candidate's live executor + measurement state."""

    def __init__(self, plan, build, label):
        from ..metrics import record_autoparallel, step_cache_counts
        self.plan = plan
        self.tag = f"{label}:{plan.tag()}"
        before = step_cache_counts()
        built = build(plan)
        self.ex, self.fd = built[0], built[1]
        self.name = built[2] if len(built) > 2 \
            else next(iter(self.ex.eval_node_dict))
        self.walls = []
        self.step()                    # the compile step — never counted
        self.walls.clear()
        after = step_cache_counts()
        self.compiled = (after.get("step_cache_miss", 0)
                         + after.get("step_cache_uncachable", 0)) \
            > (before.get("step_cache_miss", 0)
               + before.get("step_cache_uncachable", 0))
        if self.compiled:
            record_autoparallel("autoparallel_plans_compiled")
        if after.get("step_cache_hit", 0) > before.get("step_cache_hit", 0):
            record_autoparallel("autoparallel_candidate_cache_hits")

    def step(self, record=False):
        import numpy as np
        from ..metrics import record_step_time
        t0 = time.perf_counter()
        out = self.ex.run(self.name, feed_dict=self.fd)
        v = out[0]
        # host scalar read: the only reliable sync (async dispatch makes
        # run() return before the device finishes; materializing one
        # output of the jitted step waits for the whole executable)
        float(np.asarray(v.asnumpy() if hasattr(v, "asnumpy") else v)
              .ravel()[0])
        dt = time.perf_counter() - t0
        self.walls.append(dt * 1e6)
        if record:
            record_step_time(dt * 1e6, label=self.tag)
        return dt

    def finalize(self):
        from ..metrics import record_autoparallel, record_run_gauges
        record_autoparallel("autoparallel_plans_measured")
        # min over THIS run's walls — the registry histogram under the
        # same tag is process-wide (it may hold an earlier measurement's
        # steps), so the per-candidate verdict never reads back through it
        step_us = min(self.walls)
        record_run_gauges(self.tag, step_us / 1e3)
        mfu = None
        peak = device_peak_flops()[0]
        if peak:
            try:
                # the FORWARD fetch only (the loss, out[0] by the build
                # contract): the optimizer fetch carries the backward
                # matmuls, which graph_flops' train=True 3x multiplier
                # already prices — including it would double-count
                flops = graph_flops([self.ex.eval_node_dict[self.name][0]],
                                    feeds=self.fd)
            except Exception:
                flops = None  # MFU is best-effort; the step time is the verdict
            if flops:
                # the step spans every device in the executor's mesh; the
                # peak is per device
                mesh = getattr(self.ex, "mesh", None)
                n_dev = mesh.size if mesh is not None else 1
                mfu = flops / (step_us / 1e6) / (peak * n_dev)
        est = getattr(self.plan, "est_time", None)
        self.plan.measured_time = step_us / 1e6
        return PlanMeasurement(
            plan=self.plan, label=self.tag, step_time_us=step_us,
            walls_us=list(self.walls),
            predicted_us=None if est is None else est * 1e6, mfu=mfu,
            compiled=self.compiled)


def measure_plan(plan, build, steps=4, warmup=1, label="autoparallel"):
    """Run one candidate for ``steps`` measured steps; returns a
    :class:`PlanMeasurement`.

    ``build``: ``plan -> (executor, feed_dict[, subgraph_name])`` — must
    construct a FRESH graph for each call (plans annotate graph nodes in
    place, so candidates cannot share one graph).  The executor should be
    built with ``Executor(plan=plan)`` so the candidate's fingerprint
    keys the compiled-step cache.
    """
    run = _CandidateRun(plan, build, label)
    for _ in range(max(0, warmup)):
        run.step()
    run.walls.clear()
    for _ in range(max(1, steps)):
        run.step(record=True)
    return run.finalize()


def measure_plans(candidates, build, steps=4, warmup=1,
                  label="autoparallel"):
    """Measure every candidate (``plan.candidates`` order); returns the
    :class:`PlanMeasurement` list ``ParallelPlan.rerank`` consumes.

    All candidates are built (and compiled) FIRST, then the measured
    steps run in interleaved rounds — candidate A step, candidate B
    step, ... — so allocator warm-up, page-cache state and background
    load perturb every candidate alike instead of flattering whichever
    ran last (the interleaved-rounds discipline of the host-overhead
    bench)."""
    runs = [_CandidateRun(p, build, label) for p in candidates]
    for _ in range(max(0, warmup)):
        for r in runs:
            r.step()
    for r in runs:
        r.walls.clear()
    for _ in range(max(1, steps)):
        for r in runs:
            r.step(record=True)
    return [r.finalize() for r in runs]


def plan_diff(plan, measured=None, hw=None, microbatches=None):
    """Per-layer predicted-vs-measured cost report for one plan.

    ``measured``: seconds, or a :class:`PlanMeasurement` (falls back to
    ``plan.measured_time``).  Per-layer predicted microseconds come from
    re-pricing each layer with :class:`TimeCostModel` under the plan's
    own HardwareSpec; the measured total is attributed per layer by
    predicted share — the finest honest attribution a fused XLA step
    allows (no per-layer timers survive fusion) — so ``model_error``
    (= measured_total / predicted_total) is the cost model's end-to-end
    miss and each row's predicted-vs-measured gap scales with it."""
    from .cost_model import HardwareSpec, TimeCostModel
    hw = hw or getattr(plan, "hw", None) or HardwareSpec.from_artifact() \
        or HardwareSpec()
    tm = TimeCostModel(hw, microbatches or plan.microbatches)
    if measured is None:
        measured = plan.measured_time
    if isinstance(measured, PlanMeasurement):
        measured = measured.seconds
    rows = []
    for spec, s in zip(plan.specs, plan.strategies):
        t = tm.layer_time(spec, s) * spec.count
        rows.append({"layer": spec.name, "count": spec.count,
                     "strategy": str(s), "predicted_us": t * 1e6})
    ptotal = sum(r["predicted_us"] for r in rows)
    out = {"plan": plan.tag(), "layers": rows,
           "predicted_total_us": ptotal,
           "measured_total_us": None, "model_error": None}
    if measured is not None and ptotal > 0:
        mtotal = float(measured) * 1e6
        scale = mtotal / ptotal
        for r in rows:
            r["measured_us"] = r["predicted_us"] * scale
        out["measured_total_us"] = mtotal
        out["model_error"] = scale
    return out


def format_plan_diff(diff):
    """Human table for a :func:`plan_diff` report."""
    lines = [f"plan {diff['plan']}  predicted "
             f"{diff['predicted_total_us']:.0f}us  measured "
             + (f"{diff['measured_total_us']:.0f}us  (model error "
                f"{diff['model_error']:.2f}x)"
                if diff["measured_total_us"] is not None else "—"),
             f"  {'layer':<28}{'strategy':<22}{'predicted':>12}"
             f"{'measured':>12}"]
    for r in diff["layers"]:
        meas = f"{r['measured_us']:.0f}us" if "measured_us" in r else "—"
        lines.append(f"  {r['layer']:<28}{r['strategy']:<22}"
                     f"{r['predicted_us']:>10.0f}us{meas:>12}")
    return "\n".join(lines)


__all__ = ["PlanMeasurement", "measure_plan", "measure_plans",
           "plan_diff", "format_plan_diff"]
