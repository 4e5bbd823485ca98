"""Graph lint: rule registry + actionable, provenance-carrying diagnostics.

Every rule sees the whole fetch subgraph with its static shapes (from
:mod:`hetu_tpu.analysis.shapes`) and yields :class:`Diagnostic`s that name
the offending node AND the user line that created it (``Op.creation_site``)
— so ``Executor(validate='error')`` fails fast with "your feed disagrees
with placeholder 'x' created at train.py:42", not an XLA trace dump.

Rule catalog (see README "Static analysis & graph validation"):

* ``uninferable`` (error) — a node's abstract lowering raised
* ``shape-rule-mismatch`` (error) — hand ``infer_shape`` disagrees with
  the abstract interpreter
* ``feed-mismatch`` (error) — fed value shape/dtype disagrees with the
  placeholder's declaration
* ``grad-nontrainable`` (error) — gradient requested w.r.t. a
  non-trainable / non-variable node
* ``duplicate-var-name`` (warn) — two variables share a checkpoint name
* ``ps-embedding-width`` (error) — declared embedding width != the PS
  table's actual width
* ``mesh-axis`` (warn) — an op / sharding names a mesh axis the
  executor's mesh does not have (silent fallback / silent replication)
* ``pipeline-stage`` (error/warn) — pipeline stages don't divide over the
  'pp' axis; ht.context placement chain fragments
* ``flash-fallback`` (warn) — attention config statically guaranteed to
  fall off the Pallas flash path on TPU (ragged causal mod-128,
  unsupported mask/bias broadcast shape)
* ``zero-sharding`` (warn) — ``Executor(zero=...)`` requested on a mesh
  with no usable 'dp' axis (silently replicated), or a slab bucket that
  needs zero-padding to shard over 'dp' (the ragged params are named;
  buckets whose total divides evenly are silent)
* ``train-only-op-in-serving`` (error/warn) — only under
  ``lint(serving=True)`` (the :class:`hetu_tpu.serving.InferenceExecutor`
  validation path): an optimizer update or gradient node reachable from a
  serving fetch set is an error (serving must never construct grad or
  optimizer subgraphs); a dropout node is a warning (it lowers to
  identity under ``training=False``, but its presence usually means the
  fetch set was lifted from a training head)
* ``decode-incompatible-op`` (error) — only under ``lint(decode=True)``
  (the ``InferenceExecutor(decode=True)`` validation path): an op whose
  lowering cannot run under incremental one-token decode — full-sequence
  attention (use ``sdpa_decode_op`` over a ``kv_cache_append_op`` cache)
  or batch-coupled statistics (BatchNorm — breaks the decode
  bitwise-stability guarantee under continuous batching)
* ``feed-schema-churn`` (warn, RUNTIME) — emitted by the executor's
  run-plan cache (``graph/run_plan.py``), not a static pass: successive
  ``run()`` calls keep missing the plan cache because a fed
  placeholder's shape ping-pongs (an unbucketed ragged batch) — every
  new schema re-plans the dispatch path AND retraces/compiles a fresh
  XLA program.  Same diagnostic shape as the static rules (rule name,
  offending node, creation site, concrete fix: bucket ragged batches,
  e.g. to the mod-128 buckets the flash kernel entry uses)
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..graph.node import Op, PlaceholderOp, format_site
from ..graph.gradients import GradientOp
from .shapes import GraphShapes, infer_graph, _normalize_feeds

#: rule name -> callable(GraphInfo) -> iterable[Diagnostic]
RULES = {}


def rule(name):
    def deco(fn):
        RULES[name] = fn
        fn.rule_name = name
        return fn
    return deco


@dataclass
class Diagnostic:
    rule: str
    severity: str          # 'error' | 'warn'
    message: str
    node: object = None    # offending Op, when one exists
    #: True for analyzer-internal problems (a rule crashed): reported,
    #: but never escalated to an exception — an analyzer bug must not
    #: reject a working graph
    internal: bool = False

    def __str__(self):
        loc = ""
        if self.node is not None:
            loc = (f" [node '{self.node.name}' created at "
                   f"{format_site(getattr(self.node, 'creation_site', None))}]")
        return f"{self.severity}[{self.rule}]: {self.message}{loc}"


class GraphInfo:
    """What a lint rule sees: topo + static shapes + executor config."""

    def __init__(self, shapes: GraphShapes, feeds, mesh=None, pipeline=None,
                 feed_values=None, zero=0, serving=False, remat="off",
                 plan=None, decode=False):
        self.shapes = shapes
        self.topo = shapes.topo
        self.feeds = feeds
        #: {node: actual fed array} for feeds given as VALUES (not bare
        #: shapes) — lets rules check value-level properties statically
        self.feed_values = feed_values or {}
        self.mesh = mesh
        self.pipeline = pipeline
        #: the auto-parallel ParallelPlan the executor will compile under
        #: (``Executor(plan=...)``) — enables the plan-coverage rule and
        #: escalates plan-managed mesh-axis findings to errors (an
        #: unrealizable plan must fail fast, not silently measure the
        #: wrong program)
        self.plan = plan
        #: requested ZeRO stage (Executor(zero=...)); 0 = off
        self.zero = int(zero or 0)
        #: True when linting a SERVING fetch set (InferenceExecutor):
        #: enables the train-only-op-in-serving rule
        self.serving = bool(serving)
        #: True when the fetch set is an incremental-DECODE step
        #: (InferenceExecutor(decode=True), hetu_tpu.serving.decode):
        #: enables the decode-incompatible-op rule
        self.decode = bool(decode)
        #: requested remat policy (Executor(remat=...)) — raw, NOT
        #: resolved: the remat-policy rule diagnoses unknown names
        self.remat = remat

    def shape(self, node):
        return self.shapes.shape(node)

    def struct(self, node):
        return self.shapes.struct(node)


class LintReport:
    """Diagnostics + the shape assignment they were derived from."""

    def __init__(self, shapes: GraphShapes, diagnostics):
        self.shapes = shapes
        order = {"error": 0, "warn": 1}
        self.diagnostics = sorted(diagnostics,
                                  key=lambda d: order.get(d.severity, 2))

    @property
    def errors(self):
        return [d for d in self.diagnostics if d.severity == "error"]

    @property
    def warnings(self):
        return [d for d in self.diagnostics if d.severity == "warn"]

    @property
    def ok(self):
        return not self.diagnostics

    @property
    def complete(self):
        """Every value-producing node got a static (shape, dtype)."""
        return self.shapes.complete

    def __bool__(self):
        return self.ok

    def __str__(self):
        if self.ok:
            return "lint: clean"
        return "\n".join(str(d) for d in self.diagnostics)

    def raise_errors(self, all_severities=False):
        bad = self.diagnostics if all_severities else self.errors
        bad = [d for d in bad if not d.internal]
        if bad:
            raise GraphValidationError(
                "graph validation failed:\n" +
                "\n".join(f"  {d}" for d in bad))


class GraphValidationError(ValueError):
    """Raised by ``Executor(validate='error')`` / ``LintReport.raise_errors``."""


# --------------------------------------------------------------------- rules

@rule("uninferable")
def _r_uninferable(gi):
    for node, why in gi.shapes.failed.items():
        yield Diagnostic(
            "uninferable", "error",
            f"abstract evaluation of {node.op_type} '{node.name}' failed: "
            f"{why}", node)


@rule("shape-rule-mismatch")
def _r_shape_rule(gi):
    """Cross-check hand-written shape rules against the interpreter."""
    for node in gi.topo:
        if node in gi.shapes.failed or node in gi.shapes.pending \
                or isinstance(node, (PlaceholderOp, GradientOp)):
            continue
        if not _has_hand_rule(node):
            continue
        in_shapes = [gi.shape(i) for i in node.inputs]
        if any(s is None for s in in_shapes):
            continue
        try:
            declared = node.infer_shape(in_shapes)
        except Exception as e:
            yield Diagnostic(
                "shape-rule-mismatch", "error",
                f"hand shape rule of {node.op_type} '{node.name}' raised "
                f"{type(e).__name__}: {e}", node)
            continue
        if declared is None:
            continue
        actual = gi.shape(node)
        if _norm_shape(declared) != _norm_shape(actual):
            yield Diagnostic(
                "shape-rule-mismatch", "error",
                f"hand shape rule of {node.op_type} '{node.name}' says "
                f"{_norm_shape(declared)} but its lowering produces "
                f"{_norm_shape(actual)}", node)


def _has_hand_rule(node):
    if getattr(node, "has_shape_rule", None) is not None:
        return bool(node.has_shape_rule)   # SimpleOp: explicit shape_fn
    # other subclasses: an overridden infer_shape method is a hand rule
    return type(node).infer_shape is not Op.infer_shape


def _norm_shape(s):
    if s is None:
        return None
    if isinstance(s, (tuple, list)):
        return tuple(_norm_shape(x) if isinstance(x, (tuple, list))
                     else int(x) for x in s)
    return s


@rule("feed-mismatch")
def _r_feed(gi):
    for node, st in gi.feeds.items():
        if isinstance(st, (tuple, list)):
            continue  # nested (multi-part) feed: no single shape to check
        if not isinstance(node, PlaceholderOp):
            yield Diagnostic(
                "feed-mismatch", "error",
                f"feed target '{getattr(node, 'name', node)}' is not a "
                f"placeholder (op type {getattr(node, 'op_type', '?')})",
                node if isinstance(node, Op) else None)
            continue
        if node.is_variable:
            yield Diagnostic(
                "feed-mismatch", "error",
                f"'{node.name}' is a variable, not a fed placeholder — "
                f"use executor.load_dict / set_value to change it", node)
            continue
        if node.shape is not None and tuple(st.shape) != tuple(node.shape):
            yield Diagnostic(
                "feed-mismatch", "error",
                f"feed for placeholder '{node.name}' has shape "
                f"{tuple(st.shape)} but the placeholder declares "
                f"{tuple(node.shape)}", node)
            continue
        # dtype: the executor ADOPTS the declared dtype (feeds are cast),
        # so a kind mismatch is only an error when the cast would destroy
        # actual values — checkable when the feed was given as values
        val = gi.feed_values.get(node)
        if node.dtype is not None and val is not None \
                and np.issubdtype(np.dtype(node.dtype), np.integer) \
                and np.issubdtype(np.asarray(val).dtype, np.floating) \
                and not np.all(np.mod(np.asarray(val), 1.0) == 0):
            yield Diagnostic(
                "feed-mismatch", "error",
                f"feed for placeholder '{node.name}' holds fractional "
                f"float values but the placeholder declares "
                f"{np.dtype(node.dtype)} — the executor's dtype adoption "
                f"would truncate them", node)


@rule("grad-nontrainable")
def _r_grad(gi):
    for node in gi.topo:
        if not isinstance(node, GradientOp):
            continue
        wrt = node.wrt
        if not (isinstance(wrt, PlaceholderOp) and wrt.is_variable):
            yield Diagnostic(
                "grad-nontrainable", "error",
                f"gradient requested w.r.t. '{wrt.name}' which is not a "
                f"variable ({wrt.op_type})", wrt)
        elif not wrt.trainable:
            yield Diagnostic(
                "grad-nontrainable", "error",
                f"gradient requested w.r.t. NON-TRAINABLE variable "
                f"'{wrt.name}' — the optimizer would silently train it "
                f"(mark trainable=True or drop it from the loss params)",
                wrt)


@rule("duplicate-var-name")
def _r_dup_names(gi):
    seen = {}
    for node in gi.topo:
        if isinstance(node, PlaceholderOp) and node.is_variable:
            first = seen.setdefault(node.name, node)
            if first is not node:
                yield Diagnostic(
                    "duplicate-var-name", "warn",
                    f"two variables share checkpoint name '{node.name}' "
                    f"(first created at "
                    f"{format_site(first.creation_site)}) — the executor "
                    f"renames the second to '{node.name}~1', making the "
                    f"checkpoint identity creation-order-dependent", node)


@rule("ps-embedding-width")
def _r_ps_width(gi):
    for node in gi.topo:
        if not getattr(node, "is_ps", False):
            continue
        store, table = node.store, node.table
        if not hasattr(store, "width"):
            continue
        try:
            actual = int(store.width(table))
        except Exception as e:
            yield Diagnostic(
                "ps-embedding-width", "error",
                f"PS embedding '{node.name}': table {table} is not "
                f"readable from its store ({type(e).__name__}: {e})", node)
            continue
        if node.width is not None and int(node.width) != actual:
            yield Diagnostic(
                "ps-embedding-width", "error",
                f"PS embedding '{node.name}' declares width {node.width} "
                f"but table {table} has width {actual} — every pulled row "
                f"would be mis-shaped", node)


#: graph ops whose lowering changes behavior based on a named mesh axis;
#: with a mesh lacking the axis they SILENTLY run the fallback path
_MESH_AXIS_OPS = {
    "AllToAll": ("ep",),
    "HAllToAll": ("ep", "ep_outer", "ep_inner"),
    "RingAttention": ("cp",),
    "RingAttentionMasked": ("cp",),
    "UlyssesAttention": ("cp",),
    "UlyssesAttentionMasked": ("cp",),
    "PipelineBlock": ("pp",),
}


#: axes the auto-parallel strategy space manages — a plan-validated graph
#: missing one of THESE is an illegal plan (error), while e.g. an 'ep'
#: sharding replicating on a dp-only plan mesh is the intended dense
#: fallback (stays a warning)
_PLAN_AXES = frozenset(("dp", "tp", "pp", "cp"))


@rule("mesh-axis")
def _r_mesh_axis(gi):
    if gi.mesh is None:
        return  # single-device run: fallback paths are the intended paths
    axes = set(gi.mesh.axis_names)

    plan_axes = frozenset()
    if gi.plan is not None:
        try:
            plan_axes = frozenset(a for a, s in gi.plan.mesh_axes().items()
                                  if s > 1) & _PLAN_AXES
        except Exception:
            plan_axes = _PLAN_AXES   # unpriceable plan: stay strict

    def sev(involved):
        # under Executor(plan=...): an axis the plan ACTUALLY USES going
        # silently replicated/fallback is an unrealizable plan — fail
        # fast.  Axes the plan sets to 1 stay warnings: a
        # pipeline_block-built model under a pp=1 plan (or ring
        # attention under cp=1) falls back to exactly the
        # single-stage/dense program the cost model priced.
        return "error" if set(involved) & plan_axes else "warn"

    for node in gi.topo:
        want = _MESH_AXIS_OPS.get(node.op_type)
        if want and not any(a in axes for a in want):
            yield Diagnostic(
                "mesh-axis", sev(want),
                f"{node.op_type} '{node.name}' expects mesh axis "
                f"'{want[0]}' but the executor mesh has axes "
                f"{sorted(axes)} — it will silently run its "
                f"non-distributed fallback", node)
        spec = getattr(node, "sharding", None)
        if spec is not None:
            missing = [a for a in spec
                       if a is not None and not isinstance(a, tuple)
                       and a not in axes]
            if missing:
                yield Diagnostic(
                    "mesh-axis", sev(missing),
                    f"sharding of '{node.name}' names mesh axes "
                    f"{missing} absent from the executor mesh "
                    f"{sorted(axes)} — those dims will be REPLICATED",
                    node)


@rule("pipeline-stage")
def _r_pipeline(gi):
    # (a) PipelineBlock stages must divide over the mesh 'pp' axis
    if gi.mesh is not None and "pp" in gi.mesh.axis_names:
        pp = gi.mesh.shape["pp"]
        for node in gi.topo:
            if node.op_type != "PipelineBlock":
                continue
            n = getattr(node, "n_stages", None)
            if n and pp > 1 and n % pp != 0:
                yield Diagnostic(
                    "pipeline-stage", "error",
                    f"PipelineBlock '{node.name}' has {n} stages over a "
                    f"'pp' axis of size {pp} — stages must divide evenly "
                    f"across pipeline ranks", node)
    # (b) interop placement contiguity: run-length segmentation over topo
    # order must not fragment (each alternation = one boundary transfer +
    # a separate jit)
    segments, prev = [], None
    for node in gi.topo:
        if isinstance(node, (PlaceholderOp, GradientOp)) \
                or node.raw_ctx is None:
            continue
        key = repr(node.raw_ctx)
        if key != prev:
            segments.append((key, node))
            prev = key
    distinct = len({k for k, _ in segments})
    if distinct and len(segments) > 2 * distinct:
        first_bounce = segments[distinct][1]
        yield Diagnostic(
            "pipeline-stage", "warn",
            f"ht.context placement fragments into {len(segments)} "
            f"segments over {distinct} device groups — ops per device "
            f"are not contiguous in graph order (first bounce at "
            f"'{first_bounce.name}'); group each stage's ops together",
            first_bounce)


@rule("plan-coverage")
def _r_plan_coverage(gi):
    """An ``Executor(plan=...)`` graph must actually REALIZE the plan:
    tp directives need 'tp' shardings on some kernel (``plan.apply`` /
    ``plan.bind``), pp needs a ``ht.pipeline_block``-built model, cp
    needs ring/ulysses attention ops, fsdp needs either the ZeRO slab
    route (``zero>=1``) or 'dp' param shardings.  Anything less silently
    executes (and measures!) a different program than the plan the
    search costed."""
    plan = gi.plan
    if plan is None:
        return
    try:
        need = plan.mesh_axes()
        directives = plan.layer_specs()
    except Exception as e:
        yield Diagnostic(
            "plan-coverage", "error",
            f"plan is not executable as a single mesh: {e}")
        return
    axes = set(gi.mesh.axis_names) if gi.mesh is not None else set()
    missing = sorted(a for a, s in need.items() if s > 1 and a not in axes)
    if missing:
        yield Diagnostic(
            "plan-coverage", "error",
            f"plan needs mesh axes {missing} but the executor mesh has "
            f"{sorted(axes)} — pass the plan's own mesh "
            f"(ParallelPlan.make_mesh) or rebuild the executor without "
            f"an explicit mesh=")

    def _axes_of(spec):
        out = set()
        for a in spec or ():
            if isinstance(a, (tuple, list)):
                out.update(a)
            elif a is not None:
                out.add(a)
        return out

    annotated = set()
    for node in gi.topo:
        annotated |= _axes_of(getattr(node, "sharding", None))

    def _layers(pred):
        names = [d["name"] for d in directives if pred(d)]
        more = f" (+{len(names) - 3} more)" if len(names) > 3 else ""
        return ", ".join(names[:3]) + more

    if any(d["tp"] > 1 for d in directives) and "tp" not in annotated:
        yield Diagnostic(
            "plan-coverage", "error",
            f"plan assigns tp>1 to layer(s) [{_layers(lambda d: d['tp'] > 1)}] but no "
            f"graph node carries a 'tp' sharding — the plan was never "
            f"applied; bind the model layers (plan.bind(layers)) or call "
            f"plan.apply(layers) before building the executor")
    if max(s.pp for s in plan.strategies) > 1 \
            and not any(n.op_type == "PipelineBlock" for n in gi.topo):
        yield Diagnostic(
            "plan-coverage", "error",
            f"plan assigns {max(s.pp for s in plan.strategies)} pipeline "
            f"stages but the graph has no PipelineBlock — build the "
            f"model with ht.pipeline_block and the plan's stage "
            f"assignment")
    if max(s.cp for s in plan.strategies) > 1 \
            and not any(n.op_type.startswith(("RingAttention",
                                              "UlyssesAttention"))
                        for n in gi.topo):
        yield Diagnostic(
            "plan-coverage", "error",
            f"plan assigns cp={max(s.cp for s in plan.strategies)} "
            f"context parallelism to layer(s) [{_layers(lambda d: d['cp'] > 1)}] but the "
            f"graph has no ring/ulysses attention — build attention with "
            f"context_parallel='ring' (or 'ulysses')")
    # fires for ANY unrealized fsdp directive — including tp>1 plans
    # (wants_zero() False, so the slab route never covers them): without
    # zero or 'dp' param shardings the params replicate and the search's
    # memory feasibility verdict silently does not hold
    if any(d["fsdp"] for d in directives) and not gi.zero \
            and "dp" not in annotated:
        yield Diagnostic(
            "plan-coverage", "error",
            f"plan assigns fsdp to layer(s) [{_layers(lambda d: d['fsdp'])}] but "
            f"zero= is off and no param carries a 'dp' sharding — the "
            f"fsdp memory verdict would not hold at runtime; pass "
            f"Executor(zero=3) (the default when plan= sets the "
            f"strategy) or apply the plan's param specs")


#: attention op types -> (index of k input, index of mask input or None,
#: index of bias input or None)
_ATTN_OPS = {
    "ScaledDotProductAttention": (1, None, None),
    "ScaledDotProductAttentionVarlen": (1, None, None),
    "ScaledDotProductAttentionMasked": (1, 3, None),
    "ScaledDotProductAttentionBias": (1, None, 3),
    "ScaledDotProductAttentionMaskedBias": (1, 3, 4),
    # packed (B, S, H·D) operands: lengths are dims -2 as well; its mask
    # is a key-padding mask by the layer's rule (no per-head shape to check)
    "ScaledDotProductAttentionPacked": (1, None, None),
    "RingAttention": (1, None, 3),
    "UlyssesAttention": (1, None, 3),
    "RingAttentionMasked": (1, 3, 4),
    "UlyssesAttentionMasked": (1, 3, 4),
}


@rule("flash-fallback")
def _r_flash(gi):
    """Static predictor of the attention dispatchers'
    ``flash_fallback_reason``: configs that are GUARANTEED to leave the
    Pallas fast path on TPU are flagged before anything runs (ragged
    causal mod-128 bucketing, unsupported mask/bias broadcast shapes)."""
    from ..ops.attention import (_FLASH_MIN_LEN, _broadcastable_extra,
                                 _causal_bucketable)
    for node in gi.topo:
        spec = _ATTN_OPS.get(node.op_type)
        if spec is None:
            continue
        k_i, m_i, b_i = spec
        q = gi.struct(node.inputs[0])
        k = gi.struct(node.inputs[k_i]) if k_i < len(node.inputs) else None
        if q is None or k is None:
            continue
        if q.shape[-2] < _FLASH_MIN_LEN:
            # below the empirical dispatch gate the einsum path is the
            # INTENDED path (XLA fusion wins at short seq) — nothing to
            # warn about
            continue
        causal = bool(node.attrs.get("causal", False))
        if not _causal_bucketable(q, k, causal):
            yield Diagnostic(
                "flash-fallback", "warn",
                f"{node.op_type} '{node.name}': causal attention with "
                f"ragged lengths (q={q.shape[-2]}, kv={k.shape[-2]}) — "
                f"{q.shape[-2] % 128} != {k.shape[-2] % 128} (mod 128), "
                f"so on TPU this falls back to einsum attention "
                f"(reason 'causal_ragged_mismatch'); pad q/kv to matching "
                f"mod-128 lengths", node)
        for what, idx in (("mask", m_i), ("bias", b_i)):
            if idx is None or idx >= len(node.inputs):
                continue
            extra = gi.struct(node.inputs[idx])
            if extra is not None and hasattr(extra, "shape") \
                    and not _broadcastable_extra(q, k, extra):
                yield Diagnostic(
                    "flash-fallback", "warn",
                    f"{node.op_type} '{node.name}': {what} shape "
                    f"{tuple(extra.shape)} is outside the flash kernel's "
                    f"broadcast support (1|B, 1|H, 1|S_q, S_kv) — on TPU "
                    f"this falls back to einsum attention (reason "
                    f"'{what}_shape')", node)


@rule("zero-sharding")
def _r_zero(gi):
    """ZeRO weight-update sharding preconditions (parallel/zero.py):
    the plan shards every optimizer param over the mesh 'dp' axis, so a
    missing/size-1 axis silently degrades to the replicated update, and
    a bucket whose total element count does not divide ``dp`` falls back
    to zero-padded sharding (correct, but the pad is wasted collective
    bytes — ``zero_pad_bytes`` counts it at run time).  The check
    reproduces the executor's real bucketing, so ragged params absorbed
    by co-bucketed neighbours do not warn."""
    if not gi.zero:
        return
    from ..optim.optimizer import OptimizerOp
    from ..parallel.zero import ZERO_AXIS
    opt_ops = [n for n in gi.topo if isinstance(n, OptimizerOp)]
    if not opt_ops:
        return
    dp = None
    if gi.mesh is not None and ZERO_AXIS in gi.mesh.axis_names:
        dp = int(gi.mesh.shape[ZERO_AXIS])
    if not dp or dp < 2:
        have = sorted(gi.mesh.axis_names) if gi.mesh is not None else None
        yield Diagnostic(
            "zero-sharding", "warn",
            f"zero={gi.zero} requested but the executor mesh "
            f"{'has axes ' + str(have) if have else 'is absent'} — no "
            f"'{ZERO_AXIS}' axis of size >= 2 to shard the weight update "
            f"over, so the update runs fully REPLICATED (no memory win)",
            opt_ops[0])
        return
    from ..parallel.zero import build_plan, ineligible_reason
    for op in opt_ops:
        # the executor's eligibility filter (_build_zero_plans), via the
        # SHARED predicate zero.ineligible_reason: an ineligible param
        # makes its WHOLE optimizer fall back to the replicated update —
        # zero= silently has no effect there, which is exactly what this
        # rule exists to surface (and building a plan for it would warn
        # about pad bytes of collectives that will never exist)
        ineligible = None
        for p in op.params:
            dt = getattr(p, "dtype", None) or gi.shapes.dtype(p)
            why = ineligible_reason(p, dt)
            if why is not None:
                ineligible = (p, why)
                break
        if ineligible:
            p, why = ineligible
            yield Diagnostic(
                "zero-sharding", "warn",
                f"zero={gi.zero}: optimizer '{op.name}' stays on the "
                f"fully REPLICATED update path because parameter "
                f"'{p.name}' {why} — no ZeRO memory win for its params "
                f"or moments", p)
            continue
        items, by_key = [], {}
        for i, p in enumerate(op.params):
            shape = p.shape if getattr(p, "shape", None) is not None \
                else gi.shape(p)
            if shape is None:
                continue
            dt = getattr(p, "dtype", None) or gi.shapes.dtype(p) \
                or np.float32
            key = f"p{i}"
            items.append((key, tuple(shape), np.dtype(dt).name))
            by_key[key] = p
        if not items:
            continue
        # reproduce the executor's ACTUAL bucketing (same order, same
        # byte cap, per-param for LAMB): padding is decided per BUCKET,
        # so a ragged param co-bucketed with others often shards with
        # zero waste — warning on numel % dp alone would spam biases and
        # layernorms about a non-problem
        plan = build_plan(items, dp, gi.zero,
                          per_param=bool(getattr(op.optimizer, "lamb",
                                                 False)))
        for b in plan.buckets:
            if not b.pad:
                continue
            # pad > 0 guarantees at least one member is ragged: a bucket
            # of all-divisible params would total a dp multiple itself
            ragged = [k for k, shape in zip(b.param_keys, b.shapes)
                      if (int(np.prod(shape, dtype=np.int64))
                          if shape else 1) % dp]
            names = [by_key[k].name for k in ragged]
            pad_bytes = b.pad * np.dtype(b.dtype).itemsize
            yield Diagnostic(
                "zero-sharding", "warn",
                f"ZeRO bucket of {len(b.param_keys)} param(s) "
                f"({', '.join(repr(n) for n in names[:4])}"
                f"{', ...' if len(names) > 4 else ''} not divisible by "
                f"the '{ZERO_AXIS}' axis) totals {b.numel} elements — "
                f"zero-padded to {b.padded} ({b.pad} wasted elements, "
                f"{pad_bytes} B per collective; see zero_pad_bytes)",
                by_key[ragged[0]])


@rule("remat-policy")
def _r_remat(gi):
    """Selective-remat policy preconditions (``parallel/remat.py``,
    ISSUE 13): an unknown policy name is an error (for direct
    ``ht.lint(remat=...)`` callers — ``Executor(remat=...)`` fails fast
    at construction like ``pipeline=``), a policy on a graph with no
    recomputable segment (forward-only, or no matmul-family anchors to
    segment at) is a silent no-op worth a warning, and ``'auto'`` with
    no resolvable HBM budget remats EVERY segment — the memory-
    conservative default, but almost never what the user budgeted for."""
    from ..parallel import remat as remat_mod
    pol = gi.remat
    if pol in (None, False, 0, "off"):
        return
    if pol is True:
        pol = "dots"
    anchor_node = next((n for n in gi.topo
                        if remat_mod._is_anchor(n)), None)
    site_node = anchor_node or next(
        (n for n in gi.topo
         if not isinstance(n, (PlaceholderOp, GradientOp))), None)
    if pol not in remat_mod.POLICIES:
        yield Diagnostic(
            "remat-policy", "error",
            f"unknown remat policy {pol!r} — expected one of "
            f"{'|'.join(remat_mod.POLICIES)} (True == 'dots')",
            site_node)
        return
    grads = [n for n in gi.topo if isinstance(n, GradientOp)]
    if not grads:
        yield Diagnostic(
            "remat-policy", "warn",
            f"remat={pol!r} on a forward-only graph — nothing "
            f"differentiates, so there is no backward pass to "
            f"rematerialize into (remat is a silent no-op here)",
            site_node)
    elif anchor_node is None:
        yield Diagnostic(
            "remat-policy", "warn",
            f"remat={pol!r} on a graph with NO recomputable segment — "
            f"no matmul-family/attention anchors to segment at, so the "
            f"policy frees (almost) nothing and 'full'/'auto' build an "
            f"empty plan", site_node)
    if pol == "auto":
        budget, _src = remat_mod.resolve_budget()
        if budget is None:
            yield Diagnostic(
                "remat-policy", "warn",
                "remat='auto' with no resolvable HBM budget — "
                "HETU_HBM_BUDGET_MB is unset and this backend reports "
                "no memory limit, so auto remats EVERY segment (acts "
                "like 'full'); set HETU_HBM_BUDGET_MB to get the "
                "budget-fitted plan", site_node)


#: op types whose semantics exist only for TRAINING — a serving fetch set
#: reaching them is either outright wrong (optimizer, gradient: the whole
#: point of a compile-once inference program is that these subgraphs are
#: never built) or a smell (dropout: inert under training=False, but its
#: presence usually means the fetch set was lifted straight off a
#: training head instead of the model's inference output)
_TRAIN_ONLY_ERRORS = {"OptimizerUpdate"}
_TRAIN_ONLY_WARNS = {"Dropout", "Dropout2d"}


@rule("train-only-op-in-serving")
def _r_train_only_serving(gi):
    """Serving graphs must never construct grad/optimizer subgraphs
    (``hetu_tpu.serving.InferenceExecutor`` compiles fetch subgraphs
    without a backward pass; an optimizer or gradient fetch would
    silently train — or crash — inside the request path)."""
    if not gi.serving:
        return
    for node in gi.topo:
        if isinstance(node, GradientOp):
            yield Diagnostic(
                "train-only-op-in-serving", "error",
                f"gradient node '{node.name}' (w.r.t. "
                f"'{getattr(node.wrt, 'name', node.wrt)}') is reachable "
                f"from a serving fetch set — serving must never build a "
                f"backward pass; fetch the model's inference output "
                f"instead", node)
        elif node.op_type in _TRAIN_ONLY_ERRORS:
            yield Diagnostic(
                "train-only-op-in-serving", "error",
                f"{node.op_type} '{node.name}' is reachable from a "
                f"serving fetch set — a weight update inside the request "
                f"path would train the serving replica; drop the "
                f"optimizer from the serving fetches", node)
        elif node.op_type in _TRAIN_ONLY_WARNS:
            yield Diagnostic(
                "train-only-op-in-serving", "warn",
                f"{node.op_type} '{node.name}' is reachable from a "
                f"serving fetch set — it lowers to identity under "
                f"training=False, but a dropout in an inference graph "
                f"usually means the fetch set came from a training head",
                node)


#: op types whose lowering cannot run under INCREMENTAL decode — they
#: consume the full sequence axis in one shot (the decode step sees one
#: token; a full-sequence attention in the step graph would attend over
#: whatever single token it was handed and silently emit garbage) — with
#: the incremental replacement to name in the diagnostic
_DECODE_INCOMPATIBLE_SEQ = {
    "ScaledDotProductAttention",
    "ScaledDotProductAttentionMasked",
    "ScaledDotProductAttentionBias",
    "ScaledDotProductAttentionMaskedBias",
    "ScaledDotProductAttentionVarlen",
    "ScaledDotProductAttentionPacked",
    "RingAttention",
    "RingAttentionMasked",
    "UlyssesAttention",
    "UlyssesAttentionMasked",
}
#: op types that carry BATCH-coupled running state — under continuous
#: batching the batch composition changes every token, so their
#: statistics would depend on which sequences happen to share the step
#: (breaking the bitwise-stability guarantee: same sequence, different
#: batch mates, different tokens)
_DECODE_INCOMPATIBLE_STATE = {"BatchNorm"}


@rule("decode-incompatible-op")
def _r_decode_incompatible(gi):
    """An incremental-decode step graph
    (``InferenceExecutor(decode=True)``) must be runnable one token at a
    time: full-sequence attention ops and batch-statistics ops are
    rejected at construction with their creation site, naming the
    incremental replacement."""
    if not gi.decode:
        return
    for node in gi.topo:
        if node.op_type in _DECODE_INCOMPATIBLE_SEQ:
            yield Diagnostic(
                "decode-incompatible-op", "error",
                f"{node.op_type} '{node.name}' consumes the full "
                f"sequence axis in one shot — an incremental decode "
                f"step sees ONE token per call and would silently "
                f"attend over nothing; use sdpa_decode_op over a KV "
                f"cache maintained by kv_cache_append_op instead", node)
        elif node.op_type in _DECODE_INCOMPATIBLE_STATE:
            yield Diagnostic(
                "decode-incompatible-op", "error",
                f"{node.op_type} '{node.name}' computes batch-coupled "
                f"statistics — under continuous batching the batch "
                f"composition changes every token, so its output would "
                f"depend on which sequences share the step (the "
                f"bitwise-stability guarantee cannot hold); use "
                f"LayerNorm (per-row statistics) instead", node)


# ----------------------------------------------------------------- entry

def lint(fetches, feeds=None, mesh=None, pipeline=None, training=True,
         num_microbatches=None, rules=None, zero=0, serving=False,
         remat="off", plan=None, decode=False):
    """Statically verify a fetch subgraph; returns a :class:`LintReport`.

    ``feeds``: example values (or bare shapes) for placeholders declared
    without a static shape, e.g. ``ht.lint([loss], feeds={x: (32, 784)})``.
    ``mesh`` / ``pipeline`` / ``num_microbatches`` / ``zero`` /
    ``remat``: the executor configuration the graph will compile under
    (enables the mesh-axis, pipeline-stage, zero-sharding and
    remat-policy rules, and keeps schedule-sensitive lowering on the
    same path the executor uses).
    ``plan``: the auto-parallel :class:`ParallelPlan` the executor will
    compile under (``Executor(plan=...)``) — enables the plan-coverage
    rule and escalates plan-managed mesh-axis findings to errors.
    ``serving=True``: lint the fetches as a SERVING set (enables the
    train-only-op-in-serving rule — what
    ``InferenceExecutor(validate=...)`` runs; pair with
    ``training=False``).
    ``decode=True``: the fetch set is an incremental-decode STEP
    (``InferenceExecutor(decode=True)``) — enables the
    decode-incompatible-op rule.
    ``rules``: optional iterable of rule names to run (default: all
    registered rules).
    """
    if isinstance(fetches, Op):
        fetches = [fetches]
    shapes = infer_graph(fetches, feeds=feeds, mesh=mesh, training=training,
                         num_microbatches=num_microbatches,
                         pipeline=pipeline)
    feed_values = {}
    if feeds:
        by_name = {n.name: n for n in shapes.topo
                   if isinstance(n, PlaceholderOp)}
        for k, v in feeds.items():
            node = by_name.get(k) if isinstance(k, str) else k
            if node is not None and hasattr(v, "dtype") \
                    and hasattr(v, "shape"):
                feed_values[node] = v
    gi = GraphInfo(shapes, _normalize_feeds(feeds, shapes.topo),
                   mesh=mesh, pipeline=pipeline, feed_values=feed_values,
                   zero=zero, serving=serving, remat=remat, plan=plan,
                   decode=decode)
    diags = []
    selected = RULES if rules is None else {
        name: RULES[name] for name in rules}
    for name, fn in selected.items():
        try:
            diags.extend(fn(gi))
        except Exception as e:
            # one rule crashing must not take down the report (the
            # analyzer can never be the thing that breaks a graph)
            diags.append(Diagnostic(
                name, "warn",
                f"lint rule crashed: {type(e).__name__}: {e} — "
                f"report it; the rule was skipped", internal=True))
    return LintReport(shapes, diags)
