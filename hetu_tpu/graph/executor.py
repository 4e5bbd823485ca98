"""Executor: compiles fetch subgraphs into single jitted XLA programs.

TPU-native redesign of the reference execution engine
(``python/hetu/gpu_ops/executor.py``: HetuConfig:134, Executor:365,
SubExecutor:570).  The reference interprets the graph op-by-op around CUDA
streams/events with a hand-rolled memory-reuse plan (SURVEY.md §3.1); here a
SubExecutor lowers its whole topo into ONE pure function

    step(params, states, opt_states, feeds, key, lrs) -> (fetches, new_...)

and ``jax.jit``-compiles it with buffer donation, so XLA does fusion, buffer
assignment/reuse, and async scheduling — the roles of the reference's
5-stream overlap machinery, chunk allocator and memory planner.  Shape
changes retrace automatically (jit cache keyed on shapes, replacing
``SubExecutor.run``'s realloc path, executor.py:971-975).

Gradients (GradientOp markers) resolve to one ``jax.value_and_grad`` over the
lowered forward; optimizer updates apply inside the same jitted step, so
forward+backward+update is a single XLA computation per training step.

Distribution: with a ``dist_strategy`` (e.g. DataParallel) the executor holds
a ``jax.sharding.Mesh``; feeds are device_put with the strategy's
PartitionSpec and jit emits SPMD with XLA collectives over ICI — the TPU
equivalent of the reference's NCCL allreduce insertion
(``optimizer.py:145-164``).
"""
from __future__ import annotations

import os
import pickle
import time as _time
import warnings

import numpy as np

from .node import Op, PlaceholderOp, LowerCtx, topo_sort
from .gradients import GradientOp
from ..ndarray import NDArray, wrap_device
from .. import metrics as _metrics
from ..obs.trace import TRACER as _TRACE
from ..obs.trace import annotate as _annotate
from ..obs.trace import annotate_end as _annotate_end
from ..obs.trace import span as _span


def _dev_roundtrip(h):
    """Feed-pipeline-thread unit of work for a device-cache step: the
    batched pending-push + miss-pull round trip (``_DevLookup.roundtrip``
    — store calls only, no cache state).  Traced as a ``ps.miss_pull``
    span on the feed-pipeline track, with a flow arrow opened here and
    closed inside the step span that consumes the rows."""
    if not _TRACE.on:
        return h.roundtrip()
    t0 = _time.perf_counter_ns()
    rows = h.roundtrip()
    _TRACE.complete("ps.miss_pull", t0, _time.perf_counter_ns(), cat="ps",
                    args={"miss_rows": 0 if rows is None
                          else int(rows.shape[0])})
    h.flow_id = _TRACE.flow_begin("emb.miss_fill", cat="ps")
    return rows


class _ZeroView:
    """``Executor.var_values`` stand-in for a stage-3 ZeRO parameter: the
    master bytes live dp-SHARDED inside a bucket slab
    (``Executor._zero_slabs``), so no full copy of the parameter exists
    between steps.  ``materialize()`` reconstructs the full host array
    (checkpointing, eval subgraphs, ``return_tensor_values``)."""

    __slots__ = ("ex", "node", "bucket")

    def __init__(self, ex, node, bucket):
        self.ex = ex
        self.node = node
        self.bucket = bucket

    @property
    def _index(self):
        return self.bucket.param_keys.index(self.ex._k(self.node))

    @property
    def shape(self):
        return self.bucket.shapes[self._index]

    @property
    def dtype(self):
        return np.dtype(self.bucket.dtype)

    def materialize(self):
        """Full host-side value (gathers the slab; multiprocess-safe).
        The slab fetch is memoized per step (``Executor._slab_host``):
        materializing k co-bucketed params costs ONE gather, not k."""
        from ..parallel.zero import host_unpack_slab
        slab = self.ex._slab_host(self.bucket)
        return host_unpack_slab(slab, self.bucket)[self.ex._k(self.node)]

    def __repr__(self):
        return (f"<ZeroView of '{self.node.name}' shape={self.shape} "
                f"in slab {self.bucket.key}>")


#: where jax's persistent compilation cache lives when the environment
#: names no directory: a FIXED path in the checkout (the path is part of
#: the cache key, so a temp name, pid or timestamp would never hit)
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

#: programs that compile faster than this are not written to the cache.
#: The cache the chip tool hands over is capped (``JAX_COMPILATION_CACHE_
#: MAX_SIZE``, 192 MiB) and evicts least-recently-used entries; with every
#: program cached, one ``chip_smoke.py`` pass wrote more than the cap and
#: the entry worth most — the BERT-base step, 53 MiB for 75 s of compile —
#: was always the one evicted, so nothing ever hit; the 22 small decode
#: programs of that pass are what overflowed it.  What the rule costs the
#: benchmark's decode programs was first READ in PR 39 (``obs/compile_
#: log.py``; ``PERF.md`` section 5, "where a set-up goes"): gpt2-medium's
#: one-token program compiles in 3.4-4.4 s and its chunk programs in
#: 4.3-18 s, so three of the cell's thirteen fall under the threshold in
#: a first process and — 8-10 s each there — over it in the next, and a
#: program that IS kept is read back in about 2 s.  A miss that is not stored is in
#: ``compile_counts()`` as ``<owner>:unstored`` / ``unstored_us``.
COMPILE_CACHE_MIN_COMPILE_SECS = 5.0

_compile_cache_configured = False


def configure_compile_cache():
    """The ONE compile-cache rule (``Executor``, ``InferenceExecutor`` —
    hence ``DecodeEngine`` — and ``chip_smoke.py`` call it):
    with ``JAX_COMPILATION_CACHE_DIR`` set, jax already has its directory
    and none is set in code; otherwise the cache is
    :data:`COMPILE_CACHE_DIR`.  A CPU process keeps jax's default (no
    persistent cache).  Either way the two ``jax_persistent_cache_min_*``
    thresholds are set: any size, but only programs that took
    :data:`COMPILE_CACHE_MIN_COMPILE_SECS` to compile.  Jitting with
    canonical input keys makes a rebuilt executor's HLO byte-identical,
    so a restarted process reads its step back instead of compiling."""
    global _compile_cache_configured
    if _compile_cache_configured:
        return
    import jax
    _compile_cache_configured = True
    # every program the process compiles from here on leaves a record
    # (obs/compile_log.py) — a CPU process's too
    from ..obs import compile_log
    compile_log.install()
    if jax.default_backend() == "cpu":
        # XLA:CPU compiles in seconds, and its loader logs an error (a
        # target-feature mismatch with itself) for every entry read back
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      COMPILE_CACHE_MIN_COMPILE_SECS)


def _filter_spec(mesh, spec):
    """Drop axes the mesh doesn't have (e.g. 'ep' under pure DP)."""
    from jax.sharding import PartitionSpec
    return PartitionSpec(*[a if a in mesh.axis_names else None for a in spec])


#: resolved once on first step (a per-step `from .. import chaos` plus
#: attribute walk is measurable at microsecond step rates); the injector
#: itself can still be (un)installed mid-run — only the module ref is
#: cached, active() is consulted every training step
_chaos_active_fn = None


def _chaos_active():
    global _chaos_active_fn
    if _chaos_active_fn is None:
        from .. import chaos
        _chaos_active_fn = chaos.active
    return _chaos_active_fn()


def _sync_outs(outs):
    """Wait until every step output is computed — THE sync helper
    (``HetuProfiler._sync``, the autoparallel probes and
    the async in-flight window all come here).  Training steps chain
    through the params, so waiting on the last outputs waits on every
    dispatched step.  Free on an already-complete array."""
    import jax
    jax.block_until_ready([o.jax() if hasattr(o, "jax") else o
                           for o in outs or () if o is not None])


def lower_forward(topo, ctx, resolve_leaf, mesh=None, skip=(),
                  remat_segments=None, keep=()):
    """Lower every value-producing node of ``topo`` into one traced
    environment ``{node: value}``.

    The forward lowering loop, split out of the training SubExecutor's
    session/run machinery so the serving path
    (:class:`hetu_tpu.serving.InferenceExecutor`) shares ONE definition of
    "evaluate this graph" without carrying the train-side state threading:
    placeholders resolve through ``resolve_leaf(node)``, gradient markers
    and ``skip`` nodes (optimizer updates, anything train-only) are left
    out, and sharding annotations become ``with_sharding_constraint``
    under ``mesh``.  State written during forward (BN running stats)
    lands in ``ctx.state_updates`` — the training executor commits it,
    serving discards it (read-only replicas).

    ``remat_segments`` (ISSUE 13, the ``remat='full'|'auto'`` policies):
    node lists — contiguous runs in topo order, planned by
    ``parallel/remat.py`` — that each lower inside a NESTED
    ``jax.checkpoint``, so only their boundary values (consumed outside
    the segment, or in ``keep``) survive as backward residuals; the
    interiors recompute during the backward pass.  Interior values are
    NOT in the returned env — callers needing a value must name it in
    ``keep``."""
    import jax

    def constrain(node, v):
        if node.sharding is not None and mesh is not None \
                and not isinstance(node, PlaceholderOp):
            from jax.sharding import NamedSharding
            v = jax.lax.with_sharding_constraint(
                v, NamedSharding(mesh, _filter_spec(mesh, node.sharding)))
        return v

    env = {}
    if not remat_segments:
        for node in topo:
            if isinstance(node, GradientOp) or node in skip:
                continue
            if isinstance(node, PlaceholderOp):
                env[node] = resolve_leaf(node)
            elif getattr(node, "scope", None) is None:
                env[node] = constrain(
                    node, node.lower(ctx, *[env[i] for i in node.inputs]))
            else:
                with jax.named_scope(node.scope):
                    env[node] = constrain(
                        node, node.lower(ctx, *[env[i] for i in node.inputs]))
        return env

    # segmented path: topo_sort guarantees inputs precede consumers, and
    # segments are contiguous runs of lowerable nodes, so every external
    # input of a segment is already in env when its first node arrives
    from ..parallel.remat import checkpoint_segment
    lowerable = [n for n in topo
                 if not (isinstance(n, GradientOp) or n in skip)]
    consumers = {}
    for n in lowerable:
        for i in n.inputs:
            consumers.setdefault(i, []).append(n)
    keep = set(keep)
    seg_of = {}
    for si, seg in enumerate(remat_segments):
        for n in seg:
            seg_of[n] = si
    done = set()
    for node in lowerable:
        if node in done:
            continue
        if isinstance(node, PlaceholderOp):
            env[node] = resolve_leaf(node)
            done.add(node)
            continue
        si = seg_of.get(node)
        if si is None:
            env[node] = constrain(
                node, node.lower(ctx, *[env[i] for i in node.inputs]))
            done.add(node)
            continue
        seg = remat_segments[si]
        segset = set(seg)
        ext = []
        for n in seg:
            for i in n.inputs:
                if isinstance(i, PlaceholderOp) and i not in env:
                    # a placeholder interleaved in topo order INSIDE the
                    # segment's span: leaf resolution is order-free
                    env[i] = resolve_leaf(i)
                    done.add(i)
                if i not in segset and i not in ext:
                    ext.append(i)
        outs = [n for n in seg
                if n in keep or not consumers.get(n)
                or any(c not in segset for c in consumers[n])]

        def seg_fn(ins, _seg=seg, _ext=ext, _outs=outs):
            e = dict(zip(_ext, ins))
            for n in _seg:
                e[n] = constrain(n, n.lower(ctx, *[e[i] for i in n.inputs]))
            return [e[o] for o in _outs]

        vals = checkpoint_segment(seg_fn)([env[i] for i in ext])
        for o, v in zip(outs, vals):
            env[o] = v
        done.update(seg)
    return env


class SubExecutor:
    """One fetch-list → one jitted step function."""

    def __init__(self, name, fetches, executor):
        self.name = name
        self.fetches = list(fetches)
        self.ex = executor
        self.topo = topo_sort([f for f in self.fetches if f is not None])

        from ..optim.optimizer import OptimizerOp
        self.opt_ops = [n for n in self.topo if isinstance(n, OptimizerOp)]
        self.grad_ops = [n for n in self.topo if isinstance(n, GradientOp)]
        # Training mode iff the subgraph differentiates (optimizer or raw
        # gradient fetches) or is literally the 'train' subgraph; substring
        # matching would misfire on names like 'pretrain_eval'.
        self.training = bool(self.opt_ops or self.grad_ops) or name == "train"

        # PS-backed embedding leaves: their per-step value is pulled from the
        # host store before the step; their gradient is pushed back after
        # (reference EmbeddingLookUp PS path, SURVEY.md §3.3)
        self.ps_nodes = [n for n in self.topo
                         if getattr(n, "is_ps", False)]
        # node -> (ids, Future[rows]): lookahead pulls in flight
        self._prefetched = {}
        self._prefetch_pool = None
        self.feed_nodes = [n for n in self.topo
                           if isinstance(n, PlaceholderOp) and not n.is_variable
                           and not getattr(n, "is_ps", False)]
        self.trainable_vars = sorted(
            {g.wrt for g in self.grad_ops}, key=lambda n: n.id)
        for v in self.trainable_vars:
            if not (isinstance(v, PlaceholderOp) and v.is_variable):
                raise ValueError(f"gradient w.r.t. non-variable {v} unsupported")
        self.state_vars = [n for n in self.topo
                           if isinstance(n, PlaceholderOp) and n.is_variable
                           and n not in self.trainable_vars]
        losses = {g.loss for g in self.grad_ops}
        if len(losses) > 1:
            raise ValueError("multiple distinct losses in one subgraph")
        self.loss_node = next(iter(losses)) if losses else None
        # graphs with a PipelineBlockOp pipeline via shard_map inside the
        # block; executor-level microbatching would double-split the batch
        self.has_pipeline_block = any(
            n.op_type == "PipelineBlock" for n in self.topo)
        if self.ex.pipeline and not self.has_pipeline_block and self.grad_ops:
            # loud, not silent: the schedule NAME promises stage overlap,
            # but without a PipelineBlock the executor can only run scanned
            # gradient accumulation (same numerics for mean-reduced losses;
            # 1F1B/hetpipe additionally remat each microbatch's forward).
            # The reference auto-partitions at recv/loss pivots
            # (pipeline_subexecutor.py:29-81); here stage functions must be
            # shape-homogeneous, so partitioning is the caller's call.
            import warnings
            warnings.warn(
                f"pipeline={self.ex.pipeline!r} on a graph with no "
                f"PipelineBlock: running scanned gradient accumulation "
                f"over {self.ex.num_microbatches} microbatches with NO "
                f"stage overlap — wrap the repeated layer chain in "
                f"ht.pipeline_block(...) to get the scheduled pipeline",
                UserWarning, stacklevel=4)
        # which fetches are batch-derived (transitively consume a fed
        # placeholder)? drives how microbatched aux outputs recombine
        feed_set = set(self.feed_nodes)
        deps = {}
        for node in self.topo:
            deps[node] = node in feed_set or any(
                deps.get(i, False) for i in node.inputs)
        self.fetch_depends_feed = [f is not None and deps.get(f, False)
                                   for f in self.fetches]
        self._jit = None
        # -- dispatch-path precomputation (graph/run_plan.py): everything
        # below depends only on graph structure + the executor's static
        # config, so it is resolved once here instead of per step --------
        self._plan_cache = None     # schema -> RunPlan (built lazily)
        self._feed_pool = None      # feed-pipeline device_put worker
        self._empty_lrs_dev = None  # committed (0,) lrs for all-traced
        ex = executor
        # traced lr: schedules that are pure functions of the step index
        # evaluate INSIDE the jitted step; only data-dependent ones stay
        # per-step host inputs (the `lrs` argument shrinks accordingly)
        self._opt_items = [(ex._k(op), op) for op in self.opt_ops]
        self._derive_lr_state()
        # state packing / writeback pairs: stage-3 ZeRO membership and
        # _zero_covered are fixed at Executor construction (before any
        # SubExecutor exists), so the per-step slab/view/plain split is
        # static
        self._zero3 = [
            (op, ex._zero_plans[op]) for op in self.opt_ops
            if ex._zero_plans.get(op) is not None
            and ex._zero_plans[op].stage >= 3]
        slab_nodes = set()
        self._slab_keys = []
        for op, plan in self._zero3:
            self._slab_keys += [b.key for b in plan.buckets]
            slab_nodes.update(op.params)
        covered = ex._zero_covered
        self._t_plain = [(ex._k(n), n) for n in self.trainable_vars
                         if n not in slab_nodes and n not in covered]
        self._t_view = [(ex._k(n), n) for n in self.trainable_vars
                        if n not in slab_nodes and n in covered]
        self._s_plain = [(ex._k(n), n) for n in self.state_vars
                         if n not in covered]
        self._s_view = [(ex._k(n), n) for n in self.state_vars
                        if n in covered]
        self._writeback_pairs = [(n, ex._k(n)) for n in self.trainable_vars
                                 if n not in covered]
        self._state_pairs = [(n, ex._k(n)) for n in self.state_vars]
        self._ps_items = [(n, ex._k(n), n.ids_node, ex._k(n.ids_node))
                          for n in self.ps_nodes]
        # device-resident HET tables (DistCacheTable(device=True)) take
        # the ISSUE 11 path: slot-plan host-side, batched miss pull on
        # the feed-pipeline thread (overlapping the dense forward),
        # slot-indexed on-device gather in the step, grads back through
        # the device scatter-add kernel.  Host-mode tables keep the
        # pull-rows-as-leaf path below unchanged.
        self._ps_dev_items = [t for t in self._ps_items
                              if getattr(t[0], "device_mode", False)]
        self._ps_host_items = [t for t in self._ps_items
                               if not getattr(t[0], "device_mode", False)]
        #: node -> in-flight _DevLookup handle (consumed by _ps_post_step
        #: for the summed-grad commit)
        self._dev_live = {}
        self._feed_node_set = frozenset(self.feed_nodes)
        self._dev_node_set = frozenset(t[0] for t in self._ps_dev_items)
        # PS rows are pulled full-batch; executor-level microbatching
        # splits feeds — statically incompatible (raised per run)
        self._ps_microbatch_clash = bool(
            self.ps_nodes and self.grad_ops and ex.pipeline
            and (ex.num_microbatches or 1) > 1
            and not self.has_pipeline_block)
        # ISSUE 13 selective remat: the segment plan for the
        # 'full'/'auto' policies, priced by the PR 5 cost model — built
        # at construction so Executor.remat_plan() answers before the
        # first run and the step-cache signature hashes the decisions
        from ..parallel import remat as _remat
        self._remat_plan = _remat.plan_for(self)
        self._remat_fingerprint = None if self._remat_plan is None \
            else self._remat_plan.fingerprint()
        if _TRACE.on and ex.remat != "off" and self.grad_ops:
            # build-time provenance in any exported trace: which policy
            # (and how many segments) this executor's measured steps ran
            # under — one instant at construction, zero hot-path cost
            _TRACE.instant("remat:plan", cat="executor", args={
                "sub": self.name, "policy": ex.remat,
                "segments_rematted": 0 if self._remat_plan is None
                else self._remat_plan.n_remat})

    # -- lowering ---------------------------------------------------------

    def _forward(self, tparams, sparams, feeds, key, remat_segments=None):
        """Evaluate every non-grad node; returns (env, state_updates).

        ``remat_segments`` (the ``remat='full'|'auto'`` training path):
        planned node lists that lower inside nested ``jax.checkpoint``
        scopes — see :func:`lower_forward`.  Only the gradient path
        passes them; eval subgraphs and the profiler's shape trace keep
        the flat lowering (and a complete env)."""
        ctx = LowerCtx(self.training, key, self.ex.mesh,
                       num_microbatches=self.ex.num_microbatches,
                       pipeline=self.ex.pipeline)

        def resolve(node):
            k = self.ex._k(node)
            if k in tparams:
                return tparams[k]
            if k in sparams:
                return sparams[k]
            return feeds[k]

        keep = ()
        if remat_segments:
            keep = [f for f in self.fetches
                    if f is not None and not isinstance(f, GradientOp)
                    and f not in self.opt_ops]
            if self.loss_node is not None:
                keep.append(self.loss_node)
        env = lower_forward(self.topo, ctx, resolve, mesh=self.ex.mesh,
                            skip=self.opt_ops,
                            remat_segments=remat_segments, keep=keep)
        updates = {self.ex._k(n): v for n, v in ctx.state_updates.items()}
        return env, updates

    def _zero3_plans(self):
        """[(opt_op, plan)] for this subgraph's stage-3 ZeRO optimizers —
        the ones whose params enter/leave the step as bucket slabs.
        Static after construction (precomputed in ``__init__``)."""
        return self._zero3

    def _pack_state(self, materialize=False):
        """Assemble the step's ``(tparams, sparams)`` inputs.

        Stage-3 ZeRO params ride as their bucket SLABS (keyed by bucket
        key) when their optimizer runs in this subgraph; a covered param
        used here *without* its optimizer (an eval subgraph sharing the
        weights) is materialized to a full replicated value instead.
        ``materialize=True`` forces full values everywhere (the
        profiler's forward-only shape evaluation).

        The slab/view/plain split is precomputed (``__init__``) — the
        per-step work is two dict builds over prebound (key, node)
        pairs, not a per-variable isinstance walk (the dispatch-gap
        discipline, graph/run_plan.py)."""
        ex = self.ex
        if materialize:
            tparams = {ex._k(n): ex._var_value(n)
                       for n in self.trainable_vars}
            sparams = {ex._k(n): ex._var_value(n) for n in self.state_vars}
            return tparams, sparams
        vv = ex.var_values
        tparams = {k: vv[n] for k, n in self._t_plain}
        for k, n in self._t_view:
            tparams[k] = ex._var_value(n)
        sparams = {k: vv[n] for k, n in self._s_plain}
        for k, n in self._s_view:
            sparams[k] = ex._var_value(n)
        for bk in self._slab_keys:
            tparams[bk] = ex._zero_slabs[bk]
        return tparams, sparams

    def _build_step(self):
        import jax

        fetch_nodes = self.fetches

        ps_keys = [self.ex._k(n) for n in self.ps_nodes]
        # device-resident tables: key -> Pallas dispatch knob (the grad
        # scatter-add runs inside the step with the table's own
        # interpret policy)
        dev_keys = {k: n.cache.device_interpret
                    for n, k, _i, _ik in self._ps_dev_items}

        from contextlib import nullcontext

        def _precision_scope():
            prec = self.ex.matmul_precision
            return jax.default_matmul_precision(prec) if prec \
                else nullcontext()

        import jax.numpy as jnp

        def _cast_tree(tree, dt, src=None):
            src_dt = jnp.dtype(src) if src else jnp.float32
            def cast(x):
                if hasattr(x, "dtype") and x.dtype == src_dt:
                    return x.astype(dt)
                return x
            return jax.tree.map(cast, tree)

        # lr resolution: traced schedules evaluate inside the step (a pure
        # function of step_idx — zero per-step host work, no retrace since
        # step_idx is a runtime input); data-dependent ones arrive through
        # the (shrunken) host `lrs` input.  _host_lrs builds that array.
        lr_traced = self._lr_traced
        host_slot = {}
        for i, t in enumerate(lr_traced):
            if t is None:
                host_slot[i] = len(host_slot)

        def _resolve_lrs(step_idx, lrs):
            return [lr_traced[i](step_idx) if lr_traced[i] is not None
                    else lrs[host_slot[i]]
                    for i in range(len(lr_traced))]

        def step(tparams, sparams, opt_states, feeds, key, step_idx, lrs):
            with _precision_scope():
                outs, ntp, upd, nos = _step_inner(
                    tparams, sparams, opt_states, feeds, key, step_idx,
                    lrs)
            # the step counter advances ON DEVICE (step_idx + 1 fed back
            # by the executor): converting a fresh np.int32 scalar at
            # every dispatch cost ~2-3us of host time; int32 wraps at
            # 2^31 steps (the x64-canonicalization note below)
            return outs, ntp, upd, nos, step_idx + 1

        def _step_inner(tparams, sparams, opt_states, feeds, key, step_idx,
                        lrs):
            # per-step RNG derivation lives INSIDE the jitted program: an
            # eager host-side fold_in cost ~280us/step of dispatch (30x a
            # raw jit call at small step sizes); here it fuses to nothing.
            # step_idx is a traced scalar, so no per-step retrace.
            key = jax.random.fold_in(key, step_idx)
            cd = self.ex.compute_dtype
            if cd:  # mixed precision: bf16 inside the step, fp32 masters out
                sparams = _cast_tree(sparams, cd)
                feeds = _cast_tree(feeds, cd)
            if self.grad_ops:
                # stage-3 ZeRO: params arrive as dp-sharded bucket slabs;
                # gather them to full shape HERE — at the top of the step,
                # where XLA's async scheduler overlaps the all-gather of
                # step N-1's updated params with step N's early compute
                # (the GC3 overlap discipline; parallel/zero.py docstring)
                model_params = tparams
                zero3 = self._zero3_plans()
                if zero3:
                    from ..parallel import zero as _zero
                    model_params = dict(tparams)
                    for _op, plan in zero3:
                        for b in plan.buckets:
                            slab = model_params.pop(b.key)
                            model_params.update(
                                _zero.gather_full(slab, b, self.ex.mesh))

                # ISSUE 13 policy-graded remat (parallel/remat.py): the
                # segmented policies ('full'/'auto') act INSIDE the
                # lowering — each planned segment lowers in a nested
                # jax.checkpoint so only boundary values survive as
                # backward residuals; the wrap policies ('dots' dots-
                # saveable, 'offload' host-offloaded dots with a counted
                # fallback) wrap the whole loss below
                seg_lists = None
                if self._remat_plan is not None:
                    seg_lists = self._remat_plan.remat_node_lists() or None

                def loss_fn(tp, fd, sp, k):
                    if cd:
                        tp = _cast_tree(tp, cd)
                    env, updates = self._forward(
                        tp, sp, fd, k, remat_segments=seg_lists)
                    aux_vals = [None if f is None or f in self.opt_ops
                                or isinstance(f, GradientOp)
                                else env[f] for f in fetch_nodes]
                    return env[self.loss_node], (aux_vals, updates)

                if self.ex.remat in ("dots", "offload"):
                    # rematerialize the forward in the backward pass:
                    # trades FLOPs (or, offloaded, host transfers) for
                    # activation memory — the TPU-native replacement for
                    # the reference's buffer-reuse memory plan
                    # (memory_pool.py:29)
                    from ..parallel import remat as _remat
                    loss_fn = _remat.wrap_loss(loss_fn, self.ex.remat)

                M = self.ex.num_microbatches or 1
                if self.ex.pipeline and M > 1 and not self.has_pipeline_block:
                    aux_vals, updates, grads = self._microbatched_grads(
                        loss_fn, model_params, sparams, feeds, key, M)
                else:
                    (loss_val, (aux_vals, updates)), grads = \
                        jax.value_and_grad(loss_fn, has_aux=True)(
                            model_params, feeds, sparams, key)
                    del loss_val
                # PS-embedding row-gradients ride the updates side-channel;
                # the executor pushes them into the host store post-step.
                # Device-resident tables segment-sum the per-occurrence
                # grads ON DEVICE first (sort + the Pallas segment-sum
                # kernel keyed by the batch's unique-inverse map) — the
                # host then commits U pre-summed rows instead of running
                # the scipy-CSR pass over the whole batch
                for k in ps_keys:
                    if k in grads:
                        g = grads[k]
                        if k in dev_keys:
                            from ..ops.pallas import emb_cache as _emb
                            g = _emb.emb_scatter_add(
                                g.reshape(-1, g.shape[-1]),
                                feeds["psdev:" + k + ":inv"],
                                interpret=dev_keys[k])
                        updates["psgrad:" + k] = g
                new_tparams = dict(tparams)
                new_opt_states = dict(opt_states)
                lr_vals = _resolve_lrs(step_idx, lrs)
                for i, opt_op in enumerate(self.opt_ops):
                    pk = [self.ex._k(v) for v in opt_op.params]
                    sub_g = {k: grads[k] for k in pk}
                    plan = self.ex._zero_plans.get(opt_op)
                    ok = self.ex._k(opt_op)
                    if plan is None:
                        sub_p = {k: new_tparams[k] for k in pk}
                        upd, new_opt_states[ok] = opt_op.optimizer.apply(
                            sub_p, sub_g, opt_states[ok], lr_vals[i])
                    else:
                        # ZeRO: reduce-scatter the grads, update only this
                        # replica's 1/dp slice of params+moments, gather
                        # the params back (stage 3: leave them sharded)
                        from ..parallel import zero as _zero
                        if plan.stage >= 3:
                            src = {b.key: tparams[b.key]
                                   for b in plan.buckets}
                        else:
                            src = {k: new_tparams[k] for k in pk}
                        upd, new_opt_states[ok] = _zero.apply_sharded(
                            opt_op.optimizer, plan, src, sub_g,
                            opt_states[ok], lr_vals[i], self.ex.mesh)
                    new_tparams.update(upd)
                outs = []
                for f, a in zip(fetch_nodes, aux_vals):
                    if isinstance(f, GradientOp):
                        outs.append(grads[self.ex._k(f.wrt)])
                    else:
                        outs.append(a)
                if cd:  # fetched values & state updates leave in fp32
                    outs = _cast_tree(outs, jnp.float32, src=cd)
                    updates = _cast_tree(updates, jnp.float32, src=cd)
                return outs, new_tparams, updates, new_opt_states
            env, updates = self._forward(
                _cast_tree(tparams, cd) if cd else tparams,
                sparams, feeds, key)
            outs = [None if f is None else env[f] for f in fetch_nodes]
            if cd:
                outs = _cast_tree(outs, jnp.float32, src=cd)
                updates = _cast_tree(updates, jnp.float32, src=cd)
            return outs, tparams, updates, opt_states

        # donate params & optimizer state: lets XLA update weights in place.
        # The jitted step is looked up in the process-wide compiled-step
        # cache first (graph/step_cache.py): a structurally identical
        # rebuild (bench re-run, supervisor restart in-process) reuses the
        # compiled executable instead of retracing.
        self._step_fn = step
        from . import step_cache
        self._jit = step_cache.lookup_or_build(self, step)

    def _microbatched_grads(self, loss_fn, tparams, sparams, feeds, key, M):
        """GPipe-semantics microbatch gradient accumulation.

        Replaces the reference's per-rank microbatch scheduler loops
        (``gpipe_subexecutor.py:79-89``, 1F1B ``pipedream_subexecutor.py``)
        with a ``lax.scan`` over microbatches inside the jitted step; stage-
        level overlap comes from ``pipeline_block``'s shard_map schedule.
        ``pipeline='pipedream'``/'hetpipe' additionally remat the per-
        microbatch forward (1F1B's activation footprint); grads are
        averaged, so the result equals the full-batch gradient for
        mean-reduced losses.  Stateful updates (BN stats) are threaded
        sequentially microbatch→microbatch, matching per-microbatch
        execution in the reference schedulers.
        """
        import jax
        import jax.numpy as jnp

        # Only feeds whose leading dim IS the batch get split; scalars and
        # constant side-inputs (masks, tables) broadcast to every microbatch.
        # Batch size: explicit via Executor(microbatch_feeds=[...]), else the
        # most common leading dim (ties → larger).
        explicit = self.ex._extra_config.get("microbatch_feeds")
        if explicit:
            names = {self.ex._k(n) if isinstance(n, Op) else n
                     for n in explicit}
            cand = [v.shape[0] for k, v in feeds.items()
                    if k in names and v.ndim]
        else:
            cand = [v.shape[0] for v in feeds.values() if v.ndim]
        from collections import Counter
        counts = Counter(cand)
        B = max(counts, key=lambda d: (counts[d], d)) if counts else 0
        if B % M:
            raise ValueError(
                f"batch {B} not divisible into {M} microbatches")
        split = {k: v for k, v in feeds.items()
                 if v.ndim and v.shape[0] == B
                 and (not explicit or k in names)}
        if not split:
            raise ValueError("pipeline microbatching needs at least one "
                             "batch-shaped feed")
        rest = {k: v for k, v in feeds.items() if k not in split}
        feeds_mb = {k: v.reshape((M, B // M) + v.shape[1:])
                    for k, v in split.items()}
        fn = loss_fn
        if self.ex.pipeline in ("pipedream", "hetpipe") \
                and self.ex.remat == "off":
            # 1F1B's per-microbatch activation footprint: full remat BY
            # DEFAULT, routed through the one policy resolver — an
            # explicit Executor(remat=...) policy already shaped loss_fn
            # (wrap or segmented lowering), so pipeline= + remat='dots'
            # COMPOSE instead of double-rematting (ISSUE 13 small fix)
            from ..parallel import remat as _remat
            fn = _remat.wrap_loss(loss_fn, "microbatch")

        grad_fn = jax.value_and_grad(fn, has_aux=True)

        def body(carry, xs):
            fd_mb, i = xs
            acc, sp = carry
            # per-microbatch key: independent dropout masks across the scan
            (_, (aux, updates)), g = grad_fn(
                tparams, {**fd_mb, **rest}, sp, jax.random.fold_in(key, i))
            acc = jax.tree.map(jnp.add, acc, g)
            sp = {**sp, **updates}
            return (acc, sp), aux

        zeros = jax.tree.map(jnp.zeros_like, tparams)
        (acc, sp_final), aux_stack = jax.lax.scan(
            body, (zeros, dict(sparams)), (feeds_mb, jnp.arange(M)))
        grads = jax.tree.map(lambda g: g / M, acc)
        # recombination by fetch kind: batch-derived fetches (transitively
        # consume a fed placeholder) re-concat along the microbatch dim
        # (token-flattened leading dims included); batch-aggregated ones
        # (e.g. per-feature stats) average; feed-independent fetches
        # (weights, constants) are identical per microbatch → last copy
        mb = B // M if M else 0

        def merge_aux(a, dep):
            if a is None:
                return None
            if a.ndim <= 1:
                return jnp.mean(a, 0)
            if dep:
                if mb and a.shape[1] % mb == 0:
                    return a.reshape((-1,) + a.shape[2:])
                return jnp.mean(a, 0)
            return a[-1]

        aux_vals = [merge_aux(a, d) for a, d in
                    zip(aux_stack, self.fetch_depends_feed)]
        # threaded state comes back committed wholesale (unchanged leaves
        # round-trip through the scan with their original values)
        return aux_vals, dict(sp_final), grads

    # -- run --------------------------------------------------------------

    def run(self, feed_dict, convert_to_numpy_ret_vals=False, sync=True):
        # the in-step guard defers a SIGTERM/SIGINT emergency save to the
        # step boundary: mid-step, var_values/opt_states are being swapped
        # and a signal-time save could capture a half-updated state
        ex = self.ex
        if self._lr_objs:
            self._check_lr_objs()
        # telemetry: the step span (HETU_TRACE=1) and the opt-in wall-
        # time histogram share one timed wrapper; both disabled costs
        # two module/attribute reads
        timed = _TRACE.on or _metrics.step_timing
        t0 = _time.perf_counter_ns() if timed else 0
        # captured BEFORE the step increments it: the span's step arg
        # equals the StepTraceAnnotation step_num of the same run
        # (HetuProfiler.trace), and eval subgraphs — which never
        # increment — use the same convention
        step0 = ex._step_counter if timed else 0
        # the step span in the profiler's trace too, while one is being
        # captured: the device's ops and idle gaps then lie under it
        ann = _annotate("step", sub=self.name, step=step0) \
            if timed and _TRACE.on else None
        ex._in_step = True
        try:
            out = self._run_impl(feed_dict, convert_to_numpy_ret_vals,
                                 sync, t0)
        finally:
            ex._in_step = False
        ex._post_step(self.training)
        if timed:
            t1 = _time.perf_counter_ns()
            if _metrics.step_timing:
                _metrics.record_step_time((t1 - t0) / 1e3, self.name)
            tr = _TRACE
            if tr.on:
                # the span covers _post_step too: chaos kills and the
                # re-replication tick fire inside the step that
                # scheduled them.  Inline ring store with the buffer
                # getattr open-coded (hot path: the <=25% tracing-tax
                # gate counts every frame here).
                b = getattr(tr._tl, "buf", None)
                if b is None or b.gen != tr._gen:
                    b = tr._buf()
                i = b.i
                # packed "S" record (see obs/trace.py): no args dict on
                # the hot path — the exporter rebuilds it
                b.items[i % b.cap] = ("S", self.name, t0, t1, step0)
                b.i = i + 1
            _annotate_end(ann)
        return out

    def _derive_lr_state(self):
        """Everything derived from each optimizer's CURRENT lr object:
        the traced-lr closures (constant floats and pure step-indexed
        schedulers evaluate inside the jitted step), the host ``lrs``
        input membership (data-dependent schedules), the baked-constant
        snapshot the per-run mutation check compares against, and the
        ops whose optimizer/scheduler actually OVERRIDES on_step (the
        built-ins are no-ops, not worth a per-step method call each).
        Called from ``__init__`` and again by ``_check_lr_objs`` when a
        reassignment is detected — ONE derivation, so a rebuilt lr
        cannot leave part of this state stale."""
        from ..optim.optimizer import Optimizer, traced_lr_fn
        from ..optim.lr_scheduler import LRScheduler
        self._lr_traced = [traced_lr_fn(op.optimizer)
                           for op in self.opt_ops]
        self._host_lr_ops = [op for op, t in
                             zip(self.opt_ops, self._lr_traced)
                             if t is None]
        # snapshot of every optimizer's lr OBJECT: a mid-training
        # `opt.lr = x` reassignment — new float, new scheduler,
        # scheduler↔float — is detected per run (identity compares on
        # the dispatch hot path) and honored by rebuilding whatever it
        # invalidates: a TRACED lr is baked into the compiled step (full
        # rebuild), and even on the host path a structural change can
        # move the op between the traced/host sets or bring a live
        # ``on_step`` (stale ``_sched_ops``).  Same-type host-path
        # reassignment (float→float under HETU_TRACED_LR=0 — the
        # mutate-every-step workflow) stays free: the host ``lrs`` input
        # re-reads the value anyway.  Mutating a live scheduler's ATTRS
        # in place stays undetected (the lr_scheduler docstring's
        # contract).
        self._lr_objs = [(op.optimizer, op.optimizer.lr)
                         for op in self.opt_ops]
        self._sched_ops = []
        for op in self.opt_ops:
            o = op.optimizer
            # class-level overrides AND instance-assigned hooks
            # (`opt.on_step = fn`) both count — the pre-plan executor
            # dispatched on_step unconditionally every step
            if type(o).on_step is not Optimizer.on_step \
                    or "on_step" in o.__dict__ \
                    or (isinstance(o.lr, LRScheduler)
                        and (type(o.lr).on_step is not LRScheduler.on_step
                             or "on_step" in o.lr.__dict__)):
                self._sched_ops.append(op)

    def _check_lr_objs(self):
        """Honor a mid-training ``optimizer.lr = x`` reassignment (see
        the ``_lr_objs`` note above): a traced lr lives inside
        the compiled step, so the step (and the plans bound to it) is
        rebuilt against the new value — the compiled-step cache hashes
        traced lrs, so a revisited value is a cache hit, a fresh one
        retraces once.  ALL lr state re-derives (``_sched_ops``
        included: the new lr may be a scheduler with a live
        ``on_step``).  Identity-first, then: traced + equal value (a
        re-assigned identical float) changes nothing; host-path + same
        TYPE (float→float, or same scheduler class — ``host_lr`` reads
        the live object every step) just refreshes the snapshot."""
        for i, (opt, old) in enumerate(self._lr_objs):
            lr = opt.lr
            if lr is old:
                continue
            if self._lr_traced[i] is not None:
                if lr != old:       # baked value/schedule changed
                    self._rebuild_lr_state()
                    return
            elif type(lr) is not type(old):     # host path: structural
                self._rebuild_lr_state()
                return
            self._lr_objs[i] = (opt, lr)    # benign: refresh snapshot

    def _rebuild_lr_state(self):
        self._derive_lr_state()
        self._jit = None            # rebuilt on the next _run_impl
        self._plan_cache = None     # plans captured the old jit

    def _host_lrs(self, step):
        """The step's host-side lr input: one float32 per optimizer whose
        schedule is DATA-dependent (everything else is traced inside the
        jitted step from ``step_idx`` — graph/run_plan.py).  The all-
        traced case returns one committed device constant: a fresh numpy
        array would pay an H2D conversion at every dispatch for an input
        the program never reads."""
        if not self._host_lr_ops:
            lrs = self._empty_lrs_dev
            if lrs is None:
                import jax
                lrs = self._empty_lrs_dev = jax.device_put(
                    np.zeros((0,), np.float32))
            return lrs
        return np.asarray([op.optimizer.host_lr(step)
                           for op in self._host_lr_ops], np.float32)

    def _run_impl(self, feed_dict, convert_to_numpy_ret_vals=False,
                  sync=True, t_run0=0):
        if self._jit is None:
            self._build_step()
        if not self._ps_dev_items:
            return self._run_general(feed_dict, convert_to_numpy_ret_vals,
                                     sync, t_run0, None)
        # device-resident PS tables: the batched miss pull is issued on
        # the feed-pipeline thread FIRST, so it overlaps everything the
        # host does before the dispatch (dense feed placement, state
        # packing) and — under async dispatch — the previous step's
        # in-flight device work (the GC3 overlap discipline).  Any
        # failure before the commit settles the in-flight handles so
        # the cache locks release and exactly-once holds.
        dev_pending = self._begin_dev_lookups(feed_dict)
        try:
            return self._run_general(feed_dict, convert_to_numpy_ret_vals,
                                     sync, t_run0, dev_pending)
        except BaseException:
            self._settle_dev_pending(dev_pending)
            raise

    def _run_general(self, feed_dict, convert_to_numpy_ret_vals, sync,
                     t_run0, dev_pending):
        ex = self.ex
        # the cached run plan resolves feed keys, placement closures and
        # the validation verdict ONCE per feed schema (run_plan.py); the
        # per-step residue is this flat replay
        cache = self._plan_cache
        if cache is None:
            from .run_plan import PlanCache
            cache = self._plan_cache = PlanCache(self)
        tr = _TRACE if _TRACE.on else None
        if tr is not None:
            # the lookup window starts at run()'s own stamp when it has
            # one (sub-us skew, one clock read saved on the hot path)
            t_pl = t_run0 or _time.perf_counter_ns()
            ann = _annotate("run_plan.lookup")
        plan = cache.lookup(feed_dict)
        if not convert_to_numpy_ret_vals and plan._fast_eligible:
            fast = plan._fast
            if fast is None:
                fast = plan._fast = plan._make_fast()
            if tr is None:
                return fast(feed_dict, sync)
            # hand the lookup window to the fast lane: it batches ALL
            # three phase spans into one ring write (a separate emit
            # here would double the hot path's buffer walks)
            _annotate_end(ann)
            return fast(feed_dict, sync, t_pl, _time.perf_counter_ns())
        if tr is not None:
            # general path (PS / ZeRO-3 / convert): not the dispatch-gap
            # hot path — the method-call emit is fine here
            tr.complete("run_plan.lookup", t_pl, _time.perf_counter_ns(),
                        cat="executor")
            _annotate_end(ann)
            t_fd = _time.perf_counter_ns()
            ann = _annotate("feeds.place")
        feeds = plan.place_feeds(feed_dict)
        if tr is not None:
            tr.complete("feeds.place", t_fd, _time.perf_counter_ns(),
                        cat="executor")
            _annotate_end(ann)

        if self._ps_items:
            if tr is not None:
                t_ps = _time.perf_counter_ns()
            ps_vals = self._resolve_ps_rows(feed_dict, feeds)
            if tr is not None and self._ps_host_items:
                tr.complete("ps.pull_rows", t_ps,
                            _time.perf_counter_ns(), cat="ps")
            if dev_pending is not None:
                self._finish_dev_lookups(dev_pending, feeds, ps_vals)
            if self._ps_microbatch_clash:
                # only the executor-level microbatch path splits feeds;
                # PS rows are pulled full-batch — mutually exclusive
                raise NotImplementedError(
                    "PS embeddings + executor-level pipeline microbatching "
                    "are mutually exclusive (rows are pulled full-batch)")
        tparams, sparams = self._pack_state()
        if self._ps_items:
            (tparams if self.grad_ops else sparams).update(ps_vals)
        opt_states = {k: ex.opt_states[op] for k, op in self._opt_items}
        lrs = self._host_lrs(ex._step_counter)

        # step_idx rides as int32: without jax_enable_x64 an int64 input
        # is silently canonicalized to int32 anyway, and WITH x64 enabled
        # an int64 would change the traced dtype (and the jit cache key)
        # between configurations — fold_in only needs 32 bits.  It is
        # device-CHAINED: the step returns step_idx+1, fed back next run
        # (a fresh np scalar per dispatch cost ~2-3us; _step_input falls
        # back to host after construction/restore).
        if tr is not None:
            t_jit = _time.perf_counter_ns()
            ann = _annotate("jit.dispatch")
        outs, new_tparams, updates, new_opt_states, new_step = self._jit(
            tparams, sparams, opt_states, feeds, ex.master_key,
            ex._step_input(), lrs)
        if tr is not None:
            tr.complete("jit.dispatch", t_jit, _time.perf_counter_ns(),
                        cat="executor")
            _annotate_end(ann)

        # step N+1's host→device feed copies start NOW, overlapping the
        # in-flight device work (the double-buffered feed pipeline)
        plan.start_feed_prefetch()

        if self._ps_items:
            if tr is not None:
                t_push = _time.perf_counter_ns()
            self._ps_post_step(updates, sync)
            if tr is not None:
                tr.complete("ps.push_boundary", t_push,
                            _time.perf_counter_ns(), cat="ps")
        # stage-3 ZeRO: updated params come back as dp-sharded slabs —
        # they replace the slab store, never a full per-param array
        for opt_op, zplan in self._zero3:
            for b in zplan.buckets:
                ex._zero_slabs[b.key] = new_tparams[b.key]
                ex._slab_fetch_cache.pop(b.key, None)
        # covered params whose optimizer did NOT run here (eval /
        # grad-only subgraphs sharing stage-3 weights) entered as
        # transient materializations; writing those back would DETACH
        # the param from its slab — _writeback_pairs excludes them
        vv = ex.var_values
        for n, k in self._writeback_pairs:
            vv[n] = new_tparams[k]
        if updates:
            for n, k in self._state_pairs:
                if k in updates:
                    vv[n] = updates[k]
        for k, op in self._opt_items:
            ex.opt_states[op] = new_opt_states[k]
        if self.training:
            # host and device counters advance together; eval subgraphs
            # leave both untouched (their new_step is discarded)
            ex._step_counter += 1
            ex._step_dev = new_step
            for op in self._sched_ops:
                op.optimizer.on_step(ex._step_counter)

        if convert_to_numpy_ret_vals:
            if not sync:
                # the numpy conversion IS a sync point: materializing a
                # fetch waits for its step (per-run, not per-fetch)
                from ..metrics import record_run_plan
                record_run_plan("async_sync_points")
            results = [None if v is None else np.asarray(v) for v in outs]
        else:
            results = [None if v is None else wrap_device(v)
                       for v in outs]
            if not sync:
                ex._note_async(outs, new_opt_states)
        return results

    def _resolve_ps_rows(self, feed_dict, feeds):
        """PS pulls: resolve the ids batch host-side, pull rows (through
        the HET cache if configured), feed them as leaf params so jax
        computes their gradient alongside the model's.  A lookahead
        prefetch issued at the end of the PREVIOUS run (reference
        dataloader-lookahead overlap, ParameterServerCommunicate.py:69-77)
        is consumed here when its ids match — the pull then overlapped
        the prior step."""
        from ..data.dataloader import DataloaderOp
        ex = self.ex
        ps_vals = {}
        for node, key, idn, idk in self._ps_host_items:
            if idk in feeds:
                ids = np.asarray(feeds[idk])
            elif idn in feed_dict:
                ids = np.asarray(feed_dict[idn])
            elif isinstance(idn, DataloaderOp):
                ids = np.asarray(idn.get_arr(self.name))
            else:
                raise ValueError(f"cannot resolve ids for PS embedding {node}")
            rows = None
            pre = self._prefetched.pop(node, None)
            if pre is not None:
                pre_ids, fut = pre
                # compare ids BEFORE joining: a mismatched prefetch would
                # otherwise cost a full pull wait just to be discarded
                if np.array_equal(pre_ids, np.asarray(ids, np.int64)):
                    rows = fut.result()
                    node._last_ids = pre_ids
            if rows is None:
                rows = node.pull(ids)
            ps_vals[key] = ex._place_feed(node, rows)
        return ps_vals

    def _ensure_feed_pool(self):
        """The single feed-pipeline worker, shared by the dataloader
        H2D double-buffer (run_plan.start_feed_prefetch) and the
        device-cache miss pull — ONE bootstrap so the two paths can
        never build differently-configured pools."""
        pool = self._feed_pool
        if pool is None:
            import concurrent.futures
            pool = self._feed_pool = \
                concurrent.futures.ThreadPoolExecutor(
                    max_workers=1,
                    thread_name_prefix=f"feed-pipeline-{self.name}")
        return pool

    # -- device-resident PS tables (ISSUE 11) -----------------------------
    def _begin_dev_lookups(self, feed_dict):
        """Phase 1 of the device-cache step: resolve each table's ids
        batch, take the cache plan (``begin_lookup`` — slot plan +
        push-payload copies under the cache lock), and issue the one
        fallible store round trip on the feed-pipeline thread.  The
        pull overlaps the dense feed placement / state packing on this
        thread and, under async dispatch, the previous step's device
        work; ``_finish_dev_lookups`` lands the rows in the slab before
        the gather consumes them."""
        from ..data.dataloader import DataloaderOp
        ex = self.ex
        if ex._multiprocess or ex.bsp != 0:
            raise NotImplementedError(
                "device-resident embedding caches support single-process "
                "BSP training (bsp=0) — ASP/SSP and multi-process meshes "
                "need the host-mode cache (DistCacheTable(device=False))")
        pool = self._ensure_feed_pool()
        pending = []
        try:
            for node, key, idn, idk in self._ps_dev_items:
                if idn in feed_dict:
                    ids = np.asarray(feed_dict[idn], np.int64)
                elif isinstance(idn, DataloaderOp):
                    if idn in self._feed_node_set:
                        # the run plan will CONSUME this loader when it
                        # places the graph's own ids feed later in the
                        # step — PEEK here (get_arr pops the same peeked
                        # batch), or the loader would advance twice per
                        # step and desync ids from rows
                        ids = np.asarray(idn.get_next_arr(self.name),
                                         np.int64)
                    else:
                        # ids feed nothing but this lookup: nobody else
                        # consumes, so consume here (host-path parity)
                        ids = np.asarray(idn.get_arr(self.name), np.int64)
                else:
                    raise ValueError(
                        f"cannot resolve ids for PS embedding {node}")
                h = node.cache.begin_lookup(ids)
                pending.append((node, key, ids, h,
                                pool.submit(_dev_roundtrip, h)))
        except BaseException:
            self._settle_dev_pending(pending)
            raise
        return pending

    def _finish_dev_lookups(self, pending, feeds, ps_vals):
        """Phase 3: join the miss pull, COMMIT the cache plan — host
        bookkeeping plus the EAGER in-place slab fill (a tiny donated
        per-bucket fill program) — then gather the batch's rows from the
        resident slab ON DEVICE and feed them as the node's ordinary
        leaf value: the jitted step is byte-identical to host mode
        except for the grad scatter-add, and hit rows never cross the
        host boundary (host mode materialized + H2D-copied every row,
        every step).  The unique-inverse map rides along for the in-step
        grad segment-sum."""
        import jax
        from ..ops.pallas import emb_cache as _emb
        tr = _TRACE if _TRACE.on else None
        for node, key, ids, h, fut in pending:
            try:
                rows = fut.result()
            except BaseException:
                node.cache.abort_lookup(h)
                raise
            # span stamped AFTER the join: any blocked wait for the
            # overlapped pull belongs to the ps.miss_pull span on the
            # feed-pipeline track, not to the gather
            t0 = _time.perf_counter_ns() if tr is not None else 0
            cache = node.cache
            # RLock depth 2 across commit+gather (finish_lookup's
            # release drops to 1): a concurrent lookup/update on the
            # same table must not evict a just-committed slot and fill
            # another key's row into it before the gather DISPATCH has
            # captured this slab/positions pairing (the same atomicity
            # _lookup_device keeps for standalone callers)
            cache._lock.acquire()
            try:
                cache.finish_lookup(h, rows)
                m = 0 if rows is None else int(rows.shape[0])
                if tr is not None and h.flow_id is not None:
                    # the overlapped pull, as an arrow from the feed-
                    # pipeline track into the step span that consumes it
                    tr.flow_end("emb.miss_fill", h.flow_id, cat="ps")
                w = cache.width
                if h.flat.size:
                    slots_occ = h.positions[h.inv].astype(np.int32)
                    inv = h.inv.astype(np.int32)
                else:
                    slots_occ = np.zeros(0, np.int32)
                    inv = np.zeros(0, np.int32)
                g = _emb.gather_for_step(cache._ensure_dev_slab(),
                                         jax.device_put(slots_occ), w,
                                         interpret=cache.device_interpret)
            finally:
                cache._lock.release()
            ps_vals[key] = g.reshape(tuple(ids.shape) + (w,))
            feeds["psdev:" + key + ":inv"] = jax.device_put(inv)
            self._dev_live[node] = h
            if tr is not None:
                tr.complete("emb.gather", t0, _time.perf_counter_ns(),
                            cat="ps",
                            args={"unique": 0 if h.uk is None
                                  else int(h.uk.size), "miss_rows": m})

    def _settle_dev_pending(self, pending):
        """Failure path: every not-yet-committed handle must release its
        cache lock.  A round trip that already SUCCEEDED is committed
        (its pushes reached the server — dropping the plan would leave
        the pending grads marked unsent and a retry would double-apply);
        a failed or unread one is aborted with the cache untouched."""
        for node, key, ids, h, fut in pending:
            if h.done:
                continue
            try:
                rows = fut.result()
            except BaseException:
                node.cache.abort_lookup(h)
                continue
            try:
                node.cache.finish_lookup(h, rows)   # eager slab fill
            except BaseException:
                node.cache.abort_lookup(h)

    def _ps_post_step(self, updates, sync=True):
        """Post-dispatch PS plane: grad push (sync/async by ``bsp``),
        cross-rank barriers, SSP clock, next-batch row prefetch — the
        push boundary is where non-blocking stepping is FORCED to sync
        (the row gradient must be materialized to host to be pushed)."""
        import jax
        ex = self.ex
        if ex.bsp == -1 and ex.prefetch:
            # ASP: next-batch pull may overlap the in-flight step AND the
            # async push (bounded-staleness semantics already allow it)
            self._start_ps_prefetch()
        pushed = False
        dev_nodes = self._dev_node_set
        tr = _TRACE if _TRACE.on else None
        if dev_nodes:
            from ..ops.pallas.emb_cache import fill_bucket
        for node in self.ps_nodes:
            if node in dev_nodes:
                # device-resident table: commit the device-summed grads
                # — the host applies U pre-summed rows (bounded-
                # staleness bookkeeping + batched push) instead of
                # segment-summing the whole batch
                k = ex._k(node)
                h = self._dev_live.pop(node, None)
                g = updates.pop("psgrad:" + k, None)
                if g is not None and h is not None and h.uk is not None:
                    pushed = True
                    t0 = _time.perf_counter_ns() if tr is not None else 0
                    # only rows [0, U) of the padded scatter-add output
                    # are real — slice to a pow2 bucket on device first
                    # so the D2H copy (the sync point) moves ~U rows,
                    # not the whole padded batch
                    U = int(h.uk.size)
                    ub = min(g.shape[0], fill_bucket(U))
                    gv = np.asarray(g[:ub])[:U]
                    node.cache.apply_update_summed(h.uk, gv, h.cnt)
                    if tr is not None:
                        tr.complete("emb.scatter_add", t0,
                                    _time.perf_counter_ns(), cat="ps",
                                    args={"unique": int(h.uk.size)})
                continue
            g = updates.pop("psgrad:" + ex._k(node), None)
            if g is not None:
                pushed = True
                # multiprocess: the host fetch may be a cross-process
                # COLLECTIVE, so every rank runs it BEFORE the one-pusher
                # gate below.  Single-process keeps the device array —
                # ASP's worker thread does the D2H copy off the main
                # thread
                gv = self._host_fetch(g) if ex._multiprocess else g
                # multi-process: the dp-psum'd row grad is REPLICATED
                # across ranks — exactly one rank applies it (the others
                # would double-count); routing to key owners is the
                # store's job
                if ex._multiprocess and jax.process_index() != 0:
                    continue
                if ex.bsp == -1:
                    # ASP (reference bsp=-1, ParameterServerCommunicate
                    # _compute_asp_prefetch:38): push on a background
                    # thread with a bounded in-flight window; the device→
                    # host copy happens on the worker too so the main
                    # thread never blocks on the grad transfer
                    ex._ps_async_push(node, gv)
                else:
                    node.push(np.asarray(gv))
        if pushed and not sync:
            # the push boundary forces the sync point: the row gradient
            # is materialized host-side exactly here (BSP inline; ASP on
            # the worker), which is where async-vs-sync parity is pinned
            from ..metrics import record_run_plan
            record_run_plan("async_sync_points")
        if ex._multiprocess and self.ps_nodes and self.training:
            # every rank's NEXT pull must observe this step's push (the
            # reference's _compute_bsp_prefetch barrier) — ranks must
            # never assemble "replicated" global arrays from DIVERGENT
            # row values.  This also bounds ASP: pushes stay async within
            # the step (overlapping the device work) but are flushed at
            # the step boundary — cross-rank row divergence would be
            # silent corruption, not bounded staleness
            if ex.bsp == -1:
                ex.ps_flush()
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(
                f"hetu-ps-step-{ex.step_counter}")
        if ex.bsp > 0 and self.training and self.ps_nodes:
            # SSP (reference bsp>0, _compute_ssp_prefetch:42 ssp_sync):
            # tick this worker's clock after its push and block while more
            # than `bsp` steps ahead of the slowest worker.  Stores whose
            # ssp_sync really blocks (native condvar; dist server-side
            # condition) get ONE wait for the whole budget — no per-step
            # host polling at real step rates (round-4 verdict weak 5).
            # The numpy fallback reports the condition without blocking
            # and keeps the poll loop.  Either way a finite watchdog
            # raises rather than wedging every healthy worker behind one
            # dead straggler with no diagnostic.
            seen = set()
            for node in self.ps_nodes:
                store = node.store
                if id(store) in seen or not hasattr(store, "ssp_sync") \
                        or not getattr(store, "ssp_ready", True):
                    continue   # local store without ssp_init: vacuous
                seen.add(id(store))
                rank = getattr(store, "rank", 0)
                try:
                    store.clock(rank)
                except RuntimeError as e:
                    if "not initialised" in str(e):
                        # distributed store whose rank-0 clocks were never
                        # ssp_init'd: bounded staleness is vacuous
                        continue
                    raise       # real store failures must surface
                deadline = _time.monotonic() + ex.ssp_timeout_ms / 1e3
                # every house store BLOCKS in ssp_sync now (native
                # condvar, dist server-side condition, and the numpy
                # fallback's threading.Condition — all declare
                # ssp_blocking=True) — one wait over the remaining
                # budget, no 5 ms host polling.  The default stays False
                # so an unknown store with a report-only ssp_sync gets
                # the polled path instead of a hot spin
                blocking = getattr(store, "ssp_blocking", False)
                while True:
                    left_ms = (deadline - _time.monotonic()) * 1e3
                    if blocking:
                        # looped only if the store caps a single wait
                        # below the requested timeout.  Never pass 0:
                        # blocking stores read timeout_ms<=0 as
                        # wait-FOREVER (ps_store.cc clk_cv.wait; dist
                        # lr=-1.0), which would defeat the watchdog
                        ok = left_ms > 0 and store.ssp_sync(
                            rank, ex.bsp, timeout_ms=max(1, int(left_ms)))
                    else:
                        ok = store.ssp_sync(rank, ex.bsp, timeout_ms=200)
                    if ok:
                        break
                    if _time.monotonic() >= deadline:
                        raise RuntimeError(
                            f"SSP bound {ex.bsp} not satisfied within "
                            f"{ex.ssp_timeout_ms}ms — a peer worker "
                            f"is stalled or dead")
                    if not blocking:
                        _time.sleep(0.005)
        if ex.bsp != -1 and ex.prefetch:
            # BSP: the prefetch pull must observe this step's push (the
            # reference's _compute_bsp_prefetch barriers for the same
            # reason), so it starts after it — overlapping the pull with
            # the step's remaining device work (dense param updates are
            # still in flight: np.asarray above only synced the grad) and
            # host-side inter-step time
            self._start_ps_prefetch()

    def _host_fetch(self, g):
        """Bring a step output to host memory across process boundaries.

        Single-process: plain asarray.  Multi-process: value-replicated
        outputs whose sharding metadata still spans remote devices cannot
        be fetched directly — read the local replica when metadata says
        fully-replicated, else allgather (a collective: EVERY rank must
        call this for such outputs)."""
        if not self.ex._multiprocess or getattr(
                g, "is_fully_addressable", True):
            return np.asarray(g)
        if getattr(g, "is_fully_replicated", False):
            return np.asarray(g.addressable_data(0))
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(g, tiled=True))

    def _start_ps_prefetch(self):
        """Issue next-batch row pulls on a background thread for every PS
        embedding whose ids come from a Dataloader (the only source whose
        next batch is knowable — reference lookahead, ``dl_node.
        get_next_arr``).  Consumed by the next ``run`` when ids match."""
        from ..data.dataloader import DataloaderOp
        from ..ps.dist_store import DistributedStore
        for node in self.ps_nodes:
            if node in self._prefetched:
                continue
            if getattr(node, "device_mode", False):
                # device-resident tables overlap their miss pull on the
                # feed-pipeline thread instead (_begin_dev_lookups)
                continue
            if isinstance(node.store, DistributedStore) \
                    and (self.ex.bsp != -1 or self.ex._multiprocess):
                # synchronous (BSP/SSP) multi-worker training: a lookahead
                # pull issued after only the LOCAL push would miss other
                # workers' same-step gradients — one step of hidden
                # staleness. ASP tolerates that — but NOT on a cross-
                # process mesh, where a pre-barrier prefetch could hand
                # different ranks different rows for the same "replicated"
                # global array (silent corruption, not staleness).
                continue
            idn = node.ids_node
            if not isinstance(idn, DataloaderOp):
                continue
            try:
                next_ids = np.asarray(idn.get_next_arr(self.name), np.int64)
            except KeyError:       # no dataloader registered for this split
                continue
            if self._prefetch_pool is None:
                import concurrent.futures
                self._prefetch_pool = \
                    concurrent.futures.ThreadPoolExecutor(
                        max_workers=1,
                        thread_name_prefix=f"ps-prefetch-{self.name}")
            fut = self._prefetch_pool.submit(node.pull_rows, next_ids)
            self._prefetched[node] = (next_ids, fut)

    def profile(self, feed_dict, log_file=None):
        """Per-step timing via real execution (reference SubExecutor.profile:686).

        Delegates to :class:`hetu_tpu.profiler.HetuProfiler` — one timer,
        one sync discipline (remote platforms need a host read to sync).
        """
        from ..profiler import HetuProfiler
        prof = HetuProfiler(self.ex, self.name, repeats=3, warmup=1)
        dt = prof.profile_step(feed_dict) / 1e3
        if log_file:
            with open(log_file, "a") as f:
                f.write(f"{self.name}: {dt * 1e3:.3f} ms/step\n")
        return dt


class Executor:
    """Multi-subgraph executor (parity: reference Executor:365).

    ``eval_node_dict``: list of fetches (single subgraph "default") or
    ``{name: fetch_list}`` (e.g. {'train': [...], 'validate': [...]}).
    """

    def __init__(self, eval_node_dict, ctx=None, seed=None, dist_strategy=None,
                 mesh=None, comm_mode=None, pipeline=None, num_microbatches=None,
                 matmul_precision=None, **kwargs):
        import jax
        import os as _os
        from ..obs.compile_log import SetupPhase
        configure_compile_cache()
        graph = SetupPhase("setup.graph").start()
        if isinstance(eval_node_dict, dict):
            self.eval_node_dict = dict(eval_node_dict)
        else:
            self.eval_node_dict = {"default": list(eval_node_dict)}
        # ZeRO-style weight-update sharding (parallel/zero.py): kwarg wins,
        # then HETU_ZERO, then the strategy's own zero= setting, then the
        # plan's fsdp default — resolved to a stage AFTER dist_strategy
        # lands (below)
        zero_arg = kwargs.pop("zero", None)
        # plan=: a searched ParallelPlan (hetu_tpu.autoparallel) drives
        # the whole distribution setup — its mesh axes become the
        # executor mesh, its strategy the dist_strategy, its fsdp axis
        # routes through the ZeRO slab machinery (ONE sharding mechanism,
        # never two), and its fingerprint keys the compiled-step cache so
        # candidate plans measured back-to-back each get (exactly) one
        # compile.  The plan is validated against the graph by the
        # mesh-axis / pipeline-stage / plan-coverage lints BEFORE any
        # compile — an illegal plan fails at construction with the
        # offending layer + creation site, not minutes into XLA.
        self.plan = kwargs.pop("plan", None)
        self._plan_fingerprint = None
        if self.plan is not None:
            self._plan_fingerprint = self.plan.fingerprint()
            if dist_strategy is None:
                dist_strategy = self.plan.strategy()
            if mesh is None:
                mesh = self.plan.make_mesh()
            if pipeline is not None and num_microbatches is None \
                    and self.plan.microbatches > 1:
                num_microbatches = self.plan.microbatches
        # 'bfloat16' runs fp32 matmuls as single-pass bf16 on the MXU (the
        # TPU mixed-precision fast path); None keeps jax's default
        self.matmul_precision = matmul_precision
        # compute_dtype='bfloat16': cast float params/feeds to bf16 inside
        # the step (fp32 master weights + optimizer state stay outside) —
        # halves HBM traffic for the bandwidth-bound elementwise ops
        self.compute_dtype = kwargs.pop("compute_dtype", None)
        # reference Executor(timing=...) — per-run wall timers + logOut API
        self.timing = bool(kwargs.pop("timing", False))
        self.timer_logs = {}
        self.seed = 0 if seed is None else int(seed)
        self.master_key = jax.random.key(self.seed)
        self._step_counter = 0
        self._step_dev = None   # device-chained int32 step (see run loop)
        self.comm_mode = comm_mode
        # bsp: 0 = synchronous push (BSP, default); -1 = ASP async push;
        # >0 = SSP staleness bound (enforced via ps store ssp_sync by the
        # launcher/worker loop). Reference flag semantics (README ctr:33).
        self.bsp = int(kwargs.pop("bsp", 0))
        # prefetch: overlap next-batch PS row pulls with the in-flight step
        # (reference HetuConfig(prefetch=True) default); pulls start after
        # the push under BSP (read-after-write preserved) and immediately
        # under ASP
        self.prefetch = bool(kwargs.pop("prefetch", True))
        # straggler watchdog for SSP waits (bsp>0)
        self.ssp_timeout_ms = int(kwargs.pop("ssp_timeout_ms", 600000))
        # remat: recompute activations in backward — a POLICY LADDER
        # (parallel/remat.py, ISSUE 13), not a boolean:
        #   'off'     save every activation (default)
        #   'dots'    jax.checkpoint, matmul outputs saved (== the old
        #             remat=True; True still maps here)
        #   'full'    segmented remat: the forward lowers in anchored
        #             segments, each inside a nested jax.checkpoint —
        #             only segment boundaries survive to backward
        #   'offload' dot outputs saved to HOST memory on TPU; counted
        #             fallback to 'dots' elsewhere
        #             (remat_offload_fallback)
        #   'auto'    per-segment decisions from the PR 5 shape-inferred
        #             cost model against an HBM budget
        #             (HETU_HBM_BUDGET_MB / backend-reported), cheapest
        #             recompute-per-byte rematted first; plan reported
        #             by Executor.remat_plan() and hashed into the
        #             compiled-step-cache signature
        # Every policy is BITWISE loss-equal to 'off' (remat replays the
        # same ops).  Capability analogue of the reference's memory
        # reuse plan (memory_pool.py).
        from ..parallel import remat as _remat_mod
        self.remat = _remat_mod.resolve_policy(kwargs.pop("remat", False))
        # validate: static graph verification (hetu_tpu.analysis) at
        # construction + fed-shape checks on every run().  'warn' (default)
        # reports diagnostics as warnings; 'error' fails fast with the
        # offending node and its creation site; 'off' skips analysis.
        self.validate = kwargs.pop("validate", "warn")
        if self.validate not in ("warn", "error", "off"):
            raise ValueError(f"validate={self.validate!r}: expected "
                             "'warn', 'error', or 'off'")
        self._feed_warned = set()
        # preemption-safe auto-checkpointing: every `auto_save_every`
        # training steps an atomic checkpoint lands under `auto_save_dir`
        # (keep-last-`auto_save_keep` retention); SIGTERM/SIGINT triggers
        # one final emergency save.  Env knobs HETU_AUTO_SAVE_{DIR,EVERY,
        # KEEP} let a launcher turn this on without touching user code.
        import os as _os
        self.auto_save_dir = kwargs.pop(
            "auto_save_dir", _os.environ.get("HETU_AUTO_SAVE_DIR") or None)
        self.auto_save_every = int(kwargs.pop(
            "auto_save_every", _os.environ.get("HETU_AUTO_SAVE_EVERY", "0")))
        self.auto_save_keep = int(kwargs.pop(
            "auto_save_keep", _os.environ.get("HETU_AUTO_SAVE_KEEP", "3")))
        # HETU_AUTO_RESUME=1 (set by `heturun --supervise --ckpt-dir`):
        # restore the newest complete checkpoint at construction, so a
        # training script that never calls resume() still continues
        # instead of silently restarting from step 0 on every relaunch
        self._auto_resume = bool(kwargs.pop(
            "auto_resume", _os.environ.get("HETU_AUTO_RESUME", "") == "1"))
        self._in_step = False
        self._preempt_signum = None
        self._prev_handlers = {}
        self._installed_handlers = {}
        install_handlers = kwargs.pop("install_signal_handlers", None)
        if install_handlers is None:
            install_handlers = bool(self.auto_save_dir)
        if install_handlers and self.auto_save_dir:
            self._install_signal_handlers()
        self._ps_futures = []
        self._ps_pool = None
        if pipeline is None and getattr(dist_strategy, "schedule", None):
            pipeline = dist_strategy.schedule  # PipelineParallel(schedule=..)
        if pipeline is not None and pipeline not in (
                "gpipe", "pipedream", "hetpipe"):
            raise ValueError(f"unknown pipeline schedule {pipeline!r}")
        self.pipeline = pipeline
        self.num_microbatches = num_microbatches
        if pipeline and not num_microbatches:
            self.num_microbatches = 4  # reference default microbatch count
        self._extra_config = kwargs

        # distribution
        self.dist_strategy = dist_strategy
        self.mesh = mesh
        if dist_strategy is not None and mesh is None:
            self.mesh = dist_strategy.make_mesh()
        self._replicated_sharding = None
        self._multiprocess = False
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            self._replicated_sharding = NamedSharding(self.mesh, PartitionSpec())
            # a mesh spanning processes (real multi-host, or launcher-
            # spawned local ranks) needs global-array construction: every
            # process holds the FULL host value and contributes its
            # addressable shards (single-controller API over SPMD ranks)
            self._multiprocess = any(
                d.process_index != jax.process_index()
                for d in self.mesh.devices.flat)

        from ..parallel import zero as _zero
        if zero_arg is None:
            zero_arg = _os.environ.get("HETU_ZERO") or None
        if zero_arg is None:
            zero_arg = getattr(dist_strategy, "zero", None) or None
        if zero_arg is None and self.plan is not None \
                and self.plan.wants_zero():
            # the plan's fsdp directives carry ZeRO-3 semantics in the
            # memory model (params+states+grads / dp); realize them
            # through the PR 6 slab machinery rather than a second
            # (per-param GSPMD) mechanism
            zero_arg = 3
        self.zero = _zero.resolve_stage(zero_arg)
        if self.plan is not None:
            # annotate bound layers now — BEFORE variables materialize
            # (placement honors node.sharding at init) — with the
            # resolved ZeRO stage, so fsdp is realized exactly once
            self.plan.realize(zero=self.zero)

        # materialize variables once, shared across subgraphs
        all_fetches = [n for fl in self.eval_node_dict.values() for n in fl
                       if n is not None]
        self.global_topo = topo_sort(all_fetches)
        # canonical step-input keys: topo ORDINALS, not process-local node
        # ids — two structurally identical graphs built in one process get
        # byte-identical input pytrees, which is what lets the compiled-
        # step cache (graph/step_cache.py) and jax's persistent compile
        # cache (configure_compile_cache) hit across Executor rebuilds
        self._node_keys = {n: f"t{i}" for i, n in enumerate(self.global_topo)}
        self.var_values = {}
        self._init_variables()

        # ZeRO sharding plans per OptimizerOp (requires a 'dp' mesh axis of
        # size >= 2; anything else degrades to replicated + a lint warning)
        self._zero_plans = {}
        self._zero_slabs = {}     # bucket key -> (dp, width) device slab
        self._zero_covered = {}   # stage-3 param node -> its ZeroBucket
        self._slab_fetch_cache = {}   # bucket key -> (device slab, host copy)
        self._build_zero_plans()

        from ..optim.optimizer import OptimizerOp
        self.opt_states = {}
        for node in self.global_topo:
            if isinstance(node, OptimizerOp):
                plan = self._zero_plans.get(node)
                if plan is None:
                    tp = {self._k(v): self.var_values[v]
                          for v in node.params}
                else:
                    # slab-layout state: moments are born dp-sharded
                    tp = self._init_zero_slabs(node, plan)
                self.opt_states[node] = node.optimizer.init_state(tp)

        # subgraphs whose ops carry ht.context placement run on the
        # inter-op model-parallel path (per-device segment chain)
        from .interop import detect_interop, InterOpSubExecutor
        self.subexecutors = {}
        for name, fetches in self.eval_node_dict.items():
            topo = topo_sort([f for f in fetches if f is not None])
            if self.mesh is None and detect_interop(topo):
                self.subexecutors[name] = InterOpSubExecutor(
                    name, fetches, self)
            else:
                self.subexecutors[name] = SubExecutor(name, fetches, self)

        # dispatch-path statics: PS presence gates the per-step PS hooks
        # (re-replication env polling etc.) off the dense hot path, and
        # the async in-flight window bounds run(sync=False) stepping
        self._has_ps = any(getattr(se, "ps_nodes", None)
                           for se in self.subexecutors.values())
        from collections import deque
        self._async_pending = deque()
        # flow-arrow ids paired with _async_pending entries (traced runs
        # only; empty otherwise) — ties each non-blocking dispatch to
        # the sync point that materialized it in the exported trace
        self._async_fids = deque()
        try:
            self._async_window = max(
                1, int(_os.environ.get("HETU_ASYNC_WINDOW", "4")))
        except ValueError:
            self._async_window = 4

        self._validate_graphs()
        graph.stop()

        if self._auto_resume and self.auto_save_dir:
            self.resume(self.auto_save_dir)

    # -- step counter ------------------------------------------------------

    @property
    def step_counter(self):
        return self._step_counter

    @step_counter.setter
    def step_counter(self, v):
        """External assignment (load/resume/user code): the device-
        chained step scalar is stale now — the next run re-places it
        from the host value.  The run loops bump ``_step_counter``
        directly (their device copy advances inside the jitted step)."""
        self._step_counter = int(v)
        self._step_dev = None

    def _step_input(self):
        """The jitted step's ``step_idx`` input: the device scalar the
        previous step returned (zero host work), or a fresh host int32
        right after construction / checkpoint restore / external
        assignment."""
        sd = self._step_dev
        return np.int32(self._step_counter) if sd is None else sd

    # -- canonical step-input keys ----------------------------------------

    def _k(self, node):
        """Canonical (topo-ordinal) step-input key of a graph node."""
        k = self._node_keys.get(node)
        return k if k is not None else f"n{node.id}"

    # -- ZeRO weight-update sharding (parallel/zero.py) --------------------

    def _build_zero_plans(self):
        """One :class:`ZeroPlan` per OptimizerOp when ZeRO is on and the
        mesh has a 'dp' axis of size >= 2.  An optimizer whose params are
        not all float arrays (e.g. a PS-backed table riding in the same
        op), or that owns a param with an EXPLICIT sharding annotation
        (``ht.dispatch``: model-parallel layouts the dp slab packing —
        and stage <3's replicated gather — would silently destroy), is
        left on the replicated update path — a partial plan would
        silently skip the uncovered params' update."""
        from ..parallel import zero as _zero
        if not self.zero or self.mesh is None \
                or _zero.ZERO_AXIS not in self.mesh.axis_names:
            return
        dp = int(self.mesh.shape[_zero.ZERO_AXIS])
        if dp < 2:
            return
        from ..optim.optimizer import OptimizerOp
        for node in self.global_topo:
            if not isinstance(node, OptimizerOp) or not node.params:
                continue
            items, eligible = [], True
            for p in node.params:
                v = self.var_values.get(p)
                if v is None or isinstance(v, _ZeroView) \
                        or _zero.ineligible_reason(p, v.dtype) is not None:
                    eligible = False
                    break
                items.append((self._k(p), tuple(v.shape),
                              np.dtype(v.dtype).name))
            if not eligible:
                continue
            # LAMB's trust ratio needs per-PARAMETER norms: a multi-param
            # slab would compute one norm for the whole bucket
            per_param = bool(getattr(node.optimizer, "lamb", False))
            self._zero_plans[node] = _zero.build_plan(
                items, dp, self.zero, per_param=per_param,
                prefix=self._k(node) + ".")

    def _init_zero_slabs(self, op, plan):
        """Pack ``op``'s params into dp-sharded bucket slabs; at stage 3
        the slabs BECOME the master copy (var_values swaps to
        :class:`_ZeroView` stand-ins) — no full param copy persists
        between steps."""
        from ..parallel import zero as _zero
        sh = _zero.slab_sharding(self.mesh)
        by_key = {self._k(p): p for p in op.params}
        slabs = {}
        for b in plan.buckets:
            host = {k: self._fetch_host(self.var_values[by_key[k]])
                    for k in b.param_keys}
            slabs[b.key] = self._global_put(
                _zero.host_pack_slab(host, b), sh)
        if plan.stage >= 3:
            for b in plan.buckets:
                self._zero_slabs[b.key] = slabs[b.key]
                for k in b.param_keys:
                    p = by_key[k]
                    self._zero_covered[p] = b
                    self.var_values[p] = _ZeroView(self, p, b)
        return slabs

    def _var_value(self, node):
        """Device value of a variable for a step input; a stage-3
        :class:`_ZeroView` is materialized to a full replicated array
        (eval subgraphs sharing sharded training weights)."""
        v = self.var_values[node]
        if isinstance(v, _ZeroView):
            return self._place_param(v.materialize(), node)
        return v

    def _set_vars_host(self, items):
        """Install full host values for variables (``{node: array}``) —
        writing THROUGH to the bucket slabs when params' master bytes
        live sharded (stage-3 ZeRO), so load/load_dict keep the sharded
        layout.  Batched: each touched slab is fetched and re-placed ONCE
        no matter how many of its params are set (a per-param round trip
        would make restoring a 50-param bucket pay 50 full slab
        gather+scatter trips — and on a multi-process mesh every fetch is
        a collective)."""
        from ..obs.compile_log import SetupPhase
        from ..parallel import zero as _zero
        by_bucket = {}
        placed = SetupPhase("setup.weights").start()
        for node, val in items.items():
            b = self._zero_covered.get(node)
            val = np.asarray(val)
            placed.nbytes += val.nbytes
            if b is None:
                self.var_values[node] = self._place_param(val, node)
            else:
                by_bucket.setdefault(b.key, (b, {}))[1][node] = val
        for key, (b, vals) in by_bucket.items():
            slab = np.array(self._fetch_host(self._zero_slabs[key]))
            flat = slab.reshape(-1)
            for node, val in vals.items():
                i = b.param_keys.index(self._k(node))
                shape = b.shapes[i]
                size = int(np.prod(shape, dtype=np.int64)) if shape else 1
                flat[b.offsets[i]:b.offsets[i] + size] = \
                    np.asarray(val, slab.dtype).reshape(-1)
            self._zero_slabs[key] = self._global_put(
                slab, _zero.slab_sharding(self.mesh))
            self._slab_fetch_cache.pop(key, None)
        placed.stop()

    def _set_var_host(self, node, val):
        self._set_vars_host({node: val})

    def _slab_host(self, bucket):
        """Host copy of one stage-3 bucket slab, memoized against the
        CURRENT device slab: save()/eval packing/return_tensor_values
        materialize every member of a bucket, and k params in one 4 MB
        bucket must pay ONE full-slab gather (a cross-process collective
        on a multiprocess mesh), not k.  The cache invalidates by slab
        identity — every step and every restore installs a new slab
        object — and is dropped eagerly on replacement, so at most the
        current step's materialized buckets live host-side."""
        cur = self._zero_slabs[bucket.key]
        slab, host = self._slab_fetch_cache.get(bucket.key, (None, None))
        if slab is cur:
            return host
        host = self._fetch_host(cur)
        self._slab_fetch_cache[bucket.key] = (cur, host)
        return host

    # -- elastic world resize (parallel/elastic.py, ISSUE 12) --------------

    @staticmethod
    def _transcode_opt_state(tree, old_plan, new_plan):
        """Re-layout one optimizer's HOST state between ZeRO bucket
        plans: slab-keyed moment dicts of ``old_plan`` unpack to
        per-param arrays, which ``new_plan`` re-packs into its own
        ``(dp, width)`` slabs — pure data movement (flatten/concat/pad),
        so the moments survive a resize bitwise.  Either plan may be
        None (replicated layout on that side).  Scalars (Adam's ``t``)
        and non-matching subtrees pass through untouched."""
        from ..parallel import zero as _zero
        old_keys = frozenset(b.key for b in old_plan.buckets) \
            if old_plan is not None else frozenset()
        new_keys = frozenset(new_plan.param_keys) \
            if new_plan is not None else frozenset()

        def walk(t):
            if not isinstance(t, dict):
                return t
            keys = frozenset(t)
            if old_keys and keys == old_keys:
                flat = {}
                for b in old_plan.buckets:
                    flat.update(_zero.host_unpack_slab(
                        np.asarray(t[b.key]), b))
                t = flat
                keys = frozenset(t)
            if new_keys and keys == new_keys:
                return {b.key: _zero.host_pack_slab(t, b)
                        for b in new_plan.buckets}
            return {k: walk(v) for k, v in t.items()}

        return walk(tree)

    def _maybe_transcode_loaded_opt(self, op, host_tree):
        """Cross-dp checkpoint portability: a directory checkpoint
        written under a different world size carries ``op``'s ZeRO
        moment slabs in the WRITER's ``(dp, width)`` layout.  Bucket
        boundaries are dp-independent (packing is by bytes and dtype),
        so the writer's plan is reconstructible from the slab's leading
        dim — reconstruct it and transcode the moments into this
        world's layout (bitwise, pure data movement).  Anything that
        does not look like a clean cross-dp slab set (different bucket
        partition, stage mismatch) passes through untouched and the
        existing shape handling decides.  This is what lets a
        supervisor restart — or a fresh executor — resume a checkpoint
        that an elastic resize (``resize_world``) wrote at a different
        dp."""
        plan = self._zero_plans.get(op)
        if plan is None:
            return host_tree
        from ..parallel import zero as _zero
        new_shapes = {(b.dp, b.width) for b in plan.buckets}
        bucket_keys = frozenset(b.key for b in plan.buckets)
        slab_shape = []

        def scan(t):
            if not isinstance(t, dict) or slab_shape:
                return
            if frozenset(t) == bucket_keys:
                for bi, b in enumerate(plan.buckets):
                    v = t.get(b.key)
                    if getattr(v, "ndim", 0) == 2:
                        slab_shape.append((bi, tuple(v.shape)))
                        return
            for v in t.values():
                scan(v)

        scan(host_tree)
        if not slab_shape or slab_shape[0][1] in new_shapes:
            return host_tree        # same world (or nothing slab-like)
        bi, shape = slab_shape[0]
        dp_old = int(shape[0])
        items = [(k, s, b.dtype) for b in plan.buckets
                 for k, s in zip(b.param_keys, b.shapes)]
        old_plan = _zero.build_plan(
            items, dp_old, plan.stage,
            per_param=bool(getattr(op.optimizer, "lamb", False)),
            prefix=self._k(op) + ".")
        if frozenset(b.key for b in old_plan.buckets) != bucket_keys \
                or shape != (old_plan.buckets[bi].dp,
                             old_plan.buckets[bi].width):
            return host_tree        # not a clean cross-dp layout
        warnings.warn(
            f"checkpoint optimizer state for '{op.name}' was written at "
            f"dp={dp_old}; transcoding its moment slabs to this world's "
            f"dp={plan.dp} layout (elastic-resize checkpoint "
            f"portability)")
        return self._transcode_opt_state(host_tree, old_plan, plan)

    def resize_world(self, ranks):
        """Resize the data-parallel world IN PLACE — the elastic
        shrink/grow primitive (:mod:`hetu_tpu.parallel.elastic`).

        ``ranks``: the active rank indices into the BASE world (the
        device order of the mesh this executor was constructed with —
        rank r is base device r).  Everything that makes training
        continuous is preserved bitwise: params and optimizer moments
        (ZeRO slab layouts transcoded through
        :meth:`_transcode_opt_state`), the RNG key, the step counter,
        and dataloader positions (never touched).  In-flight async
        steps are drained first; the jitted step rebuilds THROUGH the
        compiled-step cache, so revisiting a world size (the grow-back)
        is a ``step_cache_hit`` — no recompile.  The transient cost is
        one full host materialization of params + moments (the same
        bytes a checkpoint restore moves) plus one compile per
        first-visited world size.

        Single-controller only: a multiprocess mesh is refused (every
        process would have to agree on the new world — that is the
        jax.distributed coordination problem, out of scope per the
        fail-stop model note in ``parallel/elastic.py``), as is any
        mesh with model-parallel axes (re-planning 'tp'/'pp' layouts is
        a different problem than re-packing dp slabs).  Returns True if
        the world actually changed, False for a no-op."""
        from .. import race as _race
        if _race.ACTIVE is not None:   # ISSUE 14 preemption point
            _race.point("exec.resize_world")
        import jax
        from ..parallel import zero as _zero
        from ..context import make_mesh
        if self.mesh is None:
            raise ValueError(
                "resize_world needs a mesh (dist_strategy=DataParallel)")
        if self._multiprocess:
            raise NotImplementedError(
                "elastic resize is single-controller: a multiprocess "
                "mesh needs coordinated re-initialization (future work; "
                "use the supervisor's restart path)")
        if tuple(self.mesh.axis_names) != (_zero.ZERO_AXIS,):
            raise NotImplementedError(
                f"elastic resize supports pure data-parallel meshes "
                f"(axes ('dp',)), got {tuple(self.mesh.axis_names)}")
        base = getattr(self, "_elastic_base_devices", None)
        if base is None:
            base = self._elastic_base_devices = list(self.mesh.devices.flat)
        ranks = sorted({int(r) for r in ranks})
        if not ranks:
            raise ValueError("resize_world: empty rank set")
        if ranks[-1] >= len(base) or ranks[0] < 0:
            raise ValueError(
                f"resize_world: rank {ranks[-1] if ranks[0] >= 0 else ranks[0]}"
                f" outside the base world of {len(base)} "
                f"(ranks index the construction-time mesh)")
        new_devices = [base[r] for r in ranks]
        if new_devices == list(self.mesh.devices.flat):
            return False

        # 1. quiesce: no dispatched step may still reference the old
        # world's buffers, and no async PS push may land mid-swap
        self._drain_async()
        self.ps_flush()

        # 2. snapshot training state host-side (ZeRO views materialize
        # one gather per bucket via the _slab_host memo; optimizer slab
        # state transcodes to per-param layout below)
        var_host = {}
        for node in self.global_topo:
            if isinstance(node, PlaceholderOp) and node.is_variable:
                var_host[node] = self._fetch_host(self.var_values[node])
        old_plans = dict(self._zero_plans)
        opt_host = {
            op: jax.tree.map(self._fetch_host, st)
            for op, st in self.opt_states.items()}

        # 3. the new world: same axis name, the surviving base devices
        # in rank order — revisiting a rank set reproduces the exact
        # mesh fingerprint, which is what turns the grow-back rebuild
        # into a compiled-step cache HIT
        self.mesh = make_mesh({_zero.ZERO_AXIS: len(new_devices)},
                              new_devices)
        from jax.sharding import NamedSharding, PartitionSpec
        self._replicated_sharding = NamedSharding(self.mesh,
                                                  PartitionSpec())
        # the caller-owned strategy object is NOT touched: it may be
        # shared by other executors (its make_mesh only runs at
        # construction; this executor's live world is self.mesh)

        # 4. redistribute: re-place every variable, re-plan the ZeRO
        # buckets for the new dp, re-pack slabs and moments
        self._zero_plans = {}
        self._zero_slabs = {}
        self._zero_covered = {}
        self._slab_fetch_cache = {}
        for node, val in var_host.items():
            self.var_values[node] = self._place_param(val, node)
        self._build_zero_plans()
        for op in list(self.opt_states):
            plan = self._zero_plans.get(op)
            if plan is not None and plan.stage >= 3:
                # re-establish the slab-resident master params (and the
                # _ZeroView stand-ins) under the new bucket widths
                self._init_zero_slabs(op, plan)
            st = self._transcode_opt_state(opt_host[op],
                                           old_plans.get(op), plan)
            self.opt_states[op] = jax.tree.map(
                lambda leaf, _op=op: self._place_opt_leaf(_op, leaf), st)

        # 5. rebuild the subexecutors against the new mesh.  The old
        # ones' background pools are shut down here (their caches stay
        # open — they belong to the graph nodes, which the new
        # subexecutors share); the new jitted steps resolve through the
        # compiled-step cache.
        for se in self.subexecutors.values():
            for attr in ("_prefetch_pool", "_feed_pool"):
                pool = getattr(se, attr, None)
                if pool is not None:
                    pool.shutdown(wait=False)
        self.subexecutors = {
            name: SubExecutor(name, [f for f in fetches], self)
            for name, fetches in self.eval_node_dict.items()}
        self._has_ps = any(getattr(se, "ps_nodes", None)
                           for se in self.subexecutors.values())
        # the device-chained step scalar lives on the old mesh — force
        # the next run to re-place it from the host counter
        self.step_counter = self._step_counter
        return True

    # -- static validation (hetu_tpu.analysis) -----------------------------

    def _validate_graphs(self):
        """Construction-time graph lint (``validate='warn'|'error'``).

        Rules that need no feed shapes (grad-onto-non-trainable, duplicate
        checkpoint names, PS table width, mesh-axis validity, pipeline
        contiguity, static flash-fallback prediction, hand-shape-rule
        cross-checks) run here, so a broken graph fails at construction
        with the node name + creation site instead of minutes into XLA
        tracing.  Fed-value shapes are checked per ``run()``."""
        if self.validate == "off" and self.plan is None:
            # validate='off' silences the lint — but never the plan gate
            # (below): a plan-driven executor always lints the plan rules
            return
        from ..analysis import lint as lint_graph
        # remat is a training-graph concern: eval subgraphs sharing the
        # executor must not warn "no recomputable segment" — unless NO
        # subgraph differentiates, in which case remat= really is a
        # no-op and the first subgraph's lint says so
        any_grads = any(getattr(s, "grad_ops", None)
                        for s in self.subexecutors.values())
        first = next(iter(self.eval_node_dict), None)
        plan_cov = {}    # subgraph -> its plan-coverage errors (plan= only)
        for name, fetches in self.eval_node_dict.items():
            sub_grads = getattr(self.subexecutors.get(name), "grad_ops",
                                None)
            lint_remat = self.remat if (
                sub_grads or (not any_grads and name == first)) else "off"
            try:
                report = lint_graph(fetches, mesh=self.mesh,
                                    pipeline=self.pipeline,
                                    num_microbatches=self.num_microbatches,
                                    zero=self.zero, remat=lint_remat,
                                    plan=self.plan)
            except Exception as e:
                if self.plan is not None:
                    # with a plan attached the gate below is load-bearing:
                    # a crashed lint would let an unrealizable plan
                    # compile the WRONG program and the measurement loop
                    # would time it — fail instead of warn
                    raise
                # the analyzer must never be the thing that breaks a
                # working graph — report and continue
                warnings.warn(f"graph lint crashed on subgraph "
                              f"'{name}': {type(e).__name__}: {e}",
                              RuntimeWarning)
                continue
            if self.plan is not None:
                # the plan gate: an illegal plan must fail BEFORE compile
                # regardless of validate='warn' — silently executing a
                # plan that cannot be realized (tp never applied, pp
                # never pipelined, a plan axis missing from the mesh)
                # would produce measurements of the WRONG program
                plan_bad = [
                    d for d in report.diagnostics
                    if not d.internal and d.severity == "error"
                    and d.rule in ("mesh-axis", "pipeline-stage")]
                if plan_bad:
                    from ..analysis.lint import GraphValidationError
                    raise GraphValidationError(
                        f"plan validation failed on subgraph '{name}' "
                        f"(plan {self.plan.tag()}):\n" +
                        "\n".join(f"  {d}" for d in plan_bad))
                # plan COVERAGE is an executor-level property: an
                # auxiliary fetch set (a grad-norm scalar, an eval head)
                # need not contain the plan-annotated kernels — the plan
                # is realized if ANY subgraph carries it.  Withhold this
                # subgraph's coverage errors (and strip them from the
                # report so validate='warn'/'error' does not surface a
                # per-subgraph false alarm); the gate after the loop
                # raises if EVERY subgraph missed.
                cov = [d for d in report.diagnostics
                       if not d.internal and d.severity == "error"
                       and d.rule == "plan-coverage"]
                plan_cov[name] = cov
                if cov:
                    cov_ids = {id(d) for d in cov}
                    report.diagnostics = [d for d in report.diagnostics
                                          if id(d) not in cov_ids]
            if self.validate == "off":
                continue          # plan gate only — the lint stays silenced
            if report.diagnostics:
                if self.validate == "error":
                    report.raise_errors(all_severities=True)
                warnings.warn(
                    f"graph lint found {len(report.diagnostics)} issue(s) "
                    f"in subgraph '{name}' "
                    f"(Executor(validate='off') silences):\n{report}",
                    UserWarning)
        if self.plan is not None and plan_cov \
                and all(plan_cov.values()):
            # no subgraph realizes the plan — the unrealized directives
            # are a property of the whole executor, reported once
            from ..analysis.lint import GraphValidationError
            worst = max(plan_cov.items(), key=lambda kv: len(kv[1]))
            raise GraphValidationError(
                f"plan validation failed (plan {self.plan.tag()}): no "
                f"subgraph realizes the plan — subgraph '{worst[0]}':\n"
                + "\n".join(f"  {d}" for d in worst[1]))

    def _check_feeds(self, sub, feed_dict):
        """Fed values vs declared placeholder shapes/dtypes — the run-time
        half of ``validate=`` (feeds are only known here)."""
        from ..analysis.lint import GraphValidationError
        from .node import format_site
        for node in sub.feed_nodes:
            if node not in feed_dict or node.shape is None:
                continue
            val = feed_dict[node]
            shape = tuple(val.shape) if hasattr(val, "shape") \
                else tuple(np.shape(val))
            if shape == tuple(node.shape):
                continue
            msg = (f"feed for placeholder '{node.name}' has shape "
                   f"{shape} but the placeholder declares "
                   f"{tuple(node.shape)} [created at "
                   f"{format_site(node.creation_site)}]")
            if self.validate == "error":
                raise GraphValidationError(msg)
            if node.id not in self._feed_warned:
                self._feed_warned.add(node.id)
                warnings.warn(msg, UserWarning)

    # -- variable init ----------------------------------------------------

    def _init_variables(self):
        import jax
        init_key = jax.random.key(self.seed)
        i = 0
        # checkpoint names must be unique even when layers share default
        # names (two `Linear(name='linear')` → two 'linear.weight' nodes)
        self.var_names = {}
        seen_names = {}
        for node in self.global_topo:
            if not (isinstance(node, PlaceholderOp) and node.is_variable):
                continue
            count = seen_names.get(node.name, 0)
            seen_names[node.name] = count + 1
            self.var_names[node] = node.name if count == 0 \
                else f"{node.name}~{count}"
            if node.shape is None and hasattr(node, "shape_from"):
                ref = node.shape_from
                node.shape = tuple(np.asarray(self.var_values[ref]).shape) \
                    if ref in self.var_values else tuple(ref.shape)
            val = node.get_init_value(jax.random.fold_in(init_key, i))
            i += 1
            if val is None:
                raise ValueError(f"variable {node} has no value/initializer")
            self.var_values[node] = self._place_param(np.asarray(val, np.float32)
                                                      if np.asarray(val).dtype == np.float64
                                                      else np.asarray(val), node)

    def _global_put(self, val, sharding):
        """Commit a full host value under a (possibly multi-process)
        sharding.  Cross-process shardings cannot be device_put from host
        data directly; each process contributes its addressable shards of
        the SAME full value (callers guarantee identical content — same
        seeds, same feeds)."""
        import jax
        if not self._multiprocess:
            return jax.device_put(val, sharding)
        val = np.asarray(val)
        return jax.make_array_from_callback(
            val.shape, sharding, lambda idx: val[idx])

    def _place_param(self, val, node=None):
        import jax
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            spec = getattr(node, "sharding", None)
            if spec is not None:
                return self._global_put(val, NamedSharding(
                    self.mesh, _filter_spec(self.mesh, spec)))
            return self._global_put(val, self._replicated_sharding)
        return jax.device_put(val)

    def _place_feed(self, node, val):
        import jax
        if isinstance(val, NDArray):
            val = val.jax()
        val = np.asarray(val) if not hasattr(val, "dtype") else val
        if getattr(val, "dtype", None) == np.float64:
            val = np.asarray(val, np.float32)
        # feeds adopt the placeholder's declared dtype: int placeholders
        # (token ids, labels) must stay integral so the compute_dtype bf16
        # cast never rounds them (bf16 is exact only up to 256)
        want = getattr(node, "dtype", None)
        if want is not None and getattr(val, "dtype", None) != np.dtype(want):
            val = val.astype(np.dtype(want)) if hasattr(val, "astype") \
                else np.asarray(val, want)
        if self.mesh is None and isinstance(val, jax.Array):
            # pre-placed device feed (the bench fast path): re-dispatching
            # device_put on a committed array costs ~55us/step for nothing
            # — but ONLY when it already lives on the default backend; an
            # array parked on another platform (cpu feed into a tpu step)
            # must still be transferred here, not at dispatch time
            try:
                on_default = all(d.platform == jax.default_backend()
                                 for d in val.devices())
            except Exception:
                on_default = False
            if on_default:
                return val
            return jax.device_put(val)
        if self.mesh is not None:
            from jax.sharding import NamedSharding
            if node.sharding is not None:  # explicit ht.dispatch on a feed
                return self._global_put(val, NamedSharding(
                    self.mesh, _filter_spec(self.mesh, node.sharding)))
            if self.dist_strategy is not None:
                spec = self.dist_strategy.feed_spec(node, np.ndim(val))
                return self._global_put(val, NamedSharding(self.mesh, spec))
            # bare-mesh executors (no strategy): replicate — a plain
            # device_put would pin to local device 0, which is
            # incompatible with a cross-process mesh
            return self._global_put(val, self._replicated_sharding)
        return jax.device_put(val)

    # -- public API (reference parity) ------------------------------------

    def run(self, name="default", eval_node_list=None, feed_dict=None,
            convert_to_numpy_ret_vals=False, sync=True, **kwargs):
        """Run one step of subgraph ``name``.

        ``sync=False`` is NON-BLOCKING stepping: the returned fetches are
        handles backed by jax's async dispatch (``NDArray`` wrappers whose
        ``.asnumpy()`` materializes on demand) and the executor keeps a
        bounded window of dispatched steps in flight
        (``HETU_ASYNC_WINDOW``, default 4) instead of letting the host
        run arbitrarily far ahead.  Sync points are forced exactly where
        correctness needs one — ``convert_to_numpy_ret_vals``, the PS
        push boundary, checkpoint saves, the window filling — and counted
        (``async_sync_points``).  Async and sync stepping run the SAME
        jitted program in the same order, so losses and final state are
        bitwise identical."""
        if isinstance(name, dict):  # run(feed_dict) shorthand
            feed_dict = name
            name = "default"
        if isinstance(eval_node_list, dict) and feed_dict is None:
            # run(name, feed_dict) positional shorthand — a dict here is
            # unambiguously a feed_dict, not a fetch-list override
            feed_dict, eval_node_list = eval_node_list, None
        feed_dict = feed_dict or {}
        if eval_node_list:
            warnings.warn("eval_node_list override is ignored; fetches are "
                          "fixed per subgraph at construction")
        if self.timing:
            # in-training timers (reference timer_subexecutor.py:109 /
            # Executor(timing=...)); per-op timing under fusion comes from
            # HetuProfiler instead.  The timer BLOCKS on the fetches:
            # dispatch returns before the device finishes, so an
            # unblocked bracket under-reports real step time — which also
            # means timing=True measures away the pipelining/async wins
            # it is asked to time.
            import time
            t0 = time.perf_counter()
            out = self.subexecutors[name].run(feed_dict,
                                              convert_to_numpy_ret_vals,
                                              sync=sync)
            _sync_outs(out)
            self.timer_logs.setdefault(name, []).append(
                (time.perf_counter() - t0) * 1e3)
            return out
        return self.subexecutors[name].run(feed_dict,
                                           convert_to_numpy_ret_vals,
                                           sync=sync)

    def run_steps(self, feeder, n, name="default", sync=False,
                  convert_to_numpy_ret_vals=False):
        """Drive ``n`` steps with pipelined host→device feeds and (by
        default) non-blocking stepping — the convenience loop around
        ``run(..., sync=False)``.

        ``feeder``: ``callable(i) -> feed_dict`` (host arrays are fine),
        a list of feed_dicts, or ``None`` for dataloader-fed graphs
        (whose feeds the run plan double-buffers on its own).  Step
        ``i+1``'s feeds are placed on a background thread while step
        ``i``'s jitted program executes, so the H2D copy overlaps compute
        (``feeds_pipelined`` counts the overlapped arrays); step 0 is
        placed inline so the feed schema stays steady from the first
        step.  Returns the list of per-step fetch lists — handles under
        ``sync=False`` (materialize with ``.asnumpy()``), bitwise equal
        to a sync loop."""
        if not isinstance(n, int) or n < 0:
            raise ValueError(f"run_steps needs a step count, got {n!r}")
        if feeder is None:
            get_fd = None
        elif callable(feeder):
            get_fd = feeder
        else:
            fds = list(feeder)
            if len(fds) < n:
                raise ValueError(
                    f"run_steps: {n} steps but only {len(fds)} feed dicts")
            get_fd = fds.__getitem__

        def place_all(fd):
            if not _TRACE.on:
                return {node: self._place_feed(node, v)
                        for node, v in fd.items()}
            # traced: the H2D copy shows up on the run-steps-feed track
            t0 = _time.perf_counter_ns()
            out = {node: self._place_feed(node, v)
                   for node, v in fd.items()}
            _TRACE.complete("feed.h2d", t0, _time.perf_counter_ns(),
                            cat="feed", args={"n": len(out)})
            return out

        from .run_plan import feed_pipeline_enabled, pipeline_min_us
        pool = fut = None
        placed, overlap = {}, False
        if get_fd and n:
            import jax
            # warm the device_put dispatch infra with one scalar so the
            # timed placement below measures steady-state cost, without
            # paying a full redundant copy of step 0's batch
            jax.device_put(np.zeros((), np.float32))
            # adaptive: only feeds whose placement outweighs a thread
            # handoff (~60-100us) are double-buffered — pipelining a
            # 256-byte copy behind a submit/result wakeup would SLOW
            # the loop.  HETU_FEED_PIPELINE=0 kills the thread entirely
            # (this driver AND the plan's dataloader double-buffer).
            t0 = _time.perf_counter()
            placed = place_all(get_fd(0))
            overlap = feed_pipeline_enabled() \
                and (_time.perf_counter() - t0) * 1e6 >= pipeline_min_us()
        results = []
        try:
            for i in range(n):
                if overlap and i + 1 < n:
                    if pool is None:
                        import concurrent.futures
                        pool = concurrent.futures.ThreadPoolExecutor(
                            max_workers=1,
                            thread_name_prefix="run-steps-feed")
                    fut = pool.submit(place_all, get_fd(i + 1))
                else:
                    fut = None
                results.append(self.run(
                    name, feed_dict=placed, sync=sync,
                    convert_to_numpy_ret_vals=convert_to_numpy_ret_vals))
                if fut is not None:
                    placed = fut.result()
                    from ..metrics import record_run_plan
                    record_run_plan("feeds_pipelined", len(placed))
                elif get_fd and i + 1 < n:
                    placed = place_all(get_fd(i + 1))
        finally:
            if pool is not None:
                pool.shutdown(wait=False)
        return results

    def _note_async(self, outs, new_opt_states):
        """Track one non-blocking step; block on the OLDEST in-flight
        step once the window fills (bounded pipelining, not unbounded
        host run-ahead)."""
        rep = next((o for o in outs if o is not None), None)
        if rep is None:     # fetch-less step: track a state leaf instead
            import jax
            leaves = jax.tree_util.tree_leaves(new_opt_states)
            rep = leaves[0] if leaves else None
        if rep is None:
            return
        self._async_pending.append(rep)
        # LOCKSTEP with _async_pending (None when tracing was off at
        # dispatch): the fids pop positionally against the handles, so
        # a mid-run enable must not shift every later arrow onto the
        # wrong dispatch
        self._async_fids.append(
            _TRACE.flow_begin("async_step", cat="async")
            if _TRACE.on else None)
        if len(self._async_pending) > self._async_window:
            from ..metrics import record_run_plan
            record_run_plan("async_sync_points")
            self._sync_oldest()

    def _sync_oldest(self):
        """Materialise the oldest in-flight ``run(sync=False)`` step.
        Traced, the wait is an ``executor.sync`` span (ring and
        profiler's trace) that the dispatch's ``async_step`` flow arrow
        ends in."""
        fid = self._async_fids.popleft() if self._async_fids else None
        oldest = [self._async_pending.popleft()]
        if not _TRACE.on:
            _sync_outs(oldest)
            return
        with _span("executor.sync", cat="async"):
            if fid is not None:
                _TRACE.flow_end("async_step", fid)
            _sync_outs(oldest)

    def _drain_async(self):
        """Force every in-flight async step to completion (counted as one
        sync point when anything was actually in flight) — called by the
        boundaries whose correctness needs a quiesced device: checkpoint
        saves and explicit flushes."""
        from .. import race as _race
        if _race.ACTIVE is not None:   # ISSUE 14 preemption point
            _race.point("exec.drain_async")
        if not self._async_pending:
            return
        from ..metrics import record_run_plan
        record_run_plan("async_sync_points")
        while self._async_pending:
            self._sync_oldest()

    def logOut(self, path, clear=True):
        """Write recorded step timings (reference Executor.logOut:548)."""
        with open(path, "a") as f:
            for name, times in self.timer_logs.items():
                for t in times:
                    f.write(f"{name}\t{t:.3f} ms\n")
        if clear:
            self.clearTimer()

    def clearTimer(self):
        self.timer_logs = {}

    def recordLoads(self):
        """Dump PS key-access loads (reference Executor.recordLoads:543)."""
        from ..ps import default_store
        return default_store().get_loads()

    def profile(self, name="default", feed_dict=None, log_file=None):
        return self.subexecutors[name].profile(feed_dict or {}, log_file)

    def export_step(self, name="default"):
        """Export the subgraph as a pure jittable function + example args.

        Returns ``(fn, example_args)`` where ``fn(tparams, sparams,
        opt_states, feeds, key, step_idx, lrs)`` is the exact step the
        executor jits (params update + state side-channel included; the
        5th output is ``step_idx + 1`` — the device-chained step
        counter).  Feeds in the example args are zeros of the
        dataloader/placeholder shapes.
        """
        import jax
        sub = self.subexecutors[name]
        if sub.ps_nodes:
            raise NotImplementedError(
                "export_step on a subgraph with PS embeddings is unsupported "
                "(row values are pulled host-side per step)")
        from ..data.dataloader import DataloaderOp
        feeds = {}
        for node in sub.feed_nodes:
            if isinstance(node, DataloaderOp):
                arr = np.zeros(node.get_cur_shape(name), np.float32)
            else:
                if node.shape is None:
                    raise ValueError(
                        f"feed {node} needs a static shape for export; "
                        "pass shape= to placeholder_op")
                arr = np.zeros(node.shape, node.dtype or np.float32)
            feeds[self._k(node)] = arr
        tparams, sparams = sub._pack_state()
        opt_states = {self._k(op): self.opt_states[op] for op in sub.opt_ops}
        # host lrs cover only the data-dependent schedules; traced ones
        # live inside the step (graph/run_plan.py)
        lrs = sub._host_lrs(0)
        key = jax.random.key(self.seed)
        if sub._jit is None:
            sub._build_step()
        # _step_fn is the raw pure step (the executor's own jit adds
        # donation); step_idx is int32 like the live step passes it (the
        # x64-canonicalization note in SubExecutor.run)
        return sub._step_fn, (tparams, sparams, opt_states, feeds, key,
                              np.int32(0), lrs)

    def get_batch_num(self, name="default"):
        from ..data.dataloader import DataloaderOp
        nums = [n.get_batch_num(name) for n in self.subexecutors[name].feed_nodes
                if isinstance(n, DataloaderOp)]
        return min(nums) if nums else None

    @property
    def rank(self):
        import jax
        return jax.process_index()

    @property
    def config(self):
        return self

    def _ps_async_push(self, node, grad):
        from concurrent.futures import ThreadPoolExecutor
        if self._ps_pool is None:
            self._ps_pool = ThreadPoolExecutor(max_workers=1)
        # bounded in-flight window: eventual consistency, bounded
        # staleness; completed futures are RESULT-ed (not just dropped) so
        # a failing background push raises at the next step instead of
        # silently losing gradients
        pending = []
        for f in self._ps_futures:
            if f.done():
                f.result()
            else:
                pending.append(f)
        self._ps_futures = pending
        while len(self._ps_futures) >= 32:
            self._ps_futures.pop(0).result()
        # ids are captured NOW: by the time the worker runs, the next step
        # may already have overwritten node._last_ids (via pull or prefetch
        # consumption) — a deferred read would push step-N grads onto
        # step-N+1's rows
        ids = node._last_ids
        self._ps_futures.append(self._ps_pool.submit(
            lambda: node.push_to(ids, np.asarray(grad))))

    def ps_flush(self):
        """Barrier: wait until every ASP async push has been applied."""
        for f in self._ps_futures:
            f.result()
        self._ps_futures = []

    def _flush_ps_caches(self):
        """Push every embedding cache's accumulated (push-bound-pending)
        grads to the store.  Save paths call this after :meth:`ps_flush`:
        PS tables persist SERVER-side, so grads still sitting in a client
        cache would otherwise be absent from the checkpoint — and lost
        entirely when a preempted process resumes from it.  Not part of
        ``ps_flush`` itself: that runs on per-step multiprocess barriers,
        where a forced flush would defeat ``push_bound``."""
        flushed = set()
        for se in self.subexecutors.values():
            for node in getattr(se, "ps_nodes", []):
                cache = getattr(node, "cache", None)
                if cache is not None and id(cache) not in flushed \
                        and hasattr(cache, "flush"):
                    flushed.add(id(cache))
                    cache.flush()

    # -- fault tolerance: auto-checkpoint, preemption, resume --------------

    def _post_step(self, training):
        """Step-boundary hooks: periodic auto-save, chaos schedule tick,
        PS redundancy repair, deferred preemption handling.  Called by
        SubExecutor.run AFTER the state swap, so everything below sees a
        consistent step."""
        if training:
            if self.auto_save_dir and self.auto_save_every > 0 \
                    and self.step_counter % self.auto_save_every == 0:
                self._auto_save()
            inj = _chaos_active()
            if inj is not None:
                # the injected kill lands AFTER this step's auto-save: a
                # schedule's `kill:ps@rank<r>:step<s>` is reproducibly
                # "step s completed, then the server died"
                inj.on_step(self.step_counter)
            if self._has_ps:    # dense graphs skip the PS repair hooks
                self._tick_re_replication()
        if self._preempt_signum is not None:
            self._handle_preemption()

    def _tick_re_replication(self):
        """Background re-replication driver (HETU_PS_REREPLICATE_EVERY
        steps, 0 = off): after a PS failover left a shard running without
        its backup, each tick asks every replicated store this executor's
        graphs use to try restoring redundancy onto the relaunched
        holder — a still-dead target defers quietly
        (``ps_re_replicate_deferred``) to the next tick, a repaired shard
        makes a SECOND failure survivable with no operator action."""
        import os as _os
        every = int(_os.environ.get("HETU_PS_REREPLICATE_EVERY", "0"))
        if every <= 0 or self.step_counter % every != 0:
            return
        seen = set()
        for se in self.subexecutors.values():
            for node in getattr(se, "ps_nodes", []):
                store = getattr(node, "store", None)
                if store is None or id(store) in seen \
                        or not hasattr(store, "maybe_re_replicate"):
                    continue
                seen.add(id(store))
                store.maybe_re_replicate()

    def _install_signal_handlers(self):
        """SIGTERM/SIGINT → one final emergency save, then the previous
        disposition.  Main-thread only (signal module constraint); the
        previous handlers are chained, not clobbered.  The registered
        handler holds only a WEAK reference to this executor — the signal
        module must not pin a dead executor (and its full parameter
        state) in memory; once collected, the handler falls through to
        the previous disposition."""
        import signal
        import threading
        import weakref
        if threading.current_thread() is not threading.main_thread():
            return
        ref = weakref.ref(self)
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                prev = signal.getsignal(sig)

                def handler(signum, frame, _ref=ref, _prev=prev):
                    ex = _ref()
                    if ex is not None:
                        return ex._on_preempt(signum, frame)
                    if callable(_prev):
                        return _prev(signum, frame)
                    if _prev == signal.SIG_IGN:
                        return      # honor an explicit prior ignore
                    if signum == signal.SIGINT:
                        raise KeyboardInterrupt
                    raise SystemExit(128 + signum)

                signal.signal(sig, handler)
                self._prev_handlers[sig] = prev
                self._installed_handlers[sig] = handler
            except (ValueError, OSError):  # non-main ctx raced, or exotic
                pass                       # platform: skip, never crash

    def uninstall_signal_handlers(self):
        """Restore the previous SIGTERM/SIGINT dispositions (only where
        this executor's handler is still the installed one — a later
        executor's handler already chains to ours and must stay)."""
        import signal
        for sig, h in list(self._installed_handlers.items()):
            try:
                if signal.getsignal(sig) is h:
                    signal.signal(sig, self._prev_handlers[sig])
            except (ValueError, OSError):
                pass
            self._installed_handlers.pop(sig, None)

    def _on_preempt(self, signum, frame):
        self._preempt_signum = signum
        if not self._in_step:
            self._handle_preemption()
        # else: the in-flight step finishes; _post_step handles it at the
        # boundary where params/opt/step are consistent

    def _handle_preemption(self):
        import signal
        from ..metrics import record_fault
        signum, self._preempt_signum = self._preempt_signum, None
        record_fault("emergency_save")
        try:
            # multiprocess: save() runs COLLECTIVE fetches + barriers; a
            # signal that reached only this rank would deadlock inside
            # them.  Cross-process preemption safety comes from the
            # periodic auto-saves (every rank saves at the same step) +
            # the supervisor relaunch, not from a one-rank handler.
            if self.auto_save_dir and not self._multiprocess:
                self._auto_save()
        finally:
            prev = self._prev_handlers.get(signum)
            if callable(prev):
                prev(signum, None)   # includes default_int_handler
            elif prev == signal.SIG_IGN:
                # the process explicitly ignored this signal before we
                # chained: save-and-continue, not save-and-die
                pass
            elif signum == signal.SIGINT:
                raise KeyboardInterrupt
            else:
                raise SystemExit(128 + signum)  # 143 for SIGTERM

    def _auto_save(self):
        """One atomic checkpoint at the current step under auto_save_dir
        (idempotent per step) + keep-last-N retention."""
        import os
        from ..metrics import record_fault
        d = self.auto_save_dir
        if not d:
            return None
        final = os.path.join(d, f"ckpt-{self.step_counter:08d}")
        if not os.path.exists(os.path.join(final, "meta.json")):
            os.makedirs(d, exist_ok=True)
            self.save(final)
            record_fault("auto_save")
            self._prune_auto_saves()
        return final

    def _prune_auto_saves(self):
        import glob
        import os
        import shutil
        import jax
        if self._multiprocess and jax.process_index() != 0:
            return                      # rank 0 owns retention
        cands = sorted(p for p in glob.glob(
            os.path.join(self.auto_save_dir, "ckpt-*"))
            if os.path.isdir(p) and not p.endswith((".saving",
                                                    ".replaced")))
        complete = [p for p in cands if self._checkpoint_complete(p)]
        for stale in complete[:-max(1, self.auto_save_keep)]:
            shutil.rmtree(stale, ignore_errors=True)

    @staticmethod
    def _checkpoint_complete(path):
        """A checkpoint is COMPLETE iff its meta.json parses, declares the
        format, and every file it names exists (with the recorded size,
        when the manifest carries one).  A preemption mid-save leaves
        either no meta.json (meta is written last, atomically) or a
        manifest naming files that are missing/short — both rejected."""
        import json
        import glob
        import os
        meta_path = os.path.join(path, "meta.json")
        try:
            with open(meta_path) as f:
                meta = json.load(f)
        except (OSError, json.JSONDecodeError):
            return False
        if not str(meta.get("format", "")).startswith("hetu_tpu.ckpt"):
            return False
        manifest = meta.get("manifest", {})
        names = [os.path.join("params", fn)
                 for fn in meta.get("params", {}).values()]
        for entry in meta.get("opt", []):
            names += [os.path.join("opt", fn)
                      for fn in entry.get("leaves", {}).values()]
        for rel in names:
            fp = os.path.join(path, rel)
            if not os.path.exists(fp):
                return False
            want = manifest.get(rel)
            if want is not None and os.path.getsize(fp) != want:
                return False
        for entry in meta.get("ps_tables", []):
            # per-rank shard suffixes (".shard<r>") make exact names rank-
            # dependent; existence of any file for the entry is the check
            if not glob.glob(os.path.join(path, entry["file"]) + "*"):
                return False
        return True

    def resume(self, path_or_dir):
        """Restore the newest COMPLETE checkpoint for an exact-continuation
        restart (params, optimizer state, PS rows, dataloader cursors,
        step counter).

        ``path_or_dir`` is either one checkpoint directory (meta.json
        inside) or an auto-save directory of ``ckpt-<step>`` entries —
        the newest complete one wins; incomplete/truncated ones are
        counted (``ckpt_incomplete_skipped``) and skipped.  A crash
        between the two renames of an overwriting save can strand the
        only complete copy at ``<path>.replaced``/``<path>.saving`` —
        those are probed too (at lower priority than published
        checkpoints).  Returns the restored step, or None when nothing
        loadable exists (caller starts fresh)."""
        import glob
        import os
        import warnings as _warnings
        from ..metrics import record_fault

        def _try(cand, count_incomplete=False):
            if not os.path.isdir(cand):
                return False
            if not self._checkpoint_complete(cand):
                if count_incomplete:
                    record_fault("ckpt_incomplete_skipped")
                    _warnings.warn(f"skipping incomplete checkpoint "
                                   f"{cand}", RuntimeWarning)
                return False
            self.load(cand)
            record_fault("resume")
            return True

        # a single checkpoint path, or its rename-crash remnants
        for cand in (path_or_dir, str(path_or_dir) + ".saving",
                     str(path_or_dir) + ".replaced"):
            if os.path.exists(os.path.join(cand, "meta.json")) \
                    and _try(cand):
                return self.step_counter
        if os.path.isdir(path_or_dir):
            import re

            def order(c):
                # newest step first; a published dir outranks a stranded
                # remnant of the SAME step, but a stranded newer step
                # (complete, just never renamed into place) beats an
                # older published one — it is the more exact restore
                m = re.search(r"ckpt-(\d+)", os.path.basename(c))
                published = not c.endswith((".saving", ".replaced"))
                return (int(m.group(1)) if m else -1, published)

            for cand in sorted(glob.glob(
                    os.path.join(path_or_dir, "ckpt-*")),
                    key=order, reverse=True):
                # an incomplete .saving remnant is the EXPECTED shape of
                # a preempted save, not an anomaly worth counting
                if _try(cand, count_incomplete=not cand.endswith(
                        (".saving", ".replaced"))):
                    return self.step_counter
        return None

    def __del__(self):
        if getattr(self, "_installed_handlers", None):
            try:
                self.uninstall_signal_handlers()
            except Exception:
                pass
        pool = getattr(self, "_ps_pool", None)
        if pool is not None:
            pool.shutdown(wait=False)
        closed = set()
        for se in getattr(self, "subexecutors", {}).values():
            pp = getattr(se, "_prefetch_pool", None)
            if pp is not None:
                pp.shutdown(wait=False)
            fp = getattr(se, "_feed_pool", None)
            if fp is not None:
                fp.shutdown(wait=False)
            # embedding caches owned by this graph: flush pending grads
            # and release their resources (CacheSparseTable leaked its
            # per-table ThreadPoolExecutor without this)
            for node in getattr(se, "ps_nodes", []):
                cache = getattr(node, "cache", None)
                if cache is None or id(cache) in closed \
                        or not hasattr(cache, "close"):
                    continue
                closed.add(id(cache))
                try:
                    cache.close()
                except Exception:
                    pass

    def _opt_rename_maps(self, op):
        """(nodekey→param-name, param-name→nodekey) for one optimizer op —
        node keys ('n<id>') are process-local; param names are the stable
        checkpoint identity."""
        fwd = {self._k(p): self.var_names[p] for p in op.params}
        return fwd, {v: k for k, v in fwd.items()}

    @staticmethod
    def _rename_dict_keys(tree, ren):
        if isinstance(tree, dict):
            return {ren.get(k, k): Executor._rename_dict_keys(v, ren)
                    for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            return type(tree)(Executor._rename_dict_keys(v, ren)
                              for v in tree)
        return tree

    def _named_opt_state(self, op, st):
        return self._rename_dict_keys(st, self._opt_rename_maps(op)[0])

    def _unname_opt_state(self, op, st):
        return self._rename_dict_keys(st, self._opt_rename_maps(op)[1])

    def _dataloader_sites(self):
        """Distinct DataloaderOps across subgraphs, stable graph order —
        their positions are training state (an exact resume must continue
        at the NEXT batch, not restart the epoch)."""
        from ..data.dataloader import DataloaderOp
        seen, sites = set(), []
        for name in sorted(self.subexecutors):
            se = self.subexecutors[name]
            nodes = list(getattr(se, "feed_nodes", [])) \
                + [n.ids_node for n in getattr(se, "ps_nodes", [])]
            for node in nodes:
                if isinstance(node, DataloaderOp) and id(node) not in seen:
                    seen.add(id(node))
                    sites.append(node)
        return sites

    def _ps_table_sites(self):
        """Distinct (store, table) pairs across all subgraphs, in a stable
        graph order — the ordinal is the checkpoint identity of a table."""
        seen, sites = set(), []
        for name in sorted(self.subexecutors):
            for node in getattr(self.subexecutors[name], "ps_nodes", []):
                key = (id(node.store), node.table)
                if key not in seen:
                    seen.add(key)
                    sites.append(node)
        return sites

    def _place_opt_leaf(self, op, leaf):
        """Place a restored optimizer-state leaf: slab-shaped leaves of a
        ZeRO-planned optimizer go back dp-SHARDED (a replicated restore
        would silently pay the full moment memory the plan exists to
        shed); everything else replicates like a param."""
        plan = self._zero_plans.get(op)
        if plan is not None and getattr(leaf, "ndim", 0) == 2:
            from ..parallel import zero as _zero
            if tuple(leaf.shape) in {(b.dp, b.width) for b in plan.buckets}:
                return self._global_put(np.asarray(leaf),
                                        _zero.slab_sharding(self.mesh))
        return self._place_param(leaf)

    def _fetch_host(self, v):
        """Host copy of a (possibly cross-process-sharded) tensor.

        On a multi-process mesh this is a COLLECTIVE for non-addressable
        arrays (allgather) — every rank must call it, even ranks that then
        discard the result (save gates the file writes on rank 0)."""
        import jax
        if isinstance(v, _ZeroView):    # stage-3 ZeRO: gather from slab
            return v.materialize()
        if not self._multiprocess or getattr(v, "is_fully_addressable", True):
            return np.asarray(v)
        if getattr(v, "is_fully_replicated", False):
            return np.asarray(v.addressable_data(0))
        from jax.experimental import multihost_utils
        return np.asarray(multihost_utils.process_allgather(v, tiled=True))

    def save(self, path, file=None):
        """Checkpoint params + optimizer state + PS tables + step.

        Default format is a DIRECTORY with one .npy per tensor (streamed —
        at no point is the whole state in host memory at once) and PS
        tables persisted server-side by their own store (per-host shard
        files under a DistributedStore — reference per-server SaveParam,
        ``ps-lite/src/python_binding.cc:111-118``).  The reference's save
        (:461) loses optimizer state; we keep it (SURVEY.md §5.4).
        ``file=`` selects the legacy single-pickle blob instead.

        Multiprocess: EVERY rank must call save (tensor fetches are
        collectives and each rank persists its own PS shard) but only rank
        0 writes params/opt/meta — concurrent same-path np.save from
        several local ranks interleaves and corrupts tensors.

        Atomicity (preemption-safe): the directory format is assembled in
        ``<path>.saving`` and PUBLISHED by one rename, with meta.json
        written last + atomically and carrying a size manifest — a
        preemption at ANY point leaves either the previous checkpoint at
        ``path`` untouched or a work dir ``resume`` never considers;
        never a half-written checkpoint that validates."""
        self._drain_async()  # async stepping: quiesce before fetching
        self.ps_flush()  # ASP pushes must land before persisting
        self._flush_ps_caches()  # cache-pending grads too: tables persist
        import json                 # server-side
        import os
        import shutil
        import jax
        rank0 = not self._multiprocess or jax.process_index() == 0
        path = os.path.normpath(path)
        if file is not None:    # legacy single-file blob (atomic replace)
            os.makedirs(path, exist_ok=True)
            blob = {
                "params": {self.var_names[n]: self._fetch_host(v)
                           for n, v in self.var_values.items()},
                "opt_states": {op.name: jax.tree.map(self._fetch_host, st)
                               for op, st in self.opt_states.items()},
                "step": self.step_counter,
            }
            if rank0:
                tmp = os.path.join(path, file + ".tmp")
                with open(tmp, "wb") as f:
                    pickle.dump(blob, f)
                os.replace(tmp, os.path.join(path, file))
            return
        work = path + ".saving"
        if rank0 and os.path.exists(work):  # leftovers of a preempted save
            shutil.rmtree(work)
        # ranks write PS shards into the SAME work dir: nobody may write
        # before rank 0's cleanup, and rank 0 must not publish before
        # everybody finished writing — hence the barriers
        self._save_barrier("clean")
        os.makedirs(os.path.join(work, "params"), exist_ok=True)
        os.makedirs(os.path.join(work, "opt"), exist_ok=True)
        meta = {"format": "hetu_tpu.ckpt.v1", "step": self.step_counter,
                "seed": self.seed, "params": {}, "opt": [],
                "ps_tables": [], "manifest": {}}

        def _persist(rel, host_val):
            fp = os.path.join(work, rel)
            np.save(fp, host_val)
            # np.save appends .npy only when missing; rel always has it
            meta["manifest"][rel] = os.path.getsize(fp)

        for i, (n, v) in enumerate(self.var_values.items()):
            fn = f"p{i}.npy"
            hv = self._fetch_host(v)        # collective: all ranks
            if rank0:
                _persist(os.path.join("params", fn), hv)
            meta["params"][self.var_names[n]] = fn
        for k, (op, st) in enumerate(self.opt_states.items()):
            named = self._named_opt_state(op, st)
            leaves = {}
            for j, (kpath, leaf) in enumerate(
                    jax.tree_util.tree_flatten_with_path(named)[0]):
                fn = f"o{k}_{j}.npy"
                hl = self._fetch_host(leaf)  # collective: all ranks
                if rank0:
                    _persist(os.path.join("opt", fn), hl)
                leaves[jax.tree_util.keystr(kpath)] = fn
            meta["opt"].append({"name": op.name, "leaves": leaves})
        for i, node in enumerate(self._ps_table_sites()):
            if not hasattr(node.store, "save"):
                continue
            fn = f"ps{i}.bin"
            # a DistributedStore (has a .server) self-suffixes .shard{rank}
            # — every rank persists its own shard.  A plain per-process
            # EmbeddingStore writes ONE path: rank 0 only (contents are
            # replicated by the one-pusher gating), or concurrent ranks
            # would interleave into the same file.
            if hasattr(node.store, "server") or rank0:
                node.store.save(node.table, os.path.join(work, fn))
            meta["ps_tables"].append({"file": fn, "node": node.name})
        meta["dataloaders"] = [
            {split: dl.state_dict() for split, dl in op.dataloaders.items()}
            for op in self._dataloader_sites()]
        # meta must land after EVERY rank's writes (PS shards included):
        # without this barrier a crash could leave a meta.json that
        # validates next to another rank's still-truncated shard file
        self._save_barrier("written")
        if rank0:
            tmp = os.path.join(work, "meta.json.tmp")
            with open(tmp, "w") as f:  # meta last + atomic: marks a
                json.dump(meta, f, indent=1)    # complete checkpoint
            os.replace(tmp, os.path.join(work, "meta.json"))
        self._save_barrier("meta")
        if rank0:
            if os.path.exists(path):
                # overwrite: two renames (dirs can't os.replace); a crash
                # between them leaves the complete old copy at .replaced
                old = path + ".replaced"
                if os.path.exists(old):
                    shutil.rmtree(old)
                os.rename(path, old)
                os.rename(work, path)
                shutil.rmtree(old, ignore_errors=True)
            else:
                os.rename(work, path)
        self._save_barrier("published")

    def _save_barrier(self, tag):
        """Cross-rank ordering for the shared-work-dir save protocol."""
        if not self._multiprocess:
            return
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(
            f"hetu-save-{tag}-{self.step_counter}")

    def save_orbax(self, path):
        """Orbax-format checkpoint — the JAX-ecosystem standard format,
        as an optional alternative to the native streamed-npy format
        (``save``); lets orbax-based tooling (inspection, cloud copies,
        emergency-restore pipelines) consume hetu_tpu state directly.

        The tree is {"params": {name: array}, "opt": {ordinal: named
        state}, "ps": {ordinal: row matrix}, "step": int} — the same
        name/ordinal identities ``load`` uses, so the two formats are
        semantically interchangeable for params, optimizer state, the
        step counter AND the PS embedding rows.  The one asymmetry:
        server-side PS optimizer slots/versions live only in the native
        format (``save`` persists full table state through the store's
        own ``save``); the orbax tree carries the ROW DATA, i.e. a
        restored Adam PS table warm-starts its server moments.
        Single-process convenience: multiprocess meshes should use
        ``save`` (its collective fetch + rank-0-write discipline).
        """
        import os
        import jax
        import orbax.checkpoint as ocp
        if self._multiprocess:
            raise NotImplementedError(
                "save_orbax is single-process; multiprocess meshes use "
                "save() (collective fetch + rank-0 writes)")
        self._drain_async()
        self.ps_flush()
        self._flush_ps_caches()
        tree = {
            "params": {self.var_names[n]: self._fetch_host(v)
                       for n, v in self.var_values.items()},
            "opt": {str(i): jax.tree.map(
                self._fetch_host, self._named_opt_state(op, st))
                for i, (op, st) in enumerate(self.opt_states.items())},
            "step": self.step_counter,
        }
        ps = {}
        for i, node in enumerate(self._ps_table_sites()):
            if not hasattr(node.store, "get_data"):
                raise NotImplementedError(
                    f"save_orbax cannot serialize PS table of "
                    f"'{node.name}': store "
                    f"{type(node.store).__name__} exposes no get_data — "
                    f"use save() (server-side table persistence)")
            ps[str(i)] = np.asarray(node.store.get_data(node.table))
        if ps:
            tree["ps"] = ps
        ocp.PyTreeCheckpointer().save(os.path.abspath(path), tree,
                                      force=True)

    def load_orbax(self, path, params_only=False):
        """Restore a ``save_orbax`` checkpoint (params by name, optimizer
        state and PS tables by ordinal; ``params_only=True`` is the
        warm-start form — like ``load`` it still restores the PS
        embedding rows, leaving optimizer moments and the step counter
        fresh)."""
        import os
        import orbax.checkpoint as ocp
        import jax
        tree = ocp.PyTreeCheckpointer().restore(os.path.abspath(path))
        self.load_dict(tree.get("params", {}))
        # PS rows restore in BOTH forms — symmetric with load(), whose
        # params_only branch also reloads the ps table files
        for i, node in enumerate(self._ps_table_sites()):
            rows = (tree.get("ps") or {}).get(str(i))
            if rows is None:
                continue     # older checkpoint without a ps subtree
            if not hasattr(node.store, "set_data"):
                # mirror save_orbax's loudness: dropping checkpointed
                # rows on the floor would "warm-start" from fresh
                # random embeddings with nothing pointing at the restore
                raise NotImplementedError(
                    f"load_orbax cannot restore PS table of "
                    f"'{node.name}': store "
                    f"{type(node.store).__name__} exposes no set_data — "
                    f"use load() (server-side table persistence)")
            node.store.set_data(node.table, np.asarray(rows))
        if params_only:
            return
        for i, (op, live) in enumerate(list(self.opt_states.items())):
            named = tree.get("opt", {}).get(str(i))
            if named is None:
                continue
            named_live = self._named_opt_state(op, live)
            paths, treedef = jax.tree_util.tree_flatten_with_path(
                named_live)
            saved = {jax.tree_util.keystr(kp): leaf for kp, leaf in
                     jax.tree_util.tree_flatten_with_path(named)[0]}
            leaves = [saved.get(jax.tree_util.keystr(kp), old)
                      for kp, old in paths]
            self.opt_states[op] = self._unname_opt_state(
                op, jax.tree.unflatten(
                    treedef, [self._place_opt_leaf(op, l) for l in leaves]))
        self.step_counter = int(tree.get("step", 0))

    def load(self, path, file=None, consider_splits=False,
             params_only=False):
        """Restore a checkpoint.  ``params_only=True`` is the WARM-START
        form (pretrain → fine-tune): it restores parameters (and PS
        embedding rows) by name and leaves optimizer moments, the step
        counter, and dataloader cursors at their fresh state — a full
        restore would resume the pretrain LR schedule mid-curve and
        apply stale Adam second moments to the new task."""
        import json
        import os
        import jax
        meta_path = os.path.join(path, "meta.json") \
            if os.path.isdir(path) else None
        if file is None and meta_path and os.path.exists(meta_path):
            with open(meta_path) as f:
                meta = json.load(f)
            by_name = {self.var_names[n]: n for n in self.var_values}
            # streamed one tensor at a time, except stage-3 ZeRO params:
            # those accumulate and land as ONE slab write per bucket (the
            # transient host copy is bounded by the slab total)
            pending = {}
            for name, fn in meta["params"].items():
                node = by_name.get(name)
                if node is None:
                    continue
                val = np.load(os.path.join(path, "params", fn))
                if node in self._zero_covered:
                    pending[node] = val
                else:
                    self._set_var_host(node, val)
            if pending:
                self._set_vars_host(pending)
            if params_only:
                entries = {e["file"] for e in meta["ps_tables"]}
                for i, node in enumerate(self._ps_table_sites()):
                    fn = f"ps{i}.bin"
                    if fn in entries and hasattr(node.store, "load"):
                        node.store.load(node.table,
                                        os.path.join(path, fn))
                return
            # optimizer states match by ORDINAL (graph order is the stable
            # identity; auto-generated op names are not) and leaves match
            # by param-name-translated tree path (raw paths embed node-id
            # keys, which differ across processes)
            for entry, (op, live) in zip(meta["opt"],
                                         list(self.opt_states.items())):
                named_live = self._named_opt_state(op, live)
                paths, treedef = jax.tree_util.tree_flatten_with_path(
                    named_live)
                host_leaves, missed = [], []
                for kpath, old_leaf in paths:
                    fn = entry["leaves"].get(jax.tree_util.keystr(kpath))
                    if fn is None:
                        missed.append(jax.tree_util.keystr(kpath))
                        host_leaves.append(old_leaf)
                    else:
                        host_leaves.append(
                            np.load(os.path.join(path, "opt", fn)))
                if not missed:
                    # dp portability (elastic resizes change the world
                    # between save and restore): slab moments written
                    # under a different dp transcode to this world's
                    # bucket layout instead of failing shape placement
                    tree = self._maybe_transcode_loaded_opt(
                        op, jax.tree.unflatten(treedef, host_leaves))
                    host_leaves = jax.tree_util.tree_leaves(tree)
                leaves = [self._place_opt_leaf(op, leaf)
                          if isinstance(leaf, np.ndarray) else leaf
                          for leaf in host_leaves]
                if missed and entry["leaves"]:
                    # ZeRO slab state is keyed by bucket layout: loading
                    # across a zero-stage / graph-structure change finds
                    # no matching leaves and would otherwise resume with
                    # FRESH moments silently
                    warnings.warn(
                        f"checkpoint optimizer state for '{op.name}': "
                        f"{len(missed)}/{len(paths)} live leaves absent "
                        f"from the checkpoint (e.g. {missed[0]}) — "
                        "keeping existing values. A ZeRO stage or "
                        "bucket-layout mismatch between save and load "
                        "resumes with fresh moments.")
                self.opt_states[op] = self._unname_opt_state(
                    op, jax.tree.unflatten(treedef, leaves))
            entries = {e["file"] for e in meta["ps_tables"]}
            for i, node in enumerate(self._ps_table_sites()):
                fn = f"ps{i}.bin"
                if fn in entries and hasattr(node.store, "load"):
                    node.store.load(node.table, os.path.join(path, fn))
            for op, states in zip(self._dataloader_sites(),
                                  meta.get("dataloaders", [])):
                for split, st in states.items():
                    if split in op.dataloaders:
                        op.dataloaders[split].load_state(st)
            self.step_counter = meta.get("step", 0)
            return
        if os.path.isdir(path):
            path = os.path.join(path, file or "checkpoint.hetu")
        with open(path, "rb") as f:
            blob = pickle.load(f)
        self.load_dict(blob["params"])
        if params_only:
            return
        ops = list(self.opt_states)
        by_name = {op.name: op for op in ops}
        blob_states = list(blob.get("opt_states", {}).items())
        matched = [by_name.get(name) for name, _ in blob_states]
        if not any(op is not None for op in matched) \
                and len(blob_states) == len(ops):
            # auto-generated OptimizerOp names embed a process-global
            # counter, so a same-process rebuild never name-matches —
            # fall back to graph order (the dir format's identity)
            # instead of silently resuming with fresh moments.  Only
            # when NO name matched: under partial overlap, positionally
            # installing the leftovers could cross-wire one optimizer's
            # moments into another
            matched = ops
        for op, (name, st) in zip(matched, blob_states):
            if op is None:
                continue
            # slab-shaped leaves of a ZeRO-planned optimizer go back
            # dp-SHARDED (_place_opt_leaf) — a replicated restore of the
            # moments would pay the full dp x memory the plan exists to
            # shed, at exactly the resume moment
            self.opt_states[op] = jax.tree.map(
                lambda l, op=op: self._place_opt_leaf(op, l), st)
        self.step_counter = blob.get("step", 0)

    def load_dict(self, state_dict):
        by_name = {self.var_names[n]: n for n in self.var_values}
        self._set_vars_host({by_name[name]: np.asarray(val)
                             for name, val in state_dict.items()
                             if name in by_name})

    def return_tensor_values(self):
        return {self.var_names[n]: self._fetch_host(v)
                for n, v in self.var_values.items()}

    def memory_accounting(self, feed_dict=None, name=None):
        """Per-device byte accounting of the persistent training state —
        the numbers the ZeRO memory claim is judged on
        (``tests/test_zero.py``; works on CPU where ``memory_stats``
        reports nothing).

        * ``param_bytes_per_device`` — full per-param master arrays
          (replicated: each device pays all of it).  Stage-3 ZeRO params
          live in slabs and are counted there instead.
        * ``zero_slab_bytes_per_device`` — dp-sharded master slabs
          (each device holds 1/dp, padding included).
        * ``opt_state_bytes_per_device`` — optimizer moments etc.;
          dp-sharded leaves count their one-device shard only.
        * ``grad_bytes_per_device`` — ANALYTIC layout of the transient
          backward output: full per-param unless the plan pins the grad
          slab sharded (stage >= 2).
        * ``live_buffer_bytes_per_device`` — every live jax array's
          worst-device residency (process-wide).
        * ``peak_hbm_gb`` — backend-reported peak, None where the
          backend (XLA-CPU) keeps no stats.

        With ``feed_dict`` (ISSUE 13 — the remat claims' evidence) two
        more keys land, from XLA's own buffer assignment of the compiled
        step (AOT compile; hits jax's jit cache after the first run, so
        this is cheap on a warm executor):

        * ``step_temp_bytes_per_device`` — the compiled step's TEMP
          allocation (``memory_analysis().temp_size_in_bytes``): the
          transient activation/workspace peak INSIDE one step, which
          between-steps live-array sums cannot see — exactly what
          ``remat=`` trades.  None where the backend does not answer
          AOT analysis.
        * ``live_buffer_peak_bytes_per_device`` — live buffers + step
          temp: the projected worst in-step residency.
        """
        import jax

        def per_dev(arr):
            if isinstance(arr, _ZeroView):
                return 0            # master bytes counted under the slab
            shards = getattr(arr, "addressable_shards", None)
            if shards:
                by_dev = {}
                for s in shards:
                    by_dev[s.device.id] = \
                        by_dev.get(s.device.id, 0) + s.data.nbytes
                return max(by_dev.values())
            return int(getattr(arr, "nbytes", 0))

        params = sum(per_dev(v) for v in self.var_values.values())
        slabs = sum(per_dev(v) for v in self._zero_slabs.values())
        opt = sum(per_dev(leaf) for st in self.opt_states.values()
                  for leaf in jax.tree_util.tree_leaves(st))
        grads = 0
        from ..optim.optimizer import OptimizerOp
        for node in self.global_topo:
            if not isinstance(node, OptimizerOp):
                continue
            plan = self._zero_plans.get(node)
            if plan is None:
                grads += sum(
                    int(np.prod(p.shape, dtype=np.int64))
                    * np.dtype(getattr(self.var_values.get(p), "dtype",
                                       np.float32)).itemsize
                    for p in node.params if p.shape is not None)
            else:
                for b in plan.buckets:
                    grads += b.nbytes // (plan.dp if plan.stage >= 2 else 1)
        live = sum(per_dev(a) for a in jax.live_arrays())
        # the worst device of the ones this executor runs on; XLA-CPU
        # keeps no stats (None), a TPU that reports none is an error
        devs = list(self.mesh.devices.flat) if self.mesh is not None \
            else jax.devices()[:1]
        stats = [d.memory_stats() for d in devs]
        if jax.default_backend() == "tpu" and None in stats:
            raise RuntimeError("the TPU backend reported no memory_stats")
        peak = round(max(s["peak_bytes_in_use"] for s in stats) / 2**30, 3) \
            if None not in stats else None
        out = {
            "n_devices": len(jax.devices()),
            "zero_stage": self.zero if self._zero_plans else 0,
            "param_bytes_per_device": int(params),
            "zero_slab_bytes_per_device": int(slabs),
            "opt_state_bytes_per_device": int(opt),
            "grad_bytes_per_device": int(grads),
            "live_buffer_bytes_per_device": live,
            "peak_hbm_gb": peak,
        }
        if feed_dict is not None:
            temp = None
            try:
                from ..profiler import HetuProfiler
                sub_name = name or ("train" if "train" in
                                    self.subexecutors
                                    else next(iter(self.subexecutors)))
                ma = HetuProfiler(self, name=sub_name) \
                    ._compiled(feed_dict).memory_analysis()
                temp = int(ma.temp_size_in_bytes)
            except Exception:
                temp = None
            out["step_temp_bytes_per_device"] = temp
            out["live_buffer_peak_bytes_per_device"] = \
                None if (temp is None or live is None) else live + temp
        return out

    def remat_plan(self, name=None):
        """The resolved selective-remat plan (``parallel/remat.py``).

        Returns ``{"policy": ..., "plans": {subgraph: plan report}}``;
        with ``name``, just that subgraph's report (or None).  Plans
        exist only for the segmented policies (``'full'``/``'auto'``) on
        differentiating subgraphs — the wrap policies (``'dots'``/
        ``'offload'``) have no per-segment decisions to report."""
        plans = {}
        for sname, sub in self.subexecutors.items():
            plan = getattr(sub, "_remat_plan", None)
            if plan is not None:
                plans[sname] = plan.report()
        if name is not None:
            return plans.get(name)
        return {"policy": self.remat, "plans": plans}


# reference-parity no-op shims (MPI/PS boilerplate not needed under XLA SPMD)
def worker_init():
    pass


def worker_finish():
    pass


def server_init():
    pass


def server_finish():
    pass


def scheduler_init():
    pass


def scheduler_finish():
    pass
