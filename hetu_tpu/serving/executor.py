"""InferenceExecutor: compile-once serving over frozen weights.

The training :class:`~hetu_tpu.graph.executor.Executor` is a session: it
threads params/opt-state/RNG through a donated jitted step, swaps state
after every run, and owns checkpoint/resume/signal machinery.  Serving
needs none of that — it needs a FIXED set of pre-compiled executables fed
by a request router.  This class is the inference half of the old
session/run loop, split out (the shared forward lowering lives in
``graph.executor.lower_forward``):

* **Compile-once per shape bucket.**  Requests arrive at arbitrary batch
  sizes; recompiling per size would make tail latency a compile queue.
  The executor owns a fixed set of batch buckets (:func:`default_buckets`
  — powers of two up to 128, then multiples of 128: PR 1's mod-128 rule,
  which keeps every padded batch flash-legal for attention models) and
  compiles ONE executable per bucket, on first use, reused forever.  The
  per-bucket program is looked up in the process-wide serve cache
  (``graph/step_cache.py: lookup_or_build_serve``) first, so a rebuilt
  executor over a structurally identical graph — a supervisor-driven
  reconstruction, a bench re-run — reuses the compiled executable
  instead of retracing; restart reuse across processes rides jax's
  persistent compilation cache (``graph.executor.
  configure_compile_cache``) exactly like training.

* **Read-only weights.**  Parameters load once — from a live training
  ``Executor``, a ``{name: array}`` dict, or a checkpoint directory —
  and are placed device-side as the NON-donated argument of every call.
  Request feeds ARE donated: they are fresh per batch, so XLA may reuse
  their buffers for the outputs.

* **Read-mostly embedding serving.**  PS embedding leaves pull their
  rows host-side per batch exactly like training, but through a
  ``DistCacheTable(read_only=True)``: lookups never burn pull-bound
  budget or touch the grad slab, and staleness is version-based
  (``refresh_embeddings``).  With a replicated store (``replication=2``)
  a killed shard primary fails over INSIDE the pull — the serving path
  carries no failover logic of its own and keeps answering mid-kill with
  zero restarts.

* **No train subgraphs, statically enforced.**  ``validate='error'``
  (the default) runs ``ht.lint(fetches, serving=True)``: an optimizer
  update or gradient node reachable from the serving fetch set is
  rejected at construction with its creation site
  (``train-only-op-in-serving``); dropout warns (it lowers to identity
  under ``training=False``).  Serving therefore never constructs grad or
  optimizer subgraphs — there is no backward pass to mis-build.
"""
from __future__ import annotations

import warnings

import numpy as np

from ..graph.node import Op, PlaceholderOp, LowerCtx, topo_sort
from ..graph.gradients import GradientOp
from ..graph.executor import lower_forward
from ..metrics import record_serve


def default_buckets(max_batch=128):
    """Flash-legal serving buckets up to ``max_batch``: powers of two to
    64, then multiples of 128 (PR 1's mod-128 bucketing — a padded batch
    on a 128 boundary stays on the Pallas flash path for attention
    models), plus ``max_batch`` itself as the cap."""
    max_batch = int(max_batch)
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    out = {max_batch}
    b = 1
    while b < max_batch and b <= 64:
        out.add(b)
        b *= 2
    b = 128
    while b < max_batch:
        out.add(b)
        b += 128
    return tuple(sorted(out))


def _pad_rows(v, bucket):
    """Zero-pad ``v`` along the leading (batch) dim to ``bucket`` rows."""
    v = np.asarray(v)
    if v.ndim == 0 or v.shape[0] == bucket:
        return v
    if v.shape[0] > bucket:
        raise ValueError(f"batch {v.shape[0]} exceeds bucket {bucket}")
    pad = np.zeros((bucket - v.shape[0],) + v.shape[1:], v.dtype)
    return np.concatenate([v, pad], 0)


class InferenceExecutor:
    """Compile-once inference over a fetch subgraph (see module docstring).

    ``fetches``: the serving outputs (e.g. ``[prob]``).
    ``weights``: ``None`` (seeded initializer values — tests), a live
    training ``Executor`` (its current values, by checkpoint name), a
    ``{name: array}`` dict, or a checkpoint directory path (the native
    ``Executor.save`` format; PS tables reload through their stores).
    ``buckets`` / ``max_batch``: the legal padded batch sizes (default
    :func:`default_buckets`).
    ``validate``: ``'error'`` (default — train-only nodes are rejected at
    construction), ``'warn'``, or ``'off'``.
    ``plan``: a searched :class:`~hetu_tpu.autoparallel.ParallelPlan` —
    the executor compiles on the plan's own mesh (unless ``mesh=`` is
    given), realizes any bound layer directives, and the plan-coverage
    lint gates construction: a tp plan whose layers were never bound
    fails fast instead of silently serving a replicated program.
    ``decode=True``: the fetch set is an incremental-decode step
    (``hetu_tpu.serving.decode``) — enables the ``decode-incompatible-op``
    lint rule, so an op whose lowering cannot run one token at a time
    (trains state, consumes the full sequence axis non-causally) is
    rejected at construction with its creation site.
    """

    def __init__(self, fetches, weights=None, buckets=None, max_batch=128,
                 mesh=None, seed=0, validate="error", donate=True,
                 plan=None, decode=False):
        import jax
        from ..graph.executor import configure_compile_cache
        from ..obs.compile_log import SetupPhase
        configure_compile_cache()
        graph = SetupPhase("setup.graph").start()
        if isinstance(fetches, Op):
            fetches = [fetches]
        self.fetches = list(fetches)
        self.plan = plan
        self._plan_fingerprint = None
        if plan is not None:
            # realize BEFORE topo/lint: bound layer directives annotate
            # graph nodes, and both the lowering and the plan-coverage
            # rule read those annotations.  zero=0: serving has no
            # optimizer state, so the ZeRO slab route never applies.
            plan.realize(zero=0, strict=True)
            self._plan_fingerprint = plan.fingerprint()
            if mesh is None:
                mesh = plan.make_mesh()
        self.decode = bool(decode)
        self.topo = topo_sort([f for f in self.fetches if f is not None])
        self.mesh = mesh
        self.seed = int(seed)
        self.donate = bool(donate)
        if validate not in ("warn", "error", "off"):
            raise ValueError(f"validate={validate!r}: expected "
                             "'warn', 'error', or 'off'")
        self.validate = validate
        from ..optim.optimizer import OptimizerOp
        #: train-only nodes are never lowered; their fetch value is None
        #: (validate='error' rejects them at construction instead)
        self._skip = set(n for n in self.topo
                         if isinstance(n, (GradientOp, OptimizerOp)))
        self._validate_graph()
        # canonical topo-ordinal input keys (the Executor._k discipline):
        # a structurally identical rebuild produces byte-identical input
        # pytrees, which is what lets the serve step cache hit
        self._node_keys = {n: f"s{i}" for i, n in enumerate(self.topo)}
        self.ps_nodes = [n for n in self.topo if getattr(n, "is_ps", False)]
        self.feed_nodes = [n for n in self.topo
                           if isinstance(n, PlaceholderOp)
                           and not n.is_variable
                           and not getattr(n, "is_ps", False)]
        self.var_nodes = [n for n in self.topo
                          if isinstance(n, PlaceholderOp) and n.is_variable]
        bset = buckets if buckets is not None else default_buckets(max_batch)
        self.buckets = tuple(sorted({int(b) for b in bset}))
        if not self.buckets or self.buckets[0] < 1:
            raise ValueError(f"bad bucket set {self.buckets}")
        self.max_batch = self.buckets[-1]
        # which fetches are batch-derived (transitively consume a fed
        # placeholder or PS rows)? those are padded/sliced per request
        leaf_set = set(self.feed_nodes) | set(self.ps_nodes)
        deps = {}
        for node in self.topo:
            deps[node] = node in leaf_set or any(
                deps.get(i, False) for i in node.inputs)
        self.fetch_batched = [f is not None and deps.get(f, False)
                              for f in self.fetches]
        self._key = jax.random.key(self.seed)
        self.params = {}
        self.var_names = {}
        graph.stop()
        with SetupPhase("setup.weights") as placed:
            placed.nbytes = self._load_weights(weights)
        self._compiled = {}     # bucket -> jitted serving step
        self._fetch_rows = {}   # (bucket, feed schema) -> scatter plan

    # -- canonical keys ----------------------------------------------------

    def _k(self, node):
        k = self._node_keys.get(node)
        return k if k is not None else f"n{node.id}"

    # -- static validation -------------------------------------------------

    def _validate_graph(self):
        """``ht.lint(fetches, serving=True)`` at construction: train-only
        nodes (optimizer/gradient) are errors — ``validate='error'``
        rejects them with their creation site; dropout and the general
        rule catalog surface as warnings.  Unlike the training Executor,
        ``'error'`` escalates only error-severity diagnostics: a dropout
        in the forward path of a served model is legitimate (inert under
        ``training=False``) and must not block deployment."""
        if self.validate == "off":
            return
        from ..analysis import lint as lint_graph
        try:
            report = lint_graph(self.fetches, mesh=self.mesh,
                                training=False, serving=True,
                                decode=self.decode, plan=self.plan)
        except Exception as e:
            warnings.warn(f"serving graph lint crashed: "
                          f"{type(e).__name__}: {e}", RuntimeWarning)
            return
        if report.diagnostics:
            if self.validate == "error":
                report.raise_errors()
            warnings.warn(
                f"serving lint found {len(report.diagnostics)} issue(s) "
                f"(InferenceExecutor(validate='off') silences):\n{report}",
                UserWarning)

    # -- weights -----------------------------------------------------------

    def _weights_dict(self, weights):
        """Normalize a weights source to ``{checkpoint name: array}``."""
        import json
        import os
        if isinstance(weights, dict):
            return weights
        if hasattr(weights, "return_tensor_values"):   # live Executor
            return weights.return_tensor_values()
        path = os.fspath(weights)
        meta_path = os.path.join(path, "meta.json")
        if not os.path.exists(meta_path):
            raise ValueError(
                f"weights source {path!r} is not a checkpoint directory "
                f"(no meta.json) — pass an Executor, a name->array dict, "
                f"or a directory written by Executor.save")
        with open(meta_path) as f:
            meta = json.load(f)
        out = {}
        for name, fn in meta.get("params", {}).items():
            out[name] = np.load(os.path.join(path, "params", fn))
        # PS tables restore SERVER-side through each node's own store,
        # matched by the NODE NAME meta recorded — the file ordinals are
        # the TRAINING graph's table order, and a serving graph reaching
        # only a subset (or in another order) must not load the wrong
        # table's rows.  A live-PS deployment simply has no ps files here
        # and keeps serving the live tables.
        import glob
        by_name = {e["node"]: e["file"]
                   for e in meta.get("ps_tables", [])}
        for node in self.ps_nodes:
            fn = by_name.get(node.name)
            if fn is None:
                if by_name:
                    warnings.warn(
                        f"checkpoint has no PS table for serving node "
                        f"'{node.name}' (tables: {sorted(by_name)}) — "
                        f"serving the store's LIVE rows", RuntimeWarning)
                continue
            fp = os.path.join(path, fn)
            if hasattr(node.store, "load") and glob.glob(fp + "*"):
                node.store.load(node.table, fp)
        return out

    def _load_weights(self, weights):
        import jax
        init_key = jax.random.key(self.seed)
        seen = {}
        for node in self.var_nodes:
            count = seen.get(node.name, 0)
            seen[node.name] = count + 1
            self.var_names[node] = node.name if count == 0 \
                else f"{node.name}~{count}"
        named = self._weights_dict(weights) if weights is not None else {}

        def stored(node, v):
            """``v`` in the float type the variable declares, if any."""
            want = None if node.dtype is None else np.dtype(node.dtype)
            if want is not None and v.dtype != want \
                    and jax.numpy.issubdtype(want, jax.numpy.floating):
                v = v.astype(want)
            return v

        vals, missing = {}, []
        # initializers run ONLY for variables the weights source does not
        # cover (a large-model cold start must not pay a full random init
        # it immediately overwrites); the fold_in index stays the node's
        # topo position so partial inits are seed-stable either way
        for i, node in enumerate(self.var_nodes):
            v = named.get(self.var_names[node])
            if v is not None:
                # a device array stays where it is (no trip through the
                # host, and a second executor handed the first one's
                # arrays shares their buffers); anything is stored in the
                # type the variable declares, if it declares a float type
                if not isinstance(v, jax.Array):
                    v = np.asarray(v)
                vals[node] = stored(node, v)
                continue
            if weights is not None:
                missing.append(self.var_names[node])
            val = node.get_init_value(jax.random.fold_in(init_key, i))
            if val is None:
                raise ValueError(f"variable {node} has no value/initializer")
            val = stored(node, np.asarray(val))
            vals[node] = val.astype(np.float32) \
                if val.dtype == np.float64 else val
        if missing:
            warnings.warn(
                f"weights source provides no value for "
                f"{len(missing)} variable(s) (e.g. {missing[0]!r}) — "
                f"serving their seeded INITIALIZER values",
                RuntimeWarning)
        self.params = {self._k(n): self._place(v) for n, v in vals.items()}
        # host bytes that went to the device (a device array stayed put)
        return sum(v.nbytes for v in vals.values()
                   if not isinstance(v, jax.Array))

    def _place(self, val, node=None):
        import jax
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            return jax.device_put(
                val, NamedSharding(self.mesh, PartitionSpec()))
        return jax.device_put(val)

    def _place_feed(self, node, val):
        val = np.asarray(val)
        if val.dtype == np.float64:
            val = val.astype(np.float32)
        want = getattr(node, "dtype", None)
        if want is not None and val.dtype != np.dtype(want):
            val = val.astype(np.dtype(want))
        return self._place(val, node)

    # -- compile-once per bucket -------------------------------------------

    def bucket_for(self, n):
        """Smallest legal bucket >= ``n``, or None when ``n`` exceeds the
        largest bucket (the router's rejection condition)."""
        for b in self.buckets:
            if b >= n:
                return b
        return None

    def _infer_fn(self):
        """The pure serving step ``fn(params, feeds) -> [fetch values]``
        — forward lowering only (``lower_forward``), training=False,
        state updates discarded (read-only replica).

        The closure captures ONLY the graph structure (topo, key map,
        fetches, mesh, RNG key) — never ``self``: the process-wide serve
        cache keeps this callable alive across executor rebuilds, and a
        closure over the executor would pin its full device-resident
        weight copy (``self.params``) for the cache entry's lifetime —
        two live weight copies after every rebuild.  (The graph NODES are
        pinned either way, same as the training step cache.)"""
        skip = set(self._skip)
        fetch_nodes = list(self.fetches)
        topo = self.topo
        key_of = dict(self._node_keys)
        base_key = self._key
        mesh = self.mesh

        def infer(params, feeds):
            ctx = LowerCtx(False, base_key, mesh)

            def resolve(node):
                k = key_of.get(node, f"n{node.id}")
                if k in params:
                    return params[k]
                return feeds[k]

            env = lower_forward(topo, ctx, resolve, mesh=mesh, skip=skip)
            return [None if f is None or f in skip else env[f]
                    for f in fetch_nodes]

        return infer

    def compiled(self, bucket):
        """The jitted serving step for one bucket — built AT MOST once
        per (graph, bucket) per process (``serve_bucket_compiles`` counts
        the jit wrappers made; the process-wide serve cache makes
        rebuilds reuse the same executable; the compile at the wrapper's
        first call is a ``serve:b<bucket>`` record of
        ``HetuProfiler.compile_log()``)."""
        if bucket not in self.buckets:
            raise ValueError(f"{bucket} is not a legal bucket "
                             f"{self.buckets}")
        fn = self._compiled.get(bucket)
        if fn is None:
            # serve_bucket_compiles is recorded INSIDE the cache's build
            # path: a cross-rebuild hit here builds nothing
            from ..graph import step_cache
            fn = step_cache.lookup_or_build_serve(self, bucket,
                                                  self._infer_fn())
            self._compiled[bucket] = fn
        return fn

    def compiler_options(self):
        """What the graph's nodes ask of THIS backend's compiler: the union
        of every node's ``compiler_options[backend]`` (``ops.kda``: a chunk
        scan wants the TPU's memory-space assignment off), handed to
        ``jax.jit`` with the program.  Empty for a graph that asks
        nothing, which is every graph on the CPU."""
        import jax
        asked = {}
        for node in self.topo:
            asked.update(getattr(node, "compiler_options", {}).get(
                jax.default_backend(), {}))
        return asked

    # -- inference ---------------------------------------------------------

    #: scatter-plan sentinel: batch-DERIVED but its leading dim does not
    #: scale with the batch — the fetch aggregated over it
    _AGGREGATE = -1

    def _eval_fetch_shapes(self, padded, ps_rows, b):
        """Abstract fetch shapes at batch size ``b`` — one
        ``jax.eval_shape`` of the serving step (no FLOPs, no compile),
        feeds synthesized from the real batch's trailing dims/dtypes."""
        import jax
        from ..metrics import suppress_perf_counters

        def sds(node, v, dt=None):
            v = np.asarray(v)
            if dt is None:
                dt = v.dtype
                if dt == np.float64:
                    dt = np.dtype(np.float32)
                want = getattr(node, "dtype", None)
                if want is not None:
                    dt = np.dtype(want)
            return jax.ShapeDtypeStruct((b,) + v.shape[1:], dt)

        fd = {self._k(n): sds(n, padded[n]) for n in self.feed_nodes}
        fd.update({self._k(n): sds(n, ps_rows[n], np.dtype(np.float32))
                   for n in self.ps_nodes})
        with suppress_perf_counters():
            return jax.eval_shape(self._infer_fn(), self.params, fd)

    def _fetch_row_scaling(self, padded, ps_rows, bucket):
        """Scatter plan per fetch: ``k`` (>=1) when the fetch's leading
        dim is exactly ``k * batch`` rows in row-major sample order (the
        padding slice and the router hand each sample its k rows), None
        when the fetch never touches the batch, ``_AGGREGATE`` when it
        is batch-derived but does NOT row-scale.  Shape-at-one-size is
        AMBIGUOUS (a reduce whose output dim happens to equal the bucket
        looks per-row), so the plan compares abstract shapes at TWO
        batch sizes; cached per (bucket, trailing-dims schema)."""
        key = (bucket,
               tuple((self._k(n), np.shape(v)[1:], str(np.asarray(v).dtype))
                     for d in (padded, ps_rows)
                     for n, v in sorted(d.items(), key=lambda kv: kv[0].id)))
        plan = self._fetch_rows.get(key)
        if plan is not None:
            return plan
        s1 = self._eval_fetch_shapes(padded, ps_rows, bucket)
        s2 = self._eval_fetch_shapes(padded, ps_rows, 2 * bucket)
        plan = []
        for a, b2, batched in zip(s1, s2, self.fetch_batched):
            if a is None or not batched:
                plan.append(None)
            elif (len(a.shape) and a.shape[0] and a.shape[0] % bucket == 0
                  and b2.shape[0] == (a.shape[0] // bucket) * 2 * bucket):
                plan.append(a.shape[0] // bucket)
            else:
                plan.append(self._AGGREGATE)
        self._fetch_rows[key] = plan
        return plan

    def _batch_size(self, feed_dict):
        sizes = {int(np.shape(v)[0]) for v in feed_dict.values()
                 if np.ndim(v)}
        if len(sizes) != 1:
            raise ValueError(f"feeds disagree on batch size: {sizes}")
        return sizes.pop()

    def infer(self, feed_dict, convert=True):
        """Run ONE request batch: pad to the smallest legal bucket, one
        jitted call, slice batch-derived fetches back to the true size.

        ``feed_dict``: ``{placeholder: array}`` with a shared leading
        batch dim; PS embeddings resolve their ids from the feed of
        their ``ids_node``.  Returns one value per fetch (numpy when
        ``convert``); train-only fetches (skipped subgraphs) are None.
        """
        return self.infer_rows(feed_dict, convert)[0]

    def infer_rows(self, feed_dict, convert=True):
        """:meth:`infer` plus the per-fetch scatter plan: returns
        ``(results, rows_per_sample)`` where ``rows_per_sample[i]`` is
        the number of leading rows each sample contributed to fetch i
        (the router hands request ``j`` rows ``j*k:(j+1)*k``), or None
        for a batch-invariant / aggregating fetch whose whole value
        belongs to every request alike."""
        n = self._batch_size(feed_dict)
        bucket = self.bucket_for(n)
        if bucket is None:
            raise ValueError(
                f"request batch {n} exceeds the largest serving bucket "
                f"{self.max_batch} — split the request or raise max_batch")
        record_serve("serve_pad_rows", bucket - n)
        # PS rows resolve against the REAL ids, BEFORE padding: zero-pad
        # ids would otherwise pull id 0's row (bucket-n) times per field
        # — store traffic, skewed hit stats, and an LFU frequency boost
        # that could make key 0 unevictable.  The returned rows pad with
        # zeros instead (sliced off below like any padded output).
        ps_rows = {}
        for node in self.ps_nodes:
            ids = feed_dict.get(node.ids_node)
            if ids is None:
                raise ValueError(
                    f"missing ids feed for PS embedding {node} "
                    f"(feed its ids placeholder {node.ids_node})")
            rows = node.pull_rows(np.asarray(ids, np.int64))
            ps_rows[node] = _pad_rows(np.asarray(rows), bucket)
        padded = {node: _pad_rows(v, bucket)
                  for node, v in feed_dict.items()}
        for node in self.feed_nodes:
            if node not in padded:
                raise ValueError(f"missing feed for {node}")
        # the scatter plan is consulted BEFORE any device work: it is
        # pure abstract shapes (cached jax.eval_shape — no FLOPs), so a
        # padded batch with an aggregating fetch is refused without
        # paying a full inference (or a cold bucket compile) first
        scaling = self._fetch_row_scaling(padded, ps_rows, bucket)
        if n != bucket:
            for i, k in enumerate(scaling):
                if k == self._AGGREGATE:
                    # a batch-derived fetch whose leading dim does NOT
                    # scale with the batch AGGREGATED over it (a mean, a
                    # loss, a flattened transpose) — over zero-padding
                    # rows its value is silently wrong for every request
                    raise ValueError(
                        f"fetch {self.fetches[i]} aggregates over the "
                        f"batch dim (leading dim does not scale with "
                        f"batch size): its value would include the "
                        f"{bucket - n} zero-padding row(s) of bucket "
                        f"{bucket} — fetch the per-row form and "
                        f"aggregate client-side, or submit exact-bucket "
                        f"batches")
        outs = self._run_bucket(padded, bucket, ps_rows)
        results, rows_per_sample = [], []
        for o, k in zip(outs, scaling):
            if o is None:
                results.append(None)
                rows_per_sample.append(None)
                continue
            if k is None or k == self._AGGREGATE:
                # batch-invariant, or an exact-fit aggregate: whole
                # value to every request alike
                rows_per_sample.append(None)
            else:
                # per-row fetch: slice the padding rows off.  A leading
                # dim of k*bucket is the row-major batch-flattened
                # layout (reshape(-1, d) of (bucket, k, d) — the same
                # convention the training executor's microbatch merge
                # uses), so the real rows are the first n*k
                if n != bucket:
                    o = o[: n * k]
                rows_per_sample.append(k)
            results.append(np.asarray(o) if convert else o)
        return results, rows_per_sample

    def _run_bucket(self, padded, bucket, ps_rows=None, record=True):
        """One jitted call at an exact bucket: place feeds, feed the
        pre-pulled PS rows (``infer`` pulls them for the REAL ids through
        the read-only cache — transparent failover lives in the store
        underneath; ``warm`` passes exact-bucket feeds plus zero rows and
        ``record=False`` — warming runs serve no requests and must not
        inflate the batch counters), run the pinned executable."""
        feeds = {}
        for node in self.feed_nodes:
            if node not in padded:
                raise ValueError(f"missing feed for {node}")
            feeds[self._k(node)] = self._place_feed(node, padded[node])
        for node in self.ps_nodes:
            rows = (ps_rows or {}).get(node)
            if rows is None:
                ids = padded.get(node.ids_node)
                if ids is None:
                    raise ValueError(
                        f"missing ids feed for PS embedding {node} "
                        f"(feed its ids placeholder {node.ids_node})")
                rows = node.pull_rows(np.asarray(ids, np.int64))
            feeds[self._k(node)] = self._place_feed(node, rows)
        fn = self.compiled(bucket)
        outs = fn(self.params, feeds)
        if record:
            record_serve("serve_batches")
            record_serve("serve_batch_rows", bucket)
        return outs

    def warm(self, example_feeds=None):
        """Pre-compile every bucket (cold-start control): tile/slice the
        example request (default: zeros of the declared feed shapes) to
        each bucket and run it once."""
        if example_feeds is None:
            example_feeds = {}
            for node in self.feed_nodes + [n.ids_node
                                           for n in self.ps_nodes]:
                if getattr(node, "shape", None) is None:
                    raise ValueError(
                        f"warm() needs an example feed for {node} "
                        f"(no declared shape)")
                dt = getattr(node, "dtype", None) or np.float32
                example_feeds[node] = np.zeros(node.shape, dt)
        for bucket in self.buckets:
            fd = {}
            for node, v in example_feeds.items():
                v = np.asarray(v)
                reps = -(-bucket // max(1, v.shape[0]))  # ceil
                tiled = np.concatenate([v] * reps, 0)[:bucket]
                fd[node] = tiled
            # compilation needs SHAPES, not data: feed zero rows for PS
            # embeddings directly instead of pulling the example ids
            # (all-zero by default) through the cache — (bucket) pulls
            # of id 0 per field would be store traffic, skewed hit
            # stats, and an LFU frequency boost that could make key 0
            # unevictable (the same trap infer()'s padding comment
            # documents)
            ps_rows = {
                node: np.zeros(np.shape(fd[node.ids_node]) + (node.width,),
                               np.float32)
                for node in self.ps_nodes
                if node.ids_node in fd and node.width is not None}
            self._run_bucket(fd, bucket, ps_rows, record=False)
        return len(self.buckets)

    def refresh_embeddings(self):
        """Version-based staleness sweep over every read-only embedding
        cache this graph serves through (``DistCacheTable.refresh_stale``)
        — rows a trainer kept writing are re-pulled in one batched round
        trip per cache.  Returns total refreshed rows."""
        seen, total = set(), 0
        for node in self.ps_nodes:
            cache = getattr(node, "cache", None)
            if cache is None or id(cache) in seen \
                    or not hasattr(cache, "refresh_stale"):
                continue
            seen.add(id(cache))
            refreshed = cache.refresh_stale()
            total += refreshed
            record_serve("serve_emb_refresh_rows", refreshed)
        return total


__all__ = ["InferenceExecutor", "default_buckets"]
