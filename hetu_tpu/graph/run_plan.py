"""Cached run plans: the per-step Python of ``SubExecutor._run_impl``
resolved ONCE per (subgraph, feed schema).

A round-5 CPU measurement put the executor's dispatch path at 5.2x a raw
``jax.jit`` call — at real TPU step rates the per-step Python (feed-key resolution,
``_place_feed`` placement/cast introspection, ``_check_feeds``
validation, the ``host_lr`` calls and the little dicts rebuilt every
step) IS the step time floor, no matter what XLA does.  Everything in
that list depends only on the *feed schema* — which placeholders are
fed, with what container type / dtype / shape — so it is resolved once
into a :class:`RunPlan` and replayed as a flat loop of prebound
closures:

* **feed placement** — one specialized closure per feed node
  (device-committed fast path, dtype-adopting numpy path, mesh
  placement with the strategy's ``PartitionSpec`` prebound), replacing
  the per-step isinstance/dtype/device introspection of
  ``Executor._place_feed``;
* **validation** — the ``validate='warn'|'error'`` fed-shape check runs
  once per schema (an ``error`` verdict raises at plan build, so a bad
  schema still fails every ``run()``);
* **pipelined feeds** — dataloader-fed placeholders are double-buffered:
  step N+1's batch is peeked (``get_next_arr``) and ``device_put`` on a
  background thread while step N's jitted program executes, so the
  host→device copy overlaps compute instead of serializing in front of
  the dispatch (composing with, not duplicating, the PS row prefetch).
  The consume check is by host-array IDENTITY — ``get_arr`` returns the
  exact peeked object — so a restored dataloader position can never
  serve a stale prefetched batch.

A schema change (new shapes, dtypes, feed set) transparently re-plans;
``plan_cache_hit``/``plan_cache_miss`` counters (``hetu_tpu.metrics``,
surfaced by ``HetuProfiler.run_plan_counters()``) prove the reuse, and
sustained misses from ping-ponging feed shapes raise the
``feed-schema-churn`` warning (PR 5 diagnostic style: the churning
placeholder and its creation site are named) pointing at batch
bucketing as the fix.  ``HETU_FEED_PIPELINE=0`` disables the
double-buffer; ``HETU_RUN_PLAN_CACHE`` bounds the per-subgraph plan
cache (default 8, LRU).
"""
from __future__ import annotations

import os
import time as _time
import warnings
from collections import OrderedDict

import numpy as np

from ..metrics import record_run_plan
from ..ndarray import NDArray, wrap_device
from ..obs.trace import TRACER as _TR
from ..obs.trace import annotate as _annotate
from ..obs.trace import annotate_end as _annotate_end


#: marks "this feed node is dataloader-fed (absent from feed_dict)" in
#: the identity memo — None would collide with a feed that disappeared
_DL_SENTINEL = object()

#: jax.Array class, resolved on first schema computation (keeps the jax
#: import off the module import path, like the executor's discipline)
_JaxArray = None


def feed_pipeline_enabled():
    return os.environ.get("HETU_FEED_PIPELINE", "1") != "0"


def pipeline_min_us():
    """Feed placements cheaper than this run INLINE: a Python thread
    handoff (submit + result wakeup + GIL churn) costs ~60-100us, so
    double-buffering a cheap host→device copy would SLOW the step down.
    Real batches (100KB+) clear this easily; microbench-sized feeds
    stay inline."""
    try:
        return float(os.environ.get("HETU_FEED_PIPELINE_MIN_US", "150"))
    except ValueError:
        return 150.0


def _schema_of(sub, feed_dict):
    """Hashable fingerprint of HOW this run is fed: per feed node, the
    container kind + dtype + shape (the inputs every placement/validation
    decision in ``_run_impl`` depends on).  Cheap on purpose — it runs
    every step as the plan-cache key."""
    global _JaxArray
    if _JaxArray is None:
        import jax
        _JaxArray = jax.Array
    from ..data.dataloader import DataloaderOp
    items = []
    for node in sub.feed_nodes:
        if node in feed_dict:
            v = feed_dict[node]
            # dtype OBJECTS, not strings: np.dtype hashes/compares fast,
            # while str(dtype) walks numpy's name machinery (~3us — real
            # money at per-step rates)
            if type(v) is np.ndarray:
                items.append(("np", v.dtype, v.shape))
            elif isinstance(v, _JaxArray):
                items.append(("jax", v.dtype, v.shape))
            elif isinstance(v, NDArray):
                a = v.jax()
                items.append(("ndarray", a.dtype, tuple(a.shape)))
            elif isinstance(v, np.ndarray):     # ndarray subclass
                items.append(("np", v.dtype, v.shape))
            else:   # list / scalar / exotic: the generic placement path
                items.append(("py", np.shape(v)))
        elif isinstance(node, DataloaderOp):
            items.append(("dl",))
        else:
            raise ValueError(f"missing feed for {node}")
    return tuple(items)


def _feed_dtype(node, src_dtype):
    """The dtype a feed of ``src_dtype`` is placed AS — the one
    resolution rule (``Executor._place_feed``'s float64 demotion +
    declared-dtype adoption), shared by every specialized placer so the
    fast paths cannot drift from the general one."""
    want = np.dtype(src_dtype)
    if want == np.float64:
        want = np.dtype(np.float32)
    declared = getattr(node, "dtype", None)
    if declared is not None:
        want = np.dtype(declared)
    return want


def _np_placer(ex, node, src_dtype):
    """Specialized placement for a numpy feed of known dtype: the dtype
    resolution happens HERE, once, leaving a cast-or-not + put closure
    for the hot path.  Returns ``None`` when placement needs the value's
    ndim under a dist strategy (``_bind_strategy_specs`` rebinds those
    once shapes are known)."""
    import jax
    want = _feed_dtype(node, src_dtype)
    cast = want != np.dtype(src_dtype)
    if ex.mesh is None:
        if cast:
            return lambda v: jax.device_put(v.astype(want))
        return jax.device_put
    from jax.sharding import NamedSharding
    from .executor import _filter_spec
    if node.sharding is not None:
        sh = NamedSharding(ex.mesh, _filter_spec(ex.mesh, node.sharding))
    elif ex.dist_strategy is not None:
        return None     # ndim-dependent spec: bound by the schema pass
    else:
        sh = ex._replicated_sharding
    if cast:
        return lambda v: ex._global_put(v.astype(want), sh)
    return lambda v: ex._global_put(v, sh)


class RunPlan:
    """One feed schema's resolved dispatch path (see module docstring)."""

    def __init__(self, sub, schema, feed_dict):
        ex = sub.ex
        self.sub = sub
        self.ex = ex
        self.schema = schema
        # validation verdict: once per schema.  'error' raises HERE —
        # the failed plan is never cached, so every run() with the bad
        # schema fails exactly like the per-step check did.
        if getattr(ex, "validate", "off") != "off" and feed_dict:
            ex._check_feeds(sub, feed_dict)
        self._steps = []        # (key, fetch(feed_dict) -> device value)
        self._dl_entries = []   # (node, placer) — feed-pipeline sources
        self._pre = {}          # node -> (host batch, Future[device val])
        self._dl_cost = {}      # node -> last inline placement cost (us)
        self._pipelined = 0     # consumed prefetches since last flush
        # id(arr) -> arr vetted as committed-on-default-backend.  WEAK
        # values: a fresh-array-per-step feeder (the run_steps driver)
        # must not pin dead batch buffers alive, and a dead entry's id
        # is auto-removed before the id can be recycled
        import weakref
        self._vetted = weakref.WeakValueDictionary()
        for node, item in zip(sub.feed_nodes, schema):
            key = ex._k(node)
            kind = item[0]
            if kind == "dl":
                fetch = self._dataloader_fetch(node, sub.name)
            elif kind == "np":
                place = _np_placer(ex, node, item[1])
                if place is None:
                    place = lambda v, n=node: ex._place_feed(n, v)
                fetch = (lambda fd, n=node, p=place: p(fd[n]))
            elif kind == "jax":
                fetch = self._jax_fetch(node)
            else:   # "ndarray" / "py": the generic path, prebound
                fetch = (lambda fd, n=node: ex._place_feed(n, fd[n]))
            self._steps.append((key, fetch))
        if feed_pipeline_enabled():
            for node, item in zip(sub.feed_nodes, schema):
                if item[0] == "dl":
                    self._dl_entries.append(
                        (node, lambda v, n=node: ex._place_feed(n, v)))
        # mesh strategies place numpy feeds per-ndim; resolve now that
        # shapes are known (replaces the None spec from _mesh_put)
        if ex.mesh is not None and ex.dist_strategy is not None:
            self._bind_strategy_specs(schema)
        # fast lane (see _make_fast): the dense, no-ZeRO-slab common case
        # replays as ONE prebound closure instead of the general
        # _run_impl walk — built lazily so the jitted step exists first
        self._fast = None
        self._fast_eligible = (
            os.environ.get("HETU_RUN_PLAN_FAST", "1") != "0"
            and not sub._ps_items and not sub._zero3
            and not sub._t_view and not sub._s_view)

    def _make_fast(self):
        """The per-step residue of ``SubExecutor._run_impl`` for the
        dense common case, compiled into one closure with every
        attribute chain prebound as a cell variable (LOAD_DEREF beats
        LOAD_ATTR walks at microsecond step rates).  MUST stay in
        lockstep with the general ``_run_impl`` path — the run-plan
        tests hold the two bitwise-equal (``HETU_RUN_PLAN_FAST=0``
        forces the general path for comparison)."""
        plan = self
        sub = self.sub
        ex = sub.ex
        jit = sub._jit
        steps = self._steps
        t_plain = sub._t_plain
        s_plain = sub._s_plain
        opt_items = sub._opt_items
        writeback = sub._writeback_pairs
        state_pairs = sub._state_pairs
        sched_ops = sub._sched_ops
        training = sub.training
        host_lrs = sub._host_lrs
        # all-traced lrs: ONE committed device constant, prebound (the
        # per-step call would just return it anyway)
        lrs_const = host_lrs(0) if not sub._host_lr_ops else None
        start_prefetch = self.start_feed_prefetch if self._dl_entries \
            else None
        step_input = ex._step_input
        tracer = _TR      # cell-bound: LOAD_DEREF beats LOAD_GLOBAL

        def fast(feed_dict, sync, t_pl=0, t0=0):
            # trace stamps ride INLINE in the one shared body (a traced
            # twin would drift from this path; the off cost is three
            # flag reads).  Emission is BATCHED — one buffer fetch for
            # all three phase spans, boundary timestamps shared —
            # because this closure is the dispatch-gap hot path the
            # <=25% tracing-tax gate measures.  ``t_pl``/``t0`` carry
            # the caller's run-plan-lookup window; the step span lives
            # in SubExecutor.run.
            tr = tracer if tracer.on else None
            if tr is not None:
                if not t0:
                    t0 = _time.perf_counter_ns()
                # the same boundaries in the profiler's trace, while one
                # is being captured (obs/trace.py ``annotate``)
                ann = _annotate("feeds.place")
            feeds = {}
            for key, fetch in steps:
                feeds[key] = fetch(feed_dict)
            piped = plan._pipelined
            if piped:
                plan._pipelined = 0
                record_run_plan("feeds_pipelined", piped)
            vv = ex.var_values
            tparams = {k: vv[n] for k, n in t_plain}
            sparams = {k: vv[n] for k, n in s_plain}
            os_ = ex.opt_states
            opt_states = {k: os_[op] for k, op in opt_items}
            step = ex._step_counter
            if tr is not None:
                t1 = _time.perf_counter_ns()
                _annotate_end(ann)
                ann = _annotate("jit.dispatch")
            outs, new_tparams, updates, new_opt_states, new_step = jit(
                tparams, sparams, opt_states, feeds, ex.master_key,
                step_input(),
                lrs_const if lrs_const is not None else host_lrs(step))
            if tr is not None:
                _annotate_end(ann)
                # ONE packed record for the whole phase set ("P" —
                # expanded to three spans by the exporter): one
                # allocation, one ring store, no per-step dicts; GC
                # churn was a measurable slice of the tracing tax
                b = getattr(tr._tl, "buf", None)
                if b is None or b.gen != tr._gen:
                    b = tr._buf()
                i = b.i
                b.items[i % b.cap] = ("P", t_pl, t0, t1,
                                      _time.perf_counter_ns())
                b.i = i + 1
            if start_prefetch is not None:
                start_prefetch()
            for n, k in writeback:
                vv[n] = new_tparams[k]
            if updates:
                for n, k in state_pairs:
                    if k in updates:
                        vv[n] = updates[k]
            for k, op in opt_items:
                os_[op] = new_opt_states[k]
            if training:
                # host and device counters advance together (the device
                # scalar came back from the step — zero host conversion)
                ex._step_counter = step + 1
                ex._step_dev = new_step
                for op in sched_ops:
                    op.optimizer.on_step(step + 1)
            results = [None if v is None else wrap_device(v)
                       for v in outs]
            if not sync:
                ex._note_async(outs, new_opt_states)
            return results
        return fast

    # -- feed fetch closures ------------------------------------------------

    def _bind_strategy_specs(self, schema):
        """Rebind numpy placers under a dist strategy with the ndim-
        resolved PartitionSpec prebound (feed_spec needs the value's
        ndim, which the schema fixes)."""
        import jax
        from jax.sharding import NamedSharding
        ex = self.ex
        steps = []
        for (key, fetch), (node, item) in zip(
                self._steps, zip(self.sub.feed_nodes, schema)):
            if item[0] == "np" and node.sharding is None:
                spec = ex.dist_strategy.feed_spec(node, len(item[2]))
                sh = NamedSharding(ex.mesh, spec)
                want = _feed_dtype(node, item[1])
                if want != np.dtype(item[1]):
                    fetch = (lambda fd, n=node, s=sh, w=want:
                             ex._global_put(fd[n].astype(w), s))
                else:
                    fetch = (lambda fd, n=node, s=sh:
                             ex._global_put(fd[n], s))
            steps.append((key, fetch))
        self._steps = steps

    def _jax_fetch(self, node):
        """Fed device arrays: an identity memo skips the per-step
        committed-on-default-backend device walk for feeds that are the
        SAME array object step after step (the steady-state training
        loop); anything else takes the full ``_place_feed`` path once
        and is memoized if it came back untouched (weakly — see
        ``_vetted``)."""
        ex = self.ex
        vetted = self._vetted

        def fetch(fd):
            v = fd[node]
            if vetted.get(id(v)) is v:
                return v
            out = ex._place_feed(node, v)
            if out is v:
                vetted[id(v)] = v
            return out
        return fetch

    def _dataloader_fetch(self, node, name):
        """Dataloader feed: consume a pipelined device_put when the
        prefetched host batch is identical (by identity) to the batch
        the loader hands out; otherwise place inline through the general
        ``_place_feed`` (a ``func``-transformed loader may change
        container types batch to batch, so no dtype is baked here)."""
        ex = self.ex
        pre = self._pre
        import time as _time

        def fetch(fd, _node=node, _name=name):
            val = _node.get_arr(_name)
            entry = pre.pop(_node, None)
            if entry is not None and entry[0] is val:
                self._pipelined += 1
                return entry[1].result()
            # inline placement: timed, so start_feed_prefetch only
            # double-buffers batches whose copy outweighs the handoff
            t0 = _time.perf_counter()
            out = ex._place_feed(_node, val)
            self._dl_cost[_node] = (_time.perf_counter() - t0) * 1e6
            return out
        return fetch

    # -- per-step entry points ----------------------------------------------

    def place_feeds(self, feed_dict):
        feeds = {}
        for key, fetch in self._steps:
            feeds[key] = fetch(feed_dict)
        n = self._pipelined
        if n:
            self._pipelined = 0
            record_run_plan("feeds_pipelined", n)
        return feeds

    def start_feed_prefetch(self):
        """Issue step N+1's host→device feed transfers on a background
        thread (called right after step N's dispatch, so the copy
        overlaps the in-flight device work).  Only dataloader-backed
        feeds have a knowable next batch; ``run_steps`` pipelines
        caller-fed placeholders the same way from the driver side."""
        if not self._dl_entries:
            return
        pool = None
        min_us = pipeline_min_us()
        for node, place in self._dl_entries:
            if node in self._pre:
                continue
            # adaptive: a batch whose inline copy is cheaper than the
            # thread handoff stays inline (cost measured by the fetch
            # closure; unmeasured nodes stay inline too — step 0 always
            # places inline, so the measurement exists from step 1 on)
            cost = self._dl_cost.get(node)
            if cost is None or cost < min_us:
                continue
            if pool is None:
                pool = self.sub._ensure_feed_pool()
            try:
                host = node.get_next_arr(self.sub.name)
            except KeyError:    # no dataloader registered for this split
                continue
            self._pre[node] = (host,
                               pool.submit(_place_traced, place, host))
        if self._pre:
            record_run_plan("feed_pipeline_depth_hw", len(self._pre))


def _place_traced(place, host):
    """The prefetch pool's unit of work: the H2D copy, shown as a
    ``feed.h2d`` span on the feed-pipeline thread's track when tracing
    (one extra frame on a background thread otherwise)."""
    if not _TR.on:
        return place(host)
    t0 = _time.perf_counter_ns()
    out = place(host)
    _TR.complete("feed.h2d", t0, _time.perf_counter_ns(), cat="feed")
    return out


class PlanCache:
    """Per-SubExecutor schema → :class:`RunPlan` map (LRU-bounded) with
    hit/miss accounting and feed-schema-churn detection."""

    #: misses before churn detection speaks up
    _CHURN_MISSES = 4
    #: distinct shapes one feed node must show to count as churning
    _CHURN_SHAPES = 3

    def __init__(self, sub):
        self.sub = sub
        self.plans = OrderedDict()
        try:
            self.max = max(1, int(os.environ.get("HETU_RUN_PLAN_CACHE",
                                                 "8")))
        except ValueError:
            self.max = 8
        self.misses = 0
        self._last = None           # (nodes, vals, plan) identity memo
        self._shapes_seen = {}      # feed node -> set of shapes at misses
        self._schemas_seen = set()  # distinct schemas ever missed (capped)
        self._repeat_misses = 0     # misses on a schema seen BEFORE
        self._churn_warned = False

    def lookup(self, feed_dict):
        # identity fast path: the steady-state training loop feeds the
        # SAME array objects step after step — identical objects imply an
        # identical schema, so the schema fingerprint itself is skipped
        last = self._last
        if last is not None and len(feed_dict) == last[2]:
            nodes, vals, _, plan = last
            for node, v in zip(nodes, vals):
                if feed_dict.get(node, _DL_SENTINEL) is not v:
                    break
            else:
                record_run_plan("plan_cache_hit")
                return plan
        schema = _schema_of(self.sub, feed_dict)
        plan = self.plans.get(schema)
        if plan is not None:
            self.plans.move_to_end(schema)
            record_run_plan("plan_cache_hit")
        else:
            record_run_plan("plan_cache_miss")
            self.misses += 1
            self._note_churn(schema)
            plan = RunPlan(self.sub, schema, feed_dict)
            self.plans[schema] = plan
            while len(self.plans) > self.max:
                self.plans.popitem(last=False)
        nodes = tuple(self.sub.feed_nodes)
        vals = tuple(feed_dict.get(n, _DL_SENTINEL) for n in nodes)
        nfed = sum(1 for v in vals if v is not _DL_SENTINEL)
        self._last = (nodes, vals, nfed, plan)
        return plan

    def _note_churn(self, schema):
        """feed-schema-churn: successive ``run()`` calls KEEP missing the
        plan cache because some feed's shape ping-pongs (an unbucketed
        ragged batch) — every re-plan retraces/compiles a fresh XLA
        program, which swamps any dispatch-path win.  A fixed bucket set
        is NOT churn: each bucket misses once while warming and hits
        forever after, so the warning requires SUSTAINED misses — either
        a schema missing AGAIN after it was already planned (evicted and
        cycling back), or more distinct schemas than the cache can hold.
        Warned once per subgraph, PR 5 diagnostic style (rule name,
        offending node, creation site, concrete fix)."""
        if self._churn_warned:
            return
        if schema in self._schemas_seen:
            self._repeat_misses += 1
        elif len(self._schemas_seen) < 64:
            self._schemas_seen.add(schema)
        for node, item in zip(self.sub.feed_nodes, schema):
            if len(item) < 3:
                continue    # no shape to track (dl / py feeds)
            seen = self._shapes_seen.setdefault(node, set())
            if len(seen) < 8:
                seen.add(tuple(item[2]))
        if self.misses < self._CHURN_MISSES:
            return
        if self._repeat_misses < 2 and len(self._schemas_seen) <= self.max:
            return      # bucket warm-up, not sustained churn
        churners = [(node, shapes) for node, shapes in
                    self._shapes_seen.items()
                    if len(shapes) >= self._CHURN_SHAPES]
        if not churners:
            return
        self._churn_warned = True
        from ..analysis.lint import Diagnostic
        node, shapes = churners[0]
        shown = ", ".join(str(s) for s in sorted(shapes)[:4])
        if len(self._schemas_seen) > self.max and len(shapes) <= 16:
            # a FIXED bucket set merely larger than the plan cache: the
            # per-shape XLA executables stay cached inside the one jit —
            # only the cheap Python plan rebuilds — so the actionable
            # fix is a bigger plan cache, not (re-)bucketing
            fix = (f"this looks like a fixed bucket set larger than the "
                   f"plan cache (bound {self.max}) — raise "
                   f"HETU_RUN_PLAN_CACHE to cover every bucket")
        else:
            fix = ("each genuinely new shape also retraces/compiles a "
                   "fresh XLA program; bucket ragged batches to a small "
                   "fixed set of shapes (pad to the mod-128 buckets the "
                   "flash kernel entry uses, or fix the dataloader "
                   "batch size)")
        diag = Diagnostic(
            "feed-schema-churn", "warn",
            f"feed shapes for placeholder '{node.name}' keep missing "
            f"the run-plan cache across run() calls (saw {shown}"
            f"{', ...' if len(shapes) > 4 else ''}; {self.misses} misses "
            f"so far) — {fix}", node)
        warnings.warn(str(diag), UserWarning, stacklevel=5)


class KeyedPlanCache:
    """Keyed dispatch-plan cache for planes that resolve their own step
    closures (the decode engine's per-(batch, len)-bucket step plans:
    feed-key order, donation layout, placement — the serving analogue of
    what :class:`RunPlan` prebinds per schema).  Same accounting contract
    as :class:`PlanCache`: every lookup records ``plan_cache_hit`` or
    ``plan_cache_miss``, so the steady-state claim — the 100-request
    stream's per-token dispatch is a plan-cache hit — is provable from
    the one counter family the overhead bench already watches."""

    def __init__(self, max_entries=32):
        self.plans = OrderedDict()
        self.max = max(1, int(max_entries))

    def lookup(self, key, build):
        """The plan for ``key`` — built by ``build()`` on first sight,
        replayed from the cache (LRU-refreshed) after."""
        plan = self.plans.get(key)
        if plan is not None:
            self.plans.move_to_end(key)
            record_run_plan("plan_cache_hit")
            return plan
        record_run_plan("plan_cache_miss")
        plan = build()
        self.plans[key] = plan
        while len(self.plans) > self.max:
            self.plans.popitem(last=False)
        return plan


__all__ = ["RunPlan", "PlanCache", "KeyedPlanCache",
           "feed_pipeline_enabled"]
