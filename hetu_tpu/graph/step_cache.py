"""Compiled-step cache: reuse jitted training steps across Executor
instances.

Rebuilding an Executor over a structurally identical graph (bench re-runs
in one process, `tools/hlo_audit.py --config all`, a supervisor-driven
reconstruction) used to pay the full trace + XLA compile again, because
each SubExecutor owned a private ``jax.jit``.  Here the jitted step is
cached process-wide, keyed on a structural SIGNATURE of everything that
determines the traced program: the topo (op types, attrs — constant
arrays hashed by content —, edges, placeholder shapes/dtypes), the fetch
layout, the optimizer hyperparameters, the mesh fingerprint, and the
executor knobs (compute_dtype, zero stage + bucket size, pipeline,
microbatches, remat, matmul precision).  Canonical topo-ordinal input
keys (``Executor._k``) make two same-shaped graphs produce byte-identical
pytree structures, so the cached callable accepts the new instance's
inputs directly.

Anything the signature cannot prove hashable (an Op or unknown object
inside ``attrs``) makes the graph UNCACHABLE — counted, never
wrong-cached.  PS-backed subgraphs are uncachable by policy: a cached
step pins its builder executor alive through the closure, and a PS
executor's teardown contract ("del executor closes its embedding
caches/pools") must keep working.  ``HETU_STEP_CACHE=0`` disables the
cache; entries are LRU-bounded (``HETU_STEP_CACHE_MAX``, default 8)
because of that same executor pinning.

Cross-process reuse (the supervisor's post-restart resume) rides jax's
persistent compilation cache instead (``JAX_COMPILATION_CACHE_DIR``, or
``<checkout>/.jax_cache`` when the environment names none — see
``graph.executor.configure_compile_cache``): the byte-identical HLO a
canonical-key rebuild produces becomes a disk cache hit.
"""
from __future__ import annotations

import hashlib
import os
from collections import OrderedDict

import numpy as np

from ..metrics import record_step_cache
from ..obs.compile_log import name_program
from ..obs.lock_witness import make_lock as _make_lock

_CACHE = OrderedDict()          # signature -> jitted step
#: serving executables (hetu_tpu.serving.InferenceExecutor): signature
#: already folds the bucket in, so one entry pins one (graph, bucket)
#: compiled program.  Separate from _CACHE because serving graphs MAY be
#: PS-backed (rows ride as per-call inputs, so the compiled code never
#: touches the store — the teardown-contract argument that makes PS
#: training graphs uncachable does not apply) and because a serving fleet
#: legitimately pins one executable per bucket (own size bound).
_SERVE_CACHE = OrderedDict()
_LOCK = _make_lock("step_cache._LOCK")


class _Uncachable(Exception):
    pass


def enabled():
    return os.environ.get("HETU_STEP_CACHE", "1") != "0"


def _max_entries():
    try:
        return max(1, int(os.environ.get("HETU_STEP_CACHE_MAX", "8")))
    except ValueError:
        return 8


def _feed(h, *parts):
    for p in parts:
        h.update(str(p).encode())
        h.update(b"\x00")


def _hash_value(h, v, depth=0):
    """Hash an attr value by CONTENT; unknown types raise _Uncachable
    (silently skipping them could alias two different programs)."""
    if depth > 6:
        raise _Uncachable("attr nesting too deep")
    if v is None or isinstance(v, (bool, int, float, complex, str, bytes)):
        _feed(h, type(v).__name__, repr(v))
    elif isinstance(v, (np.generic,)):
        _feed(h, "npscalar", v.dtype.str, repr(v.item()))
    elif isinstance(v, np.ndarray):
        _feed(h, "ndarray", v.dtype.str, v.shape)
        h.update(np.ascontiguousarray(v).tobytes())
    elif isinstance(v, (list, tuple)):
        _feed(h, type(v).__name__, len(v))
        for item in v:
            _hash_value(h, item, depth + 1)
    elif isinstance(v, dict):
        _feed(h, "dict", len(v))
        for k in sorted(v, key=repr):
            _feed(h, repr(k))
            _hash_value(h, v[k], depth + 1)
    elif callable(v):
        # hash by CODE + captured state, not by name: op lowering fns are
        # often module-level lambdas (same code every build), and factory-
        # made closures are equal iff their cell contents are
        code = getattr(v, "__code__", None)
        if code is None:
            import functools
            if isinstance(v, functools.partial):
                _hash_value(h, v.func, depth + 1)
                _hash_value(h, list(v.args), depth + 1)
                _hash_value(h, dict(v.keywords), depth + 1)
                return
            raise _Uncachable(
                f"callable of type {type(v).__name__} has no code object")
        _feed(h, "fn", getattr(v, "__module__", ""),
              getattr(v, "__qualname__", ""))
        _hash_code(h, code)
        for cell in getattr(v, "__closure__", None) or ():
            _hash_value(h, cell.cell_contents, depth + 1)
        for d in getattr(v, "__defaults__", None) or ():
            _hash_value(h, d, depth + 1)
    elif hasattr(v, "dtype") and hasattr(v, "shape"):   # jax array const
        _feed(h, "devarray", str(v.dtype), tuple(v.shape))
        h.update(np.ascontiguousarray(np.asarray(v)).tobytes())
    else:
        raise _Uncachable(f"unhashable attr of type {type(v).__name__}")


def _hash_code(h, code, depth=0):
    """Hash a code object by content (bytecode + names + nested code) —
    address-free, so two module reloads of the same source agree."""
    if depth > 4:
        raise _Uncachable("code nesting too deep")
    _feed(h, "code", code.co_code.hex(), code.co_names,
          code.co_varnames[:code.co_argcount])
    for c in code.co_consts:
        if hasattr(c, "co_code"):
            _hash_code(h, c, depth + 1)
        else:
            _feed(h, repr(c))


def _hash_optimizer(h, opt):
    from ..optim.lr_scheduler import LRScheduler
    from ..optim.optimizer import traced_lr_fn
    _feed(h, "opt", type(opt).__module__, type(opt).__qualname__)
    # lr: a TRACED schedule (constant float or pure step-indexed
    # scheduler, graph/run_plan.py) is baked into the compiled program —
    # hash its full definition, or two executors differing only in lr
    # would alias one compiled step.  A host-path lr (data-dependent
    # scheduler, HETU_TRACED_LR=0) rides as a runtime input, never baked.
    if traced_lr_fn(opt) is not None:
        sched = opt.lr
        if isinstance(sched, LRScheduler):
            _feed(h, "lr-sched", type(sched).__module__,
                  type(sched).__qualname__)
            for k in sorted(sched.__dict__):
                _feed(h, k)
                _hash_value(h, sched.__dict__[k])
        else:
            _feed(h, "lr-const")
            _hash_value(h, float(sched))
    for k in sorted(opt.__dict__):
        if k == "lr":
            continue    # handled above (traced) or a runtime input (host)
        v = opt.__dict__[k]
        if isinstance(v, LRScheduler):
            continue    # schedulers only shape host_lr, never the trace
        # every other attr may be baked into apply()'s traced math —
        # content-hash it; an unhashable type raises _Uncachable (the
        # _hash_value policy: silently skipping could alias two programs)
        _feed(h, k)
        _hash_value(h, v)


def _mesh_fingerprint(mesh):
    if mesh is None:
        return "nomesh"
    devs = tuple((d.id, d.platform, d.process_index)
                 for d in mesh.devices.flat)
    return f"{tuple(mesh.axis_names)}|{tuple(mesh.devices.shape)}|{devs}"


def _hash_nodes(h, topo, fetches, key_fn):
    """Hash the structural graph content shared by the training and
    serving signatures: the fetch layout + every node's type, canonical
    key, edges, placeholder declaration, optimizer hypers and attrs.
    Returns the topo-ordinal map for callers that hash extras.

    Op entries hash as topo ordinals, NOT repr: node reprs embed
    process-global ids that differ on every structurally identical
    rebuild, which would guarantee a cache miss for exactly the rebuilds
    the cache exists for."""
    from .node import PlaceholderOp
    from ..optim.optimizer import OptimizerOp
    ordinal = {n: i for i, n in enumerate(topo)}
    _feed(h, "fetches",
          tuple(None if f is None else ordinal.get(f, -1)
                for f in fetches))
    for i, node in enumerate(topo):
        # key_fn(node) is part of the signature: the cached closure
        # addresses its inputs by the BUILDER's canonical keys, so a
        # same-shaped subgraph living at different global-topo
        # ordinals (extra sibling subgraphs) must not hit
        _feed(h, i, node.op_type, key_fn(node),
              tuple(ordinal[inp] for inp in node.inputs),
              node.sharding, getattr(node, "is_ps", False))
        lf = getattr(node, "_lower_fn", None)
        if lf is not None:
            _hash_value(h, lf)
        if isinstance(node, PlaceholderOp):
            _feed(h, "ph", node.shape, np.dtype(node.dtype).str
                  if node.dtype is not None else None,
                  node.trainable, node.is_variable,
                  getattr(node, "is_embed", False),
                  getattr(node, "width", None))
        if isinstance(node, OptimizerOp):
            _hash_optimizer(h, node.optimizer)
        if getattr(node, "index", None) is not None:
            _feed(h, "idx", node.index)
        for k in sorted(node.attrs):
            _feed(h, "attr", k)
            _hash_value(h, node.attrs[k])
    return ordinal


def signature(sub):
    """Structural fingerprint of one SubExecutor's step, or None when the
    graph contains something content-hashing cannot cover."""
    from .node import Op
    ex = sub.ex
    h = hashlib.sha256()
    try:
        if getattr(sub, "ps_nodes", None):
            # a cached step pins its builder executor alive — fine for
            # pure-tensor graphs, but a PS-backed executor owns host
            # resources (embedding caches, worker pools) whose teardown
            # contract is "del executor closes them"
            raise _Uncachable("PS-backed subgraph pins host resources")
        import jax
        # v4: traced-lr schedules are part of the program (hashed in
        # _hash_optimizer); the env gate flips every optimizer between
        # the traced and host-input paths, so it keys the signature too.
        # ex.remat is the ISSUE 13 POLICY string, and the auto/full
        # segment plan's decision fingerprint rides along — two policies
        # (or two auto plans under different HBM budgets) must never
        # alias one compiled executable.  The auto-parallel plan
        # fingerprint (ISSUE 15) keys candidate plans measured
        # back-to-back: node shardings already hash below, but a plan can
        # differ with identical annotations (fsdp-via-zero defaults,
        # microbatch pricing) — and the measurement loop's
        # one-compile-per-candidate accounting needs distinct candidates
        # to be distinct entries
        _feed(h, "v4", os.environ.get("HETU_TRACED_LR", "1"),
              jax.__version__, jax.default_backend(),
              _mesh_fingerprint(ex.mesh),
              ex.compute_dtype, ex.matmul_precision, ex.remat,
              getattr(sub, "_remat_fingerprint", None),
              getattr(ex, "_plan_fingerprint", None),
              ex.pipeline, ex.num_microbatches, sub.name, sub.training,
              ex.zero, os.environ.get("HETU_ZERO_BUCKET_MB", ""),
              type(ex.dist_strategy).__name__ if ex.dist_strategy else "")
        ordinal = _hash_nodes(h, sub.topo, sub.fetches, ex._k)
        mf = ex._extra_config.get("microbatch_feeds")
        _feed(h, "mbf", None if mf is None else tuple(
            sorted((f"o{ordinal[n]}" if n in ordinal
                    else f"name:{n.name}") if isinstance(n, Op)
                   else str(n) for n in mf)))
    except _Uncachable:
        return None
    except Exception:
        return None     # a signature bug must never break step building
    return h.hexdigest()


def serve_signature(iex, bucket):
    """Structural fingerprint of one serving executable: the inference
    fetch subgraph (PS embedding leaves INCLUDED — their rows ride as
    per-call inputs, keyed like any feed) + the padded batch bucket +
    everything that shapes the compiled program (backend, mesh, donation,
    RNG seed — the serving key is baked into the trace; the auto-parallel
    plan fingerprint when the executor compiles under ``plan=``).  A
    rebuilt :class:`~hetu_tpu.serving.InferenceExecutor` over a
    structurally identical graph reuses the compiled executable per
    bucket instead of retracing (the serving analogue of the training
    step cache; restart reuse across processes rides jax's persistent
    compilation cache exactly like training).

    ``bucket``: the padded batch bucket (int), or a tuple for the
    autoregressive-decode plane — a (batch_bucket, len_bucket) pair for
    the one-token entry, a (batch_bucket, chunk_bucket, len_bucket)
    triple for the chunked-prefill entry (ISSUE 18) — each key pins its
    own executable, which is what lets the decode counters prove at
    most one compile per bucket key."""
    h = hashlib.sha256()
    try:
        import jax
        bkey = tuple(int(b) for b in bucket) \
            if isinstance(bucket, (tuple, list)) else int(bucket)
        _feed(h, "serve-v2", jax.__version__, jax.default_backend(),
              _mesh_fingerprint(iex.mesh), bkey,
              bool(iex.donate), iex.seed,
              getattr(iex, "_plan_fingerprint", None))
        _hash_nodes(h, iex.topo, iex.fetches, iex._k)
    except _Uncachable:
        return None
    except Exception:
        return None     # a signature bug must never break serving
    return h.hexdigest()


def lookup_or_build(sub, step_fn):
    """Return a jitted step for ``sub``: a cached one when an identical
    build exists, else ``jax.jit(step_fn)`` (stored for the next build)."""
    import jax
    # the name jax reports this program under (obs/compile_log.py)
    name_program(step_fn, "train", sub.name)
    if not enabled():
        return jax.jit(step_fn, donate_argnums=(0, 2))
    sig = signature(sub)
    if sig is None:
        record_step_cache("step_cache_uncachable")
        return jax.jit(step_fn, donate_argnums=(0, 2))
    with _LOCK:
        hit = _CACHE.get(sig)
        if hit is not None:
            _CACHE.move_to_end(sig)
            record_step_cache("step_cache_hit")
            return hit
    fn = jax.jit(step_fn, donate_argnums=(0, 2))
    with _LOCK:
        record_step_cache("step_cache_miss")
        _CACHE[sig] = fn
        while len(_CACHE) > _max_entries():
            _CACHE.popitem(last=False)
    return fn


def _max_serve_entries():
    """Serving pins one executable per (graph, bucket) — a router over 8
    buckets must not evict its own working set, so the bound is separate
    from (and larger than) the training cache's."""
    try:
        return max(1, int(os.environ.get("HETU_STEP_CACHE_SERVE_MAX",
                                         "32")))
    except ValueError:
        return 32


def lookup_or_build_serve(iex, bucket, infer_fn):
    """Return a jitted serving step for ``(iex, bucket)``: a cached one
    when a structurally identical build exists (cross-rebuild reuse),
    else a fresh ``jax.jit`` (stored for the next build).  Feeds are
    DONATED (``infer_fn(params, feeds)`` — params are the read-only
    weights and are never donated)."""
    import jax
    from ..metrics import record_serve
    donate = (1,) if iex.donate else ()
    # a tuple is the decode plane's key: (batch, len) for the one-token
    # entry, (batch, chunk, len) for a chunked one
    if isinstance(bucket, (tuple, list)):
        bb, *chunk, lb = bucket
        name_program(infer_fn, "decode",
                     f"b{bb}:c{chunk[0] if chunk else 1}:l{lb}")
    else:
        name_program(infer_fn, "serve", f"b{bucket}")

    def build():
        # the compile-once evidence: one count a jit WRAPPER constructed
        # — a cross-rebuild cache hit below constructs none and must not
        # inflate the counter the acceptance check compares to the
        # number of distinct buckets used.  The XLA compile happens at
        # the wrapper's first call and leaves its own record
        # (obs/compile_log.py: ``compile_counts()`` "<owner>:programs")
        record_serve("serve_bucket_compiles")
        asked = iex.compiler_options()
        return jax.jit(infer_fn, donate_argnums=donate,
                       **({"compiler_options": asked} if asked else {}))

    if not enabled():
        return build()
    sig = serve_signature(iex, bucket)
    if sig is None:
        record_step_cache("step_cache_serve_uncachable")
        return build()
    with _LOCK:
        hit = _SERVE_CACHE.get(sig)
        if hit is not None:
            _SERVE_CACHE.move_to_end(sig)
            record_step_cache("step_cache_serve_hit")
            return hit
    fn = build()
    with _LOCK:
        record_step_cache("step_cache_serve_miss")
        _SERVE_CACHE[sig] = fn
        while len(_SERVE_CACHE) > _max_serve_entries():
            _SERVE_CACHE.popitem(last=False)
    return fn


def clear():
    """Drop every cached step (tests; frees the pinned builder executors)."""
    with _LOCK:
        _CACHE.clear()
        _SERVE_CACHE.clear()


__all__ = ["signature", "serve_signature", "lookup_or_build",
           "lookup_or_build_serve", "clear", "enabled"]
