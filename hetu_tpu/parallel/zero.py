"""ZeRO-style cross-replica sharding of the weight update.

Reference: "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" (Xu et al., PAPERS.md) — instead of every replica
paying the full Adam update (and 3x param memory for m/v), the gradient is
reduce-SCATTERED over the ``dp`` axis, each replica updates only its 1/dp
slice of every parameter, and the updated params are all-GATHERED back.
GC3's collective-scheduling framing (PAPERS.md) supplies the overlap
discipline: at stage 3 the gather of step N's params moves INTO step N+1's
program, where XLA's async scheduler overlaps it with early compute.

TPU-native realization: no hand-inserted collectives.  The update runs
under GSPMD sharding CONSTRAINTS — grads and optimizer state are pinned to
a ``PartitionSpec('dp', None)`` slab layout, so the SPMD partitioner emits
the reduce-scatter / all-gather pair itself (the paper's "automatic"
half), while this module owns the layout: every parameter is flattened,
padded to a ``dp`` multiple and packed into fixed buckets
(``HETU_ZERO_BUCKET_MB``), so arbitrary shapes shard evenly and small
params ride one collective instead of one each.

Stages (``Executor(zero=...)`` / ``HETU_ZERO``):

* ``1`` — shard optimizer state only: grads stay replicated (XLA
  all-reduces them as before), each replica updates its slice, params are
  all-gathered.  Memory win: optimizer moments / dp.
* ``2`` — stage 1 + the grad slab is constrained to the sharded layout, so
  the partitioner may lower the mean-loss reduction as a reduce-scatter
  (it does on TPU; XLA-CPU lowers it as all-reduce + slice): transient
  grad buffers shrink to 1/dp too.
* ``3`` — stage 2 + master params LIVE sharded between steps: the step
  consumes and returns slabs, and the all-gather of step N's updated
  params happens at the top of step N+1 where it overlaps forward
  compute.  Param memory between steps drops to 1/dp as well.

Bitwise discipline (the parity tests assert EXACT equality with the
replicated path for sgd / adam / adamw, stages 1-3): two things hold it.

* The WHOLE update chain (moment updates, the ``p - lr*upd`` axpy) is
  computed under the slab sharding before anything is gathered.  If the
  final subtract is left outside the sharded region, the partitioner
  gathers ``p`` and ``lr*upd`` separately and the mul+sub lands in two
  fusions — losing the FMA contraction the replicated program gets.
  Hence every intermediate below is explicitly re-constrained to the slab
  spec.
* The update arithmetic itself leaves the backend no choice of
  contraction.  The slab layout moves elements between a loop's vector
  body and its scalar tail, and XLA:CPU contracts ``a*b + c*d`` into an
  FMA around a different product in the two (the optimised HLO of the
  sharded and the replicated Adam update is op-for-op identical; only the
  emitted loops differ).  That was the 1-ulp drift of ROADMAP D0; Adam's
  moments are therefore written in lerp form, one product per add
  (``optim/optimizer.py``).  An optimizer whose update still sums two
  products (Momentum's ``mu*v - lr*g``) is NOT covered by the bitwise
  promise: expect last-ulp differences per step there.

Bitwise equality is promised where the compiled arithmetic is the same —
sharded vs replicated update as above, sync vs async stepping, replication
on vs off.  Across a change of reduction order (dp=N vs one device: the
psum of per-shard sums) the guarantee is the dp parity gate, rtol 2e-4 on
the loss (``tests/test_parallel.py``), not equality.
"""
from __future__ import annotations

import os
from dataclasses import dataclass, field

import numpy as np

from ..metrics import record_zero

#: the data-parallel mesh axis the weight update shards over
ZERO_AXIS = "dp"

#: default collective bucket size (MB); 0 = one bucket per parameter
DEFAULT_BUCKET_MB = 4.0


def bucket_bytes():
    """Configured bucket size in bytes (``HETU_ZERO_BUCKET_MB``)."""
    try:
        mb = float(os.environ.get("HETU_ZERO_BUCKET_MB",
                                  str(DEFAULT_BUCKET_MB)))
    except ValueError:
        mb = DEFAULT_BUCKET_MB
    return int(mb * 2**20)


@dataclass
class ZeroBucket:
    """One fused collective: a group of params packed into a flat slab.

    The slab layout is ``concat(flatten(p) for p in params) + zero pad``
    reshaped to ``(dp, width)`` — contiguous, so packing/unpacking is pure
    data movement (bitwise-preserving) and the per-device row is exactly
    the replica's 1/dp slice."""

    key: str                    # step-input key of this bucket's slab
    param_keys: list            # canonical step keys of member params
    shapes: list                # original array shapes, same order
    offsets: list               # start of each param in the flat concat
    numel: int                  # total unpadded elements
    dp: int
    dtype: str = "float32"

    @property
    def padded(self):
        return -(-self.numel // self.dp) * self.dp

    @property
    def pad(self):
        return self.padded - self.numel

    @property
    def width(self):
        return self.padded // self.dp

    @property
    def nbytes(self):
        return self.padded * np.dtype(self.dtype).itemsize


@dataclass
class ZeroPlan:
    """Per-OptimizerOp sharding plan: stage + bucket layout."""

    stage: int
    dp: int
    buckets: list = field(default_factory=list)
    axis: str = ZERO_AXIS

    @property
    def param_keys(self):
        return [k for b in self.buckets for k in b.param_keys]


def resolve_stage(value):
    """Normalize a user/env zero setting to an int stage in {0,1,2,3}."""
    if value is None or value is False:
        return 0
    if value is True:
        return 2            # the canonical reduce-scatter mode
    try:
        stage = int(value)
    except (TypeError, ValueError):
        stage = -1          # HETU_ZERO=on etc. get the range message
    if stage < 0 or stage > 3:
        raise ValueError(f"zero={value!r}: expected a stage in 0..3 "
                         "(0=off, 1=opt-state, 2=+reduce-scatter, "
                         "3=+sharded params)")
    return stage


def ineligible_reason(param, dtype):
    """Why ``param`` keeps its WHOLE optimizer off the ZeRO plan, or
    ``None`` if it doesn't.

    Single source of truth for the eligibility filter, shared by the
    executor's plan builder (``Executor._build_zero_plans``) and the
    ``zero-sharding`` lint rule so the two can never drift: an explicit
    sharding annotation marks a model-parallel layout the dp slab
    packing (and stage <3's replicated gather) would silently destroy,
    and a non-float dtype has no moments worth sharding.  ``dtype=None``
    (shape inference failed) is treated as eligible — the lint side
    reports uninferable nodes separately.
    """
    if any(s is not None for s in (getattr(param, "sharding", None) or ())):
        return ("carries an explicit sharding annotation "
                "(model parallelism)")
    if dtype is not None and not np.issubdtype(np.dtype(dtype),
                                               np.floating):
        return f"is not a float array (dtype {np.dtype(dtype).name})"
    return None


def build_plan(param_items, dp, stage, max_bytes=None, per_param=False,
               prefix=""):
    """Pack ``param_items`` (``[(key, shape, dtype), ...]`` in a stable
    order) into buckets of at most ``max_bytes`` each.

    ``per_param=True`` forces one bucket per parameter — required by
    LAMB-style optimizers whose update needs per-PARAMETER norms, and by
    stage 3 consumers that restore individual params into their slab.
    Params are grouped by dtype (a slab is one homogeneous buffer).
    ``prefix`` namespaces the bucket keys (several OptimizerOps' slabs
    share one step-input dict)."""
    if max_bytes is None:
        max_bytes = bucket_bytes()
    plan = ZeroPlan(stage=stage, dp=dp)
    cur = None
    for key, shape, dtype in param_items:
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        dts = np.dtype(dtype).name
        itemsize = np.dtype(dtype).itemsize
        if (per_param or cur is None or cur.dtype != dts
                or (cur.numel + size) * itemsize > max_bytes):
            cur = ZeroBucket(key=f"{prefix}zb{len(plan.buckets)}",
                             param_keys=[],
                             shapes=[], offsets=[], numel=0, dp=dp,
                             dtype=dts)
            plan.buckets.append(cur)
        cur.param_keys.append(key)
        cur.shapes.append(tuple(shape))
        cur.offsets.append(cur.numel)
        cur.numel += size
    return plan


# -- shardings ---------------------------------------------------------------

def slab_sharding(mesh, axis=ZERO_AXIS):
    from jax.sharding import NamedSharding
    from .collectives import slab_spec
    return NamedSharding(mesh, slab_spec(axis))


def replicated_sharding(mesh):
    from jax.sharding import NamedSharding
    from .collectives import replicated_spec
    return NamedSharding(mesh, replicated_spec())


# -- slab packing (trace-time: pure data movement, bitwise-preserving) -------

def pack_slab(vals, bucket):
    """``{param_key: array}`` → ``(dp, width)`` slab (flatten+concat+pad)."""
    import jax.numpy as jnp
    flat = [jnp.ravel(vals[k]) for k in bucket.param_keys]
    cat = flat[0] if len(flat) == 1 else jnp.concatenate(flat)
    if bucket.pad:
        cat = jnp.pad(cat, (0, bucket.pad))
    return cat.reshape(bucket.dp, bucket.width)


def unpack_slab(slab, bucket):
    """Inverse of :func:`pack_slab` → ``{param_key: array}``."""
    flat = slab.reshape(-1)
    out = {}
    for k, shape, off in zip(bucket.param_keys, bucket.shapes,
                             bucket.offsets):
        size = int(np.prod(shape, dtype=np.int64)) if shape else 1
        seg = flat[off:off + size]
        out[k] = seg.reshape(shape)
    return out


def host_pack_slab(np_vals, bucket):
    """Host-side (numpy) slab packing for initial placement / restore."""
    flat = [np.asarray(np_vals[k], np.dtype(bucket.dtype)).reshape(-1)
            for k in bucket.param_keys]
    cat = flat[0] if len(flat) == 1 else np.concatenate(flat)
    if bucket.pad:
        cat = np.pad(cat, (0, bucket.pad))
    return cat.reshape(bucket.dp, bucket.width)


def host_unpack_slab(slab, bucket):
    """Host-side inverse: slab (numpy) → ``{param_key: array}``."""
    return unpack_slab(np.asarray(slab), bucket)


# -- the sharded update ------------------------------------------------------

def gather_full(slab, bucket, mesh):
    """All-gather a slab back to the full per-param arrays (inside jit).

    At stage 3 this runs at the TOP of the next step, where XLA's async
    scheduler can overlap the gather with compute that does not yet need
    these params (GC3's scheduling discipline)."""
    import jax
    full = jax.lax.with_sharding_constraint(slab, replicated_sharding(mesh))
    record_zero("zero_all_gather_bytes", bucket.nbytes)
    return unpack_slab(full, bucket)


def apply_sharded(optimizer, plan, params, grads, state, lr, mesh):
    """One optimizer step with the update sharded over ``dp``.

    ``params``/``grads``: full arrays keyed by canonical param key
    (stages 1/2), or — stage 3 — ``params`` holds ``(dp, width)`` slabs
    keyed by bucket key (grads are always full: they fall out of
    ``jax.grad`` in param shape).  ``state`` is the slab-layout state this
    module's plan initialized.  Returns ``(new_params, new_state)`` where
    ``new_params`` is keyed like ``params`` came in (full per-param
    updates for stages 1/2; new slabs for stage 3).

    Counter semantics (``HetuProfiler.zero_counters()``): recorded per
    TRACE like the flash-fallback counters — a growing count across steps
    means the jit cache is thrashing."""
    import jax

    slab_sh = slab_sharding(mesh, plan.axis)
    p_slabs, g_slabs = {}, {}
    for b in plan.buckets:
        g = pack_slab(grads, b)
        if plan.stage >= 2:
            # pin the grad slab to the sharded layout: the partitioner may
            # now satisfy the mean-loss reduction with a reduce-scatter
            # instead of a full all-reduce (the paper's core move)
            g = jax.lax.with_sharding_constraint(g, slab_sh)
            record_zero("zero_reduce_scatter_bytes", b.nbytes)
        if plan.stage >= 3:
            p = params[b.key]           # already a slab
        else:
            p = pack_slab(params, b)
        # params enter the update sharded even when replicated outside:
        # slicing a replicated buffer is free, and it keeps the WHOLE
        # update chain inside the sharded region (see module docstring)
        p = jax.lax.with_sharding_constraint(p, slab_sh)
        record_zero("zero_pad_bytes",
                    b.pad * np.dtype(b.dtype).itemsize)
        p_slabs[b.key], g_slabs[b.key] = p, g

    new_slabs, new_state = optimizer.apply(p_slabs, g_slabs, state, lr)

    def _pin(x):
        if hasattr(x, "ndim") and x.ndim == 2:
            return jax.lax.with_sharding_constraint(x, slab_sh)
        return x                        # scalars (Adam t) stay replicated

    # re-constrain every slab-shaped output: the new params AND the new
    # moments must be COMPUTED sharded (bitwise discipline + they must
    # leave the step still sharded so the donated buffers stay 1/dp)
    new_slabs = {k: _pin(v) for k, v in new_slabs.items()}
    new_state = jax.tree.map(_pin, new_state)

    if plan.stage >= 3:
        return new_slabs, new_state
    upd = {}
    for b in plan.buckets:
        upd.update(gather_full(new_slabs[b.key], b, mesh))
    return upd, new_state


__all__ = ["ZERO_AXIS", "ZeroBucket", "ZeroPlan", "resolve_stage",
           "ineligible_reason", "build_plan", "bucket_bytes",
           "slab_sharding",
           "replicated_sharding", "pack_slab", "unpack_slab",
           "host_pack_slab", "host_unpack_slab", "gather_full",
           "apply_sharded"]
