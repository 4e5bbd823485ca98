"""hetu_tpu.autoparallel — Galvatron-parity hybrid-parallel strategy search.

Workflow (reference ``tools/Galvatron/README.md:15-100``):

1. **profile** — measure device flops + collective bandwidths
   (:class:`hetu_tpu.profiler.CollectiveProfiler`) or supply a
   :class:`HardwareSpec`;
2. **search** — :func:`search` runs the layerwise DP algorithm
   (:class:`DPAlg`) over (pp, tp, dp, fsdp) candidates under the memory
   budget;
3. **train** — :meth:`ParallelPlan.strategy` + :meth:`ParallelPlan.apply`
   hand the result to the executor as a mesh + GSPMD sharding annotations.
"""
from .cost_model import (HardwareSpec, LayerSpec, MemoryCostModel, Strategy,
                         TimeCostModel, transformer_layer_spec,
                         attention_layer_spec, mlp_layer_spec,
                         embedding_layer_spec, model_layer_specs,
                         swin_layer_specs, graph_layer_spec,
                         graph_layer_specs, bert_split)
from .search import DPAlg, candidate_strategies, search, search_graph
from .plan import ParallelPlan
from .measure import (PlanMeasurement, measure_plan, measure_plans,
                      plan_diff, format_plan_diff, graph_flops,
                      device_peak_flops)


def calibrate_hardware(mesh=None, mem_bytes=None,
                       matmul_dim=4096, chain=64,
                       probe_bytes=1 << 22, **overrides):
    """Measure a HardwareSpec from the live devices (profile step of the
    Galvatron workflow): matmul-probe flops + collective bandwidth."""
    import time

    import jax
    import jax.numpy as jnp

    from ..graph.executor import _sync_outs
    from ..profiler import CollectiveProfiler

    n = matmul_dim

    def probe(a, length):
        # data-dependent matmul chain: each product feeds the next, so
        # the device cannot overlap or elide any of them
        def body(y, _):
            return y @ a, None
        y, _ = jax.lax.scan(body, a, None, length=length)
        return y

    if chain < 2:
        raise ValueError("calibrate_hardware needs chain >= 2 (the probe "
                         "subtracts a 1-matmul latency baseline)")
    x = jax.random.normal(jax.random.PRNGKey(0), (n, n), jnp.bfloat16) * 0.01
    f = jax.jit(probe, static_argnums=1)
    _sync_outs([f(x, chain), f(x, 1)])  # warm both lengths
    reps = 3

    def timed(length):
        # best-of-reps suppresses scheduler noise (a single noisy sample
        # can otherwise make dt < lat and nonsense flops)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            _sync_outs([f(x, length)])
            best = min(best, time.perf_counter() - t0)
        return best

    lat = timed(1)
    dt = timed(chain)
    per_matmul = (dt - lat) / (chain - 1)
    if per_matmul <= 0:  # noise floor: fall back to the un-baselined rate
        per_matmul = dt / chain
    flops = 2 * n ** 3 / per_matmul
    prof = CollectiveProfiler(mesh=mesh, repeats=3)
    width = prof.mesh.shape[prof.axis]
    if width > 1:
        ar = prof.profile_allreduce(probe_bytes)
        ici_bw = (probe_bytes * 2 * (width - 1) / width / ar) if ar > 0 \
            else HardwareSpec.ici_bw
        overlap = measure_overlap(prof.mesh, prof.axis, probe_bytes,
                                  matmul_dim=min(matmul_dim, 1024))
    else:  # bandwidth unmeasurable on a 1-wide axis; keep the defaults
        ici_bw = HardwareSpec.ici_bw
        overlap = HardwareSpec.overlap
    dev = jax.local_devices()[0]
    if mem_bytes is None:
        stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
        mem_bytes = (stats or {}).get("bytes_limit", 16e9)
    kw = dict(flops=flops, mem_bytes=float(mem_bytes),
              ici_bw=float(ici_bw), overlap=float(overlap))
    kw.update(overrides)
    return HardwareSpec(**kw)


def measure_overlap(mesh, axis, probe_bytes=1 << 22, matmul_dim=1024,
                    repeats=3):
    """Measured compute/communication overlap coefficient ∈ [0, 1]
    (Galvatron profiles this as overlap_coe, ``utils/cost_model.py:38``;
    the round-2 spec used a guessed constant).

    Times three jitted shard_map programs — compute-only (matmul chain),
    comm-only (psum), and both with independent dataflow so XLA may
    schedule them concurrently — and reports what fraction of the shorter
    phase was hidden: ``(t_comp + t_comm - t_both) / min(t_comp, t_comm)``.
    """
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..graph.executor import _sync_outs

    n = mesh.shape[axis]
    elems = max(128, probe_bytes // 4)
    buf = jax.device_put(jnp.zeros((n, elems), jnp.float32),
                         NamedSharding(mesh, P(axis, None)))
    a = jax.device_put(
        jnp.full((n, matmul_dim, matmul_dim), 1e-3, jnp.bfloat16),
        NamedSharding(mesh, P(axis, None, None)))

    def compute(v):                       # per-device matmul chain
        y = v
        for _ in range(4):
            y = y @ v
        return jnp.sum(y, dtype=jnp.float32).reshape(1)

    def comm(b):
        return jnp.sum(jax.lax.psum(b, axis)[:1],
                       dtype=jnp.float32).reshape(1)

    f_comp = jax.jit(jax.shard_map(
        lambda v, b: compute(v), mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None)), out_specs=P(axis)))
    f_comm = jax.jit(jax.shard_map(
        lambda v, b: comm(b), mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None)), out_specs=P(axis)))
    f_both = jax.jit(jax.shard_map(
        lambda v, b: compute(v) + comm(b), mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None)), out_specs=P(axis)))

    def timed(f):
        _sync_outs([f(a, buf)])
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            _sync_outs([f(a, buf)])
            best = min(best, time.perf_counter() - t0)
        return best

    t_comp, t_comm, t_both = timed(f_comp), timed(f_comm), timed(f_both)
    hidden = t_comp + t_comm - t_both
    denom = min(t_comp, t_comm)
    if denom <= 0:
        return HardwareSpec.overlap
    return float(np.clip(hidden / denom, 0.0, 1.0))


def long_context_cp_plan(n_devices, mem_bytes=2.5e9, hw=None, layers=4,
                         hidden=512, seq=262144):
    """The canonical long-context cp search: batch 1 caps dp, so only
    sequence sharding can spread one sequence's activations — the regime
    the cp axis exists for (shared by the dryrun config D and
    examples/autoparallel/search_and_train.py --long-context so the two
    demonstrations cannot drift)."""
    from .cost_model import HardwareSpec, attention_layer_spec
    from .search import search
    if hw is None:
        hw = HardwareSpec(mem_bytes=mem_bytes)
    spec = attention_layer_spec(hidden=hidden, seq=seq, batch=1,
                                count=layers)
    plan = search([spec], n_devices=n_devices, hw=hw, allow_pp=False,
                  max_tp=1, max_dp=1, allow_cp=True)
    axes = plan.mesh_axes()
    axes.setdefault("dp", 1)
    return plan, axes


__all__ = ["HardwareSpec", "LayerSpec", "MemoryCostModel", "TimeCostModel",
           "long_context_cp_plan", "Strategy", "transformer_layer_spec", "attention_layer_spec",
           "mlp_layer_spec", "embedding_layer_spec", "model_layer_specs",
           "swin_layer_specs", "graph_layer_spec", "graph_layer_specs",
           "bert_split", "DPAlg", "candidate_strategies", "search", "search_graph",
           "ParallelPlan", "PlanMeasurement", "measure_plan",
           "measure_plans", "plan_diff", "format_plan_diff",
           "calibrate_hardware", "measure_overlap"]
