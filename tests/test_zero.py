"""ZeRO weight-update sharding tests (ISSUE 6; ``parallel/zero.py``).

The contract under test is the Xu-et-al. decomposition run as GSPMD
sharding constraints: reduce-scatter the grads over 'dp', update only the
replica's 1/dp slice of params + optimizer moments, all-gather the params
back — with the parity claim held BITWISE against the replicated update
(same mesh, same feeds, zero=0), not approximately.  Satellites covered
here: ragged-param padded slab round-trip, preduce (dead-rank masked
mean) composed with the scattered grad layout, the ``zero-sharding`` lint
rule, the zero_* byte counters, the compiled-step cache, per-device
memory accounting, and stage-3 checkpoint save/load continuation.
"""
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.parallel import zero


# --------------------------------------------------------------- parity

# deliberately ragged: w1 has 7*9=63 elements (divides neither 2 nor 4),
# b1 has 9 — both shard only via the zero-padded slab path; w2's 36
# divides evenly.  One bucket holds all three (default bucket size).
_SHAPES = {"w1": (7, 9), "b1": (9,), "w2": (9, 4)}

_OPTS = {
    "sgd": lambda: ht.optim.SGDOptimizer(0.05),
    "momentum": lambda: ht.optim.MomentumOptimizer(0.05, momentum=0.9),
    "adam": lambda: ht.optim.AdamOptimizer(0.01),
    "adamw": lambda: ht.optim.AdamWOptimizer(0.01, weight_decay=0.01),
}


def _build(opt_name, dp, stage, seed=0):
    rng = np.random.RandomState(seed)
    x = ht.placeholder_op("x")
    y_ = ht.placeholder_op("y_")
    w1 = ht.Variable("w1", value=rng.randn(*_SHAPES["w1"])
                     .astype(np.float32) * 0.3)
    b1 = ht.Variable("b1", value=np.zeros(_SHAPES["b1"], np.float32))
    w2 = ht.Variable("w2", value=rng.randn(*_SHAPES["w2"])
                     .astype(np.float32) * 0.3)
    h = ht.relu_op(ht.linear_op(x, w1, b1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    opt = _OPTS[opt_name]()
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0,
                     dist_strategy=ht.dist.DataParallel(num_devices=dp),
                     zero=stage)
    return x, y_, loss, ex


def _loss_bits(opt_name, dp, stage, steps=10):
    x, y_, _, ex = _build(opt_name, dp, stage)
    rng = np.random.RandomState(1)
    xv = rng.randn(8, 7).astype(np.float32)
    yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)]
    bits = []
    for _ in range(steps):
        out = ex.run("train", feed_dict={x: xv, y_: yv})
        bits.append(np.float32(out[0].asnumpy()).tobytes().hex())
    return bits, ex


@pytest.mark.parametrize("dp", [2, 4])
@pytest.mark.parametrize("opt_name", ["sgd", "adam", "adamw"])
def test_sharded_update_bitwise_parity(dp, opt_name):
    """>=10 steps, sharded (stages 2 and 3) vs replicated on the SAME
    dp mesh and feeds: the loss trajectory must be bit-for-bit equal —
    the whole update chain runs under the slab sharding AND the update
    arithmetic has one product per add, so neither a moved fusion nor a
    differently-contracted FMA can drift it (zero.py module docstring;
    the two-product Adam moments drifted 1 ulp here — ROADMAP D0)."""
    base, _ = _loss_bits(opt_name, dp, stage=0)
    z2, ex2 = _loss_bits(opt_name, dp, stage=2)
    z3, ex3 = _loss_bits(opt_name, dp, stage=3)
    assert z2 == base, f"stage 2 drifted from replicated {opt_name}@dp={dp}"
    assert z3 == base, f"stage 3 drifted from replicated {opt_name}@dp={dp}"
    assert ex2._zero_plans and ex3._zero_plans  # really ran sharded
    assert ex3._zero_slabs                      # stage 3: params live as slabs


def test_stage1_and_strategy_zero_kwarg_parity():
    """Stage 1 (opt-state-only sharding) holds the same bitwise contract,
    configured through DataParallel(zero=...) instead of the kwarg."""
    base, _ = _loss_bits("adam", 4, stage=0)
    rng = np.random.RandomState(0)
    x = ht.placeholder_op("x")
    y_ = ht.placeholder_op("y_")
    w1 = ht.Variable("w1", value=rng.randn(7, 9).astype(np.float32) * 0.3)
    b1 = ht.Variable("b1", value=np.zeros(9, np.float32))
    w2 = ht.Variable("w2", value=rng.randn(9, 4).astype(np.float32) * 0.3)
    h = ht.relu_op(ht.linear_op(x, w1, b1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    ex = ht.Executor(
        {"train": [loss, ht.optim.AdamOptimizer(0.01).minimize(loss)]},
        seed=0,
        dist_strategy=ht.dist.DataParallel(num_devices=4, zero=1))
    assert ex.zero == 1 and ex._zero_plans
    rng = np.random.RandomState(1)
    xv = rng.randn(8, 7).astype(np.float32)
    yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)]
    bits = [np.float32(ex.run("train", feed_dict={x: xv, y_: yv})[0]
                       .asnumpy()).tobytes().hex() for _ in range(10)]
    assert bits == base


# ------------------------------------------------- slab packing / plans

def test_ragged_padding_roundtrip():
    """flatten+concat+pad+reshape and its inverse are exact for shapes
    that do NOT divide dp — including a scalar — on host and device."""
    rng = np.random.RandomState(7)
    vals = {"a": rng.randn(3, 5).astype(np.float32),      # 15
            "b": rng.randn(7).astype(np.float32),         # 7
            "c": np.float32(rng.randn()).reshape(())}     # 1 -> 23 total
    items = [(k, v.shape, v.dtype.name) for k, v in vals.items()]
    plan = zero.build_plan(items, dp=4, stage=2)
    assert len(plan.buckets) == 1
    b = plan.buckets[0]
    assert b.numel == 23 and b.padded == 24 and b.pad == 1 and b.width == 6
    slab = zero.host_pack_slab(vals, b)
    assert slab.shape == (4, 6)
    back = zero.host_unpack_slab(slab, b)
    for k, v in vals.items():
        assert back[k].shape == v.shape
        np.testing.assert_array_equal(back[k], v)
    # device-side (traceable) path agrees with the host path
    import jax
    dback = jax.jit(lambda d: zero.unpack_slab(zero.pack_slab(d, b), b))(vals)
    for k, v in vals.items():
        np.testing.assert_array_equal(np.asarray(dback[k]), v)


def test_build_plan_buckets_by_size_and_dtype():
    """Bucketing: the byte cap starts a new slab, a dtype change starts a
    new slab (one homogeneous buffer each), per_param forces one each."""
    items = [("p0", (1024,), "float32"), ("p1", (1024,), "float32"),
             ("p2", (1024,), "float32"), ("h0", (64,), "float16")]
    plan = zero.build_plan(items, dp=2, stage=2, max_bytes=2 * 1024 * 4)
    assert [b.param_keys for b in plan.buckets] == \
        [["p0", "p1"], ["p2"], ["h0"]]
    assert plan.buckets[2].dtype == "float16"
    assert plan.buckets[0].offsets == [0, 1024]
    pp = zero.build_plan(items, dp=2, stage=2, per_param=True)
    assert [len(b.param_keys) for b in pp.buckets] == [1, 1, 1, 1]
    assert plan.param_keys == [k for k, _, _ in items]


def test_resolve_stage():
    assert zero.resolve_stage(None) == 0
    assert zero.resolve_stage(False) == 0
    assert zero.resolve_stage(True) == 2
    assert zero.resolve_stage(3) == 3
    with pytest.raises(ValueError):
        zero.resolve_stage(5)


def test_eval_subgraph_does_not_detach_stage3_slabs():
    """An eval subgraph sharing stage-3 weights materializes them
    transiently — it must NOT write the full arrays back into
    var_values, or later train steps would keep updating the slab while
    save()/return_tensor_values() served a frozen stale copy."""
    from hetu_tpu.graph.executor import _ZeroView

    rng = np.random.RandomState(0)
    x = ht.placeholder_op("x")
    y_ = ht.placeholder_op("y_")
    w1 = ht.Variable("w1", value=rng.randn(7, 9).astype(np.float32) * 0.3)
    b1 = ht.Variable("b1", value=np.zeros(9, np.float32))
    w2 = ht.Variable("w2", value=rng.randn(9, 4).astype(np.float32) * 0.3)
    h = ht.relu_op(ht.linear_op(x, w1, b1))
    logits = ht.matmul_op(h, w2)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(logits, y_), [0])
    opt = ht.optim.AdamOptimizer(0.01)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)],
                      "eval": [logits]}, seed=0,
                     dist_strategy=ht.dist.DataParallel(num_devices=4),
                     zero=3)
    xv = rng.randn(8, 7).astype(np.float32)
    yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)]
    ex.run("train", feed_dict={x: xv, y_: yv})
    ex.run("eval", feed_dict={x: xv})
    assert isinstance(ex.var_values[w1], _ZeroView)   # still slab-backed
    before = ex.return_tensor_values()["w1"].copy()
    ex.run("train", feed_dict={x: xv, y_: yv})
    after = ex.return_tensor_values()["w1"]
    assert not np.array_equal(before, after)   # sees the LATEST update
    # and eval after more training reads the updated weights
    e1 = np.asarray(ex.run("eval", feed_dict={x: xv})[0].asnumpy())
    ex.run("train", feed_dict={x: xv, y_: yv})
    e2 = np.asarray(ex.run("eval", feed_dict={x: xv})[0].asnumpy())
    assert not np.array_equal(e1, e2)


def test_model_parallel_params_excluded_from_zero():
    """A param carrying an explicit sharding annotation (ht.dispatch —
    model parallelism) must keep its layout: the dp slab packing (and the
    stage<3 replicated gather) would silently destroy it, so the whole
    optimizer falls back to the replicated update path."""
    from jax.sharding import PartitionSpec as P

    rng = np.random.RandomState(0)
    x = ht.placeholder_op("x")
    y_ = ht.placeholder_op("y_")
    w1 = ht.Variable("w1", value=rng.randn(8, 8).astype(np.float32) * 0.3)
    w2 = ht.Variable("w2", value=rng.randn(8, 4).astype(np.float32) * 0.3)
    ht.dispatch(w1, P(None, "tp"))          # column-parallel
    h = ht.relu_op(ht.matmul_op(x, w1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    mesh = ht.make_mesh({"dp": 4, "tp": 2})
    ex = ht.Executor(
        {"train": [loss, ht.optim.AdamOptimizer(0.01).minimize(loss)]},
        seed=0, mesh=mesh,
        dist_strategy=ht.dist.ModelParallel({"dp": 4, "tp": 2}), zero=2)
    assert ex.zero == 2 and not ex._zero_plans
    xv = rng.randn(8, 8).astype(np.float32)
    yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)]
    ex.run("train", feed_dict={x: xv, y_: yv})   # replicated update works
    # and the mp layout survived the step
    spec = ex.var_values[w1].sharding.spec
    assert "tp" in [ax for s in spec for ax in
                    (s if isinstance(s, tuple) else (s,)) if ax]
    # the lint rule mirrors the eligibility filter: it explains the
    # no-effect instead of warning about collectives that never exist
    opt_op = [n for n in ex.global_topo
              if type(n).__name__ == "OptimizerOp"][0]
    rep = ht.lint([loss, opt_op], mesh=mesh, zero=2)
    diags = [d for d in rep.diagnostics if d.rule == "zero-sharding"]
    assert len(diags) == 1 and "REPLICATED" in diags[0].message
    assert "w1" in diags[0].message


# ----------------------------------------- preduce composition (dead rank)

def test_preduce_scatter_composes_dead_rank_mean():
    """Partial-reduce's alive-mask mean composed with the ZeRO grad
    layout: with one dead rank, every device's scattered slice equals its
    row of the full masked mean — straggler tolerance and 1/dp grad
    memory in ONE collective (preduce pays a full all-reduce)."""
    import jax
    from jax.sharding import PartitionSpec as P
    from hetu_tpu.parallel.preduce import preduce_mean, preduce_scatter_mean

    dp, width = 4, 6
    mesh = ht.make_mesh({"dp": dp})
    rng = np.random.RandomState(3)
    # G[r] is rank r's local grad slab (dp, width); rank 2 is dead
    G = rng.randn(dp, dp, width).astype(np.float32)
    mask = np.array([1, 1, 0, 1], np.float32)

    def scat(g, m):
        return preduce_scatter_mean(g[0], m[0], "dp")

    def full(g, m):
        return preduce_mean(g[0], m[0], "dp")[None]

    scattered = jax.jit(jax.shard_map(
        scat, mesh=mesh, in_specs=(P("dp"), P("dp")),
        out_specs=P("dp")))(G, mask)
    gathered = jax.jit(jax.shard_map(
        full, mesh=mesh, in_specs=(P("dp"), P("dp")),
        out_specs=P("dp")))(G, mask)
    expect = (G * mask[:, None, None]).sum(0) / mask.sum()
    np.testing.assert_allclose(np.asarray(gathered)[0], expect, rtol=1e-6)
    # each rank's scattered row == its slice of the full masked mean
    np.testing.assert_array_equal(np.asarray(scattered),
                                  np.asarray(gathered)[0])


# --------------------------------------------------------------- lint rule

def _lint_graph():
    rng = np.random.RandomState(0)
    x = ht.placeholder_op("x", shape=(8, 7))
    y_ = ht.placeholder_op("y_", shape=(8, 4))
    w1 = ht.Variable("ragged_w", value=rng.randn(7, 9).astype(np.float32))
    w2 = ht.Variable("even_w", value=rng.randn(9, 4).astype(np.float32))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(
            ht.matmul_op(ht.matmul_op(x, w1), w2), y_), [0])
    return loss, ht.optim.SGDOptimizer(0.1).minimize(loss)


def test_lint_zero_rule_warns_without_dp_axis():
    loss, opt_op = _lint_graph()
    mesh = ht.make_mesh({"tp": 4})
    rep = ht.lint([loss, opt_op], mesh=mesh, zero=2)
    diags = [d for d in rep.diagnostics if d.rule == "zero-sharding"]
    assert len(diags) == 1 and diags[0].severity == "warn"
    assert "'dp'" in diags[0].message and "REPLICATED" in diags[0].message
    # no mesh at all warns too
    rep2 = ht.lint([loss, opt_op], mesh=None, zero=3)
    assert any(d.rule == "zero-sharding" for d in rep2.diagnostics)


def test_lint_zero_rule_flags_ragged_params_with_site():
    loss, opt_op = _lint_graph()
    mesh = ht.make_mesh({"dp": 4})
    rep = ht.lint([loss, opt_op], mesh=mesh, zero=2)
    diags = [d for d in rep.diagnostics if d.rule == "zero-sharding"]
    # the bucket totals 63+36=99, not divisible by 4 -> one warn naming
    # the ragged member (ragged_w, 63); even_w (36) divides and is not
    # blamed
    assert len(diags) == 1
    msg = str(diags[0])
    assert "ragged_w" in msg and "zero-padded to 100" in diags[0].message
    assert "test_zero.py" in msg          # creation-site provenance
    assert "even_w" not in diags[0].message


def test_lint_zero_rule_silent_when_bucket_absorbs_padding():
    """The rule mirrors the executor's REAL bucketing: a ragged param
    whose bucket total still divides dp shards with zero waste and must
    not warn (per-param numel % dp would spam about a non-problem)."""
    rng = np.random.RandomState(0)
    x = ht.placeholder_op("x", shape=(8, 7))
    y_ = ht.placeholder_op("y_", shape=(8, 4))
    w1 = ht.Variable("w1", value=rng.randn(7, 9).astype(np.float32))  # 63
    b1 = ht.Variable("b1", value=np.zeros(9, np.float32))             # 9
    w2 = ht.Variable("w2", value=rng.randn(9, 4).astype(np.float32))  # 36
    h = ht.relu_op(ht.linear_op(x, w1, b1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    opt_op = ht.optim.SGDOptimizer(0.1).minimize(loss)
    mesh = ht.make_mesh({"dp": 4})
    rep = ht.lint([loss, opt_op], mesh=mesh, zero=2)   # 108 % 4 == 0
    assert not [d for d in rep.diagnostics if d.rule == "zero-sharding"]


def test_lint_zero_rule_silent_when_off_or_clean():
    loss, opt_op = _lint_graph()
    mesh = ht.make_mesh({"dp": 4})
    rep = ht.lint([loss, opt_op], mesh=mesh)          # zero not requested
    assert not [d for d in rep.diagnostics if d.rule == "zero-sharding"]


# -------------------------------------------------------------- counters

def test_zero_counters_recorded_and_clean_run_empty():
    from hetu_tpu.metrics import reset_zero_counts
    from hetu_tpu.profiler import HetuProfiler
    from hetu_tpu.graph import step_cache

    step_cache.clear()      # a cache hit would skip the recording trace
    reset_zero_counts()
    _loss_bits("adam", 4, stage=0, steps=1)
    assert HetuProfiler.zero_counters() == {}   # replicated: nothing ticks

    step_cache.clear()
    reset_zero_counts()
    _loss_bits("adam", 4, stage=2, steps=1)
    c = HetuProfiler.zero_counters()
    # one bucket: 63+9+36=108 elems -> padded 108 (divides 4) -> 432 B;
    # zero pad bytes record NOTHING (counters only tick on real traffic)
    assert c["zero_reduce_scatter_bytes"] == 432
    assert "zero_pad_bytes" not in c
    assert c["zero_all_gather_bytes"] == 432

    step_cache.clear()
    reset_zero_counts()
    _loss_bits("adam", 8, stage=2, steps=1)
    c = HetuProfiler.zero_counters()
    # 108 elems at dp=8 pad to 112: 4 wasted elems = 16 B, counted
    assert c["zero_pad_bytes"] == 16
    assert c["zero_reduce_scatter_bytes"] == 112 * 4

    step_cache.clear()
    reset_zero_counts()
    _loss_bits("adam", 2, stage=3, steps=1)
    c = HetuProfiler.zero_counters()
    # stage 3 still gathers (inside the next step's program)
    assert c["zero_all_gather_bytes"] >= 432
    reset_zero_counts()


# -------------------------------------------------------- step cache

def test_step_cache_reuses_compiled_step_across_executors():
    from hetu_tpu.graph import step_cache
    from hetu_tpu.metrics import reset_step_cache_counts, step_cache_counts

    step_cache.clear()
    reset_step_cache_counts()
    bits1, ex1 = _loss_bits("adam", 2, stage=2, steps=2)
    c = step_cache_counts()
    assert c.get("step_cache_miss", 0) >= 1
    first_hits = c.get("step_cache_hit", 0)
    bits2, ex2 = _loss_bits("adam", 2, stage=2, steps=2)
    c = step_cache_counts()
    assert c.get("step_cache_hit", 0) > first_hits     # identical rebuild
    assert ex2.subexecutors["train"]._jit is ex1.subexecutors["train"]._jit
    assert bits1 == bits2                              # and it computes the same
    # a different zero stage is a different program -> no false hit
    misses = c.get("step_cache_miss", 0)
    _loss_bits("adam", 2, stage=3, steps=1)
    assert step_cache_counts().get("step_cache_miss", 0) > misses
    step_cache.clear()
    reset_step_cache_counts()


def test_step_cache_signature_none_for_ps_graphs():
    """PS-backed subgraphs must be uncachable: a cached step pins its
    builder executor alive, which would leak the PS cache teardown."""
    from hetu_tpu.graph import step_cache

    from hetu_tpu.ps import EmbeddingStore

    rng = np.random.RandomState(0)
    st = EmbeddingStore()
    t = st.init_table(30, 8, opt="sgd", lr=0.1, seed=0)
    ids = ht.placeholder_op("ids")
    y_ = ht.placeholder_op("y_")
    h = ht.ps_embedding_lookup_op((st, t), ids, width=8)
    w = ht.Variable("w", value=rng.randn(8, 3).astype(np.float32))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w), y_), [0])
    ex = ht.Executor(
        {"train": [loss, ht.optim.SGDOptimizer(0.1).minimize(loss)]},
        seed=0)
    sub = ex.subexecutors["train"]
    if sub._jit is None:
        sub._build_step()
    assert step_cache.signature(sub) is None


# ------------------------------------------------------ memory accounting

def test_memory_accounting_opt_state_shrinks_by_dp():
    """The headline claim at test scale: per-device Adam moment bytes at
    stage 2 == replicated/dp (+ slab padding), computed from the real
    device buffers (addressable shards), not from formulas."""
    dp = 4
    _, ex0 = _loss_bits("adam", dp, stage=0, steps=1)
    _, ex2 = _loss_bits("adam", dp, stage=2, steps=1)
    _, ex3 = _loss_bits("adam", dp, stage=3, steps=1)
    m0, m2, m3 = (e.memory_accounting() for e in (ex0, ex2, ex3))
    numel = sum(int(np.prod(s)) for s in _SHAPES.values())      # 108
    padded = -(-numel // dp) * dp
    assert m0["opt_state_bytes_per_device"] == 2 * numel * 4 + 4   # m,v,t
    assert m2["opt_state_bytes_per_device"] == 2 * (padded // dp) * 4 + 4
    assert m2["opt_state_bytes_per_device"] <= \
        m0["opt_state_bytes_per_device"] / dp + 2 * 4 * dp + 4
    # stage 3: master params live as slabs at 1/dp too
    assert m3["param_bytes_per_device"] == 0 or \
        m3["param_bytes_per_device"] < m0["param_bytes_per_device"]
    assert m3["zero_slab_bytes_per_device"] == (padded // dp) * 4
    assert m3["zero_stage"] == 3 and m0["zero_stage"] == 0
    # grads: analytic layout — full at stage 0, 1/dp at stage >= 2
    assert m2["grad_bytes_per_device"] == m0["grad_bytes_per_device"] // dp


def test_legacy_blob_restore_keeps_moments_sharded(tmp_path):
    """The single-pickle checkpoint format must also restore ZeRO slab
    moments dp-SHARDED — a replicated restore would pay the full dp x
    moment memory at exactly the resume moment."""
    import jax

    x, y_, _, ex = _build("adam", 4, 2)
    rng = np.random.RandomState(1)
    xv = rng.randn(8, 7).astype(np.float32)
    yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)]
    ref = [np.float32(ex.run("train", feed_dict={x: xv, y_: yv})[0]
                      .asnumpy()).tobytes() for _ in range(6)]

    x1, y1_, _, ex1 = _build("adam", 4, 2)
    first = [np.float32(ex1.run("train", feed_dict={x1: xv, y1_: yv})[0]
                        .asnumpy()).tobytes() for _ in range(3)]
    ex1.save(str(tmp_path), file="ck.blob")
    x2, y2_, _, ex2 = _build("adam", 4, 2)
    ex2.load(str(tmp_path), file="ck.blob")
    slab_spec = zero.slab_sharding(ex2.mesh).spec
    slabs = [leaf for st in ex2.opt_states.values()
             for leaf in jax.tree_util.tree_leaves(st)
             if getattr(leaf, "ndim", 0) == 2]
    assert slabs and all(leaf.sharding.spec == slab_spec for leaf in slabs)
    cont = [np.float32(ex2.run("train", feed_dict={x2: xv, y2_: yv})[0]
                       .asnumpy()).tobytes() for _ in range(3)]
    assert first + cont == ref


# ------------------------------------------------- stage-3 state round trip

def test_stage3_checkpoint_and_values_roundtrip(tmp_path):
    """Save at step 3 under stage 3 (params live as sharded slabs), load
    into a FRESH stage-3 executor, continue — bitwise-identical to the
    uninterrupted run; return_tensor_values materializes full params."""
    steps_a, steps_b = 3, 4

    def fresh():
        return _build("adam", 4, 3)

    rng = np.random.RandomState(1)
    xv = rng.randn(8, 7).astype(np.float32)
    yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, 8)]

    x, y_, _, ex = fresh()
    fd = {x: xv, y_: yv}
    uninterrupted = [np.float32(ex.run("train", feed_dict=fd)[0].asnumpy())
                     .tobytes() for _ in range(steps_a + steps_b)]

    x, y_, _, ex1 = fresh()
    fd1 = {x: xv, y_: yv}
    first = [np.float32(ex1.run("train", feed_dict=fd1)[0].asnumpy())
             .tobytes() for _ in range(steps_a)]
    vals = ex1.return_tensor_values()
    assert vals["w1"].shape == _SHAPES["w1"]    # materialized, not a slab
    ex1.save(str(tmp_path / "ck"))

    x, y_, _, ex2 = fresh()
    fd2 = {x: xv, y_: yv}
    ex2.load(str(tmp_path / "ck"))
    assert ex2.step_counter == steps_a
    # restored state must still be SHARDED (a replicated restore would
    # silently pay the memory the plan exists to shed)
    m = ex2.memory_accounting()
    assert m["zero_slab_bytes_per_device"] > 0
    import jax
    for st in ex2.opt_states.values():
        for leaf in jax.tree_util.tree_leaves(st):
            if getattr(leaf, "ndim", 0) == 2:
                assert leaf.sharding.spec == \
                    zero.slab_sharding(ex2.mesh).spec
    cont = [np.float32(ex2.run("train", feed_dict=fd2)[0].asnumpy())
            .tobytes() for _ in range(steps_b)]
    assert first + cont == uninterrupted
