"""Auto-parallel search tests (Galvatron parity: cost models + DP search +
plan emission; reference tools/Galvatron/utils/{cost_model,dp_utils}.py)."""
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu.autoparallel import (DPAlg, HardwareSpec, LayerSpec,
                                   MemoryCostModel, Strategy, TimeCostModel,
                                   candidate_strategies, search,
                                   transformer_layer_spec)


def test_candidate_strategies_factorize_devices():
    cands = candidate_strategies(8)
    assert all(s.world == 8 for s in cands)
    assert Strategy(1, 1, 8, False) in cands
    assert Strategy(1, 1, 8, True) in cands      # ZeRO
    assert Strategy(2, 2, 2, False) in cands     # 3D
    assert Strategy(1, 8, 1, False) in cands     # pure TP
    nopp = candidate_strategies(8, allow_pp=False)
    assert all(s.pp == 1 for s in nopp)


def test_memory_model_fsdp_and_tp_shard_states():
    hw = HardwareSpec(mem_bytes=1e12)
    mem = MemoryCostModel(hw)
    spec = transformer_layer_spec(hidden=1024, seq=512, batch=32)
    full = mem.layer_bytes(spec, Strategy(1, 1, 8, False))
    fsdp = mem.layer_bytes(spec, Strategy(1, 1, 8, True))
    tp = mem.layer_bytes(spec, Strategy(1, 8, 1, False))
    assert fsdp < full        # optimizer states sharded over dp
    assert tp < full          # params sharded over tp


def test_time_model_tp_adds_comm_cost():
    hw = HardwareSpec()
    tm = TimeCostModel(hw)
    spec = transformer_layer_spec(hidden=1024, seq=512, batch=32)
    t_dp = tm.layer_time(spec, Strategy(1, 1, 8, False))
    t_tp = tm.layer_time(spec, Strategy(1, 8, 1, False))
    # same compute spread, but TP pays activation allreduces every layer
    assert t_tp > t_dp


def test_search_prefers_dp_when_memory_is_ample():
    specs = [transformer_layer_spec(512, 128, 16, name=f"l{i}")
             for i in range(4)]
    plan = search(specs, 8, hw=HardwareSpec(mem_bytes=64e9))
    assert all(s.dp == 8 and s.tp == 1 for s in plan.strategies)


def test_search_shards_under_memory_pressure():
    # one replica of the whole model doesn't fit -> must shard states
    specs = [transformer_layer_spec(4096, 1024, 8, name=f"l{i}")
             for i in range(8)]
    one_layer_full = MemoryCostModel(HardwareSpec()).layer_bytes(
        specs[0], Strategy(1, 1, 8, False))
    hw = HardwareSpec(mem_bytes=one_layer_full * len(specs) * 0.45)
    plan = search(specs, 8, hw=hw)
    assert any(s.fsdp or s.tp > 1 or s.pp > 1 for s in plan.strategies)
    assert MemoryCostModel(hw).stage_bytes(specs, plan.strategies) \
        <= hw.mem_bytes


def test_search_infeasible_raises():
    specs = [transformer_layer_spec(8192, 2048, 64, name="big", count=48)]
    with pytest.raises(ValueError, match="no feasible"):
        search(specs, 2, hw=HardwareSpec(mem_bytes=1e9))


def test_dp_switch_cost_discourages_flip_flop():
    specs = [transformer_layer_spec(1024, 256, 16, name=f"l{i}")
             for i in range(6)]
    alg = DPAlg(specs, 8, hw=HardwareSpec(mem_bytes=64e9))
    t, strategies = alg.fit()
    assert t < float("inf")
    # homogeneous layers -> homogeneous plan (no gratuitous resharding)
    assert len(set(strategies)) == 1


def test_plan_emission_and_execution():
    """Search → plan → mesh/strategy → executor runs on the virtual mesh."""
    specs = [transformer_layer_spec(64, 16, 16, name=f"l{i}")
             for i in range(2)]
    plan = search(specs, 8, hw=HardwareSpec(mem_bytes=1e9), uniform=True,
                  allow_pp=False)
    axes = plan.mesh_axes()
    assert np.prod(list(axes.values())) <= 8
    strat = plan.strategy()

    # tiny 2-layer MLP trained under the emitted strategy
    x = ht.placeholder_op("x")
    y = ht.placeholder_op("y")
    from hetu_tpu.layers.core import Linear
    l1 = Linear(32, 64, activation="relu", name="ap.l1")
    l2 = Linear(64, 10, name="ap.l2")
    for layer, d in zip([l1, l2], plan.layer_specs()):
        if d["tp"] > 1:
            ht.dispatch(l1.weight_var, d["kernel_spec"])
            ht.dispatch(l2.weight_var, d["out_kernel_spec"])
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_sparse_op(l2(l1(x)), y), [0])
    opt = ht.optim.SGDOptimizer(0.1)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]},
                     dist_strategy=strat, seed=0)
    feeds = {x: np.random.randn(16, 32).astype(np.float32),
             y: np.random.randint(0, 10, (16,)).astype(np.int32)}
    vals = [float(ex.run("train", feed_dict=feeds)[0].asnumpy())
            for _ in range(3)]
    assert np.isfinite(vals).all() and vals[-1] < vals[0]


def test_mixed_plan_mesh_overflow_raises():
    from hetu_tpu.autoparallel.plan import ParallelPlan
    specs = [transformer_layer_spec(256, 64, 8, name=f"l{i}")
             for i in range(2)]
    plan = ParallelPlan(specs, [Strategy(4, 1, 2), Strategy(1, 4, 2)], 8)
    with pytest.raises(ValueError, match="uniform"):
        plan.mesh_axes()


def test_layer_specs_expand_by_count():
    specs = [transformer_layer_spec(256, 64, 8, name="blk", count=24)]
    plan = search(specs, 8, hw=HardwareSpec(mem_bytes=64e9))
    directives = plan.layer_specs()
    assert len(directives) == 24
    assert directives[0]["name"] == "blk.0"
    pp = max(s.pp for s in plan.strategies)
    stages = {d["stage"] for d in directives}
    assert stages == set(range(pp))  # blocks spread over all stages


def test_describe_is_readable():
    specs = [transformer_layer_spec(256, 64, 8, name="blk", count=4)]
    plan = search(specs, 8, hw=HardwareSpec(mem_bytes=64e9))
    out = plan.describe()
    assert "mesh=" in out and "blk" in out


def test_hardware_spec_measure():
    """Calibrated HardwareSpec from this machine: matmul probe + measured
    allreduce bandwidth (reference Galvatron test_env profile step)."""
    hw = HardwareSpec.measure(matmul_dim=256, probe_bytes=1 << 16)
    assert hw.flops > 0 and np.isfinite(hw.flops)
    assert hw.ici_bw > 0 and np.isfinite(hw.ici_bw)
    # measured numbers drive the search without errors
    specs = [transformer_layer_spec(256, 64, 8, name=f"l{i}")
             for i in range(2)]
    plan = search(specs, 8, hw=hw)
    assert plan.est_time > 0


def test_plan_apply_rejects_unrealizable_pp():
    specs = [transformer_layer_spec(512, 128, 16, name=f"l{i}")
             for i in range(4)]
    from hetu_tpu.autoparallel.plan import ParallelPlan
    plan = ParallelPlan(specs, [Strategy(2, 1, 4, False)] * 4, 8,
                        est_time=1.0)

    class FakeLayer:
        in_kernels = ()
        out_kernels = ()
    with pytest.raises(ValueError, match="pipeline"):
        plan.apply([FakeLayer() for _ in range(4)])


def test_search_to_execution_end_to_end():
    """Close the loop: measure hw → search → emit mesh+shardings → run one
    training step on the 8-device mesh with the emitted plan."""
    import jax
    d_model, seq, batch = 64, 16, 16
    n_layers = 2
    specs = [transformer_layer_spec(d_model, seq, batch, name=f"blk{i}")
             for i in range(n_layers)]
    hw = HardwareSpec.measure(matmul_dim=256, probe_bytes=1 << 16)
    # force a sharded regime: budget fits ~60% of the fully-replicated model
    full = MemoryCostModel(hw).layer_bytes(specs[0], Strategy(1, 1, 8, False))
    hw = HardwareSpec(flops=hw.flops, ici_bw=hw.ici_bw,
                      mem_bytes=full * n_layers * 0.6)
    plan = search(specs, 8, hw=hw, allow_pp=False)
    assert any(s.fsdp or s.tp > 1 for s in plan.strategies)

    mesh = ht.make_mesh(plan.mesh_axes())
    x = ht.placeholder_op("x", shape=(batch * seq, d_model))
    y = ht.placeholder_op("y", shape=(batch * seq, d_model))

    class Block:
        def __init__(self, i):
            self.fc1 = ht.layers.Linear(d_model, 4 * d_model,
                                        activation="relu", name=f"b{i}.fc1")
            self.fc2 = ht.layers.Linear(4 * d_model, d_model,
                                        name=f"b{i}.fc2")
            self.in_kernels = [self.fc1.weight_var]
            self.out_kernels = [self.fc2.weight_var]

        def __call__(self, h):
            return h + self.fc2(self.fc1(h))

    blocks = [Block(i) for i in range(n_layers)]
    plan.apply(blocks)
    h = x
    for b in blocks:
        h = b(h)
    loss = ht.ops.reduce_mean_op(ht.ops.mul_op(h - y, h - y), [0, 1])
    opt = ht.optim.AdamOptimizer(1e-3)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0,
                     dist_strategy=plan.strategy(), mesh=mesh)
    rng = np.random.RandomState(0)
    xv = rng.randn(batch * seq, d_model).astype(np.float32)
    yv = rng.randn(batch * seq, d_model).astype(np.float32)
    l0 = float(ex.run("train", feed_dict={x: xv, y: yv})[0].asnumpy())
    assert np.isfinite(l0)
    # shardings were actually applied (fsdp or tp on some kernel)
    assert any(getattr(b.fc1.weight_var, "sharding", None) is not None
               or getattr(b.fc2.weight_var, "sharding", None) is not None
               for b in blocks)


# ------------------------------------- multi-layer-type joint search
# (reference tools/Galvatron/utils/dp_utils.py:259 multi-layer-type DP)

def test_model_layer_specs_builds_interleaved_types():
    from hetu_tpu.autoparallel import model_layer_specs
    specs = model_layer_specs(3, hidden=256, seq=64, batch=8, vocab=50000)
    names = [s.name for s in specs]
    assert names == ["embed", "attn0", "mlp0", "attn1", "mlp1", "attn2",
                     "mlp2"]
    # embedding is parameter-dominated; sublayers are FLOP-dominated
    assert specs[0].param_bytes > 10 * specs[1].param_bytes
    assert specs[2].fwd_flops > 0


def test_multi_layer_type_search_differentiates_types():
    """The joint DP assigns DIFFERENT strategies to different layer types
    when their cost structures demand it: a huge embedding only fits
    sharded (fsdp), while the small compute layers stay unsharded (fsdp
    would cost them allgather time for no memory benefit)."""
    from hetu_tpu.autoparallel import model_layer_specs
    specs = model_layer_specs(2, hidden=256, seq=64, batch=8, vocab=2_000_000)
    hw = HardwareSpec(flops=1e14, ici_bw=4e10, mem_bytes=2.5e9)
    emb_full = MemoryCostModel(hw).layer_bytes(
        specs[0], Strategy(1, 1, 8, False))
    assert emb_full > hw.mem_bytes          # replicated embedding can't fit
    alg = DPAlg(specs, 8, hw=hw, allow_pp=False)
    t, strategies = alg.fit()
    assert strategies is not None and np.isfinite(t)
    by_name = dict(zip([s.name for s in specs], strategies))
    assert by_name["embed"].fsdp            # embedding must shard params
    # at least one compute sublayer chose a different strategy than the
    # embedding (the chain is NOT uniform — types are searched jointly)
    assert any(by_name[n] != by_name["embed"]
               for n in ("attn0", "mlp0", "attn1", "mlp1"))


def test_multi_layer_type_search_to_execution():
    """e2e with 2 layer types: search a heterogeneous (attn-spec, mlp-spec)
    chain, emit the mesh + per-layer directives, run a training step."""
    from hetu_tpu.autoparallel import attention_layer_spec, mlp_layer_spec
    d_model, seq, batch = 64, 16, 16
    specs = [attention_layer_spec(d_model, seq, batch, name="attn0"),
             mlp_layer_spec(d_model, seq, batch, name="mlp0")]
    hw = HardwareSpec.measure(matmul_dim=256, probe_bytes=1 << 16)
    full = max(MemoryCostModel(hw).layer_bytes(s, Strategy(1, 1, 8, False))
               for s in specs)
    hw = HardwareSpec(flops=hw.flops, ici_bw=hw.ici_bw,
                      mem_bytes=full * len(specs) * 0.6)
    plan = search(specs, 8, hw=hw, allow_pp=False)
    assert any(s.fsdp or s.tp > 1 for s in plan.strategies)

    mesh = ht.make_mesh(plan.mesh_axes())
    x = ht.placeholder_op("x", shape=(batch * seq, d_model))
    y = ht.placeholder_op("y", shape=(batch * seq, d_model))

    class AttnBlock:                       # 4 projections, attn-shaped
        def __init__(self):
            self.q = ht.layers.Linear(d_model, d_model, name="mt.q")
            self.k = ht.layers.Linear(d_model, d_model, name="mt.k")
            self.v = ht.layers.Linear(d_model, d_model, name="mt.v")
            self.o = ht.layers.Linear(d_model, d_model, name="mt.o")
            self.in_kernels = [self.q.weight_var, self.k.weight_var,
                               self.v.weight_var]
            self.out_kernels = [self.o.weight_var]

        def __call__(self, h):
            return h + self.o(ht.relu_op(self.q(h) + self.k(h) + self.v(h)))

    class MlpBlock:
        def __init__(self):
            self.fc1 = ht.layers.Linear(d_model, 4 * d_model,
                                        activation="relu", name="mt.fc1")
            self.fc2 = ht.layers.Linear(4 * d_model, d_model, name="mt.fc2")
            self.in_kernels = [self.fc1.weight_var]
            self.out_kernels = [self.fc2.weight_var]

        def __call__(self, h):
            return h + self.fc2(self.fc1(h))

    blocks = [AttnBlock(), MlpBlock()]
    plan.apply(blocks)
    h = x
    for b in blocks:
        h = b(h)
    loss = ht.ops.reduce_mean_op(ht.ops.mul_op(h - y, h - y), [0, 1])
    opt = ht.optim.AdamOptimizer(1e-3)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0,
                     dist_strategy=plan.strategy(), mesh=mesh)
    rng = np.random.RandomState(0)
    xv = rng.randn(batch * seq, d_model).astype(np.float32)
    yv = rng.randn(batch * seq, d_model).astype(np.float32)
    l0 = float(ex.run("train", feed_dict={x: xv, y: yv})[0].asnumpy())
    assert np.isfinite(l0)


def test_hardware_spec_from_artifact(tmp_path):
    import json
    p = tmp_path / "cal.json"
    p.write_text(json.dumps({"backend": "tpu", "spec": {
        "flops": 1.23e14, "mem_bytes": 1.6e10, "ici_bw": 5e10,
        "dcn_bw": 2e9, "overlap": 0.6}}))
    hw = HardwareSpec.from_artifact(str(p))
    assert hw.flops == 1.23e14 and hw.overlap == 0.6
    assert HardwareSpec.from_artifact(str(tmp_path / "missing.json")) is None


def test_measure_overlap_bounds():
    """overlap_coe is MEASURED (Galvatron utils/cost_model.py:38) — on the
    8-dev simulated mesh it must return a sane [0, 1] coefficient and flow
    into calibrate_hardware's HardwareSpec."""
    from hetu_tpu.autoparallel import measure_overlap, calibrate_hardware
    mesh = ht.make_mesh({"dp": 8})
    ov = measure_overlap(mesh, "dp", probe_bytes=1 << 14, matmul_dim=128,
                         repeats=2)
    assert 0.0 <= ov <= 1.0
    hw = calibrate_hardware(mesh=mesh, matmul_dim=128, chain=4,
                            probe_bytes=1 << 14)
    assert 0.0 <= hw.overlap <= 1.0


# ------------------------------------------------- cp axis (net-new vs ref)

def test_cp_candidates_generated():
    from hetu_tpu.autoparallel.search import candidate_strategies
    base = candidate_strategies(8)
    with_cp = candidate_strategies(8, allow_cp=True)
    assert all(s.cp == 1 for s in base)         # opt-in: default unchanged
    cps = {s.cp for s in with_cp}
    assert cps == {1, 2, 4, 8}
    assert all(s.world == 8 for s in with_cp)


def test_cp_wins_when_activations_dominate():
    """Long-sequence attention workload whose activations blow the budget
    at dp-only: the searcher must trade dp for cp (sequence sharding cuts
    per-device activations; params replicate)."""
    from hetu_tpu.autoparallel.cost_model import (HardwareSpec,
                                                  attention_layer_spec)
    from hetu_tpu.autoparallel.search import search

    # long-context, batch 1: dp is capped at the global batch, so only
    # sequence sharding can spread the activations over devices
    spec = attention_layer_spec(hidden=512, seq=262144, batch=1, count=4)
    hw = HardwareSpec(mem_bytes=2.5e9)
    import pytest as _pt
    with _pt.raises(ValueError):                 # infeasible without cp
        search([spec], n_devices=8, hw=hw, allow_pp=False, max_tp=1,
               max_dp=1)
    plan = search([spec], n_devices=8, hw=hw, allow_pp=False, max_tp=1,
                  max_dp=1, allow_cp=True)
    assert max(s.cp for s in plan.strategies) > 1
    assert "cp" in plan.mesh_axes()


def test_cp_ring_cost_only_for_attention_layers():
    from hetu_tpu.autoparallel.cost_model import (HardwareSpec, LayerSpec,
                                                  Strategy, TimeCostModel)
    hw = HardwareSpec(overlap=0.0)
    tm = TimeCostModel(hw)
    attn = LayerSpec("a", 1e6, 1e12, 1e9, attn=True)
    mlp = LayerSpec("m", 1e6, 1e12, 1e9, attn=False)
    s_cp = Strategy(dp=1, cp=4)
    s_dp = Strategy(dp=4, cp=1)
    # same compute split; the attention layer pays the ring on top
    assert tm.layer_time(attn, s_cp) > tm.layer_time(mlp, s_cp)
    # non-attention layers: cp == dp in time (grad sync spans dp*cp both)
    assert abs(tm.layer_time(mlp, s_cp) - tm.layer_time(mlp, s_dp)) < 1e-9


@pytest.mark.slow     # 12s at HEAD (ISSUE 12 tier-1 budget);
# plan execution stays via the cheaper end-to-end plan tests
def test_cp_plan_executes_t5_end_to_end():
    """plan(cp) → mesh axes → T5-tiny(context_parallel) trains — the
    profile→search→execute workflow over the new axis."""
    import jax
    import hetu_tpu as ht
    from hetu_tpu.autoparallel.cost_model import (HardwareSpec,
                                                  attention_layer_spec)
    from hetu_tpu.autoparallel.search import search
    from hetu_tpu.models.t5 import T5Config, t5_seq2seq_graph
    from hetu_tpu.models import synthetic_seq2seq_batch

    spec = attention_layer_spec(hidden=512, seq=262144, batch=1, count=4)
    plan = search([spec], n_devices=4,
                  hw=HardwareSpec(mem_bytes=2.2e9),
                  allow_pp=False, max_tp=1, max_dp=1, allow_cp=True)
    axes = plan.mesh_axes()
    assert axes.get("cp", 1) > 1
    axes.setdefault("dp", 1)
    # the searched mesh runs a REAL cp model (tiny shapes for test speed)
    cfg = T5Config.tiny(batch_size=2 * axes["dp"], src_len=16, tgt_len=16,
                        num_heads=4, dropout_rate=0.0,
                        context_parallel="ring")
    feeds, loss, _ = t5_seq2seq_graph(cfg)
    mesh = ht.make_mesh(axes, jax.devices()[:plan.n_devices])
    ex = ht.Executor({"train": [loss,
                                ht.optim.AdamOptimizer(1e-3).minimize(loss)]},
                     seed=0, mesh=mesh,
                     dist_strategy=ht.dist.ModelParallel(axes))
    src, tgt_in, labels = synthetic_seq2seq_batch(cfg)
    out = ex.run("train", feed_dict={feeds["input_ids"]: src,
                                     feeds["decoder_input_ids"]: tgt_in,
                                     feeds["labels"]: labels})
    assert np.isfinite(float(out[0].asnumpy()))


def test_flash_ab_gate_rules_and_layouts(tmp_path, monkeypatch):
    """tools/flash_ab.py: the gate requires a MEASURED kmask win (review
    finding); the sweep holds the kept geometry only (whole key range);
    and its two layouts are one computation — the head-major entry as a
    layer called it equals the packed operands' own attention."""
    import sys
    import os
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
    import jax.numpy as jnp
    import tools.flash_ab as ab
    from hetu_tpu.ops.attention import dispatch_sdpa_packed, sdpa_reference

    monkeypatch.setattr(ab, "ROOT", str(tmp_path))
    row = {"winner_dense": "flash", "winner_kmask": "flash"}
    # gate: an unmeasured kmask case is NOT a win
    out = ab._persist("cpu", {"128": {"winner_dense": "flash"}}, False)
    assert out["flash_min_len"] == ab.SEQS[-1] * 2        # sentinel
    out = ab._persist("cpu", {"128": row}, False)
    assert out["flash_min_len"] == 128
    assert not hasattr(ab, "_load_previous_rows")   # nothing of
    # ``artifacts/`` survives a chip call: there was nothing to resume

    assert ab._sweep_blocks(512) == [(128, 512), (256, 512), (512, 512)]
    assert ab._sweep_blocks(1024) == [(128, 1024), (256, 1024),
                                      (512, 1024)]
    rng = np.random.RandomState(0)
    q, k, v = (jnp.asarray(rng.randn(2, ab.HEADS, 16, ab.HEAD_DIM),
                           jnp.float32) for _ in range(3))
    packed = tuple(ab._pack(x) for x in (q, k, v))
    assert packed[0].shape == (2, 16, ab.HEADS * ab.HEAD_DIM)
    layer = ab._as_layer(sdpa_reference)(*packed)
    np.testing.assert_array_equal(
        np.asarray(layer), np.asarray(ab._pack(sdpa_reference(q, k, v))))
    np.testing.assert_array_equal(
        np.asarray(layer),
        np.asarray(dispatch_sdpa_packed(*packed, head_dim=ab.HEAD_DIM)))
    assert ab._max_diff(ab._as_layer(sdpa_reference), sdpa_reference,
                        packed, (q, k, v)) == 0.0


def test_plan_responds_to_hardware_constants():
    """The searched plan must be a function of the MEASURED constants
    (round-4 verdict item 6), not a fixed answer: starving the collective
    bandwidth moves the plan away from comm-heavy strategies, and the
    estimated time responds monotonically."""
    specs = [transformer_layer_spec(2048, 512, 32, name=f"l{i}")
             for i in range(6)]
    # memory tight enough that pure dp8 is infeasible -> the search must
    # pick SOME sharded/hybrid strategy, and the interconnect speed
    # decides which
    one_full = MemoryCostModel(HardwareSpec()).layer_bytes(
        specs[0], Strategy(1, 1, 8, False))
    mem = one_full * len(specs) * 0.5
    fast = HardwareSpec(mem_bytes=mem, ici_bw=4.5e10)
    slow = HardwareSpec(mem_bytes=mem, ici_bw=4.5e8)   # 100x starved
    plan_fast = search(specs, 8, hw=fast)
    plan_slow = search(specs, 8, hw=slow)
    assert plan_slow.est_time > plan_fast.est_time
    # under a starved interconnect the plan must not use MORE tensor-
    # parallel ways (the strategy whose comm term pays activation
    # allreduces every layer) than the fast-interconnect plan
    assert max(s.tp for s in plan_slow.strategies) \
        <= max(s.tp for s in plan_fast.strategies)


def test_search_consumes_committed_calibration(tmp_path):
    """HardwareSpec.from_artifact grounds the search in the committed
    on-chip measurement (tools/calibrate_tpu.py artifact schema)."""
    import dataclasses
    import json
    art = {"backend": "tpu", "device_kind": "TPU v5 lite",
           "spec": dataclasses.asdict(HardwareSpec(
               flops=1e12, mem_bytes=2e9, ici_bw=1e9, overlap=0.5))}
    p = tmp_path / "tpu_calibration.json"
    p.write_text(json.dumps(art))
    hw = HardwareSpec.from_artifact(str(p))
    assert hw is not None and hw.flops == 1e12 and hw.ici_bw == 1e9
    # the loaded constants drive the estimate: same plan costed under the
    # measured (slow) spec is strictly slower than under the default
    specs = [transformer_layer_spec(1024, 256, 16, name=f"l{i}")
             for i in range(4)]
    t_default = DPAlg(specs, 8, hw=HardwareSpec()).fit()[0]
    t_measured = DPAlg(specs, 8, hw=hw).fit()[0]
    assert t_measured > t_default


def test_swin_layer_specs_stage_ladder():
    """The swin chain exposes the hierarchy the search must see: windowed
    attention keeps the score term cheap, and patch merges trade tokens
    for width (later stages parameter-heavy, earlier activation-heavy)."""
    from hetu_tpu.autoparallel import swin_layer_specs
    specs = swin_layer_specs(image_size=224, patch_size=4, embed_dim=96,
                             depths=(2, 2, 6, 2), num_heads=(3, 6, 12, 24),
                             window_size=7, batch=8)
    by_name = {s.name: s for s in specs}
    # 1 embed + sum(depths)*2 blocks + 3 merges
    assert len(specs) == 1 + 2 * (2 + 2 + 6 + 2) + 3
    # width doubles per stage: params grow ~4x stage-over-stage
    assert by_name["s3.attn0"].param_bytes == \
        pytest.approx(64 * by_name["s0.attn0"].param_bytes)
    # tokens quarter per stage: activations shrink
    assert by_name["s3.mlp0"].act_bytes < by_name["s0.mlp0"].act_bytes
    # windowed attention: the score term is w2-bounded, so stage-0
    # attention FLOPs stay within ~2x of its projection FLOPs (a global
    # 3136-token attention would be ~25x)
    proj_flops = 2 * (8 * 56 * 56) * 4 * 96 * 96
    assert by_name["s0.attn0"].fwd_flops < 2 * proj_flops
    # the chain is searchable end-to-end
    plan = search(specs, n_devices=8)
    assert len(plan.strategies) == len(specs)


def test_swin_specs_reject_untileable_geometry_and_skip_cp_charge():
    """Geometry the model would refuse must fail the cost model too;
    UNSHIFTED window attention pays no cp ring rotation, while SHIFTED
    blocks (which straddle any window-aligned shard cut) carry a halo
    kv_bytes charge — and blocks where window == resolution never shift
    (models/swin.py's shift rule)."""
    from hetu_tpu.autoparallel import swin_layer_specs
    with pytest.raises(AssertionError):
        swin_layer_specs(224, 4, 96, (2, 2), (3, 6), window_size=12,
                         batch=8)
    specs = swin_layer_specs(32, 4, 32, (2, 2), (2, 4), 4, batch=8)
    by_name = {s.name: s for s in specs}
    assert not by_name["s0.attn0"].attn                 # unshifted
    assert by_name["s0.attn1"].attn                     # shifted: halo
    assert by_name["s0.attn1"].kv_bytes > 0
    # stage 1: window == resolution → no shift anywhere
    assert not by_name["s1.attn0"].attn
    assert not by_name["s1.attn1"].attn


# ---------------------------------------------- ISSUE 15: the closed loop
# search → Executor(plan=) → measured step times → rerank

def _plan_mlp_graph(dim=16, batch=16):
    """Tiny 2-linear MLP + Adam step for the executor-plan tests."""
    x = ht.placeholder_op("x", shape=(batch, dim))
    y = ht.placeholder_op("y", shape=(batch, dim))
    l1 = ht.layers.Linear(dim, 2 * dim, activation="relu", name="pl.l1")
    l2 = ht.layers.Linear(2 * dim, dim, name="pl.l2")
    out = l2(l1(x))
    loss = ht.ops.reduce_mean_op(ht.ops.mul_op(out - y, out - y), [0, 1])
    opt_op = ht.optim.AdamOptimizer(1e-3).minimize(loss)
    rng = np.random.RandomState(0)
    fd = {x: rng.randn(batch, dim).astype(np.float32),
          y: rng.randn(batch, dim).astype(np.float32)}
    return loss, opt_op, fd, (l1, l2)


def _mlp_plan(strategy):
    spec = LayerSpec("mlp", 1e4, 1e6, 1e5)
    from hetu_tpu.autoparallel.plan import ParallelPlan
    return ParallelPlan([spec], [strategy], 8, est_time=1e-3)


def test_time_cost_model_hand_math_and_calibrated_wiring(monkeypatch):
    """The satellite: calibrate_hardware()'s measured constants drive the
    TimeCostModel terms — checked against the hand formula, and the
    `calibrated()` constructor actually consumes the measured spec."""
    hw = HardwareSpec(flops=1e12, ici_bw=1e9, overlap=0.25, mem_bytes=1e12)
    tm = TimeCostModel(hw)
    spec = LayerSpec("l", param_bytes=8e6, fwd_flops=2e9, act_bytes=1e6)
    s = Strategy(dp=8)
    # compute: 3*flops/(dp)/F; dp grad sync: 2(n-1)/n ring volume over
    # measured bw, scaled by the measured un-overlapped fraction
    compute = 3.0 * 2e9 / 8 / 1e12
    dp_comm = (8e6 * 2 * 7 / 8) / 1e9 * (1.0 - 0.25)
    assert tm.layer_time(spec, s) == pytest.approx(compute + dp_comm)
    # fsdp adds the forward all-gather of dp-sharded params
    s_f = Strategy(dp=8, fsdp=True)
    ag = (8e6 * 7 / 8) / 1e9 * 0.5
    assert tm.layer_time(spec, s_f) == pytest.approx(
        compute + dp_comm + ag)

    measured = HardwareSpec(flops=3.3e12, ici_bw=7e9, overlap=0.5)
    monkeypatch.setattr(HardwareSpec, "measure",
                        classmethod(lambda cls, mesh=None, **kw: measured))
    tm2 = TimeCostModel.calibrated()
    assert tm2.hw is measured
    # and search(calibrate=True) prices with the same measured spec
    plan = search([spec], 8, calibrate=True, uniform=True, allow_pp=False)
    assert plan.hw is measured


def test_graph_layer_specs_buckets_real_graph():
    """Per-layer pricing of a REAL graph: buckets follow the layer-name
    anchors through dataflow, identical layers price identically, and
    the bucketed chain conserves the fused totals."""
    from hetu_tpu.autoparallel import graph_layer_spec, graph_layer_specs
    from hetu_tpu.models.bert import (BertConfig, bert_pretrain_graph,
                                      synthetic_mlm_batch)
    cfg = BertConfig.tiny(batch_size=4, seq_len=16)
    feeds, loss, _ = bert_pretrain_graph(cfg)
    ids, tt, labels, attn = synthetic_mlm_batch(cfg)
    fd = {feeds["input_ids"]: np.asarray(ids, np.int32),
          feeds["token_type_ids"]: np.asarray(tt, np.int32),
          feeds["masked_lm_labels"]: np.asarray(labels, np.int32),
          feeds["attention_mask"]: np.asarray(attn, np.int32)}
    from hetu_tpu.autoparallel import bert_split

    specs = graph_layer_specs([loss], feeds=fd, split=bert_split)
    by_name = {s.name: s for s in specs}
    assert "bert.layer0" in by_name and "bert.layer1" in by_name
    # identical encoder layers must price identically (regression: the
    # mask reshape must not capture a layer's attention into the stem,
    # and the MLM head must not leak into layer1)
    assert by_name["bert.layer0"].fwd_flops == pytest.approx(
        by_name["bert.layer1"].fwd_flops)
    assert by_name["head"].fwd_flops > 0      # vocab decoder matmul
    assert by_name["bert.layer0"].attn and by_name["bert.layer1"].attn
    assert by_name["bert.layer0"].param_bytes > 0
    # bucketed chain == fused single-spec walk (same numbers, same walk)
    fused = graph_layer_spec([loss], feeds=fd)  # default split irrelevant

    assert sum(s.fwd_flops for s in specs) == pytest.approx(fused.fwd_flops)
    assert sum(s.param_bytes for s in specs) == pytest.approx(
        fused.param_bytes)
    assert sum(s.act_bytes for s in specs) == pytest.approx(fused.act_bytes)
    # the chain is searchable end-to-end with candidates attached
    from hetu_tpu.autoparallel import search_graph
    plan = search_graph([loss], 8, feeds=fd, split=bert_split,
                        hw=HardwareSpec(mem_bytes=64e9), uniform=True,
                        allow_pp=False, max_tp=1, topk=3)
    assert plan.candidates and plan.candidates[0] is plan
    assert [c.est_time for c in plan.candidates] == sorted(
        c.est_time for c in plan.candidates)
    assert len(plan.specs) == len(specs)


def test_autoparallel_counters_and_profiler_accessor():
    from hetu_tpu.metrics import (autoparallel_counts,
                                  reset_autoparallel_counts)
    from hetu_tpu.profiler import HetuProfiler
    reset_autoparallel_counts()
    specs = [transformer_layer_spec(128, 32, 8, name="l0")]
    search(specs, 8, hw=HardwareSpec(mem_bytes=64e9), uniform=True)
    counts = autoparallel_counts()
    assert counts.get("autoparallel_plans_searched", 0) >= 1
    assert HetuProfiler.autoparallel_counters() == counts
    assert "autoparallel" in HetuProfiler.all_counters()
    reset_autoparallel_counts()
    assert autoparallel_counts() == {}


def test_rerank_reorders_candidates_from_measurements():
    """The feedback leg: a mispriced cost model ranks the slow plan
    first; measurements re-order the candidates and flip the best —
    counted as a rerank flip."""
    from hetu_tpu.metrics import (autoparallel_counts,
                                  reset_autoparallel_counts)
    spec = LayerSpec("mlp", 1e4, 1e6, 1e5)
    from hetu_tpu.autoparallel.plan import ParallelPlan
    # mispriced: the model thinks fsdp is faster (est 1ms < 2ms)
    fast_pred = ParallelPlan([spec], [Strategy(dp=8, fsdp=True)], 8,
                             est_time=1e-3)
    slow_pred = ParallelPlan([spec], [Strategy(dp=8)], 8, est_time=2e-3)
    fast_pred.candidates = [fast_pred, slow_pred]
    reset_autoparallel_counts()
    # measurement says the opposite: plain dp is 4x faster
    best = fast_pred.rerank({0: 8e-3, 1: 2e-3})
    assert best is slow_pred
    assert best.measured_time == pytest.approx(2e-3)
    assert fast_pred.measured_time == pytest.approx(8e-3)
    assert best.candidates[0] is slow_pred
    assert autoparallel_counts().get("autoparallel_rerank_flips") == 1
    # re-ranking again with the same verdict is stable (no second flip)
    best.rerank({0: 2e-3, 1: 8e-3})
    assert autoparallel_counts().get("autoparallel_rerank_flips") == 1
    reset_autoparallel_counts()


def test_executor_plan_parity_and_compositions():
    """Acceptance regressions: plan-annotated execution is loss-equal to
    unplanned execution at the same dp; plan+zero routes fsdp through
    the slab machinery (ONE mechanism — params stay un-annotated, slab
    plans exist); plan+remat composes without double-remat."""
    loss, opt_op, fd, _ = _plan_mlp_graph()
    ex_plain = ht.Executor({"train": [loss, opt_op]}, seed=0,
                           dist_strategy=ht.dist.DataParallel(
                               num_devices=8))
    ref = [float(ex_plain.run("train", feed_dict=fd)[0].asnumpy())
           for _ in range(2)]

    loss, opt_op, fd, _ = _plan_mlp_graph()
    ex_dp = ht.Executor({"train": [loss, opt_op]}, seed=0,
                        plan=_mlp_plan(Strategy(dp=8)))
    got = [float(ex_dp.run("train", feed_dict=fd)[0].asnumpy())
           for _ in range(2)]
    assert got == ref                       # same mesh, same math: bitwise
    assert ex_dp.zero == 0

    # fsdp plan: defaults to zero=3 via the PR 6 slab route, params carry
    # NO per-param GSPMD annotation (no double-sharding), loss matches
    loss, opt_op, fd, _ = _plan_mlp_graph()
    ex_f = ht.Executor({"train": [loss, opt_op]}, seed=0,
                       plan=_mlp_plan(Strategy(dp=8, fsdp=True)))
    assert ex_f.zero == 3 and len(ex_f._zero_plans) == 1
    assert all(getattr(n, "sharding", None) is None
               for n in ex_f.global_topo)
    got_f = [float(ex_f.run("train", feed_dict=fd)[0].asnumpy())
             for _ in range(2)]
    np.testing.assert_allclose(got_f, ref, rtol=1e-6)

    # plan + remat: the remat policy still applies (its plan fingerprints
    # into the step signature), bitwise loss-equal — no double-remat
    loss, opt_op, fd, _ = _plan_mlp_graph()
    ex_r = ht.Executor({"train": [loss, opt_op]}, seed=0,
                       plan=_mlp_plan(Strategy(dp=8)), remat="dots")
    assert ex_r.remat == "dots"
    got_r = [float(ex_r.run("train", feed_dict=fd)[0].asnumpy())
             for _ in range(2)]
    assert got_r == ref


def test_executor_plan_lint_rejects_unrealized_plan():
    """An illegal plan fails fast at construction, naming the offending
    layer — regardless of validate='warn' (the default)."""
    from hetu_tpu.analysis.lint import GraphValidationError
    loss, opt_op, fd, _ = _plan_mlp_graph()
    tp_plan = _mlp_plan(Strategy(tp=2, dp=4))
    with pytest.raises(GraphValidationError, match="mlp"):
        ht.Executor({"train": [loss, opt_op]}, seed=0, plan=tp_plan)
    # cp plan against a graph with no ring/ulysses attention
    loss, opt_op, fd, _ = _plan_mlp_graph()
    cp_plan = _mlp_plan(Strategy(dp=4, cp=2))
    with pytest.raises(GraphValidationError, match="ring"):
        ht.Executor({"train": [loss, opt_op]}, seed=0, plan=cp_plan)
    # validate='off' silences the lint but NEVER the plan gate: an
    # unrealized plan compiling anyway would hand the measurement loop
    # the wrong program
    loss, opt_op, fd, _ = _plan_mlp_graph()
    tp_plan = _mlp_plan(Strategy(tp=2, dp=4))
    with pytest.raises(GraphValidationError, match="mlp"):
        ht.Executor({"train": [loss, opt_op]}, seed=0, plan=tp_plan,
                    validate="off")


def test_plan_coverage_is_executor_level_not_per_subgraph():
    """Plan coverage is a property of the EXECUTOR, not of each fetch
    set: an auxiliary subgraph that never touches the plan-annotated
    kernels (a feed statistic, an eval head) must not fail validation
    when the train subgraph realizes the plan."""
    loss, opt_op, fd, (l1, l2) = _plan_mlp_graph()

    class _Pair:
        in_kernels = [l1.weight_var]
        out_kernels = [l2.weight_var]

    tp_plan = _mlp_plan(Strategy(tp=2, dp=4))
    tp_plan.bind([_Pair()])
    x = next(iter(fd))
    aux = ht.ops.reduce_mean_op(ht.ops.mul_op(x, x), [0, 1])
    ex = ht.Executor({"train": [loss, opt_op], "aux": [aux]}, seed=0,
                     plan=tp_plan)
    assert np.isfinite(
        float(ex.run("aux", feed_dict={x: fd[x]})[0].asnumpy()))
    assert np.isfinite(
        float(ex.run("train", feed_dict=fd)[0].asnumpy()))


def test_measure_plans_compile_once_and_plan_diff():
    """The measurement pass: one compile per distinct candidate (an
    identical re-measure HITS the compiled-step cache), per-plan
    step_time_us histogram mins land on the obs registry, and plan_diff
    reports the per-layer predicted-vs-measured table."""
    from hetu_tpu.autoparallel import measure_plans, plan_diff
    from hetu_tpu.metrics import (autoparallel_counts,
                                  reset_autoparallel_counts,
                                  step_time_stats)

    def build(plan):
        # dims unique to THIS test: an earlier test's identical graph in
        # the process-wide step cache would turn the first candidate's
        # expected compile into a hit
        loss, opt_op, fd, _ = _plan_mlp_graph(dim=24, batch=8)
        ex = ht.Executor({"train": [loss, opt_op]}, seed=0, plan=plan)
        return ex, fd, "train"

    reset_autoparallel_counts()
    # two IDENTICAL dp plans: the second must reuse the first's compiled
    # step (fingerprints equal), not build a second executable
    cands = [_mlp_plan(Strategy(dp=8)), _mlp_plan(Strategy(dp=8))]
    ms = measure_plans(cands, build, steps=2, warmup=0, label="t15")
    counts = autoparallel_counts()
    assert counts.get("autoparallel_plans_measured") == 2
    assert counts.get("autoparallel_plans_compiled", 0) >= 1
    assert counts.get("autoparallel_candidate_cache_hits", 0) >= 1
    assert ms[0].compiled and not ms[1].compiled
    for m in ms:
        # each candidate's verdict is the min over ITS OWN measured
        # walls — never read back through the process-wide registry
        # (identical plans share a histogram tag there; an earlier run's
        # faster steps must not masquerade as this one's min)
        assert m.step_time_us == pytest.approx(min(m.walls_us))
    # ... but every measured step IS published to the shared registry
    # histogram: its min is the best step over BOTH runs
    all_walls = [w for m in ms for w in m.walls_us]
    snap = step_time_stats().get(ms[0].label)
    assert snap and snap["min"] == pytest.approx(min(all_walls))
    assert snap["count"] >= len(all_walls)
    d = plan_diff(ms[0].plan, measured=ms[0],
                  hw=HardwareSpec(mem_bytes=64e9))
    assert d["layers"][0]["layer"] == "mlp"
    assert d["measured_total_us"] == pytest.approx(ms[0].step_time_us)
    assert d["model_error"] > 0
    assert d["layers"][0]["measured_us"] == pytest.approx(
        d["measured_total_us"])
    reset_autoparallel_counts()


def test_plan_fingerprint_keys_step_cache_signature():
    """Two executors over structurally identical graphs, differing only
    in plan, must not alias one compiled step."""
    from hetu_tpu.graph import step_cache
    loss, opt_op, fd, _ = _plan_mlp_graph()
    ex_a = ht.Executor({"train": [loss, opt_op]}, seed=0,
                       plan=_mlp_plan(Strategy(dp=8)))
    loss, opt_op, fd, _ = _plan_mlp_graph()
    ex_b = ht.Executor({"train": [loss, opt_op]}, seed=0,
                       dist_strategy=ht.dist.DataParallel(num_devices=8))
    sig_a = step_cache.signature(ex_a.subexecutors["train"])
    sig_b = step_cache.signature(ex_b.subexecutors["train"])
    assert sig_a is not None and sig_b is not None and sig_a != sig_b


@pytest.mark.slow    # the full measured sweep: ~2-4 min of candidate
# compiles + interleaved measured steps in a fresh pinned-CPU process
def test_plan_diff_tool_full_sweep(tmp_path):
    """Acceptance: on the 8-device CPU mesh the reranked searched plan
    beats (measured-min, never loses to) naive DP for bert-tiny and the
    small moe, with the per-layer predicted-vs-measured table and the
    autoparallel counters in the artifact."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "autoparallel_bench.json"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)       # the tool pins its own device count
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "plan_diff.py"),
         "--config", "all", "--steps", "4", "--warmup", "1",
         "--out", str(out)],
        capture_output=True, text=True, timeout=560, env=env, cwd=root)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    art = json.loads(out.read_text())
    assert art["metric"] == "autoparallel_best_vs_naive_dp_speedup_min"
    cfgs = art["extra"]["configs"]
    for name in ("bert", "moe"):
        row = cfgs[name]
        assert row["beats_naive_dp"], row
        assert row["best_step_us"] <= row["naive_dp_step_us"]
        # per-layer predicted-vs-measured table present and scaled
        layers = row["plan_diff"]["layers"]
        assert layers and all("predicted_us" in r and "measured_us" in r
                              for r in layers)
        assert len(row["candidates"]) >= 2
    counters = art["extra"]["autoparallel_counters"]
    assert counters["autoparallel_plans_measured"] >= 4
    assert counters["autoparallel_plans_compiled"] >= 4
