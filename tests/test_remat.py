"""ISSUE 13: policy-graded selective remat + verified collective overlap.

Lean by design (tier-1 budget pressure): tiny graphs, shared baselines,
the dp=4 overlap audit exercised on SYNTHETIC HLO (the real config's
verdicts live in the committed ``artifacts/hlo_audit_cpu.json``).
"""
import numpy as np
import pytest

import hetu_tpu as ht
from hetu_tpu import metrics
from hetu_tpu.graph import step_cache
from hetu_tpu.parallel import remat as remat_mod

POLICIES = ("dots", "full", "auto", "offload")


def _mlp(batch=32, din=16, hidden=64, classes=4, seed=0, **ex_kw):
    """3-matmul dense graph: >= 2 segments at 1 anchor/segment."""
    rng = np.random.RandomState(seed)
    x = ht.placeholder_op("x", shape=(batch, din))
    y_ = ht.placeholder_op("y", shape=(batch, classes))
    w1 = ht.Variable("w1", value=rng.randn(din, hidden).astype(np.float32) * .2)
    w2 = ht.Variable("w2", value=rng.randn(hidden, hidden).astype(np.float32) * .2)
    w3 = ht.Variable("w3", value=rng.randn(hidden, classes).astype(np.float32) * .2)
    h = ht.relu_op(ht.matmul_op(x, w1))
    h = ht.relu_op(ht.matmul_op(h, w2))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w3), y_), [0])
    opt = ht.optim.AdamOptimizer(0.01)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0, **ex_kw)
    xv = rng.randn(batch, din).astype(np.float32)
    yv = np.eye(classes, dtype=np.float32)[rng.randint(0, classes, batch)]
    return ex, {x: xv, y_: yv}


def _loss_bits(ex, fd, n=4):
    out = None
    bits = []
    for _ in range(n):
        out = ex.run("train", feed_dict=fd)
        bits.append(np.float32(out[0].asnumpy()).tobytes().hex())
    return bits


def test_resolve_policy_ladder():
    assert remat_mod.resolve_policy(None) == "off"
    assert remat_mod.resolve_policy(False) == "off"
    assert remat_mod.resolve_policy(True) == "dots"      # pre-13 meaning
    for p in remat_mod.POLICIES:
        assert remat_mod.resolve_policy(p) == p
    with pytest.raises(ValueError, match="bogus"):
        remat_mod.resolve_policy("bogus")
    # construction fails fast like pipeline= does
    with pytest.raises(ValueError, match="remat"):
        _mlp(remat="bogus")


def test_policy_parity_dense_bitwise(monkeypatch):
    """Every policy's training losses are BITWISE equal to off — remat
    replays the same ops (dropout keys fold at trace time), so parity is
    exact, not approximate."""
    monkeypatch.setenv("HETU_REMAT_SEGMENT_ANCHORS", "1")
    # a budget far below the toy's persistent+activation bytes, so the
    # greedy auto planner must remat every segment
    monkeypatch.setenv("HETU_HBM_BUDGET_MB", "0.01")
    step_cache.clear()
    ex, fd = _mlp(remat="off")
    base = _loss_bits(ex, fd)
    for pol in POLICIES:
        step_cache.clear()
        ex, fd = _mlp(remat=pol)
        assert _loss_bits(ex, fd) == base, pol
        if pol in ("full", "auto"):
            plan = ex.remat_plan("train")
            assert plan and plan["segments_rematted"] >= 1, pol


@pytest.mark.slow
def test_bert_tiny_full_remat_parity_and_peak_drop():
    """The acceptance family: bert-tiny off vs full (segmented) — 3
    steps bitwise (dropout + attention + layernorm all replay), and the
    compiled step's XLA temp (the in-step activation peak
    ``memory_accounting(feed_dict)`` reports) strictly drops.  ``slow``
    per the >10s tier-1 budget rule — the dense + wdl-PS parity tests
    above hold the tier-1 coverage.
    bs4/seq64 is the verified-bitwise config: at bs2/seq32 XLA's
    fusion choices introduce a 1-ulp FMA drift in the recompute (the
    ``parallel/zero.py`` FMA-contraction trap), which is about fusion,
    not remat correctness."""
    from hetu_tpu.models.bert import (BertConfig, bert_pretrain_graph,
                                      synthetic_mlm_batch)

    def build(pol):
        step_cache.clear()
        cfg = BertConfig.tiny(batch_size=4, seq_len=64)
        feeds, loss, _logits = bert_pretrain_graph(cfg)
        opt = ht.optim.AdamOptimizer(1e-3)
        ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0,
                         remat=pol)
        ids, tt, labels, attn = synthetic_mlm_batch(cfg)
        fd = {feeds["input_ids"]: np.asarray(ids, np.int32),
              feeds["token_type_ids"]: np.asarray(tt, np.int32),
              feeds["masked_lm_labels"]: np.asarray(labels, np.int32),
              feeds["attention_mask"]: np.asarray(attn, np.int32)}
        return ex, fd

    ex, fd = build("off")
    base = _loss_bits(ex, fd, n=3)
    t_off = ex.memory_accounting(feed_dict=fd, name="train")[
        "step_temp_bytes_per_device"]
    del ex
    ex, fd = build("full")
    assert _loss_bits(ex, fd, n=3) == base
    assert ex.remat_plan("train")["segments_rematted"] >= 1
    mem = ex.memory_accounting(feed_dict=fd, name="train")
    t_full = mem["step_temp_bytes_per_device"]
    assert mem["live_buffer_peak_bytes_per_device"] \
        == mem["live_buffer_bytes_per_device"] + t_full
    assert t_off and t_full and t_full < t_off


def test_policy_parity_wdl_ps_bitwise(monkeypatch):
    """The sparse family: PS-embedding CTR graph — remat composes with
    the host pull/push path, losses AND server table bitwise equal."""
    from hetu_tpu.ps import EmbeddingStore
    monkeypatch.setenv("HETU_REMAT_SEGMENT_ANCHORS", "1")

    rng = np.random.RandomState(0)
    vocab, dim, batch = 32, 8, 16
    table0 = rng.randn(vocab, dim).astype(np.float32) * 0.1
    ids_v = rng.randint(0, vocab, batch)
    yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, batch)]
    w0 = rng.randn(dim, 16).astype(np.float32) * 0.3
    v0 = rng.randn(16, 4).astype(np.float32) * 0.3

    def run(pol):
        step_cache.clear()
        st = EmbeddingStore()
        t = st.init_table(vocab, dim, opt="sgd", lr=0.05, seed=0)
        st.set_data(t, table0.copy())
        ids = ht.placeholder_op("ids")
        y_ = ht.placeholder_op("y")
        h = ht.ps_embedding_lookup_op((st, t), ids, width=dim)
        w = ht.Variable("w", value=w0.copy())
        v = ht.Variable("v", value=v0.copy())
        hidden = ht.relu_op(ht.matmul_op(h, w))
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(hidden, v), y_), [0])
        opt = ht.optim.AdamOptimizer(0.01)
        ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=3,
                         remat=pol)
        bits = [np.float32(
            ex.run("train", feed_dict={ids: ids_v, y_: yv})[0].asnumpy()
        ).tobytes().hex() for _ in range(3)]
        rows = st.pull(t, np.arange(vocab)).copy()
        del ex
        return bits, rows

    base_bits, base_rows = run("off")
    for pol in ("full", "dots"):
        bits, rows = run(pol)
        assert bits == base_bits, pol
        np.testing.assert_array_equal(rows, base_rows)


def test_auto_plan_matches_cost_model_hand_math(monkeypatch):
    """2-segment toy: greedy auto remats the CHEAPEST-recompute-per-
    byte segment first, exactly as the cost-model hand math says."""
    monkeypatch.setenv("HETU_REMAT_SEGMENT_ANCHORS", "1")
    # two 1-anchor segments with hand-computable pricing:
    #   A = [relu(x), matmul -> (64,512)]: interior relu frees
    #       64*32*4 = 8 KB, recompute 2*64*512*32 ~ 2.1 MFLOP
    #   B = [relu(ha), matmul -> (64,4)]: interior relu frees
    #       64*512*4 = 128 KB, recompute 2*64*4*512 ~ 0.26 MFLOP
    # -> B is ~128x cheaper per byte freed; greedy must pick B first
    batch, din = 64, 32
    from hetu_tpu.graph.node import topo_sort
    rng = np.random.RandomState(0)
    x = ht.placeholder_op("x", shape=(batch, din))
    y_ = ht.placeholder_op("y", shape=(batch, 4))
    wa = ht.Variable("wa", value=rng.randn(din, 512).astype(np.float32) * .1)
    wb = ht.Variable("wb", value=rng.randn(512, 4).astype(np.float32) * .1)
    ha = ht.relu_op(ht.matmul_op(ht.relu_op(x), wa))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(ha, wb), y_), [0])
    opt = ht.optim.SGDOptimizer(0.1)
    fetches = [loss, opt.minimize(loss)]
    topo = topo_sort(fetches)
    skip = [n for n in topo if n.op_type == "OptimizerUpdate"]

    plan_all = remat_mod.build_plan(topo, fetches, "full", skip=skip)
    assert len(plan_all.segments) == 2 and plan_all.priced
    segs = sorted(plan_all.segments, key=lambda s: s.cost_per_byte)
    assert segs[0].saved_bytes > segs[1].saved_bytes   # B frees more

    # budget that only needs ONE segment's saving: greedy picks segs[0]
    persistent = 0
    total = sum(s.act_bytes for s in plan_all.segments)
    budget = int(persistent + total - segs[0].saved_bytes)
    plan = remat_mod.build_plan(topo, fetches, "auto", skip=skip,
                                persistent_bytes=persistent,
                                budget=budget, budget_source="test")
    rematted = [s.index for s in plan.segments if s.remat]
    assert rematted == [segs[0].index]
    # no budget resolvable -> conservative: remat everything, noted
    monkeypatch.delenv("HETU_HBM_BUDGET_MB", raising=False)
    plan_nb = remat_mod.build_plan(topo, fetches, "auto", skip=skip)
    assert plan_nb.n_remat == len(plan_nb.segments)
    assert "no HBM budget" in plan_nb.note


def test_policy_and_plan_in_step_cache_signature(monkeypatch):
    """Revisited policy = hit; new policy = miss; an auto plan under a
    DIFFERENT budget = miss (the plan fingerprint is in the signature)."""
    monkeypatch.setenv("HETU_REMAT_SEGMENT_ANCHORS", "1")
    step_cache.clear()
    metrics.reset_step_cache_counts()

    def build(pol, budget=None):
        if budget is not None:
            monkeypatch.setenv("HETU_HBM_BUDGET_MB", str(budget))
        else:
            monkeypatch.delenv("HETU_HBM_BUDGET_MB", raising=False)
        ex, fd = _mlp(remat=pol)
        ex.run("train", feed_dict=fd)
        del ex

    build("dots")
    build("dots")                  # revisit -> hit
    build("full")                  # new policy -> miss
    build("dots")                  # revisit -> hit
    sc = metrics.step_cache_counts()
    assert sc.get("step_cache_miss") == 2
    assert sc.get("step_cache_hit") == 2
    # two different budgets -> two different auto plans -> two misses
    step_cache.clear()
    metrics.reset_step_cache_counts()
    build("auto", budget=0.01)     # unreachable -> remats everything
    build("auto", budget=100000)   # fits -> remats nothing
    sc = metrics.step_cache_counts()
    assert sc.get("step_cache_miss") == 2
    assert not sc.get("step_cache_hit")


def test_remat_policy_lint_rule(monkeypatch):
    """The rule fires with node provenance: unknown name (error),
    forward-only no-op (warn), auto with no budget (warn)."""
    monkeypatch.delenv("HETU_HBM_BUDGET_MB", raising=False)
    rng = np.random.RandomState(0)
    x = ht.placeholder_op("x", shape=(4, 8))
    w = ht.Variable("w", value=rng.randn(8, 2).astype(np.float32))
    out = ht.matmul_op(x, w)

    rep = ht.lint([out], remat="bogus")
    errs = [d for d in rep.errors if d.rule == "remat-policy"]
    assert errs and "bogus" in errs[0].message
    assert "created at" in str(errs[0])

    rep = ht.lint([out], remat="full")     # forward-only: no-op warn
    warns = [d for d in rep.warnings if d.rule == "remat-policy"]
    assert warns and "forward-only" in warns[0].message

    loss = ht.reduce_mean_op(out, [0, 1])
    opt = ht.optim.SGDOptimizer(0.1)
    rep = ht.lint([loss, opt.minimize(loss)], remat="auto")
    warns = [d for d in rep.warnings if d.rule == "remat-policy"]
    assert warns and "HETU_HBM_BUDGET_MB" in warns[0].message

    # the executor path (validate='warn') surfaces the same rule
    with pytest.warns(UserWarning, match="remat-policy"):
        _mlp(remat="auto")


def test_offload_fallback_counted_and_hard_fail(monkeypatch):
    """On a TPU-less backend 'offload' takes the counted on-device
    fallback; HETU_REQUIRE_OFFLOAD=1 makes it a hard failure."""
    metrics.reset_remat_counts()
    step_cache.clear()
    ex, fd = _mlp(remat="offload")
    base_off_ex, base_fd = _mlp(remat="off")
    assert _loss_bits(ex, fd, n=2) == _loss_bits(base_off_ex, base_fd, n=2)
    assert metrics.remat_counts().get("remat_offload_fallback", 0) >= 1
    monkeypatch.setenv("HETU_REQUIRE_OFFLOAD", "1")
    step_cache.clear()
    with pytest.raises(RuntimeError, match="HETU_REQUIRE_OFFLOAD"):
        ex2, fd2 = _mlp(remat="offload")
        ex2.run("train", feed_dict=fd2)


def test_clean_run_records_no_remat_counters():
    metrics.reset_remat_counts()
    step_cache.clear()
    ex, fd = _mlp(remat="off")
    ex.run("train", feed_dict=fd)
    assert metrics.remat_counts() == {}
    assert ht.HetuProfiler.remat_counters() == {}


def test_pipeline_default_routes_through_resolver(monkeypatch):
    """pipeline='pipedream' + remat='dots' composes: ONE wrap with the
    explicit policy, no second per-microbatch full wrap (the pre-13
    double-remat); remat='off' keeps the 1F1B default via the same
    resolver."""
    calls = []
    real = remat_mod.wrap_loss

    def spy(fn, pol):
        calls.append(pol)
        return real(fn, pol)

    monkeypatch.setattr(remat_mod, "wrap_loss", spy)

    def build(pol):
        import warnings
        step_cache.clear()
        calls.clear()
        with warnings.catch_warnings():
            # no PipelineBlock: the scanned-accumulation warning is the
            # known (intended) path here
            warnings.simplefilter("ignore")
            ex, fd = _mlp(batch=32, remat=pol, pipeline="pipedream",
                          num_microbatches=2)
            ex.run("train", feed_dict=fd)
        return list(calls)

    assert build("off") == ["microbatch"]
    assert build("dots") == ["dots"]


# -------------------------------------------------- overlap audit units

def _hlo(body):
    return ("HloModule jit_step, is_scheduled=true\n\n"
            "ENTRY %main (p0: f32[4]) -> f32[4] {\n" + body + "\n}\n")


ZMETA = ('metadata={op_name="x" source_file="/r/hetu_tpu/parallel/'
         'zero.py" source_line=252}')


def test_overlap_audit_dataflow_mode():
    from tools import overlap_audit as oa
    # gather0 feeds dot.1 (descendant); dot.2 is independent -> later
    # gather (gather1) overlappable; grad reduce independent of dot.2
    body = """
  %p0 = f32[4]{0} parameter(0)
  %ag0 = f32[4]{0} all-gather(f32[4]{0} %p0), channel_id=1, __ZMETA__
  %dot.1 = f32[4]{0} dot(f32[4]{0} %ag0, f32[4]{0} %ag0)
  %ag1 = f32[4]{0} all-gather(f32[4]{0} %p0), channel_id=2, __ZMETA__
  %dot.2 = f32[4]{0} dot(f32[4]{0} %dot.1, f32[4]{0} %dot.1)
  %dot.3 = f32[4]{0} dot(f32[4]{0} %ag1, f32[4]{0} %dot.2)
  %ar0 = f32[4]{0} all-reduce(f32[4]{0} %dot.1), channel_id=3, __ZMETA__
""".replace("__ZMETA__", ZMETA)
    res = oa.audit_hlo(_hlo(body))
    assert res["mode"] == "dataflow"
    assert res["checks"]["overlap_allgather_forward"]       # ag1: dot.2
    assert res["checks"]["overlap_gradsync_backward"]       # ar0: dot.2/3
    # no zero collectives at all -> both checks FAIL (no silent pass)
    res2 = oa.audit_hlo(_hlo(
        "  %p0 = f32[4]{0} parameter(0)\n"
        "  %dot.1 = f32[4]{0} dot(f32[4]{0} %p0, f32[4]{0} %p0)"))
    assert not res2["checks"]["overlap_allgather_forward"]
    assert not res2["checks"]["overlap_gradsync_backward"]


def test_overlap_audit_async_pair_mode():
    from tools import overlap_audit as oa
    good = """
  %p0 = f32[4]{0} parameter(0)
  %ags = f32[4]{0} all-gather-start(f32[4]{0} %p0), channel_id=1, __ZMETA__
  %dot.1 = f32[4]{0} dot(f32[4]{0} %p0, f32[4]{0} %p0)
  %agd = f32[4]{0} all-gather-done(f32[4]{0} %ags)
  %rss = f32[4]{0} reduce-scatter-start(f32[4]{0} %dot.1), channel_id=2, __ZMETA__
  %dot.2 = f32[4]{0} dot(f32[4]{0} %dot.1, f32[4]{0} %dot.1)
  %rsd = f32[4]{0} reduce-scatter-done(f32[4]{0} %rss)
""".replace("__ZMETA__", ZMETA)
    res = oa.audit_hlo(_hlo(good))
    assert res["mode"] == "async-pairs"
    assert all(res["checks"].values())
    bad = """
  %p0 = f32[4]{0} parameter(0)
  %ags = f32[4]{0} all-gather-start(f32[4]{0} %p0), channel_id=1, __ZMETA__
  %agd = f32[4]{0} all-gather-done(f32[4]{0} %ags)
  %dot.1 = f32[4]{0} dot(f32[4]{0} %agd, f32[4]{0} %agd)
""".replace("__ZMETA__", ZMETA)
    res = oa.audit_hlo(_hlo(bad))
    assert not res["checks"]["overlap_allgather_forward"]


def test_overlap_trace_twin_checker():
    from tools import overlap_audit as oa
    ev = [
        {"ph": "X", "name": "step", "ts": 0, "dur": 100},
        {"ph": "X", "name": "jit.dispatch", "ts": 10, "dur": 20},
        {"ph": "s", "name": "async_step", "ts": 30},
        {"ph": "X", "name": "step", "ts": 100, "dur": 100},
        {"ph": "X", "name": "jit.dispatch", "ts": 110, "dur": 20},
        {"ph": "s", "name": "async_step", "ts": 130},   # 2 in flight
        {"ph": "f", "name": "async_step", "ts": 150},
        {"ph": "f", "name": "async_step", "ts": 190},
    ]
    res = oa.audit_trace_events(ev, min_steps=2)
    assert all(res["checks"].values())
    # a fully synchronous run never has two flows open
    sync = [e for e in ev if e["ph"] != "s" and e["ph"] != "f"]
    res = oa.audit_trace_events(sync, min_steps=2)
    assert not res["checks"]["trace_async_inflight"]
