"""Elastic data-parallel training tests (ISSUE 12;
``parallel/elastic.py`` + ``Executor.resize_world``).

The contract under test: a dp=4 job survives a rank loss by shrinking
to dp=3 WITHOUT a restart — state redistributed bitwise, the dp=3
executable a one-time compile, gradients rescaled by construction (the
shrunk-world mean equals the partial-reduce alive-mask mean, held
bitwise through an optimizer step) — and grows back to dp=4 when the
rank rejoins, hitting the compiled-step cache instead of recompiling.
Every resize is telemetry: ``elastic_*`` counters, ``elastic.resize``
spans + ``elastic:shrink``/``elastic:grow`` instants placed BETWEEN
step spans in the exported Perfetto trace (machine-checked).

All tests are in-process: the dp ranks are mesh devices
(``conftest.py`` forces an 8-device CPU host platform), liveness is
either a deterministic handle mask (step-clock chaos kills) or a real
2-rank dist-store heartbeat table.
"""
import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))    # repo root: tools import

import hetu_tpu as ht
from hetu_tpu import chaos, obs
from hetu_tpu.graph import step_cache
from hetu_tpu.metrics import (elastic_counts, fault_counts,
                              reset_elastic_counts, reset_faults,
                              reset_step_cache_counts, step_cache_counts)
from hetu_tpu.parallel.elastic import (ElasticController, LogicalRank,
                                       alive_mask, handles_alive_fn)
from hetu_tpu.parallel.preduce import PartialReduce


# --------------------------------------------------------------- helpers

def _build(dp, zero=0, seed=0, lr=0.01):
    rng = np.random.RandomState(seed)
    x = ht.placeholder_op("x")
    y_ = ht.placeholder_op("y_")
    w1 = ht.Variable("w1", value=rng.randn(7, 9).astype(np.float32) * 0.3)
    b1 = ht.Variable("b1", value=np.zeros(9, np.float32))
    w2 = ht.Variable("w2", value=rng.randn(9, 4).astype(np.float32) * 0.3)
    h = ht.relu_op(ht.linear_op(x, w1, b1))
    loss = ht.reduce_mean_op(
        ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
    opt = ht.optim.AdamOptimizer(lr)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0,
                     dist_strategy=ht.dist.DataParallel(num_devices=dp),
                     zero=zero)
    return x, y_, ex


def _batch(step, world, per_rank=2):
    """Deterministic per-step batch sized to the CURRENT world — the
    dp-matched reference run regenerates the identical stream from the
    same (step, world)."""
    rng = np.random.RandomState(1000 + step)
    n = per_rank * world
    xv = rng.randn(n, 7).astype(np.float32)
    yv = np.eye(4, dtype=np.float32)[rng.randint(0, 4, n)]
    return xv, yv


#: world-size trajectory shared by the e2e tests: kill after step 2
#: (chaos step3 fires post-step-2), rejoin before step 5
_WORLDS = [4, 4, 4, 3, 3, 4, 4, 4]


def _run_reference(zero=0):
    """The uninterrupted dp-matched reference: same graph, same feeds,
    same world trajectory — via EXPLICIT resizes, no chaos, no
    controller."""
    x, y_, ex = _build(4, zero=zero)
    losses, active = [], [0, 1, 2, 3]
    for i, w in enumerate(_WORLDS):
        if w != len(active):
            active = [0, 1, 3] if w == 3 else [0, 1, 2, 3]
            ex.resize_world(active)
        xv, yv = _batch(i, w)
        out = ex.run("train", feed_dict={x: xv, y_: yv})
        losses.append(np.float32(out[0].asnumpy()).tobytes().hex())
    return losses


# ------------------------------------------- grad-rescale parity (satellite)

def _masked_vs_true_mean(grads4):
    """(masked dp=4 mean with rank 3 dead, true dp=3 mean) — both as
    XLA collectives over real device meshes."""
    import jax
    from jax.sharding import PartitionSpec as P
    mask = alive_mask(4, dead=[3]).reshape(4, 1)
    mesh4 = ht.make_mesh({"dp": 4})
    masked = jax.jit(jax.shard_map(
        lambda g, m: PartialReduce.preduce(g, m[0, 0], "dp"),
        mesh=mesh4, in_specs=(P("dp"), P("dp")), out_specs=P("dp")))(
        grads4, mask)
    mesh3 = ht.make_mesh({"dp": 3})
    # the mask rides as a runtime input on BOTH sides: a literal 1.0
    # would constant-fold psum(mask) and change how XLA lowers the
    # divide (reciprocal-multiply vs true division) — that would test
    # compiler rewrites, not the mask algebra
    plain = jax.jit(jax.shard_map(
        lambda g, m: PartialReduce.preduce(g, m[0, 0], "dp"),
        mesh=mesh3, in_specs=(P("dp"), P("dp")), out_specs=P("dp")))(
        grads4[:3], np.ones((3, 1), np.float32))
    # every device holds the group mean
    return np.asarray(masked)[0], np.asarray(plain)[0]


def test_alive_mask_mean_equals_true_dp3_mean_bitwise():
    """dp=4 with one dead rank via the partial-reduce alive-mask mean
    ``psum(mask*g)/psum(mask)`` == the true dp=3 mean of the survivors'
    grads — BITWISE, and still bitwise after an Adam optimizer step.
    This equivalence is why the elastic shrink preserves gradient
    semantics (elastic.py module docstring, step 4).

    The masked path introduces NO rounding of its own: ``mask*g`` is
    exact for a 0/1 mask, the dead rank contributes an exactly-added
    zero, and the divisor ``psum(mask) == 3.0`` is exact.  The one
    thing that CAN differ is XLA's summation association for a 4-shard
    vs 3-shard all-reduce — which is reduction-order noise XLA owns,
    not a property of the mask algebra — so the bitwise claim is held
    on association-exact grads (integer-valued float32: addition is
    exact under any grouping) and the float case is pinned to <= 1 ulp
    below."""
    import jax
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    grads4 = rng.randint(-512, 512, (4, 33)).astype(np.float32)
    masked, plain = _masked_vs_true_mean(grads4)
    assert masked.tobytes() == plain.tobytes()

    # and through the optimizer: bitwise-equal mean -> bitwise-equal step
    opt = ht.optim.AdamOptimizer(0.01)
    p0 = {"w": jnp.asarray(rng.randn(33).astype(np.float32))}
    st = opt.init_state(p0)
    upd_m, _ = jax.jit(opt.apply)(p0, {"w": jnp.asarray(masked)}, st, 0.01)
    upd_p, _ = jax.jit(opt.apply)(p0, {"w": jnp.asarray(plain)}, st, 0.01)
    assert np.asarray(upd_m["w"]).tobytes() \
        == np.asarray(upd_p["w"]).tobytes()


def test_alive_mask_mean_float_within_one_ulp():
    """Real-valued grads: the masked dp=4 mean matches the true dp=3
    mean to <= 1 ulp (the residue is the all-reduce association order,
    not the mask — see the bitwise test's docstring)."""
    rng = np.random.RandomState(4)
    grads4 = rng.randn(4, 257).astype(np.float32)
    masked, plain = _masked_vs_true_mean(grads4)
    ulps = np.abs(masked.view(np.int32) - plain.view(np.int32))
    assert ulps.max() <= 1, ulps.max()


# ------------------------------------------------- resize state preservation

@pytest.mark.parametrize("zero", [0, 3])
def test_resize_preserves_params_and_moments_bitwise(zero):
    """Shrinking 4->3 moves every param and optimizer moment through
    the host redistribution (ZeRO slabs transcoded through the
    per-param layout) without changing a single bit."""
    x, y_, ex = _build(4, zero=zero)
    xv, yv = _batch(0, 4)
    for _ in range(3):
        ex.run("train", feed_dict={x: xv, y_: yv})

    def snap():
        params = {n.name: ex._fetch_host(v).tobytes()
                  for n, v in ex.var_values.items()}
        import jax
        moments = {}
        for op, st in ex.opt_states.items():
            plan = ex._zero_plans.get(op)
            host = jax.tree.map(ex._fetch_host, st)
            host = ex._transcode_opt_state(host, plan, None)
            leaves, _ = jax.tree_util.tree_flatten(host)
            moments[ex._k(op)] = [np.asarray(v).tobytes() for v in leaves]
        return params, moments

    before = snap()
    assert ex.resize_world([0, 1, 3]) is True
    assert int(np.prod(ex.mesh.devices.shape)) == 3
    after = snap()
    assert before == after


@pytest.mark.parametrize("zero", [0, 2])
def test_resize_matches_checkpoint_restart_bitwise(tmp_path, zero):
    """The elastic shrink IS the restart it avoids, numerically: train
    3 steps at dp=4, then either (a) resize_world to dp=3 in place or
    (b) checkpoint, rebuild a fresh dp=3 executor, restore — the two
    continuations produce bitwise-identical losses."""
    x, y_, ex = _build(4, zero=zero)
    for i in range(3):
        xv, yv = _batch(i, 4)
        ex.run("train", feed_dict={x: xv, y_: yv})
    ex.save(str(tmp_path / "ckpt"))

    ex.resize_world([0, 1, 2])
    elastic_losses = []
    for i in range(3, 6):
        xv, yv = _batch(i, 3)
        out = ex.run("train", feed_dict={x: xv, y_: yv})
        elastic_losses.append(np.float32(out[0].asnumpy()).tobytes().hex())

    x2, y2, ex2 = _build(3, zero=zero)
    ex2.load(str(tmp_path / "ckpt"))
    restart_losses = []
    for i in range(3, 6):
        xv, yv = _batch(i, 3)
        out = ex2.run("train", feed_dict={x2: xv, y2: yv})
        restart_losses.append(np.float32(out[0].asnumpy()).tobytes().hex())
    assert elastic_losses == restart_losses


def test_resize_world_guards():
    x, y_, ex = _build(2)
    with pytest.raises(ValueError, match="empty rank set"):
        ex.resize_world([])
    with pytest.raises(ValueError, match="outside the base world"):
        ex.resize_world([0, 5])
    assert ex.resize_world([0, 1]) is False     # no-op: same world
    # meshless executors have no world to resize
    rng = np.random.RandomState(0)
    x3 = ht.placeholder_op("x3")
    w = ht.Variable("w3", value=rng.randn(4, 2).astype(np.float32))
    loss = ht.reduce_mean_op(ht.matmul_op(x3, w), [0, 1])
    opt = ht.optim.SGDOptimizer(0.1)
    ex3 = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0)
    with pytest.raises(ValueError, match="needs a mesh"):
        ex3.resize_world([0])


# ------------------------------------------------------- end-to-end elastic

def test_elastic_shrink_grow_end_to_end():
    """The ISSUE 12 acceptance scenario, in-process and lean: kill one
    of dp=4 at an exact step boundary (the new step-clock chaos spec);
    training continues at dp=3 on the very next poll with restarts=0
    and a continuous loss trajectory; the rank rejoins and the world
    grows back to dp=4 — a compiled-step-cache HIT, not a recompile —
    with losses bitwise equal to the uninterrupted dp-matched
    reference."""
    step_cache.clear()
    reset_elastic_counts()
    reset_faults()
    reset_step_cache_counts()

    handles = [LogicalRank(r) for r in range(4)]
    inj = chaos.ChaosInjector.from_spec("7:kill:proc@rank2:step3")
    for h in handles:
        inj.register_proc(h.rank, h)
    prev = chaos.install(inj)
    try:
        x, y_, ex = _build(4)
        ctl = ElasticController(ex, world=4,
                                alive_fn=handles_alive_fn(handles),
                                min_dp=2)
        losses, worlds = [], []
        for i in range(len(_WORLDS)):
            xv, yv = _batch(i, ctl.dp)
            out = ex.run("train", feed_dict={x: xv, y_: yv})
            losses.append(np.float32(out[0].asnumpy()).tobytes().hex())
            worlds.append(ctl.dp)
            if i == 4:
                handles[2].rejoin()     # the standby comes back
            ctl.poll()
    finally:
        chaos.install(prev)

    assert worlds == _WORLDS, worlds
    assert ctl.active == [0, 1, 2, 3]
    ec = elastic_counts()
    assert ec["elastic_shrink"] == 1 and ec["elastic_grow"] == 1
    assert ec["elastic_dead_rank"] == 1 and ec["elastic_rejoin"] == 1
    assert ec["elastic_resize_ms"] >= 1
    # both resize events on the controller timeline, with recovery_ms
    kinds = [(e["kind"], e["from_dp"], e["to_dp"]) for e in ctl.events]
    assert kinds == [("shrink", 4, 3), ("grow", 3, 4)]
    assert all(e["recovery_ms"] > 0 for e in ctl.events)
    # the chaos kill really went through the step clock
    assert fault_counts().get("chaos_kill_proc") == 1
    # restarts=0: no supervisor restart, no resume-from-checkpoint
    fc = fault_counts()
    assert fc.get("supervisor_restart", 0) == 0
    assert fc.get("resume", 0) == 0
    # grow-back reused the dp=4 executable: 2 misses (dp=4, dp=3), then
    # a HIT when the world returns to 4
    sc = step_cache_counts()
    assert sc.get("step_cache_miss") == 2, sc
    assert sc.get("step_cache_hit", 0) >= 1, sc

    # continuous trajectory == the uninterrupted dp-matched reference
    assert losses == _run_reference()


def test_shrink_refused_below_min_dp():
    reset_elastic_counts()
    handles = [LogicalRank(r) for r in range(2)]
    x, y_, ex = _build(2)
    ctl = ElasticController(ex, world=2,
                            alive_fn=handles_alive_fn(handles), min_dp=2)
    handles[1].stop()
    assert ctl.poll() is None
    assert ctl.dp == 2                  # held at the floor
    assert elastic_counts().get("elastic_shrink_refused") == 1


def test_rejoin_grace_filters_flapping_rank():
    """A flapping rank must survive ``rejoin_grace`` consecutive polls
    before the controller pays a grow."""
    reset_elastic_counts()
    handles = [LogicalRank(r) for r in range(3)]
    x, y_, ex = _build(3)
    ctl = ElasticController(ex, world=3,
                            alive_fn=handles_alive_fn(handles),
                            min_dp=2, rejoin_grace=2)
    handles[2].stop()
    ev = ctl.poll()
    assert ev and ev["kind"] == "shrink" and ctl.dp == 2
    handles[2].rejoin()
    assert ctl.poll() is None           # 1st sighting: grace not met
    handles[2].stop()
    assert ctl.poll() is None           # flapped: grace restarts
    handles[2].rejoin()
    assert ctl.poll() is None
    ev = ctl.poll()
    assert ev and ev["kind"] == "grow" and ctl.dp == 3


# ----------------------------------------------------- resize trace events

def test_resize_events_in_trace(tmp_path):
    """ISSUE 10-style machine check: the shrink and grow land as
    ``elastic.resize`` spans with ``elastic:shrink``/``elastic:grow``
    instants, placed BETWEEN step spans in the exported Perfetto trace
    (a resize runs at a step boundary — never inside a step)."""
    import json
    handles = [LogicalRank(r) for r in range(4)]
    obs.clear_trace()
    obs.enable(True)
    try:
        x, y_, ex = _build(4)
        ctl = ElasticController(ex, world=4,
                                alive_fn=handles_alive_fn(handles),
                                min_dp=2)
        for i, w in enumerate(_WORLDS):
            xv, yv = _batch(i, ctl.dp)
            ex.run("train", feed_dict={x: xv, y_: yv})
            if i == 2:
                handles[2].stop()
            if i == 4:
                handles[2].rejoin()
            ctl.poll()
        n = obs.export_chrome_trace(str(tmp_path / "elastic_trace.json"))
        assert n > 0
    finally:
        obs.enable(False)
        obs.clear_trace()

    with open(tmp_path / "elastic_trace.json") as f:
        evs = json.load(f)["traceEvents"]
    resizes = [e for e in evs if e.get("ph") == "X"
               and e["name"] == "elastic.resize"]
    assert [e["args"]["kind"] for e in resizes] == ["shrink", "grow"]
    assert [(e["args"]["from_dp"], e["args"]["to_dp"])
            for e in resizes] == [(4, 3), (3, 4)]
    instants = {e["name"] for e in evs if e.get("ph") == "i"}
    assert {"elastic:shrink", "elastic:grow"} <= instants
    # ts containment in the step stream: every resize span sits strictly
    # between the end of one step span and the start of the next on the
    # driving thread
    steps = sorted((e["ts"], e["ts"] + e["dur"]) for e in evs
                   if e.get("ph") == "X" and e["name"] == "step")
    assert len(steps) == len(_WORLDS)
    for rz in resizes:
        t0, t1 = rz["ts"], rz["ts"] + rz["dur"]
        before = [s for s in steps if s[1] <= t0]
        after = [s for s in steps if s[0] >= t1]
        assert before and after, "resize span not between step spans"
        # and no step span overlaps the resize
        assert all(s[1] <= t0 or s[0] >= t1 for s in steps)


# --------------------------------------------- liveness through the store

def test_controller_liveness_via_store_heartbeats():
    """Detection through the REAL ISSUE 8 machinery: heartbeats ride a
    2-rank in-process dist store; a rank whose heartbeat goes silent
    AND whose server fails the direct probe is dead (shrink within one
    wait window); one that still answers the probe is UNREACHABLE —
    held, never resized over (the fail-stop boundary)."""
    from hetu_tpu.ps.dist_store import DistributedStore

    def free_ports(n):
        import socket
        socks, ports = [], []
        for _ in range(n):
            s = socket.socket()
            s.bind(("127.0.0.1", 0))
            socks.append(s)
            ports.append(s.getsockname()[1])
        for s in socks:
            s.close()
        return ports

    reset_elastic_counts()
    ports = free_ports(2)
    endpoints = [("127.0.0.1", p) for p in ports]
    stores = [DistributedStore(r, 2, endpoints, port=ports[r],
                               rpc_timeout=5.0, rpc_retries=2,
                               connect_timeout=2.0) for r in range(2)]
    handles = [LogicalRank(r).attach_heartbeat(stores[0], interval_ms=40)
               for r in range(2)]
    try:
        x, y_, ex = _build(2)
        ctl = ElasticController(ex, world=2, store=stores[0],
                                heartbeat_deadline_ms=300.0, min_dp=1)
        deadline = time.monotonic() + 3.0
        assert ctl.poll() is None   # both heartbeating: no resize
        assert ctl.dp == 2

        # heartbeat-silent but probe-answering: UNREACHABLE -> held
        handles[1].stop()
        while time.monotonic() < deadline:
            ev = ctl.poll()
            assert ev is None, "partitioned rank must not be shrunk over"
            if elastic_counts().get("elastic_unreachable_held"):
                break
            time.sleep(0.05)
        assert elastic_counts().get("elastic_unreachable_held", 0) >= 1
        assert ctl.dp == 2

        # now the server dies too: fail-stop death -> shrink
        stores[1].server.stop()
        t0 = time.monotonic()
        ev = None
        while ev is None and time.monotonic() < t0 + 4.0:
            ev = ctl.poll()
            if ev is None:
                time.sleep(0.05)
        assert ev is not None and ev["kind"] == "shrink"
        assert ctl.dp == 1 and ctl.active == [0]
        # within one wait window (+ slack for the probe timeout)
        assert (time.monotonic() - t0) < 4.0
    finally:
        for h in handles:
            h.close()
        for s in stores:
            try:
                s.close()
            except Exception:
                pass


def test_controller_needs_exactly_one_liveness_source():
    x, y_, ex = _build(2)
    with pytest.raises(ValueError, match="exactly one"):
        ElasticController(ex, world=2)
    with pytest.raises(ValueError, match="exactly one"):
        ElasticController(ex, world=2, alive_fn=lambda: [1, 1],
                          store=object())


# ------------------------------------------------------- slow scale proof

@pytest.mark.slow
def test_elastic_scenario():
    """The dp=4 end-to-end scale proof: chaos-driven kill + rejoin, loss
    parity vs the dp-matched reference, restarts=0, both resizes in the
    trace, the grow-back a step-cache hit."""
    import scenarios
    ex = scenarios.elastic_scenario()
    assert ex["restarts"] == 0 and ex["resumes"] == 0
    assert ex["loss_bitwise_equal_vs_reference"] is True
    assert ex["world_trajectory"] == ex["expected_trajectory"]
    assert ex["resize_kinds"] == ["shrink", "grow"]
    assert ex["trace"]["resize_spans"] == 2
    assert ex["step_cache"]["step_cache_miss"] == 2
    assert ex["step_cache"]["step_cache_hit"] >= 1
    assert ex["ok"] is True, ex
