"""Fault and serving scenarios the plane tests share: each runs a fixed,
seeded script end to end and returns what happened as COUNTS, bitwise
verdicts and protocol-conformance reports.  Nothing here reads a clock to
report it: a time, a rate or a ratio of two clocks is the benchmark's
business (``BENCHMARK.json``, ``benchmarks/``), on the chip.

One function per script; its test (named beside it) asserts on the parts.
``ok`` is the conjunction the parts were always gated on, kept so a test
can show WHICH part broke.  (Killing a PS server and resuming from the
newest checkpoint is ``tests/test_chaos.py::
test_kill_ps_server_mid_training_recovers_with_loss_parity`` already.)

==================  =====================================================
``failover_scenario``   double-kill a replicated primary, zero restarts
``serve_scenario``      primary kill under a zipf serving stream
``fleet_scenario``      flash crowd: scale-out, class sheds, replica kill
``decode_scenario``     ingestion modes, compile-once, prefix store, recovery
``trace_scenario``      the failover inside a step's span, all tracks
``partition_scenario``  partition + heal with fencing epochs, two cells
``elastic_scenario``    dp=4 kill + rejoin against a dp-matched reference
==================  =====================================================
"""
import os
import socket
import time

import numpy as np


def free_ports(n):
    """``n`` OS-assigned free localhost ports (bind, record, release)."""
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


def _close_all(things):
    for t in things:
        try:
            t.close()
        except Exception:
            pass


def _ps_train_graph(store, tid, width=8, **executor_kw):
    """Adam through a PS embedding into a 2-way softmax: the small
    training graph the PS fault scripts share.  Returns (ex, loss, ids, y_)."""
    import hetu_tpu as ht
    rng = np.random.RandomState(1)
    ids = ht.placeholder_op("ids")
    y_ = ht.placeholder_op("y")
    h = ht.ps_embedding_lookup_op((store, tid), ids, width=width)
    w = ht.Variable("w", value=rng.randn(width, 2).astype(np.float32) * .3)
    loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
        ht.matmul_op(h, w), y_), [0])
    ex = ht.Executor(
        {"train": [loss, ht.optim.AdamOptimizer(0.01).minimize(loss)]},
        seed=0, install_signal_handlers=False, **executor_kw)
    return ex, loss, ids, y_


def _replicated_cluster(ports, rows, width, rpc_timeout=5.0):
    """A ``replication=2`` DistributedStore per port with one seeded
    table: primaries and backups start bitwise identical (the replicated
    ``set_data`` path).  Returns (stores, tid)."""
    from hetu_tpu.ps.dist_store import DistributedStore
    world = len(ports)
    stores = [DistributedStore(
        r, world, [("127.0.0.1", p) for p in ports], port=ports[r],
        rpc_timeout=rpc_timeout, rpc_retries=2, connect_timeout=2.0,
        replication=2) for r in range(world)]
    tid = None
    for s in stores:
        tid = s.init_table(rows, width, opt="sgd", lr=0.1, init_scale=0.0)
    stores[0].set_data(tid, np.random.RandomState(42).normal(
        0, 0.01, (rows, width)).astype(np.float32))
    return stores, tid


def _seeded_feeds(steps, rows):
    rng = np.random.RandomState(0)
    return [(rng.randint(0, rows, 32),
             np.eye(2, dtype=np.float32)[rng.randint(0, 2, 32)])
            for _ in range(steps)]


class _own_chaos_env:
    """A scenario runs ITS OWN fixed schedule: an inherited ``HETU_CHAOS``
    (the stores' ``install_from_env`` would resurrect it) or re-replication
    tick must not inject into the clean run."""

    _VARS = ("HETU_CHAOS", "HETU_PS_REREPLICATE_EVERY")

    def __enter__(self):
        from hetu_tpu import chaos as chaos_mod
        self._saved = {v: os.environ.pop(v, None) for v in self._VARS}
        chaos_mod.uninstall()
        return self

    def __exit__(self, *exc):
        from hetu_tpu import chaos as chaos_mod
        chaos_mod.uninstall()
        for v, val in self._saved.items():
            os.environ.pop(v, None)
            if val is not None:
                os.environ[v] = val


# --------------------------------------------------------------- failover

def failover_scenario(steps=10, kill_step=3):
    """tests/test_ps_replication.py::test_failover_scenario — a 3-rank
    ``replication=2`` cluster trains while the schedule kills the shard-1
    PRIMARY after step ``kill_step``; the shard router promotes the live
    backup inside the failing RPC (no try/except, no resume around the
    step).  A standby then relaunches, the executor's re-replication tick
    re-attaches it (checksum-verified by ``tools/ps_fsck``), and a SECOND
    kill of the promoted ex-backup proves the restored redundancy is
    real.  The recorded protocol trace must conform to the replication
    model."""
    from hetu_tpu import chaos as chaos_mod
    from hetu_tpu.analysis.protocol import PROTO, check_conformance
    from hetu_tpu.metrics import fault_counts, reset_faults
    from hetu_tpu.ps.dist_store import DistributedStore
    from tools.ps_fsck import fsck

    world, rows, width = 3, 48, 8
    second_kill = steps - 3
    assert second_kill > kill_step + 2, "need room to re-replicate"
    feeds = _seeded_feeds(steps, rows)

    def one_step(ex, ids, y_, step):
        return float(ex.run("train", feed_dict={ids: feeds[step][0],
                                                y_: feeds[step][1]}
                            )[0].asnumpy())

    with _own_chaos_env():
        # --- uninterrupted replicated baseline: ZERO fault counters ------
        reset_faults()
        stores, tid = _replicated_cluster(free_ports(world), rows, width)
        try:
            ex, _, ids, y_ = _ps_train_graph(stores[0], tid, width)
            base = [one_step(ex, ids, y_, i) for i in range(steps)]
        finally:
            _close_all(stores)
        clean_counters = fault_counts()

        # --- chaos run: kill the shard-1 primary TWICE --------------------
        schedule = (f"11:kill:primary@shard1:step{kill_step},"
                    f"kill:primary@shard1:step{second_kill}")
        reset_faults()
        os.environ["HETU_PS_REREPLICATE_EVERY"] = "1"
        prev = chaos_mod.install(chaos_mod.ChaosInjector.from_spec(schedule))
        ports = free_ports(world)
        stores, tid = _replicated_cluster(ports, rows, width)
        standby = None
        losses = [None] * steps
        failover_steps, fsck_report = [], None
        PROTO.start()
        try:
            ex, _, ids, y_ = _ps_train_graph(stores[0], tid, width)
            for step in range(steps):
                before = fault_counts().get("ps_failover_promoted", 0)
                # NO try/except, NO resume: a killed primary is transparent
                losses[step] = one_step(ex, ids, y_, step)
                if fault_counts().get("ps_failover_promoted", 0) > before:
                    failover_steps.append(step)
                if step == kill_step + 1 and standby is None:
                    # ops relaunch a standby at the dead rank's endpoint;
                    # the executor's next re-replication tick re-attaches it
                    standby = DistributedStore(
                        1, world, [("127.0.0.1", p) for p in ports],
                        port=ports[1], rpc_timeout=5.0, rpc_retries=2,
                        connect_timeout=2.0, replication=2, standby=True)
                if step == second_kill - 2:
                    # the kill fires inside step second_kill-1's post-step
                    # hook (step_counter is 1-based), so this is the last
                    # step with the whole cluster up: redundancy must be
                    # BACK before the second kill
                    fsck_report = fsck([("127.0.0.1", p) for p in ports],
                                       n_tables=1, replication=2)
            counters = fault_counts()
        finally:
            proto_events = PROTO.stop()   # before teardown closes fire
            chaos_mod.install(prev)
            _close_all(stores + ([standby] if standby else []))
    parity = losses == base
    proto_conf = check_conformance(proto_events)
    restored = bool(fsck_report and fsck_report["ok"])
    return {
        "ok": (parity and len(failover_steps) == 2 and restored
               and proto_conf["ok"] and not clean_counters),
        "schedule": schedule,
        "failover_steps": failover_steps,
        "loss_parity": parity,
        "redundancy_restored": restored,
        "fsck_mismatches": (fsck_report or {}).get("mismatches"),
        "protocol_conformance": proto_conf,
        "fault_counters": counters,
        "clean_run_counters": clean_counters,
    }


# ------------------------------------------------------------------ serve

def serve_scenario(n_requests=300, seed=0):
    """tests/test_serving.py::test_serve_scenario — a wdl-style CTR model
    (26 zipf(1.05)-skewed categorical fields through a PS embedding, dense
    tower, sigmoid) served by InferenceExecutor + ServingRouter with the
    embedding pulled READ-ONLY through ``DistCacheTable`` from a 3-rank
    ``replication=2`` store.  The same seeded stream runs twice: clean,
    and with the shard-1 PRIMARY killed mid-load on the router's admission
    clock.  The kill must be absorbed by client-transparent failover:
    every request answered, responses BITWISE equal to the clean run."""
    import hetu_tpu as ht
    from hetu_tpu import chaos as chaos_mod
    from hetu_tpu.metrics import (fault_counts, reset_faults,
                                  reset_serve_counts, serve_counts,
                                  serve_latency_stats)
    from hetu_tpu.ps.dist_store import DistCacheTable
    from hetu_tpu.serving import InferenceExecutor, ServingRouter

    world, dim, n_fields = 3, 8, 26
    vocab = 26 * 80
    # max_wait_ms is the partial-wave ship deadline AND the packing-
    # determinism margin (see the wave comment below): full waves ship on
    # count, so only the two trailing partial waves ever wait it out
    max_batch, max_wait_ms = 64, 150.0
    kill_req = n_requests // 2

    def build_serving(store, tid):
        """wdl-style serving graph over a READ-ONLY embedding cache."""
        dense = ht.placeholder_op("dense")
        sparse = ht.placeholder_op("sparse", dtype=np.int64)
        cache = DistCacheTable(store, tid, limit=max(vocab // 2, 256),
                               policy="lru", read_only=True)
        emb = ht.ps_embedding_lookup_op(cache, sparse, width=dim)
        flat = ht.array_reshape_op(emb, (-1, n_fields * dim))
        h = ht.concat_op(flat, dense, axis=1)
        rng = np.random.RandomState(7)
        dims = [n_fields * dim + 13, 32, 1]
        for i, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
            w = ht.Variable(f"serve_w{i}",
                            value=(rng.randn(din, dout) * 0.2
                                   ).astype(np.float32))
            h = ht.matmul_op(h, w)
            if i < len(dims) - 2:
                h = ht.relu_op(h)
        prob = ht.sigmoid_op(h)
        iex = InferenceExecutor([prob], seed=0, validate="error",
                                buckets=(8, 16, 32, 64))
        return iex, dense, sparse

    # the seeded stream: zipf(1.05)-skewed ids per field + dense features,
    # chopped into deterministic waves so both runs pack IDENTICAL
    # batches (bitwise parity requires each request to run in the same
    # bucket).  A FULL wave (== max_batch) ships the moment the count is
    # reached, independent of timing; the two trailing partial waves ship
    # at the head-of-line deadline, set generously so a scheduler stall
    # mid-submission cannot split a wave into differently-bucketed halves.
    rng = np.random.RandomState(seed)
    per_field = vocab // n_fields
    p = 1.0 / (np.arange(per_field, dtype=np.float64) + 1.0) ** 1.05
    p /= p.sum()
    field = np.stack([rng.choice(per_field, n_requests, p=p)
                      for _ in range(n_fields)], axis=1)
    sparse_all = (field + np.arange(n_fields) * per_field).astype(np.int64)
    dense_all = rng.rand(n_requests, 13).astype(np.float32)
    waves = [max_batch] * (n_requests // max_batch)
    rest = n_requests % max_batch
    if rest > 1:
        waves += [rest // 2, rest - rest // 2]   # two partial buckets
    elif rest:
        waves += [rest]

    def run_stream():
        reset_serve_counts()
        stores, tid = _replicated_cluster(free_ports(world), vocab, dim,
                                          rpc_timeout=2.0)
        responses = [None] * n_requests
        try:
            iex, dense, sparse = build_serving(stores[0], tid)
            router = ServingRouter(iex, max_batch=max_batch,
                                   max_wait_ms=max_wait_ms,
                                   queue_limit=n_requests + 8)
            try:
                i = 0
                for wsize in waves:
                    futs = [(j, router.submit({dense: dense_all[j],
                                               sparse: sparse_all[j]}))
                            for j in range(i, i + wsize)]
                    for j, fut in futs:
                        responses[j] = np.asarray(fut.result(timeout=60)[0])
                    i += wsize
            finally:
                router.close()
            hist = {k: int(h.get("count", 0))
                    for k, h in serve_latency_stats().items()}
            return responses, serve_counts(), hist
        finally:
            _close_all(stores)

    with _own_chaos_env():
        # --- clean run: zero fault counters, the parity oracle -----------
        reset_faults()
        base_resp, base_serve, base_obs = run_stream()
        clean_counters = fault_counts()

        # --- chaos run: shard-1 primary killed mid-load -------------------
        schedule = f"11:kill:primary@shard1:req{kill_req}"
        reset_faults()
        prev = chaos_mod.install(
            chaos_mod.ChaosInjector.from_spec(schedule))
        try:
            resp, serve_ctrs, chaos_obs = run_stream()
        finally:
            chaos_mod.install(prev)
        counters = fault_counts()

    answered = sum(r is not None for r in resp)
    bitwise = all(r is not None and b is not None and np.array_equal(r, b)
                  for r, b in zip(resp, base_resp))
    return {
        "ok": (bitwise and answered == n_requests
               and counters.get("chaos_kill_primary", 0) == 1
               and counters.get("ps_failover_promoted", 0) >= 1
               and serve_ctrs.get("serve_failovers", 0) >= 1
               and serve_ctrs.get("serve_rejections", 0) == 0
               and not clean_counters),
        "schedule": schedule,
        "n_requests": n_requests,
        "all_answered": answered == n_requests,
        "responses_bitwise_equal": bitwise,
        "rejections": int(serve_ctrs.get("serve_rejections", 0)),
        "serve_counters": serve_ctrs,
        "clean_serve_counters": base_serve,
        # observations per label of ``serve_latency_us`` (queue_wait: one
        # per request; batch: one per device call), per run
        "latency_observations": base_obs,
        "chaos_latency_observations": chaos_obs,
        "fault_counters": counters,
        "clean_run_counters": clean_counters,
    }


# ------------------------------------------------------------------ fleet

def fleet_scenario(n_requests=420, seed=0):
    """tests/test_fleet.py::test_fleet_scenario — a seeded diurnal stream
    (calm -> 10x unpaced spike -> cool, classes mixed 70/20/10
    interactive/batch/best_effort) hits a ``FrontDoor`` that starts at ONE
    replica of a 3-layer dense serving graph, with the ``SLOAutoscaler``
    polled on the ADMISSION clock (once per submission wave).  The spike
    must breach the load watermark and scale out; interactive traffic is
    NEVER rejected while best_effort is shed EXPLICITLY (counted
    structured ``shed:best_effort`` rejections) and per-replica queues
    stay bounded; replica spin-up must be a ``step_cache_serve_hit``, not
    a compile.  The same stream then reruns with ``kill:replica@1:req<n>``
    — the scaled-out replica killed mid-spike on the door's admission
    clock — which must be absorbed by ejection + queue rescue: every
    admitted request answered, and responses bitwise equal to the clean
    run on the requests admitted in both."""
    import hetu_tpu as ht
    from hetu_tpu import chaos as chaos_mod
    from hetu_tpu.metrics import (fault_counts, fleet_counts,
                                  reset_faults, reset_fleet_counts,
                                  reset_serve_counts,
                                  reset_serve_rejection_counts,
                                  serve_counts, serve_rejection_counts,
                                  step_cache_counts)
    from hetu_tpu.serving import (FrontDoor, InferenceExecutor,
                                  ServeRejected, ServingRouter,
                                  SLOAutoscaler)

    calm_n = max(20, n_requests // 10)
    spike_n = n_requests - 2 * calm_n           # ~10x the calm volume
    wave = 20                                   # autoscaler poll cadence
    in_dim, hid, out_dim = 64, 256, 8
    max_batch, queue_limit = 8, 120
    # the kill lands mid-spike, after the first post-wave poll has
    # certainly scaled out (grow_grace=1): replica 1 exists by then
    kill_req = calm_n + 3 * wave + wave // 2

    # the serving graph: 3 dense layers — enough real device work per
    # batch that an unpaced submission burst outruns the drain on one
    # core, which is what makes the flash crowd a crowd
    rng = np.random.RandomState(seed)
    x = ht.placeholder_op("x_fleet_scenario")
    h = x
    dims = [in_dim, hid, hid, out_dim]
    for i, (din, dout) in enumerate(zip(dims[:-1], dims[1:])):
        w = ht.Variable(f"fleet_w{i}",
                        value=(rng.randn(din, dout) * 0.1
                               ).astype(np.float32))
        h = ht.matmul_op(h, w)
        if i < len(dims) - 2:
            h = ht.relu_op(h)
    y = h

    # request i's payload and class are identical across the clean and
    # chaos runs (admission DECISIONS may differ — load dynamics diverge
    # after the kill), which is what makes per-request parity meaningful
    feats = rng.randn(n_requests, in_dim).astype(np.float32)
    class_draw = rng.rand(n_requests)
    klasses = np.where(class_draw < 0.70, "interactive",
                       np.where(class_draw < 0.90, "batch", "best_effort"))

    def run_stream(schedule=None):
        reset_serve_counts()
        reset_serve_rejection_counts()
        reset_fleet_counts()
        reset_faults()
        sc0 = step_cache_counts().get("step_cache_serve_hit", 0)
        co0 = serve_counts().get("serve_bucket_compiles", 0)
        prev = None
        if schedule is not None:
            prev = chaos_mod.install(
                chaos_mod.ChaosInjector.from_spec(schedule))
        try:
            def mk(idx):
                return ServingRouter(
                    InferenceExecutor([y], seed=0, buckets=(max_batch,)),
                    max_batch=max_batch, max_wait_ms=2.0,
                    queue_limit=queue_limit, name=f"r{idx}")

            # best_effort's watermark sits LOW: the shed window is the
            # early spike, before the scale-outs triple aggregate
            # capacity and the load factor collapses (shed cheap traffic
            # first, then grow)
            door = FrontDoor(mk, 1, shed_at={"interactive": None,
                                             "batch": 0.45,
                                             "best_effort": 0.1},
                             wedge_timeout_ms=2000.0)
            scaler = SLOAutoscaler(door, p99_target_ms=500.0,
                                   min_replicas=1, max_replicas=3,
                                   grow_grace=1, shrink_grace=4,
                                   grow_load=0.15, shrink_load=0.02)
            responses = [None] * n_requests
            rejections = {}             # "klass:reason" -> count
            max_pending = 0
            futs = []

            def submit(i):
                try:
                    futs.append((i, door.submit({x: feats[i]},
                                                klass=str(klasses[i]))))
                except ServeRejected as e:
                    key = f"{klasses[i]}:{e.reason}"
                    rejections[key] = rejections.get(key, 0) + 1

            def poll():
                nonlocal max_pending
                scaler.poll()
                for rep in door.stats()["replicas"]:
                    max_pending = max(max_pending, rep["pending"])

            for i in range(n_requests):
                submit(i)
                if (i + 1) % wave == 0:
                    poll()
                if not calm_n <= i < calm_n + spike_n:
                    time.sleep(0.0005)      # calm and cool-down are paced
            failures = 0
            for i, fut in futs:
                try:
                    responses[i] = np.asarray(fut.result(timeout=60)[0])
                except Exception:   # noqa: BLE001 — counted, gated to 0
                    failures += 1
            poll()
            door.close()
            return {
                "responses": responses,
                "rejections": rejections,
                "reason_counts": dict(serve_rejection_counts()),
                "fleet_counts": dict(fleet_counts()),
                "fault_counts": dict(fault_counts()),
                "events": [{k: e[k] for k in ("admitted", "kind",
                                              "from_replicas", "to_replicas")}
                           for e in scaler.events],
                "failures": failures,
                "max_pending": max_pending,
                "serve_hit_delta":
                    step_cache_counts().get("step_cache_serve_hit", 0)
                    - sc0,
                "compile_delta":
                    serve_counts().get("serve_bucket_compiles", 0) - co0,
            }
        finally:
            if schedule is not None:
                chaos_mod.install(prev)

    with _own_chaos_env():
        clean = run_stream()
        schedule = f"13:kill:replica@1:req{kill_req}"
        chaos = run_stream(schedule)

    def admitted_ids(run):
        return {i for i, r in enumerate(run["responses"]) if r is not None}

    both = admitted_ids(clean) & admitted_ids(chaos)
    bitwise = all(np.array_equal(clean["responses"][i],
                                 chaos["responses"][i]) for i in both)

    def interactive_rejections(run):
        return sum(n for key, n in run["rejections"].items()
                   if key.startswith("interactive:"))

    # spin-up proof: across both runs exactly ONE real bucket build (the
    # very first replica of the clean run); every later replica — scaled
    # out or run-2 rebuilt — resolved through the serve step cache
    spinup_cheap = (clean["compile_delta"] == 1
                    and chaos["compile_delta"] == 0
                    and clean["serve_hit_delta"] >= len(clean["events"])
                    and chaos["serve_hit_delta"] >= 1)
    scaled_out = all(any(e["kind"] == "scale_out" for e in run["events"])
                     for run in (clean, chaos))
    sheds_counted = all(run["reason_counts"].get("shed:best_effort", 0) > 0
                        for run in (clean, chaos))
    # bounded queues: per-replica pending never exceeded the queue limit
    # (a chaos-run survivor may briefly double its depth when it ADOPTS
    # the dead replica's rescued queue — the documented bounded exception)
    bounded = (clean["max_pending"] <= queue_limit
               and chaos["max_pending"] <= 2 * queue_limit)
    kill_absorbed = (
        chaos["fault_counts"].get("chaos_kill_replica", 0) == 1
        and chaos["fleet_counts"].get("fleet_replica_ejected", 0) >= 1
        and chaos["failures"] == 0
        and chaos["fleet_counts"].get("fleet_request_failures", 0) == 0)
    return {
        "ok": (scaled_out and sheds_counted and bounded
               and interactive_rejections(clean) == 0
               and interactive_rejections(chaos) == 0
               and clean["failures"] == 0
               and kill_absorbed and bitwise and spinup_cheap
               and not clean["fault_counts"]),
        "schedule": schedule,
        "scaling": {"events": chaos["events"],
                    "clean_events": clean["events"],
                    "replicas_hw": chaos["fleet_counts"].get(
                        "fleet_replicas_hw", 1)},
        "rejections": chaos["reason_counts"],
        "clean_rejections": clean["reason_counts"],
        "per_class_rejections": {"clean": clean["rejections"],
                                 "chaos": chaos["rejections"]},
        "interactive_rejections": {
            "clean": interactive_rejections(clean),
            "chaos": interactive_rejections(chaos)},
        "bounded_queues": {"max_pending_clean": clean["max_pending"],
                           "max_pending_chaos": chaos["max_pending"],
                           "queue_limit": queue_limit, "bounded": bounded},
        "spin_up": {"cheap": spinup_cheap,
                    "clean_compiles": clean["compile_delta"],
                    "chaos_compiles": chaos["compile_delta"],
                    "clean_serve_hits": clean["serve_hit_delta"],
                    "chaos_serve_hits": chaos["serve_hit_delta"]},
        "chaos": {"kill_absorbed": kill_absorbed,
                  "responses_bitwise_equal": bool(bitwise),
                  "answered_both": len(both),
                  "failed_futures": chaos["failures"],
                  "fleet_counters": chaos["fleet_counts"],
                  "fault_counters": chaos["fault_counts"]},
        "clean_failed_futures": clean["failures"],
        "clean_run_fault_counters": clean["fault_counts"],
    }


# ----------------------------------------------------------------- decode

def decode_scenario(n_requests=16, seed=0):
    """tests/test_decode.py::test_decode_scenario — a zipf-sized seeded
    request stream (prompt lengths and generation budgets both skewed)
    decoded greedily through ``DecodeEngine`` / ``DecodeRouter``:

    * the same stream under **continuous** batching with chunked prefill,
      **token-by-token** ingestion and **request-level** batching (joins
      only into an EMPTY engine): all three token streams BITWISE equal;
    * the compile-once steady state over the chunked stream, by counters:
      jit wrappers made + serve-cache reuses == dispatch-plan misses ==
      distinct bucket keys (``(batch, len)`` pairs and ``(batch, chunk,
      len)`` triples), every other step a ``plan_cache_hit``;
    * a popularity-skewed pool stream decoded cold and with a
      ``PrefixKVStore``: repeats HIT, skip prefill rows, same tokens;
    * one ``ttft`` histogram observation per stream; zero rejections;
    * a 2-replica decode FrontDoor under ``kill:replica@0:tok<n>`` on the
      engine's own token clock: every in-flight stream migrated to the
      survivor, bitwise equal to the unkilled reference, zero failures,
      zero restarts, protocol trace conforming; and a zero-survivor kill
      that fails LOUDLY (``recovery_exhausted`` + partial tokens)."""
    from hetu_tpu import chaos as chaos_mod
    from hetu_tpu import metrics as ht_metrics
    from hetu_tpu.analysis.protocol import PROTO, check_conformance
    from hetu_tpu.models import (GPT2Config, gpt2_decode_chunked_graph,
                                 gpt2_decode_graph)
    from hetu_tpu.profiler import HetuProfiler
    from hetu_tpu.serving import (DecodeEngine, DecodeRouter, FrontDoor,
                                  PrefixKVStore, ServeRejected)

    max_slots, max_len, gen_cap = 4, 32, 6
    cfg = GPT2Config.tiny(n_positions=2 * max_len, batch_size=1,
                          seq_len=max_len)

    # most prompts short, a heavy tail, capped so prompt + generation
    # always fits max_len
    rng = np.random.RandomState(seed)
    plens = np.minimum(rng.zipf(1.5, n_requests), max_len // 2)
    news = np.minimum(rng.zipf(1.6, n_requests) + 1, gen_cap)
    prompts = [rng.randint(1, cfg.vocab_size, int(n)).astype(np.int32)
               for n in plens]

    def mk_engine(chunked, store=None):
        feeds, logits, caches, _ = gpt2_decode_graph(cfg, max_len=max_len)
        kw = {}
        if chunked:
            cf, cl, cc, _ = gpt2_decode_chunked_graph(cfg, max_len=max_len)
            kw = {"chunked": (cf, cl, cc), "max_chunk": 8}
        return DecodeEngine(feeds, logits, caches, max_slots=max_slots,
                            max_len=max_len, seed=0, prefix_store=store,
                            **kw)

    def one_pass(continuous, chunked, store=None, reqs=None):
        ht_metrics.reset_all()
        eng = mk_engine(chunked, store=store)
        rq = reqs if reqs is not None else list(zip(prompts, news))
        with DecodeRouter(eng, queue_limit=len(rq) + 8, max_wait_ms=5.0,
                          continuous=continuous) as router:
            streams = [router.submit(p, max_new_tokens=int(nw))
                       for p, nw in rq]
            tokens = [s.result(timeout=600) for s in streams]
        lat = HetuProfiler.latency_stats().get("decode_latency_us", {})
        return {
            "tokens": tokens,
            "decode": ht_metrics.decode_counts(),
            "serve": ht_metrics.serve_counts(),
            "run_plan": ht_metrics.run_plan_counts(),
            "step_cache": ht_metrics.step_cache_counts(),
            "prefix_ct": ht_metrics.prefix_cache_counts(),
            "ttft_observations": int(lat.get("ttft", {}).get("count", 0)),
            "bucket_key_bound": (len(eng.batch_ladder) * len(eng.len_ladder)
                                 * len(eng.chunk_ladder)),
        }

    tok = one_pass(True, False)     # token-by-token ingestion
    cont = one_pass(True, True)     # chunked continuous batching
    reql = one_pass(False, False)   # request-level batching

    # --- shared-prefix KV reuse: popularity-skewed pool stream ----------
    pool_n = max(4, n_requests // 8)
    pool = [rng.randint(1, cfg.vocab_size,
                        int(rng.randint(4, max_len // 2 + 1))
                        ).astype(np.int32) for _ in range(pool_n)]
    picks = np.minimum(rng.zipf(1.3, n_requests) - 1, pool_n - 1)
    pref_reqs = [(pool[int(k)], int(min(rng.zipf(1.6) + 1, gen_cap)))
                 for k in picks]
    pref_cold = one_pass(True, True, reqs=pref_reqs)
    pref_warm = one_pass(True, True, store=PrefixKVStore(), reqs=pref_reqs)

    # --- exactly-once stream recovery: mid-generation replica kill -------
    rec_n = min(n_requests, 8)
    rec_reqs = list(zip(prompts, news))[:rec_n]
    kill_tok = max(3, int(sum(int(nw) for _, nw in rec_reqs)) // 8)
    rec_ref = one_pass(True, True, reqs=rec_reqs)["tokens"]

    def poll_fleet(door, streams, timeout=300.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            door.poll()
            if all(s.done for s in streams):
                return True
            time.sleep(0.005)
        return False

    ht_metrics.reset_all()
    rec_store = PrefixKVStore()
    prev_inj = chaos_mod.install(chaos_mod.ChaosInjector.from_spec(
        f"{seed}:kill:replica@0:tok{kill_tok}"))
    # the kill run doubles as a recorded protocol trace: seat / emit /
    # detach / adopt / fence transitions replay against the decode-
    # recovery model
    PROTO.start()
    try:
        # wedge_timeout pushed out of the way: a first-touch bucket
        # compile inside a step would otherwise read as a wedge on CPU
        door = FrontDoor(
            lambda idx: DecodeRouter(mk_engine(True, store=rec_store),
                                     queue_limit=rec_n + 8,
                                     name=f"recb{idx}"),
            2, health_every_ms=1e9, wedge_timeout_ms=1e9)
        try:
            rec_streams = [door.submit(p, max_new_tokens=int(nw))
                           for p, nw in rec_reqs]
            rec_done = poll_fleet(door, rec_streams)
            rec_tokens, rec_failed = [], 0
            for s in rec_streams:
                try:
                    rec_tokens.append(s.result(timeout=60))
                except Exception:
                    rec_failed += 1
                    rec_tokens.append(None)
        finally:
            door.close()
    finally:
        rec_proto = PROTO.stop()
        chaos_mod.install(prev_inj)
    rec_conf = check_conformance(rec_proto)
    rec_c = ht_metrics.decode_recovery_counts()
    rec_fleet = ht_metrics.fleet_counts()
    rec_obs = int(HetuProfiler.latency_stats().get(
        "decode_latency_us", {}).get("recovery", {}).get("count", 0))
    rec_restarts = int(rec_fleet.get("fleet_scale_out", 0)) - 2
    reseated = rec_c.get("decode_recovery_reseated", 0)
    rec_ok = (rec_done and rec_failed == 0
              and rec_tokens == rec_ref
              and rec_fleet.get("fleet_replica_ejected", 0) == 1
              and rec_fleet.get("fleet_request_failures", 0) == 0
              and rec_restarts == 0
              and reseated >= 1
              and reseated == rec_c.get("decode_recovery_detached", 0)
              and rec_c.get("decode_recovery_exhausted", 0) == 0
              and rec_obs == reseated
              and rec_conf["ok"]
              and ht_metrics.fault_counts().get(
                  "chaos_kill_replica", 0) == 1)

    ht_metrics.reset_all()
    prev_inj = chaos_mod.install(chaos_mod.ChaosInjector.from_spec(
        f"{seed}:kill:replica@0:tok3"))
    exhausted, zs_partials_ok = 0, True
    # the lone replica's loop starts once all three streams are queued:
    # it seats them in ONE join, they emit in lockstep, and the kill at
    # the engine's third token finds one token in every journal and the
    # second step launched ahead.  Submitted at a running loop, a stream
    # seated an iteration or two behind the others (the submits race the
    # loop's thread) had emitted nothing when the clock struck — one run
    # in 25 read an EMPTY partial and failed ``partial >= 1`` (ISSUE 39)
    lone = []

    def held_replica(idx):
        lone.append(DecodeRouter(mk_engine(True), queue_limit=16,
                                 name=f"recz{idx}", start=False))
        return lone[-1]

    PROTO.start()
    try:
        door = FrontDoor(held_replica, 1, health_every_ms=1e9,
                         wedge_timeout_ms=1e9)
        try:
            zs = [door.submit(np.full(4, 3 + i, np.int32),
                              max_new_tokens=gen_cap) for i in range(3)]
            lone[0].start()
            poll_fleet(door, zs, timeout=120.0)
            for s in zs:
                try:
                    s.result(timeout=60)
                    zs_partials_ok = False     # nothing may "succeed"
                except ServeRejected as exc:
                    if exc.reason == "recovery_exhausted":
                        exhausted += 1
                        zs_partials_ok = zs_partials_ok \
                            and isinstance(exc.partial, list) \
                            and len(exc.partial) >= 1
        finally:
            door.close()
    finally:
        zs_proto = PROTO.stop()
        chaos_mod.install(prev_inj)
    zs_conf = check_conformance(zs_proto)
    exhaust_ok = (exhausted >= 1 and zs_partials_ok and zs_conf["ok"]
                  and ht_metrics.decode_recovery_counts().get(
                      "decode_recovery_exhausted", 0) == exhausted)

    # --- the verdicts -----------------------------------------------------
    bitwise = (cont["tokens"] == reql["tokens"]
               and cont["tokens"] == tok["tokens"])
    steps_n = cont["decode"]["decode_steps"]
    keys = cont["run_plan"].get("plan_cache_miss", 0)
    compiles = cont["serve"].get("serve_bucket_compiles", 0)
    serve_hits = cont["step_cache"].get("step_cache_serve_hit", 0)
    plan_hits = cont["run_plan"].get("plan_cache_hit", 0)
    compile_once = (keys > 0 and compiles + serve_hits == keys
                    and plan_hits == steps_n - keys
                    and keys <= cont["bucket_key_bound"])
    no_rejects = all(leg["decode"].get("decode_rejections", 0) == 0
                     for leg in (cont, reql, tok, pref_warm))
    pc = pref_warm["prefix_ct"]
    hits = pc.get("prefix_cache_hits", 0)
    rows_cold = pref_cold["decode"].get("decode_prefill_rows", 0)
    rows_warm = pref_warm["decode"].get("decode_prefill_rows", 0)
    prefix_ok = (pref_warm["tokens"] == pref_cold["tokens"]
                 and hits > 0 and rows_warm < rows_cold)
    ttft_counted = cont["ttft_observations"] == n_requests
    return {
        "ok": (bitwise and compile_once and no_rejects and prefix_ok
               and ttft_counted and rec_ok and exhaust_ok),
        "streams_bitwise_equal": bitwise,
        "compile_once": {
            "decode_steps": int(steps_n),
            "bucket_keys": int(keys),
            "bucket_key_bound": int(cont["bucket_key_bound"]),
            "serve_bucket_compiles": int(compiles),
            "step_cache_serve_hits": int(serve_hits),
            "plan_cache_hits": int(plan_hits),
            "holds": bool(compile_once),
        },
        "prefill": {
            "steps": int(cont["decode"].get("decode_prefill_steps", 0)),
            "steps_saved_vs_token_by_token": int(cont["decode"].get(
                "decode_prefill_steps_saved", 0)),
            "logits_fetches_skipped": int(cont["decode"].get(
                "decode_logits_skipped", 0)),
        },
        "ttft_counted_per_stream": ttft_counted,
        "rejections": {name: int(leg["decode"].get("decode_rejections", 0))
                       for name, leg in (("continuous", cont),
                                         ("request_level", reql),
                                         ("token_by_token", tok),
                                         ("prefix_warm", pref_warm))},
        "prefix_cache": {
            "hits": int(hits),
            "misses": int(pc.get("prefix_cache_misses", 0)),
            "hit_rows": int(pc.get("prefix_cache_hit_rows", 0)),
            "prefill_rows_cold": int(rows_cold),
            "prefill_rows_warm": int(rows_warm),
            "streams_bitwise_equal":
                pref_warm["tokens"] == pref_cold["tokens"],
            "holds": bool(prefix_ok),
        },
        "recovery": {
            "kill_spec": f"kill:replica@0:tok{kill_tok}",
            "streams": int(rec_n),
            "failed_streams": int(rec_failed),
            "restarts": int(rec_restarts),
            "streams_bitwise_equal_to_unkilled": rec_tokens == rec_ref,
            "counters": {k: int(v) for k, v in rec_c.items()},
            "fleet": {k: int(v) for k, v in rec_fleet.items()},
            "reseat_latency_observations": rec_obs,
            "protocol_conformance": rec_conf,
            "holds": bool(rec_ok),
            "zero_survivor": {
                "streams": 3,
                "recovery_exhausted": int(exhausted),
                "partials_attached": bool(zs_partials_ok),
                "protocol_conformance": zs_conf,
                "holds": bool(exhaust_ok),
            },
        },
        "total_tokens": int(sum(len(t) for t in cont["tokens"])),
    }


# ------------------------------------------------------------------ trace

def trace_scenario(steps=5, kill_step=2, export_to=None):
    """tests/test_obs.py::test_trace_scenario — a 5-step PS training run
    (3-rank ``replication=2`` cluster) under
    ``kill:primary@shard1:step<k>`` with span tracing live: the kill lands
    in step k's post-step hook, so the NEXT step's pull absorbs the
    failover — its ``fault:ps_rpc_retry`` / ``fault:ps_failover*`` point
    events appear INSIDE that step's span, between its per-opcode
    ``rpc:OP_*`` spans.  The run goes through
    ``Executor.run_steps(sync=False)`` with the feed pipeline forced on
    (``HETU_FEED_PIPELINE_MIN_US=0``) so the background H2D copies show up
    as a named ``run-steps-feed`` track and the non-blocking window as
    flow arrows; a small serving burst adds the serve-router track.
    Losses stay BITWISE equal to an untraced clean run.  ``export_to``
    writes the Chrome trace (``artifacts/trace_step.json`` is one)."""
    import hetu_tpu as ht
    from hetu_tpu import chaos as chaos_mod, obs
    from hetu_tpu import metrics as ht_metrics
    from hetu_tpu.metrics import fault_counts, reset_faults
    from hetu_tpu.serving import InferenceExecutor, ServingRouter

    world, rows, width = 3, 48, 8
    assert 0 < kill_step < steps - 1, "the failover needs a later step"
    feeds = _seeded_feeds(steps, rows)

    def run_train(store, tid):
        ex, _, ids, y_ = _ps_train_graph(store, tid, width)
        rs = ex.run_steps(
            lambda i: {ids: feeds[i][0], y_: feeds[i][1]}, steps,
            name="train", sync=False)
        return [np.asarray(r[0].jax(), np.float32).tobytes() for r in rs]

    env_min = os.environ.get("HETU_FEED_PIPELINE_MIN_US")
    # tiny batches: force the H2D double-buffer on so the feed-pipeline
    # track exists (the adaptive threshold would keep them inline)
    os.environ["HETU_FEED_PIPELINE_MIN_US"] = "0"
    prev_trace = obs.enabled()
    prev_timing = ht_metrics.step_timing
    try:
        with _own_chaos_env():
            # --- clean, untraced run: the parity oracle ------------------
            obs.enable(False)
            reset_faults()
            stores, tid = _replicated_cluster(free_ports(world), rows, width)
            try:
                base_losses = run_train(stores[0], tid)
            finally:
                _close_all(stores)
            clean_counters = fault_counts()

            # --- traced chaos run ----------------------------------------
            schedule = f"11:kill:primary@shard1:step{kill_step}"
            reset_faults()
            ht_metrics.reset_step_times()
            ht_metrics.enable_step_timing(True)
            obs.clear_trace()
            obs.enable(True)
            prev = chaos_mod.install(
                chaos_mod.ChaosInjector.from_spec(schedule))
            try:
                stores, tid = _replicated_cluster(free_ports(world), rows,
                                                  width)
                try:
                    chaos_losses = run_train(stores[0], tid)
                    # serving burst: the router/assemble/device-call/
                    # scatter lifecycle on its own named track
                    sx = ht.placeholder_op("sx", shape=(width,))
                    sw = ht.Variable(
                        "trace_serve_w", value=np.random.RandomState(
                            3).randn(width, 1).astype(np.float32))
                    prob = ht.sigmoid_op(ht.matmul_op(sx, sw))
                    iex = InferenceExecutor([prob], seed=0, buckets=(4, 8))
                    with ServingRouter(iex, max_batch=4,
                                       max_wait_ms=20.0) as router:
                        futs = [router.submit(
                            {sx: np.ones((width,), np.float32) * i})
                            for i in range(8)]
                        for f in futs:
                            f.result(timeout=30)
                finally:
                    _close_all(stores)
            finally:
                chaos_mod.install(prev)
                obs.enable(False)
                ht_metrics.enable_step_timing(False)
            counters = fault_counts()
            evs = obs.trace_events()
            step_observations = int(ht_metrics.step_time_stats().get(
                "train", {}).get("count", 0))
            if export_to:
                obs.export_chrome_trace(export_to)
    finally:
        if env_min is None:
            os.environ.pop("HETU_FEED_PIPELINE_MIN_US", None)
        else:
            os.environ["HETU_FEED_PIPELINE_MIN_US"] = env_min
        obs.enable(prev_trace)
        ht_metrics.enable_step_timing(prev_timing)

    names = [e["name"] for e in evs]
    tracks = [e["args"]["name"] for e in evs
              if e.get("ph") == "M" and e["name"] == "thread_name"]
    step_spans = [e for e in evs if e.get("ph") == "X"
                  and e["name"] == "step"]
    promo = [e for e in evs if e["name"] == "fault:ps_failover_promoted"]
    checks = {
        "step_spans": len(step_spans),
        "rpc_spans": sum(1 for n in names if n.startswith("rpc:")),
        "retry_events": names.count("fault:ps_rpc_retry"),
        "failover_promotions": len(promo),
        # the promotion instant lands INSIDE one step span's window
        "promotion_inside_step_span": any(
            s["ts"] <= p["ts"] <= s["ts"] + s["dur"]
            for p in promo for s in step_spans),
        "feed_pipeline_track": any("run-steps-feed" in t
                                   or "feed-pipeline" in t for t in tracks),
        "serve_router_track": any("hetu-serve-router" in t for t in tracks),
        "serve_device_calls": names.count("serve.device_call"),
        "flow_arrows": sum(1 for e in evs if e.get("ph") == "s"),
        "loss_parity": chaos_losses == base_losses,
        "clean_run_counters_empty": not clean_counters,
    }
    return {
        "ok": (checks["step_spans"] >= steps
               and checks["rpc_spans"] > 0
               and checks["failover_promotions"] >= 1
               and checks["promotion_inside_step_span"]
               and checks["feed_pipeline_track"]
               and checks["serve_router_track"]
               and checks["serve_device_calls"] >= 1
               and checks["loss_parity"]
               and checks["clean_run_counters_empty"]),
        "schedule": schedule,
        "events": len(evs),
        **checks,
        "tracks": sorted(set(tracks)),
        "step_time_observations": step_observations,
        "fault_counters": counters,
    }


# -------------------------------------------------------------- partition

def partition_scenario(steps=10, cut_step=3, heal_step=7):
    """tests/test_partition.py::test_partition_scenario.

    Part A (3-rank training): the same seeded run three times — clean,
    ``partition:rank0|rank1@step<cut>`` without heal, and with
    ``:heal<m>``.  The partition cuts the training client (rank 0) off
    shard 1's primary: the client fails over to the ring backup (epoch
    bump), training continues with ZERO restarts, and losses stay BITWISE
    equal to the clean run in both chaos variants (every acked write lands
    on the surviving lineage).  After heal, a stale client (rank 1's own
    store) writes through the healed stale ex-primary: the op-log forward
    is epoch-refused by the promoted backup (``ps_epoch_refused``), the
    ex-primary demotes itself (``ps_demotions``) instead of acking, and
    the client re-routes the SAME op to the surviving lineage — then
    epoch-checked re-replication converges both copies, proven by
    ``ps_fsck(retries=2)``: zero stable divergence and exactly one serving
    epoch per shard.  The no-heal run documents the detectable split brain
    fsck sees when nothing converges it.

    Part B (:func:`_two_cell_scenario`): 2-cell geo-replicated serving
    through a cross-cell partition and heal."""
    from hetu_tpu import chaos as chaos_mod
    from hetu_tpu.analysis.protocol import PROTO, check_conformance
    from hetu_tpu.metrics import fault_counts, reset_faults
    from tools.ps_fsck import fsck

    world, rows, width = 3, 48, 8
    assert cut_step < heal_step < steps - 1, "need post-heal steps"
    feeds = _seeded_feeds(steps, rows)
    # the stale-client probe: shard-1-owned keys, ZERO grads — sgd leaves
    # the values bitwise unchanged, so the probe can ride every variant
    # without perturbing loss parity while still exercising the write
    # path (and, post-heal, the fence dance)
    probe_keys = np.asarray([1, 4], np.int64)
    probe_grads = np.zeros((2, width), np.float32)

    def run_variant(schedule, heal):
        """One full training run, also a RECORDED protocol trace replayed
        against the replication model."""
        reset_faults()
        ports = free_ports(world)
        stores, tid = _replicated_cluster(ports, rows, width)
        losses = [None] * steps
        events = {"failover_steps": [], "deferred_in_partition": False,
                  "probe_acked": False}
        prev = chaos_mod.install(
            chaos_mod.ChaosInjector.from_spec(schedule)) if schedule \
            else chaos_mod.uninstall()
        PROTO.start()
        try:
            ex, _, ids, y_ = _ps_train_graph(stores[0], tid, width)
            for step in range(steps):
                before = fault_counts().get("ps_failover_promoted", 0)
                # NO try/except, NO restart: a partitioned primary is
                # absorbed by failover inside the failing RPC
                losses[step] = float(
                    ex.run("train", feed_dict={ids: feeds[step][0],
                                               y_: feeds[step][1]}
                           )[0].asnumpy())
                if fault_counts().get("ps_failover_promoted", 0) > before:
                    events["failover_steps"].append(step + 1)
                if schedule and step + 1 == cut_step + 2:
                    # mid-partition repair attempt: cross-cut
                    # re-replication must QUEUE (defer), not crash
                    d0 = fault_counts().get("ps_re_replicate_deferred", 0)
                    stores[0].maybe_re_replicate()
                    events["deferred_in_partition"] = \
                        fault_counts().get("ps_re_replicate_deferred",
                                           0) > d0
                if step + 1 == heal_step and (heal or not schedule):
                    # the stale client writes through the (in the heal
                    # variant: healed, still stale-serving) ex-primary —
                    # clean run: plain replicated write; heal run: the
                    # fence dance re-routes it to the surviving lineage
                    stores[1].push(tid, probe_keys, probe_grads)
                    events["probe_acked"] = True
                    stores[0].maybe_re_replicate()  # epoch-checked repair
            report = fsck([("127.0.0.1", p) for p in ports], n_tables=1,
                          replication=2, retries=2, retry_wait=0.2)
            out = (losses, events, fault_counts(), report)
        finally:
            proto_events = PROTO.stop()  # before teardown closes fire
            chaos_mod.install(prev) if schedule else None
            _close_all(stores)
        return out + (check_conformance(proto_events),)

    with _own_chaos_env():
        base, _, clean_counters, base_fsck, base_conf = \
            run_variant(None, heal=False)
        n_losses, _, n_counters, n_fsck, n_conf = run_variant(
            f"13:partition:rank0|rank1@step{cut_step}", heal=False)
        h_losses, h_ev, h_counters, h_fsck, h_conf = run_variant(
            f"13:partition:rank0|rank1@step{cut_step}:heal{heal_step}",
            heal=True)
        two_cell = _two_cell_scenario(cut_step, heal_step)

    heal_parity = h_losses == base
    noheal_parity = n_losses == base
    one_lineage = all(len(r) == 1
                      for r in h_fsck["serving_ranks"].values())
    return {
        "ok": (heal_parity and noheal_parity
               and h_ev["probe_acked"]
               and h_ev["deferred_in_partition"]
               and h_counters.get("partition_frames_dropped", 0) > 0
               and h_counters.get("ps_epoch_refused", 0) > 0
               and h_counters.get("ps_demotions", 0) > 0
               and h_counters.get("ps_epoch_bumps", 0) > 0
               and h_counters.get("ps_failover_promoted", 0) >= 1
               and h_fsck["ok"] and one_lineage
               and h_fsck["serving_ranks"][1] == [2]
               and not n_fsck["ok"]     # unhealed split brain is VISIBLE
               and bool(n_fsck["lineage_violations"])
               and base_fsck["ok"] and not clean_counters
               and base_conf["ok"] and n_conf["ok"] and h_conf["ok"]
               and two_cell["ok"]),
        "loss_parity_heal": heal_parity,
        "loss_parity_noheal": noheal_parity,
        "probe_acked": h_ev["probe_acked"],
        "failover_steps": h_ev["failover_steps"],
        "re_replication_deferred_in_partition":
            h_ev["deferred_in_partition"],
        "fault_counters": h_counters,
        "noheal_fault_counters": n_counters,
        "clean_run_counters": clean_counters,
        "fsck_ok": h_fsck["ok"],
        "fsck_retries_used": h_fsck["retries_used"],
        "fsck_serving_ranks": h_fsck["serving_ranks"],
        "fsck_epochs": {
            s: {r: v["epoch"] for r, v in eps.items()}
            for s, eps in h_fsck["epochs"].items()},
        "noheal_split_brain_detected":
            bool(n_fsck["lineage_violations"]) or not n_fsck["ok"],
        "noheal_lineage_violations": n_fsck["lineage_violations"],
        "protocol_conformance": h_conf,
        "noheal_protocol_conformance": n_conf,
        "clean_protocol_conformance": base_conf,
        "two_cell": two_cell,
    }


def _two_cell_scenario(cut_step, heal_step):
    """Part B of :func:`partition_scenario`: 4 ranks in two cells, each
    serving InferenceExecutor traffic through a ServingRouter off a
    read-only warmed DistCacheTable, a deterministic cross-cell partition
    + heal on a manual step clock.  The cut leaves BOTH cells answering
    local reads (rejections=0, errors=0); the east cell promotes a local
    backup for a missed shard (new lineage); cross-cell re-replication
    queues (deferred) until heal; at heal the west trainer's first stale
    write triggers the fence dance and ``CellHead.catch_up``
    re-replicates — fsck converges to one lineage."""
    import hetu_tpu as ht
    from hetu_tpu import chaos as chaos_mod
    from hetu_tpu.metrics import fault_counts, reset_faults
    from hetu_tpu.ps.dist_store import DistCacheTable
    from hetu_tpu.serving import (CellHead, CellMap, InferenceExecutor,
                                  ServingRouter)
    from tools.ps_fsck import fsck

    vocab, dim, n_fields = 32, 4, 4
    cells = CellMap({"west": [0, 1], "east": [2, 3]})
    ports = free_ports(cells.world)
    endpoints = [("127.0.0.1", p) for p in ports]
    reset_faults()
    stores, tid = _replicated_cluster(ports, vocab, dim, rpc_timeout=2.0)
    heads = []
    try:
        def make_head(name, store):
            sparse = ht.placeholder_op(f"ids_{name}", dtype=np.int64)
            cache = DistCacheTable(store, tid, limit=2 * vocab,
                                   policy="lru", read_only=True)
            emb = ht.ps_embedding_lookup_op(cache, sparse, width=dim)
            flat = ht.array_reshape_op(emb, (-1, n_fields * dim))
            w = ht.Variable(f"w_{name}", value=(np.random.RandomState(7)
                            .randn(n_fields * dim, 1) * 0.2
                            ).astype(np.float32))
            prob = ht.sigmoid_op(ht.matmul_op(flat, w))
            iex = InferenceExecutor([prob], seed=0, validate="error",
                                    buckets=(4, 8))
            router = ServingRouter(iex, max_batch=8, max_wait_ms=100.0,
                                   queue_limit=64)
            return CellHead(name, store, router, cache), sparse

        west, west_ids = make_head("west", stores[0])
        east, east_ids = make_head("east", stores[2])
        heads = [west, east]
        # east leaves two shard-1 keys COLD so the partition exercises
        # the local-failover path (shard 1's ring backup, rank 2, lives
        # in east); everything else is warm in both cells
        cold_east = np.asarray([1, 5], np.int64)     # key % 4 == 1
        all_keys = np.arange(vocab, dtype=np.int64)
        west.warm(all_keys)
        east.warm(np.setdiff1d(all_keys, cold_east))

        rng = np.random.RandomState(3)

        def wave(head, node, ids_batch):
            return head.serve_wave([{node: ids} for ids in ids_batch])

        def warm_ids(n, forbid=()):
            pool = np.setdiff1d(all_keys, np.asarray(forbid, np.int64))
            return [rng.choice(pool, n_fields) for _ in range(n)]

        spec = "17:" + cells.partition_spec("west", "east", cut_step,
                                            heal_step)
        inj = chaos_mod.ChaosInjector.from_spec(spec)
        prev = chaos_mod.install(inj)
        try:
            # phase 1 — link up: both cells serve, trainer writes
            _, w1 = wave(west, west_ids, warm_ids(8))
            _, e1 = wave(east, east_ids, warm_ids(8, forbid=cold_east))
            stores[0].push(tid, np.arange(vocab),
                           rng.standard_normal((vocab, dim))
                           .astype(np.float32) * 0.1)
            inj.on_step(cut_step)                    # the link dies
            # phase 2 — partitioned: warm reads keep serving in BOTH
            # cells; east also hits its cold shard-1 keys, forcing a
            # LOCAL failover promotion (new lineage for shard 1)
            _, w2 = wave(west, west_ids, warm_ids(8))
            cold_feed = [np.concatenate((cold_east,
                                         rng.choice(vocab // 2, 2)))]
            _, e2a = wave(east, east_ids, cold_feed)
            _, e2b = wave(east, east_ids,
                          warm_ids(7, forbid=cold_east))
            # cross-cell re-replication QUEUES while the link is down
            d0 = fault_counts().get("ps_re_replicate_deferred", 0)
            east.catch_up()
            deferred = fault_counts().get("ps_re_replicate_deferred",
                                          0) > d0
            inj.on_step(heal_step)                   # the link heals
            # phase 3 — heal: the west trainer's first write through the
            # stale ex-primary is epoch-refused + re-routed (the fence
            # dance); catch-up re-replicates; both cells keep serving
            stores[0].push(tid, np.asarray([1, 5, 9], np.int64),
                           np.ones((3, dim), np.float32) * 0.01)
            east.catch_up()
            west.catch_up()
            _, w3 = wave(west, west_ids, warm_ids(8))
            _, e3 = wave(east, east_ids, warm_ids(8))
        finally:
            chaos_mod.install(prev)
        counters = fault_counts()
        report = fsck(endpoints, n_tables=1, replication=2, retries=2,
                      retry_wait=0.2)
        served_through_cut = all(
            w["rejections"] == 0 and w["errors"] == 0
            and w["answered"] == w["admitted"] > 0
            for w in (w2, e2a, e2b))
        ok = (served_through_cut and deferred
              and counters.get("ps_failover_promoted", 0) >= 1
              and counters.get("ps_epoch_refused", 0) >= 1
              and counters.get("ps_demotions", 0) >= 1
              and west.stats["rejections"] == 0
              and east.stats["rejections"] == 0
              and report["ok"]
              and all(len(r) == 1
                      for r in report["serving_ranks"].values()))
        return {
            "ok": ok,
            "cells": {name: cells.ranks(name) for name in cells.cells},
            "partition_spec": spec,
            "served_through_cut": served_through_cut,
            "re_replication_deferred_in_partition": deferred,
            "cell_stats": {h.name: h.stats for h in heads},
            "waves": {"west": [w1, w2, w3], "east": [e1, e2a, e2b, e3]},
            "fsck_ok": report["ok"],
            "fsck_serving_ranks": report["serving_ranks"],
            "fault_counters": counters,
        }
    finally:
        _close_all(heads)
        _close_all(stores)


# ---------------------------------------------------------------- elastic

def elastic_scenario(steps=10, kill_step=3, rejoin_step=5, dp=4, zero=1):
    """tests/test_elastic.py::test_elastic_scenario — kill one of dp=4
    mid-run (``kill:proc@rank2:step<kill_step>`` on the deterministic step
    clock), keep training at dp=3 without a restart, grow back when the
    rank rejoins before step ``rejoin_step``; against the uninterrupted
    dp-MATCHED reference (same graph, same feeds, same world trajectory
    via explicit resizes, no chaos, no controller).  Losses BITWISE equal;
    the compiled-step cache holds 2 misses for the two world sizes and
    >= 1 HIT on the grow-back (no recompile); both resizes are spans in
    the trace."""
    import gc

    import jax
    import hetu_tpu as ht
    from hetu_tpu import chaos as chaos_mod, metrics as ht_metrics, obs
    from hetu_tpu.graph import step_cache
    from hetu_tpu.parallel.elastic import (ElasticController, LogicalRank,
                                           handles_alive_fn)

    if len(jax.devices()) < dp:
        raise RuntimeError(f"elastic_scenario needs >= {dp} devices")
    if not (0 < kill_step < rejoin_step <= steps - 2):
        raise ValueError(
            f"need 0 < kill_step < rejoin_step <= steps-2, got "
            f"kill={kill_step} rejoin={rejoin_step} steps={steps}")
    if dp < 3:
        # the script kills one rank and keeps training: the controller
        # floors the shrink at min_dp=2, so dp=2 would refuse the resize
        raise ValueError(f"elastic_scenario needs dp >= 3, got dp={dp}")

    dead_rank = dp - 2
    per_rank = 4        # per-replica batch rows: global batch = dp * 4

    def build():
        rng = np.random.RandomState(0)
        x = ht.placeholder_op("x")
        y_ = ht.placeholder_op("y_")
        w1 = ht.Variable("w1",
                         value=rng.randn(16, 32).astype(np.float32) * 0.2)
        b1 = ht.Variable("b1", value=np.zeros(32, np.float32))
        w2 = ht.Variable("w2",
                         value=rng.randn(32, 8).astype(np.float32) * 0.2)
        h = ht.relu_op(ht.linear_op(x, w1, b1))
        loss = ht.reduce_mean_op(
            ht.softmaxcrossentropy_op(ht.matmul_op(h, w2), y_), [0])
        opt = ht.optim.AdamOptimizer(0.01)
        ex = ht.Executor(
            {"train": [loss, opt.minimize(loss)]}, seed=0,
            dist_strategy=ht.dist.DataParallel(num_devices=dp), zero=zero)
        return x, y_, ex

    def batch(step, world):
        rng = np.random.RandomState(4242 + step)
        n = per_rank * world
        xv = rng.randn(n, 16).astype(np.float32)
        yv = np.eye(8, dtype=np.float32)[rng.randint(0, 8, n)]
        return xv, yv

    # the world trajectory both runs follow: shrink fires at the poll
    # after the kill (chaos on_step reports post-step counters, so
    # kill_step means "kill after the step that leaves the counter
    # there"), grow at the poll after the rejoin
    worlds = [dp if (i < kill_step or i >= rejoin_step) else dp - 1
              for i in range(steps)]

    step_cache.clear()
    gc.collect()
    ht_metrics.reset_all()

    # ---- elastic run: chaos kill + controller-driven resize ----------
    handles = [LogicalRank(r) for r in range(dp)]
    inj = chaos_mod.ChaosInjector.from_spec(
        f"7:kill:proc@rank{dead_rank}:step{kill_step}")
    for h in handles:
        inj.register_proc(h.rank, h)
    prev = chaos_mod.install(inj)
    obs.clear_trace()
    obs.enable(True)
    try:
        x, y_, ex = build()
        ctl = ElasticController(ex, world=dp,
                                alive_fn=handles_alive_fn(handles),
                                min_dp=2)
        losses, seen_worlds = [], []
        for i in range(steps):
            xv, yv = batch(i, ctl.dp)
            out = ex.run("train", feed_dict={x: xv, y_: yv})
            losses.append(np.float32(out[0].asnumpy()))
            seen_worlds.append(ctl.dp)
            if i == rejoin_step - 1:
                handles[dead_rank].rejoin()
            ctl.poll()
        trace_evs = obs.trace_events()
    finally:
        obs.enable(False)
        obs.clear_trace()
        chaos_mod.install(prev)
    elastic_counters = dict(ht_metrics.elastic_counts())
    fault_counters = dict(ht_metrics.fault_counts())
    sc = dict(ht_metrics.step_cache_counts())
    kinds = [e["kind"] for e in ctl.events]
    # drop BOTH references to the elastic executor (ctl.ex pins it) so
    # the reference run below doesn't coexist with its device buffers
    del ex, ctl
    gc.collect()

    def count(ph, name):
        return sum(1 for e in trace_evs
                   if e.get("ph") == ph and e["name"] == name)
    trace = {"resize_spans": count("X", "elastic.resize"),
             "shrink_events": count("i", "elastic:shrink"),
             "grow_events": count("i", "elastic:grow")}

    # ---- dp-matched reference: same trajectory, zero chaos -----------
    ht_metrics.reset_elastic_counts()
    x, y_, ex2 = build()
    ref_losses, active = [], list(range(dp))
    for i, w in enumerate(worlds):
        if w != len(active):
            active = [r for r in range(dp) if r != dead_rank] \
                if w == dp - 1 else list(range(dp))
            ex2.resize_world(active)
        xv, yv = batch(i, w)
        out = ex2.run("train", feed_dict={x: xv, y_: yv})
        ref_losses.append(np.float32(out[0].asnumpy()))
    clean_elastic = dict(ht_metrics.elastic_counts())
    del ex2
    step_cache.clear()
    gc.collect()

    parity = [v.tobytes() for v in losses] \
        == [v.tobytes() for v in ref_losses]
    return {
        "ok": (parity and seen_worlds == worlds
               and kinds == ["shrink", "grow"]
               and fault_counters.get("chaos_kill_proc") == 1
               and fault_counters.get("supervisor_restart", 0) == 0
               and fault_counters.get("resume", 0) == 0
               and sc.get("step_cache_miss") == 2
               and sc.get("step_cache_hit", 0) >= 1
               and trace["resize_spans"] == 2
               and trace["shrink_events"] >= 1
               and trace["grow_events"] >= 1),
        "world_trajectory": seen_worlds,
        "expected_trajectory": worlds,
        "resize_kinds": kinds,
        "loss_bitwise_equal_vs_reference": parity,
        "restarts": int(fault_counters.get("supervisor_restart", 0)),
        "resumes": int(fault_counters.get("resume", 0)),
        "elastic_counters": elastic_counters,
        "fault_counters": fault_counters,
        "clean_run_elastic_counters": clean_elastic,
        "step_cache": sc,
        "trace": trace,
    }
