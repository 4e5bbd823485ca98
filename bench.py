"""Benchmark driver — prints ONE JSON line for the round harness.

Primary config (BASELINE.json): BERT-base MLM pretraining, samples/sec/chip
and MFU vs the 45%-MFU north-star target.  ``--config resnet18`` covers the
CIFAR10 step-time config.

Artifact schema (uniform across every config and every tool artifact):
  value / unit        the headline number for this config
  vs_baseline         achieved ÷ declared baseline — >1.0 beats the
                      baseline, 1.0 matches it.  The baseline itself is
                      named in extra.baseline_def: the 45%-MFU north star
                      for bert (BASELINE.md), the committed same-workload
                      torch-CPU measurement for the rest.  0.0 ONLY when
                      the declared baseline is unavailable (baseline_def
                      then says why) — never as a euphemism for "slow".
  extra.git_sha       repo HEAD when the number was MEASURED (cached TPU
                      artifacts keep the sha of the measuring commit)
  extra.workload      the workload knobs that define the metric
  extra.workload_hash sha256[:12] of the canonical workload JSON — lets a
                      reviewer tie any artifact to the exact workload
                      without diffing dicts

The accelerator configs (bert, resnet18, wdl, moe, attn) measure in the
invoking process and refuse to run — non-zero exit, no metric printed — when
the jax backend is not ``tpu``: a CPU number is never printed under their
metric names.  The host-side configs (chaos, failover, emb, zero, serve,
decode, fleet, partition, overhead, trace, elastic, remat) measure host code
on the CPU backend by design; they run in ONE child with ``JAX_PLATFORMS=cpu``
in its environment, so this parent never touches jax (a process that has
touched jax holds the chip).
"""
import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

CHILD_ENV_FLAG = "_HETU_BENCH_CHILD"
DEFAULT_STEPS = 20
CHILD_TIMEOUT_S = int(os.environ.get("HETU_BENCH_CHILD_TIMEOUT", "420"))
#: the accelerator configs: they need the chip and say so
ACCEL_CONFIGS = ("bert", "resnet18", "wdl", "moe", "attn")


def _free_ports(n):
    """``n`` OS-assigned free localhost ports (bind, record, release) —
    shared by every in-process multi-rank chaos/serving config."""
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


def _sync(outs):
    """Wait for the step outputs (training steps chain through the
    params, so this waits for every dispatched step) — the ONE shared
    helper in graph.executor."""
    from hetu_tpu.graph.executor import _sync_outs
    _sync_outs(outs)


def _timed(run_step, steps, warmup):
    """Shared timing harness: warmup, sync, timed loop, sync → s/step
    (see _sync).  The
    timed loop runs with the per-step wall-time histogram recording
    (``metrics.step_time_us`` on the obs registry), so every config
    that uses this harness gets p50/p99 step-time percentiles
    (``_step_percentiles``) alongside the mean — not just means."""
    from hetu_tpu import metrics as ht_metrics
    out = None
    for i in range(warmup):
        out = run_step(i)
    _sync(out)
    prev = ht_metrics.step_timing
    ht_metrics.reset_step_times()
    ht_metrics.enable_step_timing(True)
    try:
        t0 = time.perf_counter()
        for i in range(steps):
            out = run_step(i)
        _sync(out)
        return (time.perf_counter() - t0) / steps
    finally:
        ht_metrics.enable_step_timing(prev)


def _step_percentiles():
    """{sub: {p50_ms, p99_ms, count}} from the step-time histogram the
    last ``_timed`` loop recorded (obs registry; per-step dispatch wall
    — under sync=False stepping this measures dispatch, not device
    completion, same caveat as ``timing=True``)."""
    from hetu_tpu.metrics import step_time_stats
    return _hist_ms(step_time_stats())


def _hist_ms(snap):
    """Compress a microsecond histogram snapshot (obs registry) to
    artifact-friendly ms percentiles: {label: {count, mean_ms, p50_ms,
    p99_ms}} — empty labels dropped."""
    out = {}
    for label, h in (snap or {}).items():
        if not h.get("count"):
            continue
        out[label] = {"count": int(h["count"]),
                      "mean_ms": round(h["mean"] / 1e3, 3),
                      "p50_ms": round(h["p50"] / 1e3, 3),
                      "p99_ms": round(h["p99"] / 1e3, 3)}
    return out


def _params_count(ex):
    return int(sum(np.prod(v.shape) for n, v in ex.var_values.items()
                   if n.trainable))


def _device_peak_flops():
    """(peak_flops_per_chip, device_kind) — the shared per-device-kind
    table in ``hetu_tpu.obs`` (one table for bench AND the autoparallel
    measurement loop; an unknown TPU kind raises there)."""
    from hetu_tpu.obs import device_peak_flops
    return device_peak_flops()


from artifact_schema import provenance as _provenance  # noqa: E402


def _torch_bench_baseline(config, workload):
    """Committed same-workload torch-CPU baseline (reference methodology:
    every example family ships comparison scripts — tf_main.py etc.).
    Returns (value, label) or (None, None) when absent or workload-
    mismatched."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "artifacts", "torch_baselines_bench.json")
    try:
        with open(path) as f:
            row = json.load(f)[config]
        value = row["value"]
    except (OSError, KeyError, json.JSONDecodeError):
        return None, None
    extra = row.get("extra", {})
    if any(extra.get(k) != v for k, v in workload.items()):
        return None, None
    return value, f"{extra.get('framework', 'torch')}-cpu same-workload"


def _flash_in_hlo(ex, fd, name="train"):
    """True iff the compiled step's HLO contains the Pallas kernel's
    custom-call (evidence the flash kernel is in the MEASURED path).
    Raises if the HLO cannot be had — never 'unknown'."""
    from hetu_tpu.profiler import HetuProfiler
    text = HetuProfiler(ex, name=name).hlo_text(fd)
    return "tpu_custom_call" in text


def _compute_dtype():
    """bf16 on TPU (the real mixed-precision config); f32 for the
    host-side configs that build these graphs on a CPU mesh (zero,
    elastic, remat) — XLA-CPU emulates bf16."""
    import jax
    return "bfloat16" if jax.default_backend() == "tpu" else None


def _load_example_models(family):
    """Load ``examples/<family>``'s models under a unique module name.

    Both cnn and ctr call their module ``models``; a plain ``import
    models`` serves whichever loaded first to the second caller when one
    process builds several configs (tools/hlo_audit.py --config all), and
    the old relative ``sys.path.insert(0, "examples/cnn")`` broke when
    invoked from outside the repo root."""
    import importlib.util
    root = os.path.dirname(os.path.abspath(__file__))
    base = os.path.join(root, "examples", family)
    path = os.path.join(base, "models", "__init__.py")
    if not os.path.exists(path):
        path = os.path.join(base, "models.py")
    name = f"_bench_{family}_models"
    if name in sys.modules:
        return sys.modules[name]
    kw = {}
    if path.endswith("__init__.py"):   # package: enable relative imports
        kw["submodule_search_locations"] = [os.path.dirname(path)]
    spec = importlib.util.spec_from_file_location(name, path, **kw)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        # never leave a half-initialized module for the next caller's
        # fast path to silently reuse
        sys.modules.pop(name, None)
        raise
    return mod


# -- shared graph builders ---------------------------------------------------
# Each bench_* measures the graph its build_*_graph builds, and
# tools/hlo_audit.py audits the SAME builders — the audited program and the
# measured program cannot drift apart.

def build_bert_graph(batch_size=32, seq_len=512,
                     compute_dtype="__bench_default__",
                     size="base", dp=None, zero=None, remat=None):
    """The flagship training step: BERT-base padded MLM (see bench_bert).
    Returns (cfg, ex, fd).

    ``dp``: build on a data-parallel mesh of that many devices;
    ``zero``: ZeRO weight-update-sharding stage on that mesh (bench_zero
    measures it); ``size``: 'base' | 'tiny' (the dp>=4 CPU-mesh memory
    bench uses tiny — same graph family, host-feasible state size);
    ``remat``: selective-remat policy (``off|dots|full|offload|auto`` —
    ``parallel/remat.py``; bench_remat sweeps it)."""
    import jax
    import hetu_tpu as ht
    from hetu_tpu.models.bert import (BertConfig, bert_pretrain_graph,
                                      synthetic_mlm_batch)

    if compute_dtype == "__bench_default__":
        compute_dtype = _compute_dtype()
    cfg = getattr(BertConfig, size)(batch_size=batch_size, seq_len=seq_len)
    feeds, loss, logits = bert_pretrain_graph(cfg)
    opt = ht.optim.AdamOptimizer(1e-4)
    strategy = ht.dist.DataParallel(num_devices=dp) if dp else None
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0,
                     compute_dtype=compute_dtype,
                     dist_strategy=strategy, zero=zero, remat=remat)
    ids, tt, labels, attn = synthetic_mlm_batch(cfg)
    # ids/labels/mask stay int32 end-to-end: integer feeds are exempt from
    # the bf16 compute_dtype cast (bf16 is exact only up to 256)
    fd = {feeds["input_ids"]: jax.device_put(np.asarray(ids, np.int32)),
          feeds["token_type_ids"]: jax.device_put(np.asarray(tt, np.int32)),
          feeds["masked_lm_labels"]: jax.device_put(np.asarray(labels, np.int32)),
          feeds["attention_mask"]: jax.device_put(np.asarray(attn, np.int32))}
    return cfg, ex, fd


def build_resnet18_graph(batch_size=128, data_format=None,
                         compute_dtype="__bench_default__"):
    """resnet18/CIFAR10 Momentum step (see bench_resnet18); data_format
    None → per-backend pick (measured: NHWC wins on TPU lane mapping,
    loses 1.5x on XLA-CPU — artifacts/resnet_cpu_root_cause.json).
    Returns (None, ex, fd)."""
    import jax
    import hetu_tpu as ht
    models = _load_example_models("cnn")

    if compute_dtype == "__bench_default__":
        compute_dtype = _compute_dtype()
    x = ht.placeholder_op("x", shape=(batch_size, 3, 32, 32))
    y_ = ht.placeholder_op("y", shape=(batch_size, 10))
    if data_format is None:
        data_format = "NHWC" if jax.default_backend() == "tpu" else "NCHW"
    loss, y = models.resnet18(x, y_, data_format=data_format)
    ex = ht.Executor(
        {"train": [loss,
                   ht.optim.MomentumOptimizer(0.1).minimize(loss)]},
        seed=0, compute_dtype=compute_dtype)
    rng = np.random.RandomState(0)
    xv = rng.rand(batch_size, 3, 32, 32).astype(np.float32)
    yv = np.eye(10, dtype=np.float32)[rng.randint(0, 10, batch_size)]
    fd = {x: jax.device_put(xv), y_: jax.device_put(yv)}
    return None, ex, fd


def build_wdl_graph(batch_size=2048, policy="lru"):
    """Wide&Deep CTR SGD step (see bench_wdl) — f32 end-to-end by design:
    the workload is embedding-lookup bound; bf16 would round 100k-row
    id-gradients for no MXU win.  Returns (None, ex, fd) plus the
    placeholder nodes for multi-batch feeding: (dense, sparse, y_)."""
    import hetu_tpu as ht
    ctr = _load_example_models("ctr")

    dense = ht.placeholder_op("dense")
    # ids must stay integral: float32 is exact only below 2^24, real
    # Criteo vocabs exceed it (the bench_bert int32-feed lesson)
    sparse = ht.placeholder_op("sparse", dtype=np.int64)
    y_ = ht.placeholder_op("y")
    loss, prob = ctr.wdl_criteo(dense, sparse, y_, batch_size,
                                vocab=100000, dim=16, embed_mode=policy,
                                lr=0.01)
    opt = ht.optim.SGDOptimizer(0.01)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0)
    d, s, y = ctr.synthetic_criteo(batch_size, vocab=100000)
    return None, ex, {dense: d, sparse: s, y_: y}, (dense, sparse, y_)


def build_moe_graph(batch_tokens=8192, compute_dtype="__bench_default__"):
    """GShard top-2 16-expert MoE Adam step (see bench_moe).
    Returns ({"d":..., "experts":...}, ex, fd) — the dims dict keeps
    bench_moe's reported metadata tied to the graph actually built."""
    import jax
    import hetu_tpu as ht

    if compute_dtype == "__bench_default__":
        compute_dtype = _compute_dtype()
    d, experts = 512, 16
    x = ht.placeholder_op("x", shape=(batch_tokens, d))
    y_ = ht.placeholder_op("y", shape=(batch_tokens, d))
    gate = ht.layers.TopKGate(d, batch_tokens, experts, k=2,
                              capacity_factor=1.25)
    moe = ht.layers.MoELayer(gate, ht.layers.Expert(experts, d, 4 * d))
    h, aux = moe(x)
    loss = ht.reduce_mean_op(ht.ops.mul_op(h - y_, h - y_), [0, 1]) \
        + aux * 0.01
    opt = ht.optim.AdamOptimizer(1e-3)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0,
                     compute_dtype=compute_dtype)
    rng = np.random.RandomState(0)
    fd = {x: jax.device_put(rng.randn(batch_tokens, d).astype(np.float32)),
          y_: jax.device_put(rng.randn(batch_tokens, d).astype(np.float32))}
    return {"d": d, "experts": experts}, ex, fd


def bench_bert(batch_size=None, seq_len=512, steps=20, warmup=3,
               remat=None):
    """Flagship config: BERT-base padded MLM pretraining.

    seq 512 (the flash-gated regime) with a real attention_mask input —
    the kernel's key-mask strip path is the measured path, per the round-3
    verdict (seq 128 dense never reached the kernel).

    The headline ``step_time_ms`` is the PIPELINED run (ISSUE 9):
    numpy-ingested feeds double-buffered to the device by
    ``Executor.run_steps`` + non-blocking (``sync=False``) stepping, at
    the backend's default compute dtype (bf16 on TPU).  The same-dtype
    unpipelined loop and (on TPU) the fp32 unpipelined reference ride in
    ``extra`` so the pipelining and bf16 wins are separable."""
    import jax
    from hetu_tpu.metrics import (reset_flash_fallbacks,
                                  reset_run_plan_counts, run_plan_counts)

    if batch_size is None:
        # seq 512: the whole bf16 step compiled for a described v5e
        # needs 6.6 GiB of temporaries at b32 and 14.4 GiB at b64 next
        # to 1.5 GiB of state — b64 does not fit a 16 GB chip
        batch_size = 32 if seq_len >= 512 else 192
    reset_flash_fallbacks()
    cfg, ex, fd = build_bert_graph(batch_size=batch_size, seq_len=seq_len,
                                   remat=remat)

    # numpy ingest: the realistic feed path (a dataloader hands the
    # executor host arrays) — exactly what the feed pipeline overlaps
    fd_np = {node: np.asarray(v) for node, v in fd.items()}

    dt_unpip = _timed(lambda i: ex.run("train", feed_dict=fd_np),
                      steps, warmup)
    reset_run_plan_counts()
    from hetu_tpu import metrics as ht_metrics
    ht_metrics.reset_step_times()
    prev_timing = ht_metrics.step_timing
    ht_metrics.enable_step_timing(True)
    try:
        t0 = time.perf_counter()
        rs = ex.run_steps(lambda i: fd_np, steps, name="train",
                          sync=False)
        _sync(rs[-1])
        dt = (time.perf_counter() - t0) / steps
    finally:
        # restore, don't clobber: HETU_STEP_TIMING=1 processes keep
        # recording after the bench (the _timed harness's discipline)
        ht_metrics.enable_step_timing(prev_timing)
    # per-step dispatch-wall percentiles of the headline (pipelined,
    # sync=False) loop — the p99 tail the mean hides
    step_hist = _step_percentiles()
    plan_counters = run_plan_counts()
    if _compute_dtype():
        # TPU: the fp32 unpipelined reference the ISSUE 9 acceptance
        # compares against (same batch/seq/environment)
        _, ex32, fd32 = build_bert_graph(batch_size=batch_size,
                                         seq_len=seq_len,
                                         compute_dtype=None, remat=remat)
        fd32_np = {node: np.asarray(v) for node, v in fd32.items()}
        dt_fp32 = _timed(lambda i: ex32.run("train", feed_dict=fd32_np),
                         max(steps // 2, 1), warmup)
        del ex32, fd32
    else:
        # a CPU caller (a test) runs f32 either way: the reference IS
        # dt_unpip
        dt_fp32 = dt_unpip
    out = ex.run("train", feed_dict=fd)

    n_params = _params_count(ex)
    # MFU counts only matmul-active params: the input embedding tables
    # (word/position/token-type) are lookups, not matmuls — counting them
    # inflated MFU ~20% (round-3 verdict).  The MLM decoder (hidden×vocab)
    # IS a matmul and stays in.
    embed_params = (cfg.vocab_size + cfg.max_position_embeddings
                    + cfg.type_vocab_size) * cfg.hidden_size
    n_matmul = n_params - embed_params
    tokens = batch_size * seq_len
    # training FLOPs/token: 6N (fwd+bwd matmuls) + attention score/value
    # terms 12·L·h·s (computed on padded shapes — that is what the MXU
    # executes; padding waste shows up as lower MFU, not hidden FLOPs)
    flops_per_token = 6 * n_matmul + 12 * cfg.num_hidden_layers \
        * cfg.hidden_size * seq_len
    flops_per_step = flops_per_token * tokens
    n_dev = len(jax.devices())
    peak, device_kind = _device_peak_flops()
    mfu = flops_per_step / dt / (peak * n_dev)
    # publish the per-run gauges on the obs registry: metrics_dump()
    # and tools/metricsd.py expose the same numbers this artifact embeds
    ht_metrics.record_run_gauges("bert", dt * 1e3, mfu)
    samples_per_sec_chip = batch_size / dt / n_dev
    final_loss = float(np.asarray(out[0].jax() if hasattr(out[0], "jax")
                                  else out[0]))
    # TPU reports its memory; XLA-CPU (a test calling this) has no stats
    st = jax.devices()[0].memory_stats()
    if st is None and jax.default_backend() == "tpu":
        raise RuntimeError("the TPU backend reported no memory_stats")
    hbm_gb = round(st["peak_bytes_in_use"] / 2**30, 2) if st else None
    from hetu_tpu.profiler import HetuProfiler
    return {
        "metric": "bert_base_pretrain_samples_per_sec_per_chip",
        "value": round(samples_per_sec_chip, 2),
        "unit": "samples/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": {
            "baseline_def": "achieved MFU / 0.45 north-star MFU (BASELINE.md)",
            **_provenance({"batch_size": batch_size, "seq_len": seq_len,
                           **({"remat": remat} if remat else {})}),
            **({"remat": remat,
                "remat_plan": ex.remat_plan("train")} if remat else {}),
            "mfu": round(mfu, 4),
            "step_time_ms": round(dt * 1e3, 2),
            "step_time_hist_ms": step_hist,
            "pipelined": True,
            "step_time_ms_unpipelined": round(dt_unpip * 1e3, 2),
            "step_time_ms_fp32_unpipelined": round(dt_fp32 * 1e3, 2),
            "vs_fp32_unpipelined": round(dt_fp32 / max(dt, 1e-9), 3),
            "run_plan_counters": {k: int(v)
                                  for k, v in plan_counters.items()},
            # the active auto-parallel plan (or the naive data-parallel
            # default): lets the BENCH trajectory attribute step-time
            # moves to plan changes (ISSUE 15)
            "plan": (ex.plan.tag() if getattr(ex, "plan", None) is not None
                     else "naive-dp"),
            "params": n_params, "matmul_params": n_matmul,
            "flops_per_step": flops_per_step,
            "peak_flops": peak, "device_kind": device_kind,
            "flash_in_hlo": _flash_in_hlo(ex, fd),
            "flash_fallbacks": HetuProfiler.flash_fallbacks(),
            "peak_hbm_gb": hbm_gb,
            # per-device param/grad/opt-state bytes + live-buffer total:
            # the memory-side evidence peak_hbm_gb cannot give on CPU
            "memory": ex.memory_accounting(),
            "compute_dtype": _compute_dtype() or "float32",
            "backend": jax.default_backend(),
            "devices": n_dev, "loss": round(final_loss, 4),
        },
    }


def bench_zero(dp=4, steps=12, warmup=2, batch_size=8, seq_len=128,
               size="tiny"):
    """ISSUE 6 acceptance: ZeRO weight-update sharding vs replicated Adam
    at dp>=4 on the bert graph family.

    Three executors over the SAME graph + feeds — zero=0 (replicated
    baseline), zero=2 (reduce-scattered update, replicated params),
    zero=3 (sharded master params) — each run ``steps`` >= 10 steps.
    Records per-device param/grad/opt-state bytes, the live-buffer peak
    across steps, mean step time, and the full loss trajectory as raw
    float bits (the parity claim is BITWISE, not approximate).  On a CPU
    host-device mesh the state-memory ratio is the headline; 'tiny'
    keeps the replicated baseline host-feasible (same graph family as
    the flagship).  Writes ``artifacts/zero_bench.json``."""
    import gc
    import jax
    from hetu_tpu.graph import step_cache
    from hetu_tpu.metrics import reset_zero_counts, zero_counts

    if len(jax.devices()) < dp:
        raise RuntimeError(
            f"bench_zero needs >= {dp} devices — run under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={dp} (bench.py "
            f"--config zero sets this for its child automatically)")

    runs = {}
    for stage in (0, 2, 3):
        # the compiled-step cache pins its builder executor (and that
        # executor's state) alive — clear it so each run's live-buffer
        # numbers describe ONE executor
        step_cache.clear()
        gc.collect()
        reset_zero_counts()
        _, ex, fd = build_bert_graph(batch_size=batch_size,
                                     seq_len=seq_len, compute_dtype=None,
                                     size=size, dp=dp, zero=stage)
        losses, live_peak = [], 0
        for i in range(steps):
            out = ex.run("train", feed_dict=fd)
            losses.append(np.asarray(
                out[0].jax() if hasattr(out[0], "jax") else out[0],
                np.float32))
            if i in (0, steps // 2, steps - 1):  # sampling is not free
                mem = ex.memory_accounting()
                live_peak = max(live_peak,
                                mem["live_buffer_bytes_per_device"] or 0)
        dt = _timed(lambda i: ex.run("train", feed_dict=fd), steps, warmup)
        mem = ex.memory_accounting()
        runs[f"zero{stage}"] = {
            "zero_stage": stage,
            "loss_bits": [v.tobytes().hex() for v in losses],
            "final_loss": float(losses[-1]),
            "step_time_ms": round(dt * 1e3, 2),
            "live_buffer_peak_bytes_per_device": live_peak,
            "zero_counters": zero_counts(),
            **{k: mem[k] for k in
               ("param_bytes_per_device", "zero_slab_bytes_per_device",
                "opt_state_bytes_per_device", "grad_bytes_per_device")},
        }
        del ex, fd
    step_cache.clear()
    gc.collect()

    base, z2, z3 = runs["zero0"], runs["zero2"], runs["zero3"]
    bitwise2 = base["loss_bits"] == z2["loss_bits"]
    bitwise3 = base["loss_bits"] == z3["loss_bits"]
    opt_ratio = base["opt_state_bytes_per_device"] \
        / max(1, z2["opt_state_bytes_per_device"])
    state3 = z3["param_bytes_per_device"] \
        + z3["zero_slab_bytes_per_device"] \
        + z3["opt_state_bytes_per_device"]
    state0 = base["param_bytes_per_device"] \
        + base["opt_state_bytes_per_device"]
    # the step-time gate judges stage 3 — the full tentpole mode, whose
    # param all-gather sits at the top of the next step where XLA's async
    # scheduler overlaps it with early compute (stage 2's reduce-scatter
    # is emulated as all-reduce+slice on XLA-CPU and pays a CPU-only tax;
    # its ratio stays in extra)
    step_ratio = base["step_time_ms"] / max(1e-9, z3["step_time_ms"])
    res = {
        "metric": "zero_opt_state_shrink_vs_replicated",
        "value": round(opt_ratio, 2),
        "unit": "x",
        # >= ~0.95 = step-time parity or better (the acceptance gate)
        "vs_baseline": round(step_ratio, 3),
        "extra": {
            "baseline_def": "value = replicated per-device optimizer-"
                            "state bytes / zero-2 bytes (target ~dp); "
                            "vs_baseline = replicated step time / zero-3 "
                            "step time (>=0.95 = parity)",
            "step_ratio_zero2": round(
                base["step_time_ms"] / max(1e-9, z2["step_time_ms"]), 3),
            **_provenance({"dp": dp, "batch_size": batch_size,
                           "seq_len": seq_len, "size": size,
                           "steps": steps}),
            "loss_bitwise_equal": {"zero2": bitwise2, "zero3": bitwise3},
            "training_state_bytes_per_device":
                {"zero0": state0, "zero3": state3,
                 "ratio": round(state0 / max(1, state3), 2)},
            "runs": runs,
            "backend": jax.default_backend(),
        },
    }
    if not (bitwise2 and bitwise3):
        res["error"] = "loss NOT bitwise-equal to replicated Adam"
    try:
        from artifact_schema import provenance as _prov
        out = {**res, **_prov({"dp": dp, "steps": steps})}
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "artifacts", "zero_bench.json")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path + ".tmp", "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        os.replace(path + ".tmp", path)
    except Exception:
        pass    # the printed result is the bench contract; file is extra
    return res


REMAT_SWEEP_POLICIES = ("off", "dots", "full", "auto")


def _remat_artifact_path():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "artifacts", "remat_bench.json")


def _write_remat_partial(path, payload):
    """Atomic write of the (possibly partial) remat-sweep artifact —
    the cell store a killed attempt resumes from."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path + ".tmp", "w") as f:
        json.dump(payload, f, indent=1, sort_keys=True)
    os.replace(path + ".tmp", path)


def bench_remat(steps=8, warmup=2, batch_size=32, seq_len=256,
                size="tiny", parity_steps=3, artifact_path=None,
                overlap_gate=True, policies=REMAT_SWEEP_POLICIES):
    """ISSUE 13 acceptance: the selective-remat policy sweep on the bert
    graph family, with PARTIAL-RUNWAY CHECKPOINTED measurement.

    One cell per policy (off / dots / full / auto), each a fresh
    executor over the same graph + feeds: bitwise loss bits
    (``parity_steps`` steps — remat replays the same ops, so parity is
    EXACT), mean + p50/p99 step time, the ``memory_accounting()``
    live-buffer peak (live arrays + the compiled step's XLA
    buffer-assignment temp — the in-step activation peak remat trades),
    a projected max-fitting batch size against the HBM budget, the MFU
    gauge, and — for the segmented policies — the resolved plan.
    ``auto``'s budget is derived from the measured ``full`` plan
    (persistent + half the priced activation bytes), so the greedy
    planner must land STRICTLY BETWEEN off and full on both peak and
    step time.

    Every completed cell is PERSISTED into the artifact immediately
    (workload-fingerprinted): a sweep killed mid-cell resumes from the
    persisted cells on the next attempt instead of re-measuring
    finished ones.  The dp=4 zero=3
    overlap audit (``tools/overlap_audit.py``) gates the same artifact:
    an audit failure is a bench ``error``, never a silent pass."""
    import gc
    import jax
    from hetu_tpu.graph import step_cache
    from hetu_tpu import metrics as ht_metrics
    from hetu_tpu.parallel import remat as remat_mod

    path = artifact_path or _remat_artifact_path()
    compute_dtype = _compute_dtype() or "float32"
    n_dev = len(jax.devices())
    workload = {"batch_size": batch_size, "seq_len": seq_len,
                "size": size, "steps": steps,
                "parity_steps": parity_steps,
                "backend": jax.default_backend(),
                "compute_dtype": compute_dtype}

    # resume: reuse completed cells iff the workload fingerprint matches
    prior_cells = {}
    try:
        with open(path) as f:
            prev = json.load(f)
        if prev.get("extra", {}).get("workload") == workload:
            prior_cells = {k: v for k, v in
                           prev.get("extra", {}).get("cells", {}).items()
                           if v.get("complete")}
    except (OSError, json.JSONDecodeError):
        pass

    peak_flops, device_kind = _device_peak_flops()
    budget_bytes, budget_source = remat_mod.resolve_budget()
    if budget_bytes is None:
        # the projection denominator when nothing is resolvable: the
        # 16G v5e the flagship is sized for (recorded, not hidden)
        budget_bytes, budget_source = int(16e9), "v5e-default"
    # attempt token: wall clocks from DIFFERENT attempts (a resumed
    # sweep) are not comparable on a shared box — the time gate below
    # re-gauges cross-attempt cells in this process
    attempt_id = f"{os.getpid()}-{int(time.time())}"

    from contextlib import contextmanager

    @contextmanager
    def _cell_build(pol, budget_mb):
        """One cell's build discipline, shared by measure_cell and the
        cross-attempt retime pass so the two can never measure under
        different conditions: cleared step cache, scoped
        HETU_HBM_BUDGET_MB, fresh executor+feeds."""
        step_cache.clear()
        gc.collect()
        prev_budget = os.environ.get("HETU_HBM_BUDGET_MB")
        if budget_mb is not None:
            os.environ["HETU_HBM_BUDGET_MB"] = str(budget_mb)
        try:
            cfg, ex, fd = build_bert_graph(
                batch_size=batch_size, seq_len=seq_len, size=size,
                remat=pol)
            yield cfg, ex, fd
        finally:
            if budget_mb is not None:
                if prev_budget is None:
                    os.environ.pop("HETU_HBM_BUDGET_MB", None)
                else:
                    os.environ["HETU_HBM_BUDGET_MB"] = prev_budget

    def measure_cell(pol, budget_mb=None):
        with _cell_build(pol, budget_mb) as (cfg, ex, fd):
            losses = []
            for _ in range(parity_steps):
                out = ex.run("train", feed_dict=fd)
                losses.append(np.asarray(
                    out[0].jax() if hasattr(out[0], "jax") else out[0],
                    np.float32))
            dt = _timed(lambda i: ex.run("train", feed_dict=fd),
                        steps, warmup)
            hist = _step_percentiles().get("train", {})
            from hetu_tpu.metrics import step_time_stats
            h_raw = step_time_stats().get("train", {})
            mem = ex.memory_accounting(feed_dict=fd, name="train")
            persistent = (mem["param_bytes_per_device"]
                          + mem["zero_slab_bytes_per_device"]
                          + mem["opt_state_bytes_per_device"]
                          + mem["grad_bytes_per_device"])
            temp = mem["step_temp_bytes_per_device"]
            peak = mem["live_buffer_peak_bytes_per_device"]
            # projected max-fitting batch: temp scales ~linearly with
            # batch rows; persistent does not
            max_batch = None
            if temp:
                max_batch = int(batch_size
                                * max(0, budget_bytes - persistent)
                                // temp)
            n_params = _params_count(ex)
            embed = (cfg.vocab_size + cfg.max_position_embeddings
                     + cfg.type_vocab_size) * cfg.hidden_size
            flops_per_step = (6 * (n_params - embed)
                              + 12 * cfg.num_hidden_layers
                              * cfg.hidden_size * seq_len) \
                * batch_size * seq_len
            # the cell's program jits onto ONE device (no mesh), so the
            # MFU denominator is one chip even in the 8-device child
            mfu = flops_per_step / dt / peak_flops
            ht_metrics.record_run_gauges(f"remat_{pol}", dt * 1e3, mfu)
            cell = {
                "policy": pol,
                "complete": True,
                "attempt": attempt_id,
                "loss_bits": [v.tobytes().hex() for v in losses],
                "final_loss": float(losses[-1]),
                "step_time_ms": round(dt * 1e3, 2),
                "step_time_p50_ms": hist.get("p50_ms"),
                "step_time_p99_ms": hist.get("p99_ms"),
                # exact per-step floor from the histogram: the noise-
                # robust ordering statistic on a shared box (the PR 9
                # min-discipline — contention only ever inflates)
                "step_time_min_ms": round(h_raw["min"] / 1e3, 3)
                if h_raw.get("min") is not None else None,
                "live_buffer_peak_bytes": peak,
                "step_temp_bytes": temp,
                "persistent_bytes": int(persistent),
                "max_batch_projected": max_batch,
                "mfu": round(mfu, 6),
                "remat_plan": ex.remat_plan("train"),
                "remat_counters": dict(ht_metrics.remat_counts()),
            }
            if budget_mb is not None:
                cell["auto_budget_mb"] = budget_mb
            del ex, fd
            return cell

    cells = {}
    for pol in policies:
        if pol in prior_cells:
            cells[pol] = {**prior_cells[pol], "resumed": True}
            continue
        budget_mb = None
        if pol == "auto":
            # budget from the measured full plan: persistent + half the
            # priced activation bytes -> the greedy planner must pick a
            # strict subset of segments
            fp = (cells.get("full") or {}).get("remat_plan") or {}
            act = fp.get("activation_bytes_total") or 0
            pers = fp.get("persistent_bytes") \
                or (cells.get("full") or {}).get("persistent_bytes", 0)
            if act:
                budget_mb = round((pers + act * 0.5) / 2**20, 2)
        ht_metrics.reset_remat_counts()
        cells[pol] = measure_cell(pol, budget_mb=budget_mb)
        _write_remat_partial(path, {
            "metric": "remat_full_peak_reduction_vs_off",
            "value": None, "unit": "fraction", "vs_baseline": 0.0,
            "error": "sweep incomplete (partial-runway checkpoint)",
            "extra": {"workload": workload, "cells": cells,
                      **_provenance(workload)},
        })

    off, full = cells.get("off"), cells.get("full")
    auto = cells.get("auto")
    # parity baseline: 'off' when swept, else the first cell — a policy
    # SUBSET run (tests, a single-policy re-measure) must not crash or
    # record spurious gate errors about cells it never requested
    base_cell = off or next(iter(cells.values()))
    parity = all(c["loss_bits"] == base_cell["loss_bits"]
                 for c in cells.values())

    def _peak(c):
        return c.get("live_buffer_peak_bytes") if c else None

    reduction = None
    if _peak(off) and _peak(full):
        reduction = 1.0 - _peak(full) / _peak(off)
    # peaks may all be None where the backend answers no AOT
    # memory analysis — that is a recorded gate FAILURE below, never a
    # TypeError crash that loses the artifact
    auto_between_peak = bool(
        _peak(off) and _peak(full) and _peak(auto)
        and _peak(full) < _peak(auto) < _peak(off))
    # time gate: wall clocks are comparable only within ONE attempt — a
    # resumed sweep re-gauges the three gating cells' step time in THIS
    # process (parity/memory evidence stays from the persisted cells)
    gate_cells = [c for c in (off, full, auto) if c]
    attempts = {c.get("attempt") for c in gate_cells}
    retimed = {}
    if (len(attempts) > 1 or None in attempts) and len(gate_cells) > 1:
        for pol in ("off", "full", "auto"):
            if pol not in cells:
                continue
            with _cell_build(pol, cells[pol].get("auto_budget_mb")) \
                    as (_cfg, ex, fd):
                _timed(lambda i: ex.run("train", feed_dict=fd),
                       steps, warmup)
                from hetu_tpu.metrics import step_time_stats
                h = step_time_stats().get("train", {})
                retimed[pol] = round(h["min"] / 1e3, 3) \
                    if h.get("min") is not None else None
                del ex, fd

    def t_floor(pol):
        c = cells[pol]
        return retimed.get(pol) or c.get("step_time_min_ms") \
            or c["step_time_p50_ms"]

    # 'between' gates on the per-step FLOOR (exact histogram min):
    # contention on a shared box only ever inflates a step, so the min
    # is the noise-robust statistic (the PR 9 min-discipline).  The
    # band is DIRECTION-AGNOSTIC with 5% tolerance: on the MXU-bound
    # TPU leg recompute strictly costs (off < auto < full); on XLA-CPU
    # remat is measured time-NEUTRAL-TO-FASTER (less activation
    # materialization beats the replay on a cache-bound core — dots'
    # floor lands ~15% under off), so 'between' means auto inside the
    # off/full envelope within tolerance, raw floors recorded per cell
    auto_between_time = False
    if off and full and auto and t_floor("auto"):
        lo = min(t_floor("off"), t_floor("full"))
        hi = max(t_floor("off"), t_floor("full"))
        auto_between_time = lo * 0.95 <= t_floor("auto") <= hi * 1.05

    overlap = {"checks": {}, "detail": {"skipped": "overlap gate off"}}
    if overlap_gate:
        try:
            from tools import overlap_audit
        except ImportError:
            import overlap_audit
        overlap = overlap_audit.run_overlap_audit()
    overlap_ok = (not overlap_gate) or (
        bool(overlap["checks"]) and all(overlap["checks"].values()))

    errors = []
    if not parity:
        errors.append("losses NOT bitwise-equal across policies")
    if off and full and (reduction is None or reduction < 0.30):
        errors.append(f"remat=full peak reduction "
                      f"{None if reduction is None else round(reduction, 3)}"
                      f" < 0.30 vs off")
    if off and full and auto \
            and not (auto_between_peak and auto_between_time):
        errors.append(f"auto not between off and full "
                      f"(peak {auto_between_peak}, "
                      f"time {auto_between_time})")
    if not overlap_ok:
        errors.append(f"overlap audit failed: {overlap['checks']}")

    res = {
        "metric": "remat_full_peak_reduction_vs_off",
        "value": round(reduction, 4) if reduction is not None else None,
        "unit": "fraction",
        # 1.0 = every policy's losses bitwise-equal to off
        "vs_baseline": 1.0 if parity else 0.0,
        "extra": {
            "baseline_def": "value = 1 - full/off live-buffer peak "
                            "(live arrays + compiled-step temp, "
                            "memory_accounting); vs_baseline 1.0 = all "
                            "policies' losses bitwise-equal to off; "
                            "auto_between.time = auto's step-time floor "
                            "inside the off/full envelope +-5% (strict "
                            "ordering is the TPU claim; XLA-CPU remat "
                            "measures time-neutral-to-faster)",
            **_provenance(workload),
            "workload": workload,
            "cells": cells,
            "loss_bitwise_equal": parity,
            "full_peak_reduction": round(reduction, 4)
            if reduction is not None else None,
            "auto_between": {"peak": auto_between_peak,
                             "time": auto_between_time},
            **({"retimed_min_ms": retimed,
                "retime_note": "cells resumed across attempts: step-"
                               "time floors re-gauged in one process "
                               "for the between gate"} if retimed
               else {}),
            "budget": {"bytes": budget_bytes, "source": budget_source},
            "overlap_audit": {"mode": overlap.get("mode"),
                              "checks": overlap["checks"],
                              **overlap["detail"]},
            "device_kind": device_kind,
            "devices": n_dev,
            "backend": jax.default_backend(),
        },
    }
    if jax.default_backend() != "tpu":
        res["extra"]["device_note"] = (
            "TPU unavailable — measured on the CPU backend at tiny "
            "size; peaks are XLA buffer-assignment bytes (backend-"
            "agnostic program evidence), step times are CPU wall")
    if errors:
        res["error"] = "; ".join(errors)
    _write_remat_partial(path, {**res, **_provenance(workload)})
    return res


def bench_overhead(smoke=False, steps=None, write_artifact=None,
                   gate_only=False):
    """See :func:`_bench_overhead_impl` — this wrapper only guarantees
    the process-global telemetry toggles (span tracing, step timing)
    are restored even when a measurement raises: the bench runs
    in-process under pytest, and leaking an inverted HETU_TRACE state
    into later tests would silently distort them."""
    from hetu_tpu import metrics as ht_metrics, obs
    prev_trace = obs.enabled()
    prev_step_timing = ht_metrics.step_timing
    try:
        return _bench_overhead_impl(smoke, steps, write_artifact,
                                    gate_only)
    finally:
        obs.enable(prev_trace)
        ht_metrics.enable_step_timing(prev_step_timing)


def _bench_overhead_impl(smoke, steps, write_artifact, gate_only):
    """ISSUE 9 acceptance: the executor's dispatch-gap evidence.

    One tiny graph (8x8 matmul + SGD — the XLA program is ~free, so
    per-step wall is dispatch + host Python) measured five ways:

    * ``raw_jit_us`` — dispatching a bare ``jax.jit`` fn (the floor)
    * ``step_jit_us`` — dispatching the executor's own jitted step
      directly (the program's floor: forward+backward+update is ~4x the
      raw program's thunks, so this is what a ZERO-overhead executor
      would cost)
    * ``device_feed_us`` / ``numpy_feed_us`` — ``ex.run`` wall per step
    * ``pipelined_feed_us`` — ``ex.run_steps(..., sync=False)`` wall per
      step with numpy feeds placed on the background feed pipeline
    * ``dispatch_overhead_us`` — the executor's per-step host Python
      measured DIRECTLY: total loop wall minus time inside the jit call
      (on CPU the loop runs under synchronous dispatch so XLA's compute
      threads cannot steal the timing core mid-Python-section), minus
      the instrumentation's own calibrated cost.

    ``overhead_multiple_vs_raw_jit`` = (overhead_pair_raw_us +
    dispatch_overhead_us) / overhead_pair_raw_us — the executor's host
    tax expressed against a raw dispatch, each quantity the minimum
    over short interleaved rounds (the ≤ 2.0 acceptance gate;
    ``raw_jit_us`` additionally folds in the standalone raw rounds, so
    recompute the gate from the pair fields).  Earlier artifacts computed
    ``device_feed_us / raw_jit_us``, which conflated the step program's
    own compute/thunk floor (now recorded as ``step_jit_us``) with host
    overhead — once the Python residue is ~1x a raw dispatch, wall time
    is compute-dominated and the tax must be measured directly.

    CI gates (``--smoke``, tier-1): plan-cache hits >= steps-1 on a
    steady feed schema, and async (``sync=False``) vs sync stepping
    bitwise-equal losses + final weights — parity, not wall clock, so
    CI stays deterministic.  ``gate_only`` measures ONLY the gate
    quantities (raw-jit floor, interleaved overhead pairs, the ISSUE 10
    tracing-tax pairs) and skips the wall/step-jit/parity measurements
    — the tier-1 subprocess guard's budget-friendly mode (parity is
    covered in-process by ``test_overhead_bench_smoke``)."""
    import gc
    import jax
    if write_artifact is None:
        write_artifact = not smoke
    # synchronous CPU dispatch for the overhead attribution: under async
    # dispatch XLA-CPU's compute threads contend with the timing thread,
    # inflating the measured Python sections 2-3x.  MUST land before ANY
    # backend query — even jax.default_backend() initializes the client,
    # after which the flag is a silent no-op (a live non-CPU backend
    # ignores it; the flag is CPU-client-specific).
    try:
        jax.config.update("jax_cpu_enable_async_dispatch", False)
    except Exception:
        pass
    import hetu_tpu as ht
    from hetu_tpu import metrics as ht_metrics, obs
    from hetu_tpu.metrics import (reset_run_plan_counts, run_plan_counts)

    # the untraced gate must measure the HETU_TRACE=0 path even when the
    # surrounding process (a test, an inherited env) enabled telemetry —
    # the bench_overhead wrapper restores both toggles on every exit;
    # the traced rounds below flip tracing explicitly
    obs.enable(False)
    ht_metrics.enable_step_timing(False)

    n = steps or (200 if smoke else 2000)
    rounds = 2 if smoke else 5
    # smoke pays 6 short pair rounds (not 3): the min-of-rounds gate
    # quantities (incl. the ISSUE 10 tracing-tax pairs) want more draws
    # on a noisy CI box, and a round is ~5ms
    pair_rounds = 6 if smoke else 12
    # the gate pairs use SHORT windows (~50ms): shared-host contention
    # arrives in bursts, and a short window has far better odds of
    # landing entirely inside a quiet slice
    pair_n = min(n, 600)

    def build():
        x = ht.placeholder_op("x", shape=(8, 8))
        w = ht.init.zeros(shape=(8, 8), name="w")
        loss = ht.reduce_mean_op(ht.ops.matmul_op(x, w), [0, 1])
        opt = ht.optim.SGDOptimizer(0.1)
        ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0)
        return ex, x

    xv = np.ones((8, 8), np.float32)
    xd = jax.device_put(xv)

    def loop_us(fn, count=n):
        t0 = time.perf_counter()
        for i in range(count):
            fn(i)
        return (time.perf_counter() - t0) / count * 1e6

    def best(fn, count=n):
        return min(loop_us(fn, count) for _ in range(rounds))

    # raw jit floor (re-measured interleaved with the overhead rounds
    # below — this standalone min feeds the wall ratios)
    f = jax.jit(lambda a, b: (a @ b).mean())
    f(xd, xd).block_until_ready()
    raw = best(lambda i: f(xd, xd))

    # dispatch overhead, measured directly and FIRST (the wall
    # measurements below leave dead executors / lingering pool threads
    # behind — the gate pairs deserve the cleanest process state): a
    # fresh executor whose jit is wrapped BEFORE any plan binds it, so
    # total - in_jit is exactly the executor's per-step Python
    # (instrumentation cost calibrated out)
    reset_run_plan_counts()
    ex2, x2 = build()
    sub2 = ex2.subexecutors["train"]
    ex2.run("train", feed_dict={x2: xd})
    real_jit = sub2._jit
    sync_cpu = jax.default_backend() == "cpu"
    in_jit = [0.0]

    def timing_jit(*a):
        t0 = time.perf_counter()
        out = real_jit(*a)
        if not sync_cpu:    # async backends: compute must not leak into
            jax.block_until_ready(out)   # the Python sections
        in_jit[0] += time.perf_counter() - t0
        return out
    sub2._jit = timing_jit
    sub2._plan_cache = None     # plans must capture the wrapped jit
    fd2 = {x2: xd}

    def overhead_round(count):
        in_jit[0] = 0.0
        t0 = time.perf_counter()
        for i in range(count):
            ex2.run("train", feed_dict=fd2)
        return (time.perf_counter() - t0 - in_jit[0]) / count * 1e6
    # calibrate the instrumentation's own cost: the timing wrapper adds
    # a Python frame, *args packing of the 7 step inputs and two
    # perf_counter reads per call — measured around a no-op with the
    # SAME call shape, so subtracting it cannot eat real overhead
    def fake(*a):
        return None
    cal_in = [0.0]

    def cal_wrap(*a):
        t0 = time.perf_counter()
        fake(*a)
        cal_in[0] += time.perf_counter() - t0
        return None
    cal_args = (0, 1, 2, 3, 4, 5, 6)

    def cal(i):
        cal_wrap(*cal_args)
    wrap_cost = min(loop_us(cal, 20000) for _ in range(3))
    # the gate multiple takes the MINIMUM of each quantity over many
    # short interleaved rounds: shared-host contention only ever
    # INFLATES a round, so the min is the least-noise estimate of each
    # true value (standard microbenchmark practice).  Selecting a
    # minimum-RATIO pair instead would be floor-seeking (a noise-
    # inflated raw round makes any overhead look small); the raw pairs
    # are recorded in the artifact for transparency.
    overhead_round(pair_n)      # warm: plan + fast lane rebuilt
    pairs = []
    for _ in range(pair_rounds):
        r = loop_us(lambda i: f(xd, xd), pair_n)
        o = max(0.0, overhead_round(pair_n) - wrap_cost)
        pairs.append((r, o))
    raw_best = min(p[0] for p in pairs)
    overhead = min(p[1] for p in pairs)
    raw = min(raw, raw_best)
    multiple = (raw_best + overhead) / max(raw_best, 1e-9)

    # the tracing tax (ISSUE 10 acceptance): the SAME instrumented
    # executor and interleaved-min discipline, with the obs span tracer
    # toggled per round — a traced step pays the ring-buffer spans (step
    # span + plan-lookup + feeds/dispatch stamps) on every dispatch.
    # Gate: the added host Python must stay <= 25% of the UNTRACED
    # dispatch path (raw dispatch + untraced overhead).
    trace_pairs = []
    for _ in range(pair_rounds):
        u = max(0.0, overhead_round(pair_n) - wrap_cost)
        obs.enable(True)
        t = max(0.0, overhead_round(pair_n) - wrap_cost)
        obs.enable(False)
        trace_pairs.append((u, t))
    obs.clear_trace()
    untraced_best = min(p[0] for p in trace_pairs)
    traced_best = min(p[1] for p in trace_pairs)
    trace_overhead_us = max(0.0, traced_best - untraced_best)
    trace_overhead_pct = trace_overhead_us \
        / max(raw_best + untraced_best, 1e-9) * 100.0
    # really free the instrumented executor: sub2/real_jit still point
    # into it, and the compiled-step cache pins its builder — clear all
    # three so the wall measurements below run without the extra state
    from hetu_tpu.graph import step_cache
    gate_counters = run_plan_counts()
    del ex2, fd2, sub2, real_jit
    step_cache.clear()
    gc.collect()

    if gate_only:
        # tier-1 guard mode: the gate quantities only — no wall /
        # step-jit / parity measurements (those cost two more executor
        # builds and are covered in-process by the run-plan smoke test)
        res = {
            "metric": "executor_host_overhead_multiple",
            "value": round(multiple, 2),
            "unit": "x",
            "vs_baseline": round(2.0 / max(multiple, 1e-9), 3),
            "extra": {
                "gate_only": True,
                "backend": jax.default_backend(),
                "raw_jit_us": round(raw, 1),
                "dispatch_overhead_us": round(overhead, 1),
                "overhead_pair_raw_us": round(raw_best, 1),
                "overhead_pairs": [[round(r, 1), round(o, 1)]
                                   for r, o in pairs],
                "overhead_multiple_vs_raw_jit": round(multiple, 2),
                "traced_dispatch_overhead_us": round(traced_best, 1),
                "trace_overhead_us": round(trace_overhead_us, 1),
                "trace_overhead_pct": round(trace_overhead_pct, 1),
                "trace_gate_pct": 25.0,
                "trace_pairs": [[round(u, 1), round(t, 1)]
                                for u, t in trace_pairs],
                "plan_cache": {k: int(v)
                               for k, v in gate_counters.items()},
            },
        }
        if trace_overhead_pct > 25.0:
            res["error"] = (
                f"HETU_TRACE=1 span tracing costs "
                f"{trace_overhead_pct:.1f}% of the untraced dispatch "
                f"path (gate: 25%)")
        return res

    # the executor's own step program, dispatched bare (donated state
    # threaded back through the loop — the zero-overhead executor)
    ex, x = build()
    ex.run("train", feed_dict={x: xd})
    sub = ex.subexecutors["train"]
    feeds = {ex._k(x): xd}
    key, lrs = ex.master_key, sub._host_lrs(0)

    def bare_round(count):
        tp, sp = sub._pack_state()
        os_ = {k: ex.opt_states[op] for k, op in sub._opt_items}
        t0 = time.perf_counter()
        for i in range(count):
            outs, tp, upd, os_, _sd = sub._jit(tp, sp, os_, feeds, key,
                                               np.int32(i), lrs)
        dt = (time.perf_counter() - t0) / count * 1e6
        for n_, k_ in sub._writeback_pairs:
            ex.var_values[n_] = tp[k_]
        for k_, op in sub._opt_items:
            ex.opt_states[op] = os_[k_]
        return dt
    step_jit = min(bare_round(n) for _ in range(rounds))

    # executor wall: device-committed and numpy feeds
    fd_dev, fd_np = {x: xd}, {x: xv}
    reset_run_plan_counts()
    dev = best(lambda i: ex.run("train", feed_dict=fd_dev))
    counters_steady = run_plan_counts()
    npf = best(lambda i: ex.run("train", feed_dict=fd_np))

    # pipelined: numpy feeds placed ahead by the run_steps driver
    def pipelined_round(count):
        t0 = time.perf_counter()
        ex.run_steps(lambda i: {x: xv}, count, name="train", sync=False)
        return (time.perf_counter() - t0) / count * 1e6
    pipelined = min(pipelined_round(n) for _ in range(rounds))

    # -- CI gates: plan-cache reuse + async/sync bitwise parity ----------
    hits = counters_steady.get("plan_cache_hit", 0)
    plan_reuse_ok = hits >= n - 1

    def losses(sync, nsteps=12):
        exp, xp = build()
        out = []
        if sync:
            for i in range(nsteps):
                r = exp.run("train", feed_dict={xp: xv})
                out.append(np.asarray(r[0].jax(), np.float32))
        else:
            rs = exp.run_steps(lambda i: {xp: xv}, nsteps, name="train",
                               sync=False)
            out = [np.asarray(r[0].jax(), np.float32) for r in rs]
        final_w = {k: np.asarray(v) for k, v in
                   exp.return_tensor_values().items()}
        del exp
        gc.collect()
        return out, final_w
    s_loss, s_w = losses(sync=True)
    a_loss, a_w = losses(sync=False)
    async_bitwise = (
        [v.tobytes() for v in s_loss] == [v.tobytes() for v in a_loss]
        and set(s_w) == set(a_w)
        and all(s_w[k].tobytes() == a_w[k].tobytes() for k in s_w))

    workload = {"graph": "8x8 matmul + SGD", "steps_timed": n}
    artifact = {
        "metric": "executor_host_overhead",
        "unit": "us/step",
        "backend": jax.default_backend(),
        "raw_jit_us": round(raw, 1),
        "step_jit_us": round(step_jit, 1),
        "device_feed_us": round(dev, 1),
        "numpy_feed_us": round(npf, 1),
        "pipelined_feed_us": round(pipelined, 1),
        "dispatch_overhead_us": round(overhead, 1),
        "overhead_pair_raw_us": round(raw_best, 1),
        "overhead_pairs": [[round(r, 1), round(o, 1)] for r, o in pairs],
        "overhead_multiple_vs_raw_jit": round(multiple, 2),
        # ISSUE 10: per-step span-tracing tax (HETU_TRACE=1) against the
        # untraced dispatch path, min over interleaved toggled rounds
        "traced_dispatch_overhead_us": round(traced_best, 1),
        "trace_overhead_us": round(trace_overhead_us, 1),
        "trace_overhead_pct": round(trace_overhead_pct, 1),
        "trace_gate_pct": 25.0,
        "trace_pairs": [[round(u, 1), round(t, 1)]
                        for u, t in trace_pairs],
        "wall_multiple_vs_raw_jit": round(dev / max(raw, 1e-9), 1),
        "plan_cache": {k: int(v) for k, v in counters_steady.items()},
        "async_bitwise_equal": bool(async_bitwise),
        "schema_note": (
            "overhead_multiple_vs_raw_jit = (overhead_pair_raw_us + "
            "dispatch_overhead_us) / overhead_pair_raw_us: the "
            "executor's per-step host Python (loop wall minus in-jit "
            "time under synchronous dispatch) over a raw jit dispatch, "
            "each the MINIMUM over many short interleaved rounds "
            "(contention only inflates a round, so min is the least-"
            "noise estimate; the per-round pairs are recorded in "
            "overhead_pairs — a minimum-RATIO pick would be floor-"
            "seeking).  Pre-ISSUE-9 artifacts used "
            "device_feed_us / raw_jit_us, which folded the step "
            "program's own compute floor (step_jit_us) into "
            "'overhead'."),
        **_provenance(workload),
    }
    if write_artifact:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "artifacts", "host_overhead.json")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path + ".tmp", "w") as fh:
            json.dump(artifact, fh, indent=1, sort_keys=True)
        os.replace(path + ".tmp", path)
    res = {
        "metric": "executor_host_overhead_multiple",
        "value": round(multiple, 2),
        "unit": "x",
        # >1.0 = beats the <=2.0 host-tax acceptance gate
        "vs_baseline": round(2.0 / max(multiple, 1e-9), 3),
        "extra": {
            "baseline_def": "2.0 / overhead_multiple_vs_raw_jit — the "
                            "ISSUE 9 host-tax gate (>=1.0 passes)",
            **artifact,
        },
    }
    errors = []
    if not plan_reuse_ok:
        errors.append(f"plan cache missed on a steady schema: "
                      f"{counters_steady}")
    if not async_bitwise:
        errors.append("async (sync=False) stepping NOT bitwise-equal "
                      "to sync stepping")
    if trace_overhead_pct > 25.0:
        errors.append(
            f"HETU_TRACE=1 span tracing costs {trace_overhead_pct:.1f}% "
            f"of the untraced dispatch path (gate: 25%)")
    if errors:
        res["error"] = " | ".join(errors)
    return res


def bench_resnet18(batch_size=128, steps=20, warmup=3):
    import jax

    _, ex, fd = build_resnet18_graph(batch_size=batch_size)
    dt = _timed(lambda i: ex.run("train", feed_dict=fd), steps, warmup)
    base_ms, label = _torch_bench_baseline("resnet18",
                                           {"batch_size": batch_size})
    return {
        "metric": "resnet18_cifar10_step_time",
        "value": round(dt * 1e3, 2),
        "unit": "ms/step",
        # ms/step inverts the achieved/baseline ratio (>1 = faster)
        "vs_baseline": round(base_ms / (dt * 1e3), 3) if base_ms else 0.0,
        "extra": {"baseline_def": f"baseline step time / achieved "
                                  f"({label})" if base_ms else
                                  "unavailable: no committed same-workload "
                                  "torch baseline",
                  **_provenance({"batch_size": batch_size}),
                  "step_time_hist_ms": _step_percentiles(),
                  "compute_dtype": _compute_dtype() or "float32",
                  "backend": jax.default_backend()},
    }


def _rss_kb():
    """Current VmRSS in kB from /proc (0 where unavailable)."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
    except (OSError, ValueError, IndexError):
        pass
    return 0


class _RssWatch:
    """Sample VmRSS on a background thread; ``peak_delta_mb`` is the
    high-water mark above the RSS at entry — the bounded-save/load
    evidence (a full in-memory table copy would show up here)."""

    def __init__(self, interval_s=0.002):
        import threading
        self._iv = interval_s
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self.base_kb = self.peak_kb = 0

    def _run(self):
        while not self._stop.wait(self._iv):
            self.peak_kb = max(self.peak_kb, _rss_kb())

    def __enter__(self):
        self.base_kb = self.peak_kb = _rss_kb()
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=5)
        self.peak_kb = max(self.peak_kb, _rss_kb())
        return False

    @property
    def peak_delta_mb(self):
        return round(max(0, self.peak_kb - self.base_kb) / 1024.0, 1)


def bench_emb(smoke=False, steps=None, seed=0):
    """ISSUE 3 scale proof: the vectorized HET cache + batched sparse RPC
    path under a zipf(1.05) id stream over a 10^7x64 embedding table
    (``--smoke``: 10^5 rows, seconds on CPU — the CI trajectory config).

    Measures (1) lookup+update rows/s through the vectorized
    ``DistCacheTable`` vs the per-key reference model
    (``refcache.PerKeyCacheTable`` — the pre-PR cost shape) on the SAME
    trace prefix, (2) steady-state throughput + HET hit rate over the full
    stream, (3) redundant rows/bytes eliminated by ``np.unique`` dedup
    before the shard fanout on the raw
    (uncached) pull/push path, and (4) peak RSS above baseline during
    save/load of the full table — bounded far below one table copy.
    Host-side metric: everything runs on the host whatever the
    accelerator is."""
    import tempfile
    import shutil

    from hetu_tpu import metrics as hmetrics
    from hetu_tpu.ps.dist_store import DistributedStore, DistCacheTable
    from hetu_tpu.ps.refcache import PerKeyCacheTable

    if smoke:
        rows, width, batch, limit = 100_000, 64, 8192, 20_000
        n_steps = steps or 6
        warm_steps, base_steps, direct_steps = 2, 2, 2
    else:
        rows, width, batch, limit = 10_000_000, 64, 2048 * 26, 1_000_000
        n_steps = steps or 40
        warm_steps, base_steps, direct_steps = 4, 7, 3
    # bounds are in USE counts (HET contract) and the zipf head key shows
    # up thousands of times per batch, so they scale with the batch: the
    # head key stays fresh for a few batches (pull staleness) and syncs
    # its accumulated grad about every ~10 batches (push staleness)
    pull_bound, push_bound, lr = max(10, batch // 2), max(4, batch), 0.05
    # the warm phase always runs (cold misses + lazy imports must not
    # pollute the steady-state number), so a tiny --steps is bumped to
    # leave at least one timed step rather than going negative
    n_steps = max(n_steps, warm_steps + 1)
    base_steps = min(base_steps, n_steps - warm_steps)
    hmetrics.reset_cache_counts()

    # zipf(1.05) over a permuted id space (head ids scattered like a real
    # hash-bucketed vocab, not contiguous)
    rng = np.random.RandomState(seed)
    p = 1.0 / np.arange(1, rows + 1, dtype=np.float64) ** 1.05
    cdf = np.cumsum(p)
    cdf /= cdf[-1]
    perm = rng.permutation(rows).astype(np.int64)

    def draw(n):
        return perm[np.searchsorted(cdf, rng.random_sample(n))]

    def run_cache(cache, trace):
        """(lookup_s, update_s) replaying lookup+update over the trace.
        Wall-clock totals: GC pauses stay attributed to the side whose
        allocations caused them (the per-key model's per-row array churn
        is a real cost of that design), with a collect() up front so one
        side never pays the other's garbage."""
        import gc
        gc.collect()
        grng = np.random.RandomState(seed + 1)
        t_lk = t_up = 0.0
        for ids in trace:
            g = grng.standard_normal((ids.size, width)).astype(np.float32) \
                * 0.01
            t0 = time.perf_counter()
            cache.lookup(ids)
            t1 = time.perf_counter()
            cache.update(ids, g)
            t_lk += t1 - t0
            t_up += time.perf_counter() - t1
        return t_lk, t_up

    t0 = time.perf_counter()
    store = DistributedStore(0, 1)
    tid = store.init_table(rows, width, opt="sgd", lr=lr, init_scale=0.01)
    init_s = time.perf_counter() - t0
    ref_store = DistributedStore(0, 1)
    ref_tid = ref_store.init_table(rows, width, opt="sgd", lr=lr,
                                   init_scale=0.01)
    try:
        warm = [draw(batch) for _ in range(warm_steps)]
        prefix = [draw(batch) for _ in range(base_steps)]

        # pre-PR per-key baseline: same zipf trace, warmed cache (a cold
        # ratio only measures the shared store-pull cost of the misses)
        ref = PerKeyCacheTable(ref_store, ref_tid, limit=limit,
                               pull_bound=pull_bound,
                               push_bound=push_bound)
        run_cache(ref, warm)
        ref_s = sum(run_cache(ref, prefix))
        ref_rows_s = base_steps * batch * 2 / ref_s

        # vectorized cache: same warm + prefix (for the like-for-like
        # ratio), then the rest of the stream for steady-state throughput
        cache = DistCacheTable(store, tid, limit=limit,
                               pull_bound=pull_bound,
                               push_bound=push_bound)
        run_cache(cache, warm)      # warm-up: cold misses + lazy imports
        pre_lk, pre_up = run_cache(cache, prefix)
        vec_prefix_rows_s = base_steps * batch * 2 / (pre_lk + pre_up)
        rest = [draw(batch) for _ in
                range(max(0, n_steps - base_steps - warm_steps))]
        lk_s, up_s = run_cache(cache, rest)
        lk_s += pre_lk
        up_s += pre_up
        t0 = time.perf_counter()
        cache.flush()
        up_s += time.perf_counter() - t0
        total_rows = (n_steps - warm_steps) * batch
        vec_rows_s = total_rows * 2 / (lk_s + up_s)
        perf = cache.perf()

        # raw (uncached) pull/push on dup-heavy zipf batches: the wire-
        # dedup path
        hmetrics.reset_cache_counts()
        t0 = time.perf_counter()
        grng = np.random.RandomState(seed + 2)
        for _ in range(direct_steps):
            ids = draw(batch)
            store.pull(tid, ids)
            store.push(tid, ids,
                       grng.standard_normal((batch, width)).astype(
                           np.float32) * 0.01, lr)
        direct_s = time.perf_counter() - t0
        direct_rows_s = direct_steps * batch * 2 / direct_s
        dedup = hmetrics.cache_counts()
        wire_rows = 2 * direct_steps * batch
        saved_rows = (dedup.get("ps_dedup_pull_rows_saved", 0)
                      + dedup.get("ps_dedup_push_rows_saved", 0))

        # bounded-RSS streamed save/load of the full table
        tmp = tempfile.mkdtemp(prefix="hetu_emb_bench_")
        path = os.path.join(tmp, "table.bin")
        try:
            with _RssWatch() as w_save:
                t0 = time.perf_counter()
                store.save(tid, path)
                save_s = time.perf_counter() - t0
            with _RssWatch() as w_load:
                t0 = time.perf_counter()
                store.load(tid, path)
                load_s = time.perf_counter() - t0
            ckpt_mb = round(os.path.getsize(f"{path}.shard0") / 2**20, 1)
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    finally:
        store.close()
        ref_store.close()

    table_mb = round(rows * width * 4 / 2**20, 1)
    speedup = vec_prefix_rows_s / ref_rows_s if ref_rows_s else 0.0
    return {
        "metric": "emb_cache_rows_per_sec",
        "value": round(vec_rows_s, 1),
        "unit": "rows/s",
        # >=10x is the acceptance bar: vectorized vs per-key on the SAME
        # cold zipf trace prefix, same table, same bounds
        "vs_baseline": round(speedup, 2),
        "extra": {
            "baseline_def": "vectorized lookup+update rows/s ÷ per-key "
                            "reference (pre-PR DistCacheTable cost shape) "
                            "on the same warm zipf trace prefix",
            **_provenance({"rows": rows, "width": width, "batch": batch,
                           "steps": n_steps, "limit": limit,
                           "zipf_a": 1.05, "pull_bound": pull_bound,
                           "push_bound": push_bound, "smoke": bool(smoke)}),
            "init_s": round(init_s, 2),
            "lookup_rows_per_s": round(total_rows / lk_s, 1),
            "update_rows_per_s": round(total_rows / up_s, 1),
            "vec_prefix_rows_per_s": round(vec_prefix_rows_s, 1),
            "ref_rows_per_s": round(ref_rows_s, 1),
            "hit_rate": round(perf["hit_rate"], 4),
            "cache_stats": {k: int(v) for k, v in perf.items()
                            if k != "hit_rate"},
            "per_key_push_rpcs_ref": ref.stats["push_rpcs"],
            "batched_push_rpcs_vec": perf["push_rpcs"],
            "direct_rows_per_s": round(direct_rows_s, 1),
            "dedup": {
                "pull_rows_saved": int(dedup.get(
                    "ps_dedup_pull_rows_saved", 0)),
                "push_rows_saved": int(dedup.get(
                    "ps_dedup_push_rows_saved", 0)),
                "bytes_saved": int(
                    dedup.get("ps_dedup_pull_bytes_saved", 0)
                    + dedup.get("ps_dedup_push_bytes_saved", 0)),
                "rows_saved_frac": round(saved_rows / wire_rows, 4),
            },
            "table_mb": table_mb,
            "checkpoint_mb": ckpt_mb,
            "save": {"seconds": round(save_s, 2),
                     "peak_rss_delta_mb": w_save.peak_delta_mb},
            "load": {"seconds": round(load_s, 2),
                     "peak_rss_delta_mb": w_load.peak_delta_mb},
            "backend": "host",
        },
    }


def _host_main(args):
    """The host-side configs, in the CPU child (``JAX_PLATFORMS=cpu``)."""
    if args.config == "chaos":
        # host-side fault-injection smoke: the dist-store transport and
        # the recovery loop run on the host either way, so CPU is the
        # intended backend here — no fallback annotation
        print(json.dumps(bench_chaos(steps=args.steps or 8)))
        return
    if args.config == "failover":
        # host-side replication smoke: double-kill a replicated PS shard,
        # prove zero-restart bitwise-equal recovery (ISSUE 4 acceptance)
        print(json.dumps(bench_failover(steps=args.steps or 10,
                                        smoke=args.smoke)))
        return
    if args.config == "emb":
        # host-side sparse-path scale bench: numpy cache + native store,
        # no accelerator in the measured path
        print(json.dumps(bench_emb(smoke=args.smoke, steps=args.steps)))
        return
    if args.config == "zero":
        # CPU host-device mesh (the child env forces >=8 devices): the
        # memory/parity acceptance run of ISSUE 6
        print(json.dumps(bench_zero(
            dp=args.dp, steps=args.steps or 12,
            batch_size=args.batch_size or 8,
            seq_len=args.seq_len or 128)))
        return
    if args.config == "serve":
        # host-side serving acceptance: router + batcher + PS transport
        # run on the host; the jitted forward is tiny (ISSUE 7)
        print(json.dumps(bench_serve(smoke=args.smoke,
                                     n_requests=args.steps)))
        return
    if args.config == "decode":
        # host-side decode-serving acceptance: continuous batching vs
        # request-level scheduling over the same jitted step (ISSUE 16)
        print(json.dumps(bench_decode(smoke=args.smoke,
                                      n_requests=args.steps)))
        return
    if args.config == "fleet":
        # host-side fleet-tier acceptance: replica-set admission,
        # SLO autoscaling and chaos replica-kill rescue (ISSUE 17)
        print(json.dumps(bench_fleet(smoke=args.smoke,
                                     n_requests=args.steps)))
        return
    if args.config == "partition":
        # host-side partition-tolerance acceptance: chaos partition DSL,
        # fencing epochs, 2-cell geo-replicated serving (ISSUE 8)
        print(json.dumps(bench_partition(steps=args.steps or 10,
                                         smoke=args.smoke)))
        return
    if args.config == "overhead":
        # host-side dispatch-gap microbench: the XLA program is ~free by
        # construction, so any backend measures the same host tax
        print(json.dumps(bench_overhead(smoke=args.smoke,
                                        steps=args.steps)))
        return
    if args.config == "trace":
        # host-side telemetry demo: chaos failover + serving + feed
        # pipeline captured in one Chrome trace (ISSUE 10)
        print(json.dumps(bench_trace(steps=args.steps or 5,
                                     smoke=args.smoke,
                                     write_artifact=True)))
        return
    if args.config == "elastic":
        # CPU host-device mesh (the parent's child env forces >=8
        # devices): the elastic resize acceptance run of ISSUE 12 —
        # chaos step-clock kill, shrink to dp-1, rejoin, grow back
        print(json.dumps(bench_elastic(steps=args.steps or 10,
                                       dp=args.dp, smoke=args.smoke)))
        return
    if args.config == "remat":
        # CPU host-device mesh (>=8 devices so the dp=4 zero=3 overlap
        # audit gates inside the same child): the ISSUE 13 policy sweep
        # with partial-runway checkpointed cells
        print(json.dumps(bench_remat(steps=args.steps or 8)))
        return

    raise ValueError(f"{args.config} is not a host-side config")


def _accel_main(args):
    """The accelerator configs, in THIS process, on the chip or not at
    all: a non-TPU backend is refused before anything is measured, and a
    run that was supposed to be on a kernel but fell off it — or served
    the wdl tables from the numpy store — fails instead of printing the
    reason into ``extra``."""
    import jax
    from hetu_tpu.graph.executor import configure_compile_cache
    if jax.default_backend() != "tpu":
        print(f"bench.py --config {args.config} measures the TPU; jax's "
              f"backend here is {jax.default_backend()!r} — refusing to "
              f"print a {args.config} metric from it", file=sys.stderr)
        return 2
    configure_compile_cache()
    steps = args.steps if args.steps is not None else DEFAULT_STEPS
    if args.config == "bert":
        res = bench_bert(batch_size=args.batch_size,
                         seq_len=args.seq_len or 512, steps=steps,
                         remat=args.remat)
        ex = res["extra"]
        if ex["workload"]["seq_len"] >= 256 and (
                not ex["flash_in_hlo"] or ex["flash_fallbacks"]):
            raise RuntimeError(
                f"bert measured off the flash kernel: flash_in_hlo="
                f"{ex['flash_in_hlo']}, fallbacks {ex['flash_fallbacks']}")
    elif args.config == "wdl":
        # --emb-policy routes the CTR embedding through the NEW vectorized
        # cache path (direct PS store / vectorized LRU / vectorized LFU);
        # --wdl-embed keeps selecting the native C++ cache or dense
        policy = args.wdl_embed
        if args.emb_policy:
            policy = {"direct": "ps", "lru": "vlru",
                      "lfu": "vlfu"}[args.emb_policy]
        res = bench_wdl(batch_size=args.batch_size or 2048, steps=steps,
                        policy=policy, emb_device=args.emb_device or "host")
        ex = res["extra"]
        if ex["ps_store"] != "native":
            raise RuntimeError(f"wdl served its tables from the "
                               f"{ex['ps_store']} store, not the native one")
        if ex["cache_mode"] == "device" and ex["emb_pallas_fallback_reason"]:
            raise RuntimeError(
                f"wdl --emb-device device measured off the Pallas kernels: "
                f"{ex['emb_pallas_fallback_reason']}")
    elif args.config == "moe":
        res = bench_moe(batch_tokens=args.batch_size or 8192, steps=steps)
    elif args.config == "attn":
        res = bench_attention(steps=steps)
        bad = {k: c for k, c in res["extra"]["cells"].items()
               if "skipped" not in c
               and (c.get("error") or not c.get("flash_in_hlo")
                    or c.get("flash_fallbacks"))}
        if bad:
            raise RuntimeError(f"attn cells off the flash kernel: {bad}")
    else:
        res = bench_resnet18(batch_size=args.batch_size or 128, steps=steps)
    dev = jax.devices()[0]
    res["extra"]["device"] = {"platform": dev.platform,
                              "kind": dev.device_kind,
                              "count": len(jax.devices())}
    print(json.dumps(res))
    return 0


def _error_result(args, msg):
    names = {"chaos": ("chaos_recovery_ms", "ms"),
             "failover": ("failover_recovery_ms", "ms"),
             "partition": ("partition_recovery_ms", "ms"),
             "emb": ("emb_cache_rows_per_sec", "rows/s"),
             "serve": ("serve_qps", "requests/s"),
             "decode": ("decode_tokens_per_s", "tokens/s"),
             "fleet": ("fleet_spike_interactive_p99_ms", "ms"),
             "zero": ("zero_opt_state_shrink_vs_replicated", "x"),
             "overhead": ("executor_host_overhead_multiple", "x"),
             "trace": ("trace_step_events", "events"),
             "remat": ("remat_full_peak_reduction_vs_off", "fraction"),
             "elastic": ("elastic_resize_recovery_ms", "ms")}
    metric, unit = names[args.config]
    return {"metric": metric, "value": 0.0, "unit": unit,
            "vs_baseline": 0.0, "error": msg[-2000:]}


def _parse_child_json(stdout):
    """Last valid {"metric": ...} JSON line from a child's stdout, or None."""
    for line in reversed(stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                parsed = json.loads(line)
            except json.JSONDecodeError:
                continue
            if "metric" in parsed:
                return parsed
    return None


def _host_parent_main(args):
    """Run a host-side config in its single child.  The child gets
    ``JAX_PLATFORMS=cpu`` (host code is what these configs measure, on
    any machine); this parent never imports jax."""
    env = dict(os.environ, **{CHILD_ENV_FLAG: "1", "JAX_PLATFORMS": "cpu"})
    if args.config in ("zero", "elastic", "remat"):
        # these acceptance runs measure a dp>=4 CPU mesh (remat's
        # overlap-audit gate compiles the dp=4 zero=3 config): the
        # device count flag must land before the child's backend init
        flags = env.get("XLA_FLAGS", "")
        if "host_platform_device_count" not in flags:
            n = max(8, args.dp)
            env["XLA_FLAGS"] = (
                f"{flags} "
                f"--xla_force_host_platform_device_count={n}").strip()
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)] + sys.argv[1:],
            env=env, capture_output=True, text=True,
            timeout=CHILD_TIMEOUT_S)
        parsed = _parse_child_json(proc.stdout)
        if parsed is None:
            parsed = _error_result(
                args, f"host-side bench rc={proc.returncode} "
                      f"stderr: {proc.stderr[-1500:]}")
    except subprocess.TimeoutExpired:
        parsed = _error_result(args, "host-side bench exceeded wall clock")
    print(json.dumps(parsed))


def bench_wdl(batch_size=2048, steps=20, warmup=3, policy="lru",
              emb_device="host"):
    """BASELINE config 4: Wide&Deep CTR with the HET embedding cache —
    rows pulled through the bounded-staleness cache around each jitted
    step (reference run_hetu.py:121-126 cache flags).

    ``emb_device="device"`` (ISSUE 11) routes the embedding through the
    DEVICE-RESIDENT cache slab (``--emb-device device`` requires a
    vectorized-cache policy: vlru/vlfu): hit rows are gathered on-device
    by slot index, only miss rows cross the host boundary (overlapped
    with the forward on the feed-pipeline thread), and the grad
    segment-sum runs on device.  The artifact then ALSO measures the
    host-mode cache on the SAME warm zipf trace and records
    ``vs_host_cache`` — the acceptance comparison — plus the
    ``emb_pallas_fallback_reason`` counters (empty = the Pallas kernels
    were the measured path; ``bench.py --config wdl --emb-device device``
    fails on anything else) and ``ps_store``, the store that served."""
    import jax
    from hetu_tpu import metrics as hmetrics
    from hetu_tpu.ps import store_kind

    if emb_device not in ("host", "device"):
        raise ValueError(f"emb_device must be host|device, got "
                         f"{emb_device!r}")
    if emb_device == "device":
        if policy not in ("vlru", "vlfu"):
            # the device slab belongs to DistCacheTable; map the native
            # cache names onto their vectorized twins
            policy = {"lru": "vlru", "lfu": "vlfu"}.get(policy)
            if policy is None:
                raise ValueError(
                    "--emb-device device needs a DistCacheTable policy "
                    "(--emb-policy lru|lfu)")
        policy = policy + "_dev"

    ctr = _load_example_models("ctr")
    # Zipf-skewed ids: the HET cache's hit pattern (and therefore the
    # measured step time) is only meaningful under Criteo-like skew
    d_all, s_all, y_all = ctr.synthetic_criteo_skewed(8 * batch_size,
                                                      vocab=100000)
    batches = [(d_all[i * batch_size:(i + 1) * batch_size],
                s_all[i * batch_size:(i + 1) * batch_size],
                y_all[i * batch_size:(i + 1) * batch_size])
               for i in range(8)]

    def _measure(pol):
        _, ex, _fd0, (dense, sparse, y_) = build_wdl_graph(
            batch_size=batch_size, policy=pol)

        def run_step(i):
            dv, sv, yv = batches[i % len(batches)]
            return ex.run("train",
                          feed_dict={dense: dv, sparse: sv, y_: yv})

        dt = _timed(run_step, steps, warmup)   # resets step times itself
        hist = _step_percentiles()
        perf = {}
        for node in ex.subexecutors["train"].ps_nodes:
            c = getattr(node, "cache", None)
            if c is not None and hasattr(c, "perf"):
                perf = c.perf() or {}
            if c is not None and hasattr(c, "flush"):
                # flush BEFORE the executor is dropped: a pending-grad
                # flush deferred to GC-time __del__ runs with the store
                # graph half-collected (pre-existing teardown hazard)
                c.flush()
        return dt, perf, hist

    hmetrics.reset_emb_pallas_fallbacks()
    dt, cache_perf, step_hist = _measure(policy)
    fallbacks = dict(hmetrics.emb_pallas_fallback_counts())
    host_dt = host_hist = None
    h2d_rows = None
    if emb_device == "device" and cache_perf.get("lookups"):
        # the backend-independent evidence: rows crossing the host
        # boundary per step.  Device mode H2D-transfers only the PULLED
        # (miss/refresh) rows; host mode materializes + transfers every
        # looked-up occurrence, every step
        n_steps = steps + warmup
        h2d_rows = {
            "device_miss_rows_per_step":
                round(cache_perf["fetches"] / n_steps, 1),
            "host_all_rows_per_step":
                round(cache_perf["lookups"] / n_steps, 1)}
    if emb_device == "device":
        # the acceptance twin: the HOST-mode cache on the same trace
        host_dt, _, host_hist = _measure(policy[:-4])
    base, label = _torch_bench_baseline("wdl", {"batch_size": batch_size})
    # NB: the torch baseline is a PLAIN device embedding — it implements
    # no bounded-staleness cache.  vs_baseline is only a same-semantics
    # number when policy="dense" (plain vs plain); the cache policies are
    # the richer-functionality headline (BASELINE config 4) and measure
    # the cache machinery's cost on ONE process, where it cannot pay off
    same_semantics = policy == "dense"
    return {
        # the metric NAME carries the mode: a plain-embedding run is not
        # the cache metric and must not key-collide with it downstream
        "metric": "wdl_criteo_dense_samples_per_sec" if same_semantics
        else "wdl_criteo_cache_samples_per_sec",
        "value": round(batch_size / dt, 1),
        "unit": "samples/s",
        "vs_baseline": round(batch_size / dt / base, 3)
        if base and same_semantics else 0.0,
        "extra": {"baseline_def": f"achieved / baseline samples/s "
                                  f"({label}, plain-embedding both sides)"
                  if base and same_semantics else
                  ("n/a: HET-cache path vs torch plain embedding is not "
                   "same-semantics — run --wdl-embed dense for the "
                   "comparable number" if base else
                   "unavailable: no committed same-workload torch "
                   "baseline"),
                  **_provenance({"batch_size": batch_size,
                                 "embed": policy}),
                  "cache": policy,
                  "cache_mode": emb_device,
                  "ps_store": store_kind(),
                  "cache_hit_rate": round(cache_perf["hit_rate"], 4)
                  if "hit_rate" in cache_perf else None,
                  "emb_pallas_fallback_reason": fallbacks,
                  **({"host_step_time_ms": round(host_dt * 1e3, 2),
                      # wall ratio (includes the device path's one-time
                      # per-bucket fill compiles inside the timed
                      # window) ...
                      "vs_host_cache": round(host_dt / dt, 3),
                      # ... and the steady-state ratio: p50-vs-p50 from
                      # the step-time histograms, which is the number a
                      # long-running job converges to
                      "vs_host_cache_p50": _p50_ratio(host_hist,
                                                      step_hist),
                      "host_step_time_hist_ms": host_hist}
                     if host_dt is not None else {}),
                  **({"h2d_rows_per_step": h2d_rows}
                     if h2d_rows is not None else {}),
                  "step_time_ms": round(dt * 1e3, 2),
                  "step_time_hist_ms": step_hist,
                  "backend": jax.default_backend()},
    }


def _p50_ratio(host_hist, dev_hist):
    """host p50 / measured p50 from two ``_step_percentiles`` snapshots
    (>1 = the measured mode is faster at steady state)."""
    try:
        return round(host_hist["train"]["p50_ms"]
                     / dev_hist["train"]["p50_ms"], 3)
    except (KeyError, TypeError, ZeroDivisionError):
        return None


def bench_attention(steps=10, warmup=2):
    """Attention microbench: the {bias, no-bias} × {aligned, ragged} ×
    {cp=1, cp>1} sweep behind the universal flash fast path (additive
    bias in the ring-flash kernel + ragged-length bucketing).  Each cell
    times a jitted fwd+bwd step and records whether the Pallas custom-
    call is in ITS compiled HLO plus any ``flash_fallback_reason``
    counters its trace recorded — the evidence `flash_in_hlo: true`
    claims need, per cell rather than per flagship run."""
    import jax
    import jax.numpy as jnp
    import hetu_tpu as ht
    from hetu_tpu import metrics as hmetrics
    from hetu_tpu.ops.attention import dispatch_sdpa, dispatch_sdpa_bias
    from hetu_tpu.parallel.ring_attention import ring_attention

    # ragged is even so the cp>1 cells can shard S over the ring; it is
    # NOT 128-divisible (420 % 128 == 36), which is the whole point
    B, H, D, aligned, ragged = 4, 8, 64, 512, 420
    rng = np.random.RandomState(0)
    n_dev = len(jax.devices())
    cp_sizes = [1] + ([2] if n_dev >= 2 else [])

    def _cell(s, with_bias, cp):
        q, k, v = (jnp.asarray(rng.randn(B, H, s, D).astype(np.float32)
                               * 0.3) for _ in range(3))
        bias = jnp.asarray(rng.randn(1, H, s, s).astype(np.float32) * 0.5) \
            if with_bias else None
        mesh = ht.make_mesh({"cp": cp}, jax.devices()[:cp]) if cp > 1 \
            else None

        def attn(q, k, v, b):
            if cp > 1:
                return ring_attention(q, k, v, mesh, bias=b)
            if b is not None:
                return dispatch_sdpa_bias(q, k, v, b)
            return dispatch_sdpa(q, k, v)

        if with_bias:
            def loss(q, k, v, b):
                return (attn(q, k, v, b) ** 2).sum()
            step = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
            args = (q, k, v, bias)
        else:
            def loss(q, k, v):
                return (attn(q, k, v, None) ** 2).sum()
            step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            args = (q, k, v)

        # trace+compile ONCE, bracketed by the fallback counters so the
        # cell's reasons are ITS OWN (dispatch records at trace time);
        # the same AOT executable serves both the HLO inspection and the
        # timed loop (calling `step` again would recompile from a cold
        # jit cache — doubling XLA compile time across the sweep)
        hmetrics.reset_flash_fallbacks()
        compiled = step.lower(*args).compile()
        fallbacks = hmetrics.flash_fallback_counts()
        hlo = compiled.as_text()
        flash = "tpu_custom_call" in hlo

        dt = _timed(lambda i: compiled(*args), steps, warmup)
        return {"step_ms": round(dt * 1e3, 3),
                "tokens_per_sec": round(B * s / dt, 1),
                "flash_in_hlo": flash,
                "flash_fallbacks": fallbacks or None}

    cells = {}
    for cp in cp_sizes:
        for kind, s in (("aligned", aligned), ("ragged", ragged)):
            for with_bias in (False, True):
                key = (f"{'bias' if with_bias else 'nobias'}"
                       f"_{kind}_cp{cp}")
                try:
                    cells[key] = _cell(s, with_bias, cp)
                except Exception as e:     # a broken cell must not kill
                    cells[key] = {"error": repr(e)[:300]}  # the sweep
    if n_dev < 2:
        cells["cp2"] = {"skipped": f"needs >=2 devices, have {n_dev}"}

    headline = cells.get("bias_ragged_cp1", {})
    ideal = cells.get("nobias_aligned_cp1", {})
    value = headline.get("tokens_per_sec", 0.0)
    ideal_tps = ideal.get("tokens_per_sec", 0.0)
    return {
        "metric": "attn_flash_sweep_tokens_per_sec",
        "value": value,
        "unit": "tokens/s",
        # how close the newly-unlocked cell (bias+ragged) runs to the
        # ideal dense aligned fast path on the same chip
        "vs_baseline": round(value / ideal_tps, 3) if ideal_tps else 0.0,
        "extra": {
            "baseline_def": "bias+ragged cp=1 tokens/s ÷ nobias+aligned "
                            "cp=1 tokens/s (same run, same chip)",
            **_provenance({"batch_size": B, "heads": H, "head_dim": D,
                           "seq_aligned": aligned, "seq_ragged": ragged}),
            "cells": cells,
            "backend": jax.default_backend(),
            "devices": n_dev,
        },
    }


def bench_moe(batch_tokens=8192, steps=20, warmup=3):
    """BASELINE config 5: MoE transformer expert-parallel step (GShard
    top-2 gate, 16 experts; on one chip the a2a is local, on an 'ep'
    mesh XLA shards the expert dim)."""
    import jax

    dims, ex, fd = build_moe_graph(batch_tokens=batch_tokens)
    experts = dims["experts"]
    dt = _timed(lambda i: ex.run("train", feed_dict=fd), steps, warmup)
    base, label = _torch_bench_baseline("moe", {"tokens": batch_tokens})
    return {
        "metric": "moe_ep_tokens_per_sec",
        "value": round(batch_tokens / dt, 1),
        "unit": "tokens/s",
        "vs_baseline": round(batch_tokens / dt / base, 3) if base else 0.0,
        "extra": {"baseline_def": f"achieved / baseline tokens/s "
                                  f"({label})" if base else
                                  "unavailable: no committed same-workload "
                                  "torch baseline",
                  **_provenance({"tokens": batch_tokens}),
                  "experts": experts,
                  "step_time_ms": round(dt * 1e3, 2),
                  "step_time_hist_ms": _step_percentiles(),
                  "compute_dtype": _compute_dtype() or "float32",
                  "backend": jax.default_backend()},
    }


def bench_chaos(steps=8, kill_step=3):
    """Fault-injection smoke (ISSUE 2 CI satellite): a short PS training
    loop under a FIXED chaos schedule — the rank-1 PS server is killed
    after step ``kill_step`` — measuring detection+recovery wall time and
    restart count, with loss parity against the uninterrupted run as the
    correctness gate.  Host-side metric: the dist-store transport and the
    retry/resume path run on the host whatever the accelerator is."""
    import glob as _glob
    import shutil
    import tempfile

    import jax
    import hetu_tpu as ht
    from hetu_tpu import chaos as chaos_mod
    from hetu_tpu.graph.executor import Executor
    from hetu_tpu.metrics import fault_counts, reset_faults
    from hetu_tpu.ps.dist_store import DistributedStore

    def store_pair(ports):
        endpoints = [("127.0.0.1", p) for p in ports]
        stores = [DistributedStore(r, 2, endpoints, port=ports[r],
                                   rpc_timeout=5.0, rpc_retries=2,
                                   connect_timeout=2.0) for r in range(2)]
        table = np.random.RandomState(42).normal(
            0, 0.01, (64, 8)).astype(np.float32)
        tid = None
        for r, s in enumerate(stores):
            tid = s.init_table(64, 8, opt="sgd", lr=0.1, init_scale=0.0)
            s.local.set_data(tid, table[np.arange(32) * 2 + r])
        return stores[0], stores[1], tid

    def build(store, tid, **kw):
        rng = np.random.RandomState(1)
        ids = ht.placeholder_op("ids")
        y_ = ht.placeholder_op("y")
        h = ht.ps_embedding_lookup_op((store, tid), ids, width=8)
        w = ht.Variable("w", value=rng.randn(8, 2).astype(np.float32) * .3)
        loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
            ht.matmul_op(h, w), y_), [0])
        ex = ht.Executor(
            {"train": [loss, ht.optim.AdamOptimizer(0.01).minimize(loss)]},
            seed=0, install_signal_handlers=False, **kw)
        return ex, ids, y_

    def save_shard1(s1, tid, save_dir, step):
        # in a real deployment every rank's executor saves its own PS
        # shard; this single-process smoke mirrors rank 1's shard save
        ck = os.path.join(save_dir, f"ckpt-{step:08d}")
        if os.path.isdir(ck):
            s1.save(tid, os.path.join(ck, "ps0.bin"))

    rng = np.random.RandomState(0)
    feeds = [(rng.randint(0, 64, 32),
              np.eye(2, dtype=np.float32)[rng.randint(0, 2, 32)])
             for _ in range(steps)]

    # the smoke measures ITS OWN fixed schedule: an inherited HETU_CHAOS
    # must not inject into the baseline (the stores' install_from_env
    # would resurrect it) or contaminate the clean-run counters
    env_chaos = os.environ.pop("HETU_CHAOS", None)
    chaos_mod.uninstall()

    # uninterrupted baseline (also proves a clean run records NO faults)
    reset_faults()
    s0, s1, tid = store_pair(_free_ports(2))
    ex, ids, y_ = build(s0, tid)
    base = [float(ex.run("train", feed_dict={ids: f[0], y_: f[1]}
                         )[0].asnumpy()) for f in feeds]
    s0.close()
    s1.close()
    clean_counters = fault_counts()

    save_dir = tempfile.mkdtemp(prefix="hetu_chaos_bench_")
    schedule = f"11:kill:ps@rank1:step{kill_step}"
    reset_faults()
    prev = chaos_mod.install(chaos_mod.ChaosInjector.from_spec(schedule))
    ports = _free_ports(2)
    s0, s1, tid = store_pair(ports)
    recovery_ms, restarts = 0.0, 0
    losses = [None] * steps
    t_run0 = time.monotonic()
    try:
        ex, ids, y_ = build(s0, tid, auto_save_dir=save_dir,
                            auto_save_every=1)
        step = 0
        while step < steps:
            try:
                losses[step] = float(
                    ex.run("train", feed_dict={ids: feeds[step][0],
                                               y_: feeds[step][1]}
                           )[0].asnumpy())
                step += 1
                save_shard1(s1, tid, save_dir, step)
            except RuntimeError:
                t_fail = time.monotonic()
                restarts += 1
                if restarts > 3:
                    raise
                cands = [c for c in sorted(
                    _glob.glob(os.path.join(save_dir, "ckpt-*")),
                    reverse=True) if Executor._checkpoint_complete(c)]
                if not cands:
                    raise RuntimeError(
                        "chaos recovery: no complete checkpoint to "
                        "restore from (kill landed before the first "
                        "auto-save?)")
                newest = cands[0]
                endpoints = [("127.0.0.1", p) for p in ports]
                s1 = DistributedStore(1, 2, endpoints, port=ports[1],
                                      rpc_timeout=5.0, rpc_retries=2,
                                      connect_timeout=2.0)
                s1.init_table(64, 8, opt="sgd", lr=0.1, init_scale=0.0)
                s1.load(tid, os.path.join(newest, "ps0.bin"))
                ex, ids, y_ = build(s0, tid, auto_save_dir=save_dir,
                                    auto_save_every=1)
                step = ex.resume(save_dir)
                if step is None:
                    raise RuntimeError(
                        "chaos recovery: resume found no loadable "
                        "checkpoint under " + save_dir)
                # recovery-time clock stops at the END of the first post-
                # resume step: detect → restore → prove training moves
                losses[step] = float(
                    ex.run("train", feed_dict={ids: feeds[step][0],
                                               y_: feeds[step][1]}
                           )[0].asnumpy())
                step += 1
                save_shard1(s1, tid, save_dir, step)
                recovery_ms += (time.monotonic() - t_fail) * 1e3
        parity = losses == base
        counters = fault_counts()
    finally:
        chaos_mod.install(prev)
        if env_chaos is not None:
            os.environ["HETU_CHAOS"] = env_chaos
        for s in (s0, s1):
            try:
                s.close()
            except Exception:
                pass
        shutil.rmtree(save_dir, ignore_errors=True)
    total_ms = (time.monotonic() - t_run0) * 1e3
    return {
        "metric": "chaos_recovery_ms",
        "value": round(recovery_ms, 1),
        "unit": "ms",
        "vs_baseline": 1.0 if parity and restarts else 0.0,
        "extra": {
            "baseline_def": "1.0 iff the chaos run's loss trajectory is "
                            "exactly equal to the uninterrupted run's "
                            "(and at least one injected failure + "
                            "recovery actually happened)",
            **_provenance({"steps": steps, "kill_step": kill_step,
                           "schedule": schedule}),
            "restarts": restarts,
            "total_wall_ms": round(total_ms, 1),
            "loss_parity": parity,
            "fault_counters": counters,
            "clean_run_counters": clean_counters,
            "backend": jax.default_backend(),
        },
    }


def bench_failover(steps=10, kill_step=3, smoke=True):
    """ISSUE 4 acceptance: live PS shard replication under chaos.  A
    3-rank replicated (``replication=2``) store cluster trains while the
    schedule kills the shard-1 PRIMARY after step ``kill_step``; the
    shard router promotes the live backup inside the failing RPC — ZERO
    supervisor restarts, ZERO lost steps, per-step losses bitwise equal
    to the uninterrupted run.  A standby rank then relaunches, the
    executor's re-replication tick re-attaches it (checksum-verified by
    tools/ps_fsck), and a SECOND kill of the promoted ex-backup proves
    the restored redundancy is real.  ``recovery_ms`` is the total wall
    time of the steps that absorbed a failover — the bound to beat is
    one rpc_timeout + heartbeat deadline (vs PR 2's kill-everything
    recovery measured in checkpoint-resume minutes).  Host-side metric:
    transport + failover run on the host whatever the accelerator is."""

    import jax
    import hetu_tpu as ht
    from hetu_tpu import chaos as chaos_mod
    from hetu_tpu.analysis.protocol import PROTO, check_conformance
    from hetu_tpu.metrics import fault_counts, reset_faults
    from hetu_tpu.ps.dist_store import DistributedStore
    from tools.ps_fsck import fsck

    world, rows, width = 3, 48, 8
    rpc_timeout, hb_deadline_ms = 5.0, 1500.0
    second_kill = steps - 3
    assert second_kill > kill_step + 2, "need room to re-replicate"

    def make_store(rank, ports, standby=False):
        return DistributedStore(
            rank, world, [("127.0.0.1", p) for p in ports],
            port=ports[rank], rpc_timeout=rpc_timeout, rpc_retries=2,
            connect_timeout=2.0, replication=2, standby=standby)

    def make_cluster(ports):
        stores = [make_store(r, ports) for r in range(world)]
        tid = None
        for s in stores:
            tid = s.init_table(rows, width, opt="sgd", lr=0.1,
                               init_scale=0.0)
        table = np.random.RandomState(42).normal(
            0, 0.01, (rows, width)).astype(np.float32)
        # through the REPLICATED set_data path: primaries and backups
        # start bitwise identical
        stores[0].set_data(tid, table)
        return stores, tid

    def build(store, tid):
        rng = np.random.RandomState(1)
        ids = ht.placeholder_op("ids")
        y_ = ht.placeholder_op("y")
        h = ht.ps_embedding_lookup_op((store, tid), ids, width=width)
        w = ht.Variable("w", value=rng.randn(width, 2).astype(np.float32)
                        * .3)
        loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
            ht.matmul_op(h, w), y_), [0])
        ex = ht.Executor(
            {"train": [loss, ht.optim.AdamOptimizer(0.01).minimize(loss)]},
            seed=0, install_signal_handlers=False)
        return ex, ids, y_

    rng = np.random.RandomState(0)
    feeds = [(rng.randint(0, rows, 32),
              np.eye(2, dtype=np.float32)[rng.randint(0, 2, 32)])
             for _ in range(steps)]

    # an inherited HETU_CHAOS must not contaminate the baseline, and the
    # re-replication tick is this bench's own knob
    env_chaos = os.environ.pop("HETU_CHAOS", None)
    env_tick = os.environ.pop("HETU_PS_REREPLICATE_EVERY", None)
    chaos_mod.uninstall()

    # --- uninterrupted replicated baseline: ZERO fault counters ----------
    reset_faults()
    stores, tid = make_cluster(_free_ports(world))
    try:
        ex, ids, y_ = build(stores[0], tid)
        base = [float(ex.run("train", feed_dict={ids: f[0], y_: f[1]}
                             )[0].asnumpy()) for f in feeds]
    finally:
        for s in stores:
            s.close()
    clean_counters = fault_counts()

    # --- chaos run: kill the shard-1 primary TWICE -----------------------
    schedule = (f"11:kill:primary@shard1:step{kill_step},"
                f"kill:primary@shard1:step{second_kill}")
    reset_faults()
    os.environ["HETU_PS_REREPLICATE_EVERY"] = "1"
    prev = chaos_mod.install(chaos_mod.ChaosInjector.from_spec(schedule))
    ports = _free_ports(world)
    stores, tid = make_cluster(ports)
    standby = None
    losses = [None] * steps
    step_ms = [0.0] * steps
    failover_steps, fsck_report = [], None
    t_run0 = time.monotonic()
    # the chaos run is also a RECORDED protocol trace: every promote /
    # fence / apply transition is replayed against the replication
    # model's transition relation (ISSUE 20) and conformance gates ok
    PROTO.start()
    try:
        ex, ids, y_ = build(stores[0], tid)
        for step in range(steps):
            before = fault_counts().get("ps_failover_promoted", 0)
            t0 = time.monotonic()
            # NO try/except, NO resume: a killed primary is transparent
            losses[step] = float(
                ex.run("train", feed_dict={ids: feeds[step][0],
                                           y_: feeds[step][1]}
                       )[0].asnumpy())
            step_ms[step] = (time.monotonic() - t0) * 1e3
            if fault_counts().get("ps_failover_promoted", 0) > before:
                failover_steps.append(step)
            if step == kill_step + 1 and standby is None:
                # ops relaunch a standby at the dead rank's endpoint; the
                # executor's next re-replication tick re-attaches it
                standby = make_store(1, ports, standby=True)
            if step == second_kill - 2:
                # the kill fires inside step second_kill-1's post-step
                # hook (step_counter is 1-based), so this is the last
                # step with the whole cluster up:
                # redundancy must be BACK before the second kill
                fsck_report = fsck([("127.0.0.1", p) for p in ports],
                                   n_tables=1, replication=2)
        parity = losses == base
        counters = fault_counts()
    finally:
        proto_events = PROTO.stop()   # before teardown closes fire
        chaos_mod.install(prev)
        if env_chaos is not None:
            os.environ["HETU_CHAOS"] = env_chaos
        os.environ.pop("HETU_PS_REREPLICATE_EVERY", None)
        if env_tick is not None:
            os.environ["HETU_PS_REREPLICATE_EVERY"] = env_tick
        for s in stores + ([standby] if standby else []):
            try:
                s.close()
            except Exception:
                pass
    total_ms = (time.monotonic() - t_run0) * 1e3
    recovery_ms = sum(step_ms[s] for s in failover_steps)
    bound_ms = rpc_timeout * 1e3 + hb_deadline_ms
    proto_conf = check_conformance(proto_events)
    ok = (parity and len(failover_steps) == 2 and recovery_ms < bound_ms
          and bool(fsck_report and fsck_report["ok"])
          and proto_conf["ok"]
          and not clean_counters)
    return {
        "metric": "failover_recovery_ms",
        "value": round(recovery_ms, 1),
        "unit": "ms",
        "vs_baseline": 1.0 if ok else 0.0,
        "extra": {
            "baseline_def": "1.0 iff the double-kill run's loss "
                            "trajectory is bitwise equal to the "
                            "uninterrupted replicated run's, both kills "
                            "were absorbed by failover (restarts=0, no "
                            "resume), recovery stayed under one "
                            "rpc_timeout + heartbeat deadline, fsck "
                            "verified the re-replicated backup, the "
                            "recorded protocol trace conformed to the "
                            "replication model, and the clean run "
                            "recorded zero fault counters",
            **_provenance({"steps": steps, "kill_step": kill_step,
                           "second_kill_step": second_kill,
                           "world": world, "replication": 2,
                           "schedule": schedule, "smoke": bool(smoke)}),
            "restarts": 0,
            "resumes": 0,
            "failover_steps": failover_steps,
            "recovery_bound_ms": bound_ms,
            "step_ms": [round(m, 1) for m in step_ms],
            "total_wall_ms": round(total_ms, 1),
            "loss_parity": parity,
            "redundancy_restored": bool(fsck_report
                                        and fsck_report["ok"]),
            "fsck_mismatches": (fsck_report or {}).get("mismatches"),
            "protocol_conformance": proto_conf,
            "fault_counters": counters,
            "clean_run_counters": clean_counters,
            "backend": jax.default_backend(),
        },
    }


def bench_serve(smoke=True, n_requests=None, seed=0):
    """ISSUE 7 acceptance: online inference serving under chaos.  A
    wdl-style CTR model (26 zipf(1.05)-skewed categorical fields through
    a PS embedding, dense tower, sigmoid click prob) is served by the
    new ``hetu_tpu.serving`` stack — InferenceExecutor (compile-once per
    batch bucket) + ServingRouter (bounded queue, adaptive micro-batch)
    — with the embedding pulled READ-ONLY through ``DistCacheTable``
    from a 3-rank ``replication=2`` DistributedStore.  The same seeded
    request stream runs twice: clean, and with a chaos schedule that
    kills the shard-1 PRIMARY mid-load (``kill:primary@shard1:req<n>``,
    fired on the router's admission clock).  The kill must be absorbed
    by client-transparent failover: restarts=0, every request answered,
    responses BITWISE equal to the clean run, p99 degradation bounded by
    one rpc_timeout + heartbeat deadline.  Host-side metric: routing,
    batching and the PS transport run on the host whatever the
    accelerator is."""

    import jax
    import hetu_tpu as ht
    from hetu_tpu import chaos as chaos_mod
    from hetu_tpu.metrics import (fault_counts, reset_faults,
                                  reset_serve_counts, serve_counts,
                                  serve_latency_stats)
    from hetu_tpu.ps.dist_store import DistCacheTable, DistributedStore
    from hetu_tpu.serving import InferenceExecutor, ServingRouter

    n_requests = int(n_requests or (300 if smoke else 2000))
    world, dim, n_fields = 3, 8, 26
    vocab = 26 * 80 if smoke else 26 * 2000       # per-field 80 / 2000
    rpc_timeout, hb_deadline_ms = 2.0, 1500.0
    # max_wait_ms is the partial-wave ship deadline AND the packing-
    # determinism margin (see the wave comment below): full waves ship
    # on count, so only the two trailing partial waves ever pay it —
    # 150ms is ~150x the ~1ms wave-submission window a stall would have
    # to outlast to split a wave, without drowning p99 in deadline time
    max_batch, max_wait_ms = 64, 150.0
    kill_req = n_requests // 2

    def make_cluster(ports):
        stores = [DistributedStore(
            r, world, [("127.0.0.1", p) for p in ports], port=ports[r],
            rpc_timeout=rpc_timeout, rpc_retries=2, connect_timeout=2.0,
            replication=2) for r in range(world)]
        tid = None
        for s in stores:
            tid = s.init_table(vocab, dim, opt="sgd", lr=0.1,
                               init_scale=0.0)
        table = np.random.RandomState(42).normal(
            0, 0.01, (vocab, dim)).astype(np.float32)
        stores[0].set_data(tid, table)   # replicated path: primaries and
        return stores, tid               # backups bitwise identical

    def build_serving(store, tid):
        """wdl-style serving graph over a READ-ONLY embedding cache."""
        dense = ht.placeholder_op("dense")
        sparse = ht.placeholder_op("sparse", dtype=np.int64)
        cache = DistCacheTable(store, tid, limit=max(vocab // 2, 256),
                               policy="lru", read_only=True)
        emb = ht.ps_embedding_lookup_op(cache, sparse, width=dim)
        flat = ht.array_reshape_op(emb, (-1, n_fields * dim))
        x = ht.concat_op(flat, dense, axis=1)
        h = x
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
        return iex, dense, sparse, cache

    # the seeded stream: zipf(1.05)-skewed ids per field + dense features,
    # chopped into deterministic waves so both runs pack IDENTICAL
    # batches (bitwise parity requires each request to run in the same
    # bucket).  Determinism mechanics: a FULL wave (== max_batch) ships
    # the moment the count is reached, independent of timing; the two
    # trailing partial waves ship at the head-of-line deadline, which is
    # set generously below so a scheduler stall mid-submission cannot
    # split a wave into differently-bucketed halves between the runs.
    rng = np.random.RandomState(seed)
    per_field = vocab // n_fields
    ranks = np.arange(per_field, dtype=np.float64)
    p = 1.0 / (ranks + 1.0) ** 1.05
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

    env_chaos = os.environ.pop("HETU_CHAOS", None)
    chaos_mod.uninstall()

    def run_stream(tag):
        """One full serving run over the stream; returns (responses,
        per-request latency ms, per-wave wall ms, wave serve_failover
        deltas, rejections)."""
        reset_serve_counts()
        ports = _free_ports(world)
        stores, tid = make_cluster(ports)
        responses = [None] * n_requests
        lat_ms = [0.0] * n_requests
        wave_ms, wave_failover = [], []
        try:
            iex, dense, sparse, cache = build_serving(stores[0], tid)
            router = ServingRouter(iex, max_batch=max_batch,
                                   max_wait_ms=max_wait_ms,
                                   queue_limit=n_requests + 8)
            try:
                i = 0
                for wsize in waves:
                    t0 = time.monotonic()
                    before = serve_counts().get("serve_failovers", 0)
                    futs = []
                    for j in range(i, i + wsize):
                        t_sub = time.monotonic()
                        fut = router.submit({dense: dense_all[j],
                                             sparse: sparse_all[j]})
                        fut.add_done_callback(
                            lambda f, j=j, t=t_sub: lat_ms.__setitem__(
                                j, (time.monotonic() - t) * 1e3))
                        futs.append((j, fut))
                    for j, fut in futs:
                        responses[j] = np.asarray(fut.result(timeout=60)[0])
                    wave_ms.append((time.monotonic() - t0) * 1e3)
                    wave_failover.append(
                        serve_counts().get("serve_failovers", 0) - before)
                    i += wsize
            finally:
                router.close()
            return (responses, lat_ms, wave_ms, wave_failover,
                    serve_counts(), serve_latency_stats())
        finally:
            for s in stores:
                try:
                    s.close()
                except Exception:
                    pass

    try:
        # --- clean run: zero fault counters, the parity oracle -----------
        reset_faults()
        base_resp, base_lat, base_wave_ms, _, base_serve, base_hist = \
            run_stream("clean")
        clean_counters = fault_counts()

        # --- chaos run: shard-1 primary killed mid-load -------------------
        schedule = f"11:kill:primary@shard1:req{kill_req}"
        reset_faults()
        prev = chaos_mod.install(
            chaos_mod.ChaosInjector.from_spec(schedule))
        t0 = time.monotonic()
        try:
            resp, lat, wave_ms, wave_failover, serve_ctrs, chaos_hist = \
                run_stream("chaos")
        finally:
            chaos_mod.install(prev)
        total_ms = (time.monotonic() - t0) * 1e3
        counters = fault_counts()
    finally:
        if env_chaos is not None:
            os.environ["HETU_CHAOS"] = env_chaos

    answered = sum(r is not None for r in resp)
    bitwise = all(r is not None and b is not None and np.array_equal(r, b)
                  for r, b in zip(resp, base_resp))
    recovery_ms = sum(m for m, d in zip(wave_ms, wave_failover) if d)
    bound_ms = rpc_timeout * 1e3 + hb_deadline_ms
    qps = n_requests / (sum(wave_ms) / 1e3)
    base_qps = n_requests / (sum(base_wave_ms) / 1e3)

    def pct(xs, q):
        return float(np.percentile(np.asarray(xs), q))

    ok = (bitwise and answered == n_requests
          and counters.get("chaos_kill_primary", 0) == 1
          and counters.get("ps_failover_promoted", 0) >= 1
          and serve_ctrs.get("serve_failovers", 0) >= 1
          and serve_ctrs.get("serve_rejections", 0) == 0
          and recovery_ms < bound_ms
          and not clean_counters)
    return {
        "metric": "serve_qps",
        "value": round(base_qps, 1),
        "unit": "requests/s",
        "vs_baseline": 1.0 if ok else 0.0,
        "extra": {
            "baseline_def": "1.0 iff the chaos run's responses are "
                            "bitwise equal to the clean run's over the "
                            "same zipf(1.05) stream, every request was "
                            "answered with zero restarts and zero "
                            "rejections, exactly one primary kill was "
                            "absorbed by >=1 client-transparent failover "
                            "mid-serve, the failover wave stayed under "
                            "one rpc_timeout + heartbeat deadline, and "
                            "the clean run recorded zero fault counters",
            **_provenance({"n_requests": n_requests, "vocab": vocab,
                           "dim": dim, "world": world, "replication": 2,
                           "zipf_a": 1.05, "max_batch": max_batch,
                           "max_wait_ms": max_wait_ms,
                           "buckets": [8, 16, 32, 64],
                           "schedule": schedule, "smoke": bool(smoke)}),
            "p50_ms": round(pct(base_lat, 50), 2),
            "p99_ms": round(pct(base_lat, 99), 2),
            "qps": round(base_qps, 1),
            "chaos_p50_ms": round(pct(lat, 50), 2),
            "chaos_p99_ms": round(pct(lat, 99), 2),
            "chaos_qps": round(qps, 1),
            # queue-wait / batch-latency distributions from the obs
            # registry's log-bucketed histograms (ISSUE 10): the
            # router's contribution to tail latency vs the device
            # call's, separable per run — means alone could not tell a
            # p99 spike from a shifted mean
            "latency_hist_ms": _hist_ms(base_hist),
            "chaos_latency_hist_ms": _hist_ms(chaos_hist),
            "rejections": int(serve_ctrs.get("serve_rejections", 0)),
            "failover_recovery_ms": round(recovery_ms, 1),
            "recovery_bound_ms": bound_ms,
            "restarts": 0,
            "all_answered": answered == n_requests,
            "responses_bitwise_equal": bitwise,
            "serve_counters": serve_ctrs,
            "clean_serve_counters": base_serve,
            "fault_counters": counters,
            "clean_run_counters": clean_counters,
            "total_wall_ms": round(total_ms, 1),
            "backend": jax.default_backend(),
        },
    }


def bench_fleet(smoke=True, n_requests=None, seed=0, write_artifact=None):
    """ISSUE 17 acceptance: the fleet serving tier under a flash crowd.

    A seeded diurnal request stream (calm -> 10x spike -> cool, classes
    mixed 70/20/10 interactive/batch/best_effort) hits a ``FrontDoor``
    that starts at ONE replica of a 3-layer dense serving graph.  The
    ``SLOAutoscaler`` is polled on the ADMISSION clock (once per
    submission wave); the spike must breach its load watermark and the
    recorded scale-out must grow aggregate bounded-queue capacity so
    that the interactive p99 SLO holds and interactive traffic is NEVER
    rejected, while best_effort is shed EXPLICITLY (counted structured
    ``shed:best_effort`` rejections, zero unbounded queues).  Replica
    spin-up must be a ``step_cache_serve_hit``, not a compile.  The same
    stream then reruns with ``kill:replica@1:req<n>`` — the scaled-out
    replica killed mid-spike on the door's admission clock — which must
    be absorbed by ejection + queue rescue: restarts=0, every admitted
    request answered, and responses bitwise equal to the clean run on
    the requests admitted in both.  Host-side metric: admission,
    dispatch, health and scaling logic run on the host whatever the
    accelerator is; one CPU core drains both runs, so the scale-out win
    is CAPACITY (sheds stop, queues stay bounded), not raw throughput.
    """
    import jax
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

    n_requests = int(n_requests or (420 if smoke else 1400))
    calm_n = max(20, n_requests // 10)
    spike_n = n_requests - 2 * calm_n           # ~10x the calm volume
    wave = 20                                   # autoscaler poll cadence
    in_dim, hid, out_dim = 64, 256, 8
    max_batch, queue_limit = 8, 120
    slo_ms = 500.0 if smoke else 700.0
    # the kill lands mid-spike, after the first post-wave poll has
    # certainly scaled out (grow_grace=1): replica 1 exists by then
    kill_req = calm_n + 3 * wave + wave // 2

    # the serving graph: 3 dense layers — enough real device work per
    # batch that an unpaced submission burst outruns the drain on one
    # core, which is what makes the flash crowd a crowd
    rng = np.random.RandomState(seed)
    x = ht.placeholder_op("x_fleet_bench")
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

    # the seeded stream: request features + class mix, identical across
    # the clean and chaos runs (admission DECISIONS may differ — load
    # dynamics diverge after the kill — but request i's payload and
    # class never do, which is what makes per-request parity meaningful)
    feats = rng.randn(n_requests, in_dim).astype(np.float32)
    class_draw = rng.rand(n_requests)
    klasses = np.where(class_draw < 0.70, "interactive",
                       np.where(class_draw < 0.90, "batch",
                                "best_effort"))

    env_chaos = os.environ.pop("HETU_CHAOS", None)
    chaos_mod.uninstall()

    def run_stream(tag, schedule=None):
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
            # capacity and the load factor collapses — exactly the
            # degradation story (shed cheap traffic first, then grow)
            door = FrontDoor(mk, 1, shed_at={"interactive": None,
                                             "batch": 0.45,
                                             "best_effort": 0.1},
                             wedge_timeout_ms=2000.0)
            scaler = SLOAutoscaler(door, p99_target_ms=slo_ms,
                                   min_replicas=1, max_replicas=3,
                                   grow_grace=1, shrink_grace=4,
                                   grow_load=0.15, shrink_load=0.02)
            responses = [None] * n_requests
            lat_ms = [None] * n_requests
            rejections = {}             # (klass, reason) -> count
            max_pending = 0
            futs = []

            def submit(i):
                t0 = time.monotonic()
                try:
                    fut = door.submit({x: feats[i]},
                                      klass=str(klasses[i]))
                except ServeRejected as e:
                    key = f"{klasses[i]}:{e.reason}"
                    rejections[key] = rejections.get(key, 0) + 1
                    return
                fut.add_done_callback(
                    lambda f, i=i, t=t0: lat_ms.__setitem__(
                        i, (time.monotonic() - t) * 1e3))
                futs.append((i, fut))

            def poll():
                nonlocal max_pending
                scaler.poll()
                for rep in door.stats()["replicas"]:
                    max_pending = max(max_pending, rep["pending"])

            t_run = time.monotonic()
            for i in range(calm_n):                     # calm
                submit(i)
                if (i + 1) % wave == 0:
                    poll()
                time.sleep(0.0005)
            for i in range(calm_n, calm_n + spike_n):   # 10x flash crowd
                submit(i)
                if (i + 1) % wave == 0:
                    poll()
            for i in range(calm_n + spike_n, n_requests):   # cool-down
                submit(i)
                if (i + 1) % wave == 0:
                    poll()
                time.sleep(0.0005)
            failures = 0
            for i, fut in futs:
                try:
                    responses[i] = np.asarray(fut.result(timeout=60)[0])
                except Exception:   # noqa: BLE001 — counted, gated to 0
                    failures += 1
            poll()
            wall_ms = (time.monotonic() - t_run) * 1e3
            door.close()
            return {
                "tag": tag,
                "responses": responses,
                "lat_ms": lat_ms,
                "rejections": rejections,
                "reason_counts": dict(serve_rejection_counts()),
                "fleet_counts": dict(fleet_counts()),
                "fault_counts": dict(fault_counts()),
                "events": list(scaler.events),
                "failures": failures,
                "max_pending": max_pending,
                "wall_ms": wall_ms,
                "serve_hit_delta":
                    step_cache_counts().get("step_cache_serve_hit", 0)
                    - sc0,
                "compile_delta":
                    serve_counts().get("serve_bucket_compiles", 0) - co0,
            }
        finally:
            if schedule is not None:
                chaos_mod.install(prev)

    try:
        clean = run_stream("clean")
        schedule = f"13:kill:replica@1:req{kill_req}"
        chaos = run_stream("chaos", schedule=schedule)
    finally:
        if env_chaos is not None:
            os.environ["HETU_CHAOS"] = env_chaos

    def p99_interactive(run):
        lats = [l for i, l in enumerate(run["lat_ms"])
                if l is not None and klasses[i] == "interactive"]
        return float(np.percentile(np.asarray(lats), 99)) if lats \
            else 0.0

    def admitted_ids(run):
        return {i for i, r in enumerate(run["responses"])
                if r is not None}

    both = admitted_ids(clean) & admitted_ids(chaos)
    bitwise = all(np.array_equal(clean["responses"][i],
                                 chaos["responses"][i]) for i in both)
    clean_p99 = p99_interactive(clean)
    chaos_p99 = p99_interactive(chaos)

    def interactive_rejections(run):
        return sum(n for key, n in run["rejections"].items()
                   if key.startswith("interactive:"))

    # spin-up proof: across both runs exactly ONE real bucket build (the
    # very first replica of the clean run); every later replica — scaled
    # out or run-2 rebuilt — resolved through the serve step cache
    spinup_cheap = (clean["compile_delta"] == 1
                    and chaos["compile_delta"] == 0
                    and clean["serve_hit_delta"]
                    >= len(clean["events"])
                    and chaos["serve_hit_delta"] >= 1)

    scaled_out = (any(e["kind"] == "scale_out" for e in clean["events"])
                  and any(e["kind"] == "scale_out"
                          for e in chaos["events"]))
    sheds_counted = (clean["reason_counts"].get("shed:best_effort", 0)
                     > 0
                    and chaos["reason_counts"].get("shed:best_effort", 0)
                     > 0)
    # bounded queues: per-replica pending never exceeded the queue
    # limit (chaos run may briefly double a survivor's depth when it
    # ADOPTS the dead replica's rescued queue — that is the documented
    # bounded exception, not unbounded growth)
    bounded = (clean["max_pending"] <= queue_limit
               and chaos["max_pending"] <= 2 * queue_limit)
    kill_absorbed = (
        chaos["fault_counts"].get("chaos_kill_replica", 0) == 1
        and chaos["fleet_counts"].get("fleet_replica_ejected", 0) >= 1
        and chaos["failures"] == 0
        and chaos["fleet_counts"].get("fleet_request_failures", 0) == 0)

    ok = (clean_p99 <= slo_ms and chaos_p99 <= slo_ms
          and scaled_out and sheds_counted and bounded
          and interactive_rejections(clean) == 0
          and interactive_rejections(chaos) == 0
          and clean["failures"] == 0
          and kill_absorbed and bitwise and spinup_cheap
          and not clean["fault_counts"])

    result = {
        "metric": "fleet_spike_interactive_p99_ms",
        "value": round(clean_p99, 2),
        "unit": "ms",
        "vs_baseline": 1.0 if ok else 0.0,
        "extra": {
            "baseline_def": "1.0 iff the interactive p99 held the SLO "
                            "through the 10x spike in BOTH runs via a "
                            "recorded scale-out (replica spin-up proven "
                            "a step_cache_serve_hit, zero new "
                            "compiles), best_effort was shed as counted "
                            "structured rejections with zero "
                            "interactive rejections and bounded "
                            "per-replica queues, and the mid-spike "
                            "replica kill was absorbed by ejection + "
                            "queue rescue with restarts=0, zero failed "
                            "futures, and responses bitwise equal to "
                            "the clean run on every request admitted "
                            "in both",
            **_provenance({"n_requests": n_requests, "calm_n": calm_n,
                           "spike_n": spike_n, "wave": wave,
                           "dims": dims, "max_batch": max_batch,
                           "queue_limit": queue_limit,
                           "slo_ms": slo_ms, "schedule": schedule,
                           "class_mix": "70/20/10",
                           "smoke": bool(smoke)}),
            "slo": {"target_ms": slo_ms, "held": bool(ok or (
                        clean_p99 <= slo_ms and chaos_p99 <= slo_ms)),
                    "clean_p99_ms": round(clean_p99, 2),
                    "chaos_p99_ms": round(chaos_p99, 2)},
            "scaling": {"events": chaos["events"],
                        "clean_events": clean["events"],
                        "replicas_hw": chaos["fleet_counts"].get(
                            "fleet_replicas_hw", 1)},
            "rejections": chaos["reason_counts"],
            "clean_rejections": clean["reason_counts"],
            "per_class_rejections": {"clean": clean["rejections"],
                                     "chaos": chaos["rejections"]},
            "interactive_rejections": interactive_rejections(chaos),
            "bounded_queues": {"max_pending_clean": clean["max_pending"],
                               "max_pending_chaos": chaos["max_pending"],
                               "queue_limit": queue_limit,
                               "bounded": bounded},
            "spin_up": {"cheap": spinup_cheap,
                        "clean_compiles": clean["compile_delta"],
                        "chaos_compiles": chaos["compile_delta"],
                        "clean_serve_hits": clean["serve_hit_delta"],
                        "chaos_serve_hits": chaos["serve_hit_delta"]},
            "chaos": {"schedule": schedule, "kill_req": kill_req,
                      "restarts": 0,
                      "responses_bitwise_equal": bool(bitwise),
                      "answered_both": len(both),
                      "failed_futures": chaos["failures"],
                      "fleet_counters": chaos["fleet_counts"],
                      "fault_counters": chaos["fault_counts"]},
            "clean_fleet_counters": clean["fleet_counts"],
            "clean_run_fault_counters": clean["fault_counts"],
            "wall_ms": {"clean": round(clean["wall_ms"], 1),
                        "chaos": round(chaos["wall_ms"], 1)},
            "backend": jax.default_backend(),
        },
    }
    if write_artifact is None:
        # unlike the perf benches, the SMOKE run IS the committed
        # artifact: every gate is a robustness invariant, not a margin
        write_artifact = True
    if write_artifact:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "artifacts", "fleet_bench.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
    return result


def bench_decode(smoke=True, n_requests=None, seed=0, write_artifact=None):
    """ISSUE 16 acceptance: continuous-batching autoregressive decode.

    A zipf-sized seeded request stream (prompt lengths and generation
    budgets both skewed) decodes greedily through the
    ``hetu_tpu.serving.decode`` plane — incremental per-layer KV caches
    bucketed on the serving ladder, one jitted step per
    ``(batch_bucket, len_bucket)`` pair — under two scheduling policies:

    * **continuous** (the tentpole): sequences join/leave the in-flight
      batch per token, freed KV slots recycled immediately;
    * **request-level** (the baseline): joins only into an EMPTY engine,
      so the whole batch drains at the pace of its slowest sequence.

    ISSUE 18 (v2) adds prompt-INGESTION legs on top:

    * **token-by-token** (the PR 16 ingestion baseline): the same
      continuous stream with no chunked entry — every prompt token is
      one engine step;
    * the continuous leg now runs CHUNKED prefill (``max_chunk=8``):
      prompts ingest in ``ceil(P/chunk)`` mixed-batch steps through the
      q_len=C graph entry, pure-prefill steps skip the logits D2H;
    * **prefix**: a popularity-skewed pool stream decoded twice through
      chunked engines — cold (reference) and with a
      :class:`PrefixKVStore`, whose hits seat repeat prompts with their
      KV rows pre-filled and skip prefill outright;
    * **ttft**: time-to-first-token measured directly on engines (join
      -> first emitted token, min over reps) at controlled prompt
      lengths, chunked vs token-by-token.

    ISSUE 19 (v3) adds the RECOVERY legs: a 2-replica decode FrontDoor
    under a ``kill:replica@0:tok<n>`` chaos fault on the engine's own
    token clock — every in-flight stream migrated to the survivor and
    bitwise-equal to the unkilled reference with zero failures and zero
    restarts — plus a zero-survivor kill that must fail loudly
    (``recovery_exhausted`` + partial tokens), never hang.

    Gates: ALL policy/ingestion legs produce BITWISE-identical token
    streams (scheduling and ingestion mode must not change results);
    continuous beats request-level on tokens/s with a no-worse p99
    time-to-token, and chunked tokens/s is no worse than token-by-token;
    chunked TTFT beats token-by-token at EVERY measured prompt length;
    the prefix run's streams match its cold reference with hits > 0 and
    prefill rows saved; every stream records exactly one ``ttft``
    histogram observation; the counter proof of the compile-once steady
    state holds over the chunked stream (real compiles + serve-cache
    reuses == dispatch-plan misses == distinct bucket keys — ``(batch,
    len)`` pairs and ``(batch, chunk, len)`` triples — every other step
    a ``plan_cache_hit``); zero rejections.  A further leg times one
    incremental decode step against the naive full re-prefill forward at
    every measured cache length — the O(1)-vs-O(len) per-token claim.
    Host-side scheduling dominates the measured deltas, so CPU is a
    faithful backend for the policy comparison (the jitted step is the
    same program either way)."""
    import jax
    from hetu_tpu import metrics as ht_metrics
    from hetu_tpu.models import (GPT2Config, gpt2_decode_chunked_graph,
                                 gpt2_decode_graph)
    from hetu_tpu.models.gpt2 import gpt2_lm_graph
    from hetu_tpu.profiler import HetuProfiler
    from hetu_tpu.serving import (DecodeEngine, DecodeRouter,
                                  InferenceExecutor, PrefixKVStore)
    from hetu_tpu.serving.decode import _DecodeRequest

    if write_artifact is None:
        write_artifact = not smoke
    n_requests = int(n_requests or (16 if smoke else 100))
    max_slots = 4 if smoke else 8
    max_len = 32 if smoke else 64
    gen_cap = 6 if smoke else 12
    cfg = GPT2Config.tiny(n_positions=2 * max_len, batch_size=1,
                          seq_len=max_len)

    # the seeded zipf stream: most prompts short, a heavy tail, capped so
    # prompt + generation always fits max_len
    rng = np.random.RandomState(seed)
    plens = np.minimum(rng.zipf(1.5, n_requests), max_len // 2)
    news = np.minimum(rng.zipf(1.6, n_requests) + 1, gen_cap)
    prompts = [rng.randint(1, cfg.vocab_size, int(l)).astype(np.int32)
               for l in plens]

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
        lat_ms = []          # time-to-token over EVERY emitted token
        rq = reqs if reqs is not None else list(zip(prompts, news))
        with DecodeRouter(eng, queue_limit=len(rq) + 8,
                          max_wait_ms=5.0,
                          continuous=continuous) as router:
            t0 = time.monotonic()
            streams = []
            for p, nw in rq:
                t_sub = time.monotonic()
                s = router.submit(p, max_new_tokens=int(nw))
                for i in range(int(nw)):
                    s.token(i).add_done_callback(
                        lambda f, t=t_sub: lat_ms.append(
                            (time.monotonic() - t) * 1e3)
                        if not f.cancelled() and f.exception() is None
                        else None)
                streams.append(s)
            tokens = [s.result(timeout=600) for s in streams]
            wall_s = time.monotonic() - t0
        lat = HetuProfiler.latency_stats().get("decode_latency_us", {})
        return {
            "tokens": tokens,
            "lat_ms": lat_ms,
            "wall_s": wall_s,
            "tps": sum(len(t) for t in tokens) / wall_s,
            "decode": ht_metrics.decode_counts(),
            "serve": ht_metrics.serve_counts(),
            "run_plan": ht_metrics.run_plan_counts(),
            "step_cache": ht_metrics.step_cache_counts(),
            "prefix_ct": ht_metrics.prefix_cache_counts(),
            "ttft_hist": lat.get("ttft", {}),
            "ladder": (len(eng.batch_ladder), len(eng.len_ladder),
                       len(eng.chunk_ladder)),
        }

    # Warmup passes populate the process-wide serve cache so the
    # measured passes time SCHEDULING, not first-touch XLA compiles (the
    # steady state a long-lived server actually runs in; the measured
    # passes' counters still prove the compile-once claim — their builds
    # all land as step_cache_serve_hits).  The legs then run in
    # INTERLEAVED rounds with best-of on tokens/s: shared-host
    # contention and allocator warm-up drift only ever SLOW a pass and
    # hit whichever leg is running, so sequential legs would fold
    # process age into the policy comparison; interleaving gives every
    # leg the same noise exposure and the fastest pass is the
    # least-noise estimate of each (counters and token streams are
    # deterministic across passes — any pass serves as the proof).
    legs = {"tok": (True, False),    # PR 16 token-by-token ingestion
            "cont": (True, True),    # chunked continuous (the tentpole)
            "reql": (False, False)}  # request-level baseline
    for continuous, chunked in legs.values():
        one_pass(continuous, chunked)
    passes = {k: [] for k in legs}
    for _ in range(1 if smoke else 4):
        for k, (continuous, chunked) in legs.items():
            passes[k].append(one_pass(continuous, chunked))
    tok, cont, reql = (max(passes[k], key=lambda p: p["tps"])
                       for k in ("tok", "cont", "reql"))

    # --- shared-prefix KV reuse: popularity-skewed pool stream ----------
    # The same chunked engine decodes the pool stream cold (reference)
    # and with a PrefixKVStore; repeats must HIT, skip their prefill,
    # and still produce the cold run's exact tokens.
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
    # A 2-replica decode FrontDoor (chunked engines, one SHARED
    # PrefixKVStore) decodes a slice of the zipf stream while
    # ``kill:replica@0:tok<n>`` fail-stops replica 0 on its own
    # deterministic token clock; the door's sweep detaches the seated
    # streams with their journals and resurrects them on the survivor.
    # Gates: zero failed streams, zero restarts (the dead replica is
    # never rebuilt), every stream bitwise-equal to the uninterrupted
    # single-engine reference, and the decode_recovery counters + the
    # ``recovery`` decode-latency label tell a consistent timeline
    # (every detached stream reseated, one latency observation each).
    # A second leg kills the ONLY replica of a 1-replica door: every
    # in-flight stream must fail LOUDLY — structured
    # ``recovery_exhausted`` with the partial tokens attached — never
    # hang silently.
    from hetu_tpu import chaos as chaos_mod
    from hetu_tpu.serving import FrontDoor, ServeRejected

    rec_n = min(n_requests, 8 if smoke else 24)
    rec_reqs = list(zip(prompts, news))[:rec_n]
    rec_total = int(sum(int(nw) for _, nw in rec_reqs))
    kill_tok = max(3, rec_total // 8)
    rec_ref = one_pass(True, True, reqs=rec_reqs)["tokens"]

    def _poll_fleet(door, streams, timeout=300.0):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            door.poll()
            if all(s.done for s in streams):
                return True
            time.sleep(0.005)
        return False

    from hetu_tpu.analysis.protocol import PROTO, check_conformance

    ht_metrics.reset_all()
    rec_store = PrefixKVStore()
    inj = chaos_mod.ChaosInjector.from_spec(
        f"{seed}:kill:replica@0:tok{kill_tok}")
    prev_inj = chaos_mod.install(inj)
    # the kill run doubles as a recorded protocol trace: seat / emit /
    # detach / adopt / fence transitions replay against the decode-
    # recovery model (ISSUE 20) and conformance gates the leg
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
            t0 = time.monotonic()
            rec_streams = [door.submit(p, max_new_tokens=int(nw))
                           for p, nw in rec_reqs]
            rec_done = _poll_fleet(door, rec_streams)
            rec_wall = time.monotonic() - t0
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
    rec_lat = HetuProfiler.latency_stats().get(
        "decode_latency_us", {}).get("recovery", {})
    rec_restarts = int(rec_fleet.get("fleet_scale_out", 0)) - 2
    rec_ok = (rec_done and rec_failed == 0
              and rec_tokens == rec_ref
              and rec_fleet.get("fleet_replica_ejected", 0) == 1
              and rec_fleet.get("fleet_request_failures", 0) == 0
              and rec_restarts == 0
              and rec_c.get("decode_recovery_reseated", 0) >= 1
              and rec_c.get("decode_recovery_reseated", 0)
              == rec_c.get("decode_recovery_detached", 0)
              and rec_c.get("decode_recovery_exhausted", 0) == 0
              and int(rec_lat.get("count", 0))
              == rec_c.get("decode_recovery_reseated", 0)
              and rec_conf["ok"]
              and ht_metrics.fault_counts().get(
                  "chaos_kill_replica", 0) == 1)

    ht_metrics.reset_all()
    inj0 = chaos_mod.ChaosInjector.from_spec(
        f"{seed}:kill:replica@0:tok3")
    prev_inj = chaos_mod.install(inj0)
    exhausted, zs_partials_ok = 0, True
    PROTO.start()
    try:
        door = FrontDoor(
            lambda idx: DecodeRouter(mk_engine(True), queue_limit=16,
                                     name=f"recz{idx}"),
            1, health_every_ms=1e9, wedge_timeout_ms=1e9)
        try:
            zs = [door.submit(np.full(4, 3 + i, np.int32),
                              max_new_tokens=gen_cap) for i in range(3)]
            _poll_fleet(door, zs, timeout=120.0)
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
    exhaust_ok = (exhausted >= 1 and zs_partials_ok
                  and zs_conf["ok"]
                  and ht_metrics.decode_recovery_counts().get(
                      "decode_recovery_exhausted", 0) == exhausted)

    def pct(xs, q):
        return float(np.percentile(np.asarray(xs), q))

    # --- incremental KV cache vs naive re-prefill, per cache length ------
    # This leg uses a WIDER model than the policy streams above: the
    # O(1)-vs-O(len) claim is about device math, and on the tiny stream
    # model the per-step host scheduling overhead (~1ms on CPU) would
    # drown the length-dependent term at small L.  The engine's max_len
    # leaves headroom above the largest measured length so the timed
    # steps never exhaust the cache and drop the sequence mid-measure.
    lengths = (8, 16, 32) if smoke else (8, 16, 32, 64)
    reps = 5 if smoke else 9
    kv_max_len = 128
    kvcfg = GPT2Config.tiny(n_positions=2 * kv_max_len, batch_size=1,
                            seq_len=kv_max_len, n_embd=384, n_layer=4,
                            n_head=4)
    feeds, logits, caches, _ = gpt2_decode_graph(kvcfg,
                                                 max_len=kv_max_len)
    eng = DecodeEngine(feeds, logits, caches, max_slots=1,
                       max_len=kv_max_len, seed=0)
    per_len = []
    for L in lengths:
        req = _DecodeRequest(np.full(L, 3, np.int32),
                             max_new=reps + 4, eos_id=None, fid=None)
        eng.join(req)
        for _ in range(L - 1):        # prefill to position L-1
            eng.step()
        eng.step()                    # warmup the generate-leg compile
        ts = []
        for _ in range(reps):
            t = time.perf_counter()
            eng.step()
            ts.append(time.perf_counter() - t)
        eng.abort(RuntimeError("bench drain"))
        incr_ms = float(min(ts)) * 1e3
        # the naive alternative: one FULL forward over the L-token
        # prefix for every generated token, including the host-side
        # fetch + argmax the engine's step also pays
        lcfg = GPT2Config.tiny(n_positions=2 * kv_max_len, batch_size=1,
                               seq_len=L, n_embd=384, n_layer=4,
                               n_head=4)
        f2, _loss, logits2 = gpt2_lm_graph(lcfg)
        iex_full = InferenceExecutor([logits2], buckets=(1,), seed=0,
                                     validate="off", donate=False)
        fn = iex_full.compiled(1)
        ids = np.full((1, L), 3, np.int32)
        fd = {iex_full._k(f2["input_ids"]): ids}
        jax.block_until_ready(fn(iex_full.params, fd))    # warmup
        ts = []
        for _ in range(reps):
            t = time.perf_counter()
            out = fn(iex_full.params, fd)
            row = np.asarray(out[0]).reshape(L, -1)[L - 1]
            int(np.argmax(row))
            ts.append(time.perf_counter() - t)
        reprefill_ms = float(min(ts)) * 1e3
        per_len.append({"len": L, "incremental_ms": round(incr_ms, 3),
                        "reprefill_ms": round(reprefill_ms, 3),
                        "speedup": round(reprefill_ms / incr_ms, 2)})

    # --- time-to-first-token: chunked vs token-by-token ingestion --------
    # Measured directly on engines (join -> stream complete with
    # max_new=1), min over reps after a compile-warmup rep.  Chunked
    # ingestion pays ceil(L/chunk) steps where token-by-token pays L, so
    # the win is structural, not a timing accident.
    ttft_lens = (4, 8, 16) if smoke else (4, 8, 16, 24)
    ttft_reps = 3 if smoke else 5
    engines = {"token_by_token": mk_engine(chunked=False),
               "chunked": mk_engine(chunked=True)}
    ttft_rows = []
    for L in ttft_lens:
        prompt = np.full(L, 3, np.int32)
        ms, toks = {}, {}
        for name, eng in engines.items():
            best = None
            for r in range(ttft_reps + 1):     # rep 0: compile warmup
                req = _DecodeRequest(prompt, max_new=1, eos_id=None,
                                     fid=None)
                t = time.perf_counter()
                eng.join(req)
                while eng.active:
                    eng.step()
                dt = (time.perf_counter() - t) * 1e3
                toks[name] = req.stream.result(timeout=60)
                if r:
                    best = dt if best is None else min(best, dt)
            ms[name] = best
        ttft_rows.append({
            "prompt_len": int(L),
            "token_by_token_ms": round(ms["token_by_token"], 3),
            "chunked_ms": round(ms["chunked"], 3),
            "speedup": round(ms["token_by_token"] / ms["chunked"], 2),
            "bitwise_equal": toks["token_by_token"] == toks["chunked"],
        })
    ttft_wins = all(r["chunked_ms"] < r["token_by_token_ms"]
                    and r["bitwise_equal"] for r in ttft_rows)

    # --- the acceptance gates --------------------------------------------
    bitwise = (cont["tokens"] == reql["tokens"]
               and cont["tokens"] == tok["tokens"])
    steps_n = cont["decode"]["decode_steps"]
    pairs = cont["run_plan"].get("plan_cache_miss", 0)
    compiles = (cont["serve"].get("serve_bucket_compiles", 0)
                + cont["step_cache"].get("step_cache_serve_hit", 0))
    compile_once = (pairs > 0 and compiles == pairs
                    and cont["run_plan"].get("plan_cache_hit", 0)
                    == steps_n - pairs
                    and pairs <= cont["ladder"][0] * cont["ladder"][1]
                    * cont["ladder"][2])
    kv_wins = all(r["incremental_ms"] < r["reprefill_ms"]
                  for r in per_len)
    no_rejects = all(leg["decode"].get("decode_rejections", 0) == 0
                     for leg in (cont, reql, tok, pref_warm))
    pc = pref_warm["prefix_ct"]
    hits = pc.get("prefix_cache_hits", 0)
    misses = pc.get("prefix_cache_misses", 0)
    prefix_ok = (pref_warm["tokens"] == pref_cold["tokens"]
                 and hits > 0
                 and pref_warm["decode"].get("decode_prefill_rows", 0)
                 < pref_cold["decode"].get("decode_prefill_rows", 0))
    ttft_counted = cont["ttft_hist"].get("count", 0) == n_requests
    cont_p99 = pct(cont["lat_ms"], 99)
    req_p99 = pct(reql["lat_ms"], 99)
    perf_ok = (cont["tps"] > reql["tps"] and cont_p99 <= req_p99
               and cont["tps"] >= tok["tps"])
    ok = bitwise and compile_once and kv_wins and no_rejects \
        and ttft_wins and prefix_ok and ttft_counted \
        and rec_ok and exhaust_ok \
        and (perf_ok or smoke)     # the perf margin gates the full run

    result = {
        "metric": "decode_tokens_per_s",
        "value": round(cont["tps"], 1),
        "unit": "tokens/s",
        "vs_baseline": round(cont["tps"] / reql["tps"], 3) if ok else 0.0,
        "extra": {
            "baseline_def": "chunked continuous-batching tokens/s over "
                            "request-level batching of the SAME seeded "
                            "zipf stream (bitwise-identical token "
                            "streams required across continuous, "
                            "request-level AND token-by-token "
                            "ingestion); 0.0 unless every gate held: "
                            "compile-once per (batch,len) pair and "
                            "(batch,chunk,len) triple with "
                            "plan-cache-hit steady state, incremental "
                            "KV step faster than re-prefill at every "
                            "measured length, chunked TTFT faster than "
                            "token-by-token at every measured prompt "
                            "length, prefix-cache hits with prefill "
                            "rows saved and a bitwise-equal stream, one "
                            "ttft histogram observation per stream, "
                            "zero rejections, a mid-generation "
                            "kill:replica@0:tok<n> recovery leg with "
                            "zero failed streams / zero restarts and "
                            "every stream bitwise-equal to the "
                            "unkilled reference (and a zero-survivor "
                            "kill failing loudly with "
                            "recovery_exhausted + partial tokens), "
                            "and (full runs) better "
                            "tokens/s at no-worse p99 time-to-token "
                            "with chunked tokens/s no worse than "
                            "token-by-token",
            **_provenance({"n_requests": n_requests,
                           "max_slots": max_slots, "max_len": max_len,
                           "gen_cap": gen_cap, "zipf_prompt_a": 1.5,
                           "zipf_gen_a": 1.6, "n_embd": cfg.n_embd,
                           "n_layer": cfg.n_layer, "seed": seed,
                           "max_chunk": 8, "prefix_pool": pool_n,
                           "zipf_pool_a": 1.3,
                           "ttft_lens": list(ttft_lens),
                           "kv_leg_n_embd": 384, "kv_leg_n_layer": 4,
                           "kv_leg_max_len": kv_max_len,
                           "recovery_streams": rec_n,
                           "recovery_kill_tok": int(kill_tok),
                           "smoke": bool(smoke)}),
            "continuous": {
                "tokens_per_s": round(cont["tps"], 1),
                "p50_ms": round(pct(cont["lat_ms"], 50), 2),
                "p99_ms": round(cont_p99, 2),
                "wall_s": round(cont["wall_s"], 2),
                "counters": cont["decode"],
            },
            "request_level": {
                "tokens_per_s": round(reql["tps"], 1),
                "p50_ms": round(pct(reql["lat_ms"], 50), 2),
                "p99_ms": round(req_p99, 2),
                "wall_s": round(reql["wall_s"], 2),
                "counters": reql["decode"],
            },
            "token_by_token": {
                "tokens_per_s": round(tok["tps"], 1),
                "p50_ms": round(pct(tok["lat_ms"], 50), 2),
                "p99_ms": round(pct(tok["lat_ms"], 99), 2),
                "wall_s": round(tok["wall_s"], 2),
                "counters": tok["decode"],
            },
            "streams_bitwise_equal": bitwise,
            "compile_once": {
                "decode_steps": int(steps_n),
                "bucket_keys": int(pairs),
                "bucket_key_bound": int(cont["ladder"][0]
                                        * cont["ladder"][1]
                                        * cont["ladder"][2]),
                "serve_bucket_compiles": int(
                    cont["serve"].get("serve_bucket_compiles", 0)),
                "step_cache_serve_hits": int(
                    cont["step_cache"].get("step_cache_serve_hit", 0)),
                "plan_cache_hits": int(
                    cont["run_plan"].get("plan_cache_hit", 0)),
                "holds": bool(compile_once),
            },
            "prefill": {
                "steps": int(cont["decode"].get(
                    "decode_prefill_steps", 0)),
                "steps_saved_vs_token_by_token": int(cont["decode"].get(
                    "decode_prefill_steps_saved", 0)),
                "logits_fetches_skipped": int(cont["decode"].get(
                    "decode_logits_skipped", 0)),
            },
            "ttft_vs_token_by_token": ttft_rows,
            "ttft_wins_every_length": ttft_wins,
            "ttft_histogram": cont["ttft_hist"],
            "ttft_counted_per_stream": ttft_counted,
            "prefix_cache": {
                "hits": int(hits),
                "misses": int(misses),
                "hit_rate": round(hits / max(1, hits + misses), 3),
                "hit_rows": int(pc.get("prefix_cache_hit_rows", 0)),
                "evictions": int(pc.get("prefix_cache_evictions", 0)),
                "bytes_hw": int(pc.get("prefix_cache_bytes_hw", 0)),
                "prefill_rows_cold": int(pref_cold["decode"].get(
                    "decode_prefill_rows", 0)),
                "prefill_rows_warm": int(pref_warm["decode"].get(
                    "decode_prefill_rows", 0)),
                "streams_bitwise_equal": pref_warm["tokens"]
                == pref_cold["tokens"],
                "holds": bool(prefix_ok),
            },
            "kv_cache_vs_reprefill": per_len,
            "kv_incremental_wins_every_length": kv_wins,
            "recovery": {
                "kill_spec": f"kill:replica@0:tok{kill_tok}",
                "streams": int(rec_n),
                "failed_streams": int(rec_failed),
                "restarts": int(rec_restarts),
                "streams_bitwise_equal_to_unkilled":
                    rec_tokens == rec_ref,
                "counters": {k: int(v) for k, v in rec_c.items()},
                "fleet": {k: int(v) for k, v in rec_fleet.items()},
                "reseat_latency_us": rec_lat,
                "wall_s": round(rec_wall, 2),
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
            "backend": jax.default_backend(),
        },
    }
    if write_artifact:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "artifacts", "decode_bench.json")
        with open(path, "w") as f:
            json.dump(result, f, indent=1)
    return result


def bench_trace(steps=5, kill_step=2, smoke=True, write_artifact=None):
    """ISSUE 10 demo: one unified telemetry trace of the framework's
    signature behaviours — ``artifacts/trace_step.json``.

    A 5-step wdl-style PS training run (3-rank ``replication=2``
    cluster, Adam through a PS embedding) executes under a
    ``kill:primary@shard1:step<k>`` chaos schedule with ``HETU_TRACE=1``
    live: the kill lands in step k's post-step hook, so the NEXT step's
    pull absorbs the failover — its ``fault:ps_rpc_retry`` /
    ``fault:ps_failover*`` point events appear INSIDE that step's span,
    between its per-opcode ``rpc:OP_*`` spans.  The run is driven by
    ``Executor.run_steps(sync=False)`` with the feed pipeline forced on
    (``HETU_FEED_PIPELINE_MIN_US=0``) so the background H2D copies show
    up as a named ``run-steps-feed`` track and the non-blocking window
    as flow arrows; a small serving burst through
    ``InferenceExecutor``/``ServingRouter`` adds the serve-router track
    (enqueue -> assemble -> device call -> scatter).  Losses stay
    BITWISE equal to an untraced clean run — telemetry and failover are
    both transparent.  The exported Chrome JSON loads directly in
    Perfetto; the step-time histogram and the MFU gauge (inferred-shape
    FLOPs over measured step time) land on the metrics registry and
    ride in ``extra``."""
    import jax
    import hetu_tpu as ht
    from hetu_tpu import chaos as chaos_mod, obs
    from hetu_tpu import metrics as ht_metrics
    from hetu_tpu.metrics import fault_counts, reset_faults
    from hetu_tpu.ps.dist_store import DistributedStore
    from hetu_tpu.serving import InferenceExecutor, ServingRouter

    if write_artifact is None:
        write_artifact = not smoke
    world, rows, width = 3, 48, 8
    rpc_timeout = 5.0
    assert 0 < kill_step < steps - 1, "the failover needs a later step"

    def make_cluster(ports):
        stores = [DistributedStore(
            r, world, [("127.0.0.1", p) for p in ports], port=ports[r],
            rpc_timeout=rpc_timeout, rpc_retries=2, connect_timeout=2.0,
            replication=2) for r in range(world)]
        tid = None
        for s in stores:
            tid = s.init_table(rows, width, opt="sgd", lr=0.1,
                               init_scale=0.0)
        table = np.random.RandomState(42).normal(
            0, 0.01, (rows, width)).astype(np.float32)
        stores[0].set_data(tid, table)
        return stores, tid

    def build(store, tid):
        rng = np.random.RandomState(1)
        ids = ht.placeholder_op("ids")
        y_ = ht.placeholder_op("y")
        h = ht.ps_embedding_lookup_op((store, tid), ids, width=width)
        w = ht.Variable("w", value=rng.randn(width, 2).astype(np.float32)
                        * .3)
        loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
            ht.matmul_op(h, w), y_), [0])
        ex = ht.Executor(
            {"train": [loss, ht.optim.AdamOptimizer(0.01).minimize(loss)]},
            seed=0, install_signal_handlers=False)
        return ex, loss, ids, y_

    rng = np.random.RandomState(0)
    feeds = [(rng.randint(0, rows, 32),
              np.eye(2, dtype=np.float32)[rng.randint(0, 2, 32)])
             for _ in range(steps)]

    def run_train(store, tid):
        ex, loss, ids, y_ = build(store, tid)
        rs = ex.run_steps(
            lambda i: {ids: feeds[i][0], y_: feeds[i][1]}, steps,
            name="train", sync=False)
        fd0 = {ids: feeds[0][0], y_: feeds[0][1]}
        return ex, loss, fd0, [
            np.asarray(r[0].jax(), np.float32).tobytes() for r in rs]

    env_chaos = os.environ.pop("HETU_CHAOS", None)
    env_min = os.environ.get("HETU_FEED_PIPELINE_MIN_US")
    # tiny batches: force the H2D double-buffer on so the feed-pipeline
    # track exists (the adaptive threshold would keep them inline)
    os.environ["HETU_FEED_PIPELINE_MIN_US"] = "0"
    chaos_mod.uninstall()
    prev_trace = obs.enabled()
    prev_timing = ht_metrics.step_timing

    try:
        # --- clean, untraced run: the parity oracle ----------------------
        obs.enable(False)
        reset_faults()
        stores, tid = make_cluster(_free_ports(world))
        try:
            _, _, _, base_losses = run_train(stores[0], tid)
        finally:
            for s in stores:
                s.close()
        clean_counters = fault_counts()

        # --- traced chaos run -------------------------------------------
        schedule = f"11:kill:primary@shard1:step{kill_step}"
        reset_faults()
        ht_metrics.reset_step_times()
        ht_metrics.enable_step_timing(True)
        obs.clear_trace()
        obs.enable(True)
        prev = chaos_mod.install(
            chaos_mod.ChaosInjector.from_spec(schedule))
        try:
            stores, tid = make_cluster(_free_ports(world))
            try:
                ex, loss, fd0, chaos_losses = run_train(stores[0], tid)
                # MFU gauge: PR 5 inferred-shape FLOPs over the MEASURED
                # per-step wall from the step_time_us histogram (the
                # run just recorded it) — a wall clock around the whole
                # run would fold cluster setup + compile into "step
                # time" and understate MFU ~100x on a 5-step run
                flops = obs.graph_flops([loss], feeds=fd0)
                # p50, not mean: step 0's recorded wall contains the
                # jit compile, which would dominate a 5-step mean
                step_s = ht_metrics.step_time_stats()["train"]["p50"] \
                    / 1e6
                peak, device_kind = _device_peak_flops()
                mfu = obs.record_mfu("trace_wdl", flops, step_s, peak)
                # serving burst: the router/assemble/device-call/scatter
                # lifecycle on its own named track
                sx = ht.placeholder_op("sx", shape=(width,))
                sw = ht.Variable("trace_serve_w", value=np.random.RandomState(
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
                for s in stores:
                    try:
                        s.close()
                    except Exception:
                        pass
        finally:
            chaos_mod.install(prev)
            obs.enable(False)
            ht_metrics.enable_step_timing(False)
        counters = fault_counts()
        evs = obs.trace_events()
        step_stats = ht_metrics.step_time_stats().get("train", {})
    finally:
        if env_chaos is not None:
            os.environ["HETU_CHAOS"] = env_chaos
        if env_min is None:
            os.environ.pop("HETU_FEED_PIPELINE_MIN_US", None)
        else:
            os.environ["HETU_FEED_PIPELINE_MIN_US"] = env_min
        obs.enable(prev_trace)
        ht_metrics.enable_step_timing(prev_timing)

    # --- trace self-checks (the acceptance claims, machine-checked) ------
    names = [e["name"] for e in evs]
    tracks = [e["args"]["name"] for e in evs
              if e.get("ph") == "M" and e["name"] == "thread_name"]
    step_spans = [e for e in evs if e.get("ph") == "X"
                  and e["name"] == "step"]
    promo = [e for e in evs if e["name"] == "fault:ps_failover_promoted"]
    # the promotion instant must land INSIDE one step span's window
    promo_in_step = any(
        s["ts"] <= p["ts"] <= s["ts"] + s["dur"]
        for p in promo for s in step_spans)
    checks = {
        "step_spans": len(step_spans),
        "rpc_spans": sum(1 for n in names if n.startswith("rpc:")),
        "retry_events": sum(1 for n in names
                            if n == "fault:ps_rpc_retry"),
        "failover_promotions": len(promo),
        "promotion_inside_step_span": bool(promo_in_step),
        "feed_pipeline_track": any("run-steps-feed" in t
                                   or "feed-pipeline" in t
                                   for t in tracks),
        "serve_router_track": any("hetu-serve-router" in t
                                  for t in tracks),
        "serve_device_calls": names.count("serve.device_call"),
        "flow_arrows": sum(1 for e in evs if e.get("ph") == "s"),
        "loss_parity": chaos_losses == base_losses,
        "clean_run_counters_empty": not clean_counters,
    }
    ok = (checks["step_spans"] >= steps
          and checks["rpc_spans"] > 0
          and checks["failover_promotions"] >= 1
          and checks["promotion_inside_step_span"]
          and checks["feed_pipeline_track"]
          and checks["serve_router_track"]
          and checks["serve_device_calls"] >= 1
          and checks["loss_parity"]
          and checks["clean_run_counters_empty"])

    if write_artifact:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "artifacts", "trace_step.json")
        obs.export_chrome_trace(path)

    workload = {"steps": steps, "kill_step": kill_step, "world": world,
                "replication": 2, "schedule": schedule,
                "smoke": bool(smoke)}
    return {
        "metric": "trace_step_events",
        "value": len(evs),
        "unit": "events",
        "vs_baseline": 1.0 if ok else 0.0,
        "extra": {
            "baseline_def": "1.0 iff the exported trace carries >= "
                            "steps step spans, per-opcode rpc spans, "
                            "the failover promotion as a point event "
                            "INSIDE a step span, the feed-pipeline and "
                            "serve-router thread tracks, >= 1 serving "
                            "device call, bitwise loss parity vs the "
                            "untraced clean run, and the clean run "
                            "recorded zero fault counters",
            **_provenance(workload),
            **checks,
            "tracks": sorted(set(tracks)),
            "step_time_us_p50": step_stats.get("p50"),
            "step_time_us_p99": step_stats.get("p99"),
            "mfu": mfu,
            "flops_per_step": flops,
            "device_kind": device_kind,
            "fault_counters": counters,
            "backend": jax.default_backend(),
        },
    }


def bench_partition(steps=10, cut_step=3, heal_step=7, smoke=True):
    """ISSUE 8 acceptance: partition tolerance with fencing epochs.

    Part A (3-rank training): the same seeded run three times — clean,
    ``partition:rank0|rank1@step<cut>`` without heal, and with
    ``:heal<m>``.  The partition cuts the training client (rank 0) off
    shard 1's primary: the client fails over to the ring backup (epoch
    bump), training continues with ZERO restarts, and losses stay
    BITWISE equal to the clean run in both chaos variants (every acked
    write lands on the surviving lineage).  After heal, a stale client
    (rank 1's own store) writes through the healed stale ex-primary:
    the op-log forward is epoch-refused by the promoted backup
    (``ps_epoch_refused``), the ex-primary demotes itself
    (``ps_demotions``) instead of acking, and the client re-routes the
    SAME op to the surviving lineage — then epoch-checked
    re-replication converges both copies, proven by
    ``ps_fsck(retries=2)``: zero stable divergence and exactly one
    serving epoch per shard.  The no-heal run documents the detectable
    split brain fsck sees when nothing converges it.

    Part B (2-cell geo-replicated serving): 4 ranks in two cells, each
    serving InferenceExecutor traffic through a ServingRouter off a
    read-only warmed DistCacheTable.  A cross-cell partition leaves
    BOTH cells answering local reads (rejections=0, errors=0); the east
    cell promotes a local backup for a missed shard (new lineage);
    cross-cell re-replication queues (deferred) until heal; at heal the
    west trainer's first stale write triggers the fence dance and
    ``CellHead.catch_up`` re-replicates — fsck converges to one lineage.

    Host-side metric: transport, fencing and routing run on the host
    whatever the accelerator is."""

    import jax
    import hetu_tpu as ht
    from hetu_tpu import chaos as chaos_mod
    from hetu_tpu.analysis.protocol import PROTO, check_conformance
    from hetu_tpu.metrics import fault_counts, reset_faults
    from hetu_tpu.ps.dist_store import DistributedStore
    from tools.ps_fsck import fsck

    world, rows, width = 3, 48, 8
    rpc_timeout = 5.0
    assert cut_step < heal_step < steps - 1, "need post-heal steps"

    def make_cluster(ports, nranks=world, nrows=rows, w=width):
        stores = [DistributedStore(
            r, nranks, [("127.0.0.1", p) for p in ports], port=ports[r],
            rpc_timeout=rpc_timeout, rpc_retries=2, connect_timeout=2.0,
            replication=2) for r in range(nranks)]
        tid = None
        for s in stores:
            tid = s.init_table(nrows, w, opt="sgd", lr=0.1, init_scale=0.0)
        table = np.random.RandomState(42).normal(
            0, 0.01, (nrows, w)).astype(np.float32)
        stores[0].set_data(tid, table)   # replicated seeding path
        return stores, tid

    def build(store, tid):
        rng = np.random.RandomState(1)
        ids = ht.placeholder_op("ids")
        y_ = ht.placeholder_op("y")
        h = ht.ps_embedding_lookup_op((store, tid), ids, width=width)
        w = ht.Variable("w", value=rng.randn(width, 2).astype(np.float32)
                        * .3)
        loss = ht.reduce_mean_op(ht.softmaxcrossentropy_op(
            ht.matmul_op(h, w), y_), [0])
        ex = ht.Executor(
            {"train": [loss, ht.optim.AdamOptimizer(0.01).minimize(loss)]},
            seed=0, install_signal_handlers=False)
        return ex, ids, y_

    rng = np.random.RandomState(0)
    feeds = [(rng.randint(0, rows, 32),
              np.eye(2, dtype=np.float32)[rng.randint(0, 2, 32)])
             for _ in range(steps)]
    # the stale-client probe: shard-1-owned keys, ZERO grads — sgd leaves
    # the values bitwise unchanged, so the probe can ride every variant
    # without perturbing loss parity while still exercising the write
    # path (and, post-heal, the fence dance)
    probe_keys = np.asarray([1, 4], np.int64)
    probe_grads = np.zeros((2, width), np.float32)

    env_chaos = os.environ.pop("HETU_CHAOS", None)
    env_tick = os.environ.pop("HETU_PS_REREPLICATE_EVERY", None)
    chaos_mod.uninstall()

    def run_variant(schedule, heal):
        """One full training run; returns (losses, per-step ms, events,
        fault counters, fsck report, protocol-conformance report) — the
        run is also a RECORDED protocol trace replayed against the
        replication model (ISSUE 20)."""
        reset_faults()
        ports = _free_ports(world)
        stores, tid = make_cluster(ports)
        losses, step_ms = [None] * steps, [0.0] * steps
        events = {"failover_steps": [], "deferred_in_partition": False,
                  "probe_acked": False, "heal_catchup_ms": 0.0}
        prev = chaos_mod.install(
            chaos_mod.ChaosInjector.from_spec(schedule)) if schedule \
            else chaos_mod.uninstall()
        PROTO.start()
        try:
            ex, ids, y_ = build(stores[0], tid)
            for step in range(steps):
                before = fault_counts().get("ps_failover_promoted", 0)
                t0 = time.monotonic()
                # NO try/except, NO restart: a partitioned primary is
                # absorbed by failover inside the failing RPC
                losses[step] = float(
                    ex.run("train", feed_dict={ids: feeds[step][0],
                                               y_: feeds[step][1]}
                           )[0].asnumpy())
                step_ms[step] = (time.monotonic() - t0) * 1e3
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
                    t1 = time.monotonic()
                    stores[1].push(tid, probe_keys, probe_grads)
                    events["probe_acked"] = True
                    stores[0].maybe_re_replicate()  # epoch-checked repair
                    events["heal_catchup_ms"] = \
                        (time.monotonic() - t1) * 1e3
            report = fsck([("127.0.0.1", p) for p in ports], n_tables=1,
                          replication=2, retries=2, retry_wait=0.2)
            out = (losses, step_ms, events, fault_counts(), report)
        finally:
            proto_events = PROTO.stop()  # before teardown closes fire
            chaos_mod.install(prev) if schedule else None
            for s in stores:
                try:
                    s.close()
                except Exception:
                    pass
        return out + (check_conformance(proto_events),)

    two_cell = None
    try:
        base, base_ms, base_ev, clean_counters, base_fsck, base_conf = \
            run_variant(None, heal=False)
        noheal = run_variant(
            f"13:partition:rank0|rank1@step{cut_step}", heal=False)
        heal = run_variant(
            f"13:partition:rank0|rank1@step{cut_step}:heal{heal_step}",
            heal=True)
        two_cell = _two_cell_scenario(cut_step, heal_step)
    finally:
        chaos_mod.uninstall()
        if env_chaos is not None:
            os.environ["HETU_CHAOS"] = env_chaos
        if env_tick is not None:
            os.environ["HETU_PS_REREPLICATE_EVERY"] = env_tick

    h_losses, h_ms, h_ev, h_counters, h_fsck, h_conf = heal
    n_losses, _, n_ev, n_counters, n_fsck, n_conf = noheal
    heal_parity = h_losses == base
    noheal_parity = n_losses == base
    one_lineage = all(len(r) == 1
                      for r in h_fsck["serving_ranks"].values())
    recovery_ms = sum(h_ms[s - 1] for s in h_ev["failover_steps"]) \
        + h_ev["heal_catchup_ms"]
    ok = (heal_parity and noheal_parity
          and h_ev["probe_acked"]
          and h_ev["deferred_in_partition"]
          and h_counters.get("partition_frames_dropped", 0) > 0
          and h_counters.get("ps_epoch_refused", 0) > 0
          and h_counters.get("ps_demotions", 0) > 0
          and h_counters.get("ps_epoch_bumps", 0) > 0
          and h_counters.get("ps_failover_promoted", 0) >= 1
          and h_fsck["ok"] and one_lineage
          and h_fsck["serving_ranks"][1] == [2]
          and not n_fsck["ok"]          # unhealed split brain is VISIBLE
          and bool(n_fsck["lineage_violations"])
          and base_fsck["ok"] and not clean_counters
          and base_conf["ok"] and n_conf["ok"] and h_conf["ok"]
          and bool(two_cell) and two_cell["ok"])
    return {
        "metric": "partition_recovery_ms",
        "value": round(recovery_ms, 1),
        "unit": "ms",
        "vs_baseline": 1.0 if ok else 0.0,
        "extra": {
            "baseline_def": "1.0 iff BOTH partition runs' loss "
                            "trajectories are bitwise equal to the clean "
                            "run's (restarts=0, zero lost acked writes), "
                            "the healed stale ex-primary was epoch-"
                            "refused and demoted instead of serving, "
                            "in-partition re-replication deferred, post-"
                            "heal fsck (retries=2) found zero stable "
                            "divergence and exactly one serving epoch "
                            "per shard, the UNHEALED run's split brain "
                            "stayed fsck-visible, the clean run recorded "
                            "zero fault counters, every variant's "
                            "recorded protocol trace conformed to the "
                            "replication model, and the 2-cell "
                            "scenario served local reads through the "
                            "cut (rejections=0) and converged after "
                            "heal",
            **_provenance({"steps": steps, "cut_step": cut_step,
                           "heal_step": heal_step, "world": world,
                           "replication": 2, "smoke": bool(smoke)}),
            "restarts": 0,
            "resumes": 0,
            "loss_parity_heal": heal_parity,
            "loss_parity_noheal": noheal_parity,
            "probe_acked": h_ev["probe_acked"],
            "failover_steps": h_ev["failover_steps"],
            "re_replication_deferred_in_partition":
                h_ev["deferred_in_partition"],
            "heal_catchup_ms": round(h_ev["heal_catchup_ms"], 1),
            "step_ms": [round(m, 1) for m in h_ms],
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
            "two_cell": two_cell,
            "backend": jax.default_backend(),
        },
    }


def _two_cell_scenario(cut_step, heal_step):
    """Part B of ``bench_partition`` (docstring there): 2 cells x 2
    ranks, replicated store, per-cell read-only serving heads, a
    deterministic cross-cell partition + heal on a manual step clock."""
    import hetu_tpu as ht
    from hetu_tpu import chaos as chaos_mod
    from hetu_tpu.metrics import fault_counts, reset_faults
    from hetu_tpu.ps.dist_store import DistCacheTable, DistributedStore
    from hetu_tpu.serving import (CellHead, CellMap, InferenceExecutor,
                                  ServingRouter)
    from tools.ps_fsck import fsck

    vocab, dim, n_fields = 32, 4, 4
    cells = CellMap({"west": [0, 1], "east": [2, 3]})
    ports = _free_ports(cells.world)
    endpoints = [("127.0.0.1", p) for p in ports]
    reset_faults()
    stores = [DistributedStore(r, cells.world, endpoints, port=ports[r],
                               rpc_timeout=2.0, rpc_retries=2,
                               connect_timeout=2.0, replication=2)
              for r in range(cells.world)]
    heads = []
    try:
        tid = None
        for s in stores:
            tid = s.init_table(vocab, dim, opt="sgd", lr=0.1,
                               init_scale=0.0)
        stores[0].set_data(tid, np.random.RandomState(42).normal(
            0, 0.01, (vocab, dim)).astype(np.float32))

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
        waves = {"west": [w1, w2, w3], "east": [e1, e2a, e2b, e3]}
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
            "waves": waves,
            "fsck_ok": report["ok"],
            "fsck_serving_ranks": report["serving_ranks"],
            "fault_counters": counters,
        }
    finally:
        for h in heads:
            try:
                h.close()
            except Exception:
                pass
        for s in stores:
            try:
                s.close()
            except Exception:
                pass


def bench_elastic(steps=10, kill_step=3, rejoin_step=5, dp=4, zero=1,
                  smoke=True):
    """ISSUE 12 acceptance: elastic data-parallel training — kill one of
    dp=4 mid-run, keep training at dp=3 without a restart, grow back on
    rejoin.

    One chaos-driven run (``kill:proc@rank2:step<kill_step>`` on the
    deterministic step clock; the rank rejoins before step
    ``rejoin_step``) against the uninterrupted dp-MATCHED reference (same
    graph, same feeds, same world trajectory via explicit resizes, no
    chaos, no controller).  The artifact records the resize timeline
    (step, dp transition, recovery_ms per resize), restarts=0/resumes=0,
    BITWISE loss parity vs the reference, the compiled-step-cache
    evidence (2 misses for the two world sizes, >= 1 HIT on the
    grow-back — no recompile), the elastic counters, and both resizes as
    spans/instants counted out of the exported Perfetto trace.  Writes
    ``artifacts/elastic_smoke.json``."""
    import gc
    import jax
    import hetu_tpu as ht
    from hetu_tpu import chaos as chaos_mod, metrics as ht_metrics, obs
    from hetu_tpu.graph import step_cache
    from hetu_tpu.parallel.elastic import (ElasticController, LogicalRank,
                                           handles_alive_fn)

    if len(jax.devices()) < dp:
        raise RuntimeError(
            f"bench_elastic needs >= {dp} devices — run under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={dp} (bench.py "
            f"--config elastic sets this for its child automatically)")
    if not (0 < kill_step < rejoin_step <= steps - 2):
        raise ValueError(
            f"need 0 < kill_step < rejoin_step <= steps-2, got "
            f"kill={kill_step} rejoin={rejoin_step} steps={steps}")
    if dp < 3:
        # the scenario kills one rank and keeps training: the controller
        # floors the shrink at min_dp=2, so dp=2 would refuse the resize
        # and the run would fail the acceptance instead of explaining
        raise ValueError(
            f"bench_elastic needs dp >= 3 (kill one of dp, survive at "
            f"dp-1 >= the min_dp=2 floor), got dp={dp}")

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
    t_wall0 = time.perf_counter()
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
    wall_s = time.perf_counter() - t_wall0
    elastic_counters = dict(ht_metrics.elastic_counts())
    fault_counters = dict(ht_metrics.fault_counts())
    sc = dict(ht_metrics.step_cache_counts())
    timeline = list(ctl.events)
    # drop BOTH references to the elastic executor (ctl.ex pins it) so
    # the reference run below doesn't coexist with its device buffers
    del ex, ctl
    gc.collect()

    resize_spans = [e for e in trace_evs if e.get("ph") == "X"
                    and e["name"] == "elastic.resize"]
    shrink_events = [e for e in trace_evs if e.get("ph") == "i"
                     and e["name"] == "elastic:shrink"]
    grow_events = [e for e in trace_evs if e.get("ph") == "i"
                   and e["name"] == "elastic:grow"]

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

    loss_bits = [v.tobytes().hex() for v in losses]
    ref_bits = [v.tobytes().hex() for v in ref_losses]
    parity = loss_bits == ref_bits
    recovery_ms = max((e["recovery_ms"] for e in timeline), default=None)
    kinds = [e["kind"] for e in timeline]
    ok = (parity and seen_worlds == worlds
          and kinds == ["shrink", "grow"]
          and fault_counters.get("chaos_kill_proc") == 1
          and fault_counters.get("supervisor_restart", 0) == 0
          and fault_counters.get("resume", 0) == 0
          and sc.get("step_cache_miss") == 2
          and sc.get("step_cache_hit", 0) >= 1
          and len(resize_spans) == 2
          and len(shrink_events) >= 1 and len(grow_events) >= 1)

    res = {
        "metric": "elastic_resize_recovery_ms",
        "value": recovery_ms,
        "unit": "ms",
        # 1.0 = the elastic trajectory is bitwise the dp-matched
        # uninterrupted reference (the continuous-loss-trajectory gate)
        "vs_baseline": 1.0 if parity else 0.0,
        "extra": {
            "baseline_def": "value = slowest resize (detection poll -> "
                            "resized executor); vs_baseline 1.0 = losses "
                            "bitwise equal to an uninterrupted dp-matched "
                            "reference run (no restart, no checkpoint "
                            "resume anywhere)",
            **_provenance({"dp": dp, "steps": steps, "zero": zero,
                           "kill_step": kill_step,
                           "rejoin_step": rejoin_step,
                           "per_rank_batch": per_rank}),
            "world_trajectory": seen_worlds,
            "resize_timeline": timeline,
            "loss_bits": loss_bits,
            "final_loss": float(losses[-1]),
            "loss_bitwise_equal_vs_reference": parity,
            "restarts": int(fault_counters.get("supervisor_restart", 0)),
            "resumes": int(fault_counters.get("resume", 0)),
            "elastic_counters": elastic_counters,
            "fault_counters": fault_counters,
            "clean_run_elastic_counters": clean_elastic,
            "step_cache": sc,
            "trace": {"resize_spans": len(resize_spans),
                      "shrink_events": len(shrink_events),
                      "grow_events": len(grow_events)},
            "wall_s": round(wall_s, 2),
            "backend": jax.default_backend(),
            "smoke": bool(smoke),
        },
    }
    if not ok:
        res["error"] = (
            "elastic acceptance failed: "
            + "; ".join(filter(None, [
                None if parity else "loss NOT bitwise vs reference",
                None if seen_worlds == worlds
                else f"world trajectory {seen_worlds} != {worlds}",
                None if kinds == ["shrink", "grow"]
                else f"resize kinds {kinds}",
                None if sc.get("step_cache_hit", 0) >= 1
                else f"no step-cache hit on grow-back ({sc})",
                None if len(resize_spans) == 2
                else f"{len(resize_spans)} resize spans in trace",
            ])))
    try:
        from artifact_schema import provenance as _prov
        out = {**res, **_prov({"dp": dp, "steps": steps, "zero": zero,
                               "kill_step": kill_step,
                               "rejoin_step": rejoin_step})}
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "artifacts", "elastic_smoke.json")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path + ".tmp", "w") as f:
            json.dump(out, f, indent=1, sort_keys=True)
        os.replace(path + ".tmp", path)
    except Exception:
        pass    # the printed result is the bench contract; file is extra
    return res


if __name__ == "__main__":
    p = argparse.ArgumentParser()
    p.add_argument("--config", default="bert",
                   choices=["bert", "resnet18", "wdl", "moe", "attn",
                            "chaos", "failover", "emb", "zero", "serve",
                            "decode", "fleet", "partition", "overhead",
                            "trace", "elastic", "remat"])
    p.add_argument("--remat", default=None,
                   choices=["off", "dots", "full", "offload", "auto"],
                   help="bert: selective-remat policy for the flagship "
                        "measurement (parallel/remat.py).  The full "
                        "off/dots/full/auto sweep with per-cell "
                        "checkpointed resume is --config remat "
                        "(artifacts/remat_bench.json)")
    p.add_argument("--dp", type=int, default=4,
                   help="zero/elastic: data-parallel mesh size (the child "
                        "runs on a CPU host-device mesh of >= this; "
                        "elastic needs >= 3 — kill one, survive at dp-1)")
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--seq-len", type=int, default=None,
                   help="bert only: sequence length (default 512 — the "
                        "flash-gated masked flagship config)")
    p.add_argument("--wdl-embed", default="lru",
                   choices=["lru", "lfu", "lfuopt", "dense"],
                   help="wdl embedding mode: HET cache policies (the "
                        "BASELINE config-4 headline) or 'dense' (plain "
                        "device embedding — the same-semantics torch "
                        "comparison)")
    p.add_argument("--emb-policy", default=None,
                   choices=["direct", "lru", "lfu"],
                   help="wdl only: route the CTR embedding through the "
                        "vectorized HET cache path (direct = PS store "
                        "without a cache; lru/lfu = vectorized "
                        "DistCacheTable) — overrides --wdl-embed")
    p.add_argument("--emb-device", default=None,
                   choices=["host", "device"],
                   help="wdl: where the HET cache's row slab lives "
                        "(default host).  device = ISSUE 11 device-"
                        "resident slab: on-device slot gather, "
                        "overlapped miss pulls, Pallas grad scatter-add; "
                        "the artifact extra records cache_mode, hit "
                        "rate, emb_pallas_fallback_reason and the same-"
                        "trace host-cache comparison (vs_host_cache)")
    p.add_argument("--smoke", action="store_true",
                   help="emb: 10^5-row smoke config (seconds, CPU) "
                        "instead of the 10^7x64 scale run; failover: "
                        "the CI-sized double-kill run; serve: the "
                        "300-request CI config (artifacts/"
                        "serve_smoke.json); partition: the CI-sized "
                        "partition+heal run (artifacts/"
                        "partition_smoke.json); overhead: the CI parity/"
                        "plan-cache gate (no artifact write); elastic: "
                        "the chaos-driven dp=4 kill+rejoin run "
                        "(artifacts/elastic_smoke.json); decode: the "
                        "16-request stream with all gates but the strict "
                        "perf margin (no artifact write)")
    p.add_argument("--steps", type=int, default=None,
                   help=f"timed steps (default {DEFAULT_STEPS} for the "
                        "accelerator configs)")
    args = p.parse_args()
    if args.config in ACCEL_CONFIGS:
        sys.exit(_accel_main(args))
    elif os.environ.get(CHILD_ENV_FLAG):
        _host_main(args)
    else:
        _host_parent_main(args)
