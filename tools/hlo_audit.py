"""HLO audit of the training steps of ``tools/audit_graphs.py`` (round-4
verdict item 2, extended to every tracked config in round 5).

Audits the programs off hardware (run it with ``JAX_PLATFORMS=cpu`` and,
for the ``zero`` config's dp=4 mesh, eight virtual devices through
``XLA_FLAGS``): AOT-compiles those graphs
(flagship BERT seq-512 padded MLM, resnet18 NHWC, WDL dense, MoE top-2)
and audits each compiled HLO for the properties that set the TPU
performance ceiling:

  one_entry            whole step is ONE fused XLA computation (no
                       per-op dispatch — SURVEY.md L3 executor design)
  no_retrace           jit cache stays at one entry across repeated steps
                       with stable shapes (live-run check, small config,
                       flagship only)
  contractions_bf16    every dot AND conv contraction runs on bf16
                       operands (f32 contractions on the MXU halve
                       throughput); the fp32 master copies live OUTSIDE
                       the step's matmuls.  WDL is exempt: CTR trains
                       f32 end-to-end by design (embedding-lookup bound,
                       bf16 would round 100k-row ids' gradients for no
                       MXU win — ``build_wdl_graph`` passes no
                       compute_dtype).
  donation             params + optimizer state buffers are donated
                       (input_output_alias in the compiled module) so
                       weights update in place — no 2x HBM residency
  no_host_transfers    no infeed/outfeed/send/recv custom-calls inside
                       the step
  flops reconciliation (flagship only) XLA cost_analysis FLOPs vs
                       the analytic 6N+attention formula — the ratio
                       says how far a 6N-based MFU denominator is off

Writes ``artifacts/hlo_audit_{backend}.json``; exits non-zero if a MUST
property fails.  Runs on any backend (the audit is structural); flash-
kernel presence is additionally asserted when the backend is really the
TPU (the gate at ops/attention.py:_use_flash is tpu-only by design).
"""
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


# The audit compiles the graphs of tools/audit_graphs.py with
# compute_dtype bfloat16 and resnet18 in NHWC: it predicts the TPU
# program even when compiled on CPU.

from tools import audit_graphs  # noqa: E402


def _build_bert(**kw):
    return audit_graphs.build_bert_graph(compute_dtype="bfloat16", **kw)


def _build_resnet18(**kw):
    return audit_graphs.build_resnet18_graph(compute_dtype="bfloat16", **kw)


def _build_wdl(**kw):
    """The jitted step with the plain (dense) embedding; the HET-cache
    row traffic happens OUTSIDE the step and does not change the
    compiled program."""
    return audit_graphs.build_wdl_graph(policy="dense", **kw)


def _build_moe(**kw):
    return audit_graphs.build_moe_graph(compute_dtype="bfloat16", **kw)


#: name → (builder, expect_bf16_contractions)
BUILDERS = {
    "bert": (_build_bert, True),
    "resnet18": (_build_resnet18, True),
    "wdl": (_build_wdl, False),   # f32 by design — see module docstring
    "moe": (_build_moe, True),
}


def _audit_contractions(lowered_text):
    """Operand-dtype census over dot_general AND convolution ops in the
    LOWERED (pre-backend) program — the program's own dtype discipline,
    uncontaminated by backend quirks (XLA-CPU upcasts bf16 contractions
    to f32; the TPU MXU runs them native).  A contraction counts as bf16
    iff BOTH operands are bf16; the deliberate exceptions (attention-
    scores einsums that keep an f32 RESULT from bf16 operands for softmax
    range) still have bf16 operands and count as bf16.  f32xf32
    contractions are the mixed-precision leak this audit exists to catch:
    an f32 primal output makes the cotangent f32 and the whole backward
    runs at half MXU throughput (the round-4 flagship bug: 196/294 dots)."""
    n_bf16 = n_f32 = 0
    f32_lines = []
    for line in lowered_text.splitlines():
        if "stablehlo.dot_general" not in line \
                and "stablehlo.convolution" not in line:
            continue
        sig = line.rsplit(":", 1)[-1]
        in_sig = sig.split("->")[0]
        tys = re.findall(r"tensor<[^>]*x(\w+)>", in_sig)
        if tys and set(tys) == {"bf16"}:
            n_bf16 += 1
        else:
            n_f32 += 1
            if len(f32_lines) < 8:
                f32_lines.append(line.strip()[:180])
    return n_bf16, n_f32, f32_lines


def _audit_aliasing(lowered_text, compiled_text):
    """Donated buffers: counted from the lowered program's aliasing
    attributes — ``tf.aliasing_output`` when jit resolves the alias at
    lowering (single-device programs) and ``jax.buffer_donor`` when the
    assignment is deferred to the compiler (mesh-sharded programs, e.g.
    the ZeRO step: jit marks the donor, XLA pairs it post-SPMD).  Either
    marker is program-semantics donation, present on every backend; the
    count is cross-checked against the compiled module's
    input_output_alias (backend honor: XLA-CPU drops donation, the TPU
    runtime applies it)."""
    lowered = (lowered_text.count("tf.aliasing_output")
               + lowered_text.count("jax.buffer_donor"))
    m = re.search(r"input_output_alias=\{([^}]*)\}", compiled_text)
    compiled = m.group(1).count("(") if m else 0
    return lowered, compiled


def _retrace_check(steps=4):
    """Small live flagship config: the jit cache must not grow."""
    _, ex, fd = _build_bert(batch_size=2, seq_len=128)
    sub = ex.subexecutors["train"]
    for _ in range(steps):
        ex.run("train", feed_dict=fd)
    size_fn = getattr(sub._jit, "_cache_size", None)
    return int(size_fn()) if size_fn else None


def _audit_config(name, backend, args):
    import jax
    from hetu_tpu.profiler import HetuProfiler

    import inspect

    builder, expect_bf16 = BUILDERS[name]
    # --batch-size/--seq-len apply to bert only; the other configs audit
    # the builders' OWN defaults (read from their signatures, not
    # re-hardcoded here)
    if name == "bert":
        kw = {"batch_size": args.batch_size or 64,
              "seq_len": args.seq_len or 512}
    else:
        kw = {}
    bench_fn = getattr(audit_graphs, f"build_{name}_graph")
    # effective workload dims recorded in the artifact so bert's
    # bench_formula_flops can always be tied to the dimensions it was
    # computed with
    dims = {pname: p.default
            for pname, p in inspect.signature(bench_fn).parameters.items()
            if isinstance(p.default, (int, float))}
    dims.update(kw)
    print(f"audit[{name}]: compiling ...", flush=True)
    cfg, ex, fd = builder(**kw)
    prof = HetuProfiler(ex, name="train")
    lowered = prof.lowered_text(fd)
    hlo = prof.hlo_text(fd)
    cost = prof.hlo_cost(fd)

    n_entry = len(re.findall(r"^ENTRY ", hlo, re.MULTILINE))
    n_bf16, n_f32, f32_lines = _audit_contractions(lowered)
    n_alias_prog, n_alias_compiled = _audit_aliasing(lowered, hlo)
    host_ops = [op for op in ("infeed", "outfeed", "send(", "recv(")
                if op in hlo]
    flash_in_hlo = any(t in hlo for t in ("tpu_custom_call", "mosaic"))

    n_contr = n_bf16 + n_f32
    checks = {
        "one_entry": n_entry == 1,
        "donation": n_alias_prog > 0,
        "no_host_transfers": not host_ops,
    }
    if expect_bf16:
        checks["contractions_bf16"] = n_contr > 0 and n_f32 == 0
    if backend == "tpu" and name == "bert":
        checks["flash_in_hlo"] = flash_in_hlo

    # v5e compute-leg projection from the compiled program's own FLOP
    # count: the step-time FLOOR at 100% MXU utilization, and what the
    # step time would be at the 0.45 north-star MFU (BASELINE.md) — the
    # number a reviewer reconciles against a healthy-window measurement.
    # The memory leg is deliberately NOT projected from this module:
    # the CPU-compiled cost analysis counts bytes through unfused f32
    # upcasts (measured ~1 TB/step for the 133M-param flagship — off by
    # an order of magnitude for a TPU layout); the real roofline comes
    # from tools/calibrate_tpu.py's measured constants at a healthy
    # window.  bytes_accessed stays in the detail as a CPU diagnostic.
    V5E_PEAK_FLOPS = 197e12   # bf16, public spec (benchmarks/peaks.json)
    xla_flops = float(cost.get("flops", 0.0))
    compute_s = xla_flops / V5E_PEAK_FLOPS
    projection = {
        "compute_floor_ms": round(compute_s * 1e3, 3) if compute_s
        else None,
        "step_ms_at_north_star_mfu": round(compute_s / 0.45 * 1e3, 3)
        if compute_s else None,
        "peak_flops": V5E_PEAK_FLOPS,
        "note": "compute leg only; CPU-module bytes are not a TPU "
                "memory-leg estimate",
    }

    detail = {
        "workload": dims,
        "v5e_projection": projection,
        "entry_computations": n_entry,
        "contractions_total": n_contr,
        "contractions_bf16": n_bf16, "contractions_f32": n_f32,
        "f32_contraction_samples": f32_lines,
        "alias_pairs_program": n_alias_prog,
        "alias_pairs_compiled": n_alias_compiled,
        "host_ops_found": host_ops,
        "flash_in_hlo": flash_in_hlo,
        "xla_cost_flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": cost.get("bytes accessed"),
    }

    if name == "bert":
        # reconcile XLA-counted FLOPs with the analytic 6N + attention
        # formula: cost_analysis counts the optimized
        # module's real flops — fwd+bwd matmuls, attention, remat replays
        import numpy as np
        bs, sl = kw["batch_size"], kw["seq_len"]
        n_params = int(sum(np.prod(v.shape)
                           for n, v in ex.var_values.items() if n.trainable))
        embed = (cfg.vocab_size + cfg.max_position_embeddings
                 + cfg.type_vocab_size) * cfg.hidden_size
        bench_flops = (6 * (n_params - embed) + 12 * cfg.num_hidden_layers
                       * cfg.hidden_size * sl) * bs * sl
        detail["bench_formula_flops"] = bench_flops
        # >1: XLA counts more (remat replay, attention softmax);
        # <1: bench formula overcounts → MFU would be inflated
        detail["xla_over_bench_ratio"] = \
            round(detail["xla_cost_flops"] / bench_flops, 4) \
            if bench_flops else None
        if not args.skip_retrace:
            cache_size = _retrace_check()
            checks["no_retrace"] = cache_size in (1, None)
            detail["jit_cache_size_after_steps"] = cache_size

    return {"checks": checks, "ok": all(checks.values()), "detail": detail}


def _audit_zero(backend, args, dp=4):
    """ISSUE 6 donation audit: the stage-3 ZeRO step must keep every
    persistent buffer (bucket slabs + optimizer-state slabs) DONATED and
    dp-SHARDED — zero spurious full-param copies living between steps.

    Checks:
      zero_donation        every slab + slab-shaped state leaf is covered
                           by the program's aliasing pairs
      zero_state_sharded   every slab-shaped optimizer-state leaf and
                           every master slab carries PartitionSpec('dp',)
      zero_gather_in_hlo   the compiled step really all-gathers (params
                           are NOT stored full between steps)
      one_entry / no_host_transfers as in the other configs
      overlap_*            ISSUE 13 (tools/overlap_audit.py): the
                           stage-3 all-gather really overlaps forward
                           compute and the grad sync overlaps backward
                           (async-pair bracketing on TPU; dataflow-
                           availability on CPU, device_note recorded) +
                           the Perfetto-trace twin's measured-run
                           containment (trace_*)
    """
    import jax
    from jax.sharding import PartitionSpec
    from hetu_tpu.profiler import HetuProfiler

    if len(jax.devices()) < dp:
        return {"checks": {}, "ok": True,
                "detail": {"skipped": f"needs >= {dp} devices, have "
                                      f"{len(jax.devices())}"}}
    cfg, ex, fd = audit_graphs.build_bert_graph(
        batch_size=4, seq_len=128, size="tiny", dp=dp, zero=3)
    ex.run("train", feed_dict=fd)    # build + prove the live path once
    prof = HetuProfiler(ex, name="train")
    lowered = prof.lowered_text(fd)
    hlo = prof.hlo_text(fd)

    slab_spec = PartitionSpec("dp", None)
    n_slabs = len(ex._zero_slabs)
    slabs_sharded = n_slabs > 0 and all(
        v.sharding.spec == slab_spec for v in ex._zero_slabs.values())
    state_slab_leaves = [
        leaf for st in ex.opt_states.values()
        for leaf in jax.tree_util.tree_leaves(st)
        if getattr(leaf, "ndim", 0) == 2]
    state_sharded = bool(state_slab_leaves) and all(
        leaf.sharding.spec == slab_spec for leaf in state_slab_leaves)

    n_alias_prog, n_alias_compiled = _audit_aliasing(lowered, hlo)
    persistent = n_slabs + len(state_slab_leaves)
    host_ops = [op for op in ("infeed", "outfeed", "send(", "recv(")
                if op in hlo]
    n_entry = len(re.findall(r"^ENTRY ", hlo, re.MULTILINE))
    gathers = hlo.count("all-gather")
    reduces = hlo.count("all-reduce") + hlo.count("reduce-scatter")

    checks = {
        "one_entry": n_entry == 1,
        "no_host_transfers": not host_ops,
        # every persistent ZeRO buffer donated: no second full-size (or
        # even slab-size) residency for params/moments across steps
        "zero_donation": n_alias_prog >= persistent > 0,
        "zero_state_sharded": slabs_sharded and state_sharded,
        # the gather really happens inside the step — master params are
        # not stored full anywhere between steps
        "zero_gather_in_hlo": gathers > 0,
    }
    detail = {
        "workload": {"dp": dp, "batch_size": 4, "seq_len": 128,
                     "size": "tiny", "zero": 3},
        "n_slabs": n_slabs,
        "n_state_slab_leaves": len(state_slab_leaves),
        "alias_pairs_program": n_alias_prog,
        "alias_pairs_compiled": n_alias_compiled,
        "all_gather_ops": gathers,
        "reduce_ops": reduces,
        "host_ops_found": host_ops,
        "memory": ex.memory_accounting(),
    }
    # ISSUE 13: the overlap verdicts ride the zero config's artifact
    # entry — scheduled-HLO bracketing/availability + the measured-run
    # Perfetto twin (tools/overlap_audit.py audits its OWN compile of
    # the same builder at 1 MB buckets so several gathers exist)
    del ex, fd
    try:
        from tools import overlap_audit
    except ImportError:
        import overlap_audit
    ov = overlap_audit.run_overlap_audit(dp=dp)
    checks.update(ov["checks"])
    detail["overlap"] = {"mode": ov["mode"], **ov["detail"]}
    return {"checks": checks, "ok": all(checks.values()), "detail": detail}


def main():
    import argparse
    import jax

    from artifact_schema import provenance

    p = argparse.ArgumentParser()
    p.add_argument("--config", default="all",
                   choices=["all", "zero"] + list(BUILDERS))
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--seq-len", type=int, default=None)
    p.add_argument("--skip-retrace", action="store_true")
    args = p.parse_args()

    backend = jax.default_backend()
    names = list(BUILDERS) + ["zero"] if args.config == "all" \
        else [args.config]
    configs = {}
    for name in names:
        configs[name] = _audit_zero(backend, args) if name == "zero" \
            else _audit_config(name, backend, args)
        print(json.dumps({name: configs[name]["checks"],
                          "ok": configs[name]["ok"]}))

    os.makedirs(os.path.join(ROOT, "artifacts"), exist_ok=True)
    path = os.path.join(ROOT, "artifacts", f"hlo_audit_{backend}.json")
    # MERGE into the existing artifact: a quick single-config re-check
    # must not erase the other configs' evidence (each config entry keeps
    # the provenance of the run that produced it; top-level ok covers the
    # merged set)
    merged = {}
    try:
        with open(path) as f:
            prior = json.load(f).get("configs", {})
        merged = {k: v for k, v in prior.items()
                  if isinstance(v, dict) and "ok" in v}   # schema guard
    except (OSError, json.JSONDecodeError):
        pass
    prov = provenance({"configs": names})
    for name in names:
        configs[name].update(prov)
    merged.update(configs)
    out = {
        "backend": backend,
        "device_kind": jax.devices()[0].device_kind,
        "configs": merged,
        "ok": all(c["ok"] for c in merged.values()),
        **prov,
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    print(json.dumps({"backend": backend, "ok": out["ok"],
                      "per_config": {k: v["ok"] for k, v in
                                     configs.items()}}))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
