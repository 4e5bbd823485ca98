"""Does the system still start on the chip?  The quickest proof there is.

``python chip_smoke.py`` drives the three main paths once on ONE TPU chip,
through the entry points a user calls, and checks what comes out:

* ``train``   — BERT-base MLM pretraining at published width through
  ``ht.Executor`` (``run`` + pipelined ``run_steps``), bf16 compute.
* ``decode``  — GPT-2 small through ``DecodeEngine`` / ``DecodeRouter``
  (continuous batching, chunked prefill), compared with the same engine
  serving one request at a time and with a plain full-sequence forward.
* ``kernels`` — every Pallas kernel the repo ships, compiled (never
  interpreted) at the widths its callers use, against its jnp reference.

``python chip_smoke.py --chips 4`` runs ONLY the multi-chip phase: dp=4
(and ZeRO-3) BERT-base and the ep=4 MoE step against their one-device runs.

One process, no child that needs the chip.  Every phase prints one JSON
object; any failed check raises, so the exit code is non-zero and the final
line is never printed.  The LAST stdout line on success is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The script refuses to run a phase when jax finds no TPU.  Nothing here is a
benchmark: times are printed as context (compile seconds are set-up), and
no number from this script is a performance claim.

The phases are plain functions that take their sizes — the CPU rehearsal
and ``tests/test_tpu_compile.py`` call ``train`` and ``decode`` at tiny
widths, where the TPU-only assertions (flash kernel in the HLO) do not
apply and are reported as such.
"""
import argparse
import gc
import json
import math
import sys
import time

import numpy as np

def _emit(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def _check(ok, what):
    if not ok:
        raise AssertionError(what)


def _on_tpu():
    import jax
    return jax.default_backend() == "tpu"


def _flash_expected(seq_len):
    """On TPU, attention at this length runs on the Pallas flash kernel
    (the repo's own measured gate); below it the XLA path is the design."""
    from hetu_tpu.ops.attention import _FLASH_MIN_LEN
    return _on_tpu() and seq_len >= _FLASH_MIN_LEN


class _CompileLog:
    """Counts jax's compile requests (every program handed to the backend
    compiler, whether compiled or read back from the persistent cache) and
    the cache hits among them — jax's own monitoring events — so a phase
    can assert 'no compile after warm-up' and report how much of its
    set-up the compile cache served."""

    _REQ = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"

    _instance = None

    @classmethod
    def get(cls):
        """The process's one log (jax keeps listeners for good)."""
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def __init__(self):
        import jax
        self.requests = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_req)
        jax.monitoring.register_event_listener(self._on_hit)

    def _on_req(self, event, duration, **_):
        self.requests += event == self._REQ

    def _on_hit(self, event, **_):
        self.hits += event == self._HIT

    def mark(self):
        return (self.requests, self.hits)

    def since(self, mark):
        return {"compile_requests": self.requests - mark[0],
                "cache_hits": self.hits - mark[1]}


def _peak_bytes(devices=None):
    """Per-device ``peak_bytes_in_use`` — the backend must report it on
    TPU; elsewhere (the CPU rehearsal) there is nothing to report."""
    import jax
    out = []
    for d in devices or jax.devices()[:1]:
        st = d.memory_stats()
        if st is None:
            _check(d.platform != "tpu", f"{d} reports no memory_stats")
            out.append(None)
        else:
            out.append(int(st["peak_bytes_in_use"]))
    return out


def _rel_err(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    _check(got.shape == want.shape, f"shape {got.shape} != {want.shape}")
    _check(np.isfinite(got).all(), "non-finite kernel output")
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-6))


# ---------------------------------------------------------------- train
def build_bert(batch, seq_len, *, size="base", compute_dtype="bfloat16",
               dp=None, zero=None, seed=0):
    """BERT MLM pretraining exactly as users build it.  Returns
    (cfg, executor, numpy feed dict of one padded batch)."""
    import hetu_tpu as ht
    from hetu_tpu.models.bert import (BertConfig, bert_pretrain_graph,
                                      synthetic_mlm_batch)
    cfg = getattr(BertConfig, size)(batch_size=batch, seq_len=seq_len)
    feeds, loss, _ = bert_pretrain_graph(cfg)
    opt = ht.optim.AdamOptimizer(1e-4)
    strategy = ht.dist.DataParallel(num_devices=dp) if dp else None
    ex = ht.Executor({"train": [loss, opt.minimize(loss), loss.mlm_overflow]},
                     seed=seed, compute_dtype=compute_dtype,
                     dist_strategy=strategy, zero=zero)
    ids, tt, labels, attn = synthetic_mlm_batch(cfg, seed=seed)
    fd = {feeds["input_ids"]: ids, feeds["token_type_ids"]: tt,
          feeds["masked_lm_labels"]: labels, feeds["attention_mask"]: attn}
    return cfg, ex, {k: np.asarray(v, np.int32) for k, v in fd.items()}


def _loss_of(out):
    return float(np.asarray(out[0].asnumpy()))


def _hlo(ex, fd):
    """The compiled train step's HLO (raises if it cannot be had)."""
    from hetu_tpu.profiler import HetuProfiler
    return HetuProfiler(ex, name="train").hlo_text(fd)


def _flash_in_hlo(ex, fd):
    """The compiled step carries the Pallas custom call."""
    return "tpu_custom_call" in _hlo(ex, fd)


def _train_loop(ex, fd, warmup, steps, log):
    """One blocking ``run`` (the compile) and ``warmup - 1`` pipelined
    warm-up steps, then ``steps`` pipelined ``run_steps(sync=False)``
    steps on the same batch.  Returns (losses,
    info); asserts that the measured window compiled nothing."""
    import jax
    from hetu_tpu.metrics import reset_run_plan_counts, run_plan_counts
    t0 = time.perf_counter()
    m0 = log.mark()
    losses = [_loss_of(ex.run("train", feed_dict=fd))]
    compile_s = time.perf_counter() - t0
    first = log.since(m0)
    # the pipelined driver places its feeds itself (a second feed schema,
    # hence a second run plan): warm it too
    losses += [_loss_of(r) for r in ex.run_steps(
        lambda i: fd, warmup - 1, name="train", sync=False)]
    reset_run_plan_counts()
    m1 = log.mark()
    t0 = time.perf_counter()
    rs = ex.run_steps(lambda i: fd, steps, name="train", sync=False)
    jax.block_until_ready([r[0].jax() if hasattr(r[0], "jax") else r[0]
                           for r in rs])
    step_s = (time.perf_counter() - t0) / steps
    window = log.since(m1)
    plan = {k: int(v) for k, v in run_plan_counts().items()}
    losses += [_loss_of(r) for r in rs]
    _check(window["compile_requests"] == 0,
           f"compiled inside the measured window: {window}")
    _check(plan.get("plan_cache_miss", 0) == 0
           and plan.get("plan_cache_hit", 0) >= steps,
           f"run plan was rebuilt after warm-up: {plan}")
    _check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    _check(losses[-1] < losses[0],
           f"loss on the repeated batch did not fall: {losses}")
    return losses, {"compile_s_setup": round(compile_s, 2),
                    "first_step_cache": first,
                    "step_wall_s": round(step_s, 4), "run_plan": plan}


def train(batch=32, seq_len=512, size="base", warmup=3, steps=6,
          loss_band=0.6):
    """A few MLM pretraining steps on one device.

    ``batch`` is ONE fixed number (no halve-on-OOM retry, no remat policy):
    the whole bf16 step compiled for a described v5e needs 6.6 GiB of
    temporaries at b32 next to 1.5 GiB of state, and 14.4 GiB at b64 —
    15.9 GiB against a 16 GB chip before the runtime's own reserve — so
    b32 it is.  ``loss_band``: the first loss must sit within this of
    ln(vocab): seeded N(0, 0.02) weights predict near-uniformly."""
    import jax
    from hetu_tpu.metrics import reset_flash_fallbacks
    from hetu_tpu.profiler import HetuProfiler
    log = _CompileLog.get()
    reset_flash_fallbacks()
    cfg, ex, fd = build_bert(batch, seq_len, size=size)
    losses, info = _train_loop(ex, fd, warmup, steps, log)
    want = math.log(cfg.vocab_size)
    _check(abs(losses[0] - want) < loss_band,
           f"first loss {losses[0]:.3f} not within {loss_band} of "
           f"ln({cfg.vocab_size}) = {want:.3f}")
    # the rows the MLM head runs on, and the fed batch's rows over that
    # capacity (0: the head ran once a step)
    over = int(ex.run("train", feed_dict=fd)[2].asnumpy())
    k = cfg.max_predictions_per_seq
    rows = f"{k}of{seq_len}:" + ("gathered" if k < seq_len else "all")
    _check(HetuProfiler.mlm_head_calls().get(rows),
           f"no MLM head over {rows} was traced: "
           f"{HetuProfiler.mlm_head_calls()}")
    flash = _flash_in_hlo(ex, fd)
    fallbacks = HetuProfiler.flash_fallbacks()
    if _flash_expected(seq_len):
        _check(flash, "no tpu_custom_call in the compiled train step")
        _check(not fallbacks, f"attention left the flash path: {fallbacks}")
    out = {"model": f"bert-{size}", "batch": batch, "seq_len": seq_len,
           "compute_dtype": "bfloat16", "losses": [round(v, 4) for v in losses],
           "ln_vocab": round(want, 4), "flash_in_hlo": flash,
           "flash_fallbacks": fallbacks,
           "mlm_head": {"rows": rows, "rows_over_capacity": over,
                        "calls": HetuProfiler.mlm_head_calls()},
           "peak_bytes_in_use": _peak_bytes()[0],
           # everything the runtime reports, so the peak can be read
           # against the compiler's own temporaries
           "memory_stats": jax.devices()[0].memory_stats(), **info}
    _emit("train", **out)
    return out


# --------------------------------------------------------------- decode
def _tap_engine_cls():
    from hetu_tpu.serving import DecodeEngine

    class TapEngine(DecodeEngine):
        """The user's engine, plus a record of the logits row behind
        every emitted token (keyed by stream)."""

        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.tap = {}

        def _emit_token(self, i, seq, tok, now):
            self.tap.setdefault(seq.req.stream, []).append(
                np.array(self.last_logits[i]))
            return super()._emit_token(i, seq, tok, now)

    return TapEngine


def _serve(router, prompts, max_new, together):
    """Submit ``prompts`` (all at once, or each after the previous one
    finished) and return the streams."""
    streams = []
    for p in prompts:
        streams.append(router.submit(p, max_new_tokens=max_new))
        if not together:
            streams[-1].result(timeout=900)
    for s in streams:
        s.result(timeout=900)
    return streams


def _compare_streams(name, got_tok, got_logits, ref_tok, ref_logits, tol,
                     teacher_forced):
    """Logits within ``tol``; tokens equal except where the reference's
    top-2 gap is below ``tol`` — every such position is reported.  Unless
    the reference was ``teacher_forced`` on the compared tokens, the two
    histories part at a flip and the rest of that stream says nothing."""
    worst, flips = 0.0, []
    for r, (gt, gl, rt, rl) in enumerate(zip(got_tok, got_logits, ref_tok,
                                             ref_logits)):
        _check(len(gt) == len(rt), f"{name}: stream {r} length differs")
        for i, (a, b) in enumerate(zip(gl, rl)):
            worst = max(worst, float(np.max(np.abs(a - b))))
            if gt[i] != rt[i]:
                top2 = np.partition(b, -2)[-2:]
                gap = float(top2[1] - top2[0])
                flips.append({"stream": r, "index": i, "got": int(gt[i]),
                              "ref": int(rt[i]), "ref_top2_gap": gap})
                _check(gap < tol, f"{name}: stream {r} token {i} differs "
                                  f"({gt[i]} vs {rt[i]}) with top-2 gap "
                                  f"{gap:.4g} >= {tol}")
                if not teacher_forced:
                    break
    _check(worst < tol, f"{name}: logits differ by {worst:.4g} >= {tol}")
    return {"max_abs_logit_diff": worst, "near_tie_flips": flips}


def decode(prompt_lens=(9, 32, 96, 160, 200), max_new=32, max_slots=8,
           max_len=256, size="small", tol=3e-2, seed=0):
    """Greedy generation for a handful of mixed-length prompts.

    ``tol`` (absolute, on logits of std ~0.5): f32 matmuls run on the MXU
    as bf16 passes at default precision, and the one-token, chunked,
    batched and full-sequence programs tile their reductions differently —
    a few 1e-3 per logit after 12 layers.  Seeded weights give near-flat
    logits, so an argmax may flip where the top-2 gap is inside ``tol``;
    each flip is printed, and a flip with a wider gap fails."""
    from hetu_tpu import metrics as ht_metrics
    from hetu_tpu.models import (GPT2Config, gpt2_decode_chunked_graph,
                                 gpt2_decode_graph)
    from hetu_tpu.models.gpt2 import gpt2_lm_graph
    from hetu_tpu.profiler import HetuProfiler
    from hetu_tpu.serving import DecodeRouter, InferenceExecutor
    log = _CompileLog.get()
    ht_metrics.reset_all()
    cfg = getattr(GPT2Config, size)(batch_size=1, seq_len=max_len,
                                    n_positions=max(1024, max_len))
    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, cfg.vocab_size, n).astype(np.int32)
               for n in prompt_lens]
    _check(max(prompt_lens) + max_new <= max_len, "prompt + new > max_len")

    feeds, logits, caches, _ = gpt2_decode_graph(cfg, max_len=max_len)
    cf, cl, cc, _ = gpt2_decode_chunked_graph(cfg, max_len=max_len)
    t0 = time.perf_counter()
    eng = _tap_engine_cls()(feeds, logits, caches, max_slots=max_slots,
                            max_len=max_len, seed=seed,
                            chunked=(cf, cl, cc))
    runs = {}
    with DecodeRouter(eng, queue_limit=len(prompts) + 8) as router:
        for name, together in (("solo", False), ("batched", True),
                               ("batched_again", True)):
            m = log.mark()
            t1 = time.perf_counter()
            streams = _serve(router, prompts, max_new, together)
            runs[name] = {
                "tokens": [s.result() for s in streams],
                "logits": [eng.tap.pop(s) for s in streams],
                "wall_s": round(time.perf_counter() - t1, 2),
                **log.since(m)}
    for r in runs.values():
        _check(all(len(t) == max_new for t in r["tokens"]),
               "a stream ended early")
    # steady state: the second batched pass compiles nothing and repeats
    # the first bit for bit
    _check(runs["batched_again"]["compile_requests"] == 0,
           f"decode compiled after warm-up: {runs['batched_again']}")
    _check(runs["batched_again"]["tokens"] == runs["batched"]["tokens"],
           "same batch, same engine, different tokens")

    # (b) plain full-sequence forward of the same weights BY NAME, teacher-
    # forced on the solo run's own tokens: one call per stream
    w = {eng.iex.var_names[n]: np.asarray(eng.iex.params[eng.iex._k(n)])
         for n in eng.iex.var_nodes}
    f2, _loss, logits2 = gpt2_lm_graph(cfg)
    full = InferenceExecutor([logits2], weights=w, buckets=(1,), seed=seed,
                             validate="off")
    fn_full = full.compiled(1)
    ref_logits = []
    for p, toks in zip(prompts, runs["solo"]["tokens"]):
        ids = np.zeros((1, max_len), np.int32)
        seq = np.concatenate([p, toks[:-1]])
        ids[0, :len(seq)] = seq
        out = np.asarray(fn_full(full.params, {full._k(f2["input_ids"]): ids})
                         [0]).reshape(max_len, cfg.vocab_size)
        ref_logits.append([out[len(p) - 1 + i] for i in range(max_new)])
    ref_tok = [[int(np.argmax(r)) for r in rows] for rows in ref_logits]
    _check(all(np.isfinite(r).all() for rows in ref_logits for r in rows),
           "reference logits not finite")

    solo, batched = runs["solo"], runs["batched"]
    cmp_full = _compare_streams("solo vs full-sequence forward",
                                solo["tokens"], solo["logits"],
                                ref_tok, ref_logits, tol, True)
    cmp_batch = _compare_streams("batched vs solo",
                                 batched["tokens"], batched["logits"],
                                 solo["tokens"], solo["logits"], tol, False)
    fallbacks = HetuProfiler.flash_fallbacks()
    _check(not any(k.startswith("backend:") for k in fallbacks)
           or not _on_tpu(), f"decode fell back by backend: {fallbacks}")
    counters = HetuProfiler.decode_counters()
    out = {"model": f"gpt2-{size}", "prompt_lens": list(prompt_lens),
           "max_new": max_new, "logit_tol": tol,
           "solo_vs_full_forward": cmp_full, "batched_vs_solo": cmp_batch,
           "runs": {k: {f: v for f, v in r.items()
                        if f not in ("tokens", "logits")}
                    for k, r in runs.items()},
           "serve_bucket_compiles":
               HetuProfiler.serve_counters().get("serve_bucket_compiles", 0),
           "decode_steps": counters.get("decode_steps", 0),
           "decode_prefill_steps": counters.get("decode_prefill_steps", 0),
           "len_bucket": eng.lb, "batch_bucket": eng.bb,
           "flash_fallbacks": fallbacks,
           "setup_and_run_s": round(time.perf_counter() - t0, 2),
           "peak_bytes_in_use": _peak_bytes()[0]}
    _emit("decode", **out)
    return out


# -------------------------------------------------------------- kernels
def _compiled_call(fn, *args):
    """jit + run ``fn`` compiled (the Mosaic custom call must be in the
    HLO on TPU: proof by presence, not by the absence of an error)."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    if _on_tpu():
        _check("tpu_custom_call" in compiled.as_text(),
               "no tpu_custom_call in the compiled kernel program")
    return compiled(*args)


def _flash_cases(interpret, small_shape, model_shapes):
    """Every flash specialization fwd+bwd at ``small_shape``, and (with
    ``model_shapes``) at the shapes the models use.  Tolerance is relative
    to the largest reference value: 2e-2 for f32 (the kernel and the XLA reference both
    run the MXU's default-precision bf16 passes but order them
    differently: ~1e-3 observed), 5e-2 for bf16 operands (8-bit mantissa
    in q/k/v/p and in the stored output)."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops.attention import sdpa_reference
    from hetu_tpu.ops.pallas.flash_attention import flash_attention
    rng = np.random.RandomState(0)
    results = {}

    def run(name, shape, dtype, fkw, rkw, tol, grad=True, kv_len=None):
        b, h, s, d = shape
        q = jnp.asarray(rng.randn(b, h, s, d), dtype)
        k, v = [jnp.asarray(rng.randn(b, h, kv_len or s, d), dtype)
                for _ in range(2)]
        diff = (0, 1, 2) + ((3,) if "bias" in fkw else ())
        extra = (fkw["bias"],) if "bias" in fkw else ()

        def flash(q, k, v, *bias):
            kw = dict(fkw, **({"bias": bias[0]} if bias else {}))
            return flash_attention(q, k, v, interpret=interpret, **kw)

        def ref(q, k, v, *bias):
            kw = dict(rkw, **({"bias": bias[0]} if bias else {}))
            return sdpa_reference(q, k, v, **kw)

        out = _compiled_call(flash, q, k, v, *extra)
        entry = {"fwd": _rel_err(out, jax.jit(ref)(q, k, v, *extra))}
        if grad:
            def loss(f):
                return lambda *a: jnp.sum(f(*a).astype(jnp.float32))
            g = _compiled_call(jax.grad(loss(flash), argnums=diff),
                               q, k, v, *extra)
            gr = jax.jit(jax.grad(loss(ref), argnums=diff))(q, k, v, *extra)
            entry["grad"] = max(_rel_err(a, b) for a, b in zip(g, gr))
        _check(max(entry.values()) < tol, f"flash {name}: {entry} >= {tol}")
        results[name] = {k_: float(f"{v_:.3g}") for k_, v_ in entry.items()}

    B, H, S, D = small_shape
    lengths = jnp.asarray(rng.randint(S // 4, S + 1, B), jnp.int32)
    km = jnp.asarray(rng.rand(B, S) > 0.3)
    fm = jnp.asarray(rng.rand(1, 1, S, S) > 0.3)
    bias = jnp.asarray(rng.randn(1, H, S, S), jnp.float32)
    kbias = jnp.asarray(rng.randn(B, 1, 1, S), jnp.float32)
    lmask = jnp.arange(S)[None, None, None, :] < lengths[:, None, None, None]
    small = {
        "dense": ({}, {}),
        "causal": ({"causal": True}, {"causal": True}),
        "lengths": ({"lengths": lengths}, {"mask": lmask}),
        "key_mask": ({"key_mask": km}, {"mask": km[:, None, None, :]}),
        "full_mask": ({"mask": fm}, {"mask": fm}),
        "bias": ({"bias": bias}, {"bias": bias}),
        "key_bias": ({"bias": kbias}, {"bias": kbias}),
        "causal_lengths_kmask": (
            {"causal": True, "lengths": lengths, "key_mask": km},
            {"causal": True,
             "mask": jnp.logical_and(lmask, km[:, None, None, :])}),
        # the rule gives every non-causal case above the whole key range
        # in one block (straight softmax, one-pass backward); these keep
        # the online-softmax forward and the dq + dkv backward on the chip
        "key_mask_128x128": ({"key_mask": km, "block_q": 128,
                              "block_k": 128}, {"mask": km[:, None, None, :]}),
        "bias_128x128": ({"bias": bias, "block_q": 128, "block_k": 128},
                         {"bias": bias}),
    }
    for name, (fkw, rkw) in small.items():
        run(name, (B, H, S, D), jnp.float32, fkw, rkw, 2e-2)
    if not model_shapes:
        return results
    # the shapes the models run: bert-base key-mask, gpt2-medium causal
    km512 = jnp.asarray(rng.rand(4, 512) > 0.3)
    run("bert_key_mask_bf16", (4, 12, 512, 64), jnp.bfloat16,
        {"key_mask": km512}, {"mask": km512[:, None, None, :]}, 5e-2)
    run("gpt2_causal_bf16", (2, 16, 1024, 64), jnp.bfloat16,
        {"causal": True}, {"causal": True}, 5e-2)
    # the decode engine's q_len=1 entry against a 256-row cache bucket
    dl = jnp.asarray(rng.randint(1, 257, 8), jnp.int32)
    run("decode_q1_lengths", (8, 12, 1, 64), jnp.float32, {"lengths": dl},
        {"mask": jnp.arange(256)[None, None, None, :]
         < dl[:, None, None, None]}, 2e-2, grad=False, kv_len=256)
    return results


def _moe_cases(interpret, tokens=8192, width=512, experts=16, k=2):
    """row_gather / sparse_dispatch / sparse_combine fwd+bwd at the moe
    config's own sizes, index maps from the real top-2 router.  A gather
    copies bits: forward tolerance is 0.  Backward sums at most k rows
    (dispatch) or contracts over ``width`` (combine d_w): 1e-5 for f32
    (summation order), 2e-2 for bf16 (8-bit mantissa partial sums)."""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops.moe import _topk_sparse_indices
    from hetu_tpu.ops.pallas.moe_dispatch import (row_gather,
                                                  sparse_combine,
                                                  sparse_dispatch)
    rng = np.random.RandomState(1)
    cap = int(tokens * k * 1.25 / experts)
    tos, sot, kos, gate_w, _ = jax.jit(
        lambda l: _topk_sparse_indices(l, k, cap))(
            jnp.asarray(rng.randn(tokens, experts), jnp.float32))
    _check(int(jnp.sum(tos < 0)) > 0 and int(jnp.sum(sot < 0)) >= 0,
           "router produced no empty slot to exercise the zero fill")

    def take(src, idx):
        rows = jnp.take(src, jnp.maximum(idx, 0), axis=0)
        return jnp.where((idx >= 0)[:, None], rows, 0)

    def ref_combine(buf, w):
        return sum(w[:, j:j + 1] * take(buf, sot[:, j]) for j in range(k))

    results = {}
    for dtype, btol in ((jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)):
        tag = jnp.dtype(dtype).name
        x = jnp.asarray(rng.randn(tokens, width), dtype)
        buf = jnp.asarray(rng.randn(experts * cap, width), dtype)
        g_slots = jnp.asarray(rng.randn(experts * cap, width), dtype)
        g_tok = jnp.asarray(rng.randn(tokens, width), dtype)
        w = gate_w.astype(dtype)
        e = {}
        e["row_gather"] = _rel_err(
            _compiled_call(lambda s, i: row_gather(s, i, interpret=interpret),
                           x, tos), take(x, tos))
        e["dispatch_fwd"] = _rel_err(
            _compiled_call(lambda t: sparse_dispatch(t, tos, sot, interpret),
                           x), take(x, tos))
        _check(e["row_gather"] == 0 and e["dispatch_fwd"] == 0,
               f"moe {tag}: a gather changed bits: {e}")
        e["dispatch_bwd"] = _rel_err(
            _compiled_call(jax.grad(lambda t: jnp.sum(
                (sparse_dispatch(t, tos, sot, interpret) * g_slots)
                .astype(jnp.float32))), x),
            jax.jit(jax.grad(lambda t: jnp.sum(
                (take(t, tos) * g_slots).astype(jnp.float32))))(x))
        e["combine_fwd"] = _rel_err(
            _compiled_call(lambda b, w: sparse_combine(
                b, w, sot, tos, kos, interpret), buf, w),
            jax.jit(ref_combine)(buf, w))
        got = _compiled_call(jax.grad(lambda b, w: jnp.sum(
            (sparse_combine(b, w, sot, tos, kos, interpret) * g_tok)
            .astype(jnp.float32)), argnums=(0, 1)), buf, w)
        want = jax.jit(jax.grad(lambda b, w: jnp.sum(
            (ref_combine(b, w) * g_tok).astype(jnp.float32)),
            argnums=(0, 1)))(buf, w)
        e["combine_bwd"] = max(_rel_err(a, b) for a, b in zip(got, want))
        _check(max(e.values()) < btol, f"moe {tag}: {e} >= {btol}")
        results[tag] = {k_: float(f"{v_:.3g}") for k_, v_ in e.items()}
    return results


def _emb_cases(interpret, widths=(16, 64, 128), rows=1 << 16, n=4096):
    """gather_rows / scatter_add_grads / sorted_segment_sum at the widths
    WDL (16), the emb scale run (64) and a lane-wide table (128) use.  The
    gather copies bits (tolerance 0).  The segment sums contract a 0/1
    indicator with f32 rows on the MXU at HIGHEST precision (multi-pass
    bf16, ~f32): 1e-4 relative covers its rounding and the summation
    order.  (At default precision the rows were rounded to one bf16 pass:
    1.3e-3 to 1.8e-3 measured on the v5e, which is why the kernel asks
    for HIGHEST.)"""
    import jax
    import jax.numpy as jnp
    from hetu_tpu.ops.pallas import emb_cache
    from hetu_tpu.ops.pallas.segment_sum import sorted_segment_sum
    rng = np.random.RandomState(2)
    results = {}
    for w in widths:
        slab = jnp.asarray(rng.randn(rows + 1, emb_cache.slab_width(w)),
                           jnp.float32)
        slots = jnp.asarray(rng.randint(0, rows + 1, n), jnp.int32)
        grad = jnp.asarray(rng.randn(n, w), jnp.float32)
        # a true unique-inverse map: every value in [0, U) occurs
        inv = jnp.asarray(np.unique(rng.randint(0, n // 8, n),
                                    return_inverse=True)[1], jnp.int32)
        seg = jnp.sort(inv)
        e = {}
        e["gather_rows"] = _rel_err(
            _compiled_call(lambda s, i: emb_cache.gather_rows(
                s, i, interpret=interpret), slab, slots),
            jnp.take(slab, slots, axis=0))
        _check(e["gather_rows"] == 0, f"emb w={w}: gather changed bits")
        e["scatter_add_grads"] = _rel_err(
            _compiled_call(lambda g, i: emb_cache.scatter_add_grads(
                g, i, interpret=interpret), grad, inv),
            jax.ops.segment_sum(grad, inv, num_segments=n))
        e["sorted_segment_sum"] = _rel_err(
            _compiled_call(lambda r, s: sorted_segment_sum(
                r, s, n, interpret=interpret), grad, seg),
            jax.ops.segment_sum(grad, seg, num_segments=n))
        _check(max(e.values()) < 1e-4, f"emb w={w}: {e} >= 1e-4")
        results[f"w{w}"] = {k_: float(f"{v_:.3g}") for k_, v_ in e.items()}
    return results


def kernels(flash_small=(2, 4, 256, 64), flash_models=True,
            moe_tokens=8192, moe_width=512, emb_rows=1 << 16, emb_n=4096):
    """Every Pallas kernel the repo ships, compiled on the chip (the
    defaults are the sizes its callers use; the CPU rehearsal passes tiny
    ones, because there the kernels are interpreted)."""
    log = _CompileLog.get()
    interpret = not _on_tpu()      # the CPU rehearsal only
    m0 = log.mark()
    t0 = time.perf_counter()
    out = {"interpreted": interpret,
           "flash": _flash_cases(interpret, flash_small, flash_models),
           "moe_dispatch": _moe_cases(interpret, moe_tokens, moe_width),
           "emb_cache": _emb_cases(interpret, rows=emb_rows, n=emb_n)}
    out.update(wall_s=round(time.perf_counter() - t0, 2),
               **log.since(m0))
    _emit("kernels", **out)
    return out


# ------------------------------------------------------------ four chips
def _assert_spread(ex, devices, what):
    """Work is really spread: dp-sharded slabs live on every device,
    replicated params too, and each device reports bytes in use."""
    want = {d.id for d in devices}
    seen = set()
    for v in list(ex._zero_slabs.values()) + [
            v for v in ex.var_values.values() if hasattr(v, "devices")]:
        ids = {d.id for d in v.devices()}
        _check(ids == want, f"{what}: an array lives on {sorted(ids)}, "
                            f"the plan says {sorted(want)}")
        seen |= ids
    _check(len(seen) == len(devices), f"{what}: {len(seen)} devices hold "
                                      f"state, expected {len(devices)}")
    used = [d.memory_stats()["bytes_in_use"] if d.memory_stats() else None
            for d in devices]
    _check(all(u is None or u > 0 for u in used),
           f"{what}: a device holds no bytes: {used}")
    return used


def _bert_losses(batch, seq_len, steps, check=None, **kw):
    """Losses of ``steps`` blocking steps; ``check(ex)`` runs while the
    executor lives, and its state is freed before the next one is built."""
    _, ex, fd = build_bert(batch, seq_len, **kw)
    losses = [_loss_of(ex.run("train", feed_dict=fd)) for _ in range(steps)]
    if check is not None:
        check(ex)
    del ex
    gc.collect()
    return losses


def build_moe(tokens, *, ep=None, compute_dtype=None, seed=0):
    """The moe config's graph (GShard top-2, 16 experts, d=512) with the
    expert axis over an ``ep`` mesh axis when asked."""
    import hetu_tpu as ht
    d, experts = 512, 16
    x = ht.placeholder_op("x", shape=(tokens, d))
    y_ = ht.placeholder_op("y", shape=(tokens, d))
    gate = ht.layers.TopKGate(d, tokens, experts, k=2, capacity_factor=1.25)
    moe = ht.layers.MoELayer(gate, ht.layers.Expert(experts, d, 4 * d))
    h, aux = moe(x)
    loss = ht.reduce_mean_op(ht.ops.mul_op(h - y_, h - y_), [0, 1]) \
        + aux * 0.01
    strategy = ht.dist.ModelParallel({"ep": ep}) if ep else None
    ex = ht.Executor(
        {"train": [loss, ht.optim.AdamOptimizer(1e-3).minimize(loss)]},
        seed=seed, compute_dtype=compute_dtype, dist_strategy=strategy)
    rng = np.random.RandomState(seed)
    fd = {x: rng.randn(tokens, d).astype(np.float32),
          y_: rng.randn(tokens, d).astype(np.float32)}
    return ex, fd


def multichip(n=4, parity_batch=16, seq_len=512, size="base", steps=5,
              real_batch_per_chip=32, moe_tokens=8192, rtol=2e-4,
              zero_rtol=2e-4):
    """Data-parallel BERT (plain and ZeRO-3) and expert-parallel MoE over
    ``n`` devices against their one-device runs.

    ``rtol`` is the repo's own dp parity gate (tests/test_parallel.py):
    sharding the batch changes only the order of f32 accumulations (the
    psum of per-shard sums), a few ulps per step.  ``zero_rtol``: ZeRO-3
    promises bit-equality with zero=0 where the backend contracts the
    update the same way in both layouts (parallel/zero.py; held on
    XLA:CPU by tests/test_zero.py).  The TPU compiler's choice is not
    known to us, so the chip is held to the dp band and the measured
    drift — 0.0 if the promise carries over — is printed.

    ``parity_batch``: fp32 BERT-base compiled for a described v5e needs
    5.8 GiB of temporaries at 16x512 and 11.6 GiB at 32x512 next to
    1.5 GiB of state; 16 leaves the one-device run room."""
    import jax
    devices = jax.devices()[:n]
    _check(len(devices) == n, f"need {n} devices, jax reports "
                              f"{len(jax.devices())}")
    out = {"devices": n}

    # 1. parity in fp32 at a global batch one device can also hold
    def check_zero3(ex):
        _check(ex._zero_plans and ex._zero_slabs, "zero=3 did not shard")
        for slab in ex._zero_slabs.values():
            per_dev = {sh.device.id: sh.data.shape
                       for sh in slab.addressable_shards}
            _check(len(per_dev) == n and all(
                shp[0] == slab.shape[0] // n for shp in per_dev.values()),
                f"zero slab not dp-sharded: {per_dev}")
        _assert_spread(ex, devices, "zero=3")

    kw = dict(size=size, compute_dtype=None)
    one = _bert_losses(parity_batch, seq_len, steps, **kw)
    dp = _bert_losses(parity_batch, seq_len, steps, dp=n,
                      check=lambda ex: _assert_spread(ex, devices, "dp"),
                      **kw)
    z3 = _bert_losses(parity_batch, seq_len, steps, dp=n, zero=3,
                      check=check_zero3, **kw)
    d_dp = float(np.max(np.abs(np.array(dp) - one) / np.abs(one)))
    d_z3 = float(np.max(np.abs(np.array(z3) - dp) / np.abs(dp)))
    out["parity_fp32"] = {"global_batch": parity_batch, "one_device": one,
                          f"dp{n}": dp, f"dp{n}_zero3": z3,
                          "max_rel_dp_vs_one": d_dp,
                          "max_rel_zero3_vs_zero0": d_z3}
    _check(one[-1] < one[0], f"one-device loss did not fall: {one}")
    _check(d_dp < rtol, f"dp={n} drifted {d_dp:.3g} >= {rtol} from the "
                        f"one-device run: {one} vs {dp}")
    _check(d_z3 < zero_rtol, f"zero=3 drifted {d_z3:.3g} >= {zero_rtol} "
                             f"from zero=0: {dp} vs {z3}")

    # 2. the real size once: bf16, n x the per-chip batch of `train`
    log = _CompileLog.get()
    cfg, ex, fd = build_bert(real_batch_per_chip * n, seq_len, size=size,
                             dp=n)
    losses, info = _train_loop(ex, fd, 2, 4, log)
    flash = _flash_in_hlo(ex, fd)
    if _flash_expected(seq_len):
        _check(flash, "no tpu_custom_call in the dp train step")
    used = _assert_spread(ex, devices, "dp real size")
    out["real_size_bf16"] = {
        "global_batch": real_batch_per_chip * n, "losses": losses,
        "flash_in_hlo": flash, "bytes_in_use_per_device": used,
        "peak_bytes_per_device": _peak_bytes(devices), **info}
    del ex
    gc.collect()

    # 3. expert parallel: the moe step with experts over ep=n
    def moe_losses(ep):
        ex, fd = build_moe(moe_tokens, ep=ep)
        ls = [_loss_of(ex.run("train", feed_dict=fd)) for _ in range(steps)]
        if ep:
            for node, v in ex.var_values.items():
                if getattr(node, "sharding", None) is not None:
                    shards = {s.device.id: s.data.shape
                              for s in v.addressable_shards}
                    _check(len(shards) == n and all(
                        sh[0] == v.shape[0] // n for sh in shards.values()),
                        f"expert weight {node.name} not ep-sharded: "
                        f"{shards}")
            hlo = _hlo(ex, fd)
            _check("all-to-all" in hlo or "all-gather" in hlo
                   or "collective-permute" in hlo,
                   "no collective in the ep step's HLO")
            colls = {c: hlo.count(c + "(") + hlo.count(c + "-start(")
                     for c in ("all-to-all", "all-gather", "all-reduce",
                               "reduce-scatter", "collective-permute")}
        else:
            colls = None
        return ls, colls

    m1, _ = moe_losses(None)
    mep, colls = moe_losses(n)
    d_ep = float(np.max(np.abs(np.array(mep) - m1) / np.abs(m1)))
    out["moe_ep"] = {"tokens": moe_tokens, "one_device": m1, f"ep{n}": mep,
                     "max_rel_ep_vs_one": d_ep, "collectives_in_hlo": colls}
    _check(all(math.isfinite(v) for v in m1 + mep), "moe loss not finite")
    _check(m1[-1] < m1[0], f"moe loss did not fall: {m1}")
    _check(d_ep < rtol, f"ep={n} drifted {d_ep:.3g} >= {rtol}: {m1} vs "
                        f"{mep}")
    _emit("multichip", **out)
    return out


# ----------------------------------------------------------------- main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip phase (the builder "
                         "runs this; the driver never does)")
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: jax found no TPU (platform {dev.platform!r}); "
              f"refusing to run a phase", file=sys.stderr)
        return 2
    from hetu_tpu.graph.executor import configure_compile_cache
    configure_compile_cache()
    t0 = time.perf_counter()
    if args.chips == 4:
        multichip(4)
    else:
        train()
        gc.collect()
        decode()
        gc.collect()
        kernels()
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.0f}s", file=sys.stderr)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
