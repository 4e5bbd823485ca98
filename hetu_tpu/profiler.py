"""Profilers: per-op replay timing, HLO cost analysis, collective benchmarks.

Capability parity with the reference's ``python/hetu/profiler.py``:

* ``HetuProfiler`` (reference ``HetuProfiler:55``) — times each graph op by
  replaying it with synthesized inputs. Under XLA the *fused* step cost is
  what really matters, so the profiler additionally reports whole-step wall
  time and the compiled step's HLO cost analysis (FLOPs / bytes accessed /
  peak memory) — the honest TPU analogue of per-op CUDA-event timing.
* ``CollectiveProfiler`` (reference ``NCCLProfiler:390``) — measures
  allreduce / sendrecv (ppermute) / alltoall latency and bandwidth over the
  device mesh; feeds the auto-parallel cost models.
* Device memory via ``device.memory_stats()`` (reference uses pynvml:69-75).
"""
from __future__ import annotations

import time

import numpy as np


def _rand_like(shape_struct, rng):
    """Synthesize a concrete input for a ShapeDtypeStruct (reference
    profiler feeds random arrays, profiler.py:120)."""
    import jax.numpy as jnp
    dt = np.dtype(shape_struct.dtype)
    if np.issubdtype(dt, np.integer):
        return jnp.zeros(shape_struct.shape, dt)
    return jnp.asarray(rng.standard_normal(shape_struct.shape), dt)


class HetuProfiler:
    """Per-op replay + whole-step + HLO-cost profiling for one subexecutor.

    Usage::

        prof = ht.HetuProfiler(executor, 'train')
        per_op = prof.profile_ops(feed_dict)       # op name -> ms
        step_ms = prof.profile_step(feed_dict)     # fused step wall time
        cost = prof.hlo_cost(feed_dict)            # flops/bytes from XLA
    """

    def __init__(self, executor, name="default", repeats=10, warmup=2):
        self.ex = executor
        self.sub = executor.subexecutors[name]
        self.repeats = repeats
        self.warmup = warmup

    # -- input packing / shape inference -------------------------------------
    def _pack(self, feed_dict, materialize=False):
        """Assemble (tparams, sparams, feeds, master_key, step_idx)
        exactly like sub.run (the step folds the key itself).

        ``materialize=True`` forces stage-3 ZeRO params to full
        replicated values instead of bucket slabs — the forward-only
        abstract shape evaluation needs per-param keys."""
        from .data.dataloader import DataloaderOp
        sub, ex = self.sub, self.ex
        feeds = {}
        for node in sub.feed_nodes:
            if isinstance(node, DataloaderOp) and node not in feed_dict:
                val = node.get_arr(sub.name)
            elif node in feed_dict:
                val = feed_dict[node]
            else:
                raise ValueError(f"missing feed for {node}")
            feeds[ex._k(node)] = ex._place_feed(node, val)
        if hasattr(sub, "_pack_state"):   # ZeRO-aware packing (SubExecutor)
            tparams, sparams = sub._pack_state(materialize=materialize)
        else:
            tparams = {ex._k(n): ex.var_values[n]
                       for n in sub.trainable_vars}
            sparams = {ex._k(n): ex.var_values[n] for n in sub.state_vars}
        # PS embeddings: pull rows host-side like sub.run does, else the
        # placeholder lookup in _forward falls through to feeds and KeyErrors
        for node in sub.ps_nodes:
            idn = node.ids_node
            if ex._k(idn) in feeds:
                ids = np.asarray(feeds[ex._k(idn)])
            elif idn in feed_dict:
                ids = np.asarray(feed_dict[idn])
            elif isinstance(idn, DataloaderOp):
                ids = np.asarray(idn.get_arr(sub.name))
            else:
                raise ValueError(f"cannot resolve ids for PS embedding {node}")
            val = ex._place_feed(node, node.pull(ids))
            (tparams if sub.grad_ops else sparams)[ex._k(node)] = val
        # the executor folds per-step RNG INSIDE the jitted program; the
        # pack mirrors its (master_key, step_idx:int32) calling convention
        # (int32 keeps the traced dtype identical with and without x64)
        return tparams, sparams, feeds, ex.master_key, \
            np.int32(ex.step_counter)

    def _node_shapes(self, feed_dict):
        """Abstractly evaluate the forward graph → {node: ShapeDtypeStruct}."""
        import jax

        sub = self.sub
        tparams, sparams, feeds, key, step_idx = self._pack(
            feed_dict, materialize=True)
        key = jax.random.fold_in(key, step_idx)
        nodes = [n for n in sub.topo
                 if not hasattr(n, "loss") and n not in sub.opt_ops]

        def fwd(tp, sp, fd, k):
            env, _ = sub._forward(tp, sp, fd, k)
            return {str(n.id): env[n] for n in nodes if n in env}

        shapes = jax.eval_shape(fwd, tparams, sparams, feeds, key)
        return {n: shapes[str(n.id)] for n in nodes if str(n.id) in shapes}

    def profile_ops(self, feed_dict, log_file=None):
        """Replay every op in isolation with random inputs → {name: ms}.

        Ops whose lowering needs collective context (mesh axes) are skipped —
        their cost shows up in :meth:`profile_step` where they run fused.
        """
        import jax
        from .graph.node import LowerCtx

        shapes = self._node_shapes(feed_dict)
        rng = np.random.default_rng(0)
        results = {}
        self.skipped = {}  # op label -> reason (kept visible, not swallowed)
        for node in self.sub.topo:
            if node not in shapes or not node.inputs:
                continue
            if any(i not in shapes for i in node.inputs):
                continue
            ins = [_rand_like(shapes[i], rng) for i in node.inputs]
            key = jax.random.PRNGKey(0)

            def one(args, _node=node, _key=key):
                ctx = LowerCtx(False, _key, self.ex.mesh)
                return _node.lower(ctx, *args)

            try:
                fn = jax.jit(one)
                out = fn(ins)
                self._sync([out])
                for _ in range(self.warmup):
                    out = fn(ins)
                self._sync([out])  # warmup drained before timing
                t0 = time.perf_counter()
                for _ in range(self.repeats):
                    out = fn(ins)
                self._sync([out])
                dt = (time.perf_counter() - t0) / self.repeats
            except Exception as e:  # collective ops outside their mesh scope
                self.skipped[f"{node.op_type}:{node.name}"] = repr(e)
                continue
            results[f"{node.op_type}:{node.name}"] = dt * 1e3
        if log_file:
            with open(log_file, "a") as f:
                for k, v in sorted(results.items(), key=lambda kv: -kv[1]):
                    f.write(f"{k}\t{v:.4f} ms\n")
                for k, why in self.skipped.items():
                    f.write(f"{k}\tSKIPPED\t{why}\n")
        return results

    @staticmethod
    def _sync(outs):
        """Wait for a step's outputs (``graph.executor._sync_outs``, the
        one sync helper): consecutive training steps form a
        data-dependent chain through the params, so waiting on the last
        outputs waits on every dispatched step."""
        from .graph.executor import _sync_outs
        _sync_outs(outs)

    def profile_step(self, feed_dict):
        """Fused whole-step wall time (ms) — the number that matters on TPU."""
        self.sub.run(feed_dict)  # compile
        outs = None
        for _ in range(self.warmup):
            outs = self.sub.run(feed_dict)
        if outs is not None:
            self._sync(outs)  # warmup must finish before the timer starts
        t0 = time.perf_counter()
        for _ in range(self.repeats):
            outs = self.sub.run(feed_dict)
        self._sync(outs)
        return (time.perf_counter() - t0) / self.repeats * 1e3

    def _lowered(self, feed_dict):
        """Lower (cache-hitting) the executor's jitted step for analysis."""
        sub, ex = self.sub, self.ex
        if sub._jit is None:
            sub._build_step()
        tparams, sparams, feeds, key, step_idx = self._pack(feed_dict)
        opt_states = {ex._k(op): ex.opt_states[op] for op in sub.opt_ops}
        # only data-dependent schedules ride the host lrs input (traced
        # ones live inside the step) — mirror the live calling convention
        lrs = sub._host_lrs(ex.step_counter) if hasattr(sub, "_host_lrs") \
            else np.zeros((len(sub.opt_ops),), np.float32)
        # reuse the executor's jitted step — .lower on the same jit object
        # hits jax's compilation cache instead of recompiling
        return sub._jit.lower(tparams, sparams, opt_states, feeds, key,
                              step_idx, lrs)

    def _compiled(self, feed_dict):
        """Compile (cache-hitting) the executor's jitted step for analysis."""
        return self._lowered(feed_dict).compile()

    def hlo_cost(self, feed_dict):
        """XLA's cost analysis of the compiled step: flops, bytes accessed.

        Replaces per-op replay as the source of cost-model inputs (SURVEY.md
        §7 'per-op profiler semantics under fusion').
        """
        cost = self._compiled(feed_dict).cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        return dict(cost) if cost else {}

    def hlo_text(self, feed_dict):
        """Compiled-step HLO text (evidence of custom-call kernels, fusion
        decisions) — what the reference reads off nvprof timelines."""
        return self._compiled(feed_dict).as_text()

    def lowered_text(self, feed_dict):
        """Pre-backend (StableHLO) program text: the step's own dtype and
        donation semantics, uncontaminated by backend quirks (XLA-CPU
        upcasts bf16 dots and drops donation; tools/hlo_audit.py reads
        this for the program-level checks)."""
        return self._lowered(feed_dict).as_text()

    @staticmethod
    def all_counters():
        """{family: {kind: count}} over EVERY counter family on the
        observability registry in one call (``hetu_tpu.metrics``
        ``all_counts``): flash_fallbacks, flash_calls,
        flash_head_major, decode_attn_calls, kv_append_calls,
        mlm_head_calls, moe_calls,
        sparse_attn_calls, ssd_calls,
        emb_pallas_fallbacks, faults, elastic, autoparallel, cache, zero,
        step_cache, compile, setup_us, setup_bytes, run_plan, serve,
        decode, prefix_cache, decode_recovery, serve_rejection_reason,
        fleet, protocol, ps_rpc_bytes.  The per-family
        accessors below are thin slices of this — same registry, same
        numbers; ``obs.metrics_dump()`` adds the histogram/gauge half."""
        from .metrics import all_counts
        return all_counts()

    @staticmethod
    def latency_stats():
        """Latency-distribution snapshots from the observability
        registry's log-bucketed histograms (count/sum/min/max/mean/
        p50/p90/p99 per label): ``ps_rpc_us`` per opcode (+ payload
        bytes), ``serve_latency_us`` (per-request queue wait /
        per-batch device call), ``decode_latency_us`` (time-to-token /
        join wait / time-to-first-token ``ttft`` / engine step /
        detach->reseat stream ``recovery`` on the decode plane),
        ``step_time_us``
        per subexecutor (opt-in — ``metrics.enable_step_timing`` or
        ``HETU_STEP_TIMING=1``), and the per-run
        ``step_time_ms`` gauge."""
        from .metrics import (decode_latency_stats, rpc_stats,
                              run_gauges, serve_latency_stats,
                              step_time_stats)
        return {"ps_rpc": rpc_stats(),
                "serve_latency_us": serve_latency_stats(),
                "decode_latency_us": decode_latency_stats(),
                "step_time_us": step_time_stats(),
                "gauges": run_gauges()}

    @staticmethod
    def flash_fallbacks():
        """{reason: count} of attention dispatches that LEFT the Pallas
        flash fast path (``hetu_tpu.metrics`` registry).  Counts are per
        trace, not per step — any nonzero entry means some compiled
        program runs einsum attention; pair with ``hlo_text`` (custom-call
        evidence) to pin which.  ``HETU_REQUIRE_FLASH=1`` makes these
        hard failures instead of counters."""
        from .metrics import flash_fallback_counts
        return flash_fallback_counts()

    @staticmethod
    def flash_calls():
        """{"<block_q>x<block_k>:<one_pass|two_pass>[:packed]": count} of
        traced flash-attention calls by the block shapes the kernel
        module's rule chose for them, the backward they get (one kernel
        while the whole key range is one block, else dq + dkv) and the
        operand layout: ``:packed`` = (B, S, H·D) as the projections
        leave it, no tag = (B, H, S, D).  Per trace."""
        from .metrics import flash_call_counts
        return flash_call_counts()

    @staticmethod
    def flash_head_major():
        """{reason: count} of attention calls that KEPT the (B, H, S, D)
        layout and its transposes where the packed entry would have
        spared them: ``bias``, ``mask_shape:…``, ``context_parallel:…``,
        ``head_dim:…`` from ``MultiHeadAttention`` (per graph build),
        ``tp_splits_column_block:…`` from the op under a mesh (per
        trace)."""
        from .metrics import flash_head_major_counts
        return flash_head_major_counts()

    @staticmethod
    def decode_attn_calls():
        """{"<heads per program>x<block rows>": count} of traced
        one-token attention calls over a KV slab by the geometry
        ``ops/pallas/decode_attention.py`` chose for them — its four
        callers alike: GPT-2's packed heads (``16x128`` in the chat cell),
        the shared-KV readers (``10x512``), the grouped-query read
        (``1x4096``) and the latent read of ``ops/mla.py`` (``1x2048``:
        one head of 640-lane rows).  Per trace; empty where a decode program
        reads its slabs through jnp."""
        from .metrics import decode_attn_call_counts
        return decode_attn_call_counts()

    @staticmethod
    def kv_append_calls():
        """{"<block rows>x<lanes>:<kernel|loop>": count} of traced
        cache-row appends (``kv_cache_append_op``, a window's one-row
        ring write): the block of the state buffer one program rewrites
        — one sublane tile, all heads — and whether the write is the
        aliased kernel of ``ops/pallas/kv_append.py`` (the TPU) or the
        loop over the batch (every other backend).  A chat one-token
        program reads ``{"8x128:kernel": 48}``, glm's ``{"16x640:kernel":
        13}``.  Per trace."""
        from .metrics import kv_append_call_counts
        return kv_append_call_counts()

    @staticmethod
    def mlm_head_calls():
        """{"<rows>of<seq_len>:<gathered|all>": count} of traced
        masked-LM heads (``models.common.LabelledRowsLossOp``) by the
        rows they run on: ``80of512:gathered`` = each row's 80 labelled
        positions of 512 a round (BERT-base at 512), ``32of32:all`` = a
        capacity of the whole row.  Whether a step needed a second round
        is the graph's ``loss.mlm_overflow`` fetch.  Per trace."""
        from .metrics import mlm_head_call_counts
        return mlm_head_call_counts()

    @staticmethod
    def moe_calls():
        """{"<held>of<all>:top<k>:<ragged|kernel>": count} of traced
        dropless expert-layer products (``ops.moe.moe_experts_op``): the
        experts the layer holds of all the router scores, the experts a
        token takes, and the grouped product's path.  Per trace."""
        from .metrics import moe_call_counts
        return moe_call_counts()

    @staticmethod
    def sparse_attn_calls():
        """{"<blocks>x<rows>:<kernel|jnp>": count} of traced block-sparse
        attention reads (``ops.sparse_attention``): the blocks a head group
        reads past ``dense_len``, their rows, and whether the read is the
        selected-block kernel (a one-token step on the chip) or the masked
        read of the whole slab (a chunk, the CPU).  Per trace."""
        from .metrics import sparse_attn_call_counts
        return sparse_attn_call_counts()

    @staticmethod
    def ssd_calls():
        """{"<ssd_step_calls|ssd_chunk_calls>:<heads>x<head dim>x<state
        dim>": count} of traced Mamba-2 state updates (``ops.ssd``): the
        one-token update, which reads and writes the state once, or the
        chunk form in matrix products.  Per trace."""
        from .metrics import ssd_call_counts
        return ssd_call_counts()

    @staticmethod
    def emb_pallas_fallbacks():
        """{reason: count} of embedding-cache dispatches that LEFT the
        Pallas device-kernel path (``hetu_tpu.metrics`` registry) — the
        slot-indexed gather or the grad scatter-add compiled onto the
        ``jnp.take`` / ``jax.ops.segment_sum`` fallback instead
        (``ops/pallas/emb_cache.py``).  Flash semantics: per trace, not
        per step; ``HETU_REQUIRE_PALLAS_EMB=1`` makes these hard
        failures instead of counters."""
        from .metrics import emb_pallas_fallback_counts
        return emb_pallas_fallback_counts()

    @staticmethod
    def remat_counters():
        """{kind: count} of selective-remat plan builds
        (``hetu_tpu.metrics`` registry; ``parallel/remat.py``): segments
        found (``remat_layers_total``) and chosen for remat
        (``remat_layers_rematted``), activation bytes the plan frees
        (``remat_bytes_saved``) vs the matmul FLOPs a backward replay
        re-pays (``remat_recompute_flops``), and activation-offload
        requests served by the counted on-device fallback
        (``remat_offload_fallback`` — ``HETU_REQUIRE_OFFLOAD=1`` makes
        these hard failures).  Per plan BUILD, not per step; a run
        without ``Executor(remat=...)`` reports an empty dict."""
        from .metrics import remat_counts
        return remat_counts()

    @staticmethod
    def elastic_counters():
        """{kind: count} of elastic data-parallel resize events
        (``hetu_tpu.metrics`` registry; ``parallel/elastic.py``):
        dead-rank detections (``elastic_dead_rank``), shrinks/grows
        executed (``elastic_shrink``/``elastic_grow``), shrinks refused
        at the ``min_dp`` floor, rejoins detected, partitioned ranks
        HELD instead of resized over (``elastic_unreachable_held``),
        and cumulative resize wall time (``elastic_resize_ms``).
        Whether a grow-back recompiled is :meth:`step_cache_counters`'s
        story (``step_cache_hit`` = executable reused).  A fixed-world
        run reports an empty dict."""
        from .metrics import elastic_counts
        return elastic_counts()

    @staticmethod
    def concurrency_counters():
        """{kind: count} of concurrency-verifier runtime events
        (``hetu_tpu.metrics`` registry; ISSUE 14): lock-witness graph
        facts published by ``obs.lock_witness.WITNESS.check()`` —
        distinct lock classes seen (``concurrency_witness_locks``),
        acquisition edges observed (``concurrency_witness_edges``),
        cycles detected (``concurrency_witness_cycles`` — any nonzero
        value is a deadlock-able order) — and deterministic race-harness
        activity (``hetu_tpu.race``): forced preemptions fired
        (``concurrency_preemptions``) and rendezvous timeouts
        (``concurrency_race_timeouts``).  A run with the witness off
        and no race schedule installed reports an empty dict."""
        from .metrics import concurrency_counts
        return concurrency_counts()

    @staticmethod
    def autoparallel_counters():
        """{kind: count} of auto-parallel loop events
        (``hetu_tpu.metrics`` registry; ``autoparallel/``): plans
        searched (``autoparallel_plans_searched`` — one per
        ``search``/``search_graph`` call), candidate executables built
        fresh during measurement (``autoparallel_plans_compiled``) vs
        reused through the compiled-step cache
        (``autoparallel_candidate_cache_hits`` — one compile per
        distinct candidate, re-measures hit), candidates run for
        measured step times (``autoparallel_plans_measured``), and
        measured re-ranks that overturned the predicted best
        (``autoparallel_rerank_flips``).  A run that never searches or
        measures plans reports an empty dict."""
        from .metrics import autoparallel_counts
        return autoparallel_counts()

    @staticmethod
    def cache_counters():
        """{kind: count} of HET-cache / sparse-transport batching events
        (``hetu_tpu.metrics`` registry): cache hit/miss/evict rows, rows
        per batched push RPC, wire rows+bytes saved by ``np.unique``
        dedup, fused push+pull round trips.  Only sparse-PS traffic
        records here — a clean dense run reports an empty dict."""
        from .metrics import cache_counts
        return cache_counts()

    @staticmethod
    def zero_counters():
        """{kind: bytes} of ZeRO sharded-update traffic
        (``hetu_tpu.metrics`` registry): grad-slab bytes pinned to the
        reduce-scatter layout (``zero_reduce_scatter_bytes``),
        updated-param bytes all-gathered back (``zero_all_gather_bytes``)
        and zero-fill padding added so ragged shapes shard evenly
        (``zero_pad_bytes``).  Per-trace semantics like
        :meth:`flash_fallbacks`; a run without ``Executor(zero=...)``
        reports an empty dict."""
        from .metrics import zero_counts
        return zero_counts()

    @staticmethod
    def step_cache_counters():
        """{kind: count} of compiled-step cache events
        (``hetu_tpu.metrics`` registry): ``step_cache_hit`` — a jitted
        step was reused across Executor instances (no retrace),
        ``step_cache_miss`` — built fresh and stored,
        ``step_cache_uncachable`` — the graph signature could not be
        computed so caching was skipped."""
        from .metrics import step_cache_counts
        return step_cache_counts()

    @staticmethod
    def compile_counters():
        """{"<owner>:<what>": n} of what jax did to every program of the
        process (``hetu_tpu.metrics`` registry, folded from
        ``jax.monitoring`` by ``obs/compile_log.py``): owner ``train`` /
        ``serve`` / ``decode`` (the steps ``graph/step_cache.py`` jits)
        or ``other``; what ``programs``, ``trace_us``, ``lower_us``,
        ``backend_us`` (the XLA compile, or the read from the persistent
        cache in its place), ``cache_hits``, ``cache_misses``,
        ``cache_read_us``, ``unstored`` / ``unstored_us`` (misses jax's
        rule did not write: the next process compiles them again).  A
        steady process adds nothing; :meth:`compile_log` names the
        programs."""
        from .metrics import compile_counts
        return compile_counts()

    @staticmethod
    def compile_log():
        """The newest 256 compile records, oldest first — what to print
        after a slow start or a stall: ``{owner, program, t_end,
        trace_us, lower_us, backend_us, cache: hit|miss|off,
        cache_read_us, saved_us, stored}`` a program (``program`` is the
        subgraph's name, a serving bucket ``b<batch>``, a decode bucket
        key ``b<batch>:c<chunk>:l<len>``, or the jitted function's own
        name under owner ``other``).  A ``miss`` with ``stored`` false is
        compiled again by every later process."""
        from .obs import compile_log
        return compile_log.records()

    @staticmethod
    def setup_counters():
        """{"us": {phase: us}, "bytes": {phase: bytes}} of the program's
        own set-up phases, compilation apart: ``setup.graph`` (an
        executor's construction), ``setup.weights`` (host arrays to the
        device: ``InferenceExecutor`` weights, ``Executor.load_dict`` /
        ``load``), ``setup.state`` (a ``DecodeEngine``'s slabs, rings and
        recurrent state: construction, ``reserve`` and every growth).
        ``metrics.setup_breakdown()`` reduces these and
        :meth:`compile_counters` to five numbers."""
        from .metrics import setup_counts
        return setup_counts()

    @staticmethod
    def run_plan_counters():
        """{kind: count} of cached-run-plan / async-dispatch events
        (``hetu_tpu.metrics`` registry): ``plan_cache_hit`` /
        ``plan_cache_miss`` — per-step plan lookups (a steady feed schema
        misses once and hits every step after; climbing misses mean the
        schema churns — see the ``feed-schema-churn`` warning),
        ``feeds_pipelined`` — feed arrays whose host→device transfer was
        issued ahead of the consuming step (dataloader double-buffering
        and the ``Executor.run_steps`` driver), ``feed_pipeline_depth_hw``
        — high-water count of dataloader feed nodes with an outstanding
        prefetched transfer (one step deep per node; a max gauge, not a
        sum), and ``async_sync_points`` — forced materializations on the
        ``run(..., sync=False)`` path (numpy conversion, PS push
        boundary, checkpoint save, bounded-window overflow)."""
        from .metrics import run_plan_counts
        return run_plan_counts()

    @staticmethod
    def serve_counters():
        """{kind: count} of online-serving events (``hetu_tpu.metrics``
        registry): requests admitted/answered, batches dispatched with
        their total bucket rows (``serve_batch_rows``, real plus
        padding) of which ``serve_pad_rows`` were padding (the micro-
        batcher's bucket waste), queue-full rejections (backpressure), queue-depth high-water
        (``serve_queue_depth_hw`` — a max gauge, not a sum), PS
        failovers absorbed mid-serve, per-bucket jit wrappers constructed
        (``serve_bucket_compiles`` — compile-once means this equals the
        number of distinct buckets used; the compilations themselves are
        :meth:`compile_counters`), and read-only embedding
        refresh rows.  A process that never serves reports an empty
        dict."""
        from .metrics import serve_counts
        return serve_counts()

    @staticmethod
    def decode_counters():
        """{kind: count} of continuous-batching autoregressive-decode
        events (``hetu_tpu.metrics`` registry): tokens streamed to
        callers (``decode_tokens``), sequences joining/leaving the
        in-flight batch (``decode_joins`` / ``decode_leaves``), KV-cache
        slots recycled to a later sequence (``decode_slot_recycles``),
        engine steps (``decode_steps`` — one jitted call per token
        batch) with their per-row prefill/generate split
        (``decode_prefill_rows`` / ``decode_generate_rows``), bucket
        ladder growths (``decode_batch_grows`` / ``decode_len_grows``;
        ``decode_step_compile_us`` is the part of
        ``decode_step_dispatch_us`` that compiled a program —
        each at most one fresh compile), queue-full rejections, the
        device-resident KV-cache footprint high-water mark
        (``decode_kv_bytes_hw`` — a max gauge, not a sum) with the slab
        format it is stored in (``decode_kv_slab_format_hw``: key rows
        per 128-lane slab row, chosen by ``head_dim``), and the
        chunked-prefill accounting (ISSUE 18): steps through the
        q_len=C entry (``decode_prefill_steps``), dispatches saved vs
        token-by-token ingestion (``decode_prefill_steps_saved``), and
        logits D2H copies skipped on pure-prefill steps
        (``decode_logits_skipped``).  Where a step's time goes, in
        microseconds summed over steps (ISSUE 25):
        ``decode_step_{plan,feed,dispatch,wait,readback,host}_us`` —
        the phases of ``DecodeEngine.step`` from entry to return (chunk
        pick and plan lookup; host feeds; the jitted call; until the
        logits are ready on the device; their D2H; argmax, emission and
        bookkeeping) — and ``decode_between_steps_us``, the router
        loop's time between two steps; ``decode_join_wait_us`` sums
        submit -> seated over ``decode_joins``;
        ``decode_padded_row_tokens`` (batch bucket x chunk bucket per
        step) is the denominator of the share of computed row-tokens
        that were not padding, ``decode_chunk_width`` the chunk bucket
        summed over ``decode_prefill_steps``.  Per-token latency rides
        ``metrics.decode_latency_stats()``.  A process that never
        decodes reports an empty dict."""
        from .metrics import decode_counts
        return decode_counts()

    @staticmethod
    def prefix_cache_counters():
        """{kind: count} of shared-prefix KV-store events
        (``hetu_tpu.metrics`` registry, ISSUE 18): lookups that seated a
        sequence with pre-filled cache rows (``prefix_cache_hits``) vs
        not (``prefix_cache_misses``), prompt tokens whose prefill was
        skipped outright (``prefix_cache_hit_rows``), snapshots stored /
        deduplicated (``prefix_cache_inserts`` /
        ``prefix_cache_dup_inserts``), LRU evictions and the bytes they
        freed (``prefix_cache_evictions`` /
        ``prefix_cache_evicted_bytes``), and the resident-bytes
        high-water mark (``prefix_cache_bytes_hw`` — a max gauge, not a
        sum).  A process with no :class:`PrefixKVStore` reports an
        empty dict."""
        from .metrics import prefix_cache_counts
        return prefix_cache_counts()

    @staticmethod
    def decode_recovery_counters():
        """{kind: count} of exactly-once in-flight stream migrations
        (``hetu_tpu.metrics`` registry, ISSUE 19): streams detached off
        a dead/wedged replica with their emitted-token journal
        (``decode_recovery_detached``) and re-seated on a survivor
        through chunked prefill (``decode_recovery_reseated``), the KV
        rows that reseat actually re-prefilled
        (``decode_recovery_replayed_rows``) vs seated free off a
        PrefixKVStore hit (``decode_recovery_prefix_assisted``),
        streams failed fast with ``recovery_exhausted`` instead of
        resurrected (``decode_recovery_exhausted``), second-and-later
        recoveries of one stream (``decode_recovery_retries``), and
        stale emissions the replay-epoch fence dropped
        (``decode_recovery_fenced``).  Detach->reseat latency rides the
        ``recovery`` label of ``metrics.decode_latency_stats()``.  A
        process that never migrates a stream reports an empty dict."""
        from .metrics import decode_recovery_counts
        return decode_recovery_counts()

    @staticmethod
    def serve_rejection_counters():
        """{reason: count} of serving rejections keyed by the structured
        ``ServeRejected.reason`` vocabulary (``queue_full`` |
        ``over_max_len`` | ``deadline`` | ``shed:<class>`` |
        ``recovery_exhausted`` | ``draining``) — the per-cause breakdown
        behind the coarse ``*_rejections`` totals in ``serve_counters``
        / ``decode_counters``.  Bench artifacts and tests read this
        instead of string-matching exception text."""
        from .metrics import serve_rejection_counts
        return serve_rejection_counts()

    @staticmethod
    def fleet_counters():
        """{kind: count} of replica-set serving-tier events
        (``hetu_tpu.metrics`` registry): front-door admissions and
        replica dispatches (``fleet_admitted`` / ``fleet_dispatch``),
        replicas added/retired (``fleet_scale_out`` /
        ``fleet_scale_in``), dead-or-wedged ejections and post-recovery
        re-admissions (``fleet_replica_ejected`` /
        ``fleet_replica_readmitted``), queued requests rescued onto a
        survivor (``fleet_rescued``), admitted requests whose future
        failed (``fleet_request_failures`` — the fleet bench gates this
        at zero), autoscaler polls and bound-refused resizes
        (``fleet_autoscaler_polls`` / ``fleet_scale_refused``), and the
        live-replica high-water mark (``fleet_replicas_hw`` — a max
        gauge, not a sum).  A process with no FrontDoor reports an
        empty dict."""
        from .metrics import fleet_counts
        return fleet_counts()

    @staticmethod
    def protocol_counters():
        """{kind: count} of protocol model-checking and trace-
        conformance events (``hetu_tpu.metrics`` registry, ISSUE 20):
        transition events the ``analysis.protocol.PROTO`` recorder
        captured at the live protocol sites and buffer-cap drops
        (``protocol_events`` / ``protocol_events_dropped``), recorded
        events replayed against the models' transition relations
        (``protocol_conformance_checks``) with the replays a monitor
        rejected (``protocol_divergences`` — the chaos benches gate on
        zero) or accepted under a documented allowlist entry
        (``protocol_divergences_allowlisted``), plus checker activity:
        canonical states the BFS explored
        (``protocol_states_explored``) and invariant violations found
        (``protocol_violations`` — nonzero only under a seeded
        mutation).  A process that never verifies a protocol reports an
        empty dict."""
        from .metrics import protocol_counts
        return protocol_counts()

    @staticmethod
    def fault_counters():
        """{kind: count} of fault-tolerance events (``hetu_tpu.metrics``
        registry): transport retries/exhaustions, chaos injections,
        dead-rank exclusions, auto/emergency saves, resumes, supervisor
        restarts, the PS replication plane — shard failovers and
        promotions (``ps_failover*``/``ps_promoted``), op-log forward
        breakage (``repl_forward_failed``), redundancy repair
        (``ps_re_replicated``/``ps_re_replicate_*``), standby respawns —
        and the partition-tolerance plane: chaos-partition frame drops
        (``partition_frames_dropped``), fencing-epoch bumps/refusals
        (``ps_epoch_bumps``/``ps_epoch_refused``), stale ex-primary
        demotions (``ps_demotions``), and partitioned-but-alive ranks
        (``ps_unreachable``).  Every entry except the routine
        ``auto_save`` bookkeeping is evidence of a detected fault or a
        recovery action; a clean run — replicated or not — reports none
        of those (and an empty dict when auto-checkpointing is off)."""
        from .metrics import fault_counts
        return fault_counts()

    def memory_stats(self):
        """Per-device memory stats (reference polls pynvml)."""
        import jax
        out = {}
        for d in jax.local_devices():
            st = d.memory_stats() if hasattr(d, "memory_stats") else None
            if st:
                out[str(d)] = {k: int(v) for k, v in st.items()}
        return out

    def trace(self, feed_dict, log_dir, steps=3):
        """Capture a hardware trace of real steps into ``log_dir``
        (TensorBoard/XProf format via ``jax.profiler`` — the TPU-native
        replacement for the reference's per-op CUDA-event timeline;
        SURVEY.md §5.1), with the program's own spans in the SAME trace,
        on the device's clock: span tracing (``obs.enable``) is on for
        the capture and put back as it was after, and while a profiler
        session captures the executor opens a
        ``jax.profiler.TraceAnnotation`` at every boundary it stamps —
        ``step`` (arguments ``sub``, ``step``), ``run_plan.lookup``,
        ``feeds.place``, ``jit.dispatch``, ``executor.sync`` — as does
        every ``obs.span``.  So the ``.xplane.pb`` says what the host
        was doing over each idle gap of the device
        (``benchmarks/trace_reduce.py`` reduces one to busy/idle time,
        time by operation and the owner of each gap).  Each step is also
        wrapped in ``jax.profiler.StepTraceAnnotation`` so XProf groups
        its device slices under the step index.  Returns the directory
        for convenience."""
        import jax
        from .obs import TRACER
        if steps < 1:
            raise ValueError("trace needs steps >= 1")
        self._sync(self.sub.run(feed_dict))  # compile+warm OUTSIDE the trace
        first = int(self.ex.step_counter)
        was_on = TRACER.on
        TRACER.enable(True)
        try:
            with jax.profiler.trace(str(log_dir)):
                for i in range(steps):
                    with jax.profiler.StepTraceAnnotation(
                            "hetu_step", step_num=first + i):
                        out = self.sub.run(feed_dict)
                self._sync(out)
        finally:
            TRACER.enable(was_on)
        return str(log_dir)


class CollectiveProfiler:
    """Collective latency/bandwidth over mesh axes (reference NCCLProfiler).

    Results feed the auto-parallel cost model: ``{'allreduce': {bytes: s},
    'sendrecv': {...}, 'alltoall': {...}}`` plus ``bandwidth()`` estimates.
    """

    def __init__(self, mesh=None, axis=None, repeats=5):
        import jax
        from .context import make_mesh
        if mesh is None:
            n = len(jax.devices())
            mesh = make_mesh({"dp": n})
        self.mesh = mesh
        self.axis = axis or list(mesh.shape)[0]
        self.repeats = repeats

    def _timed(self, build_fn, nbytes):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        n = self.mesh.shape[self.axis]
        elems = max(1, nbytes // 4)
        x = jnp.zeros((n, elems), jnp.float32)
        x = jax.device_put(x, NamedSharding(self.mesh, P(self.axis, None)))
        fn = build_fn(n)
        out = fn(x)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(self.repeats):
            out = fn(x)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / self.repeats

    def profile_allreduce(self, nbytes):
        import jax
        from jax.sharding import PartitionSpec as P
        def build(n):
            @jax.jit
            def f(x):
                return jax.shard_map(
                    lambda v: jax.lax.psum(v, self.axis),
                    mesh=self.mesh, in_specs=P(self.axis, None),
                    out_specs=P(self.axis, None))(x)
            return f
        return self._timed(build, nbytes)

    def profile_sendrecv(self, nbytes):
        import jax
        from jax.sharding import PartitionSpec as P
        def build(n):
            perm = [(i, (i + 1) % n) for i in range(n)]

            @jax.jit
            def f(x):
                return jax.shard_map(
                    lambda v: jax.lax.ppermute(v, self.axis, perm),
                    mesh=self.mesh, in_specs=P(self.axis, None),
                    out_specs=P(self.axis, None))(x)
            return f
        return self._timed(build, nbytes)

    def profile_alltoall(self, nbytes):
        import jax
        from jax.sharding import PartitionSpec as P
        n = self.mesh.shape[self.axis]
        if n == 1:
            return 0.0

        def build(n):
            @jax.jit
            def f(x):
                # per-shard (1, e): split the feature dim n ways, concat on
                # the leading dim — the canonical tiled all_to_all
                return jax.shard_map(
                    lambda v: jax.lax.all_to_all(v, self.axis, 1, 0,
                                                 tiled=True),
                    mesh=self.mesh, in_specs=P(self.axis, None),
                    out_specs=P(self.axis, None))(x)
            return f
        # the feature dim must divide by n: round elems to a multiple of n
        elems = max(n, (max(1, nbytes // 4) // n) * n)
        return self._timed(build, elems * 4)

    def bandwidth_table(self, sizes=(1 << 16, 1 << 20, 1 << 24)):
        """{collective: {nbytes: (seconds, GB/s)}} over the probe sizes."""
        table = {}
        for name, fn in (("allreduce", self.profile_allreduce),
                         ("sendrecv", self.profile_sendrecv),
                         ("alltoall", self.profile_alltoall)):
            table[name] = {}
            for s in sizes:
                dt = fn(s)
                gbps = (s / dt) / 1e9 if dt > 0 else 0.0
                table[name][s] = (dt, gbps)
        return table


__all__ = ["HetuProfiler", "CollectiveProfiler"]
