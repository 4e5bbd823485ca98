"""Numpy-side metrics (reference ``python/hetu/metrics.py``: AUC:120,
accuracy:154, precision/recall/F1:220-315) + host-side performance
counters on the unified observability registry (ISSUE 10).

Every counter family, latency histogram and gauge here registers
against :data:`hetu_tpu.obs.registry` — the thin ``record_*`` wrappers
below are the ONE recording API the rest of the package calls, and
``obs.metrics_dump()`` / ``tools/metricsd.py`` read the same registry
back out (one source of truth; the per-family accessors are kept as
thin views over it).  The wrappers keep the exact hot-path cost of the
pre-registry module-level families: ``record_run_plan`` runs once per
training step on the dispatch path, so its counter branch is one lock +
one dict add, nothing more.  ``reset_all()`` zeroes everything;
the per-family ``reset_*`` functions remain as thin delegates.

When span tracing is on (``HETU_TRACE=1``), every fault-counter
recording also lands as an instant event on the active thread's trace
track — retries, failovers, promotions, epoch refusals and chaos
injections appear INSIDE the step/RPC span that absorbed them.
"""
from __future__ import annotations

import contextlib
import threading

import numpy as np

from .obs.compile_log import OWNERS as _OWNERS
from .obs.registry import REGISTRY
from .obs.trace import TRACER as _TR

# ------------------------------------------------------- counter suppression
# The static analyzer (``hetu_tpu.analysis``) abstractly evaluates op
# lowering rules with ``jax.eval_shape``; dispatch-time counters (flash
# fallbacks) must not record those fake traces as real dispatches.

# thread-LOCAL: an abstract trace on one thread must not silence real
# dispatch recording (or the HETU_REQUIRE_FLASH hard-fail) on another
_suppress = threading.local()


@contextlib.contextmanager
def suppress_perf_counters():
    """Scope in which dispatch-time perf counters do not record (used by
    abstract shape evaluation, which traces lowering rules without running
    them).  Per-thread: only the analyzing thread is suppressed."""
    _suppress.depth = getattr(_suppress, "depth", 0) + 1
    try:
        yield
    finally:
        _suppress.depth -= 1


def counters_suppressed():
    """True inside a :func:`suppress_perf_counters` scope (this thread)."""
    return getattr(_suppress, "depth", 0) > 0

# --------------------------------------------------- flash fallback counters
# The attention dispatchers record WHY a call left the Pallas fast path
# (backend, gate, shape, mask layout, ring chunking).  Counts are per
# TRACE, not per step — dispatch happens when jax traces the program, so
# a counter that keeps climbing across steps means the jit cache is
# thrashing, and a single nonzero entry means that workload compiled onto
# the slow path.  Surfaced by ``HetuProfiler.flash_fallbacks()``
# and ``chip_smoke.py``; ``HETU_REQUIRE_FLASH=1`` turns any
# recording into a hard failure (ops/attention.py).

_flash = REGISTRY.counter_family(
    "flash_fallbacks",
    "attention dispatches that left the Pallas flash fast path, by "
    "reason (per jax trace, not per step)")


def record_flash_fallback(reason):
    """Count one attention dispatch that fell back off the flash path."""
    if counters_suppressed():
        return  # abstract (eval_shape) trace, not a real dispatch
    _flash.inc(str(reason))


def flash_fallback_counts():
    """{reason: count} snapshot of recorded fallbacks."""
    return _flash.counts()


def reset_flash_fallbacks():
    _flash.reset()


# Which geometry the flash kernels compiled with: the block shapes come
# from a rule over the call's shapes (``flash_attention._pick_blocks``),
# and this says what it chose.  Per TRACE like the fallbacks, and like
# them silent under an abstract shape trace.
_flash_calls = REGISTRY.counter_family(
    "flash_calls",
    "flash-attention calls by block shape, backward kind and layout, "
    "\"<block_q>x<block_k>:<one_pass|two_pass>[:packed]\" (per jax trace)")


def record_flash_call(block_q, block_k, one_pass, packed=False):
    """Count one traced flash-attention call by the geometry it runs;
    ``packed``: operands (B, S, H·D) as the projections leave them, no
    head-major copy of any (the key without the tag is a (B, H, S, D)
    call)."""
    if counters_suppressed():
        return
    _flash_calls.inc(f"{block_q}x{block_k}:"
                     f"{'one_pass' if one_pass else 'two_pass'}"
                     f"{':packed' if packed else ''}")


def flash_call_counts():
    """{"<block_q>x<block_k>:<one_pass|two_pass>[:packed]": count}
    snapshot."""
    return _flash_calls.counts()


# Why an attention layer or op kept the head-major (B, H, S, D) layout —
# and with it the transposes around the kernel — where the packed entry
# would have spared them: counted like a fallback, per graph build (the
# layer's rule) or per jax trace (the op's, under a mesh).
_flash_head_major = REGISTRY.counter_family(
    "flash_head_major",
    "attention calls that kept the (B, H, S, D) layout, by reason")


def record_flash_head_major(reason):
    """Count one attention call that could not take the packed layout."""
    if counters_suppressed():
        return
    _flash_head_major.inc(str(reason))


def flash_head_major_counts():
    """{reason: count} of calls that kept the head-major layout."""
    return _flash_head_major.counts()


# Which geometry the one-token attention over a KV slab compiled with
# (``decode_attention.geometry``: heads per program and slab rows per key
# block, from the call's shape and dtype).  Per TRACE, silent under an
# abstract shape trace; a decode program that shows none here reads its
# slabs whole through the jnp path.
_decode_attn_calls = REGISTRY.counter_family(
    "decode_attn_calls",
    "KV-slab attention calls by geometry, "
    "\"<heads per program>x<block rows>[:c<chunk>]\" (per jax trace)")


def record_decode_attn_call(heads, block_rows, chunk=1):
    """Count one traced one-token attention call by its geometry, a
    chunk's (``chunk > 1``) by its positions behind it: ``"16x128:c32"``."""
    if counters_suppressed():
        return
    _decode_attn_calls.inc(f"{heads}x{block_rows}"
                           + (f":c{chunk}" if chunk > 1 else ""))


def decode_attn_call_counts():
    """{"<heads per program>x<block rows>": count} snapshot."""
    return _decode_attn_calls.counts()


# How a decode step writes its new cache rows (``ops.attention.
# _kv_cache_append``, ``ops.ssm._ring_put``): the block of the state
# buffer one program rewrites, and whether the write is the aliased
# kernel (``ops/pallas/kv_append.py``) or the loop over the batch that
# every backend but the TPU runs.  Per TRACE, as the families above.
_kv_append_calls = REGISTRY.counter_family(
    "kv_append_calls",
    "cache-row appends by block and path, "
    "\"<block rows>x<lanes>:<kernel|loop>\" (per jax trace)")


def record_kv_append_call(block_rows, lanes, how):
    """Count one traced cache-row append."""
    if counters_suppressed():
        return
    _kv_append_calls.inc(f"{block_rows}x{lanes}:{how}")


def kv_append_call_counts():
    """{"<block rows>x<lanes>:<kernel|loop>": count} snapshot."""
    return _kv_append_calls.counts()


# Which rows a masked-LM head was compiled over (``models.common.
# LabelledRowsLossOp``): ``"<K>of<S>:gathered"`` = each row's K labelled
# positions of S a round, ``"<S>of<S>:all"`` = a capacity of the whole
# row, nothing gathered.  Per TRACE, as the families above; whether a
# step needed more than one round is the graph's ``overflow`` fetch.
_mlm_head_calls = REGISTRY.counter_family(
    "mlm_head_calls",
    "masked-LM heads by rows a sequence, "
    "\"<rows>of<seq_len>:<gathered|all>\" (per jax trace)")


def record_mlm_head_call(rows, seq_len, how):
    """Count one traced masked-LM head by the rows it runs on."""
    if counters_suppressed():
        return
    _mlm_head_calls.inc(f"{rows}of{seq_len}:{how}")


def mlm_head_call_counts():
    """{"<rows>of<seq_len>:<gathered|all>": count} snapshot."""
    return _mlm_head_calls.counts()


# How a decode graph's expert layer multiplies (``ops.moe._moe_experts``):
# the experts it holds of all the router chooses among, the experts a
# token takes, and whether the grouped product is ``jax.lax.ragged_dot``
# or a kernel.  Per TRACE, as the attention families above.
_moe_calls = REGISTRY.counter_family(
    "moe_calls",
    "dropless expert-layer products by share and path, "
    "\"<held>of<all>:top<k>:<ragged|kernel>\" (per jax trace)")


def record_moe_call(held, n_experts, top_k, how="ragged"):
    """Count one traced expert-layer product."""
    if counters_suppressed():
        return
    _moe_calls.inc(f"{held}of{n_experts}:top{top_k}:{how}")


def moe_call_counts():
    """{"<held>of<all>:top<k>:<ragged|kernel>": count} snapshot."""
    return _moe_calls.counts()


# How a decode graph's block-sparse layer reads its slabs
# (``ops.sparse_attention._sparse_attention_kv``): the blocks a head group
# reads past ``dense_len`` and their rows, and whether the read is the
# selected-block kernel (``ops/pallas/decode_attention.py``) or the masked
# read of the whole slab through jnp.  Per TRACE, as the families above.
_sparse_attn_calls = REGISTRY.counter_family(
    "sparse_attn_calls",
    "block-sparse attention reads by selection and path, "
    "\"<blocks>x<rows>:<kernel|jnp>\" (per jax trace)")


def record_sparse_attn_call(blocks, rows, how):
    """Count one traced block-sparse read."""
    if counters_suppressed():
        return
    _sparse_attn_calls.inc(f"{blocks}x{rows}:{how}")


def sparse_attn_call_counts():
    """{"<blocks>x<rows>:<kernel|jnp>": count} snapshot."""
    return _sparse_attn_calls.counts()


# Which form of the Mamba-2 state update a decode graph traced
# (``ops.ssd._ssd_chunk``): the one-token update (the state read once and
# written once) or the chunk form in matrix products, and over what state.
# Per TRACE, as the families above.
_ssd_calls = REGISTRY.counter_family(
    "ssd_calls",
    "Mamba-2 state updates by form and state, "
    "\"<ssd_step_calls|ssd_chunk_calls>:<heads>x<head dim>x<state dim>\" "
    "(per jax trace)")


def record_ssd_call(chunk, heads, head_dim, d_state):
    """Count one traced Mamba-2 state update of a ``chunk``-column call."""
    if counters_suppressed():
        return
    form = "ssd_step_calls" if chunk == 1 else "ssd_chunk_calls"
    _ssd_calls.inc(f"{form}:{heads}x{head_dim}x{d_state}")


def ssd_call_counts():
    """{"<form>:<heads>x<head dim>x<state dim>": count} snapshot."""
    return _ssd_calls.counts()


# ---------------------------------------------- embedding Pallas fallbacks
# The device-resident embedding-cache dispatchers
# (``ops/pallas/emb_cache.py``) record WHY a gather / grad scatter-add
# left the Pallas kernel path (backend, forced interpret policy).  Flash
# semantics: counts are per jax TRACE, not per step — one nonzero entry
# means that workload compiled onto the fallback (``jnp.take`` /
# ``jax.ops.segment_sum``) path, and a count climbing across steps means
# the jit cache is thrashing.  Surfaced by
# ``HetuProfiler.emb_pallas_fallbacks()``;
# ``HETU_REQUIRE_PALLAS_EMB=1`` turns any
# recording into a hard failure (emb_cache._note_fallback).

_emb_pallas = REGISTRY.counter_family(
    "emb_pallas_fallbacks",
    "embedding-cache dispatches that left the Pallas device-kernel "
    "path, by reason (per jax trace, not per step)")


def record_emb_pallas_fallback(reason):
    """Count one embedding-cache dispatch that fell back off Pallas."""
    if counters_suppressed():
        return  # abstract (eval_shape) trace, not a real dispatch
    _emb_pallas.inc(str(reason))


def emb_pallas_fallback_counts():
    """{reason: count} snapshot of recorded embedding-kernel fallbacks."""
    return _emb_pallas.counts()


def reset_emb_pallas_fallbacks():
    _emb_pallas.reset()


# ------------------------------------------------------ fault-event counters
# The fault-tolerance layer records every detection/recovery event here so
# a run can PROVE what happened: transport retries (``ps_rpc_retry``),
# exhausted peers (``ps_peer_unreachable``), injected chaos
# (``chaos_drop``/``chaos_kill_ps``/``chaos_kill_primary``/...), dead
# ranks excluded from a partial-reduce group
# (``preduce_dead_rank_excluded``), checkpoints written/skipped
# (``auto_save``, ``emergency_save``, ``ckpt_incomplete_skipped``),
# resumes (``resume``), supervisor restarts (``supervisor_restart``),
# standby respawns (``standby_spawn``), and the PS replication plane:
# client-side failovers (``ps_failover`` detected, ``ps_failover_promoted``
# rerouted, ``ps_failover_failed`` both copies gone,
# ``ps_failover_primary_reported_alive`` possible partition), server-side
# promotions (``ps_promoted``), op-log forward breakage
# (``repl_forward_failed``), redundancy repair (``ps_re_replicated``
# / ``ps_re_replicate_deferred`` / ``ps_re_replicate_failed``), and the
# partition-tolerance plane: frames the chaos DSL's partition window
# dropped (``partition_frames_dropped``), fencing-epoch advances at
# promotion (``ps_epoch_bumps``), frames refused for carrying a stale or
# deposed lineage's epoch (``ps_epoch_refused``), stale ex-primaries that
# stopped serving on learning of a newer lineage (``ps_demotions``), and
# heartbeat-silent ranks that still answered a direct probe
# (``ps_unreachable`` — partition, not crash).
# Invariant (asserted by the chaos + replication tests): every counter
# EXCEPT the ``auto_save`` bookkeeping records a detected fault or a
# recovery action, so a clean run — replicated or not — reports none of
# those, and a clean run without auto-checkpointing records nothing at
# all.  Surfaced by ``HetuProfiler.fault_counters()``; the fault scenarios
# (``tests/scenarios.py``) assert on them.

_faults = REGISTRY.counter_family(
    "faults",
    "fault-tolerance events: detections, injections, recoveries "
    "(a clean run records none but auto_save bookkeeping)")


def record_fault(kind, n=1):
    """Count one fault-tolerance event (detection, injection, recovery).
    With tracing on, the event also lands as an instant on the calling
    thread's trace track — a failover/retry/epoch-refusal is visible
    INSIDE the step or RPC span that absorbed it."""
    kind = str(kind)
    _faults.inc(kind, n)
    if _TR.on:
        _TR.instant("fault:" + kind, cat="fault")


def fault_counts():
    """{kind: count} snapshot of recorded fault events."""
    return _faults.counts()


def reset_faults():
    _faults.reset()


# ------------------------------------------------------ elastic-resize counters
# The elastic data-parallel layer (``parallel/elastic.py``) records every
# world-resize event here: dead ranks detected (``elastic_dead_rank``),
# shrinks executed (``elastic_shrink``) and shrinks refused at the
# ``min_dp`` floor (``elastic_shrink_refused``), rejoins detected
# (``elastic_rejoin``) and grows executed (``elastic_grow``),
# heartbeat-silent-but-probe-answering ranks HELD instead of resized
# over (``elastic_unreachable_held`` — partition evidence, the fencing
# epochs' problem), and the cumulative resize wall time
# (``elastic_resize_ms`` — detection poll to resized executor, summed
# over resizes; per-event recovery_ms lives on the controller's
# timeline).  Whether a resize recompiled or reused an executable is
# the step-cache family's story (``step_cache_hit`` on a grow-back).
# Invariant (asserted by the elastic tests): a fixed-world run records
# nothing here.  Surfaced by ``HetuProfiler.elastic_counters()``.

_elastic = REGISTRY.counter_family(
    "elastic",
    "elastic data-parallel resize events: dead-rank detections, "
    "shrinks/grows, held partitions (a fixed-world run records none)")


def record_elastic(kind, n=1):
    """Count ``n`` elastic-resize events of ``kind``.  With tracing on,
    the event also lands as an instant on the calling thread's track —
    a shrink/grow is visible next to the step spans it sits between."""
    kind = str(kind)
    if n:
        _elastic.inc(kind, int(n))
    if _TR.on:
        _TR.instant("elastic:" + kind, cat="elastic")


def elastic_counts():
    """{kind: count} snapshot of elastic-resize events."""
    return _elastic.counts()


def reset_elastic_counts():
    _elastic.reset()


# ----------------------------------------------------- selective-remat counters
# The remat policy layer (``parallel/remat.py``) records each plan build
# here: segments found (``remat_layers_total``) and chosen for remat
# (``remat_layers_rematted``), the activation bytes the chosen plan
# frees (``remat_bytes_saved``) vs the matmul FLOPs a backward replay
# re-pays (``remat_recompute_flops``), and activation-offload requests
# served by the counted on-device fallback because the backend cannot
# host-offload (``remat_offload_fallback`` — flash-dispatcher style,
# ``HETU_REQUIRE_OFFLOAD=1`` hard-fails instead).  Counts are per plan
# BUILD, not per step (flash-counter semantics: a count climbing across
# steps means executors are being rebuilt).  Surfaced by
# ``HetuProfiler.remat_counters()``; a
# run without ``Executor(remat=...)`` records nothing.

_remat = REGISTRY.counter_family(
    "remat",
    "selective-remat plan builds: segments rematted, bytes freed vs "
    "recompute flops, offload fallbacks (empty without remat=)")


def record_remat(kind, n=1):
    """Count ``n`` selective-remat events of ``kind`` (plan builds,
    offload fallbacks)."""
    if counters_suppressed():
        return  # abstract (eval_shape) trace, not a real build
    if n:
        _remat.inc(str(kind), int(n))


def remat_counts():
    """{kind: count} snapshot of selective-remat plan counters."""
    return _remat.counts()


def reset_remat_counts():
    _remat.reset()


# ------------------------------------------------- concurrency-verifier counters
# The concurrency verifier (ISSUE 14) records its runtime evidence here:
# the lock-witness (``obs/lock_witness.py``, ``HETU_LOCK_WITNESS=1``)
# publishes distinct lock classes seen (``concurrency_witness_locks``),
# acquisition-graph edges observed (``concurrency_witness_edges``) and
# cycles detected (``concurrency_witness_cycles`` — any nonzero count is
# a deadlock-able order, the tier-1 witness smoke asserts ZERO) at each
# ``WITNESS.check()`` as deltas since the previous check; the
# deterministic race harness (``hetu_tpu.race``) counts forced
# preemptions actually fired (``concurrency_preemptions`` — a loser
# thread held at its site until the winner's region completed) and
# rendezvous that timed out because the peer site never arrived
# (``concurrency_race_timeouts`` — the harness's no-deadlock escape
# hatch; a deterministic repro should count zero).  Invariant: a run
# with the witness off and no race schedule installed records nothing.
# Surfaced by ``HetuProfiler.concurrency_counters()``.

_concurrency = REGISTRY.counter_family(
    "concurrency",
    "concurrency-verifier runtime events: witness locks/edges/cycles, "
    "race-harness preemptions (empty without HETU_LOCK_WITNESS/"
    "HETU_RACE)")


def record_concurrency(kind, n=1):
    """Count ``n`` concurrency-verifier events of ``kind`` (witness
    graph deltas, race-harness preemptions/timeouts)."""
    if n:
        _concurrency.inc(str(kind), int(n))


def concurrency_counts():
    """{kind: count} snapshot of concurrency-verifier counters."""
    return _concurrency.counts()


def reset_concurrency_counts():
    _concurrency.reset()


# ------------------------------------------------- autoparallel-loop counters
# The auto-parallel search/execute/measure loop (``autoparallel/``)
# records its lifecycle here: searches that produced a plan
# (``autoparallel_plans_searched`` — one per :func:`search`/
# :func:`search_graph` call), candidate executables built fresh by the
# measurement pass (``autoparallel_plans_compiled`` — a compiled-step
# cache miss while measuring) vs candidates whose executable was REUSED
# (``autoparallel_candidate_cache_hits`` — the one-compile-per-candidate
# claim: re-measuring a plan must hit, not rebuild), candidates actually
# run for measured step times (``autoparallel_plans_measured``), and
# re-ranks where the MEASURED ordering overturned the predicted best
# (``autoparallel_rerank_flips`` — each flip is a mispricing the
# feedback loop corrected).  Invariant (asserted by the tests): a run
# that never searches or measures plans records nothing.  Surfaced by
# ``HetuProfiler.autoparallel_counters()`` and ``tools/plan_diff.py``.

_autoparallel = REGISTRY.counter_family(
    "autoparallel",
    "auto-parallel loop events: plans searched/compiled/measured, "
    "candidate executable reuse, measured re-rank flips (empty without "
    "autoparallel use)")


def record_autoparallel(kind, n=1):
    """Count ``n`` auto-parallel loop events of ``kind`` (searches,
    candidate compiles/cache hits, measurements, rerank flips)."""
    if n:
        _autoparallel.inc(str(kind), int(n))


def autoparallel_counts():
    """{kind: count} snapshot of auto-parallel loop counters."""
    return _autoparallel.counts()


def reset_autoparallel_counts():
    _autoparallel.reset()


# ------------------------------------------------- cache / sparse-RPC counters
# The HET embedding cache (``ps/dist_store.py:DistCacheTable``) and the
# sparse transport (``DistributedStore.pull/push/push_pull``) record their
# batching wins here: rows served from cache vs refreshed
# (``emb_cache_hit_rows`` / ``emb_cache_miss_rows``), rows evicted
# (``emb_cache_evict_rows``), rows pushed and the number of BATCHED push
# round trips that carried them (``emb_cache_push_rows`` /
# ``emb_cache_push_rpcs`` — the pre-PR per-key path paid one RPC per row),
# redundant rows/bytes that client-side ``np.unique`` dedup eliminated
# BEFORE the shard fanout (``ps_dedup_{pull,push}_{rows,bytes}_saved`` —
# the saving covers the local shard's share too, so on a w-rank store
# (w-1)/w of it is wire traffic), and round trips where a fused
# ``OP_PUSH_PULL`` frame carried both a push and a pull shard
# (``ps_push_pull_fused_rpcs``), and grad segment-sums that ran on the
# scipy-absent ``np.add.at`` host fallback (``emb_grad_host_fallback``
# — scipy ships with jax, so any count here means an exotic build lost
# the CSR fast path; device-resident tables skip the host pass
# entirely).  Invariant (asserted by the tests):
# only sparse-PS traffic records here, so a clean dense run reports an
# empty dict.  Surfaced by ``HetuProfiler.cache_counters()``.

_cache = REGISTRY.counter_family(
    "cache",
    "HET embedding-cache / sparse-transport batching events (a clean "
    "dense run records nothing)")


def record_cache(kind, n=1):
    """Count ``n`` cache/sparse-transport events of ``kind``."""
    if n:
        _cache.inc(str(kind), int(n))


def cache_counts():
    """{kind: count} snapshot of cache/dedup/batching counters."""
    return _cache.counts()


def reset_cache_counts():
    _cache.reset()


# ------------------------------------------------- ZeRO weight-update counters
# The ZeRO sharded-update layer (``parallel/zero.py``) records its
# collective traffic and padding waste here: grad-slab bytes pinned to the
# sharded layout (``zero_reduce_scatter_bytes`` — what the partitioner may
# lower as a reduce-scatter), updated-param bytes gathered back
# (``zero_all_gather_bytes``), and zero-fill bytes added so ragged shapes
# shard evenly (``zero_pad_bytes``).  Counts are per TRACE, not per step
# (the slabs are built when jax traces the program — flash-counter
# semantics): a count that keeps climbing across steps means the jit cache
# is thrashing.  Surfaced by ``HetuProfiler.zero_counters()``;
# a run without ``zero=`` records nothing.

_zero = REGISTRY.counter_family(
    "zero",
    "ZeRO sharded-update collective/padding bytes (per jax trace; "
    "empty without Executor(zero=...))")


def record_zero(kind, n=1):
    """Count ``n`` bytes/events of ZeRO sharded-update traffic."""
    if counters_suppressed():
        return  # abstract (eval_shape) trace, not a real build
    if n:
        _zero.inc(str(kind), int(n))


def zero_counts():
    """{kind: bytes} snapshot of ZeRO collective/padding counters."""
    return _zero.counts()


def reset_zero_counts():
    _zero.reset()


# -------------------------------------------------- compiled-step cache counters
# The executor's compiled-executable cache (``graph/step_cache.py``) keys a
# jitted step on (graph signature, mesh, compute_dtype, zero stage) and
# reuses it across Executor instances; hits skip a full XLA retrace.
# ``step_cache_hit`` / ``step_cache_miss`` count lookups;
# ``step_cache_uncachable`` counts graphs whose signature could not be
# computed (caching skipped, never wrong-cached).  Surfaced by
# ``HetuProfiler.step_cache_counters()``.

_step_cache = REGISTRY.counter_family(
    "step_cache",
    "compiled-step cache lookups: hit / miss / uncachable")


def record_step_cache(kind, n=1):
    """Count one compiled-step cache event (hit/miss/uncachable)."""
    _step_cache.inc(str(kind), n)


def step_cache_counts():
    """{kind: count} snapshot of compiled-step cache events."""
    return _step_cache.counts()


def reset_step_cache_counts():
    _step_cache.reset()


# ------------------------------------------------ compile / set-up counters
# What jax did to every program of the process, folded from its own
# ``jax.monitoring`` events by ``obs/compile_log.py`` (one record a
# program; ``HetuProfiler.compile_log()`` keeps the newest).  Keys are
# ``<owner>:<what>``: the owner is ``train`` (a ``SubExecutor`` step),
# ``serve`` (an ``InferenceExecutor`` bucket), ``decode`` (a
# ``DecodeEngine`` program) — ``graph/step_cache.py`` names what it jits
# — or ``other`` (every other ``jit``); what is
#   ``programs``       programs that reached the backend's compiler
#   ``trace_us``       Python tracing of the function (jaxpr)
#   ``lower_us``       jaxpr -> MLIR module
#   ``backend_us``     the backend interval: the XLA compile, or the read
#                      from the persistent cache in its place
#   ``cache_hits``     programs read back from the persistent cache
#   ``cache_misses``   programs compiled while the cache was on
#   ``cache_read_us``  retrieval time of the hits (part of backend_us)
#   ``unstored``       misses jax did NOT write (its rule: compile time
#                      under jax_persistent_cache_min_compile_time_secs,
#                      host callbacks, process > 0) ...
#   ``unstored_us``    ... and their backend time: what EVERY later process
#                      pays again
# A steady process adds nothing here: a count that climbs across steps
# is a program compiling again.  ``setup_us`` / ``setup_bytes`` hold the
# program's own set-up phases (``obs.compile_log.SetupPhase``):
# ``setup.graph`` an executor's construction (topology, lints, plan),
# ``setup.weights`` host arrays to the device, ``setup.state`` a decode
# engine's slabs, rings and recurrent state.  Surfaced by
# ``HetuProfiler.compile_counters()`` / ``setup_counters()``.

_compile = REGISTRY.counter_family(
    "compile",
    "programs compiled or read from the persistent cache, by "
    "<owner>:<what> (us of tracing / lowering / backend, cache hits and "
    "misses, misses not stored)")
_setup_us = REGISTRY.counter_family(
    "setup_us",
    "us of the program's own set-up phases (setup.graph / setup.weights "
    "/ setup.state), compilation apart")
_setup_bytes = REGISTRY.counter_family(
    "setup_bytes",
    "bytes the set-up phases moved to or allocated on the device")

SETUP_PHASES = ("setup.graph", "setup.weights", "setup.state")


def record_compile(rec):
    """Fold one ``obs.compile_log`` record into ``compile_counts()``
    (and a ``decode`` program's seconds into ``decode_step_compile_us``:
    it compiled inside a step's ``dispatch`` phase)."""
    owner = rec["owner"]
    add = {"programs": 1, "trace_us": rec["trace_us"],
           "lower_us": rec["lower_us"], "backend_us": rec["backend_us"]}
    if rec["cache"] == "hit":
        add.update(cache_hits=1, cache_read_us=rec["cache_read_us"])
    elif rec["cache"] == "miss":
        add["cache_misses"] = 1
        if not rec["stored"]:
            add.update(unstored=1, unstored_us=rec["backend_us"])
    for what, n in add.items():
        if n:
            _compile.inc(f"{owner}:{what}", int(n))
    if owner == "decode":
        record_decode("decode_step_compile_us", rec["trace_us"]
                      + rec["lower_us"] + rec["backend_us"])


def compile_counts():
    """{"<owner>:<what>": n} snapshot of the compile counters."""
    return _compile.counts()


def record_setup(phase, us, nbytes=0):
    """One set-up phase ended: its microseconds and bytes."""
    _setup_us.inc(str(phase), int(us))
    if nbytes:
        _setup_bytes.inc(str(phase), int(nbytes))


def setup_counts():
    """{"us": {phase: us}, "bytes": {phase: bytes}} of the set-up
    phases."""
    return {"us": _setup_us.counts(), "bytes": _setup_bytes.counts()}


def setup_breakdown(owners=_OWNERS):
    """Where the program's share of a set-up went, in the five numbers a
    ``setup_s`` reading is explained by — over ``owners`` (the program's
    own programs; ``other`` holds helpers and whatever else the process
    jits), each None where nothing was recorded::

        compile_s              backend_us: compiling the programs, or
                               reading them back (cache_read_us is PART
                               of a hit's backend interval, not beside it)
        trace_lower_s          trace_us + lower_us
        compile_cache_hit_pct  100 * hits / (hits + misses); None with
                               the persistent cache off
        compile_unstored_s     unstored_us: compiled, and not kept
        program_build_s        setup.graph + setup.weights + setup.state
    """
    c = compile_counts()

    def total(what):
        return sum(c.get(f"{o}:{what}", 0) for o in owners)

    out = dict.fromkeys(("compile_s", "trace_lower_s",
                         "compile_cache_hit_pct", "compile_unstored_s",
                         "program_build_s"))
    if total("programs"):
        out["compile_s"] = total("backend_us") / 1e6
        out["trace_lower_s"] = (total("trace_us") + total("lower_us")) / 1e6
        out["compile_unstored_s"] = total("unstored_us") / 1e6
        asked = total("cache_hits") + total("cache_misses")
        if asked:
            out["compile_cache_hit_pct"] = 100.0 * total("cache_hits") / asked
    us = _setup_us.counts()
    if us:
        out["program_build_s"] = sum(
            us.get(p, 0) for p in SETUP_PHASES) / 1e6
    return out


# ------------------------------------------------------ run-plan counters
# The executor's cached run plans (``graph/run_plan.py``) record the
# dispatch-path behaviour here: ``plan_cache_hit`` / ``plan_cache_miss``
# count per-step plan lookups (a steady feed schema hits every step after
# the first — the per-step Python work of resolving feeds, placement
# closures and validation is amortized to zero; misses climbing across
# steps mean the feed schema is churning, see the ``feed-schema-churn``
# warning), ``feeds_pipelined`` counts feed arrays whose host→device
# transfer was issued ahead of the step that consumed them (the
# double-buffered feed pipeline: dataloader prefetch + the
# ``Executor.run_steps`` driver), ``feed_pipeline_depth_hw`` is the
# high-water count of dataloader feed NODES with an outstanding
# prefetched transfer — the double-buffer is one step deep per node, so
# a 3-loader graph tops out at 3 (gauge semantics: the stored value is
# the MAX ever seen), and ``async_sync_points``
# counts the places where non-blocking stepping (``run(..., sync=False)``)
# was FORCED to materialize — a numpy conversion, a PS push boundary, a
# checkpoint save, or the bounded in-flight window filling up.  Surfaced
# by ``HetuProfiler.run_plan_counters()``.

_run_plan = REGISTRY.counter_family(
    "run_plan",
    "cached-run-plan / async-dispatch events: plan cache hits/misses, "
    "pipelined feeds, forced async sync points")


def record_run_plan(kind, n=1):
    """Count ``n`` run-plan/dispatch events of ``kind``; kinds ending in
    ``_hw`` are high-water gauges (the stored value is the max seen).
    This recorder runs once per training step on the dispatch hot path
    — the plain-counter branch is kept deliberately lean."""
    if kind.__class__ is not str:
        kind = str(kind)
    if not kind.endswith("_hw"):
        if n:
            _run_plan.inc(kind, int(n))
            if kind == "async_sync_points" and _TR.on:
                # trace view of the forced materialization (numpy
                # convert, PS push boundary, save drain, window full)
                _TR.instant("async_sync_point", cat="async")
    else:
        _run_plan.max_gauge(kind, int(n))


def run_plan_counts():
    """{kind: count} snapshot of run-plan / async-dispatch counters."""
    return _run_plan.counts()


def reset_run_plan_counts():
    _run_plan.reset()


# ------------------------------------------------------- serving counters
# The online-serving layer (``hetu_tpu.serving``) records its request /
# batching behaviour here: requests admitted (``serve_requests``) and
# answered (``serve_responses``), batches dispatched (``serve_batches``)
# with the TOTAL bucket rows they ran at (``serve_batch_rows`` — real
# plus padding), of which ``serve_pad_rows`` were padding added to reach
# a legal bucket (the micro-batcher's waste: real rows =
# ``serve_batch_rows - serve_pad_rows``), queue-full rejections
# (``serve_rejections`` — the backpressure path), PS failovers absorbed
# MID-SERVE (``serve_failovers``), dispatched batches re-run ONCE after
# a transient device-call failure before their futures fail
# (``serve_batch_retries``, ISSUE 19), per-bucket jit wrappers constructed
# (``serve_bucket_compiles`` — the compile-once claim is exactly "this
# equals the number of distinct buckets used"; it counts
# ``step_cache.lookup_or_build_serve`` making a ``jax.jit``, NOT the XLA
# compilation, which happens at the wrapper's first call and is counted,
# with its seconds, under ``compile_counts()``), read-only embedding
# refreshes (``serve_emb_refresh_rows``), and the queue-depth high-water
# mark (``serve_queue_depth_hw`` — gauge semantics: the recorded value is
# the MAX ever seen, not a sum).  Surfaced by
# ``HetuProfiler.serve_counters()``; a
# process that never serves reports an empty dict.

_serve = REGISTRY.counter_family(
    "serve",
    "online-serving request/batching events (empty in a process that "
    "never serves)")


def record_serve(kind, n=1):
    """Count ``n`` serving events of ``kind``; kinds ending in ``_hw``
    are high-water gauges (the stored value is the max seen)."""
    kind = str(kind)
    if kind.endswith("_hw"):
        _serve.max_gauge(kind, int(n))
    elif n:
        _serve.inc(kind, int(n))


def serve_counts():
    """{kind: count} snapshot of serving counters."""
    return _serve.counts()


def reset_serve_counts():
    """Reset the serving counters AND the serving latency histograms —
    one serving run's telemetry, one reset."""
    _serve.reset()
    _serve_latency.reset()


# ------------------------------------------------------- decode counters
# The autoregressive-decode serving plane (``hetu_tpu.serving.decode``)
# records its token/batch behaviour here: tokens emitted to streams
# (``decode_tokens``), sequences joining (``decode_joins``) and leaving
# (``decode_leaves``) the in-flight continuous batch, KV-cache slots
# recycled to a later sequence (``decode_slot_recycles``), engine steps
# (``decode_steps`` — one jitted decode call per token batch) split into
# the per-row prefill/generate accounting (``decode_prefill_rows``: rows
# that consumed a PROMPT token, building KV cache without emitting;
# ``decode_generate_rows``: rows that consumed a generated token), bucket
# ladder growths (``decode_batch_grows`` / ``decode_len_grows`` — each one
# is at most one fresh compile, the compile-once-per-(batch, len) bucket
# claim), queue-full rejections (``decode_rejections``), and the
# device-resident KV-cache footprint high-water mark
# (``decode_kv_bytes_hw`` — gauge semantics: the recorded value is the MAX
# ever seen) beside the slab format those bytes are stored in
# (``decode_kv_slab_format_hw``, a gauge too: key rows per slab row — 1
# for plain (B, H, L, D) rows, 2 for GPT-2's 64-wide heads, two to a
# 128-lane row; ``ops.attention.kv_slab_shape``, chosen by ``head_dim``
# alone).  Chunked prefill (ISSUE 18) adds the prompt-ingestion
# accounting: ``decode_prefill_steps`` (steps that ran the q_len=C
# chunked entry), ``decode_prefill_steps_saved`` (dispatches a chunked
# step avoided vs the token-by-token path: the widest row's chunk minus
# one, per chunked step), and ``decode_logits_skipped`` (steps that
# skipped the (batch, vocab) logits D2H because no row was past its
# prompt).  The step accounts for its own time (ISSUE 25), integer
# microseconds summed over steps, the phases touching and not overlapping
# inside one ``decode.step`` interval per loop iteration — under
# ``DecodeEngine.step`` all six of one step; under the router, which
# launches step n+1 before it collects step n (ISSUE 32), the first three
# of the step launched and the last three of the step launched the
# iteration before:
#   ``decode_step_plan_us``      chunk pick, bucket growth, plan lookup
#   ``decode_step_feed_us``      building the host feeds
#   ``decode_step_dispatch_us``  the jitted call until it returns, and the
#                                host state the launch advances
#   ``decode_step_compile_us``   the PART of ``dispatch`` in which jax traced,
#                                lowered and compiled (or read back) a
#                                ``decode`` program: a bucket's first call.
#                                0 over a steady window; the newest record
#                                of ``HetuProfiler.compile_log()`` names
#                                the program that moved it
#   ``decode_step_wait_us``      until the token ids are ready on the device
#   ``decode_step_readback_us``  their (batch,) D2H copy
#   ``decode_step_host_us``      emission, callbacks, bookkeeping
# (no wait/readback on a step that reads nothing back; ``feed`` … ``host``
# is what the ``step`` latency histogram observes), and
#   ``decode_launches_ahead``    steps launched while the step before was
#                                still un-collected, over ``decode_steps``
#                                (both count COLLECTED steps)
#   ``decode_between_steps_us``  the router loop from one iteration's end to
#                                the next one's start while rows are seated
#   ``decode_join_wait_us``      submit -> seated, summed over
#                                ``decode_joins`` (the ``join_wait``
#                                histogram's observations)
#   ``decode_padded_row_tokens`` batch bucket x chunk bucket per step: the
#                                row-tokens computed, padding included
#   ``decode_chunk_width``       chunk bucket summed over
#                                ``decode_prefill_steps``
#   ``decode_kv_rows_read``      key rows a step's attention fetches of a
#                                KV slab, summed over the batch bucket's
#                                slots: each slot's rows as far as its
#                                sequence reaches, rounded up to a copy's
#                                tile, where the one-token kernel serves
#                                (``ops.attention.kv_rows_fetched``), the
#                                whole slab on a chunked step and on the
#                                jnp path
#   ``decode_kv_rows_held``      key rows the slab holds for those slots:
#                                the denominator of the share read
#   ``decode_kv_rows_live``      key rows the stepping sequences hold once
#                                the step has appended (Σ position +
#                                tokens consumed): what a step's attention
#                                has to read, one slab's worth
#   ``moe_assignments``, ``moe_assignments_held``, ``moe_experts_touched``,
#   ``moe_expert_load_max``     folded from a step's auxiliary fetch of chosen
#                                expert ids (``DecodeEngine(aux_fold=)``;
#                                ``SolarOpen2Config.choice_counters``): (token,
#                                expert) pairs computed, those whose expert is
#                                held here, held experts with a token (summed
#                                over the layers), the fullest one's tokens
# Surfaced by ``HetuProfiler.decode_counters()``; a process that never
# decodes reports an empty dict.

_decode = REGISTRY.counter_family(
    "decode",
    "continuous-batching autoregressive decode events (empty in a "
    "process that never decodes)")


def record_decode(kind, n=1):
    """Count ``n`` decode events of ``kind``; kinds ending in ``_hw``
    are high-water gauges (the stored value is the max seen)."""
    kind = str(kind)
    if kind.endswith("_hw"):
        _decode.max_gauge(kind, int(n))
    elif n:
        _decode.inc(kind, int(n))


def decode_counts():
    """{kind: count} snapshot of decode counters."""
    return _decode.counts()


def reset_decode_counts():
    """Reset the decode counters AND the per-token latency histogram —
    one decode run's telemetry, one reset."""
    _decode.reset()
    _decode_latency.reset()


# ------------------------------------------------- prefix-cache counters
# The shared-prefix KV store (``hetu_tpu.serving.prefix_cache``, ISSUE
# 18) records its reuse economics here: lookups that found a usable
# stored prefix (``prefix_cache_hits``) vs not (``prefix_cache_misses``),
# the total KV-cache ROWS those hits seated pre-filled — i.e. prompt
# tokens whose prefill was skipped outright (``prefix_cache_hit_rows``),
# snapshots inserted (``prefix_cache_inserts``) and deduplicated against
# an existing key (``prefix_cache_dup_inserts``), entries LRU-evicted to
# stay under the capacity bound (``prefix_cache_evictions``) with the
# bytes they freed (``prefix_cache_evicted_bytes``), and the store's
# resident-bytes high-water mark (``prefix_cache_bytes_hw`` — gauge
# semantics: the recorded value is the MAX ever seen).  Surfaced by
# ``HetuProfiler.prefix_cache_counters()`` and the decode bench; a
# process with no prefix store reports an empty dict.

_prefix_cache = REGISTRY.counter_family(
    "prefix_cache",
    "shared-prefix KV snapshot reuse events (empty in a process with "
    "no PrefixKVStore)")


def record_prefix_cache(kind, n=1):
    """Count ``n`` prefix-cache events of ``kind``; kinds ending in
    ``_hw`` are high-water gauges (the stored value is the max seen)."""
    kind = str(kind)
    if kind.endswith("_hw"):
        _prefix_cache.max_gauge(kind, int(n))
    elif n:
        _prefix_cache.inc(kind, int(n))


def prefix_cache_counts():
    """{kind: count} snapshot of prefix-cache counters."""
    return _prefix_cache.counts()


def reset_prefix_cache_counts():
    _prefix_cache.reset()


# --------------------------------------------- decode recovery counters
# Exactly-once stream migration (ISSUE 19): when the fleet sweep ejects
# a dead/wedged decode replica, every SEATED in-flight generation is
# detached as a continuation request (``decode_recovery_detached`` —
# the host-side emitted-token journal becomes the replay prompt suffix
# and the stream's replay epoch is bumped, fencing the old engine) and
# re-seated on a survivor through the chunked-prefill entry
# (``decode_recovery_reseated``).  ``decode_recovery_replayed_rows``
# counts the KV rows the survivor actually re-prefilled,
# ``decode_recovery_prefix_assisted`` the rows a PrefixKVStore hit
# seated for free (the two partition the continuation prompt).
# ``decode_recovery_exhausted`` counts streams the door failed FAST
# instead of resurrecting (retry budget, deadline estimator, or zero
# survivors — the failure carries ``DecodeStream.partial()``),
# ``decode_recovery_retries`` second-and-later recoveries of the same
# stream, and ``decode_recovery_fenced`` stale emissions a migrated-away
# replica attempted that the epoch fence dropped (each one a token that
# would have been delivered TWICE without the fence).  Surfaced by
# ``HetuProfiler.decode_recovery_counters()`` and the decode bench's
# recovery leg; a process that never migrates a stream reports an empty
# dict.

_decode_recovery = REGISTRY.counter_family(
    "decode_recovery",
    "exactly-once in-flight decode stream migration events (empty in a "
    "process that never recovers a stream)")


def record_decode_recovery(kind, n=1):
    """Count ``n`` stream-recovery events of ``kind``; kinds ending in
    ``_hw`` are high-water gauges (the stored value is the max seen)."""
    kind = str(kind)
    if kind.endswith("_hw"):
        _decode_recovery.max_gauge(kind, int(n))
    elif n:
        _decode_recovery.inc(kind, int(n))


def decode_recovery_counts():
    """{kind: count} snapshot of decode stream-recovery counters."""
    return _decode_recovery.counts()


def reset_decode_recovery_counts():
    _decode_recovery.reset()


# --------------------------------------------- serving rejection reasons
# ISSUE 17: every :class:`ServeRejected` now carries a structured
# ``reason`` from the closed vocabulary ``queue_full | over_max_len |
# deadline | shed:<class> | draining``, and every raise site counts it
# here keyed BY that reason — bench artifacts and tests read this family
# instead of string-matching exception text.  The legacy ``serve`` /
# ``decode`` families keep their coarse ``*_rejections`` totals; this is
# the per-cause breakdown.

_serve_reject = REGISTRY.counter_family(
    "serve_rejection_reason",
    "serving rejections keyed by structured ServeRejected reason "
    "(queue_full | over_max_len | deadline | shed:<class> | draining)")


def record_serve_rejection(reason, n=1):
    """Count ``n`` rejections with structured ``reason`` (one of the
    ``ServeRejected.REASONS`` vocabulary, e.g. ``shed:best_effort``)."""
    if n:
        _serve_reject.inc(str(reason), int(n))


def serve_rejection_counts():
    """{reason: count} snapshot of structured serving rejections."""
    return _serve_reject.counts()


def reset_serve_rejection_counts():
    _serve_reject.reset()


# --------------------------------------------------------- fleet counters
# The replica-set serving tier (``hetu_tpu.serving.fleet``) records its
# lifecycle here: requests admitted at the front door
# (``fleet_admitted``) and dispatched to a replica (``fleet_dispatch``),
# replicas added (``fleet_scale_out``) / retired (``fleet_scale_in``),
# dead-or-wedged replicas ejected from dispatch
# (``fleet_replica_ejected``) and re-admitted after recovery
# (``fleet_replica_readmitted``), queued requests rescued off a dead or
# draining replica onto a survivor (``fleet_rescued`` — the graceful-
# degradation path: admitted work is handed over, not failed), admitted
# requests whose future ultimately failed (``fleet_request_failures`` —
# the bench gates this at zero), SLO-autoscaler polls
# (``fleet_autoscaler_polls``) and resizes refused at the min/max bound
# (``fleet_scale_refused``), and the live-replica high-water mark
# (``fleet_replicas_hw`` — gauge semantics: the recorded value is the
# MAX ever seen).  Surfaced by ``HetuProfiler.fleet_counters()``; a
# process with no fleet reports an empty dict.

_fleet = REGISTRY.counter_family(
    "fleet",
    "replica-set serving-tier events (empty in a process that never "
    "runs a FrontDoor)")


def record_fleet(kind, n=1):
    """Count ``n`` fleet events of ``kind``; kinds ending in ``_hw``
    are high-water gauges (the stored value is the max seen)."""
    kind = str(kind)
    if kind.endswith("_hw"):
        _fleet.max_gauge(kind, int(n))
    elif n:
        _fleet.inc(kind, int(n))


def fleet_counts():
    """{kind: count} snapshot of fleet serving-tier counters."""
    return _fleet.counts()


def reset_fleet_counts():
    _fleet.reset()


# ------------------------------------------------ protocol verification
# The ISSUE 20 model checker and its trace-conformance layer
# (``analysis/protocol.py``) record their activity here:
# ``protocol_events`` counts transition events the :data:`PROTO`
# recorder captured at the real protocol sites (dist_store / decode /
# fleet / elastic — zero unless ``HETU_PROTO_TRACE`` or a chaos bench
# flips the recorder on) and ``protocol_events_dropped`` events the
# buffer cap discarded; ``protocol_conformance_checks`` counts events
# replayed against the models' transition relations and
# ``protocol_divergences`` the replays a monitor rejected (the chaos
# benches gate on ZERO of these — an allowlisted divergence counts
# under ``protocol_divergences_allowlisted`` instead);
# ``protocol_states_explored`` counts canonical states the BFS checker
# visited and ``protocol_violations`` the invariant violations it found
# (nonzero only under a seeded mutation — HEAD models verify clean).
# Surfaced by ``HetuProfiler.protocol_counters()`` and
# ``tools/verify_protocols.py``; a process that never checks or records
# a protocol reports an empty dict.

_protocol = REGISTRY.counter_family(
    "protocol",
    "protocol model-checking and trace-conformance events (empty in a "
    "process that never verifies a protocol)")


def record_protocol(kind, n=1):
    """Count ``n`` protocol-verification events of ``kind``; kinds
    ending in ``_hw`` are high-water gauges (the stored value is the
    max seen)."""
    kind = str(kind)
    if kind.endswith("_hw"):
        _protocol.max_gauge(kind, int(n))
    elif n:
        _protocol.inc(kind, int(n))


def protocol_counts():
    """{kind: count} snapshot of protocol-verification counters."""
    return _protocol.counts()


def reset_protocol_counts():
    _protocol.reset()


# --------------------------------------------------- latency histograms
# Log-bucketed distributions (``obs.registry.Histogram``: 8 buckets per
# octave, p50/p90/p99 accessors) — the mean-only counters above cannot
# distinguish a p99 spike from a shifted mean; these can.

# Per-opcode PS RPC latency (one observation per CLIENT round trip,
# labeled ``OP_PULL``/``OP_PUSH``/... — ``opcodes.op_name``) plus the
# request payload bytes it carried (keys + payload, header excluded), as
# a counter family keyed the same way.  Recording rides ``_rpc``'s
# success path; counter-silent probes (``record=False``) stay silent
# here too.
_rpc_lat = REGISTRY.histogram(
    "ps_rpc_us",
    "PS client RPC round-trip latency per opcode, microseconds")
_rpc_bytes = REGISTRY.counter_family(
    "ps_rpc_bytes",
    "PS client RPC request payload bytes per opcode (keys + payload)")


def record_rpc(op, us, nbytes):
    """One successful PS client RPC: latency (us) into the per-opcode
    histogram, request bytes into the per-opcode byte counter."""
    _rpc_lat.observe(us, label=op)
    if nbytes:
        _rpc_bytes.inc(op, int(nbytes))


def rpc_stats():
    """{"latency_us": {op: histogram snapshot}, "bytes": {op: total}}."""
    return {"latency_us": _rpc_lat.snapshot(),
            "bytes": _rpc_bytes.counts()}


def reset_rpc_stats():
    _rpc_lat.reset()
    _rpc_bytes.reset()


# Serving latency: per-request queue wait (submit -> batch claim) and
# per-batch device-call time, labeled ``queue_wait`` / ``batch``.
_serve_latency = REGISTRY.histogram(
    "serve_latency_us",
    "serving latency: per-request queue wait and per-batch device "
    "call, microseconds")


def record_serve_latency(kind, us):
    """Observe one serving latency sample (``kind``: ``queue_wait`` per
    request, ``batch`` per dispatched micro-batch)."""
    _serve_latency.observe(us, label=kind)


def serve_latency_stats():
    """{kind: histogram snapshot} for the serving latency families."""
    return _serve_latency.snapshot()


# Decode latency: per-token inter-emission latency (``token`` — one
# observation per token STREAMED to a caller, the number a serving SLO is
# written against), per-request join wait (``join_wait`` — submit ->
# joined the in-flight batch), per-request time-to-first-token (``ttft``
# — submit -> FIRST generated token, the prompt-ingestion latency
# chunked prefill attacks; distinct from the steady-state ``token``
# gap), per-engine-step device call (``step``), and detach->reseat
# migration latency for recovered in-flight streams (``recovery`` — one
# observation per continuation seated on a survivor, ISSUE 19).
_decode_latency = REGISTRY.histogram(
    "decode_latency_us",
    "decode latency: per-token emission, per-request join wait, "
    "time-to-first-token, per-step device call, and detach->reseat "
    "stream recovery, microseconds")


def record_decode_latency(kind, us):
    """Observe one decode latency sample (``kind``: ``token`` per emitted
    token, ``join_wait`` per joined request, ``ttft`` once per stream at
    its first generated token, ``step`` per engine step, ``recovery``
    per migrated continuation at reseat)."""
    _decode_latency.observe(us, label=kind)


def decode_latency_stats():
    """{kind: histogram snapshot} for the decode latency families."""
    return _decode_latency.snapshot()


# Executor step wall time, labeled by subexecutor name.  OFF by default:
# the observation costs ~0.5us (two clock reads + one bucketed insert),
# which the dispatch-gap work (PR 9) fought to excise — benches and
# traced runs enable it (``enable_step_timing`` / ``HETU_STEP_TIMING=1``
# / any ``HETU_TRACE=1`` session records spans anyway).
_step_time = REGISTRY.histogram(
    "step_time_us",
    "executor step wall time per subexecutor, microseconds (enable "
    "with metrics.enable_step_timing or HETU_STEP_TIMING=1)")

#: read directly by ``SubExecutor.run`` — a module attribute load, not
#: a function call, keeps the disabled path at ~one global read
step_timing = False


def _init_step_timing():
    global step_timing
    import os
    step_timing = os.environ.get("HETU_STEP_TIMING", "0").lower() \
        not in ("", "0", "false", "off")


_init_step_timing()


def enable_step_timing(on=True):
    """Turn the per-step wall-time histogram on/off (see
    ``step_time_us``'s registration note for why it is opt-in)."""
    global step_timing
    step_timing = bool(on)


def record_step_time(us, label="default"):
    """Observe one executor step's wall time (called by
    ``SubExecutor.run`` when step timing is enabled)."""
    _step_time.observe(us, label=label)


def step_time_stats():
    """{subexecutor: histogram snapshot} of recorded step wall times."""
    return _step_time.snapshot()


def reset_step_times():
    _step_time.reset()


# ------------------------------------------------------------- run gauges
# One measured step time per run, labeled by run/plan name:
# ``autoparallel.measure`` publishes each candidate plan's verdict here.

_step_gauge = REGISTRY.gauge(
    "step_time_ms",
    "measured step wall time per run, milliseconds")


def record_run_gauges(label, step_time_ms):
    """Publish one run's measured step time."""
    _step_gauge.set(step_time_ms, label=label)


def run_gauges():
    """{"step_time_ms": {label: v}}."""
    return {"step_time_ms": _step_gauge.values()}


# ------------------------------------------------------------ one-registry view

#: the counter families in registration order — ``all_counts`` and the
#: profiler's ``all_counters`` read this instead of seven accessors
_FAMILIES = {
    "flash_fallbacks": _flash,
    "flash_calls": _flash_calls,
    "flash_head_major": _flash_head_major,
    "decode_attn_calls": _decode_attn_calls,
    "kv_append_calls": _kv_append_calls,
    "mlm_head_calls": _mlm_head_calls,
    "moe_calls": _moe_calls,
    "sparse_attn_calls": _sparse_attn_calls,
    "ssd_calls": _ssd_calls,
    "emb_pallas_fallbacks": _emb_pallas,
    "faults": _faults,
    "elastic": _elastic,
    "concurrency": _concurrency,
    "remat": _remat,
    "autoparallel": _autoparallel,
    "cache": _cache,
    "zero": _zero,
    "step_cache": _step_cache,
    "compile": _compile,
    "setup_us": _setup_us,
    "setup_bytes": _setup_bytes,
    "run_plan": _run_plan,
    "serve": _serve,
    "decode": _decode,
    "prefix_cache": _prefix_cache,
    "decode_recovery": _decode_recovery,
    "serve_rejection_reason": _serve_reject,
    "fleet": _fleet,
    "protocol": _protocol,
    "ps_rpc_bytes": _rpc_bytes,
}


def all_counts():
    """{family: {kind: count}} over EVERY counter family — the one-call
    view behind ``HetuProfiler.all_counters()`` (the per-family
    accessors are thin slices of this)."""
    return {name: fam.counts() for name, fam in _FAMILIES.items()}


def reset_all():
    """Zero every registered instrument — counters, histograms and
    gauges — in one call (replaces the per-family ``reset_*`` bodies,
    which remain as thin delegates)."""
    REGISTRY.reset_all()


def _np(x):
    return np.asarray(x)


def accuracy(y_pred, y_true):
    """Row-wise argmax accuracy; accepts one-hot or class-index y_true."""
    y_pred = _np(y_pred)
    y_true = _np(y_true)
    pred = np.argmax(y_pred, axis=-1)
    true = np.argmax(y_true, axis=-1) if y_true.ndim == y_pred.ndim else y_true
    return float((pred == true).mean())


def auc(y_pred, y_true):
    """Binary ROC-AUC via rank statistic (ties averaged)."""
    score = _np(y_pred).reshape(-1)
    label = _np(y_true).reshape(-1)
    # average ranks with ties, vectorized: rank of a tied group = mean of its
    # positions = start + (count-1)/2
    uniq, inv, counts = np.unique(score, return_inverse=True,
                                  return_counts=True)
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    ranks = (starts + (counts - 1) / 2.0 + 1.0)[inv]
    pos = label > 0.5
    n_pos, n_neg = pos.sum(), (~pos).sum()
    if n_pos == 0 or n_neg == 0:
        return 0.5
    return float((ranks[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def confusion_matrix(y_pred, y_true, num_classes=None):
    pred = np.argmax(_np(y_pred), axis=-1) if _np(y_pred).ndim > 1 else _np(y_pred)
    true = np.argmax(_np(y_true), axis=-1) if _np(y_true).ndim > 1 else _np(y_true)
    n = num_classes or int(max(pred.max(), true.max())) + 1
    cm = np.zeros((n, n), np.int64)
    np.add.at(cm, (true.astype(int), pred.astype(int)), 1)
    return cm


def precision(y_pred, y_true, cls=1):
    cm = confusion_matrix(y_pred, y_true)
    denom = cm[:, cls].sum()
    return float(cm[cls, cls] / denom) if denom else 0.0


def recall(y_pred, y_true, cls=1):
    cm = confusion_matrix(y_pred, y_true)
    denom = cm[cls, :].sum()
    return float(cm[cls, cls] / denom) if denom else 0.0


def f1_score(y_pred, y_true, cls=1):
    p = precision(y_pred, y_true, cls)
    r = recall(y_pred, y_true, cls)
    return 2 * p * r / (p + r) if (p + r) else 0.0
