"""Continuous-batching autoregressive decode over device-resident KV caches.

The request-level :class:`~hetu_tpu.serving.ServingRouter` answers one
forward pass per request; autoregressive generation answers one forward
pass per TOKEN, and the naive loop re-runs the whole prefix every step.
This module is the serving plane for that workload:

* **Incremental KV cache.**  Each decode step feeds exactly one token per
  sequence through the q_len=1 attention entry
  (:func:`~hetu_tpu.ops.sdpa_decode_op`) against per-layer KV slabs —
  the bucketed, slot-major realization of the paper's per-sequence
  ``(layers, 2, max_len, heads, head_dim)`` cache.  A slab is stored in
  the layout the attention reads, and ``head_dim`` alone picks it
  (:func:`~hetu_tpu.ops.attention.kv_slab_shape`): ``(batch_bucket,
  heads, len_bucket / r, r * head_dim)`` with ``r = 128 // head_dim``
  consecutive key rows sharing one 128-lane row when ``head_dim`` is a
  divisor of 128, plain ``(batch_bucket, heads, len_bucket, head_dim)``
  otherwise — a minor dimension under 128 lanes would be stored
  length-minor by the device and transposed, whole, for every layer of
  every step.  Caches live on device for the whole generation: the
  engine feeds the previous step's fetched cache arrays straight back
  into the next jitted call (donated, so XLA appends in place) and never
  round-trips them through the host.

* **Bucketed growth, compile-once steady state.**  Both the batch dim and
  the cache length walk the same flash-legal ladder serving uses
  (:func:`~hetu_tpu.serving.default_buckets`: powers of two, then
  multiples of 128).  One jitted step exists per ``(batch_bucket,
  len_bucket)`` pair — built through the process-wide serve cache
  (``serve_bucket_compiles`` counts the jit wrappers constructed; the XLA
  compile happens at a wrapper's first call and leaves a ``decode`` record,
  ``HetuProfiler.compile_log()``) and dispatched through a
  per-engine :class:`~hetu_tpu.graph.run_plan.KeyedPlanCache`
  (``plan_cache_hit`` is the steady-state proof: after warmup every
  token batch dispatches with zero Python planning and zero compiles).

* **Continuous batching.**  Sequences join and leave the in-flight batch
  PER TOKEN: a new request occupies a free KV-cache slot at the next
  step boundary (no waiting for the current batch to drain), a finished
  sequence frees its slot immediately for the next joiner
  (``decode_slot_recycles``).  Prompt ingestion reuses the decode step
  (one prompt token per step — ``decode_prefill_rows``), so a joining
  sequence never stalls the sequences already generating.

* **Chunked prefill (ISSUE 18).**  With a ``chunked=`` graph entry
  (:func:`~hetu_tpu.models.gpt2_decode_chunked_graph`) prompt ingestion
  consumes up to C tokens per sequence per step through the q_len=C
  attention entry (:func:`~hetu_tpu.ops.sdpa_prefill_op`) — a P-token
  prompt costs ``ceil(P/C)`` dispatches instead of P.  Chunk sizes walk
  their own flash-legal ladder; a step's chunk is the smallest bucket
  covering the largest prompt remainder, generating rows ride along
  Sarathi-style with their one token at column 0 (mixed batches — a
  long joining prompt never stalls emission), and steps where no row is
  past its prompt skip the logits D2H entirely
  (``decode_logits_skipped``).  One jitted step per ``(batch_bucket,
  chunk_bucket, len_bucket)`` triple, through the same serve cache +
  keyed plan cache; single-token steps keep dispatching the PR 16
  q_len=1 entry unchanged.  Masked cache writes keep the KV bytes
  bitwise-identical to the token-by-token path at every chunk boundary.

* **Shared-prefix KV reuse (ISSUE 18).**  With a ``prefix_store=``
  (:class:`~hetu_tpu.serving.PrefixKVStore`) the engine snapshots each
  prompt's KV rows at its first generated token and seats a later
  request whose prompt extends a stored prefix with those rows
  pre-filled — the shared part's prefill is skipped outright
  (``prefix_cache_hits`` / ``prefix_cache_hit_rows``), and because
  cache bytes are ingestion-mode-independent the hit's token stream is
  bitwise-equal to the cold path.

* **Bitwise stability.**  A sequence's tokens do not depend on its batch
  mates: each slot attends only to its own cache rows ``0..position``
  (the per-row length mask), idle slots contribute nothing, and greedy
  argmax is deterministic — the same prompt decodes to the identical
  token stream whatever else shares the batch.

* **One step ahead (ISSUE 32).**  Every step program hands back its
  rows' greedy token ids and takes the previous step's as an operand, so
  a generating row's next input never visits the host:
  :meth:`DecodeEngine.launch` puts step n+1 on the device from host
  state alone, :meth:`DecodeEngine.collect` reads a launched step's ids
  back and emits them, and the router's loop calls them in that order —
  the host's share of a step (read-back, emission, the clients'
  callbacks, joins, the next plan) hides behind the chip's.

* **Per-token streaming.**  :meth:`DecodeRouter.submit` returns a
  :class:`DecodeStream`: per-token ``concurrent.futures.Future``s
  (``stream.token(i)``), iteration (``for tok in stream``), and a
  whole-sequence ``stream.result()``.  Backpressure is explicit —
  a full queue raises :class:`~hetu_tpu.serving.ServeRejected`.

* **Exactly-once stream recovery (ISSUE 19).**  The stream's host-side
  token list is the REPLAY JOURNAL: when a fleet replica dies (or
  wedges) mid-generation, :meth:`DecodeRouter.detach_inflight` turns
  every seated sequence into a *continuation request* — original
  prompt + journal as the new prompt, remaining ``max_new``, same
  stream, original deadline — that a survivor re-ingests through
  chunked prefill (prefix store consulted first) and continues from
  the next token index.  The detach atomically bumps the stream's
  replay epoch, fencing every late emission from the dead replica:
  already-resolved ``token(i)`` futures never re-fire, no token is
  delivered twice or skipped, and greedy argmax over the replayed
  history makes the full stream bitwise-equal to an unkilled run.

Threading: the router's loop thread OWNS the engine (slots, caches,
compiled steps) — no lock guards engine state because exactly one thread
touches it after ``start()``.  The queue and the seated-request mirror
hand off under ``DecodeRouter._cv``; each stream has its own
``DecodeStream._lock``.  Neither is ever held across a device call or
while acquiring the other, so the PR 14 witness hierarchy stays acyclic.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
import warnings
from concurrent.futures import Future

import numpy as np

from .. import chaos as _chaos
from .. import race as _race
from ..analysis.protocol import PROTO as _PROTO
from ..graph.run_plan import KeyedPlanCache
from ..graph import step_cache
from ..metrics import (record_decode, record_decode_latency,
                       record_decode_recovery)
from ..obs.compile_log import SetupPhase
from ..obs.lock_witness import make_condition, make_lock
from ..obs.trace import Phases as _Phases
from ..obs.trace import TRACER as _TR
from ..obs.trace import span as _span
from .executor import InferenceExecutor, default_buckets
from .router import ServeRejected

#: the phases of one ``DecodeEngine.step`` (docstring there): span names
#: ``decode.step.<phase>``, counters ``decode_step_<phase>_us``
_STEP_PHASES = {p: (f"decode.step.{p}", f"decode_step_{p}_us") for p in (
    "plan", "feed", "dispatch", "wait", "readback", "host")}


class DecodeStream:
    """Per-request handle: tokens stream out as the engine emits them.

    ``token(i)`` returns a Future for the i-th generated token (resolved
    in emission order; failed with ``IndexError`` if generation finishes
    before ``i`` tokens).  Iterating yields tokens until the sequence
    finishes.  ``result(timeout)`` blocks for the full token list.  A
    router/engine failure fails every outstanding future AND
    ``result()`` with the same exception.

    The host-side ``_tokens`` list doubles as the REPLAY JOURNAL for
    exactly-once stream migration (ISSUE 19): when the replica holding
    this stream dies mid-generation, the front door detaches the stream
    with its journal and re-seats it on a survivor as a continuation
    request (prompt + journal re-prefilled, generation resumed at the
    next index).  ``_detach`` bumps the stream's replay EPOCH atomically
    with the journal snapshot; every engine-side mutation carries the
    epoch its request was built under, so a stale replica — wedged in a
    device call when the door gave up on it, then waking later — cannot
    re-fire an already-resolved future or double-deliver a token."""

    #: process-wide stream ids — stable names for protocol-event traces
    _IDS = itertools.count()

    def __init__(self, prompt_len, max_new_tokens):
        self.prompt_len = int(prompt_len)
        self.max_new_tokens = int(max_new_tokens)
        self.sid = next(DecodeStream._IDS)
        self._lock = make_lock("DecodeStream._lock")
        self._futs = []
        self._tokens = []
        self._aux = {}
        self._aux_from = 0
        self._epoch = 0
        self._final = Future()

    # -- consumer side -----------------------------------------------------

    def token(self, i):
        """Future for the ``i``-th generated token."""
        i = int(i)
        with self._lock:
            done_short = self._final.done() and i >= len(self._tokens)
            while len(self._futs) <= i:
                self._futs.append(Future())
            fut = self._futs[i]
        if done_short and fut.set_running_or_notify_cancel():
            # the sequence already finished with fewer tokens: a future
            # created now would otherwise never resolve
            fut.set_exception(IndexError(
                f"generation finished after {len(self._tokens)} tokens"))
        return fut

    def result(self, timeout=None):
        """Block for the complete generated-token list."""
        return self._final.result(timeout)

    @property
    def done(self):
        return self._final.done()

    @property
    def n_tokens(self):
        with self._lock:
            return len(self._tokens)

    @property
    def epoch(self):
        """Current replay epoch (bumped once per detach/migration)."""
        with self._lock:
            return self._epoch

    def partial(self):
        """Tokens generated SO FAR — a copy of the replay journal.
        Attached to a ``recovery_exhausted`` failure so a consumer
        keeps the partial generation instead of losing it with the
        replica (ISSUE 19 satellite)."""
        with self._lock:
            return list(self._tokens)

    def aux(self, name):
        """What the engine's auxiliary fetch ``name`` (``DecodeEngine(
        aux=)``) held for every token this sequence has CONSUMED so far,
        stacked in position order: row ``p`` is of the token at position
        ``p`` — the prompt's tokens, then each generated token once it was
        fed back (so ``prompt_len + n_tokens - 1`` rows when the sequence
        has finished).  A sequence seated from a prefix store consumed
        nothing of the prefix: its rows start at position
        :attr:`aux_from`.  None for a name the engine does not fetch."""
        with self._lock:
            parts = list(self._aux.get(name, ()))
        return np.concatenate(parts) if parts else None

    @property
    def aux_from(self):
        """The position of :meth:`aux`'s first row: 0, or the rows a
        prefix store seated this sequence with."""
        with self._lock:
            return self._aux_from

    def __iter__(self):
        i = 0
        while True:
            try:
                yield self.token(i).result()
            except Exception:
                # IndexError past the end, CancelledError, or the
                # engine's failure — iteration just stops; result()
                # re-raises real failures for callers who care
                return
            i += 1

    # -- engine side (router loop thread only) -----------------------------

    def _detach(self):
        """Bump the replay epoch and snapshot the journal ATOMICALLY —
        the one operation behind stream migration.  Every emission the
        old replica attempts after this point is fenced (its request
        carries the stale epoch), so the snapshot is exact: the
        continuation replays precisely the tokens consumers were
        delivered, then appends.  Returns ``(new_epoch, journal)``."""
        with self._lock:
            self._epoch += 1
            epoch, journal = self._epoch, list(self._tokens)
            # the continuation consumes every position again
            self._aux, self._aux_from = {}, 0
        if _PROTO.on:
            _PROTO.emit("decode", "detach", sid=self.sid, old=epoch - 1,
                        new=epoch, n=len(journal))
        return epoch, journal

    def _emit(self, tok, epoch=None):
        """Deliver one token.  ``epoch`` is the replay epoch of the
        emitting request; a stale epoch (the stream migrated away) is a
        no-op returning False.  Returns the journal length after the
        append — 1 means this was the stream's FIRST token ever (the
        ttft observation), regardless of which replica delivered it."""
        with self._lock:
            if epoch is not None and epoch != self._epoch:
                if _PROTO.on:
                    _PROTO.emit("decode", "fenced", sid=self.sid,
                                got=epoch, cur=self._epoch)
                return False
            while len(self._futs) <= len(self._tokens):
                self._futs.append(Future())
            fut = self._futs[len(self._tokens)]
            self._tokens.append(int(tok))
            count = len(self._tokens)
            if _PROTO.on:
                _PROTO.emit("decode", "emit", sid=self.sid,
                            epoch=self._epoch, idx=count - 1)
        # resolve OUTSIDE the stream lock: a done-callback attached by
        # the consumer runs in this thread and must not run under (or
        # re-acquire) our lock
        if fut.set_running_or_notify_cancel():
            fut.set_result(int(tok))
        return count

    def _seated_at(self, m, epoch=None):
        """A prefix store seated the sequence with its first ``m``
        positions: its auxiliary rows start there."""
        with self._lock:
            if epoch is None or epoch == self._epoch:
                self._aux_from = int(m)

    def _note_aux(self, parts, epoch=None):
        """Keep the auxiliary fetches' slices ``{name: (n, ...)}`` of the
        ``n`` tokens a step consumed for this sequence; fenced by the
        replay epoch like an emission."""
        with self._lock:
            if epoch is not None and epoch != self._epoch:
                return
            for name, part in parts.items():
                self._aux.setdefault(name, []).append(part)

    def _finish(self, epoch=None):
        with self._lock:
            if epoch is not None and epoch != self._epoch:
                return False
            tokens = list(self._tokens)
            extra = self._futs[len(tokens):]
        if _PROTO.on:
            _PROTO.emit("decode", "finish", sid=self.sid, n=len(tokens))
        for f in extra:
            if f.set_running_or_notify_cancel():
                f.set_exception(IndexError(
                    f"generation finished after {len(tokens)} tokens"))
        if self._final.set_running_or_notify_cancel():
            self._final.set_result(tokens)
        return True

    def _fail(self, exc, epoch=None):
        with self._lock:
            if epoch is not None and epoch != self._epoch:
                return False
            done = len(self._tokens)
            pending = self._futs[done:]
        if _PROTO.on:
            _PROTO.emit("decode", "fail", sid=self.sid, n=done)
        for f in pending:
            if f.set_running_or_notify_cancel():
                f.set_exception(exc)
        if self._final.set_running_or_notify_cancel():
            self._final.set_exception(exc)
        return True


class _DecodeRequest:
    __slots__ = ("prompt", "max_new", "eos_id", "stream", "t_arrival",
                 "fid", "deadline", "epoch", "retries", "detached_ts",
                 "keep_prefix")

    def __init__(self, prompt, max_new, eos_id, fid, deadline=None,
                 keep_prefix=True):
        self.prompt = prompt
        self.max_new = int(max_new)
        self.eos_id = eos_id
        self.stream = DecodeStream(len(prompt), max_new)
        self.t_arrival = time.monotonic()
        self.fid = fid
        self.deadline = deadline   # absolute monotonic, or None
        self.epoch = 0             # stream replay epoch this req emits under
        self.retries = 0           # continuation builds for this stream
        self.detached_ts = None    # set on continuations: detach time
        self.keep_prefix = bool(keep_prefix)   # snapshot the prompt's state


def _continuation(req):
    """Continuation request for a detached in-flight stream (ISSUE 19):
    the original prompt plus the emitted-token journal becomes the new
    prompt (re-ingested through chunked prefill on the survivor, prefix
    store consulted first), ``max_new`` shrinks to the remaining budget,
    and the SAME stream travels along — generation resumes at the next
    token index, so already-resolved ``token(i)`` futures never re-fire.
    The journal snapshot and the epoch bump are one atomic operation
    (``DecodeStream._detach``), fencing every later emission from the
    dead replica."""
    stream = req.stream
    epoch, journal = stream._detach()
    base = np.asarray(req.prompt, np.int32)[:stream.prompt_len]
    cont = _DecodeRequest.__new__(_DecodeRequest)
    cont.prompt = np.concatenate(
        [base, np.asarray(journal, np.int32)]) if journal else base
    cont.max_new = stream.max_new_tokens - len(journal)
    cont.eos_id = req.eos_id
    cont.stream = stream
    cont.t_arrival = req.t_arrival      # deadline math stays submit-anchored
    cont.fid = _TR.flow_begin("decode.recovery", cat="decode") \
        if _TR.on else None             # the eject->reseat flow arrow
    cont.deadline = req.deadline
    cont.epoch = epoch
    cont.retries = req.retries + 1
    cont.detached_ts = time.monotonic()
    cont.keep_prefix = req.keep_prefix
    record_decode_recovery("decode_recovery_detached")
    if cont.retries > 1:
        record_decode_recovery("decode_recovery_retries")
    return cont


class _Sequence:
    """One in-flight sequence's slot state (router loop thread only)."""

    __slots__ = ("req", "ptr", "launched", "emitted", "spent", "snap",
                 "t_last", "fid")

    def __init__(self, req):
        self.req = req
        self.ptr = 0          # next prompt index to consume
        self.launched = 0     # tokens a step has been launched for
        self.emitted = 0      # of those, tokens collected and on the stream
        self.spent = False    # its last step is launched: no later one
                              # carries it, its slot frees at that collect
        self.snap = None      # prompt KV rows for the prefix store, taken
                              # at the launch that ended the prompt
        self.t_last = time.monotonic()
        self.fid = None       # decode.join flow id (set at join)


class _Launch:
    """A step on the device whose answer the host has not read
    (:meth:`DecodeEngine.launch` makes it, :meth:`DecodeEngine.collect`
    consumes it).  ``rows``: per row it carried ``(slot, sequence, tokens
    consumed, of them prompt tokens that emit nothing, emits)`` — the
    pair ``(slot, sequence)`` is what :meth:`~DecodeEngine.collect` checks
    before it believes the row's answer.  ``back``: the device arrays
    whose copy to the host is under way (the token ids if a row emits,
    then the auxiliary fetches), ``logits`` the (batch, vocab) logits
    left on the device.  The rest is what the step's counters need:
    whether a row emits, whether the step before was still un-collected,
    the batch and chunk buckets, the KV rows ``(read, held, live, index
    rows live)``."""

    __slots__ = ("rows", "back", "logits", "emits", "ahead", "bb", "chunk",
                 "kv_rows")

    def __init__(self, rows, back, logits, emits, ahead, bb, chunk, kv_rows):
        self.rows, self.back, self.logits, self.emits = \
            rows, back, logits, emits
        self.ahead, self.bb, self.chunk, self.kv_rows = \
            ahead, bb, chunk, kv_rows


class DecodeEngine:
    """KV-cache decode executor: slots, bucket ladders, compiled steps.

    Built from :func:`~hetu_tpu.models.gpt2_decode_graph`'s return value
    (any graph with the same feed contract works): ``feeds`` maps
    ``input_ids`` (B, 1) / ``positions`` (B,) / per-layer cache
    placeholders to nodes, ``logits`` is the (B, vocab) fetch,
    ``cache_fetches`` the appended caches in feed order.  The cache
    placeholders are KV slabs (:func:`~hetu_tpu.ops.kv_slab_placeholder`:
    (B, heads, L/r, r*head_dim), ``r`` key rows per 128-lane row, chosen
    by ``head_dim`` alone); the engine allocates, grows, seats and
    snapshots them in that stored shape, and a plain (B, heads, L,
    head_dim) placeholder is the ``r = 1`` case.  Prefix snapshots keep
    external ``(heads, m, head_dim)`` rows, whatever the slab format.

    ``max_slots`` caps the in-flight batch (the top of the batch-bucket
    ladder); ``max_len`` caps the cache length (prompt + generated).
    ``plan=`` accepts a searched :class:`~hetu_tpu.parallel.ParallelPlan`
    (tp-sharded decode) — it is realized strictly at construction and
    gated by the ``plan-coverage`` lint, exactly like training.

    ``chunked=`` accepts a second graph entry ``(feeds, logits,
    cache_fetches)`` from
    :func:`~hetu_tpu.models.gpt2_decode_chunked_graph` (same weight
    names, extra ``valid`` feed): its executor is loaded FROM the
    primary executor's params — never independently initialized, so
    both entries serve the same weight bytes, held ONCE: the second
    executor keeps the first one's device arrays — and prompt ingestion
    runs ``ceil(P/C)`` chunked steps instead of P.  ``max_chunk`` caps
    the chunk ladder (default ``min(32, max_len)``).  ``prefix_store=``
    accepts a :class:`~hetu_tpu.serving.PrefixKVStore` for shared-
    prefix KV reuse (may be shared across engines).

    **State kinds (ISSUE 27).**  A state placeholder declares what it
    is (:func:`~hetu_tpu.ops.state_placeholder`, ``attrs["state_kind"]``;
    a KV slab without one is ``kv``) and the engine allocates, grows,
    seats, clears and accounts each by kind: ``kv`` slabs walk the
    length ladder; an ``index`` slab walks it at one row per ``stride``
    positions (a sparse layer's compressed keys: slabs of two geometries
    in one engine); a ``ring`` is a fixed ``window``-row buffer its graph
    writes at ``position mod window`` and reads by position; a
    ``recurrent`` state is fixed-shape and its slot's rows are ZEROED at
    :meth:`join` (``decode_state_clears``) — a slab or a ring is read
    only where the seated sequence wrote, a recurrence folds in whatever
    it finds.  ``decode_state_bytes_<kind>_hw`` gauge each kind,
    ``decode_kv_bytes_hw`` their sum.  ``plan=`` with any state but
    ``kv`` raises at construction.  :meth:`reserve` puts the engine at
    given buckets before the first request.

    **What a prefix store snapshots (ISSUE 42).**  With ``prefix_store=``
    a prompt's snapshot holds, beside its KV rows, its ``index`` rows and
    its ``recurrent`` state at the prompt's last position, and
    :meth:`join` seats all of them in place (one donated call,
    ``decode_prefix_seats`` / ``decode_prefix_seat_us``, span
    ``decode.join.seat``).  A recurrence cannot be rolled back to a
    shared partial depth: with any state but ``kv`` a lookup hits only an
    entry whose WHOLE key is a proper prefix of the prompt (a kv-only
    graph keeps partial-overlap reuse).  Which prompts are snapshotted is
    the caller's to say (``DecodeRouter.submit(keep_prefix=)``).
    ``ring`` state beside a store raises at construction.

    **The token loop closes on the device (ISSUE 32).**  Every step
    program hands back each row's greedy token as a (B,) int32 array
    beside the (B, vocab) logits: the graph's own fetch where ``tokens=``
    names one (``chunked=`` then takes a fourth element, the chunked
    graph's), else the ``argmax`` of the logits, computed by the engine's
    program (first maximum, as ``np.argmax`` has).  A step brings back
    those ids; the logits stay on the device and leave it only through
    :attr:`last_logits`.  A generating row's next input id is the
    PREVIOUS step's id array, an operand of the program that is never
    donated: the host feeds ``-1`` for such a row and the program takes
    the row's id from that array, so the next step can be launched before
    the host has seen the token it consumes.  That is what
    :meth:`launch` / :meth:`collect` are for (:meth:`step` is one after
    the other); :class:`DecodeRouter` runs one step ahead of what it has
    read back.

    **Auxiliary fetches (ISSUE 31).**  ``aux={name: node}`` names further
    fetches of the one-token graph, each ``(B, 1, ...)`` — something of
    every token a row consumed that the host wants beside the token, as
    the expert ids a mixture of experts chose — and ``chunked=`` then
    takes their ``(B, C, ...)`` twins as a last element ``{name: node}``.
    Every step brings them back with the token ids (a step in which no
    row emits reads them alone), hands each row's slice of the columns it
    consumed to its stream (:meth:`DecodeStream.aux`) and, in the
    ``readback`` phase, folds the whole array into counters:
    ``aux_fold={name: fn}``, ``fn(array) -> {counter: n}``, recorded with
    :func:`~hetu_tpu.metrics.record_decode`.  Beside a prefix store a
    sequence seated past its prefix has no slices of the positions it
    skipped: its :meth:`DecodeStream.aux` starts at
    :attr:`DecodeStream.aux_from`.

    NOT thread-safe by design: the owning :class:`DecodeRouter` loop
    thread (or a single test thread) makes every call after
    construction.  Device calls happen with no lock held."""

    def __init__(self, feeds, logits, cache_fetches, weights=None, *,
                 max_slots=8, max_len=128, plan=None, mesh=None,
                 seed=0, donate=True, validate="error",
                 chunked=None, max_chunk=None, prefix_store=None,
                 tokens=None, aux=None, aux_fold=None):
        self.cache_names = [n for n in feeds
                            if n not in ("input_ids", "positions")]
        #: per state: its kind, its shape past the batch and its type, as
        #: the placeholder declares them (``ops.state_placeholder``)
        self._kinds = {n: feeds[n].attrs.get("state_kind", "kv")
                       for n in self.cache_names}
        self._tails = {n: (tuple(feeds[n].shape[1:]), np.dtype(
            getattr(feeds[n], "dtype", None) or np.float32))
            for n in self.cache_names}
        self._recurrent = [n for n in self.cache_names
                           if self._kinds[n] == "recurrent"]
        #: the states with a rows axis that walks the length ladder, and
        #: the positions one of their rows stands for (1 for a ``kv`` slab)
        self._strides = {n: int(feeds[n].attrs.get("stride", 1))
                         for n in self.cache_names
                         if self._kinds[n] in ("kv", "index")}
        other = sorted({k for k in self._kinds.values() if k != "kv"})
        if "ring" in other and prefix_store is not None:
            raise ValueError(
                f"this graph keeps {' and '.join(other)} state beside its "
                f"KV slabs: a prefix store snapshots slab rows and "
                f"recurrent state, and a ring written at position mod "
                f"window cannot be cut at a prefix — build the engine "
                f"without prefix_store=")
        if other and plan is not None:
            raise ValueError(
                f"this graph keeps {' and '.join(other)} state beside its "
                f"KV slabs: a tp plan shards KV slabs by head and says "
                f"nothing of the other kinds — build the engine without "
                f"plan=")
        #: a state that cannot be cut at a shared partial depth: a lookup
        #: hits only an entry whose WHOLE key is a prefix of the prompt
        self._whole_hits = bool(other)
        #: auxiliary fetches by name, and what folds each into counters
        self._aux = list(aux or ())
        self._aux_fold = dict(aux_fold or {})
        #: the graph's fetches in front of the states: the greedy token
        #: ids where it computes them (``tokens=``), then the logits, then
        #: the auxiliary fetches.  ``_program`` puts ids in front where
        #: the graph has none, so a step's answer always starts ``(ids,
        #: logits, *aux)``: ``_head`` entries
        head = ([logits] if tokens is None else [tokens, logits]) \
            + [aux[name] for name in self._aux]
        self._graph_tokens = tokens is not None
        self._head = 2 + len(self._aux)
        self.iex = InferenceExecutor(
            head + list(cache_fetches), weights=weights,
            buckets=default_buckets(max_slots), mesh=mesh, seed=seed,
            donate=donate, validate=validate, plan=plan, decode=True)
        self.max_len = int(max_len)
        self.batch_ladder = self.iex.buckets
        self.len_ladder = tuple(b for b in default_buckets(self.max_len))
        # placeholder node -> executor feed key, by feed NAME
        self._fk = {name: self.iex._k(node) for name, node in feeds.items()}
        kv = self._kv = [n for n in self.cache_names
                         if self._kinds[n] == "kv"]
        ck0 = feeds[kv[0]] if kv else None
        # a KV slab's lanes hold ``_pack`` key rows of ``_head_dim`` each
        # (1 for a plain (B, H, L, D) placeholder, which says no more)
        if ck0 is not None:
            self._heads, self._lanes = ck0.shape[1], ck0.shape[3]
            self._head_dim = int(ck0.attrs.get("head_dim", self._lanes))
            self._pack = self._lanes // self._head_dim
        else:
            self._heads = self._lanes = self._head_dim = self._pack = 0
        #: ``(block rows, blocks, dense_len)`` where a one-token step reads
        #: chosen blocks of the slabs only (``ops/sparse_attention.py``)
        self._selected = ck0.attrs.get("selected") if kv else None
        #: whether a chunked step's attention reads a slab as far as its
        #: sequence reaches (``sdpa_prefill_op``) and not whole
        self._chunk_live = False
        self.ciex = None
        self.chunk_ladder = (1,)
        self.chunk_top = 1
        self.prefix = prefix_store
        if chunked is not None:
            if plan is not None:
                raise ValueError(
                    "chunked prefill under a tp plan is not supported: "
                    "bind the plan to the one-token entry only")
            cfeeds, clogits, ccaches, *ctokens = chunked
            caux = ctokens.pop() if ctokens and isinstance(
                ctokens[-1], dict) else {}
            if bool(ctokens) != (tokens is not None) \
                    or list(caux) != self._aux:
                raise ValueError("the one-token and the chunked entry must "
                                 "both fetch token ids, or neither, and "
                                 "the same auxiliary fetches")
            # the chunked executor MUST serve the primary's exact weight
            # bytes: independent construction would re-init every
            # variable from fold_in(seed, topo_index) over a DIFFERENT
            # topo order, silently diverging the two entries.  It is
            # handed the primary's DEVICE arrays, which it keeps as they
            # are: one set of weight buffers under both entries
            w = {self.iex.var_names[n]: self.iex.params[self.iex._k(n)]
                 for n in self.iex.var_nodes}
            self.ciex = InferenceExecutor(
                ctokens + [clogits] + [caux[name] for name in self._aux]
                + list(ccaches), weights=w,
                buckets=default_buckets(max_slots), mesh=mesh, seed=seed,
                donate=donate, validate=validate, decode=True)
            top = int(max_chunk) if max_chunk else min(32, self.max_len)
            self.chunk_ladder = tuple(default_buckets(max(2, top)))
            self.chunk_top = self.chunk_ladder[-1]
            self._cfk = {name: self.ciex._k(node)
                         for name, node in cfeeds.items()}
            self._chunk_live = bool(kv) and cfeeds[kv[0]].attrs.get(
                "chunk_read") == "live"
        # dispatch plans: one per (batch, len) pair for the one-token
        # entry plus one per (batch, chunk, len) triple for the chunked
        # entry — plan_cache_hit here is the steady-state proof
        self._plans = KeyedPlanCache(
            max_entries=(len(self.batch_ladder) * len(self.len_ladder)
                         * (1 + len(self.chunk_ladder))))
        self.bb = self.batch_ladder[0]
        self.lb = self.len_ladder[0]
        self.slots = [None] * self.bb
        self._used = [False] * self.bb       # slot served a sequence before
        self.tokens = np.zeros(self.bb, np.int32)
        self.positions = np.zeros(self.bb, np.int32)
        with SetupPhase("setup.state") as state:
            self.caches = {name: self._alloc(name, self.bb, self.lb)
                           for name in self.cache_names}
            state.nbytes = self.kv_bytes
        self._clear = None        # jitted zeroing of a slot's recurrent rows
        self._seat = None         # jitted write of a snapshot into a slot
        self._logits = None
        #: steps launched and not collected, oldest first (two at most,
        #: and only between a ``launch`` and the ``collect`` that follows)
        self._flights = []
        self._ids = None          # the newest launch's (bb,) device token ids
        self._t_work = None       # where the open span's ``step`` sample starts
        self._note_kv_bytes()

    @property
    def last_logits(self):
        """Host copy of the (batch_bucket, vocab) logits of the last step
        COLLECTED, None when no row of it emitted — what a parity check
        compares.  A step brings back token ids only; the logits stay on
        the device until this is asked for."""
        if self._logits is not None \
                and not isinstance(self._logits, np.ndarray):
            self._logits = np.asarray(self._logits)
        return self._logits

    # -- memory ------------------------------------------------------------

    def _slab_rows(self, n, name=None):
        """Slab rows of state ``name`` (a ``kv`` slab where None) that hold
        ``n`` positions: ``ceil(n / stride)`` rows, ``pack`` to a slab
        row."""
        rows = -(-int(n) // self._strides.get(name, 1))
        return -(-rows // self._pack)

    def _alloc(self, name, bb, lb):
        """Zeros for state ``name`` at batch bucket ``bb``: a ``kv`` or
        ``index`` slab with room for ``lb`` positions, any other kind at
        its declared shape."""
        import jax.numpy as jnp
        tail, dtype = self._tails[name]
        if name in self._strides:
            tail = (tail[0], self._slab_rows(lb, name), tail[2])
        return self.iex._place(jnp.zeros((bb,) + tail, dtype))

    def _resize(self, bb, lb):
        """Every state zero-padded to batch bucket ``bb``, the ``kv``
        and ``index`` slabs also to ``lb`` positions (``ring`` and
        ``recurrent`` state has no length)."""
        import jax.numpy as jnp
        with SetupPhase("setup.state") as state:
            before = self.kv_bytes
            for name, c in self.caches.items():
                pad = [(0, bb - self.bb)] + [(0, 0)] * (c.ndim - 1)
                if name in self._strides:
                    pad[2] = (0, self._slab_rows(lb, name)
                              - self._slab_rows(self.lb, name))
                if any(p != (0, 0) for p in pad):
                    self.caches[name] = self.iex._place(jnp.pad(c, pad))
            state.nbytes = self.kv_bytes - before
        grow = bb - self.bb
        self.slots += [None] * grow
        self._used += [False] * grow
        self.tokens = np.concatenate([self.tokens, np.zeros(grow, np.int32)])
        self.positions = np.concatenate([self.positions,
                                         np.zeros(grow, np.int32)])
        self.bb, self.lb = bb, lb
        self._note_kv_bytes()

    def reserve(self, batch=None, length=None):
        """Put the engine at the buckets that hold ``batch`` sequences of
        ``length`` tokens NOW, in one step, instead of walking the ladders
        as traffic arrives: a server of known size compiles one one-token
        program and one per chunk width, and none for the buckets on the
        way there.  Buckets never shrink; what is seated stays seated."""
        bb = self.bb if batch is None else self.iex.bucket_for(int(batch))
        lb = self.lb if length is None else next(
            (b for b in self.len_ladder if b >= int(length)), None)
        if bb is None or lb is None:
            raise ValueError(
                f"reserve({batch}, {length}) exceeds the engine's "
                f"max_slots {self.batch_ladder[-1]} / max_len {self.max_len}")
        self._resize(max(bb, self.bb), max(lb, self.lb))
        return self.bb, self.lb

    def state_bytes(self):
        """``{kind: bytes}`` of the device-resident state."""
        out = {}
        for name, c in self.caches.items():
            kind = self._kinds[name]
            out[kind] = out.get(kind, 0) + int(c.nbytes)
        return out

    def _note_kv_bytes(self):
        by_kind = self.state_bytes()
        record_decode("decode_kv_bytes_hw", sum(by_kind.values()))
        for kind, n in by_kind.items():
            record_decode(f"decode_state_bytes_{kind}_hw", n)
        # which slab format this process's engines serve from: key rows
        # per slab row (1 = plain (B, H, L, D) rows)
        record_decode("decode_kv_slab_format_hw", self._pack)

    @property
    def kv_bytes(self):
        return sum(self.state_bytes().values())

    def _kv_rows(self, chunk, consume=1):
        """``(read, held)``: the key rows this step's attention fetches
        of a KV slab, and the key rows the slab holds, summed over the
        batch bucket's slots.  ``consume``: the positions each slot takes
        this step (one where not said).  A step on the kernel path — a
        one-token step, or a chunked step whose graph reads through the
        kernel's chunk form (``chunk_read`` on its ``kv`` placeholder:
        GPT-2's) — reads each slot's rows as far as the sequence reaches
        once the step has appended, rounded up to a copy's tile
        (``ops.attention.kv_rows_fetched``: the compiled geometry, from
        the slab's shape); every other chunked step and the jnp path read
        the slab whole.  One slab's worth: every KV layer, and every
        reader of a shared slab, fetches the same."""
        if not self._kv:
            return 0, 0
        rows = self._slab_rows(self.lb)
        held = self.bb * rows * self._pack
        if chunk > 1 and not self._chunk_live:
            return held, held
        from ..ops.attention import _decode_gate_reason, kv_rows_fetched
        if chunk == 1 and self._selected \
                and _decode_gate_reason(rows * self._pack) is None:
            # the selected-block kernel: every live block below
            # ``dense_len`` keys, the chosen blocks whatever the length past
            block, blocks, dense_len = self._selected
            n = self.positions.astype(np.int64) + 1
            return int(np.where(n < dense_len, -(-n // block),
                                blocks).sum()) * block, held
        return kv_rows_fetched(
            self.positions + np.maximum(consume, 1),
            (self.bb, self._heads, rows, self._lanes), self._pack,
            self._tails[self._kv[0]][1].itemsize, chunk), held

    # -- capacity ----------------------------------------------------------

    @property
    def active(self):
        return sum(1 for s in self.slots if s is not None)

    @property
    def idle(self):
        """Nothing seated and no step to collect (an ``eos_id`` hit can
        leave the step launched after it in flight with no row seated)."""
        return self.active == 0 and not self._flights

    @property
    def in_flight(self):
        """The newest step launched and not collected, or None."""
        return self._flights[-1] if self._flights else None

    def capacity(self):
        """Free sequence slots, counting batch-ladder headroom."""
        return self.batch_ladder[-1] - self.active

    # -- bucket growth -----------------------------------------------------

    def _next_bucket(self, ladder, cur):
        for b in ladder:
            if b > cur:
                return b
        return None

    def _grow_batch(self):
        nb = self._next_bucket(self.batch_ladder, self.bb)
        if nb is None:
            raise RuntimeError(f"no free slot at max batch bucket {self.bb}")
        self._resize(nb, self.lb)
        record_decode("decode_batch_grows")

    def _grow_len_if_needed(self, span=1):
        """Ensure the cache length bucket covers every active position
        plus ``span`` rows about to be written (span > 1: a chunked
        step's write window — dynamic_update_slice CLAMPS out-of-range
        starts, which would shift the window onto wrong rows, so the
        bucket must cover it up front)."""
        need = max((int(self.positions[i]) for i, s in enumerate(self.slots)
                    if s is not None), default=-1) + int(span) - 1
        if need < self.lb:
            return
        lb = self.lb
        while lb <= need:
            lb = self._next_bucket(self.len_ladder, lb)
            if lb is None:
                raise RuntimeError(
                    f"cache position {need} exceeds max_len {self.max_len}")
            record_decode("decode_len_grows")
        self._resize(self.bb, lb)

    def _clear_recurrent(self, slot):
        """Zero slot ``slot``'s rows of every ``recurrent`` state: the
        sequence seated there starts from nothing.  (A ``kv`` slab is
        read below the sequence's position only and a ``ring`` by
        position, so neither needs it.)  One jitted, donated call for all
        of them, the slot a traced scalar: in place, compiled once per
        batch bucket."""
        names = self._recurrent
        if not names:
            return
        if self._clear is None:
            import jax

            def clear(states, slot):
                return tuple(jax.lax.dynamic_update_slice_in_dim(
                    s, jax.numpy.zeros((1,) + s.shape[1:], s.dtype), slot, 0)
                    for s in states)

            self._clear = jax.jit(clear, donate_argnums=(0,))
        new = self._clear(tuple(self.caches[n] for n in names),
                          np.int32(slot))
        self.caches.update(zip(names, new))
        record_decode("decode_state_clears")

    # -- join / leave ------------------------------------------------------

    def join(self, req):
        """Seat ``req`` in a free KV-cache slot (growing the batch bucket
        if every slot is taken); its first prompt token decodes at the
        next :meth:`step`.  With a prefix store, a prompt extending a
        stored prefix seats with its first ``m`` cache rows pre-filled
        (``ptr`` / ``positions`` start at ``m``): the shared prefix's
        prefill never runs."""
        slot = next((i for i, s in enumerate(self.slots) if s is None),
                    None)
        if slot is None:
            self._grow_batch()
            slot = next(i for i, s in enumerate(self.slots) if s is None)
        seq = _Sequence(req)
        m, rows = 0, None
        if self.prefix is not None:
            m, rows = self.prefix.lookup(req.prompt, whole=self._whole_hits)
        self.slots[slot] = seq
        seq.ptr = m
        self.tokens[slot] = req.prompt[m]
        self.positions[slot] = m
        if m:
            # the snapshot rows land at 0..m-1: grow the length bucket
            # first (the fresh padding is all-zero, like a cold slot)
            self._grow_len_if_needed()
            self._seat_snapshot(slot, m, rows)
            req.stream._seated_at(m, req.epoch)
        else:
            self._clear_recurrent(slot)
        if self._used[slot]:
            record_decode("decode_slot_recycles")
        self._used[slot] = True
        record_decode("decode_joins")
        if _PROTO.on:
            _PROTO.emit("decode", "seat", sid=req.stream.sid,
                        epoch=req.epoch, n=req.stream.n_tokens)
        if req.detached_ts is not None:
            # a migrated continuation reseats here: the journal replay is
            # the prompt suffix, minus whatever the prefix store seated
            record_decode_recovery("decode_recovery_reseated")
            record_decode_recovery("decode_recovery_replayed_rows",
                                   max(0, len(req.prompt) - m))
            if m:
                record_decode_recovery("decode_recovery_prefix_assisted", m)
            record_decode_latency(
                "recovery", (time.monotonic() - req.detached_ts) * 1e6)
        else:
            wait_us = (time.monotonic() - req.t_arrival) * 1e6
            record_decode_latency("join_wait", wait_us)
            record_decode("decode_join_wait_us", int(wait_us))
        if _TR.on:
            if req.fid is not None:
                _TR.flow_end("decode.recovery" if req.detached_ts is not None
                             else "decode.request", req.fid, cat="decode")
            seq.fid = _TR.flow_begin("decode.join", cat="decode")
        return slot

    def _vacate(self, slot):
        """Slot ``slot`` holds no sequence from now on.  A step in flight
        that carried one there finds the pair broken at its ``collect``
        and drops the row's answer."""
        self.slots[slot] = None
        self.tokens[slot] = 0
        self.positions[slot] = 0

    def _leave(self, slot):
        seq = self.slots[slot]
        self._vacate(slot)
        record_decode("decode_leaves")
        seq.req.stream._finish(seq.req.epoch)

    def abort(self, exc):
        """Fail every in-flight stream and clear the batch (router
        close / fatal step error).  Epoch-fenced: a stream the front
        door already migrated to a survivor ignores this replica's
        abort — closing a dead replica must not kill its rescued
        streams."""
        # a step in flight is dropped with its rows: nothing of it was
        # emitted, and ``caches`` already holds its output handles
        self._flights.clear()
        for i, seq in enumerate(self.slots):
            if seq is not None:
                self._vacate(i)
                seq.req.stream._fail(exc, seq.req.epoch)

    def evict_expired(self, now=None):
        """Deadline eviction (ISSUE 17 satellite): a seated sequence
        whose per-request deadline has passed leaves the batch NOW — its
        remaining token futures fail fast with
        ``ServeRejected('deadline')`` and the KV slot frees for the next
        join — instead of a stalled consumer holding a decode slot until
        ``max_new``.  Counted as ``decode_deadline_evictions``.  A step in
        flight that carries the sequence drops its answer at
        :meth:`collect`.  Router loop thread only, like every engine
        call.  Returns the number evicted."""
        now = time.monotonic() if now is None else now
        evicted = 0
        for i, seq in enumerate(self.slots):
            if seq is None or seq.req.deadline is None:
                continue
            if now >= seq.req.deadline:
                self._vacate(i)
                record_decode("decode_leaves")
                record_decode("decode_deadline_evictions")
                seq.req.stream._fail(ServeRejected(
                    "deadline",
                    f"decode deadline passed after {seq.emitted} of "
                    f"{seq.req.max_new} tokens"), seq.req.epoch)
                evicted += 1
        return evicted

    # -- the decode step ---------------------------------------------------

    def _program(self, ex, fk):
        """``fn(params, (feeds, slabs), prev)`` over executor ``ex``: its
        serving step with the KV slabs handed over as a TUPLE in
        ``cache_names`` order — the order of the step's cache fetches.
        A donated input is paired with the first output of its shape, in
        argument order, and every slab has the same shape: fed inside
        the feed dict the slabs arrive sorted by feed key (``s1016`` <
        ``s16``), each paired with ANOTHER layer's output, and the
        compiler has to copy nearly every slab, whole, in every step to
        honour the pairing.  In a tuple, slab ``i`` is donated to
        updated slab ``i`` and the append is in place.

        ``prev`` is the (batch,) int32 token ids the step before handed
        back, its own argument so that it is NOT donated (the host is
        still reading it): where the host feeds ``-1`` in a row's first
        column — a generating row whose token it has not seen — the row
        takes ``prev``'s id, one ``select`` in front of the graph.  The
        answer starts ``(ids, logits, *aux)`` for every graph: where the
        graph fetches no ids they are the ``argmax`` of its logits, first
        maximum.  Captures the keys, never the engine (the serve cache
        keeps it alive)."""
        import jax.numpy as jnp
        infer = ex._infer_fn()
        keys = [fk[name] for name in self.cache_names]
        ids_key, graph_tokens = fk["input_ids"], self._graph_tokens

        def step(params, fed, prev):
            feeds, slabs = fed
            ids = jnp.asarray(feeds[ids_key])
            first = ids[:, 0]
            ids = ids.at[:, 0].set(jnp.where(first < 0, prev, first))
            outs = infer(params, {**feeds, ids_key: ids,
                                  **dict(zip(keys, slabs))})
            if not graph_tokens:
                outs = [jnp.argmax(outs[0], axis=-1).astype(jnp.int32)] + outs
            return outs

        return step

    def _step_fn(self):
        """The jitted step for the CURRENT (batch_bucket, len_bucket):
        dispatched through the keyed plan cache (hit = zero planning),
        built at most once per pair through the process-wide serve cache
        (``serve_bucket_compiles`` counts the jit wrappers constructed: the
        compile itself happens at the wrapper's first call, inside that
        step's ``dispatch`` phase — ``decode_step_compile_us``)."""
        key = (self.bb, self.lb)

        def build():
            return step_cache.lookup_or_build_serve(
                self.iex, key, self._program(self.iex, self._fk))

        return self._plans.lookup(key, build)

    def _chunk_step_fn(self, chunk):
        """The jitted chunked-prefill step for the CURRENT
        (batch_bucket, chunk_bucket, len_bucket) triple — a 3-tuple key
        in the same keyed plan cache (the one-token entry's 2-tuples
        never collide), built at most once per triple through the same
        process-wide serve cache."""
        key = (self.bb, chunk, self.lb)

        def build():
            return step_cache.lookup_or_build_serve(
                self.ciex, key, self._program(self.ciex, self._cfk))

        return self._plans.lookup(key, build)

    def _pick_chunk(self, active):
        """Chunk bucket for this step: the smallest ladder bucket
        covering the largest per-row token demand (prompt remainder for
        mid-prompt rows, 1 for generating rows), shrunk while the write
        window would overrun ``max_len``, then shrunk again to the
        Sarathi-style mixed-batch efficiency floor: every row in a
        chunked step computes q_len=C, so a generating row (1 useful
        token) wastes C-1 padded row-tokens — the chunk shrinks while
        that waste exceeds the useful prefill volume (at least half the
        step's padded token volume must be prompt ingestion).  A lone
        prompt in an idle engine keeps the full chunk (best TTFT); a
        full batch of generators admitting one straggler prompt falls
        back toward the one-token entry instead of taxing every
        generator C-fold.  1 = run the one-token entry (no chunked
        graph, or nothing to chunk)."""
        if self.ciex is None:
            return 1
        want, gen = 1, 0
        for i in active:
            seq = self.slots[i]
            rem = len(seq.req.prompt) - seq.ptr
            if rem > want:
                want = rem
            if rem <= 1:
                gen += 1
        if want <= 1:
            return 1
        want = min(want, self.chunk_top)
        c = next(b for b in self.chunk_ladder if b >= want)
        maxp = max(int(self.positions[i]) for i in active)
        while c > 1 and maxp + c > self.max_len:
            c = max(b for b in self.chunk_ladder if b < c)
        pre = len(active) - gen
        while c > 1 and gen * (c - 1) > pre * c:
            c = max(b for b in self.chunk_ladder if b < c)
        return c

    def _emit_token(self, i, seq, tok, now):
        """What a collected token sets off, one-token and chunked steps
        alike: counters, latency (``token`` + first-token ``ttft``),
        prefix-snapshot insert, stream emission, and the done check.
        Returns 1 (one token emitted), or 0 when the stream's replay
        epoch fenced the emission — the stream migrated to a survivor
        while this replica was still stepping, so the stale seat is
        dropped without touching the stream (exactly-once delivery)."""
        count = seq.req.stream._emit(tok, seq.req.epoch)
        if count is False:
            self._vacate(i)
            record_decode("decode_leaves")
            record_decode_recovery("decode_recovery_fenced")
            return 0
        seq.emitted += 1
        record_decode("decode_generate_rows")
        record_decode("decode_tokens")
        record_decode_latency("token", (now - seq.t_last) * 1e6)
        if count == 1:
            # the stream's first token EVER (journal length 1) — a
            # continuation of a mid-prefill kill still records ttft
            # exactly once, anchored to the original submit
            record_decode_latency(
                "ttft", (now - seq.req.t_arrival) * 1e6)
        if seq.snap is not None:
            self.prefix.insert(seq.req.prompt, seq.snap)
            seq.snap = None
        seq.t_last = now
        if _TR.on and seq.fid is not None:
            _TR.flow_end("decode.join", seq.fid, cat="decode")
            seq.fid = None
        if seq.emitted == seq.launched and not seq.spent:
            # no later launch carries the row: its next step is fed this
            # id by the host (a later launch took it on the device)
            self.tokens[i] = tok
        # done by ``max_new`` or the cache's end was known at the launch
        # (``spent``); an ``eos_id`` match only now, with the row maybe
        # carried by the step in flight, whose answer ``collect`` drops
        if (seq.spent and seq.emitted == seq.launched) or (
                seq.req.eos_id is not None and tok == seq.req.eos_id):
            self._leave(i)
        return 1

    def _prefix_rows(self, i, seq):
        """Slot ``i``'s state at the end of its prompt for the prefix
        store, sliced on the device from the outputs of the launch that
        ended the prompt: rows ``0..P-1`` of a ``kv`` slab then hold
        exactly the prompt's KV (the sampled token is not yet written)
        and, by the masked-append invariant, the same bytes whatever
        ingestion path produced them; an ``index`` slab its first
        ``ceil(P / stride)`` rows; a ``recurrent`` state the slot's rows
        whole — the state after exactly ``P`` tokens.  They go into the
        store at the step's ``collect``, with its first token — once the
        step is known to have run — whatever a later launch has written to
        the slot by then.  None for a prompt the store does not keep."""
        p = len(seq.req.prompt)
        if p < self.prefix.min_tokens or not seq.req.keep_prefix:
            return None
        from ..ops.attention import kv_slab_to_rows
        out = {}
        for name in self.cache_names:
            state = self.caches[name]
            if name in self._strides:
                n = -(-p // self._strides[name])
                out[name] = kv_slab_to_rows(
                    state[i, :, :self._slab_rows(p, name), :],
                    self._head_dim)[:, :n, :]
            else:
                out[name] = state[i]
        return out

    def _seat_snapshot(self, slot, m, rows):
        """Write a stored snapshot of ``m`` positions into slot ``slot``:
        slab rows ``0 ...`` of every ``kv`` and ``index`` state (whole
        slab rows: the last one zero past the snapshot's rows, which no
        key of this sequence has reached yet), a ``recurrent`` state's
        rows whole.  One jitted, donated call for all of them, the slot a
        traced scalar: in place, queued behind the step in flight, traced
        once per snapshot length."""
        if self._seat is None:
            import jax
            from ..ops.attention import kv_slab_from_rows
            slabs = [name in self._strides for name in self.cache_names]

            def seat(states, rows, slot):
                out = []
                for state, new, slab in zip(states, rows, slabs):
                    if slab:
                        new = kv_slab_from_rows(new, state.shape[-1])
                    out.append(jax.lax.dynamic_update_slice(
                        state, new[None].astype(state.dtype),
                        (slot,) + (0,) * (state.ndim - 1)))
                return tuple(out)

            self._seat = jax.jit(seat, donate_argnums=(0,))
        t0 = time.perf_counter()
        nbytes = sum(int(rows[n].nbytes) for n in self.cache_names)
        with _span("decode.join.seat", cat="decode", rows=int(m),
                   bytes=nbytes):
            new = self._seat(
                tuple(self.caches[n] for n in self.cache_names),
                tuple(rows[n] for n in self.cache_names), np.int32(slot))
        self.caches.update(zip(self.cache_names, new))
        record_decode("decode_prefix_seats")
        record_decode("decode_prefix_seat_rows", int(m))
        record_decode("decode_prefix_seat_bytes", nbytes)
        record_decode("decode_prefix_seat_us",
                      int((time.perf_counter() - t0) * 1e6))

    @contextlib.contextmanager
    def stepping(self):
        """The records of one loop iteration: a ``decode.step`` span for
        the :meth:`launch` and the :meth:`collect` made inside it, in
        that order, and a ``step`` latency sample from the first
        boundary past ``plan`` to the end.  Its arguments: the step
        launched (``rows`` 0 where none was) and the tokens collected."""
        self._t_work = None
        with _Phases("decode.step", record_decode, _STEP_PHASES,
                     cat="decode") as ph:
            ph.args = {"batch": self.bb, "len": self.lb, "chunk": 0,
                       "rows": 0, "prefill": 0, "emitted": 0}
            yield ph
        if self._t_work is not None:
            record_decode_latency("step", (ph.t1 - self._t_work) / 1e3)

    def _mark_work(self, ph, phase):
        t = ph.mark(phase)
        if self._t_work is None:
            self._t_work = t

    def step(self):
        """Decode ONE batch step, start to end: :meth:`launch` it,
        :meth:`collect` it.  Every active slot consumes its pending
        token(s), caches append in place, rows past their prompt emit,
        and the tokens are on their streams when this returns.  Returns
        the number of tokens emitted.

        With a chunked entry, steps where some row still owes multiple
        prompt tokens run the q_len=C chunked path (each active row
        consumes up to ``chunk`` pending tokens — its prompt remainder,
        or its one generated token at column 0 — and the caches take a
        masked multi-row append); otherwise the PR 16 one-token path
        runs.  Only a step in which some row finished its prompt reads
        anything back — a pure-prefill step skips the D2H entirely.

        The step accounts for its own time (ISSUE 25): it is cut into
        the phases ``plan`` (chunk pick, bucket growth, plan lookup),
        ``feed`` (host feeds), ``dispatch`` (the jitted call until it
        returns, and the host state the launch advances) — these three
        are :meth:`launch` — and ``wait`` (until the token ids are ready
        on the device), ``readback`` (their D2H) and ``host`` (emission,
        stream callbacks, bookkeeping), which are :meth:`collect` — see
        :class:`~hetu_tpu.obs.trace.Phases` for the three records each
        boundary feeds.  The ``step`` latency histogram keeps its
        boundaries: ``feed`` … ``host``.

        :class:`DecodeRouter` makes the same two calls in another order
        (ISSUE 32): inside one :meth:`stepping` span it launches step
        n+1 and THEN collects step n, so the device runs n+1 while the
        host reads n back, emits and runs the clients' callbacks.  Its
        span's first three phases are of the step launched, the last
        three of the step launched the iteration before; their sum is
        still the interval between two tokens of a stream."""
        if self.idle:
            return 0
        with self.stepping() as ph:
            self.launch(ph)
            # the one just launched — and, for a caller that takes over
            # a router's engine, the one it left
            return sum(self.collect(fl, ph) for fl in list(self._flights))

    def launch(self, ph):
        """Put the next step on the device and return without waiting
        for it (a :class:`_Launch`, which :meth:`collect` takes), or None
        where no seated row has a step to make — or where the batch
        bucket changed since the step in flight, whose ids are of the old
        shape: collect that one first.  ``ph`` is the open
        :meth:`stepping` span.

        Everything read here is host state that a launch itself advances:
        ``positions``, a row's prompt pointer, the count of tokens it has
        been launched for — so a row done by ``max_new`` or by the cache's
        end is known here, without its token's value, and no later launch
        carries it (``spent``; its slot frees at the collect).  A
        generating row whose last token is still in flight is fed ``-1``:
        the program takes its id from the previous launch's id array
        (:meth:`_program`).  The state arrays are the previous launch's
        output handles, donated on: queued behind it on the device."""
        rows = [i for i, s in enumerate(self.slots)
                if s is not None and not s.spent]
        ahead = bool(self._flights)
        if not rows or (ahead and self._flights[-1].bb != self.bb):
            return None
        ph.mark("plan")
        chunk = self._pick_chunk(rows)
        # rows still taking in their prompt (more than its last token
        # is owed), beside the rows that generate
        prefill = sum(len(self.slots[i].req.prompt) - self.slots[i].ptr
                      > 1 for i in rows)
        ph.meta(rows=len(rows), chunk=chunk, prefill=prefill)
        self._grow_len_if_needed(span=chunk)
        if chunk > 1:
            fn, ex, fk = self._chunk_step_fn(chunk), self.ciex, self._cfk
        else:
            fn, ex, fk = self._step_fn(), self.iex, self._fk
        self._mark_work(ph, "feed")
        # fed as COPIES: jax's CPU client may alias an aligned numpy
        # feed zero-copy, and the engine mutates tokens/positions right
        # after dispatch, with the device still reading
        if chunk > 1:
            ids = np.zeros((self.bb, chunk), np.int32)
            consume = np.zeros(self.bb, np.int32)
            for i in rows:
                seq = self.slots[i]
                rem = len(seq.req.prompt) - seq.ptr
                if rem > 0:
                    n = min(rem, chunk)
                    ids[i, :n] = seq.req.prompt[seq.ptr:seq.ptr + n]
                else:
                    n = 1
                    ids[i, 0] = self.tokens[i]
                consume[i] = n
            feeds = {fk["input_ids"]: ids,
                     fk["positions"]: self.positions.copy(),
                     fk["valid"]: consume}
        else:
            consume = np.ones(self.bb, np.int32)
            feeds = {
                fk["input_ids"]: self.tokens.reshape(self.bb, 1).copy(),
                fk["positions"]: self.positions.copy()}
        # the key rows the stepping sequences hold once this step has
        # appended: what its attention has to read, exactly
        read, held = self._kv_rows(chunk, consume)
        after = self.positions[rows] + consume[rows]
        # and of an ``index`` slab, a row per ``stride`` positions (one
        # slab's worth, as the KV rows)
        stride = next((s for n, s in self._strides.items()
                       if self._kinds[n] == "index"), 0)
        kv_rows = (read, held, int(after.sum()) if self._kv else 0,
                   int((after // stride).sum()) if stride else 0)
        # the caches are DONATED device arrays fed straight back from
        # the previous launch's fetches — no host round-trip
        # (_place_feed's np.asarray would force one, so the engine
        # bypasses infer_rows) — in the fetches' own order
        # (``_program``); the previous ids likewise, not donated
        slabs = tuple(self.caches[name] for name in self.cache_names)
        prev = self._ids
        if prev is None or prev.shape != (self.bb,):
            prev = np.zeros(self.bb, np.int32)     # no row asks for one
        ph.mark("dispatch")
        with warnings.catch_warnings():
            # ids/positions are int32 inputs with no matching output
            # buffer; only the caches can (and do) donate
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            outs = fn(ex.params, (feeds, slabs), prev)
        self._ids = outs[0]
        for name, new in zip(self.cache_names, outs[self._head:]):
            self.caches[name] = new
        took, emits = [], False
        for i in rows:
            seq = self.slots[i]
            n = int(consume[i])
            self.positions[i] += n
            plen = len(seq.req.prompt)
            if seq.ptr + n < plen:
                # still mid-prompt: next prompt token, nothing to emit
                seq.ptr += n
                self.tokens[i] = seq.req.prompt[seq.ptr]
                took.append((i, seq, n, n, False))
                continue
            # prompt finished this step (n-1 of the consumed tokens were
            # prefill rows, the last is the generate row) or the row was
            # already generating (n == 1, zero prefill rows)
            took.append((i, seq, n, max(0, plen - seq.ptr - 1), True))
            seq.ptr = plen
            seq.launched += 1
            emits = True
            if seq.launched == 1 and self.prefix is not None:
                seq.snap = self._prefix_rows(i, seq)
            if seq.launched >= seq.req.max_new \
                    or int(self.positions[i]) >= self.max_len:
                # its last token (or the cache exhausted: stop cleanly):
                # an idle slot to the device from the next launch on
                seq.spent = True
                self.tokens[i] = self.positions[i] = 0
            else:
                self.tokens[i] = -1
        # the D2H is paid only when some row will read it — a
        # pure-prefill step never looks at the ids (ISSUE 18 satellite)
        # — and the auxiliary fetches are of every consumed token: read
        # whether or not a row emits.  It is queued behind the step NOW,
        # as np.asarray alone would queue it: waiting for the result
        # first and asking for the copy after costs a host wake-up and a
        # transfer dispatch per step with the chip idle
        back = ([outs[0]] if emits else []) + list(outs[2:self._head])
        for out in back:
            out.copy_to_host_async()
        fl = _Launch(took, back, outs[1], emits, ahead, self.bb, chunk,
                     kv_rows)
        self._flights.append(fl)
        ph.args.update(batch=self.bb, len=self.lb, chunk=chunk,
                       rows=len(rows), prefill=prefill)
        return fl

    def collect(self, fl, ph):
        """Wait for launched step ``fl``, read its token ids (and
        auxiliary fetches) back and act on them: emission, the clients'
        callbacks, latency records, ``aux_fold``, the prefix store, the
        step's counters (``decode_steps`` and the rest count COLLECTED
        steps).  A row is believed only while its slot still holds the
        sequence the launch carried there: one that left since — an
        ``eos_id`` matched at the collect before, a deadline eviction, a
        fenced seat — is skipped, its token never emitted; the cache row
        it wrote lies past what a later occupant reads before
        overwriting it.  A device error of the step surfaces here.
        Returns the number of tokens emitted (0 for ``fl`` None)."""
        if fl is None:
            return 0
        self._flights.remove(fl)
        read, aux = None, {}
        if fl.back:
            self._mark_work(ph, "wait")
            fl.back[-1].block_until_ready()
            ph.mark("readback")
            back = [np.asarray(out) for out in fl.back]
            read = back[0] if fl.emits else None
            aux = dict(zip(self._aux, back[len(back) - len(self._aux):]))
            for name, fold in self._aux_fold.items():
                for counter, n in fold(aux[name]).items():
                    record_decode(counter, n)
        self._mark_work(ph, "host")
        self._logits = fl.logits if fl.emits else None
        if not fl.emits:
            record_decode("decode_logits_skipped")
        record_decode("decode_steps")
        if fl.ahead:
            record_decode("decode_launches_ahead")
        # every row of the batch bucket computes ``chunk`` tokens,
        # whatever it holds: the denominator of the padding share
        record_decode("decode_padded_row_tokens", fl.bb * fl.chunk)
        record_decode("decode_kv_rows_read", fl.kv_rows[0])
        record_decode("decode_kv_rows_held", fl.kv_rows[1])
        record_decode("decode_kv_rows_live", fl.kv_rows[2])
        if fl.kv_rows[3]:
            record_decode("decode_index_rows_live", fl.kv_rows[3])
        if fl.chunk > 1:
            record_decode("decode_prefill_steps")
            record_decode("decode_chunk_width", fl.chunk)
            # dispatches saved vs token-by-token: the widest row would
            # have needed that many one-token steps; this step is one
            record_decode("decode_prefill_steps_saved",
                          max(row[2] for row in fl.rows) - 1)
        emitted = 0
        now = time.monotonic()
        for i, seq, n, pre, emits in fl.rows:
            if self.slots[i] is not seq:
                continue
            if aux:
                seq.req.stream._note_aux(
                    {name: a[i, :n] for name, a in aux.items()},
                    seq.req.epoch)
            record_decode("decode_prefill_rows", pre)
            if emits:
                emitted += self._emit_token(i, seq, int(read[i]), now)
        ph.args["emitted"] += emitted
        return emitted


class DecodeRouter:
    """Bounded-queue continuous-batching front end for one
    :class:`DecodeEngine`.

    ``submit`` admits a prompt and returns a :class:`DecodeStream`; the
    loop thread seats waiting requests into free slots at every step
    boundary (``continuous=True``) and runs decode steps while any
    sequence is in flight.  ``continuous=False`` is the request-level
    baseline the benchmark compares against: joins happen only when the
    engine is EMPTY (the whole batch runs to completion first — the
    slowest sequence holds everyone else's slot hostage), with the same
    arrival-anchored ``max_wait_ms`` fill window the request router
    uses.  ``close()`` rejects the queue and fails in-flight streams
    with :class:`~hetu_tpu.serving.ServeRejected`."""

    def __init__(self, engine, queue_limit=64, max_wait_ms=2.0,
                 continuous=True, start=True, name=""):
        self.engine = engine
        self.name = str(name)
        self.queue_limit = int(queue_limit)
        self.max_wait_ms = float(max_wait_ms)
        self.continuous = bool(continuous)
        self._q = collections.deque()
        self._cv = make_condition("DecodeRouter._cv")
        self._stop = False
        self._draining = False
        self._killed = False
        self._active_ct = 0       # loop's mirror of engine.active (under _cv)
        # seated-request mirror (under _cv): the requests behind
        # _active_ct.  Updated at POP time in _take_joins — before the
        # step, not after — so a replica that wedges inside a device
        # call with an empty queue still reports its in-flight batch
        # (the ISSUE 19 wedge-eject fix), and the front door's
        # detach_inflight can rescue seated streams without the loop
        # thread's cooperation.
        self._seated = []
        #: fleet replica index for the chaos token clock — set by the
        #: FrontDoor at registration; the loop reports cumulative
        #: emitted tokens to ChaosInjector.on_token for deterministic
        #: mid-generation kill:replica@<idx>:tok<n> faults
        self.chaos_idx = None
        self._tokens_total = 0    # loop thread only
        now = time.monotonic()
        self.hb_ts = now          # loop heartbeat (under _cv)
        self.progress_ts = now    # last step that made progress (under _cv)
        self._thread = None
        if start:
            self.start()

    # -- lifecycle ---------------------------------------------------------

    def start(self):
        with self._cv:
            if self._thread is not None or self._stop:
                return self
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="hetu-decode-router")
            self._thread.start()
        return self

    def close(self, timeout=None):
        with self._cv:
            self._stop = True
            pending = list(self._q)
            self._q.clear()
            self._cv.notify_all()
        if _race.ACTIVE is not None:   # ISSUE 14 preemption point
            _race.point("decode.close")
        for req in pending:
            req.stream._fail(
                ServeRejected("draining",
                              "router closed with the request queued"))
        if self._thread is not None:
            self._thread.join(timeout)
        # the loop thread has exited: engine state is safe to touch here
        self.engine.abort(
            ServeRejected("draining", "router closed mid-generation"))
        return self

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    @property
    def queue_depth(self):
        with self._cv:
            return len(self._q)

    # -- fleet replica contract (ISSUE 17) ---------------------------------

    @property
    def pending(self):
        """Queued + in-flight sequence count — the front door's per-
        replica load signal (``_active_ct`` is the loop's own mirror of
        ``engine.active``, so no cross-thread engine reads)."""
        with self._cv:
            return len(self._q) + self._active_ct

    @property
    def pending_steps(self):
        """Estimated engine STEPS queued ahead of a new request — the
        front door's deadline-gate signal (ISSUE 18 satellite).  A
        queued prompt costs ``ceil(prompt_len / chunk_top)`` prefill
        steps (prompt_len with no chunked entry, where chunk_top is 1),
        not the one step per request ``pending`` implies — long-prompt
        backlogs would otherwise admit doomed requests.  In-flight
        sequences count one step each (their next token is one step
        away; ``chunk_top`` is immutable after engine construction, so
        the cross-thread read is safe)."""
        ct = max(1, int(getattr(self.engine, "chunk_top", 1)))
        with self._cv:
            q = sum((len(r.prompt) + ct - 1) // ct for r in self._q)
            return q + self._active_ct

    def health(self):
        """Point-in-time health snapshot for the front door's sweep —
        same shape as ``ServingRouter.health``."""
        ct = max(1, int(getattr(self.engine, "chunk_top", 1)))
        with self._cv:
            q_steps = sum((len(r.prompt) + ct - 1) // ct
                          for r in self._q)
            return {"pending": len(self._q) + self._active_ct,
                    "queued": len(self._q),
                    "inflight": self._active_ct,
                    "pending_steps": q_steps + self._active_ct,
                    "hb_ts": self.hb_ts,
                    "progress_ts": self.progress_ts,
                    "killed": self._killed,
                    "draining": self._draining,
                    "stopped": self._stop}

    def stop_admitting(self):
        """Graceful-drain step 1: reject new submits (``draining``)
        while the loop keeps decoding queued + in-flight sequences."""
        with self._cv:
            self._draining = True
            self._cv.notify_all()

    def drain(self, timeout=10.0):
        """Block until the queue is empty and every seated sequence
        finished (call :meth:`stop_admitting` first).  Returns True when
        drained, False on timeout or a killed loop."""
        deadline = time.monotonic() + float(timeout)
        with self._cv:
            while self._q or self._active_ct:
                if self._killed or self._thread is None:
                    return False
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self._cv.wait(min(left, 0.05))
            return True

    def detach_queue(self):
        """Remove and return every QUEUED (not yet seated) request — the
        front door hands them to a surviving replica via :meth:`adopt`.
        Streams travel with their request, so consumers keep their
        handles."""
        with self._cv:
            orphans = list(self._q)
            self._q.clear()
            self._cv.notify_all()
            return orphans

    def detach_inflight(self):
        """Remove and return every SEATED in-flight sequence as a
        CONTINUATION request (ISSUE 19) — prompt + emitted-token
        journal, original arrival/deadline, retry count bumped.  The
        front door re-seats them on a survivor via :meth:`adopt`, and
        the journal snapshot bumps each stream's replay epoch, so this
        works on a WEDGED replica too: whatever its stuck loop emits
        after this point is fenced, not double-delivered.  Streams that
        already finished (or already migrated away) are skipped."""
        with self._cv:
            seated = list(self._seated)
            self._seated = []
            self._active_ct = 0
            self._cv.notify_all()
        if _race.ACTIVE is not None:   # recovery vs close interleavings
            _race.point("recovery.detach")
        conts = []
        for req in seated:
            stream = req.stream
            if stream.done or req.epoch != stream.epoch:
                continue
            conts.append(_continuation(req))
        return conts

    def adopt(self, reqs):
        """Admit requests detached from another decode replica —
        queued orphans and in-flight continuations alike; arrival
        timestamps and deadlines are preserved, and ``queue_limit`` is
        bypassed by design (rescue must not re-reject admitted work).
        Returns the count."""
        reqs = list(reqs)
        if not reqs:
            return 0
        if _race.ACTIVE is not None:   # recovery vs close interleavings
            _race.point("recovery.adopt")
        with self._cv:
            if self._stop or self._killed:
                raise ServeRejected(
                    "draining", "cannot adopt into a stopped router")
            self._q.extend(reqs)
            self._cv.notify_all()
        return len(reqs)

    def kill(self):
        """Chaos fail-stop: the loop exits at its next boundary WITHOUT
        touching the queue or the seated streams — the front door
        rescues the queue via :meth:`detach_queue` and resurrects
        in-flight generations via :meth:`detach_inflight` (their
        emitted-token journals live host-side; only the KV state dies
        with the replica).  Streams nobody detaches are failed by
        :meth:`close`.  New submits are rejected (``draining``)."""
        with self._cv:
            self._killed = True
            self._cv.notify_all()

    # -- admission ---------------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens=16, eos_id=None,
               deadline_ms=None, keep_prefix=True):
        """Admit one prompt (1-D int token ids).  Returns a
        :class:`DecodeStream`.  Raises
        :class:`~hetu_tpu.serving.ServeRejected` when the queue is full
        (``queue_full``), the router is closed/draining (``draining``),
        or the sequence cannot fit ``max_len`` (``over_max_len``).

        ``deadline_ms``: per-request completion budget from SUBMIT time.
        A request still queued past it fails fast at seat time; a seated
        sequence that outlives it is EVICTED mid-generation — remaining
        futures fail with reason ``deadline`` and the KV slot frees for
        the next join (``decode_deadline_evictions``).

        ``keep_prefix``: whether the engine's prefix store (if it has one)
        snapshots this prompt's state when its ingestion ends — the
        client's mark of a prompt worth keeping, as the cache breakpoints
        of public serving APIs.  ``False`` inserts nothing; the request
        still hits what the store holds."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size < 1:
            raise ValueError("empty prompt")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.size + max_new - 1 > self.engine.max_len:
            record_decode("decode_rejections")
            raise ServeRejected(
                "over_max_len",
                f"prompt {prompt.size} + {max_new} new tokens exceeds the "
                f"engine's max_len {self.engine.max_len}")
        deadline = None if deadline_ms is None \
            else time.monotonic() + float(deadline_ms) / 1e3
        fid = _TR.flow_begin("decode.request", cat="decode") \
            if _TR.on else None
        req = _DecodeRequest(prompt, max_new, eos_id, fid, deadline,
                             keep_prefix)
        with self._cv:
            if self._stop or self._killed:
                record_decode("decode_rejections")
                raise ServeRejected("draining", "router is closed")
            if self._draining:
                record_decode("decode_rejections")
                raise ServeRejected("draining",
                                    "router is draining — not admitting")
            if len(self._q) >= self.queue_limit:
                record_decode("decode_rejections")
                raise ServeRejected(
                    "queue_full",
                    f"decode queue full ({self.queue_limit} waiting) — "
                    f"shed load upstream and retry")
            self._q.append(req)
            self._cv.notify()
        return req.stream

    # -- the loop ----------------------------------------------------------

    def _take_joins(self):
        """Requests to seat before the next step (empty list: just step),
        or None at shutdown.  Continuous mode joins at every step
        boundary; request-level mode only into an EMPTY engine, after
        the arrival-anchored fill window."""
        with self._cv:
            while True:
                if self._stop or self._killed:
                    return None
                cap = self.engine.capacity()
                busy = not self.engine.idle
                if self._q and cap > 0 and (self.continuous or not busy):
                    if not self.continuous:
                        deadline = (self._q[0].t_arrival
                                    + self.max_wait_ms / 1e3)
                        while (len(self._q) < cap and not self._stop
                               and not self._killed):
                            left = deadline - time.monotonic()
                            if left <= 0:
                                break
                            self._cv.wait(left)
                        if self._stop or self._killed:
                            return None
                        cap = self.engine.capacity()
                    n = min(len(self._q), cap)
                    joins = [self._q.popleft() for _ in range(n)]
                    # mirror the about-to-be-seated work NOW, not after
                    # the step: between this pop and the post-step
                    # update the loop may wedge inside a device call,
                    # and a wedged replica with an empty queue would
                    # otherwise report pending=0 — invisible to the
                    # fleet sweep's eject condition (ISSUE 19 satellite)
                    self._seated.extend(joins)
                    self._active_ct = len(self._seated)
                    return joins
                if busy:
                    return []
                self.hb_ts = time.monotonic()   # idle loop still beats
                self._cv.wait(0.05)

    def _step_ahead(self):
        """One iteration of the loop on the engine: launch step n+1,
        THEN collect step n (ISSUE 32).  The device runs n+1 — queued
        behind n before n ended, so its launch latency hides too — while
        this thread reads n's token ids back, emits, runs the clients'
        callbacks (a closed-loop client's next ``submit`` among them),
        and comes round to take joins and plan n+2.  The first step after
        an idle engine is launched with nothing to collect; where nothing
        can be launched (every seated row's last step is in flight, or
        the batch bucket changed) the step in flight is collected alone.
        A ``kill`` or a detach between the two calls loses an
        un-collected step and nothing else: journals hold emitted tokens
        only."""
        with self.engine.stepping() as ph:
            before = self.engine.in_flight
            self.engine.launch(ph)
            return self.engine.collect(before, ph)

    def _loop(self):
        # open from one step's return to the next one's entry while rows
        # stay seated (only this thread seats or evicts, so a step
        # follows, or the loop ends): the loop's own share of the gap
        # between tokens
        between = None
        while True:
            joins = self._take_joins()
            if joins is None:
                with self._cv:
                    if self._killed:
                        # fail-stop WITHOUT failing seated streams:
                        # their emitted-token journals live host-side,
                        # so the front door resurrects them on a
                        # survivor (detach_inflight); close() still
                        # fails whatever nobody detached.  Leave the
                        # seated mirror as-is for that rescue.
                        self._cv.notify_all()
                return
            now = time.monotonic()
            for req in joins:
                if req.deadline is not None and now >= req.deadline:
                    # expired while queued: fail fast at seat time
                    # instead of burning a KV slot on a dead deadline
                    record_decode("decode_deadline_evictions")
                    req.stream._fail(ServeRejected(
                        "deadline",
                        "decode deadline passed waiting for a slot"),
                        req.epoch)
                    continue
                self.engine.join(req)
            if _race.ACTIVE is not None:   # the join/step boundary
                _race.point("decode.step")
            emitted = 0
            if not self.engine.idle:
                try:
                    self.engine.evict_expired()
                    if between is not None:
                        between.close()
                    emitted = self._step_ahead()
                except Exception as e:    # noqa: BLE001 — every in-flight
                    self.engine.abort(e)  # stream must learn its fate; the
                                          # router keeps serving new work
                between = None if self.engine.idle else _Phases(
                    "decode.between", record_decode,
                    total="decode_between_steps_us", cat="decode")
            with self._cv:
                seated = [s.req for s in self.engine.slots
                          if s is not None]
                active = len(seated)
                # a completed step with seated rows IS progress (tokens
                # moved); a truly wedged step never reaches this line.
                # NOTE: if the door detached the in-flight batch while
                # this (formerly wedged) step was running, the engine's
                # stale seats re-enter the mirror here — their emissions
                # are epoch-fenced, and the seats free themselves at
                # their next emit, so the inflation is transient.
                progressed = bool(joins) or bool(emitted) \
                    or active != self._active_ct
                self._seated = seated
                self._active_ct = active
                now = time.monotonic()
                self.hb_ts = now
                if progressed or active:
                    self.progress_ts = now
                self._cv.notify_all()   # drain() waits on this
            if emitted:
                # the chaos token clock: cumulative tokens THIS engine
                # emitted — deterministic, unlike the door's admission
                # clock, for mid-generation kill:replica@<idx>:tok<n>
                self._tokens_total += emitted
                inj = _chaos.active()
                if inj is not None and self.chaos_idx is not None:
                    inj.on_token(self.chaos_idx, self._tokens_total)


__all__ = ["DecodeEngine", "DecodeRouter", "DecodeStream"]
