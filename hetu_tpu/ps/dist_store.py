"""Multi-host sharded parameter server — TCP-routed key ownership.

Round-1 shipped a single-process host store; this module delivers the
reference's multi-server topology (``ps-lite/src/van.cc`` ZMQ transport,
worker routing ``include/ps/worker/PSAgent.h:50``, server shards
``PSFHandle.h``): every process owns the keys with ``key % world == rank``
(the promised ``hash(key) % nprocs`` ownership), runs a TCP server thread
answering pull/push/versions/SSP for its shard (backed by the native C++
:class:`~hetu_tpu.ps.store.EmbeddingStore`), and routes non-owned keys to
their owner over persistent sockets with a compact binary wire format
(length-prefixed frames; int64 keys + float32 rows — no pickle).

ASP (reference ``ParameterServerCommunicate.py:38`` async path):
``push_async`` enqueues onto a bounded background queue so device steps
overlap with PS traffic; ``flush`` drains.  SSP clocks live on rank 0
(the reference's scheduler role).

Deliberate non-goals (vs ps-lite's transport depth).  ps-lite ships
priority-scheduled message dispatch (``ps-lite/src/p3_van.h``) and an
RDMA/IBVerbs zero-copy van (``ibverbs_van.h``, ~1.2k LoC).  Neither is
reimplemented here, on purpose: on a TPU pod the dense-parameter path
rides XLA collectives over ICI (this store only carries sparse embedding
rows between host RAM and host RAM), the P3 priority trick exists to
overlap push/pull with GPU backprop at single-digit-ms step times —
covered here by ``push_async``'s bounded queue + the executor's
one-pusher gating — and RDMA presumes NIC hardware this runtime does not
manage.  What IS kept from ps-lite's transport: at-least-once retries
with (client, seq) dedup for pushes AND clock ticks (``resender.h``
semantics), socket timeouts + reconnect, and dead-peer diagnostics.

Live shard replication (``replication=2``, ps-lite's sketched server-side
replication done properly): shard ``s`` keeps a bitwise-identical backup
on rank ``(s+1) % world`` via seq-ordered op-log forwarding — the serving
server mirrors every state-mutating frame (``OP_PUSH``, the push half of
``OP_PUSH_PULL``, ``OP_SET_DATA``, heartbeat writes for shard 0) to the
backup over ``OP_REPLICATE`` *before* acking the client, under one
replication lock so the backup applies ops in primary apply order.  The
forwarded frame carries the ORIGINAL (client, seq) header, so the
backup's dedup window absorbs the promotion-window retry: a push the
primary ack'd-then-died-on, retried against the promoted backup, applies
exactly once.  Client-side, ``_rpc`` exhaustion against a shard's
serving rank no longer raises: the shard router promotes the backup
(``OP_PROMOTE``, idempotent), re-routes the in-flight fanout, and counts
``ps_failover*`` events — a killed parameter server costs one RPC
timeout, zero restarts, zero lost steps.  ``re_replicate`` restores
redundancy onto a relaunched holder (``OP_INIT`` replica tables, then an
``OP_SYNC`` chunked snapshot reusing the v3 streamed checkpoint format,
then op-log catch-up) so a second failure is survivable.

Failure model: fail-stop AND network partitions, fenced by **epochs**.
Every shard carries a monotonic fencing epoch, stamped on every
replication-relevant frame (``OP_PUSH``, ``OP_PUSH_PULL``,
``OP_SET_DATA``, ``OP_REPLICATE``, ``OP_PROMOTE``, ``OP_SYNC``/
``OP_SYNC_PUT``/``OP_INIT``) via the wire header.  Promotion bumps the
shard's epoch (``ps_epoch_bumps``), so after a partition strands a
still-alive ex-primary, the two lineages are ORDERED: any frame the
stale lineage sends into the new one — an op-log forward, a snapshot,
a write relayed for a stale client — is refused with an
:class:`EpochFenced` error (``ps_epoch_refused``) instead of applied,
and the refusal teaches the sender the newer epoch.  A healed stale
ex-primary therefore DEMOTES itself on first contact with the new
lineage (``ps_demotions``): it stops serving, drops promotability, and
waits for epoch-checked re-replication instead of acking clients —
split brain converges to exactly one serving lineage, and no write
acked by the surviving lineage is lost.  Reads stay UNFENCED on
purpose: a partitioned cell keeps serving (possibly stale) local reads
— the HET bounded-staleness contract — while writes are what fencing
makes safe.  The chaos DSL reproduces the failure deterministically
(``partition:rank<a>|rank<b>@step<n>[:heal<m>]``), and
``tools/ps_fsck.py --verify --retries N`` proves post-heal convergence
(bitwise digests + exactly one serving epoch per shard).
"""
from __future__ import annotations

import gc
import itertools
import os
import queue
import random
import socket
import struct
import threading
import time

import numpy as np

from .store import EmbeddingStore, _OPT_IDS, _OPT_NAMES, _V3_CHUNK
from .. import chaos as _chaos
from .. import race as _race
from ..analysis.protocol import PROTO as _PROTO
from ..metrics import record_cache, record_fault, record_rpc
from ..obs.lock_witness import make_condition, make_lock, make_rlock
from ..obs.trace import TRACER as _TR

# Opcodes register through hetu_tpu.ps.opcodes: the registry asserts wire-
# value uniqueness at import time (runtime twin of the tools/hetu_lint.py
# protocol check) and names frames in errors/chaos logs via op_name().
from .opcodes import defop as _defop, frame_repr, op_name

# A cyclic-GC pass can run an ``Executor.__del__`` → ``close()`` chain
# while the interrupted frame sits inside a native store call and sibling
# objects are destructed in arbitrary order — teardown reached from a GC
# finalizer must not touch the native store (see DistCacheTable.close).
# The flag is a plain module global: GC callbacks and the finalizers they
# trigger run on the collecting thread, and a concurrent close() on
# another thread spuriously skipping a flush only costs bounded staleness.
_GC_ACTIVE = False


def _gc_phase(phase, info):
    global _GC_ACTIVE
    _GC_ACTIVE = phase == "start"


gc.callbacks.append(_gc_phase)


def _in_gc_pass():
    """True while a cyclic-GC collection is running on this process."""
    return _GC_ACTIVE


OP_PULL = _defop("OP_PULL", 1)
OP_PUSH = _defop("OP_PUSH", 2)
OP_VERSIONS = _defop("OP_VERSIONS", 3)
OP_CLOCK = _defop("OP_CLOCK", 4)
OP_SSP_SYNC = _defop("OP_SSP_SYNC", 5)
OP_SSP_INIT = _defop("OP_SSP_INIT", 6)
OP_SHUTDOWN = _defop("OP_SHUTDOWN", 7)
OP_CLOCKS = _defop("OP_CLOCKS", 8)
OP_HEARTBEAT = _defop("OP_HEARTBEAT", 9)
OP_ALIVE = _defop("OP_ALIVE", 10)
#: fused push+pull (reference PsfType kSDPushPull): keys frame carries
#: ``[npush, push_keys..., pull_keys...]``, payload carries the grads —
#: one round trip per peer instead of serial push-then-pull
OP_PUSH_PULL = _defop("OP_PUSH_PULL", 11)
#: replication plane (see module docstring): mirror a mutating frame to a
#: backup; promote a backup to serving; create a replica table; set a
#: shard's full slab; snapshot-transfer for re-replication; state digest
OP_REPLICATE = _defop("OP_REPLICATE", 12)
OP_PROMOTE = _defop("OP_PROMOTE", 13)
OP_INIT = _defop("OP_INIT", 14)
OP_SET_DATA = _defop("OP_SET_DATA", 15)
OP_SYNC = _defop("OP_SYNC", 16)
OP_SYNC_PUT = _defop("OP_SYNC_PUT", 17)
OP_CHECKSUM = _defop("OP_CHECKSUM", 18)
#: shard lineage introspection: (fencing epoch, serving?) of one shard's
#: copy on the answering server — how ps_fsck asserts a single surviving
#: lineage and how liveness probes prove a "dead" rank is merely cut off
OP_EPOCH = _defop("OP_EPOCH", 19)

# op, table, nkeys, lr, payload_width, client rank, client sequence
# number, shard (-1 = the receiving server's own primary shard), and the
# sender's fencing EPOCH for that shard (see the module docstring).
# (client, seq) lets the server DEDUPLICATE retried pushes: the transport
# retries are at-least-once (the reference's ps-lite ``resender.h`` keeps
# the same ack+dedup discipline), and double-applying a gradient push would
# silently corrupt training.  The shard field routes a frame to the right
# replica after a failover moved serving away from the home rank; the
# epoch field is what lets a server refuse frames from a stale lineage
# (and lets a stale server discover it was deposed).
_HDR = struct.Struct("<BiqdIqqqq")
#: retried pushes are remembered per client this many ops back
_DEDUP_WINDOW = 4096


def _next_backoff(base, prev, cap, rng):
    """Decorrelated-jitter retry delay (AWS architecture-blog formula):
    ``min(cap, uniform(base, 3*prev))``.  Unlike the old linear ramp, no
    two workers sleep the same schedule — a fleet retrying a just-killed
    primary spreads out instead of stampeding the promoted backup in
    lockstep.  Split out so the schedule is unit-testable."""
    return min(cap, rng.uniform(base, 3.0 * max(base, prev)))


def _segment_sum(grads, inv, counts):
    """Per-unique-key float32 grad sums (the client-side half of wire
    dedup).  A one-hot CSR matmul when scipy is present — numpy's own
    scatter-reductions (``ufunc.at``) are scalar-dispatched and ~5x
    slower on the (batch, width) slabs this path moves; scipy ships
    with jax, so the fallback exists only for exotic builds and is
    COUNTED (``emb_grad_host_fallback`` in the cache family) so a run
    that silently lost the fast path is visible in its counters.
    Device-resident tables skip this host pass entirely: their grads
    arrive pre-summed by the Pallas scatter-add kernel
    (``ops/pallas/emb_cache.py``) through ``apply_update_summed``.
    Summation association may differ from a per-occurrence loop by
    float32 rounding; every cache/transport DECISION is value-independent
    (keys and counters only), so semantics are unaffected."""
    if counts.size == inv.size:         # all keys distinct: reorder only
        return np.ascontiguousarray(grads[np.argsort(inv, kind="stable")])
    try:
        from scipy import sparse as _sp
        onehot = _sp.csr_matrix(
            (np.ones(inv.size, np.float32), inv,
             np.arange(inv.size + 1, dtype=np.int64)),
            shape=(inv.size, counts.size))
        return np.asarray(onehot.T @ grads, np.float32)
    except ImportError:
        # DELIBERATELY np.add.at (ISSUE 11 satellite): simplest correct
        # scatter-reduce, slow per the note above — which is exactly why
        # it is counted; a build that trips this counter should install
        # scipy, not live on the fallback
        record_cache("emb_grad_host_fallback", 1)
        out = np.zeros((counts.size, grads.shape[1]), np.float32)
        np.add.at(out, inv, grads)
        return out


def _recv_exact(sock, n):
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if r == 0:
            raise ConnectionError("peer closed")
        got += r
    return bytes(buf)


def _send_frame(sock, *parts):
    body = b"".join(parts)
    sock.sendall(struct.pack("<q", len(body)) + body)


class FrameError(ConnectionError):
    """Corrupt frame header — framing on this stream is unrecoverable, so
    it subclasses ConnectionError: the server loop drops the connection
    and the client retries on a fresh one."""


class EpochFenced(RuntimeError):
    """A replication-relevant frame was refused by the fencing epoch.

    ``current`` is the refusing side's epoch for the shard and
    ``serving`` whether the refusing side still serves it — together
    they tell the client how to converge: a serving refuser means "you
    are behind, adopt my epoch and retry here"; a non-serving refuser
    means "I was deposed (or just demoted myself), adopt the epoch and
    re-route to the shard's other holder".  The message carries both in
    a parseable form because the refusal usually crosses the wire as a
    server-error string."""

    def __init__(self, shard, current, serving):
        self.shard, self.current, self.serving = \
            int(shard), int(current), bool(serving)
        super().__init__(
            f"shard {shard} epoch_fence cur={int(current)} "
            f"serving={int(bool(serving))} — frame from a different "
            f"lineage refused")


def _fence_info(err):
    """(current_epoch, refuser_still_serving) parsed from an epoch-fence
    refusal — local :class:`EpochFenced` or its over-the-wire string
    form — or None for any other error."""
    if isinstance(err, EpochFenced):
        return err.current, err.serving
    import re
    m = re.search(r"epoch_fence cur=(\d+) serving=([01])", str(err))
    return (int(m.group(1)), bool(int(m.group(2)))) if m else None


#: hard cap on a decoded frame length; a corrupt/hostile length prefix must
#: raise a clean protocol error, not ``bytearray(n)`` blowing up (negative)
#: or a multi-GB allocation.  Configurable: ``HETU_MAX_FRAME_MB``.
MAX_FRAME_BYTES = int(float(os.environ.get("HETU_MAX_FRAME_MB",
                                           "1024")) * 1e6)


def _recv_frame(sock):
    (n,) = struct.unpack("<q", _recv_exact(sock, 8))
    if n < 0 or n > MAX_FRAME_BYTES:
        record_fault("ps_bad_frame")
        raise FrameError(
            f"frame length {n} outside [0, {MAX_FRAME_BYTES}] "
            f"(HETU_MAX_FRAME_MB) — corrupt or hostile peer")
    return _recv_exact(sock, n)


class StoreServer:
    """Serves one process's shard over TCP (the reference server role).

    With ``replication=2`` this server additionally HOLDS (but does not
    serve) a bitwise replica of shard ``(rank-1) % world``, kept in sync
    by the op-log frames its primary forwards (``OP_REPLICATE``), and its
    own primary shard's mutations are mirrored to rank ``(rank+1) %
    world`` before each ack.  ``OP_PROMOTE`` flips a held replica to
    serving after the primary dies.  Forwarding rides the owning
    :class:`DistributedStore`'s client transport via :attr:`rpc_fn`.
    """

    def __init__(self, local: EmbeddingStore, world: int, rank: int,
                 host="127.0.0.1", port=0, replication=1, standby=False):
        self.local, self.world, self.rank = local, world, rank
        self.replication = int(replication)
        self.standby = bool(standby)
        self._ssp_lock = make_condition("StoreServer._ssp_lock")
        self._clocks = {}          # channel -> per-worker clock vector
        self._hb = {}              # rank -> (monotonic last-seen, step)
        self._hb_lock = make_lock("StoreServer._hb_lock")
        self._applied = {}         # client -> OrderedDict of recent push seqs
        self._applied_lock = make_lock("StoreServer._applied_lock")
        self._live_conns = set()
        # -- replication state (all guarded by _repl_lock where it matters)
        #: shard -> store holding that shard's rows on this server
        self._stores = {rank: local}
        self._ntables = {rank: 0}  # shard -> tables created (idempotent init)
        #: shards this server ANSWERS for.  A STANDBY (a relaunched
        #: replacement for a dead rank) starts serving NOTHING: its home
        #: shard's promoted ex-backup is the live truth, and claiming to
        #: serve an empty copy would let a role-resolved chaos kill (or a
        #: stale client) pick the wrong server.  It serves only after
        #: re-replication + an explicit OP_PROMOTE.
        standby = bool(standby and self.replication >= 2)
        self._serving = set() if standby else {rank}
        #: shards whose local copy may be PROMOTED into serving.  Table
        #: count alone cannot distinguish synced-from-primary from
        #: freshly-seed-initialized: a standby whose own training script
        #: calls init_table has the right table COUNT but step-0 data —
        #: promoting that would silently reset the shard.  A normal
        #: bring-up is promotable from the start (deterministic seeded
        #: init + the op-log keeps the backup bitwise-identical); a
        #: standby earns promotability only when an OP_SYNC snapshot
        #: completes (_sync_put loads the last table).
        self._promotable = set() if standby \
            else {rank, (rank - 1) % world} if self.replicable else {rank}
        #: shard -> fencing epoch of the lineage our copy belongs to.
        #: Bumped by promotion, adopted from newer frames (OP_INIT /
        #: OP_SYNC_PUT / OP_REPLICATE), compared on every replication-
        #: relevant frame (module docstring).  A fresh server starts at
        #: 0 and LEARNS the live epoch from re-replication — a standby
        #: can never leapfrog the serving lineage.
        self._epochs = {rank: 0}
        #: LEAF lock for the epoch map — deliberately NOT ``_repl_lock``:
        #: a primary holds ``_repl_lock`` ACROSS its forward RPC, so the
        #: receive side of a forward (OP_REPLICATE's epoch gate) must
        #: never block on the receiver's ``_repl_lock`` or three
        #: primaries forwarding around the ring deadlock until their
        #: socket timeouts fire.  ``_epoch_lock`` is never held across
        #: any RPC (or across ``_repl_lock``).
        self._epoch_lock = make_lock("StoreServer._epoch_lock")
        self._fwd_ok = {}          # shard -> live forwarding enabled
        #: shard -> monotonic time of the last broken-forward lineage
        #: probe (see _probe_lineage): rate-limits the reachability
        #: check a degraded primary runs before acking further writes
        self._fence_probe = {}
        self._oplog = {}           # shard -> buffered frames during OP_SYNC
        self._sync_parts = {}      # (shard, table) -> received snapshot chunks
        #: ordered apply+forward: the backup must see ops in primary apply
        #: order, so {apply locally; mirror} is one critical section
        self._repl_lock = make_rlock("StoreServer._repl_lock")
        #: set by the owning DistributedStore — forwards/syncs ride the
        #: client transport: rpc_fn(peer, op, table, keys, payload=...)
        self.rpc_fn = None
        if self.replicable:
            backup_of = (rank - 1) % world
            self._stores[backup_of] = EmbeddingStore()
            self._ntables[backup_of] = 0
            self._epochs[backup_of] = 0
            self._fwd_ok[rank] = True
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(64)
        self.port = self._sock.getsockname()[1]
        self._stop = False
        self._thread = threading.Thread(target=self._accept_loop, daemon=True)
        self._thread.start()

    # -- replication topology ----------------------------------------------
    @property
    def replicable(self):
        return self.replication >= 2 and self.world >= 2

    def serves(self, shard):
        """True iff this server currently ANSWERS for ``shard``."""
        return shard in self._serving

    def holds(self, shard):
        """True iff this server keeps a copy of ``shard`` (serving or
        standby backup) — the chaos kill-backup target predicate."""
        return shard in self._stores

    def epoch(self, shard):
        """This server's fencing epoch for ``shard`` (0 if unheld)."""
        return self._epochs.get(shard, 0)

    def _adopt_epoch(self, shard, epoch):
        """Advance ``shard``'s epoch to at least ``epoch`` — a locked
        max-merge, never a plain assignment: two handler threads racing
        adoptions (e.g. a stalled stale snapshot chunk vs a newer
        lineage's re-replication) must not let the LOWER epoch win, or
        the losing lineage's remaining frames would pass the fence."""
        with self._epoch_lock:
            adopted = epoch > self._epochs.get(shard, 0)
            if adopted:
                self._epochs[shard] = epoch
        if adopted and _PROTO.on:
            _PROTO.emit("ps", "adopt", rank=self.rank, shard=shard,
                        new=epoch)

    def _fence_or_adopt(self, shard, epoch, refuse_equal_if_serving=False):
        """The replica-plane epoch gate (OP_REPLICATE / OP_INIT /
        OP_SYNC_PUT): refuse frames from an OLDER lineage (and, for
        op-log forwards, an equal-epoch frame aimed at a copy we SERVE
        — two same-epoch primaries of one shard cannot exist); adopt a
        NEWER epoch, demoting first when we still thought we served the
        shard (the healed stale ex-primary's learning moment).  The
        compare runs under the leaf ``_epoch_lock`` (see its comment:
        this path sits on the receive side of forwards and must never
        block on ``_repl_lock``); adoption/demotion are monotone
        max-merges, so acting on the snapshot after release is safe."""
        with self._epoch_lock:
            cur = self._epochs.get(shard, 0)
            if epoch < cur or (refuse_equal_if_serving and epoch == cur
                               and shard in self._serving):
                record_fault("ps_epoch_refused")
                if _PROTO.on:
                    _PROTO.emit("ps", "fence_refused", gate="repl",
                                rank=self.rank, shard=shard, cur=cur,
                                got=epoch)
                raise EpochFenced(shard, cur,
                                  serving=shard in self._serving)
        if epoch > cur:
            if shard in self._serving:
                self._demote(shard, epoch)
            else:
                self._adopt_epoch(shard, epoch)

    def _demote(self, shard, new_epoch):
        """Stop serving ``shard``: a newer lineage exists (we just saw
        epoch ``new_epoch`` > ours).  The local copy stays on disk but
        is no longer promotable — it may hold writes the surviving
        lineage never saw, so promoting it would resurrect the split
        brain — and forwarding stops (our op-log is the STALE one).
        Idempotent; callers hold no particular lock (``_repl_lock`` is
        re-entrant for the under-forward caller)."""
        self._adopt_epoch(shard, new_epoch)
        with self._repl_lock:
            if shard not in self._serving:
                return
            self._serving.discard(shard)
            self._promotable.discard(shard)
            self._fwd_ok[shard] = False
            record_fault("ps_demotions")
            if _PROTO.on:
                _PROTO.emit("ps", "demote", rank=self.rank, shard=shard,
                            epoch=self._epochs.get(shard, 0))

    def _fence(self, shard, frame_epoch):
        """Fencing gate for a replication-relevant frame against a shard
        this server SERVES.  Equal epochs pass.  A NEWER frame epoch
        means we missed a promotion — demote ourselves and refuse (the
        caller must not be acked by a deposed lineage).  An OLDER frame
        epoch is a stale sender — refuse and teach it our epoch.  Must
        run BEFORE the (client, seq) dedup registration: a refused frame
        retried at the correct epoch must still apply."""
        with self._epoch_lock:    # leaf lock: see its init comment
            cur = self._epochs.get(shard, 0)
        if frame_epoch == cur:
            return
        record_fault("ps_epoch_refused")
        if _PROTO.on:
            _PROTO.emit("ps", "fence_refused", gate="serve",
                        rank=self.rank, shard=shard, cur=cur,
                        got=frame_epoch)
        if frame_epoch > cur:
            self._demote(shard, frame_epoch)
            raise EpochFenced(shard, frame_epoch, serving=False)
        raise EpochFenced(shard, cur, serving=shard in self._serving)

    def register_table(self, shard):
        """Owner bookkeeping for a table created directly on ``local``."""
        with self._repl_lock:
            self._ntables[shard] = self._ntables.get(shard, 0) + 1

    def _fwd_target(self, shard):
        """The OTHER holder of ``shard`` in the k=2 ring: its deterministic
        backup rank when we are the home primary, the home rank when we
        are the promoted backup."""
        return (shard + 1) % self.world if self.rank == shard else shard

    def _store_serving(self, shard):
        """(store, shard) serving ``shard`` (-1 = our home shard), or a
        client-visible error — a stale route hitting a non-serving holder
        must get a LOUD 'not served' answer the router can fail over on,
        never silently read a possibly-stale replica."""
        if shard < 0:
            shard = self.rank
        if shard not in self._serving:
            raise RuntimeError(
                f"shard {shard} not served by rank {self.rank} "
                f"(serving {sorted(self._serving)})")
        return self._stores[shard], shard

    def _accept_loop(self):
        while not self._stop:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            if self._stop:      # raced a concurrent stop(): refuse service
                try:
                    conn.close()
                except OSError:
                    pass
                return
            self._live_conns.add(conn)
            # named: handler threads carry the replication forward (the
            # op-log mirror to the backup), so they appear as a
            # "ps-serve-r<rank>" track in exported traces
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True,
                             name=f"ps-serve-r{self.rank}").start()

    def _serve(self, conn):
        try:
            while True:
                body = _recv_frame(conn)
                if self._stop:
                    # a stopped server must refuse ALL service, even on a
                    # connection that slipped past stop() (some platforms
                    # don't wake a blocked accept on close) — serving
                    # from a "dead" server would make kill-based fault
                    # tests pass vacuously
                    break
                try:
                    if _TR.on:
                        # server apply path: one span per handled frame
                        # on this rank's ps-serve track (the replication
                        # forward nests inside it)
                        t_h = time.perf_counter_ns()
                        stop = self._handle(conn, body)
                        _TR.complete("ps.apply", t_h,
                                     time.perf_counter_ns(), cat="ps",
                                     args={"bytes": len(body)})
                    else:
                        stop = self._handle(conn, body)
                except (ConnectionError, OSError):
                    raise
                except Exception as e:  # surface handler errors to the client
                    _send_frame(conn, b"\x01",
                                f"{type(e).__name__}: {e}".encode())
                    continue
                if stop:
                    break
        except (ConnectionError, OSError):
            pass
        finally:
            self._live_conns.discard(conn)
            conn.close()

    def _seen(self, client, seq):
        """True iff this (client, seq) NON-IDEMPOTENT op (push, clock) was
        already applied — a transport retry resent a frame whose ack was
        lost.  Window-bounded (reference ``resender.h`` ack+dedup
        semantics).  Clients base seq on time_ns so a RESTARTED client's
        sequences are always fresh (old seqs in the window cannot swallow
        the new instance's ops)."""
        from collections import OrderedDict
        with self._applied_lock:
            seen = self._applied.setdefault(client, OrderedDict())
            if seq in seen:
                return True
            seen[seq] = True
            while len(seen) > _DEDUP_WINDOW:
                seen.popitem(last=False)
            return False

    def _clock_vec(self, channel):
        v = self._clocks.get(channel)
        if v is None:
            raise RuntimeError(
                f"SSP channel {channel} not initialised: call "
                f"ssp_init(n_workers, channel={channel}) first")
        return v

    # -- op-log forwarding (the replication write path) --------------------
    def _forward(self, shard, body):
        """Mirror one already-applied mutating frame to ``shard``'s other
        holder.  MUST be called under ``_repl_lock`` (same critical
        section as the local apply), so the backup receives the op-log in
        primary apply order over one ordered connection.  During an
        ``OP_SYNC`` snapshot transfer the frame is buffered instead and
        drained after the snapshot lands (op-log catch-up).  A transport
        failure degrades to unreplicated serving (availability over
        redundancy) until ``re_replicate`` restores the backup — but an
        EPOCH-FENCE refusal means the peer belongs to a NEWER lineage
        (we are a healed stale ex-primary): then this server demotes
        itself and re-raises, so the handler refuses the client instead
        of acking a write onto the losing side of a split brain."""
        log = self._oplog.get(shard)
        if log is not None:
            log.append(bytes(body))
            return
        if not self._fwd_ok.get(shard):
            return
        t_fwd = time.perf_counter_ns() if _TR.on else 0
        try:
            if self.rpc_fn is None:
                raise RuntimeError("replication transport not attached")
            # the mirror must land inside the apply critical section,
            # BEFORE the ack: the backup sees ops in primary apply order
            # and an ack'd write is always replicated (_repl_lock's whole
            # reason to exist; _epoch_lock is the leaf that keeps the
            # receive side from blocking on us)
            # lint: held-rpc-ok ordered apply+mirror-before-ack protocol
            self.rpc_fn(self._fwd_target(shard), OP_REPLICATE, 0,
                        np.asarray([shard], np.int64), payload=bytes(body),
                        epoch=self._epochs.get(shard, 0))
            if _TR.on:
                # the replication-forwarder leg of the apply critical
                # section, on the serve thread's track
                _TR.complete("repl.forward", t_fwd,
                             time.perf_counter_ns(), cat="ps",
                             args={"shard": shard})
        except Exception as e:
            fence = _fence_info(e)
            if fence is not None:
                self._demote(shard, fence[0])
                raise EpochFenced(shard, fence[0], serving=False) from e
            self._fwd_ok[shard] = False
            record_fault("repl_forward_failed")
            import warnings
            warnings.warn(
                f"rank {self.rank}: op-log forward for shard {shard} to "
                f"rank {self._fwd_target(shard)} failed "
                f"({type(e).__name__}: {e}) — shard now serves "
                f"UNREPLICATED until re_replicate()", RuntimeWarning)

    def _probe_lineage(self, shard):
        """Rate-limited (``HETU_PS_FENCE_PROBE_S``, default 5s) epoch
        probe of ``shard``'s other holder while our forwarding to it is
        broken: if it answers with a NEWER epoch, we were deposed while
        cut off — demote and refuse the in-flight write instead of
        acking it onto the losing lineage.  An unreachable peer keeps
        today's degraded-but-available serving (without a quorum a lone
        primary cannot tell partition from backup death — CAP; the
        probe bounds how long a HEALED cut stays split-brained)."""
        interval = float(os.environ.get("HETU_PS_FENCE_PROBE_S", "5"))
        now = time.monotonic()
        if now - self._fence_probe.get(shard, -1e9) < interval:
            return
        self._fence_probe[shard] = now
        try:
            raw = self.rpc_fn(self._fwd_target(shard), OP_EPOCH, 0,
                              np.asarray([shard], np.int64),
                              op_timeout=2.0, record=False, retries=1)
            peer_epoch = struct.unpack("<qq", raw)[0]
        except Exception:
            return      # still unreachable/odd: availability wins
        if peer_epoch > self._epochs.get(shard, 0):
            self._demote(shard, peer_epoch)
            raise EpochFenced(shard, peer_epoch, serving=False)

    def _maybe_probe_degraded(self, shard):
        """When ``shard`` serves with its forwarding broken (and no sync
        in flight), run the rate-limited deposed-check BEFORE the apply
        and OUTSIDE ``_repl_lock`` — a probe RPC under the server-wide
        lock would stall every shard's write plane for the probe
        timeout, and refusing before the apply also spares the stale
        copy the refused mutation."""
        if not self._fwd_ok.get(shard) and self._oplog.get(shard) is None:
            self._probe_lineage(shard)

    def _apply_push(self, shard, store, table, keys, grads, lr, body):
        """Serving-side push: apply + mirror atomically (see _forward)."""
        if not self.replicable:
            store.push(table, keys // self.world, grads, lr)
            return
        self._maybe_probe_degraded(shard)
        with self._repl_lock:
            # lint: held-rpc-ok apply+mirror is ONE critical section
            store.push(table, keys // self.world, grads, lr)
            self._forward(shard, body)

    def _apply_set_data(self, shard, store, table, arr, body):
        if not self.replicable:
            store.set_data(table, arr)
            return
        self._maybe_probe_degraded(shard)
        with self._repl_lock:
            store.set_data(table, arr)
            self._forward(shard, body)

    def _apply_replicated(self, shard, inner):
        """Replay one forwarded frame against the HELD (non-serving)
        replica of ``shard``.  Ordering comes from the sender (one
        connection, forwards serialized under its _repl_lock), so no lock
        is needed here beyond the table's own; dedup registers the
        ORIGINAL (client, seq) so the promotion-window retry of an
        ack'd-then-died push is recognised as already applied."""
        # the inner frame's own epoch is ignored: the OUTER OP_REPLICATE
        # frame was already fenced against the forwarding primary's epoch
        iop, itable, inkeys, ilr, iwidth, iclient, iseq, _, _ = \
            _HDR.unpack_from(inner)
        ioff = _HDR.size
        ikeys = np.frombuffer(inner, np.int64, inkeys, ioff)
        ioff += inkeys * 8
        if iop == OP_HEARTBEAT:
            # mirrored liveness write (shard-0 replication): restamp with
            # OUR monotonic clock — timestamps don't travel across hosts
            with self._hb_lock:
                self._hb[int(ikeys[0])] = (time.monotonic(), int(ikeys[1]))
            return
        if iop == OP_SSP_INIT:
            # mirrored scheduler state (shard-0 replication): the SSP
            # barrier must survive rank-0 death like the liveness table
            n, channel = int(ikeys[0]), int(ikeys[1])
            with self._ssp_lock:
                cur = self._clocks.get(channel)
                if cur is None or cur.size != n:
                    self._clocks[channel] = np.zeros(n, np.int64)
            return
        if iop == OP_CLOCK:
            channel = int(ikeys[1]) if inkeys > 1 else 0
            worker = int(ikeys[0])
            if not self._seen(iclient, iseq):
                with self._ssp_lock:
                    v = self._clocks.get(channel)
                    if v is None or v.size <= worker:
                        # a re-attached standby can see ticks before any
                        # client re-runs ssp_init — grow instead of
                        # breaking the whole forward stream
                        nv = np.zeros(max(self.world, worker + 1),
                                      np.int64)
                        if v is not None:
                            nv[:v.size] = v
                        v = self._clocks[channel] = nv
                    v[worker] += 1
                    self._ssp_lock.notify_all()
            return
        store = self._stores.get(shard)
        if store is None:
            raise RuntimeError(
                f"rank {self.rank} holds no replica of shard {shard}")
        if iop == OP_PUSH:
            if not self._seen(iclient, iseq):
                grads = np.frombuffer(inner, np.float32, inkeys * iwidth,
                                      ioff).reshape(inkeys, iwidth)
                store.push(itable, ikeys // self.world, grads, ilr)
                if _PROTO.on:
                    _PROTO.emit("ps", "apply_replica", rank=self.rank,
                                shard=shard, client=iclient, seq=iseq)
        elif iop == OP_PUSH_PULL:
            npush = int(ikeys[0])
            if npush and not self._seen(iclient, iseq):
                grads = np.frombuffer(inner, np.float32, npush * iwidth,
                                      ioff).reshape(npush, iwidth)
                store.push(itable, ikeys[1:1 + npush] // self.world,
                           grads, ilr)
                if _PROTO.on:
                    _PROTO.emit("ps", "apply_replica", rank=self.rank,
                                shard=shard, client=iclient, seq=iseq)
        elif iop == OP_SET_DATA:
            n = (len(inner) - ioff) // 4
            store.set_data(itable, np.frombuffer(
                inner, np.float32, n, ioff).reshape(-1, iwidth))
        else:
            raise RuntimeError(
                f"{frame_repr(iop, itable, inkeys, client=iclient, seq=iseq)}"
                f" is not replicable")

    def _init_replica_table(self, shard, table, local_rows, width, opt_id,
                            seed, lr, beta1, beta2, eps, init_scale,
                            epoch=0):
        """Create table ``table`` in the held copy of ``shard`` with the
        SAME init parameters as the primary (deterministic seeded init ⇒
        bitwise-identical starting state).  Idempotent per table id —
        retried/raced OP_INIT frames are absorbed.

        The frame's ``epoch`` is the re-replication entry point of the
        fencing protocol: a NEWER epoch on a shard we still serve is how
        a healed stale ex-primary learns it was deposed (demote, accept
        the replica role); an OLDER epoch is a stale client trying to
        re-replicate the wrong lineage (refused)."""
        store = self._stores.get(shard)
        if store is None:
            raise RuntimeError(
                f"rank {self.rank} is not a replica holder for shard "
                f"{shard} (replication={self.replication})")
        self._fence_or_adopt(shard, epoch)
        with self._repl_lock:
            have = self._ntables.get(shard, 0)
            if table < have:
                return               # idempotent re-init
            if table > have:
                raise RuntimeError(
                    f"out-of-order replica init: table {table} before "
                    f"{have} on shard {shard}")
            tid = store.init_table(
                local_rows, width, opt=_OPT_NAMES[opt_id], lr=lr,
                beta1=beta1, beta2=beta2, eps=eps, seed=seed,
                init_scale=init_scale)
            assert tid == table, (tid, table)
            self._ntables[shard] = table + 1

    def _promote(self, shard, want_tables, want_epoch=0):
        """Serve ``shard`` from our held replica (idempotent); returns
        the shard's resulting fencing epoch.  Refuses when we don't hold
        the shard, hold fewer tables than the client expects, or the
        copy was never synced (a standby's self-created tables have the
        right COUNT but seed-initialized data — promoting that would
        silently reset the shard to step 0 instead of raising a loud
        both-copies-gone outage).

        A REAL promotion bumps the epoch past both our replica's last
        known epoch and the promoting client's (``want_epoch`` = client
        epoch + 1), so the new lineage strictly dominates the old one:
        the deposed primary's frames are refusable, and every client
        that promotes concurrently converges on the same epoch (the
        idempotent path returns the current epoch without bumping)."""
        with self._repl_lock:
            cur = self._epochs.get(shard, 0)
            if shard in self._serving:
                if want_epoch > cur:       # concurrent promoter raced a
                    cur = want_epoch       # newer lineage onto us: adopt
                    self._adopt_epoch(shard, cur)
                return cur
            if not self.replicable:
                raise RuntimeError(
                    f"rank {self.rank} runs unreplicated "
                    f"(replication={self.replication}) — cannot promote "
                    f"shard {shard}")
            store = self._stores.get(shard)
            if store is None or self._ntables.get(shard, 0) < want_tables:
                raise RuntimeError(
                    f"rank {self.rank} replica of shard {shard} has "
                    f"{self._ntables.get(shard, 0)}/{want_tables} tables "
                    f"— not promotable")
            if shard not in self._promotable and want_tables > 0:
                raise RuntimeError(
                    f"rank {self.rank} copy of shard {shard} was never "
                    f"synced from the serving replica — not promotable")
            new_epoch = max(cur + 1, want_epoch)
            self._adopt_epoch(shard, new_epoch)
            self._serving.add(shard)
            # the old primary is presumed dead (or fenced off): no
            # forwarding until re_replicate() attaches a fresh backup
            self._fwd_ok[shard] = False
            record_fault("ps_promoted")
            record_fault("ps_epoch_bumps")
            if _PROTO.on:
                _PROTO.emit("ps", "promote", rank=self.rank, shard=shard,
                            old=cur, new=new_epoch, want=want_epoch)
            return new_epoch

    def _sync_to(self, shard, target):
        """Re-replication source half: snapshot every table of ``shard``
        (the store's own streamed save format — v3 chunked for the numpy
        fallback), push it to ``target`` in bounded ``OP_SYNC_PUT``
        frames, then drain the op-log buffered during the transfer and
        resume live forwarding.  Mutations are blocked only for the
        snapshot-to-disk and the drain, not the transfer; the transfer
        streams chunk-by-chunk off the temp files so peak RSS stays one
        chunk, never a table copy (the v3 format's whole point)."""
        import tempfile
        if shard not in self._serving:
            raise RuntimeError(
                f"rank {self.rank} does not serve shard {shard} — "
                f"only the serving replica can source a sync")
        if not self.replicable:
            raise RuntimeError("replication disabled on this server")
        if target != self._fwd_target(shard):
            raise RuntimeError(
                f"shard {shard}: rank {target} is not its replica slot "
                f"(expected {self._fwd_target(shard)})")
        store = self._stores[shard]
        ntabs = self._ntables.get(shard, 0)
        paths = []
        with self._repl_lock:
            if self._fwd_ok.get(shard):
                return               # redundancy already live: no-op
            if self._oplog.get(shard) is not None:
                raise RuntimeError(
                    f"shard {shard}: sync already in progress")
            self._fwd_ok[shard] = False
            self._oplog[shard] = []
            for tid in range(ntabs):
                fd, path = tempfile.mkstemp(prefix="hetu_ps_sync_")
                os.close(fd)
                paths.append(path)
                store.save(tid, path)
        try:
            chunk = min(_V3_CHUNK, max(1 << 20, MAX_FRAME_BYTES // 2))
            epoch = self._epochs.get(shard, 0)
            for tid, path in enumerate(paths):
                size = os.path.getsize(path)
                nch = max(1, -(-size // chunk))
                with open(path, "rb") as f:
                    for ci in range(nch):
                        self.rpc_fn(
                            target, OP_SYNC_PUT, tid,
                            np.asarray([shard, ci, nch, size, ntabs],
                                       np.int64),
                            payload=f.read(chunk), epoch=epoch)
            with self._repl_lock:
                for frame in self._oplog.pop(shard, []):
                    # the op-log drain and the fwd_ok flip must be atomic
                    # against concurrent applies, or a racing write could
                    # land between catch-up and live forwarding
                    # lint: held-rpc-ok op-log catch-up precedes live fwd
                    self.rpc_fn(target, OP_REPLICATE, 0,
                                np.asarray([shard], np.int64),
                                payload=frame, epoch=epoch)
                self._fwd_ok[shard] = True
            record_fault("ps_re_replicated")
        except Exception as e:
            with self._repl_lock:
                self._oplog.pop(shard, None)
                self._fwd_ok[shard] = False
            fence = _fence_info(e)
            if fence is not None:
                # the target refused OUR snapshot: it belongs to a newer
                # lineage, so WE are the stale ex-primary trying to
                # overwrite the survivor — learn the epoch and demote
                # instead of retrying this doomed sync every tick
                self._demote(shard, fence[0])
                raise EpochFenced(shard, fence[0], serving=False) from e
            record_fault("ps_re_replicate_failed")
            raise
        finally:
            for path in paths:
                try:
                    os.unlink(path)
                except OSError:
                    pass

    def _sync_put(self, shard, table, ci, nch, total, ntabs, payload,
                  epoch=0):
        """Re-replication sink half: append snapshot chunks straight to a
        temp file (bounded RSS) and load the completed table via the
        store's own load path.  Once every one of the shard's ``ntabs``
        tables has landed, the copy becomes PROMOTABLE.  Chunks arrive in
        order (one connection); a retried chunk is idempotent.  The
        snapshot carries the source lineage's epoch: an OLDER epoch is a
        stale source trying to overwrite us with the losing lineage
        (refused); a newer one is adopted — and demotes us first if we
        still thought we served the shard."""
        import tempfile
        store = self._stores.get(shard)
        if store is None:
            raise RuntimeError(
                f"rank {self.rank} holds no replica of shard {shard}")
        self._fence_or_adopt(shard, epoch)
        if shard in self._serving and shard != self.rank:
            raise RuntimeError(
                f"rank {self.rank} already SERVES shard {shard} — "
                f"refusing a snapshot that would overwrite live state")
        part = self._sync_parts.get((shard, table))
        if part is None:
            fd, path = tempfile.mkstemp(prefix="hetu_ps_sync_")
            os.close(fd)
            part = self._sync_parts[(shard, table)] = {
                "path": path, "next": 0}
        if ci < part["next"]:
            return                   # retried chunk
        if ci != part["next"]:
            raise RuntimeError(
                f"sync chunk gap: got {ci}, expected {part['next']}")
        with open(part["path"], "ab") as f:
            f.write(payload)
        part["next"] = ci + 1
        if part["next"] < nch:
            return
        del self._sync_parts[(shard, table)]
        try:
            if os.path.getsize(part["path"]) != total:
                raise RuntimeError(
                    f"sync snapshot truncated: "
                    f"{os.path.getsize(part['path'])}/{total} bytes")
            store.load(table, part["path"])
        finally:
            try:
                os.unlink(part["path"])
            except OSError:
                pass
        with self._repl_lock:
            done = self._sync_parts.setdefault(("loaded", shard), set())
            done.add(table)
            if len(done) >= ntabs:
                del self._sync_parts[("loaded", shard)]
                self._promotable.add(shard)
                if _PROTO.on:
                    _PROTO.emit("ps", "sync_done", rank=self.rank,
                                shard=shard,
                                epoch=self._epochs.get(shard, 0))

    def _handle(self, conn, body):
        op, table, nkeys, lr, width, client, seq, shard, epoch = \
            _HDR.unpack_from(body)
        off = _HDR.size
        keys = np.frombuffer(body, np.int64, nkeys, off)
        off += nkeys * 8
        if op == OP_PULL:
            # reads are deliberately UNFENCED: a partitioned cell keeps
            # serving (bounded-staleness) local reads — fencing guards
            # the write plane, where split-brain divergence is made
            store, shard = self._store_serving(shard)
            out = store.pull(table, keys // self.world)
            _send_frame(conn, b"\x00",
                        np.ascontiguousarray(out, np.float32).tobytes())
        elif op == OP_PUSH:
            store, shard = self._store_serving(shard)
            # fence BEFORE the dedup registration: a refused frame
            # retried at the correct epoch must not read as a duplicate
            self._fence(shard, epoch)
            if not self._seen(client, seq):
                grads = np.frombuffer(body, np.float32, nkeys * width,
                                      off).reshape(nkeys, width)
                self._apply_push(shard, store, table, keys, grads, lr, body)
                if _PROTO.on:
                    _PROTO.emit("ps", "apply", rank=self.rank, shard=shard,
                                client=client, seq=seq, epoch=epoch)
            elif _PROTO.on:
                _PROTO.emit("ps", "dedup_hit", rank=self.rank, shard=shard,
                            client=client, seq=seq)
            _send_frame(conn, b"\x00\x01")
        elif op == OP_PUSH_PULL:
            # fused SDPushPull: apply the push shard, answer the pull shard,
            # one ack.  The push half is as non-idempotent as OP_PUSH — a
            # retried frame skips it but still serves the (idempotent) pull.
            store, shard = self._store_serving(shard)
            self._fence(shard, epoch)
            npush = int(keys[0])
            push_keys = keys[1:1 + npush]
            pull_keys = keys[1 + npush:]
            if npush and not self._seen(client, seq):
                grads = np.frombuffer(body, np.float32, npush * width,
                                      off).reshape(npush, width)
                self._apply_push(shard, store, table, push_keys, grads, lr,
                                 body)
                if _PROTO.on:
                    _PROTO.emit("ps", "apply", rank=self.rank, shard=shard,
                                client=client, seq=seq, epoch=epoch)
            out = store.pull(table, pull_keys // self.world)
            _send_frame(conn, b"\x00",
                        np.ascontiguousarray(out, np.float32).tobytes())
        elif op == OP_VERSIONS:
            store, shard = self._store_serving(shard)
            v = store.versions(table, keys // self.world)
            _send_frame(conn, b"\x00",
                        np.ascontiguousarray(v, np.int64).tobytes())
        elif op == OP_SET_DATA:
            store, shard = self._store_serving(shard)
            self._fence(shard, epoch)
            n = (len(body) - off) // 4
            arr = np.frombuffer(body, np.float32, n, off).reshape(-1, width)
            self._apply_set_data(shard, store, table, arr, body)
            _send_frame(conn, b"\x00\x01")
        elif op == OP_REPLICATE:
            # op-log from a STALE lineage (a healed ex-primary that
            # never heard it was deposed) is refused — which is what
            # turns its next client ack into a self-demotion
            s = int(keys[0])
            self._fence_or_adopt(s, epoch, refuse_equal_if_serving=True)
            self._apply_replicated(s, body[off:])
            _send_frame(conn, b"\x00\x01")
        elif op == OP_PROMOTE:
            ep = self._promote(int(keys[0]), int(keys[1]),
                               int(keys[2]) if nkeys > 2 else 0)
            _send_frame(conn, b"\x00", struct.pack("<q", ep))
        elif op == OP_INIT:
            # keys=[local_rows, width, opt_id, seed]; payload packs the
            # float init params (NaN init_scale = store default)
            p = struct.unpack_from("<5d", body, off)
            self._init_replica_table(
                shard, table, int(keys[0]), int(keys[1]), int(keys[2]),
                int(keys[3]), p[0], p[1], p[2], p[3],
                None if p[4] != p[4] else p[4], epoch=epoch)
            _send_frame(conn, b"\x00\x01")
        elif op == OP_SYNC:
            self._fence(int(keys[0]), epoch)
            self._sync_to(int(keys[0]), int(keys[1]))
            _send_frame(conn, b"\x00\x01")
        elif op == OP_SYNC_PUT:
            self._sync_put(int(keys[0]), table, int(keys[1]), int(keys[2]),
                           int(keys[3]), int(keys[4]), body[off:],
                           epoch=epoch)
            _send_frame(conn, b"\x00\x01")
        elif op == OP_EPOCH:
            # lineage introspection (fsck, liveness probes): the fencing
            # epoch of one shard's copy here + whether we serve it.
            # Answered for ANY shard (0 if unheld) — the probe must work
            # against a standby or a demoted holder too.
            s = self.rank if not nkeys else int(keys[0])
            _send_frame(conn, b"\x00",
                        struct.pack("<qq", self._epochs.get(s, 0),
                                    int(s in self._serving)))
        elif op == OP_CHECKSUM:
            # full-state digest of ANY held copy (serving or standby) —
            # tools/ps_fsck.py compares primary vs backup for divergence
            s = self.rank if shard < 0 else shard
            store = self._stores.get(s)
            if store is None:
                raise RuntimeError(
                    f"rank {self.rank} holds no copy of shard {s}")
            _send_frame(conn, b"\x00", store.state_digest(table).encode())
        elif op == OP_SSP_INIT:
            n, channel = int(keys[0]), int(keys[1])
            with self._ssp_lock:
                # idempotent: every rank calls init; re-zeroing on the
                # second caller would erase live arrivals.  A different
                # size is an explicit reset (fresh run, same server).
                cur = self._clocks.get(channel)
                if cur is None or cur.size != n:
                    self._clocks[channel] = np.zeros(n, np.int64)
            if self.replicable and 0 in self._serving:
                with self._repl_lock:
                    self._forward(0, body)
            _send_frame(conn, b"\x00\x01")
        elif op == OP_CLOCK:
            # clock ticks are as non-idempotent as pushes: a retried tick
            # whose ack was lost must not double-increment (it would fake
            # an arrival and let stale peers past the SSP bound).  Like
            # heartbeats, the scheduler's clock vectors ride shard 0's
            # replication so the SSP barrier survives rank-0 death.
            channel = int(keys[1]) if nkeys > 1 else 0
            if not self._seen(client, seq):
                with self._ssp_lock:
                    self._clock_vec(channel)[int(keys[0])] += 1
                    self._ssp_lock.notify_all()
                if self.replicable and 0 in self._serving:
                    with self._repl_lock:
                        self._forward(0, body)
            _send_frame(conn, b"\x00\x01")
        elif op == OP_SSP_SYNC:
            worker, staleness = int(keys[0]), int(keys[1])
            channel = int(keys[2]) if nkeys > 2 else 0
            # the server-side wait is ALWAYS bounded (570s < the client's
            # 600s no-timeout socket deadline) by a TOTAL monotonic
            # deadline — bounding each cond.wait alone would reset the
            # budget on every notify_all (any tick, any channel) and
            # leak this handler thread under steady clock traffic
            deadline = time.monotonic() + (lr if lr > 0 else 570.0)
            ok = True
            with self._ssp_lock:
                v = self._clock_vec(channel)
                while v[worker] - v.min() > staleness:
                    left = deadline - time.monotonic()
                    if left <= 0 or not self._ssp_lock.wait(left):
                        ok = False
                        break
                    v = self._clock_vec(channel)
            _send_frame(conn, b"\x00", b"\x01" if ok else b"\x00")
        elif op == OP_CLOCKS:
            channel = int(keys[0]) if nkeys else 0
            with self._ssp_lock:
                v = self._clock_vec(channel).copy()
            _send_frame(conn, b"\x00", v.tobytes())
        elif op == OP_HEARTBEAT:
            # liveness ping: rank + current step.  Idempotent (a retried
            # ping just refreshes the timestamp), so no dedup needed.
            with self._hb_lock:
                self._hb[int(keys[0])] = (time.monotonic(), int(keys[1]))
            # the failure DETECTOR must itself survive failure: liveness
            # state rides shard 0's replication ring, so rank 0's backup
            # holds a live alive_mask when rank 0 dies (the backup
            # restamps with its own monotonic clock on apply)
            if self.replicable and 0 in self._serving:
                with self._repl_lock:
                    self._forward(0, body)
            _send_frame(conn, b"\x00\x01")
        elif op == OP_ALIVE:
            # keys=[n_workers], lr carries deadline_ms: int64 mask, 1 iff
            # the rank pinged within the deadline.  A rank that NEVER
            # pinged counts alive: liveness only declares death for ranks
            # it has seen alive (startup stagger — e.g. 30 s of backend
            # init before the first ping — must not read as death; a
            # rank that truly never starts is the launcher/supervisor's
            # failure domain, not the heartbeat's).
            n = int(keys[0])
            # keys=[n, 1] requests STRICT mode: never-pinged counts dead
            # (the failover cross-check wants positive evidence of life,
            # not the benefit of the doubt the exclusion path grants)
            strict = nkeys > 1 and bool(keys[1])
            deadline_s = (lr if lr > 0 else 10_000.0) / 1e3
            now = time.monotonic()
            mask = np.zeros(n, np.int64)
            with self._hb_lock:
                for r in range(n):
                    rec = self._hb.get(r)
                    mask[r] = (0 if strict else 1) if rec is None else \
                        int(now - rec[0] <= deadline_s)
            _send_frame(conn, b"\x00", mask.tobytes())
        elif op == OP_SHUTDOWN:
            _send_frame(conn, b"\x00\x01")
            return True
        else:
            raise ValueError(
                f"unknown opcode in frame "
                f"{frame_repr(op, table, nkeys, shard, client, seq)}")
        return False

    def stop(self):
        self._stop = True
        try:    # shutdown (not just close) wakes a blocked accept() on
            self._sock.shutdown(socket.SHUT_RDWR)   # platforms where
        except OSError:                             # close() alone doesn't
            pass
        try:
            self._sock.close()
        except OSError:
            pass
        # close live per-connection sockets too: a stopped server must look
        # DEAD to peers (fast ConnectionError), not wedged
        for conn in list(self._live_conns):
            try:
                conn.close()
            except OSError:
                pass


class DistributedStore:
    """Worker+server pair with ``key % world`` routing (EmbeddingStore API).

    ``endpoints``: list of (host, port) for every rank, index = rank; this
    process's entry may be None (it uses its own server's bound port).
    """

    def __init__(self, rank, world, endpoints=None, host="127.0.0.1",
                 port=0, async_queue=64, rpc_timeout=60.0, rpc_retries=3,
                 connect_timeout=10.0, replication=None, standby=None):
        self.rank, self.world = rank, world
        # standby (env HETU_PS_STANDBY, set by the launcher's solo-respawn
        # path): this process replaces a dead rank — its server holds its
        # shards but serves nothing until re-replication re-attaches it
        if standby is None:
            standby = os.environ.get("HETU_PS_STANDBY", "") == "1"
        # replication=k (env default HETU_PS_REPLICATION): 1 = today's
        # single-copy topology, 2 = every shard keeps a live backup on the
        # next rank (see the module docstring).  world=1 has nowhere to
        # put a backup, so it silently degrades to 1.
        if replication is None:
            replication = int(os.environ.get("HETU_PS_REPLICATION", "1"))
        replication = int(replication)
        if not 1 <= replication <= 2:
            raise ValueError(
                f"replication={replication} unsupported: 1 (off) or 2 "
                f"(primary + one ring backup)")
        self.replication = replication if world >= 2 else 1
        self.local = EmbeddingStore()
        self.server = StoreServer(self.local, world, rank, host, port,
                                  replication=self.replication,
                                  standby=standby)
        self.endpoints = list(endpoints) if endpoints else [None] * world
        self.endpoints[rank] = (host, self.server.port)
        self.rpc_timeout = rpc_timeout
        self.rpc_retries = max(1, rpc_retries)
        self.connect_timeout = connect_timeout
        # retry backoff: exponential with decorrelated jitter (see
        # _next_backoff) so a worker fleet never stampedes a promoted
        # backup in lockstep; base is env-tunable
        self._backoff_base = float(
            os.environ.get("HETU_RPC_BACKOFF_MS", "50")) / 1e3
        self._backoff_cap = 1.0
        self._backoff_rng = random.Random()
        # seq base = time_ns: strictly increasing across process restarts,
        # so a relaunched worker's sequences can never collide with its
        # predecessor's entries still in the server dedup window
        self._seq = itertools.count(time.time_ns())  # thread-safe in CPython
        self._conns = {}
        self._conn_locks = {}
        self._connect_lock = make_lock("DistributedStore._connect_lock")  # guards the conn dicts
        self._pool = None                      # lazy RPC fan-out pool
        self._tables = {}
        self._table_init_kw = {}   # tid -> init kwargs (replica re-init)
        #: shard -> rank currently serving it; failover flips an entry to
        #: the shard's other replica holder.  Every client converges
        #: independently (promote is idempotent).
        self._route = list(range(world))
        #: shard -> the fencing epoch this client believes is current.
        #: Advanced by OP_PROMOTE acks and by epoch-fence refusals — a
        #: refused write teaches the client the surviving lineage before
        #: the retry (module docstring).
        self._epoch = [0] * world
        #: leaf lock for the fence-adoption state (_epoch/_route/_flip
        #: _epoch): _note_fence runs on whichever thread saw the refusal
        #: — fanout pool workers, the heartbeat pinger, the async push
        #: worker — and an unlocked check-then-act let two racing
        #: refusals regress the epoch or double-flip the route BACK onto
        #: the deposed rank (ISSUE 14 shared-state finding).  Never held
        #: across an RPC.
        self._fence_lock = make_lock("DistributedStore._fence_lock")
        self._flip_epoch = {}      # shard -> epoch at which route flipped
        self._failed_over = set()  # shards running without redundancy
        self._queue = queue.Queue(maxsize=async_queue)
        self._async_thread = None
        self._hb_thread = None
        self._hb_stop = threading.Event()
        # the server's op-log forwards / sync transfers ride this client's
        # transport (persistent sockets, timeouts, retries)
        self.server.rpc_fn = self._rpc
        # HETU_CHAOS=seed:spec activates the chaos harness for every store
        # in the process; the server registers as a kill:ps target
        inj = _chaos.active() or _chaos.install_from_env()
        if inj is not None:
            inj.register_server(rank, self.server)

    # -- connections -------------------------------------------------------
    def _conn(self, peer):
        # per-peer locks so a slow/unreachable peer cannot stall RPCs to
        # healthy peers; the short global lock only guards the dicts
        with self._connect_lock:
            lock = self._conn_locks.setdefault(
                peer, make_lock("DistributedStore._conn_locks[*]"))
        with lock:
            if peer not in self._conns:
                s = socket.create_connection(self.endpoints[peer],
                                             timeout=self.connect_timeout)
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                self._conns[peer] = s
            return self._conns[peer], lock

    def _drop_conn(self, peer):
        with self._connect_lock:
            lock = self._conn_locks.setdefault(
                peer, make_lock("DistributedStore._conn_locks[*]"))
        with lock:
            s = self._conns.pop(peer, None)
            if s is not None:
                try:
                    s.close()
                except OSError:
                    pass

    def _rpc(self, peer, op, table, keys, payload=b"", lr=-1.0, width=0,
             op_timeout=None, shard=-1, seq=None, record=True,
             retries=None, epoch=0):
        """One request/response against ``peer``'s shard.

        Transport discipline (reference ``ps-lite/src/resender.h``): every
        socket op carries a timeout, a failed op drops the connection and
        retries on a fresh one with decorrelated-jitter backoff (the same
        (client, seq) header lets the server dedup a retried PUSH whose
        ack was lost), and exhausted retries raise a *diagnosable*
        RuntimeError naming the peer — never a raw OSError or an
        unbounded blocking recv (the executor's SSP-watchdog discipline
        applied to the transport).  ``seq`` may be pinned by the caller so
        a failover retry of the SAME logical op against the promoted
        backup is recognised by its dedup window (see _rpc_shard)."""
        keys = np.ascontiguousarray(keys, np.int64)
        hdr = _HDR.pack(op, table, keys.size, lr, width, self.rank,
                        next(self._seq) if seq is None else seq, shard,
                        epoch)
        # per-opcode latency histogram + payload-bytes counter (the
        # telemetry registry) — a socket round trip dwarfs two clock
        # reads, so the measurement is unconditional; counter-silent
        # probes (record=False) stay invisible here too
        t_rpc = time.perf_counter_ns()
        nbytes = keys.nbytes + len(payload)
        last_err = None
        delay = 0.0
        for attempt in range(self.rpc_retries if retries is None
                             else max(1, retries)):
            if attempt:
                if record:
                    record_fault("ps_rpc_retry")
                delay = _next_backoff(self._backoff_base, delay,
                                      self._backoff_cap, self._backoff_rng)
                time.sleep(delay)
            try:
                # chaos harness: the active schedule may drop, delay,
                # duplicate, or wedge this frame (hetu_tpu.chaos); a clean
                # run pays one global read
                inj = _chaos.active()
                act = inj.on_send(peer, op, src=self.rank) \
                    if inj is not None else None
                if act is not None and act[0] == "drop":
                    raise TimeoutError(
                        f"chaos: dropped {op_name(op)} frame")
                sock, lock = self._conn(peer)
                with lock:
                    sock.settimeout(op_timeout if op_timeout is not None
                                    else self.rpc_timeout)
                    if act is not None and act[0] == "delay":
                        time.sleep(act[1] / 1e3)
                    elif act is not None and act[0] == "wedge":
                        # hold the socket past the op deadline's spirit:
                        # the client sees a timeout and retries fresh
                        time.sleep(act[1] / 1e3)
                        raise TimeoutError(
                            f"chaos: wedged socket on {op_name(op)}")
                    _send_frame(sock, hdr, keys.tobytes(), payload)
                    if act is not None and act[0] == "dup":
                        # at-least-once retry simulation: same (client,
                        # seq) frame twice — the server's dedup window
                        # must apply non-idempotent ops exactly once
                        _send_frame(sock, hdr, keys.tobytes(), payload)
                        _recv_frame(sock)       # discard the dup's ack
                    resp = _recv_frame(sock)
                break
            except (TimeoutError, ConnectionError, OSError) as e:
                last_err = e
                self._drop_conn(peer)
        else:
            if record:
                record_fault("ps_peer_unreachable")
            host_, port_ = self.endpoints[peer] or ("?", "?")
            raise RuntimeError(
                f"PS peer {peer} at {host_}:{port_} unreachable after "
                f"{self.rpc_retries} attempts sending "
                f"{frame_repr(op, table, keys.size, shard)} "
                f"({type(last_err).__name__}: {last_err}) — server process "
                f"dead or wedged")
        if not resp or resp[:1] == b"\x01":
            raise RuntimeError(
                f"PS rank {peer} error on {op_name(op)}: "
                f"{resp[1:].decode(errors='replace')}")
        if record:
            name = op_name(op)
            record_rpc(name, (time.perf_counter_ns() - t_rpc) / 1e3,
                       nbytes)
            if _TR.on:
                _TR.complete("rpc:" + name, t_rpc,
                             time.perf_counter_ns(), cat="ps",
                             args={"peer": peer, "bytes": nbytes,
                                   "shard": shard})
        return resp[1:]

    # -- shard routing + client-side failover ------------------------------
    @staticmethod
    def _failover_worthy(err):
        """Exhausted transport (peer dead/wedged) or a stale route hitting
        a non-serving holder; application errors must still raise."""
        msg = str(err)
        return "unreachable" in msg or "not served" in msg

    def _note_fence(self, shard, err):
        """Adopt the surviving lineage an epoch-fence refusal names:
        advance this client's epoch for ``shard`` (a locked max-merge —
        the server-side ``_adopt_epoch`` discipline) and — when the
        refuser no longer serves (it was deposed or just demoted
        itself) — flip the route to the shard's other holder and mark
        the shard for re-replication (the demoted copy is stale by
        construction).  The flip is recorded PER EPOCH: refusals land on
        whichever thread sent the frame (fanout pool, heartbeat pinger,
        async worker), and two racing refusals from one fence event must
        flip the route ONCE — an unguarded toggle sent the second flip
        straight back to the deposed rank (ISSUE 14 regression test)."""
        cur, serving = _fence_info(err)
        with self._fence_lock:
            known = self._epoch[shard]
            if cur > known:
                self._epoch[shard] = known = cur
            # flip only on information at least as new as ours (a STALE
            # refusal must not steer the route away from the lineage we
            # already follow), and at most once per epoch
            if not serving and cur == known \
                    and self._flip_epoch.get(shard) != cur:
                self._flip_epoch[shard] = cur
                dead = self._route[shard]
                self._route[shard] = (shard + 1) % self.world \
                    if dead == shard else shard
                self._failed_over.add(shard)
                if _PROTO.on:
                    _PROTO.emit("ps", "route_flip", rank=self.rank,
                                shard=shard, epoch=cur,
                                to=self._route[shard])

    def _rpc_shard(self, shard, op, table, keys, payload=b"", lr=-1.0,
                   width=0, op_timeout=None):
        """Shard-addressed RPC: routes to the rank currently serving
        ``shard`` and, with ``replication>=2``, turns an unreachable
        primary into a transparent failover — promote the backup, flip
        the route, retry THE SAME frame (pinned seq → the backup's dedup
        window keeps an ack'd-then-died push exactly-once).  An epoch-
        fence refusal is handled the same one-retry way: learn the
        surviving epoch from the refusal, re-route if the refuser was
        deposed, resend the SAME frame stamped with the new epoch."""
        seq = next(self._seq)
        try:
            return self._rpc(self._route[shard], op, table, keys, payload,
                             lr, width, op_timeout, shard=shard, seq=seq,
                             epoch=self._epoch[shard])
        except RuntimeError as e:
            if _fence_info(e) is not None:
                # learn the surviving epoch/route, then fall through to
                # the SAME send-with-failover discipline below — a fence
                # refusal must not cost the retry its transparent-
                # failover safety net (the corrected target can die too)
                self._note_fence(shard, e)
            elif self.replication < 2 or not self._failover_worthy(e):
                raise
            else:
                self._failover(shard, err=e)
        try:
            return self._rpc(self._route[shard], op, table, keys, payload,
                             lr, width, op_timeout, shard=shard, seq=seq,
                             epoch=self._epoch[shard])
        except RuntimeError as e:
            if self.replication < 2 or not self._failover_worthy(e):
                raise
            alt = self._failover(shard, err=e)
            return self._rpc(alt, op, table, keys, payload, lr, width,
                             op_timeout, shard=shard, seq=seq,
                             epoch=self._epoch[shard])

    def _failover(self, shard, err=None):
        """Promote ``shard``'s other replica holder and re-route.  Raises
        (chaining the transport error) when the backup is unreachable or
        not promotable — both copies gone is a real outage."""
        dead = self._route[shard]
        alt = (shard + 1) % self.world if dead == shard else shard
        record_fault("ps_failover")
        # best-effort liveness cross-check: telemetry only — the exhausted
        # retry budget IS the detector, but a mask that still believes the
        # peer alive flags a possible partition in the failover artifact.
        # One cheap, counter-silent attempt with a short deadline: in a
        # double failure (rank 0 dead too) this probe must not stack the
        # full retry budget on top of the recovery path.
        if shard != 0:
            try:
                hb_ms = float(os.environ.get("HETU_HEARTBEAT_MS", "500"))
                raw = self._rpc(self._route[0], OP_ALIVE, 0,
                                np.asarray([self.world, 1], np.int64),
                                lr=3.0 * hb_ms,
                                op_timeout=min(2.0, self.rpc_timeout),
                                record=False, retries=1)
                if np.frombuffer(raw, np.int64)[dead]:
                    record_fault("ps_failover_primary_reported_alive")
            except (RuntimeError, OSError, ConnectionError):
                pass
        try:
            # want_epoch = our epoch + 1: the promotion must strictly
            # dominate the lineage we are abandoning, so the deposed
            # primary's frames become refusable (fencing)
            raw = self._rpc(alt, OP_PROMOTE, 0,
                            np.asarray([shard, len(self._tables),
                                        self._epoch[shard] + 1], np.int64))
        except (RuntimeError, OSError, ConnectionError) as e2:
            record_fault("ps_failover_failed")
            raise RuntimeError(
                f"shard {shard}: serving rank {dead} unreachable AND "
                f"backup rank {alt} not promotable ({e2})") from err
        with self._fence_lock:
            if len(raw) >= 8:    # the ack names the resulting epoch
                self._epoch[shard] = max(self._epoch[shard],
                                         int(np.frombuffer(raw, np.int64,
                                                           1)[0]))
            self._route[shard] = alt
            # the promotion IS this epoch's route change: a fence
            # refusal racing in from the deposed primary must not
            # toggle the route away from the just-promoted holder
            self._flip_epoch[shard] = self._epoch[shard]
            self._failed_over.add(shard)
        record_fault("ps_failover_promoted")
        if _PROTO.on:
            _PROTO.emit("ps", "client_failover", rank=self.rank,
                        shard=shard, to=alt, epoch=self._epoch[shard])
        return alt

    def _fanout(self, jobs):
        """Run per-peer jobs concurrently (one in-flight RPC per peer)."""
        if len(jobs) <= 1:
            for fn in jobs:
                fn()
            return
        from concurrent.futures import ThreadPoolExecutor
        if self._pool is None:
            self._pool = ThreadPoolExecutor(max_workers=max(2, self.world))
        futs = [self._pool.submit(fn) for fn in jobs]
        for f in futs:
            f.result()

    # -- tables ------------------------------------------------------------
    def _shard_rows(self, rows, shard):
        return (rows - shard + self.world - 1) // self.world

    def _local_rows(self, rows):
        return self._shard_rows(rows, self.rank)

    def init_table(self, rows, width, **kw):
        tid = self.local.init_table(self._local_rows(rows), width, **kw)
        self.server.register_table(self.rank)
        self._tables[tid] = (rows, width)
        self._table_init_kw[tid] = dict(kw)
        if self.replication >= 2:
            # mirror-init our shard's backup with the SAME parameters:
            # seeded init is deterministic, so both copies start bitwise
            # identical and the forwarded op-log keeps them that way
            self._replica_init(tid, self.rank,
                               (self.rank + 1) % self.world, patient=True)
        return tid

    def _replica_init(self, tid, shard, target, patient=False):
        """OP_INIT ``shard``'s table ``tid`` on ``target`` (idempotent).

        ``patient``: table creation at cluster bring-up races the
        backup's server bind (processes start in arbitrary order), so the
        init path keeps knocking for a bounded startup grace instead of
        failing on the first connection refusal.  Re-replication probes
        stay impatient — a dead standby should defer fast."""
        rows, width = self._tables[tid]
        kw = self._table_init_kw.get(tid, {})
        scale = kw.get("init_scale")
        keys = np.asarray([self._shard_rows(rows, shard), width,
                           _OPT_IDS[kw.get("opt", "sgd")],
                           int(kw.get("seed", 0))], np.int64)
        payload = struct.pack(
            "<5d", float(kw.get("lr", 0.01)), float(kw.get("beta1", 0.9)),
            float(kw.get("beta2", 0.999)), float(kw.get("eps", 1e-7)),
            float("nan") if scale is None else float(scale))
        deadline = time.monotonic() + max(3 * self.connect_timeout, 15.0)
        while True:
            try:
                return self._rpc(target, OP_INIT, tid, keys, payload,
                                 shard=shard, record=not patient,
                                 epoch=self._epoch[shard])
            except RuntimeError as e:
                fence = _fence_info(e)
                if fence is not None:
                    # the target already belongs to a NEWER lineage (e.g.
                    # a standby's bring-up mirror-init raced an earlier
                    # promotion): the replica table exists there — adopt
                    # the epoch and treat the init as done
                    with self._fence_lock:
                        if fence[0] > self._epoch[shard]:
                            self._epoch[shard] = fence[0]
                    return None
                if not patient or time.monotonic() >= deadline:
                    raise
                time.sleep(0.2)

    def width(self, table):
        return self._tables[table][1]

    def set_data(self, table, arr):
        """Scatter a full ``(rows, width)`` array across every shard — and
        through each shard's replication path, so a replicated cluster
        seeded this way starts with bitwise-identical primary/backup
        copies (``s.local.set_data`` would seed only the local primary)."""
        rows, width = self._tables[table]
        arr = np.ascontiguousarray(arr, np.float32)
        if arr.shape != (rows, width):
            raise ValueError(f"set_data shape {arr.shape} != "
                             f"({rows}, {width})")
        jobs = []
        for s in range(self.world):
            part = np.ascontiguousarray(arr[s::self.world])
            if self._route[s] == self.rank and self.server.serves(s):
                jobs.append(lambda s=s, part=part:
                            self._local_set_data(s, table, part))
            else:
                jobs.append(lambda s=s, part=part: self._rpc_shard(
                    s, OP_SET_DATA, table, np.zeros(0, np.int64),
                    part.tobytes(), width=width))
        self._fanout(jobs)

    # -- serving-local apply (replication-ordered) -------------------------
    # Ops against a shard WE serve skip the wire but must still ride the
    # op-log: the server's apply+forward critical section is the single
    # ordering point for a shard's mutations, whether they arrived over
    # TCP or from this process's own client.
    def _local_store(self, shard):
        return self.server._stores[shard]

    def _local_push(self, shard, table, keys, grads, lr):
        keys = np.ascontiguousarray(keys, np.int64)
        grads = np.ascontiguousarray(grads, np.float32)
        body = None
        if self.server.replicable:
            body = _HDR.pack(OP_PUSH, table, keys.size, lr, grads.shape[1],
                             self.rank, next(self._seq), shard,
                             self._epoch[shard]) \
                + keys.tobytes() + grads.tobytes()
        try:
            self.server._apply_push(shard, self._local_store(shard), table,
                                    keys, grads, lr, body)
        except EpochFenced as e:
            # our own server just learned it is a deposed lineage (its
            # op-log forward was epoch-refused) and demoted itself.  The
            # local apply landed only on the now-demoted, never-again-
            # promotable copy — resend the op to the surviving lineage,
            # which never saw it (exactly-once there).
            self._note_fence(shard, e)
            self._rpc_shard(shard, OP_PUSH, table, keys,
                            np.ascontiguousarray(grads).tobytes(), lr,
                            grads.shape[1])

    def _local_set_data(self, shard, table, part):
        body = None
        if self.server.replicable:
            body = _HDR.pack(OP_SET_DATA, table, 0, -1.0, part.shape[1],
                             self.rank, next(self._seq), shard,
                             self._epoch[shard]) \
                + part.tobytes()
        try:
            self.server._apply_set_data(shard, self._local_store(shard),
                                        table, part, body)
        except EpochFenced as e:
            self._note_fence(shard, e)       # see _local_push
            self._rpc_shard(shard, OP_SET_DATA, table,
                            np.zeros(0, np.int64), part.tobytes(),
                            width=part.shape[1])

    # -- sparse ops (EmbeddingStore API) -----------------------------------
    # Wire-level dedup: a zipf-skewed CTR batch (2048x26 ids) is MOSTLY
    # duplicate keys — pull/push collapse to unique keys with ``np.unique``
    # BEFORE the shard fanout and scatter results back through the inverse
    # index, so the wire carries each row once.  Semantics are unchanged:
    # the server already accumulates duplicate keys within one push
    # (store.py _push_locked / the native core), so pre-summing duplicate
    # grads client-side yields the identical optimizer step and the same
    # per-key version bump.  The saved traffic is counted in
    # ``hetu_tpu.metrics`` (``ps_dedup_*``) — GC3's batching-over-many-
    # small-messages discipline, applied to the sparse path.

    @staticmethod
    def _sorted_unique(flat):
        """True iff already strictly ascending — the HET cache hands over
        pre-deduped sorted keys, so the wire path skips a re-dedup."""
        return flat.size <= 1 or bool(np.all(np.diff(flat) > 0))

    def _dedup_grads(self, keys, grads, width):
        """(unique_keys, per-unique summed grads); counts saved rows."""
        if self._sorted_unique(keys):
            return keys, grads
        uk, inv, counts = np.unique(keys, return_inverse=True,
                                    return_counts=True)
        if uk.size < keys.size:
            record_cache("ps_dedup_push_rows_saved", keys.size - uk.size)
            record_cache("ps_dedup_push_bytes_saved",
                         (keys.size - uk.size) * (width * 4 + 8))
        return uk, _segment_sum(grads, inv, counts)

    def pull(self, table, keys):
        keys = np.ascontiguousarray(keys, np.int64)
        flat = keys.reshape(-1)
        rows, width = self._tables[table]
        if self._sorted_unique(flat):
            uk, inv = flat, None
        else:
            uk, inv = np.unique(flat, return_inverse=True)
            if uk.size < flat.size:
                record_cache("ps_dedup_pull_rows_saved",
                             flat.size - uk.size)
                record_cache("ps_dedup_pull_bytes_saved",
                             (flat.size - uk.size) * (width * 4 + 8))
        out = np.empty((uk.size, width), np.float32)
        owners = uk % self.world
        jobs = []
        for s in range(self.world):
            sel = np.nonzero(owners == s)[0]
            if not sel.size:
                continue
            if self._route[s] == self.rank and self.server.serves(s):
                jobs.append(lambda s=s, sel=sel: out.__setitem__(
                    sel, self._local_store(s).pull(
                        table, uk[sel] // self.world)))
            else:
                def job(s=s, sel=sel):
                    raw = self._rpc_shard(s, OP_PULL, table, uk[sel])
                    out[sel] = np.frombuffer(raw, np.float32).reshape(
                        sel.size, width)
                jobs.append(job)
        self._fanout(jobs)
        if inv is not None:
            out = out[inv]
        return out.reshape(keys.shape + (width,))

    def push(self, table, keys, grads, lr=-1.0):
        keys = np.ascontiguousarray(keys, np.int64).reshape(-1)
        rows, width = self._tables[table]
        if not keys.size:
            return
        grads = np.ascontiguousarray(grads, np.float32).reshape(keys.size, -1)
        uk, acc = self._dedup_grads(keys, grads, width)
        owners = uk % self.world
        jobs = []
        for s in range(self.world):
            sel = np.nonzero(owners == s)[0]
            if not sel.size:
                continue
            if self._route[s] == self.rank and self.server.serves(s):
                jobs.append(lambda s=s, sel=sel: self._local_push(
                    s, table, uk[sel], acc[sel], lr))
            else:
                jobs.append(lambda s=s, sel=sel: self._rpc_shard(
                    s, OP_PUSH, table, uk[sel],
                    np.ascontiguousarray(acc[sel]).tobytes(), lr, width))
        self._fanout(jobs)

    def push_pull(self, table, push_keys, grads, pull_keys, lr=-1.0):
        """Fused SDPushPull: each peer gets ONE ``OP_PUSH_PULL`` round trip
        carrying its push shard + pull shard (server applies the push
        before answering the pull), instead of a serial push fanout
        followed by a pull fanout.  Rows are owner-partitioned, so a pull
        only ever depends on the pushes riding the same frame."""
        push_keys = np.ascontiguousarray(push_keys, np.int64).reshape(-1)
        pull_arr = np.ascontiguousarray(pull_keys, np.int64)
        pflat = pull_arr.reshape(-1)
        rows, width = self._tables[table]
        if not push_keys.size:
            return self.pull(table, pull_arr)
        grads = np.ascontiguousarray(grads, np.float32).reshape(
            push_keys.size, -1)
        upk, acc = self._dedup_grads(push_keys, grads, width)
        if self._sorted_unique(pflat):
            ulk, linv = pflat, None
        else:
            ulk, linv = np.unique(pflat, return_inverse=True)
            record_cache("ps_dedup_pull_rows_saved", pflat.size - ulk.size)
            record_cache("ps_dedup_pull_bytes_saved",
                         (pflat.size - ulk.size) * (width * 4 + 8))
        out = np.empty((ulk.size, width), np.float32)
        powners = upk % self.world
        lowners = ulk % self.world
        jobs = []
        for s in range(self.world):
            psel = np.nonzero(powners == s)[0]
            lsel = np.nonzero(lowners == s)[0]
            if not psel.size and not lsel.size:
                continue
            if self._route[s] == self.rank and self.server.serves(s):
                def local_job(s=s, psel=psel, lsel=lsel):
                    if psel.size:
                        self._local_push(s, table, upk[psel], acc[psel], lr)
                    if not lsel.size:
                        return
                    if self.server.serves(s):
                        out[lsel] = self._local_store(s).pull(
                            table, ulk[lsel] // self.world)
                    else:
                        # the push's epoch fence just demoted our own
                        # server: the pull must follow the re-route too
                        raw = self._rpc_shard(s, OP_PULL, table, ulk[lsel])
                        out[lsel] = np.frombuffer(raw, np.float32).reshape(
                            lsel.size, width)
                jobs.append(local_job)
            elif psel.size:
                def fused_job(s=s, psel=psel, lsel=lsel):
                    frame_keys = np.concatenate(
                        (np.asarray([psel.size], np.int64),
                         upk[psel], ulk[lsel]))
                    raw = self._rpc_shard(
                        s, OP_PUSH_PULL, table, frame_keys,
                        np.ascontiguousarray(acc[psel]).tobytes(), lr,
                        width)
                    if lsel.size:
                        out[lsel] = np.frombuffer(raw, np.float32).reshape(
                            lsel.size, width)
                        # only a frame that genuinely carried BOTH halves
                        # counts as a saved round trip
                        record_cache("ps_push_pull_fused_rpcs", 1)
                jobs.append(fused_job)
            else:       # nothing to push at this peer: plain pull
                def pull_job(s=s, lsel=lsel):
                    raw = self._rpc_shard(s, OP_PULL, table, ulk[lsel])
                    out[lsel] = np.frombuffer(raw, np.float32).reshape(
                        lsel.size, width)
                jobs.append(pull_job)
        self._fanout(jobs)
        if linv is not None:
            out = out[linv]
        return out.reshape(pull_arr.shape + (width,))

    def versions(self, table, keys):
        keys = np.ascontiguousarray(keys, np.int64).reshape(-1)
        uk, inv = np.unique(keys, return_inverse=True)
        out = np.empty(uk.size, np.int64)
        owners = uk % self.world
        jobs = []
        for s in range(self.world):
            sel = np.nonzero(owners == s)[0]
            if not sel.size:
                continue
            if self._route[s] == self.rank and self.server.serves(s):
                jobs.append(lambda s=s, sel=sel: out.__setitem__(
                    sel, self._local_store(s).versions(
                        table, uk[sel] // self.world)))
            else:
                def vjob(s=s, sel=sel):
                    raw = self._rpc_shard(s, OP_VERSIONS, table, uk[sel])
                    out[sel] = np.frombuffer(raw, np.int64)
                jobs.append(vjob)
        self._fanout(jobs)
        return out[inv]

    # -- ASP: bounded async push (reference asp prefetch path) -------------
    def _async_worker(self):
        while True:
            item = self._queue.get()
            if item is None:
                return
            table, keys, grads, lr = item
            self.push(table, keys, grads, lr)
            self._queue.task_done()

    def push_async(self, table, keys, grads, lr=-1.0):
        """Enqueue a push; blocks only when ``async_queue`` is full
        (bounded eventual consistency — ASP mode, ``bsp=-1``)."""
        if self._async_thread is None:
            self._async_thread = threading.Thread(target=self._async_worker,
                                                  daemon=True)
            self._async_thread.start()
        self._queue.put((table, np.array(keys, np.int64, copy=True),
                         np.array(grads, np.float32, copy=True), lr))

    def flush(self):
        """Barrier: wait until every queued async push has been applied."""
        if self._async_thread is not None:
            self._queue.join()

    # -- SSP via rank 0 (the reference scheduler role) ---------------------
    # ``channel`` separates independent clock consumers on the same server:
    # the executor's SSP step loop ticks channel 0, partial-reduce arrival
    # clocks live on their own channel — sharing one vector double-
    # incremented per step and broke preduce's 'arrival at step s ⇔
    # clock >= s+1' assumption (round-3 advisor finding).
    # SSP scheduler state is SHARD-0 traffic like the heartbeats: with
    # replication>=2 every clock tick / channel init is mirrored to shard
    # 0's backup (dedup'd under the same (client, seq)), so the barrier
    # itself fails over with the rest of the shard.
    def ssp_init(self, n_workers, channel=0):
        """Idempotent per (channel, size): every rank may call it."""
        self._rpc_shard(0, OP_SSP_INIT, 0,
                        np.asarray([n_workers, channel], np.int64))

    def clock(self, worker=None, channel=0):
        w = self.rank if worker is None else worker
        self._rpc_shard(0, OP_CLOCK, 0, np.asarray([w, channel], np.int64))

    def clocks(self, channel=0):
        """Every worker's clock value (rank-0 authoritative copy) — the
        arrival feed for partial-reduce group formation."""
        raw = self._rpc_shard(0, OP_CLOCKS, 0,
                              np.asarray([channel], np.int64))
        return np.frombuffer(raw, np.int64).copy()

    # -- liveness: heartbeats on rank 0 (the scheduler role) ---------------
    # Routed as SHARD-0 traffic: with replication>=2 the rank-0 server
    # mirrors every heartbeat write to shard 0's backup, so the failure
    # detector itself fails over — alive_mask survives rank-0 death.
    def heartbeat(self, rank=None, step=0):
        """Ping the liveness table (rank 0, or its promoted backup)."""
        w = self.rank if rank is None else rank
        self._rpc_shard(0, OP_HEARTBEAT, 0,
                        np.asarray([w, step], np.int64))

    def alive_mask(self, deadline_ms, n_workers=None):
        """int64 mask over workers: 1 iff the rank heartbeated within
        ``deadline_ms`` — or never heartbeated at all (liveness only
        declares death for ranks it has seen alive; see the OP_ALIVE
        handler).  The liveness feed for partial-reduce dead-rank
        exclusion."""
        n = self.world if n_workers is None else n_workers
        raw = self._rpc_shard(0, OP_ALIVE, 0, np.asarray([n], np.int64),
                              lr=float(deadline_ms))
        return np.frombuffer(raw, np.int64).copy()

    def start_heartbeat(self, interval_ms=None, step_fn=None):
        """Background liveness pings every ``interval_ms`` (env default
        ``HETU_HEARTBEAT_MS``=500) until ``close``.  ``step_fn`` supplies
        the step number reported with each ping (e.g. ``lambda:
        ex.step_counter``).  A failing ping is counted
        (``heartbeat_send_failed``) and retried next interval — a dead
        scheduler must not crash the worker from a daemon thread."""
        if self._hb_thread is not None:
            return
        iv = (float(os.environ.get("HETU_HEARTBEAT_MS", "500"))
              if interval_ms is None else float(interval_ms)) / 1e3

        def beat():
            while not self._hb_stop.wait(iv):
                try:
                    self.heartbeat(step=int(step_fn()) if step_fn else 0)
                except (RuntimeError, OSError, ConnectionError):
                    record_fault("heartbeat_send_failed")

        self._hb_thread = threading.Thread(
            target=beat, daemon=True, name=f"hetu-hb-{self.rank}")
        self._hb_thread.start()

    #: the server side blocks on a condition variable (OP_SSP_SYNC
    #: handler) — one RPC waits out the whole bound, no client polling
    ssp_blocking = True

    def ssp_sync(self, worker=None, staleness=0, timeout_ms=0, channel=0):
        w = self.rank if worker is None else worker
        # the server blocks until the staleness bound clears: the socket
        # deadline must outlive the requested wait (timeout_ms=0 means
        # "wait for stragglers" — bounded here at 600s rather than forever,
        # so a dead scheduler still surfaces as a diagnosable error)
        raw = self._rpc_shard(0, OP_SSP_SYNC, 0,
                              np.asarray([w, staleness, channel], np.int64),
                              lr=timeout_ms / 1e3 if timeout_ms else -1.0,
                              op_timeout=(timeout_ms / 1e3 + 30.0)
                              if timeout_ms else 600.0)
        return raw == b"\x01"

    # -- re-replication (redundancy repair after a failover) ---------------
    def re_replicate(self, shard=None):
        """Restore ``replication=2`` redundancy for ``shard`` (default:
        every shard this client failed over): re-create the replica
        tables on the shard's vacant holder (``OP_INIT``, idempotent),
        then have the serving replica stream a chunked snapshot and drain
        its op-log catch-up (``OP_SYNC``/``OP_SYNC_PUT``).  After this, a
        SECOND failure of the shard is survivable — the router promotes
        the freshly attached copy."""
        if self.replication < 2:
            raise RuntimeError("re_replicate needs replication >= 2")
        shards = sorted(self._failed_over) if shard is None else [shard]
        for s in shards:
            serving = self._route[s]
            target = s if serving != s else (s + 1) % self.world
            for tid in sorted(self._tables):
                self._replica_init(tid, s, target)
            if serving == self.rank:
                self.server._sync_to(s, target)
            else:
                self._rpc(serving, OP_SYNC, 0,
                          np.asarray([s, target], np.int64),
                          op_timeout=max(self.rpc_timeout, 600.0),
                          epoch=self._epoch[s])
            self._failed_over.discard(s)

    def re_replicate_async(self, shard=None):
        """Background :meth:`re_replicate`; failures surface as the
        ``ps_re_replicate_failed`` counter + a warning, not a crash."""
        def run():
            try:
                self.re_replicate(shard)
            except (RuntimeError, OSError, ConnectionError) as e:
                import warnings
                warnings.warn(f"background re-replication failed: {e}",
                              RuntimeWarning)
        t = threading.Thread(target=run, daemon=True,
                             name=f"hetu-resync-{self.rank}")
        t.start()
        return t

    def maybe_re_replicate(self):
        """Opportunistic redundancy repair (the executor's step-hook
        driver, ``HETU_PS_REREPLICATE_EVERY``): for each shard running
        without a backup — one this client failed over, or one OUR server
        serves whose op-log forwarding broke (the backup died) — try one
        re-replication; a still-dead target defers quietly to the next
        tick.  Returns True iff any shard was repaired."""
        if self.replication < 2:
            return False
        pending = set(self._failed_over)
        srv = self.server
        if srv.replicable:
            for s in list(srv._serving):
                if not srv._fwd_ok.get(s) and srv._oplog.get(s) is None:
                    pending.add(s)
        if not pending:
            return False
        repaired = False
        for s in sorted(pending):
            try:
                self.re_replicate(s)
                repaired = True
            except (RuntimeError, OSError, ConnectionError):
                record_fault("ps_re_replicate_deferred")
        return repaired

    def table_checksum(self, table, shard, rank=None):
        """Full-state digest of ``shard``'s copy of ``table`` held on
        ``rank`` (default: the serving rank) — the live divergence
        detector behind ``tools/ps_fsck.py --verify``."""
        peer = self._route[shard] if rank is None else rank
        if peer == self.rank:
            return self.server._stores[shard].state_digest(table)
        raw = self._rpc(peer, OP_CHECKSUM, table, np.zeros(0, np.int64),
                        shard=shard)
        return raw.decode()

    def shard_epoch(self, shard, rank=None):
        """``(epoch, serving)`` of ``shard``'s copy on ``rank`` (default:
        the rank this client routes the shard to) — the lineage probe
        behind ``ps_fsck --json`` epochs and the single-surviving-
        lineage assertion."""
        peer = self._route[shard] if rank is None else rank
        if peer == self.rank:
            return (self.server.epoch(shard), self.server.serves(shard))
        raw = self._rpc(peer, OP_EPOCH, 0, np.asarray([shard], np.int64))
        ep, serving = struct.unpack("<qq", raw)
        return int(ep), bool(serving)

    def liveness_report(self, deadline_ms, n_workers=None):
        """Classify non-heartbeating ranks as DEAD vs UNREACHABLE.

        ``alive_mask`` (the rank-0 heartbeat table) conflates "the rank
        died" with "the rank cannot reach rank 0" — under an asymmetric
        partition those demand opposite reactions (a partitioned rank
        must be fenced, not respawned over).  For every rank the mask
        declares dead, this sends ONE cheap direct probe (``OP_EPOCH``,
        short deadline, counter-silent transport): a rank that answers
        is recorded as ``unreachable`` (+ the ``ps_unreachable`` fault
        counter — partition evidence), one that doesn't as ``dead``.
        The verdict is from THIS client's vantage point: a rank this
        client also cannot reach stays ``dead`` even if it lives on the
        far side of a cut."""
        n = self.world if n_workers is None else int(n_workers)
        mask = self.alive_mask(deadline_ms, n)
        report = {"alive": [], "dead": [], "unreachable": []}
        for r in range(min(n, self.world)):
            if mask[r]:
                report["alive"].append(r)
                continue
            try:
                self._rpc(r, OP_EPOCH, 0, np.asarray([r], np.int64),
                          op_timeout=min(2.0, self.rpc_timeout),
                          record=False, retries=1)
            except (RuntimeError, OSError, ConnectionError):
                report["dead"].append(r)
            else:
                report["unreachable"].append(r)
                record_fault("ps_unreachable")
        return report

    # -- shard persistence (reference per-server SaveParam) ----------------
    # Shard files are named by SHARD, not by rank, and cover every shard
    # this server currently SERVES: after a failover the promoted server
    # checkpoints the shard it adopted (otherwise post-failover
    # auto-saves would silently omit the adopted shard's live state),
    # and a not-yet-synced standby serves nothing — so its executor's
    # auto-save can never overwrite a shard file with seed-init data.
    def save(self, table, path):
        for shard in sorted(self.server._serving):
            self.server._stores[shard].save(table, f"{path}.shard{shard}")

    def load(self, table, path):
        for shard in sorted(self.server._serving):
            self.server._stores[shard].load(table, f"{path}.shard{shard}")

    def close(self):
        self._hb_stop.set()
        self.flush()
        if self._async_thread is not None:
            self._queue.put(None)
        for peer in list(self._conns):
            try:
                # best-effort goodbye: an already-dead peer during an
                # ordered teardown is not a FAULT — don't record one
                self._rpc(peer, OP_SHUTDOWN, 0, np.zeros(0, np.int64),
                          op_timeout=min(5.0, self.rpc_timeout),
                          record=False, retries=1)
            except (OSError, RuntimeError, ConnectionError):
                pass     # peer already gone; _rpc dropped the conn
            self._drop_conn(peer)
        if self._pool is not None:
            self._pool.shutdown(wait=False)
        self.server.stop()


class _DevLookup:
    """Pending device-mode lookup (``DistCacheTable.begin_lookup``): the
    host-side plan frozen before the one fallible store round trip.
    :meth:`roundtrip` touches ONLY the store (no cache state, no lock),
    so it is safe on any thread — the executor runs it on the
    feed-pipeline thread to overlap the miss pull with the dense
    forward; stats/counters land at commit on the owning thread."""

    __slots__ = ("cache", "shape", "flat", "uk", "inv", "cnt", "slots",
                 "hit", "refresh", "rkeys", "rslots", "dirty", "plan",
                 "absent", "pk", "pg", "positions", "fill_targets",
                 "done", "flow_id")

    def __init__(self, cache, shape, flat):
        self.cache, self.shape, self.flat = cache, shape, flat
        self.uk = self.inv = self.cnt = self.slots = None
        self.hit = self.refresh = None
        self.rkeys = self.rslots = None
        self.dirty = self.plan = self.absent = None
        self.pk = self.pg = None
        self.positions = self.fill_targets = None
        self.done = False
        self.flow_id = None     # trace arrow: miss pull -> consuming step

    def roundtrip(self):
        """The one fallible step: pending pushes + the batched MISS pull,
        fused into one ``push_pull`` per peer when the store supports it
        (``_flush_to_store`` wire behaviour, counters deferred to
        commit).  Returns the pulled rows aligned to ``rkeys`` (None
        when the batch had no misses)."""
        c = self.cache
        rows = None
        if self.pk is not None:
            if self.rkeys is not None and hasattr(c.store, "push_pull"):
                rows = c.store.push_pull(c.table, self.pk, self.pg,
                                         self.rkeys, c.lr)
            else:
                c.store.push(c.table, self.pk, self.pg, c.lr)
        if rows is None and self.rkeys is not None:
            rows = c.store.pull(c.table, self.rkeys)
        return rows


class DistCacheTable:
    """HET bounded-staleness embedding cache — fully vectorized, batch-
    granular (reference ``src/hetu_cache/cache.h:21`` pull_bound_/
    push_bound_ semantics; HET VLDB'22).  Works over any store exposing
    the EmbeddingStore sparse API (:class:`DistributedStore` across hosts,
    or a plain :class:`~hetu_tpu.ps.store.EmbeddingStore` locally).

    Storage is a contiguous ``(limit, width)`` float32 slab plus an
    open-addressed int64 key→slot hash table in numpy — no per-key Python
    objects anywhere.  ``lookup``/``update`` are vectorized hit/miss
    partitions; LRU/LFU eviction picks victims with one ``lexsort`` over
    per-slot clocks; gradients accumulate via ``np.add.at`` into a dirty
    slab; and EVERY pending push (miss-refresh, eviction, push-bound
    overflow, ``flush``) rides ONE batched ``store.push`` — grouped per
    owner rank by the store's shard fanout — instead of the pre-PR one
    single-row RPC per dirty key.  A miss-refresh that also has pushes
    pending fuses both into one ``store.push_pull`` round trip per peer.

    Contract (the per-key reference model in ``refcache.py`` implements
    the SAME rules — the parity suite holds the two bitwise equal):

    - Decisions are BATCH-granular over the call's sorted unique keys: a
      key is a HIT iff cached with ``uses < pull_bound``; all its
      occurrences serve the same row, and ``uses`` grows by the
      occurrence count.  A refresh (stale or absent) re-pulls the row and
      restarts ``uses`` at the occurrence count.
    - ``update`` accumulates per-key grads client-side (``gcnt`` grows by
      occurrence count); reaching ``push_bound`` pushes the accumulated
      grad and invalidates the local row (``uses = pull_bound``), as does
      ``flush``.  Updating an uncached key allocates a grad-only slot
      whose row never serves (born stale).
    - Eviction at ``limit``: victims are the smallest ``(last-use tick,
      key)`` [LRU] or ``(freq, tick, key)`` [LFU] among slots not touched
      by the current batch; dirty victims join the batched push.  If a
      single batch's unique keys exceed capacity, the sorted-first keys
      get slots and the remainder are served (and their grads pushed)
      uncached.

    **Device-resident mode** (``device=True`` — ISSUE 11): the slot
    table, hash table, eviction clocks and the transactional commit
    protocol stay host-side and UNCHANGED (every decision above is
    byte-identical to host mode), but the row slab gains a
    device-resident mirror of shape ``(limit + device_scratch + 1,
    width)`` and the hot path stops moving hit rows across the host
    boundary: a lookup is split into :meth:`begin_lookup` (plan, under
    the lock) → a store round trip for the pushes + MISS pull only
    (:meth:`_DevLookup.roundtrip`, lock-free — the executor runs it on
    the feed-pipeline thread so it overlaps the dense forward) →
    :meth:`finish_lookup` (commit).  Hit rows are gathered ON DEVICE by
    slot index (``ops/pallas/emb_cache.py`` Pallas kernel, with counted
    ``jnp.take`` fallback off-TPU); only miss rows are H2D-transferred,
    landing in their committed slots via :func:`fill_rows`.  Batch
    unique keys that exceed capacity are served through ``device_scratch``
    scratch rows past the slab (positions ``[limit, limit+scratch)``;
    never registered, overwritten freely — the "served uncached"
    contract above), and one dump row at ``limit + scratch`` absorbs
    fill padding.  The training grad path arrives pre-summed per unique
    key from the device scatter-add kernel through
    :meth:`apply_update_summed`, replacing the host scipy-CSR segment
    sum.  The lock is HELD from ``begin_lookup`` to
    ``finish_lookup``/:meth:`abort_lookup` (the host-mode ``lookup``
    holds it for the same window), so a transport failure still leaves
    the cache untouched.  In device mode the host ``_data`` slab is NOT
    mirrored (the device slab is the one serving copy — a host mirror
    would double the per-step row traffic for a buffer nothing reads);
    served values stay bitwise equal to host mode because both modes
    fill from the same pull bytes and copy them verbatim.  Restrictions:
    mutually exclusive with ``read_only``; the executor wiring supports
    BSP single-process training (ASP/SSP/multi-process raise).

    **Read-only serving mode** (``read_only=True`` — what
    :class:`hetu_tpu.serving.InferenceExecutor` mounts): a pure lookup
    serves any cached row WITHOUT burning ``pull_bound`` budget, touching
    the dirty-grad slab, or counting toward ``push_bound`` — the
    training-mode ``uses`` clock exists to bound staleness *between this
    client's own writes*, and a serving replica never writes.  ``update``
    is rejected outright.  Staleness is VERSION-based instead: each fill
    records the row's server version (one extra batched ``versions``
    fanout on the miss path only), and :meth:`refresh_stale` — invoked
    explicitly, or every ``refresh_every`` lookups (asynchronously, on a
    background thread, so no serving batch pays the sweep in its own
    latency; :meth:`refresh_join` drains it) — re-pulls exactly the
    cached rows whose server version advanced (a trainer elsewhere kept
    writing), in one batched owner-grouped round trip.  Eviction recency
    (ticks/freq) still advances on read-only lookups: LRU/LFU victim
    choice needs it.
    """

    _EMPTY, _TOMB = -1, -2

    def __init__(self, store, table, limit=1 << 16,
                 pull_bound=100, push_bound=10, lr=-1.0, policy="lru",
                 read_only=False, refresh_every=0, device=False,
                 device_scratch=None, device_interpret=None):
        self.store, self.table = store, table
        self.width = int(store.width(table))
        self.limit = int(limit)
        self.pull_bound, self.push_bound = int(pull_bound), int(push_bound)
        self.lr = lr
        self.read_only = bool(read_only)
        #: device-resident slab mode (see class docstring)
        self.device = bool(device)
        if self.device and self.read_only:
            raise NotImplementedError(
                "DistCacheTable(device=True, read_only=True): the "
                "serving path keeps its host slab (version-refresh "
                "rides it) — device-resident serving is future work")
        #: scratch rows past the slab for capacity-overflow batches
        #: (keys served uncached still need a device row to gather)
        self._dev_scratch = int(device_scratch) if device_scratch \
            is not None else max(256, self.limit // 4)
        #: fill-padding target: one garbage row that is never gathered
        self._dev_dump = self.limit + self._dev_scratch
        #: Pallas dispatch knob forwarded to ops/pallas/emb_cache.py
        #: (None = auto: kernel on TPU, counted fallback elsewhere)
        self.device_interpret = device_interpret
        self._dev_slab = None   # lazily-built (limit+scratch+1, width)
        #: read-only mode: run a version-based refresh sweep every N
        #: lookup calls (0 = only when refresh_stale() is called)
        self.refresh_every = int(refresh_every)
        self._lookups_since_refresh = 0
        self._refresh_thread = None   # in-flight async sweep (at most one)
        policy = policy.lower()
        if policy not in ("lru", "lfu"):
            raise ValueError(f"unknown cache policy {policy!r}")
        self.policy = policy
        L, w = self.limit, self.width
        # device mode never reads the host row mirror (the device slab
        # is the one serving copy) — don't commit limit*width host bytes
        # to a buffer nothing reads
        self._data = np.zeros((0 if self.device else L, w), np.float32)
        self._grad = np.zeros((L, w), np.float32)   # pending grad slab
        self._slotkey = np.full(L, self._EMPTY, np.int64)  # slot -> key
        self._uses = np.zeros(L, np.int64)     # lookups since refresh
        self._gcnt = np.zeros(L, np.int64)     # pending update events
        self._ticks = np.zeros(L, np.int64)    # last-touch clock (LRU)
        self._freq = np.zeros(L, np.int64)     # touch count (LFU)
        #: server version at fill time, maintained in read-only mode
        #: only (training-mode staleness rides pull_bound instead)
        self._vers = np.zeros(L, np.int64)
        cap = 1 << max(6, (4 * L - 1).bit_length())   # load factor <= 1/4
        self._hcap, self._hmask = cap, cap - 1
        self._hkey = np.full(cap, self._EMPTY, np.int64)
        self._hslot = np.zeros(cap, np.int64)
        self._htomb = 0
        # O(1) slot allocator: popping from the end hands out ascending
        # slot ids (slot identity is unobservable — victim order ties
        # break on KEY, never slot)
        self._freelist = np.arange(L - 1, -1, -1, dtype=np.int64)
        self._nfree = L
        self._tick = 0
        self._lock = make_rlock("DistCacheTable._lock")   # prefetch + main
        #: (flat, uk, inv, cnt, slots) of the latest lookup — the executor
        #: and the CTR step always update() the exact ids they just looked
        #: up, so the batch partition is computed once, not twice
        self._batch_memo = None
        self.stats = {"lookups": 0, "hits": 0, "evictions": 0, "pushes": 0,
                      "fetches": 0, "updates": 0, "push_rpcs": 0}

    # -- open-addressed int64 hash table (vectorized linear probing) -------
    def _hash(self, keys):
        h = keys.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)
        h ^= h >> np.uint64(29)
        return (h & np.uint64(self._hmask)).astype(np.int64)

    def _find(self, ukeys):
        """Slot for each (unique) key, -1 if absent — every probe round
        advances ALL still-unresolved keys one step at once."""
        out = np.full(ukeys.size, -1, np.int64)
        if not ukeys.size:
            return out
        pend = np.arange(ukeys.size)
        h = self._hash(ukeys)
        while pend.size:
            hk = self._hkey[h]
            found = hk == ukeys[pend]
            if found.any():
                out[pend[found]] = self._hslot[h[found]]
            stop = found | (hk == self._EMPTY)   # TOMB keeps probing
            keep = ~stop
            if not keep.any():
                break
            pend = pend[keep]
            h = (h[keep] + 1) & self._hmask
        return out

    def _hinsert(self, ukeys, slots):
        """Insert absent unique keys; conflicting claims on one free cell
        are resolved per round (first claimant wins, rest re-probe)."""
        if not ukeys.size:
            return
        pend = np.arange(ukeys.size)
        h = self._hash(ukeys)
        while pend.size:
            hk = self._hkey[h]
            usable = (hk == self._EMPTY) | (hk == self._TOMB)
            if usable.any():
                upos, first = np.unique(h[usable], return_index=True)
                winners = np.flatnonzero(usable)[first]
                wcells = h[winners]
                self._htomb -= int((self._hkey[wcells] == self._TOMB).sum())
                self._hkey[wcells] = ukeys[pend[winners]]
                self._hslot[wcells] = slots[pend[winners]]
                keep = np.ones(pend.size, bool)
                keep[winners] = False
                pend, h = pend[keep], h[keep]
            h = (h + 1) & self._hmask

    def _hdelete(self, ukeys):
        """Tombstone present unique keys (chains through them survive)."""
        if not ukeys.size:
            return
        pend = np.arange(ukeys.size)
        h = self._hash(ukeys)
        while pend.size:
            hk = self._hkey[h]
            found = hk == ukeys[pend]
            if found.any():
                self._hkey[h[found]] = self._TOMB
                self._htomb += int(found.sum())
            keep = ~(found | (hk == self._EMPTY))
            if not keep.any():
                break
            pend, h = pend[keep], h[keep]
            h = (h + 1) & self._hmask

    def _maybe_rehash(self):
        if self._htomb <= self._hcap // 4:
            return
        self._hkey.fill(self._EMPTY)
        self._htomb = 0
        occ = np.flatnonzero(self._slotkey >= 0)
        self._hinsert(self._slotkey[occ], occ)

    # -- slot allocation + vectorized victim selection ---------------------
    def _pick_victims(self, occ, n_ev):
        """The ``n_ev`` worst occupied slots under the policy's total
        order — LRU ``(tick, key)``, LFU ``(freq, tick, key)`` — via
        argpartition on the primary clock with a deterministic lexsort
        refinement of the boundary ties (a full lexsort of 10^6 occupied
        slots per batch would dominate the whole lookup)."""
        if n_ev >= occ.size:
            return occ
        prim = self._ticks[occ] if self.policy == "lru" \
            else self._freq[occ]
        part = np.argpartition(prim, n_ev - 1)[:n_ev]
        thresh = prim[part].max()
        sure = part[prim[part] < thresh]
        ties = np.flatnonzero(prim == thresh)
        if self.policy == "lru":
            order = np.argsort(self._slotkey[occ[ties]], kind="stable")
        else:
            order = np.lexsort((self._slotkey[occ[ties]],
                                self._ticks[occ[ties]]))
        chosen = ties[order[:n_ev - sure.size]]
        return occ[np.concatenate((sure, chosen))]

    def _plan_slots(self, newkeys, protect_slots):
        """PLAN slots for absent unique (sorted) ``newkeys``: free slots
        first, then LRU/LFU victims among slots not in ``protect_slots``
        (the current batch's own slots) — overflow beyond capacity stays
        -1 (uncacheable).  Pure read: nothing is committed until
        :meth:`_commit_slots`, so the fallible store round trip can sit
        between plan and commit without ever leaving torn cache state.
        The O(limit) protect mask + occupancy scan is built only when
        eviction is actually needed."""
        slots = np.full(newkeys.size, -1, np.int64)
        take = min(newkeys.size, self._nfree)
        if take:
            slots[:take] = self._freelist[self._nfree - take:
                                          self._nfree][::-1]
        need = newkeys.size - take
        evslots = evkeys = np.empty(0, np.int64)
        if need > 0:
            protect = np.zeros(self.limit, bool)
            protect[protect_slots] = True
            occ = np.flatnonzero((self._slotkey >= 0) & ~protect)
            n_ev = min(need, occ.size)
            if n_ev > 0:
                evslots = self._pick_victims(occ, n_ev)
                evkeys = self._slotkey[evslots].copy()
                slots[take:take + n_ev] = evslots
        return slots, take, evslots, evkeys

    def _plan_dirty(self, slot_sel):
        """(dirty_slots, their keys, grad copies) among ``slot_sel`` —
        the push payload is copied out so the slab mutates only after the
        push round trip succeeds."""
        dirty = slot_sel[self._gcnt[slot_sel] > 0]
        if not dirty.size:
            return dirty, None, None
        return dirty, self._slotkey[dirty].copy(), self._grad[dirty].copy()

    def _commit_slots(self, newkeys, plan):
        """Apply a :meth:`_plan_slots` plan: pop the freelist, tombstone +
        reset victims, register the new keys.  Returns the registered
        (keys, slots)."""
        slots, take, evslots, evkeys = plan
        if _race.ACTIVE is not None:   # ISSUE 14 preemption point
            _race.point("cache.evict_commit")
        self._nfree -= take
        if evslots.size:
            self._hdelete(evkeys)
            self._grad[evslots] = 0.0
            self._gcnt[evslots] = 0
            self.stats["evictions"] += int(evslots.size)
            record_cache("emb_cache_evict_rows", int(evslots.size))
        reg = slots >= 0
        regk, regs = newkeys[reg], slots[reg]
        self._slotkey[regs] = regk
        self._hinsert(regk, regs)
        self._freq[regs] = 0
        return regk, regs

    def _flush_to_store(self, push_keys, push_grads, pull_keys=None):
        """ONE batched store round trip for everything pending: the push
        list (concatenated, already per-unique-key accumulated) and, when
        ``pull_keys`` is given, the refresh pull — fused into a single
        ``push_pull`` per peer when the store supports it.  Counters
        record only after the round trip succeeds."""
        rows = None
        if push_keys:
            pk = np.concatenate(push_keys)
            pg = np.concatenate(push_grads)
            order = np.argsort(pk, kind="stable")   # deterministic wire
            pk, pg = pk[order], pg[order]
            if pull_keys is not None and hasattr(self.store, "push_pull"):
                # lint: held-rpc-ok transactional commit protocol (plan under lock, ONE fallible round trip, then commit)
                rows = self.store.push_pull(self.table, pk, pg, pull_keys,
                                            self.lr)
            else:
                # lint: held-rpc-ok same transactional commit round trip (push half)
                self.store.push(self.table, pk, pg, self.lr)
            self.stats["pushes"] += int(pk.size)
            self.stats["push_rpcs"] += 1
            record_cache("emb_cache_push_rows", int(pk.size))
            record_cache("emb_cache_push_rpcs", 1)
        if rows is None and pull_keys is not None:
            # lint: held-rpc-ok the refresh pull is the same one fallible round trip
            rows = self.store.pull(self.table, pull_keys)
        return rows

    # -- core ops ----------------------------------------------------------
    def lookup(self, keys):
        keys = np.ascontiguousarray(keys, np.int64)
        if self.device:
            return self._lookup_device(keys)
        if _race.ACTIVE is not None:   # ISSUE 14 preemption point
            _race.point("cache.lookup")
        sweep = False
        with self._lock:
            if self.read_only:
                out = self._lookup_readonly_locked(keys.reshape(-1))
                if self.refresh_every > 0:
                    self._lookups_since_refresh += 1
                    if self._lookups_since_refresh >= self.refresh_every:
                        self._lookups_since_refresh = 0
                        sweep = True
            else:
                out = self._lookup_locked(keys.reshape(-1))
        if sweep:
            self._refresh_async()
        return out.reshape(keys.shape + (self.width,))

    def _lookup_readonly_locked(self, flat):
        """Pure read-only lookup: a cached row is a hit regardless of
        ``pull_bound`` (``uses`` budget is never consumed — that clock
        bounds staleness between this client's own pushes, and a
        read-only client never pushes), no dirty-slab planning anywhere
        (the grad slab is untouched by invariant: ``update`` is
        rejected), and each fill records the row's server version for
        :meth:`refresh_stale`.  Eviction recency still advances."""
        self._tick += 1
        self.stats["lookups"] += int(flat.size)
        if not flat.size:
            return np.empty((0, self.width), np.float32)
        uk, inv, cnt = np.unique(flat, return_inverse=True,
                                 return_counts=True)
        slots = self._find(uk)
        present = slots >= 0
        rows_out = np.empty((uk.size, self.width), np.float32)
        miss = ~present
        if miss.any():
            mkeys = uk[miss]
            plan = self._plan_slots(mkeys, slots[present])
            # the ONLY fallible step: one batched owner-grouped pull (+
            # one versions fanout over the same keys).  A transport
            # failure raises with the cache untouched — failover inside
            # the store's pull is invisible here.  Versions are read
            # BEFORE the rows: a write landing between the two RPCs then
            # leaves a version OLDER than the data (refresh_stale re-pulls
            # once, harmlessly), whereas the reverse order would record a
            # version NEWER than the data and hide the stale row from
            # refresh_stale forever
            # lint: held-rpc-ok transactional miss fill, versions first
            vers = self.store.versions(self.table, mkeys) \
                if hasattr(self.store, "versions") else None
            if _race.ACTIVE is not None:   # ISSUE 14: the racing-writer
                _race.point("cache.miss_fill")   # window (vers -> rows)
            # lint: held-rpc-ok same transactional miss-fill window
            rows = self.store.pull(self.table, mkeys)
            self.stats["fetches"] += int(mkeys.size)
            self._commit_slots(mkeys, plan)
            mslots = plan[0]
            cached = mslots >= 0
            cs = mslots[cached]
            self._data[cs] = rows[cached]
            self._uses[cs] = 0
            self._ticks[cs] = self._tick
            self._freq[cs] += cnt[miss][cached]
            self._vers[cs] = 0 if vers is None else vers[cached]
            rows_out[miss] = rows
            self._maybe_rehash()
            slots = slots.copy()
            slots[miss] = mslots
        n_hit_rows = int(cnt[present].sum())
        self.stats["hits"] += n_hit_rows
        record_cache("emb_cache_hit_rows", n_hit_rows)
        record_cache("emb_cache_miss_rows", int(flat.size) - n_hit_rows)
        if present.any():
            hs = slots[present]
            # recency/frequency clocks advance (eviction needs them);
            # the pull_bound budget (_uses) does NOT
            self._ticks[hs] = self._tick
            self._freq[hs] += cnt[present]
            rows_out[present] = self._data[hs]
        return rows_out[inv]

    def refresh_stale(self):
        """Version-based staleness refresh (read-only serving): ONE
        batched ``versions`` fanout over every cached key, then ONE
        batched pull of exactly the rows whose server version advanced
        since fill (a trainer elsewhere kept writing them).  Both store
        round trips run OUTSIDE the cache lock so concurrent lookups
        keep serving mid-sweep; the commit re-validates that each slot
        still holds its key (eviction races skip) and only moves
        versions FORWARD (a racing miss fill that pulled fresher data
        wins).  Returns the number of refreshed rows."""
        if not hasattr(self.store, "versions"):
            return 0
        with self._lock:
            occ = np.flatnonzero(self._slotkey >= 0)
            if not occ.size:
                return 0
            keys = self._slotkey[occ]
            order = np.argsort(keys, kind="stable")   # deterministic wire
            keys = keys[order]
            have = self._vers[occ[order]].copy()
        vers = np.asarray(self.store.versions(self.table, keys), np.int64)
        stale = vers > have
        if not stale.any():
            return 0
        sk = keys[stale]
        rows = np.asarray(self.store.pull(self.table, sk), np.float32)
        sv = vers[stale]
        if _race.ACTIVE is not None:   # ISSUE 14 preemption point
            _race.point("cache.refresh_commit")
        refreshed = 0
        with self._lock:
            slots = self._find(sk)
            live = slots >= 0
            if live.any():
                s = slots[live]
                newer = sv[live] > self._vers[s]
                s = s[newer]
                self._data[s] = rows[live][newer]
                self._vers[s] = sv[live][newer]
                refreshed = int(s.size)
        if refreshed:
            record_cache("emb_cache_refresh_rows", refreshed)
        return refreshed

    def _refresh_async(self):
        """Run :meth:`refresh_stale` on a background daemon thread (at
        most one in flight): the serving batch whose lookup trips the
        ``refresh_every`` counter must not pay the sweep's store round
        trips in its own tail latency."""
        with self._lock:
            if self._refresh_thread is not None \
                    and self._refresh_thread.is_alive():
                return
            t = threading.Thread(target=self._refresh_quiet, daemon=True,
                                 name="hetu-emb-refresh")
            # started INSIDE the lock: a concurrent refresh_join must
            # never observe (and try to join) a not-yet-started thread,
            # and a concurrent _refresh_async must never read the
            # unstarted thread as not-alive and spawn a second sweep
            t.start()
            self._refresh_thread = t

    def _refresh_quiet(self):
        t0 = time.perf_counter_ns() if _TR.on else 0
        try:
            n = self.refresh_stale()
            if _TR.on:
                # the read-only staleness sweep, on its own
                # "hetu-emb-refresh" track
                _TR.complete("emb.refresh", t0, time.perf_counter_ns(),
                             cat="serve", args={"rows": n})
        except Exception:
            pass    # best-effort: the next counter trip retries

    def refresh_join(self, timeout=None):
        """Wait for an in-flight async staleness sweep (deterministic
        tests, drain-before-shutdown).  Returns True when no sweep is
        running afterwards."""
        with self._lock:
            t = self._refresh_thread
        if t is None:
            return True
        t.join(timeout)
        return not t.is_alive()

    # -- device-resident mode (ISSUE 11; see class docstring) --------------
    def _ensure_dev_slab(self):
        """The device row slab: ``limit`` cache slots + ``device_scratch``
        overflow rows + one dump row for fill padding, each row
        ``slab_width(width)`` wide (the gather kernel moves whole
        128-lane rows; readers slice back to ``width``).  Built lazily
        so a host-mode table never touches jax."""
        if self._dev_slab is None:
            import jax.numpy as jnp
            from ..ops.pallas.emb_cache import slab_width
            self._dev_slab = jnp.zeros(
                (self.limit + self._dev_scratch + 1,
                 slab_width(self.width)), jnp.float32)
        return self._dev_slab

    def begin_lookup(self, keys):
        """Device-mode lookup, phase 1 of 3: take the cache lock and PLAN
        — hit/refresh partition, victim/slot plan, push payload copies,
        device positions — exactly the pre-RPC half of the host-mode
        ``_lookup_locked``.  Returns a :class:`_DevLookup` handle whose
        :meth:`_DevLookup.roundtrip` runs the one fallible store round
        trip LOCK-FREE (any thread — the executor uses the feed-pipeline
        thread so the miss pull overlaps the dense forward), after which
        :meth:`finish_lookup` commits, or :meth:`abort_lookup` releases
        with the cache untouched (transactional contract: a transport
        failure registers no never-filled slot and loses no pending
        grad).  The lock is HELD until finish/abort — the same window
        the host-mode ``lookup`` holds it for."""
        if not self.device:
            raise RuntimeError("begin_lookup requires device=True")
        keys = np.ascontiguousarray(keys, np.int64)
        flat = keys.reshape(-1)
        self._lock.acquire()
        try:
            h = _DevLookup(self, keys.shape, flat)
            self._tick += 1
            self._batch_memo = None
            self.stats["lookups"] += int(flat.size)
            if not flat.size:
                return h
            uk, inv, cnt = np.unique(flat, return_inverse=True,
                                     return_counts=True)
            slots = self._find(uk)
            present = slots >= 0
            hit = np.zeros(uk.size, bool)
            hit[present] = self._uses[slots[present]] < self.pull_bound
            refresh = ~hit
            h.uk, h.inv, h.cnt = uk, inv, cnt
            h.slots, h.hit, h.refresh = slots, hit, refresh
            push_keys, push_grads = [], []
            if refresh.any():
                rkeys = uk[refresh]
                rslots = slots[refresh].copy()
                stale = rslots >= 0
                dirty, dkeys, dgrads = self._plan_dirty(rslots[stale])
                if dirty.size:
                    push_keys.append(dkeys)
                    push_grads.append(dgrads)
                absent = ~stale
                plan = None
                if absent.any():
                    plan = self._plan_slots(rkeys[absent], slots[present])
                    ev_dirty, evk, evg = self._plan_dirty(plan[2])
                    if ev_dirty.size:
                        push_keys.append(evk)
                        push_grads.append(evg)
                    rslots[absent] = plan[0]
                h.rkeys, h.rslots = rkeys, rslots
                h.dirty, h.plan, h.absent = dirty, plan, absent
            if push_keys:
                pk = np.concatenate(push_keys)
                pg = np.concatenate(push_grads)
                order = np.argsort(pk, kind="stable")  # deterministic wire
                h.pk, h.pg = pk[order], pg[order]
            # device positions per unique key: committed/planned slot,
            # or a scratch row for capacity-overflow keys (served — and
            # grad-pushed — uncached, never registered)
            pos = slots.copy()
            if h.rslots is not None:
                pos[refresh] = h.rslots
            over = pos < 0
            n_over = int(over.sum())
            if n_over > self._dev_scratch:
                raise RuntimeError(
                    f"device-mode batch overflow: {n_over} uncacheable "
                    f"unique keys exceed device_scratch="
                    f"{self._dev_scratch} — raise device_scratch (or "
                    f"limit), or use the host cache for this workload")
            pos[over] = self.limit + np.arange(n_over)
            h.positions = pos
            if h.rkeys is not None:
                h.fill_targets = pos[refresh].astype(np.int32)
            return h
        except BaseException:
            self._lock.release()
            raise

    def finish_lookup(self, h, rows):
        """Device-mode lookup, phase 3: COMMIT the plan with the pulled
        miss ``rows`` (aligned to ``h.rkeys``) — the post-RPC half of
        the host-mode ``_lookup_locked`` (slot registration, hit/
        eviction bookkeeping, counters) plus the eager in-place device
        fill (:meth:`_apply_dev_fill`) — and release the lock.
        Standalone callers and the executor share this one commit
        path; the consuming gather (in the step, or eagerly in
        ``lookup``) happens after it."""
        try:
            if h.flat.size == 0:
                return
            uk, cnt, hit, refresh = h.uk, h.cnt, h.hit, h.refresh
            if h.pk is not None:
                self.stats["pushes"] += int(h.pk.size)
                self.stats["push_rpcs"] += 1
                record_cache("emb_cache_push_rows", int(h.pk.size))
                record_cache("emb_cache_push_rpcs", 1)
            slots = h.slots
            if h.rkeys is not None:
                rslots = h.rslots
                self.stats["fetches"] += int(h.rkeys.size)
                if h.dirty.size:
                    self._grad[h.dirty] = 0.0
                    self._gcnt[h.dirty] = 0
                if h.plan is not None:
                    self._commit_slots(h.rkeys[h.absent], h.plan)
                cached = rslots >= 0
                if cached.all():
                    cs, cnt_r = rslots, cnt[refresh]
                else:
                    cs = rslots[cached]
                    cnt_r = cnt[refresh][cached]
                # NB: no ``_data[cs] = rows`` here — in device mode the
                # filled slab IS the serving copy; mirroring every miss
                # row into the host slab would double the per-step row
                # traffic for a buffer nothing reads
                self._uses[cs] = cnt_r
                self._ticks[cs] = self._tick
                self._freq[cs] += cnt_r
                self._maybe_rehash()
                slots = slots.copy()
                slots[refresh] = rslots
            n_hit_rows = int(cnt[hit].sum())
            self.stats["hits"] += n_hit_rows
            record_cache("emb_cache_hit_rows", n_hit_rows)
            record_cache("emb_cache_miss_rows",
                         int(h.flat.size) - n_hit_rows)
            if hit.any():
                hs = slots[hit]
                self._uses[hs] += cnt[hit]
                self._ticks[hs] = self._tick
                self._freq[hs] += cnt[hit]
            self._batch_memo = (h.flat, uk, h.inv, cnt, slots)
            if h.rkeys is not None:
                try:
                    self._apply_dev_fill(rows, h.fill_targets)
                except BaseException:
                    # the host commit above is already irreversible (and
                    # correct — the pushes landed); a failed FILL must
                    # not leave registered slots whose slab rows were
                    # never written, so poison them stale: they re-pull
                    # on the next lookup instead of serving garbage
                    if cs.size:
                        self._uses[cs] = self.pull_bound
                    raise
        finally:
            h.done = True
            self._lock.release()

    def _apply_dev_fill(self, rows, targets):
        """Land pulled rows in the device slab IN PLACE: the fill
        arrays are padded to a pow2 bucket (padding targets the dump
        row) so miss-count jitter cycles a bounded set of tiny compiled
        fill programs, and the slab rides through a jit donated on TPU
        so no per-step ``(limit + scratch, width)`` copy exists there
        (CPU cannot honor donation and copies either way).  The
        training step's own program never sees the fill — its input
        shapes stay fixed."""
        import jax
        from ..ops.pallas import emb_cache as _emb
        m = int(rows.shape[0])
        bucket = _emb.fill_bucket(m)
        # np.empty: padding rows are garbage by design — their targets
        # all point at the dump row, which is never gathered (and every
        # reader cuts the lanes past ``width`` away)
        slab = self._ensure_dev_slab()
        fr = np.empty((bucket, slab.shape[1]), np.float32)
        ft = np.full((bucket,), self._dev_dump, np.int32)
        fr[:m, :self.width] = rows
        ft[:m] = targets
        self._dev_slab = _emb.fill_rows_inplace(
            slab, jax.device_put(fr), jax.device_put(ft))

    def abort_lookup(self, h):
        """Release a :meth:`begin_lookup` handle after a failed round
        trip: the plan is discarded, nothing host- or device-side was
        mutated by it (the tick/lookup stats advanced, as they do on a
        failed host-mode lookup)."""
        if not h.done:
            h.done = True
            self._lock.release()

    def _lookup_device(self, keys):
        """Standalone device-mode lookup (parity tests, the profiler,
        non-executor callers — e.g. ``PSEmbeddingLookupOp.pull_rows``
        on a prefetch thread): the same begin → round trip → commit
        protocol the executor drives, with the gather run eagerly
        through the dispatcher.  The RLock is re-entered around
        commit+gather so the whole serve is ATOMIC like the host-mode
        ``lookup`` — without it, a concurrent lookup could evict one of
        this batch's slots and fill another key's row into it between
        the commit and the gather.  Returns host rows like host mode."""
        h = self.begin_lookup(keys)
        try:
            rows = h.roundtrip()
        except BaseException:
            self.abort_lookup(h)
            raise
        # RLock depth 2 (begin holds depth 1): finish_lookup's release
        # drops to depth 1, keeping other threads out until the gather
        # below has served this batch's rows
        self._lock.acquire()
        try:
            self.finish_lookup(h, rows)
            if not h.flat.size:
                return np.empty(keys.shape + (self.width,), np.float32)
            import jax.numpy as jnp
            from ..ops.pallas import emb_cache as _emb
            out = _emb.emb_gather(self._ensure_dev_slab(),
                                  jnp.asarray(h.positions[h.inv]
                                              .astype(np.int32)),
                                  interpret=self.device_interpret)
            return np.asarray(out[:, :self.width]).reshape(
                keys.shape + (self.width,))
        finally:
            self._lock.release()

    def _lookup_locked(self, flat):
        self._tick += 1
        self._batch_memo = None
        self.stats["lookups"] += int(flat.size)
        if not flat.size:
            return np.empty((0, self.width), np.float32)
        uk, inv, cnt = np.unique(flat, return_inverse=True,
                                 return_counts=True)
        slots = self._find(uk)
        present = slots >= 0
        hit = np.zeros(uk.size, bool)
        hit[present] = self._uses[slots[present]] < self.pull_bound
        rows_out = np.empty((uk.size, self.width), np.float32)
        refresh = ~hit
        if refresh.any():
            rkeys = uk[refresh]
            rslots = slots[refresh].copy()
            push_keys, push_grads = [], []
            # stale rows keep their slots; their pending grads must land
            # BEFORE the re-pull so the refreshed value includes them —
            # payloads are COPIES, the slab clears only on success
            stale = rslots >= 0
            dirty, dkeys, dgrads = self._plan_dirty(rslots[stale])
            if dirty.size:
                push_keys.append(dkeys)
                push_grads.append(dgrads)
            absent = ~stale
            plan = None
            if absent.any():
                plan = self._plan_slots(rkeys[absent], slots[present])
                ev_dirty, evk, evg = self._plan_dirty(plan[2])
                if ev_dirty.size:
                    push_keys.append(evk)
                    push_grads.append(evg)
                rslots[absent] = plan[0]
            # the ONLY fallible step: one fused round trip.  A transport
            # failure raises with the cache untouched — no key registered
            # for a row that was never filled, no pending grad lost
            rows = self._flush_to_store(push_keys, push_grads, rkeys)
            self.stats["fetches"] += int(rkeys.size)
            if dirty.size:
                self._grad[dirty] = 0.0
                self._gcnt[dirty] = 0
            if plan is not None:
                self._commit_slots(rkeys[absent], plan)
            cached = rslots >= 0
            if cached.all():            # common case: no overflow spill
                cs, rows_c, cnt_r = rslots, rows, cnt[refresh]
            else:
                cs, rows_c = rslots[cached], rows[cached]
                cnt_r = cnt[refresh][cached]
            self._data[cs] = rows_c
            self._uses[cs] = cnt_r
            self._ticks[cs] = self._tick
            self._freq[cs] += cnt_r
            rows_out[refresh] = rows
            self._maybe_rehash()
            slots = slots.copy()
            slots[refresh] = rslots
        # hit bookkeeping commits AFTER the fallible round trip: a raised
        # lookup must not burn pull_bound budget (or count hits) for rows
        # that were never served
        n_hit_rows = int(cnt[hit].sum())
        self.stats["hits"] += n_hit_rows
        record_cache("emb_cache_hit_rows", n_hit_rows)
        record_cache("emb_cache_miss_rows", int(flat.size) - n_hit_rows)
        if hit.any():
            hs = slots[hit]
            self._uses[hs] += cnt[hit]
            self._ticks[hs] = self._tick
            self._freq[hs] += cnt[hit]
            rows_out[hit] = self._data[hs]
        self._batch_memo = (flat, uk, inv, cnt, slots)
        return rows_out[inv]

    def update(self, keys, grads):
        if self.read_only:
            raise RuntimeError(
                "DistCacheTable(read_only=True) rejects update(): a "
                "serving replica must never push gradients — train "
                "through a read-write cache and serve through this one")
        keys = np.ascontiguousarray(keys, np.int64).reshape(-1)
        if not keys.size:
            return
        grads = np.ascontiguousarray(grads, np.float32).reshape(keys.size,
                                                                -1)
        if self.device:
            # standalone device-mode update: the per-unique-key segment
            # sum runs through the device scatter-add dispatcher (the
            # executor hands in pre-summed grads via apply_update_summed
            # instead — same kernel, summed inside the jitted step)
            import jax.numpy as jnp
            from ..ops.pallas import emb_cache as _emb
            uk, inv, cnt = np.unique(keys, return_inverse=True,
                                     return_counts=True)
            acc = np.asarray(_emb.emb_scatter_add(
                jnp.asarray(grads), jnp.asarray(inv.astype(np.int32)),
                interpret=self.device_interpret))[:uk.size]
            self.apply_update_summed(uk, acc, cnt)
            return
        with self._lock:
            self._update_locked(keys, grads)

    def apply_update_summed(self, uk, acc, cnt):
        """Device-path update entry: ``acc`` already holds the
        per-unique-key grad sums (the device scatter-add kernel replaced
        the host scipy-CSR pass), ``uk`` the batch's sorted unique keys
        and ``cnt`` their occurrence counts — everything the bounded-
        staleness bookkeeping (``gcnt``/``push_bound``/eviction clocks)
        needs, with identical integer decisions to the host-mode
        ``update`` on the same batch."""
        uk = np.ascontiguousarray(uk, np.int64).reshape(-1)
        acc = np.ascontiguousarray(acc, np.float32).reshape(uk.size, -1)
        cnt = np.ascontiguousarray(cnt, np.int64).reshape(-1)
        with self._lock:
            self._tick += 1
            self._batch_memo = None
            self.stats["updates"] += int(cnt.sum())
            if not uk.size:
                return
            self._apply_update(uk, cnt, self._find(uk), acc)

    def _update_locked(self, flat, grads):
        self._tick += 1
        memo, self._batch_memo = self._batch_memo, None
        self.stats["updates"] += int(flat.size)
        if not flat.size:
            return
        if memo is not None and memo[0].size == flat.size \
                and np.array_equal(memo[0], flat):
            # the immediately-preceding lookup partitioned this exact
            # batch; nothing mutated in between (same lock)
            _, uk, inv, cnt, slots = memo
            slots = slots.copy()
        else:
            uk, inv, cnt = np.unique(flat, return_inverse=True,
                                     return_counts=True)
            slots = self._find(uk)
        acc = _segment_sum(grads, inv, cnt)
        self._apply_update(uk, cnt, slots, acc)

    def _apply_update(self, uk, cnt, slots, acc):
        """Post-segment-sum half of ``update`` (shared by the host path
        and the device path's pre-summed entry): slot planning for
        absent keys, push-bound accounting, the one batched push round
        trip, and the transactional commit."""
        present = slots >= 0
        push_keys, push_grads = [], []
        absent = ~present
        plan = None
        if absent.any():
            plan = self._plan_slots(uk[absent], slots[present])
            ev_dirty, evk, evg = self._plan_dirty(plan[2])
            if ev_dirty.size:
                push_keys.append(evk)
                push_grads.append(evg)
            slots[absent] = plan[0]
        cached = slots >= 0
        if cached.all():
            cs, acc_c, cnt_c = slots, acc, cnt
        else:
            cs, acc_c, cnt_c = slots[cached], acc[cached], cnt[cached]
            # capacity overflow: these keys' grads go straight out with
            # the same batched push (early push is within the bound)
            push_keys.append(uk[~cached])
            push_grads.append(acc[~cached])
        # push-bound overflow computed on the HYPOTHETICAL post-batch
        # counts; payloads are fresh sums, the slab commits only after
        # the push lands, so a failed round trip leaves the CACHE
        # unapplied and a caller retry is exactly-once against a
        # single-shard store.  (Across a multi-peer fanout the push is
        # at-least-once on a partial failure — per-peer acks land
        # independently, the reference ps-lite semantics.)  Slots
        # PLANNED for new keys still hold their victim's uncommitted
        # gcnt/grad — a fresh key starts from zero, not from those
        fresh = None
        if plan is not None:
            # over uk: absent keys that got a slot this batch
            fresh = (absent & (slots >= 0))[cached] if not cached.all() \
                else absent
        prior_gcnt = self._gcnt[cs] if fresh is None \
            else np.where(fresh, 0, self._gcnt[cs])
        new_gcnt = prior_gcnt + cnt_c
        exceed = new_gcnt >= self.push_bound
        if exceed.any():
            es = cs[exceed]
            pgrads = self._grad[es] + acc_c[exceed]
            if fresh is not None and fresh[exceed].any():
                pgrads[fresh[exceed]] = acc_c[exceed][fresh[exceed]]
            push_keys.append(uk[cached][exceed])
            push_grads.append(pgrads)
        # the ONLY fallible step: one batched push round trip
        self._flush_to_store(push_keys, push_grads)
        if plan is not None:
            regk, regs = self._commit_slots(uk[absent], plan)
            # grad-only slots: the row was never pulled, so it must never
            # serve — born stale (device mode has no host row mirror to
            # zero; uses=pull_bound alone keeps the slot unservable)
            if not self.device:
                self._data[regs] = 0.0
            self._uses[regs] = self.pull_bound
        self._grad[cs] += acc_c
        self._gcnt[cs] = new_gcnt
        self._ticks[cs] = self._tick
        self._freq[cs] += cnt_c
        if exceed.any():
            self._grad[es] = 0.0
            self._gcnt[es] = 0
            self._uses[es] = self.pull_bound   # server is ahead: stale
        self._maybe_rehash()

    def flush(self):
        """Push every pending accumulated grad (ONE batched push) and
        invalidate the pushed rows (checkpoint barrier)."""
        with self._lock:
            d = np.flatnonzero((self._slotkey >= 0) & (self._gcnt > 0))
            if d.size:
                d = d[np.argsort(self._slotkey[d], kind="stable")]
                self._flush_to_store([self._slotkey[d].copy()],
                                     [self._grad[d].copy()])
                self._grad[d] = 0.0
                self._gcnt[d] = 0
                self._uses[d] = self.pull_bound

    def close(self):
        """Flush pending grads; safe to call repeatedly / at teardown.

        During interpreter finalization OR a garbage-collection pass the
        flush is SKIPPED: pushing through numpy/ctypes while the runtime
        is being torn down segfaults (observed via ``Executor.__del__``
        at process exit), and a GC-triggered ``__del__`` can reach this
        close while the interrupted main-thread frame sits INSIDE a
        native push on a store whose peers are being destructed in the
        same pass in arbitrary order (observed as a segfault in
        ``PSAgent.rows`` mid-collection) — finalizer context must never
        touch the native store.  Pending grads are bounded-staleness
        state; anything that must be durable goes through an explicit
        ``flush``/checkpoint from live code (``Executor.save`` already
        calls ``ps_flush``)."""
        import sys
        if sys.is_finalizing() or _in_gc_pass():
            return
        try:
            self.flush()
        except Exception:
            pass    # store already closed at teardown

    def perf(self):
        """Counter snapshot + read hit rate (CacheSparseTable.perf parity:
        the HET cache's citable number)."""
        with self._lock:
            d = dict(self.stats)
            d["size"] = int((self._slotkey >= 0).sum())
        d["hit_rate"] = (d["hits"] / d["lookups"]) if d["lookups"] else 0.0
        return d

    def __len__(self):
        with self._lock:
            return int((self._slotkey >= 0).sum())
