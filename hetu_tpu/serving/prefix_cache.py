"""Shared-prefix KV reuse for the decode plane (ISSUE 18 tentpole §3).

Serving traffic repeats prompts: few-shot templates, system preambles,
and zipf-popular queries share long token prefixes, and the KV rows a
prefix produces are a pure function of the prefix (each cache row
attends only to rows before it — batch mates and suffix tokens are
invisible).  :class:`PrefixKVStore` exploits that determinism: when a
sequence finishes ingesting its prompt the engine snapshots the
prompt's KV rows here, and a later request whose prompt extends a
stored prefix seats with those rows pre-filled — its prefill is
skipped outright (``O(0)`` steps for the shared part) instead of
chunked (``O(P/C)``) or token-by-token (``O(P)``).

The index is a token trie: one node per stored-prefix position, each
node remembering ONE entry whose key passes through it, so a lookup
walks at most ``len(prompt) - 1`` nodes and can reuse the first ``d``
rows of a LONGER stored prompt that shares only ``d`` leading tokens
(partial-overlap reuse, not just exact-prefix hits).  Snapshots are
immutable device arrays; capacity is bounded in BYTES with LRU
eviction on the PR 3 tick-clock discipline (hit/insert refreshes the
tick, eviction removes the minimum).  Bitwise safety is inherited, not
re-proven: the engine's masked cache writes make KV bytes independent
of ingestion mode, so a hit's token stream is bitwise-equal to the
cold path (gated in tests and the decode bench).

The same determinism makes the store a RECOVERY accelerator (ISSUE
19): a migrated in-flight stream replays ``original prompt + emitted
tokens`` as its continuation prompt on a survivor, and because stores
are shared across a fleet's engines, the dead replica's snapshot of
the original prompt (inserted at the stream's first generated token)
seats the continuation with those rows pre-filled — the lookup's
partial-overlap walk needs no recovery-specific code, and only the
journal suffix is re-prefilled
(``decode_recovery_prefix_assisted`` / ``decode_recovery_replayed_rows``
partition the continuation prompt).

Threading: ``_lock`` (witnessed, leaf-level — nothing nests under it)
guards the trie/entry maps so a store may be shared across engines;
row slicing — a device call — happens strictly OUTSIDE the lock, per
the PR 14 hierarchy's no-device-call-under-lock rule.  Counters ride
the ``prefix_cache`` family (hits/misses/hit-rows/inserts/evictions/
bytes high-water).
"""
from __future__ import annotations

import numpy as np

from ..metrics import record_prefix_cache
from ..obs.lock_witness import make_lock


class _Entry:
    __slots__ = ("key", "rows", "nbytes", "tick")

    def __init__(self, key, rows, nbytes, tick):
        self.key = key          # tuple of int token ids, the full prefix
        self.rows = rows        # {state name: array}: KV rows (heads,
                                # len(key), head_dim), and whatever else
                                # the engine snapshots beside them
        self.nbytes = nbytes
        self.tick = tick


class _Node:
    __slots__ = ("kids", "owner", "ends")

    def __init__(self):
        self.kids = {}          # token id -> _Node
        self.owner = None       # key of ONE entry passing through here
        self.ends = False       # an entry's key ends exactly here


class PrefixKVStore:
    """Bounded, LRU-evicted store of KV snapshots keyed on token
    prefixes.

    ``capacity_bytes`` bounds the resident snapshot bytes (eviction
    frees least-recently-used entries until under); ``min_tokens``
    skips storing prefixes too short to save a dispatch.  Safe to share
    across engines (one leaf-level lock); the arrays handed to
    :meth:`insert` must be immutable (jax device arrays are)."""

    def __init__(self, capacity_bytes=64 << 20, min_tokens=2):
        self.capacity_bytes = int(capacity_bytes)
        self.min_tokens = int(min_tokens)
        self._lock = make_lock("PrefixKVStore._lock")
        self._root = _Node()
        self._entries = {}      # key tuple -> _Entry
        self._bytes = 0
        self._clock = 0

    def __len__(self):
        with self._lock:
            return len(self._entries)

    @property
    def nbytes(self):
        with self._lock:
            return self._bytes

    def stats(self):
        with self._lock:
            return {"entries": len(self._entries), "bytes": self._bytes,
                    "capacity_bytes": self.capacity_bytes}

    # -- lookup ------------------------------------------------------------

    def lookup(self, prompt, whole=False):
        """Longest usable stored prefix of ``prompt``: returns
        ``(m, rows)`` where ``rows[name]`` holds the first ``m`` KV rows
        (``(heads, m, head_dim)``), or ``(0, None)`` on a miss.  ``m``
        is capped at ``len(prompt) - 1`` — at least one prompt token
        must still be fed to produce the first-token logits.

        ``whole=True``: only an entry whose WHOLE key is a prefix of
        ``prompt`` hits, and its snapshot comes back as stored — for a
        graph whose snapshots hold state that cannot be cut at a shared
        partial depth (``index`` rows, ``recurrent`` state: whatever the
        engine put in beside the KV rows)."""
        toks = np.asarray(prompt).reshape(-1).tolist()
        limit = len(toks) - 1
        with self._lock:
            node, depth = self._root, 0
            best_key, best_m = None, 0
            while depth < limit:
                node = node.kids.get(toks[depth])
                if node is None:
                    break
                depth += 1
                if whole:
                    key = tuple(toks[:depth]) if node.ends else None
                    if key in self._entries:
                        best_key, best_m = key, depth
                elif node.owner is not None and node.owner in self._entries:
                    best_key, best_m = node.owner, depth
            if best_key is None:
                record_prefix_cache("prefix_cache_misses")
                return 0, None
            ent = self._entries[best_key]
            self._clock += 1
            ent.tick = self._clock
            rows_full = ent.rows
            record_prefix_cache("prefix_cache_hits")
            record_prefix_cache("prefix_cache_hit_rows", best_m)
        # slice OUTSIDE the lock: this is a device call; the source
        # arrays are immutable so the late read races nothing
        if best_m == len(best_key):
            return best_m, dict(rows_full)
        return best_m, {name: r[:, :best_m, :]
                        for name, r in rows_full.items()}

    # -- insert / evict ----------------------------------------------------

    def insert(self, prompt, rows):
        """Store ``rows`` (``{cache_name: (heads, len(prompt),
        head_dim)}`` immutable arrays) under ``prompt``'s token key.
        Returns True when stored, False when skipped (too short, larger
        than the whole capacity, or an exact-key duplicate — duplicates
        just refresh the LRU tick)."""
        key = tuple(np.asarray(prompt).reshape(-1).tolist())
        if len(key) < self.min_tokens:
            return False
        nbytes = sum(int(r.nbytes) for r in rows.values())
        if nbytes > self.capacity_bytes:
            return False
        with self._lock:
            self._clock += 1
            ent = self._entries.get(key)
            if ent is not None:
                ent.tick = self._clock
                record_prefix_cache("prefix_cache_dup_inserts")
                return False
            self._entries[key] = _Entry(key, dict(rows), nbytes,
                                        self._clock)
            self._bytes += nbytes
            node = self._root
            for t in key:
                node = node.kids.setdefault(t, _Node())
                node.owner = key
            node.ends = True
            record_prefix_cache("prefix_cache_inserts")
            while self._bytes > self.capacity_bytes:
                self._evict_locked()
            record_prefix_cache("prefix_cache_bytes_hw", self._bytes)
        return True

    def _evict_locked(self):
        victim = min(self._entries.values(), key=lambda e: e.tick)
        del self._entries[victim.key]
        self._bytes -= victim.nbytes
        record_prefix_cache("prefix_cache_evictions")
        record_prefix_cache("prefix_cache_evicted_bytes", victim.nbytes)
        # walk the victim's path bottom-up: clear owner references that
        # still point at it and prune nodes no live entry needs
        path, node = [self._root], self._root
        for t in victim.key:
            node = node.kids.get(t)
            if node is None:
                break
            path.append(node)
        if len(path) == len(victim.key) + 1:
            path[-1].ends = False
        for depth in range(len(path) - 1, 0, -1):
            node = path[depth]
            if node.owner == victim.key:
                node.owner = None
            if not node.kids and node.owner is None and not node.ends:
                del path[depth - 1].kids[victim.key[depth - 1]]

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._root = _Node()
            self._bytes = 0


__all__ = ["PrefixKVStore"]
