"""Online inference serving (ISSUE 7 tentpole).

The HET design this repo reproduces is a serving-era system; this package
is the serving half the training executor never had:

* :class:`InferenceExecutor` — compile-once serving over frozen weights:
  one pre-compiled executable per flash-legal batch bucket, read-only
  weight loading (live Executor / dict / checkpoint), donated request
  feeds, and static rejection of train-only subgraphs
  (``train-only-op-in-serving``).
* :class:`ServingRouter` — bounded request queue feeding an adaptive
  micro-batcher: pack waiting requests to the smallest legal bucket under
  a head-of-line deadline, one jitted call, scatter the rows back;
  queue-full is an explicit :class:`ServeRejected`, not unbounded growth.
* Read-mostly embedding serving rides
  ``DistCacheTable(read_only=True)`` + PR 4's replicated store: a killed
  shard primary fails over inside the batch's pull with zero restarts.
* :class:`DecodeEngine` / :class:`DecodeRouter` (ISSUE 16) —
  continuous-batching autoregressive decode over device-resident
  incremental KV caches: per-token join/leave with slot recycling,
  bucketed batch/length growth compiling once per
  ``(batch_bucket, len_bucket)`` pair, per-token futures on
  :class:`DecodeStream`, optional tp-sharded steps via a bound
  ``ParallelPlan`` — results bitwise-independent of batch composition.
* Chunked prefill + :class:`PrefixKVStore` (ISSUE 18) — prompt
  ingestion in ``ceil(P/chunk)`` mixed-batch steps through a q_len=C
  graph entry (one compile per ``(batch, chunk, len)`` bucket triple,
  pure-prefill steps skip the logits D2H), and shared-prefix KV
  snapshots seating repeat prompts with their cache rows pre-filled —
  prefill skipped outright, token streams bitwise-equal to the
  token-by-token path in every mode.
* :class:`CellMap` / :class:`CellHead` — geo-replicated serving cells:
  disjoint rank sets each serving local traffic off the read-only
  cache, surviving a cross-cell network partition (reads keep flowing,
  writes are epoch-fenced) and converging via epoch-checked
  re-replication at heal.
* :class:`FrontDoor` / :class:`SLOAutoscaler` (ISSUE 17) — the fleet
  tier: N router replicas behind one door with load-aware dispatch,
  class-based admission control (``interactive | batch | best_effort``
  shed lowest-first as structured :class:`ServeRejected` reasons),
  per-class deadlines rejected at the door, heartbeat
  ejection/rescue/re-admission, p99-SLO autoscaling on the elastic
  plane's flap-damping machinery, and graceful drain that hands queued
  work to survivors.
* Exactly-once stream recovery (ISSUE 19) — in-flight decode
  generations SURVIVE replica death: the stream's host-side
  emitted-token journal is detached with the queue when the sweep
  ejects a dead/wedged replica, replayed through chunked prefill on
  the least-loaded survivor (:class:`PrefixKVStore` consulted first)
  under a bumped replay epoch that fences the dead replica's late
  emissions — already-resolved ``token(i)`` futures never re-fire and
  the recovered stream is bitwise-equal to an unkilled run.
  Resurrection is gated (retry budget, deadline estimator, survivor
  existence); a doomed stream fails fast with
  ``ServeRejected('recovery_exhausted')`` carrying
  ``DecodeStream.partial()``.

Proven end-to-end by ``tests/scenarios.py``: ``serve_scenario`` (zipf
request stream, chaos primary-kill mid-load with bitwise response parity)
and ``partition_scenario`` (cross-cell partition + heal with zero local
rejections and post-heal fsck convergence).
"""
from .cells import CellHead, CellMap
from .decode import DecodeEngine, DecodeRouter, DecodeStream
from .executor import InferenceExecutor, default_buckets
from .fleet import CLASSES, FrontDoor, SLOAutoscaler
from .prefix_cache import PrefixKVStore
from .router import ServingRouter, ServeRejected

__all__ = ["InferenceExecutor", "ServingRouter", "ServeRejected",
           "default_buckets", "CellMap", "CellHead",
           "DecodeEngine", "DecodeRouter", "DecodeStream",
           "PrefixKVStore", "FrontDoor", "SLOAutoscaler", "CLASSES"]
