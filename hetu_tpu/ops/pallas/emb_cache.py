"""Device-resident HET-cache embedding kernels (ISSUE 11 tentpole).

The HET client cache (``ps/dist_store.py:DistCacheTable``, PR 3) keeps
its slot table, eviction clocks and transactional commit protocol
host-side — but the *math* of the hot path used to be host numpy too:
every cached row rode host→device each step, and the grad segment-sum
came back through a scipy-CSR host pass.  This module moves the math
onto the chip over a device-resident ``(limit + scratch + 1, width)``
float32 slab (``width`` rounded up to the chip's 128-lane tile,
:func:`slab_width`):

* :func:`gather_rows` — Pallas gather by slot index: per-row async DMA
  from the HBM slab into the output block (the rows of one block are
  all in flight before the first wait — ``moe_dispatch.row_gather``,
  with the always-valid slot indices the cache hands out).
* :func:`scatter_add_grads` — the training-path grad reduction:
  device-side sort by the batch's unique-inverse map + the existing
  :func:`~hetu_tpu.ops.pallas.segment_sum.sorted_segment_sum` MXU
  kernel.  Replaces the scipy-CSR host pass of ``_segment_sum`` for
  device-resident tables: row ``j`` of the result is the summed grad
  of the batch's ``j``-th sorted unique key.
* :func:`fill_rows` — the miss landing: scatter freshly-pulled rows
  into their committed slots (an XLA ``.at[].set`` — the only H2D
  traffic left per step is the miss rows themselves; hits never cross
  the host boundary again).

Dispatch mirrors the flash-attention discipline (PR 1): the
``emb_*`` entry points take the Pallas path on TPU (or under
``interpret=True`` in CPU CI), otherwise fall back to ``jnp.take`` /
``jax.ops.segment_sum`` with the reason counted in the
``emb_pallas_fallbacks`` family (``metrics.emb_pallas_fallback_counts``,
surfaced by ``HetuProfiler.emb_pallas_fallbacks()``); never silent.
``HETU_REQUIRE_PALLAS_EMB=1`` escalates any fallback to a hard failure
so a TPU run cannot quietly train off the kernel path.
"""
import os

import jax
import jax.numpy as jnp

from .moe_dispatch import LANES, row_gather
from .segment_sum import sorted_segment_sum

#: slot indices handled per grid step — each row is one async DMA, so a
#: block is also the DMA queue depth kept in flight
ROW_BLOCK = 8


def _note_fallback(reason):
    """Count one embedding dispatch that left the Pallas path.  Like the
    flash counters, counts are per jax TRACE (dispatch happens when the
    program traces), so a count climbing across steps means the jit
    cache is thrashing and ONE nonzero entry means the workload compiled
    onto the slow path."""
    from ...metrics import counters_suppressed, record_emb_pallas_fallback
    # the recorder guards counting itself; THIS guard exists for the
    # HETU_REQUIRE_PALLAS_EMB raise below — an abstract eval_shape
    # trace must not hard-fail a lint pass (the flash _note_* idiom)
    if counters_suppressed():
        return
    record_emb_pallas_fallback(reason)
    if os.environ.get("HETU_REQUIRE_PALLAS_EMB") == "1":
        raise RuntimeError(
            f"HETU_REQUIRE_PALLAS_EMB=1: embedding-cache dispatch fell "
            f"back off the Pallas path (reason: {reason})")


# ----------------------------------------------------------------- gather
def slab_width(width):
    """Lane-aligned row width the cache allocates its device slab at.
    :func:`gather_rows` moves whole 128-lane rows; a slab already at
    this width is gathered in place, any other is padded (one copy of
    the slab) on every call."""
    return -(-width // LANES) * LANES


def gather_rows(slab, slots, block=ROW_BLOCK, interpret=False):
    """``out[i] = slab[slots[i]]`` — Pallas per-row async DMA gather
    (:func:`~hetu_tpu.ops.pallas.moe_dispatch.row_gather`).

    ``slots`` (n,) int must all be valid slab rows (the cache's slot
    plan guarantees it: hits gather their committed slot, misses were
    filled first, overflow keys gather their scratch row)."""
    if slots.shape[0] == 0:
        return jnp.zeros((0, slab.shape[1]), slab.dtype)
    return row_gather(slab, slots, block=block, interpret=interpret)


# ------------------------------------------------------------ scatter-add
def scatter_add_grads(grad, inv, block=128, interpret=False):
    """Per-unique-key grad sums on device (the scipy-CSR replacement).

    ``grad`` (n, w) row gradients, ``inv`` (n,) the batch's
    unique-inverse map (``np.unique(..., return_inverse=True)`` —
    values in [0, U)).  Sorts the rows by segment in XLA (fast bitonic
    sort on TPU) and reduces each run with the
    :func:`sorted_segment_sum` MXU kernel.  Returns (n, w): rows [0, U)
    hold the per-sorted-unique-key sums, the tail is zero padding (U is
    only known host-side — static shapes rule)."""
    n = grad.shape[0]
    if n == 0:
        return jnp.zeros_like(grad)
    inv = inv.astype(jnp.int32)
    order = jnp.argsort(inv)            # stable (lax.sort)
    seg = jnp.take(inv, order)
    rows = jnp.take(grad, order, axis=0)
    return sorted_segment_sum(rows, seg, n, block=block,
                              interpret=interpret)


# ------------------------------------------------------------- miss fill
def fill_rows(slab, rows, targets):
    """Land freshly-pulled miss rows in their committed slots:
    ``slab[targets[i]] = rows[i]``.  Padding entries all point at the
    cache's dump row (never gathered), so the fill arrays can ride in a
    small set of fixed bucket shapes without retracing per miss count.
    Plain XLA scatter — the expensive half of a miss is the PS pull,
    which the executor overlaps with the dense forward on the
    feed-pipeline thread; this lands the pulled bytes in their slots."""
    if rows.shape[0] == 0:
        return slab
    return slab.at[targets].set(rows.astype(slab.dtype))


#: the fill executables, keyed by donate flag (built on first use; one
#: tiny program per fill-bucket shape in jax's own jit cache)
_FILL_JIT = {}


def fill_bucket(m):
    """Pad a step's miss-fill arrays to a small pow2 bucket set (min 8):
    miss-count jitter then cycles a bounded set of compiled fill
    programs instead of compiling one per distinct miss count."""
    return 8 if m <= 8 else 1 << (m - 1).bit_length()


def fill_rows_inplace(slab, rows, targets):
    """The cache-commit fill: :func:`fill_rows` jitted with the slab
    DONATED on TPU, so XLA updates the resident slab in place instead
    of copying ``(limit + scratch, width)`` bytes per step.  (CPU/other
    backends cannot honor buffer donation — they copy either way — so
    donation is skipped there rather than warning on every fill.)  Runs
    EAGERLY at ``finish_lookup`` — keeping the fill out of the training
    step's program means the big jit sees only fixed shapes (slab,
    slots, inv) and never retraces on miss-count jitter; the fill
    itself is one tiny per-bucket executable."""
    donate = jax.default_backend() == "tpu"
    fn = _FILL_JIT.get(donate)
    if fn is None:
        fn = _FILL_JIT[donate] = jax.jit(
            fill_rows, donate_argnums=(0,) if donate else ())
    return fn(slab, rows, targets)


# ------------------------------------------------------------ dispatchers
def _want_pallas(interpret):
    """(use_pallas, interpret) under the flash dispatch rules: Pallas on
    TPU, Pallas-interpret when explicitly asked (CPU CI), fallback —
    counted — otherwise."""
    if interpret:
        return True, True
    if interpret is None and jax.default_backend() == "tpu":
        return True, False
    return False, False


def emb_gather(slab, slots, interpret=None):
    """Slot-indexed row gather with explicit fallback accounting.

    ``interpret``: None = auto (Pallas on TPU, counted ``jnp.take``
    fallback elsewhere), True = force the Pallas kernel in interpret
    mode (CPU CI parity tests), False = force the compiled kernel."""
    use, interp = _want_pallas(interpret)
    if use or interpret is False:
        return gather_rows(slab, slots, interpret=interp)
    _note_fallback(f"gather:backend_{jax.default_backend()}")
    return jnp.take(slab, slots.astype(jnp.int32), axis=0)


#: jitted gather entries per dispatch policy — the per-step gather runs
#: EAGERLY (device→device, enqueued just before the training step), and
#: routing it through one cached jit keeps the dispatcher body (and its
#: fallback counter) at trace-time cost: one recording per shape, not
#: one per step
_GATHER_JIT = {}


def gather_for_step(slab, slots, width, interpret=None):
    """The executor's per-step gather: ``emb_gather`` (cut back to the
    table's ``width`` — the slab is lane-padded) under a cached
    ``jax.jit`` so steady-state steps replay a compiled executable and
    the fallback counter keeps flash per-trace semantics."""
    fn = _GATHER_JIT.get((interpret, width))
    if fn is None:
        fn = _GATHER_JIT[(interpret, width)] = jax.jit(
            lambda slab, slots: emb_gather(
                slab, slots, interpret=interpret)[:, :width])
    return fn(slab, slots)


def emb_scatter_add(grad, inv, interpret=None):
    """Unique-inverse grad segment-sum with explicit fallback
    accounting (same knob semantics as :func:`emb_gather`)."""
    use, interp = _want_pallas(interpret)
    if use or interpret is False:
        return scatter_add_grads(grad, inv, interpret=interp)
    _note_fallback(f"scatter_add:backend_{jax.default_backend()}")
    return jax.ops.segment_sum(grad, inv.astype(jnp.int32),
                               num_segments=grad.shape[0])
