"""One-token attention over a KV slab — Pallas TPU kernel.

A decode step's attention has a handful of query rows per (sequence, KV
head) and reads that head's cache: it is bound by the slab's bytes, so the
kernel reads the slab exactly as it is stored (:func:`~hetu_tpu.ops.
attention.kv_slab_shape`: ``(B, H, L/r, r*D)``, ``r`` consecutive key rows
side by side in one 128-lane row, ``r = 1`` for plain rows) and fetches
ONLY the key blocks below each sequence's length.  No relayout copy stands
between the append and this call (a slab whose minor dimension is a whole
number of lane rows is stored row-major and unpadded, the layout a
``pallas_call`` demands of its operands).

Every one-token caller is this one call (a third, ``ops/kda.py``'s
grouped-query read, hands the ``R`` heads of a key head as ``R`` rows; the
fourth, ``ops/mla.py``'s latent read, is the latent mode below):

* GPT-2's packed heads (``dispatch_sdpa_decode``; ``r = 2``, float32): a
  query becomes ``r`` rows (:func:`~hetu_tpu.ops.attention.
  kv_slab_queries`), copy ``j`` in lanes ``[j*D, (j+1)*D)`` and zero
  elsewhere, so row ``j``, column ``m`` of ``rows @ K_slab^T`` is the score
  of key ``m*r + j`` — the plain products plus exact zeros;
* the shared-KV readers of a differential-attention decoder
  (``ops/ssm.py::_diff_attention_kv``; ``r = 1``, bfloat16 paired rows
  ``[k1; k2]``): the four heads that read a key pair are four rows, each
  laid in its own 64-lane half.

A chunked step's ``C`` positions a sequence are the same call's CHUNK
form (``chunk=C``; ``dispatch_sdpa_prefill``, GPT-2's): ``C * r`` score
rows a head, the schedule and the last block's copy run to where the rows
the step appended end, and each row masked at its own position's limit.

Every score row keeps its own online softmax (running max, sum and
``P @ V`` accumulator, float32); the ``r`` rows of a query are merged at
the end by their log-sum-exp, ``out = sum_j e^(m_j - m) acc_j[lanes j] /
sum_j e^(m_j - m) l_j``, which for ``r = 1`` is the row's own ``acc / l``.

Geometry (:func:`geometry`, from the call's shape and dtype alone): one
program holds ALL the heads of a slot over a block of key rows sized to
``BLOCK_BYTES``, and the grid walks a schedule of the LIVE (slot, key
block) pairs only: the schedule and its length are computed from
``lengths`` in front of the call and ride as scalar prefetch and as the
grid's traced bound, so a block past a sequence's length is neither
fetched, computed nor stepped over.

The slabs stay in HBM (``memory_space=pl.ANY``) and the kernel copies its
blocks itself into a ring of ``DEPTH`` buffers a slab, the copies of the
next ``DEPTH - 1`` schedule entries started before this entry's products
— across a slot's end too.  Of a sequence's LAST live block a copy moves
only the rows below the sequence's length, rounded up to a sublane tile
of the slab's dtype, and ONE product takes only the sub-blocks of
``SUB_ROWS`` rows that hold a copied row (a branch a count, so that every
operand's size is static; the rows between the copy's end and the
sub-block's are zeroed: they hold what an earlier block left there, or
nothing yet, and a weight of zero times a NaN is a NaN).  So neither the
bytes nor the products of a step depend on where a block's edge falls,
and a block is as long as VMEM lets it be.  Measured on the chip
(PERF.md §6, PR 41) against the BlockSpec pipeline over whole blocks it
replaced (1.46 times the time at the latent shape, 1.11 to 1.61 at the
others), a product a sub-block in a loop (each pays the softmax's chain
of dependent steps, 0.38 us: 1.75 times the time at 128 rows), one copy
ahead (the lengths of neighbouring slots differ too much: 1.26 times)
and a program a slot with a loop over its blocks (level with one copy
ahead; the entry two ahead is a walk over the lengths there where the
schedule makes it a lookup).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF

#: bytes of one key block: all the heads of a program, every slab operand
#: of the call (K and V; the latent read's one slab takes rows twice as
#: long) — ``DEPTH`` of them wait in VMEM.  A block's product costs 0.4 us
#: whatever it multiplies (the softmax's chain of dependent steps) and a
#: grid step a third of one, and with a sequence's last block copied and
#: multiplied only as far as the sequence reaches a longer block wastes
#: nothing: measured on the chip at the four cells' shapes
#: (``tools/decode_attn_ab.py``, PERF.md §6 PR 41), half of it reads 4 %
#: slower at glm's shape and 10 to 19 % at chat's (phi4's and solar's the
#: same), and ``DEPTH`` blocks of twice of it do not fit a kernel's VMEM
BLOCK_BYTES = 2560 * 1024
#: key blocks in VMEM at once, one multiplied and the others on their way:
#: the lengths of neighbouring slots differ by tens to one, so with ONE
#: copy ahead a long block's copy waits on a short block's product and the
#: reverse (glm's call 0.41 ms at 2, 0.34 at 3, 0.325 at 4: 10 of the 16
#: MiB a kernel may use)
DEPTH = 4
#: what a kernel's buffers may take of the 16 MiB of VMEM it is given, the
#: compiler's own temporaries beside them
VMEM_BYTES = 14 * 1024 * 1024
#: slab rows to a sub-block: the products of a sequence's last block stop
#: at the first sub-block boundary past its rows (at 128 twice the
#: branches and 1 to 7 % slower)
SUB_ROWS = 256
#: a program takes fewer heads before its key blocks get shorter than this
MIN_BLOCK_ROWS = 64


def _fit(hb, rows, lanes, itemsize, slabs):
    """Slab rows the key block of a program of ``hb`` heads and ``rows``
    score rows a head may have: ``BLOCK_BYTES`` of them over the call's
    slabs, and no more than ``VMEM_BYTES`` leave beside what the score
    rows hold — the queries (two buffers), the running max, sum and
    ``P @ V`` (float32), and a score and a weight a key row — for
    ``DEPTH`` blocks."""
    row = lanes * itemsize * slabs
    held = hb * rows * (2 * lanes * itemsize + 4 * (2 * 128 + lanes))
    return min(BLOCK_BYTES // (hb * row),
               (VMEM_BYTES - held) // (hb * (DEPTH * row + 8 * rows)))


def geometry(heads, slab_rows, lanes, itemsize, slabs=2, rows=8):
    """``(heads per program, slab rows per key block)`` of a call of
    ``rows`` score rows a head over ``slabs`` slabs of ``(B, heads,
    slab_rows, lanes)``: every head of a slot in one program while a
    block of ``MIN_BLOCK_ROWS`` rows of them fits (:func:`_fit`; else
    their largest divisor that does), and the largest sublane-aligned
    divisor of the slab's rows that keeps the block inside it.  The few
    score rows of a one-token call leave ``BLOCK_BYTES`` the bound; a
    chunk's ``C * pack`` take their share of VMEM first."""
    floor = min(slab_rows, MIN_BLOCK_ROWS)
    hb = max((h for h in range(1, heads + 1)
              if heads % h == 0
              and _fit(h, rows, lanes, itemsize, slabs) >= floor),
             default=1)
    tile = 32 // itemsize
    fit = _fit(hb, rows, lanes, itemsize, slabs)
    block = max((r for r in range(tile, min(fit, slab_rows) + 1, tile)
                 if slab_rows % r == 0), default=slab_rows)
    return hb, block


def fits(heads, slab_rows, lanes, itemsize, slabs=2, rows=8):
    """Whether such a call's key block is one :func:`_fit` allows: score
    rows too many for one head's program leave it none."""
    hb, block = geometry(heads, slab_rows, lanes, itemsize, slabs, rows)
    return block <= _fit(hb, rows, lanes, itemsize, slabs)


def _tail(block_k, itemsize):
    """``(copy tile, sub-block)`` of a key block of ``block_k`` slab rows:
    a sequence's last block is copied as far as the sequence reaches
    rounded up to a sublane tile of the slab's dtype (``None``: a block
    that is no whole number of tiles is copied whole), and multiplied in
    sub-blocks of ``SUB_ROWS`` rows (a block that is no whole number of
    them: in one)."""
    tile = 32 // itemsize
    return (tile if block_k % tile == 0 else None,
            SUB_ROWS if block_k % SUB_ROWS == 0 else block_k)


def _init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _block(q, k, v, first, length, m_scr, l_scr, acc_scr, pack, chunk=None):
    """Some key rows into the running softmax of every score row.  ``q``:
    (heads, rows, lanes); ``k`` / ``v``: (heads, n, lanes), slab rows
    ``first ...``.  ``chunk``: None, every row sees the keys below
    ``length``; else ``(start, per)``, the rows are a chunk's, ``per`` a
    position: row ``i`` sees the keys below ``start + i // per``, and none
    at or past ``length``, where the rows the step wrote end."""
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)          # (heads, rows, block_k)
    shape = s.shape if chunk is None else (1,) + s.shape[1:]
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 2)
    if chunk is not None:
        start, per = chunk
        length = jnp.minimum(start + jax.lax.div(row, per), length)
    # row i, column m scores key (first + m)*r + i % r
    _fold(s, (col + first) * pack + row % pack < length, v, m_scr, l_scr,
          acc_scr)


def _fold(s, valid, v, m_scr, l_scr, acc_scr):
    """Scores ``s`` (heads, rows, n), counted where ``valid``, and their
    values ``v`` (heads, n, lanes) into the running softmax."""
    s = jnp.where(valid, s, NEG_INF)
    m_prev = m_scr[:, :, :1]                         # (heads, rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # a row with no key yet has m_new = NEG_INF: its exp(0) must not count
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_new = alpha * l_scr[:, :, :1] + jnp.sum(p, axis=2, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + _pv(p, v)
    m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)


def _pv(p, v):
    """``P @ V`` in float32: (heads, rows, block_k) float32 weights over
    (heads, block_k, lanes) values.  Values narrower than float32 meet
    the weights as TWO rows of their own type, ``p = hi + lo`` (the
    rounding to that type and what it left), stacked so that V crosses
    the MXU once: each score row is rounded on its own here — before its
    sum is known and before ``A1 − λ A2`` is taken of a differential
    pair, where one rounding of bfloat16 weights reads up to three times
    the error of rounding the combined, normalised weights (PERF.md §6,
    PR 30) — and with the second row nothing of a weight is lost that
    float32 sums would keep."""
    dims = (((2,), (1,)), ((0,), (0,)))
    if v.dtype == jnp.float32:
        return jax.lax.dot_general(p, v, dims,
                                   preferred_element_type=jnp.float32)
    hi = p.astype(v.dtype)
    lo = (p - hi.astype(jnp.float32)).astype(v.dtype)
    out = jax.lax.dot_general(jnp.concatenate([hi, lo], axis=1), v, dims,
                              preferred_element_type=jnp.float32)
    return out[:, :p.shape[1]] + out[:, p.shape[1]:]


def _finish(o_ref, m_scr, l_scr, acc_scr, pack):
    """The ``pack`` rows of each query merged by their log-sum-exp."""
    queries, d = o_ref.shape[-2:]
    for g in range(queries):
        at = [g * pack + j for j in range(pack)]
        m = [m_scr[:, i:i + 1, :1] for i in at]      # (heads, 1, 1) each
        top = functools.reduce(jnp.maximum, m)
        w = [jnp.exp(mj - top) for mj in m]
        den = sum(wj * l_scr[:, i:i + 1, :1] for wj, i in zip(w, at))
        num = sum(wj * acc_scr[:, i:i + 1, j * d:(j + 1) * d]
                  for j, (wj, i) in enumerate(zip(w, at)))
        o_ref[:, g:g + 1, :] = (
            num / jnp.where(den == 0.0, 1.0, den)).astype(o_ref.dtype)


def _finish_chunk(o_ref, m_scr, l_scr, acc_scr, pack):
    """:func:`_finish` of a chunk's many queries at once: row ``j`` of
    every query is a strided read of the scratch."""
    queries, d = o_ref.shape[-2:]
    at = [pl.ds(j, queries, stride=pack) for j in range(pack)]
    m = [m_scr[:, i, :][:, :, :1] for i in at]       # (heads, queries, 1)
    top = functools.reduce(jnp.maximum, m)
    w = [jnp.exp(mj - top) for mj in m]
    den = sum(wj * l_scr[:, i, :][:, :, :1] for wj, i in zip(w, at))
    num = sum(wj * acc_scr[:, i, :][:, :, j * d:(j + 1) * d]
              for j, (wj, i) in enumerate(zip(w, at)))
    o_ref[...] = (num / jnp.where(den == 0.0, 1.0, den)).astype(o_ref.dtype)


def _live_rows(len_ref, slot, ki, block_k, pack, tile):
    """Slab rows a copy moves of key block ``ki`` of ``slot``: the block's
    rows below the sequence's length, rounded up to a sublane tile
    (``tile``; ``None``: the block whole)."""
    if not tile:
        return jnp.int32(block_k)
    left = (len_ref[slot] - 1) // pack + 1 - ki * block_k
    return pl.multiple_of(
        jnp.minimum(block_k, (left + tile - 1) // tile * tile), tile)


def _fetch(hi, slot, ki, place, rows, slabs, bufs, sems):
    """The copies, one a slab, of the first ``rows`` rows of key block
    ``ki`` of ``slot`` (head group ``hi``) into ``place`` of the ring."""
    hb, block_k = bufs[0].shape[1:3]
    return [pltpu.make_async_copy(
        slab.at[slot, pl.ds(hi * hb, hb), pl.ds(ki * block_k, rows), :],
        buf.at[place, :, pl.ds(0, rows), :], sems.at[place, i])
        for i, (slab, buf) in enumerate(zip(slabs, bufs))]


def _zero_past(buf, place, rows, sub):
    """Zeros over the rows of the last sub-block past the ``rows`` copied:
    they hold what an earlier block left there, or nothing yet, and a
    weight of zero times a NaN is a NaN."""
    @pl.when(rows % sub != 0)
    def _():
        j = pl.multiple_of(rows // sub * sub, sub)
        blk = buf[place, :, pl.ds(j, sub), :]
        row = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
        buf[place, :, pl.ds(j, sub), :] = jnp.where(
            row < rows - j, blk, jnp.zeros_like(blk))


def _products(q, bufs, place, rows, first, length, scr, pack, v_lanes, sub,
              chunk=None):
    """The ``rows`` copied rows at ``place`` of the ring (slab rows
    ``first ...``) into the running softmax: ONE product over as
    many sub-blocks of ``sub`` rows as hold a copied row — a product a
    sub-block would pay the softmax's chain of dependent steps each time
    (0.4 us, PERF.md §6 PR 41) — so one branch a count, every operand's
    size static."""
    kbuf, vbuf = bufs[0], bufs[-1]
    _zero_past(vbuf, place, rows, sub)
    taken = (rows + sub - 1) // sub
    for n in range(1, kbuf.shape[2] // sub + 1):
        @pl.when(taken == n)
        def _(n=n):
            k = kbuf[place, :, :n * sub, :]
            v = (k[:, :, :v_lanes] if v_lanes
                 else vbuf[place, :, :n * sub, :])
            _block(q, k, v, first, length, *scr, pack, chunk)


def _kernel(len_ref, slot_ref, blk_ref, *rest, pack, v_lanes, tile, sub,
            per):
    """``rest``: in the chunk form (``per``: the score rows a chunk
    position, None for one token's) a fourth scalar, the
    keys the FIRST position of each slot's chunk sees (``len_ref`` is then
    where the rows the step wrote end: the schedule's and the copies'
    length); the score rows; the slabs where they lie (K, V — or, in the
    latent mode (``v_lanes``), ONE: the value is the first ``v_lanes``
    lanes of the key block already in VMEM), the output, then the scratch:
    a ring of block buffers a slab, their semaphores, and the running
    softmax."""
    start_ref = rest[0] if per else None
    q_ref, *rest = rest[bool(per):]
    n = 1 if v_lanes else 2
    slabs, o_ref, bufs = rest[:n], rest[n], rest[n + 1:2 * n + 1]
    sems, *scr = rest[2 * n + 1:]
    hi, t = pl.program_id(0), pl.program_id(1)
    steps = pl.num_programs(1)
    total = pl.num_programs(0) * steps
    at = hi * steps + t
    depth, _, block_k = bufs[0].shape[:3]

    def fetch(at):
        """The copies of schedule entry ``at`` into its place in the ring,
        and the rows they move."""
        hi, t = jax.lax.div(at, steps), jax.lax.rem(at, steps)
        rows = _live_rows(len_ref, slot_ref[t], blk_ref[t], block_k, pack,
                          tile)
        return _fetch(hi, slot_ref[t], blk_ref[t], jax.lax.rem(at, depth),
                      rows, slabs, bufs, sems), rows

    def start(at):
        @pl.when(at < total)
        def _():
            for c in fetch(at)[0]:
                c.start()

    # the blocks of the NEXT entries are on their way before this one's
    # products: across a slot's end too, and a head group's
    @pl.when(at == 0)
    def _():
        for ahead in range(depth - 1):
            start(at + ahead)
    start(at + depth - 1)

    ki = blk_ref[t]
    length = len_ref[slot_ref[t]]
    pl.when(ki == 0)(lambda: _init(*scr))
    mine, rows = fetch(at)
    for c in mine:
        c.wait()
    _products(q_ref[...], bufs, jax.lax.rem(at, depth), rows, ki * block_k,
              length, scr, pack, v_lanes, sub,
              (start_ref[slot_ref[t]], per) if per else None)
    # the slot's last live block
    pl.when(ki == (length - 1) // (block_k * pack))(
        lambda: (_finish_chunk if per else _finish)(o_ref, *scr, pack))


def _schedule(lengths, keys_per_block, num_kv):
    """The live (slot, key block) pairs in slot order, padded to ``B *
    num_kv`` entries, and how many there are."""
    b = lengths.shape[0]
    live = (lengths - 1) // keys_per_block + 1                     # (B,)
    ends = jnp.cumsum(live)
    steps = jnp.arange(b * num_kv, dtype=jnp.int32)
    slot = jnp.minimum(
        jnp.searchsorted(ends, steps, side="right").astype(jnp.int32), b - 1)
    block = jnp.minimum(steps - (ends - live)[slot], live[slot] - 1)
    return slot, block, ends[-1]


def decode_attention(rows, k_slab, v_slab, lengths, pack=1,
                     interpret=False, v_lanes=None, chunk=1, count=None):
    """Attention of a few query rows per (sequence, KV head) over KV slabs.

    ``rows``: (B, H, n, lanes) score rows, scaled, in the slabs' dtype —
    ``n / pack`` queries of ``pack`` rows each; row ``i`` scores, at slab
    row ``m``, key ``m * pack + i % pack``.  ``k_slab`` / ``v_slab``: (B,
    H, L/r, lanes) with ``r = pack`` key rows a slab row.  ``lengths``:
    (B,) int — keys at positions ``>= lengths[b]`` are invisible; clamped
    to ``[1, L]``.  Returns (B, H, n / pack, lanes / pack) float32: per
    query the softmax over its ``pack`` rows together, lanes ``[j*D,
    (j+1)*D)`` of row ``j`` of ``P @ V`` summed over ``j``.
    ``interpret=True`` runs the TPU's Pallas interpreter with VMEM that
    reads NaN until written (the CPU tests exercise the same body; a
    ``pltpu.InterpretParams`` is passed through).

    **The chunk form** (``chunk = C > 1``; ``dispatch_sdpa_prefill``): the
    rows are those of ``C`` consecutive positions, ``n / C`` to a position
    and a position's together; ``lengths`` is what the FIRST sees, and row
    ``i`` sees the keys below ``lengths[b] + i // (n / C)``.  ``count``:
    (B,) int, the positions of the chunk a sequence really took (its
    append wrote), clamped to ``[1, C]``, ``C`` where None: the schedule
    and the last block's copy end at ``lengths[b] + count[b] - 1`` and no
    row sees a key at or past it, so a row past ``count`` — a don't-care
    of the caller's — reads what the last real one does, a finite number.
    The same kernel over the same schedule; the trace knows the call as
    ``flash_fwd_qc``, ``decode_attn_calls`` by ``:c<C>`` behind its
    geometry.

    **The latent mode** (``v_slab=None``, ``v_lanes``, ``pack == 1``;
    ``ops/mla.py``): a cache row is a key whose first ``v_lanes`` lanes are
    also its value, so the one slab is fetched once — a second operand of
    the same slab would read it twice — and the result is (B, H, n,
    ``v_lanes``).  The trace knows that call as ``mla_fwd_q1``."""
    b, h, n, lanes = rows.shape
    latent = v_slab is None
    if latent and (pack != 1 or not v_lanes):
        raise ValueError("the latent mode reads plain rows (pack 1) and "
                         "needs v_lanes")
    if chunk < 1 or n % (chunk * pack):
        raise ValueError(f"{n} score rows are no {chunk} positions of "
                         f"queries of {pack} rows")
    if lanes % 128:
        # plain rows narrower than a lane row (no caller stores them so:
        # ``kv_slab_shape`` packs them) lie padded in HBM, where a copy
        # cannot cut them: widened with zeros, which score and weigh nothing
        if pack != 1:
            raise ValueError("packed rows fill whole lane rows")
        wide = (lambda x: x if x is None else jnp.pad(
            x, ((0, 0),) * 3 + ((0, -lanes % 128),)))
        out = decode_attention(wide(rows), wide(k_slab), wide(v_slab),
                               lengths, 1, interpret, v_lanes, chunk, count)
        return out if latent else out[..., :lanes]
    slabs = (k_slab,) if latent else (k_slab, v_slab)
    itemsize = k_slab.dtype.itemsize
    hb, block_k = geometry(h, k_slab.shape[2], lanes, itemsize, len(slabs),
                           n)
    from ...metrics import record_decode_attn_call
    record_decode_attn_call(hb, block_k, chunk)
    count = (None if chunk == 1
             else jnp.full((b,), chunk, jnp.int32) if count is None
             else jnp.asarray(count, jnp.int32))
    return _call(jnp.asarray(lengths, jnp.int32), count, rows, *slabs, hb=hb,
                 block_k=block_k, tail=_tail(block_k, itemsize), depth=DEPTH,
                 pack=pack, v_lanes=int(v_lanes) if latent else None,
                 chunk=int(chunk), interpret=interpret)


# jitted so that the calls of one program that share a shape — every
# layer's, every reader's of a shared slab: 8 to 24 in a one-token program
# — are traced and lowered to ONE kernel: lowered one by one, its branches
# added 4.5 s to every start of the glm cell (PERF.md section 6, PR 41)
@functools.partial(jax.jit, static_argnames=(
    "hb", "block_k", "tail", "depth", "pack", "v_lanes", "chunk",
    "interpret"))
def _call(lengths, count, rows, *slabs, hb, block_k, tail, depth, pack,
          v_lanes, chunk, interpret):
    b, h, n, lanes = rows.shape
    slab_rows = slabs[0].shape[2]
    out_lanes = v_lanes or lanes
    lengths = jnp.clip(lengths, 1, slab_rows * pack)
    scalars = ()
    if chunk > 1:
        # the schedule and the copies run to where the rows the step wrote
        # end; what the chunk's first position sees rides behind them
        scalars = (lengths,)
        lengths = jnp.minimum(lengths + jnp.clip(count, 1, chunk) - 1,
                              slab_rows * pack)
    slot, block, steps = _schedule(lengths, block_k * pack,
                                   slab_rows // block_k)
    tile = 32 // rows.dtype.itemsize             # a whole sublane tile
    padded = -(-n // tile) * tile
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, padded - n), (0, 0)))

    def at_slot(hi, t, len_ref, slot_ref, blk_ref, *_):
        return slot_ref[t], hi, 0, 0

    return pl.pallas_call(
        functools.partial(_kernel, pack=pack, tile=tail[0], sub=tail[1],
                          v_lanes=v_lanes,
                          per=n // chunk if chunk > 1 else None),
        # the one-token call keeps the name the device trace knows it by
        name=("mla_fwd_q" if v_lanes else "flash_fwd_q")
        + ("c" if chunk > 1 else "1"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(scalars),
            # the second bound is the traced count of live blocks
            grid=(h // hb, steps),
            in_specs=[pl.BlockSpec((None, hb, padded, lanes), at_slot)]
            # the slabs stay where they lie: the kernel copies its blocks
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(slabs),
            out_specs=pl.BlockSpec((None, hb, n // pack, out_lanes // pack),
                                   at_slot),
            scratch_shapes=[pltpu.VMEM((depth, hb, block_k, lanes),
                                       slabs[0].dtype) for _ in slabs]
            + [pltpu.SemaphoreType.DMA((depth, len(slabs))),
               pltpu.VMEM((hb, padded, 128), jnp.float32),    # running max
               pltpu.VMEM((hb, padded, 128), jnp.float32),    # running sum
               pltpu.VMEM((hb, padded, out_lanes), jnp.float32)]),  # P @ V
        out_shape=jax.ShapeDtypeStruct(
            (b, h, n // pack, out_lanes // pack), jnp.float32),
        # a copy of a traced number of rows needs the TPU's own
        # interpreter; it hands out VMEM that reads NaN until written
        interpret=(pltpu.InterpretParams(uninitialized_memory="nan")
                   if interpret is True else interpret),
    )(lengths, slot, block, *scalars, rows, *slabs)


# ------------------------------------------------------ selected blocks
# The fifth caller (``ops/sparse_attention.py``): a sequence reads only the
# key blocks an indexer chose for it, per key head.  The schedule is the
# chosen block ids themselves, sorted, per (slot, key head), scalar-
# prefetched with their count; the grid walks (slot, key head, group of
# ``SEL_BLOCKS`` chosen blocks) and a program copies its group's blocks from
# the slabs in HBM into ONE buffer a slab, side by side, and multiplies them
# in one product — a product costs the softmax's chain of dependent steps
# whatever it multiplies.  The copies of the NEXT program's group are started
# before this one's products, across a head's and a slot's end.

#: chosen key blocks a program copies and multiplies at once
SEL_BLOCKS = 16


def _sel_kernel(len_ref, cnt_ref, blk_ref, q_ref, k_hbm, v_hbm, o_ref,
                kbuf, vbuf, sems, *scr, rows, per, width):
    """One (slot, key head, group) program.  ``blk_ref``: the chosen block
    ids, ``width`` a (slot, key head), the first ``cnt_ref`` of them real;
    ``q_ref`` / ``o_ref``: (1, score rows, lanes); ``kbuf`` / ``vbuf``: (2,
    1, per * rows, lanes), this program's group and the next one's."""
    heads, groups = pl.num_programs(1), pl.num_programs(2)
    at = (pl.program_id(0) * heads + pl.program_id(1)) * groups \
        + pl.program_id(2)
    total = pl.num_programs(0) * heads * groups

    def entry(at):
        """``(slot, key head, group, its (slot, head)'s count)``."""
        pair, group = jax.lax.div(at, groups), jax.lax.rem(at, groups)
        return (jax.lax.div(pair, heads), jax.lax.rem(pair, heads), group,
                cnt_ref[pair])

    def block_of(at, i):
        """The ``i``-th block id of entry ``at``: past the count the last
        real one again, which the mask drops — every row of the buffer is
        copied, none reads what an earlier program left."""
        pair, group = jax.lax.div(at, groups), jax.lax.rem(at, groups)
        return blk_ref[pair * width + jnp.minimum(group * per + i,
                                                  cnt_ref[pair] - 1)]

    def copies(at):
        slot, head, _, _ = entry(at)
        place = jax.lax.rem(at, 2)
        return [pltpu.make_async_copy(
            hbm.at[slot, pl.ds(head, 1),
                   pl.ds(pl.multiple_of(block_of(at, i) * rows, rows), rows),
                   :],
            buf.at[place, :, pl.ds(i * rows, rows), :], sems.at[place, j])
            for i in range(per)
            for j, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))]

    def live(at):
        _, _, group, count = entry(at)
        return group * per < count

    def start(at):
        @pl.when(jnp.logical_and(at < total, live(jnp.minimum(at,
                                                              total - 1))))
        def _():
            for c in copies(at):
                c.start()

    pl.when(at == 0)(lambda: start(at))
    start(at + 1)

    slot, _, group, count = entry(at)
    pl.when(group == 0)(lambda: _init(*scr))

    @pl.when(live(at))
    def _():
        for c in copies(at):
            c.wait()
        place = jax.lax.rem(at, 2)
        k, v = kbuf[place], vbuf[place]
        s = jax.lax.dot_general(
            q_ref[...], k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)      # (1, score rows, n)
        col = jax.lax.broadcasted_iota(jnp.int32, (1, 1, per * rows), 2)
        which = col // rows
        first = jnp.zeros_like(col)
        for i in range(per):
            first = jnp.where(which == i, block_of(at, i) * rows, first)
        seen = jnp.logical_and(first + col % rows < len_ref[slot],
                               group * per + which < count)
        _fold(s, seen, v, *scr)

    pl.when(group == (count - 1) // per)(lambda: _finish(o_ref, *scr, 1))


def decode_attention_blocks(rows, k_slab, v_slab, lengths, blocks, counts,
                            block_rows=64, interpret=False):
    """Attention of a few query rows per (sequence, KV head) over CHOSEN
    key blocks of plain-row KV slabs.

    ``rows``: (B, H, n, lanes) score rows, scaled, in the slabs' dtype;
    ``k_slab`` / ``v_slab``: (B, H, L, lanes); ``lengths``: (B,) — keys at
    positions ``>= lengths[b]`` are invisible; ``blocks``: (B, H, W) int —
    the ids of the ``block_rows``-row key blocks head ``h`` of sequence
    ``b`` reads, the first ``counts[b, h]`` (>= 1) of them real, distinct
    and sorted (what is past the count is not read); ``W`` a multiple of
    ``SEL_BLOCKS``.  Returns (B, H, n, lanes) float32, the softmax over the
    keys of the chosen blocks below the length.  The trace knows the call as
    ``sparse_fwd_q1``."""
    b, h, n, lanes = rows.shape
    width = blocks.shape[-1]
    if lanes % 128 or k_slab.shape[2] % block_rows or width % SEL_BLOCKS:
        raise ValueError(
            f"the selected-block read takes whole lane rows, a slab of whole "
            f"blocks and a schedule of whole groups; got lanes {lanes}, "
            f"{k_slab.shape[2]} rows of blocks of {block_rows}, width "
            f"{width} (groups of {SEL_BLOCKS})")
    from ...metrics import record_decode_attn_call
    record_decode_attn_call(1, f"{SEL_BLOCKS}x{block_rows}")
    return _sel_call(jnp.asarray(lengths, jnp.int32),
                     jnp.asarray(counts, jnp.int32).reshape(-1),
                     jnp.asarray(blocks, jnp.int32).reshape(-1), rows,
                     k_slab, v_slab, block_rows=int(block_rows),
                     interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def _sel_call(lengths, counts, blocks, rows, k_slab, v_slab, block_rows,
              interpret):
    b, h, n, lanes = rows.shape
    width = blocks.shape[0] // (b * h)
    lengths = jnp.clip(lengths, 1, k_slab.shape[2])
    counts = jnp.clip(counts, 1, width)
    tile = 32 // rows.dtype.itemsize             # a whole sublane tile
    padded = -(-n // tile) * tile
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, padded - n), (0, 0)))

    def at_head(bi, hi, gi, *_):
        return bi, hi, 0, 0

    return pl.pallas_call(
        functools.partial(_sel_kernel, rows=block_rows, per=SEL_BLOCKS,
                          width=width),
        name="sparse_fwd_q1",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, h, width // SEL_BLOCKS),
            in_specs=[pl.BlockSpec((None, 1, padded, lanes), at_head),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, 1, n, lanes), at_head),
            scratch_shapes=[
                pltpu.VMEM((2, 1, SEL_BLOCKS * block_rows, lanes),
                           k_slab.dtype),
                pltpu.VMEM((2, 1, SEL_BLOCKS * block_rows, lanes),
                           v_slab.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((1, padded, 128), jnp.float32),    # running max
                pltpu.VMEM((1, padded, 128), jnp.float32),    # running sum
                pltpu.VMEM((1, padded, lanes), jnp.float32)]),  # P @ V
        out_shape=jax.ShapeDtypeStruct((b, h, n, lanes), jnp.float32),
        interpret=(pltpu.InterpretParams(uninitialized_memory="nan")
                   if interpret is True else interpret),
    )(lengths, counts, blocks, rows, k_slab, v_slab)
