"""Flash attention — Pallas TPU kernel (forward + backward).

The reference has no attention kernel at all (SURVEY.md §5.7): its
transformers compose batch_matmul + softmax ops, materialising the (S, S)
score matrix in HBM (and its BERT composes attention with explicit additive
masks — ``examples/transformers/bert/hetu_bert.py``).  This kernel is the
TPU-native replacement: blockwise online-softmax attention that keeps scores
in VMEM, with a custom VJP whose backward recomputes scores per block
(flash-attention-2 style), so memory is O(S·D) instead of O(S²).

Layout: TWO entries, one set of kernels.  Head-major inputs (B, H, S, D)
run as (B·H, S, D), one head a program.  PACKED inputs (B, S, H·D) — what a
projection ``x @ W`` leaves after a free reshape, ``flash_attention(...,
heads=H)`` — are read and written as they are: a BlockSpec selects a
lane-aligned COLUMN BLOCK of heads out of the last axis
(:func:`packed_width`: 128 lanes = two heads at d = 64, four at d = 32; the
head itself where d is a multiple of 128), so no transposed or 64-lane-padded
copy of q, k, v, the output or a gradient exists in HBM (at BERT's shape the
head-major layout cost 15 whole-tensor ``copy`` operations a layer, 14.4 ms of
a 107.75 ms step: PERF.md §6, PR 44).  A program takes its heads one after
another: a head's scores contract a block of q in which every other head's
lanes are zeroed against the whole block of k (exact: it adds zeros; on a
128 × 128 MXU a contraction over 64 lanes already costs a full pass), and a
product with the whole block of v / k / q / dO is right in that head's output
lanes, which a lane select keeps — the same MXU passes as head-major, no lane
shuffle.  The head-major entry IS the packed kernel with one head a row and
the block the whole last axis; where the block shapes are equal the two
agree to the last bit.  The grid is sequential, (program, q_block,
kv_block), program = (batch row, column block) — accumulators live in VMEM
scratch and persist across the minor-most kv grid steps; outputs are written
once on the final kv step (standard TPU revisiting-grid pattern).

Block shapes come from ONE rule, :func:`_pick_blocks`, that reads only what
the call can observe (both lengths, the width of a row block — the head
size, or a packed call's column block — the operand item size, ``causal``
and how many dense (S_q, S_kv) extras ride along) against a
stated VMEM budget; explicit ``block_q=`` / ``block_k=`` still win.  No
table, no environment variable, no model name chooses a tile (a grid step
costs ≈0.3 µs on a v5e, as much as a 128 × 128 tile's work: PERF.md §6,
PR 28).  When one block holds the whole key range (``num_kv == 1``: BERT's
512 keys) the kernels specialise, statically like the mask menu below:

* the forward is a straight softmax — no running max / sum scratch, no
  ``alpha`` rescale of the accumulator, ``lse`` written directly;
* the backward is ONE kernel (``flash_bwd``): K and V of a (b, h) stay
  resident over the query blocks, scores / probabilities / dP / dS are
  computed once and feed dq, dk and dv — 5 products where the two-pass
  kernels (``flash_bwd_dq`` + ``flash_bwd_dkv``, kept for key ranges that
  do not fit one block) spend 7; ``delta`` is computed in the kernel.

Row statistics (``lse``, ``delta``) cross HBM lane-oriented, (B·H, 1, S) in
both layouts (a packed program writes its heads' rows side by side): a
(B·H, S, 1) f32 array is stored padded to 128 lanes (100 MB a layer where
0.8 MB is data at BERT's shape: compile rehearsal, PR 28); the kernels turn
a column of statistics into a row and back in VMEM (:func:`_col_to_row`).

Masking/bias menu (every combination is a STATIC trace-time specialization,
so the dense hot path compiles the original straight-line code):

* ``causal``        — diagonal blocks masked, above-diagonal blocks pruned
                      via ``pl.when`` (no FLOPs);
* ``lengths``       — per-sequence valid-KEY counts (padding), SMEM scalar,
                      fully-padded key blocks pruned;
* ``key_mask``      — arbitrary per-key boolean mask (B, S_kv), loaded as
                      (1, block_k) column strips — O(S) memory, the BERT
                      padded-pretraining path;
* ``mask``          — full boolean mask broadcast as (1|B, 1|H, S_q, S_kv)
                      (XLNet two-stream perms), loaded blockwise without
                      materialising the broadcast;
* ``bias``          — additive logit bias broadcast likewise (T5 relative
                      position bias), differentiable: backward emits per-
                      block dbias tiles (dbias is inherently O(S²) — same
                      footprint as the bias itself).  A (·, ·, 1, S_kv)
                      row-broadcast bias is auto-routed to a per-key strip
                      path: O(S) loads forward, O(S) column-sum gradient
                      backward — never materialised to (S_q, S_kv).

Fully-masked rows/blocks produce ZERO output (not a uniform-softmax leak):
probabilities are multiplied by the block validity mask, so an all-masked
block contributes nothing even though exp(s - m) == 1 there.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
#: flash-legal sequence lengths are multiples of this (the Mosaic lane
#: width); ragged lengths are padded UP to the next bucket (128/256/384/…)
FLASH_BUCKET = 128


def flash_bucket(s):
    """Smallest flash-legal (bucketed) length >= ``s``."""
    return -(-int(s) // FLASH_BUCKET) * FLASH_BUCKET


def _pad_seq(x, axis, pad, value=0.0):
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# ------------------------------------------------------------ block shapes
#: what one program's blocks and score-shaped temporaries may take of
#: VMEM by :func:`_block_bytes`' count — half of Mosaic's default scoped
#: limit of 16 MiB.  The count is an upper bound: a 512 × 512 one-pass
#: backward, 7.3 MiB by it, compiles under a 4 MiB limit (compile
#: rehearsal, PR 28)
_VMEM_BUDGET = 8 * 2 ** 20
#: f32 (block_q, block_k) values a backward program holds at once: s, p,
#: dp, t, ds and the bf16 copies of p and ds that feed the MXU
_SCORE_TEMPS = 6
#: the Mosaic lane width: a packed call's column block of heads
_LANES = 128


def packed_width(d):
    """Lanes of one column block of a packed (B, S, H·D) operand: 128
    where the head size divides them (``128 // d`` heads a block), the head
    itself where it is a multiple of them; ``None`` for any other head
    size (a block would cut a head in two or leave the lane tiling)."""
    if _LANES % d == 0:
        return _LANES
    return d if d % _LANES == 0 else None


def _block_bytes(block_q, block_k, width, itemsize, dense_tiles, num_q=2,
                 num_kv=2):
    """VMEM one program takes at these blocks, counted from shapes: the
    score-shaped f32 temporaries (one head's at a time), each dense extra
    (bias, full mask, the ``dbias`` output: 4-byte (block_q, block_k)
    tiles, double-buffered), the row and key blocks of ``width`` lanes —
    the head size, or ``heads_per_block · d`` of a packed call — (q, o,
    do, dq over block_q; k, v, dk, dv over block_k; double-buffered) and
    the f32 accumulators of the sums that cross grid steps: dq and the
    forward's output over ``num_kv`` key blocks, dk and dv over ``num_q``
    query blocks (the dkv kernel keeps its two whenever there are key
    blocks).  A program that holds both ranges whole sums across nothing:
    BERT's 512 × 512 in the packed layout counts 8 MiB to the byte, and
    takes 1.317 ms a layer forward + backward where 256 × 512 takes 1.481
    (chip run, PR 44, PERF.md §6)."""
    tiles = block_q * block_k * 4 * (_SCORE_TEMPS + 2 * dense_tiles)
    rows = 2 * itemsize * width * 4 * (block_q + block_k)
    sums = block_q * (num_kv > 1) + 2 * block_k * (num_q > 1 or num_kv > 1)
    return tiles + rows + 4 * width * sums


def _pick_blocks(s_q, s_kv, width, itemsize, causal=False, dense_tiles=0):
    """(block_q, block_k) from what the call can observe — the ONE place
    a tile is chosen (callers' explicit blocks are honoured before this).

    Candidates are the multiples of 128 that divide each (bucketed)
    length.  A block that holds the WHOLE key range is taken when it fits
    ``_VMEM_BUDGET`` with at least 128 query rows, with the most query
    rows that still fit: the kernels then drop the online-softmax state
    and the backward runs in one pass — under ``causal`` too, where it
    computes the masked half it could have pruned and still wins (at
    s = 1024 one pass over whole rows takes 3.4 ms where pruned 512 × 512
    tiles take 4.5: PERF.md §6, PR 28).  Otherwise the largest tile that
    fits, the squarest among equals (a two-pass backward re-fetches K/V
    once per query block and q/dO once per key block); under ``causal``
    such a tile spans at most half of each length, so blocks above the
    diagonal exist to be pruned.  Dense extras shrink the tile: each
    costs ``block_q × block_k × 4 B``, double-buffered."""
    fits = [(bq, bk)
            for bq in range(128, s_q + 1, 128) if s_q % bq == 0
            for bk in range(128, s_kv + 1, 128) if s_kv % bk == 0
            and _block_bytes(bq, bk, width, itemsize, dense_tiles,
                             s_q // bq, s_kv // bk) <= _VMEM_BUDGET]
    whole = [c for c in fits if c[1] == s_kv]
    if whole:
        return max(whole)
    if causal:
        fits = [(bq, bk) for bq, bk in fits
                if bq <= max(128, s_q // 2) and bk <= max(128, s_kv // 2)]
    return max(fits, key=lambda c: (c[0] * c[1], -abs(c[0] - c[1]), c[1]),
               default=(128, 128))


def _col_to_row(x):
    """(n, 1) f32 column of row statistics → (1, n) lane-oriented row: a
    lane broadcast and one aligned (n, 128) transpose in VMEM."""
    return jnp.broadcast_to(x, (x.shape[0], 128)).T[:1]


def _row_to_col(x):
    """(1, n) lane-oriented row → (n, 1) column (sublane broadcast, one
    aligned transpose)."""
    return jnp.broadcast_to(x, (128, x.shape[1])).T[:, :1]


# ------------------------------------------------- the heads of one block
def _only(x, j, d):
    """A (rows, width) block with every lane outside head ``j``'s
    ``[j·d, (j+1)·d)`` zeroed, so a contraction over the whole block is
    head ``j``'s alone (it adds exact zeros).  ``x`` itself when the block
    is one head."""
    if x.shape[-1] == d:
        return x
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    return jnp.where(jnp.logical_and(lane >= j * d, lane < (j + 1) * d), x,
                     jnp.zeros_like(x))


def _merge(parts, d, shape):
    """One ``shape`` = (rows, width) block from a value per head of it:
    head ``j``'s lanes from ``parts[j]``, each a full-width product that
    is right in those lanes, or a (rows, 1) column of head ``j``'s
    statistics.  ``parts[0]`` itself when the block is one head."""
    out = parts[0]
    for j, part in enumerate(parts[1:], 1):
        lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        out = jnp.where(lane >= j * d, part, out)
    return out


def _operand_spec(rows, width, cols, at):
    """BlockSpec of a (1, rows, width) block of a (·, S, cols · width)
    operand.  ``at(*grid)`` → (program, row block); program ``n`` reads
    column block ``n % cols`` of batch row ``n // cols`` (head-major: one
    column block, the whole last axis)."""
    def index(*g):
        n, r = at(*g)
        return (n, r, 0) if cols == 1 else (n // cols, r, n % cols)
    return pl.BlockSpec((1, rows, width), index)


# ------------------------------------------------------------- index maps
def _g_index(gmode, heads):
    """Map the flattened (b·h) grid index to a broadcast-group row for a
    mask/bias stored un-broadcast as (G, S_q, S_kv):
    'one' G=1, 'h' G=H (shared over batch), 'b' G=B (shared over heads),
    'bh' G=B·H (full)."""
    return {
        "one": lambda bh: 0,
        "h": lambda bh: bh % heads,
        "b": lambda bh: bh // heads,
        "bh": lambda bh: bh,
    }[gmode]


def _extra_specs(order, heads, gmode_mask, gmode_bias, gmode_kbias, block_q,
                 block_k, *, has_lengths, has_kmask, has_kbias, has_fmask,
                 has_bias):
    """BlockSpecs for the optional inputs, in kernel-argument order.
    ``order`` maps grid indices to (program, qi, ki) — the dkv kernel
    iterates (program, ki, qi).  ``heads``: programs per batch row — the
    heads, which is what a program is wherever a dense extra rides along
    (one head a block), or a packed call's column blocks."""
    specs = []
    if has_lengths:
        # stored (bh, 1, 1): block (1, 1, 1) keeps the last two dims equal
        # to the array's (the rank-2 (1, 1) block violated Mosaic tiling)
        specs.append(pl.BlockSpec(
            (1, 1, 1), lambda *g: (order(*g)[0], 0, 0),
            memory_space=pltpu.SMEM))
    if has_kmask:
        # stored (B, 1, S_kv): the unit middle dim keeps the block's last
        # two dims (1, block_k) legal under Mosaic's tiling rule (a
        # (1, block_k) block over a rank-2 (B, S_kv) array is NOT — the
        # sublane dim must divide 8 or equal the array dim)
        specs.append(pl.BlockSpec(
            (1, 1, block_k),
            lambda *g: (order(*g)[0] // heads, 0, order(*g)[2])))
    if has_kbias:
        # per-KEY additive bias, stored un-broadcast as (G, 1, S_kv) and
        # loaded as O(block_k) column strips — a (·, ·, 1, S_kv) bias never
        # materialises its (S_q, S_kv) broadcast (round-3 advisor finding)
        gkb = _g_index(gmode_kbias, heads)
        specs.append(pl.BlockSpec(
            (1, 1, block_k),
            lambda *g: (gkb(order(*g)[0]), 0, order(*g)[2])))
    if has_fmask:
        gm = _g_index(gmode_mask, heads)
        specs.append(pl.BlockSpec(
            (1, block_q, block_k),
            lambda *g: (gm(order(*g)[0]), order(*g)[1], order(*g)[2])))
    if has_bias:
        gb = _g_index(gmode_bias, heads)
        specs.append(pl.BlockSpec(
            (1, block_q, block_k),
            lambda *g: (gb(order(*g)[0]), order(*g)[1], order(*g)[2])))
    return specs


# ---------------------------------------------------------------- masking
def _block_logits(qi, ki, q, k, *, len_ref, kmask_ref, kbias_ref, fmask_ref,
                  bias_ref, scale, causal, block_q, block_k, kv_off):
    """Masked+biased logits for one (qi, ki) block → (s, valid).

    ``valid`` is None on the pure-dense path (no masking of any kind) so
    the hot path keeps the original straight-line code; otherwise it is the
    boolean validity of every score — callers MUST multiply probabilities
    by it (exp(s - m) == 1 on an all-masked block, which would otherwise
    leak a uniform average of the value vectors)."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale      # (bq, bk)
    if bias_ref is not None:
        s = s + bias_ref[0].astype(jnp.float32)
    if kbias_ref is not None:
        # (1, 1, block_k) strip broadcasts over the query rows
        s = s + kbias_ref[0].astype(jnp.float32)
    valid = None

    def _and(a, b):
        return b if a is None else jnp.logical_and(a, b)

    if len_ref is not None:
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
            + ki * block_k
        valid = _and(valid, cols < len_ref[0, 0, 0])
    if kmask_ref is not None:
        # (1, 1, block_k) block → (1, block_k) load broadcasts over rows
        valid = _and(valid, kmask_ref[0] != 0)
    if fmask_ref is not None:
        valid = _and(valid, fmask_ref[0] != 0)
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
            + qi * block_q + kv_off
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) \
            + ki * block_k
        valid = _and(valid, rows >= cols)
    if valid is not None:
        s = jnp.where(valid, s, NEG_INF)
    return s, valid


def _live(qi, ki, len_ref, *, causal, block_q, block_k, kv_off):
    """Block-prune predicate: blocks entirely above the causal diagonal or
    entirely past the valid-key count are skipped (no FLOPs).  key_mask /
    full-mask blocks are never pruned (their validity is vector data)."""
    live = (qi * block_q + block_q - 1 + kv_off >= ki * block_k) \
        if causal else True
    if len_ref is not None:
        cond = ki * block_k < len_ref[0, 0, 0]
        live = cond if live is True else jnp.logical_and(live, cond)
    return live


def _unpack(refs, *, has_lengths, has_kmask, has_kbias, has_fmask, has_bias):
    """Split the flat pallas ref list into (fixed-ins, extras, outs+scratch).
    Optional inputs are present only when their static flag is set, keeping
    the kernel arity minimal per specialization; ``extras`` names each one
    (``None`` when absent) as :func:`_block_logits` takes them."""
    rest = list(refs[3:])
    extras = {
        name: rest.pop(0) if present else None
        for name, present in (
            ("len_ref", has_lengths), ("kmask_ref", has_kmask),
            ("kbias_ref", has_kbias), ("fmask_ref", has_fmask),
            ("bias_ref", has_bias))}
    return refs[:3], extras, rest


# ---------------------------------------------------------------- forward
def _fwd_kernel(*refs, scale, flags, geom, num_kv, d):
    (q_ref, k_ref, v_ref), extras, rest = _unpack(refs, **flags)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    logits = functools.partial(_block_logits, qi, ki, scale=scale, **extras,
                               **geom)
    shape = q_ref.shape[1:]                            # (bq, width)
    heads = range(shape[1] // d)                       # of this block

    if num_kv == 1:
        # the block IS the key range: a straight softmax.  Same numbers
        # as one step of the loop below (alpha = exp(NEG_INF - m) = 0 on
        # a zero accumulator); a row with no valid key still reads zero
        # (p * valid), so a dead block needs no pruning predicate
        o_ref, lse_ref = rest
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        outs = []
        for j in heads:
            s, valid = logits(_only(q, j, d), k)
            m = jnp.max(s, axis=-1, keepdims=True)
            p = jnp.exp(s - m)
            if valid is not None:
                p = p * valid
            l = jnp.sum(p, axis=-1, keepdims=True)
            l_safe = jnp.where(l == 0.0, 1.0, l)
            acc = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            outs.append(acc / l_safe)
            lse_ref[j] = _col_to_row(m + jnp.log(l_safe))
        o_ref[0] = _merge(outs, d, shape).astype(o_ref.dtype)
        return

    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    live = _live(qi, ki, extras["len_ref"], **geom)

    @pl.when(live)
    def _block():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]                                   # (bk, width)
        alphas, pvs = [], []
        for j in heads:
            s, valid = logits(_only(q, j, d), k)       # (bq, bk)
            m_prev = m_scr[j, :, :1]                   # (bq, 1)
            l_prev = l_scr[j, :, :1]
            m_cur = jnp.max(s, axis=-1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)                      # (bq, bk)
            if valid is not None:
                p = p * valid                           # no all-masked leak
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            alphas.append(alpha)
            pvs.append(jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            m_scr[j] = jnp.broadcast_to(m_new, m_scr.shape[1:])
            l_scr[j] = jnp.broadcast_to(l_new, l_scr.shape[1:])
        acc_scr[:] = acc_scr[:] * _merge(alphas, d, shape) \
            + _merge(pvs, d, shape)

    @pl.when(ki == num_kv - 1)
    def _finish():
        sums = []
        for j in heads:
            l = l_scr[j, :, :1]
            sums.append(jnp.where(l == 0.0, 1.0, l))
            lse_ref[j] = _col_to_row(m_scr[j, :, :1] + jnp.log(sums[j]))
        o_ref[0] = (acc_scr[:] / _merge(sums, d, shape)).astype(o_ref.dtype)


def _flags(lengths, kmask, kbias, fmask, bias):
    return dict(has_lengths=lengths is not None, has_kmask=kmask is not None,
                has_kbias=kbias is not None, has_fmask=fmask is not None,
                has_bias=bias is not None)


def _geometry(q, heads, d):
    """→ ``(d, width, cols, groups, bh)`` of operands (rows, S, lanes):
    the head size (the whole last axis when ``d`` is None: head-major,
    rows = B·H), the lanes of a program's column block, column blocks a
    row, programs per batch row and rows of statistics (one per head)."""
    lanes = q.shape[2]
    d = d or lanes
    width = lanes if lanes == d else packed_width(d)
    return (d, width, lanes // width, heads // (width // d),
            q.shape[0] * (lanes // d))


# The two entries below are jitted so that the calls of one program that
# share a shape and a specialization — every layer of a model — are traced
# and lowered to ONE kernel each: a two-head body traced 24 times put 1.4 s
# on the bert cell's set-up (PERF.md §6, PR 44; ``kv_append._call``, PR 37)
@functools.partial(jax.jit, static_argnums=tuple(range(8, 19)))
def _flash_fwd(q, k, v, lengths, kmask, kbias, fmask, bias, scale, causal,
               gmode_mask, gmode_bias, gmode_kbias, heads, block_q, block_k,
               interpret, name="flash_fwd", d=None):
    """→ ``(out, lse (bh, s_q, 1) f32)``, ``out`` shaped like ``q``:
    (B·H, s_q, d) head-major, or with ``d`` = the head size a packed
    (B, s_q, H·d).  The kernel writes ``lse`` lane-oriented, (bh, 1, s_q);
    the reshape to the column form callers combine with
    (``parallel/ring_flash.py``) moves no data."""
    # ``name=`` on each pallas_call: the device trace calls the kernel's
    # instruction after its place in jax's name stack, so without one the
    # forward reads ``jvp__`` or ``infer`` after whatever traced it and
    # the backward kernels share ``transpose_jvp___`` (ISSUE 25)
    d, width, cols, groups, bh = _geometry(q, heads, d)
    s_q, s_kv = q.shape[1], k.shape[1]
    num_q = s_q // block_q
    num_kv = s_kv // block_k
    grid = (q.shape[0] * cols, num_q, num_kv)
    flags = _flags(lengths, kmask, kbias, fmask, bias)
    inputs = [q, k, v] + [x for x in (lengths, kmask, kbias, fmask, bias)
                          if x is not None]
    qspec = _operand_spec(block_q, width, cols, lambda n, i, j: (n, i))
    kspec = _operand_spec(block_k, width, cols, lambda n, i, j: (n, j))

    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, scale=scale, flags=flags, num_kv=num_kv, d=d,
            geom=dict(causal=causal, block_q=block_q, block_k=block_k,
                      kv_off=s_kv - s_q)),
        name=name,
        grid=grid,
        in_specs=[qspec, kspec, kspec]
        + _extra_specs(lambda n, i, j: (n, i, j), groups, gmode_mask,
                       gmode_bias, gmode_kbias, block_q, block_k, **flags),
        out_specs=[
            qspec,
            pl.BlockSpec((width // d, 1, block_q), lambda n, i, j: (n, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s_q), jnp.float32),
        ],
        scratch_shapes=[] if num_kv == 1 else [
            # running max and sum of each head, the output accumulator
            pltpu.VMEM((width // d, block_q, 128), jnp.float32),
            pltpu.VMEM((width // d, block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, width), jnp.float32),
        ],
        interpret=interpret,
    )(*inputs)
    return out, lse.reshape(bh, s_q, 1)


# ---------------------------------------------------------------- backward
def _grad_logits(logits, q, k, v, do, lse, delta):
    """→ ``(p, t)`` of one block: the probabilities recomputed from the
    saved ``lse`` and ``t = p ⊙ (dO·Vᵀ − delta)``, the gradient of the
    logits before ``scale`` (what ``dbias`` is)."""
    s, valid = logits(q, k)
    p = jnp.exp(s - lse)                                # (bq, bk)
    if valid is not None:
        p = p * valid
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (bq, bk)
    return p, p * (dp - delta)


def _bwd_kernel(*refs, scale, flags, geom, emit_dbias, emit_dkbias, num_q, d):
    """One pass over a program's heads whose whole key range is one block:
    grid (program, num_q, 1), K/V (and the dk/dv output blocks) keep their
    index over ``qi`` — fetched once, written once — and every
    score-shaped value is computed once for dq, dk and dv."""
    (q_ref, k_ref, v_ref), extras, rest = _unpack(refs, **flags)
    o_ref, do_ref, lse_ref, dq_ref, dk_ref, dv_ref = rest[:6]
    rest = rest[6:]
    dbias_ref = rest.pop(0) if emit_dbias else None
    dkb_ref = rest.pop(0) if emit_dkbias else None
    qi = pl.program_id(1)
    q = q_ref[0]                                        # (bq, width)
    k = k_ref[0]                                        # (bk, width)
    v = v_ref[0]
    do = do_ref[0]
    o = o_ref[0].astype(jnp.float32)
    logits = functools.partial(_block_logits, qi, 0, scale=scale, **extras,
                               **geom)
    dqs, dvs, dks = [], [], []
    for j in range(q.shape[1] // d):
        do_j = _only(do, j, d)
        # delta_i = rowsum(dO ⊙ O), in the kernel: it never crosses HBM
        delta = jnp.sum(do_j.astype(jnp.float32) * o,
                        axis=-1, keepdims=True)         # (bq, 1)
        p, t = _grad_logits(logits, _only(q, j, d), k, v, do_j,
                            _row_to_col(lse_ref[j]), delta)
        if emit_dbias:
            dbias_ref[0] = t.astype(dbias_ref.dtype)
        ds = (t * scale).astype(k.dtype)                # (bq, bk)
        # each right in head j's lanes: dQ = dS·K, dV = Pᵀ·dO, dK = dSᵀ·Q
        dqs.append(jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
        dvs.append(jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
        dks.append(jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
    dq_ref[0] = _merge(dqs, d, q.shape).astype(dq_ref.dtype)
    sums = [(dv_ref, _merge(dvs, d, k.shape)),
            (dk_ref, _merge(dks, d, k.shape))]
    if emit_dkbias:
        # d(key-bias)[k] = column sums of t (one head a block)
        sums.append((dkb_ref, jnp.sum(t, axis=0, keepdims=True)))
    if num_q == 1:
        for ref, val in sums:
            ref[0] = val.astype(ref.dtype)
        return
    # several query blocks: sum over them in f32 scratch, write at the last
    for (ref, val), scr in zip(sums, rest):
        @pl.when(qi == 0)
        def _init(scr=scr):
            scr[:] = jnp.zeros_like(scr)

        scr[:] += val

        @pl.when(qi == num_q - 1)
        def _finish(ref=ref, scr=scr):
            ref[0] = scr[:].astype(ref.dtype)


def _dq_kernel(*refs, scale, flags, geom, emit_dbias, num_kv, d):
    """dq of one query block over the key blocks — and ``delta``, each
    head's rowsum(dO ⊙ O), computed at the first key block into an output
    the later steps (and the dkv kernel) read: it crosses HBM once, as
    (heads, 1, rows) statistics, and no XLA reduction over a packed
    operand's 64-lane heads stands beside the kernels."""
    (q_ref, k_ref, v_ref), extras, rest = _unpack(refs, **flags)
    o_ref, do_ref, lse_ref, dq_ref, delta_ref = rest[:5]
    dbias_ref = rest[5] if emit_dbias else None
    dq_scr = rest[-1]
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    heads = range(q_ref.shape[2] // d)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        do = do_ref[0]
        o = o_ref[0].astype(jnp.float32)
        for j in heads:
            delta_ref[j] = _col_to_row(jnp.sum(
                _only(do, j, d).astype(jnp.float32) * o, axis=-1,
                keepdims=True))

    live = _live(qi, ki, extras["len_ref"], **geom)
    live_static = live is True

    def _body(write_dbias):
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        logits = functools.partial(_block_logits, qi, ki, scale=scale,
                                   **extras, **geom)
        dqs = []
        for j in heads:
            _, t = _grad_logits(
                logits, _only(q, j, d), k, v, _only(do, j, d),
                _row_to_col(lse_ref[j]), _row_to_col(delta_ref[j]))
            if write_dbias:
                dbias_ref[0] = t.astype(dbias_ref.dtype)
            ds = t * scale
            dqs.append(jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        dq_scr[:] += _merge(dqs, d, q.shape)

    if live_static:
        _body(emit_dbias)
    else:
        @pl.when(live)
        def _b():
            _body(emit_dbias)
        if emit_dbias:
            # pruned blocks must still define their dbias tile
            @pl.when(jnp.logical_not(live))
            def _z():
                dbias_ref[0] = jnp.zeros_like(dbias_ref[0])

    @pl.when(ki == num_kv - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, flags, geom, emit_dkbias, num_q, d):
    (q_ref, k_ref, v_ref), extras, rest = _unpack(refs, **flags)
    if emit_dkbias:
        (do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dkb_ref,
         dk_scr, dv_scr, dkb_scr) = rest
    else:
        do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr = rest
        dkb_ref = dkb_scr = None
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)
        if emit_dkbias:
            dkb_scr[:] = jnp.zeros_like(dkb_scr)

    live = _live(qi, ki, extras["len_ref"], **geom)

    @pl.when(live)
    def _block():
        q = q_ref[0]                                    # (bq, width)
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        logits = functools.partial(_block_logits, qi, ki, scale=scale,
                                   **extras, **geom)
        dvs, dks = [], []
        for j in range(q.shape[1] // d):
            p, t = _grad_logits(
                logits, _only(q, j, d), k, v, _only(do, j, d),
                _row_to_col(lse_ref[j]), _row_to_col(delta_ref[j]))
            # dV += P^T @ dO
            dvs.append(jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            if emit_dkbias:
                # d(key-bias)[k] = sum over query rows of t — accumulated
                # across this ki column's q blocks (broadcast over the
                # scratch sublanes; row 0 is written out)
                dkb_scr[:] += jnp.broadcast_to(
                    jnp.sum(t, axis=0, keepdims=True), dkb_scr.shape)
            ds = t * scale                               # (bq, bk)
            # dK += dS^T @ Q
            dks.append(jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        dv_scr[:] += _merge(dvs, d, k.shape)
        dk_scr[:] += _merge(dks, d, k.shape)

    @pl.when(qi == num_q - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)
        if emit_dkbias:
            dkb_ref[0] = dkb_scr[:1]


@functools.partial(jax.jit, static_argnums=tuple(range(11, 21)))
def _flash_bwd(q, k, v, lengths, kmask, kbias, fmask, bias, out, lse, do,
               scale, causal, gmode_mask, gmode_bias, gmode_kbias, heads,
               block_q, block_k, interpret, d=None):
    """→ ``(dq, dk, dv, dbias, dkbias)`` from the forward's ``out`` and an
    ``lse`` (bh, s_q, 1) that need not be this call's own (the ring hands
    in its global one); operands and gradients in ``q``'s layout
    (:func:`_flash_fwd`).  One pass when ``block_k`` is the whole key
    range, else the dq and dkv kernels."""
    d, width, cols, groups, bh = _geometry(q, heads, d)
    s_q, s_kv = q.shape[1], k.shape[1]
    num_q = s_q // block_q
    num_kv = s_kv // block_k
    programs = q.shape[0] * cols
    flags = _flags(lengths, kmask, kbias, fmask, bias)
    geom = dict(causal=causal, block_q=block_q, block_k=block_k,
                kv_off=s_kv - s_q)
    emit_dbias = bias is not None
    emit_dkbias = kbias is not None
    extras = [x for x in (lengths, kmask, kbias, fmask, bias)
              if x is not None]
    lse = lse.reshape(bh, 1, s_q)             # lane-oriented, no data moved

    qspec = _operand_spec(block_q, width, cols, lambda n, i, j: (n, i))
    kspec = _operand_spec(block_k, width, cols, lambda n, i, j: (n, j))
    rowspec = pl.BlockSpec((width // d, 1, block_q),
                           lambda n, i, j: (n, 0, i))
    extra_specs = _extra_specs(
        lambda n, i, j: (n, i, j), groups, gmode_mask, gmode_bias,
        gmode_kbias, block_q, block_k, **flags)
    # dbias is dense — O(B·H·S²) like the score matrix; unavoidable, the
    # bias gradient has that shape before broadcast-reduction
    dbias_spec = pl.BlockSpec((1, block_q, block_k),
                              lambda n, i, j: (n, i, j))
    dbias_shape = jax.ShapeDtypeStruct((bh, s_q, s_kv), jnp.float32)
    dq_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
    dkv_shapes = [jax.ShapeDtypeStruct(k.shape, k.dtype),
                  jax.ShapeDtypeStruct(v.shape, v.dtype)]
    # d(key-bias): O(S) per bh, a column strip reduced over the broadcast
    # group by the VJP wrapper
    dkb_shape = jax.ShapeDtypeStruct((bh, 1, s_kv), jnp.float32)

    if num_kv == 1:
        outs = [(qspec, dq_shape), (kspec, dkv_shapes[0]),
                (kspec, dkv_shapes[1])]
        scratch = [pltpu.VMEM((s_kv, width), jnp.float32),
                   pltpu.VMEM((s_kv, width), jnp.float32)]
        if emit_dbias:
            outs.append((dbias_spec, dbias_shape))
        if emit_dkbias:
            outs.append((pl.BlockSpec((1, 1, s_kv),
                                      lambda n, i, j: (n, 0, 0)), dkb_shape))
            scratch.append(pltpu.VMEM((1, s_kv), jnp.float32))
        res = pl.pallas_call(
            functools.partial(_bwd_kernel, scale=scale, flags=flags,
                              geom=geom, emit_dbias=emit_dbias,
                              emit_dkbias=emit_dkbias, num_q=num_q, d=d),
            name="flash_bwd",
            grid=(programs, num_q, 1),
            in_specs=[qspec, kspec, kspec] + extra_specs
            + [qspec, qspec, rowspec],
            out_specs=[spec for spec, _ in outs],
            out_shape=[shape for _, shape in outs],
            scratch_shapes=scratch if num_q > 1 else [],
            interpret=interpret,
        )(q, k, v, *extras, out, do, lse)
        dq, dk, dv = res[:3]
        rest = list(res[3:])
        dbias = rest.pop(0) if emit_dbias else None
        dkbias = rest.pop(0) if emit_dkbias else None
        return dq, dk, dv, dbias, dkbias

    stat_shape = jax.ShapeDtypeStruct((bh, 1, s_q), jnp.float32)
    res = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, flags=flags, geom=geom,
                          emit_dbias=emit_dbias, num_kv=num_kv, d=d),
        name="flash_bwd_dq",
        grid=(programs, num_q, num_kv),
        in_specs=[qspec, kspec, kspec] + extra_specs
        + [qspec, qspec, rowspec],
        out_specs=[qspec, rowspec] + [dbias_spec] * emit_dbias,
        out_shape=[dq_shape, stat_shape] + [dbias_shape] * emit_dbias,
        scratch_shapes=[pltpu.VMEM((block_q, width), jnp.float32)],
        interpret=interpret,
    )(q, k, v, *extras, out, do, lse)
    dq, delta = res[:2]
    dbias = res[2] if emit_dbias else None

    # dkv iterates (program, kv_block, q_block): remap grid→(n, qi, ki)
    order = lambda n, j, i: (n, i, j)                    # noqa: E731
    qspec = _operand_spec(block_q, width, cols, lambda n, j, i: (n, i))
    kspec = _operand_spec(block_k, width, cols, lambda n, j, i: (n, j))
    rowspec = pl.BlockSpec((width // d, 1, block_q),
                           lambda n, j, i: (n, 0, i))
    dkv_outs = [kspec, kspec]
    dkv_scratch = [
        pltpu.VMEM((block_k, width), jnp.float32),
        pltpu.VMEM((block_k, width), jnp.float32),
    ]
    if emit_dkbias:
        dkv_outs.append(pl.BlockSpec((1, 1, block_k),
                                     lambda n, j, i: (n, 0, j)))
        dkv_shapes.append(dkb_shape)
        dkv_scratch.append(pltpu.VMEM((8, block_k), jnp.float32))
    res2 = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, flags=flags, geom=geom,
                          emit_dkbias=emit_dkbias, num_q=num_q, d=d),
        name="flash_bwd_dkv",
        grid=(programs, num_kv, num_q),
        in_specs=[qspec, kspec, kspec]
        + _extra_specs(order, groups, gmode_mask, gmode_bias, gmode_kbias,
                       block_q, block_k, **flags)
        + [qspec, rowspec, rowspec],
        out_specs=dkv_outs,
        out_shape=dkv_shapes,
        scratch_shapes=dkv_scratch,
        interpret=interpret,
    )(q, k, v, *extras, do, lse, delta)
    if emit_dkbias:
        dk, dv, dkbias = res2
    else:
        (dk, dv), dkbias = res2, None
    return dq, dk, dv, dbias, dkbias


# ---------------------------------------------------------------- public op
def _f0(x):
    import numpy as _np
    from jax import dtypes as _jd
    return _np.zeros(x.shape, _jd.float0)


def _group_reduce(d, gmode, b, heads, shape, dtype):
    """Sum a per-(b·h) gradient over its broadcast group → original
    storage shape."""
    g = d.reshape(b, heads, *d.shape[1:])
    if gmode == "one":
        d = g.sum(axis=(0, 1))[None]
    elif gmode == "h":
        d = g.sum(axis=0)
    elif gmode == "b":
        d = g.sum(axis=1)
    return d.reshape(shape).astype(dtype)


_STATIC = tuple(range(8, 19))


@functools.partial(jax.custom_vjp, nondiff_argnums=_STATIC)
def _flash(q3, k3, v3, lengths, kmask, kbias, fmask, bias, scale, causal,
           gmode_mask, gmode_bias, gmode_kbias, heads, block_q, block_k,
           interpret, fwd_name, d):
    out, _ = _flash_fwd(q3, k3, v3, lengths, kmask, kbias, fmask, bias,
                        scale, causal, gmode_mask, gmode_bias, gmode_kbias,
                        heads, block_q, block_k, interpret, fwd_name, d)
    return out


def _flash_vjp_fwd(q3, k3, v3, lengths, kmask, kbias, fmask, bias, scale,
                   causal, gmode_mask, gmode_bias, gmode_kbias, heads,
                   block_q, block_k, interpret, fwd_name, d):
    out, lse = _flash_fwd(q3, k3, v3, lengths, kmask, kbias, fmask, bias,
                          scale, causal, gmode_mask, gmode_bias, gmode_kbias,
                          heads, block_q, block_k, interpret, fwd_name, d)
    return out, (q3, k3, v3, lengths, kmask, kbias, fmask, bias, out, lse)


def _flash_vjp_bwd(scale, causal, gmode_mask, gmode_bias, gmode_kbias, heads,
                   block_q, block_k, interpret, fwd_name, d, res, do):
    q3, k3, v3, lengths, kmask, kbias, fmask, bias, out, lse = res
    dq, dk, dv, dbias, dkbias = _flash_bwd(
        q3, k3, v3, lengths, kmask, kbias, fmask, bias, out, lse, do, scale,
        causal, gmode_mask, gmode_bias, gmode_kbias, heads, block_q, block_k,
        interpret, d)
    b = lse.shape[0] // heads
    if bias is not None:
        # reduce the dense (B·H, S, S) tile grads over the broadcast group
        dbias = _group_reduce(dbias, gmode_bias, b, heads, bias.shape,
                              bias.dtype)
    if kbias is not None:
        dkbias = _group_reduce(dkbias, gmode_kbias, b, heads, kbias.shape,
                               kbias.dtype)
    return (dq, dk, dv,
            None if lengths is None else _f0(lengths),
            None if kmask is None else _f0(kmask),
            None if kbias is None else dkbias,
            None if fmask is None else _f0(fmask),
            None if bias is None else dbias)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _classify_group(x, b, h, s_q, s_kv, name):
    """Validate a (1|B, 1|H, S_q|1, S_kv)-broadcastable tensor and return
    its broadcast-group mode — the ONE place group semantics live (the
    dense-bias and key-bias paths both classify through here)."""
    if x.ndim != 4:
        raise ValueError(f"{name} must be rank-4 broadcastable, "
                         f"got {x.shape}")
    xb, xh, xq, xk = x.shape
    if xk != s_kv or xq not in (1, s_q) or xb not in (1, b) \
            or xh not in (1, h):
        raise ValueError(f"{name} shape {x.shape} not broadcastable to "
                         f"({b}, {h}, {s_q}, {s_kv})")
    return {(True, True): "one", (True, False): "h",
            (False, True): "b", (False, False): "bh"}[(xb == 1, xh == 1)]


def _broadcast_group(x, b, h, s_q, s_kv, name):
    """Classify into un-broadcast (G, S_q, S_kv) storage + gmode — no
    materialisation of the broadcast (beyond q-row expansion)."""
    gmode = _classify_group(x, b, h, s_q, s_kv, name)
    if x.shape[2] == 1 and s_q != 1:
        x = jnp.broadcast_to(
            x, (x.shape[0], x.shape[1], s_q, s_kv))  # rows only: O(S²/Sq)
    return x.reshape(-1, s_q, s_kv), gmode


def flash_attention(q, k, v, causal=False, scale=None, lengths=None,
                    key_mask=None, mask=None, bias=None,
                    block_q=None, block_k=None, interpret=False, heads=None):
    """Blockwise flash attention for (B, H, S, D) inputs — or, with
    ``heads=H``, for PACKED (B, S, H·D) inputs, read and written as a
    projection leaves them (module docstring): the output and the three
    gradients come back packed too, and no transposed copy of any of them
    is made.  A packed call needs a head size that :func:`packed_width`
    accepts with ``H·D`` a multiple of it, and takes ``causal``,
    ``lengths`` and ``key_mask`` alone (a dense ``mask`` / ``bias`` is laid
    out per head: head-major callers').

    ``lengths``: optional (B,) int32 valid-KEY counts per sequence — keys
    at positions >= lengths[b] are masked out (padding mask); fully masked
    key blocks spend no FLOPs (the block body is predicated off; the
    block's K/V DMA still occurs — true block pruning would need
    scalar-prefetch grid shrinking).
    ``key_mask``: optional (B, S_kv) (or (B, 1, 1, S_kv)) boolean per-key
    mask — the general padding-mask form when validity is not a prefix.
    ``mask``: optional full boolean mask, broadcastable
    (1|B, 1|H, 1|S_q, S_kv); loaded blockwise without materialising the
    broadcast.
    ``bias``: optional additive logit bias, same broadcast menu,
    differentiable (T5 relative position bias).
    With none of these the kernels compile the original dense
    straight-line code with zero masking overhead.
    ``block_q`` / ``block_k``: left ``None``, :func:`_pick_blocks` chooses
    them from this call's shapes (the whole key range in one block where
    it fits VMEM); given, they are used as they are.  Which geometry and
    layout a trace compiled is counted in ``metrics.flash_call_counts()``
    (``"512x512:one_pass"``, ``"256x512:one_pass:packed"``).
    Ragged (non-128-multiple) sequence lengths are BUCKETED: padded up to
    the next flash-legal bucket (128/256/384/…), the pad keys masked by the
    kernel's existing lengths/key-mask strip path, and the output sliced
    back to the caller's length — ``seq=384+r`` stays on the fast path.
    The one unbucketable case is causal CROSS-attention whose lengths
    differ mod 128 (padding would shift the bottom-right-aligned
    diagonal); that raises, and the dispatcher falls back with an
    explicit ``flash_fallback_reason``.  ``interpret=True`` runs the
    Pallas interpreter so CPU CI exercises the same kernel code.
    """
    packed = heads is not None
    if packed:
        b, s_q, lanes = q.shape
        h, d, seq = heads, lanes // heads, 1
        width = packed_width(d)
        if width is None or lanes % width:
            raise ValueError(
                f"packed flash attention needs heads of a size that "
                f"divides or is a multiple of {_LANES} lanes in whole "
                f"column blocks, got {h} heads of {d}")
        if mask is not None or bias is not None:
            raise ValueError("packed flash attention takes causal, lengths "
                             "and key_mask only")
    else:
        b, h, s_q, d = q.shape
        seq, width = 2, d
    s_kv = k.shape[seq]
    pad_q = flash_bucket(s_q) - s_q
    pad_k = flash_bucket(s_kv) - s_kv
    if causal and pad_q != pad_k:
        # padding q and kv by different amounts would move the kernel's
        # kv_off diagonal against the reference's tril(s_kv - s_q)
        raise ValueError(
            f"causal flash attention cannot bucket lengths ({s_q}, {s_kv})"
            f" — they differ mod {FLASH_BUCKET}, so padding would shift "
            f"the bottom-right-aligned diagonal")
    s_q_orig = s_q
    if pad_q or pad_k:
        q = _pad_seq(q, seq, pad_q)
        k = _pad_seq(k, seq, pad_k)
        v = _pad_seq(v, seq, pad_k)
        if pad_k:
            # pad KEYS must be invisible: ``lengths`` already masks cols
            # >= lengths[b] <= s_kv; a given key_mask/mask extends with
            # invalid columns; with no key validity input at all, the pad
            # rides the O(1) SMEM lengths path (fully-padded key blocks
            # are pruned, not computed)
            if key_mask is not None:
                km = jnp.asarray(key_mask)
                km = _pad_seq(km, km.ndim - 1, pad_k,
                              value=jnp.zeros((), km.dtype))
                key_mask = km
            if mask is not None and jnp.ndim(mask) == 4:
                m = jnp.asarray(mask)
                m = _pad_seq(m, 3, pad_k, value=jnp.zeros((), m.dtype))
                mask = m
            if lengths is None and key_mask is None and mask is None:
                lengths = jnp.full((b,), s_kv, jnp.int32)
            if bias is not None and jnp.ndim(bias) == 4:
                bias = _pad_seq(jnp.asarray(bias, jnp.float32), 3, pad_k)
        if pad_q:
            # pad QUERY rows compute garbage that is sliced off below;
            # their kernel inputs only need legal shapes
            if mask is not None and jnp.ndim(mask) == 4 \
                    and mask.shape[2] != 1:
                mask = _pad_seq(jnp.asarray(mask), 2, pad_q,
                                value=jnp.zeros((), jnp.asarray(mask).dtype))
            if bias is not None and jnp.ndim(bias) == 4 \
                    and bias.shape[2] != 1:
                bias = _pad_seq(jnp.asarray(bias, jnp.float32), 2, pad_q)
        s_q += pad_q
        s_kv += pad_k
    scale = float(scale) if scale is not None else 1.0 / (d ** 0.5)
    if packed:
        q3, k3, v3 = q, k, v
    else:
        q3 = q.reshape(b * h, s_q, d)
        k3 = k.reshape(b * h, s_kv, d)
        v3 = v.reshape(b * h, s_kv, d)
    if lengths is None:
        len3 = None    # static: kernels compile the dense straight-line path
    else:
        # one scalar a program: a head, or a packed column block of heads
        groups = h * d // width
        len3 = jnp.broadcast_to(
            jnp.asarray(lengths, jnp.int32).reshape(b, 1), (b, groups)
        ).reshape(b * groups, 1, 1)
    gmode_mask = gmode_bias = gmode_kbias = "one"
    kmask2 = kbias3 = fmask3 = bias3 = None
    if key_mask is not None:
        km = jnp.asarray(key_mask)
        if km.ndim == 4:     # (B, 1, 1, S_kv) attention-mask convention
            km = km.reshape(km.shape[0], km.shape[-1])
        if km.shape != (b, s_kv):
            raise ValueError(f"key_mask must be (B, S_kv), got "
                            f"{key_mask.shape}")
        # stored (B, 1, S_kv) — see the kmask BlockSpec note
        kmask2 = km.astype(jnp.int32)[:, None, :]
    if mask is not None:
        fmask3, gmode_mask = _broadcast_group(
            jnp.asarray(mask).astype(jnp.int32), b, h, s_q, s_kv, "mask")
    if bias is not None:
        ba = jnp.asarray(bias, jnp.float32)
        if ba.ndim == 4 and ba.shape[2] == 1 and s_q != 1:
            # per-KEY (row-broadcast) bias: O(S) column strips, never
            # materialised to (S_q, S_kv) (round-3 advisor finding)
            gmode_kbias = _classify_group(ba, b, h, s_q, s_kv, "bias")
            kbias3 = ba.reshape(-1, 1, s_kv)
        else:
            bias3, gmode_bias = _broadcast_group(ba, b, h, s_q, s_kv, "bias")
    # blocks by the rule, from this call's shapes; a dense bias counts
    # twice (its tile and the backward's dbias tile).  Explicit blocks win.
    rule_q, rule_k = _pick_blocks(
        s_q, s_kv, width, q.dtype.itemsize, causal,
        (fmask3 is not None) + 2 * (bias3 is not None))
    block_q = block_q or rule_q
    block_k = block_k or rule_k
    if s_q % block_q or s_kv % block_k:
        raise ValueError(
            f"flash_attention needs seq divisible by block "
            f"({s_q}, {s_kv}) vs ({block_q}, {block_k})")
    from ...metrics import record_flash_call
    record_flash_call(block_q, block_k, one_pass=block_k == s_kv,
                      packed=packed)
    # the one-token decode call (one query row, padded to a bucket of
    # 128) under a name of its own in the device trace
    out = _flash(q3, k3, v3, len3, kmask2, kbias3, fmask3, bias3, scale,
                 causal, gmode_mask, gmode_bias, gmode_kbias, h, block_q,
                 block_k, interpret,
                 "flash_fwd_q1" if s_q_orig == 1 else "flash_fwd",
                 d if packed else None)
    if not packed:
        out = out.reshape(b, h, s_q, d)
    if s_q != s_q_orig:
        # unpad: bucketing is caller-invisible
        out = jax.lax.slice_in_dim(out, 0, s_q_orig, axis=seq)
    return out
