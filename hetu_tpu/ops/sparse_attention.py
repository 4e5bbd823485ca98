"""Decode-time ops of trainable block-sparse attention (InfLLM-v2,
arXiv:2506.07900 §2 / arXiv:2509.24663): grouped-query attention whose
queries read only the key BLOCKS an indexer chose for them
(``hetu_tpu/models/minicpm_sala.py``).

Beside its growable K and V slabs a layer keeps **compressed keys**: row
``s`` is the mean of keys ``[s·stride, s·stride + kernel)``, ``kernel = 2 ·
stride`` — one row per ``stride`` positions (an ``index`` state,
``ops.state_placeholder``), appended when its kernel completes.  With ``n =
t + 1`` keys and ``G`` key heads of ``R`` query heads each:

* below ``dense_len`` a query attends to every key;
* else ``p_h = softmax_s(q_h · k̄_{g,s} / √D)`` over the complete rows,
  ``a_{g,s} = Σ_{h∈g} p_{h,s}``, a block's score the largest ``a`` among the
  kernels that overlap it, and head group ``g`` reads the ``window / block``
  blocks that cover the last positions, and of the others the ``topk`` best,
  the first ``init_blocks`` always: ``topk + window / block`` blocks,
  whatever the length.

They follow the conventions of :mod:`~hetu_tpu.ops.ssm`: a ``(B, C)`` chunk
of tokens a call, the residual stream flattened to ``(B*C, ·)``, ``positions``
(B,) of each row's first column, an optional ``valid`` (B,).

**The one-token read on the chip** hands the chosen block ids, sorted, to the
selected-block mode of ``ops/pallas/decode_attention.py``
(``decode_attention_blocks``: only those blocks leave HBM).  **A chunk, the
CPU and the full-sequence graph** read the slabs whole through ``jnp`` under
a mask built from the same selection per query row — the same chosen set —
slot group by slot group (:func:`_read_masked`).  ``sparse_attn_calls``
counts either path per trace.

**The pooling state.**  A compressed row needs the last ``kernel`` keys; a
gather of them out of a slab at a traced position relays the slab out, so a
layer carries the two open sums instead (``recurrent``, ``(B, G, 2, D)``
float32): ``[0]`` the keys of the ``stride``-group being filled, ``[1]`` the
group before it.  When a group completes, ``([1] + [0]) / kernel`` is the row
of the kernel that ends there.
"""
import jax
import jax.numpy as jnp

from .base import def_op, tuple_outputs
# float32 scores one pass of a whole-slab read may hold
from .mla import _SCORE_BYTES
from .ssm import _count, _f32

_NEG = -1e30


class SparseSizes:
    """The indexer's sizes (a model's ``sparse`` group)."""

    def __init__(self, kernel_size=32, kernel_stride=16, block_size=64,
                 window_size=2048, topk=64, init_blocks=1, dense_len=8192):
        self.kernel, self.stride = int(kernel_size), int(kernel_stride)
        self.block, self.window = int(block_size), int(window_size)
        self.topk, self.init = int(topk), int(init_blocks)
        self.dense_len = int(dense_len)
        if self.kernel != 2 * self.stride or self.block % self.stride \
                or self.window % self.block:
            raise ValueError(
                "the indexer pools kernels of two strides, scores blocks of "
                "whole strides and keeps a window of whole blocks; got "
                f"{self.as_dict()}")
        far = (self.dense_len - 1) // self.block - self.near + 1
        if far < self.topk or self.dense_len < self.kernel:
            raise ValueError(
                f"at dense_len {self.dense_len} a sequence has {far} blocks "
                f"outside its window of {self.near}: fewer than topk "
                f"{self.topk}")

    def as_dict(self):
        return {"kernel_size": self.kernel, "kernel_stride": self.stride,
                "block_size": self.block, "window_size": self.window,
                "topk": self.topk, "init_blocks": self.init,
                "dense_len": self.dense_len}

    @property
    def near(self):
        """Blocks of the window."""
        return self.window // self.block

    @property
    def chosen(self):
        """Blocks a head group reads at or past ``dense_len``."""
        return self.topk + self.near

    def done(self, n):
        """Complete compressed rows of a sequence of ``n`` keys."""
        return jnp.maximum((n - self.kernel) // self.stride + 1, 0)


# ------------------------------------------------------- compressed rows

def _pool_rows(c, k, pool, positions, ids, valid=None, stride=16):
    """The compressed rows a (B, C) chunk completes.  ``k``: (B, G, C, D)
    the chunk's keys; ``pool``: (B, G, 2, D) the open sums.  Returns
    ``(rows, first, count, pool')``: ``rows`` (B, G, ceil(C / stride), D)
    float32, the first ``count`` (B,) of them real, for compressed positions
    ``first ...`` (B,) — one for one token, two for 32 at stride 16 — what
    ``kv_cache_append_op`` takes as rows, positions and valid."""
    b, chunk = ids.shape
    stride = int(stride)
    m = (chunk + stride - 2) // stride + 1
    p0 = positions.astype(jnp.int32)
    n = _count(ids, valid)
    col = jnp.arange(chunk, dtype=jnp.int32)[None, :]
    j0 = p0 // stride
    # (B, C, M): column c falls in group j0 + m, and is real
    member = jnp.logical_and(
        ((p0[:, None] + col) // stride - j0[:, None])[..., None]
        == jnp.arange(m, dtype=jnp.int32), (col < n[:, None])[..., None])
    pool = _f32(pool)
    sums = jnp.sum(_f32(k)[:, :, :, None, :]
                   * member[:, None, :, :, None], axis=2)    # (B, G, M, D)
    sums = sums.at[:, :, 0].add(pool[:, :, 0])
    before = jnp.concatenate([pool[:, :, 1:], sums[:, :, :-1]], axis=2)
    rows = (before + sums) / (2.0 * stride)
    full = jnp.sum((j0[:, None] + jnp.arange(1, m + 1, dtype=jnp.int32))
                   * stride <= (p0 + n)[:, None], axis=1)      # (B,)
    # group 0 ends no kernel: a sequence's first row is of groups 0 and 1
    lead = (j0 == 0).astype(jnp.int32)
    rows = jnp.where(lead[:, None, None, None] == 1,
                     jnp.roll(rows, -1, axis=2), rows)
    padded = jnp.concatenate([sums, jnp.zeros_like(sums[:, :, :1])], axis=2)

    def pick(at):
        return jnp.take_along_axis(
            padded, jnp.broadcast_to(at[:, None, None, None],
                                     padded[:, :, :1].shape), axis=2)

    some = (full > 0)[:, None, None, None]
    new = jnp.concatenate(
        [jnp.where(some, pick(full), sums[:, :, :1]),
         jnp.where(some, pick(jnp.maximum(full - 1, 0)), pool[:, :, 1:])],
        axis=2)
    # a chunk touches ``m`` groups and completes at most ``ceil(C / stride)``
    most = -(-chunk // stride)
    return (rows[:, :, :most], jnp.maximum(j0 - 1, 0),
            jnp.maximum(full - lead, 0), new)


_pool_rows_node = def_op("SparsePoolRows", _pool_rows)


def pool_rows_op(*inputs, name=None, stride=16):
    """``(rows, first, count, pool')`` nodes of :func:`_pool_rows`."""
    return tuple_outputs(_pool_rows_node(*inputs, name=name, stride=stride),
                         4)


# ----------------------------------------------------------- selection

def block_scores(a, done, per, overlap):
    """``a`` (..., S) the group-summed indexer weights of the compressed
    rows, of which the first ``done`` (...) are complete -> (..., ceil(S /
    per)) block scores: block ``j`` the largest of rows ``per·j − overlap
    .. per·j + per − 1`` that are complete, ``−inf`` where none is."""
    s = a.shape[-1]
    blocks = -(-s // per)
    a = jnp.where(jnp.arange(s, dtype=jnp.int32) < done[..., None], a,
                  -jnp.inf)
    wide = jnp.pad(a, [(0, 0)] * (a.ndim - 1)
                   + [(overlap, blocks * per - s)], constant_values=-jnp.inf)
    return jnp.stack([wide[..., i:i + per * blocks:per]
                      for i in range(per + overlap)]).max(axis=0)


def select_blocks(scores, t, z):
    """The ``z.topk`` blocks outside the window a query at position ``t``
    (...) reads, from block ``scores`` (..., J): sorted ids (..., topk)
    int32, ``-1`` throughout where the query reads everything (fewer than
    ``z.dense_len`` keys).  Equal scores go to the lower block id
    (``jax.lax.top_k``'s rule)."""
    j = jnp.arange(max(scores.shape[-1], z.topk), dtype=jnp.int32)
    scores = jnp.pad(scores, [(0, 0)] * (scores.ndim - 1)
                     + [(0, j.shape[0] - scores.shape[-1])],
                     constant_values=-jnp.inf)
    edge = (t // z.block - (z.near - 1))[..., None]      # the window's first
    ranked = jnp.where(j < z.init, jnp.inf, scores)
    ranked = jnp.where(j < edge, ranked, -jnp.inf)
    ids = jnp.sort(jax.lax.top_k(ranked, z.topk)[1].astype(jnp.int32), -1)
    return jnp.where((t + 1 < z.dense_len)[..., None], -1, ids)


def _indexer(q, index, t, z):
    """Block scores (b, G, C, J) of queries ``q`` (b, C, G, R, D), scaled,
    at positions ``t`` (b, C) over the compressed rows ``index`` (b, G, S,
    D)."""
    s = jnp.einsum("bcgrd,bgsd->bgrcs", q, index,
                   preferred_element_type=jnp.float32)
    done = z.done(t + 1)                                        # (b, C)
    live = jnp.arange(index.shape[2], dtype=jnp.int32) < done[..., None]
    p = jax.nn.softmax(jnp.where(live[:, None, None], s, _NEG), axis=-1)
    return block_scores(jnp.sum(p, axis=2),
                        jnp.broadcast_to(done[:, None], p.shape[:2]
                                         + done.shape[1:]),
                        z.block // z.stride, z.kernel // z.stride - 1)




def _read_masked(q, keys, vals, index, t, count, z):
    """Attention of ``q`` (B, C, G, R, D), scaled and in the slabs' type,
    over the rows ``keys`` / ``vals`` (B, G, L, D) read whole, query ``(b,
    c)`` at position ``t[b, c]`` seeing the keys ``<= t`` of the blocks
    chosen for it from ``index`` (B, G, S, D): ``(out (B, C, G, R, D)
    float32, ids (B, C, G, topk))``.  The slots go through in equal groups
    small enough for ``_SCORE_BYTES``; a group none of whose rows has a real
    column (``count`` 0: idle slots while a few long prompts go in) is
    skipped."""
    b, chunk, g, r, d = q.shape
    length = keys.shape[2]

    def read(args):
        q, keys, vals, index, t = args
        ids = select_blocks(_indexer(q, index, t, z), t[:, None], z)
        blocks = -(-length // z.block)
        at = jnp.arange(blocks, dtype=jnp.int32)
        edge = (t // z.block - (z.near - 1))[:, None, :, None]
        chosen = jnp.logical_or(
            jnp.any(ids[..., None] == at, axis=-2),             # (b,G,C,J)
            jnp.logical_or(at >= edge, ids[..., :1] < 0))
        seen = jnp.logical_and(
            jnp.repeat(chosen, z.block, axis=-1)[..., :length],
            jnp.arange(length, dtype=jnp.int32) <= t[:, None, :, None])
        s = jnp.einsum("bcgrd,bgmd->bgrcm", q, keys,
                       preferred_element_type=jnp.float32)
        p = jax.nn.softmax(jnp.where(seen[:, :, None], s, _NEG), axis=-1)
        out = jnp.einsum("bgrcm,bgmd->bcgrd", p.astype(vals.dtype), vals,
                         preferred_element_type=jnp.float32)
        return out, ids.transpose(0, 2, 1, 3)

    def guarded(args):
        out, ids = jax.eval_shape(read, args[:-1])
        return jax.lax.cond(
            jnp.any(args[-1] > 0), read,
            lambda a: (jnp.zeros(out.shape, out.dtype),
                       jnp.full(ids.shape, -1, ids.dtype)), args[:-1])

    fit = max(1, _SCORE_BYTES // (chunk * g * r * length * 4))
    groups = next(n for n in range(1, b + 1) if b % n == 0 and b // n <= fit)
    args = (q, keys, vals, index, t, count)
    if groups == 1:
        return read(args[:-1])
    out, ids = jax.lax.map(guarded, tuple(
        x.reshape((groups, b // groups) + x.shape[1:]) for x in args))
    return (out.reshape((b,) + out.shape[2:]),
            ids.reshape((b,) + ids.shape[2:]))


def _schedule(ids, t, length, z):
    """What the selected-block kernel walks for one-token queries at ``t``
    (B,) with far ids ``ids`` (B, G, topk): ``(blocks (B, G, W), counts (B,
    G))`` — the far blocks then the window's, sorted; every live block where
    the query reads everything."""
    from .pallas.decode_attention import SEL_BLOCKS
    width = max(z.chosen, -(-min(z.dense_len, length) // z.block))
    width = -(-width // SEL_BLOCKS) * SEL_BLOCKS
    at = jnp.arange(width, dtype=jnp.int32)
    cur = t // z.block
    near = (cur - (z.near - 1))[:, None, None] + at[:z.near]
    sparse = jnp.concatenate(
        [ids, jnp.broadcast_to(near, ids.shape[:2] + (z.near,)),
         jnp.zeros(ids.shape[:2] + (width - z.chosen,), jnp.int32)], axis=-1)
    dense = ids[..., :1] < 0
    return (jnp.where(dense, at, sparse),
            jnp.where(dense[..., 0], (cur + 1)[:, None], z.chosen))


def _sparse_attention_kv(c, q, k_slab, v_slab, index_slab, positions, ids,
                         valid=None, head_dim=128, sizes=None):
    """Block-sparse attention of a (B, C) chunk's queries over growable KV
    slabs and the compressed-key slab, all of which already hold the chunk's
    own rows.  ``q``: (B*C, H * D), normed; slabs (B, G, L/r, r * D) and (B,
    G, S/r, r * D); query head ``h`` reads key head ``h // (H // G)``;
    ``sizes``: the indexer's (:class:`SparseSizes` keywords).  Returns
    ``(att (B*C, H * D) float32, ids (B, C, G, topk) int32)``: the far
    blocks each query chose, sorted, ``-1`` where it read everything."""
    from ..metrics import record_sparse_attn_call
    from .attention import _decode_gate_reason, kv_slab_to_rows
    z = SparseSizes(**sizes)
    d = int(head_dim)
    b, chunk = ids.shape
    g, slab_rows, lanes = k_slab.shape[1:]
    pack = lanes // d
    length = slab_rows * pack
    q = (_f32(q) * d ** -0.5).reshape(b, chunk, g, -1, d).astype(k_slab.dtype)
    at = positions.astype(jnp.int32)
    index = kv_slab_to_rows(index_slab, d)
    if (chunk == 1 and pack == 1 and getattr(c, "mesh", None) is None
            and length % z.block == 0
            and _decode_gate_reason(length) is None):
        from .pallas.decode_attention import decode_attention_blocks
        record_sparse_attn_call(z.chosen, z.block, "kernel")
        far = select_blocks(_indexer(q, index, at[:, None], z)[:, :, 0],
                            at[:, None], z)                   # (B, G, topk)
        blocks, counts = _schedule(far, at, length, z)
        out = decode_attention_blocks(q[:, 0], k_slab, v_slab, at + 1,
                                      blocks, counts, block_rows=z.block)
        return out.reshape(b, -1), far[:, None]
    record_sparse_attn_call(z.chosen, z.block, "jnp")
    t = at[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :]
    out, far = _read_masked(q, kv_slab_to_rows(k_slab, d),
                            kv_slab_to_rows(v_slab, d), index, t,
                            _count(ids, valid), z)
    return out.reshape(b * chunk, -1), far


_sparse_attention_kv_node = def_op("SparseAttentionKV", _sparse_attention_kv)


def sparse_attention_kv_op(*inputs, name=None, **attrs):
    """``(att, ids)`` nodes of :func:`_sparse_attention_kv`."""
    return tuple_outputs(
        _sparse_attention_kv_node(*inputs, name=name, **attrs), 2)


def _sparse_choices(c, *ids):
    """The far blocks every sparse layer's queries chose, (B, C, layers, G,
    topk) int16: what a decode step hands back beside its tokens."""
    return jnp.stack(ids, axis=2).astype(jnp.int16)


sparse_choices_op = def_op("SparseChoices", _sparse_choices)


def block_counters(sizes):
    """``fold(blocks) -> {counter: n}`` for ``DecodeEngine(aux_fold=)``:
    what one step's chosen far blocks ``(rows, C, layers, G, topk)`` say of
    the selected-block reads, summed over rows, columns, layers and key
    heads — ``sparse_reads`` (queries that read selectively),
    ``sparse_blocks_chosen`` (blocks they read, the window's among them),
    ``sparse_blocks_far`` (chosen outside the window and the always-read
    first blocks) and ``sparse_block_runs`` (maximal runs of adjacent chosen
    blocks, the window one run of its own: the copies a kernel that merges
    neighbours would issue)."""
    import numpy as np
    z = SparseSizes(**sizes)

    def fold(blocks):
        ids = blocks.reshape(-1, blocks.shape[-1]).astype(np.int32)
        ids = ids[ids[:, 0] >= 0]
        runs = 2 * len(ids) + (np.diff(ids, axis=1) != 1).sum()
        return {"sparse_reads": len(ids),
                "sparse_blocks_chosen": len(ids) * z.chosen,
                "sparse_blocks_far": int((ids >= z.init).sum()),
                "sparse_block_runs": int(runs)}

    return fold
