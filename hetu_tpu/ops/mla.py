"""Decode-time ops of multi-head LATENT attention (DeepSeek-V2's MLA, as
``glm4_moe_lite`` publishes it; ``hetu_tpu/models/glm4_moe_lite.py``).

A token leaves ONE row in a layer's cache, whatever the number of heads:
``[c (rank); k_rope (rope)]`` — ``c = n(W_dkv y)[:rank]`` the normed
compressed latent from which every head's keys and values are projected up,
``k_rope`` the one rotary key all heads share.  The published layer writes
its attention two ways, and both are here:

* **materialised** (:func:`mla_attention_op`; the full-sequence graph, the
  plain reference): ``[k_nope,h; v_h] = W_ukv,h c`` per head, scores
  ``(q_nope,h · k_nope,h + q_rope,h · k_rope) / √(nope + rope)``;
* **absorbed** (:func:`mla_attention_kv_op`; every served step): the
  up-projection moves to the query's side, ``q'_h = W_uk,hᵀ q_nope,h``, so
  the score is ``q'_h · c + q_rope,h · k_rope`` — ``H`` query rows over the
  ONE cached row, whose first ``rank`` lanes are also the value: ``o_h =
  W_uv,h (Σ p c)``.  No per-head key or value ever exists.

**The cache row** is stored padded to whole 128-lane rows
(:func:`latent_lanes`: 576 -> 640): a minor dimension that is no whole
number of lane rows is stored length-minor and copied whole every step
(PERF.md §6, PR 26), and one slab lets the one-token kernel fetch a key
block once for the scores and for the values.  It is a ``kv`` state of one
head (``ops.state_placeholder``), appended by ``kv_cache_append_op``.

They follow the conventions of :mod:`~hetu_tpu.ops.ssm`: a ``(B, C)`` chunk
of tokens per call, the residual stream flattened to ``(B*C, ·)``, ``(B,
C)`` recovered from the ``ids`` feed, ``positions`` (B,) the position of
each row's first column.  Scores, softmax, norms and the rotation run in
float32; a product with a stored weight takes its left operand through the
weight's type and accumulates in float32 (``matmul_op(out_dtype=)``'s
rule); the absorbed query and the rotary query meet the slab in the slab's
type.
"""
import jax
import jax.numpy as jnp

from .base import def_op
from .kda import _rms
from .ssm import _f32

_LANES = 128


def latent_lanes(rank, rope_dim):
    """Lanes of a stored cache row of ``rank + rope_dim`` values: the next
    whole number of 128-lane rows."""
    return -(-(int(rank) + int(rope_dim)) // _LANES) * _LANES


def _rotate(x, at, theta):
    """Rotary embedding of ``x`` (..., D) float32 at positions ``at``
    (broadcastable to ``x.shape[:-1]``), rotate-half pairing: dims ``i``
    and ``i + D/2`` turn by ``at · theta^(−2i/D)``, no scaling."""
    half = x.shape[-1] // 2
    inv = jnp.asarray(theta, jnp.float32) ** (
        -jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.asarray(at, jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _at(positions, ids):
    """(B, C) position of every column: ``positions + column``."""
    return positions.astype(jnp.int32)[:, None] + jnp.arange(
        ids.shape[1], dtype=jnp.int32)[None, :]


def _rope(c, x, positions, ids, theta=10000.0, head_dim=64, rope_dim=64):
    """The last ``rope_dim`` of every ``head_dim``-wide head of ``x`` (B*C,
    H * head_dim) rotated at ``positions + column``; the rest handed
    through.  Float32."""
    b, chunk = ids.shape
    x = _f32(x).reshape(b, chunk, -1, int(head_dim))
    keep = int(head_dim) - int(rope_dim)
    turned = _rotate(x[..., keep:], _at(positions, ids)[:, :, None], theta)
    return jnp.concatenate([x[..., :keep], turned], axis=-1).reshape(
        b * chunk, -1)


rope_op = def_op("RotaryEmbedding", _rope)


def _mla_latent_rows(c, kv, scale, positions, ids, rank=512, theta=10000.0,
                     eps=1e-5, lanes=None):
    """``W_dkv y`` -> the cache rows.  ``kv``: (B*C, rank + rope) ``[c_kv;
    k_r]``; ``scale``: (rank,).  Returns (B, 1, C, lanes) float32 ``[n(c_kv);
    R(k_r); 0]`` for ``kv_cache_append_op``, which casts them to the
    cache's type."""
    b, chunk = ids.shape
    kv = _f32(kv).reshape(b, chunk, -1)
    rank = int(rank)
    rows = jnp.concatenate([
        _rms(kv[..., :rank], _f32(scale), eps),
        _rotate(kv[..., rank:], _at(positions, ids), theta)], axis=-1)
    lanes = int(lanes or rows.shape[-1])
    rows = jnp.pad(rows, ((0, 0), (0, 0), (0, lanes - rows.shape[-1])))
    return rows[:, None]


mla_latent_rows_op = def_op("MLALatentRows", _mla_latent_rows)


def _heads(q, w_ukv, ids, heads, nope, rank):
    """``q`` (B*C, H * (nope + rope)) as (B, C, H, ·) float32 and ``w_ukv``
    (rank, H * (nope + v)) as (rank, H, ·)."""
    b, chunk = ids.shape
    return (_f32(q).reshape(b, chunk, int(heads), -1),
            w_ukv.reshape(int(rank), int(heads), -1))


def _stored(x, w, eq):
    """``einsum(eq, x, w)`` over a stored weight: ``x`` through ``w``'s
    type, float32 out."""
    return jnp.einsum(eq, x.astype(w.dtype), w,
                      preferred_element_type=jnp.float32)


def _mla_attention_kv(c, q, slab, w_ukv, positions, ids, heads=1, nope=128,
                      rank=512):
    """ABSORBED attention of a (B, C) chunk over the latent slab, which
    already holds the chunk's own rows: query ``j`` of sequence ``b`` sees
    rows ``<= positions[b] + j``.  ``q``: (B*C, H * (nope + rope)), rotated;
    ``slab``: (B, 1, L, lanes) rows ``[c; k_rope; 0]``; ``w_ukv``: (rank, H
    * (nope + v)).  Returns (B*C, H * v) float32, before ``W_o``.

    The one-token step on the chip (``C == 1``, no mesh, the decode gate of
    ``ops.attention``) hands the slab AS STORED to the one-token kernel in
    its latent mode (``ops/pallas/decode_attention.py``: ``H`` score rows
    over one key block whose first ``rank`` lanes are the value); a chunk
    and the CPU read it whole through ``jnp``."""
    from .attention import _decode_gate_reason
    b, chunk = ids.shape
    nope, rank = int(nope), int(rank)
    q, w = _heads(q, w_ukv, ids, heads, nope, rank)
    h, lanes = q.shape[2], slab.shape[-1]
    scale = q.shape[-1] ** -0.5
    rows = jnp.concatenate(
        [_stored(q[..., :nope], w[..., :nope], "bchd,rhd->bchr"),
         q[..., nope:]], axis=-1) * scale
    rows = jnp.pad(rows, ((0, 0),) * 3 + ((0, lanes - rows.shape[-1]),)
                   ).astype(slab.dtype)                  # (B, C, H, lanes)
    at = positions.astype(jnp.int32)
    if (chunk == 1 and getattr(c, "mesh", None) is None
            and _decode_gate_reason(slab.shape[2]) is None):
        from .pallas.decode_attention import decode_attention
        ctx = decode_attention(rows.transpose(0, 2, 1, 3).reshape(
            b, 1, h, lanes), slab, None, at + 1, v_lanes=rank)
        ctx = ctx.reshape(b, 1, h, rank)
    else:
        ctx = _read_whole(rows, slab[:, 0], _at(positions, ids))[..., :rank]
    out = _stored(ctx, w[..., nope:], "bchr,rhd->bchd")
    return out.reshape(b * chunk, -1)


mla_attention_kv_op = def_op("MLAAttentionKV", _mla_attention_kv)

#: float32 scores one pass of the whole-slab read may hold; above it the
#: slots are read group by group (at 128 slots x 20 heads x 32 columns x
#: 4096 rows the scores alone are 1.34 GB a layer, and the chunk-32 program
#: of the served share did not leave the chip that much, PERF.md §6 PR 36)
_SCORE_BYTES = 256 << 20


def _read_whole(rows, keys, at):
    """Softmax attention of ``rows`` (B, C, H, lanes) over the cache rows
    ``keys`` (B, L, lanes) read whole, query ``(b, c)`` seeing rows ``<=
    at[b, c]``; the value is the key row itself: (B, C, H, lanes) float32.
    The slots go through in equal groups small enough for
    ``_SCORE_BYTES``."""
    b, chunk, h, _ = rows.shape

    def read(args):
        rows, keys, at = args
        seen = jnp.arange(keys.shape[1], dtype=jnp.int32)[None, None, :] \
            <= at[:, :, None]                                # (b, C, L)
        s = jnp.einsum("bchl,bml->bhcm", rows, keys,
                       preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(jnp.where(seen[:, None], s, -1e30), axis=-1)
        # over the whole row, the small result cut by the caller: a lane
        # slice of the slab would be a copy of four fifths of it
        return jnp.einsum("bhcm,bml->bchl", probs.astype(keys.dtype), keys,
                          preferred_element_type=jnp.float32)

    fit = max(1, _SCORE_BYTES // (chunk * h * keys.shape[1] * 4))
    groups = next(g for g in range(1, b + 1) if b % g == 0 and b // g <= fit)
    if groups == 1:
        return read((rows, keys, at))
    out = jax.lax.map(read, tuple(
        x.reshape((groups, b // groups) + x.shape[1:])
        for x in (rows, keys, at)))
    return out.reshape((b,) + out.shape[2:])


def _mla_attention(c, q, rows, w_ukv, ids, heads=1, nope=128, rank=512):
    """MATERIALISED causal attention of a full (B, T) sequence from
    position 0: ``q`` (B*T, H * (nope + rope)) rotated; ``rows`` (B, 1, T,
    >= rank + rope) the sequence's own cache rows (:func:`_mla_latent_rows`);
    ``w_ukv`` (rank, H * (nope + v)).  Every head's keys and values are
    projected up from ``c``; returns (B*T, H * v) float32."""
    b, t = ids.shape
    nope, rank = int(nope), int(rank)
    q, w = _heads(q, w_ukv, ids, heads, nope, rank)
    rope = q.shape[-1] - nope
    lat = _f32(rows[:, 0])
    kv = _stored(lat[..., :rank], w, "bmr,rhd->bmhd")    # (B, T, H, nope+v)
    s = (jnp.einsum("bchd,bmhd->bhcm", q[..., :nope], kv[..., :nope])
         + jnp.einsum("bchd,bmd->bhcm", q[..., nope:],
                      lat[..., rank:rank + rope])) * q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))
    probs = jax.nn.softmax(jnp.where(causal[None, None], s, -1e30), axis=-1)
    out = jnp.einsum("bhcm,bmhd->bchd", probs, kv[..., nope:])
    return out.reshape(b * t, -1)


mla_attention_op = def_op("MLAAttention", _mla_attention)
