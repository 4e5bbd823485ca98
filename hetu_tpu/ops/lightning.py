"""Decode-time op of Lightning linear attention (Lightning Attention-2,
arXiv:2401.04658): a linear recurrence with ONE decay a head,

    S_t = λ_h S_{t−1} + k_tᵀ v_t,        o_t = (q_t / √D) S_t,

``λ_h = exp(−2^{−8(h+1)/H})``, over a ``recurrent`` state of one ``(D, D)``
float32 matrix a head and sequence (``(B, H, D, D)``, the key axis first:
2.1 MB a layer and slot at 32 heads of 128), as one layer kind of a hybrid
decoder (``hetu_tpu/models/minicpm_sala.py``).  It follows the conventions
of :mod:`~hetu_tpu.ops.ssm` and :mod:`~hetu_tpu.ops.kda`: a ``(B, C)`` chunk
of tokens a call, the residual stream flattened to ``(B*C, ·)``, ``(B, C)``
recovered from the ``ids`` feed, an optional trailing ``valid`` input ``(B,)``
of the columns that are real, the state advancing by exactly that many
tokens.

Because the decay is a scalar a head, a chunk needs no scan over its columns
(``ops/kda.py``'s delta rule does): with ``Λ_i = λ^{i+1}`` and ``D_ij =
λ^{i−j}`` for ``j ≤ i`` (else 0),

    O  = ((Q Kᵀ) ⊙ D) V + Λ ⊙ (Q S),
    S' = λ^n S + Σ_{j<n} λ^{n−1−j} k_jᵀ v_j          (n = valid columns)

— four small products a head.  ``C = 1`` is the one-token update, written as
elementwise multiply-and-sum so that no float32 operand crosses the MXU at a
lower precision; the chunk's products run at ``HIGHEST`` precision for the
same reason (they are of set-up's steps, 0.2 ms a layer at the served size).
Norms, rotation, decay and state are float32 whatever the storage type.
"""
import jax
import jax.numpy as jnp

from .base import def_op, tuple_outputs
from .kda import _rms
from .mla import _at, _rotate
from .ssm import _count, _f32

_HIGHEST = jax.lax.Precision.HIGHEST


def decay_rates(heads):
    """``−log λ_h = 2^{−8(h+1)/H}`` for ``h = 0 .. H−1``: (H,) float32."""
    h = jnp.arange(1, int(heads) + 1, dtype=jnp.float32)
    return jnp.exp2(-8.0 * h / int(heads))


def _one_token(q, k, v, rate, state, live):
    """``q``, ``k``, ``v``: (B, H, D); ``state``: (B, H, D, D); ``live``:
    (B,) bool — a row that is not leaves the state alone."""
    lam = jnp.exp(-rate)[None, :, None, None]
    new = lam * state + k[..., :, None] * v[..., None, :]
    new = jnp.where(live[:, None, None, None], new, state)
    return jnp.sum(q[..., None] * new, axis=-2), new


def _chunk(q, k, v, rate, state, count):
    """``q``, ``k``, ``v``: (B, C, H, D); ``count``: (B,) — columns at or
    past it leave the state alone (their outputs are not read)."""
    chunk = q.shape[1]
    col = jnp.arange(chunk, dtype=jnp.float32)
    live = (jnp.arange(chunk, dtype=jnp.int32)[None, :]
            < count[:, None])                                     # (B, C)
    gap = col[:, None] - col[None, :]                             # i − j
    mask = jnp.where(gap >= 0, jnp.exp(
        -rate[:, None, None] * jnp.maximum(gap, 0.0)), 0.0)       # (H, C, C)
    a = jnp.einsum("bihd,bjhd->bhij", q, k, precision=_HIGHEST) \
        * mask[None] * live[:, None, None, :]
    inter = jnp.einsum("bihd,bhde->bihe", q, state, precision=_HIGHEST) \
        * jnp.exp(-rate[None, :] * (col[:, None] + 1.0))[None, :, :, None]
    o = jnp.einsum("bhij,bjhd->bihd", a, v, precision=_HIGHEST) + inter
    n = count.astype(jnp.float32)
    left = n[:, None] - 1.0 - col[None, :]                        # (B, C)
    w = jnp.where(live[:, None, :], jnp.exp(
        -rate[None, :, None] * jnp.maximum(left, 0.0)[:, None, :]), 0.0)
    new = jnp.exp(-rate[None, :] * n[:, None])[..., None, None] * state \
        + jnp.einsum("bjhd,bhj,bjhe->bhde", k, w, v, precision=_HIGHEST)
    return o, new


def _lightning_chunk(c, qkv, q_scale, k_scale, state, positions, ids,
                     valid=None, heads=1, theta=10000.0, eps=1e-6):
    """Lightning attention of a (B, C) chunk over the carried state.
    ``qkv``: (B*C, 3 * H * D) ``[q | k | v]``, each head-major; ``q_scale``,
    ``k_scale``: (D,) the learned scales of the per-head RMSNorm of ``q``
    and ``k``; ``state``: (B, H, D, D) float32; ``positions``: (B,) of each
    row's first column.  ``q`` and ``k`` are normed, rotated (rotate-half,
    all ``D`` dims, at ``positions + column``) and ``q`` scaled by ``1/√D``.
    Returns ``(o, state')``, ``o`` (B*C, H * D) before the output norm."""
    b, chunk = ids.shape
    h = int(heads)
    x = _f32(qkv).reshape(b, chunk, 3, h, -1)
    d = x.shape[-1]
    at = _at(positions, ids)[:, :, None]
    q = _rotate(_rms(x[:, :, 0], _f32(q_scale), eps), at, theta) * d ** -0.5
    k = _rotate(_rms(x[:, :, 1], _f32(k_scale), eps), at, theta)
    rate, count = decay_rates(h), _count(ids, valid)
    if chunk == 1:
        o, new = _one_token(q[:, 0], k[:, 0], x[:, 0, 2], rate, _f32(state),
                            count > 0)
        o = o[:, None]
    else:
        o, new = _chunk(q, k, x[:, :, 2], rate, _f32(state), count)
    return o.reshape(b * chunk, h * d), new.astype(state.dtype)


_lightning_chunk_node = def_op("LightningChunk", _lightning_chunk)


def lightning_chunk_op(*inputs, name=None, **attrs):
    """``(o, state')`` nodes of :func:`_lightning_chunk`; ``C = 1`` is the
    one-token update."""
    return tuple_outputs(_lightning_chunk_node(*inputs, name=name, **attrs),
                         2)
