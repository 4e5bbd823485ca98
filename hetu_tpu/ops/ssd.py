"""Decode-time op of Mamba-2 (state-space duality, arXiv:2405.21060): a
linear recurrence with ONE input-dependent decay a head over a MATRIX state,

    Δ_t = softplus(dt_t + dt_bias_h),    a_t = exp(Δ_t A_h),   A_h = −exp(A_log_h)
    S_t = a_t S_{t−1} + Δ_t x_t B_tᵀ,    y_t = S_t C_t + D_h x_t,

``x_t`` the head's ``P`` channels, ``B_t`` and ``C_t`` (``N`` wide) shared by
the heads of a group, over a ``recurrent`` state ``(B, H, P, N)`` float32
with the state axis ``N`` minor (128 lanes at the published width: 2 MiB a
layer and slot at 64 heads of 64), as one layer kind of a hybrid decoder
(``hetu_tpu/models/granite_hybrid.py``).  Mamba-1 (:mod:`~hetu_tpu.ops.ssm`)
decays every channel of an ``(N, E)`` state on its own and has to scan; here
the decay is a scalar a head, so a chunk is a handful of matrix products.
The conventions are those of :mod:`~hetu_tpu.ops.ssm`: a ``(B, C)`` chunk of
tokens a call, the residual stream flattened to ``(B*C, ·)``, ``(B, C)``
recovered from the ``ids`` feed, an optional trailing ``valid`` input ``(B,)``
of the columns that are real.

**One token** (``C = 1``): the state is read once and written once —
elementwise multiply-and-sum, no float32 operand through the MXU.

**A chunk**, with ``ℓ_t = Σ_{s≤t} Δ_s A_h`` (never positive):

    y_t = Σ_{s≤t} e^{ℓ_t−ℓ_s} (C_t·B_s) Δ_s x_s + e^{ℓ_t} S_0 C_t + D_h x_t
    S_C = e^{ℓ_C} S_0 + Σ_s e^{ℓ_C−ℓ_s} Δ_s x_s B_sᵀ

— no scan and no per-token pass over the state (a chunked program of scanned
64 MB states does not come back from the TPU compiler, ROADMAP.md D23; these
are 134 MB a layer at 64 slots).  Only differences ``ℓ_t − ℓ_s`` with ``s ≤
t`` are exponentiated, masked BEFORE the ``exp``.  A column at or past
``valid`` has ``Δ = 0``: it decays nothing and adds nothing, so the state
advances by exactly the columns consumed and ``valid = 0`` leaves it as it
was (the outputs of such columns are don't-cares).  A chunk longer than
``segment`` columns (the full-sequence graph) runs segment after segment
with the state carried, unrolled.  The products run at ``HIGHEST`` precision
(float32 operands; they are of set-up's steps).  The update and the read-out
of either form lie under the scope ``ssd.update``.
"""
import jax
import jax.numpy as jnp

from .base import def_op, tuple_outputs
from .ssm import _count, _f32

_HIGHEST = jax.lax.Precision.HIGHEST


def _one_token(x, delta, la, bm, cm, state):
    """``x``: (B, H, P); ``delta``, ``la`` (= Δ A): (B, H); ``bm``, ``cm``:
    (B, G, N); ``state``: (B, H, P, N)."""
    b, g, _ = bm.shape
    s = state.reshape(b, g, -1, *state.shape[2:])            # (B, G, R, P, N)
    new = jnp.exp(la).reshape(b, g, -1, 1, 1) * s \
        + (delta[..., None] * x).reshape(s.shape[:4] + (1,)) \
        * bm[:, :, None, None, :]
    y = jnp.sum(new * cm[:, :, None, None, :], axis=-1)
    return y.reshape(x.shape), new.reshape(state.shape)


def _chunk(x, delta, la, bm, cm, state):
    """``x``: (B, C, H, P); ``delta``, ``la``: (B, C, H); ``bm``, ``cm``:
    (B, C, G, N), ``G`` groups of ``H / G`` heads; ``state``: (B, H, P,
    N)."""
    b, chunk, h, _ = x.shape
    g = bm.shape[2]
    cum = jnp.cumsum(la, axis=1)                                 # (B, C, H)
    col = jnp.arange(chunk, dtype=jnp.int32)
    gap = cum[:, :, None, :] - cum[:, None, :, :]                # ℓ_t − ℓ_s
    decay = jnp.exp(jnp.where((col[:, None] >= col[None, :])[None, :, :,
                                                             None],
                              gap, -jnp.inf))                    # (B,T,S,H)
    cb = jnp.einsum("btgn,bsgn->btsg", cm, bm, precision=_HIGHEST)
    w = jnp.repeat(cb, h // g, axis=-1) * decay * delta[:, None, :, :]
    grown = jnp.exp(cum)                                         # e^{ℓ_t}
    s_g = state.reshape(b, g, h // g, *state.shape[2:])
    y = jnp.einsum("btsh,bshp->bthp", w, x, precision=_HIGHEST) \
        + jnp.einsum("bgrpn,btgn->btgrp", s_g, cm,
                     precision=_HIGHEST).reshape(x.shape) * grown[..., None]
    left = jnp.exp(cum[:, -1:, :] - cum) * delta                 # (B, S, H)
    xs = (x * left[..., None]).reshape(b, chunk, g, h // g, -1)
    new = grown[:, -1][..., None, None] * state \
        + jnp.einsum("bsgrp,bsgn->bgrpn", xs, bm,
                     precision=_HIGHEST).reshape(state.shape)
    return y, new


def _ssd_chunk(c, xbc, dt, dt_bias, a_log, d, state, ids, valid=None,
               heads=1, groups=1, segment=256):
    """Mamba-2 state update of a (B, C) chunk.  ``xbc``: (B*C, E + 2 G N)
    the convolved, activated ``[x | B | C]``, ``E = H · P``; ``dt``: (B*C,
    H) the projected step sizes before bias and softplus; ``dt_bias``,
    ``a_log``, ``d``: (H,); ``state``: (B, H, P, N) float32.  Returns ``(y,
    state')``, ``y`` (B*C, E) BEFORE the output gate and norm."""
    from ..metrics import record_ssd_call
    b, chunk = ids.shape
    h, g = int(heads), int(groups)
    p, n = state.shape[2:]
    e = h * p
    record_ssd_call(chunk, h, p, n)
    xbc = _f32(xbc).reshape(b, chunk, -1)
    x = xbc[..., :e].reshape(b, chunk, h, p)
    bm = xbc[..., e:e + g * n].reshape(b, chunk, g, n)
    cm = xbc[..., e + g * n:].reshape(b, chunk, g, n)
    live = jnp.arange(chunk, dtype=jnp.int32)[None, :] \
        < _count(ids, valid)[:, None]                            # (B, C)
    delta = jax.nn.softplus(_f32(dt).reshape(b, chunk, h) + _f32(dt_bias)) \
        * live[..., None]
    la = -jnp.exp(_f32(a_log)) * delta
    with jax.named_scope("ssd.update"):
        if chunk == 1:
            y, new = _one_token(x[:, 0], delta[:, 0], la[:, 0], bm[:, 0],
                                cm[:, 0], _f32(state))
            y = y[:, None]
        else:
            ys, new = [], _f32(state)
            for at in range(0, chunk, int(segment)):
                to = at + int(segment)
                y, new = _chunk(x[:, at:to], delta[:, at:to], la[:, at:to],
                                bm[:, at:to], cm[:, at:to], new)
                ys.append(y)
            y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
    y = y + _f32(d)[:, None] * x
    return y.reshape(b * chunk, e), new.astype(state.dtype)


_ssd_chunk_node = def_op("SSDChunk", _ssd_chunk)


def ssd_chunk_op(*inputs, name=None, **attrs):
    """``(y, state')`` nodes of :func:`_ssd_chunk`."""
    return tuple_outputs(_ssd_chunk_node(*inputs, name=name, **attrs), 2)


def ssd_step_op(*inputs, name=None, **attrs):
    """The one-token update: :func:`_ssd_chunk` at ``C = 1`` (the state read
    once and written once, no product)."""
    return ssd_chunk_op(*inputs, name=name, **attrs)
