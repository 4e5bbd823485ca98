"""Granite-4.0-H (``granitemoehybrid``): Mamba-2 (SSD) layers 9:1 with NoPE
grouped-query attention, a dense SwiGLU after every mixer, muP multipliers
and a tied head — as decode graphs for
:class:`~hetu_tpu.serving.DecodeEngine`.

With ``x`` the float32 residual, ``n`` RMSNorm with a learned scale and ``r
= residual_multiplier``::

    x = embedding_multiplier · E[id];      x <- x + r · mixer_i(n(x));
    x <- x + r · W_out(silu(a) ⊙ b),       [a | b] = W_in n(x);
    logits = n(x) Eᵀ / logits_scaling      (the head IS the embedding)

* ``layer_types[i] == "attention"``: ``q, k, v = W y`` (``H`` query heads
  over ``G`` key heads of ``D``), no bias, NO positional term, causal
  ``softmax(q kᵀ · attention_multiplier) v``, ``W_o``;
* ``"mamba"``: ``[z | xBC | dt] = W_in y``; ``xBC <- silu(conv_K(xBC) +
  bias)`` (depthwise, causal); ``[x | B | C] = xBC``; per head ``Δ_t =
  softplus(dt_t + dt_bias_h)``, ``S_t = exp(Δ_t A_h) S_{t−1} + Δ_t x_t B_tᵀ``,
  ``y_t = S_t C_t + D_h x_t`` (``ops/ssd.py``); ``out = W_out[n_inner(y ⊙
  silu(z))]`` — the gate first, ONE norm over the whole inner width.

The model has no experts here (``num_local_experts`` 0): every layer's
feed-forward is the dense ``shared_mlp``.  One block definition serves the
one-token graph, the chunked graph and the full-sequence graph, as in
``solar_open2.py``.  States by kind: per attention layer ``k_cache_i`` /
``v_cache_i`` (``kv`` slabs); per Mamba layer ``conv_i`` (``recurrent``, (B,
K−1, E + 2GN) float32, the convolution's window) and ``ssd_i``
(``recurrent``, (B, H, P, N) float32).  Scopes: ``mix.gqa``, ``mix.ssm`` (the
state update and read-out inside it under ``ssd.update``), ``mlp``,
``lm_head``.
"""
from __future__ import annotations

import math

import numpy as np

from .. import ops
from ..graph.node import name_scope
from ..ops import kda, ssm
from .common import (build_decoder, cols as _cols, decoder_param_names,
                     swiglu_mlp)

KINDS = ("mamba", "attention")


class GraniteHybridConfig:
    """Sizes as published (granite-4.0-h-micro's are the defaults).
    ``param_dtype`` is the weights' storage type, ``cache_dtype`` that of
    the ``kv`` state; recurrent state is float32 always."""

    def __init__(self, vocab_size=100352, hidden_size=2048,
                 shared_intermediate_size=8192, num_hidden_layers=40,
                 layer_types=None, num_attention_heads=32,
                 num_key_value_heads=8, head_dim=None, mamba_n_heads=64,
                 mamba_d_head=64, mamba_d_state=128, mamba_n_groups=1,
                 mamba_d_conv=4, mamba_chunk_size=256,
                 embedding_multiplier=12.0, residual_multiplier=0.22,
                 attention_multiplier=0.015625, logits_scaling=8.0,
                 rms_norm_eps=1e-5, initializer_range=0.1,
                 param_dtype=np.float32, cache_dtype=np.float32,
                 batch_size=1):
        if num_attention_heads % num_key_value_heads \
                or mamba_n_heads % mamba_n_groups:
            raise ValueError("query heads must be a multiple of key heads, "
                             "Mamba heads of Mamba groups")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.shared_intermediate_size = int(shared_intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.layer_types = list(layer_types or [
            "attention" if i % 10 == 5 else "mamba"
            for i in range(self.num_hidden_layers)])
        if len(self.layer_types) != self.num_hidden_layers \
                or set(self.layer_types) - set(KINDS):
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of kinds "
                f"{sorted(set(self.layer_types))}; expected "
                f"{self.num_hidden_layers} of {KINDS}")
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim
                            or self.hidden_size // self.num_attention_heads)
        self.mamba_n_heads = int(mamba_n_heads)
        self.mamba_d_head = int(mamba_d_head)
        self.mamba_d_state = int(mamba_d_state)
        self.mamba_n_groups = int(mamba_n_groups)
        self.mamba_d_conv = int(mamba_d_conv)
        self.mamba_chunk_size = int(mamba_chunk_size)
        self.embedding_multiplier = float(embedding_multiplier)
        self.residual_multiplier = float(residual_multiplier)
        self.attention_multiplier = float(attention_multiplier)
        self.logits_scaling = float(logits_scaling)
        self.rms_norm_eps = float(rms_norm_eps)
        self.initializer_range = float(initializer_range)
        self.param_dtype = np.dtype(param_dtype)
        self.cache_dtype = np.dtype(cache_dtype)
        self.batch_size = int(batch_size)

    @classmethod
    def tiny(cls, **over):
        """The test preset: Mamba-2, attention, Mamba-2, Mamba-2 at toy
        widths, two groups of two Mamba heads, two query heads a key
        head; multipliers under which 32-wide branches still move the
        residual (at the published ones the input's embedding, 12-fold,
        would drown them and the tied head would echo the input)."""
        kw = dict(vocab_size=96, hidden_size=32, shared_intermediate_size=48,
                  num_hidden_layers=4,
                  layer_types=["mamba", "attention", "mamba", "mamba"],
                  num_attention_heads=4, num_key_value_heads=2, head_dim=8,
                  mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
                  mamba_n_groups=2, mamba_chunk_size=8,
                  embedding_multiplier=2.0, residual_multiplier=0.5,
                  attention_multiplier=0.25, logits_scaling=4.0,
                  initializer_range=0.3)
        kw.update(over)
        return cls(**kw)

    def layer_kind(self, i):
        return "gqa" if self.layer_types[i] == "attention" else "ssm"


def _mix_gqa(g, y, i, name):
    """Grouped-query attention over the layer's own growable slabs: no
    positional term, no gate, the scores scaled by
    ``attention_multiplier``."""
    cfg = g.cfg
    d, hd = cfg.hidden_size, cfg.head_dim
    q_w, kv_w = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    qkv = g.dense(y, name + ".qkv", d, q_w + 2 * kv_w)
    slab = dict(batch=cfg.batch_size, heads=cfg.num_key_value_heads,
                length=g.max_len, head_dim=hd)
    new = []
    for leaf, at in (("k", q_w), ("v", q_w + kv_w)):
        cache = g.state(f"{leaf}_cache_{i}", "kv", None, cfg.cache_dtype,
                        **slab)
        new.append(ops.kv_cache_append_op(
            cache, kda.gqa_rows_op(_cols(qkv, at, at + kv_w), g.ids,
                                   head_dim=hd), g.positions, *g.valid))
    g.fetches += new
    att = kda.gqa_attention_kv_op(_cols(qkv, 0, q_w), *new, g.positions,
                                  g.ids, head_dim=hd,
                                  scale=cfg.attention_multiplier)
    return g.dense(att, name + ".o", q_w, d)


def _mix_ssm(g, y, i, name):
    """Mamba-2: one in-projection -> short causal convolution, silu -> the
    scalar-decay recurrence over the carried matrix state -> gate, one
    norm over the inner width -> out."""
    cfg = g.cfg
    d, h, p = cfg.hidden_size, cfg.mamba_n_heads, cfg.mamba_d_head
    n, k, b = cfg.mamba_d_state, cfg.mamba_d_conv, cfg.batch_size
    e = h * p
    conv_w = e + 2 * cfg.mamba_n_groups * n
    window = g.state(f"conv_{i}", "recurrent", (b, k - 1, conv_w),
                     np.float32)
    state = g.state(f"ssd_{i}", "recurrent", (b, h, p, n), np.float32)
    zxd = g.dense(y, name + ".in_proj", d, e + conv_w + h)
    xbc, window2 = ssm.conv_state_shift_op(
        _cols(zxd, e, e + conv_w), window,
        g.var(name + ".conv.weight", (k, conv_w), 0.0, 1.0 / math.sqrt(k)),
        g.var(name + ".conv.bias", (conv_w,)), g.ids, *g.valid)
    # Mamba-2's own initialisation: A in [1, 16], a step of 1e-3 .. 1e-1
    # (dt_bias its inverse softplus, about log of it), D = 1
    scanned, state2 = ops.ssd_chunk_op(
        xbc, _cols(zxd, e + conv_w, None),
        g.var(name + ".dt_bias", (h,), -4.6, 1.0),
        g.var(name + ".A_log", (h,), 1.4, 0.7),
        g.var(name + ".D", (h,), 1.0), state, g.ids, *g.valid, heads=h,
        groups=cfg.mamba_n_groups, segment=cfg.mamba_chunk_size)
    g.fetches += [window2, state2]
    gated = ops.silu_gate_op(_cols(zxd, 0, e), scanned)
    return g.dense(g.norm(gated, name + ".norm", e), name + ".out_proj", e,
                   d)


def _layer(g, x, i, name):
    cfg = g.cfg
    kind = cfg.layer_kind(i)
    with name_scope("mix." + kind):
        y = g.norm(x, name + ".ln1")
        mixed = _mix_gqa(g, y, i, name + ".attn") if kind == "gqa" \
            else _mix_ssm(g, y, i, name + ".mamba")
        x = x + mixed * cfg.residual_multiplier
    with name_scope("mlp"):
        return x + swiglu_mlp(
            g, g.norm(x, name + ".ln2"), name + ".mlp",
            cfg.shared_intermediate_size) * cfg.residual_multiplier


def _build(cfg, chunk, max_len, name, **kw):
    g, logits, tokens, _ = build_decoder(
        cfg, _layer, chunk, max_len, name,
        embed_scale=cfg.embedding_multiplier,
        logit_scale=1.0 / cfg.logits_scaling, tied_head=True, **kw)
    return g, logits, tokens


def granite_hybrid_decode_graph(cfg, max_len, name="granite"):
    """One-token decode graph.  Feeds ``input_ids`` (B, 1), ``positions``
    (B,) and the state placeholders (module docstring).  Returns ``(feeds,
    logits, state_fetches, tokens)``: ``tokens`` (B,) int32 the greedy
    token of each row."""
    g, logits, tokens = _build(cfg, 1, max_len, name, with_valid=False)
    return g.feeds, logits, g.fetches, tokens


def granite_hybrid_decode_chunked_graph(cfg, max_len, chunk=4,
                                        name="granite"):
    """Chunked-prefill twin: ``input_ids`` (B, C), ``positions`` (B,) of
    each row's first column, ``valid`` (B,) columns consumed; the same
    weights by name and the same states.  ``logits`` / ``tokens`` are of
    each row's last consumed column."""
    g, logits, tokens = _build(cfg, int(chunk), max_len, name)
    return g.feeds, logits, g.fetches, tokens


def granite_hybrid_lm_graph(cfg, seq_len, name="granite"):
    """Full-sequence forward over zero states (tests): feed ``input_ids``
    (B, T); returns ``(feeds, logits)``, ``logits`` (B*T, vocab)."""
    g, logits, _ = _build(cfg, int(seq_len), int(seq_len), name, fed=False,
                          with_valid=False)
    return g.feeds, logits


def param_names(cfg, name="granite"):
    """Checkpoint names and shapes of every variable, in graph order (the
    head is the embedding: there is no ``lm_head``)."""
    return decoder_param_names(granite_hybrid_lm_graph, cfg, name)


__all__ = ["GraniteHybridConfig", "granite_hybrid_decode_graph",
           "granite_hybrid_decode_chunked_graph", "granite_hybrid_lm_graph",
           "param_names"]
