"""Solar-Open2: gated-delta-rule linear attention 3:1 with gated NoPE
grouped-query attention, every layer a dropless mixture of experts with a
shared expert — as decode graphs for :class:`~hetu_tpu.serving.
DecodeEngine`, of the SHARE one chip of a tensor- and expert-parallel group
holds.

With ``x`` the float32 residual and ``n`` RMSNorm with a learned scale::

    x <- x + mixer_i(n(x));   x <- x + moe(n(x));   logits = n(x) W_head

* layer ``i`` is **GQA** where ``i % (gqa_interval + 1) == 0``: ``[q k v g]
  = W y``, ``H`` query heads over ``H / 8`` key heads, causal softmax of
  ``q·k/√D`` with no positional term, ``out = W_o[att ⊙ sigmoid(g)]``;
* else **KDA**, the channel-wise gated delta rule of Kimi Linear
  (arXiv:2510.26692): ``q, k, v = silu(conv_K(W y))``, ``q`` and ``k``
  L2-normalised per head, decay ``a_t = exp(−exp(A_h) softplus(W_f↑ W_f↓
  y + b))``, ``β_t = 2 sigmoid(w_β y)``, ``S_t = (I − β_t k_t k_tᵀ)
  Diag(a_t) S_{t−1} + β_t k_t v_tᵀ``, ``o_t = S_tᵀ q_t``, ``out =
  W_o[n_head(o_t) ⊙ sigmoid(W_g↑ W_g↓ y)]`` (``ops/kda.py``);
* **MoE**: ``s = sigmoid(W_r y)`` over ALL experts in float32, the top
  ``k`` of ``s + bias`` chosen, weights ``s_e / Σ_chosen s``; ``moe(y) =
  Σ_{e chosen ∧ held} w_e E_e(y) + E_shared(y)``, ``E(y) = W_d(silu(W_g y) ⊙
  W_u y)``.  No capacity, no dropped token (``ops/moe.py``).

**The share.**  ``num_attention_heads``, ``num_key_value_heads`` and
``linear_attn_heads`` count the heads HELD; ``held = (first, count)`` the
routed experts held of ``n_routed_experts``, which the router keeps at its
full width; ``vocab_size`` the rows of the embedding and of the head.  The
graph computes what its heads and its experts give and the shared expert
whole; a tensor-parallel group would add its chips' results up after each
mixer and each expert layer, and nothing here stands in for that.

One block definition serves the one-token graph, the chunked graph and the
full-sequence graph, as in ``phi4flash.py``.  State placeholders declare
their kind: ``kv`` slabs for a GQA layer's keys and values, ``recurrent``
for a KDA layer's ``(H, D, D)`` float32 state and its convolution window.
Scopes: ``mix.gqa``, ``mix.kda``, ``moe.route``, ``moe.experts``,
``moe.shared``, ``lm_head``.  Beside the greedy token ids each graph hands
back ``choices``, the chosen expert ids ``(B, C, layers, k)`` int16:
``DecodeEngine(aux={"moe_choices": choices}, aux_fold=...)``.
"""
from __future__ import annotations

import math

import numpy as np

from .. import ops
from ..graph.node import name_scope
from ..ops import kda, ssm
from .common import (build_decoder, choice_counters, cols as _cols,
                     decoder_param_names, moe_block)


class SolarOpen2Config:
    """Sizes of the share.  ``param_dtype`` is the weights' storage type,
    ``cache_dtype`` that of the ``kv`` state; recurrent state is float32
    always."""

    def __init__(self, vocab_size=196608, hidden_size=4096,
                 num_hidden_layers=48, num_attention_heads=64,
                 num_key_value_heads=8, head_dim=128, linear_attn_heads=64,
                 linear_head_dim=128, short_conv_kernel_size=4,
                 gate_rank=128, gqa_interval=3, moe_intermediate_size=1280,
                 n_routed_experts=320, held=None, num_experts_per_tok=8,
                 routed_scaling_factor=1.0, rms_norm_eps=1e-5,
                 initializer_range=0.02,
                 param_dtype=np.float32, cache_dtype=np.float32,
                 batch_size=1):
        if num_attention_heads % num_key_value_heads:
            raise ValueError("query heads must be a multiple of key heads")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.linear_attn_heads = int(linear_attn_heads)
        self.linear_head_dim = int(linear_head_dim)
        self.short_conv_kernel_size = int(short_conv_kernel_size)
        self.gate_rank = int(gate_rank)
        self.gqa_interval = int(gqa_interval)
        self.moe_intermediate_size = int(moe_intermediate_size)
        self.n_routed_experts = int(n_routed_experts)
        first, count = held or (0, self.n_routed_experts)
        if not 0 <= first <= first + count <= self.n_routed_experts:
            raise ValueError(f"held {held} lies outside the "
                             f"{self.n_routed_experts} routed experts")
        self.held = (int(first), int(count))
        self.num_experts_per_tok = int(num_experts_per_tok)
        self.routed_scaling_factor = float(routed_scaling_factor)
        self.rms_norm_eps = float(rms_norm_eps)
        self.initializer_range = float(initializer_range)
        self.param_dtype = np.dtype(param_dtype)
        self.cache_dtype = np.dtype(cache_dtype)
        self.batch_size = int(batch_size)

    @classmethod
    def tiny(cls, **over):
        """The test preset: one period (GQA, KDA, KDA, KDA) at toy widths,
        an eighth of 64 experts held."""
        kw = dict(vocab_size=96, hidden_size=32, num_hidden_layers=4,
                  num_attention_heads=8, num_key_value_heads=1, head_dim=16,
                  linear_attn_heads=8, linear_head_dim=8, gate_rank=8,
                  moe_intermediate_size=16, n_routed_experts=64,
                  held=(24, 8), num_experts_per_tok=4)
        kw.update(over)
        return cls(**kw)

    def layer_kind(self, i):
        return "kda" if i % (self.gqa_interval + 1) else "gqa"

    def choice_counters(self):
        """``fold`` for ``DecodeEngine(aux_fold=)`` over the held experts
        (:func:`~hetu_tpu.models.common.choice_counters`)."""
        return choice_counters(self.held)


def _mix_gqa(g, y, i, name):
    """Gated grouped-query attention over the layer's own growable slabs,
    no positional term."""
    cfg = g.cfg
    d, hd = cfg.hidden_size, cfg.head_dim
    q_w, kv_w = cfg.num_attention_heads * hd, cfg.num_key_value_heads * hd
    qkvg = g.dense(y, name + ".qkvg", d, 2 * q_w + 2 * kv_w)
    slab = dict(batch=cfg.batch_size, heads=cfg.num_key_value_heads,
                length=g.max_len, head_dim=hd)
    new = []
    for leaf, at in (("k", q_w), ("v", q_w + kv_w)):
        cache = g.state(f"{leaf}_cache_{i}", "kv", None, cfg.cache_dtype,
                        **slab)
        new.append(ops.kv_cache_append_op(
            cache, kda.gqa_rows_op(_cols(qkvg, at, at + kv_w), g.ids,
                                   head_dim=hd), g.positions, *g.valid))
    g.fetches += new
    att = kda.gqa_attention_kv_op(_cols(qkvg, 0, q_w), *new, g.positions,
                                  g.ids, head_dim=hd)
    gate = _cols(qkvg, q_w + 2 * kv_w, None)
    return g.dense(kda.sigmoid_gate_op(gate, att), name + ".o", q_w, d)


def _mix_kda(g, y, i, name):
    """KDA: projections -> short causal convolution, silu -> the gated
    delta rule over the carried state -> per-head norm, gate -> out."""
    cfg = g.cfg
    d, h, r = cfg.hidden_size, cfg.linear_attn_heads, cfg.gate_rank
    e, k = h * cfg.linear_head_dim, cfg.short_conv_kernel_size
    b = cfg.batch_size
    conv = g.state(f"conv_{i}", "recurrent", (b, k - 1, 3 * e), np.float32)
    state = g.state(f"kda_{i}", "recurrent",
                    (b, h, cfg.linear_head_dim, cfg.linear_head_dim),
                    np.float32)
    qkv, conv2 = ssm.conv_state_shift_op(
        g.dense(y, name + ".qkv", d, 3 * e), conv,
        g.var(name + ".conv.weight", (k, 3 * e), 0.0, 1.0 / math.sqrt(k)),
        g.ids, *g.valid, bias=False)
    f = g.dense(g.dense(y, name + ".f_down", d, r), name + ".f_up", r, e) \
        + g.var(name + ".dt_bias", (e,), -3.0, 1.0)
    o, state2 = kda.kda_chunk_op(
        qkv, f, g.dense(y, name + ".beta", d, h),
        g.var(name + ".A_log", (h,), 1.0, 0.5), state, g.ids, *g.valid,
        heads=h)
    g.fetches += [conv2, state2]
    gate = g.dense(g.dense(y, name + ".g_down", d, r), name + ".g_up", r, e)
    normed = kda.kda_out_op(
        o, gate, g.var(name + ".norm.scale", (cfg.linear_head_dim,), 1.0),
        eps=cfg.rms_norm_eps)
    return g.dense(normed, name + ".o", e, d)


def _layer(g, x, i, name):
    kind = g.cfg.layer_kind(i)
    with name_scope("mix." + kind):
        y = g.norm(x, name + ".ln1")
        x = x + (_mix_gqa(g, y, i, name + ".attn") if kind == "gqa"
                 else _mix_kda(g, y, i, name + ".kda"))
    return moe_block(g, x, name)


def _build(cfg, chunk, max_len, name, **kw):
    return build_decoder(cfg, _layer, chunk, max_len, name, **kw)


def solar_open2_decode_graph(cfg, max_len, name="solar"):
    """One-token decode graph.  Feeds ``input_ids`` (B, 1), ``positions``
    (B,) and the state placeholders: per GQA layer ``k_cache_i`` /
    ``v_cache_i``, ``kv`` slabs; per KDA layer ``conv_i`` (B, K-1, 3E) and
    ``kda_i`` (B, H, D, D), ``recurrent``.  Returns ``(feeds, logits,
    state_fetches, tokens, choices)``: ``tokens`` (B,) int32 the greedy
    token of each row, ``choices`` (B, 1, layers, k) int16 the expert ids
    its token chose in every layer."""
    g, logits, tokens, choices = _build(cfg, 1, max_len, name,
                                        with_valid=False)
    return g.feeds, logits, g.fetches, tokens, choices


def solar_open2_decode_chunked_graph(cfg, max_len, chunk=4, name="solar"):
    """Chunked-prefill twin: ``input_ids`` (B, C), ``positions`` (B,) of
    each row's first column, ``valid`` (B,) columns consumed; the same
    weights by name and the same states.  ``logits`` / ``tokens`` are of
    each row's last consumed column, ``choices`` (B, C, layers, k) of
    every column."""
    g, logits, tokens, choices = _build(cfg, int(chunk), max_len, name)
    return g.feeds, logits, g.fetches, tokens, choices


def solar_open2_lm_graph(cfg, seq_len, name="solar"):
    """Full-sequence forward over zero states (tests): feed ``input_ids``
    (B, T); returns ``(feeds, logits, choices)``, ``logits`` (B*T,
    vocab)."""
    g, logits, _, choices = _build(cfg, int(seq_len), int(seq_len), name,
                                   fed=False, with_valid=False)
    return g.feeds, logits, choices


def param_names(cfg, name="solar"):
    """Checkpoint names and shapes of every variable, in graph order."""
    return decoder_param_names(solar_open2_lm_graph, cfg, name)


__all__ = ["SolarOpen2Config", "solar_open2_decode_graph",
           "solar_open2_decode_chunked_graph", "solar_open2_lm_graph",
           "param_names"]
