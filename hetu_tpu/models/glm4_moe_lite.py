"""GLM-4.7-Flash (``glm4_moe_lite``): multi-head LATENT attention in every
layer, a leading dense SwiGLU layer, then dropless mixtures of experts —
sigmoid top-4 of 64 scaled by 1.8, with a shared expert — as decode graphs
for :class:`~hetu_tpu.serving.DecodeEngine`, of the SHARE one chip of an
expert-parallel group holds.

With ``x`` the float32 residual and ``n`` RMSNorm with a learned scale::

    x <- x + mla(n(x));   x <- x + ffn_i(n(x));   logits = n(x) W_head

* **MLA** (``ops/mla.py``): ``c_q = n(W_dq y)``, per head ``[q_nope;
  q_rope] = W_uq,h c_q``; ``[c_kv; k_r] = W_dkv y``, ``c = n(c_kv)``,
  ``k_rope = R_t(k_r)`` ONE row for all heads, ``q_rope <- R_t(q_rope)``;
  ``[k_nope,h; v_h] = W_ukv,h c``; scores ``(q_nope,h · k_nope,h + q_rope,h ·
  k_rope) / √(nope + rope)``, causal softmax, ``out = W_o [Σ p v_h]_h``.
  **The cache is** ``(c, k_rope)``, one row a token and layer.  The served
  graphs ABSORB the up-projections (``q'_h = W_uk,hᵀ q_nope,h`` scores
  against ``c``, which is also the value); the full-sequence graph
  materialises keys and values per head.  The same function, written twice.
* **FFN**: layers ``< first_k_dense_replace`` a SwiGLU of
  ``intermediate_size``; the others ``s = sigmoid(W_r y)`` over ALL experts
  in float32, the top ``k`` of ``s + bias``, weights ``routed_scaling_factor
  · s_e / Σ_chosen s``, ``Σ_{chosen ∧ held} w_e E_e(y) + E_shared(y)``
  (``models/common.py:moe_block``, shared with ``solar_open2.py``).

**The share.**  ``held = (first, count)`` the routed experts held of
``n_routed_experts``, which the router keeps at its full width; attention
(all heads), the shared expert, the router, the dense layer, embedding and
head are replicated over the group, each chip serving its own streams and
keeping their latent cache.  An expert-parallel group would bring each
expert its tokens from all chips and take their results back (two
all-to-alls a layer); nothing here stands in for that.  The published
next-token module (``num_nextn_predict_layers``) is not part of next-token
logits and is not built.

State: per layer ONE ``kv`` slab of one head, ``latent_cache_i``, rows of
``ops.mla.latent_lanes(rank, rope)`` lanes.  Scopes: ``mix.mla``, ``mlp``
(the dense layers), ``moe.route``, ``moe.experts``, ``moe.shared``,
``lm_head``.  Beside the greedy token ids each graph hands back
``choices``, the chosen expert ids ``(B, C, expert layers, k)`` int16.
"""
from __future__ import annotations

import numpy as np

from .. import ops
from ..graph.node import name_scope
from ..ops import mla
from .common import (build_decoder, choice_counters, decoder_param_names,
                     moe_block, swiglu_mlp)


class Glm4MoeLiteConfig:
    """Sizes of the share.  ``param_dtype`` is the weights' storage type,
    ``cache_dtype`` that of the latent cache."""

    def __init__(self, vocab_size=154880, hidden_size=2048,
                 num_hidden_layers=47, num_attention_heads=20,
                 q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=192,
                 qk_rope_head_dim=64, v_head_dim=256, rope_theta=1e6,
                 intermediate_size=10240, first_k_dense_replace=1,
                 moe_intermediate_size=1536, n_routed_experts=64, held=None,
                 num_experts_per_tok=4, routed_scaling_factor=1.8,
                 rms_norm_eps=1e-5, initializer_range=0.02,
                 param_dtype=np.float32, cache_dtype=np.float32,
                 batch_size=1):
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.q_lora_rank = int(q_lora_rank)
        self.kv_lora_rank = int(kv_lora_rank)
        self.qk_nope_head_dim = int(qk_nope_head_dim)
        self.qk_rope_head_dim = int(qk_rope_head_dim)
        self.v_head_dim = int(v_head_dim)
        self.rope_theta = float(rope_theta)
        self.intermediate_size = int(intermediate_size)
        self.first_k_dense_replace = int(first_k_dense_replace)
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
        """The test preset: the dense layer and 4 expert layers at toy
        widths, an eighth of 64 experts held."""
        kw = dict(vocab_size=96, hidden_size=32, num_hidden_layers=5,
                  num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
                  qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16,
                  intermediate_size=48, moe_intermediate_size=16,
                  n_routed_experts=64, held=(24, 8), num_experts_per_tok=4)
        kw.update(over)
        return cls(**kw)

    @property
    def latent_lanes(self):
        """Lanes of a stored cache row (``ops.mla.latent_lanes``)."""
        return mla.latent_lanes(self.kv_lora_rank, self.qk_rope_head_dim)

    def layer_kind(self, i):
        return "dense" if i < self.first_k_dense_replace else "moe"

    def choice_counters(self):
        """``fold`` for ``DecodeEngine(aux_fold=)`` over the held experts
        of the expert layers (a dense layer chooses nothing and is not in
        ``choices``)."""
        return choice_counters(self.held)


def _mix_mla(g, y, i, name):
    """Latent attention: low-rank query, one compressed cache row a token,
    the absorbed read of the cache (served) or keys and values per head
    (the full-sequence graph)."""
    cfg = g.cfg
    d, h, rank = cfg.hidden_size, cfg.num_attention_heads, cfg.kv_lora_rank
    nope, rope, v = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim)
    cq = g.norm(g.dense(y, name + ".q_down", d, cfg.q_lora_rank),
                name + ".q_norm", cfg.q_lora_rank)
    q = mla.rope_op(
        g.dense(cq, name + ".q_up", cfg.q_lora_rank, h * (nope + rope)),
        g.positions, g.ids, theta=cfg.rope_theta, head_dim=nope + rope,
        rope_dim=rope)
    rows = mla.mla_latent_rows_op(
        g.dense(y, name + ".kv_down", d, rank + rope),
        g.var(name + ".kv_norm.scale", (rank,), 1.0), g.positions, g.ids,
        rank=rank, theta=cfg.rope_theta, eps=cfg.rms_norm_eps,
        lanes=cfg.latent_lanes)
    w_ukv = g.var(name + ".kv_up.weight", (rank, h * (nope + v)))
    sizes = dict(heads=h, nope=nope, rank=rank)
    if g.fed:
        cache = g.state(f"latent_cache_{i}", "kv", None, cfg.cache_dtype,
                        batch=cfg.batch_size, heads=1, length=g.max_len,
                        head_dim=cfg.latent_lanes)
        slab = ops.kv_cache_append_op(cache, rows, g.positions, *g.valid)
        g.fetches.append(slab)
        att = mla.mla_attention_kv_op(q, slab, w_ukv, g.positions, g.ids,
                                      **sizes)
    else:
        att = mla.mla_attention_op(q, rows, w_ukv, g.ids, **sizes)
    return g.dense(att, name + ".o", h * v, d)


def _layer(g, x, i, name):
    with name_scope("mix.mla"):
        x = x + _mix_mla(g, g.norm(x, name + ".ln1"), i, name + ".attn")
    if g.cfg.layer_kind(i) == "moe":
        return moe_block(g, x, name)
    with name_scope("mlp"):
        return x + swiglu_mlp(g, g.norm(x, name + ".ln2"), name + ".mlp",
                              g.cfg.intermediate_size)


def glm4_moe_lite_decode_graph(cfg, max_len, name="glm"):
    """One-token decode graph.  Feeds ``input_ids`` (B, 1), ``positions``
    (B,) and per layer ``latent_cache_i``, a ``kv`` slab of one head.
    Returns ``(feeds, logits, state_fetches, tokens, choices)``: ``tokens``
    (B,) int32 the greedy token of each row, ``choices`` (B, 1, expert
    layers, k) int16 the expert ids its token chose."""
    g, logits, tokens, choices = build_decoder(cfg, _layer, 1, max_len, name,
                                               with_valid=False)
    return g.feeds, logits, g.fetches, tokens, choices


def glm4_moe_lite_decode_chunked_graph(cfg, max_len, chunk=4, name="glm"):
    """Chunked-prefill twin: ``input_ids`` (B, C), ``positions`` (B,) of
    each row's first column, ``valid`` (B,) columns consumed; the same
    weights by name and the same states.  ``logits`` / ``tokens`` are of
    each row's last consumed column, ``choices`` of every column."""
    g, logits, tokens, choices = build_decoder(cfg, _layer, int(chunk),
                                               max_len, name)
    return g.feeds, logits, g.fetches, tokens, choices


def glm4_moe_lite_lm_graph(cfg, seq_len, name="glm"):
    """Full-sequence forward from position 0 with keys and values
    MATERIALISED per head (tests): feed ``input_ids`` (B, T); returns
    ``(feeds, logits, choices)``, ``logits`` (B*T, vocab)."""
    g, logits, _, choices = build_decoder(cfg, _layer, int(seq_len),
                                          int(seq_len), name, fed=False,
                                          with_valid=False)
    return g.feeds, logits, choices


def param_names(cfg, name="glm"):
    """Checkpoint names and shapes of every variable, in graph order."""
    return decoder_param_names(glm4_moe_lite_lm_graph, cfg, name)


__all__ = ["Glm4MoeLiteConfig", "glm4_moe_lite_decode_graph",
           "glm4_moe_lite_decode_chunked_graph", "glm4_moe_lite_lm_graph",
           "param_names"]
