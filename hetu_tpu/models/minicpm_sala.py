"""MiniCPM-SALA: trainable block-sparse attention (InfLLM-v2, the
``minicpm4`` mixer) 1:3 with Lightning linear attention, a dense SwiGLU
after every mixer, muP scalings — as decode graphs for
:class:`~hetu_tpu.serving.DecodeEngine`.

With ``x`` the float32 residual, ``n`` RMSNorm with a learned scale and ``ρ
= scale_depth / √mup_denominator``::

    x = scale_emb · E[id];   x <- x + ρ · mixer_i(n(x));
    x <- x + ρ · W_d(silu(W_g n(x)) ⊙ W_u n(x));
    logits = W_head(n(x) / (hidden_size / dim_model_base))

* ``mixer_types[i] == "lightning-attn"``: ``q, k, v = W y`` (``H`` heads of
  ``D``), ``q`` and ``k`` normed per head (a learned ``D``-scale each) and
  rotated (rotate-half, all ``D`` dims), ``S_t = λ_h S_{t−1} + k_tᵀ v_t``,
  ``o_t = (q_t / √D) S_t``, ``out = W_o[n_head(o) ⊙ sigmoid(W_gate y)]``
  (``ops/lightning.py``);
* ``"minicpm4"``: ``q = n_head(W_q y)`` (``H`` heads), ``k = n_head(W_k y)``,
  ``v = W_v y`` (``G`` key heads), no positional term; below ``dense_len``
  keys a query attends to all, past it to the blocks an indexer over
  mean-pooled compressed keys chose (``ops/sparse_attention.py``); ``out =
  W_o[att ⊙ sigmoid(W_gate y)]``.

One block definition serves the one-token graph, the chunked graph and the
full-sequence graph, as in ``solar_open2.py``.  States by kind: per sparse
layer ``k_cache_i`` / ``v_cache_i`` (``kv`` slabs), ``index_i`` (``index``:
the compressed keys, one row per ``kernel_stride`` positions) and ``pool_i``
(``recurrent``: the two open pooling sums); per Lightning layer
``lightning_i`` (``recurrent``, ``(H, D, D)`` float32).  Scopes:
``mix.sparse``, ``mix.lightning``, ``mlp``, ``lm_head``.  Beside the greedy
token ids each graph hands back ``blocks``, the far blocks every query chose
``(B, C, sparse layers, G, topk)`` int16 (the window is arithmetic; ``-1``
where a query read everything): ``DecodeEngine(aux={"sparse_blocks":
blocks}, aux_fold=...)``.
"""
from __future__ import annotations

import math

import numpy as np

from .. import ops
from ..graph.node import name_scope
from ..ops import kda, lightning, sparse_attention as sparse
from ..ops.sparse_attention import SparseSizes
from .common import (build_decoder, cols as _cols, decoder_param_names,
                     swiglu_mlp)

#: MiniCPM4's ``sparse_config`` (arXiv:2506.07900), which MiniCPM-SALA's own
#: config does not carry
SPARSE = {"kernel_size": 32, "kernel_stride": 16, "block_size": 64,
          "window_size": 2048, "topk": 64, "init_blocks": 1,
          "dense_len": 8192}
KINDS = ("minicpm4", "lightning-attn")


class MiniCPMSALAConfig:
    """Sizes as published.  ``param_dtype`` is the weights' storage type,
    ``cache_dtype`` that of the ``kv`` and ``index`` state; recurrent state
    is float32 always."""

    def __init__(self, vocab_size=73448, hidden_size=4096,
                 intermediate_size=16384, num_hidden_layers=32,
                 mixer_types=None, num_attention_heads=32,
                 num_key_value_heads=2, head_dim=128, lightning_nh=32,
                 lightning_head_dim=128, rope_theta=10000.0,
                 rms_norm_eps=1e-6, scale_emb=12.0, scale_depth=1.4,
                 mup_denominator=32, dim_model_base=256, sparse=None,
                 initializer_range=0.02, param_dtype=np.float32,
                 cache_dtype=np.float32, batch_size=1):
        if num_attention_heads % num_key_value_heads:
            raise ValueError("query heads must be a multiple of key heads")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.mixer_types = list(mixer_types or (
            ["minicpm4"] + ["lightning-attn"] * 3)
            * (self.num_hidden_layers // 4))
        if len(self.mixer_types) != self.num_hidden_layers \
                or set(self.mixer_types) - set(KINDS):
            raise ValueError(
                f"mixer_types names {len(self.mixer_types)} layers of kinds "
                f"{sorted(set(self.mixer_types))}; expected "
                f"{self.num_hidden_layers} of {KINDS}")
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.head_dim = int(head_dim)
        self.lightning_nh = int(lightning_nh)
        self.lightning_head_dim = int(lightning_head_dim)
        self.rope_theta = float(rope_theta)
        self.rms_norm_eps = float(rms_norm_eps)
        self.scale_emb = float(scale_emb)
        self.residual_scale = float(scale_depth) / math.sqrt(mup_denominator)
        self.logit_scale = float(dim_model_base) / self.hidden_size
        self.sparse = SparseSizes(**dict(sparse or SPARSE)).as_dict()
        self.initializer_range = float(initializer_range)
        self.param_dtype = np.dtype(param_dtype)
        self.cache_dtype = np.dtype(cache_dtype)
        self.batch_size = int(batch_size)

    @classmethod
    def tiny(cls, **over):
        """The test preset: the served cut's eight layers (sparse, 6 x
        Lightning, sparse) at toy widths, an indexer of stride 2 whose
        selection starts at 32 keys."""
        kw = dict(vocab_size=96, hidden_size=32, intermediate_size=48,
                  num_hidden_layers=8,
                  mixer_types=["minicpm4"] + ["lightning-attn"] * 6
                  + ["minicpm4"], num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, lightning_nh=4,
                  lightning_head_dim=8, mup_denominator=8, dim_model_base=16,
                  sparse={"kernel_size": 4, "kernel_stride": 2,
                          "block_size": 8, "window_size": 16, "topk": 2,
                          "init_blocks": 1, "dense_len": 32})
        kw.update(over)
        return cls(**kw)

    def layer_kind(self, i):
        return "sparse" if self.mixer_types[i] == "minicpm4" else "lightning"

    def block_counters(self):
        """``fold`` for ``DecodeEngine(aux_fold=)``
        (:func:`~hetu_tpu.ops.sparse_attention.block_counters`)."""
        return sparse.block_counters(self.sparse)


def _mix_sparse(g, y, i, name):
    """Gated block-sparse grouped-query attention over the layer's own
    growable slabs and its compressed keys, no positional term."""
    cfg = g.cfg
    d, hd, b = cfg.hidden_size, cfg.head_dim, cfg.batch_size
    heads, groups = cfg.num_attention_heads, cfg.num_key_value_heads
    q_w, kv_w = heads * hd, groups * hd
    z = cfg.sparse
    qkvg = g.dense(y, name + ".qkvg", d, 2 * q_w + 2 * kv_w)
    slab = dict(batch=b, heads=groups, length=g.max_len, head_dim=hd)

    def head_norm(t, leaf):
        return kda.head_norm_op(
            t, g.var(f"{name}.{leaf}_norm.scale", (hd,), 1.0),
            eps=cfg.rms_norm_eps)

    k_rows = kda.gqa_rows_op(head_norm(_cols(qkvg, q_w, q_w + kv_w), "k"),
                             g.ids, head_dim=hd)
    v_rows = kda.gqa_rows_op(_cols(qkvg, q_w + kv_w, q_w + 2 * kv_w), g.ids,
                             head_dim=hd)
    new = []
    for leaf, rows in (("k", k_rows), ("v", v_rows)):
        cache = g.state(f"{leaf}_cache_{i}", "kv", None, cfg.cache_dtype,
                        **slab)
        if g.fed:
            # what a one-token step fetches (``DecodeEngine._kv_rows``)
            cache.attrs["selected"] = (
                z["block_size"], z["topk"] + z["window_size"]
                // z["block_size"], z["dense_len"])
        new.append(ops.kv_cache_append_op(cache, rows, g.positions,
                                          *g.valid))
    pool = g.state(f"pool_{i}", "recurrent", (b, groups, 2, hd), np.float32)
    pooled, first, count, pool2 = sparse.pool_rows_op(
        k_rows, pool, g.positions, g.ids, *g.valid,
        stride=z["kernel_stride"])
    index = ops.kv_cache_append_op(
        g.state(f"index_{i}", "index", None, cfg.cache_dtype,
                stride=z["kernel_stride"], **slab), pooled, first, count)
    g.fetches += new + [pool2, index]
    att, chosen = sparse.sparse_attention_kv_op(
        head_norm(_cols(qkvg, 0, q_w), "q"), *new, index, g.positions,
        g.ids, *g.valid, head_dim=hd, sizes=z)
    g.chosen.append(chosen)
    gate = _cols(qkvg, q_w + 2 * kv_w, None)
    return g.dense(kda.sigmoid_gate_op(gate, att), name + ".o", q_w, d)


def _mix_lightning(g, y, i, name):
    """Lightning attention: projections -> per-head norms, rotation -> the
    scalar-decay recurrence over the carried state -> per-head norm, gate
    -> out."""
    cfg = g.cfg
    d, h, hd = cfg.hidden_size, cfg.lightning_nh, cfg.lightning_head_dim
    e = h * hd
    state = g.state(f"lightning_{i}", "recurrent",
                    (cfg.batch_size, h, hd, hd), np.float32)
    qkvg = g.dense(y, name + ".qkvg", d, 4 * e)
    o, state2 = lightning.lightning_chunk_op(
        _cols(qkvg, 0, 3 * e), g.var(name + ".q_norm.scale", (hd,), 1.0),
        g.var(name + ".k_norm.scale", (hd,), 1.0), state, g.positions,
        g.ids, *g.valid, heads=h, theta=cfg.rope_theta,
        eps=cfg.rms_norm_eps)
    g.fetches.append(state2)
    normed = kda.kda_out_op(
        o, _cols(qkvg, 3 * e, None),
        g.var(name + ".o_norm.scale", (hd,), 1.0), eps=cfg.rms_norm_eps)
    return g.dense(normed, name + ".o", e, d)


def _layer(g, x, i, name):
    cfg = g.cfg
    kind = cfg.layer_kind(i)
    with name_scope("mix." + kind):
        y = g.norm(x, name + ".ln1")
        mixed = _mix_sparse(g, y, i, name + ".attn") if kind == "sparse" \
            else _mix_lightning(g, y, i, name + ".lightning")
        x = x + mixed * cfg.residual_scale
    with name_scope("mlp"):
        return x + swiglu_mlp(g, g.norm(x, name + ".ln2"), name + ".mlp",
                              cfg.intermediate_size) * cfg.residual_scale


def _build(cfg, chunk, max_len, name, **kw):
    return build_decoder(
        cfg, _layer, chunk, max_len, name, embed_scale=cfg.scale_emb,
        logit_scale=cfg.logit_scale,
        chosen=lambda ids, *chosen: sparse.sparse_choices_op(*chosen), **kw)


def minicpm_sala_decode_graph(cfg, max_len, name="sala"):
    """One-token decode graph.  Feeds ``input_ids`` (B, 1), ``positions``
    (B,) and the state placeholders (module docstring).  Returns ``(feeds,
    logits, state_fetches, tokens, blocks)``: ``tokens`` (B,) int32 the
    greedy token of each row, ``blocks`` (B, 1, sparse layers, G, topk)
    int16 the far blocks its token's queries chose."""
    g, logits, tokens, blocks = _build(cfg, 1, max_len, name,
                                       with_valid=False)
    return g.feeds, logits, g.fetches, tokens, blocks


def minicpm_sala_decode_chunked_graph(cfg, max_len, chunk=4, name="sala"):
    """Chunked-prefill twin: ``input_ids`` (B, C), ``positions`` (B,) of
    each row's first column, ``valid`` (B,) columns consumed; the same
    weights by name and the same states.  ``logits`` / ``tokens`` are of
    each row's last consumed column, ``blocks`` (B, C, sparse layers, G,
    topk) of every column."""
    g, logits, tokens, blocks = _build(cfg, int(chunk), max_len, name)
    return g.feeds, logits, g.fetches, tokens, blocks


def minicpm_sala_lm_graph(cfg, seq_len, name="sala"):
    """Full-sequence forward over zero states (tests): feed ``input_ids``
    (B, T); returns ``(feeds, logits, blocks)``, ``logits`` (B*T, vocab)."""
    g, logits, _, blocks = _build(cfg, int(seq_len), int(seq_len), name,
                                  fed=False, with_valid=False)
    return g.feeds, logits, blocks


def param_names(cfg, name="sala"):
    """Checkpoint names and shapes of every variable, in graph order."""
    return decoder_param_names(minicpm_sala_lm_graph, cfg, name)


__all__ = ["MiniCPMSALAConfig", "minicpm_sala_decode_graph",
           "minicpm_sala_decode_chunked_graph", "minicpm_sala_lm_graph",
           "param_names"]
