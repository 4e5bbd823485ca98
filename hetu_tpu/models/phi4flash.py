"""Phi-4-mini-flash-reasoning: the SambaY decoder-hybrid-decoder
(arXiv:2507.06607) as decode graphs for :class:`~hetu_tpu.serving.
DecodeEngine`.

A self-decoder of Mamba-1 layers alternating with sliding-window
differential attention, one full differential-attention layer whose keys
and values are the ONLY growing cache of the model, and a cross-decoder
whose layers alternate a gated memory unit (reading the last Mamba
layer's scan output of the same token) with cross attention over that
one layer's keys and values.  No positional encoding anywhere, SwiGLU
MLPs without bias, LayerNorm with scale and bias, output head tied to
the embedding.

The layer plan follows from the depth alone (``mb_per_layer`` 2): with
``half = num_hidden_layers // 2``, layers ``0 .. half - 1`` alternate
``ssm`` / ``swa``, layer ``half`` is the ``ssm`` layer that also hands on
its memory, layer ``half + 1`` is ``full``, and the rest alternate
``gmu`` / ``cross`` (32 layers: 9 ssm, 8 swa, 1 full, 7 gmu, 7 cross).

Heads pair up for differential attention (arXiv:2410.05258) by
neighbours: query heads ``2p`` and ``2p + 1`` are pair ``p``, key/value
heads ``2g`` and ``2g + 1`` pair ``g``, and query pair ``p`` reads pair
``p // (P // G)``.  A pair's keys lie side by side in one cache row
``[k1; k2]`` (so do ``[v1; v2]``, which is the value differential
attention multiplies by anyway): with 64-wide heads a row fills the 128
lanes and is stored as it is read.

One block definition per mixer kind serves the one-token graph, the
chunked graph and the full-sequence graph: each op takes a ``(B, C)``
chunk (``ops/ssm.py``); the one-token graph is ``C = 1`` without the
``valid`` feed, the full-sequence graph one chunk over zero states.
State placeholders declare their kind for the engine
(:func:`~hetu_tpu.ops.state_placeholder`): ``kv`` for layer ``half +
1``'s slabs, ``ring`` for each window layer's, ``recurrent`` for each
Mamba layer's scan state and convolution window.  Each mixer's nodes are
made under a :class:`~hetu_tpu.graph.node.name_scope` (``mix.ssm``,
``mix.swa``, ``mix.full``, ``mix.cross``, ``mix.gmu``, ``mlp``,
``lm_head``), which a device trace keeps.
"""
from __future__ import annotations

import math

import numpy as np

from .. import initializers as init
from .. import ops
from ..graph.node import Variable, name_scope, placeholder_op
from ..ops import ssm


class Phi4FlashConfig:
    """Sizes of the model.  The first nine are published keys; the rest
    are the Mamba-1 and Differential-Transformer defaults the published
    model uses (``benchmarks/configs/phi4-mini-flash.json`` lists them
    under ``assumed``).  ``param_dtype`` is the weights' storage type,
    ``cache_dtype`` that of the ``kv`` and ``ring`` state; recurrent
    state is float32 always."""

    def __init__(self, vocab_size=200064, hidden_size=2560,
                 intermediate_size=10240, num_hidden_layers=32,
                 num_attention_heads=40, num_key_value_heads=20,
                 sliding_window=512, mb_per_layer=2, layer_norm_eps=1e-5,
                 d_state=16, d_conv=4, expand=2, dt_rank=None,
                 initializer_range=0.02, param_dtype=np.float32,
                 cache_dtype=np.float32, batch_size=1):
        if mb_per_layer != 2 or num_hidden_layers % 4:
            raise ValueError("the layer plan is written for mb_per_layer 2 "
                             "and a depth that is a multiple of 4")
        if num_attention_heads % 2 or num_key_value_heads % 2 or (
                num_attention_heads // 2) % (num_key_value_heads // 2):
            raise ValueError("differential attention pairs heads: both "
                             "head counts must be even and the query pairs "
                             "a multiple of the key pairs")
        self.vocab_size = int(vocab_size)
        self.hidden_size = int(hidden_size)
        self.intermediate_size = int(intermediate_size)
        self.num_hidden_layers = int(num_hidden_layers)
        self.num_attention_heads = int(num_attention_heads)
        self.num_key_value_heads = int(num_key_value_heads)
        self.sliding_window = int(sliding_window)
        self.mb_per_layer = int(mb_per_layer)
        self.layer_norm_eps = float(layer_norm_eps)
        self.d_state = int(d_state)
        self.d_conv = int(d_conv)
        self.d_inner = int(expand) * self.hidden_size
        self.dt_rank = int(dt_rank or math.ceil(self.hidden_size / 16))
        self.head_dim = self.hidden_size // self.num_attention_heads
        self.initializer_range = float(initializer_range)
        self.param_dtype = np.dtype(param_dtype)
        self.cache_dtype = np.dtype(cache_dtype)
        self.batch_size = int(batch_size)

    @classmethod
    def tiny(cls, **over):
        """The test preset: every kind of layer at toy widths (2 Mamba /
        window pairs, the memory Mamba, full, one GMU / cross pair)."""
        kw = dict(vocab_size=97, hidden_size=64, intermediate_size=96,
                  num_hidden_layers=8, num_attention_heads=8,
                  num_key_value_heads=4, sliding_window=8, d_state=4)
        kw.update(over)
        return cls(**kw)

    @property
    def kv_pairs(self):
        return self.num_key_value_heads // 2

    def layer_kind(self, i):
        half = self.num_hidden_layers // 2
        if i <= half:
            return "swa" if i % 2 else "ssm"
        if i == half + 1:
            return "full"
        return "cross" if i % 2 else "gmu"

    def lambda_init(self, i):
        return 0.8 - 0.6 * math.exp(-0.3 * i)


class _Graph:
    """What the blocks of one graph share: the config, the chunk's feeds,
    how a state comes to be (a placeholder the engine feeds, or zeros for
    the full-sequence graph), the state fetches in feed order, and the
    memory and shared keys and values once their layers have run."""

    def __init__(self, cfg, ids, positions, valid, max_len, fed):
        self.cfg, self.ids, self.positions = cfg, ids, positions
        self.valid = () if valid is None else (valid,)
        self.max_len, self.fed = int(max_len), fed
        self.feeds, self.fetches = {}, []
        self.memory = self.shared = None

    def var(self, name, shape, mean=0.0, std=None):
        std = self.cfg.initializer_range if std is None else std
        return Variable(name, initializer=init.NormalInit(mean, std),
                        shape=tuple(shape), dtype=self.cfg.param_dtype)

    def dense(self, x, name, n_in, n_out):
        """``x @ W`` over the weight as it is stored, float32 out."""
        return ops.matmul_op(x, self.var(name + ".weight", (n_in, n_out)),
                             out_dtype=np.float32)

    def norm(self, x, name):
        d = self.cfg.hidden_size
        return ops.layer_normalization_op(
            x, self.var(name + ".scale", (d,), 1.0),
            self.var(name + ".bias", (d,)), eps=self.cfg.layer_norm_eps)

    def state(self, name, kind, shape, dtype, **slab):
        if not self.fed:
            if kind == "kv":
                shape = ops.kv_slab_shape(**slab)
            return ssm.zeros_op(self.ids, tail=tuple(shape[1:]),
                                dtype=np.dtype(dtype))
        node = ops.state_placeholder(name, kind, shape, dtype, **slab)
        self.feeds[name] = node
        return node

    def diff_args(self, i, name):
        """Trailing inputs and attributes of a differential-attention op:
        the four λ vectors, the sub-norm's scale, λ_init of the layer."""
        cfg = self.cfg
        lam = [self.var(f"{name}.{leaf}", (cfg.head_dim,), 0.0, 0.1)
               for leaf in ("lambda_q1", "lambda_k1", "lambda_q2",
                            "lambda_k2")]
        lam.append(self.var(name + ".subln.weight", (2 * cfg.head_dim,),
                            1.0))
        return lam, dict(head_dim=cfg.head_dim,
                         lam_init=cfg.lambda_init(i), eps=1e-5)


def _cols(x, start, stop):
    """Columns ``start:stop`` of a (rows, width) node."""
    return ops.slice_op(x, begin=(0, start), end=(None, stop))


def _mix_ssm(g, y, i, name):
    """Mamba-1: in_proj -> causal conv, silu -> selective scan -> gate
    -> out_proj.  Layer ``half`` also keeps the scan's output (before the
    gate) as the memory the gated memory units read."""
    cfg = g.cfg
    d, e, n, r = cfg.hidden_size, cfg.d_inner, cfg.d_state, cfg.dt_rank
    b = cfg.batch_size
    conv = g.state(f"conv_{i}", "recurrent", (b, cfg.d_conv - 1, e),
                   np.float32)
    scan = g.state(f"ssm_{i}", "recurrent", (b, n, e), np.float32)
    uz = g.dense(y, name + ".in_proj", d, 2 * e)
    u, conv2 = ssm.conv_state_shift_op(
        _cols(uz, 0, e), conv,
        g.var(name + ".conv.weight", (cfg.d_conv, e), 0.0,
              1.0 / math.sqrt(cfg.d_conv)),
        g.var(name + ".conv.bias", (e,)), g.ids, *g.valid)
    dbc = g.dense(u, name + ".x_proj", e, r + 2 * n)
    dt = g.dense(_cols(dbc, 0, r),
                 name + ".dt_proj", r, e) \
        + g.var(name + ".dt_proj.bias", (e,), -4.6, 1.0)
    s, scan2 = ssm.ssm_chunk_scan_op(
        u, dt, _cols(dbc, r, None),
        g.var(name + ".A_log", (n, e), 1.5, 0.7),
        g.var(name + ".D", (e,), 1.0), scan, g.ids, *g.valid)
    g.fetches += [conv2, scan2]
    if i == cfg.num_hidden_layers // 2:
        g.memory = s
    z = _cols(uz, e, None)
    return g.dense(ssm.silu_gate_op(z, s), name + ".out_proj", e, d)


def _mix_attn(g, y, i, name, kind):
    """Window (``swa``) or full differential attention with keys and
    values of its own: the window layer over a ring it appends to, the
    full layer over growable slabs, which it leaves for the cross
    layers."""
    cfg = g.cfg
    d, hd, gp = cfg.hidden_size, cfg.head_dim, cfg.kv_pairs
    kv = cfg.num_key_value_heads * hd
    qkv = g.dense(y, name + ".qkv", d, d + 2 * kv)
    q = _cols(qkv, 0, d)
    k = _cols(qkv, d, d + kv)
    v = _cols(qkv, d + kv, None)
    lam, attrs = g.diff_args(i, name)
    b = cfg.batch_size
    if kind == "swa":
        shape = (b, gp, cfg.sliding_window, 2 * hd)
        kr = g.state(f"k_ring_{i}", "ring", shape, cfg.cache_dtype)
        vr = g.state(f"v_ring_{i}", "ring", shape, cfg.cache_dtype)
        att, kr2, vr2 = ssm.diff_attention_ring_op(
            q, k, v, kr, vr, g.positions, g.ids, *lam, *g.valid, **attrs)
        g.fetches += [kr2, vr2]
    else:
        slab = dict(batch=b, heads=gp, length=g.max_len, head_dim=2 * hd)
        kc = g.state(f"k_cache_{i}", "kv", None, cfg.cache_dtype, **slab)
        vc = g.state(f"v_cache_{i}", "kv", None, cfg.cache_dtype, **slab)
        kc2 = ops.kv_cache_append_op(
            kc, ssm.pair_rows_op(k, g.ids, head_dim=hd), g.positions,
            *g.valid)
        vc2 = ops.kv_cache_append_op(
            vc, ssm.pair_rows_op(v, g.ids, head_dim=hd), g.positions,
            *g.valid)
        g.fetches += [kc2, vc2]
        g.shared = (kc2, vc2)
        att = ssm.diff_attention_kv_op(q, kc2, vc2, g.positions, g.ids,
                                       *lam, **attrs)
    return g.dense(att, name + ".o", d, d)


def _mix_cross(g, y, i, name):
    """Cross attention over the full layer's keys and values: a query
    and an output projection, the layer's own λ, no cache of its own."""
    d = g.cfg.hidden_size
    lam, attrs = g.diff_args(i, name)
    att = ssm.diff_attention_kv_op(
        g.dense(y, name + ".q", d, d), *g.shared, g.positions, g.ids, *lam,
        **attrs)
    return g.dense(att, name + ".o", d, d)


def _mix_gmu(g, y, i, name):
    """Gated memory unit: ``W2(silu(W1 y) ⊙ m)``, ``m`` the memory of the
    same token.  No state."""
    d, e = g.cfg.hidden_size, g.cfg.d_inner
    gate = g.dense(y, name + ".in_proj", d, e)
    return g.dense(ssm.silu_gate_op(gate, g.memory), name + ".out_proj",
                   e, d)


def _layer(g, x, i, name):
    """``h = x + Mix(LN(x))``, ``x' = h + W_down(silu(g) ⊙ u)``."""
    cfg = g.cfg
    kind = cfg.layer_kind(i)
    with name_scope("mix." + kind):
        y = g.norm(x, name + ".ln1")
        if kind == "ssm":
            mixed = _mix_ssm(g, y, i, name + ".ssm")
        elif kind in ("swa", "full"):
            mixed = _mix_attn(g, y, i, name + ".attn", kind)
        elif kind == "cross":
            mixed = _mix_cross(g, y, i, name + ".attn")
        else:
            mixed = _mix_gmu(g, y, i, name + ".gmu")
        x = x + mixed
    with name_scope("mlp"):
        gu = g.dense(g.norm(x, name + ".ln2"), name + ".mlp.gate_up",
                     cfg.hidden_size, 2 * cfg.intermediate_size)
        return x + g.dense(ssm.swiglu_op(gu), name + ".mlp.down",
                           cfg.intermediate_size, cfg.hidden_size)


def _build(cfg, chunk, max_len, name, fed=True, with_valid=True):
    b = cfg.batch_size
    ids = placeholder_op("input_ids", shape=(b, chunk), dtype=np.int32)
    if fed:
        positions = placeholder_op("positions", shape=(b,), dtype=np.int32)
    else:
        positions = ssm.zeros_op(ids, tail=(), dtype=np.dtype(np.int32))
    valid = placeholder_op("valid", shape=(b,), dtype=np.int32) \
        if with_valid else None
    g = _Graph(cfg, ids, positions, valid, max_len, fed)
    g.feeds["input_ids"] = ids
    if fed:
        g.feeds["positions"] = positions
    if valid is not None:
        g.feeds["valid"] = valid
    table = g.var(name + ".embed", (cfg.vocab_size, cfg.hidden_size))
    x = ops.array_reshape_op(                            # (B*C, d)
        ops.embedding_lookup_op(table, ids, dtype=np.float32),
        output_shape=(-1, cfg.hidden_size))
    for i in range(cfg.num_hidden_layers):
        x = _layer(g, x, i, f"{name}.l{i}")
    with name_scope("lm_head"):
        if valid is not None:
            # each sequence's last consumed row, before the norm and the
            # vocabulary product (both row-wise)
            x = ops.chunk_emit_gather_op(x, ids, valid)
        # the tied head reads the embedding table as it lies
        logits = ops.matmul_op(g.norm(x, name + ".ln_f"), table,
                               trans_B=True, out_dtype=np.float32)
        tokens = ssm.greedy_token_op(logits)
    return g, logits, tokens


def phi4flash_decode_graph(cfg, max_len, name="phi4"):
    """One-token decode graph.  Feeds ``input_ids`` (B, 1), ``positions``
    (B,) and the state placeholders: per Mamba layer ``conv_i`` (B, K-1,
    E) and ``ssm_i`` (B, N, E), ``recurrent``; per window layer
    ``k_ring_i`` / ``v_ring_i`` (B, G, W, 2D), ``ring``; ``k_cache_i`` /
    ``v_cache_i`` of the one full layer, ``kv`` slabs of paired rows.
    Returns ``(feeds, logits, state_fetches, tokens)``: ``logits`` (B,
    vocab) float32, ``state_fetches`` the updated states in feed order,
    ``tokens`` (B,) int32 the greedy token of each row — hand it to
    ``DecodeEngine(tokens=)`` and the logits stay on the device."""
    g, logits, tokens = _build(cfg, 1, max_len, name, with_valid=False)
    return g.feeds, logits, g.fetches, tokens


def phi4flash_decode_chunked_graph(cfg, max_len, chunk=4, name="phi4"):
    """Chunked-prefill twin: ``input_ids`` (B, C), ``positions`` (B,) of
    each row's first column, ``valid`` (B,) columns consumed; the same
    weights by name and the same states.  ``logits`` / ``tokens`` are of
    each row's last consumed column."""
    g, logits, tokens = _build(cfg, int(chunk), max_len, name)
    return g.feeds, logits, g.fetches, tokens


def phi4flash_lm_graph(cfg, seq_len, name="phi4"):
    """Full-sequence forward over zero states (tests): feed ``input_ids``
    (B, T); returns ``(feeds, logits)`` with ``logits`` (B*T, vocab)."""
    g, logits, _ = _build(cfg, int(seq_len), int(seq_len), name, fed=False,
                          with_valid=False)
    return g.feeds, logits


def param_names(cfg, name="phi4"):
    """Checkpoint names and shapes of every variable, in graph order."""
    from ..graph.node import PlaceholderOp, topo_sort
    _, logits = phi4flash_lm_graph(cfg, 2, name)
    return {n.name: n.shape for n in topo_sort([logits])
            if isinstance(n, PlaceholderOp) and n.is_variable}


__all__ = ["Phi4FlashConfig", "phi4flash_decode_graph",
           "phi4flash_decode_chunked_graph", "phi4flash_lm_graph",
           "param_names"]
