"""GPT-2 (reference ``examples/transformers/gpt2/hetu_gpt2.py`` — HF-style
GPT-2 composed from hetu ops).  TPU-native rewrite: pre-LN blocks, fused
causal ``sdpa_op`` (Pallas flash kernel on TPU) instead of composed
batch_matmul+softmax+mask, activations as (batch*seq, hidden) MXU matmuls.
"""
from __future__ import annotations

import numpy as np

from .. import ops
from .. import initializers as init
from ..graph.node import Variable, placeholder_op
from ..layers.attention import MultiHeadAttention
from ..layers.core import Linear, LayerNorm


class GPT2Config:
    def __init__(self, vocab_size=50257, n_positions=1024, n_embd=768,
                 n_layer=12, n_head=12, resid_pdrop=0.1, embd_pdrop=0.1,
                 attn_pdrop=0.1, layer_norm_epsilon=1e-5,
                 batch_size=8, seq_len=128):
        self.vocab_size = vocab_size
        self.n_positions = n_positions
        self.n_embd = n_embd
        self.n_layer = n_layer
        self.n_head = n_head
        self.resid_pdrop = resid_pdrop
        self.embd_pdrop = embd_pdrop
        self.attn_pdrop = attn_pdrop
        self.layer_norm_epsilon = layer_norm_epsilon
        self.batch_size = batch_size
        self.seq_len = seq_len

    @classmethod
    def small(cls, **kw):
        return cls(**kw)

    @classmethod
    def medium(cls, **kw):
        kw.setdefault("n_embd", 1024)
        kw.setdefault("n_layer", 24)
        kw.setdefault("n_head", 16)
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("n_embd", 128)
        kw.setdefault("n_layer", 2)
        kw.setdefault("n_head", 2)
        kw.setdefault("vocab_size", 512)
        return cls(**kw)


def _block(cfg, x, name):
    """Pre-LN transformer block: x + attn(ln1(x)); x + mlp(ln2(x))."""
    h = LayerNorm(cfg.n_embd, cfg.layer_norm_epsilon, name + ".ln1")(x)
    # attn_pdrop applies to the attention OUTPUT, not the probabilities
    # (flash-incompatible) — see the design note in layers/attention.py
    mha = MultiHeadAttention(cfg.n_embd, cfg.n_head, dropout=cfg.attn_pdrop,
                             causal=True, name=name + ".attn")
    x = x + mha(h, cfg.batch_size, cfg.seq_len)
    h = LayerNorm(cfg.n_embd, cfg.layer_norm_epsilon, name + ".ln2")(x)
    h = Linear(cfg.n_embd, 4 * cfg.n_embd, activation="gelu",
               initializer=init.GenTruncatedNormal(0.0, 0.02),
               name=name + ".mlp_fc")(h)
    h = Linear(4 * cfg.n_embd, cfg.n_embd,
               initializer=init.GenTruncatedNormal(0.0, 0.02),
               name=name + ".mlp_proj")(h)
    h = ops.dropout_op(h, 1.0 - cfg.resid_pdrop)
    return x + h


def gpt2_model(cfg, input_ids, name="gpt2"):
    """Returns hidden states node of shape (batch*seq, n_embd)."""
    wte = init.truncated_normal((cfg.vocab_size, cfg.n_embd), 0.0, 0.02,
                                name=name + ".wte")
    wpe = init.truncated_normal((cfg.n_positions, cfg.n_embd), 0.0, 0.01,
                                name=name + ".wpe")
    positions = Variable(name + ".pos_ids",
                         value=np.arange(cfg.seq_len, dtype=np.float32),
                         trainable=False)
    x = ops.embedding_lookup_op(wte, input_ids) \
        + ops.embedding_lookup_op(wpe, positions)
    x = ops.array_reshape_op(
        x, output_shape=(cfg.batch_size * cfg.seq_len, cfg.n_embd))
    x = ops.dropout_op(x, 1.0 - cfg.embd_pdrop)
    for i in range(cfg.n_layer):
        x = _block(cfg, x, f"{name}.h{i}")
    return LayerNorm(cfg.n_embd, cfg.layer_norm_epsilon, name + ".ln_f")(x)


def gpt2_lm_graph(cfg, name="gpt2"):
    """Causal LM training graph: next-token prediction.

    Returns (feeds dict, loss node, logits node).  ``labels``: (batch, seq)
    with -1 at padded positions (ignored).
    """
    shape = (cfg.batch_size, cfg.seq_len)
    # int32: fp32 id feeds would ride the bf16 compute_dtype cast (exact
    # only up to 256 — silent corruption for any real vocab)
    input_ids = placeholder_op("input_ids", shape=shape, dtype=np.int32)
    labels = placeholder_op("labels", shape=shape, dtype=np.int32)
    hidden = gpt2_model(cfg, input_ids, name)
    logits = Linear(cfg.n_embd, cfg.vocab_size,
                    initializer=init.GenTruncatedNormal(0.0, 0.02),
                    name=name + ".lm_head")(hidden)
    from .common import masked_lm_loss
    loss = masked_lm_loss(logits, labels, cfg.batch_size * cfg.seq_len)
    return {"input_ids": input_ids, "labels": labels}, loss, logits


class _DecodeBlockLayer:
    """Per-block kernel handles for ``ParallelPlan.bind``/``apply``:
    column-parallel q/k/v + mlp_fc, row-parallel o + mlp_proj (the
    canonical Megatron pair) — lets a searched tp plan annotate the
    decode graph exactly like the training model's layers."""

    def __init__(self, in_kernels, out_kernels):
        self.in_kernels = in_kernels
        self.out_kernels = out_kernels


def _block_decode(cfg, x, k_cache, v_cache, positions, name):
    """One-token decode of :func:`_block`: identical weights BY NAME
    (``.ln1``/``.attn.{q,k,v,o}``/``.ln2``/``.mlp_fc``/``.mlp_proj``),
    attention against the bucketed KV slabs through the one-token
    kernel (``sdpa_decode_op``) instead of the full sequence.  No
    dropout: decode is a serving graph.  Returns (x, new_k_cache,
    new_v_cache, layer)."""
    dk = cfg.n_embd // cfg.n_head
    h = LayerNorm(cfg.n_embd, cfg.layer_norm_epsilon, name + ".ln1")(x)

    def heads(t):
        # (B, n_embd) -> (B, H, 1, dk); -1 keeps the graph batch-agnostic
        # (decode buckets the batch dim at runtime)
        t = ops.array_reshape_op(t, output_shape=(-1, 1, cfg.n_head, dk))
        return ops.transpose_op(t, perm=(0, 2, 1, 3))

    lq = Linear(cfg.n_embd, cfg.n_embd, name=name + ".attn.q")
    lk = Linear(cfg.n_embd, cfg.n_embd, name=name + ".attn.k")
    lv = Linear(cfg.n_embd, cfg.n_embd, name=name + ".attn.v")
    lo = Linear(cfg.n_embd, cfg.n_embd, name=name + ".attn.o")
    q = heads(lq(h))
    kc = ops.kv_cache_append_op(k_cache, heads(lk(h)), positions)
    vc = ops.kv_cache_append_op(v_cache, heads(lv(h)), positions)
    att = ops.sdpa_decode_op(q, kc, vc, positions)       # (B, H, 1, dk)
    att = ops.transpose_op(att, perm=(0, 2, 1, 3))
    att = ops.array_reshape_op(att, output_shape=(-1, cfg.n_embd))
    x = x + lo(att)
    h = LayerNorm(cfg.n_embd, cfg.layer_norm_epsilon, name + ".ln2")(x)
    fc = Linear(cfg.n_embd, 4 * cfg.n_embd, activation="gelu",
                initializer=init.GenTruncatedNormal(0.0, 0.02),
                name=name + ".mlp_fc")
    proj = Linear(4 * cfg.n_embd, cfg.n_embd,
                  initializer=init.GenTruncatedNormal(0.0, 0.02),
                  name=name + ".mlp_proj")
    x = x + proj(fc(h))
    layer = _DecodeBlockLayer(
        [lq.weight_var, lk.weight_var, lv.weight_var, fc.weight_var],
        [lo.weight_var, proj.weight_var])
    return x, kc, vc, layer


def gpt2_decode_graph(cfg, max_len=None, name="gpt2"):
    """One-token autoregressive decode graph over per-layer KV caches.

    Weight names match :func:`gpt2_lm_graph` exactly, so a trained
    checkpoint (or a live Executor) loads into the decode executor BY
    NAME with zero conversion.  Feeds (all batch-leading, bucketed by the
    decode engine at runtime):

    * ``input_ids`` (B, 1) int32 — the one token each sequence consumes
      this step (a prompt token during prefill, the previous sample
      during generation)
    * ``positions`` (B,) int32 — the cache row that token writes; keys
      beyond it stay invisible to the q_len=1 attention
    * ``k_cache_i`` / ``v_cache_i`` per layer — the device-resident KV
      slabs, fed back from the previous step's fetches (donated: XLA
      updates them in place).  Their stored shape follows from
      ``head_dim`` alone (:func:`~hetu_tpu.ops.attention.kv_slab_shape`):
      (B, n_head, L/r, r*head_dim) with ``r = 128 // head_dim``
      consecutive key rows per 128-lane row when ``head_dim`` divides 128
      (GPT-2's 64: two), plain (B, n_head, L, head_dim) otherwise — the
      layout the append and the attention both read without a relayout

    Returns ``(feeds, logits, cache_fetches, layers)``: ``feeds`` maps
    the names above to placeholder nodes, ``logits`` is (B, vocab) for
    the fed token, ``cache_fetches`` is [k0', v0', k1', v1', ...] (the
    appended caches, in feed order), and ``layers`` are per-block kernel
    handles for ``ParallelPlan.bind`` (tp-sharded decode)."""
    max_len = int(max_len or cfg.n_positions)
    dk = cfg.n_embd // cfg.n_head
    shape = (cfg.batch_size, 1)
    ids = placeholder_op("input_ids", shape=shape, dtype=np.int32)
    positions = placeholder_op("positions", shape=(cfg.batch_size,),
                               dtype=np.int32)
    wte = init.truncated_normal((cfg.vocab_size, cfg.n_embd), 0.0, 0.02,
                                name=name + ".wte")
    wpe = init.truncated_normal((cfg.n_positions, cfg.n_embd), 0.0, 0.01,
                                name=name + ".wpe")
    x = ops.embedding_lookup_op(wte, ids)                # (B, 1, n_embd)
    x = ops.array_reshape_op(x, output_shape=(-1, cfg.n_embd))
    x = x + ops.embedding_lookup_op(wpe, positions)      # (B, n_embd)
    feeds = {"input_ids": ids, "positions": positions}
    cache_fetches, layers = [], []
    for i in range(cfg.n_layer):
        kc = ops.kv_slab_placeholder(
            f"k_cache_{i}", cfg.batch_size, cfg.n_head, max_len, dk)
        vc = ops.kv_slab_placeholder(
            f"v_cache_{i}", cfg.batch_size, cfg.n_head, max_len, dk)
        feeds[f"k_cache_{i}"] = kc
        feeds[f"v_cache_{i}"] = vc
        x, kc2, vc2, layer = _block_decode(cfg, x, kc, vc, positions,
                                           f"{name}.h{i}")
        cache_fetches += [kc2, vc2]
        layers.append(layer)
    x = LayerNorm(cfg.n_embd, cfg.layer_norm_epsilon, name + ".ln_f")(x)
    logits = Linear(cfg.n_embd, cfg.vocab_size,
                    initializer=init.GenTruncatedNormal(0.0, 0.02),
                    name=name + ".lm_head")(x)
    return feeds, logits, cache_fetches, layers


def _block_decode_chunked(cfg, x, ids, k_cache, v_cache, positions, valid,
                          name):
    """Chunked-prefill twin of :func:`_block_decode` (ISSUE 18): the
    residual stream is (B*C, n_embd) for a (B, C) token chunk, weights
    identical BY NAME, the cache write masked by ``valid`` (rows past a
    sequence's real consumption keep the old cache bytes) and attention
    through the q_len=C entry with causal-within-chunk masking.
    Returns (x, new_k_cache, new_v_cache, layer)."""
    h = LayerNorm(cfg.n_embd, cfg.layer_norm_epsilon, name + ".ln1")(x)

    def heads(t):
        # (B*C, n_embd) -> (B, H, C, dk), (B, C) recovered from ids
        return ops.split_heads_chunk_op(t, ids, n_head=cfg.n_head)

    lq = Linear(cfg.n_embd, cfg.n_embd, name=name + ".attn.q")
    lk = Linear(cfg.n_embd, cfg.n_embd, name=name + ".attn.k")
    lv = Linear(cfg.n_embd, cfg.n_embd, name=name + ".attn.v")
    lo = Linear(cfg.n_embd, cfg.n_embd, name=name + ".attn.o")
    q = heads(lq(h))
    kc = ops.kv_cache_append_op(k_cache, heads(lk(h)), positions, valid)
    vc = ops.kv_cache_append_op(v_cache, heads(lv(h)), positions, valid)
    att = ops.sdpa_prefill_op(q, kc, vc, positions, valid)  # (B, H, C, dk)
    att = ops.merge_heads_chunk_op(att)                  # (B*C, n_embd)
    x = x + lo(att)
    h = LayerNorm(cfg.n_embd, cfg.layer_norm_epsilon, name + ".ln2")(x)
    fc = Linear(cfg.n_embd, 4 * cfg.n_embd, activation="gelu",
                initializer=init.GenTruncatedNormal(0.0, 0.02),
                name=name + ".mlp_fc")
    proj = Linear(4 * cfg.n_embd, cfg.n_embd,
                  initializer=init.GenTruncatedNormal(0.0, 0.02),
                  name=name + ".mlp_proj")
    x = x + proj(fc(h))
    layer = _DecodeBlockLayer(
        [lq.weight_var, lk.weight_var, lv.weight_var, fc.weight_var],
        [lo.weight_var, proj.weight_var])
    return x, kc, vc, layer


def gpt2_decode_chunked_graph(cfg, max_len=None, chunk=4, name="gpt2"):
    """Chunked-prefill autoregressive decode graph (ISSUE 18): each step
    consumes a (B, C) token CHUNK instead of one token per sequence, so
    a P-token prompt ingests in ceil(P/C) dispatches instead of P.

    Weight names match :func:`gpt2_decode_graph` / :func:`gpt2_lm_graph`
    exactly — the decode engine loads this graph's executor FROM the
    primary executor's params so both entries serve the same bytes.
    Feeds (batch AND chunk dim bucketed by the engine at runtime —
    ``chunk`` here only sizes the nominal placeholders):

    * ``input_ids`` (B, C) int32 — up to C prompt tokens per sequence
      this step (generating rows ride along with their one token at
      column 0)
    * ``positions`` (B,) int32 — the cache row of each sequence's FIRST
      chunk token
    * ``valid`` (B,) int32 — how many chunk columns each sequence
      actually consumes (0 for idle slots); rows ``>= valid`` neither
      write the cache nor reach the logits
    * ``k_cache_i`` / ``v_cache_i`` per layer — the same KV slabs as the
      one-token graph's (the two executors hand the same device arrays
      back and forth), donated, fed back from the previous step's fetches

    Returns ``(feeds, logits, cache_fetches, layers)`` like the
    one-token graph; ``logits`` is (B, vocab) for each sequence's LAST
    consumed chunk token (gathered before ln_f/lm_head so the vocab
    projection stays B-row)."""
    max_len = int(max_len or cfg.n_positions)
    chunk = int(chunk)
    dk = cfg.n_embd // cfg.n_head
    ids = placeholder_op("input_ids", shape=(cfg.batch_size, chunk),
                         dtype=np.int32)
    positions = placeholder_op("positions", shape=(cfg.batch_size,),
                               dtype=np.int32)
    valid = placeholder_op("valid", shape=(cfg.batch_size,),
                           dtype=np.int32)
    wte = init.truncated_normal((cfg.vocab_size, cfg.n_embd), 0.0, 0.02,
                                name=name + ".wte")
    wpe = init.truncated_normal((cfg.n_positions, cfg.n_embd), 0.0, 0.01,
                                name=name + ".wpe")
    pos2d = ops.chunk_positions_op(positions, ids,
                                   limit=cfg.n_positions)   # (B, C)
    x = ops.embedding_lookup_op(wte, ids)             # (B, C, n_embd)
    x = ops.array_reshape_op(x, output_shape=(-1, cfg.n_embd))
    pe = ops.embedding_lookup_op(wpe, pos2d)          # (B, C, n_embd)
    pe = ops.array_reshape_op(pe, output_shape=(-1, cfg.n_embd))
    x = x + pe
    feeds = {"input_ids": ids, "positions": positions, "valid": valid}
    cache_fetches, layers = [], []
    for i in range(cfg.n_layer):
        kc = ops.kv_slab_placeholder(
            f"k_cache_{i}", cfg.batch_size, cfg.n_head, max_len, dk)
        vc = ops.kv_slab_placeholder(
            f"v_cache_{i}", cfg.batch_size, cfg.n_head, max_len, dk)
        # ``sdpa_prefill_op`` reads a slab as far as its sequence reaches
        # (what ``DecodeEngine._kv_rows`` counts a chunked step by)
        kc.attrs["chunk_read"] = vc.attrs["chunk_read"] = "live"
        feeds[f"k_cache_{i}"] = kc
        feeds[f"v_cache_{i}"] = vc
        x, kc2, vc2, layer = _block_decode_chunked(
            cfg, x, ids, kc, vc, positions, valid, f"{name}.h{i}")
        cache_fetches += [kc2, vc2]
        layers.append(layer)
    # each sequence's last consumed row, BEFORE ln_f/lm_head: LayerNorm
    # is row-wise so the gather commutes, and the vocab matmul shrinks C×
    x = ops.chunk_emit_gather_op(x, ids, valid)       # (B, n_embd)
    x = LayerNorm(cfg.n_embd, cfg.layer_norm_epsilon, name + ".ln_f")(x)
    logits = Linear(cfg.n_embd, cfg.vocab_size,
                    initializer=init.GenTruncatedNormal(0.0, 0.02),
                    name=name + ".lm_head")(x)
    return feeds, logits, cache_fetches, layers


def synthetic_lm_batch(cfg, seed=0):
    """Next-token synthetic batch: ids shifted left for labels."""
    rng = np.random.RandomState(seed)
    ids = rng.randint(0, cfg.vocab_size, (cfg.batch_size, cfg.seq_len + 1))
    return (ids[:, :-1].astype(np.float32), ids[:, 1:].astype(np.float32))
