"""BERT (reference ``examples/transformers/bert/hetu_bert.py`` — an HF-style
BERT built from hetu ops).  TPU-native rewrite: same graph-API surface, but
attention is the fused ``sdpa_op`` (Pallas flash kernel on TPU) instead of
composed batch_matmul+softmax, and activations flow as (batch*seq, hidden)
2-D tensors so every projection is one MXU matmul.
"""
from __future__ import annotations

import numpy as np

from .. import ops
from .. import initializers as init
from ..graph.node import Variable
from ..layers.attention import MultiHeadAttention
from ..layers.core import Linear, LayerNorm, Embedding


class BertConfig:
    """``max_predictions_per_seq``: the most positions of a row that carry
    an MLM label, the published pre-training's field of that name
    (``google-research/bert`` ``run_pretraining.py``:
    ``gather_indexes(input_tensor, masked_lm_positions)`` before the
    transform; 20 at 128 positions, 80 at 512).  ``bert_pretrain_graph``
    runs the MLM head on that many rows a sequence and not on all
    ``seq_len``.  ``None`` (the default): 15 % of ``seq_len`` rounded up
    to a multiple of 8 — 24 at 128, 80 at 512.  A batch in which some
    row carries MORE labels than this still trains on every one of
    them: that step runs the head again over each row's next
    ``max_predictions_per_seq`` (a round more of the head's cost;
    ``loss.mlm_overflow`` counts such rows), so raise the field where
    the masking labels more."""

    def __init__(self, vocab_size=30522, hidden_size=768,
                 num_hidden_layers=12, num_attention_heads=12,
                 intermediate_size=3072, max_position_embeddings=512,
                 type_vocab_size=2, hidden_dropout_prob=0.1,
                 attention_probs_dropout_prob=0.1, layer_norm_eps=1e-12,
                 batch_size=8, seq_len=128, max_predictions_per_seq=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.layer_norm_eps = layer_norm_eps
        self.batch_size = batch_size
        self.seq_len = seq_len
        if max_predictions_per_seq is None:
            max_predictions_per_seq = -(-seq_len * 15 // 100 // 8) * 8
        self.max_predictions_per_seq = min(max_predictions_per_seq, seq_len)

    @classmethod
    def base(cls, **kw):
        return cls(**kw)

    @classmethod
    def large(cls, **kw):
        kw.setdefault("hidden_size", 1024)
        kw.setdefault("num_hidden_layers", 24)
        kw.setdefault("num_attention_heads", 16)
        kw.setdefault("intermediate_size", 4096)
        return cls(**kw)

    @classmethod
    def tiny(cls, **kw):
        kw.setdefault("hidden_size", 128)
        kw.setdefault("num_hidden_layers", 2)
        kw.setdefault("num_attention_heads", 2)
        kw.setdefault("intermediate_size", 512)
        kw.setdefault("vocab_size", 1024)
        return cls(**kw)


def _embeddings(cfg, input_ids, token_type_ids, name="embeddings"):
    word = Embedding(cfg.vocab_size, cfg.hidden_size,
                     init.GenTruncatedNormal(0.0, 0.02), name + ".word")
    pos_table = init.truncated_normal(
        (cfg.max_position_embeddings, cfg.hidden_size), 0.0, 0.02,
        name=name + ".position")
    ttype = Embedding(cfg.type_vocab_size, cfg.hidden_size,
                      init.GenTruncatedNormal(0.0, 0.02), name + ".token_type")
    positions = Variable(
        name + ".pos_ids",
        value=np.arange(cfg.seq_len, dtype=np.float32), trainable=False)
    e = word(input_ids) + ops.embedding_lookup_op(pos_table, positions) \
        + ttype(token_type_ids)
    e = ops.array_reshape_op(
        e, output_shape=(cfg.batch_size * cfg.seq_len, cfg.hidden_size))
    e = LayerNorm(cfg.hidden_size, cfg.layer_norm_eps, name + ".ln")(e)
    return ops.dropout_op(e, 1.0 - cfg.hidden_dropout_prob)


def _encoder_layer(cfg, x, name, mask=None):
    # attention_probs_dropout_prob applies to the attention OUTPUT, not
    # the probabilities (flash-incompatible) — see layers/attention.py
    mha = MultiHeadAttention(cfg.hidden_size, cfg.num_attention_heads,
                             dropout=cfg.attention_probs_dropout_prob,
                             name=name + ".attn")
    attn = mha(x, cfg.batch_size, cfg.seq_len, mask=mask)
    x = LayerNorm(cfg.hidden_size, cfg.layer_norm_eps,
                  name + ".ln1")(x + attn)
    h = Linear(cfg.hidden_size, cfg.intermediate_size, activation="gelu",
               initializer=init.GenTruncatedNormal(0.0, 0.02),
               name=name + ".ffn1")(x)
    h = Linear(cfg.intermediate_size, cfg.hidden_size,
               initializer=init.GenTruncatedNormal(0.0, 0.02),
               name=name + ".ffn2")(h)
    h = ops.dropout_op(h, 1.0 - cfg.hidden_dropout_prob)
    return LayerNorm(cfg.hidden_size, cfg.layer_norm_eps,
                     name + ".ln2")(x + h)


def bert_model(cfg, input_ids, token_type_ids, attention_mask=None,
               name="bert"):
    """Returns sequence_output node of shape (batch*seq, hidden).

    ``attention_mask``: optional (batch, seq) node of 1/0 key-validity flags
    (reference hetu_bert.py's extended_attention_mask input) — reshaped once
    to the (B, 1, 1, S) key-padding form that ``sdpa_masked_op`` routes to
    the flash kernel's O(S) key-mask strip path.
    """
    x = _embeddings(cfg, input_ids, token_type_ids, name + ".embeddings")
    mask = None
    if attention_mask is not None:
        mask = ops.array_reshape_op(
            attention_mask, output_shape=(cfg.batch_size, 1, 1, cfg.seq_len))
    for i in range(cfg.num_hidden_layers):
        x = _encoder_layer(cfg, x, f"{name}.layer{i}", mask=mask)
    return x


def bert_pretrain_graph(cfg, name="bert", use_mask=True, use_nsp=False):
    """Full MLM pretraining graph (reference train_hetu_bert_dp.py flow).

    Returns (placeholders dict, loss node, logits node).
    masked_lm_labels: (batch, seq) with -1 for unmasked positions.
    ``loss.mlm_overflow``: a scalar node a caller may fetch, the rows of
    the fed batch with more than ``cfg.max_predictions_per_seq`` labels
    (0: the step ran the head once, on the labelled rows).
    ``use_mask=True`` (the flagship default) adds an ``attention_mask``
    (batch, seq) int32 input so padded pretraining attends only to real
    tokens (reference hetu_bert.py attention_mask input).
    ``use_nsp=True`` adds the next-sentence-prediction objective of the
    reference's full pretrain loss (train_hetu_bert.py:59 — mlm + nsp):
    pooler over [CLS] → 2-way head, a ``next_sentence_label`` (batch,)
    feed, and loss = mlm_mean + nsp_mean.  Opt-in so the flagship bench
    workload (MLM-only, BASELINE.md) is unchanged.
    """
    from ..graph.node import placeholder_op
    shape = (cfg.batch_size, cfg.seq_len)
    # int32 placeholders: token ids/labels must never ride the fp32→bf16
    # compute_dtype cast (bf16 only represents integers exactly up to 256)
    input_ids = placeholder_op("input_ids", shape=shape, dtype=np.int32)
    token_type_ids = placeholder_op("token_type_ids", shape=shape,
                                    dtype=np.int32)
    labels = placeholder_op("masked_lm_labels", shape=shape, dtype=np.int32)
    attention_mask = placeholder_op("attention_mask", shape=shape,
                                    dtype=np.int32) if use_mask else None

    seq = bert_model(cfg, input_ids, token_type_ids,
                     attention_mask=attention_mask, name=name)
    # MLM head: transform + tied-ish decoder (fresh decoder weights, like the
    # reference which also keeps an independent decoder matrix).  The loss
    # runs it on the labelled rows (``BertConfig.max_predictions_per_seq``);
    # ``logits`` is the same three layers over every position, computed
    # only where a caller fetches it
    from ..graph.node import name_scope
    from .common import labelled_rows_lm_loss
    with name_scope("mlm_head"):
        transform = Linear(cfg.hidden_size, cfg.hidden_size,
                           activation="gelu",
                           initializer=init.GenTruncatedNormal(0.0, 0.02),
                           name=name + ".mlm_transform")
        ln = LayerNorm(cfg.hidden_size, cfg.layer_norm_eps, name + ".mlm_ln")
        decoder = Linear(cfg.hidden_size, cfg.vocab_size,
                         initializer=init.GenTruncatedNormal(0.0, 0.02),
                         name=name + ".mlm_decoder")

        def head(rows):
            return decoder(ln(transform(rows)))

        logits = head(seq)
        loss, overflow = labelled_rows_lm_loss(
            seq, labels, head, cfg.batch_size, cfg.seq_len,
            cfg.max_predictions_per_seq)
    feeds = {"input_ids": input_ids, "token_type_ids": token_type_ids,
             "masked_lm_labels": labels}
    if use_nsp:
        nsp_label = placeholder_op("next_sentence_label",
                                   shape=(cfg.batch_size,), dtype=np.int32)
        pooled = bert_pooler(cfg, seq, name + ".pooler")
        nsp_logits = Linear(cfg.hidden_size, 2,
                            initializer=init.GenTruncatedNormal(0.0, 0.02),
                            name=name + ".seq_relationship")(pooled)
        loss = loss + ops.reduce_mean_op(
            ops.softmaxcrossentropy_sparse_op(nsp_logits, nsp_label), [0])
        feeds["next_sentence_label"] = nsp_label
    if attention_mask is not None:
        feeds["attention_mask"] = attention_mask
    loss.mlm_overflow = overflow
    return feeds, loss, logits


def bert_pooler(cfg, seq, name="bert.pooler"):
    """HF-style pooler: dense+tanh over the [CLS] (first) token
    (reference hetu_bert.py BertPooler).  ``seq``: (batch*seq_len,
    hidden) → (batch, hidden)."""
    x = ops.array_reshape_op(
        seq, output_shape=(cfg.batch_size, cfg.seq_len, cfg.hidden_size))
    cls = ops.slice_op(x, begin=(0, 0, 0),
                       size=(cfg.batch_size, 1, cfg.hidden_size))
    cls = ops.array_reshape_op(
        cls, output_shape=(cfg.batch_size, cfg.hidden_size))
    return Linear(cfg.hidden_size, cfg.hidden_size, activation="tanh",
                  initializer=init.GenTruncatedNormal(0.0, 0.02),
                  name=name + ".dense")(cls)


def bert_classify_graph(cfg, num_labels, name="bert", use_mask=True):
    """Sequence-classification fine-tuning graph (the reference's GLUE
    flow: ``examples/transformers/bert/test_glue_hetu_bert.py`` —
    pooler + classifier head over the pretrained encoder).

    Returns (placeholders dict, loss node, logits node).  ``labels``:
    (batch,) int class ids.  Warm-start: encoder/embedding variable
    names match ``bert_pretrain_graph``'s exactly, so
    ``Executor.load(pretrain_ckpt, params_only=True)`` restores the
    shared trunk by name and leaves the fresh pooler/classifier at
    their init — the pretrain → fine-tune flow needs no remapping.
    (``params_only`` matters: a full ``load`` would also resume the
    pretrain LR-schedule step and Adam moments into the new task.)
    """
    from ..graph.node import placeholder_op
    shape = (cfg.batch_size, cfg.seq_len)
    input_ids = placeholder_op("input_ids", shape=shape, dtype=np.int32)
    token_type_ids = placeholder_op("token_type_ids", shape=shape,
                                    dtype=np.int32)
    labels = placeholder_op("labels", shape=(cfg.batch_size,),
                            dtype=np.int32)
    attention_mask = placeholder_op("attention_mask", shape=shape,
                                    dtype=np.int32) if use_mask else None

    seq = bert_model(cfg, input_ids, token_type_ids,
                     attention_mask=attention_mask, name=name)
    pooled = bert_pooler(cfg, seq, name + ".pooler")
    pooled = ops.dropout_op(pooled, 1.0 - cfg.hidden_dropout_prob)
    logits = Linear(cfg.hidden_size, num_labels,
                    initializer=init.GenTruncatedNormal(0.0, 0.02),
                    name=name + ".classifier")(pooled)
    loss = ops.reduce_mean_op(
        ops.softmaxcrossentropy_sparse_op(logits, labels), [0])
    feeds = {"input_ids": input_ids, "token_type_ids": token_type_ids,
             "labels": labels}
    if attention_mask is not None:
        feeds["attention_mask"] = attention_mask
    return feeds, loss, logits


def synthetic_mlm_batch(cfg, seed=0, mask_frac=0.15, full_frac=0.35):
    """Deterministic synthetic MLM batch (hermetic benches/tests).

    Returns (ids, token_type_ids, labels, attention_mask).  Sequence lengths
    follow a padded-pretraining distribution: ``full_frac`` of the batch is
    packed full-length, the rest is uniform over [seq/4, seq] (real MLM
    corpora mix packed segments with short documents).  Positions beyond a
    row's length are PAD: id 0, label -1, attention_mask 0.  A row carries
    at most ``cfg.max_predictions_per_seq`` labels, the cap of the
    published data pipeline (``create_pretraining_data.py``).
    """
    rng = np.random.RandomState(seed)
    b, s = cfg.batch_size, cfg.seq_len
    ids = rng.randint(0, cfg.vocab_size, (b, s))
    tt = np.zeros((b, s), np.int32)
    lengths = np.full((b,), s, np.int32)
    short = rng.rand(b) >= full_frac
    lengths[short] = rng.randint(max(1, s // 4), s + 1, short.sum())
    attn = (np.arange(s)[None, :] < lengths[:, None])
    ids[~attn] = 0
    labels = np.full((b, s), -1, np.int64)
    mask = (rng.rand(b, s) < mask_frac) & attn
    for row in np.flatnonzero(mask.sum(1) > cfg.max_predictions_per_seq):
        at = rng.permutation(np.flatnonzero(mask[row]))
        mask[row, at[cfg.max_predictions_per_seq:]] = False
    labels[mask] = ids[mask]
    return (ids.astype(np.int32), tt, labels.astype(np.int32),
            attn.astype(np.int32))
