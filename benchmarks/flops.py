"""Operation and byte counts, as functions of a configuration's sizes.

Kept with the benchmark so that no later PR can move the yardstick.  A
multiply-add counts as two operations.  Recomputed operations never count.
"""


def bert_mlm_train_flops(cfg, batch, seq, masked):
    """Forward + backward operations one BERT MLM training step REQUIRES.

    Matrix products only (they are >99% of the work): per token and layer
    the four attention projections (4·h²) and the two FFN products
    (2·h·ffn); attention's QKᵀ and PV (2·seq·h per token and layer); the MLM
    transform (h²) and decoder (h·vocab) on the ``masked`` positions only —
    the loss reads no other, so a graph that projects every position onto
    the vocabulary does work this count does not credit.  Backward is twice
    the forward (one product for the input gradient, one for the weight
    gradient).
    """
    h = cfg["hidden_size"]
    ffn = cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    vocab = cfg["vocab_size"]
    tokens = batch * seq
    trunk = layers * (4 * h * h + 2 * h * ffn + 2 * seq * h) * tokens
    head = (h * h + h * vocab) * masked
    return 3 * 2 * (trunk + head)


def flash_fwd_bwd(batch, heads, seq, head_dim, dtype_bytes):
    """(operations, bytes) of one non-causal attention layer, forward and
    backward, as an ideal kernel needs them: forward QKᵀ and PV; backward
    recomputes QKᵀ (inherent to flash: the scores are never stored) and
    does dV, dP, dQ, dK — 2 + 5 products of 2·seq²·d each.  Bytes: forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and writes
    dq, dk, dv (row statistics are negligible)."""
    one = 2 * batch * heads * seq * seq * head_dim
    tensor = batch * heads * seq * head_dim * dtype_bytes
    return 7 * one, (4 + 8) * tensor


def roofline_seconds(ops, nbytes, peak):
    """Least time the chip could take, and which bound sets it."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")
