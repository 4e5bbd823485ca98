"""Plain reference: BERT masked-language-model training, float32.

Straightforward ``jax.numpy``: no kernels, no fused attention, no mixed
precision.  It follows Devlin et al. 2018 (post-LN encoder, learned absolute
positions, MLM head = dense + gelu + LayerNorm + decoder) with the
departures the configuration file states: gelu is the tanh approximation,
the decoder matrix is its own parameter, dropout is 0.  Matrix products run
at ``highest`` precision (on a TPU a float32 product is otherwise a single
bfloat16 pass).  It imports nothing of the program; parameter NAMES are the
program's checkpoint names, which is the one thing the two share.

``precision="fp8"`` is the CONTROL, not a reference: every matrix product
takes its operands through float8_e4m3 with a per-tensor scale — the step
below the bfloat16 the configuration states, which a later PR might be
tempted by.  The comparison that decides ``correct`` has to fail it.
"""
import functools
import math

def param_spec(cfg):
    """``{checkpoint name: (shape, mean, std)}`` in a fixed order."""
    h, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    std = cfg["initializer_range"]
    spec = {
        "bert.embeddings.word.weight": ((cfg["vocab_size"], h), 0.0, std),
        "bert.embeddings.position":
            ((cfg["max_position_embeddings"], h), 0.0, std),
        "bert.embeddings.token_type.weight":
            ((cfg["type_vocab_size"], h), 0.0, std),
    }

    def norm(name):
        spec[name + ".scale"] = ((h,), 1.0, std)
        spec[name + ".bias"] = ((h,), 0.0, std)

    def dense(name, n_in, n_out):
        spec[name + ".weight"] = ((n_in, n_out), 0.0, std)
        spec[name + ".bias"] = ((n_out,), 0.0, std)

    norm("bert.embeddings.ln")
    for i in range(cfg["num_hidden_layers"]):
        p = f"bert.layer{i}"
        for leaf in ("q", "k", "v", "o"):
            dense(f"{p}.attn.{leaf}", h, h)
        norm(p + ".ln1")
        dense(p + ".ffn1", h, ffn)
        dense(p + ".ffn2", ffn, h)
        norm(p + ".ln2")
    dense("bert.mlm_transform", h, h)
    norm("bert.mlm_ln")
    dense("bert.mlm_decoder", h, cfg["vocab_size"])
    return spec


def _quant_fp8(x):
    """x through float8_e4m3 with a per-tensor scale, straight-through."""
    import jax
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _einsum(precision):
    import jax
    import jax.numpy as jnp
    if precision == "fp8":
        return lambda eq, a, b: jnp.einsum(
            eq, _quant_fp8(a), _quant_fp8(b),
            precision=jax.lax.Precision.HIGHEST)
    if precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def _layer_norm(x, scale, bias, eps):
    import jax
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _gelu(x):
    import jax.numpy as jnp
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def loss_sum(params, batch, cfg, precision="highest"):
    """Sum of the cross-entropy over the masked positions of ``batch``
    (rows of input_ids, token_type_ids, attention_mask, masked_lm_labels;
    label -1 = not masked)."""
    import jax
    import jax.numpy as jnp
    es = _einsum(precision)
    ids, tt, attn, labels = (batch[k] for k in (
        "input_ids", "token_type_ids", "attention_mask", "masked_lm_labels"))
    b, s = ids.shape
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    eps = cfg["layer_norm_eps"]
    x = params["bert.embeddings.word.weight"][ids] \
        + params["bert.embeddings.position"][:s][None] \
        + params["bert.embeddings.token_type.weight"][tt]
    x = _layer_norm(x, params["bert.embeddings.ln.scale"],
                    params["bert.embeddings.ln.bias"], eps)
    key_bias = jnp.where(attn[:, None, None, :] > 0, 0.0, -1e30)

    def dense(x, w, name):
        return es("...i,io->...o", x, w[name + ".weight"]) \
            + w[name + ".bias"]

    def layer(x, w):
        def split(t):
            return t.reshape(b, s, heads, h // heads).transpose(0, 2, 1, 3)
        q, k, v = (split(dense(x, w, "attn." + n)) for n in "qkv")
        scores = es("bhqd,bhkd->bhqk", q, k) / math.sqrt(h // heads)
        probs = jax.nn.softmax(scores + key_bias, axis=-1)
        ctx = es("bhqk,bhkd->bhqd", probs, v)
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h)
        x = _layer_norm(x + dense(ctx, w, "attn.o"),
                        w["ln1.scale"], w["ln1.bias"], eps)
        f = dense(_gelu(dense(x, w, "ffn1")), w, "ffn2")
        return _layer_norm(x + f, w["ln2.scale"], w["ln2.bias"], eps), None

    # the layers are alike: one scanned (and rematerialised) body keeps the
    # compiled reference small, whatever the depth
    depth = cfg["num_hidden_layers"]
    leaves = [k[len("bert.layer0."):] for k in params
              if k.startswith("bert.layer0.")]
    stacked = {leaf: jnp.stack([params[f"bert.layer{i}.{leaf}"]
                                for i in range(depth)]) for leaf in leaves}
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stacked)
    t = _gelu(dense(x, params, "bert.mlm_transform"))
    t = _layer_norm(t, params["bert.mlm_ln.scale"],
                    params["bert.mlm_ln.bias"], eps)
    logits = dense(t, params, "bert.mlm_decoder")
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(labels >= 0, picked, 0.0))


def _adam(params, grads, state, opt):
    import jax.numpy as jnp
    b1, b2, eps, lr = (opt[k] for k in (
        "beta1", "beta2", "epsilon", "learning_rate"))
    t = state["t"] + 1
    bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
    new_p, m, v = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        m[k] = state["m"][k] + (1 - b1) * (g - state["m"][k])
        v[k] = state["v"][k] + (1 - b2) * (g * g - state["v"][k])
        new_p[k] = p - lr * ((m[k] / bc1) / (jnp.sqrt(v[k] / bc2) + eps))
    return new_p, {"m": m, "v": v, "t": t}


def follow(params, batches, cfg, precision="highest", block_rows=8):
    """Train ``len(batches)`` steps from ``params``.  Returns
    ``{"losses": [...], "grad_norm": {leaf: ‖g₁‖}, "delta_norm": {leaf:
    ‖p_n − p_0‖}, "first_grads": {leaf: g₁}}`` — the first gradient and the
    parameters' change after the last step, one norm per leaf, and the first
    gradient itself (device arrays).  Rows go through in blocks of
    ``block_rows`` (the loss is a sum over positions, so gradients add)."""
    import jax
    import jax.numpy as jnp
    opt = cfg["optimizer"]
    if cfg["dropout"]:
        raise ValueError("the reference has no dropout: it cannot follow "
                         "masks the program draws from its own keys")
    if opt["kind"] != "adam":
        raise ValueError(f"reference has no optimizer {opt['kind']!r}")
    grad_fn = jax.jit(jax.value_and_grad(
        functools.partial(loss_sum, cfg=cfg, precision=precision)))
    adam = jax.jit(functools.partial(_adam, opt=opt))
    norms = jax.jit(lambda tree: {k: jnp.sqrt(jnp.sum(jnp.square(v)))
                                  for k, v in tree.items()})
    start = params
    state = {"m": jax.tree.map(jnp.zeros_like, params),
             "v": jax.tree.map(jnp.zeros_like, params), "t": 0}
    out = {"losses": []}
    for step, batch in enumerate(batches):
        rows = batch["input_ids"].shape[0]
        count = float((batch["masked_lm_labels"] >= 0).sum()) + 1e-6
        total, grads = 0.0, None
        for r in range(0, rows, block_rows):
            blk = {k: jnp.asarray(v[r:r + block_rows])
                   for k, v in batch.items()}
            val, g = grad_fn(params, blk)
            total += float(val)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        grads = jax.tree.map(lambda g: g / count, grads)
        out["losses"].append(total / count)
        if step == 0:
            out["grad_norm"] = {k: float(v)
                                for k, v in norms(grads).items()}
            out["first_grads"] = grads
        params, state = adam(params, grads, state)
    out["delta_norm"] = {
        k: float(v) for k, v in norms(
            {k: params[k] - start[k] for k in params}).items()}
    return out
