"""Plain reference: GPT-2 causal language model, full-sequence forward.

Straightforward ``jax.numpy`` float32 (Radford et al. 2019: pre-LN blocks,
learned positions, tanh gelu), no cache, no kernels, no batching: one
sequence in, the logits of every position out.  Departure, stated in the
configuration file: the output head is its own ``(n_embd, vocab)`` matrix
with a bias (the program's graph does not tie it to ``wte``).  Matrix
products run at ``highest`` precision.  It imports nothing of the program;
parameter NAMES are the program's checkpoint names.

``precision="bfloat16"`` is the CONTROL, not a reference: weights,
activations and products all in bfloat16 — the step below the float32 the
configuration states.
"""
import math


def param_spec(cfg):
    """``{checkpoint name: (shape, mean, std)}`` in a fixed order."""
    h = cfg["n_embd"]
    std = cfg["initializer_range"]
    spec = {"gpt2.wte": ((cfg["vocab_size"], h), 0.0, std),
            "gpt2.wpe": ((cfg["n_positions"], h), 0.0, std / 2)}

    def norm(name):
        spec[name + ".scale"] = ((h,), 1.0, std)
        spec[name + ".bias"] = ((h,), 0.0, std)

    def dense(name, n_in, n_out):
        spec[name + ".weight"] = ((n_in, n_out), 0.0, std)
        spec[name + ".bias"] = ((n_out,), 0.0, std)

    for i in range(cfg["n_layer"]):
        p = f"gpt2.h{i}"
        norm(p + ".ln1")
        for leaf in ("q", "k", "v", "o"):
            dense(f"{p}.attn.{leaf}", h, h)
        norm(p + ".ln2")
        dense(p + ".mlp_fc", h, 4 * h)
        dense(p + ".mlp_proj", 4 * h, h)
    norm("gpt2.ln_f")
    dense("gpt2.lm_head", h, cfg["vocab_size"])
    return spec


def logits(params, ids, cfg, precision="highest"):
    """(T,) int token ids -> (T, vocab) float32 logits, causal."""
    import jax
    import jax.numpy as jnp
    if precision == "highest":
        dt, prec = jnp.float32, jax.lax.Precision.HIGHEST
    elif precision == "bfloat16":
        dt, prec = jnp.bfloat16, jax.lax.Precision.DEFAULT
    else:
        raise ValueError(f"unknown precision {precision!r}")
    w = {k: v.astype(dt) for k, v in params.items()}
    t = ids.shape[0]
    h, heads = cfg["n_embd"], cfg["n_head"]
    eps = cfg["layer_norm_epsilon"]

    def norm(x, w, name):
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + eps) \
            * w[name + ".scale"] + w[name + ".bias"]

    def dense(x, w, name):
        return jnp.einsum("ti,io->to", x, w[name + ".weight"],
                          precision=prec) + w[name + ".bias"]

    def gelu(x):
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))

    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(x, w):
        a = norm(x, w, "ln1")
        q, k, v = (dense(a, w, "attn." + n).reshape(t, heads, h // heads)
                   for n in "qkv")
        scores = jnp.einsum("qhd,khd->hqk", q, k, precision=prec) \
            / math.sqrt(h // heads)
        scores = jnp.where(causal[None], scores, -1e30)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(dt)
        ctx = jnp.einsum("hqk,khd->qhd", probs, v,
                         precision=prec).reshape(t, h)
        x = x + dense(ctx, w, "attn.o")
        x = x + dense(gelu(dense(norm(x, w, "ln2"), w, "mlp_fc")), w,
                      "mlp_proj")
        return x, None

    # the blocks are alike: one scanned body keeps the compiled reference
    # small, whatever the depth
    leaves = [k[len("gpt2.h0."):] for k in w if k.startswith("gpt2.h0.")]
    stacked = {leaf: jnp.stack([w[f"gpt2.h{i}.{leaf}"]
                                for i in range(cfg["n_layer"])])
               for leaf in leaves}
    x = w["gpt2.wte"][ids] + w["gpt2.wpe"][:t]
    x, _ = jax.lax.scan(block, x, stacked)
    return dense(norm(x, w, "gpt2.ln_f"), w,
                 "gpt2.lm_head").astype(jnp.float32)
