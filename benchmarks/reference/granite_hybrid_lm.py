"""Plain reference: Granite-4.0-H (``granitemoehybrid``; Mamba-2 layers 9:1
with NoPE grouped-query attention, dense SwiGLU, muP multipliers, a tied
head), full-sequence forward.

Straightforward ``jax.numpy`` float32 written from the equations, one
sequence in, the logits of every position out: no cache, no kernels, no
batching, the Mamba-2 recurrence TOKEN BY TOKEN as a ``lax.scan`` over time
(the definition, not the chunk form the program computes), attention over
the whole sequence under its causal mask.  Matrix products run at
``highest`` precision.  It imports nothing of the program; parameter NAMES
and shapes are the program's checkpoint names.  It takes the weights as the
configuration stores them (rounded to bfloat16) and computes on their float32
values.

With ``n`` RMSNorm (eps ``rms_norm_eps``, a learned scale) and ``r =
residual_multiplier``::

    x0 = embedding_multiplier · E[ids]             (no positional term)
    h  = x + r · Mix_l(n(x));   x' = h + r · W_out(silu(a) ⊙ b),  [a | b] = W_in n'(h)
    logits = n_f(x_L) Eᵀ / logits_scaling          (the head IS the embedding)

    gqa  (layer_types[l] == "attention")  q, k, v = W y; H heads read G key heads
          (head h reads h // (H // G)); softmax(q kᵀ · attention_multiplier)
          causal, no positional term, no bias; W_o
    ssm  ("mamba")  [z | xBC | dt] = W_in y of widths E | E + 2GN | H;
          xBC <- silu(conv1d_causal_depthwise(xBC, K) + bias); [x | B | C] = xBC;
          per head h (its group g = h // (H // G)):
            Δ_t = softplus(dt_t + dt_bias_h);  a_t = exp(−Δ_t exp(A_log_h))
            S_t = a_t S_{t−1} + Δ_t x_t B_tᵀ     (S: P × N, zero at t = 0)
            y_t = S_t C_t + D_h x_t
          o = RMSNorm_E(y ⊙ silu(z)) ⊙ w;  W_out o

Departures from the published code (``modeling_granitemoehybrid.py``), each
also under ``assumed`` in the configuration: the recurrent state is float32
whatever the model's type (the published code keeps it in the model's);
``time_step_limit`` is (0, ∞), the published default, so no clamp of ``Δ`` is
written; the norm of the Mamba mixer gates FIRST and norms the whole inner
width as one group (``mamba_n_groups`` 1 at the published size; with more
groups this reference still norms the whole width); ``head_dim`` is
``hidden_size / num_attention_heads``; the feed-forward is the dense
``shared_mlp`` alone (``num_local_experts`` 0: no router is built).

``precision="bfloat16"`` is the WITNESS of the stated precision: every matrix
product with a stored matrix, and the attention's two, takes its operands
through bfloat16 and sums in float32, which is what the program does with
bfloat16 weights, keys and values.  The recurrence stays float32, as the
program's does.  ``precision="fp8"`` is the CONTROL, not a reference: those
products take their operands through float8_e4m3 with a per-tensor scale.
The comparison that decides ``correct`` has to fail it.
"""
import functools
import math

#: the stem of every parameter name, and the leaves the head reads
STEM = "granite"
HEAD = ("embed", "ln_f.scale")


def sizes(cfg):
    """The sizes the equations use, from the configuration's published
    keys and the one the published file lacks (``assumed.head_dim``)."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    g, n = cfg["mamba_n_groups"], cfg["mamba_d_state"]
    return {"d": cfg["hidden_size"], "ffn": cfg["shared_intermediate_size"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["assumed"]["head_dim"]["value"],
            "h": h, "p": p, "g": g, "n": n, "e": h * p,
            "conv": h * p + 2 * g * n, "k": cfg["mamba_d_conv"],
            "eps": cfg["rms_norm_eps"]}


def layer_kind(cfg, i):
    return "gqa" if cfg["layer_types"][i] == "attention" else "ssm"


def layer_spec(cfg, i):
    """``{leaf name inside the layer: (shape, mean, std)}`` of layer ``i``."""
    z = sizes(cfg)
    a = cfg["assumed"]["weights"]
    d, std = z["d"], a["initializer_range"]
    spec = {"ln1.scale": ((d,), 1.0, std)}
    if layer_kind(cfg, i) == "ssm":
        spec.update({
            "mamba.in_proj.weight": ((d, z["e"] + z["conv"] + z["h"]), 0.0,
                                     std),
            "mamba.conv.weight": ((z["k"], z["conv"]), 0.0,
                                  1 / math.sqrt(z["k"])),
            "mamba.conv.bias": ((z["conv"],), 0.0, std),
            "mamba.dt_bias": ((z["h"],), *a["dt_bias"]),
            "mamba.A_log": ((z["h"],), *a["A_log"]),
            "mamba.D": ((z["h"],), *a["D"]),
            "mamba.norm.scale": ((z["e"],), 1.0, std),
            "mamba.out_proj.weight": ((z["e"], d), 0.0, std)})
    else:
        q_w, kv_w = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
        spec.update({"attn.qkv.weight": ((d, q_w + 2 * kv_w), 0.0, std),
                     "attn.o.weight": ((q_w, d), 0.0, std)})
    spec.update({"ln2.scale": ((d,), 1.0, std),
                 "mlp.gate_up.weight": ((d, 2 * z["ffn"]), 0.0, std),
                 "mlp.down.weight": ((z["ffn"], d), 0.0, std)})
    return spec


def param_spec(cfg):
    """``{checkpoint name: (shape, mean, std)}`` in a fixed order."""
    z = sizes(cfg)
    std = cfg["assumed"]["weights"]["initializer_range"]
    spec = {f"{STEM}.embed": ((z["vocab"], z["d"]), 0.0, std)}
    for i in range(z["layers"]):
        for leaf, entry in layer_spec(cfg, i).items():
            spec[f"{STEM}.l{i}.{leaf}"] = entry
    spec[f"{STEM}.ln_f.scale"] = ((z["d"],), 1.0, std)
    return spec


def layer_params(params, i):
    """The leaves of layer ``i`` under their names inside the layer."""
    p = f"{STEM}.l{i}."
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}


# ------------------------------------------------------------- the maths

def _quant_fp8(x):
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _einsum(precision):
    import jax
    import jax.numpy as jnp
    if precision == "fp8":
        return lambda eq, a, b: jnp.einsum(
            eq, _quant_fp8(a), _quant_fp8(b),
            precision=jax.lax.Precision.HIGHEST)
    if precision == "bfloat16":
        def rounded(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return lambda eq, a, b: jnp.einsum(
            eq, rounded(a), rounded(b), precision=jax.lax.Precision.HIGHEST)
    if precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return functools.partial(jnp.einsum, precision=jax.lax.Precision.HIGHEST)


def _f32(w):
    import jax.numpy as jnp
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _rms_norm(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _mamba2(w, y, z, ein):
    """(T, d) -> the mixer's output (T, d)."""
    import jax
    import jax.numpy as jnp
    t = y.shape[0]
    e, h, p, g, n, k = z["e"], z["h"], z["p"], z["g"], z["n"], z["k"]
    zxd = ein("ti,io->to", y, w["mamba.in_proj.weight"])
    gate, xbc, dt = zxd[:, :e], zxd[:, e:e + z["conv"]], zxd[:, e + z["conv"]:]
    padded = jnp.concatenate([jnp.zeros((k - 1, z["conv"]), xbc.dtype), xbc])
    xbc = sum(padded[i:i + t] * w["mamba.conv.weight"][i] for i in range(k))
    xbc = jax.nn.silu(xbc + w["mamba.conv.bias"])
    x = xbc[:, :e].reshape(t, h, p)
    # a head reads the B and C of its group
    b_in = jnp.repeat(xbc[:, e:e + g * n].reshape(t, g, n), h // g, axis=1)
    c_out = jnp.repeat(xbc[:, e + g * n:].reshape(t, g, n), h // g, axis=1)
    delta = jax.nn.softplus(dt + w["mamba.dt_bias"])             # (T, H)
    a = -jnp.exp(w["mamba.A_log"])                               # (H,)

    def step(s, inp):
        x_t, d_t, b_t, c_t = inp          # (H, P), (H,), (H, N), (H, N)
        s = jnp.exp(d_t * a)[:, None, None] * s \
            + (d_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.sum(s * c_t[:, None, :], axis=-1)

    _, scanned = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                              (x, delta, b_in, c_out))
    scanned = (scanned + w["mamba.D"][:, None] * x).reshape(t, e)
    normed = _rms_norm(scanned * jax.nn.silu(gate), w["mamba.norm.scale"],
                       z["eps"])
    return ein("te,eo->to", normed, w["mamba.out_proj.weight"])


def _attention(w, y, z, scale, ein):
    """(T, d) -> the mixer's output (T, d): full causal softmax attention,
    every query over every key."""
    import jax
    import jax.numpy as jnp
    t = y.shape[0]
    hd, heads, groups = z["head_dim"], z["heads"], z["kv_heads"]
    q_w, kv_w = heads * hd, groups * hd
    qkv = ein("ti,io->to", y, w["attn.qkv.weight"])
    q = qkv[:, :q_w].reshape(t, heads, hd)
    k = jnp.repeat(qkv[:, q_w:q_w + kv_w].reshape(t, groups, hd),
                   heads // groups, axis=1)
    v = jnp.repeat(qkv[:, q_w + kv_w:].reshape(t, groups, hd),
                   heads // groups, axis=1)
    s = ein("qhd,khd->hqk", q, k) * scale
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
    att = ein("hqk,khd->qhd", probs, v).reshape(t, q_w)
    return ein("ti,io->to", att, w["attn.o.weight"])


def embed(table, ids, cfg):
    import jax.numpy as jnp
    return table.astype(jnp.float32)[ids] * cfg["embedding_multiplier"]


def layer(kind, w, x, carry, cfg, precision="highest"):
    """One layer over one sequence.  ``x``: (T, d); no layer reads
    another's state, so ``carry`` passes through.  Returns ``(x',
    carry)``."""
    import jax
    z = sizes(cfg)
    ein = _einsum(precision)
    w = _f32(w)
    r = cfg["residual_multiplier"]
    y = _rms_norm(x, w["ln1.scale"], z["eps"])
    mixed = _mamba2(w, y, z, ein) if kind == "ssm" \
        else _attention(w, y, z, cfg["attention_multiplier"], ein)
    h = x + r * mixed
    gu = ein("ti,io->to", _rms_norm(h, w["ln2.scale"], z["eps"]),
             w["mlp.gate_up.weight"])
    ffn = z["ffn"]
    return h + r * ein("tf,fo->to", jax.nn.silu(gu[:, :ffn]) * gu[:, ffn:],
                       w["mlp.down.weight"]), carry


def head(table, scale, x, cfg, precision="highest"):
    """``n_f(x) Eᵀ / logits_scaling``: (rows, d) -> (rows, vocab) float32."""
    import jax.numpy as jnp
    f = jnp.float32
    y = _rms_norm(x, scale.astype(f), cfg["rms_norm_eps"])
    return _einsum(precision)("td,vd->tv", y, table.astype(f)) \
        / cfg["logits_scaling"]


def logits(params, ids, cfg, precision="highest"):
    """(T,) int token ids -> (T, vocab) float32 logits, causal."""
    x = embed(params[f"{STEM}.embed"], ids, cfg)
    for i in range(cfg["num_hidden_layers"]):
        x, _ = layer(layer_kind(cfg, i), layer_params(params, i), x, {}, cfg,
                     precision)
    return head(params[f"{STEM}.embed"], params[f"{STEM}.ln_f.scale"], x,
                cfg, precision)
