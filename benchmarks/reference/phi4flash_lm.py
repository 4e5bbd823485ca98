"""Plain reference: Phi-4-mini-flash-reasoning (SambaY, arXiv:2507.06607),
full-sequence forward.

Straightforward ``jax.numpy`` float32 written from the equations, one
sequence in, the logits of every position out: no cache, no kernels, no
batching, the selective scan a ``lax.scan`` over time, attention over the
whole sequence under its mask (in blocks of queries, so that the scores of
a 4,608-token sequence fit).  Matrix products run at ``highest`` precision.
It imports nothing of the program; parameter NAMES and shapes are the
program's checkpoint names.  It takes the weights as the configuration
stores them (rounded to bfloat16) and computes on their float32 values.

The model, layer ``l`` of ``n``, ``half = n // 2`` (``n = 32``: 9 Mamba, 8
window, 1 full, 7 gated-memory, 7 cross)::

    x0 = E[ids]                                  (no positional term)
    h  = x + Mix_l(LN(x));  x' = h + W_down(silu(g) * u),  [g, u] = W_gate_up LN'(h)
    logits = LN_f(x_n) E^T

    ssm   (l even, l <= half)  Mamba-1; layer ``half`` hands its scan output
                               (before the z gate) on as the memory m_t
    swa   (l odd,  l <  half)  differential attention, causal, i - j < window
    full  (l = half + 1)       differential attention, causal
    cross (l odd,  l >  half+1) W_q and W_o only; keys and values of ``full``
    gmu   (l even, l >  half)  W2(silu(W1 y) * m_t)

Differential attention (arXiv:2410.05258): heads ``2p``, ``2p+1`` are pair
``p``; key/value heads ``2g``, ``2g+1`` pair ``g``; pair ``p`` reads pair ``p
// (P // G)``.  ``A1 = softmax(q1 k1^T / sqrt(D))``, ``A2`` likewise, ``o =
(A1 - lam A2) [v1; v2]``, ``o <- RMSNorm(o) * w * (1 - lam_init)``, ``lam =
exp(lq1.lk1) - exp(lq2.lk2) + lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3 l)``.

``precision="bfloat16"`` is the WITNESS of the stated precision: every matrix
product takes its operands through bfloat16 and sums in float32, which is
what the program does with bfloat16 weights, keys and values.  No cell's
``correct`` reads it; ``tools/decode_precision_witness.py`` does, to tell a
sound run's gap to this reference (rounding) from a fault's.

``precision="fp8"`` is the CONTROL, not a reference: every matrix product
takes its operands through float8_e4m3 with a per-tensor scale — below the
bfloat16 the configuration states.  The comparison that decides ``correct``
has to fail it.
"""
import functools
import math


def sizes(cfg):
    """The sizes the equations use, from the configuration's published
    keys and the ``assumed`` ones the published file lacks."""
    a = cfg["assumed"]
    d = cfg["hidden_size"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"d": d, "ffn": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"], "vocab": cfg["vocab_size"],
            "heads": heads, "kv_heads": kv_heads, "head_dim": d // heads,
            "window": cfg["sliding_window"], "eps": cfg["layer_norm_eps"],
            "e": a["d_inner"], "n": a["d_state"], "k": a["d_conv"],
            "r": a["dt_rank"]}


def layer_kind(cfg, i):
    half = cfg["num_hidden_layers"] // 2
    if i <= half:
        return "swa" if i % 2 else "ssm"
    if i == half + 1:
        return "full"
    return "cross" if i % 2 else "gmu"


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def layer_spec(cfg, i):
    """``{leaf name inside the layer: (shape, mean, std)}`` of layer ``i``."""
    z = sizes(cfg)
    d, e, n, r, hd = z["d"], z["e"], z["n"], z["r"], z["head_dim"]
    std = cfg["assumed"]["initializer_range"]
    kv = z["kv_heads"] * hd
    spec = {"ln1.scale": ((d,), 1.0, std), "ln1.bias": ((d,), 0.0, std)}
    kind = layer_kind(cfg, i)
    if kind == "ssm":
        spec.update({
            "ssm.in_proj.weight": ((d, 2 * e), 0.0, std),
            "ssm.conv.weight": ((z["k"], e), 0.0, 1 / math.sqrt(z["k"])),
            "ssm.conv.bias": ((e,), 0.0, std),
            "ssm.x_proj.weight": ((e, r + 2 * n), 0.0, std),
            "ssm.dt_proj.weight": ((r, e), 0.0, std),
            "ssm.dt_proj.bias": ((e,), -4.6, 1.0),
            "ssm.A_log": ((n, e), 1.5, 0.7),
            "ssm.D": ((e,), 1.0, std),
            "ssm.out_proj.weight": ((e, d), 0.0, std)})
    elif kind == "gmu":
        spec.update({"gmu.in_proj.weight": ((d, e), 0.0, std),
                     "gmu.out_proj.weight": ((e, d), 0.0, std)})
    else:
        if kind == "cross":
            spec["attn.q.weight"] = ((d, d), 0.0, std)
        else:
            spec["attn.qkv.weight"] = ((d, d + 2 * kv), 0.0, std)
        for leaf in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            spec["attn." + leaf] = ((hd,), 0.0, 0.1)
        spec["attn.subln.weight"] = ((2 * hd,), 1.0, std)
        spec["attn.o.weight"] = ((d, d), 0.0, std)
    spec.update({"ln2.scale": ((d,), 1.0, std), "ln2.bias": ((d,), 0.0, std),
                 "mlp.gate_up.weight": ((d, 2 * z["ffn"]), 0.0, std),
                 "mlp.down.weight": ((z["ffn"], d), 0.0, std)})
    return spec


def param_spec(cfg):
    """``{checkpoint name: (shape, mean, std)}`` in a fixed order."""
    z = sizes(cfg)
    std = cfg["assumed"]["initializer_range"]
    spec = {"phi4.embed": ((z["vocab"], z["d"]), 0.0, std)}
    for i in range(z["layers"]):
        for leaf, entry in layer_spec(cfg, i).items():
            spec[f"phi4.l{i}.{leaf}"] = entry
    spec["phi4.ln_f.scale"] = ((z["d"],), 1.0, std)
    spec["phi4.ln_f.bias"] = ((z["d"],), 0.0, std)
    return spec


def layer_params(params, i):
    """The leaves of layer ``i`` under their names inside the layer."""
    p = f"phi4.l{i}."
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


def _layer_norm(x, scale, bias, eps):
    import jax
    import jax.numpy as jnp
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def _mamba(w, y, z, ein):
    """(T, d) -> (mixer output (T, d), scan output before the gate (T, E))."""
    import jax
    import jax.numpy as jnp
    t = y.shape[0]
    e, n, r, k = z["e"], z["n"], z["r"], z["k"]
    uz = ein("ti,io->to", y, w["ssm.in_proj.weight"])
    u, gate = uz[:, :e], uz[:, e:]
    padded = jnp.concatenate([jnp.zeros((k - 1, e), u.dtype), u], axis=0)
    u = sum(padded[i:i + t] * w["ssm.conv.weight"][i] for i in range(k))
    u = jax.nn.silu(u + w["ssm.conv.bias"])
    dbc = ein("te,eo->to", u, w["ssm.x_proj.weight"])
    delta = jax.nn.softplus(
        ein("tr,re->te", dbc[:, :r], w["ssm.dt_proj.weight"])
        + w["ssm.dt_proj.bias"])
    b_in, c_out = dbc[:, r:r + n], dbc[:, r + n:]
    a = -jnp.exp(w["ssm.A_log"])                              # (N, E)

    def step(s, inp):
        u_t, d_t, b_t, c_t = inp
        s = jnp.exp(d_t[None, :] * a) * s + (d_t * u_t)[None, :] * b_t[:, None]
        return s, jnp.sum(s * c_t[:, None], axis=0)

    _, scanned = jax.lax.scan(step, jnp.zeros((n, e), jnp.float32),
                              (u, delta, b_in, c_out))
    scanned = scanned + w["ssm.D"] * u
    return (ein("te,eo->to", scanned * jax.nn.silu(gate),
                w["ssm.out_proj.weight"]), scanned)


def _diff_attention(w, q, keys, values, z, lam_init, window, ein,
                    block=512):
    """``q``: (T, heads * D); ``keys`` / ``values``: (T, kv_heads * D)."""
    import jax
    import jax.numpy as jnp
    t = q.shape[0]
    hd = z["head_dim"]
    pairs, groups = z["heads"] // 2, z["kv_heads"] // 2
    q = q.reshape(t, pairs, 2, hd)
    k = jnp.repeat(keys.reshape(t, groups, 2, hd), pairs // groups, axis=1)
    v = jnp.repeat(values.reshape(t, groups, 2 * hd), pairs // groups, axis=1)
    lam = (jnp.exp(jnp.sum(w["attn.lambda_q1"] * w["attn.lambda_k1"]))
           - jnp.exp(jnp.sum(w["attn.lambda_q2"] * w["attn.lambda_k2"]))
           + lam_init)
    cols = jnp.arange(t)

    def rows(args):
        qb, at = args                        # (Q, P, 2, D), (Q,) positions
        s = ein("qpwd,kpwd->pwqk", qb, k) / math.sqrt(hd)
        seen = cols[None, :] <= at[:, None]
        if window is not None:
            seen = jnp.logical_and(seen, at[:, None] - cols[None, :] < window)
        a = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        o = ein("pqk,kpl->qpl", a[:, 0] - lam * a[:, 1], v)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + 1e-5)
        return (o * w["attn.subln.weight"] * (1.0 - lam_init)).reshape(
            qb.shape[0], pairs * 2 * hd)

    block = min(block, t)
    pad = -t % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    at = jnp.arange(t + pad)
    out = jax.lax.map(rows, (qp.reshape(-1, block, pairs, 2, hd),
                             at.reshape(-1, block)))
    return out.reshape(t + pad, -1)[:t]


def embed(table, ids):
    import jax.numpy as jnp
    return table.astype(jnp.float32)[ids]


def layer(kind, w, x, carry, lam_init, cfg, precision="highest"):
    """One layer over one sequence.  ``x``: (T, d); ``carry``: what later
    layers read — ``memory`` (T, E) after a Mamba layer, ``kv`` ((T, kv),
    (T, kv)) after the full layer.  Returns ``(x', carry')``."""
    import jax
    z = sizes(cfg)
    ein = _einsum(precision)
    w = _f32(w)
    d, hd = z["d"], z["head_dim"]
    kv = z["kv_heads"] * hd
    y = _layer_norm(x, w["ln1.scale"], w["ln1.bias"], z["eps"])
    carry = dict(carry)
    if kind == "ssm":
        mixed, carry["memory"] = _mamba(w, y, z, ein)
    elif kind == "gmu":
        gate = ein("ti,io->to", y, w["gmu.in_proj.weight"])
        mixed = ein("te,eo->to", jax.nn.silu(gate) * carry["memory"],
                    w["gmu.out_proj.weight"])
    else:
        if kind == "cross":
            q = ein("ti,io->to", y, w["attn.q.weight"])
            keys, values = carry["kv"]
        else:
            qkv = ein("ti,io->to", y, w["attn.qkv.weight"])
            q, keys, values = qkv[:, :d], qkv[:, d:d + kv], qkv[:, d + kv:]
            if kind == "full":
                carry["kv"] = (keys, values)
        att = _diff_attention(w, q, keys, values, z, lam_init,
                              z["window"] if kind == "swa" else None, ein)
        mixed = ein("ti,io->to", att, w["attn.o.weight"])
    h = x + mixed
    gu = ein("ti,io->to", _layer_norm(h, w["ln2.scale"], w["ln2.bias"],
                                      z["eps"]), w["mlp.gate_up.weight"])
    ffn = z["ffn"]
    return h + ein("tf,fo->to", jax.nn.silu(gu[:, :ffn]) * gu[:, ffn:],
                   w["mlp.down.weight"]), carry


def head(table, scale, bias, x, cfg, precision="highest"):
    """``LN_f(x) E^T``: (rows, d) -> (rows, vocab) float32."""
    import jax.numpy as jnp
    f = jnp.float32
    y = _layer_norm(x, scale.astype(f), bias.astype(f),
                    cfg["layer_norm_eps"])
    return _einsum(precision)("td,vd->tv", y, table.astype(f))


def logits(params, ids, cfg, precision="highest"):
    """(T,) int token ids -> (T, vocab) float32 logits, causal."""
    x = embed(params["phi4.embed"], ids)
    carry = {}
    for i in range(cfg["num_hidden_layers"]):
        x, carry = layer(layer_kind(cfg, i), layer_params(params, i), x,
                         carry, lambda_init(i), cfg, precision)
    return head(params["phi4.embed"], params["phi4.ln_f.scale"],
                params["phi4.ln_f.bias"], x, cfg, precision)
