"""Plain reference: Solar-Open2 (gated-delta-rule linear attention 3:1 with
gated NoPE grouped-query attention; every layer a dropless mixture of
experts with a shared expert), full-sequence forward of ONE CHIP'S SHARE.

Straightforward ``jax.numpy`` float32 written from the equations, one
sequence in, the hidden states of every position out: no cache, no kernels,
no batching, no sorting; the KDA state a ``lax.scan`` over time, attention
over the whole sequence under its causal mask (in blocks of queries), every
held expert applied to every token and weighted by what the router gave it
(zero where it was not chosen).  Matrix products run at ``highest``
precision.  It imports nothing of the program; parameter NAMES and shapes
are the program's checkpoint names.  It takes the weights as the
configuration stores them (rounded to bfloat16) and computes on their
float32 values.

The model, ``x`` the residual, ``n`` RMSNorm with a learned scale::

    x <- x + mixer_i(n(x));   x <- x + moe(n(x));   logits = n(x) W_head

    gqa (i % (gqa_interval + 1) == 0)   [q k v g] = W y; H query heads over
        H / 8 key heads; causal softmax(q.k / sqrt(D)), no positional term;
        out = W_o[att * sigmoid(g)]
    kda (other layers; Kimi Linear, arXiv:2510.26692)
        q, k, v = silu(conv_K(W y)) per channel, causal; q <- q/|q|/sqrt(D),
        k <- k/|k| per head; a_t = exp(-exp(A_h) softplus(W_f^ W_fv y + b));
        beta_t = 2 sigmoid(w_b y);
        S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T;
        o_t = S_t^T q_t;  out = W_o[n_head(o_t) * sigmoid(W_g^ W_gv y)]
    moe  s = sigmoid(W_r y) over ALL experts; chosen = top-k of s + bias;
        w_e = s_e / sum_chosen s;  moe(y) = sum_{e chosen and held} w_e E_e(y)
        + E_shared(y),  E(y) = W_d(silu(W_g y) * W_u y)

**The share** (``cfg["held_experts"]``: ``first``, ``count`` of ``of``): the
router scores all ``of`` experts, the experts ``first .. first + count`` are
held, and what the absent ones would add is left out, as the program leaves
it out.  The head counts and the vocabulary of the configuration are the
share's; with all heads, all experts and the whole vocabulary this is the
uncut model.

**Following a program's routing.**  Among some hundreds of scores the k-th
and the (k+1)-th best lie closer than the rounding of a bfloat16 forward
moves them, and a swapped expert changes everything downstream.  So
``layer(..., choices=ids)`` computes its own float32 scores, reports
``route_margin`` — the most, over the tokens, by which its own k-th best
biased score exceeds the lowest biased score among the experts handed in (0
where they are its own) — and then follows ``ids`` with its own float32
weights for them.  A wrong expert reads a margin of tenths; a sound
program's near-tie one of thousandths.  ``differs`` counts the tokens whose
handed-in set is not the reference's own.

``precision="bfloat16"`` is the WITNESS of the stated precision and
``precision="fp8"`` the CONTROL, as in ``phi4flash_lm.py``: every product
with a stored matrix, and the attention's two, takes its operands through
that type.  The router's product and the KDA state stay float32 in both, as
the configuration states them.
"""
import functools
import math


def sizes(cfg):
    lin, a, held = cfg["linear_attn_config"], cfg["assumed"], \
        cfg["held_experts"]
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"], "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "head_dim": cfg["head_dim"], "lin_heads": cfg["linear_attn_heads"],
            "lin_dim": lin["head_dim"], "conv": lin["short_conv_kernel_size"],
            "rank": a["gate_rank"], "ffn": cfg["moe_intermediate_size"],
            "experts": held["of"], "first": held["first"],
            "held": held["count"], "top_k": cfg["num_experts_per_tok"],
            "eps": cfg["rms_norm_eps"], "period": cfg["gqa_interval"] + 1}


def layer_kind(cfg, i):
    return "kda" if i % (cfg["gqa_interval"] + 1) else "gqa"


def layer_spec(cfg, i):
    """``{leaf name inside the layer: (shape, mean, std)}`` of layer ``i``."""
    z = sizes(cfg)
    d, f, std = z["d"], z["ffn"], cfg["assumed"]["initializer_range"]
    spec = {"ln1.scale": ((d,), 1.0, std)}
    if layer_kind(cfg, i) == "gqa":
        q, kv = z["heads"] * z["head_dim"], z["kv_heads"] * z["head_dim"]
        spec.update({"attn.qkvg.weight": ((d, 2 * q + 2 * kv), 0.0, std),
                     "attn.o.weight": ((q, d), 0.0, std)})
    else:
        h, r, k = z["lin_heads"], z["rank"], z["conv"]
        e = h * z["lin_dim"]
        spec.update({
            "kda.qkv.weight": ((d, 3 * e), 0.0, std),
            "kda.conv.weight": ((k, 3 * e), 0.0, 1 / math.sqrt(k)),
            "kda.f_down.weight": ((d, r), 0.0, std),
            "kda.f_up.weight": ((r, e), 0.0, std),
            "kda.dt_bias": ((e,), -3.0, 1.0),
            "kda.beta.weight": ((d, h), 0.0, std),
            "kda.A_log": ((h,), 1.0, 0.5),
            "kda.g_down.weight": ((d, r), 0.0, std),
            "kda.g_up.weight": ((r, e), 0.0, std),
            "kda.norm.scale": ((z["lin_dim"],), 1.0, std),
            "kda.o.weight": ((e, d), 0.0, std)})
    spec.update({
        "ln2.scale": ((d,), 1.0, std),
        "moe.router.weight": ((d, z["experts"]), 0.0, std),
        "moe.router.bias": ((z["experts"],), 0.0, 0.5 * std),
        "moe.experts.gate_up": ((z["held"], d, 2 * f), 0.0, std),
        "moe.experts.down": ((z["held"], f, d), 0.0, std),
        "moe.shared.gate_up.weight": ((d, 2 * f), 0.0, std),
        "moe.shared.down.weight": ((f, d), 0.0, std)})
    return spec


def param_spec(cfg):
    """``{checkpoint name: (shape, mean, std)}`` in a fixed order."""
    z = sizes(cfg)
    std = cfg["assumed"]["initializer_range"]
    spec = {"solar.embed": ((z["vocab"], z["d"]), 0.0, std)}
    for i in range(z["layers"]):
        for leaf, entry in layer_spec(cfg, i).items():
            spec[f"solar.l{i}.{leaf}"] = entry
    spec["solar.ln_f.scale"] = ((z["d"],), 1.0, std)
    spec["solar.lm_head.weight"] = ((z["d"], z["vocab"]), 0.0, std)
    return spec


def layer_params(params, i):
    """The leaves of layer ``i`` under their names inside the layer."""
    p = f"solar.l{i}."
    return {k[len(p):]: v for k, v in params.items() if k.startswith(p)}


# ------------------------------------------------------------- the maths

def _quant_fp8(x):
    import jax.numpy as jnp
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _einsum(precision):
    import jax
    import jax.numpy as jnp
    highest = functools.partial(jnp.einsum,
                                precision=jax.lax.Precision.HIGHEST)
    if precision == "fp8":
        return lambda eq, a, b: highest(eq, _quant_fp8(a), _quant_fp8(b))
    if precision == "bfloat16":
        def rounded(x):
            return x.astype(jnp.bfloat16).astype(jnp.float32)
        return lambda eq, a, b: highest(eq, rounded(a), rounded(b))
    if precision != "highest":
        raise ValueError(f"unknown precision {precision!r}")
    return highest


def _rms(x, scale, eps):
    import jax
    import jax.numpy as jnp
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _gqa(w, y, z, ein, block=512):
    """(T, d) -> (T, d): gated causal softmax attention, no positions."""
    import jax
    import jax.numpy as jnp
    t = y.shape[0]
    hd, heads, groups = z["head_dim"], z["heads"], z["kv_heads"]
    q_w, kv_w = heads * hd, groups * hd
    qkvg = ein("ti,io->to", y, w["attn.qkvg.weight"])
    q = qkvg[:, :q_w].reshape(t, groups, heads // groups, hd)
    k = qkvg[:, q_w:q_w + kv_w].reshape(t, groups, hd)
    v = qkvg[:, q_w + kv_w:q_w + 2 * kv_w].reshape(t, groups, hd)
    gate = qkvg[:, q_w + 2 * kv_w:]
    cols = jnp.arange(t)

    def rows(args):
        qb, at = args                           # (Q, G, R, D), (Q,)
        s = ein("qgrd,kgd->grqk", qb, k) / math.sqrt(hd)
        seen = cols[None, :] <= at[:, None]
        a = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        return ein("grqk,kgd->qgrd", a, v).reshape(qb.shape[0], q_w)

    block = min(block, t)
    pad = -t % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0), (0, 0)))
    att = jax.lax.map(rows, (qp.reshape((-1, block) + q.shape[1:]),
                             jnp.arange(t + pad).reshape(-1, block)))
    att = att.reshape(t + pad, q_w)[:t]
    return ein("te,eo->to", att * jax.nn.sigmoid(gate), w["attn.o.weight"])


def _kda(w, y, z, ein):
    """(T, d) -> (T, d): the gated delta rule, a scan over time."""
    import jax
    import jax.numpy as jnp
    exact = _einsum("highest")
    t = y.shape[0]
    h, d, k = z["lin_heads"], z["lin_dim"], z["conv"]
    e = h * d
    qkv = ein("ti,io->to", y, w["kda.qkv.weight"])
    padded = jnp.concatenate([jnp.zeros((k - 1, 3 * e), qkv.dtype), qkv], 0)
    qkv = jax.nn.silu(sum(padded[i:i + t] * w["kda.conv.weight"][i]
                          for i in range(k)))
    q, key, v = (qkv[:, i * e:(i + 1) * e].reshape(t, h, d) for i in range(3))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / math.sqrt(d)
    key = key / jnp.sqrt(jnp.sum(key * key, -1, keepdims=True) + 1e-6)
    f = ein("tr,re->te", ein("ti,ir->tr", y, w["kda.f_down.weight"]),
            w["kda.f_up.weight"]) + w["kda.dt_bias"]
    a = jnp.exp(-jnp.exp(w["kda.A_log"])[None, :, None]
                * jax.nn.softplus(f).reshape(t, h, d))
    beta = 2.0 * jax.nn.sigmoid(ein("ti,ih->th", y, w["kda.beta.weight"]))

    def step(s, inp):                               # s: (H, Dk, Dv)
        q_t, k_t, v_t, a_t, b_t = inp
        s = a_t[:, :, None] * s
        s = s - b_t[:, None, None] * k_t[:, :, None] * exact(
            "hk,hkv->hv", k_t, s)[:, None, :] \
            + b_t[:, None, None] * k_t[:, :, None] * v_t[:, None, :]
        return s, exact("hk,hkv->hv", q_t, s)

    _, o = jax.lax.scan(step, jnp.zeros((h, d, d), jnp.float32),
                        (q, key, v, a, beta))
    o = _rms(o, w["kda.norm.scale"], z["eps"]).reshape(t, e)
    gate = ein("tr,re->te", ein("ti,ir->tr", y, w["kda.g_down.weight"]),
               w["kda.g_up.weight"])
    return ein("te,eo->to", o * jax.nn.sigmoid(gate), w["kda.o.weight"])


def _expert(ein, y, gate_up, down):
    import jax
    import jax.numpy as jnp
    f = down.shape[0]
    h = ein("ti,io->to", y, gate_up.astype(jnp.float32))
    return ein("tf,fo->to", jax.nn.silu(h[:, :f]) * h[:, f:],
               down.astype(jnp.float32))


def _moe(w, y, z, ein, choices):
    """(T, d) -> ((T, d), info).  ``choices``: (T, k) expert ids to follow
    (a token whose ids are negative follows its own), or None to follow
    one's own throughout."""
    import jax
    import jax.numpy as jnp
    scores = jax.nn.sigmoid(_einsum("highest")(
        "ti,ie->te", y, w["moe.router.weight"]))
    biased = scores + w["moe.router.bias"]
    own_top, own = jax.lax.top_k(biased, z["top_k"])
    ids = own if choices is None else jnp.where(
        choices[:, :1] < 0, own, choices.astype(jnp.int32))
    handed = jnp.take_along_axis(biased, ids, axis=-1)
    margin = jnp.max(own_top[:, -1] - jnp.min(handed, axis=-1))
    differs = jnp.sum(jnp.any(jnp.sort(ids, -1) != jnp.sort(own, -1), -1))
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    # (T, held): what the router gave each held expert, 0 where not chosen
    given = jnp.sum(jax.nn.one_hot(ids - z["first"], z["held"])
                    * weights[..., None], axis=1)

    def one(acc, e):
        gate_up, down, col = e
        return acc + col[:, None] * _expert(ein, y, gate_up, down), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(y),
        (w["moe.experts.gate_up"], w["moe.experts.down"], given.T))
    shared = _expert(ein, y, w["moe.shared.gate_up.weight"],
                     w["moe.shared.down.weight"])
    return routed + shared, {"choices": ids, "route_margin": margin,
                             "differs": differs, "shared": shared}


def embed(table, ids):
    import jax.numpy as jnp
    return table.astype(jnp.float32)[ids]


def _f32_but_stacks(w):
    """The leaves in float32, the expert stacks as stored (an expert is
    converted when its turn comes)."""
    import jax.numpy as jnp
    stacks = ("moe.experts.gate_up", "moe.experts.down")
    return {k: v if k in stacks else v.astype(jnp.float32)
            for k, v in w.items()}


def mixer(kind, w, y, cfg, precision="highest"):
    """The layer's mixer alone, of the normed input ``y`` (T, d): what a
    tensor-parallel group adds up over its chips."""
    fn = _gqa if kind == "gqa" else _kda
    return fn(_f32_but_stacks(w), y, sizes(cfg), _einsum(precision))


def moe(w, y, cfg, precision="highest", choices=None):
    """The layer's mixture alone, of the normed input ``y``: ``(routed part
    of the held experts + shared expert, info)``; ``info["shared"]`` is the
    shared expert's part, which every chip computes alike."""
    return _moe(_f32_but_stacks(w), y, sizes(cfg), _einsum(precision),
                choices)


def layer(kind, w, x, carry, cfg, precision="highest", choices=None):
    """One layer over one sequence.  ``x``: (T, d); ``carry`` is handed
    through (no layer of this model reads another's).  ``choices``: (T, k)
    expert ids to follow in this layer's mixture, or None.  Returns ``(x',
    carry, info)``, ``info`` the ids followed, ``route_margin`` and
    ``differs`` (module docstring)."""
    z = sizes(cfg)
    w = _f32_but_stacks(w)
    x = x + mixer(kind, w, _rms(x, w["ln1.scale"], z["eps"]), cfg, precision)
    out, info = moe(w, _rms(x, w["ln2.scale"], z["eps"]), cfg, precision,
                    choices)
    info.pop("shared")
    return x + out, carry, info


def head(weight, scale, x, cfg, precision="highest"):
    """``n(x) W_head``: (rows, d) -> (rows, vocab) float32."""
    import jax.numpy as jnp
    f = jnp.float32
    return _einsum(precision)(
        "td,dv->tv", _rms(x, scale.astype(f), cfg["rms_norm_eps"]),
        weight.astype(f))


def logits(params, ids, cfg, precision="highest", choices=None):
    """(T,) int token ids -> ((T, vocab) float32 logits, info): ``info``
    the stacked ``choices`` (T, layers, k) followed, the largest
    ``route_margin`` and the summed ``differs``.  ``choices``: (T, layers,
    k) to follow, or None."""
    import jax.numpy as jnp
    x = embed(params["solar.embed"], ids)
    infos = []
    for i in range(cfg["num_hidden_layers"]):
        x, _, info = layer(layer_kind(cfg, i), layer_params(params, i), x,
                           {}, cfg, precision,
                           None if choices is None else choices[:, i])
        infos.append(info)
    return head(params["solar.lm_head.weight"], params["solar.ln_f.scale"],
                x, cfg, precision), {
        "choices": jnp.stack([i["choices"] for i in infos], axis=1),
        "route_margin": jnp.max(jnp.stack(
            [i["route_margin"] for i in infos])),
        "differs": jnp.sum(jnp.stack([i["differs"] for i in infos]))}
