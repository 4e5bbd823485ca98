"""Plain reference: GLM-4.7-Flash (``glm4_moe_lite``: multi-head latent
attention, a leading dense layer, then dropless mixtures of experts with a
shared one), full-sequence forward of ONE CHIP'S SHARE.

Straightforward ``jax.numpy`` float32 written from the equations, one
sequence in, the hidden states of every position out: no cache, no kernels,
no batching, no sorting, no absorbed projection — every head's keys and
values are MATERIALISED from the latent, attention runs over the whole
sequence under its causal mask (in blocks of queries), every held expert is
applied to every token and weighted by what the router gave it (zero where
it was not chosen).  Matrix products run at ``highest`` precision.  It
imports nothing of the program; parameter NAMES and shapes are the
program's checkpoint names under the stem the routed driver asks for
(``STEM``).  It takes the weights as the configuration stores them (rounded
to bfloat16) and computes on their float32 values.

The model, ``x`` the residual, ``n`` RMSNorm with a learned scale::

    x <- x + mla(n(x));   x <- x + ffn_i(n(x));   logits = n(x) W_head

    mla  c_q = n(W_dq y); per head h: [q_nope; q_rope] = W_uq,h c_q;
        [c_kv; k_r] = W_dkv y; c = n(c_kv); k_rope = R_t(k_r), one row for
        all heads; q_rope <- R_t(q_rope); [k_nope,h; v_h] = W_ukv,h c;
        causal softmax((q_nope,h . k_nope,h + q_rope,h . k_rope)
        / sqrt(nope + rope)); out = W_o [sum p v_h]_h.  R_t turns dims i and
        i + rope/2 by t theta^(-2i/rope) (rotate-half), no scaling
    dense (i < first_k_dense_replace)  W_d(silu(W_g y) * W_u y)
    moe  s = sigmoid(W_r y) over ALL experts; chosen = top-k of s + bias;
        w_e = routed_scaling_factor s_e / sum_chosen s;
        moe(y) = sum_{e chosen and held} w_e E_e(y) + E_shared(y)

**The share** (``cfg["held_experts"]``: ``first``, ``count`` of ``of``): the
router scores all ``of`` experts, the experts ``first .. first + count`` are
held and computed; everything else is held whole by every chip of the group.

**Following a program's routing** as ``solar_open2_lm.py`` does: ``layer(...,
choices=ids)`` computes its own float32 scores, reports ``route_margin`` and
``differs`` and then follows ``ids`` with its own weights for them.  A dense
layer chooses nothing: it hands back ids of -1, a margin of 0.

``precision="bfloat16"`` is the WITNESS of the stated precision and
``precision="fp8"`` the CONTROL: every product with a stored matrix, and
the attention's two, takes its operands through that type.  The router's
product stays float32 in both, as the configuration states it.
"""
import functools
import math

#: the stem of every parameter name: ``drivers/closed_loop_decode_routed``
#: asks the spec for ``solar.embed``, ``solar.l<i>.``, ``solar.ln_f.scale``
#: and ``solar.lm_head.weight``; ``systems/glm4_moe_lite_decode.py`` hands
#: the leaves to the program under its own stem
STEM = "solar"


def sizes(cfg):
    held = cfg["held_experts"]
    return {"d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
            "vocab": cfg["vocab_size"], "heads": cfg["num_attention_heads"],
            "q_rank": cfg["q_lora_rank"], "rank": cfg["kv_lora_rank"],
            "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
            "v": cfg["v_head_dim"], "theta": cfg["rope_theta"],
            "dense_ffn": cfg["intermediate_size"],
            "dense_layers": cfg["first_k_dense_replace"],
            "ffn": cfg["moe_intermediate_size"], "experts": held["of"],
            "first": held["first"], "held": held["count"],
            "top_k": cfg["num_experts_per_tok"],
            "scale": cfg["routed_scaling_factor"],
            "eps": cfg["rms_norm_eps"]}


def layer_kind(cfg, i):
    return "dense" if i < cfg["first_k_dense_replace"] else "moe"


def layer_spec(cfg, i):
    """``{leaf name inside the layer: (shape, mean, std)}`` of layer ``i``."""
    z = sizes(cfg)
    d, h, std = z["d"], z["heads"], cfg["assumed"]["initializer_range"]
    spec = {
        "ln1.scale": ((d,), 1.0, std),
        "attn.q_down.weight": ((d, z["q_rank"]), 0.0, std),
        "attn.q_norm.scale": ((z["q_rank"],), 1.0, std),
        "attn.q_up.weight": ((z["q_rank"], h * (z["nope"] + z["rope"])),
                             0.0, std),
        "attn.kv_down.weight": ((d, z["rank"] + z["rope"]), 0.0, std),
        "attn.kv_norm.scale": ((z["rank"],), 1.0, std),
        "attn.kv_up.weight": ((z["rank"], h * (z["nope"] + z["v"])),
                              0.0, std),
        "attn.o.weight": ((h * z["v"], d), 0.0, std),
        "ln2.scale": ((d,), 1.0, std)}
    if layer_kind(cfg, i) == "dense":
        f = z["dense_ffn"]
        spec.update({"mlp.gate_up.weight": ((d, 2 * f), 0.0, std),
                     "mlp.down.weight": ((f, d), 0.0, std)})
        return spec
    f = z["ffn"]
    spec.update({
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
    spec = {f"{STEM}.embed": ((z["vocab"], z["d"]), 0.0, std)}
    for i in range(z["layers"]):
        for leaf, entry in layer_spec(cfg, i).items():
            spec[f"{STEM}.l{i}.{leaf}"] = entry
    spec[f"{STEM}.ln_f.scale"] = ((z["d"],), 1.0, std)
    spec[f"{STEM}.lm_head.weight"] = ((z["d"], z["vocab"]), 0.0, std)
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


def _rotate(x, t, theta):
    """``R_t``: ``x`` (..., D) at positions ``t`` (broadcastable to
    ``x.shape[:-1]``); dims ``i`` and ``i + D/2`` turn together."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    freq = jnp.float32(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.asarray(t, jnp.float32)[..., None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _mla(w, y, z, ein, block=512):
    """(T, d) -> (T, d): latent attention with every head's keys and values
    materialised from the compressed row."""
    import jax
    import jax.numpy as jnp
    t = y.shape[0]
    h, nope, rope, v, rank = (z["heads"], z["nope"], z["rope"], z["v"],
                              z["rank"])
    at = jnp.arange(t)
    cq = _rms(ein("ti,ir->tr", y, w["attn.q_down.weight"]),
              w["attn.q_norm.scale"], z["eps"])
    q = ein("tr,ro->to", cq, w["attn.q_up.weight"]).reshape(t, h, nope + rope)
    q = jnp.concatenate(
        [q[..., :nope], _rotate(q[..., nope:], at[:, None], z["theta"])], -1)
    kv = ein("ti,io->to", y, w["attn.kv_down.weight"])
    c = _rms(kv[:, :rank], w["attn.kv_norm.scale"], z["eps"])
    k_rope = _rotate(kv[:, rank:], at, z["theta"])              # (T, rope)
    up = ein("tr,ro->to", c, w["attn.kv_up.weight"]).reshape(t, h, nope + v)
    keys = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_rope[:, None], (t, h, rope))], -1)
    vals = up[..., nope:]

    def rows(args):
        qb, qat = args                              # (Q, H, D), (Q,)
        s = ein("qhd,khd->hqk", qb, keys) / math.sqrt(nope + rope)
        seen = at[None, :] <= qat[:, None]
        a = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        return ein("hqk,khd->qhd", a, vals).reshape(qb.shape[0], h * v)

    block = min(block, t)
    pad = -t % block
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    att = jax.lax.map(rows, (qp.reshape((-1, block) + q.shape[1:]),
                             jnp.arange(t + pad).reshape(-1, block)))
    return ein("te,eo->to", att.reshape(t + pad, h * v)[:t],
               w["attn.o.weight"])


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
    weights = z["scale"] * chosen / jnp.sum(chosen, axis=-1, keepdims=True)
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


def mixer(w, y, cfg, precision="highest"):
    """The layer's attention alone, of the normed input ``y`` (T, d)."""
    return _mla(_f32_but_stacks(w), y, sizes(cfg), _einsum(precision))


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
    import jax.numpy as jnp
    z = sizes(cfg)
    w = _f32_but_stacks(w)
    x = x + mixer(w, _rms(x, w["ln1.scale"], z["eps"]), cfg, precision)
    y = _rms(x, w["ln2.scale"], z["eps"])
    if kind == "dense":
        out = _expert(_einsum(precision), y, w["mlp.gate_up.weight"],
                      w["mlp.down.weight"])
        info = {"choices": jnp.full((x.shape[0], z["top_k"]), -1, jnp.int32),
                "route_margin": jnp.float32(0.0), "differs": jnp.int32(0)}
    else:
        out, info = moe(w, y, cfg, precision, choices)
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
    the stacked ``choices`` (T, layers, k) followed (-1 in a dense layer),
    the largest ``route_margin`` and the summed ``differs``.  ``choices``:
    (T, layers, k) to follow, or None."""
    import jax.numpy as jnp
    x = embed(params[f"{STEM}.embed"], ids)
    infos = []
    for i in range(cfg["num_hidden_layers"]):
        x, _, info = layer(layer_kind(cfg, i), layer_params(params, i), x,
                           {}, cfg, precision,
                           None if choices is None else choices[:, i])
        infos.append(info)
    return head(params[f"{STEM}.lm_head.weight"],
                params[f"{STEM}.ln_f.scale"], x, cfg, precision), {
        "choices": jnp.stack([i["choices"] for i in infos], axis=1),
        "route_margin": jnp.max(jnp.stack(
            [i["route_margin"] for i in infos])),
        "differs": jnp.sum(jnp.stack([i["differs"] for i in infos]))}
