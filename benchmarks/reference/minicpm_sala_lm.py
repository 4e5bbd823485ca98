"""Plain reference: MiniCPM-SALA (``minicpm_sala``: InfLLM-v2 block-sparse
attention 1:3 with Lightning linear attention, dense SwiGLU, muP scalings),
full-sequence forward of the served cut.

Straightforward ``jax.numpy`` float32 written from the equations, one
sequence in, the hidden states of every position out: no cache, no kernels,
no batching, no carried state — the linear attention is the explicit sum
``o_t = Σ_{s≤t} λ^{t−s} (q_t·k_s) v_s`` over the whole sequence (in blocks of
queries), the sparse attention scores EVERY key and every compressed key of
the sequence for every query, chooses its blocks from those full score
matrices and masks the rest.  Matrix products run at ``highest`` precision.
It imports nothing of the program; parameter NAMES and shapes are the
program's checkpoint names under this file's own ``STEM``.  It takes the
weights as the configuration stores them (rounded to bfloat16) and computes
on their float32 values.

The model, ``x`` the residual, ``n`` RMSNorm with a learned scale, ``ρ =
scale_depth / sqrt(mup_denominator)``::

    x = scale_emb E[id];  x <- x + ρ mixer_i(n(x));
    x <- x + ρ W_d(silu(W_g n(x)) * W_u n(x));
    logits = W_head(n(x) / (hidden_size / dim_model_base))

    lightning  [q k v g] = W y (H heads of D); q, k <- n_head(.) (a learned
        D-scale each), rotated (dims i and i + D/2 turn by t theta^(-2i/D));
        o_t = sum_{s<=t} lambda_h^(t-s) (q_t . k_s / sqrt(D)) v_s,
        lambda_h = exp(-2^(-8(h+1)/H)); out = W_o[n_head(o) * sigmoid(g)]
    minicpm4   q = n_head(W_q y) (H heads), k = n_head(W_k y), v = W_v y (G
        key heads; group g = query heads (H/G) g ...), no positional term.
        With n = t + 1 keys: n < dense_len attends to all.  Else compressed
        keys kc_{g,s} = mean(k_{g, stride s .. stride s + kernel - 1}) for
        every s with stride s + kernel <= n; p_h = softmax_s(q_h . kc_{g,s}
        / sqrt(D)); a_{g,s} = sum_{h in g} p_{h,s}; block score b_{g,j} =
        max a_{g,s} over the complete kernels that overlap rows block j ..
        block j + block - 1; chosen = the window/block blocks that end at
        t's own, and of the others the topk best by b, the first init_blocks
        always (equal scores: the lower block).  att_h = softmax over the
        chosen keys i <= t of q_h . k_{g,i} / sqrt(D) . v;
        out = W_o[att * sigmoid(W_gate y)]

**Following a program's selection** as ``glm4_moe_lite_lm.py`` follows a
routing: selection is DISCONTINUOUS in its input (the 64th and 65th best of
some hundred block scores lie closer than a bfloat16 forward moves them), so
``layer(..., blocks=ids)`` computes its own float32 block scores, reports
``select_margin`` — how far, in those scores, the worst block handed in lies
below its own topk-th best — and ``differs``, and then attends to the blocks
handed in.  A Lightning layer chooses nothing: ids of -1, a margin of 0.

``precision="bfloat16"`` is the WITNESS of the stated precision and
``precision="fp8"`` the CONTROL: every product with a stored matrix, and the
attentions' own, takes its operands through that type.
"""
import functools
import math

#: the stem of every parameter name; ``drivers/closed_loop_sessions`` takes
#: it, and the layers, from here, and ``systems/minicpm_sala_decode.py``
#: hands the leaves to the program under the program's own stem
STEM = "minicpm"


def sizes(cfg):
    z = dict(cfg["assumed"]["sparse"]["value"])
    z.update({
        "d": cfg["hidden_size"], "layers": cfg["num_hidden_layers"],
        "vocab": cfg["vocab_size"], "ffn": cfg["intermediate_size"],
        "heads": cfg["num_attention_heads"],
        "groups": cfg["num_key_value_heads"], "hd": cfg["head_dim"],
        "l_heads": cfg["lightning_nh"], "l_hd": cfg["lightning_head_dim"],
        "theta": cfg["rope_theta"], "eps": cfg["rms_norm_eps"],
        "emb": cfg["scale_emb"],
        "rho": cfg["scale_depth"] / math.sqrt(cfg["mup_denominator"]),
        "logit": cfg["dim_model_base"] / cfg["hidden_size"]})
    return z


def layer_kind(cfg, i):
    return {"minicpm4": "sparse",
            "lightning-attn": "lightning"}[cfg["mixer_types"][i]]


def layer_spec(cfg, i):
    """``{leaf name inside the layer: (shape, mean, std)}`` of layer ``i``."""
    z = sizes(cfg)
    d, std = z["d"], cfg["assumed"]["initializer_range"]
    spec = {"ln1.scale": ((d,), 1.0, std)}
    if layer_kind(cfg, i) == "sparse":
        q_w, kv_w = z["heads"] * z["hd"], z["groups"] * z["hd"]
        spec.update({
            "attn.qkvg.weight": ((d, 2 * q_w + 2 * kv_w), 0.0, std),
            "attn.k_norm.scale": ((z["hd"],), 1.0, std),
            "attn.q_norm.scale": ((z["hd"],), 1.0, std),
            "attn.o.weight": ((q_w, d), 0.0, std)})
    else:
        e = z["l_heads"] * z["l_hd"]
        spec.update({
            "lightning.qkvg.weight": ((d, 4 * e), 0.0, std),
            "lightning.q_norm.scale": ((z["l_hd"],), 1.0, std),
            "lightning.k_norm.scale": ((z["l_hd"],), 1.0, std),
            "lightning.o_norm.scale": ((z["l_hd"],), 1.0, std),
            "lightning.o.weight": ((e, d), 0.0, std)})
    spec.update({"ln2.scale": ((d,), 1.0, std),
                 "mlp.gate_up.weight": ((d, 2 * z["ffn"]), 0.0, std),
                 "mlp.down.weight": ((z["ffn"], d), 0.0, std)})
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


def parameters_published(cfg):
    """Parameters of the UNCUT model: the published ``mixer_types`` at the
    published depth with these shapes (the configuration's
    ``parameters_published_check``)."""
    kinds = cfg["published"]["mixer_types"]
    whole = dict(cfg, mixer_types=kinds, num_hidden_layers=len(kinds))
    return sum(math.prod(shape) for shape, _, _ in param_spec(whole).values())


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
    """``x`` (T, H, D) at positions ``t`` (T,); dims ``i`` and ``i + D/2``
    turn together."""
    import jax.numpy as jnp
    half = x.shape[-1] // 2
    freq = jnp.float32(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.asarray(t, jnp.float32)[:, None, None] * freq
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _by_rows(fn, rows, block, *more):
    """``fn(rows block, positions of the block, *more)`` over blocks of
    ``block`` rows of ``rows`` (a pytree of arrays of T rows), stacked
    back."""
    import jax
    import jax.numpy as jnp
    t = jax.tree.leaves(rows)[0].shape[0]
    block = min(block, t)
    pad = -t % block

    def cut(x):
        x = jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1))
        return x.reshape((-1, block) + x.shape[1:])

    out = jax.lax.map(
        lambda a: fn(a[0], a[1], *more),
        (jax.tree.map(cut, rows), jnp.arange(t + pad).reshape(-1, block)))
    return jax.tree.map(lambda x: x.reshape((t + pad,) + x.shape[2:])[:t],
                        out)


def _lightning(w, y, z, ein, block=128):
    """(T, d) -> (T, d): the linear attention as its explicit sum."""
    import jax
    import jax.numpy as jnp
    t = y.shape[0]
    h, d = z["l_heads"], z["l_hd"]
    e = h * d
    at = jnp.arange(t)
    x = _by_rows(lambda yb, _: ein("ti,io->to", yb,
                                   w["lightning.qkvg.weight"]), y, 2048)
    q, k, v = (x[:, i * e:(i + 1) * e].reshape(t, h, d) for i in range(3))
    q = _rotate(_rms(q, w["lightning.q_norm.scale"], z["eps"]), at,
                z["theta"]) / math.sqrt(d)
    k = _rotate(_rms(k, w["lightning.k_norm.scale"], z["eps"]), at,
                z["theta"])
    rate = jnp.exp2(-8.0 * jnp.arange(1, h + 1, dtype=jnp.float32) / h)

    def rows(qb, qat):
        gap = (qat[:, None] - at[None, :]).astype(jnp.float32)   # t − s
        decay = jnp.where(gap >= 0, jnp.exp(
            -rate[:, None, None] * jnp.maximum(gap, 0.0)), 0.0)  # (H, Q, T)
        a = ein("qhd,khd->hqk", qb, k) * decay
        return ein("hqk,khd->qhd", a, v)

    o = _rms(_by_rows(rows, q, block), w["lightning.o_norm.scale"], z["eps"])
    gated = o.reshape(t, e) * jax.nn.sigmoid(x[:, 3 * e:])
    return _by_rows(lambda gb, _: ein("te,eo->to", gb,
                                      w["lightning.o.weight"]), gated, 2048)


def _compressed(k, z):
    """``k`` (T, G, D) -> (S, G, D): row ``s`` the mean of keys ``stride s
    .. stride s + kernel − 1`` (rows whose kernel runs past T are never
    complete and never read)."""
    import jax.numpy as jnp
    t = k.shape[0]
    s = -(-t // z["kernel_stride"])
    at = (jnp.arange(s)[:, None] * z["kernel_stride"]
          + jnp.arange(z["kernel_size"])[None, :])
    return jnp.mean(k[jnp.minimum(at, t - 1)], axis=1)


def _sparse(w, y, z, ein, blocks, block=128):
    """(T, d) -> ((T, d), info).  ``blocks``: (T, G, topk) block ids to
    attend to beside the window (a query whose ids are negative chooses for
    itself), or None to choose for oneself throughout."""
    import jax
    import jax.numpy as jnp
    t = y.shape[0]
    h, g, d = z["heads"], z["groups"], z["hd"]
    q_w, kv_w = h * d, g * d
    size, stride, width = z["kernel_size"], z["kernel_stride"], z["block_size"]
    topk, near = z["topk"], z["window_size"] // z["block_size"]
    x = _by_rows(lambda yb, _: ein("ti,io->to", yb, w["attn.qkvg.weight"]),
                 y, 2048)
    q = _rms(x[:, :q_w].reshape(t, h, d), w["attn.q_norm.scale"], z["eps"])
    k = _rms(x[:, q_w:q_w + kv_w].reshape(t, g, d), w["attn.k_norm.scale"],
             z["eps"])
    v = x[:, q_w + kv_w:q_w + 2 * kv_w].reshape(t, g, d)
    kc = _compressed(k, z)                                     # (S, G, D)
    n_rows, n_blocks = kc.shape[0], max(-(-t // width), topk)
    s_at, j_at, key_at = (jnp.arange(n) for n in (n_rows, n_blocks, t))
    # kernel s covers rows stride s .. stride s + size − 1; block j rows
    # width j .. width j + width − 1
    overlaps = jnp.logical_and(
        s_at[:, None] * stride + size - 1 >= j_at[None, :] * width,
        s_at[:, None] * stride <= j_at[None, :] * width + width - 1)
    if blocks is None:
        blocks = jnp.full((t, g, topk), -1, jnp.int32)

    def rows(args, qat):
        qb, handed = args                          # (Q, H, D), (Q, G, topk)
        qg = qb.reshape(-1, g, h // g, d)
        n = qat + 1
        done = s_at[None, :] * stride + size <= n[:, None]         # (Q, S)
        p = jax.nn.softmax(jnp.where(
            done[:, None, None], ein("qgrd,sgd->qgrs", qg, kc)
            / math.sqrt(d), -1e30), axis=-1)
        a = jnp.where(done[:, None], jnp.sum(p, axis=2), -jnp.inf)  # (Q,G,S)
        # one block at a time: the largest a among its kernels
        b = jax.lax.map(lambda o: jnp.max(
            jnp.where(o, a, -jnp.inf), axis=-1), overlaps.T)      # (J, Q, G)
        b = jnp.moveaxis(b, 0, -1)
        first = (qat // width - (near - 1))[:, None, None]   # the window's
        ranked = jnp.where(j_at < z["init_blocks"], jnp.inf, b)
        ranked = jnp.where(j_at < first, ranked, -jnp.inf)
        order = jnp.argsort(-ranked, axis=-1, stable=True)[..., :topk]
        own = jnp.sort(order, axis=-1)
        kth = jnp.take_along_axis(ranked, order[..., -1:], axis=-1)[..., 0]
        follow = handed[..., :1] >= 0
        ids = jnp.where(follow, handed, own)
        worst = jnp.min(jnp.take_along_axis(ranked, jnp.maximum(ids, 0),
                                            axis=-1), axis=-1)
        sparse = (n >= z["dense_len"])[:, None]
        margin = jnp.max(jnp.where(sparse, kth - worst, 0.0))
        differs = jnp.sum(jnp.logical_and(sparse, jnp.any(
            jnp.sort(ids, -1) != own, axis=-1)))
        chosen = jnp.logical_or(
            jnp.any(ids[..., None] == j_at, axis=-2), j_at >= first)
        seen = jnp.logical_and(
            jnp.logical_or(jnp.repeat(chosen, width, axis=-1)[..., :t],
                           ~sparse[..., None]),
            key_at <= qat[:, None, None])                         # (Q, G, T)
        att = jax.nn.softmax(jnp.where(
            seen[:, :, None], ein("qgrd,kgd->qgrk", qg, k) / math.sqrt(d),
            -1e30), axis=-1)
        return (ein("qgrk,kgd->qgrd", att, v).reshape(-1, q_w),
                jnp.where(sparse[..., None], ids, -1),
                jnp.broadcast_to(margin, qat.shape),
                jnp.broadcast_to(differs, qat.shape))

    att, ids, margin, differs = _by_rows(rows, (q, blocks), block)
    gated = att * jax.nn.sigmoid(x[:, q_w + 2 * kv_w:])
    out = _by_rows(lambda gb, _: ein("te,eo->to", gb, w["attn.o.weight"]),
                   gated, 2048)
    # a block of queries reports its numbers at every row of the block
    every = jnp.arange(0, t, min(block, t))
    return out, {"blocks": ids, "select_margin": jnp.max(margin),
                 "differs": jnp.sum(differs[every])}


def _mlp(w, y, ein):
    import jax

    def rows(yb, _):
        f = w["mlp.down.weight"].shape[0]
        hid = ein("ti,io->to", yb, w["mlp.gate_up.weight"])
        return ein("tf,fo->to", jax.nn.silu(hid[:, :f]) * hid[:, f:],
                   w["mlp.down.weight"])

    return _by_rows(rows, y, 2048)


def embed(table, ids, cfg):
    import jax.numpy as jnp
    return cfg["scale_emb"] * table.astype(jnp.float32)[ids]


def mixer(kind, w, y, cfg, precision="highest", blocks=None):
    """The layer's mixer alone, of the normed input ``y`` (T, d): ``(out,
    info)``."""
    import jax.numpy as jnp
    z = sizes(cfg)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    if kind == "sparse":
        return _sparse(w, y, z, _einsum(precision), blocks)
    return _lightning(w, y, z, _einsum(precision)), {
        "blocks": jnp.full((y.shape[0], z["groups"], z["topk"]), -1,
                           jnp.int32),
        "select_margin": jnp.float32(0.0), "differs": jnp.int32(0)}


def layer(kind, w, x, carry, cfg, precision="highest", blocks=None):
    """One layer over one sequence.  ``x``: (T, d); ``carry`` is handed
    through (no layer of this model reads another's).  ``blocks``: (T, G,
    topk) block ids to follow in a sparse layer, or None.  Returns ``(x',
    carry, info)``, ``info`` the ids followed (-1 where a query read
    everything, and in a Lightning layer), ``select_margin`` and
    ``differs`` (module docstring)."""
    import jax.numpy as jnp
    z = sizes(cfg)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    mixed, info = mixer(kind, w, _rms(x, w["ln1.scale"], z["eps"]), cfg,
                        precision, blocks)
    x = x + z["rho"] * mixed
    return x + z["rho"] * _mlp(w, _rms(x, w["ln2.scale"], z["eps"]),
                               _einsum(precision)), carry, info


def head(weight, scale, x, cfg, precision="highest"):
    """``W_head(n(x) / (hidden / base))``: (rows, d) -> (rows, vocab)."""
    import jax.numpy as jnp
    f = jnp.float32
    normed = _rms(x, scale.astype(f), cfg["rms_norm_eps"]) \
        * (cfg["dim_model_base"] / cfg["hidden_size"])
    return _einsum(precision)("td,dv->tv", normed, weight.astype(f))


def logits(params, ids, cfg, precision="highest", blocks=None):
    """(T,) int token ids -> ((T, vocab) float32 logits, info): ``info``
    the stacked ``blocks`` (T, sparse layers, G, topk) followed, the largest
    ``select_margin`` and the summed ``differs``.  ``blocks``: (T, sparse
    layers, G, topk) to follow, or None."""
    import jax.numpy as jnp
    x = embed(params[f"{STEM}.embed"], ids, cfg)
    infos, at = [], 0
    for i in range(cfg["num_hidden_layers"]):
        kind = layer_kind(cfg, i)
        handed = None
        if kind == "sparse":
            handed = None if blocks is None else blocks[:, at]
            at += 1
        x, _, info = layer(kind, layer_params(params, i), x, {}, cfg,
                           precision, handed)
        if kind == "sparse":
            infos.append(info)
    return head(params[f"{STEM}.lm_head.weight"],
                params[f"{STEM}.ln_f.scale"], x, cfg, precision), {
        "blocks": jnp.stack([i["blocks"] for i in infos], axis=1),
        "select_margin": jnp.max(jnp.stack(
            [i["select_margin"] for i in infos])),
        "differs": jnp.sum(jnp.stack([i["differs"] for i in infos]))}
