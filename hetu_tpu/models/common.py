"""Shared model-graph helpers."""
import numpy as np

from .. import ops
from ..graph.node import Op


def masked_lm_loss(logits, labels, n_tokens, ignored_index=-1):
    """Token-masked cross-entropy: mean over positions whose label !=
    ``ignored_index``.  ``logits``: (n_tokens, vocab); ``labels``: any shape
    flattening to (n_tokens,).  Used by every LM head (BERT MLM, GPT-2
    causal LM, T5/transformer seq2seq)."""
    flat = ops.array_reshape_op(labels, output_shape=(n_tokens,))
    per_tok = ops.softmaxcrossentropy_sparse_op(logits, flat,
                                                ignored_index=ignored_index)
    valid = ops.ne_op(flat, flat * 0.0 + float(ignored_index))
    return ops.reduce_sum_op(per_tok, [0]) \
        / (ops.reduce_sum_op(valid, [0]) + 1e-6)


# ------------------------------------------ an MLM head's labelled rows
# A masked-LM batch labels some 15 % of its positions and the loss reads no
# other, so the head need not run on the rest: per row, the hidden states
# of ``capacity`` labelled positions are gathered and the head — any graph
# from hidden rows to logits — runs on those.  The work follows the labels
# a step is fed: a row that carries more than ``capacity`` of them sends
# the head round again over each row's next ``capacity``, so no label is
# ever dropped and no tensor of (all positions, vocab) ever written.

def _rows_over_capacity(c, labels, capacity, ignored_index=-1):
    import jax.numpy as jnp
    return jnp.sum(jnp.sum(labels != ignored_index, axis=1) > capacity,
                   dtype=jnp.int32)


rows_over_capacity_op = ops.def_op("RowsOverCapacity", _rows_over_capacity,
                                   lambda a, **kw: ())


class _HeadArm:
    """``Σ cross-entropy(head(rows), labels) · scale`` over ``n_rows`` rows
    as a graph of its own on three stand-in inputs: what one round of
    :class:`LabelledRowsLossOp` lowers.  ``scale`` is one over the count
    of ALL the batch's labels, inside the sum's gradient as the division
    of :func:`masked_lm_loss` is."""

    def __init__(self, head, n_rows, ignored_index):
        from ..graph.node import PlaceholderOp, placeholder_op, topo_sort
        self.rows = placeholder_op("head_rows")
        self.labels = placeholder_op("head_row_labels", dtype=np.int32,
                                     shape=(n_rows,))
        self.scale = placeholder_op("head_loss_scale", shape=())
        per_row = ops.softmaxcrossentropy_sparse_op(
            head(self.rows), self.labels, ignored_index=ignored_index)
        self.loss = ops.reduce_sum_op(per_row, [0]) * self.scale
        self.topo = topo_sort([self.loss])
        self.variables = [n for n in self.topo
                          if isinstance(n, PlaceholderOp) and n.is_variable]

    def lower(self, ctx, rows, labels, scale, values):
        """The scaled sum for traced inputs; ``values``: the variables'
        traced values in ``self.variables``' order.  The head draws no
        random numbers and writes no state (its context has neither)."""
        from ..graph.node import LowerCtx
        env = {self.rows: rows, self.labels: labels, self.scale: scale,
               **dict(zip(self.variables, values))}
        sub = LowerCtx(ctx.training, mesh=ctx.mesh)
        for node in self.topo:
            if node not in env:
                env[node] = node.lower(sub, *[env[i] for i in node.inputs])
        return env[self.loss]

    def fingerprint(self):
        """The arm's structure by content, for the compiled-step cache:
        the arm is no input of its op, so the op carries this."""
        import hashlib
        from ..graph import step_cache
        from ..graph.node import PlaceholderOp
        h = hashlib.sha256()
        try:
            step_cache._hash_nodes(
                h, self.topo, [self.loss],
                lambda n: n.name if isinstance(n, PlaceholderOp) else "")
        except step_cache._Uncachable:
            return self      # no content hash: the step is not cached
        return h.hexdigest()


class LabelledRowsLossOp(Op):
    """``masked_lm_loss(head(seq), labels)`` — the mean cross-entropy over
    the labelled positions of ``labels (batch, seq_len)`` — with the head
    run on labelled rows alone.  Inputs: ``seq (batch·seq_len, h)``,
    ``labels`` and the head's variables.

    One ROUND gathers, per row of the batch, the hidden states of its
    next ``capacity`` labelled positions (in order; a row with fewer pads
    with unlabelled ones) and runs the head over those ``batch·capacity``
    rows.  The first round always runs; a ``while_loop`` runs as many
    more as the fullest row needs, so a batch within capacity pays one
    and a row over it costs a round, not the head over every position.
    Per row, so that a sharded batch axis stays sharded.  (A capacity of
    the whole row gathers every position, in order: the plain head.)

    Each round computes its gradients beside its sum, inside the loop
    (the loss is a scalar: a backward pass only scales them), so nothing
    but the sums and the gradients themselves outlives a round."""

    op_type = "LabelledRowsLoss"

    def __init__(self, seq, labels, head, batch, seq_len, capacity,
                 ignored_index=-1, name=None):
        capacity = min(int(capacity), seq_len)
        self.arm = _HeadArm(head, batch * capacity, ignored_index)
        super().__init__([seq, labels] + self.arm.variables, name=name,
                         batch=batch, seq_len=seq_len, capacity=capacity,
                         ignored_index=ignored_index,
                         arm=self.arm.fingerprint())

    def infer_shape(self, input_shapes):
        return ()

    def fwd_flops(self, gs):
        """One round's forward matrix products, for the cost model (which
        walks a graph's own nodes and sees into no arm): a capacity
        that fits the masking runs no second."""
        from ..autoparallel.cost_model import graph_layer_spec
        rows = (self.arm.labels.shape[0], gs.shape(self.inputs[0])[-1])
        return graph_layer_spec([self.arm.loss],
                                feeds={self.arm.rows: rows}).fwd_flops

    def _sum(self, ctx, seq, labels, values, with_grads):
        """``(loss, d loss / d seq, d loss / d values)`` summed over the
        rounds; the gradients None unless ``with_grads``."""
        import jax
        import jax.numpy as jnp
        b, s, k, ignored = (self.attrs[a] for a in (
            "batch", "seq_len", "capacity", "ignored_index"))
        h = seq.shape[-1]
        seq3 = seq.reshape(b, s, h)
        held = labels != ignored
        counts = jnp.sum(held, axis=1)
        scale = 1.0 / (jnp.sum(counts).astype(jnp.float32) + 1e-6)
        rounds = -(-jnp.max(counts) // k)
        # every row's labelled positions in order, then ``s`` for "none"
        at = jnp.sort(jnp.where(held, jnp.arange(s), s), axis=1)
        at = jnp.pad(at, ((0, 0), (0, -s % k)), constant_values=s)

        def one(r, d_seq3):
            """Round ``r``: ``(its sum, d_seq3 with its rows' gradients
            added, d sum / d values)``."""
            pos = jax.lax.dynamic_slice_in_dim(at, r * k, k, axis=1)
            live, pos = pos < s, jnp.minimum(pos, s - 1)
            rows = jnp.take_along_axis(seq3, pos[:, :, None], axis=1)
            row_labels = jnp.where(
                live, jnp.take_along_axis(labels, pos, axis=1), ignored)

            def f(rows, values):
                return self.arm.lower(ctx, rows.reshape(b * k, h),
                                      row_labels.reshape(b * k), scale,
                                      values)
            if not with_grads:
                return f(rows, values), None, None
            total, (d_rows, d_values) = jax.value_and_grad(
                f, argnums=(0, 1))(rows, values)
            d_seq3 = jax.vmap(
                lambda d, p, u: d.at[p].add(u, indices_are_sorted=True))(
                    d_seq3, pos, d_rows)
            return total, d_seq3, d_values

        def more(c):
            r, total, d_seq3, d_values = c
            t, d_seq3, d_v = one(r, d_seq3)
            return (r + 1, total + t, d_seq3,
                    jax.tree.map(jnp.add, d_values, d_v))

        # the first round outside the loop: a batch within capacity, every
        # batch of a well-set capacity, adds nothing to anything
        first = one(0, jnp.zeros_like(seq3) if with_grads else None)
        _, total, d_seq3, d_values = jax.lax.while_loop(
            lambda c: c[0] < rounds, more, (1, *first))
        if with_grads:
            d_seq3 = d_seq3.reshape(b * s, h)
        return total, d_seq3, d_values

    def lower(self, ctx, seq, labels, *values):
        from contextlib import nullcontext
        import jax
        import jax.numpy as jnp
        from ..metrics import record_mlm_head_call
        k, s = self.attrs["capacity"], self.attrs["seq_len"]
        record_mlm_head_call(k, s, "gathered" if k < s else "all")

        # the node's scope once more, inside: a gradient taken around this
        # op renames the outer one ``jvp(<scope>)``, which no reader of
        # scopes matches, and these operations are forward and backward
        def scope():
            return jax.named_scope(self.scope) if self.scope \
                else nullcontext()

        @jax.custom_vjp
        def loss(seq, labels, values):
            with scope():
                return self._sum(ctx, seq, labels, values, False)[0]

        def fwd(seq, labels, values):
            with scope():
                total, *grads = self._sum(ctx, seq, labels, values, True)
            return total, grads

        def bwd(grads, g):
            d_seq, d_values = jax.tree.map(
                lambda x: (g * x).astype(x.dtype), grads)
            return d_seq, None, d_values

        loss.defvjp(fwd, bwd)
        return loss(seq, labels.astype(jnp.int32), tuple(values))


def labelled_rows_lm_loss(seq, labels, head, batch, seq_len, capacity,
                          ignored_index=-1):
    """The masked-LM loss of ``head`` — ``head(rows) -> logits`` — over
    ``seq (batch·seq_len, h)`` and ``labels (batch, seq_len)``: ``(loss,
    overflow)``, a :class:`LabelledRowsLossOp` and a scalar node a caller
    may fetch — the rows of the fed batch that carry more than
    ``capacity`` labels (above 0 the step ran the head more than once:
    ``capacity`` is too small for the masking)."""
    loss = LabelledRowsLossOp(seq, labels, head, batch, seq_len, capacity,
                              ignored_index)
    return loss, rows_over_capacity_op(
        labels, capacity=loss.attrs["capacity"], ignored_index=ignored_index)


def patchify(images, batch, channels, image_size, patch_size, hidden,
             name, bias=True):
    """(B, C, H, W) → (B*P, hidden) with one MXU GEMM (shared by ViT/CLIP/
    MAE — reshape (B,C,g,p,g,p) → transpose → (B*g*g, C*p*p) @ W)."""
    from .. import initializers as init
    from ..layers.core import Linear
    p_ = patch_size
    g = image_size // p_
    x = ops.array_reshape_op(
        images, output_shape=(batch, channels, g, p_, g, p_))
    x = ops.transpose_op(x, perm=(0, 2, 4, 1, 3, 5))
    x = ops.array_reshape_op(
        x, output_shape=(batch * g * g, channels * p_ * p_))
    return Linear(channels * p_ * p_, hidden, bias=bias,
                  initializer=init.GenTruncatedNormal(0.0, 0.02),
                  name=name)(x)


def pre_ln_block(hidden, heads, seq, batch, eps, name, causal=False,
                 dropout=0.0):
    """Standard pre-LN transformer encoder block builder (shared by
    ViT/CLIP/MAE towers): x + attn(ln1(x)); x + mlp(ln2(x))."""
    from .. import initializers as init
    from ..layers.attention import MultiHeadAttention
    from ..layers.core import Linear, LayerNorm

    def block(x):
        h = LayerNorm(hidden, eps, name + ".ln1")(x)
        mha = MultiHeadAttention(hidden, heads, causal=causal,
                                 dropout=dropout, name=name + ".attn")
        x = x + mha(h, batch, seq)
        h = LayerNorm(hidden, eps, name + ".ln2")(x)
        h = Linear(hidden, 4 * hidden, activation="gelu",
                   initializer=init.GenTruncatedNormal(0.0, 0.02),
                   name=name + ".mlp1")(h)
        h = Linear(4 * hidden, hidden,
                   initializer=init.GenTruncatedNormal(0.0, 0.02),
                   name=name + ".mlp2")(h)
        if dropout:
            h = ops.dropout_op(h, 1.0 - dropout)
        return x + h
    return block


def split_heads(x, batch, seq, heads, head_dim):
    """(batch*seq, hidden) → (batch, heads, seq, head_dim)."""
    x = ops.array_reshape_op(x, output_shape=(batch, seq, heads, head_dim))
    return ops.transpose_op(x, perm=(0, 2, 1, 3))


def merge_heads(x, batch, seq, hidden):
    """(batch, heads, seq, head_dim) → (batch*seq, hidden)."""
    x = ops.transpose_op(x, perm=(0, 2, 1, 3))
    return ops.array_reshape_op(x, output_shape=(batch * seq, hidden))


def post_ln_encoder_stack(x, cfg, attn_factory, name):
    """BERT-style post-LN encoder stack shared by the static-sparse-mask
    models (Longformer/BigBird): per layer, x = LN(x + attn(x));
    x = LN(x + dropout(FFN(x))).  ``attn_factory(layer_name) -> callable``.
    Reads hidden_size / num_hidden_layers / intermediate_size /
    hidden_dropout_prob / layer_norm_eps off ``cfg``."""
    from .. import initializers as init
    from ..layers.core import Linear, LayerNorm
    for i in range(cfg.num_hidden_layers):
        ln = f"{name}.layer{i}"
        attn = attn_factory(ln + ".attn")
        x = LayerNorm(cfg.hidden_size, cfg.layer_norm_eps,
                      ln + ".ln1")(x + attn(x))
        h = Linear(cfg.hidden_size, cfg.intermediate_size, activation="gelu",
                   initializer=init.GenTruncatedNormal(0.0, 0.02),
                   name=ln + ".ffn1")(x)
        h = Linear(cfg.intermediate_size, cfg.hidden_size,
                   initializer=init.GenTruncatedNormal(0.0, 0.02),
                   name=ln + ".ffn2")(h)
        h = ops.dropout_op(h, 1.0 - cfg.hidden_dropout_prob)
        x = LayerNorm(cfg.hidden_size, cfg.layer_norm_eps,
                      ln + ".ln2")(x + h)
    return x


# ------------------------------------------------ served decoder graphs
# What the decode graphs of the expert models share (Solar-Open2,
# GLM-4.7-Flash): one block definition under a one-token graph, a chunked
# graph and a full-sequence graph, a float32 residual stream flattened to
# ``(B*C, d)``, RMSNorm with a learned scale, weights stored in
# ``cfg.param_dtype``, the dropless expert layer of one chip's share.

class DecodeGraph:
    """What the blocks of one graph share (as ``phi4flash._Graph``)."""

    def __init__(self, cfg, ids, positions, valid, max_len, fed):
        self.cfg, self.ids, self.positions = cfg, ids, positions
        self.valid = () if valid is None else (valid,)
        self.max_len, self.fed = int(max_len), fed
        self.feeds, self.fetches, self.chosen = {}, [], []

    def var(self, name, shape, mean=0.0, std=None):
        from .. import initializers as init
        from ..graph.node import Variable
        std = self.cfg.initializer_range if std is None else std
        return Variable(name, initializer=init.NormalInit(mean, std),
                        shape=tuple(shape), dtype=self.cfg.param_dtype)

    def dense(self, x, name, n_in, n_out):
        """``x @ W`` over the weight as it is stored, float32 out."""
        return ops.matmul_op(x, self.var(name + ".weight", (n_in, n_out)),
                             out_dtype=np.float32)

    def norm(self, x, name, width=None):
        return ops.rms_norm_op(
            x, self.var(name + ".scale",
                        (width or self.cfg.hidden_size,), 1.0),
            eps=self.cfg.rms_norm_eps)

    def state(self, name, kind, shape, dtype, **slab):
        if not self.fed:
            if kind in ("kv", "index"):
                slab = dict(slab)
                slab["length"] = -(-slab["length"] // slab.pop("stride", 1))
                shape = ops.kv_slab_shape(**slab)
            return ops.zeros_op(self.ids, tail=tuple(shape[1:]),
                                dtype=np.dtype(dtype))
        node = ops.state_placeholder(name, kind, shape, dtype, **slab)
        self.feeds[name] = node
        return node


def cols(x, start, stop):
    return ops.slice_op(x, begin=(0, start), end=(None, stop))


def swiglu_mlp(g, y, name, width):
    """``W_d(silu(W_g y) ⊙ W_u y)`` of ``width``, ``[gate | up]`` one
    matrix."""
    d = g.cfg.hidden_size
    return g.dense(ops.swiglu_op(g.dense(y, name + ".gate_up", d, 2 * width)),
                   name + ".down", width, d)


def moe_block(g, x, name):
    """``x + Σ_{chosen ∧ held} w_e E_e(n(x)) + E_shared(n(x))``, the
    weights scaled by ``cfg.routed_scaling_factor``."""
    from ..graph.node import name_scope
    cfg = g.cfg
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    first, count = cfg.held
    with name_scope("moe.route"):
        y = g.norm(x, name + ".ln2")
        ids, weights = ops.moe_route_op(
            y, g.var(name + ".moe.router.weight", (d, cfg.n_routed_experts)),
            g.var(name + ".moe.router.bias", (cfg.n_routed_experts,), 0.0,
                  0.5 * cfg.initializer_range),
            cfg.num_experts_per_tok,
            scale=cfg.routed_scaling_factor)
        g.chosen.append(ids)
    with name_scope("moe.experts"):
        routed = ops.moe_experts_op(
            y, ids, weights,
            g.var(name + ".moe.experts.gate_up", (count, d, 2 * f)),
            g.var(name + ".moe.experts.down", (count, f, d)),
            first=first, n_experts=cfg.n_routed_experts)
    with name_scope("moe.shared"):
        return x + routed + swiglu_mlp(g, y, name + ".moe.shared", f)


def build_decoder(cfg, layer, chunk, max_len, name, fed=True,
                  with_valid=True, embed_scale=1.0, logit_scale=1.0,
                  chosen=None, tied_head=False):
    """The graph of ``cfg.num_hidden_layers`` blocks ``layer(g, x, i,
    name) -> x`` between the embedding and the head: ``(g, logits, greedy
    token ids, chosen expert ids)``.  ``fed=False``: zero states and
    position 0 (the full-sequence graph).  ``embed_scale`` / ``logit_scale``
    multiply the embedding and the logits (a muP-scaled model's);
    ``chosen(ids, *g.chosen) -> node`` stacks what the layers left in
    ``g.chosen`` in place of ``ops.moe_choices_op`` (None where no layer
    left anything).  ``tied_head``: the
    head IS the embedding, ``logits = n(x) Eᵀ``, and no ``lm_head`` leaf
    exists."""
    from ..graph.node import name_scope, placeholder_op
    b = cfg.batch_size
    ids = placeholder_op("input_ids", shape=(b, chunk), dtype=np.int32)
    if fed:
        positions = placeholder_op("positions", shape=(b,), dtype=np.int32)
    else:
        positions = ops.zeros_op(ids, tail=(), dtype=np.dtype(np.int32))
    valid = placeholder_op("valid", shape=(b,), dtype=np.int32) \
        if with_valid else None
    g = DecodeGraph(cfg, ids, positions, valid, max_len, fed)
    g.feeds["input_ids"] = ids
    if fed:
        g.feeds["positions"] = positions
    if valid is not None:
        g.feeds["valid"] = valid
    embed = g.var(name + ".embed", (cfg.vocab_size, cfg.hidden_size))
    x = ops.array_reshape_op(                                # (B*C, d)
        ops.embedding_lookup_op(embed, ids, dtype=np.float32),
        output_shape=(-1, cfg.hidden_size))
    if embed_scale != 1.0:
        x = x * float(embed_scale)
    for i in range(cfg.num_hidden_layers):
        x = layer(g, x, i, f"{name}.l{i}")
    if chosen is not None:
        choices = chosen(ids, *g.chosen)
    elif g.chosen:
        with name_scope("moe.route"):
            choices = ops.moe_choices_op(ids, *g.chosen)
    else:
        choices = None                   # no layer chooses anything
    with name_scope("lm_head"):
        if valid is not None:
            x = ops.chunk_emit_gather_op(x, ids, valid)
        x = g.norm(x, name + ".ln_f")
        logits = ops.matmul_op(x, embed, trans_B=True,
                               out_dtype=np.float32) if tied_head \
            else g.dense(x, name + ".lm_head", cfg.hidden_size,
                         cfg.vocab_size)
        if logit_scale != 1.0:
            logits = logits * float(logit_scale)
        tokens = ops.greedy_token_op(logits)
    return g, logits, tokens, choices


def choice_counters(held):
    """``fold(choices) -> {counter: n}`` for ``DecodeEngine(aux_fold=)``:
    what one step's chosen expert ids ``(rows, C, layers, k)`` say of the
    expert layers' work where experts ``held = (first, count)`` are held —
    ``moe_assignments`` (rows x k x layers), ``moe_assignments_held``
    (those whose expert is held here), ``moe_experts_touched`` (held
    experts with at least one token, summed over the layers) and
    ``moe_expert_load_max`` (the most tokens one held expert of one layer
    took this step; summed over steps like the others)."""
    first, count = held

    def fold(choices):
        local = choices.astype(np.int32) - first
        layers = local.shape[-2]
        held = np.logical_and(local >= 0, local < count)
        at = (local + count * np.arange(layers)[:, None])[held]
        load = np.bincount(at, minlength=count * layers)
        return {"moe_assignments": local.size,
                "moe_assignments_held": int(held.sum()),
                "moe_experts_touched": int(np.count_nonzero(load)),
                "moe_expert_load_max": int(load.max())}

    return fold


def decoder_param_names(lm_graph, cfg, name):
    """Checkpoint names and shapes of every variable of ``lm_graph(cfg, 2,
    name)``, in graph order."""
    from ..graph.node import PlaceholderOp, topo_sort
    logits = lm_graph(cfg, 2, name)[1]
    return {n.name: n.shape for n in topo_sort([logits])
            if isinstance(n, PlaceholderOp) and n.is_variable}
