"""MoE ops — gating, dispatch, expert-parallel collectives.

Reference machinery (SURVEY.md §2.6): LayoutTransform.cu (Tutel-style token
dispatch), ReverseLayoutTransform, AllToAll.cu / HAllToAll (hierarchical),
TopKIdx/TopKVal, Cumsum, OneHot, BalanceAssignment (BASE layer auction).

TPU-native redesign: dispatch/combine are *dense einsums* against one-hot
capacity masks (the GShard formulation) — MXU-friendly, static shapes, no
scatter; expert parallelism is expressed by sharding the expert axis over the
'ep' mesh axis, letting XLA emit all_to_all over ICI (the explicit
``lax.all_to_all`` path lives in :mod:`hetu_tpu.parallel.collectives` for
shard_map users).  Capacity overflow drops tokens exactly like the
reference's fixed-capacity LayoutTransform.
"""
import jax
import jax.numpy as jnp

from .base import def_op, SimpleOp, tuple_outputs


def _one_hot_f(idx, n):
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


def _top1_gating(logits, capacity):
    """Returns (dispatch (s,e,c), combine (s,e,c), aux_loss) — GShard top-1."""
    s, e = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)
    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot_f(idx1, e)                       # (s, e)
    # position of each token within its expert queue
    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - mask1  # (s, e), 0-based
    keep1 = mask1 * (pos1 < capacity)
    gate1 = jnp.sum(gates * keep1, axis=-1)           # (s,)
    # aux load-balance loss (reference TopGate.py balance_loss:6)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    aux = jnp.sum(me * ce) * e
    pos_in_e = jnp.sum(pos1 * keep1, axis=-1).astype(jnp.int32)  # (s,)
    dispatch = keep1[:, :, None] * _one_hot_f(pos_in_e, capacity)[:, None, :]
    combine = gate1[:, None, None] * dispatch
    return dispatch, combine, aux


def _top2_gating(logits, capacity):
    s, e = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)
    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = _one_hot_f(idx1, e)
    gates2 = gates * (1 - mask1)
    idx2 = jnp.argmax(gates2, axis=-1)
    mask2 = _one_hot_f(idx2, e)

    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - mask1
    # expert-2 queue positions come after all expert-1 tokens of that expert
    pos2 = (jnp.cumsum(mask2, axis=0) * mask2 - mask2) \
        + jnp.sum(mask1, axis=0, keepdims=True)
    keep1 = mask1 * (pos1 < capacity)
    keep2 = mask2 * (pos2 < capacity)

    g1 = jnp.sum(gates * keep1, axis=-1)
    g2 = jnp.sum(gates * keep2, axis=-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(mask1, axis=0)
    aux = jnp.sum(me * ce) * e

    p1 = jnp.sum(pos1 * keep1, axis=-1).astype(jnp.int32)
    p2 = jnp.sum(pos2 * keep2, axis=-1).astype(jnp.int32)
    d1 = keep1[:, :, None] * _one_hot_f(p1, capacity)[:, None, :]
    d2 = keep2[:, :, None] * _one_hot_f(p2, capacity)[:, None, :]
    dispatch = jnp.maximum(d1, d2)
    combine = g1[:, None, None] * d1 + g2[:, None, None] * d2
    return dispatch, combine, aux


def _dispatch_from(keep, pos, capacity, gate_w=None):
    """Build (s,e,c) dispatch / combine tensors from a keep mask (s,e) and
    per-token queue positions (s,)."""
    d = keep[:, :, None] * _one_hot_f(pos, capacity)[:, None, :]
    if gate_w is None:
        return d
    return d, gate_w[:, None, None] * d


def _ktop1_gating(logits, k, capacity):
    """KTop1 (reference ``layers/KTop1Gate.py`` ktop1gating:14): experts are
    split into k prototype groups of e/k; each token routes top-1 within
    EVERY group (so k experts per token, one per group); balance loss summed
    per group."""
    s, e = logits.shape
    g = e // k
    dis_parts, com_parts = [], []
    aux = 0.0
    for i in range(k):
        gates = jax.nn.softmax(logits[:, i * g:(i + 1) * g], axis=-1)
        idx = jnp.argmax(gates, axis=-1)
        mask = _one_hot_f(idx, g)
        posm = jnp.cumsum(mask, axis=0) * mask - mask
        keep = mask * (posm < capacity)
        gate_w = jnp.sum(gates * keep, axis=-1)
        aux = aux + jnp.sum(jnp.mean(gates, 0) * jnp.mean(mask, 0)) * g
        p = jnp.sum(posm * keep, axis=-1).astype(jnp.int32)
        d, c = _dispatch_from(keep, p, capacity, gate_w)
        dis_parts.append(d)
        com_parts.append(c)
    dispatch = jnp.concatenate(dis_parts, axis=1)   # (s, e, c)
    combine = jnp.concatenate(com_parts, axis=1)
    return dispatch, combine, aux


def _sam_gating(logits, k, capacity, group_size):
    """SAM gate (reference ``layers/SAMGate.py`` samgating:22 + SamMax.cu,
    SamGroupSum.cu, GroupTopKIdx.cu): softmax over all experts; pick the
    group (node) with the largest summed prob; route top-k within that group;
    alignment loss = hinge on out-group probs exceeding the selected k-th
    expert's prob."""
    s, e = logits.shape
    ngroups = e // group_size
    gates = jax.nn.softmax(logits, axis=-1)
    gsum = gates.reshape(s, ngroups, group_size).sum(-1)
    top_group = jnp.argmax(gsum, axis=-1)                       # (s,)
    in_group = _one_hot_f(top_group, ngroups)                   # (s, ngroups)
    in_group_e = jnp.repeat(in_group, group_size, axis=1)       # (s, e)
    masked_gates = jnp.where(in_group_e > 0, gates, -jnp.inf)

    dispatch = jnp.zeros((s, e, capacity), jnp.float32)
    combine = jnp.zeros((s, e, capacity), jnp.float32)
    aux = 0.0
    used = jnp.zeros((s, e), jnp.float32)  # masks already routed experts
    kth_prob = None
    for i in range(k):
        idx = jnp.argmax(jnp.where(used > 0, -jnp.inf, masked_gates), axis=-1)
        mask = _one_hot_f(idx, e)
        used = used + mask
        # queue positions account for earlier-k selections (acc_base)
        posm = jnp.cumsum(mask, axis=0) * mask - mask \
            + jnp.sum(used - mask, axis=0, keepdims=True) * mask
        keep = mask * (posm < capacity)
        gate_w = jnp.sum(gates * keep, axis=-1)
        aux = aux + jnp.sum(jnp.mean(gates, 0) * jnp.mean(mask, 0)) * e
        p = jnp.sum(posm * keep, axis=-1).astype(jnp.int32)
        d, c = _dispatch_from(keep, p, capacity, gate_w)
        dispatch = dispatch + d
        combine = combine + c
        kth_prob = jnp.sum(gates * mask, axis=-1)               # (s,)
    # SamMax hinge: out-group probs exceeding the k-th selected prob
    out_group = 1.0 - in_group_e
    align = jnp.sum(jnp.maximum(gates - kth_prob[:, None], 0.0) * out_group)
    return dispatch, combine, aux, align


def ktop1_gate_op(logits_node, k, capacity, name=None):
    """Fused KTop1 gating node → (dispatch, combine, aux_loss)."""
    node = SimpleOp("KTop1Gate", [logits_node],
                    lambda c, logits, k=1, capacity=None:
                        _ktop1_gating(logits, k, capacity),
                    name=name, k=k, capacity=capacity)
    return tuple_outputs(node, 3)


def sam_gate_op(logits_node, k, capacity, group_size, name=None):
    """Fused SAM gating node → (dispatch, combine, aux_loss, align_loss)."""
    node = SimpleOp("SAMGate", [logits_node],
                    lambda c, logits, k=1, capacity=None, group_size=1:
                        _sam_gating(logits, k, capacity, group_size),
                    name=name, k=k, capacity=capacity, group_size=group_size)
    return tuple_outputs(node, 4)


def topk_gate_op(logits_node, k=1, capacity=None, name=None):
    """Fused GShard gating: returns (dispatch, combine, aux_loss) nodes."""
    assert k in (1, 2)

    def lower(c, logits, k=1, capacity=None):
        fn = _top1_gating if k == 1 else _top2_gating
        return fn(logits, capacity)

    node = SimpleOp("TopKGate", [logits_node], lower, name=name,
                    k=k, capacity=capacity)
    return tuple_outputs(node, 3)


# dense dispatch/combine einsums (the reference's layout_transform /
# reverse_layout_transform, ``LayoutTransform.py:12``)
layout_transform_op = def_op(
    "LayoutTransform",
    lambda c, dispatch, tokens: jnp.einsum(
        "sec,sm->ecm", dispatch.astype(tokens.dtype), tokens))

reverse_layout_transform_op = def_op(
    "ReverseLayoutTransform",
    lambda c, combine, expert_out: jnp.einsum(
        "sec,ecm->sm", combine.astype(expert_out.dtype), expert_out))


def _hash_dispatch(c, idx, num_experts=1, capacity=None):
    """Hash gating (reference HashGate.py): expert = token_id % E."""
    e = num_experts
    expert_of = (idx.astype(jnp.int32) % e)
    mask = _one_hot_f(expert_of, e)
    pos = jnp.cumsum(mask, axis=0) * mask - mask
    keep = mask * (pos < capacity)
    p = jnp.sum(pos * keep, axis=-1).astype(jnp.int32)
    dispatch = keep[:, :, None] * _one_hot_f(p, capacity)[:, None, :]
    return dispatch


def hash_dispatch_op(idx_node, num_experts, capacity, name=None):
    return SimpleOp("HashDispatch", [idx_node], _hash_dispatch, name=name,
                    num_experts=num_experts, capacity=capacity)


def _balanced_assignment(scores, rounds=4):
    """Balanced token→expert assignment: every expert gets exactly
    tokens/experts tokens and every token is assigned exactly once.

    TPU-native replacement for the reference's auction kernel
    (``BalanceAssignment.cu``): a fixed number of dense greedy rounds —
    each round, unassigned tokens bid for their best expert with remaining
    capacity and the top bidders win — then a deterministic fill matches any
    leftovers to the remaining slots.  All static shapes, no data-dependent
    loops (rounds is a compile-time constant).

    Returns slot→token ids, shape (s,), grouped by expert: slot q*cap+i holds
    the i-th token assigned to expert q — a true permutation of arange(s).
    """
    s, e = scores.shape
    cap = s // e
    # Sinkhorn normalization evens out scale differences between experts
    p = scores
    for _ in range(4):
        p = p - jax.nn.logsumexp(p, axis=1, keepdims=True)
        p = p - jax.nn.logsumexp(p, axis=0, keepdims=True)

    assigned = jnp.full((s,), -1, jnp.int32)      # token -> expert
    pos = jnp.zeros((s,), jnp.int32)              # token -> queue pos in expert
    used = jnp.zeros((e,), jnp.int32)             # expert -> #tokens taken
    NEG = jnp.asarray(-1e30, p.dtype)
    for _ in range(rounds):
        open_e = used < cap                       # (e,)
        unas = assigned < 0                       # (s,)
        masked = jnp.where(open_e[None, :] & unas[:, None], p, NEG)
        choice = jnp.argmax(masked, axis=1)       # (s,)
        bid = jnp.where(unas & jnp.take(open_e, choice),
                        jnp.take_along_axis(masked, choice[:, None], 1)[:, 0],
                        NEG)
        cmask = _one_hot_f(choice, e) * (bid > NEG / 2)[:, None]  # (s, e)
        score_col = jnp.where(cmask > 0, bid[:, None], NEG)
        # rank tokens per chosen expert by bid (descending, stable)
        order = jnp.argsort(-score_col, axis=0)
        rank = jnp.argsort(order, axis=0)         # (s, e) rank within column
        accept = (cmask > 0) & (rank < (cap - used)[None, :])
        tok_rank = jnp.sum(jnp.where(accept, rank, 0), axis=1)
        acc_any = jnp.any(accept, axis=1)
        new_pos = jnp.sum(jnp.where(accept, used[None, :], 0), axis=1) + tok_rank
        assigned = jnp.where(acc_any, choice.astype(jnp.int32), assigned)
        pos = jnp.where(acc_any, new_pos.astype(jnp.int32), pos)
        used = used + jnp.sum(accept, axis=0).astype(jnp.int32)

    # deterministic fill: k-th leftover token -> k-th free slot
    unas = assigned < 0
    token_rank = jnp.cumsum(unas.astype(jnp.int32)) - 1          # (s,)
    slot_expert = jnp.repeat(jnp.arange(e), cap)                 # (s,)
    slot_idx = jnp.tile(jnp.arange(cap), e)                      # pos within expert
    free = slot_idx >= jnp.take(used, slot_expert)               # (s,) slot free?
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
    # token with rank r takes the slot with rank r
    fill_expert = jnp.zeros((s,), jnp.int32).at[
        jnp.where(free, free_rank, s)].set(slot_expert.astype(jnp.int32),
                                           mode="drop")
    fill_pos = jnp.zeros((s,), jnp.int32).at[
        jnp.where(free, free_rank, s)].set(slot_idx.astype(jnp.int32),
                                           mode="drop")
    assigned = jnp.where(unas, jnp.take(fill_expert, token_rank), assigned)
    pos = jnp.where(unas, jnp.take(fill_pos, token_rank), pos)

    slot_of_token = assigned * cap + pos                          # (s,)
    token_of_slot = jnp.zeros((s,), jnp.int32).at[slot_of_token].set(
        jnp.arange(s, dtype=jnp.int32))
    return token_of_slot


def balance_assignment_op(scores_node, name=None):
    """BASE-layer balanced assignment node: scores (tokens, experts) →
    slot→token permutation (see :func:`_balanced_assignment`)."""
    return SimpleOp("BalanceAssignment", [scores_node],
                    lambda c, scores: _balanced_assignment(scores), name=name)


# explicit graph-level alltoall (EP over mesh): identity + sharding constraint;
# real lax.all_to_all lives in parallel.collectives for shard_map programs
def _alltoall(c, x):
    if c.mesh is not None and "ep" in c.mesh.axis_names:
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(c.mesh, PartitionSpec("ep", *([None] * (x.ndim - 1)))))
    return x


alltoall_op = def_op("AllToAll", _alltoall)


def _halltoall(c, x):
    """Hierarchical a2a (reference HAllToAll.cu + mpi_nccl dlarrayHAllToAll
    :396).  Under a 2-D ('ep_outer','ep_inner') mesh the leading dim is
    exchanged with the explicit intra-node → inter-node 2-phase schedule;
    on a flat 'ep' mesh it degrades to the sharding-constraint alltoall."""
    mesh = c.mesh
    if mesh is not None and "ep_outer" in mesh.axis_names \
            and "ep_inner" in mesh.axis_names:
        from jax.sharding import PartitionSpec as P
        from ..parallel.collectives import hierarchical_all_to_all
        spec = P(("ep_outer", "ep_inner"), *([None] * (x.ndim - 1)))
        return jax.shard_map(
            lambda v: hierarchical_all_to_all(v, "ep_outer", "ep_inner"),
            mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False)(x)
    return _alltoall(c, x)


halltoall_op = def_op("HAllToAll", _halltoall)


# ---------------------------------------------------------------------------
# Sparse (index-map) dispatch path — Pallas row-gather kernel, O(s·m) memory
# instead of the (s, e, c) one-hot tensors above; same routing/drop semantics.
# ---------------------------------------------------------------------------

def _topk_sparse_indices(logits, k, capacity):
    """GShard top-1/2 routing as index maps (no (s,e,c) tensors).

    Returns (token_of_slot (e*cap,), slot_of_token (s, k),
    k_of_slot (e*cap,), gate_w (s, k), aux_loss) with routing, capacity
    drops, gate normalisation, and aux loss identical to
    :func:`_top1_gating` / :func:`_top2_gating`.
    """
    s, e = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)
    remaining = gates
    count_prev = jnp.zeros((1, e), jnp.float32)
    slots, gws, masks = [], [], []
    for _ in range(k):
        idx = jnp.argmax(remaining, axis=-1)
        mask = _one_hot_f(idx, e)
        pos = (jnp.cumsum(mask, axis=0) * mask - mask) + count_prev * mask
        keep = mask * (pos < capacity)
        kept = jnp.sum(keep, axis=-1) > 0                     # (s,) bool
        gws.append(jnp.sum(gates * keep, axis=-1))            # (s,)
        p = jnp.sum(pos * keep, axis=-1).astype(jnp.int32)
        slot = jnp.where(kept, idx.astype(jnp.int32) * capacity + p, -1)
        slots.append(slot)
        masks.append(mask)
        count_prev = count_prev + jnp.sum(mask, axis=0, keepdims=True)
        remaining = remaining * (1 - mask)
    gate_w = jnp.stack(gws, axis=1)                           # (s, k)
    if k > 1:  # top-2 renormalisation (reference TopGate.py)
        denom = jnp.maximum(jnp.sum(gate_w, axis=1, keepdims=True), 1e-9)
        gate_w = gate_w / denom
    slot_of_token = jnp.stack(slots, axis=1)                  # (s, k)
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(masks[0], axis=0)
    aux = jnp.sum(me * ce) * e

    n_slots = e * capacity
    tok_ids = jnp.arange(s, dtype=jnp.int32)
    token_of_slot = jnp.full((n_slots,), -1, jnp.int32)
    k_of_slot = jnp.zeros((n_slots,), jnp.int32)
    for j in range(k):
        tgt = jnp.where(slots[j] >= 0, slots[j], n_slots)
        token_of_slot = token_of_slot.at[tgt].set(tok_ids, mode="drop")
        k_of_slot = k_of_slot.at[tgt].set(j, mode="drop")
    return token_of_slot, slot_of_token, k_of_slot, gate_w, aux


def topk_gate_sparse_op(logits_node, k=1, capacity=None, name=None):
    """Sparse GShard gating → (token_of_slot, slot_of_token, k_of_slot,
    gate_w, aux_loss) nodes for the Pallas dispatch path."""
    node = SimpleOp("TopKGateSparse", [logits_node],
                    lambda c, logits, k=1, capacity=None:
                        _topk_sparse_indices(logits, k, capacity),
                    name=name, k=k, capacity=capacity)
    return tuple_outputs(node, 5)


def _pallas_interpret():
    return jax.default_backend() != "tpu"


def _sparse_dispatch_lower(c, tokens, token_of_slot, slot_of_token):
    from .pallas.moe_dispatch import sparse_dispatch
    return sparse_dispatch(tokens, token_of_slot, slot_of_token,
                           _pallas_interpret())


sparse_dispatch_op = def_op("SparseDispatch", _sparse_dispatch_lower)


def _sparse_combine_lower(c, buffers, gate_w, slot_of_token, token_of_slot,
                          k_of_slot):
    from .pallas.moe_dispatch import sparse_combine
    return sparse_combine(buffers, gate_w, slot_of_token, token_of_slot,
                          k_of_slot, _pallas_interpret())


sparse_combine_op = def_op("SparseCombine", _sparse_combine_lower)


# ------------------------------------------------- dropless serving layer
# What a SERVED mixture of experts needs and the capacity gates above do
# not give: no token is dropped, the scores are sigmoids with a selection
# bias (the DeepSeek-V3 convention), and the layer is told which experts it
# HOLDS — it routes over all of them and computes its own experts' part of
# the result, what one chip of an expert-parallel group does before the
# group's all-reduce.  ``models/common.py:moe_block`` is the caller (the
# Solar-Open2 and GLM-4.7-Flash graphs).

def _route_pick(s, bias, k):
    """The ``k`` experts whose BIASED score is highest, and their plain
    scores: the bias selects and does not weigh."""
    _, ids = jax.lax.top_k(s + bias, k)
    return ids, jnp.take_along_axis(s, ids, axis=-1)


def _route_norm(chosen):
    """Weights normalised over the chosen (``norm_topk_prob``)."""
    return chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def _moe_route(c, y, w_r, bias, top_k=1, scale=1.0):
    """``s = sigmoid(y W_r)`` over ALL experts, in float32 from the float32
    input at the highest matrix precision; chosen = the ``top_k`` of ``s +
    bias``; weights ``scale · s_e / Σ_chosen s`` (``routed_scaling_factor``;
    at 1 nothing is multiplied).  ``y``: (N, d); ``w_r``: (d, E); ``bias``:
    (E,).  Returns ``(ids (N, k) int32, weights (N, k) float32)``."""
    f32 = jnp.float32
    s = jax.nn.sigmoid(jnp.matmul(y.astype(f32), w_r.astype(f32),
                                  precision=jax.lax.Precision.HIGHEST))
    ids, chosen = _route_pick(s, bias.astype(f32), int(top_k))
    weights = _route_norm(chosen)
    if scale != 1.0:
        weights = weights * f32(scale)
    return ids.astype(jnp.int32), weights


_moe_route_node = def_op("MoERoute", _moe_route)


def moe_route_op(y, w_r, bias, top_k, scale=1.0, name=None):
    """``(ids, weights)`` nodes of :func:`_moe_route`."""
    return tuple_outputs(_moe_route_node(y, w_r, bias, top_k=top_k,
                                         scale=float(scale), name=name), 2)


def _held(local, count):
    """Which (token, expert) pairs are this layer's: ``local`` the chosen
    ids counted from the first held expert."""
    return jnp.logical_and(local >= 0, local < count)


#: row tile of the kernel path.  A group of a few rows pays one tile of
#: MXU rows whatever its size; at 128 that stays under the time its
#: weights take to cross (the compiler's own ragged-dot takes 512-row
#: tiles and is bound by them at serving batch sizes)
_ROW_TILE = 128
#: bytes of one weight block (k tile x n tile) a grid step of the kernel
#: path fetches, double-buffered under the default scoped VMEM
_WEIGHT_BLOCK = 2 << 20


def _grouped_how():
    """The Pallas grouped matmul on the TPU; ``jax.lax.ragged_dot`` serves
    the CPU only (tests, the reference's platform)."""
    return "kernel" if jax.default_backend() == "tpu" else "ragged"


def _weight_tiles(k, n, itemsize):
    """(k tile, n tile) of the kernel path: the whole contraction in one
    block while a 512-lane strip of it fits ``_WEIGHT_BLOCK``, then the
    widest strip of whole 128-lane columns that divides ``n`` and fits."""
    tk = k
    while tk * 512 * itemsize > _WEIGHT_BLOCK and tk % 256 == 0:
        tk //= 2
    fits = [t for t in range(128, n + 1, 128)
            if n % t == 0 and tk * t * itemsize <= _WEIGHT_BLOCK]
    return tk, (max(fits) if fits else n)


def _grouped_matmul(rows, w, sizes, how):
    """``rows[group g's rows] @ w[g]`` for rows sorted by group, float32
    out; rows behind the last group come back undefined.  ``rows``: (M,
    k); ``w``: (G, k, n); ``sizes``: (G,) int32."""
    if how == "ragged":
        return jax.lax.ragged_dot(rows, w, sizes,
                                  preferred_element_type=jnp.float32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m = rows.shape[0]
    pad = -m % _ROW_TILE
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    tk, tn = _weight_tiles(w.shape[1], w.shape[2], w.dtype.itemsize)
    out = gmm(rows, w, sizes, preferred_element_type=jnp.float32,
              tiling=(_ROW_TILE, tk, tn),
              interpret=jax.default_backend() != "tpu")
    return out[:m] if pad else out


def _moe_experts(c, y, ids, weights, w_gu, w_d, first=0, n_experts=None):
    """The routed part of a dropless expert layer, of the experts HELD:
    ``Σ_{e chosen ∧ held} w_e W_d,e(silu(W_g,e y) ⊙ W_u,e y)``.  ``y``: (N,
    d); ``ids`` / ``weights``: (N, k) over all ``n_experts``; ``w_gu``: (G,
    d, 2f) the held experts' ``[gate | up]``, experts ``first .. first +
    G``; ``w_d``: (G, f, d).  The step's (token, expert) pairs are sorted
    by held expert and multiplied group by group — on the TPU by the
    Pallas grouped matmul (``jax.experimental.pallas.ops.tpu.megablox``),
    elsewhere by ``jax.lax.ragged_dot``: each held expert's weights cross
    once, an expert nobody chose not at all; a pair whose expert is held
    elsewhere sorts behind the last group, where nothing is computed."""
    from ..metrics import record_moe_call
    n, k = ids.shape
    g, _, f2 = w_gu.shape
    how = _grouped_how()
    record_moe_call(g, n_experts or g, k, how)
    local = ids - int(first)
    held = _held(local, g)
    key = jnp.where(held, local, g).reshape(-1)                  # (N*k,)
    order = jnp.argsort(key, stable=True)        # sorted place -> pair
    sizes = jnp.sum(key[:, None] == jnp.arange(g, dtype=key.dtype)[None, :],
                    axis=0, dtype=jnp.int32)
    rows = y.astype(w_gu.dtype)[order // k]
    h = _grouped_matmul(rows, w_gu, sizes, how)
    act = (jax.nn.silu(h[:, :f2 // 2]) * h[:, f2 // 2:]).astype(w_d.dtype)
    out = _grouped_matmul(act, w_d, sizes, how)
    # rows behind the last group are no group's: whatever lies there
    live = jnp.arange(n * k, dtype=jnp.int32) < jnp.sum(sizes)
    out = jnp.where(live[:, None], out, 0.0)
    back = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32), unique_indices=True)
    w = jnp.where(held, weights.astype(jnp.float32), 0.0)
    return jnp.sum(out[back].reshape(n, k, -1) * w[..., None], axis=1)


moe_experts_op = def_op("MoEExperts", _moe_experts)


def _moe_choices(c, feed, *ids):
    """The chosen expert ids of every expert layer, (B, C, layers, k)
    int16, ``(B, C)`` from the ``feed`` of token ids: what a decode step
    hands back beside its tokens."""
    stacked = jnp.stack(ids, axis=1).astype(jnp.int16)     # (B*C, L, k)
    return stacked.reshape(feed.shape + stacked.shape[1:])


moe_choices_op = def_op("MoEChoices", _moe_choices)
