"""Memory and time cost models for hybrid-parallel strategy search.

Capability parity with Galvatron (reference ``tools/Galvatron/utils/
cost_model.py:3`` MemoryCostModel, ``:38`` TimeCostModel_with_overlap),
re-targeted at TPU meshes: a *strategy* is ``(pp, tp, dp, fsdp)`` — pipeline
stages, tensor-parallel width, data-parallel width, and whether optimizer
state + params are fully sharded over dp (ZeRO-3 semantics, which is how the
"PS/fsdp" capability maps to synchronous TPU training).

All byte counts are per-device; bandwidths come from a measured
:class:`hetu_tpu.profiler.CollectiveProfiler` table or caller-supplied
constants.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Strategy:
    """One per-layer parallelization choice.

    ``cp`` (net-new vs Galvatron, whose dims are pp/tp/dp/fsdp only —
    ``utils/cost_model.py:13-16``): context/sequence parallelism over the
    'cp' mesh axis — tokens shard over cp everywhere, attention runs the
    ring schedule (``parallel/ring_attention.py``).  Params replicate over
    cp, so gradient sync spans dp x cp."""
    pp: int = 1
    tp: int = 1
    dp: int = 1
    fsdp: bool = False
    cp: int = 1

    @property
    def world(self):
        return self.pp * self.tp * self.dp * self.cp

    def __str__(self):
        tag = f"pp{self.pp}-tp{self.tp}-dp{self.dp}"
        if self.cp > 1:
            tag += f"-cp{self.cp}"
        return tag + ("-fsdp" if self.fsdp else "")


@dataclass
class LayerSpec:
    """Static per-layer workload description (Galvatron profiles these;
    we derive them from model config or HLO cost analysis).

    * ``param_bytes`` — parameter bytes of one layer replica
    * ``fwd_flops`` — forward FLOPs for the whole (global) batch
    * ``act_bytes`` — activation bytes for the whole batch (what pipeline
      p2p moves, and what remat trades)
    * ``count`` — how many identical layers share this spec
    * ``attn`` — contains self-attention: under cp the layer pays the ring
      K/V rotation (token-parallel layers without attention do not)
    """
    name: str
    param_bytes: float
    fwd_flops: float
    act_bytes: float
    count: int = 1
    attn: bool = False
    #: K+V bytes for the whole batch (what the cp ring actually rotates);
    #: act_bytes carries a ~6-12x liveset multiplier and must not be used
    #: for ring volume.  0 → approximated as act_bytes / 3.
    kv_bytes: float = 0.0


@dataclass
class HardwareSpec:
    """Device + interconnect model.

    ``flops``: sustained per-device FLOP/s (not peak — calibrate with a
    matmul probe). Bandwidths in bytes/s. ``overlap`` ∈ [0,1]: fraction of
    dp grad-allreduce hidden behind backward compute (Galvatron's
    overlap_coe).
    """
    flops: float = 100e12          # ~bf16 sustained on one v5e core
    mem_bytes: float = 16e9
    ici_bw: float = 4.5e10         # allreduce algo-bandwidth over ICI
    dcn_bw: float = 2.5e9
    overlap: float = 0.7

    def coll_bw(self, width):
        """Bandwidth for a collective of given participant count; >8-wide
        groups are assumed to cross DCN (multi-host)."""
        return self.ici_bw if width <= 8 else self.dcn_bw

    @classmethod
    def from_artifact(cls, path=None, **overrides):
        """The committed on-chip calibration (tools/calibrate_tpu.py →
        ``artifacts/tpu_calibration.json``), or None when absent/invalid —
        so a search off the chip is still grounded in MEASURED hardware."""
        import json
        import os
        if path is None:
            path = os.path.join(os.path.dirname(__file__), os.pardir,
                                os.pardir, "artifacts",
                                "tpu_calibration.json")
        import dataclasses
        try:
            with open(path) as f:
                data = json.load(f)
            kw = dict(data["spec"])
        except (OSError, KeyError, ValueError, TypeError):
            return None
        kw.update(overrides)
        fields = {f.name for f in dataclasses.fields(cls)}
        try:   # tolerate unknown/extra keys — invalid artifact means None
            return cls(**{k: v for k, v in kw.items() if k in fields})
        except (TypeError, ValueError):
            return None

    @classmethod
    def measure(cls, mesh=None, probe_bytes=1 << 22, matmul_dim=1024,
                **overrides):
        """Calibrated spec from THIS machine — delegates to
        :func:`hetu_tpu.autoparallel.calibrate_hardware` (the profile step
        of the Galvatron workflow) with test-friendly probe sizes."""
        from . import calibrate_hardware
        return calibrate_hardware(mesh=mesh, matmul_dim=matmul_dim,
                                  chain=8, probe_bytes=probe_bytes,
                                  **overrides)


OPT_STATE_MULT = 3.0   # param + adam m + v, fp32 master (bytes ×3 of fp32)
GRAD_MULT = 1.0


class MemoryCostModel:
    """Per-device memory of running one layer under a strategy
    (Galvatron MemoryCostModel: model states ×1/dp under fsdp:18-23).

    ``remat`` here is the SEARCH-level boolean knob (does the strategy
    assume activation recompute at all); the executor-side realization
    is the graded policy ladder in ``parallel/remat.py``, whose planner
    prices real graphs with this module's :func:`matmul_flops` /
    :data:`MATMUL_OPS` tables — one FLOP model for both."""

    def __init__(self, hw: HardwareSpec, microbatches: int = 1,
                 remat: bool = False):
        self.hw = hw
        self.microbatches = max(1, microbatches)
        self.remat = remat

    def layer_bytes(self, spec: LayerSpec, s: Strategy):
        shard = s.tp  # params shard over tp always
        params = spec.param_bytes / shard
        states = params * OPT_STATE_MULT
        grads = params * GRAD_MULT
        if s.fsdp:
            states /= s.dp
            params /= s.dp  # gathered transiently; steady-state sharded
            grads /= s.dp   # reduce-scattered
        acts = spec.act_bytes / (s.dp * s.tp * s.cp) / self.microbatches
        if self.remat:
            acts = acts / 4 + spec.act_bytes * 0.01  # boundary stashes
        return params + states + grads + acts

    def stage_bytes(self, specs, strategies):
        """Total per-device bytes when each layer i runs strategy[i] —
        layers divide over pp stages, so each stage holds 1/pp of them."""
        per_stage = {}
        for spec, s in zip(specs, strategies):
            b = self.layer_bytes(spec, s) * spec.count / s.pp
            per_stage[s.pp] = per_stage.get(s.pp, 0.0) + b
        return max(per_stage.values()) if per_stage else 0.0

    def fits(self, specs, strategies):
        return self.stage_bytes(specs, strategies) <= self.hw.mem_bytes


class TimeCostModel:
    """Per-layer step time under a strategy (Galvatron
    TimeCostModel_with_overlap:38): compute + tp collectives + un-overlapped
    dp gradient sync + pp bubble amortization."""

    def __init__(self, hw: HardwareSpec, microbatches: int = 1):
        self.hw = hw
        self.microbatches = max(1, microbatches)

    @classmethod
    def calibrated(cls, mesh=None, microbatches=1, **probe_kw):
        """Construct over THIS machine's measured constants: matmul-probe
        FLOP/s, allreduce bandwidth and the measured compute/comm overlap
        coefficient from :func:`~hetu_tpu.autoparallel.calibrate_hardware`
        — the profile leg of the Galvatron workflow wired directly into
        cost-model construction (previously callers had to plumb the
        measured spec by hand, so defaults were what actually priced
        searches)."""
        spec = HardwareSpec.measure(mesh=mesh, **probe_kw)
        return cls(spec, microbatches=microbatches)

    def layer_time(self, spec: LayerSpec, s: Strategy):
        hw = self.hw
        # fwd+bwd ≈ 3× fwd flops, spread over tp*dp*cp devices (batch over
        # dp, matmul width over tp, tokens over cp)
        compute = 3.0 * spec.fwd_flops / (s.tp * s.dp * s.cp) / hw.flops
        # TP: 2 allreduces fwd + 2 bwd per transformer layer over the
        # activation bytes (Megatron pattern), ring cost ×2(n-1)/n
        tp_comm = 0.0
        if s.tp > 1:
            vol = 4.0 * spec.act_bytes / (s.dp * s.tp * s.cp)
            tp_comm = vol * 2 * (s.tp - 1) / s.tp / hw.coll_bw(s.tp)
        # CP: the ring rotates each rank's local K+V chunk (cp-1) times;
        # the schedule overlaps permute with blockwise compute, so only
        # the un-overlapped fraction is charged.  Token-parallel layers
        # without attention pay nothing.
        cp_comm = 0.0
        if s.cp > 1 and spec.attn:
            kv_total = spec.kv_bytes or (spec.act_bytes / 3.0)
            kv = kv_total / (s.dp * s.tp * s.cp)
            cp_comm = kv * (s.cp - 1) / hw.coll_bw(s.cp) \
                * (1.0 - hw.overlap)
        # DP: grad allreduce (or reduce-scatter+all-gather for fsdp — same
        # ring volume), partly overlapped with backward.  Params replicate
        # over cp, so the sync ring spans dp*cp participants.
        dp_comm = 0.0
        n_sync = s.dp * s.cp
        if n_sync > 1:
            vol = (spec.param_bytes / s.tp) * 2 * (n_sync - 1) / n_sync
            dp_comm = vol / hw.coll_bw(n_sync) * (1.0 - hw.overlap)
        if s.fsdp and s.dp > 1:
            # extra fwd all-gather of sharded params (not overlappable fully)
            vol = (spec.param_bytes / s.tp) * (s.dp - 1) / s.dp
            dp_comm += vol / hw.coll_bw(s.dp) * 0.5
        # PP: p2p activations between stages + bubble overhead factor
        pp_cost = 0.0
        if s.pp > 1:
            p2p = spec.act_bytes / (s.dp * s.tp * s.cp) / hw.coll_bw(2)
            bubble = (s.pp - 1) / self.microbatches
            pp_cost = p2p + compute * bubble
        return compute + tp_comm + cp_comm + dp_comm + pp_cost

    def total(self, specs, strategies):
        return sum(self.layer_time(sp, st) * sp.count
                   for sp, st in zip(specs, strategies))


def transformer_layer_spec(hidden, seq, batch, ffn_mult=4, dtype_bytes=2,
                           name="layer", count=1):
    """Derive a LayerSpec for one transformer block from model dims."""
    params = (4 * hidden * hidden + 2 * ffn_mult * hidden * hidden) \
        * dtype_bytes
    tokens = batch * seq
    flops = 2 * tokens * (4 * hidden * hidden + 2 * ffn_mult * hidden
                          * hidden) + 2 * 2 * batch * seq * seq * hidden
    acts = tokens * hidden * dtype_bytes * 12  # rough per-block liveset
    return LayerSpec(name, float(params), float(flops), float(acts), count,
                     attn=True, kv_bytes=float(2 * tokens * hidden
                                               * dtype_bytes))


# -- per-type specs (Galvatron multi-layer-type DP, dp_utils.py:259) --------

def attention_layer_spec(hidden, seq, batch, dtype_bytes=2, name="attn",
                         count=1):
    """Self-attention sublayer: 4 h×h projections + the s² score term."""
    tokens = batch * seq
    params = 4 * hidden * hidden * dtype_bytes
    flops = 2 * tokens * 4 * hidden * hidden \
        + 2 * 2 * batch * seq * seq * hidden
    acts = tokens * hidden * dtype_bytes * 6
    return LayerSpec(name, float(params), float(flops), float(acts), count,
                     attn=True, kv_bytes=float(2 * tokens * hidden
                                               * dtype_bytes))


def mlp_layer_spec(hidden, seq, batch, ffn_mult=4, dtype_bytes=2,
                   name="mlp", count=1):
    """FFN sublayer: up/down projections."""
    tokens = batch * seq
    params = 2 * ffn_mult * hidden * hidden * dtype_bytes
    flops = 2 * tokens * 2 * ffn_mult * hidden * hidden
    acts = tokens * hidden * dtype_bytes * (2 + ffn_mult)
    return LayerSpec(name, float(params), float(flops), float(acts), count)


def embedding_layer_spec(vocab, hidden, seq, batch, dtype_bytes=2,
                         name="embed", tied_head=True, count=1):
    """Token embedding (+ tied LM head): parameter-dominated, nearly
    FLOP-free on lookup; the head matmul carries the vocab FLOPs."""
    tokens = batch * seq
    params = vocab * hidden * dtype_bytes
    flops = (2 * tokens * vocab * hidden) if tied_head else tokens * hidden
    acts = tokens * max(hidden, vocab if tied_head else hidden) \
        * dtype_bytes
    return LayerSpec(name, float(params), float(flops), float(acts), count)


def model_layer_specs(n_layers, hidden, seq, batch, vocab, ffn_mult=4,
                      dtype_bytes=2):
    """Interleaved multi-type chain for the joint DP search: embedding,
    then (attention, mlp) per block — the reference searches these types
    JOINTLY rather than one uniform per-block spec
    (``tools/Galvatron/utils/dp_utils.py:259`` multi-layer-type)."""
    specs = [embedding_layer_spec(vocab, hidden, seq, batch, dtype_bytes)]
    for i in range(n_layers):
        specs.append(attention_layer_spec(hidden, seq, batch, dtype_bytes,
                                          name=f"attn{i}"))
        specs.append(mlp_layer_spec(hidden, seq, batch, ffn_mult,
                                    dtype_bytes, name=f"mlp{i}"))
    return specs


def swin_layer_specs(image_size, patch_size, embed_dim, depths, num_heads,
                     window_size, batch, mlp_ratio=4, dtype_bytes=2):
    """Hierarchical swin chain for the multi-layer-type DP search — the
    reference's fourth Galvatron runtime family (``tools/Galvatron/swin/``
    profiles these same per-layer costs from torch; here they derive from
    the geometry of ``models/swin.py``).

    Swin's cost structure differs from the uniform-transformer chain in
    two ways the search must see: (1) attention is WINDOWED — the s² score
    term runs at seq=w² over batch·nW windows, so it stays cheap while the
    projection/MLP cost tracks the full token count; (2) the stage ladder
    halves tokens and doubles width at each patch-merge, so early stages
    are activation-heavy (pipeline-split-expensive) while late stages are
    parameter-heavy (fsdp/tp-friendly).
    """
    import dataclasses
    del num_heads  # head count does not change FLOPs/bytes at this level
    assert image_size % patch_size == 0
    specs = []
    res = image_size // patch_size
    in_dim = 3 * patch_size * patch_size
    specs.append(LayerSpec(
        "patch_embed", float(in_dim * embed_dim * dtype_bytes),
        float(2 * batch * res * res * in_dim * embed_dim),
        float(batch * res * res * embed_dim * dtype_bytes * 2)))
    dim = embed_dim
    for si, depth in enumerate(depths):
        w = min(window_size, res)
        # mirror the model's build-time geometry contract
        # (models/swin.py SwinConfig): silently floor-dividing here would
        # price a model that cannot be built
        assert res % w == 0, (
            f"stage {si}: resolution {res} not divisible by window {w}")
        tokens = batch * res * res            # == (batch·nW) · w²
        for bi in range(depth):
            spec = attention_layer_spec(
                hidden=dim, seq=w * w, batch=tokens // (w * w),
                dtype_bytes=dtype_bytes, name=f"s{si}.attn{bi}")
            shifted = bi % 2 == 1 and w < res  # models/swin.py shift rule
            if not shifted:
                # unshifted windows are mutually independent: a cp shard
                # aligned to window boundaries exchanges NO K/V, so the
                # ring charge (TimeCostModel attn path) must not apply
                spec = dataclasses.replace(spec, attn=False, kv_bytes=0.0)
            else:
                # SHIFTED windows straddle any window-aligned shard cut:
                # each shard swaps a w/2-row halo strip (both H and W
                # rolls) with ONE neighbour.  Keep attn=True with
                # kv_bytes = the halo volume; the ring formula's (cp-1)
                # multiplier overcounts a single-neighbour exchange, so
                # this prices cp PESSIMISTICALLY on shifted blocks —
                # the safe direction for an un-modeled halo schedule.
                halo = 2 * batch * res * (w // 2) * dim * dtype_bytes
                spec = dataclasses.replace(spec, kv_bytes=float(2 * halo))
            specs.append(spec)
            specs.append(mlp_layer_spec(
                hidden=dim, seq=res * res, batch=batch,
                ffn_mult=mlp_ratio, dtype_bytes=dtype_bytes,
                name=f"s{si}.mlp{bi}"))
        if si + 1 < len(depths):
            assert res % 2 == 0, f"stage {si}: odd resolution {res}"
            merged = tokens // 4
            specs.append(LayerSpec(
                f"s{si}.merge", float(4 * dim * 2 * dim * dtype_bytes),
                float(2 * merged * 4 * dim * 2 * dim),
                float(merged * 4 * dim * dtype_bytes)))
            res //= 2
            dim *= 2
    return specs


#: matmul-family op -> index of the LEFT matrix operand (Addmm/Baddbmm
#: carry the additive input first).  Public surface: the selective-remat
#: planner (``parallel/remat.py``) prices per-SEGMENT recompute FLOPs
#: with exactly this table + :func:`matmul_flops`, so the remat plan and
#: the strategy search can never disagree about what a matmul costs.
MATMUL_OPS = {"MatrixMult": 0, "Linear": 0, "BatchMatrixMult": 0,
              "Addmm": 1, "Baddbmm": 1}
_MATMUL_OPS = MATMUL_OPS          # original (private) alias, kept
_ATTN_OPS = ("ScaledDotProductAttention", "RingAttention",
             "UlyssesAttention")


def matmul_flops(node, gs, out_shape):
    """2·(output elements)·(contracted size) for one matmul-family node,
    or None when shapes are unknown."""
    import numpy as np
    t = node.op_type
    if t == "Einsum":
        eq = node.attrs.get("subscripts", "")
        if "->" not in eq:
            return None
        lhs, out = eq.split("->")
        terms = lhs.split(",")
        shapes = [gs.shape(i) for i in node.inputs]
        sizes = {}
        for term, shp in zip(terms, shapes):
            if shp is None or len(term) != len(shp):
                return None
            sizes.update(zip(term, shp))
        contracted = [sizes[lab] for lab in set("".join(terms)) - set(out)]
        if not contracted:
            return None
        return 2.0 * float(np.prod(out_shape)) * float(np.prod(contracted))
    a_idx = _MATMUL_OPS[t]
    if a_idx >= len(node.inputs):
        return None
    a = gs.shape(node.inputs[a_idx])
    if not a:
        return None
    k = a[-2] if node.attrs.get("trans_A", False) else a[-1]
    return 2.0 * float(np.prod(out_shape)) * float(k)


_matmul_flops = matmul_flops      # original (private) alias, kept


#: groups "<prefix>.layer<N>.<rest>" node names into one bucket per layer
#: (the ``models/`` naming convention: bert.layer3.ffn1, gpt2.layer0.attn)
_LAYER_NAME_RE = None   # compiled lazily (re import stays function-local)


def _default_split(node_name):
    """Bucket key for :func:`graph_layer_specs`' default segmentation, or
    None to stay in the current bucket."""
    global _LAYER_NAME_RE
    if _LAYER_NAME_RE is None:
        import re
        _LAYER_NAME_RE = re.compile(r"^(.*?\.layer\d+)(?:\.|$)")
    m = _LAYER_NAME_RE.match(node_name or "")
    return m.group(1) if m else None


def bert_split(node_name):
    """:func:`graph_layer_specs` ``split`` for bert-style graphs: the
    ``<prefix>.layer<N>`` anchors plus explicit stem/head routing —
    the default split alone merges the trailing MLM head (and pooler)
    into the LAST encoder layer and the embeddings into the stem."""
    if not node_name:
        return None
    if ".embeddings" in node_name:
        return "embeddings"
    if ".mlm_" in node_name or ".pooler" in node_name:
        return "head"
    return _default_split(node_name)


def graph_layer_specs(fetches, feeds=None, split=None, name="graph",
                      dtype_bytes=4):
    """Per-layer :class:`LayerSpec` chain from a REAL fetch subgraph —
    the end-to-end pricing path (callers previously hand-assembled layer
    lists from model dims; this walks the graph that will actually
    compile).

    Uses the static shape assignment from
    :func:`hetu_tpu.analysis.infer_graph` (every node's ``(shape, dtype)``
    with zero FLOPs — no ``None`` holes).  Per bucket:

    * ``param_bytes`` — sum over trainable variable leaves,
    * ``fwd_flops`` — 2·M·N·K over every matmul-family node (attention
      score/value contractions counted from q/k shapes),
    * ``act_bytes`` — sum of output bytes over compute nodes (the
      activation liveset upper bound that remat/pipeline p2p trade in).

    ``split``: callable ``node_name -> bucket key | None`` (None = no
    opinion).  The default groups by the ``<prefix>.layer<N>`` naming
    convention the ``models/`` builders follow.  Auto-named compute
    nodes INHERIT the bucket of their inputs (a matmul consuming
    ``bert.layer0.ffn1.weight`` belongs to ``bert.layer0``; downstream
    elementwise ops follow their producers) — layer params are the
    naming anchors, so attribution tracks dataflow, not topo accidents.
    A node whose inputs span several buckets joins the latest-created
    one (a residual add of layer i-1's output and layer i's branch is
    layer i work); nodes with no named ancestor land in
    ``"<name>.stem"``.  Pass forward fetches (the loss), not the
    optimizer op — :class:`TimeCostModel` applies the fwd+bwd
    multiplier itself.

    Returns the buckets as LayerSpecs in first-seen topo order; a graph
    with no matching names collapses to one whole-graph spec (exactly
    :func:`graph_layer_spec`)."""
    import numpy as np
    from ..analysis.shapes import infer_graph
    from ..graph.node import PlaceholderOp

    if split is None:
        split = _default_split
    gs = infer_graph(fetches, feeds=feeds)
    stem = f"{name}.stem"
    order = []                   # bucket keys, first-seen topo order
    acc = {}                     # key -> [params, flops, acts, attn]
    node_bucket = {}             # node -> its bucket key

    def _acc_of(key):
        if key not in acc:
            order.append(key)
            acc[key] = [0.0, 0.0, 0.0, False]
        return acc[key]

    def _assign(node):
        key = split(getattr(node, "name", None))
        if key is None:
            # inherit from inputs: the latest-created NAMED bucket wins
            # (stem is the no-opinion bucket — a mask reshape feeding
            # every attention layer must not capture them)
            best = -1
            for inp in getattr(node, "inputs", ()) or ():
                k = node_bucket.get(inp)
                if k is not None and k != stem:
                    idx = order.index(k)
                    if idx > best:
                        best, key = idx, k
        if key is None:
            key = stem
        node_bucket[node] = key
        return key

    for node in gs.topo:
        st = gs.struct(node)
        if st is None or isinstance(st, (tuple, list)):
            continue
        nbytes = float(np.prod(st.shape)) * dtype_bytes if st.shape \
            else float(dtype_bytes)
        if isinstance(node, PlaceholderOp):
            if node.is_variable and getattr(node, "trainable", False):
                key = _assign(node)
                _acc_of(key)[0] += nbytes
            else:
                # non-variable placeholders (feeds) anchor nothing: let
                # compute inherit from params, not from input ids
                node_bucket[node] = None
            continue
        b = _acc_of(_assign(node))
        b[2] += nbytes
        if node.op_type in _MATMUL_OPS or node.op_type == "Einsum":
            f = _matmul_flops(node, gs, st.shape)
            if f:
                b[1] += f
        elif node.op_type.startswith(_ATTN_OPS) and len(node.inputs) >= 2:
            q = gs.shape(node.inputs[0])
            kv = gs.shape(node.inputs[1])
            if q and kv:
                b_h = float(np.prod(q[:-2]))
                s_q, d = float(q[-2]), float(q[-1])
                s_kv = float(kv[-2])
                b[3] = True
                b[1] += 2.0 * 2.0 * b_h * s_q * s_kv * d  # scores + values
        elif hasattr(node, "fwd_flops"):
            b[1] += node.fwd_flops(gs)   # an op that prices its own graph
    if not acc:
        return [LayerSpec(name, 0.0, 0.0, 0.0)]
    return [LayerSpec(k, *acc[k][:3], count=1, attn=acc[k][3])
            for k in order]


def graph_layer_spec(fetches, feeds=None, name="graph", dtype_bytes=4,
                     count=1):
    """One fused :class:`LayerSpec` for a REAL fetch subgraph — the
    single-bucket view of :func:`graph_layer_specs` (same walk, same
    numbers; ``measure.graph_flops`` and the remat planner read this)."""
    specs = graph_layer_specs(fetches, feeds=feeds,
                              split=lambda _n: None, name=name,
                              dtype_bytes=dtype_bytes)
    merged = specs[0]
    merged.name = name
    merged.count = count
    return merged


__all__ = ["Strategy", "LayerSpec", "HardwareSpec", "MemoryCostModel",
           "TimeCostModel", "transformer_layer_spec",
           "attention_layer_spec", "mlp_layer_spec",
           "embedding_layer_spec", "model_layer_specs",
           "swin_layer_specs", "graph_layer_spec", "graph_layer_specs",
           "bert_split", "MATMUL_OPS", "matmul_flops"]
