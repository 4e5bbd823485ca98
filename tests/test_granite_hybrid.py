"""Granite-4.0-H (Mamba-2 layers 9:1 with NoPE grouped-query attention, muP
multipliers, a tied head) through ``DecodeEngine``, against the plain
full-sequence reference of ``benchmarks/reference/granite_hybrid_lm.py`` —
the recurrence token by token — in float32 on the CPU; the Mamba-2 op's
chunk form against its one-token update."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu import metrics
from hetu_tpu.models import (GraniteHybridConfig,
                             granite_hybrid_decode_chunked_graph,
                             granite_hybrid_decode_graph,
                             granite_hybrid_lm_graph)
from hetu_tpu.models.granite_hybrid import param_names
from hetu_tpu.ops import ssd
from hetu_tpu.profiler import HetuProfiler
from hetu_tpu.serving import DecodeEngine, DecodeRouter, InferenceExecutor
from hetu_tpu.serving.decode import _DecodeRequest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import sys  # noqa: E402
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.reference import granite_hybrid_lm as ref  # noqa: E402

with open(os.path.join(ROOT, "tests", "bench_harness", "data",
                       "tiny-granite.json")) as _f:
    #: the tiny preset as the reference reads a configuration
    TINY = json.load(_f)
MAX_LEN = 128
#: float32 sums in another order (a chunk's products against the token by
#: token recurrence, states of size ~10): a logit of size ~1 to 2e-5
TOL = 2e-5
MULTIPLIERS = {"embedding_multiplier": 5.0, "residual_multiplier": 0.6,
               "attention_multiplier": 1.5, "logits_scaling": 3.0}


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def draw(cfg, seed=0):
    """Seeded weights with the matrices twice the spec's spread, so that
    the mixers move the logits and a wrong one shows."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, (shape, mean, std) in ref.param_spec(cfg).items():
        wide = 2 if name.endswith(".weight") else 1
        out[name] = (rng.standard_normal(shape) * std * wide
                     + mean).astype(np.float32)
    return out


@pytest.fixture(scope="module")
def weights():
    return draw(TINY)


def reference(weights, cfg=TINY):
    """``ids -> logits`` of the reference over a sequence padded to
    ``MAX_LEN`` (one program)."""
    w = {k: jnp.asarray(v) for k, v in weights.items()}
    fn = jax.jit(lambda ids: ref.logits(w, ids, cfg))

    def run(ids):
        return np.asarray(fn(jnp.asarray(
            np.pad(ids, (0, MAX_LEN - len(ids))), jnp.int32)))[:len(ids)]
    return run


@pytest.fixture(scope="module")
def ref_logits(weights):
    return reference(weights)


def engine(weights, max_chunk=8, slots=4, cfg=None, **kw):
    cfg = cfg or GraniteHybridConfig.tiny()
    f, lg, st, tok = granite_hybrid_decode_graph(cfg, MAX_LEN)
    chunked = granite_hybrid_decode_chunked_graph(cfg, MAX_LEN) \
        if max_chunk else None
    eng = DecodeEngine(f, lg, st, weights=weights, tokens=tok,
                       max_slots=slots, max_len=MAX_LEN, chunked=chunked,
                       max_chunk=max_chunk or None, **kw)
    eng.reserve(slots, MAX_LEN)
    return eng


def serve(eng, prompts, new, ref_logits=None):
    """Drive ``prompts`` through ``eng`` to the end; returns the token
    streams, every served row's logits per request, and the worst gap
    between a served row's logits and the reference's at that position."""
    reqs = [_DecodeRequest(np.asarray(p, np.int32), new, None, None)
            for p in prompts]
    slot = {id(r): eng.join(r) for r in reqs}
    rows = {id(r): [] for r in reqs}
    while not eng.idle:
        before = {id(r): r.stream.n_tokens for r in reqs}
        eng.step()
        for r in reqs:
            if r.stream.n_tokens != before[id(r)]:
                rows[id(r)].append(eng.last_logits[slot[id(r)]].copy())
    worst = 0.0
    for r in reqs:
        if ref_logits is None:
            continue
        tokens = r.stream.result(0)
        seq = np.concatenate([r.prompt, np.asarray(tokens[:-1], np.int32)])
        want = ref_logits(seq)[len(r.prompt) - 1:]
        worst = max(worst, float(np.abs(np.stack(rows[id(r)]) - want).max()))
        assert tokens == list(want.argmax(-1))
    return ([r.stream.result(0) for r in reqs],
            [np.stack(rows[id(r)]) for r in reqs], worst)


def prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], n) for n in lengths]


# ------------------------------------------------------------- the model

def test_the_whole_model_counts_what_the_configuration_file_says():
    """Every key of the catalog's ``config`` as published and nothing cut;
    what the published file leaves out under ``assumed`` with reasons; 3.19
    B parameters — from the reference's spec, from the program's variables
    and in the file — and no ``lm_head`` among them."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite4-h-micro.json")) as f:
        cfg = json.load(f)
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == []
    assert [i for i, k in enumerate(cfg["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert cfg["layer_types"].count("mamba") == 36
    for key in ("head_dim", "in_proj_order", "gated_norm", "time_step_limit",
                "recurrent_dtype", "decoding", "weights"):
        assert cfg["assumed"][key]
    assert cfg["storage"] == {"weights": "bfloat16", "cache": "bfloat16",
                              "recurrent": "float32"}
    spec = ref.param_spec(cfg)
    count = sum(int(np.prod(shape)) for shape, _, _ in spec.values())
    assert count == cfg["parameters"] == 3191396096            # 3.19 B
    mcfg = GraniteHybridConfig()         # the published sizes are the defaults
    names = param_names(mcfg)
    assert {k: tuple(v[0]) for k, v in spec.items()} == names
    assert not [k for k in names if "lm_head" in k]
    assert [mcfg.layer_kind(i) for i in range(40)] \
        == [ref.layer_kind(cfg, i) for i in range(40)]
    assert mcfg.head_dim == cfg["assumed"]["head_dim"]["value"] == 64
    assert (mcfg.embedding_multiplier, mcfg.residual_multiplier,
            mcfg.attention_multiplier, mcfg.logits_scaling) == (
        cfg["embedding_multiplier"], cfg["residual_multiplier"],
        cfg["attention_multiplier"], cfg["logits_scaling"]) \
        == (12, 0.22, 1 / 64, 8)
    tiny = param_names(GraniteHybridConfig.tiny())
    assert {k: tuple(v[0]) for k, v in ref.param_spec(TINY).items()} == tiny


def test_full_sequence_graph_is_the_reference(weights, ref_logits):
    """77 positions: ten segments of the chunk form (``mamba_chunk_size`` 8
    here) with the state carried, against the recurrence token by token."""
    cfg = GraniteHybridConfig.tiny()
    ids = prompts(1, [77])[0].astype(np.int32)
    feeds, logits = granite_hybrid_lm_graph(cfg, len(ids))
    iex = InferenceExecutor([logits], weights=weights, buckets=(1,))
    got, = iex.infer({feeds["input_ids"]: ids[None]})
    assert np.abs(got - ref_logits(ids)).max() < TOL


@pytest.mark.parametrize("name", list(MULTIPLIERS))
def test_each_multiplier_moves_the_logits(weights, ref_logits, name):
    """A multiplier the graph ignored, or took at a wrong default, would
    leave it on the published reference: with another value the graph is
    the reference AT that value and off the published one."""
    ids = prompts(5, [40])[0].astype(np.int32)
    cfg = GraniteHybridConfig.tiny(**{name: MULTIPLIERS[name]})
    feeds, logits = granite_hybrid_lm_graph(cfg, len(ids))
    got, = InferenceExecutor([logits], weights=weights, buckets=(1,)).infer(
        {feeds["input_ids"]: ids[None]})
    moved = reference(weights, dict(TINY, **{name: MULTIPLIERS[name]}))
    assert np.abs(got - moved(ids)).max() < TOL
    assert np.abs(got - ref_logits(ids)).max() > 1e-3


# ------------------------------------------------------------- the engine

@pytest.mark.parametrize("max_chunk", [0, 8, 32],
                         ids=["one_token", "chunk8", "chunk32"])
def test_engine_serves_the_reference_at_every_position(weights, ref_logits,
                                                       max_chunk):
    """Prompts of 1 to 70 tokens prefilled by chunks up to ``max_chunk`` (0:
    token by token) beside rows that generate and a slot that stays empty
    (``valid`` 0), 20 tokens generated in a mixed batch, then the slots
    seated AGAIN: at every served position the engine's logits are the
    plain forward's."""
    before = metrics.decode_counts().get("decode_state_clears", 0)
    eng = engine(weights, max_chunk)
    *_, worst = serve(eng, prompts(2, [3, 45, 70]), 20, ref_logits)
    assert worst < TOL
    *_, worst = serve(eng, prompts(3, [37, 2, 1, 64]), 12, ref_logits)
    assert worst < TOL
    assert metrics.decode_counts()["decode_state_clears"] - before == 7


@pytest.mark.parametrize("width", [2, 4, 8, 16, 32, 17],
                         ids=lambda w: f"then_{w}")
def test_a_lone_prompt_runs_the_chunked_program_of_every_width(
        weights, ref_logits, width):
    """A lone prompt of 32 + w tokens goes in as a chunk of 32, then one of
    the bucket that covers w — 17 in the bucket of 32, partly valid — the
    Mamba state and the convolution's window carried from one chunked
    program into another, then token by token."""
    metrics.reset_all()
    eng = engine(weights, 32, slots=2)
    *_, worst = serve(eng, prompts(6 + width, [32 + width]), 6, ref_logits)
    assert worst < TOL
    buckets = {1, 32, next(b for b in (2, 4, 8, 16, 32) if b >= width)}
    calls = HetuProfiler.ssd_calls()
    assert calls["ssd_step_calls:4x8x16"] == 3            # one a Mamba layer
    assert calls["ssd_chunk_calls:4x8x16"] == 3 * (len(buckets) - 1)


def test_one_token_path_and_chunked_path_serve_the_same(weights):
    ps = prompts(4, [11, 50, 5])
    slow = serve(engine(weights, 0), ps, 10)[0]
    fast = serve(engine(weights, 16), ps, 10)[0]
    assert slow == fast


def test_a_reused_slot_serves_what_a_fresh_slot_serves(weights, monkeypatch):
    """The second occupant of a slot starts from zeroed recurrent state
    (``decode_state_clears``): its logits are those of the same prompt in
    an engine nobody used.  Without the clearing they are not."""
    first, second = prompts(7, [30, 9])
    fresh = serve(engine(weights, 8, slots=1), [second], 8)[1][0]
    eng = engine(weights, 8, slots=1)
    before = metrics.decode_counts().get("decode_state_clears", 0)
    serve(eng, [first], 5)
    again = serve(eng, [second], 8)[1][0]
    assert metrics.decode_counts()["decode_state_clears"] - before == 2
    assert np.array_equal(again, fresh)
    eng = engine(weights, 8, slots=1)
    serve(eng, [first], 5)
    monkeypatch.setattr(DecodeEngine, "_clear_recurrent",
                        lambda self, slot: None)
    stale = serve(eng, [second], 8)[1][0]
    assert np.abs(stale - fresh).max() > 1e-3


def test_router_serves_it(weights, ref_logits):
    eng = engine(weights, 8)
    prompt = prompts(8, [40])[0].astype(np.int32)
    with DecodeRouter(eng) as router:
        tokens = router.submit(prompt, max_new_tokens=9).result(timeout=120)
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    assert tokens == list(ref_logits(seq)[len(prompt) - 1:].argmax(-1))


def test_state_kinds_scopes_and_counters(weights):
    """``kv`` and ``recurrent`` state only — two slabs an attention layer,
    a window and a matrix state a Mamba layer, float32 whatever the weights'
    type; the layers lower under their scopes, the state update under
    ``ssd.update`` INSIDE ``mix.ssm``; the traces are counted."""
    metrics.reset_all()
    cfg = GraniteHybridConfig.tiny(param_dtype=jnp.bfloat16,
                                   cache_dtype=jnp.bfloat16)
    eng = engine(weights, 4, slots=2, cfg=cfg)
    kinds = eng._kinds
    assert [sum(k == kind for k in kinds.values())
            for kind in ("kv", "recurrent")] == [2, 6] \
        and len(kinds) == 8
    assert eng.caches["ssd_0"].shape == (2, 4, 8, 16)
    assert eng.caches["conv_0"].shape == (2, 3, 4 * 8 + 2 * 2 * 16)
    by_kind = {}
    for name, c in eng.caches.items():
        by_kind.setdefault(kinds[name], set()).add(str(c.dtype))
    assert by_kind == {"kv": {"bfloat16"}, "recurrent": {"float32"}}
    assert {str(v.dtype) for v in eng.iex.params.values()} == {"bfloat16"}
    feeds = {eng._fk["input_ids"]: np.zeros((2, 1), np.int32),
             eng._fk["positions"]: np.zeros(2, np.int32)}
    text = jax.jit(eng._program(eng.iex, eng._fk)).lower(
        eng.iex.params, (feeds, tuple(eng.caches.values())),
        np.zeros(2, np.int32)).as_text(debug_info=True)
    for scope in ("mix.gqa", "mix.ssm", "mix.ssm/ssd.update", "mlp",
                  "lm_head"):
        assert f"/{scope}/" in text, scope
    assert "mix.gqa/ssd.update" not in text
    assert HetuProfiler.ssd_calls() == {"ssd_step_calls:4x8x16": 3}
    # (the CPU has no bfloat16 product into float32: float32 serves)
    eng = engine(weights, 4, slots=2)
    tokens, *_ = serve(eng, prompts(11, [6]), 5)
    assert len(tokens[0]) == 5
    c = metrics.decode_counts()
    assert c["decode_state_bytes_recurrent_hw"] \
        == eng.state_bytes()["recurrent"] \
        == 2 * 3 * 4 * (4 * 8 * 16 + 3 * 96)


@pytest.mark.parametrize("chunk", [1, 3])
def test_gqa_reads_its_slabs_with_the_models_own_scale(chunk):
    """4 query heads a key head of 64 (two key rows a 128-lane slab row),
    the scores times ``attention_multiplier`` and not ``1/√D``, against
    attention written out."""
    from hetu_tpu.ops import kda
    from hetu_tpu.ops.attention import kv_slab_from_rows
    rng = np.random.default_rng(4)
    b, g, r, d, length, scale = 2, 2, 4, 64, 32, 1 / 64
    keys = rng.standard_normal((b, g, length, d)).astype(np.float32)
    vals = rng.standard_normal((b, g, length, d)).astype(np.float32)
    q = 4 * rng.standard_normal((b * chunk, g * r * d)).astype(np.float32)
    at = np.array([4, 17], np.int32)
    got = kda._gqa_attention_kv(
        None, q, kv_slab_from_rows(jnp.asarray(keys), 128),
        kv_slab_from_rows(jnp.asarray(vals), 128), at,
        np.zeros((b, chunk), np.int32), head_dim=d, scale=scale)
    for i in range(b):
        for j in range(chunk):
            n = at[i] + j + 1
            for h in range(g * r):
                qv = q[i * chunk + j, h * d:(h + 1) * d]
                s = keys[i, h // r, :n] @ qv * scale
                p = np.exp(s - s.max())
                want = (p / p.sum()) @ vals[i, h // r, :n]
                assert np.abs(got[i * chunk + j, h * d:(h + 1) * d]
                              - want).max() < 1e-5
    unscaled = kda._gqa_attention_kv(
        None, q, kv_slab_from_rows(jnp.asarray(keys), 128),
        kv_slab_from_rows(jnp.asarray(vals), 128), at,
        np.zeros((b, chunk), np.int32), head_dim=d)
    assert np.abs(np.asarray(unscaled) - np.asarray(got)).max() > 1e-2


def test_one_token_kernel_reads_the_new_geometry(monkeypatch):
    """The kernel's newest caller: 8 key heads of 64, 4 query heads each —
    4 score rows x 2 key rows a slab row, every head of a slot in one
    program — in interpret mode, bfloat16 rows as stored, against the
    ``jnp`` path over the same values."""
    import functools
    from hetu_tpu.ops import kda
    from hetu_tpu.ops.attention import kv_slab_from_rows
    from hetu_tpu.ops.pallas import decode_attention as da
    rng = np.random.default_rng(5)
    lengths = np.array([1, 63, 64, 65, 256, 200, 17], np.int32)
    b, g, r, d, rows = len(lengths), 8, 4, 64, 256
    dead = np.arange(rows)[None, :] >= lengths[:, None]
    slabs = []
    for fill in (3.0e4, -3.0e4):
        t = rng.standard_normal((b, g, rows, d)).astype(np.float32)
        slabs.append(kv_slab_from_rows(jnp.asarray(np.where(
            dead[:, None, :, None], fill, t), jnp.bfloat16), 128))
    # queries whose SCALED values are bfloat16 values: the kernel takes its
    # score rows in the slabs' type
    q = jnp.asarray(rng.standard_normal((b, g * r * d)), jnp.bfloat16) \
        .astype(jnp.float32) * np.float32(64.0)
    ids = jnp.zeros((b, 1), jnp.int32)
    want = kda._gqa_attention_kv(
        None, q, *(t.astype(jnp.float32) for t in slabs), lengths - 1, ids,
        head_dim=d, scale=1 / 64)
    # the cell's call: 8 key heads, 768 positions = 384 slab rows, bfloat16
    assert da.geometry(8, 384, 128, 2) == (8, 384)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, interpret=True))
    metrics.reset_all()
    got = kda._gqa_attention_kv(None, q, *slabs, lengths - 1, ids,
                                head_dim=d, scale=1 / 64)
    assert metrics.decode_attn_call_counts() == {"8x128": 1}
    assert got.shape == want.shape == (b, g * r * d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.5


# ---------------------------------------------------------------- the op

def _ssd_inputs(rng, b, chunk, h=4, p=8, n=16, g=2):
    e = h * p
    return dict(
        xbc=rng.standard_normal((b * chunk, e + 2 * g * n)).astype(
            np.float32),
        dt=rng.standard_normal((b * chunk, h)).astype(np.float32) - 1.0,
        dt_bias=rng.standard_normal(h).astype(np.float32) - 2.0,
        a_log=rng.uniform(0.0, 2.5, h).astype(np.float32),
        d=rng.standard_normal(h).astype(np.float32),
        state=rng.standard_normal((b, h, p, n)).astype(np.float32))


@pytest.mark.parametrize("valid", [None, [32, 17, 0, 1], [5, 0, 32, 31]],
                         ids=["all", "ragged", "ragged_too"])
def test_the_chunk_form_is_c_one_token_updates(valid):
    """One call of the chunk form against ``C`` calls of the one-token
    update, row by row: the outputs of the columns consumed and the state
    left.  A row advances by exactly ``valid`` tokens; ``valid`` 0 leaves
    its state bit for bit."""
    b, chunk = 4, 32
    t = _ssd_inputs(np.random.default_rng(0), b, chunk)
    ids = jnp.zeros((b, chunk), jnp.int32)
    args = [jnp.asarray(t[k]) for k in ("dt_bias", "a_log", "d")]
    count = np.full(b, chunk) if valid is None else np.asarray(valid)
    y, new = ssd._ssd_chunk(
        None, t["xbc"], t["dt"], *args, jnp.asarray(t["state"]), ids,
        *(() if valid is None else (jnp.asarray(valid, jnp.int32),)),
        heads=4, groups=2)
    y = np.asarray(y).reshape(b, chunk, -1)
    state, outs = jnp.asarray(t["state"]), []
    one = jnp.zeros((b, 1), jnp.int32)
    for j in range(chunk):
        col = [t[k].reshape(b, chunk, -1)[:, j] for k in ("xbc", "dt")]
        step, state = ssd._ssd_chunk(
            None, *col, *args, state, one,
            jnp.asarray(j < count, jnp.int32), heads=4, groups=2)
        outs.append(np.asarray(step))
    outs = np.stack(outs, axis=1)
    for r in np.flatnonzero(count):
        assert np.abs(y[r, :count[r]] - outs[r, :count[r]]).max() < 1e-4
    assert np.abs(np.asarray(new) - np.asarray(state)).max() < 1e-4
    for r in np.flatnonzero(count == 0):
        assert np.array_equal(np.asarray(new)[r], t["state"][r])


def test_segments_carry_the_state():
    """A chunk longer than ``segment`` is the chunk form run segment after
    segment: the same as one segment over all of it."""
    t = _ssd_inputs(np.random.default_rng(1), 2, 21)
    ids = jnp.zeros((2, 21), jnp.int32)
    args = [jnp.asarray(t[k]) for k in ("xbc", "dt", "dt_bias", "a_log", "d",
                                        "state")]
    whole = ssd._ssd_chunk(None, *args, ids, heads=4, groups=2, segment=32)
    cut = ssd._ssd_chunk(None, *args, ids, heads=4, groups=2, segment=8)
    for a, b in zip(whole, cut):
        assert np.abs(np.asarray(a) - np.asarray(b)).max() < 1e-4


def test_the_chunked_program_holds_no_loop_over_the_state(weights):
    """No ``while`` of the chunked step's program carries a Mamba state: a
    chunk costs products, not a scan (ROADMAP.md D23).  (Off the chip the
    KV slabs' append walks the batch: the one kind of loop there is.)"""
    eng = engine(weights, 8, slots=2)
    feeds = {eng._cfk["input_ids"]: np.zeros((2, 8), np.int32),
             eng._cfk["positions"]: np.zeros(2, np.int32),
             eng._cfk["valid"]: np.zeros(2, np.int32)}
    text = jax.jit(eng._program(eng.ciex, eng._cfk)).lower(
        eng.ciex.params, (feeds, tuple(eng.caches.values())),
        np.zeros(2, np.int32)).as_text()
    loops = [line for line in text.splitlines() if "stablehlo.while" in line]
    assert len(loops) == 2 and "tensor<2x4x8x16xf32>" in text   # K and V
    assert not [line for line in loops if "2x4x8x16xf32" in line]
