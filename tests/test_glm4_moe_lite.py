"""GLM-4.7-Flash (latent attention over one compressed cache row a token, a
leading dense layer, sigmoid top-4 of 64 experts scaled by 1.8 with a shared
one) through ``DecodeEngine`` as one chip's share, against the plain
full-sequence reference of ``benchmarks/reference/glm4_moe_lite_lm.py`` in
float32 on the CPU."""
import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu import metrics
from hetu_tpu.models import (Glm4MoeLiteConfig,
                             glm4_moe_lite_decode_chunked_graph,
                             glm4_moe_lite_decode_graph,
                             glm4_moe_lite_lm_graph)
from hetu_tpu.models.glm4_moe_lite import param_names
from hetu_tpu.ops import mla, moe
from hetu_tpu.serving import DecodeEngine, DecodeRouter, InferenceExecutor
from hetu_tpu.serving.decode import _DecodeRequest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import sys  # noqa: E402
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.reference import glm4_moe_lite_lm as ref  # noqa: E402

with open(os.path.join(ROOT, "tests", "bench_harness", "data",
                       "tiny-glm.json")) as _f:
    #: the tiny preset as the reference reads a configuration
    TINY = json.load(_f)
TINY["assumed"] = dict(TINY["assumed"], initializer_range=0.02)
MAX_LEN = 64
#: float32 sums in another order: a logit of size ~1 to 1e-5
TOL = 1e-5
CHOICES = "moe_choices"


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _draw(cfg, seed=0):
    """Seeded weights with the matrices three times the spec's spread, so
    that attention and the experts move the logits (to a size of ~1) and a
    wrong one shows."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, (shape, mean, std) in ref.param_spec(cfg).items():
        wide = name.endswith(".weight")
        out[name] = (rng.standard_normal(shape) * std * (3 if wide else 1)
                     + mean).astype(np.float32)
    return out


def _program_names(w):
    """The reference's leaves under the program's stem."""
    return {"glm" + k[k.index("."):]: v for k, v in w.items()}


@pytest.fixture(scope="module")
def ref_weights():
    return _draw(TINY)


@pytest.fixture(scope="module")
def weights(ref_weights):
    return _program_names(ref_weights)


@pytest.fixture(scope="module")
def ref_logits(ref_weights):
    """``ids -> (logits, choices of the expert layers)`` of the reference
    routing for itself."""
    w = {k: jnp.asarray(v) for k, v in ref_weights.items()}
    fn = jax.jit(lambda ids: ref.logits(w, ids, TINY))

    def run(ids):
        logits, info = fn(jnp.asarray(ids, jnp.int32))
        chosen = np.asarray(info["choices"])
        assert (chosen[:, :1] == -1).all()          # the dense layer
        return np.asarray(logits), chosen[:, 1:]
    return run


def _engine(weights, max_chunk=8, slots=4, cfg=None, **kw):
    cfg = cfg or Glm4MoeLiteConfig.tiny()
    f, lg, st, tok, ch = glm4_moe_lite_decode_graph(cfg, MAX_LEN)
    chunked = None
    if max_chunk:
        cf, cl, cs, ctok, cch = glm4_moe_lite_decode_chunked_graph(
            cfg, MAX_LEN)
        chunked = (cf, cl, cs, ctok, {CHOICES: cch})
    return DecodeEngine(f, lg, st, weights=weights, tokens=tok,
                        aux={CHOICES: ch},
                        aux_fold={CHOICES: cfg.choice_counters()},
                        max_slots=slots, max_len=MAX_LEN, chunked=chunked,
                        max_chunk=max_chunk or None, **kw)


def _serve(eng, prompts, new, ref_logits=None):
    """Drive ``prompts`` through ``eng`` to the end; returns the token
    streams and the worst gap between a served row's logits and the
    reference's at that position.  The expert ids each stream was handed
    are the reference's own at every consumed position."""
    reqs = [_DecodeRequest(np.asarray(p, np.int32), new, None, None)
            for p in prompts]
    slot = {id(r): eng.join(r) for r in reqs}
    worst = 0.0
    while not eng.idle:
        before = {id(r): r.stream.n_tokens for r in reqs}
        eng.step()
        if ref_logits is None:
            continue
        got = eng.last_logits
        for r in reqs:
            n = r.stream.n_tokens
            if n == before[id(r)]:
                continue
            toks = r.stream.partial()
            want, chosen = ref_logits(np.concatenate(
                [r.prompt, np.asarray(toks[:n - 1], np.int32)]))
            worst = max(worst,
                        float(np.abs(got[slot[id(r)]] - want[-1]).max()))
            assert int(np.argmax(want[-1])) == toks[n - 1]
            assert np.array_equal(np.sort(r.stream.aux(CHOICES), -1),
                                  np.sort(chosen, -1))
    return [r.stream.result(0) for r in reqs], worst


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], n) for n in lengths]


# ------------------------------------------------------------- the model

def _cell_config():
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "glm47-flash.json")) as f:
        return json.load(f)


def test_the_share_counts_what_the_configuration_file_says():
    """The cell's configuration: every published key as published but the
    two the share reduces, the published values beside them, and the
    parameter count of the share from the reference's spec, from the
    program's variables and in the file."""
    cfg = _cell_config()
    published = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_shared_experts": 1, "routed_scaling_factor": 1.8,
        "num_experts_per_tok": 4, "first_k_dense_replace": 1,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": (13, 47), "n_routed_experts": (8, 64)}
    assert cfg["reduced"] == list(cut)
    flat = {k: v for group in cfg["published"].values()
            for k, v in group.items()}
    assert {k: (cfg[k], flat[k]) for k in cut} == cut
    assert cfg["held_experts"] == {"first": 24, "count": 8, "of": 64}
    assert "8 chips share each layer" in cfg["deployment"]
    assert "all-to-alls" in cfg["deployment"]
    assert "num_nextn_predict_layers" in cfg["assumed"]["unused"]
    spec = ref.param_spec(cfg)
    count = sum(int(np.prod(shape)) for shape, _, _ in spec.values())
    assert count == cfg["parameters"] == 2001017856
    mcfg = Glm4MoeLiteConfig(num_hidden_layers=13, held=(24, 8))
    assert _program_names({k: tuple(v[0]) for k, v in spec.items()}) \
        == param_names(mcfg)
    kinds = [mcfg.layer_kind(i) for i in range(13)]
    assert kinds == ["dense"] + 12 * ["moe"] \
        == [ref.layer_kind(cfg, i) for i in range(13)]
    # the cache row: 576 published values in 640 stored lanes
    assert mcfg.latent_lanes == 640 and mla.latent_lanes(16, 8) == 128


def test_published_sizes_give_the_published_parameter_count():
    """30B-A3B from the equations: the uncut model's spec counts 29.94 B,
    of which 3.26 B work on a token between the embedding and the head
    (the published A3B)."""
    cfg = _cell_config()
    flat = {k: v for group in cfg["published"].values()
            for k, v in group.items()}
    whole = dict(cfg, **flat, held_experts={"first": 0, "count": 64,
                                            "of": 64})
    spec = ref.param_spec(whole)
    size = {k: int(np.prod(shape)) for k, (shape, _, _) in spec.items()}
    total = sum(size.values())
    assert abs(total / 1e9 - 29.94) < 0.01
    experts = sum(v for k, v in size.items() if ".moe.experts." in k)
    ends = size["solar.embed"] + size["solar.lm_head.weight"]
    active = total - ends - experts + experts * 4 // 64
    assert abs(active / 1e9 - 3.26) < 0.01
    layer = sum(v for k, v in size.items() if k.startswith("solar.l1."))
    assert abs(layer / 1e6 - 635.3) < 0.1


def test_full_sequence_graph_is_the_reference(weights, ref_logits):
    """The MATERIALISED form as the program writes it."""
    cfg = Glm4MoeLiteConfig.tiny()
    ids = _prompts(1, [29])[0].astype(np.int32)
    feeds, logits, choices = glm4_moe_lite_lm_graph(cfg, len(ids))
    iex = InferenceExecutor([logits, choices], weights=weights, buckets=(1,))
    got, chosen = iex.infer({feeds["input_ids"]: ids[None]})
    want, own = ref_logits(ids)
    assert np.abs(got - want).max() < TOL
    assert chosen.shape == (1, 29, 4, 4)          # the 4 expert layers
    assert np.array_equal(np.sort(chosen[0], -1), np.sort(own, -1))


# ---------------------------------------------------------------- the ops

def _latent_case(seed, b, chunk, at, h=4, nope=12, rope=8, v=16, rank=16,
                 length=32):
    rng = np.random.default_rng(seed)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    sizes = dict(heads=h, nope=nope, rank=rank)
    return (draw(b * chunk, h * (nope + rope)), draw(b, 1, length, 128),
            draw(rank, h * (nope + v)), np.asarray(at, np.int32),
            np.zeros((b, chunk), np.int32), sizes)


@pytest.mark.parametrize("chunk", [1, 5])
def test_absorbed_is_materialised_on_one_sequence(chunk):
    """The two written forms of one layer: the absorbed read of a cache
    that holds a sequence's rows, at its last ``chunk`` positions, is the
    materialised attention of the whole sequence at those positions."""
    t = 23
    q, slab, w, _, _, sizes = _latent_case(2, 1, t, [0], length=t)
    slab = slab.at[..., 24:].set(0.0)              # [c; k_rope; 0]
    whole = mla._mla_attention(None, q, slab, w, np.zeros((1, t), np.int32),
                               **sizes)
    got = mla._mla_attention_kv(
        None, q[t - chunk:], slab, w, np.array([t - chunk], np.int32),
        np.zeros((1, chunk), np.int32), **sizes)
    assert got.shape == (chunk, 4 * 16)
    assert np.abs(got - whole[t - chunk:]).max() < TOL
    assert float(jnp.abs(whole).max()) > 0.5


def test_absorbed_read_is_attention_written_out():
    """Against numpy, per head: scores ``q'_h . c + q_rope . k_rope`` over
    the rows below each query's position, the value the row's first
    ``rank`` lanes, ``W_uv`` after the sum."""
    b, chunk, h, nope, rope, v, rank = 2, 3, 4, 12, 8, 16, 16
    q, slab, w, at, ids, sizes = _latent_case(3, b, chunk, [4, 17])
    got = np.asarray(mla._mla_attention_kv(None, q, slab, w, at, ids,
                                           **sizes))
    q, slab = np.asarray(q).reshape(b, chunk, h, -1), np.asarray(slab)
    w = np.asarray(w).reshape(rank, h, nope + v)
    for i in range(b):
        for j in range(chunk):
            n = at[i] + j + 1
            c, kr = slab[i, 0, :n, :rank], slab[i, 0, :n, rank:rank + rope]
            for k in range(h):
                s = (c @ (w[:, k, :nope] @ q[i, j, k, :nope])
                     + kr @ q[i, j, k, nope:]) / np.sqrt(nope + rope)
                p = np.exp(s - s.max())
                want = ((p / p.sum()) @ c) @ w[:, k, nope:]
                assert np.abs(got[i * chunk + j, k * v:(k + 1) * v]
                              - want).max() < TOL


def test_whole_slab_read_goes_group_by_group_above_its_budget(monkeypatch):
    q, slab, w, at, ids, sizes = _latent_case(4, 8, 3, np.arange(8) + 2)
    want = mla._mla_attention_kv(None, q, slab, w, at, ids, **sizes)
    monkeypatch.setattr(mla, "_SCORE_BYTES", 2 * 3 * 4 * 32 * 4)
    got = jax.jit(functools.partial(mla._mla_attention_kv, None, **sizes))(
        q, slab, w, at, ids)
    assert np.abs(got - want).max() < TOL


def test_rotation_turns_pairs_half_a_head_apart_by_position():
    x = jnp.asarray(np.random.default_rng(5).standard_normal((3, 8)),
                    jnp.float32)
    at = np.array([0, 1, 7])
    got = np.asarray(mla._rotate(x, at, 1e6))
    assert np.abs(got[0] - x[0]).max() == 0.0
    for t, row, out in zip(at, np.asarray(x), got):
        for i in range(4):
            ang = t * 1e6 ** (-i / 4)
            assert abs(out[i] - (row[i] * np.cos(ang)
                                 - row[i + 4] * np.sin(ang))) < 1e-5
            assert abs(out[i + 4] - (row[i + 4] * np.cos(ang)
                                     + row[i] * np.sin(ang))) < 1e-5
    # and the program's is the reference's, written apart
    assert np.abs(got - ref._rotate(x, at, 1e6)).max() < 1e-6
    # a head's leading dims pass through, its last rope_dim turn
    flat = mla._rope(None, jnp.tile(x[:1], (2, 2)), np.array([3]),
                     np.zeros((1, 2), np.int32), theta=1e6, head_dim=8,
                     rope_dim=4)
    assert np.array_equal(flat[:, :4], jnp.tile(x[:1, :4], (2, 1)))
    assert np.abs(flat[1, 4:8] - mla._rotate(x[0, 4:], 4, 1e6)).max() < 1e-6


@pytest.mark.parametrize("blocks", ["one_block", "blocks"])
def test_one_token_kernel_reads_the_latent_rows(blocks, monkeypatch):
    """The kernel's fourth caller, its latent mode: ``_mla_attention_kv``
    at ``C = 1`` hands 20 score rows over ONE slab of 640-lane rows to the
    one-token kernel (interpret mode) — bfloat16 rows as stored, rows past
    each length filled with garbage, lengths 1, on both sides of a block
    edge and full — and reads what its own ``jnp`` path reads over the same
    values; the value is lanes 0..511 of the key block."""
    from hetu_tpu.ops.pallas import decode_attention as da
    rng = np.random.default_rng(6)
    lengths = np.array([1, 63, 64, 65, 256, 200], np.int32)
    b, h, nope, rope, v, rank, lanes, rows = len(lengths), 20, 24, 64, 32, \
        512, 640, 256
    dead = np.arange(rows)[None, :] >= lengths[:, None]
    slab = rng.standard_normal((b, 1, rows, lanes)).astype(np.float32)
    slab[..., rank + rope:] = 0.0
    slab = jnp.asarray(np.where(dead[:, None, :, None], 3.0e4, slab),
                       jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((rank, h * (nope + v))) * 0.05,
                    jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, h * (nope + rope))),
                    jnp.float32)
    ids = jnp.zeros((b, 1), jnp.int32)
    sizes = dict(heads=h, nope=nope, rank=rank)
    want = mla._mla_attention_kv(None, q, slab.astype(jnp.float32), w,
                                 lengths - 1, ids, **sizes)
    # the cell's call: one head of 640-lane rows, 4096 of them, bfloat16
    assert da.geometry(1, 4096, 640, 2, 1) == (1, 2048)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, interpret=True))
    if blocks == "blocks":
        monkeypatch.setattr(da, "BLOCK_BYTES", 64 * lanes * 2)
        monkeypatch.setattr(da, "MIN_BLOCK_ROWS", 8)
    metrics.reset_all()
    got = mla._mla_attention_kv(None, q, slab, w, lengths - 1, ids, **sizes)
    assert metrics.decode_attn_call_counts() == {
        "1x64" if blocks == "blocks" else "1x256": 1}
    assert got.shape == want.shape == (b, h * v)
    # the kernel takes its score rows in the slab's type: the absorbed
    # query is rounded to bfloat16 on its way in, the float32 side is not
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-2)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    with pytest.raises(ValueError, match="latent mode"):
        da.decode_attention(jnp.zeros((1, 1, 8, 128)), jnp.zeros(
            (1, 1, 16, 128)), None, jnp.ones((1,), jnp.int32), pack=2)


def test_latent_kernel_is_the_plain_softmax_in_float32():
    """The latent mode alone, float32, against attention written out: the
    key block's first ``v_lanes`` lanes are the value."""
    from hetu_tpu.ops.pallas.decode_attention import decode_attention
    rng = np.random.default_rng(7)
    lengths = np.array([1, 8, 9, 32], np.int32)
    b, n, lanes, v_lanes, rows = 4, 5, 256, 128, 32
    slab = jnp.asarray(rng.standard_normal((b, 1, rows, lanes)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, 1, n, lanes)) * 0.2, jnp.float32)
    got = np.asarray(decode_attention(q, slab, None, lengths,
                                      v_lanes=v_lanes, interpret=True))
    assert got.shape == (b, 1, n, v_lanes)
    for i in range(b):
        keys = np.asarray(slab)[i, 0, :lengths[i]]
        s = np.asarray(q)[i, 0] @ keys.T
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ keys[:, :v_lanes]
        assert np.abs(got[i, 0] - want).max() < 2e-5


def test_router_scales_the_normalised_weights():
    rng = np.random.default_rng(8)
    y = jnp.asarray(rng.standard_normal((7, 32)), jnp.float32)
    w_r = jnp.asarray(rng.standard_normal((32, 64)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(64) * 0.05, jnp.float32)
    ids, plain = moe._moe_route(None, y, w_r, bias, top_k=4)
    ids2, scaled = moe._moe_route(None, y, w_r, bias, top_k=4, scale=1.8)
    assert np.array_equal(ids, ids2)
    assert np.abs(scaled - 1.8 * plain).max() < 1e-6
    assert np.abs(np.asarray(scaled).sum(-1) - 1.8).max() < 1e-5


# --------------------------------------------------------------- the share

WHOLE = dict(TINY, held_experts={"first": 0, "count": 64, "of": 64})


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """What ties the share to the model: attention is replicated — every
    share computes the uncut layer's — and the routed parts of the 8 expert
    shares with the shared expert counted ONCE add up to the uncut
    reference's layer."""
    whole = {k: jnp.asarray(v) for k, v in ref.layer_params(
        _draw(WHOLE, seed=3), 1).items()}
    x = jnp.asarray(np.random.default_rng(4).standard_normal((19, 32)),
                    jnp.float32)
    want, _, _ = ref.layer("moe", whole, x, {}, WHOLE)

    def share(s):
        cfg = dict(TINY, held_experts={"first": 8 * s, "count": 8, "of": 64})
        w = dict(whole)
        for leaf in ("moe.experts.gate_up", "moe.experts.down"):
            w[leaf] = whole[leaf][8 * s:8 * (s + 1)]
        return cfg, w

    shares = [share(s) for s in range(8)]
    y = ref._rms(x, whole["ln1.scale"], 1e-5)
    mixed = ref.mixer(whole, y, WHOLE)
    for cfg, w in shares[:2]:
        assert np.array_equal(ref.mixer(w, y, cfg), mixed)
    h = x + mixed
    y2 = ref._rms(h, whole["ln2.scale"], 1e-5)
    routed = 0.0
    for cfg, w in shares:
        out, info = ref.moe(w, y2, cfg)
        routed = routed + (out - info["shared"])
    assert np.abs(h + routed + info["shared"] - want).max() < TOL
    # and one share alone is NOT the layer: the parts matter
    assert np.abs(h + out - want).max() > 10 * TOL


# ------------------------------------------------------------ the engine

@pytest.mark.parametrize("max_chunk", [0, 2, 4, 8, 16, 32])
def test_engine_serves_the_reference_at_every_chunk_width(
        weights, ref_logits, max_chunk):
    """Prompts of 1 to 37 tokens prefilled by chunks up to ``max_chunk``
    (0: token by token), 14 tokens generated in a mixed batch, then the
    slots seated AGAIN (a ``join`` into a used slot, whose latent rows are
    read by position only): at every served position the engine's logits
    are the plain forward's, and the expert ids handed on with the tokens
    the reference's own."""
    eng = _engine(weights, max_chunk)
    _, worst = _serve(eng, _prompts(2, [3, 13, 37, 1]), 14, ref_logits)
    assert worst < TOL
    _, worst = _serve(eng, _prompts(3, [17, 2]), 12, ref_logits)
    assert worst < TOL


def test_one_token_path_and_chunked_path_serve_the_same(weights):
    prompts = _prompts(4, [11, 30, 5])
    slow, _ = _serve(_engine(weights, 0), prompts, 10)
    fast, _ = _serve(_engine(weights, 16), prompts, 10)
    assert slow == fast


def test_reseated_slot_serves_what_a_fresh_engine_serves(weights):
    eng = _engine(weights, 8, slots=1)
    _serve(eng, _prompts(5, [33]), 20)
    again, _ = _serve(eng, _prompts(6, [9]), 12)
    fresh, _ = _serve(_engine(weights, 8, slots=1), _prompts(6, [9]), 12)
    assert again == fresh


def test_router_serves_it_through_the_front_door(weights, ref_logits):
    """``DecodeRouter.submit``: the tokens are the reference's, and the
    stream holds the expert ids of every consumed position, of the four
    expert layers."""
    eng = _engine(weights, 8)
    prompt = _prompts(8, [12])[0].astype(np.int32)
    with DecodeRouter(eng) as router:
        stream = router.submit(prompt, max_new_tokens=11)
        tokens = stream.result(timeout=60)
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    want, chosen = ref_logits(seq)
    assert tokens == list(want[len(prompt) - 1:].argmax(-1))
    got = stream.aux(CHOICES)
    assert got.shape == (len(seq), 4, 4) and got.dtype == np.int16
    assert np.array_equal(np.sort(got, -1), np.sort(chosen, -1))


@pytest.mark.parametrize("chunk", [0, 8], ids=["one_token", "chunked"])
def test_router_one_step_ahead_emits_the_serial_loops_streams(weights, chunk):
    """Step n+1 launched before step n is collected: the same token streams
    as a loop of ``engine.step()``, bit for bit, and each stream's slices
    of chosen expert ids still line up with the tokens it consumed; seven
    requests through three slots."""
    from decode_ahead import assert_same_streams
    specs = [(p.astype(np.int32), n, None) for p, n in zip(
        _prompts(21, [9, 2, 17, 5, 1, 12, 3]), [6, 9, 1, 12, 4, 7, 10])]
    serial, ahead = assert_same_streams(
        lambda: _engine(weights, chunk, slots=3), specs, aux=(CHOICES,))
    assert ahead["decode_steps"] >= serial["decode_steps"]
    assert ahead["moe_assignments"] \
        == ahead["decode_padded_row_tokens"] * 4 * 4    # layers x k
    assert ahead["decode_launches_ahead"] > 0


def test_engine_seats_latent_rows_from_a_prefix_store(weights):
    """A prefix store beside the chosen expert ids (refused until PR 42):
    this graph keeps KV state only, so a prompt that shares only its first
    12 tokens with a stored one is seated with those 12 latent rows, is
    served the cold engine's tokens, and its expert ids start at position
    12."""
    from hetu_tpu.serving import PrefixKVStore
    eng = _engine(weights, 8, prefix_store=PrefixKVStore())
    first = _prompts(30, [20])[0]
    _serve(eng, [first], 3)
    other = np.concatenate([first[:12], _prompts(31, [9])[0]])
    reqs = []
    for e in (eng, _engine(weights, 8)):
        req = _DecodeRequest(other.astype(np.int32), 8, None, None)
        e.join(req)
        while not e.idle:
            e.step()
        reqs.append(req.stream)
    seated, cold = reqs
    assert seated.result(0) == cold.result(0)
    assert (seated.aux_from, cold.aux_from) == (12, 0)
    assert np.array_equal(seated.aux(CHOICES), cold.aux(CHOICES)[12:])


def test_counters_fold_the_choices_and_count_the_live_rows(weights):
    """``moe_*`` per step from the fetched ids of the FOUR expert layers
    (the dense layer chooses nothing); ``decode_kv_rows_live`` the rows the
    stepping sequences hold once the step has appended, exactly."""
    metrics.reset_decode_counts()
    eng = _engine(weights, 0, slots=4)
    eng.reserve(4, MAX_LEN)
    lengths, new = [5, 9, 2, 7], 6
    _serve(eng, _prompts(12, lengths), new)
    c = metrics.decode_counts()
    steps = c["decode_steps"]
    assert c["moe_assignments"] == steps * 4 * 4 * 4
    assert 0 < c["moe_assignments_held"] < c["moe_assignments"] / 3
    assert 0 < c["moe_experts_touched"] <= min(
        c["moe_assignments_held"], steps * 4 * 8)
    # token by token, a sequence of p prompt tokens and n new ones makes
    # p + n - 1 steps and holds 1, 2, ... rows after each
    assert c["decode_kv_rows_live"] == sum(
        (p + new - 1) * (p + new) // 2 for p in lengths)
    assert c["decode_kv_rows_live"] < c["decode_kv_rows_read"] \
        == c["decode_kv_rows_held"] == steps * 4 * MAX_LEN
    assert metrics.moe_call_counts().get("8of64:top4:ragged", 0) >= 4


def test_chunked_steps_count_the_rows_they_append(weights):
    metrics.reset_decode_counts()
    eng = _engine(weights, 8, slots=1)
    _serve(eng, _prompts(13, [20]), 1)
    c = metrics.decode_counts()
    # 20 prompt tokens by chunks of 8, 8, 4: 8 + 16 + 20 rows held
    assert (c["decode_steps"], c["decode_kv_rows_live"]) == (3, 44)


def test_the_latent_cache_is_one_kv_slab_of_one_head_a_layer(weights):
    metrics.reset_decode_counts()
    eng = _engine(weights, 8)
    assert set(eng._kinds.values()) == {"kv"}
    assert eng.cache_names == [f"latent_cache_{i}" for i in range(5)]
    assert (eng._heads, eng._lanes, eng._pack) == (1, 128, 1)
    eng.reserve(4, MAX_LEN)
    by = eng.state_bytes()
    assert by == {"kv": 4 * 5 * MAX_LEN * 128 * 4}
    assert metrics.decode_counts()["decode_state_bytes_kv_hw"] == by["kv"]
    assert all(c.shape == (4, 1, MAX_LEN, 128) for c in eng.caches.values())


def test_weights_are_stored_in_the_type_the_variables_declare(weights):
    cfg = Glm4MoeLiteConfig.tiny(param_dtype=jnp.bfloat16,
                                 cache_dtype=jnp.bfloat16)
    eng = _engine(weights, 4, slots=2, cfg=cfg)
    assert {str(v.dtype) for v in eng.iex.params.values()} == {"bfloat16"}
    assert {str(c.dtype) for c in eng.caches.values()} == {"bfloat16"}
    tokens, _ = _serve(eng, _prompts(11, [6]), 5)
    assert len(tokens[0]) == 5


def test_layers_lower_under_their_scopes(weights):
    eng = _engine(weights, 0)
    feeds = {eng._fk["input_ids"]: np.zeros((1, 1), np.int32),
             eng._fk["positions"]: np.zeros(1, np.int32)}
    text = jax.jit(eng._program(eng.iex, eng._fk)).lower(
        eng.iex.params, (feeds, tuple(eng.caches.values())),
        np.zeros(1, np.int32)).as_text(debug_info=True)
    for scope in ("mix.mla", "mlp", "moe.route", "moe.experts",
                  "moe.shared", "lm_head"):
        assert f"/{scope}/" in text, scope


def test_solar_and_glm_share_one_expert_block():
    """``models/common.py`` holds what both graphs share; the solar graph's
    parameter names are what they were."""
    from hetu_tpu.models import SolarOpen2Config, common, solar_open2
    assert solar_open2.moe_block is common.moe_block
    names = list(solar_open2.param_names(SolarOpen2Config.tiny()))
    assert names[:3] == ["solar.embed", "solar.l0.ln1.scale",
                         "solar.l0.attn.qkvg.weight"]
    assert names[4:10] == [
        "solar.l0.ln2.scale", "solar.l0.moe.router.weight",
        "solar.l0.moe.router.bias", "solar.l0.moe.experts.gate_up",
        "solar.l0.moe.experts.down", "solar.l0.moe.shared.gate_up.weight"]
