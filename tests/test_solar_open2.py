"""Solar-Open2 (KDA 3:1 with gated NoPE GQA, dropless experts with a shared
one) through ``DecodeEngine`` as one chip's share, against the plain
full-sequence reference of ``benchmarks/reference/solar_open2_lm.py`` in
float32 on the CPU."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu import metrics
from hetu_tpu.models import (SolarOpen2Config, solar_open2_decode_chunked_graph,
                             solar_open2_decode_graph, solar_open2_lm_graph)
from hetu_tpu.models.solar_open2 import param_names
from hetu_tpu.ops import kda, moe
from hetu_tpu.serving import DecodeEngine, DecodeRouter, InferenceExecutor
from hetu_tpu.serving.decode import _DecodeRequest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import sys  # noqa: E402
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.reference import solar_open2_lm as ref  # noqa: E402

with open(os.path.join(ROOT, "tests", "bench_harness", "data",
                       "tiny-solar.json")) as _f:
    #: the tiny preset as the reference reads a configuration
    TINY = json.load(_f)
TINY["assumed"] = dict(TINY["assumed"], initializer_range=0.02)
MAX_LEN = 64
#: float32 sums in another order: a logit of size ~0.7 to 1e-5
TOL = 1e-5


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _draw(cfg, seed=0):
    """Seeded weights with the matrices three times the spec's spread, so
    that the mixers and the experts move the logits (to a size of ~0.7)
    and a wrong one shows."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, (shape, mean, std) in ref.param_spec(cfg).items():
        wide = name.endswith(".weight") and "conv" not in name
        out[name] = (rng.standard_normal(shape) * std * (3 if wide else 1)
                     + mean).astype(np.float32)
    return out


@pytest.fixture(scope="module")
def weights():
    return _draw(TINY)


@pytest.fixture(scope="module")
def ref_logits(weights):
    """``ids -> (logits, choices)`` of the reference routing for itself."""
    w = {k: jnp.asarray(v) for k, v in weights.items()}
    fn = jax.jit(lambda ids: ref.logits(w, ids, TINY))

    def run(ids):
        logits, info = fn(jnp.asarray(ids, jnp.int32))
        return np.asarray(logits), np.asarray(info["choices"])
    return run


def _engine(weights, max_chunk=8, slots=4, cfg=None, **kw):
    cfg = cfg or SolarOpen2Config.tiny()
    f, lg, st, tok, ch = solar_open2_decode_graph(cfg, MAX_LEN)
    chunked = None
    if max_chunk:
        cf, cl, cs, ctok, cch = solar_open2_decode_chunked_graph(cfg, MAX_LEN)
        chunked = (cf, cl, cs, ctok, {"moe_choices": cch})
    return DecodeEngine(f, lg, st, weights=weights, tokens=tok,
                        aux={"moe_choices": ch},
                        aux_fold={"moe_choices": cfg.choice_counters()},
                        max_slots=slots, max_len=MAX_LEN, chunked=chunked,
                        max_chunk=max_chunk or None, **kw)


def _serve(eng, prompts, new, ref_logits=None, seated=0):
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
            # a stream a prefix store seated holds the ids from there on
            assert r.stream.aux_from == seated
            assert np.array_equal(np.sort(r.stream.aux("moe_choices"), -1),
                                  np.sort(chosen[seated:], -1))
    return [r.stream.result(0) for r in reqs], worst


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], n) for n in lengths]


# ------------------------------------------------------------- the model

def test_the_share_counts_what_the_configuration_file_says():
    """The cell's configuration: every published key as published but the
    six the share reduces, the published values beside them, and the
    parameter count of the share from the reference's spec, from the
    program's variables and in the file."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "solar-open2.json")) as f:
        cfg = json.load(f)
    published = {
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "head_dim": 128, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "rope_theta": 10000, "tie_word_embeddings": False,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3,
        "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
        "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "n_shared_experts": 1,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "num_experts_per_tok": 8}
    assert {k: cfg[k] for k in published} == published
    cut = {"num_hidden_layers": (8, 48), "n_routed_experts": (40, 320),
           "num_attention_heads": (8, 64), "num_key_value_heads": (1, 8),
           "linear_attn_heads": (8, 64), "vocab_size": (24576, 196608)}
    assert cfg["reduced"] == list(cut)
    flat = {k: v for group in cfg["published"].values()
            for k, v in group.items()}
    assert {k: (cfg[k], flat[k]) for k in cut} == cut
    assert cfg["held_experts"] == {"first": 120, "count": 40, "of": 320}
    assert "8 chips share each layer" in cfg["deployment"]
    spec = ref.param_spec(cfg)
    count = sum(int(np.prod(shape)) for shape, _, _ in spec.values())
    assert count == cfg["parameters"]
    mcfg = SolarOpen2Config(
        vocab_size=24576, num_hidden_layers=8, num_attention_heads=8,
        num_key_value_heads=1, linear_attn_heads=8, held=(120, 40))
    assert {k: tuple(v[0]) for k, v in spec.items()} == param_names(mcfg)
    kinds = [mcfg.layer_kind(i) for i in range(8)]
    assert kinds == 2 * ["gqa", "kda", "kda", "kda"] \
        == [ref.layer_kind(cfg, i) for i in range(8)]
    assert [i for i in range(48) if SolarOpen2Config().layer_kind(i)
            == "gqa"] == cfg["gqa_layers"]


def test_published_sizes_give_the_published_parameter_count():
    """250B-A15B from the equations: the uncut model's spec counts 250.3 B,
    of which 14.7 B work on a token."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "solar-open2.json")) as f:
        cfg = json.load(f)
    flat = {k: v for group in cfg["published"].values()
            for k, v in group.items()}
    whole = dict(cfg, **flat, held_experts={"first": 0, "count": 320,
                                            "of": 320})
    spec = ref.param_spec(whole)
    size = {k: int(np.prod(shape)) for k, (shape, _, _) in spec.items()}
    total = sum(size.values())
    assert abs(total / 1e9 - 250.28) < 0.01
    experts = sum(v for k, v in size.items() if ".moe.experts." in k)
    active = total - experts + experts * 8 // 320
    assert abs(active / 1e9 - 14.7) < 0.1


def test_full_sequence_graph_is_the_reference(weights, ref_logits):
    cfg = SolarOpen2Config.tiny()
    ids = _prompts(1, [29])[0].astype(np.int32)
    feeds, logits, choices = solar_open2_lm_graph(cfg, len(ids))
    iex = InferenceExecutor([logits, choices], weights=weights, buckets=(1,))
    got, chosen = iex.infer({feeds["input_ids"]: ids[None]})
    want, own = ref_logits(ids)
    assert np.abs(got - want).max() < TOL
    assert np.array_equal(np.sort(chosen[0], -1), np.sort(own, -1))


# --------------------------------------------------------------- the share

#: the uncut tiny model and its 8 shares: 64 query heads over 8 key heads,
#: 64 KDA heads, 64 experts, all as ``TINY`` holds an eighth of them
WHOLE = dict(TINY, num_attention_heads=64, num_key_value_heads=8,
             linear_attn_heads=64,
             held_experts={"first": 0, "count": 64, "of": 64})


def _share_of(w, s, kind):
    """Layer weights ``w`` of the uncut tiny model cut to what chip ``s`` of
    8 holds: its heads' columns of the input projections and rows of the
    output projection, its experts; what every chip holds alike whole."""
    hd, ld = TINY["head_dim"], TINY["linear_attn_config"]["head_dim"]

    def cols(a, sections, width):
        """Columns ``[s * width, (s + 1) * width)`` of each section."""
        out, at = [], 0
        for n, per in sections:
            out.append(a[..., at + s * per * width:at + (s + 1) * per * width])
            at += n * width
        return np.concatenate(out, axis=-1)

    out = dict(w)
    if kind == "gqa":
        out["attn.qkvg.weight"] = cols(
            w["attn.qkvg.weight"], [(64, 8), (8, 1), (8, 1), (64, 8)], hd)
        out["attn.o.weight"] = w["attn.o.weight"][s * 8 * hd:(s + 1) * 8 * hd]
    else:
        three = [(64, 8)] * 3
        out["kda.qkv.weight"] = cols(w["kda.qkv.weight"], three, ld)
        out["kda.conv.weight"] = cols(w["kda.conv.weight"], three, ld)
        for leaf in ("kda.f_up.weight", "kda.dt_bias", "kda.g_up.weight"):
            out[leaf] = cols(w[leaf], [(64, 8)], ld)
        for leaf in ("kda.beta.weight", "kda.A_log"):
            out[leaf] = cols(w[leaf], [(64, 8)], 1)
        out["kda.o.weight"] = w["kda.o.weight"][s * 8 * ld:(s + 1) * 8 * ld]
    for leaf in ("moe.experts.gate_up", "moe.experts.down"):
        out[leaf] = w[leaf][s * 8:(s + 1) * 8]
    return out


@pytest.mark.parametrize("kind", ["gqa", "kda"])
def test_the_eight_shares_add_up_to_the_uncut_layer(kind):
    """What ties the share to the model: the mixer parts of the 8 head
    shares, and the routed parts of the 8 expert shares with the shared
    expert counted once, add up to the uncut reference's layer."""
    i = 0 if kind == "gqa" else 1
    whole = {k: jnp.asarray(v) for k, v in ref.layer_params(
        _draw(WHOLE, seed=3), i).items()}
    x = jnp.asarray(np.random.default_rng(4).standard_normal((19, 32)),
                    jnp.float32)
    want, _, _ = ref.layer(kind, whole, x, {}, WHOLE)

    def share(s):
        cfg = dict(TINY, held_experts={"first": 8 * s, "count": 8, "of": 64})
        return cfg, {k: jnp.asarray(v) for k, v in _share_of(
            {k: np.asarray(v) for k, v in whole.items()}, s, kind).items()}

    shares = [share(s) for s in range(8)]
    y = ref._rms(x, whole["ln1.scale"], 1e-5)
    mixed = sum(ref.mixer(kind, w, y, cfg) for cfg, w in shares)
    assert np.abs(mixed - ref.mixer(kind, whole, y, WHOLE)).max() < TOL
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
    slots seated AGAIN (a ``join`` into a used slot): at every served
    position the engine's logits are the plain forward's, and the expert
    ids handed on with the tokens the reference's own."""
    eng = _engine(weights, max_chunk)
    _, worst = _serve(eng, _prompts(2, [3, 13, 37, 1]), 14, ref_logits)
    assert worst < TOL
    _, worst = _serve(eng, _prompts(3, [17, 2]), 12, ref_logits)
    assert worst < TOL
    assert metrics.decode_counts()["decode_state_clears"] >= 6


def test_one_token_path_and_chunked_path_serve_the_same(weights):
    prompts = _prompts(4, [11, 30, 5])
    slow, _ = _serve(_engine(weights, 0), prompts, 10)
    fast, _ = _serve(_engine(weights, 16), prompts, 10)
    assert slow == fast


def test_reseated_slot_serves_what_a_fresh_engine_serves(weights):
    """A slot that held a longer sequence: its KDA state and convolution
    window are zeroed at ``join``, its slabs are read by position only."""
    eng = _engine(weights, 8, slots=1)
    _serve(eng, _prompts(5, [33]), 20)
    again, _ = _serve(eng, _prompts(6, [9]), 12)
    fresh, _ = _serve(_engine(weights, 8, slots=1), _prompts(6, [9]), 12)
    assert again == fresh


def test_unzeroed_kda_state_serves_other_tokens(weights, monkeypatch):
    fresh, _ = _serve(_engine(weights, 8, slots=1), _prompts(6, [9]), 12)
    monkeypatch.setattr(DecodeEngine, "_clear_recurrent",
                        lambda self, slot: None)
    eng = _engine(weights, 8, slots=1)
    _serve(eng, _prompts(5, [33]), 20)
    again, _ = _serve(eng, _prompts(6, [9]), 12)
    assert again != fresh


def test_router_serves_it_through_the_front_door(weights, ref_logits):
    """``DecodeRouter.submit``: the tokens are the reference's, and the
    stream holds the expert ids of every consumed position."""
    eng = _engine(weights, 8)
    prompt = _prompts(8, [12])[0].astype(np.int32)
    with DecodeRouter(eng) as router:
        stream = router.submit(prompt, max_new_tokens=11)
        tokens = stream.result(timeout=60)
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    want, chosen = ref_logits(seq)
    assert tokens == list(want[len(prompt) - 1:].argmax(-1))
    got = stream.aux("moe_choices")
    assert got.shape == (len(seq), 4, 4) and got.dtype == np.int16
    assert np.array_equal(np.sort(got, -1), np.sort(chosen, -1))
    assert stream.aux("nothing") is None


def test_engine_seats_its_recurrent_state_from_a_prefix_store(
        weights, ref_logits):
    """A prefix store beside KDA state and convolution windows (refused
    until PR 42): a prompt that extends a stored one WHOLE is seated with
    its KV rows and its recurrent state, and is served what the reference
    says at every position it consumed."""
    from hetu_tpu.serving import PrefixKVStore
    store = PrefixKVStore()
    eng = _engine(weights, 8, prefix_store=store)
    first = _prompts(30, [16])[0]
    _serve(eng, [first], 3, ref_logits)
    assert len(store) == 1
    metrics.reset_decode_counts()
    longer = np.concatenate([first, _prompts(31, [7])[0]])
    _, worst = _serve(eng, [longer], 9, ref_logits, seated=16)
    assert worst < TOL
    c = metrics.decode_counts()
    assert (c["decode_prefix_seats"], c["decode_prefix_seat_rows"]) == (1, 16)
    assert c["decode_prefill_rows"] == len(longer) - 16 - 1


def test_auxiliary_fetches_must_match_and_start_where_a_store_seated():
    """The engine's own rules, on a graph that keeps KV state only: both
    entries fetch the same; beside a prefix store (refused until PR 42) a
    seated stream's slices start at the first position it consumed, and
    the stream says where."""
    from hetu_tpu.models import (GPT2Config, gpt2_decode_chunked_graph,
                                 gpt2_decode_graph)
    from hetu_tpu.serving import PrefixKVStore
    g = GPT2Config(vocab_size=50, n_positions=32, n_embd=16, n_layer=1,
                   n_head=2, batch_size=1, seq_len=32)
    f, lg, caches, _ = gpt2_decode_graph(g, max_len=32)
    eng = DecodeEngine(f, lg, caches, max_len=32, aux={"ids": f["input_ids"]},
                       prefix_store=PrefixKVStore())
    first = np.arange(3, 13, dtype=np.int32)
    longer = np.concatenate([first[:8], [40, 41, 42]]).astype(np.int32)
    streams = []
    for prompt in (first, longer):
        req = _DecodeRequest(prompt, 4, None, None)
        eng.join(req)
        while not eng.idle:
            eng.step()
        streams.append(req.stream)
    cold, seated = streams
    assert (cold.aux_from, seated.aux_from) == (0, 8)   # a partial overlap
    fed = np.concatenate([longer, seated.result(0)[:-1]])
    assert seated.aux("ids").tolist() == fed[8:].tolist()
    assert len(cold.aux("ids")) == len(first) + 3
    cf, cl, cc, _ = gpt2_decode_chunked_graph(g, max_len=32)
    with pytest.raises(ValueError, match="same auxiliary fetches"):
        DecodeEngine(f, lg, caches, max_len=32, aux={"ids": f["input_ids"]},
                     chunked=(cf, cl, cc))


@pytest.mark.parametrize("chunked", [False, True], ids=["one_token", "chunked"])
def test_auxiliary_fetches_beside_engine_derived_tokens(chunked):
    """``aux=`` on a graph WITHOUT ``tokens=``: the step brings back the
    ids the engine's program derives from the logits and the auxiliary
    array beside them, each kept apart.  The fetch here is the token ids
    the graph was fed, so a stream's slices are the tokens it consumed —
    under the router too, where the host feeds ``-1`` for a generating row
    and the program puts the previous step's id there — and the tokens
    served are those of an engine that fetches nothing beside."""
    from decode_ahead import assert_same_streams, serve_serial
    from hetu_tpu.models import (GPT2Config, gpt2_decode_chunked_graph,
                                 gpt2_decode_graph)
    g = GPT2Config(vocab_size=50, n_positions=32, n_embd=16, n_layer=1,
                   n_head=2, batch_size=1, seq_len=32)
    specs = [(np.arange(3, 12, dtype=np.int32), 5, None),
             (np.arange(20, 22, dtype=np.int32), 5, None),
             (np.arange(30, 34, dtype=np.int32), 7, None)]

    def engine(with_aux=True):
        f, lg, caches, _ = gpt2_decode_graph(g, max_len=32)
        more = {"aux": {"ids": f["input_ids"]}} if with_aux else {}
        if chunked:
            cf, cl, cc, _ = gpt2_decode_chunked_graph(g, max_len=32)
            more["chunked"] = (cf, cl, cc) + (
                ({"ids": cf["input_ids"]},) if with_aux else ())
            more["max_chunk"] = 4
        return DecodeEngine(f, lg, caches, max_slots=2, max_len=32, seed=3,
                            **more)

    plain, _ = serve_serial(engine(with_aux=False), specs)
    assert_same_streams(engine, specs, aux=("ids",))
    eng = engine()
    fetched, _ = serve_serial(eng, specs)
    assert eng.last_logits.shape[-1] == 50
    for (p, n, _), a, b in zip(specs, plain, fetched):
        assert a.partial() == b.partial() and len(b.partial()) == n
        assert a.aux("ids") is None
        assert np.array_equal(
            b.aux("ids").reshape(-1),
            np.concatenate([p, np.asarray(b.partial()[:-1], np.int32)]))


@pytest.mark.parametrize("chunk", [0, 8], ids=["one_token", "chunked"])
def test_router_one_step_ahead_emits_the_serial_loops_streams(weights, chunk):
    """Step n+1 launched before step n is collected (ISSUE 32): the same
    token streams as a loop of ``engine.step()``, bit for bit, and each
    stream's slices of chosen expert ids still line up with the tokens it
    consumed; seven requests through three slots."""
    from decode_ahead import assert_same_streams
    specs = [(p.astype(np.int32), n, None) for p, n in zip(
        _prompts(21, [9, 2, 17, 5, 1, 12, 3]), [6, 9, 1, 12, 4, 7, 10])]
    serial, ahead = assert_same_streams(
        lambda: _engine(weights, chunk, slots=3), specs,
        aux=("moe_choices",))
    # a slot is free at the COLLECT of its last step, one launch later
    # than in the serial loop: a few steps more, each folded once
    assert ahead["decode_steps"] >= serial["decode_steps"]
    assert ahead["moe_assignments"] \
        == ahead["decode_padded_row_tokens"] * 4 * 4    # layers x k


def test_counters_fold_the_choices_of_every_step(weights):
    """``moe_*`` per step from the fetched ids: every row of the batch
    bucket times k times the layers; the held among them; the held experts
    touched (at most 8 a layer here); the fullest expert's load."""
    metrics.reset_decode_counts()
    eng = _engine(weights, 0, slots=4)
    eng.reserve(4, MAX_LEN)
    _serve(eng, _prompts(12, [5, 9, 2, 7]), 6)
    c = metrics.decode_counts()
    steps = c["decode_steps"]
    assert c["moe_assignments"] == steps * 4 * 4 * 4
    assert 0 < c["moe_assignments_held"] < c["moe_assignments"] / 3
    assert 0 < c["moe_experts_touched"] <= min(
        c["moe_assignments_held"], steps * 4 * 8)
    assert steps <= c["moe_expert_load_max"] <= steps * 4
    fold = SolarOpen2Config.tiny().choice_counters()
    ids = np.array([[[[24, 25, 3, 31]] * 4], [[[24, 60, 61, 62]] * 4]],
                   np.int16)                       # (2 rows, 1, 4 layers, 4)
    assert fold(ids) == {"moe_assignments": 32, "moe_assignments_held": 16,
                         "moe_experts_touched": 12, "moe_expert_load_max": 2}
    calls = metrics.moe_call_counts()
    assert calls.get("8of64:top4:ragged", 0) >= 4


def test_state_kinds_are_allocated_and_accounted_by_kind(weights):
    metrics.reset_decode_counts()
    eng = _engine(weights, 8)
    kinds = sorted(set(eng._kinds.values()))
    assert kinds == ["kv", "recurrent"]
    assert [sum(k == kind for k in eng._kinds.values()) for kind in kinds] \
        == [2, 6]
    eng.reserve(4, MAX_LEN)
    by = eng.state_bytes()
    # three KDA layers: (8, 8, 8) float32 a slot each, and 3 x 192 of window
    assert by["recurrent"] == 4 * 3 * (8 * 8 * 8 + 3 * 192) * 4
    assert by["kv"] == 4 * 2 * MAX_LEN * 16 * 4
    c = metrics.decode_counts()
    assert c["decode_state_bytes_recurrent_hw"] == by["recurrent"]
    assert c["decode_kv_bytes_hw"] == sum(by.values())


def test_weights_are_stored_in_the_type_the_variables_declare(weights):
    cfg = SolarOpen2Config.tiny(param_dtype=jnp.bfloat16,
                                cache_dtype=jnp.bfloat16)
    eng = _engine(weights, 4, slots=2, cfg=cfg)
    assert {str(v.dtype) for v in eng.iex.params.values()} == {"bfloat16"}
    by_kind = {}
    for name, c in eng.caches.items():
        by_kind.setdefault(eng._kinds[name], set()).add(str(c.dtype))
    assert by_kind == {"kv": {"bfloat16"}, "recurrent": {"float32"}}
    tokens, _ = _serve(eng, _prompts(11, [6]), 5)
    assert len(tokens[0]) == 5


def test_layers_lower_under_their_scopes(weights):
    eng = _engine(weights, 0)
    feeds = {eng._fk["input_ids"]: np.zeros((1, 1), np.int32),
             eng._fk["positions"]: np.zeros(1, np.int32)}
    text = jax.jit(eng._program(eng.iex, eng._fk)).lower(
        eng.iex.params, (feeds, tuple(eng.caches.values())),
        np.zeros(1, np.int32)).as_text(
            debug_info=True)
    for scope in ("mix.gqa", "mix.kda", "moe.route", "moe.experts",
                  "moe.shared", "lm_head"):
        assert f"/{scope}/" in text, scope


def test_the_graph_is_the_same_program_with_the_softmax_scale_left_out(
        weights, monkeypatch):
    """``_gqa_attention_kv`` took a ``scale=`` for a muP-scaled model
    (ISSUE 45); this graph names none and its nodes carry none: the step
    lowers to the program it lowers to with ``1/√D`` spelled out."""
    def lowered():
        eng = _engine(weights, 0)
        feeds = {eng._fk["input_ids"]: np.zeros((1, 1), np.int32),
                 eng._fk["positions"]: np.zeros(1, np.int32)}
        return jax.jit(eng._program(eng.iex, eng._fk)).lower(
            eng.iex.params, (feeds, tuple(eng.caches.values())),
            np.zeros(1, np.int32)).as_text()
    from hetu_tpu.graph.node import topo_sort
    cfg = SolarOpen2Config.tiny()
    logits = solar_open2_decode_graph(cfg, MAX_LEN)[1]
    reads = [n for n in topo_sort([logits])
             if getattr(n, "op_type", "") == "GQAAttentionKV"]
    assert reads and all("scale" not in n.attrs for n in reads)
    plain = lowered()
    real = kda.gqa_attention_kv_op
    monkeypatch.setattr(kda, "gqa_attention_kv_op", lambda *a, **kw: real(
        *a, scale=kw["head_dim"] ** -0.5, **kw))
    assert lowered() == plain


# ---------------------------------------------------------------- the ops

def test_kda_step_is_the_delta_rule_written_out():
    """The two-pass form against ``S' = (I − β k kᵀ) Diag(a) S + β k vᵀ``,
    ``o = S'ᵀ q`` with matrices."""
    rng = np.random.default_rng(0)
    s = rng.standard_normal((2, 3, 8, 8)).astype(np.float32)
    q, k, v = (rng.standard_normal((2, 3, 8)).astype(np.float32)
               for _ in range(3))
    a = rng.uniform(0.2, 1.0, (2, 3, 8)).astype(np.float32)
    b = rng.uniform(0.0, 2.0, (2, 3)).astype(np.float32)
    new, o = kda._kda_step(jnp.asarray(s), q, k, v, a, b)
    eye = np.eye(8, dtype=np.float32)
    for i in range(2):
        for h in range(3):
            want = (eye - b[i, h] * np.outer(k[i, h], k[i, h])) \
                @ (a[i, h][:, None] * s[i, h]) \
                + b[i, h] * np.outer(k[i, h], v[i, h])
            assert np.abs(new[i, h] - want).max() < 1e-5
            assert np.abs(o[i, h] - want.T @ q[i, h]).max() < 1e-5


def test_kda_chunk_advances_each_row_by_what_it_consumed():
    """A chunk with ``valid`` moves a row's state as that many one-token
    steps do, and a row with none keeps its state."""
    rng = np.random.default_rng(1)
    b, chunk, h, d = 3, 5, 2, 8
    ids = np.zeros((b, chunk), np.int32)
    qkv = rng.standard_normal((b * chunk, 3 * h * d)).astype(np.float32)
    f = rng.standard_normal((b * chunk, h * d)).astype(np.float32)
    beta = rng.standard_normal((b * chunk, h)).astype(np.float32)
    a_log = rng.standard_normal(h).astype(np.float32)
    s0 = rng.standard_normal((b, h, d, d)).astype(np.float32)
    valid = np.array([5, 2, 0], np.int32)
    o, s1 = kda._kda_chunk(None, qkv, f, beta, a_log, s0, ids, valid, heads=h)
    want = s0.copy()
    for j in range(chunk):
        rows = np.arange(b) * chunk + j
        oj, nxt = kda._kda_chunk(None, qkv[rows], f[rows], beta[rows], a_log,
                                 want, ids[:, :1], heads=h)
        live = j < valid
        want = np.where(live[:, None, None, None], nxt, want)
        assert np.abs(np.asarray(o)[rows][live] - np.asarray(oj)[live]).max() \
            < 1e-5
    assert np.abs(s1 - want).max() < 1e-5
    assert np.array_equal(s1[2], s0[2])


@pytest.mark.parametrize("m,sizes", [
    (52, [5, 0, 7, 1, 0, 0, 20, 3]),         # rows behind the last group
    (128, [16] * 8),                         # a whole row tile, all groups
    (200, [0, 0, 130, 0, 0, 70, 0, 0])],     # groups wider than a tile
    ids=["padded_rows", "one_tile", "wide_groups"])
def test_both_grouped_products_multiply_alike(m, sizes):
    """``_grouped_matmul``'s two paths on the same sorted rows: the
    compiler's ``ragged_dot`` (what the CPU takes) and the Pallas grouped
    matmul the TPU takes, here interpreted, its rows padded to a tile."""
    rng = np.random.default_rng(5)
    rows = rng.standard_normal((m, 16)).astype(np.float32)
    w = rng.standard_normal((8, 16, 128)).astype(np.float32) * 0.3
    sizes = np.asarray(sizes, np.int32)
    ragged, kernel = (np.asarray(moe._grouped_matmul(rows, w, sizes, how))
                      for how in ("ragged", "kernel"))
    live = int(sizes.sum())
    assert ragged.shape == kernel.shape == (m, 128)
    assert np.abs(ragged[:live] - kernel[:live]).max() < 1e-4
    at = np.repeat(np.arange(8), sizes)
    assert np.abs(ragged[:live] - np.einsum(
        "mk,mkn->mn", rows[:live], w[at])).max() < 1e-4


@pytest.mark.parametrize("first", [0, 24, 56])
def test_experts_op_is_the_dense_sum_over_the_held(first):
    """The sorted, grouped product against every held expert applied to
    every token: tokens with no held choice get zero, nothing is dropped
    however many tokens one expert takes."""
    rng = np.random.default_rng(2)
    n, d, f, g, k = 13, 16, 8, 8, 4
    y = rng.standard_normal((n, d)).astype(np.float32)
    w_gu = rng.standard_normal((g, d, 2 * f)).astype(np.float32) * 0.3
    w_d = rng.standard_normal((g, f, d)).astype(np.float32) * 0.3
    others = np.delete(np.arange(64), first + 2)
    ids = np.stack([rng.choice(others, k, replace=False) for _ in range(n)])
    ids[:5, 0] = first + 2                 # one expert takes five tokens
    ids[5] = [1, 2, 3, 4] if first else [60, 61, 62, 63]    # none held
    ids = ids.astype(np.int32)
    w = rng.uniform(0.1, 1.0, (n, k)).astype(np.float32)
    got = moe._moe_experts(None, y, ids, w, w_gu, w_d, first=first,
                           n_experts=64)
    want = np.zeros((n, d), np.float32)
    for t in range(n):
        for j in range(k):
            e = ids[t, j] - first
            if 0 <= e < g:
                h = y[t] @ w_gu[e]
                want[t] += w[t, j] * ((h[:f] / (1 + np.exp(-h[:f])))
                                      * h[f:]) @ w_d[e]
    assert np.abs(got - want).max() < 1e-4
    assert np.all(np.asarray(got)[5] == 0)


def test_router_chooses_by_the_biased_score_and_weighs_by_the_plain():
    rng = np.random.default_rng(3)
    y = rng.standard_normal((6, 16)).astype(np.float32)
    w_r = rng.standard_normal((16, 32)).astype(np.float32)
    bias = (rng.standard_normal(32) * 0.5).astype(np.float32)
    ids, w = moe._moe_route(None, y, w_r, bias, top_k=4)
    s = 1 / (1 + np.exp(-(y @ w_r)))
    want = np.argsort(-(s + bias), axis=-1)[:, :4]
    assert np.array_equal(np.sort(ids, -1), np.sort(want, -1))
    assert not np.array_equal(np.sort(ids, -1),
                              np.sort(np.argsort(-s, -1)[:, :4], -1))
    picked = np.take_along_axis(s, np.asarray(ids), -1)
    assert np.abs(w - picked / picked.sum(-1, keepdims=True)).max() < 1e-6


@pytest.mark.parametrize("chunk", [1, 3])
def test_gqa_reads_its_slabs_as_the_plain_softmax_does(chunk):
    """8 query heads over one key head, packed slabs (head_dim 16: 8 key
    rows a slab row), against attention written out."""
    from hetu_tpu.ops.attention import kv_slab_from_rows
    rng = np.random.default_rng(4)
    b, r, d, length = 2, 8, 16, 32
    keys = rng.standard_normal((b, 1, length, d)).astype(np.float32)
    vals = rng.standard_normal((b, 1, length, d)).astype(np.float32)
    q = rng.standard_normal((b * chunk, r * d)).astype(np.float32)
    at = np.array([4, 17], np.int32)
    got = kda._gqa_attention_kv(
        None, q, kv_slab_from_rows(jnp.asarray(keys), 128),
        kv_slab_from_rows(jnp.asarray(vals), 128), at,
        np.zeros((b, chunk), np.int32), head_dim=d)
    for i in range(b):
        for j in range(chunk):
            n = at[i] + j + 1
            for h in range(r):
                qv = q[i * chunk + j, h * d:(h + 1) * d]
                s = keys[i, 0, :n] @ qv / np.sqrt(d)
                p = np.exp(s - s.max())
                want = (p / p.sum()) @ vals[i, 0, :n]
                assert np.abs(got[i * chunk + j, h * d:(h + 1) * d]
                              - want).max() < 1e-5


@pytest.mark.parametrize("blocks", ["one_block", "blocks"])
def test_one_token_kernel_is_the_gqa_layers_read(blocks, monkeypatch):
    """The kernel's third caller: ``_gqa_attention_kv`` at ``C = 1`` hands
    8 query heads over one 128-wide key head to the one-token kernel
    (interpret mode) as 8 score rows of one program — bfloat16 rows as
    stored, rows past each length filled with garbage — and reads what its
    own ``jnp`` path reads over the same values."""
    import functools
    from hetu_tpu.ops.pallas import decode_attention as da
    rng = np.random.default_rng(5)
    lengths = np.array([1, 63, 64, 65, 256, 200, 17], np.int32)
    b, d, rows = len(lengths), 128, 256
    dead = np.arange(rows)[None, :] >= lengths[:, None]
    slabs = []
    for fill in (3.0e4, -3.0e4):
        slab = rng.standard_normal((b, 1, rows, d)).astype(np.float32)
        slabs.append(jnp.asarray(np.where(dead[:, None, :, None], fill, slab),
                                 jnp.bfloat16))
    # queries whose SCALED values are bfloat16 values: the kernel takes
    # its score rows in the slabs' type, the float32 side as they come
    q = jnp.asarray(rng.standard_normal((b, 8 * d)), jnp.bfloat16) \
        .astype(jnp.float32) * np.float32(d ** 0.5)
    ids = jnp.zeros((b, 1), jnp.int32)
    want = kda._gqa_attention_kv(None, q, slabs[0].astype(jnp.float32),
                                 slabs[1].astype(jnp.float32), lengths - 1,
                                 ids, head_dim=d)
    # the cell's call: one key head of 128, 4096 rows, bfloat16
    assert da.geometry(1, 4096, 128, 2) == (1, 4096)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(da, "decode_attention", functools.partial(
        da.decode_attention, interpret=True))
    if blocks == "blocks":
        monkeypatch.setattr(da, "BLOCK_BYTES", 2 * 64 * 128 * 2)
        monkeypatch.setattr(da, "MIN_BLOCK_ROWS", 8)
    metrics.reset_all()
    got = kda._gqa_attention_kv(None, q, *slabs, lengths - 1, ids, head_dim=d)
    assert metrics.decode_attn_call_counts() == {
        "1x64" if blocks == "blocks" else "1x256": 1}
    assert got.shape == want.shape == (b, 8 * d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert float(jnp.max(jnp.abs(want))) > 0.5
