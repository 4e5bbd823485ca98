"""Phi-4-mini-flash (SambaY) through ``DecodeEngine``: three kinds of
per-sequence state side by side, against the plain full-sequence reference
of ``benchmarks/reference/phi4flash_lm.py`` in float32 on the CPU."""
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hetu_tpu import metrics
from hetu_tpu.models import (Phi4FlashConfig, phi4flash_decode_chunked_graph,
                             phi4flash_decode_graph, phi4flash_lm_graph)
from hetu_tpu.models.phi4flash import param_names
from hetu_tpu.ops import ssm
from hetu_tpu.serving import DecodeEngine, DecodeRouter, InferenceExecutor
from hetu_tpu.serving.decode import _DecodeRequest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
import sys  # noqa: E402
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.reference import phi4flash_lm as ref  # noqa: E402

#: the tiny preset as the reference reads a configuration
TINY = dict(hidden_size=64, intermediate_size=96, num_hidden_layers=8,
            num_attention_heads=8, num_key_value_heads=4, sliding_window=8,
            vocab_size=97, layer_norm_eps=1e-5,
            assumed=dict(d_inner=128, d_state=4, d_conv=4, dt_rank=4,
                         initializer_range=0.02))
MAX_LEN = 64
#: float32 sums in another order: a logit of size ~0.5 to 1e-5
TOL = 1e-5


@pytest.fixture(scope="module", autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def weights():
    """Seeded weights with the matrices four times the spec's spread, so
    that the mixers move the logits and a wrong one shows."""
    rng = np.random.default_rng(0)
    out = {}
    for name, (shape, mean, std) in ref.param_spec(TINY).items():
        wide = name.endswith(".weight") and "conv" not in name \
            and "subln" not in name
        out[name] = (rng.standard_normal(shape) * std * (4 if wide else 1)
                     + mean).astype(np.float32)
    return out


@pytest.fixture(scope="module")
def ref_logits(weights):
    w = {k: jnp.asarray(v) for k, v in weights.items()}
    fn = jax.jit(lambda ids: ref.logits(w, ids, TINY))
    return lambda ids: np.asarray(fn(jnp.asarray(ids, jnp.int32)))


def _engine(weights, max_chunk=8, slots=4, **kw):
    cfg = Phi4FlashConfig.tiny()
    f, lg, st, tok = phi4flash_decode_graph(cfg, MAX_LEN)
    chunked = None
    if max_chunk:
        chunked = phi4flash_decode_chunked_graph(cfg, MAX_LEN)
        chunked = chunked[:3] + (chunked[3],)
    return DecodeEngine(f, lg, st, weights=weights, tokens=tok,
                        max_slots=slots, max_len=MAX_LEN, chunked=chunked,
                        max_chunk=max_chunk or None, **kw)


def _serve(eng, prompts, new, ref_logits=None):
    """Drive ``prompts`` through ``eng`` to the end; returns the token
    streams and the worst gap between a served row's logits and the
    reference's at that position."""
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
            want = ref_logits(np.concatenate(
                [r.prompt, np.asarray(toks[:n - 1], np.int32)]))[-1]
            worst = max(worst, float(np.abs(got[slot[id(r)]] - want).max()))
            assert int(np.argmax(want)) == toks[n - 1]
    return [r.stream.result(0) for r in reqs], worst


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], n) for n in lengths]


# ------------------------------------------------------------- the model

def test_published_sizes_give_the_published_parameter_count():
    """3.85 B from the program's variables, from the reference's spec and
    in the configuration file, which carries every published key as
    published and reduces none."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "phi4-mini-flash.json")) as f:
        cfg = json.load(f)
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512,
        "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False, "vocab_size": 200064}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == [] and cfg["source"].endswith(
        "microsoft/Phi-4-mini-flash-reasoning/blob/main/config.json")
    assert {"d_inner", "d_state", "d_conv", "dt_rank", "lambda_init",
            "departures"} <= set(cfg["assumed"])
    spec = ref.param_spec(cfg)
    count = sum(int(np.prod(shape)) for shape, _, _ in spec.values())
    assert count == cfg["parameters"] == 3852457984
    names = param_names(Phi4FlashConfig())
    assert {k: tuple(v[0]) for k, v in spec.items()} == names
    kinds = [Phi4FlashConfig().layer_kind(i) for i in range(32)]
    assert [kinds.count(k) for k in ("ssm", "swa", "full", "gmu", "cross")] \
        == [9, 8, 1, 7, 7]
    assert kinds == [ref.layer_kind(cfg, i) for i in range(32)]


def test_full_sequence_graph_is_the_reference(weights, ref_logits):
    """One chunk over zero states, longer than three windows."""
    cfg = Phi4FlashConfig.tiny()
    ids = _prompts(1, [29])[0].astype(np.int32)
    feeds, logits = phi4flash_lm_graph(cfg, len(ids))
    iex = InferenceExecutor([logits], weights=weights, buckets=(1,))
    got = iex.infer({feeds["input_ids"]: ids[None]})[0]
    assert np.abs(got - ref_logits(ids)).max() < TOL


# ------------------------------------------------------------ the engine

@pytest.mark.parametrize("max_chunk", [0, 2, 4, 8, 16, 32])
def test_engine_serves_the_reference_at_every_chunk_width(
        weights, ref_logits, max_chunk):
    """Prompts of 1 to 37 tokens prefilled by chunks up to ``max_chunk``
    (0: token by token), 14 tokens generated — past the window of 8 in
    every ring — in a mixed batch, then the slots seated AGAIN: at every
    served position the engine's logits are the plain forward's."""
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


def test_states_written_by_the_aliased_kernel_serve_the_same(
        weights, monkeypatch):
    """ISSUE 37: behind a backend that says tpu every one-token step
    writes its slab rows AND its ring rows through ``kv_append`` (here in
    interpret mode), every chunked step its slab rows: the streams, and
    every byte of every state at the end, are the CPU path's — so each
    window layer's attention read its ring before the aliased write."""
    import functools

    from hetu_tpu.ops.pallas import kv_append as ka
    prompts = _prompts(4, [11, 30, 5])
    plain = _engine(weights, 8)
    want, _ = _serve(plain, prompts, 10)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(ka, "kv_append", functools.partial(
        ka.kv_append, interpret=True))
    metrics.reset_all()
    eng = _engine(weights, 8)
    got, _ = _serve(eng, prompts, 10)
    assert got == want
    for name, state in eng.caches.items():
        assert np.array_equal(np.asarray(state),
                              np.asarray(plain.caches[name])), name
    calls = metrics.kv_append_call_counts()
    assert calls and all(k.endswith(":kernel") for k in calls)
    # the rings' one-row writes among them (tiny heads: 16 lanes a pair)
    assert "8x16:kernel" in calls


def test_reseated_slot_serves_what_a_fresh_engine_serves(weights):
    """A slot that held a longer sequence: its recurrent state is zeroed
    at ``join``, its rings and slabs are read by position only."""
    eng = _engine(weights, 8, slots=1)
    _serve(eng, _prompts(5, [33]), 20)
    again, _ = _serve(eng, _prompts(6, [9]), 12)
    fresh, _ = _serve(_engine(weights, 8, slots=1), _prompts(6, [9]), 12)
    assert again == fresh


def test_a_row_does_not_depend_on_its_batch_mates(weights):
    """Rows in different phases (one mid-prompt, one generating, one idle
    slot between them) against each served alone."""
    prompts = _prompts(7, [26, 4])
    eng = _engine(weights, 8)
    first = _DecodeRequest(np.asarray(prompts[0], np.int32), 9, None, None)
    eng.join(first)
    eng.step()
    mixed, _ = _serve(eng, [prompts[1]], 9)
    while not eng.idle:
        eng.step()
    alone0, _ = _serve(_engine(weights, 8), [prompts[0]], 9)
    alone1, _ = _serve(_engine(weights, 8), [prompts[1]], 9)
    assert first.stream.result(0) == alone0[0] and mixed == alone1


def test_unzeroed_recurrent_state_serves_other_tokens(weights, monkeypatch):
    """What the clearing is for: without it a re-seated slot carries the
    last sequence's scan state on."""
    fresh, _ = _serve(_engine(weights, 8, slots=1), _prompts(6, [9]), 12)
    monkeypatch.setattr(DecodeEngine, "_clear_recurrent",
                        lambda self, slot: None)
    eng = _engine(weights, 8, slots=1)
    _serve(eng, _prompts(5, [33]), 20)
    again, _ = _serve(eng, _prompts(6, [9]), 12)
    assert again != fresh


@pytest.mark.parametrize("chunk", [0, 8], ids=["one_token", "chunked"])
def test_router_one_step_ahead_emits_the_serial_loops_streams(weights, chunk):
    """Step n+1 launched before step n is collected (ISSUE 32): the same
    token streams as a loop of ``engine.step()``, bit for bit, with three
    kinds of state; seven requests through three slots, so a slot's ring
    and scan state are taken over under a step in flight."""
    from decode_ahead import assert_same_streams
    specs = [(p.astype(np.int32), n, None) for p, n in zip(
        _prompts(21, [9, 2, 17, 5, 1, 12, 3]), [6, 9, 1, 12, 4, 7, 10])]
    serial, ahead = assert_same_streams(
        lambda: _engine(weights, chunk, slots=3), specs)
    assert ahead["decode_state_clears"] == serial["decode_state_clears"] == 7


def test_eos_hit_with_a_step_in_flight_leaves_a_clean_slot(weights):
    """``eos_id`` matches with the next step launched and carrying the
    row: its answer is dropped, and the one slot's next occupant finds the
    recurrent state zeroed behind that stray step — the tokens of a fresh
    engine."""
    from decode_ahead import serve_router, serve_serial
    a, b = (p.astype(np.int32) for p in _prompts(5, [11, 9]))
    (free, after), _ = serve_serial(
        _engine(weights, 8, slots=1), [(a, 14, None), (b, 12, None)])
    toks = free.result(0)
    k = max(i for i in range(len(toks) - 1) if toks[i] not in toks[:i])
    specs = [(a, 14, toks[k]), (b, 12, None)]
    _, c_serial = serve_serial(_engine(weights, 8, slots=1), specs)
    ahead, c_ahead = serve_router(_engine(weights, 8, slots=1), specs)
    assert ahead[0].result(0) == toks[:k + 1]
    assert ahead[1].result(0) == after.result(0)
    assert c_ahead["decode_tokens"] == c_serial["decode_tokens"]
    assert c_ahead["decode_steps"] == c_serial["decode_steps"] + 1


def test_router_serves_it_through_the_front_door(weights, ref_logits):
    eng = _engine(weights, 8)
    prompt = _prompts(8, [12])[0].astype(np.int32)
    with DecodeRouter(eng) as router:
        tokens = router.submit(prompt, max_new_tokens=11).result(timeout=60)
    seq = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    assert tokens == list(ref_logits(seq)[len(prompt) - 1:].argmax(-1))


@pytest.mark.parametrize("what", ["prefix_store", "plan"])
def test_engine_refuses_what_only_kv_state_supports(weights, what):
    from hetu_tpu.serving import PrefixKVStore
    kw = {"prefix_store": PrefixKVStore()} if what == "prefix_store" \
        else {"plan": object()}
    with pytest.raises(ValueError, match="recurrent and ring state"):
        _engine(weights, 0 if what == "plan" else 8, **kw)


def test_state_kinds_are_allocated_grown_and_accounted_by_kind(weights):
    metrics.reset_decode_counts()
    eng = _engine(weights, 8)
    kinds = sorted(set(eng._kinds.values()))
    assert kinds == ["kv", "recurrent", "ring"]
    assert [sum(k == kind for k in eng._kinds.values()) for kind in kinds] \
        == [2, 6, 4]
    one = eng.state_bytes()
    eng.reserve(4, MAX_LEN)
    assert (eng.bb, eng.lb) == (4, MAX_LEN)
    grown = eng.state_bytes()
    # the batch multiplies every kind, the length only the slabs
    assert grown["ring"] == 4 * one["ring"]
    assert grown["recurrent"] == 4 * one["recurrent"]
    assert grown["kv"] == 4 * eng._slab_rows(MAX_LEN) * one["kv"]
    c = metrics.decode_counts()
    assert [c[f"decode_state_bytes_{k}_hw"] for k in kinds] \
        == [grown[k] for k in kinds]
    assert c["decode_kv_bytes_hw"] == sum(grown.values()) == eng.kv_bytes
    assert "decode_len_grows" not in c and "decode_batch_grows" not in c
    with pytest.raises(ValueError, match="exceeds"):
        eng.reserve(5, MAX_LEN)


def test_reserved_engine_compiles_one_program_per_chunk_width(weights):
    """Reserved at its final buckets, the engine compiles the one-token
    program and one program per chunk width it meets — none for the
    lengths on the way — and the step after that compiles nothing."""
    from hetu_tpu.graph import step_cache
    step_cache.clear()            # no program of an earlier test's engine
    metrics.reset_serve_counts()
    eng = _engine(weights, 8)
    eng.reserve(4, MAX_LEN)
    _serve(eng, _prompts(9, [2]), 2)         # chunk 2, then one token
    _serve(eng, _prompts(9, [4]), 2)         # chunk 4
    _serve(eng, _prompts(9, [7]), 2)         # chunk 8
    assert metrics.serve_counts()["serve_bucket_compiles"] == 4
    _serve(eng, _prompts(10, [3, 29, 8, 5]), 30)
    assert metrics.serve_counts()["serve_bucket_compiles"] == 4
    assert "decode_len_grows" not in metrics.decode_counts()


def test_both_entries_hold_one_set_of_weight_buffers(weights):
    eng = _engine(weights, 8)
    names = {eng.iex.var_names[n]: eng.iex.params[eng.iex._k(n)]
             for n in eng.iex.var_nodes}
    twin = {eng.ciex.var_names[n]: eng.ciex.params[eng.ciex._k(n)]
            for n in eng.ciex.var_nodes}
    assert names.keys() == twin.keys() and len(names) == len(weights)
    assert all(names[k] is twin[k] for k in names)
    assert len({a.unsafe_buffer_pointer() for a in names.values()}
               | {a.unsafe_buffer_pointer() for a in twin.values()}) \
        == len(names)


def test_weights_are_stored_in_the_type_the_variables_declare(weights):
    cfg = Phi4FlashConfig.tiny(param_dtype=jnp.bfloat16,
                               cache_dtype=jnp.bfloat16)
    f, lg, st, tok = phi4flash_decode_graph(cfg, MAX_LEN)
    eng = DecodeEngine(f, lg, st, weights=weights, tokens=tok, max_slots=2,
                       max_len=MAX_LEN)
    assert {str(v.dtype) for v in eng.iex.params.values()} == {"bfloat16"}
    by_kind = {}
    for name, c in eng.caches.items():
        by_kind.setdefault(eng._kinds[name], set()).add(str(c.dtype))
    assert by_kind == {"kv": {"bfloat16"}, "ring": {"bfloat16"},
                       "recurrent": {"float32"}}
    tokens, _ = _serve(eng, _prompts(11, [6]), 5)
    assert len(tokens[0]) == 5


def test_logits_stay_on_the_device_until_asked_for(weights):
    eng = _engine(weights, 0)
    eng.join(_DecodeRequest(np.arange(3, dtype=np.int32), 4, None, None))
    eng.step()
    assert eng.last_logits is None            # mid-prompt: nothing read
    eng.step(), eng.step()
    assert isinstance(eng._logits, jax.Array)
    got = eng.last_logits
    assert isinstance(got, np.ndarray) and got.shape == (eng.bb, 97)


def test_mixers_lower_under_their_scopes(weights):
    """The compiled step's metadata names each mixer: what a device trace
    groups by (``benchmarks/trace_scopes.py``)."""
    eng = _engine(weights, 0)
    feeds = {eng._fk["input_ids"]: np.zeros((1, 1), np.int32),
             eng._fk["positions"]: np.zeros(1, np.int32)}
    text = jax.jit(eng._program(eng.iex, eng._fk)).lower(
        eng.iex.params, (feeds, tuple(eng.caches.values())),
        np.zeros(1, np.int32)).as_text(
            debug_info=True)
    for scope in ("mix.ssm", "mix.swa", "mix.full", "mix.cross", "mix.gmu",
                  "mlp", "lm_head"):
        assert f"/{scope}/" in text, scope


# ---------------------------------------------------------------- the ops

@pytest.mark.parametrize("tied", [False, True], ids=["stored", "tied"])
def test_products_read_the_weights_as_they_are_stored(tied):
    """``matmul_op(out_dtype=)``: float32 activations over a bfloat16
    weight — the activations take the weight's type, the sums and the
    result are float32; the tied head reads an ``(out, in)`` table.  A
    table's rows come out in the ``dtype=`` asked for, exactly."""
    from hetu_tpu.ops.embedding import _lookup
    from hetu_tpu.ops.matmul import _mm
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((5, 48)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((48, 24)), jnp.bfloat16)
    got = _mm(None, x, w.T if tied else w, trans_B=tied,
              out_dtype=jnp.float32)
    rounded = x.astype(jnp.bfloat16).astype(jnp.float32)
    want = np.asarray(rounded) @ np.asarray(w.astype(jnp.float32))
    assert got.dtype == jnp.float32 and np.allclose(got, want, atol=1e-5)
    assert _mm(None, w, w.T).dtype == jnp.bfloat16      # the default stays
    rows = _lookup(None, w, jnp.asarray([[3, 0]]), dtype=jnp.float32)
    assert rows.dtype == jnp.float32 and rows.shape == (1, 2, 24)
    assert np.array_equal(rows[0, 0], w[3].astype(jnp.float32))
    assert _lookup(None, w, jnp.asarray([1])).dtype == jnp.bfloat16


def test_chunk_scan_advances_each_row_by_what_it_consumed():
    """A row that consumes 3 of a chunk's 8 columns moves its state 3
    steps: the same as three one-token updates."""
    rng = np.random.default_rng(0)
    b, c, e, n = 3, 8, 16, 4
    ids = jnp.zeros((b, c), jnp.int32)
    u, dt = (jnp.asarray(rng.standard_normal((b * c, e)), jnp.float32)
             for _ in range(2))
    bc = jnp.asarray(rng.standard_normal((b * c, 2 * n)), jnp.float32)
    a_log = jnp.asarray(rng.standard_normal((n, e)), jnp.float32)
    d = jnp.ones((e,), jnp.float32)
    s0 = jnp.asarray(rng.standard_normal((b, n, e)), jnp.float32)
    valid = jnp.asarray([3, 0, 8], jnp.int32)
    y, s = ssm._ssm_chunk_scan(None, u, dt, bc, a_log, d, s0, ids, valid)
    one = jnp.zeros((b, 1), jnp.int32)
    step, ys = s0, []
    for j in range(c):
        live = (j < valid).astype(jnp.int32)
        cut = lambda t: t.reshape(b, c, -1)[:, j]          # noqa: E731
        yj, step = ssm._ssm_chunk_scan(None, cut(u), cut(dt), cut(bc),
                                       a_log, d, step, one, live)
        ys.append(yj)
    assert np.allclose(s, step, atol=1e-6)
    assert np.array_equal(s[1], s0[1])                      # consumed none
    got = np.asarray(y).reshape(b, c, e)
    assert np.allclose(got[0, :3], np.stack(ys, 1)[0, :3], atol=1e-6)
    assert np.allclose(got[2], np.stack(ys, 1)[2], atol=1e-6)


def test_conv_state_keeps_the_last_inputs_consumed():
    rng = np.random.default_rng(1)
    b, c, e, k = 2, 5, 8, 4
    ids = jnp.zeros((b, c), jnp.int32)
    u = jnp.asarray(rng.standard_normal((b * c, e)), jnp.float32)
    st = jnp.asarray(rng.standard_normal((b, k - 1, e)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, e)), jnp.float32)
    out, new = ssm._conv_state_shift(None, u, st, w, jnp.zeros(e), ids,
                                     jnp.asarray([2, 5], jnp.int32))
    seq = np.concatenate([st, np.asarray(u).reshape(b, c, e)], 1)
    assert np.allclose(new[0], seq[0, 2:5]) and np.allclose(new[1],
                                                            seq[1, 5:8])
    want = sum(seq[:, i:i + c] * np.asarray(w)[i] for i in range(k))
    assert np.allclose(out, jax.nn.silu(want).reshape(b * c, e), atol=1e-6)


@pytest.mark.parametrize("chunk", [1, 3, 8, 19])
def test_ring_append_writes_at_position_mod_window(chunk):
    """Rows land at ``(p + j) mod W``; past ``valid`` nothing is written;
    where a chunk laps the ring the last row of a slot stays."""
    rng = np.random.default_rng(chunk)
    b, g, w, d = 3, 2, 8, 4
    ring = jnp.asarray(rng.standard_normal((b, g, w, d)), jnp.float32)
    new = jnp.asarray(rng.standard_normal((b, g, chunk, d)), jnp.float32)
    pos = np.asarray([0, 6, 13], np.int32)
    valid = np.asarray([chunk, max(chunk - 2, 0), 0], np.int32)
    got = ssm._ring_append(None, ring, new, jnp.asarray(pos),
                           jnp.zeros((b, chunk), jnp.int32),
                           jnp.asarray(valid))
    want = np.array(ring)
    for r in range(b):
        for j in range(valid[r]):
            want[r, :, (pos[r] + j) % w] = np.asarray(new)[r, :, j]
    assert np.array_equal(got, want)
