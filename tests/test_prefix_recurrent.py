"""Prefix snapshots that carry recurrent state and indexer rows (ISSUE 42):
a stream seated from a snapshot is, bit for bit, the cold stream — for the
block-sparse / Lightning hybrid and for the KDA hybrid; what hits, what is
kept, what is evicted, what is still refused."""
import numpy as np
import pytest

from hetu_tpu import metrics
from hetu_tpu.serving import DecodeRouter, PrefixKVStore
from hetu_tpu.serving.decode import _DecodeRequest

import test_minicpm_sala as sala
import test_solar_open2 as solar


@pytest.fixture(scope="module")
def sala_weights():
    return sala.draw(sala.TINY)


@pytest.fixture(scope="module")
def solar_weights():
    return solar._draw(solar.TINY)


def _through(eng, prompt, new, **req):
    """One request to its end: ``(tokens, the logits of every row it was
    served, its stream)``."""
    r = _DecodeRequest(np.asarray(prompt, np.int32), new, None, None, **req)
    slot = eng.join(r)
    rows = []
    while not eng.idle:
        before = r.stream.n_tokens
        eng.step()
        if r.stream.n_tokens != before:
            rows.append(eng.last_logits[slot].copy())
    return r.stream.result(0), np.stack(rows), r.stream


MODELS = {
    # (engine maker, weights fixture, aux name, first prompt, its tail):
    # the first prompt a whole number of chunks, so that the cold stream's
    # chunks end where the seated stream's begin and both run the same
    # programs over the same operands
    "sala": (sala.engine, "sala_weights", sala.BLOCKS, 48, 9),
    "solar": (solar._engine, "solar_weights", "moe_choices", 16, 7)}


@pytest.mark.parametrize("chunk", [0, 8], ids=["one_token", "chunked"])
@pytest.mark.parametrize("model", list(MODELS))
def test_a_seated_stream_is_bitwise_the_cold_stream(model, chunk, request):
    make, fixture, aux, n_first, n_tail = MODELS[model]
    weights = request.getfixturevalue(fixture)
    rng = np.random.default_rng(11)
    first = rng.integers(0, 96, n_first)
    longer = np.concatenate([first, rng.integers(0, 96, n_tail)])
    store = PrefixKVStore()
    eng = make(weights, chunk, prefix_store=store)
    _through(eng, first, 2)
    assert len(store) == 1
    metrics.reset_decode_counts()
    tokens, logits, stream = _through(eng, longer, 12, keep_prefix=False)
    c = metrics.decode_counts()
    assert c["decode_prefix_seats"] == 1
    assert c["decode_prefix_seat_rows"] == n_first
    assert "decode_state_clears" not in c      # seated, not zeroed
    assert len(store) == 1                     # nothing inserted
    cold_tokens, cold_logits, cold = _through(make(weights, chunk), longer,
                                              12)
    assert tokens == cold_tokens
    assert np.array_equal(logits, cold_logits)
    assert (stream.aux_from, cold.aux_from) == (n_first, 0)
    assert np.array_equal(stream.aux(aux), cold.aux(aux)[n_first:])


def test_with_recurrent_state_only_a_whole_key_hits(sala_weights):
    """A recurrence cannot be rolled back to a shared partial depth: a
    prompt that shares 40 of a stored prompt's 48 tokens misses, one that
    extends all 48 hits, and the prompt itself (no token left to feed)
    misses; a kv-only graph keeps its partial-overlap reuse
    (``tests/test_solar_open2.py``, ``test_glm4_moe_lite.py``)."""
    rng = np.random.default_rng(12)
    first = rng.integers(0, 96, 48)
    store = PrefixKVStore()
    eng = sala.engine(sala_weights, 8, prefix_store=store)
    _through(eng, first, 2)
    assert eng._whole_hits
    for prompt, hit in ((np.concatenate([first[:40], [1, 2, 3]]), 0),
                        (np.concatenate([first, [1, 2, 3]]), 48),
                        (first, 0)):
        assert store.lookup(prompt, whole=True)[0] == hit
    assert store.lookup(np.concatenate([first[:40], [1, 2, 3]]))[0] == 40
    metrics.reset_decode_counts()
    partial = np.concatenate([first[:40], rng.integers(0, 96, 5)])
    tokens, _, stream = _through(eng, partial, 6, keep_prefix=False)
    assert "decode_prefix_seats" not in metrics.decode_counts()
    assert stream.aux_from == 0
    assert tokens == _through(sala.engine(sala_weights, 8), partial, 6)[0]


def test_the_caller_says_which_prompts_are_kept(sala_weights):
    """``DecodeRouter.submit(keep_prefix=)``: ``False`` inserts nothing and
    still hits; the default snapshots every finished prompt, as before."""
    rng = np.random.default_rng(13)
    doc = rng.integers(0, 96, 48).astype(np.int32)
    ask = np.concatenate([doc, rng.integers(0, 96, 6)]).astype(np.int32)
    store = PrefixKVStore()
    metrics.reset_prefix_cache_counts()
    with DecodeRouter(sala.engine(sala_weights, 8,
                                  prefix_store=store)) as router:
        router.submit(ask[:20], 2, keep_prefix=False).result(timeout=120)
        assert len(store) == 0
        router.submit(doc, 2).result(timeout=120)
        assert len(store) == 1
        seated = router.submit(ask, 5, keep_prefix=False)
        tokens = seated.result(timeout=120)
        assert (len(store), seated.aux_from) == (1, 48)
        router.submit(ask, 5).result(timeout=120)
        assert len(store) == 2
    counts = metrics.prefix_cache_counts()
    assert (counts["prefix_cache_hits"], counts["prefix_cache_inserts"]) \
        == (2, 2)
    assert tokens == _through(sala.engine(sala_weights, 8), ask, 5)[0]


def test_an_evicted_snapshot_misses_and_the_stream_is_served_cold(
        sala_weights):
    rng = np.random.default_rng(14)
    docs = [rng.integers(0, 96, 48) for _ in range(3)]
    eng = sala.engine(sala_weights, 8, prefix_store=PrefixKVStore())
    _through(eng, docs[0], 2)
    one = eng.prefix.nbytes
    # the snapshot holds every kind: KV rows, index rows, recurrent state
    kinds = {eng._kinds[n] for n in eng.prefix._entries[
        tuple(docs[0].tolist())].rows}
    assert kinds == {"kv", "index", "recurrent"}
    store = PrefixKVStore(capacity_bytes=2 * one)
    eng = sala.engine(sala_weights, 8, prefix_store=store)
    metrics.reset_prefix_cache_counts()
    for doc in docs:
        _through(eng, doc, 2)
    assert len(store) == 2
    assert metrics.prefix_cache_counts()["prefix_cache_evictions"] == 1
    ask = [np.concatenate([d, [5, 6, 7]]) for d in docs]
    assert [store.lookup(a, whole=True)[0] for a in ask] == [0, 48, 48]
    tokens, _, stream = _through(eng, ask[0], 6, keep_prefix=False)
    assert stream.aux_from == 0
    assert tokens == _through(sala.engine(sala_weights, 8), ask[0], 6)[0]


def test_ring_state_is_still_refused():
    """The refusal is made before a weight is looked at."""
    import test_phi4flash as phi4
    with pytest.raises(ValueError, match="ring written at position"):
        phi4._engine(None, 8, prefix_store=PrefixKVStore())
