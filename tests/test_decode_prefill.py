"""ISSUE 18 acceptance: chunked prefill + shared-prefix KV reuse —
bitwise parity chunked-vs-incremental-vs-full-re-prefill across chunk
buckets and ragged prompt lengths, mid-chunk EOS, prefix-cache
hit/miss/evict parity, the compile-once counter formula over the
(batch, chunk, len) bucket-key axis, the pure-prefill logits-D2H skip,
the ``ttft`` latency label, and the fleet door's prompt-length-aware
deadline gate over ``DecodeRouter.pending_steps``.
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from hetu_tpu import metrics                               # noqa: E402
from hetu_tpu.models import (GPT2Config,                   # noqa: E402
                             gpt2_decode_chunked_graph, gpt2_decode_graph)
from hetu_tpu.models.gpt2 import gpt2_lm_graph             # noqa: E402
from hetu_tpu.profiler import HetuProfiler                 # noqa: E402
from hetu_tpu.serving import (DecodeEngine, DecodeRouter,  # noqa: E402
                              FrontDoor, InferenceExecutor, PrefixKVStore,
                              ServeRejected)
from hetu_tpu.serving.decode import _DecodeRequest         # noqa: E402

_CFG = GPT2Config.tiny(n_positions=64, batch_size=1, seq_len=16)
_MAX_LEN = 16


@pytest.fixture(scope="module")
def graphs():
    """One tiny one-token graph + one chunked graph shared by the
    module (weight init is seed-deterministic per graph; engines load
    the chunked executor FROM the primary's params)."""
    return (gpt2_decode_graph(_CFG, max_len=_MAX_LEN),
            gpt2_decode_chunked_graph(_CFG, max_len=_MAX_LEN))


def _engine(graphs, chunked=True, **kw):
    (feeds, logits, caches, _), cg = graphs
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", _MAX_LEN)
    if chunked:
        kw.setdefault("chunked", (cg[0], cg[1], cg[2]))
    return DecodeEngine(feeds, logits, caches, seed=0, **kw)


def _run(eng, prompt, max_new=6, eos_id=None):
    """Single-sequence decode directly on the engine; returns (tokens,
    engine steps taken)."""
    req = _DecodeRequest(np.asarray(prompt, np.int32), max_new, eos_id,
                         None)
    eng.join(req)
    steps = 0
    while eng.active:
        eng.step()
        steps += 1
    return req.stream.result(timeout=60), steps


# ----------------------------------------------------- bitwise parity

def test_chunked_vs_incremental_vs_full_reprefill_parity(graphs):
    """The non-negotiable invariant: chunked ingestion, token-by-token
    ingestion, and full-sequence greedy re-prefill produce the IDENTICAL
    token stream for every ragged prompt length and chunk bucket."""
    ref = _engine(graphs, chunked=False, max_slots=2)
    w = {ref.iex.var_names[n]: np.asarray(ref.iex.params[ref.iex._k(n)])
         for n in ref.iex.var_nodes}
    f2, _loss, logits2 = gpt2_lm_graph(_CFG)
    iex_full = InferenceExecutor([logits2], weights=w, buckets=(1,),
                                 seed=0, validate="off")
    fn_full = iex_full.compiled(1)

    def full_greedy(prompt, max_new):
        seq, out = list(prompt), []
        for _ in range(max_new):
            ids = np.zeros((1, _CFG.seq_len), np.int32)
            ids[0, :len(seq)] = seq
            lg = np.asarray(fn_full(
                iex_full.params,
                {iex_full._k(f2["input_ids"]): ids,
                 iex_full._k(f2["labels"]): ids})[0])
            tok = int(np.argmax(lg[len(seq) - 1]))
            seq.append(tok)
            out.append(tok)
        return out

    rng = np.random.RandomState(7)
    prompts = [rng.randint(1, _CFG.vocab_size, p).tolist()
               for p in (1, 2, 3, 5, 8, 11)]
    full = [full_greedy(p, 4) for p in prompts]
    incr = [_run(ref, p, 4) for p in prompts]
    for mc in (2, 8):
        eng = _engine(graphs, max_slots=2, max_chunk=mc)
        for p, f, (itoks, isteps) in zip(prompts, full, incr):
            ctoks, csteps = _run(eng, p, 4)
            assert ctoks == itoks == f, \
                f"parity broke: chunk {mc}, prompt len {len(p)}"
            # chunked ingestion never takes MORE steps, and strictly
            # fewer once the prompt spans multiple chunks
            assert csteps <= isteps
            if len(p) > mc:
                assert csteps < isteps


def test_mixed_batch_prefill_with_generating_rows(graphs):
    """Sarathi-style mixed steps: a long prompt joining mid-generation
    rides chunked steps WITH the already-generating row, and neither
    stream's tokens change (bitwise batch-composition independence)."""
    rng = np.random.RandomState(3)
    p_short = rng.randint(1, _CFG.vocab_size, 2).tolist()
    p_long = rng.randint(1, _CFG.vocab_size, 9).tolist()
    # solo references
    eng = _engine(graphs, max_slots=2, max_chunk=4)
    solo_short, _ = _run(eng, p_short, 6)
    solo_long, _ = _run(eng, p_long, 4)
    # mixed: short joins first and generates; long joins at step 2
    eng2 = _engine(graphs, max_slots=2, max_chunk=4)
    r1 = _DecodeRequest(np.asarray(p_short, np.int32), 6, None, None)
    r2 = _DecodeRequest(np.asarray(p_long, np.int32), 4, None, None)
    eng2.join(r1)
    eng2.step()
    eng2.step()
    eng2.join(r2)
    while eng2.active:
        eng2.step()
    assert r1.stream.result(timeout=60) == solo_short
    assert r2.stream.result(timeout=60) == solo_long


def test_mid_chunk_eos(graphs):
    """A prompt whose remainder ends mid-chunk emits its first token in
    that same chunked step; when that token is EOS the sequence leaves
    immediately with exactly one token."""
    rng = np.random.RandomState(11)
    prompt = rng.randint(1, _CFG.vocab_size, 5).tolist()
    eng = _engine(graphs, max_slots=2, max_chunk=8)
    cold, _ = _run(eng, prompt, 6)
    eng2 = _engine(graphs, max_slots=2, max_chunk=8)
    toks, steps = _run(eng2, prompt, 6, eos_id=cold[0])
    assert toks == [cold[0]]
    assert steps == 1            # one chunked step: prefill 5 + emit EOS
    assert eng2.active == 0


# ------------------------------------------------- shared-prefix KV reuse

def test_prefix_cache_hit_bitwise_equal_and_counted(graphs):
    """A prefix-cache hit seats with rows pre-filled and skips prefill
    (counted), and its token stream is bitwise-equal to the cold path."""
    metrics.reset_prefix_cache_counts()
    metrics.reset_decode_counts()
    store = PrefixKVStore(capacity_bytes=1 << 20)
    eng = _engine(graphs, max_slots=2, max_chunk=4, prefix_store=store)
    rng = np.random.RandomState(5)
    prompt = rng.randint(1, _CFG.vocab_size, 8).tolist()
    cold, _ = _run(eng, prompt, 5)
    pc = metrics.prefix_cache_counts()
    assert pc["prefix_cache_misses"] == 1
    assert pc["prefix_cache_inserts"] == 1
    pre = metrics.decode_counts().get("decode_prefill_rows", 0)
    hit, _ = _run(eng, prompt, 5)
    assert hit == cold, "prefix hit diverged from the cold path"
    pc = metrics.prefix_cache_counts()
    assert pc["prefix_cache_hits"] == 1
    # the stored prefix covers len-1 tokens (one must still be fed)
    assert pc["prefix_cache_hit_rows"] == len(prompt) - 1
    # the hit run did ZERO prefill rows: ingestion skipped outright
    assert metrics.decode_counts().get("decode_prefill_rows", 0) == pre
    # partial overlap: first 4 tokens shared, rest fresh — still
    # bitwise-equal to ITS OWN cold decode
    p2 = prompt[:4] + rng.randint(1, _CFG.vocab_size, 3).tolist()
    warm2, _ = _run(eng, p2, 5)
    eng_cold = _engine(graphs, max_slots=2, max_chunk=4)
    cold2, _ = _run(eng_cold, p2, 5)
    assert warm2 == cold2
    assert metrics.prefix_cache_counts()["prefix_cache_hits"] == 2


#: what the parent commit (f1935ec: (B, H, L, D) slabs) served for the
#: scenario below, every combination alike — recorded from its checkout
_PARENT_STREAMS = [[321, 321, 92, 321, 92, 210], [321, 321, 92, 321, 92, 210],
                   [67, 67, 67, 67, 67], [321, 356, 461, 147],
                   [321, 321, 321, 321, 321, 321]]


@pytest.mark.parametrize("store", [False, True], ids=["cold", "prefix_hit"])
@pytest.mark.parametrize("chunked", [False, True],
                         ids=["one_token", "chunked"])
def test_router_streams_are_the_parents_token_for_token(graphs, chunked,
                                                        store):
    """ISSUE 26: the slab format is invisible in what is served.  One
    lone request, then four together (the first prompt again, a
    one-token prompt, a prompt sharing the first one's five leading
    tokens, a short one) through ``DecodeRouter``: the streams are the
    parent's on the same seed, one-token and chunked ingestion, with
    and without ``PrefixKVStore`` hits (whose snapshots stay external
    (H, m, D) rows)."""
    metrics.reset_prefix_cache_counts()
    rng = np.random.RandomState(26)
    prompts = [rng.randint(1, _CFG.vocab_size, n).tolist()
               for n in (9, 1, 6, 3)]
    kw = {"prefix_store": PrefixKVStore(capacity_bytes=1 << 20)} \
        if store else {}
    if chunked:
        kw["max_chunk"] = 4
    eng = _engine(graphs, chunked=chunked, **kw)
    with DecodeRouter(eng) as router:
        first = router.submit(prompts[0], max_new_tokens=6).result(
            timeout=120)
        streams = [router.submit(p, max_new_tokens=n) for p, n in (
            (prompts[0], 6), (prompts[1], 5),
            (prompts[0][:5] + prompts[2], 4), (prompts[3], 6))]
        got = [first] + [s.result(timeout=120) for s in streams]
    assert got == _PARENT_STREAMS
    if store:
        pc = metrics.prefix_cache_counts()
        assert pc["prefix_cache_hits"] >= 2
        assert pc["prefix_cache_hit_rows"] >= 8 + 5
        rows = next(iter(kw["prefix_store"]._entries.values())).rows
        assert all(r.shape[0::2] == (2, 64) for r in rows.values())


def test_prefix_cache_lru_eviction_bound(graphs):
    """Capacity is a hard byte bound: inserts past it evict the
    least-recently-used entry (counted, bytes freed), and an evicted
    prefix simply misses — never wrong tokens."""
    metrics.reset_prefix_cache_counts()
    # one 8-token snapshot: 2 layers * 2 caches * (2, 8, 64) f32
    one = 2 * 2 * _CFG.n_head * 8 * (_CFG.n_embd // _CFG.n_head) * 4
    store = PrefixKVStore(capacity_bytes=int(one * 2.5))
    eng = _engine(graphs, max_slots=2, max_chunk=4, prefix_store=store)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, _CFG.vocab_size, 8).tolist()
               for _ in range(4)]
    colds = [_run(eng, p, 3)[0] for p in prompts]
    pc = metrics.prefix_cache_counts()
    assert pc["prefix_cache_inserts"] == 4
    assert pc["prefix_cache_evictions"] >= 2
    assert pc["prefix_cache_evicted_bytes"] > 0
    assert store.nbytes <= store.capacity_bytes
    # the evicted first prompt re-decodes bitwise-identically (miss,
    # re-inserted), while a surviving entry still hits
    again, _ = _run(eng, prompts[0], 3)
    assert again == colds[0]


# ------------------------------- compile-once over the chunk-bucket axis

def test_compile_once_over_chunk_bucket_axis(graphs):
    """The PR 16 compile-once formula extends over the chunk axis: one
    plan-cache miss per distinct bucket key — (batch, len) pairs for
    one-token steps, (batch, chunk, len) triples for chunked steps —
    one real compile or cross-rebuild serve hit per miss, and every
    other step a plan-cache hit."""
    (feeds, logits, caches, _), cg = graphs
    metrics.reset_all()
    eng = DecodeEngine(feeds, logits, caches, max_slots=4,
                       max_len=_MAX_LEN, seed=0,
                       chunked=(cg[0], cg[1], cg[2]), max_chunk=4)
    rng = np.random.RandomState(0)
    with DecodeRouter(eng, queue_limit=64) as router:
        streams = []
        for _ in range(24):
            plen = int(rng.zipf(1.8)) % 7 + 1
            prompt = rng.randint(1, _CFG.vocab_size, plen)
            streams.append(router.submit(prompt, max_new_tokens=3))
        for s in streams:
            s.result(timeout=300)
    decode = metrics.decode_counts()
    serve = metrics.serve_counts()
    rp = metrics.run_plan_counts()
    steps = decode["decode_steps"]
    keys = rp.get("plan_cache_miss", 0)
    assert decode.get("decode_prefill_steps", 0) > 0, \
        "stream never exercised the chunked entry"
    assert steps > keys, "stream too short to show a steady state"
    assert serve["serve_bucket_compiles"] + \
        metrics.step_cache_counts().get("step_cache_serve_hit", 0) == keys
    assert rp["plan_cache_hit"] == steps - keys
    # the ladders bound the keys: (batch, len) pairs + (batch, chunk,
    # len) triples with chunk > 1
    bound = len(eng.batch_ladder) * len(eng.len_ladder) \
        * len(eng.chunk_ladder)
    assert keys <= bound


# --------------------------------------------- satellite: logits D2H skip

def test_pure_prefill_steps_skip_logits_fetch(graphs):
    """One-token ingestion of a P-token prompt pays P-1 steps where no
    row reads logits — each now skips the (batch, vocab) D2H copy and
    counts ``decode_logits_skipped``; chunked ingestion of the same
    prompt emits in its first step (nothing to skip)."""
    metrics.reset_decode_counts()
    eng = _engine(graphs, chunked=False, max_slots=2)
    prompt = [3, 7, 11, 2, 5, 9]
    _run(eng, prompt, 2)
    c = metrics.decode_counts()
    assert c["decode_logits_skipped"] == len(prompt) - 1
    metrics.reset_decode_counts()
    eng2 = _engine(graphs, max_slots=2, max_chunk=8)
    toks2, _ = _run(eng2, prompt, 2)
    c2 = metrics.decode_counts()
    assert c2["decode_prefill_steps"] == 1
    assert c2["decode_prefill_steps_saved"] == len(prompt) - 1
    assert c2.get("decode_logits_skipped", 0) == 0


# ------------------------------------------------- satellite: ttft label

def test_ttft_label_in_latency_stats(graphs):
    """Every stream records exactly one ``ttft`` observation (admission
    -> first generated token), surfaced via
    ``HetuProfiler.latency_stats()`` beside the steady-state ``token``
    gap."""
    metrics.reset_decode_counts()
    eng = _engine(graphs, max_slots=4, max_chunk=4)
    with DecodeRouter(eng, queue_limit=16) as router:
        streams = [router.submit([3 + i, 5, 7], max_new_tokens=3)
                   for i in range(5)]
        for s in streams:
            s.result(timeout=120)
    lat = HetuProfiler.latency_stats()["decode_latency_us"]
    assert "ttft" in lat, sorted(lat)
    assert lat["ttft"]["count"] == 5
    assert lat["token"]["count"] == 15


# ------------------------- satellite: fleet deadline gate on pending_steps

def test_pending_steps_folds_prompt_length(graphs):
    """``DecodeRouter.pending_steps`` charges a queued prompt
    ceil(prompt_len / chunk_top) steps — the quantity the fleet door's
    drain estimate needs — while ``pending`` (the load signal) still
    counts sequences."""
    eng = _engine(graphs, max_slots=2, max_chunk=4)
    router = DecodeRouter(eng, queue_limit=8, start=False)
    try:
        router.submit([1] * 10, max_new_tokens=2)   # ceil(10/4) = 3
        router.submit([2] * 3, max_new_tokens=2)    # ceil(3/4) = 1
        assert router.pending == 2
        assert router.pending_steps == 4
    finally:
        router.close()


def test_fleet_door_deadline_gate_counts_prefill_steps(graphs):
    """The door's deadline gate folds prompt length in: a backlog of
    long prompts rejects a tight-deadline request that the old
    one-step-per-request estimate would have admitted (and doomed)."""
    (feeds, logits, caches, _), _cg = graphs
    routers = {}

    def mk(idx):
        eng = DecodeEngine(feeds, logits, caches, seed=0, max_slots=2,
                           max_len=_MAX_LEN)
        # start=False: the queue accumulates, so the estimate is
        # deterministic at submit time
        routers[idx] = DecodeRouter(eng, queue_limit=64, start=False,
                                    name=f"d{idx}")
        return routers[idx]

    door = FrontDoor(mk, 1, health_every_ms=1e9)
    try:
        for _ in range(2):
            door.submit([1] * 12, max_new_tokens=2)
        rep = door._replicas[0]
        assert rep.router.pending == 2
        # old estimate: (2 // 1 + 1) * 1.0ms = 3ms fits a 10ms deadline;
        # pending_steps: (12 + 12 queued prefill steps + 1) * 1.0ms
        # does not — the doomed request is rejected AT THE DOOR
        assert rep.router.pending_steps == 24
        with pytest.raises(ServeRejected) as ei:
            door.submit([5, 6], max_new_tokens=1, deadline_ms=10.0)
        assert ei.value.reason == "deadline"
        # a deadline the true backlog CAN meet still admits
        s = door.submit([5, 6], max_new_tokens=1, deadline_ms=60000.0)
        for r in routers.values():
            r.start()
        assert len(s.result(timeout=120)) == 1
    finally:
        door.close()


# ------------------------------------------------------- slow scale proof

@pytest.mark.slow
def test_chunked_prefill_scale_proof(graphs):
    """Scale leg: long prompts near the cache cap, every chunk bucket in
    the ladder exercised, parity against token-by-token ingestion, and
    the step count collapses by ~chunk_top."""
    (feeds, logits, caches, _), _cg = graphs
    cfg = GPT2Config.tiny(n_positions=256, batch_size=1, seq_len=16)
    g1 = gpt2_decode_graph(cfg, max_len=128)
    g2 = gpt2_decode_chunked_graph(cfg, max_len=128)
    ref = DecodeEngine(g1[0], g1[1], g1[2], seed=0, max_slots=2,
                       max_len=128)
    eng = DecodeEngine(g1[0], g1[1], g1[2], seed=0, max_slots=2,
                       max_len=128, chunked=(g2[0], g2[1], g2[2]),
                       max_chunk=32)
    rng = np.random.RandomState(1)
    for plen in (17, 47, 96):
        p = rng.randint(1, cfg.vocab_size, plen).tolist()
        it, isteps = _run(ref, p, 4)
        ct, csteps = _run(eng, p, 4)
        assert ct == it
        assert csteps <= (plen + 31) // 32 + 4 + 1
