"""ISSUE 25: the decode step and the router's loop account for their own
time.  Each boundary of ``DecodeEngine.step`` is stamped once and feeds an
always-on counter, a ``jax.profiler.TraceAnnotation`` and (``HETU_TRACE=1``)
the ``obs`` ring: the counters add up to the wall time of the steps, the
annotations nest in the profiler's own trace, the ring holds the same names.
"""
import collections
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from hetu_tpu import metrics, obs                          # noqa: E402
from hetu_tpu.models import (GPT2Config,                   # noqa: E402
                             gpt2_decode_chunked_graph, gpt2_decode_graph)
from hetu_tpu.obs.trace import Phases                      # noqa: E402
from hetu_tpu.serving import DecodeEngine, DecodeRouter    # noqa: E402
from hetu_tpu.serving.decode import _DecodeRequest         # noqa: E402

_CFG = GPT2Config.tiny(n_embd=256, n_layer=4, n_head=4, vocab_size=4096,
                       n_positions=64, batch_size=1, seq_len=16)
_MAX_LEN = 16
PHASES = ("plan", "feed", "dispatch", "wait", "readback", "host")
STEP_KINDS = [f"decode_step_{p}_us" for p in PHASES]


@pytest.fixture(scope="module")
def graphs():
    return (gpt2_decode_graph(_CFG, max_len=_MAX_LEN),
            gpt2_decode_chunked_graph(_CFG, max_len=_MAX_LEN))


def _engine(graphs, **kw):
    (feeds, logits, caches, _), cg = graphs
    return DecodeEngine(feeds, logits, caches, seed=0, max_slots=2,
                        max_len=_MAX_LEN, chunked=(cg[0], cg[1], cg[2]),
                        max_chunk=4, **kw)


def _join(eng, prompt, max_new):
    eng.join(_DecodeRequest(np.asarray(prompt, np.int32), max_new, None,
                            None))


def _drive(eng, script):
    """``script``: [(prompts to join, steps to make)].  Returns the wall
    time of the ``step()`` calls (ns) and, per step, the batch bucket, the
    chunk the engine picked and whether the logits were skipped."""
    wall, seen = 0, []
    picked = []
    pick = eng._pick_chunk
    eng._pick_chunk = lambda active: picked.append(pick(active)) or picked[-1]
    try:
        for prompts, steps in script:
            for p, n in prompts:
                _join(eng, p, n)
            for _ in range(steps):
                skipped = metrics.decode_counts().get(
                    "decode_logits_skipped", 0)
                assert eng.active
                t = time.perf_counter_ns()
                eng.step()
                wall += time.perf_counter_ns() - t
                seen.append((eng.bb, picked[-1], metrics.decode_counts().get(
                    "decode_logits_skipped", 0) - skipped))
    finally:
        del eng._pick_chunk
    return wall, seen


# a lone 9-token prompt: two pure-prefill chunks of 4 (logits skipped), then
# one-token steps; a second prompt joins mid-generation (batch bucket 1 -> 2)
# and goes in by chunks beside the generating row
_SCRIPT = [([(list(range(1, 10)), 6)], 4),
           ([([3, 4, 5, 6, 7, 8], 3)], 4)]
_FORCED = [(1, 4, 1), (1, 4, 1), (1, 1, 0), (1, 1, 0),
           (2, 4, 0), (2, 2, 0), (2, 1, 0), (2, 1, 0)]


def test_phase_counters_add_up_to_the_steps_wall_time(graphs, monkeypatch):
    """The counters against what the program itself stamped: the phases of
    a step add up to the span from its first boundary to its last, in the
    whole microseconds of the same stamps — exactly.  (An outside clock
    around ``step()`` also holds the call, the annotation's entry and
    whatever the scheduler does to the thread between the two pairs of
    stamps; it only bounds the sum from above.)"""
    from hetu_tpu.serving import decode as decode_mod
    made = []

    class Recording(Phases):
        __slots__ = ("first",)

        def mark(self, phase):
            now = super().mark(phase)
            if phase == "plan":
                self.first = now
                made.append(self)
            return now

    eng = _engine(graphs)
    _drive(eng, _SCRIPT)                 # compile every program first
    assert eng.idle
    eng = _engine(graphs)
    monkeypatch.setattr(decode_mod, "_Phases", Recording)
    metrics.reset_decode_counts()
    wall, seen = _drive(eng, _SCRIPT)
    assert eng.idle and seen == _FORCED
    c = metrics.decode_counts()
    assert c["decode_steps"] == len(_FORCED) == len(made)
    # every phase ran, so every counter is positive; wait and read-back only
    # on the steps that read their logits, which the others cannot show here
    assert all(c[k] > 0 for k in STEP_KINDS), c
    phases_us = sum(c[k] for k in STEP_KINDS)
    assert phases_us == sum(ph.t1 // 1000 - ph.first // 1000 for ph in made)
    assert all(ph.t0 <= ph.first for ph in made)
    assert phases_us <= wall / 1e3
    # the step histogram keeps its boundaries, feed ... host, in fractional
    # microseconds of the same stamps: under one apart a step
    step = metrics.decode_latency_stats()["step"]
    assert step["count"] == len(_FORCED)
    assert abs(phases_us - c["decode_step_plan_us"] - step["sum"]) \
        < len(_FORCED)
    # the chunk accounting, from the (batch bucket, chunk) of each step
    assert c["decode_padded_row_tokens"] == sum(b * k for b, k, _ in _FORCED)
    assert c["decode_chunk_width"] == sum(k for _, k, _ in _FORCED if k > 1)
    assert c["decode_prefill_steps"] == sum(k > 1 for _, k, _ in _FORCED)
    assert c["decode_logits_skipped"] == 2
    # what the padded row-tokens held: every prompt and generated token once
    assert c["decode_prefill_rows"] + c["decode_generate_rows"] \
        == (9 - 1) + 6 + (6 - 1) + 3
    assert c["decode_join_wait_us"] >= 0 and c["decode_joins"] == 2


def test_a_step_that_skips_its_logits_has_no_wait_and_no_readback(graphs):
    eng = _engine(graphs)
    _join(eng, list(range(1, 10)), 2)
    metrics.reset_decode_counts()
    eng.step()                           # a pure-prefill chunk
    c = metrics.decode_counts()
    assert c["decode_logits_skipped"] == 1
    assert "decode_step_wait_us" not in c
    assert "decode_step_readback_us" not in c
    assert all(k in c for k in ("decode_step_plan_us", "decode_step_feed_us",
                                "decode_step_dispatch_us",
                                "decode_step_host_us"))
    while eng.active:
        eng.step()
    c = metrics.decode_counts()
    assert c["decode_step_wait_us"] > 0 and c["decode_step_readback_us"] > 0


def _serve_two(eng):
    with DecodeRouter(eng) as router:
        s1 = router.submit(list(range(1, 10)), max_new_tokens=4)
        s2 = router.submit([3, 4], max_new_tokens=4)
        s1.result(timeout=120)
        s2.result(timeout=120)


def _inside(child, parent):
    return parent[1] <= child[1] and \
        child[1] + child[2] <= parent[1] + parent[2]


def test_phases_nest_in_the_profilers_own_trace(graphs, tmp_path):
    """Under a ``jax.profiler`` session (nothing else switched on) the
    benchmark's reducer finds ``decode.step`` with its six phases nested in
    time on one thread, and ``decode.between`` between consecutive steps."""
    import jax
    from benchmarks import trace_reduce
    eng = _engine(graphs)
    _serve_two(_engine(graphs))          # compile outside the session
    assert not obs.enabled()
    obs.clear_trace()                    # whatever this worker's earlier files left
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve_two(eng)
    finally:
        jax.profiler.stop_trace()
    planes = trace_reduce.load(trace_reduce.find_xplane(str(tmp_path)))
    lines = [evs for lines in planes.values() for evs in lines.values()
             if any(n == "decode.step" for n, _, _ in evs)]
    assert len(lines) == 1               # one thread: the router's loop
    evs = sorted(lines[0], key=lambda e: e[1])
    steps = [e for e in evs if e[0] == "decode.step"]
    assert len(steps) >= 4
    for p in PHASES:
        kids = [e for e in evs if e[0] == "decode.step." + p]
        assert kids and all(any(_inside(k, s) for s in steps) for k in kids)
    # a step that read its logits holds all six, in order, touching
    full = next(s for s in steps if sum(
        _inside(e, s) for e in evs if e[0].startswith("decode.step.")) == 6)
    kids = [e for e in evs if e[0].startswith("decode.step.")
            and _inside(e, full)]
    assert [k[0].rsplit(".", 1)[1] for k in kids] == list(PHASES)
    assert all(a[1] + a[2] <= b[1] + 1e3 for a, b in zip(kids, kids[1:]))
    # between two consecutive steps lies one decode.between
    gaps = [e for e in evs if e[0] == "decode.between"]
    assert gaps
    for g in gaps:
        before = max((s for s in steps if s[1] + s[2] <= g[1] + 1e3),
                     key=lambda s: s[1])
        after = min((s for s in steps if s[1] >= g[1] + g[2] - 1e3),
                    key=lambda s: s[1])
        assert steps.index(after) == steps.index(before) + 1
    # no session, tracer off: the ring holds nothing of it
    assert obs.TRACER.records() == []


def test_the_ring_holds_the_same_names_when_the_tracer_is_on(graphs):
    eng = _engine(graphs)
    obs.enable(False)
    obs.clear_trace()
    _serve_two(_engine(graphs))
    assert obs.TRACER.records() == []    # off: nothing recorded
    obs.enable(True)
    try:
        _serve_two(eng)
    finally:
        obs.enable(False)
    evs = [e for e in obs.trace_events() if e.get("ph") == "X"]
    obs.clear_trace()
    names = {e["name"] for e in evs}
    assert {"decode.step", "decode.between"} | {
        "decode.step." + p for p in PHASES} <= names
    steps = [e for e in evs if e["name"] == "decode.step"]
    assert all({"batch", "len", "chunk", "rows", "emitted"} <= set(e["args"])
               for e in steps)
    # the phases are children of the step span: enclosed in time, same thread
    for e in evs:
        if e["name"].startswith("decode.step."):
            assert any(s["tid"] == e["tid"] and s["ts"] <= e["ts"] and
                       e["ts"] + e["dur"] <= s["ts"] + s["dur"] + 1e-3
                       for s in steps), e


def test_a_router_iteration_is_one_span_launch_then_collect(graphs):
    """ISSUE 32: the router launches step n+1 and THEN collects step n,
    inside ONE ``decode.step`` span: its ``plan`` / ``feed`` / ``dispatch``
    are of the step launched, its ``wait`` / ``readback`` / ``host`` of the
    step launched the iteration before.  A run from an idle engine opens
    with a launch alone and ends with a collect alone: one span more than
    steps, one ``step`` sample a span, every launch but the first made
    ahead, and no second span of that name for any step."""
    eng = _engine(graphs)
    _serve_two(_engine(graphs))          # compile first
    obs.enable(False)
    obs.clear_trace()
    metrics.reset_decode_counts()
    obs.enable(True)
    try:
        with DecodeRouter(eng) as router:
            got = router.submit([3, 4], max_new_tokens=6).result(timeout=120)
            assert router.drain(timeout=60)
    finally:
        obs.enable(False)
    evs = [e for e in obs.trace_events() if e.get("ph") == "X"]
    obs.clear_trace()
    steps = sorted((e for e in evs if e["name"] == "decode.step"),
                   key=lambda e: e["ts"])
    kids = [[k["name"].rsplit(".", 1)[1] for k in sorted(
        (e for e in evs if e["name"].startswith("decode.step.")
         and s["ts"] <= e["ts"] and e["ts"] + e["dur"]
         <= s["ts"] + s["dur"] + 1e-3), key=lambda e: e["ts"])]
        for s in steps]
    # the prompt's two tokens in one chunked step, then five one-token steps
    assert len(got) == 6
    assert kids == [list(PHASES[:3])] + [list(PHASES)] * 5 + [list(PHASES[3:])]
    assert [s["args"]["rows"] for s in steps] == [1] * 6 + [0]
    assert [s["args"]["emitted"] for s in steps] == [0] + [1] * 6
    c = metrics.decode_counts()
    assert c["decode_steps"] == 6 and c["decode_launches_ahead"] == 5
    assert metrics.decode_latency_stats()["step"]["count"] == len(steps) == 7
    # each phase ran once a STEP, whichever span held it
    marked = collections.Counter(k for ks in kids for k in ks)
    assert set(marked.values()) == {6} and set(marked) == set(PHASES)


def test_phases_helper_counts_whole_microseconds_that_add_up():
    got = {}
    kinds = {"a": ("t.a", "t_a_us"), "b": ("t.b", "t_b_us")}

    def record(kind, n):
        got[kind] = got.get(kind, 0) + n

    with Phases("t", record, kinds, total="t_us") as ph:
        ph.mark("a")
        time.sleep(0.002)
        ph.mark("b")
        time.sleep(0.001)
        ph.mark("a")
    assert ph.t1 is not None and got["t_us"] >= 3000
    # differences of truncated stamps: the phases add up to the whole, less
    # what lay before the first mark
    assert 0 <= got["t_us"] - got["t_a_us"] - got["t_b_us"] <= 50
    ph.close()                           # closing twice counts once
    assert got["t_us"] == ph.t1 // 1000 - ph.t0 // 1000
    # an interval without phases: the whole alone
    whole = Phases("t", record, total="q_us")
    time.sleep(0.001)
    whole.close()
    assert got["q_us"] >= 1000
