"""ISSUE 39: every program the process compiles leaves a record — who
asked for it, its trace / lower / backend seconds, read from the cache
or not, stored or not — and the program's own set-up phases are counted.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import hetu_tpu as ht                                      # noqa: E402
from hetu_tpu import metrics, obs                          # noqa: E402
from hetu_tpu.graph import step_cache                      # noqa: E402
from hetu_tpu.graph.executor import configure_compile_cache  # noqa: E402
from hetu_tpu.models import GPT2Config, gpt2_decode_graph  # noqa: E402
from hetu_tpu.obs import compile_log                       # noqa: E402
from hetu_tpu.profiler import HetuProfiler                 # noqa: E402
from hetu_tpu.serving import (DecodeEngine, DecodeRouter,  # noqa: E402
                              InferenceExecutor)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
WRITTEN = "/jax/compilation_cache/cache_misses"
READ = "/jax/compilation_cache/cache_retrieval_time_sec"
SAVED = "/jax/compilation_cache/compile_time_saved_sec"

_CFG = GPT2Config.tiny(n_positions=64, batch_size=1, seq_len=16)


@pytest.fixture(autouse=True)
def _fresh():
    """The listener is the process's (``configure_compile_cache``
    registers it once); records, counters and jitted steps are dropped
    so that every test compiles what it asks for."""
    configure_compile_cache()
    obs.enable(False)
    obs.clear_trace()
    step_cache.clear()
    compile_log.clear()
    metrics.reset_all()
    yield
    obs.enable(False)
    obs.clear_trace()


def _emit(name, *, t0=1000.0, trace=0.25, lower=0.5, backend=2.0,
          inside=()):
    """The events jax emits for one program, in jax's order: a jitted
    helper's trace closes inside the program's; the cache's events carry
    no name and fall inside the backend interval."""
    monitoring.record_event_time_span(TRACE, t0 + 0.01, t0 + 0.02,
                                      fun_name="helper")
    monitoring.record_event_time_span(TRACE, t0, t0 + trace, fun_name=name)
    t1 = t0 + trace
    monitoring.record_event_time_span(LOWER, t1, t1 + lower,
                                      fun_name=f"jit({name})")
    for event, *value in inside:
        if value:
            monitoring.record_event_duration_secs(event, *value)
        else:
            monitoring.record_event(event)
    t2 = t1 + lower
    monitoring.record_event_time_span(BACKEND, t2, t2 + backend,
                                      fun_name=f"jit({name})")
    return HetuProfiler.compile_log()[-1]


CASES = {
    # name, cache on by jax.config, events inside the backend interval
    # -> owner, program, cache, stored, counters beside the four times
    "hit": ("decode:b16:c1:l768", True,
            [(HIT,), (SAVED, 5.0), (READ, 0.4)],
            ("decode", "b16:c1:l768", "hit", False),
            {"cache_hits": 1, "cache_read_us": 400000}),
    "stored_miss": ("train:default", True, [(WRITTEN,)],
                    ("train", "default", "miss", True),
                    {"cache_misses": 1}),
    "unstored_miss": ("serve:b8", True, [],
                      ("serve", "b8", "miss", False),
                      {"cache_misses": 1, "unstored": 1,
                       "unstored_us": 2000000}),
    "cache_off": ("decode:b4:c32:l128", False, [],
                  ("decode", "b4:c32:l128", "off", False), {}),
    "unnamed_jit": ("<lambda>", False, [],
                    ("other", "<lambda>", "off", False), {}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_synthetic_events_fold_into_one_record(case, monkeypatch):
    name, cache_on, inside, want, extra = CASES[case]
    monkeypatch.setattr(compile_log, "_cache_on", lambda: cache_on)
    rec = _emit(name, inside=inside)
    owner, program, cache, stored = want
    assert (rec["owner"], rec["program"], rec["cache"], rec["stored"]) \
        == want
    # the helper's trace lies inside the program's and is not added to it
    assert (rec["trace_us"], rec["lower_us"], rec["backend_us"]) \
        == (250000, 500000, 2000000)
    assert rec["t_end"] == pytest.approx(1002.75)
    assert rec["saved_us"] == (5000000 if case == "hit" else 0)
    assert len(HetuProfiler.compile_log()) == 1
    want_counts = {"programs": 1, "trace_us": 250000, "lower_us": 500000,
                   "backend_us": 2000000, **extra}
    assert HetuProfiler.compile_counters() == {
        f"{owner}:{k}": v for k, v in want_counts.items()}
    assert HetuProfiler.all_counters()["compile"] \
        == obs.metrics_dump()["counters"]["compile"]
    # only a decode program's seconds are a decode step's
    compiled = metrics.decode_counts().get("decode_step_compile_us", 0)
    assert compiled == (2750000 if owner == "decode" else 0)


def test_a_trace_that_was_never_compiled_is_nobodys():
    """``.lower()`` without ``.compile()``, ``make_jaxpr``: the interval
    stays on the thread and the next program does not inherit it."""
    monitoring.record_event_time_span(TRACE, 10.0, 14.0, fun_name="orphan")
    monitoring.record_event_time_span(LOWER, 14.0, 15.0,
                                      fun_name="jit(orphan)")
    rec = _emit("train:default")
    assert (rec["trace_us"], rec["lower_us"]) == (250000, 500000)
    # and the thread holds nothing over
    rec = _emit("<lambda>", trace=0.5)
    assert rec["trace_us"] == 500000


def test_a_helper_compiled_inside_a_lowering_takes_nothing_away():
    """On the TPU the lowering of a decode program runs small jitted
    helpers eagerly: their backend intervals close between the program's
    trace and its own, on the same thread (my chip run, PR 39: the chat
    cell's one-token program read ``trace_us`` 0 until this held)."""
    monitoring.record_event_time_span(TRACE, 10.0, 11.0,
                                      fun_name="decode:b16:c1:l768")
    inner = _emit("helper_in_lowering", t0=11.1, trace=0.01, lower=0.01,
                  backend=0.05)
    assert inner["owner"] == "other"
    # ... and traces hundreds that are never compiled on their own (every
    # jnp function of a Pallas kernel's body): glm's and phi4's programs
    # read ``trace_us`` 0 while the thread held its intervals in a list
    # capped at 64
    for i in range(300):
        monitoring.record_event_time_span(TRACE, 11.2 + i * 1e-3,
                                          11.2 + i * 1e-3 + 1e-4,
                                          fun_name=f"jnp_helper_{i}")
    monitoring.record_event_time_span(
        LOWER, 11.0, 11.75, fun_name="jit(decode:b16:c1:l768)")
    monitoring.record_event_time_span(
        BACKEND, 11.75, 15.0, fun_name="jit(decode:b16:c1:l768)")
    rec = HetuProfiler.compile_log()[-1]
    assert (rec["program"], rec["trace_us"], rec["lower_us"],
            rec["backend_us"]) == ("b16:c1:l768", 1000000, 750000, 3250000)


def test_the_log_keeps_the_newest_records():
    for i in range(compile_log.KEEP + 5):
        _emit(f"serve:b{i}")
    log = HetuProfiler.compile_log()
    assert len(log) == compile_log.KEEP
    assert log[-1]["program"] == f"b{compile_log.KEEP + 4}"
    assert log[0]["program"] == "b5"
    assert HetuProfiler.compile_counters()["serve:programs"] \
        == compile_log.KEEP + 5


def test_stored_is_jaxs_own_rule_at_that_moment(tmp_path):
    """A real persistent cache on the CPU: under the threshold jax's
    config holds AT THAT MOMENT the program is compiled and not kept;
    at it, kept; and the next compile of the same program reads it back."""
    from jax._src import compilation_cache as cc

    def program():
        # a new function object each time: jax compiles it anew, and the
        # persistent cache's key (the module) is the same
        def f(x):
            return jnp.sin(x) * 3 + 1
        return compile_log.name_program(f, "serve", "b4")

    x = np.ones(4, np.float32)
    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs")}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        got = []
        for threshold in (1e6, 0.0, 0.0):
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              threshold)
            jax.jit(program())(x)
            got.append([r for r in HetuProfiler.compile_log()
                        if r["owner"] == "serve"][-1])
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert [(r["cache"], r["stored"]) for r in got] \
        == [("miss", False), ("miss", True), ("hit", False)]
    assert got[2]["cache_read_us"] > 0
    assert got[2]["cache_read_us"] <= got[2]["backend_us"]
    c = HetuProfiler.compile_counters()
    assert (c["serve:programs"], c["serve:cache_misses"],
            c["serve:cache_hits"], c["serve:unstored"]) == (3, 2, 1, 1)
    assert c["serve:unstored_us"] == got[0]["backend_us"]
    b = metrics.setup_breakdown()
    assert b["compile_cache_hit_pct"] == pytest.approx(100 / 3)
    assert b["compile_unstored_s"] == pytest.approx(
        got[0]["backend_us"] / 1e6)


# ------------------------------------------------------- who asked for it

@pytest.fixture(scope="module")
def decode_graph():
    return gpt2_decode_graph(_CFG, max_len=16)


def _engine(decode_graph, **kw):
    feeds, logits, caches, _layers = decode_graph
    return DecodeEngine(feeds, logits, caches, seed=0, max_slots=4,
                        max_len=16, **kw)


def _decode_records():
    return [r for r in HetuProfiler.compile_log() if r["owner"] == "decode"]


def test_a_decode_engine_leaves_one_record_a_program(decode_graph):
    eng = _engine(decode_graph)
    eng.reserve(4, 16)
    assert _decode_records() == []          # building compiles no step
    with DecodeRouter(eng) as router:
        router.submit([5, 9, 13], max_new_tokens=3).result(timeout=120)
        first = _decode_records()
        router.submit([7, 3], max_new_tokens=3).result(timeout=120)
    # ONE program served both requests, and only its first step compiled
    assert [r["program"] for r in first] == ["b4:c1:l16"]
    assert _decode_records() == first
    rec = first[0]
    assert rec["cache"] == "off" and not rec["stored"]
    assert min(rec["trace_us"], rec["lower_us"], rec["backend_us"]) > 0
    c = HetuProfiler.compile_counters()
    assert c["decode:programs"] == 1
    d = metrics.decode_counts()
    # the compile happened inside a step's dispatch phase
    assert d["decode_step_compile_us"] == rec["trace_us"] \
        + rec["lower_us"] + rec["backend_us"]
    assert 0 < d["decode_step_compile_us"] <= d["decode_step_dispatch_us"]
    assert HetuProfiler.decode_counters()["decode_step_compile_us"] \
        == d["decode_step_compile_us"]


def test_a_bucket_that_compiles_mid_run_is_named_by_the_newest_record(
        decode_graph):
    """An engine that walks its length ladder compiles on the way: the
    newest record says which program stalled the step."""
    eng = _engine(decode_graph)
    with DecodeRouter(eng) as router:
        stream = router.submit([5, 9, 13], max_new_tokens=2)
        stream.result(timeout=120)
        before = metrics.decode_counts()["decode_step_compile_us"]
        seen = len(_decode_records())
        router.submit([5, 9, 13, 2, 4, 6, 8], max_new_tokens=4).result(
            timeout=120)
    grew = _decode_records()[seen:]
    assert grew, "the longer request needed a longer bucket"
    assert grew[-1]["program"] == f"b{eng.bb}:c1:l{eng.lb}"
    assert metrics.decode_counts()["decode_step_compile_us"] - before \
        == sum(r["trace_us"] + r["lower_us"] + r["backend_us"]
               for r in grew)
    # every program once
    names = [r["program"] for r in _decode_records()]
    assert len(names) == len(set(names))


def test_a_chunked_program_carries_its_chunk_width():
    from hetu_tpu.models import gpt2_decode_chunked_graph
    feeds, logits, caches, _ = gpt2_decode_graph(_CFG, max_len=16)
    chunked = gpt2_decode_chunked_graph(_CFG, max_len=16)[:3]
    eng = DecodeEngine(feeds, logits, caches, seed=0, max_slots=2,
                       max_len=16, chunked=chunked, max_chunk=4)
    eng.reserve(2, 16)
    with DecodeRouter(eng) as router:
        router.submit([5, 9, 13, 2, 4, 6], max_new_tokens=2).result(
            timeout=120)
    names = {r["program"] for r in _decode_records()}
    assert "b2:c4:l16" in names and "b2:c1:l16" in names


def _tiny_executor():
    x = ht.placeholder_op("x", shape=(8, 8))
    w = ht.init.zeros(shape=(8, 8), name="w")
    loss = ht.reduce_mean_op(ht.ops.matmul_op(x, w), [0, 1])
    opt = ht.optim.SGDOptimizer(0.1)
    ex = ht.Executor({"train": [loss, opt.minimize(loss)]}, seed=0)
    return ex, x


def test_an_executor_step_is_train_and_compiles_once():
    ex, x = _tiny_executor()
    feed = {x: np.ones((8, 8), np.float32)}
    ex.run("train", feed_dict=feed)
    mine = [r for r in HetuProfiler.compile_log() if r["owner"] == "train"]
    assert [r["program"] for r in mine] == ["train"]
    ex.run("train", feed_dict=feed)
    assert HetuProfiler.compile_counters()["train:programs"] == 1
    # the graph's construction and nothing else of a set-up is counted
    setup = HetuProfiler.setup_counters()
    assert setup["us"]["setup.graph"] > 0
    assert "setup.state" not in setup["us"]
    # load_dict: the bytes that went to the device
    ex.load_dict({"w": np.ones((8, 8), np.float32)})
    assert HetuProfiler.setup_counters()["bytes"] == {"setup.weights": 256}


def test_an_inference_bucket_is_serve():
    x = ht.placeholder_op("x", shape=(4, 8))
    w = ht.init.ones(shape=(8, 2), name="w")
    iex = InferenceExecutor([ht.ops.matmul_op(x, w)], buckets=(2, 4),
                            seed=0, validate="off")
    iex.infer({x: np.ones((3, 8), np.float32)})
    mine = [r for r in HetuProfiler.compile_log() if r["owner"] == "serve"]
    assert [r["program"] for r in mine] == ["b4"]
    # ``serve_bucket_compiles`` counts the jit wrapper made for it
    assert metrics.serve_counts()["serve_bucket_compiles"] == 1


# ------------------------------------------------------------- the spans

def test_compile_spans_lie_inside_the_step_that_caused_them(
        decode_graph, tmp_path):
    import json
    obs.enable(True)
    eng = _engine(decode_graph)
    eng.reserve(4, 16)
    with DecodeRouter(eng) as router:
        router.submit([5, 9, 13], max_new_tokens=2).result(timeout=120)
    obs.enable(False)
    path = tmp_path / "trace.json"
    obs.export_chrome_trace(str(path))
    evs = [e for e in json.loads(path.read_text())["traceEvents"]
           if e.get("ph") == "X"]
    whole = [e for e in evs if e["name"] == "compile"
             and e["args"]["owner"] == "decode"]
    assert len(whole) == 1
    whole = whole[0]
    assert whole["args"] == {"owner": "decode", "program": "b4:c1:l16",
                             "cache": "off", "stored": False}

    def inside(inner, outer, slack=50.0):      # us: two clocks meet here
        return outer["ts"] - slack <= inner["ts"] and \
            inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + slack

    parts = [e for e in evs if e["tid"] == whole["tid"]
             and e["name"].startswith("compile.") and inside(e, whole, 1.0)]
    assert sorted(e["name"] for e in parts) == [
        "compile.backend", "compile.lower", "compile.trace"]
    steps = [e for e in evs if e["name"] == "decode.step"
             and e["tid"] == whole["tid"] and inside(whole, e)]
    assert len(steps) == 1
    dispatch = [e for e in evs if e["name"] == "decode.step.dispatch"
                and e["tid"] == whole["tid"] and inside(whole, e)]
    assert len(dispatch) == 1
    # the set-up phases are on the constructing thread's track
    names = {e["name"] for e in evs}
    assert {"setup.graph", "setup.weights", "setup.state"} <= names


def test_tracing_off_writes_no_compile_span(decode_graph):
    eng = _engine(decode_graph)
    with DecodeRouter(eng) as router:
        router.submit([5], max_new_tokens=1).result(timeout=120)
    assert _decode_records()
    assert not [e for e in obs.trace_events() if e.get("ph") == "X"]


# ------------------------------------------------------- set-up counters

def test_setup_counters_hold_the_bytes_the_engine_reports(decode_graph):
    eng = _engine(decode_graph)
    setup = HetuProfiler.setup_counters()
    assert setup["bytes"]["setup.state"] == sum(eng.state_bytes().values())
    weights = sum(int(v.nbytes) for v in eng.iex.params.values())
    assert setup["bytes"]["setup.weights"] == weights
    assert all(setup["us"][p] > 0 for p in metrics.SETUP_PHASES)
    # growth is counted where it happens
    eng.reserve(4, 16)
    grown = HetuProfiler.setup_counters()["bytes"]["setup.state"]
    assert grown == sum(eng.state_bytes().values()) == eng.kv_bytes
    # a second executor over the first one's device arrays moves nothing
    again = InferenceExecutor(
        [eng.iex.fetches[0]], buckets=(1,), seed=0, validate="off",
        weights={eng.iex.var_names[n]: eng.iex.params[eng.iex._k(n)]
                 for n in eng.iex.var_nodes}, decode=True)
    assert again.params
    assert HetuProfiler.setup_counters()["bytes"]["setup.weights"] == weights


TABLE = {
    "decode:programs": 6, "decode:trace_us": 9_000_000,
    "decode:lower_us": 3_000_000, "decode:backend_us": 40_000_000,
    "decode:cache_hits": 2, "decode:cache_read_us": 1_500_000,
    "decode:cache_misses": 4, "decode:unstored": 3,
    "decode:unstored_us": 12_500_000,
    "train:programs": 1, "train:trace_us": 1_000_000,
    "train:lower_us": 500_000, "train:backend_us": 20_000_000,
    "train:cache_misses": 1,
    # the reference's and the helpers' programs are left out
    "other:programs": 40, "other:trace_us": 7_000_000,
    "other:lower_us": 7_000_000, "other:backend_us": 70_000_000,
    "other:cache_hits": 30, "other:cache_misses": 10,
    "other:unstored": 10, "other:unstored_us": 70_000_000,
}
SETUP_US = {"setup.graph": 2_000_000, "setup.weights": 5_500_000,
            "setup.state": 250_000}
WANT = {"compile_s": 60.0, "trace_lower_s": 13.5,
        "compile_cache_hit_pct": 100 * 2 / 7, "compile_unstored_s": 12.5,
        "program_build_s": 7.75}


@pytest.mark.parametrize("name", sorted(WANT))
def test_setup_breakdown_reads_the_programs_own_counters(name, monkeypatch):
    assert metrics.setup_breakdown()[name] is None      # nothing recorded
    for key, n in TABLE.items():
        metrics._compile.inc(key, n)
    for phase, us in SETUP_US.items():
        metrics.record_setup(phase, us)
    assert metrics.setup_breakdown()[name] == pytest.approx(WANT[name])
    # a process whose own programs never reached the compiler
    metrics.reset_all()
    for key, n in TABLE.items():
        if key.startswith("other:"):
            metrics._compile.inc(key, n)
    assert metrics.setup_breakdown()[name] is None
