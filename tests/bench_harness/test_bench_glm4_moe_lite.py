"""The GLM-4.7-Flash cell: its data files, its table, its readers on made
runs, and whole runs of the harness at CPU size — sound, under the control,
and with the timed path broken seven ways."""
import json
import math
import os
from statistics import NormalDist

import numpy as np
import pytest

from conftest import ROOT
import glm_root
from test_bench_cells import _driver

from benchmarks import harness, mla_bytes, traffic

CELL = glm_root.REAL
NEW = ("mla_share_pct.serve", "mla_decode_roofline",
       "latent_bytes_per_token.serve")
JOINED = ("moe_share_pct.serve", "moe_experts_roofline",
          "moe_tokens_per_expert.serve", "kv_rows_read_pct.serve",
          "kv_copy_share_pct.serve", "device_idle_pct.serve",
          "launch_ahead_pct.serve", "ttft_mean_ms.serve",
          "ttft_p95_ms.serve", "queue_wait_ms.serve",
          "prefill_tokens_per_step.serve", "row_token_fill_pct.serve")


@pytest.fixture()
def root(tmp_path):
    from hetu_tpu.graph import step_cache
    step_cache.clear()      # a broken run must trace its own programs
    yield glm_root.build(str(tmp_path))
    step_cache.clear()


@pytest.fixture(scope="module")
def mix():
    return harness.Files(ROOT).mix("think-c128")


def _run(root, control=False):
    return harness.run_cell(glm_root.TINY, 3000000019, 1.0, False,
                            files=harness.Files(root), require_tpu=False,
                            out_dir=os.path.join(root, "out"),
                            control=control)


def test_the_cell_is_found_by_the_names_in_its_files():
    files = harness.Files(ROOT)
    cell = files.cell(CELL)
    cfg = files.config(cell["config"])
    assert (cell["chips"], cell["config"], cfg["system"], cfg["reference"]) \
        == (1, "glm47-flash", "glm4_moe_lite_decode", "glm4_moe_lite_lm")
    assert files.mix(cell["traffic"])["driver"] == "closed_loop_decode_routed"
    assert set(files.limits(CELL)) == {"logit_gap_max", "logit_gap_sq_mean",
                                       "route_margin_max"}
    # judged by its rate, as the solar cell: a completion every few steps
    ends = {m["name"] for m in files.metrics("end_to_end", CELL)}
    assert ends == {"serve_tokens_per_s", "setup_s"}
    layers = {m["name"] for m in files.metrics("per_layer", CELL)}
    assert layers == set(NEW) | set(JOINED)
    moved = {m["name"]: m["moves"] for m in files.bench["per_layer"]}
    assert {moved[name] for name in layers} == {"serve_tokens_per_s"}
    for name in layers:
        assert callable(files.reader(name))
    # the new readers belong to this cell alone, at the end of the list
    assert [m["name"] for m in files.bench["per_layer"][-3:]] == list(NEW)
    for m in files.bench["per_layer"][-3:]:
        assert m["workloads"] == [CELL]
    by_name = {m["name"]: m for m in files.bench["per_layer"]}
    assert (by_name["mla_share_pct.serve"]["layer"],
            by_name["mla_decode_roofline"]["layer"],
            by_name["latent_bytes_per_token.serve"]["layer"]) \
        == ("model step", "kernels", "decode engine")
    assert (by_name["mla_decode_roofline"]["unit"],
            by_name["mla_decode_roofline"]["source"]) == ("%", "device_trace")


def test_table_is_what_the_mix_file_says_it_is(mix):
    nd, n = NormalDist(), len(mix["table"])
    lengths = mix["lengths"]
    assert (lengths["prompt"], lengths["output"]) == (
        {"dist": "lognormal", "median": 256.0, "sigma": 0.8, "min": 32,
         "max": 1024},
        {"dist": "lognormal", "median": 1800.0, "sigma": 0.5, "min": 512,
         "max": 3008})

    def column(d):
        return [int(min(d["max"], max(d["min"], round(d["median"] * math.exp(
            d["sigma"] * nd.inv_cdf((i + 0.5) / n)))))) for i in range(n)]
    prompts, outputs = column(lengths["prompt"]), column(lengths["output"])
    assert (n, prompts[0], prompts[-1], outputs[0], outputs[-1]) \
        == (128, 32, 1024, 512, 3008)
    for i in range(8):
        for j in range(16):
            assert mix["table"][16 * i + j] \
                == [prompts[16 * i + j], outputs[8 * j + 7 - i]]
    table = np.asarray(mix["table"])
    assert abs(np.corrcoef(np.argsort(np.argsort(table[:, 0])),
                           np.argsort(np.argsort(table[:, 1])))[0, 1]) < 0.1
    assert max(p + o for p, o in mix["table"]) <= lengths["sum_max"] == 4032
    assert mix["blocks"] == harness.Files(ROOT).mix("assist-c128")["blocks"]
    work = [sum(sum(mix["table"][k]) for k in b) for b in mix["blocks"]]
    assert max(work) < 1.2 * min(work)
    assert (mix["clients"], mix["max_slots"], mix["max_len"],
            mix["max_chunk"]) == (128, 128, 4096, 32)
    # the furthest row a step can ask for, a top chunk running beside the
    # longest request, is inside what the engine is reserved at
    assert lengths["sum_max"] - 2 + mix["max_chunk"] - 1 < mix["max_len"]
    s = traffic.Schedule(mix, 154880, 3000000019)
    ids, new = s.request(7)
    assert (len(ids), new) == s.lengths(7) and ids.max() < 154880


def test_byte_function_counts_the_published_row():
    cfg = harness.Files(ROOT).config("glm47-flash")
    assert mla_bytes.row_bytes(cfg) == (512 + 64) * 2 == 1152
    assert mla_bytes.live_bytes(cfg, 1000) == 1152 * 13 * 1000
    # 128 slots at a mean of 1,200 live rows: the forecast's 2.3 GB a step
    assert abs(mla_bytes.live_bytes(cfg, 128 * 1200) / 1e9 - 2.30) < 0.01


def _made_run(counters=None, op_s=None, state=None):
    files = harness.Files(ROOT)
    return {"trace": None if op_s is None else {"op_s": op_s},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "mix": files.mix("think-c128"),
            "cfg": files.config("glm47-flash"),
            "window": {"counters": counters or {}, "seconds": 51.0,
                       "state_bytes": state, "slots": 128}}


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """The parent of this PR has no such scope, kernel, counter or state:
    None, never 0, and nothing raises."""
    files = harness.Files(ROOT)
    for name in NEW:
        assert files.reader(name)(_made_run()) is None
        assert files.reader(name)(_made_run(
            {"decode_steps": 4000}, {"pallas:flash_fwd_q1": 1.0},
            {"ring": 5})) is None


def test_latent_bytes_per_token_divides_the_kv_state_by_its_rows():
    read = harness.Files(ROOT).reader("latent_bytes_per_token.serve")
    rows = 128 * 4096 * 13
    assert read(_made_run(state={"kv": rows * 1280})) == 1280.0
    assert read(_made_run(state={"kv": rows * 1152})) == 1152.0


def test_latent_roofline_cannot_read_over_100(monkeypatch):
    """The bytes counted are the PUBLISHED rows of the live positions; the
    kernel fetches whole key blocks of padded rows, never fewer.  At the
    HBM's peak over exactly what it fetches the share reads under 100, and
    100 for full blocks of rows stored unpadded."""
    from benchmarks.metrics import moe_experts_roofline
    from hetu_tpu.ops.attention import kv_rows_read
    monkeypatch.setattr(moe_experts_roofline, "traced_steps", lambda run: 400)
    read = harness.Files(ROOT).reader("mla_decode_roofline")
    rng = np.random.default_rng(1)
    for _ in range(5):
        lengths = rng.integers(1, 4097, 128)
        monkeypatch.setattr("jax.default_backend", lambda: "tpu")
        fetched = kv_rows_read(lengths, (128, 1, 4096, 640), 1, 2)
        assert fetched >= lengths.sum() and fetched % 512 == 0
        spent = 400 * fetched * 1280 * 13 / 819e9
        got = read(_made_run({"decode_steps": 4000, "decode_kv_rows_live":
                              4000 * int(lengths.sum())},
                             {"pallas:mla_fwd_q1": spent}))
        assert got == pytest.approx(
            100.0 * lengths.sum() * 1152 / (fetched * 1280))
        assert 0 < got < 90.0001
    full = read(_made_run({"decode_steps": 10, "decode_kv_rows_live":
                           10 * 128 * 4096},
                          {"pallas:mla_fwd_q1":
                           400 * 128 * 4096 * 1152 * 13 / 819e9}))
    assert full == pytest.approx(100.0)


def test_the_cell_at_test_size_runs_and_is_correct(root):
    """Float32 on one backend: the engine serves exactly the tokens the
    plain reference (keys and values materialised per head), followed layer
    by layer with the program's choices, puts first, and every choice is
    the reference's own."""
    out = _run(root)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["compared"]["logit_gap_max"]["value"] < 1e-4
    assert out["compared"]["logit_gap_sq_mean"]["value"] < 1e-9
    assert out["compared"]["route_margin_max"]["value"] < 1e-6
    json.dumps(out)


def test_a_window_feeds_the_program_counter_readers(root):
    """What the counters' readers find in a tiny window on the CPU: the
    latent row as stored (128 lanes of float32 at test size), live rows
    counted, the experts' load; the trace readers find no trace."""
    files = harness.Files(root)
    d, _ = _driver(root, glm_root.TINY, seed=5)
    try:
        run = d.window(0.5, None)
        counters = d.sys.counters()
    finally:
        d.free()
    run.update(cfg=d.cfg, mix=d.mix, trace=None, peaks=None)
    assert files.reader("latent_bytes_per_token.serve")(run) == 128 * 4
    c = run["window"]["counters"]
    assert 0 < c["decode_kv_rows_live"] < c["decode_kv_rows_read"]
    assert counters["moe_calls:8of64:top4:ragged"] >= 4
    assert 0 < files.reader("moe_tokens_per_expert.serve")(run) < 4
    assert files.reader("kv_rows_read_pct.serve")(run) == 100.0
    for name in ("mla_share_pct.serve", "mla_decode_roofline"):
        assert files.reader(name)(run) is None


# ------------------------------------------------------ the planted faults

def _rotation_dropped(monkeypatch):
    from hetu_tpu.ops import mla
    monkeypatch.setattr(mla, "_rotate", lambda x, at, theta: x)


def _rotation_stays_at_a_chunks_first_position(monkeypatch):
    """Every column of a chunk turned by the position of its first: right
    token by token, wrong after any chunk."""
    from hetu_tpu.ops import mla
    real = mla._rotate
    monkeypatch.setattr(mla, "_rotate", lambda x, at, theta: real(
        x, mla.jnp.broadcast_to(at[:, :1], at.shape), theta))


def _scale_dropped(monkeypatch):
    """The normalised weights without ``routed_scaling_factor``."""
    from hetu_tpu.ops import moe
    real = moe._route_norm
    monkeypatch.setattr(moe, "_route_norm", lambda chosen: real(chosen) / 1.8)


def _value_lanes_shifted(monkeypatch):
    """The value read one lane off the key's: lanes 1.. for 0..."""
    from hetu_tpu.ops import mla
    real = mla._read_whole
    monkeypatch.setattr(mla, "_read_whole", lambda *a: mla.jnp.roll(
        real(*a), -1, axis=-1))


def _query_not_absorbed(monkeypatch):
    """``q_nope`` scored against the latent as it is, ``W_uk`` left out."""
    from hetu_tpu.ops import mla
    real = mla._stored

    def stored(x, w, eq):
        if eq != "bchd,rhd->bchr":
            return real(x, w, eq)
        return mla.jnp.pad(x, ((0, 0),) * 3 + ((0, w.shape[0] - x.shape[-1]),))
    monkeypatch.setattr(mla, "_stored", stored)


def _latent_not_normed(monkeypatch):
    from hetu_tpu.ops import mla
    monkeypatch.setattr(mla, "_rms", lambda x, scale, eps: x)


def _wrong_expert(monkeypatch):
    """The last of the chosen gives way to the expert that scored lowest:
    the reference follows it, so only the route check can tell."""
    from hetu_tpu.ops import moe
    real = moe._route_pick

    def pick(s, bias, k):
        ids, _ = real(s, bias, k)
        ids = ids.at[:, -1].set(moe.jnp.argmin(s + bias, axis=-1))
        return ids, moe.jnp.take_along_axis(s, ids, axis=-1)
    monkeypatch.setattr(moe, "_route_pick", pick)


BREAKS = {"rotation_dropped": _rotation_dropped,
          "rotation_at_the_wrong_position_after_a_chunk":
              _rotation_stays_at_a_chunks_first_position,
          "scaling_factor_dropped": _scale_dropped,
          "value_lanes_shifted_by_one": _value_lanes_shifted,
          "q_nope_used_unabsorbed": _query_not_absorbed,
          "inner_norm_of_the_latent_skipped": _latent_not_normed,
          "a_wrong_expert_chosen": _wrong_expert}


@pytest.mark.parametrize("fault", ["control_precision"] + list(BREAKS))
def test_a_broken_run_is_not_correct(root, monkeypatch, fault):
    """The fp8 control in the program's place, and the timed path broken
    seven ways, under the REAL cell's limits: ``correct`` comes out false.
    A wrong expert, which the reference follows, is caught by
    ``route_margin_max`` alone."""
    if fault in BREAKS:
        BREAKS[fault](monkeypatch)
    out = _run(root, control=fault == "control_precision")
    assert out["correct"] is False, out["compared"]
    if fault == "a_wrong_expert_chosen":
        c = out["compared"]
        assert c["route_margin_max"]["value"] > c["route_margin_max"]["limit"]
        assert c["logit_gap_max"]["value"] < 1e-4
