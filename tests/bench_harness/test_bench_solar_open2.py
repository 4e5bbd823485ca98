"""The Solar-Open2 cell: its data files, its table, and whole runs of the
harness at CPU size — sound, under the control, and with the timed path
broken seven ways."""
import json
import math
import os
from statistics import NormalDist

import numpy as np
import pytest

from conftest import ROOT
import solar_root
from test_bench_cells import _driver

from benchmarks import harness, traffic

CELL = solar_root.REAL


@pytest.fixture()
def root(tmp_path):
    from hetu_tpu.graph import step_cache
    step_cache.clear()      # a broken run must trace its own programs
    yield solar_root.build(str(tmp_path))
    step_cache.clear()


@pytest.fixture(scope="module")
def mix():
    return harness.Files(ROOT).mix("assist-c128")


def _run(root, control=False):
    return harness.run_cell(solar_root.TINY, 3000000019, 1.0, False,
                            files=harness.Files(root), require_tpu=False,
                            out_dir=os.path.join(root, "out"),
                            control=control)


def test_the_cell_is_found_by_the_names_in_its_files():
    files = harness.Files(ROOT)
    cell = files.cell(CELL)
    cfg = files.config(cell["config"])
    assert (cell["chips"], cfg["system"], cfg["reference"]) \
        == (1, "solar_open2_decode", "solar_open2_lm")
    assert files.mix(cell["traffic"])["driver"] == "closed_loop_decode_routed"
    assert set(files.limits(CELL)) == {"logit_gap_max", "logit_gap_sq_mean",
                                       "route_margin_max"}
    # no inter-token metric: one gap in four holds a completion, and every
    # percentile that could be judged lies on that shelf — ``itl_p90_ms``
    # spread 0.26 % over six seeds and 0.91 % over the same six again, over a
    # quarter of its 3 % bound; ``itl_p95_ms`` 2.8–4.0 % (PERF.md section 6,
    # PR 35, PR 31).  The cell is judged by its rate, the metrics that move
    # the gaps stay off it, and its gaps' profile is in every run's window
    ends = {m["name"] for m in files.metrics("end_to_end", CELL)}
    assert ends == {"serve_tokens_per_s", "setup_s"}
    layers = {m["name"] for m in files.metrics("per_layer", CELL)}
    assert {"moe_share_pct.serve", "moe_experts_roofline",
            "linear_attn_share_pct.serve", "moe_tokens_per_expert.serve",
            "gqa_attn_share_pct.serve", "kv_rows_read_pct.serve",
            "device_idle_pct.serve"} <= layers
    moved = {m["name"]: m["moves"] for m in files.bench["per_layer"]}
    assert {moved[name] for name in layers} == {"serve_tokens_per_s"}
    for name in layers:
        assert callable(files.reader(name))
    # readers that name another model's scopes, read the width of chunks in
    # a window that holds none, or take every Pallas call for the attention
    # kernel (the grouped product is one here) do not hold for this cell
    assert not {"mixer_share_pct.serve", "ssm_share_pct.serve",
                "cross_attn_share_pct.serve", "chunk_width_mean.serve",
                "decode_attn_share_pct.serve"} & layers
    # the new readers belong to this cell alone
    for old in ("gpt2-medium.chat-c16", "phi4-mini-flash.reason-c64"):
        assert not {"moe_share_pct.serve", "moe_experts_roofline",
                    "linear_attn_share_pct.serve",
                    "moe_tokens_per_expert.serve",
                    "gqa_attn_share_pct.serve"} \
            & {m["name"] for m in files.metrics("per_layer", old)}


def test_table_is_what_the_mix_file_says_it_is(mix):
    nd, n = NormalDist(), len(mix["table"])
    lengths = mix["lengths"]

    def column(d):
        return [int(min(d["max"], max(d["min"], round(d["median"] * math.exp(
            d["sigma"] * nd.inv_cdf((i + 0.5) / n)))))) for i in range(n)]
    prompts, outputs = column(lengths["prompt"]), column(lengths["output"])
    assert (n, prompts[0], prompts[-1], outputs[0], outputs[-1]) \
        == (128, 32, 1024, 128, 3008)
    assert sorted(p for p, _ in mix["table"]) == prompts
    assert sorted(o for _, o in mix["table"]) == outputs   # none had to be cut
    for i in range(8):
        for j in range(16):
            assert mix["table"][16 * i + j] \
                == [prompts[16 * i + j], outputs[8 * j + 7 - i]]
    table = np.asarray(mix["table"])
    assert abs(np.corrcoef(np.argsort(np.argsort(table[:, 0])),
                           np.argsort(np.argsort(table[:, 1])))[0, 1]) < 0.1
    assert max(p + o for p, o in mix["table"]) <= lengths["sum_max"] == 4032
    assert mix["blocks"] == [
        [16 * i + 2 * ((i + b // 2) % 8) + (i + b) % 2 for i in range(8)]
        for b in range(16)]
    work = [sum(sum(mix["table"][k]) for k in b) for b in mix["blocks"]]
    assert max(work) < 1.2 * min(work)
    assert mix["clients"] == mix["max_slots"] == 128
    # the furthest row a step can ask for, a top chunk running beside the
    # longest request, is inside what the engine is reserved at
    assert lengths["sum_max"] - 2 + mix["max_chunk"] - 1 < mix["max_len"]
    s = traffic.Schedule(mix, 24576, 3000000019)
    ids, new = s.request(7)
    assert (len(ids), new) == s.lengths(7) and ids.max() < 24576


def test_byte_function_counts_three_matrices_an_expert():
    from benchmarks import moe_bytes
    cfg = harness.Files(ROOT).config("solar-open2")
    assert moe_bytes.expert_bytes(cfg) == 3 * 4096 * 1280 * 2 == 31457280
    assert moe_bytes.touched_bytes(cfg, 40 * 8) == 320 * 31457280
    # every held expert of every layer touched in a step: the 10.07 GB the
    # forecast of a one-token step starts from
    assert abs(moe_bytes.touched_bytes(cfg, 40 * 8) / 1e9 - 10.07) < 0.01


def test_readers_return_nothing_where_there_is_nothing_to_read():
    files = harness.Files(ROOT)
    untraced = {"trace": None, "peaks": None,
                "mix": {"name": "assist-c128"}, "cfg": {"name": "solar-open2"},
                "window": {"counters": {}, "seconds": 51.0}}
    for name in ("moe_share_pct.serve", "moe_experts_roofline",
                 "linear_attn_share_pct.serve", "gqa_attn_share_pct.serve",
                 "moe_tokens_per_expert.serve"):
        assert files.reader(name)(untraced) is None
    counted = dict(untraced, window={"seconds": 51.0, "counters": {
        "moe_assignments_held": 1280, "moe_experts_touched": 384}})
    assert files.reader("moe_tokens_per_expert.serve")(counted) \
        == pytest.approx(10 / 3)
    assert files.reader("moe_experts_roofline")(counted) is None


def test_the_cell_at_test_size_runs_and_is_correct(root):
    """Float32 on one backend: the engine serves exactly the tokens the
    plain reference, followed layer by layer with the program's choices,
    puts first, and every choice is the reference's own."""
    out = _run(root)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["compared"]["logit_gap_max"]["value"] < 1e-4
    assert out["compared"]["logit_gap_sq_mean"]["value"] < 1e-9
    assert out["compared"]["route_margin_max"]["value"] < 1e-6
    json.dumps(out)


def test_a_window_keeps_the_gaps_profile_though_none_of_it_is_judged(root):
    """The result line of this cell names no inter-token metric; the
    window's ``itl_ms`` block (and the ``[serve]`` line) still say where its
    gaps lie, and the readers that move the gaps would find their counters
    here the day the cell is put on their lists."""
    files = harness.Files(root)
    d, _ = _driver(root, solar_root.TINY, seed=5)
    try:
        run = d.window(0.5, None)
    finally:
        d.free()
    itl = run["window"]["itl_ms"]
    assert set(itl) == {"p50", "p90", "p95", "p99", "slowest5_mean",
                        "slow_pct"}
    assert 0 < itl["p50"] <= itl["p90"] <= itl["p99"]
    for name in ("engine_step_ms.serve", "step_wait_ms.serve",
                 "step_readback_ms.serve", "step_host_ms.serve",
                 "between_steps_ms.serve", "state_bytes_per_slot.serve"):
        assert files.reader(name)(run) > 0, name


def _dropped_assignment(monkeypatch):
    """A token's last choice is never computed."""
    from hetu_tpu.ops import moe
    real = moe._held
    monkeypatch.setattr(moe, "_held", lambda local, count: real(
        local, count).at[:, -1].set(False))


def _not_normalised(monkeypatch):
    from hetu_tpu.ops import moe
    monkeypatch.setattr(moe, "_route_norm", lambda chosen: chosen)


def _bias_in_the_weights(monkeypatch):
    from hetu_tpu.ops import moe
    real = moe._route_pick

    def pick(s, bias, k):
        ids, _ = real(s, bias, k)
        return ids, moe.jnp.take_along_axis(s + bias, ids, axis=-1)
    monkeypatch.setattr(moe, "_route_pick", pick)


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


def _beta_not_doubled(monkeypatch):
    from hetu_tpu.ops import kda
    monkeypatch.setattr(kda, "_kda_beta", kda.jax.nn.sigmoid)


def _decay_after_the_write(monkeypatch):
    """``Diag(a) ((I − β k kᵀ) S + β k vᵀ)`` for ``(I − β k kᵀ) Diag(a) S +
    β k vᵀ``."""
    from hetu_tpu.ops import kda
    jnp = kda.jnp

    def step(s, q_t, k_t, v_t, a_t, b_t):
        u = jnp.sum(s * k_t[..., None], axis=-2)
        nxt = (s + k_t[..., None] * (b_t[..., None] * (v_t - u))[
            ..., None, :]) * a_t[..., None]
        return nxt, jnp.sum(nxt * q_t[..., None], axis=-2)
    monkeypatch.setattr(kda, "_kda_step", step)


def _no_clearing(monkeypatch):
    from hetu_tpu.serving import DecodeEngine
    monkeypatch.setattr(DecodeEngine, "_clear_recurrent",
                        lambda self, slot: None)


BREAKS = {"an_assignment_dropped": _dropped_assignment,
          "weights_not_normalised": _not_normalised,
          "bias_used_in_the_weights": _bias_in_the_weights,
          "beta_not_doubled": _beta_not_doubled,
          "decay_applied_after_the_write": _decay_after_the_write,
          "kda_state_not_zeroed_at_join": _no_clearing,
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
