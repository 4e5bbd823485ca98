"""The MiniCPM-SALA cell and the second GPT-2 cell: their data files, their
tables, the readers that wait on made runs, and whole runs of the harness at
CPU size — sound, under the control, and with the timed path broken."""
import json
import math
import os
from statistics import NormalDist

import pytest

from conftest import ROOT
import sala_root
from test_bench_cells import _driver

from benchmarks import harness, sparse_bytes, traffic

CELL = sala_root.REAL
DOCQA16 = "gpt2-medium.docqa-c16"
NEW = tuple(m["name"] for m in sala_root.ENTRIES)    # the readers that wait
JOINED = ("ttft_mean_ms.serve", "ttft_p95_ms.serve",
          "prefill_tokens_per_step.serve", "kv_copy_share_pct.serve",
          "device_idle_pct.serve", "queue_wait_ms.serve",
          "row_token_fill_pct.serve", "kv_rows_read_pct.serve",
          "launch_ahead_pct.serve")


@pytest.fixture()
def root(tmp_path):
    from hetu_tpu.graph import step_cache
    step_cache.clear()      # a broken run must trace its own programs
    yield sala_root.build(str(tmp_path))
    step_cache.clear()


@pytest.fixture(scope="module")
def mix():
    return harness.Files(ROOT).mix("docqa-c64")


def _run(root, control=False):
    return harness.run_cell(sala_root.TINY, 3000000019, 1.0, False,
                            files=harness.Files(root), require_tpu=False,
                            out_dir=os.path.join(root, "out"),
                            control=control)


def test_the_cell_is_found_by_the_names_in_its_files():
    files = harness.Files(ROOT)
    cell = files.cell(CELL)
    cfg = files.config(cell["config"])
    assert (cell["chips"], cell["config"], cfg["system"], cfg["reference"]) \
        == (1, "minicpm-sala", "minicpm_sala_decode", "minicpm_sala_lm")
    assert files.mix(cell["traffic"])["driver"] == "closed_loop_sessions"
    assert set(files.limits(CELL)) == {"logit_gap_max", "logit_gap_sq_mean",
                                       "select_margin_max"}
    ends = {m["name"] for m in files.metrics("end_to_end", CELL)}
    assert ends == {"serve_tokens_per_s", "setup_s"}
    layers = {m["name"] for m in files.metrics("per_layer", CELL)}
    assert layers == set(JOINED)
    moved = {m["name"]: m["moves"] for m in files.bench["per_layer"]}
    assert {moved[name] for name in layers} == {"serve_tokens_per_s"}
    for name in layers:
        assert callable(files.reader(name))
    entry = next(c for c in files.bench["configs"]
                 if c["name"] == "minicpm-sala")
    assert entry["reduced"] == cfg["reduced"] \
        == ["num_hidden_layers", "mixer_types"]


def test_the_waiting_readers_are_ready_to_be_listed(root):
    """The cell's own five wait beside ``metrics/`` (``sala_root``): none is
    listed yet, each is a reader as the contract wants one, the layers they
    name are layers the benchmark has, and the root a ``benchmark`` PR would
    make lists them for this cell alone, after every entry that was there."""
    real = harness.Files(ROOT).bench["per_layer"]
    assert not {m["name"] for m in real} & set(NEW)
    assert sorted(f[:-3] for f in os.listdir(sala_root.WAITING)
                  if f.endswith(".py")) == sorted(NEW)
    for m in sala_root.ENTRIES:
        mod = sala_root.waiting_reader(m["name"])
        assert callable(mod.read) and mod.MOVES == m["moves"]
        assert m["layer"] in {e["layer"] for e in real}
    roofline = sala_root.ENTRIES[2]
    assert (roofline["name"], roofline["unit"], roofline["source"]) \
        == ("sparse_read_roofline", "%", "device_trace")
    files = harness.Files(root)
    assert [m["name"] for m in files.bench["per_layer"]] \
        == [m["name"] for m in real] + list(NEW)
    assert {m["name"] for m in files.metrics("per_layer", sala_root.TINY)} \
        == set(NEW) | set(JOINED)
    for name in NEW:
        assert callable(files.reader(name))


def test_the_tool_lists_the_waiting_readers_for_the_real_cell(tmp_path):
    """``tools/waiting_metrics.py`` runs the real cell on a root that lists
    them: every file of ``benchmarks/``, the entries at the end."""
    from tools import waiting_metrics
    files = harness.Files(waiting_metrics.build_root(str(tmp_path / "r")))
    real = harness.Files(ROOT)
    assert files.bench["per_layer"] == real.bench["per_layer"] \
        + sala_root.ENTRIES
    assert {m["name"] for m in files.metrics("per_layer", CELL)} \
        == set(NEW) | set(JOINED)
    assert files.metrics("per_layer", DOCQA16) \
        == real.metrics("per_layer", DOCQA16)
    for name in NEW:
        assert callable(files.reader(name))
    assert files.config("minicpm-sala") == real.config("minicpm-sala")
    assert files.limits(CELL) == real.limits(CELL)


def _column(d, n):
    nd = NormalDist()
    return [int(min(d["max"], max(d["min"], round(d["median"] * math.exp(
        d["sigma"] * nd.inv_cdf((i + 0.5) / n)))))) for i in range(n)]


def test_table_is_what_the_mix_file_says_it_is(mix):
    lengths, n = mix["lengths"], len(mix["table"])
    assert (lengths["prompt"], lengths["output"]) == (
        {"dist": "lognormal", "median": 64.0, "sigma": 0.5, "min": 16,
         "max": 192},
        {"dist": "lognormal", "median": 512.0, "sigma": 0.6, "min": 128,
         "max": 1536})
    asks, answers = _column(lengths["prompt"], n), \
        _column(lengths["output"], n)
    assert (n, asks[0], asks[-1], answers[0], answers[-1]) \
        == (64, 19, 192, 128, 1536)
    for i in range(4):
        for j in range(16):
            assert mix["table"][16 * i + j] \
                == [asks[16 * i + j], answers[4 * j + 3 - i]]
    assert mix["blocks"] == [
        [16 * i + 4 * ((i + b // 4) % 4) + (i + b) % 4 for i in range(4)]
        for b in range(16)]
    work = [sum(sum(mix["table"][k]) for k in b) for b in mix["blocks"]]
    assert max(work) < 1.25 * min(work)
    docs = lengths["document"]
    assert docs["lengths"] == [int(round(
        (16384 + (i + 0.5) / 16 * (30720 - 16384)) / 64) * 64)
        for i in range(16)]
    assert (docs["lengths"][0], docs["lengths"][-1],
            sum(docs["lengths"]) / 16) == (16832, 30272, 23552.0)
    assert (mix["clients"], mix["max_slots"], mix["max_len"],
            mix["max_chunk"], mix["documents"], mix["warmup_requests"]) \
        == (64, 64, 32768, 32, 16, 64)
    # the furthest row a step can ask for, a top chunk running beside the
    # longest request, is inside what the engine is reserved at
    longest = max(docs["lengths"]) + max(p + o for p, o in mix["table"])
    assert longest <= lengths["sum_max"] == 32448
    assert lengths["sum_max"] - 2 + mix["max_chunk"] - 1 < mix["max_len"]
    s = traffic.Schedule(mix, 73448, 3000000019)
    ids, new = s.request(7)
    assert (len(ids), new) == s.lengths(7) and ids.max() < 73448


def test_docqa_c16_table_is_what_its_mix_file_says_it_is():
    """The second GPT-2 cell: data files only, chat's engine and driver, a
    page of context in and a sentence out."""
    files = harness.Files(ROOT)
    cell, chat = files.cell(DOCQA16), files.mix("chat-c16")
    mix = files.mix(cell["traffic"])
    assert (cell["config"], cell["chips"], mix["driver"]) \
        == ("gpt2-medium", 1, "closed_loop_decode")
    assert all(mix[k] == chat[k] for k in (
        "clients", "max_slots", "max_len", "max_chunk", "blocks",
        "warmup_requests", "trace_seconds"))
    lengths = mix["lengths"]
    prompts, outputs = _column(lengths["prompt"], 64), \
        _column(lengths["output"], 64)
    assert (lengths["prompt"]["median"], lengths["prompt"]["sigma"],
            prompts[0], prompts[-1]) == (680.0, 0.2, 512, 896)
    assert (lengths["output"]["median"], lengths["output"]["sigma"],
            outputs[0], outputs[-1]) == (32.0, 0.5, 16, 64)
    for i in range(8):
        for j in range(8):
            at = (i + j + 1) % 8
            assert mix["table"][8 * i + j] == [prompts[8 * i + at],
                                               outputs[8 * j + at]]
    assert mix["table"][-1] == [896, 64]
    assert mix["prime"]["prompt"] + mix["prime"]["output"] == 960 \
        == max(p + o for p, o in mix["table"])
    assert 960 - 2 + mix["max_chunk"] - 1 < mix["max_len"]
    assert set(files.limits(DOCQA16)) == {"logit_gap_max",
                                          "logit_gap_sq_mean"}
    ends = {m["name"] for m in files.metrics("end_to_end", DOCQA16)}
    assert ends == {"serve_tokens_per_s", "setup_s"}     # NOT itl_p90_ms
    on_chat = {m["name"] for m in files.metrics("per_layer",
                                                "gpt2-medium.chat-c16")
               if m["moves"] == "serve_tokens_per_s"}
    assert {m["name"] for m in files.metrics("per_layer", DOCQA16)} == on_chat


def test_byte_functions_count_the_published_rows():
    cfg = harness.Files(ROOT).config("minicpm-sala")
    # K and V of 64 rows of one key head; 1,024 B a position over both
    assert sparse_bytes.block_bytes(cfg) == 64 * 2 * 128 * 2 == 32768
    assert 2 * sparse_bytes.block_bytes(cfg) // 64 == 1024
    # 96 blocks a head group: the forecast's 6.29 MB a slot and layer
    assert sparse_bytes.chosen_bytes(cfg, 2 * 96) == 6291456
    # a compressed row of both heads is 512 B, in both sparse layers
    assert sparse_bytes.index_bytes(cfg, 1000) == 1000 * 2 * 512


def _made_run(counters=None, op_s=None):
    files = harness.Files(ROOT)
    return {"trace": None if op_s is None else {"op_s": op_s},
            "peaks": {"hbm_bytes_per_s": 819e9},
            "mix": files.mix("docqa-c64"),
            "cfg": files.config("minicpm-sala"),
            "window": {"counters": counters or {}, "seconds": 51.0}}


def test_readers_return_nothing_where_there_is_nothing_to_read():
    """The parent of this PR has no such scope, kernel or counter: None,
    never 0, and nothing raises."""
    for name in NEW:
        read = sala_root.waiting_reader(name).read
        assert read(_made_run()) is None
        assert read(_made_run(
            {"decode_steps": 4000, "decode_joins": 400,
             "decode_prefill_rows": 90000},
            {"pallas:flash_fwd_q1": 1.0})) is None


def test_counter_readers_read_the_store_and_the_seats():
    run = _made_run({"decode_prefix_seats": 400,
                     "decode_prefix_seat_us": 1_200_000,
                     "prefix_cache_hit_rows": 400 * 23552,
                     "decode_prefill_rows": 400 * 71, "decode_joins": 400})
    assert sala_root.waiting_reader("prefix_seat_ms.serve").read(run) == 3.0
    assert sala_root.waiting_reader("prefix_hit_rows_pct.serve").read(run) \
        == pytest.approx(100 * 23552 / (23552 + 72))


def test_sparse_roofline_cannot_read_over_100(monkeypatch):
    """The bytes counted are the chosen blocks' published rows; the kernel
    copies every chosen block whole.  At the HBM's peak over exactly those
    blocks the share reads 100."""
    from benchmarks.metrics import moe_experts_roofline
    monkeypatch.setattr(moe_experts_roofline, "traced_steps", lambda run: 400)
    read = sala_root.waiting_reader("sparse_read_roofline").read
    per_step = 64 * 2 * 2 * 96                 # rows x layers x heads x blocks
    spent = 400 * per_step * 32768 / 819e9
    counters = {"decode_steps": 4000, "sparse_blocks_chosen": 4000 * per_step}
    assert read(_made_run(counters, {"pallas:sparse_fwd_q1": spent})) \
        == pytest.approx(100.0)
    assert read(_made_run(counters, {"pallas:sparse_fwd_q1": 4 * spent})) \
        == pytest.approx(25.0)
    assert read(_made_run(counters, {"pallas:flash_fwd_q1": spent})) is None


def test_the_cell_at_test_size_runs_and_is_correct(root):
    """Float32 on one backend: the engine, seated from the store, serves
    exactly the tokens the plain reference (no cache, full score matrices)
    puts first over document + question + answer, and every block it chose
    is the reference's own."""
    out = _run(root)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["compared"]["logit_gap_max"]["value"] < 1e-4
    assert out["compared"]["logit_gap_sq_mean"]["value"] < 1e-9
    assert out["compared"]["select_margin_max"]["value"] < 1e-6
    json.dumps(out)


def test_a_window_feeds_the_program_counter_readers(root):
    """What the counters' readers find in a tiny window on the CPU: every
    request seated from its document whole, the seats timed, the chosen
    blocks folded; the trace readers find no trace."""
    files = harness.Files(root)
    d, _ = _driver(root, sala_root.TINY, seed=5)
    try:
        run = d.window(0.5, None)
    finally:
        d.free()
    run.update(cfg=d.cfg, mix=d.mix, trace=None, peaks=None)
    c = run["window"]["counters"]
    # a join's lookup, seat and count are three moments of the router's
    # thread: an edge of the window can fall between them, once
    joined = [c[k] for k in ("prefix_cache_hits", "decode_prefix_seats",
                             "decode_joins")]
    assert min(joined) > 0 and max(joined) - min(joined) <= 1
    assert "prefix_cache_misses" not in c or c["prefix_cache_misses"] == 0
    assert 80 < files.reader("prefix_hit_rows_pct.serve")(run) < 100
    assert files.reader("prefix_seat_ms.serve")(run) > 0
    assert c["sparse_blocks_chosen"] == 4 * c["sparse_reads"] > 0
    assert c["decode_index_rows_live"] > 0
    assert run["window"]["state_bytes"].keys() == {"kv", "index",
                                                   "recurrent"}
    assert run["window"]["store"]["capacity_bytes"] \
        == int(1.5 * run["window"]["store"]["document_bytes"])
    for name in ("sparse_attn_share_pct.serve", "lightning_share_pct.serve",
                 "sparse_read_roofline"):
        assert files.reader(name)(run) is None


# ------------------------------------------------------ the planted faults

def _decay_of_another_head(monkeypatch):
    from hetu_tpu.ops import lightning
    real = lightning.decay_rates
    monkeypatch.setattr(lightning, "decay_rates",
                        lambda h: lightning.jnp.roll(real(h), 1))


def _snapshot_without_its_state(monkeypatch):
    """A seat that writes the slabs and leaves the recurrent state as the
    slot's last occupant left it."""
    from hetu_tpu.serving import DecodeEngine
    real = DecodeEngine._seat_snapshot

    def seat(self, slot, m, rows):
        kept = {n: self.caches[n] for n in self._recurrent}
        stale = {n: rows[n] * 0 + 0.5 for n in self._recurrent}
        real(self, slot, m, {**rows, **stale})
        del kept
    monkeypatch.setattr(DecodeEngine, "_seat_snapshot", seat)


def _window_one_block_short(monkeypatch):
    """The oldest block of the window dropped from every selective read."""
    from hetu_tpu.ops import sparse_attention as sparse
    real = sparse.SparseSizes.near
    monkeypatch.setattr(sparse.SparseSizes, "near",
                        property(lambda self: real.fget(self) - 1))


def _a_wrong_block_chosen(monkeypatch):
    """The last of the chosen gives way to the block that scored lowest
    among the candidates: the reference follows it, so only the selection
    check can tell."""
    from hetu_tpu.ops import sparse_attention as sparse
    real = sparse.select_blocks

    def select(scores, t, z):
        ids = real(scores, t, z)
        edge = (t // z.block - (z.near - 1))[..., None]
        j = sparse.jnp.arange(scores.shape[-1], dtype=sparse.jnp.int32)
        worst = sparse.jnp.argmin(sparse.jnp.where(
            sparse.jnp.logical_and(j >= z.init, j < edge), scores,
            sparse.jnp.inf), axis=-1).astype(sparse.jnp.int32)
        swapped = sparse.jnp.sort(ids.at[..., -1].set(worst), -1)
        return sparse.jnp.where(ids[..., :1] < 0, ids, swapped)
    monkeypatch.setattr(sparse, "select_blocks", select)


BREAKS = {"a_lightning_head_decays_as_its_neighbour": _decay_of_another_head,
          "a_seat_that_leaves_the_recurrent_state_stale":
              _snapshot_without_its_state,
          "the_window_one_block_short": _window_one_block_short,
          "a_wrong_block_chosen": _a_wrong_block_chosen}


@pytest.mark.parametrize("fault", ["control_precision"] + list(BREAKS))
def test_a_broken_run_is_not_correct(root, monkeypatch, fault):
    """The fp8 control in the program's place, and the timed path broken
    four ways, under the REAL cell's limits: ``correct`` comes out false.  A
    wrong block, which the reference follows, is caught by
    ``select_margin_max`` alone."""
    if fault in BREAKS:
        BREAKS[fault](monkeypatch)
    out = _run(root, control=fault == "control_precision")
    assert out["correct"] is False, out["compared"]
    if fault == "a_wrong_block_chosen":
        c = out["compared"]
        assert c["select_margin_max"]["value"] \
            > c["select_margin_max"]["limit"]
        assert c["logit_gap_max"]["value"] < 1e-4
