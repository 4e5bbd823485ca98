"""The per-layer metrics that read the program's own phase counters (ISSUE
25): each reader against a hand-made run, and a whole tiny run's window."""
import pytest

from conftest import TINY_SERVE

from benchmarks import harness
from test_bench_cells import _driver

# a window of 1,000 steps of 47 ms as the program would count it
COUNTERS = {
    "decode_steps": 1000, "decode_joins": 60, "decode_prefill_steps": 50,
    "decode_prefill_rows": 4000, "decode_generate_rows": 13000,
    "decode_step_plan_us": 40_000, "decode_step_feed_us": 30_000,
    "decode_step_dispatch_us": 900_000, "decode_step_wait_us": 40_000_000,
    "decode_step_readback_us": 3_500_000, "decode_step_host_us": 2_000_000,
    "decode_between_steps_us": 300_000, "decode_join_wait_us": 45_000,
    "decode_padded_row_tokens": 20_000, "decode_chunk_width": 500}
WANT = {
    "step_wait_ms.serve": 40.0, "step_readback_ms.serve": 3.5,
    "step_host_ms.serve": 2.97, "between_steps_ms.serve": 0.3,
    "queue_wait_ms.serve": 0.75, "row_token_fill_pct.serve": 85.0,
    "chunk_width_mean.serve": 10.0, "chunked_step_share_pct.serve": 5.0}
NEEDS = {
    "step_wait_ms.serve": "decode_step_wait_us",
    "step_readback_ms.serve": "decode_step_readback_us",
    "step_host_ms.serve": "decode_step_host_us",
    "between_steps_ms.serve": "decode_between_steps_us",
    "queue_wait_ms.serve": "decode_join_wait_us",
    "row_token_fill_pct.serve": "decode_padded_row_tokens",
    "chunk_width_mean.serve": "decode_chunk_width",
    "chunked_step_share_pct.serve": "decode_prefill_steps"}


def _read(root, name, counters):
    run = {"window": {"seconds": 51.0, "counters": counters}, "trace": None,
           "peaks": None}
    return harness.Files(root).reader(name)(run)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_phase_metric_reads_its_counters(tiny_root, name):
    files = harness.Files(tiny_root)
    entry = next(m for m in files.bench["per_layer"] if m["name"] == name)
    assert entry["source"] == "program_counter"
    assert entry["workloads"] == [TINY_SERVE]
    assert _read(tiny_root, name, COUNTERS) == pytest.approx(WANT[name])
    # a program without the counter (the parent commit): nothing, never 0
    older = {k: v for k, v in COUNTERS.items() if k != NEEDS[name]}
    assert _read(tiny_root, name, older) is None
    assert _read(tiny_root, name, {}) is None


def test_a_window_without_a_chunked_step_has_no_chunk_width(tiny_root):
    quiet = dict(COUNTERS, decode_prefill_steps=0, decode_chunk_width=0)
    assert _read(tiny_root, "chunk_width_mean.serve", quiet) is None


def test_a_tiny_chat_window_holds_every_phase_counter(tiny_root):
    """The system file passes the whole ``decode`` family through, so the
    new counters reach ``run["window"]["counters"]`` with no edit to it, and
    the readers find them."""
    d, _ = _driver(tiny_root, TINY_SERVE)
    try:
        run = d.window(1.0, None)
    finally:
        d.free()
    c = run["window"]["counters"]
    assert set(NEEDS.values()) <= set(c)
    assert all(f"decode_step_{p}_us" in c
               for p in ("plan", "feed", "dispatch", "host"))
    steps = c["decode_steps"]
    assert steps > 0 and c["decode_between_steps_us"] > 0
    # the phases of the window's steps: feed ... host is the step histogram
    inner = sum(c[f"decode_step_{p}_us"] for p in (
        "feed", "dispatch", "wait", "readback", "host"))
    assert inner == pytest.approx(c["step_us_sum"], rel=0.05)
    assert steps <= c["decode_padded_row_tokens"] <= steps * 4 * 4
    files = harness.Files(tiny_root)
    got = {m["name"]: files.reader(m["name"])(run)
           for m in files.metrics("per_layer", TINY_SERVE)
           if m["name"] in WANT}
    assert set(got) == set(WANT)
    width = got.pop("chunk_width_mean.serve")
    assert width is None or 2 <= width <= 4
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert 0 < got["chunked_step_share_pct.serve"] <= 100
    assert 0 < got["row_token_fill_pct.serve"] <= 100
