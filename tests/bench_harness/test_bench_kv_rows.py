"""``kv_rows_read_pct.serve`` (ISSUE 30): the reader against hand-made
counters, a program without them, and a whole tiny chat window."""
import pytest

from conftest import TINY_SERVE

from benchmarks import harness
from test_bench_cells import _driver

NAME = "kv_rows_read_pct.serve"


def _read(root, counters):
    run = {"window": {"seconds": 51.0, "counters": counters}, "trace": None,
           "peaks": None}
    return harness.Files(root).reader(NAME)(run)


def test_the_entry_is_a_program_counter_of_the_kernels_layer(tiny_root):
    files = harness.Files(tiny_root)
    entry = next(m for m in files.bench["per_layer"] if m["name"] == NAME)
    assert entry == {"name": NAME, "unit": "%", "better": "lower",
                     "source": "program_counter", "layer": "kernels",
                     "moves": "serve_tokens_per_s",
                     "workloads": [TINY_SERVE]}
    assert NAME in {m["name"] for m in files.metrics("per_layer",
                                                      TINY_SERVE)}


@pytest.mark.parametrize("read,held,want", [
    (64 * 1536 * 1000, 64 * 4608 * 1000, 100.0 / 3),     # live blocks only
    (16 * 768 * 500, 16 * 768 * 500, 100.0),             # slabs read whole
    (0, 1024, 0.0)])
def test_the_reader_divides_rows_read_by_rows_held(tiny_root, read, held,
                                                   want):
    got = _read(tiny_root, {"decode_steps": 1000,
                            "decode_kv_rows_read": read,
                            "decode_kv_rows_held": held})
    assert got == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    {}, {"decode_steps": 1000},
    {"decode_steps": 1000, "decode_kv_rows_read": 5},
    {"decode_steps": 1000, "decode_kv_rows_held": 5},
    {"decode_steps": 0, "decode_kv_rows_read": 0, "decode_kv_rows_held": 0}],
    ids=["empty", "parent", "read_only", "held_only", "no_step"])
def test_a_program_without_the_counters_reads_nothing(tiny_root, counters):
    """The parent commit's run, or a window with no step: None, never 0."""
    assert _read(tiny_root, counters) is None


def test_a_tiny_chat_window_on_the_cpu_reads_its_slabs_whole(tiny_root):
    """The counters reach ``run["window"]["counters"]`` through the system
    file as it is; off the chip every step takes the jnp path: 100 %."""
    d, _ = _driver(tiny_root, TINY_SERVE)
    try:
        run = d.window(1.0, None)
    finally:
        d.free()
    c = run["window"]["counters"]
    assert c["decode_kv_rows_held"] > 0
    assert c["decode_kv_rows_read"] == c["decode_kv_rows_held"]
    assert harness.Files(tiny_root).reader(NAME)(run) == 100.0
