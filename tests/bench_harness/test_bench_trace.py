"""The trace reducer on the small trace recorded on a TPU v5e
(``benchmarks/trace_fixture.py``): 20 dispatches of a four-iteration scan of
two matrix products, a 2 ms pause after every fifth.  The expected numbers
were read off the trace by hand (event by event) when it was recorded."""
import os

import pytest

from conftest import BENCH

from benchmarks import trace_reduce as tr


@pytest.fixture(scope="module")
def planes():
    return tr.load(os.path.join(BENCH, "trace_fixture.xplane.pb"))


def test_planes_and_lines_are_where_the_reducer_looks(planes):
    assert "/device:TPU:0" in planes
    ops = planes["/device:TPU:0"]["XLA Ops"]
    assert len(ops) == 260                 # 20 dispatches x 13 operations
    host = [n for p, lines in planes.items() if not p.startswith("/device")
            for ev in lines.values() for n, _, _ in ev]
    assert host.count(tr.WINDOW) == 1 and host.count("fixture.pause") == 4


def test_busy_idle_and_own_time(planes):
    red = tr.reduce(planes)
    assert red["devices"] == 1
    assert red["window_s"] == pytest.approx(0.01652283, rel=1e-6)
    assert red["busy_s"] == pytest.approx(231.301e-6, rel=1e-6)
    # nested events are not counted twice: the operations' own times add
    # up to the busy time, though their durations add up to 2.3 x as much
    assert sum(red["op_s"].values()) == pytest.approx(red["busy_s"])
    raw = sum(d for _, _, d in planes["/device:TPU:0"]["XLA Ops"]) / 1e9
    assert raw == pytest.approx(534.693e-6, rel=1e-6)
    assert red["device_ops"][0][0] == "convolution_multiply_fusion"
    assert red["device_ops"][0][1] == pytest.approx(190.779e-6, rel=1e-6)
    assert red["op_s"]["while"] == pytest.approx(1.192e-6, rel=1e-3)
    assert tr.share(red, r"^(copy|dynamic-update-slice)") \
        == pytest.approx(17.0038, rel=1e-4)
    assert tr.share(red, r"^pallas:") is None       # nothing to read: no 0


def test_idle_gaps_are_owned_by_what_the_host_did(planes):
    red = tr.reduce(planes)
    assert len(red["idle_gaps"]) == 10
    owners = [o for o, _ in red["idle_gaps"]]
    assert owners[:2] == ["fixture.pause", "fixture.pause"]
    assert red["idle_gaps"][0][1] == pytest.approx(0.004495477, rel=1e-6)
    assert all(a[1] >= b[1] for a, b in zip(red["idle_gaps"],
                                            red["idle_gaps"][1:]))


def test_operation_names():
    line = ("%infer.24 = (f32[256,128,64]{2,1,0}) custom-call(f32[256,128,64]"
            " %x), custom_call_target=\"tpu_custom_call\"")
    assert tr.op_name(line) == "pallas:infer"
    assert tr.op_name("%copy.11 = bf16[512,512]{1,0} copy(%y)") == "copy"
    assert tr.op_name("%fusion.119 = (f32[768,30522]{0,1}, f32[768]) "
                      "fusion(%p), kind=kOutput") == "fusion:f32[768,30522]"
    assert tr.op_name("%dynamic-update-slice.3 = f32[8]{0} "
                      "dynamic-update-slice(%a)") == "dynamic-update-slice"


def test_self_times_of_nested_intervals():
    got = tr.self_times([("%while.1 = x", 0.0, 100.0),
                         ("%a.1 = x", 10.0, 30.0), ("%b.2 = x", 40.0, 90.0),
                         ("%c.3 = x", 50.0, 60.0), ("%a.4 = x", 120.0, 130.0)])
    assert got == {"while": 30.0, "a": 30.0, "b": 40.0, "c": 10.0}
