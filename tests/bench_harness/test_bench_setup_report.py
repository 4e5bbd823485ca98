"""``tools/setup_report.py`` over whole runs at CPU size: what the program
compiled for a cell, by name, and the five numbers a ``setup_s`` reading
is explained by (ISSUE 39).  The benchmark prints none of it and no file
of the harness knows of it: the tool reads the program from outside."""
import importlib.util
import io
import json
import os
import time

import pytest

from conftest import ROOT, TINY_SERVE, TINY_TRAIN

from benchmarks import harness
from hetu_tpu import metrics
from hetu_tpu.graph import step_cache
from hetu_tpu.obs import compile_log


@pytest.fixture()
def tool():
    spec = importlib.util.spec_from_file_location(
        "setup_report", os.path.join(ROOT, "tools", "setup_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(tiny_root, workload, monkeypatch):
    """One run as ``run.py`` makes it, the window's start stamped where
    the tool stamps it: at the harness's ``setup_s`` line."""
    step_cache.clear()
    compile_log.clear()
    metrics.reset_all()
    window, log = [], harness.log

    def stamped(*parts):
        if parts and str(parts[0]).startswith("[bench] setup_s"):
            window.append(time.time())
        log(*parts)

    monkeypatch.setattr(harness, "log", stamped)
    out = harness.run_cell(workload, 3900000007, 1.0, False,
                           files=harness.Files(tiny_root),
                           require_tpu=False,
                           out_dir=os.path.join(tiny_root, "out"))
    return out, window[0]


@pytest.mark.parametrize("workload,owner", [(TINY_SERVE, "decode"),
                                            (TINY_TRAIN, "train")])
def test_the_report_names_every_program_of_a_cell(tiny_root, tool, workload,
                                                  owner, monkeypatch):
    out, t_window = _run(tiny_root, workload, monkeypatch)
    assert out["correct"] is True
    text = io.StringIO()
    blob = tool.report(t_window, out=text)
    lines = text.getvalue().splitlines()
    assert json.loads(lines[-1]) == json.loads(json.dumps(blob))
    mine = blob["programs"]
    assert mine and {p["owner"] for p in mine} == {owner}
    names = [p["program"] for p in mine]
    assert len(names) == len(set(names))        # every program once
    if owner == "decode":
        # the one-token program of the window's bucket and a program for
        # every chunk width the mix allows, each by its bucket key
        mix = harness.Files(tiny_root).mix(TINY_SERVE.split(".")[1])
        last = names[-1].split(":")
        assert {n.split(":")[1] for n in names
                if n.split(":")[2] == last[2]} >= {
            f"c{w}" for w in (1, 2, 4, 8) if w <= mix["max_chunk"]}
        # nothing compiled inside the window, and the counter says so
        assert blob["window_programs"] == []
        assert blob["window_decode_step_compile_us"] == 0
        assert blob["decode_step_compile_us"] == sum(
            p["trace_us"] + p["lower_us"] + p["backend_us"] for p in mine)
    five = blob["breakdown"]
    assert five["compile_s"] > 0 and five["trace_lower_s"] > 0
    assert five["program_build_s"] > 0
    assert five["compile_cache_hit_pct"] is None     # the suite's cache is off
    assert five["compile_unstored_s"] == 0
    # the program's share lies inside the run's set-up; what is left is
    # the harness's own: import, the weights' draw, warm-up traffic
    assert five["compile_s"] + five["trace_lower_s"] \
        + five["program_build_s"] < out["metrics"]["setup_s"]["value"]
    assert any(f"{owner}:{names[0]}" in line for line in lines)
