"""Shared by the benchmark's own tests: a throw-away benchmark root built
from FILES ONLY (a tiny configuration, a tiny mix, limits, the real metric
readers), which is how a later PR adds a cell."""
import gc
import json
import os
import shutil
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
DATA = os.path.join(HERE, "data")
BENCH = os.path.join(ROOT, "benchmarks")

TINY_TRAIN = "tiny-bert.tiny-mlm"
TINY_SERVE = "tiny-gpt2.tiny-chat"
REAL_OF = {TINY_TRAIN: "bert-base.mlm-s512-b32",
           TINY_SERVE: "gpt2-medium.chat-c16"}


def build_root(root):
    """A benchmark root under ``root`` whose two cells are the real cells
    cut to CPU size, judged by the REAL cells' limits.  A metric's list
    keeps the cells this root stands in for and drops the others: a later
    PR appends its cell to such lists."""
    data = os.path.join(root, "benchmarks")
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(data, d))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(data, "metrics"))
    shutil.copy(os.path.join(BENCH, "peaks.json"), data)
    for name in ("tiny-bert", "tiny-gpt2"):
        shutil.copy(os.path.join(DATA, name + ".json"),
                    os.path.join(data, "configs"))
    for name in ("tiny-mlm", "tiny-chat"):
        shutil.copy(os.path.join(DATA, name + ".json"),
                    os.path.join(data, "traffic"))
    for tiny, real in REAL_OF.items():
        shutil.copy(os.path.join(BENCH, "limits", real + ".json"),
                    os.path.join(data, "limits", tiny + ".json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [
        {"name": n, "file": f"benchmarks/configs/{n}.json"}
        for n in ("tiny-bert", "tiny-gpt2")]
    bench["workloads"] = [
        {"name": w, "config": w.split(".")[0], "traffic": w.split(".")[1],
         "chips": 1} for w in REAL_OF]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [tiny for tiny, real in REAL_OF.items()
                              if real in m["workloads"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


@pytest.fixture()
def tiny_root(tmp_path):
    return build_root(str(tmp_path))


@pytest.fixture(autouse=True)
def _full_collections_back():
    """A driver's set-up turns full collections off for its window and
    ``free`` turns them on again; a test that stops short of ``free`` must
    not leave the rest of the run without them."""
    before = gc.get_threshold()
    yield
    gc.set_threshold(*before)
