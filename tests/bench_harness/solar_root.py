"""A throw-away benchmark root holding one cell: the Solar-Open2 cell cut to
CPU size, judged by the REAL cell's limits (as ``phi4_root.build`` does for
its cell)."""
import json
import os
import shutil

from conftest import BENCH, DATA, ROOT

REAL = "solar-open2.assist-c128"
TINY = "tiny-solar.tiny-assist"


def build(root):
    data = os.path.join(root, "benchmarks")
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(data, d))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(data, "metrics"))
    shutil.copy(os.path.join(BENCH, "peaks.json"), data)
    shutil.copy(os.path.join(DATA, "tiny-solar.json"),
                os.path.join(data, "configs"))
    shutil.copy(os.path.join(DATA, "tiny-assist.json"),
                os.path.join(data, "traffic"))
    shutil.copy(os.path.join(BENCH, "limits", REAL + ".json"),
                os.path.join(data, "limits", TINY + ".json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny-solar",
                         "file": "benchmarks/configs/tiny-solar.json"}]
    bench["workloads"] = [{"name": TINY, "config": "tiny-solar",
                           "traffic": "tiny-assist", "chips": 1}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY] if REAL in m["workloads"] else []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
