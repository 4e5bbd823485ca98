"""A throw-away benchmark root holding one cell: the GLM-4.7-Flash cell cut to
CPU size, judged by the REAL cell's limits (as ``phi4_root.build`` does for
its cell)."""
import json
import os
import shutil

from conftest import BENCH, DATA, ROOT

REAL = "glm47-flash.think-c128"
TINY = "tiny-glm.tiny-think"


def build(root):
    data = os.path.join(root, "benchmarks")
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(data, d))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(data, "metrics"))
    shutil.copy(os.path.join(BENCH, "peaks.json"), data)
    shutil.copy(os.path.join(DATA, "tiny-glm.json"),
                os.path.join(data, "configs"))
    shutil.copy(os.path.join(DATA, "tiny-think.json"),
                os.path.join(data, "traffic"))
    shutil.copy(os.path.join(BENCH, "limits", REAL + ".json"),
                os.path.join(data, "limits", TINY + ".json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny-glm",
                         "file": "benchmarks/configs/tiny-glm.json"}]
    bench["workloads"] = [{"name": TINY, "config": "tiny-glm",
                           "traffic": "tiny-think", "chips": 1}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY] if REAL in m["workloads"] else []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
