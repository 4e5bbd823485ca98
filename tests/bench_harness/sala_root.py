"""A throw-away benchmark root holding one cell: the MiniCPM-SALA cell cut to
CPU size, judged by the REAL cell's limits (as ``glm_root.build`` does for
its cell).

The cell's five own readers wait in ``benchmarks/metrics_waiting/``: an entry
of ``per_layer`` goes at the END of the list, and
``test_bench_glm4_moe_lite.py`` pins glm's three there, so the real
``BENCHMARK.json`` cannot list them until a ``benchmark`` PR loosens that pin.
This root is what that PR makes of the real one: the readers beside the
others, ``metrics_waiting/entries.json`` appended (``tools/waiting_metrics.py``
makes the same of the real cell for one run on the chip)."""
import importlib.util
import json
import os
import shutil

from conftest import BENCH, DATA, ROOT

REAL = "minicpm-sala.docqa-c64"
TINY = "tiny-sala.tiny-docqa"
WAITING = os.path.join(BENCH, "metrics_waiting")
with open(os.path.join(WAITING, "entries.json")) as _f:
    ENTRIES = json.load(_f)     # as they go at the end of ``per_layer``


def waiting_reader(name):
    """The module of a reader that waits, loaded as the harness loads one."""
    spec = importlib.util.spec_from_file_location(
        "waiting_" + name.replace(".", "_"),
        os.path.join(WAITING, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def build(root):
    data = os.path.join(root, "benchmarks")
    for d in ("configs", "traffic", "limits"):
        os.makedirs(os.path.join(data, d))
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(data, "metrics"))
    for m in ENTRIES:
        shutil.copy(os.path.join(WAITING, m["name"] + ".py"),
                    os.path.join(data, "metrics"))
    shutil.copy(os.path.join(BENCH, "peaks.json"), data)
    shutil.copy(os.path.join(DATA, "tiny-sala.json"),
                os.path.join(data, "configs"))
    shutil.copy(os.path.join(DATA, "tiny-docqa.json"),
                os.path.join(data, "traffic"))
    shutil.copy(os.path.join(BENCH, "limits", REAL + ".json"),
                os.path.join(data, "limits", TINY + ".json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny-sala",
                         "file": "benchmarks/configs/tiny-sala.json"}]
    bench["workloads"] = [{"name": TINY, "config": "tiny-sala",
                           "traffic": "tiny-docqa", "chips": 1}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY] if REAL in m["workloads"] else []
    bench["per_layer"] += [dict(m, workloads=[TINY]) for m in ENTRIES]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
