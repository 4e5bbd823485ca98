"""A throw-away benchmark root holding one cell: the Granite-4.0-H cell cut
to CPU size, judged by the REAL cell's limits (as ``sala_root.build`` does
for its cell).

The cell's two own readers wait in ``benchmarks/metrics_waiting/
granite4-h-micro/`` (a sub-directory: ``test_bench_minicpm_sala.py`` pins the
``.py`` files of the directory itself to sala's five) for the ``benchmark``
PR that loosens ``test_bench_glm4_moe_lite.py``'s pin on the end of
``per_layer``.  This root is what that PR makes of the real one: the readers
beside the others, their ``entries.json`` appended
(``tools/waiting_metrics.py --waiting granite4-h-micro`` makes the same of
the real cell for one run on the chip)."""
import importlib.util
import json
import os
import shutil

from conftest import BENCH, DATA, ROOT

REAL = "granite4-h-micro.chat-c64"
TINY = "tiny-granite.tiny-chat64"
SUB = "granite4-h-micro"
WAITING = os.path.join(BENCH, "metrics_waiting", SUB)
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
    shutil.copy(os.path.join(DATA, "tiny-granite.json"),
                os.path.join(data, "configs"))
    shutil.copy(os.path.join(DATA, "tiny-chat64.json"),
                os.path.join(data, "traffic"))
    shutil.copy(os.path.join(BENCH, "limits", REAL + ".json"),
                os.path.join(data, "limits", TINY + ".json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"] = [{"name": "tiny-granite",
                         "file": "benchmarks/configs/tiny-granite.json"}]
    bench["workloads"] = [{"name": TINY, "config": "tiny-granite",
                           "traffic": "tiny-chat64", "chips": 1}]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [TINY] if REAL in m["workloads"] else []
    bench["per_layer"] += [dict(m, workloads=[TINY]) for m in ENTRIES]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
