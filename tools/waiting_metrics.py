"""One traced run of a cell with the readers that WAIT listed too.

    python tools/waiting_metrics.py --workload minicpm-sala.docqa-c64 \
        --seed 4200000301 --seconds 51

``benchmarks/metrics_waiting/`` holds per-layer readers and their entries
(``entries.json``; a later cell's in a sub-directory of its own, ``--waiting
granite4-h-micro``) that ``BENCHMARK.json`` cannot list yet (PERF.md §7: an
entry goes at the end of ``per_layer``, and a test the benchmark owns pins
another cell's entries there).  This builds, under ``.bench_out/``, the root a
``benchmark`` PR would make — the readers beside the others, the entries
appended — and runs ``benchmarks/run.py``'s own entry on it with
``--trace 1``: the result line on standard output as ever, the waiting
metrics among the others.
"""
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# the repo's one compile-cache rule, as benchmarks/run.py states it
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))


def build_root(dest, sub=""):
    """``dest``: ``BENCHMARK.json`` with the waiting entries appended, and
    ``benchmarks/`` with the waiting readers in ``metrics/``.  ``sub``: the
    sub-directory of ``metrics_waiting/`` whose readers wait (its own
    ``entries.json``); the directory itself by default."""
    bench_dir = os.path.join(ROOT, "benchmarks")
    waiting = os.path.join(bench_dir, "metrics_waiting", sub)
    shutil.rmtree(dest, ignore_errors=True)
    shutil.copytree(bench_dir, os.path.join(dest, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(waiting, "entries.json")) as f:
        entries = json.load(f)
    for m in entries:
        shutil.copy(os.path.join(waiting, m["name"] + ".py"),
                    os.path.join(dest, "benchmarks", "metrics"))
    bench["per_layer"] += entries
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return dest


def main(argv):
    import argparse
    from benchmarks import harness
    ap = argparse.ArgumentParser(prog="tools/waiting_metrics.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--waiting", default="",
                    help="sub-directory of benchmarks/metrics_waiting/")
    args = ap.parse_args(argv)
    out = os.path.join(ROOT, ".bench_out")
    files = harness.Files(build_root(os.path.join(out, "waiting_root"),
                                     args.waiting))
    try:
        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  True, files=files, t_start=T_START,
                                  out_dir=out)
    except harness.Refused as e:
        print(f"[bench] refused: {e}", file=sys.stderr, flush=True)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
