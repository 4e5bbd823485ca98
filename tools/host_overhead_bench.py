"""Executor host-overhead microbench (reproducible evidence for the
dispatch-gap work: round 5 and the ISSUE 9 cached-run-plan path).

Delegates to ``bench.bench_overhead`` — ONE definition of the
measurement (``bench.py --config overhead`` and the tier-1 smoke test
run the same code).  See that docstring for the measured rows; in
short: ``raw_jit_us`` (bare jit floor), ``step_jit_us`` (the executor's
own program dispatched bare — its compute/thunk floor),
``device_feed_us``/``numpy_feed_us``/``pipelined_feed_us`` (executor
wall per step), ``dispatch_overhead_us`` (the executor's per-step host
Python, measured directly as wall minus in-jit time),
``overhead_multiple_vs_raw_jit`` = (raw + overhead) / raw — the host
tax the ISSUE 9 gate holds at <= 2.0 — and the ISSUE 10 tracing tax:
``trace_overhead_pct`` (the HETU_TRACE=1 span path's added host Python
over the untraced dispatch path, gated <= 25%).

Flags: ``--smoke`` runs the short CI-sized rounds, ``--no-artifact``
skips the artifacts/host_overhead.json write, ``--gate-only`` measures
just the gate quantities (raw-jit floor + interleaved overhead pairs +
tracing-tax pairs; one executor build instead of three — the tier-1
guard runs this tool as a fresh subprocess because the synchronous-
dispatch flag only lands in a process that has not initialized the CPU
client yet).

History (committed artifacts): round-5 start was 634 us/step on the
device-feed path; moving the per-step RNG fold inside the jitted
program and short-circuiting device_put on committed feeds brought it
to ~77 us/step; the cached run plans + traced-lr + fast-lane dispatch
of ISSUE 9 cut the per-step host Python itself to ~1x a raw dispatch.

Writes ``artifacts/host_overhead.json``.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    from bench import bench_overhead

    smoke = "--smoke" in sys.argv
    gate_only = "--gate-only" in sys.argv
    res = bench_overhead(
        smoke=smoke, gate_only=gate_only,
        write_artifact=not smoke and not gate_only
        and "--no-artifact" not in sys.argv)
    print(json.dumps(res["extra"] if "extra" in res else res))
    return 0 if "error" not in res else 1


if __name__ == "__main__":
    sys.exit(main())
