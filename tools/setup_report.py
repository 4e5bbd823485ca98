"""Where a start went: run a program, then print what it compiled.

    python tools/setup_report.py --workload gpt2-medium.chat-c16 \
        --seed 3900000001 --seconds 51 --trace 1

runs ONE run of a benchmark cell in this process (``benchmarks/run.py``'s
own entry, its arguments passed through, its result line on standard
output as ever) and then prints to standard error what the program's
compile log and set-up counters hold (``HetuProfiler.compile_log()``,
``compile_counters()``, ``setup_counters()``,
``metrics.setup_breakdown()``): one line a program of the owners
``train`` / ``serve`` / ``decode`` — trace, lower and backend seconds,
read from the persistent cache or compiled, stored or not — the totals
by owner (``other`` too: helpers, the reference), the five numbers a
``setup_s`` reading is explained by, and the window's share of
``decode_step_compile_us``.  The last line of standard error is one JSON
object of the same, for a script to read.

The benchmark prints none of this itself; an operator prints the same
after a slow start with four lines of Python (README, observability).
"""
import json
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# the repo's one compile-cache rule, as benchmarks/run.py states it
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))


def _fate(r):
    if r["cache"] == "hit":
        return f"read in {r['cache_read_us'] / 1e6:.3f}"
    if r["cache"] == "miss":
        return "stored" if r["stored"] else "NOT stored"
    return ""


def report(t_window=None, out=sys.stderr):
    """Print the report; ``t_window`` (``time.time()`` where the measured
    window began) splits ``decode_step_compile_us`` at it."""
    from hetu_tpu import metrics
    from hetu_tpu.profiler import HetuProfiler

    def say(*parts):
        print(*parts, file=out, flush=True)

    log = HetuProfiler.compile_log()
    counts = HetuProfiler.compile_counters()
    mine = [r for r in log if r["owner"] != "other"]
    say(f"[setup] {len(log)} newest compile records; the program's own:")
    for r in mine:
        say(f"[setup]   {r['owner']}:{r['program']:<22} "
            f"trace {r['trace_us'] / 1e6:7.3f}  "
            f"lower {r['lower_us'] / 1e6:7.3f}  "
            f"backend {r['backend_us'] / 1e6:7.3f}  "
            f"cache {r['cache']:<4} {_fate(r)}")
    for o in sorted({k.split(":", 1)[0] for k in counts}):
        c = {k.split(":", 1)[1]: v for k, v in counts.items()
             if k.startswith(o + ":")}
        say(f"[setup] {o}: {c.get('programs', 0)} programs, "
            f"trace {c.get('trace_us', 0) / 1e6:.3f} s, "
            f"lower {c.get('lower_us', 0) / 1e6:.3f} s, "
            f"backend {c.get('backend_us', 0) / 1e6:.3f} s; cache "
            f"{c.get('cache_hits', 0)} hits "
            f"({c.get('cache_read_us', 0) / 1e6:.3f} s reading), "
            f"{c.get('cache_misses', 0)} misses of which "
            f"{c.get('unstored', 0)} not stored "
            f"({c.get('unstored_us', 0) / 1e6:.3f} s)")
    setup = HetuProfiler.setup_counters()
    say(f"[setup] phases us {setup['us']} bytes {setup['bytes']}")
    five = metrics.setup_breakdown()
    say(f"[setup] breakdown {five}")
    blob = {"breakdown": five, "compile_counts": counts, "setup": setup,
            "decode_step_compile_us": metrics.decode_counts().get(
                "decode_step_compile_us", 0),
            "programs": mine}
    heads = HetuProfiler.mlm_head_calls()
    if heads:       # a training graph with a masked-LM head: its rows
        say(f"[setup] mlm_head_calls {heads}")
        blob["mlm_head_calls"] = heads
    if t_window is not None:
        late = [r for r in mine if r["t_end"] > t_window]
        blob["window_decode_step_compile_us"] = sum(
            r["trace_us"] + r["lower_us"] + r["backend_us"]
            for r in late if r["owner"] == "decode")
        blob["window_programs"] = [f"{r['owner']}:{r['program']}"
                                   for r in late]
        say(f"[setup] in the window: decode_step_compile_us "
            f"{blob['window_decode_step_compile_us']}, programs "
            f"{blob['window_programs']}")
    say(json.dumps(blob))
    return blob


if __name__ == "__main__":
    from benchmarks import harness
    window = []
    log = harness.log

    def stamped(*parts):
        # the harness says where set-up ends; the window begins there
        if parts and str(parts[0]).startswith("[bench] setup_s"):
            window.append(time.time())
        log(*parts)

    harness.log = stamped
    rc = harness.main(sys.argv[1:], T_START)
    report(window[0] if window else None)
    sys.exit(rc)
