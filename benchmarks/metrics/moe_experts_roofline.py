"""The routed experts' product against its roofline: the least time the
chip could take to read the matrices of the experts the traced steps
TOUCHED (``moe_bytes.touched_bytes``, over the peak HBM rate — at a few
tokens an expert the bound is bytes), over the device time under
``moe.experts``, which also holds the sort and the gathers around the
product.  The traced steps are COUNTED — the program's ``decode.step``
spans inside the traced window — and each is given the window's mean of
``moe_experts_touched`` a step: a share of the window by time would count
the steps of a pause (starting the profiler can stall the server for a
second) that never ran.  An expert nobody chose is not counted, so a
product that skips it reads no more than 100."""
import os

from benchmarks import trace_reduce, trace_scopes

MOVES = "serve_tokens_per_s"
SCOPE = "moe.experts"
STEP = "decode.step"


def traced_steps(run):
    """How many of the program's step spans lie inside the traced window;
    None when the run was not traced here."""
    from benchmarks import harness
    try:
        path = trace_reduce.find_xplane(os.path.join(
            harness.ROOT, ".bench_out", "trace",
            f"{run['cfg']['name']}.{run['mix']['name']}"))
    except FileNotFoundError:
        return None
    # the one parse the scope readers share
    _, planes = trace_scopes._parsed(path, os.stat(path).st_mtime_ns)
    lo, hi = trace_reduce.window_of(planes)
    return sum(name == STEP and lo <= start and start + length <= hi
               for name, start, length in trace_reduce._host_events(planes))


def read(run):
    from benchmarks import moe_bytes
    counters = run["window"]["counters"]
    touched, steps = (counters.get(k) for k in ("moe_experts_touched",
                                                "decode_steps"))
    if run["trace"] is None or run["peaks"] is None or not touched \
            or not steps:
        return None
    got = trace_scopes.of_run(run, (SCOPE,))
    if not got or got[SCOPE] <= 0:
        return None
    traced = traced_steps(run)
    if not traced:
        return None
    least = moe_bytes.touched_bytes(run["cfg"], touched / steps * traced) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / got[SCOPE]
