"""Verified collective overlap on the compute leg (ISSUE 13).

PR 6's stage-3 ZeRO moved the param all-gather of step N's updated
weights to the TOP of step N+1's program "where XLA's async scheduler
overlaps it with forward compute" — an ASSUMPTION until now.  This pass
reads the SCHEDULED, COMPILED HLO of the dp=4 zero=3 step and turns the
claim into a gated artifact, plus a Perfetto-trace twin over a MEASURED
run.

Two HLO modes, picked by what the backend emits:

* ``async-pairs`` (TPU): the compiled module carries
  ``all-gather-start`` / ``all-gather-done`` (and reduce-scatter)
  pairs.  The audit walks the entry computation in SCHEDULE order (a
  compiled module prints ``is_scheduled=true`` — textual order IS the
  schedule) and asserts real compute (``dot``/``convolution``/dot-
  bearing fusions) sits strictly BETWEEN each start and its done: the
  collective is in flight while the MXU works.
* ``dataflow`` (XLA-CPU lowers collectives synchronously — no
  start/done exists to bracket): the audit proves the overlap is
  STRUCTURALLY AVAILABLE to an async scheduler — for each ZeRO
  collective it counts the ``dot`` instructions that are neither
  ancestors nor descendants in the def-use graph (work a latency-hiding
  scheduler may run concurrently with the collective).  The FIRST param
  gather in schedule order is exempt from the per-gather floor: nothing
  upstream of the earliest gather exists to overlap with (its slack is
  the RNG/index preamble) — the GC3 discipline is about gathers 2..n
  riding behind earlier buckets' compute.  The artifact records
  ``mode`` and a ``device_note`` per the repo's CPU-honesty convention.

The ZeRO collectives are identified by their HLO metadata — the
partitioner stamps ``source_file=.../parallel/zero.py`` on the
constraint ops ``gather_full``/``apply_sharded`` emit (param gather /
grad reduce-scatter, lowered as all-reduce+slice on CPU), so the audit
never guesses which collective is whose.

Trace twin (``--trace``): a measured dp=4 zero=3 run under
``run(sync=False)`` with PR 10 tracing on.  Machine-checks the exported
events for (a) every ``jit.dispatch`` span ts-CONTAINED in its ``step``
span, and (b) ≥1 step whose dispatch lands while an earlier step's
async flow (dispatch → sync point) is still open — the gather-bearing
program of step N+1 was enqueued while step N was in flight, the host-
side half of the overlap the HLO proves available/scheduled on the
device side.

``main`` prints the verdict JSON and exits non-zero on failure;
``tools/hlo_audit.py --config zero`` embeds the same checks in
``artifacts/hlo_audit_{backend}.json`` (the regenerated-artifact half
of the acceptance).
"""
from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: audited config: buckets small enough that several gathers exist —
#: multi-bucket is HOW the overlap works (gather bucket k+1 behind
#: bucket k's compute); one 4 MB bucket would swallow bert-tiny whole
AUDIT_BUCKET_MB = "1"

#: dataflow mode: minimum overlappable dots per collective (param
#: gathers after the first; every grad reduce)
MIN_OVERLAP_DOTS = 1


# ------------------------------------------------------------- HLO parsing

def parse_entry(hlo_text):
    """The entry computation's instructions, in schedule order.

    Returns ``[{name, opcode, operands(indices), source, line}]``.
    Operand references are ``%name`` tokens resolved against names
    defined in the same computation (``calls=``/``to_apply=`` refs to
    other computations resolve to nothing and drop out).
    """
    m = re.search(r"^ENTRY [^{]*\{(.*?)^\}", hlo_text, re.M | re.S)
    if not m:
        raise ValueError("no ENTRY computation found in HLO text")
    instrs = []
    for raw in m.group(1).splitlines():
        lm = re.match(r"\s+(%[^\s=]+) = ", raw)
        if not lm:
            continue
        rest = raw[lm.end():]
        om = re.search(r"([a-z][\w\-]*)\(", rest)
        sm = re.search(r'source_file="([^"]*)" source_line=(\d+)', raw)
        instrs.append({
            "name": lm.group(1),
            "opcode": om.group(1) if om else "?",
            "refs": re.findall(r"%[\w.\-]+", rest),
            "source": sm.group(1) if sm else "",
            "srcline": int(sm.group(2)) if sm else 0,
        })
    idx = {ins["name"]: i for i, ins in enumerate(instrs)}
    for ins in instrs:
        ins["operands"] = sorted({idx[r] for r in ins["refs"]
                                  if r in idx and r != ins["name"]})
        del ins["refs"]
    return instrs


def _reach(starts, edges):
    seen = set(starts)
    stack = list(starts)
    while stack:
        i = stack.pop()
        for j in edges[i]:
            if j not in seen:
                seen.add(j)
                stack.append(j)
    return seen


def _is_zero_meta(ins):
    return "parallel/zero.py" in ins["source"].replace(os.sep, "/")


def audit_hlo(hlo_text):
    """Overlap verdicts over one compiled (scheduled) HLO module.

    Returns ``{mode, checks: {...}, detail: {...}}`` — callers gate on
    ``all(checks.values())``."""
    instrs = parse_entry(hlo_text)
    n = len(instrs)
    consumers = [[] for _ in range(n)]
    operands = [ins["operands"] for ins in instrs]
    for i in range(n):
        for j in operands[i]:
            consumers[j].append(i)
    dots = [i for i in range(n)
            if instrs[i]["opcode"] in ("dot", "convolution")]

    async_pairs = any(instrs[i]["opcode"] == "all-gather-start"
                      for i in range(n))
    mode = "async-pairs" if async_pairs else "dataflow"

    # ZeRO collectives by metadata: the param gather (gather_full's
    # sharding constraint) and the grad slab sync (apply_sharded's —
    # reduce-scatter on TPU, all-reduce+slice on CPU)
    gather_ops = ("all-gather", "all-gather-start")
    reduce_ops = ("reduce-scatter", "reduce-scatter-start",
                  "all-reduce", "all-reduce-start")
    gathers = [i for i in range(n)
               if instrs[i]["opcode"] in gather_ops and
               _is_zero_meta(instrs[i])]
    reduces = [i for i in range(n)
               if instrs[i]["opcode"] in reduce_ops and
               _is_zero_meta(instrs[i])]

    per_gather, per_reduce = [], []
    if mode == "async-pairs":
        # schedule-order bracketing: real compute strictly between each
        # start and its done (textual order == schedule for a compiled
        # module, is_scheduled=true)
        done_of = {}
        for i in range(n):
            if instrs[i]["opcode"].endswith("-done"):
                for j in operands[i]:
                    done_of[j] = i
        for g in gathers:
            d = done_of.get(g)
            inside = [k for k in dots if d is not None and g < k < d]
            per_gather.append({"name": instrs[g]["name"],
                               "done_found": d is not None,
                               "compute_inside": len(inside)})
        for g in reduces:
            d = done_of.get(g)
            inside = [k for k in dots if d is not None and g < k < d]
            per_reduce.append({"name": instrs[g]["name"],
                               "done_found": d is not None,
                               "compute_inside": len(inside)})
        gather_ok = bool(per_gather) and all(
            p["done_found"] and p["compute_inside"] >= 1
            for p in per_gather)
        reduce_ok = bool(per_reduce) and all(
            p["done_found"] and p["compute_inside"] >= 1
            for p in per_reduce)
    else:
        # dataflow availability: dots neither upstream nor downstream of
        # the collective can run concurrently under an async scheduler
        def overlappable(i):
            desc = _reach([i], consumers)
            anc = _reach([i], operands)
            return [d for d in dots if d not in desc and d not in anc]

        for g in gathers:
            per_gather.append({"name": instrs[g]["name"],
                               "overlappable_dots": len(overlappable(g))})
        for g in reduces:
            per_reduce.append({"name": instrs[g]["name"],
                               "overlappable_dots": len(overlappable(g))})
        # the FIRST gather in schedule order has no earlier bucket's
        # compute to hide behind — exempt from the per-gather floor
        later = per_gather[1:] if per_gather else []
        gather_ok = bool(per_gather) and (
            not later or all(p["overlappable_dots"] >= MIN_OVERLAP_DOTS
                             for p in later))
        reduce_ok = bool(per_reduce) and all(
            p["overlappable_dots"] >= MIN_OVERLAP_DOTS
            for p in per_reduce)

    return {
        "mode": mode,
        "checks": {
            "overlap_allgather_forward": gather_ok,
            "overlap_gradsync_backward": reduce_ok,
        },
        "detail": {
            "instructions": n,
            "dots": len(dots),
            "zero_param_gathers": per_gather,
            "zero_grad_reduces": per_reduce,
            "device_note": None if mode == "async-pairs" else (
                "XLA-CPU emits synchronous collectives (no "
                "all-gather-start/done to bracket); verdict is the "
                "DATAFLOW form — overlap structurally available to an "
                "async scheduler — per the CPU-honesty convention; the "
                "async-pair bracketing gates automatically on a TPU "
                "backend"),
        },
    }


# --------------------------------------------------------------- the config

def build_zero_config(dp=4, batch_size=4, seq_len=128):
    """The audited program: the dp=4 zero=3 bert-tiny of
    ``tools/audit_graphs.py``, with 1 MB ZeRO
    buckets so several param gathers exist to overlap.  The bucket env
    is scoped to the build — an explicit caller setting wins, and
    nothing leaks into later builds in the same process."""
    from tools.audit_graphs import build_bert_graph
    prev = os.environ.get("HETU_ZERO_BUCKET_MB")
    if prev is None:
        os.environ["HETU_ZERO_BUCKET_MB"] = AUDIT_BUCKET_MB
    try:
        cfg, ex, fd = build_bert_graph(batch_size=batch_size,
                                       seq_len=seq_len,
                                       size="tiny", dp=dp, zero=3)
        # build the jitted step INSIDE the env scope: the step-cache
        # signature reads HETU_ZERO_BUCKET_MB at build time and must see
        # the same value the bucket plan was constructed under (else a
        # later default-bucket build could alias this executable)
        ex.run("train", feed_dict=fd)
    finally:
        if prev is None:
            os.environ.pop("HETU_ZERO_BUCKET_MB", None)
    return ex, fd


def audit_zero_config(dp=4, batch_size=4, seq_len=128, ex=None, fd=None):
    """Compile the dp=4 zero=3 config (or audit a caller-built one) and
    audit its scheduled HLO."""
    import jax
    if len(jax.devices()) < dp:
        return {"mode": "skipped", "checks": {},
                "detail": {"skipped": f"needs >= {dp} devices, have "
                                      f"{len(jax.devices())}"}}
    from hetu_tpu.profiler import HetuProfiler
    if ex is None:
        ex, fd = build_zero_config(dp=dp, batch_size=batch_size,
                                   seq_len=seq_len)
    hlo = HetuProfiler(ex, name="train").hlo_text(fd)
    out = audit_hlo(hlo)
    out["detail"]["workload"] = {
        "dp": dp, "batch_size": batch_size, "seq_len": seq_len,
        "size": "tiny", "zero": 3,
        "zero_bucket_mb": os.environ.get("HETU_ZERO_BUCKET_MB",
                                         AUDIT_BUCKET_MB)}
    return out


# ------------------------------------------------------------ trace twin

def audit_trace_events(events, min_steps=2):
    """Machine-check exported PR 10 trace events for the measured-run
    containment: dispatch spans inside step spans, and ≥1 dispatch
    landing while an earlier step's async flow was still open."""
    steps = sorted((e for e in events
                    if e.get("ph") == "X" and e.get("name") == "step"),
                   key=lambda e: e["ts"])
    dispatches = [e for e in events
                  if e.get("ph") == "X" and e.get("name") == "jit.dispatch"]
    contained = 0
    for d in dispatches:
        d0, d1 = d["ts"], d["ts"] + d.get("dur", 0)
        if any(s["ts"] <= d0 and d1 <= s["ts"] + s.get("dur", 0)
               for s in steps):
            contained += 1
    # async flows: 's' opens at dispatch, 'f' closes at the sync point;
    # two flows open at once == the next step's program (whose top is
    # the stage-3 gather) was enqueued while the previous executed
    flow = [(e["ts"], 1 if e["ph"] == "s" else -1) for e in events
            if e.get("ph") in ("s", "f")
            and e.get("name") == "async_step"]
    depth = peak = 0
    for _ts, d in sorted(flow):
        depth += d
        peak = max(peak, depth)
    return {
        "checks": {
            "trace_step_spans": len(steps) >= min_steps,
            "trace_dispatch_contained":
                bool(dispatches) and contained == len(dispatches),
            "trace_async_inflight": peak >= 2,
        },
        "detail": {
            "step_spans": len(steps),
            "dispatch_spans": len(dispatches),
            "dispatch_contained": contained,
            "async_inflight_peak": peak,
        },
    }


def trace_twin(dp=4, batch_size=4, seq_len=128, steps=4, ex=None,
               fd=None):
    """The measured-run half: run the SAME dp=4 zero=3 config a few
    non-blocking steps with tracing on, export, machine-check."""
    import jax
    if len(jax.devices()) < dp:
        return {"checks": {}, "detail": {"skipped": "too few devices"}}
    from hetu_tpu import obs
    if ex is None:
        ex, fd = build_zero_config(dp=dp, batch_size=batch_size,
                                   seq_len=seq_len)  # compiles one step
    obs.clear_trace()
    obs.enable(True)
    try:
        for _ in range(steps):
            out = ex.run("train", feed_dict=fd, sync=False)
        ex._drain_async()
        del out
        events = obs.trace_events()
    finally:
        obs.enable(False)
        obs.clear_trace()
    return audit_trace_events(events, min_steps=steps - 1)


def run_overlap_audit(dp=4, batch_size=4, seq_len=128, trace=True):
    """Both halves over ONE build of the audited config — the entry
    callers gate on (three identical multi-second compiles otherwise:
    the HLO pass, the twin, and an hlo_audit host).  Returns the HLO
    verdict dict with the twin's checks merged in."""
    import jax
    if len(jax.devices()) < dp:
        return {"mode": "skipped", "checks": {},
                "detail": {"skipped": f"needs >= {dp} devices, have "
                                      f"{len(jax.devices())}"}}
    ex, fd = build_zero_config(dp=dp, batch_size=batch_size,
                               seq_len=seq_len)
    res = audit_zero_config(dp=dp, batch_size=batch_size,
                            seq_len=seq_len, ex=ex, fd=fd)
    if trace:
        tw = trace_twin(dp=dp, ex=ex, fd=fd)
        res["checks"].update(tw["checks"])
        res["detail"]["trace_twin"] = tw["detail"]
    return res


# ------------------------------------------------------------------- main

def main():
    import argparse
    p = argparse.ArgumentParser()
    p.add_argument("--dp", type=int, default=4)
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--no-trace", action="store_true",
                   help="skip the measured-run Perfetto twin")
    args = p.parse_args()

    res = run_overlap_audit(dp=args.dp, batch_size=args.batch_size,
                            seq_len=args.seq_len,
                            trace=not args.no_trace)
    res["ok"] = bool(res["checks"]) and all(res["checks"].values())
    print(json.dumps(res, indent=1, sort_keys=True))
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
