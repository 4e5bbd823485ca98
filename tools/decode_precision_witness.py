"""A second witness for a serving cell's limits: is a sound run's gap to
the float32 reference ROUNDING, at the cell's own size?

A cell's ``correct`` grades the served tokens against the plain reference
in float32.  Where the configuration stores weights, keys and values in
bfloat16 that gap is not zero, and a limit set from sound runs alone bakes
in whatever the program reads at full width.  This runs the cell as the
harness does (set-up, the window, the window's sample), then

* grades the SAME sample against the reference in the stated precision
  (``precision="bfloat16"``: every product's operands through bfloat16,
  float32 sums) — a program that only rounds where the configuration says
  it rounds lies far closer to that reference than to the float32 one;
* reads the control of the stated precision: the bfloat16 reference's own
  first token under the float32 reference — what rounding alone costs;
* plants a fault in the live engine (a re-seated slot keeps its recurrent
  state), serves short requests through the re-used slots and grades them
  in float32 against the cell's limits.

A chip tool (``chiprun -- python tools/decode_precision_witness.py
--workload phi4-mini-flash.reason-c64 --seed N``); ``--root`` and
``--cpu 1`` run it at test size.  Prints one JSON line; PERF.md §4 holds
the readings.
"""
import argparse
import importlib
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def numbers(gaps):
    return {"logit_gap_max": float(gaps.max()),
            "logit_gap_sq_mean": float(np.square(gaps).mean()),
            "not_first": int((gaps > 0).sum()), "tokens": int(len(gaps))}


def main(argv):
    ap = argparse.ArgumentParser(prog="tools/decode_precision_witness.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--cpu", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault_requests", type=int, default=8)
    ap.add_argument("--fault_prompt", type=int, default=32)
    ap.add_argument("--fault_output", type=int, default=64)
    args = ap.parse_args(argv)
    from benchmarks import harness
    files = harness.Files(args.root)
    cell = files.cell(args.workload)
    cfg, mix = files.config(cell["config"]), files.mix(cell["traffic"])
    harness.device_info(cell["chips"], not args.cpu)
    d = importlib.import_module("benchmarks.drivers." + mix["driver"]).Driver(
        cfg=cfg, mix=mix, seed=args.seed, compiles=harness.CompileLog.get(),
        system=importlib.import_module("benchmarks.systems." + cfg["system"]),
        reference=importlib.import_module(
            "benchmarks.reference." + cfg["reference"]), log=harness.log)
    d.setup()
    d.window(args.seconds, None)
    sound = d.sample
    # the fault, planted where the tests plant it; the window's requests
    # still hold every slot, so each of these is seated where one of them
    # (or a lone prompt of set-up) left its state
    from hetu_tpu.serving import DecodeEngine
    DecodeEngine._clear_recurrent = lambda self, slot: None
    rng = np.random.default_rng([args.seed, 6])
    broken = d._serve_alone(
        [rng.integers(0, cfg["vocab_size"], args.fault_prompt, dtype=np.int32)
         for _ in range(args.fault_requests)], args.fault_output)
    d.free()
    out = {"workload": args.workload, "seed": args.seed,
           "limits": files.limits(args.workload),
           "sample": {"requests": len(sound)}}
    d.sample = sound
    out["served_under_float32"] = numbers(d.gaps())
    out["served_under_bfloat16"] = numbers(d.gaps(judge="bfloat16"))
    out["bfloat16_under_float32"] = numbers(d.gaps("bfloat16", served=False))
    d.sample = broken
    out["state_not_cleared_under_float32"] = numbers(d.gaps())
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
