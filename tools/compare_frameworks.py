"""Side-by-side framework comparison on identical workloads.

Runs each requested config through OUR bench (bench.py child path) and the
PyTorch baseline (examples/compare/torch_baselines.py) on the SAME machine
and prints a merged JSON table — the reference's comparison methodology
(``examples/cnn/tf_main.py`` etc.) with committed, reproducible scripts.

Ours measures on the TPU (``bench.py`` refuses any other backend for these
configs) while torch on this image is CPU-only: the table is a cross-device
comparison and says so through each side's ``backend`` field.  This parent
never touches jax; the children run one after another.
"""
import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _run(cmd, env=None, timeout=900):
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, env=env, cwd=ROOT)
    except subprocess.TimeoutExpired:
        # degrade to an error row — one hung child must not lose the
        # other configs' results
        return {"error": f"timed out after {timeout}s"}
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return {"error": f"rc={proc.returncode}: {proc.stderr[-300:]}"}


# CPU-feasible batch sizes used for BOTH frameworks when --batch-size is
# absent — an identical workload is the whole point; letting each side pick
# its own default would compare different batch sizes
CPU_BATCH = {"bert": 8, "resnet18": 64, "wdl": 512, "moe": 1024}
# likewise the bert seq_len MUST be pinned on both sides: bench.py's
# flagship default moved to seq 512 while the torch baseline defaults to
# 128 — unpinned, the "speedup" would compare different workloads
DEFAULT_SEQ = {"bert": 128}


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--configs", default="resnet18,wdl",
                   help="comma list of bert,resnet18,wdl,moe")
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--seq-len", type=int, default=None,
                   help="bert sequence length, pinned on BOTH sides")
    args = p.parse_args()
    configs = [c.strip() for c in args.configs.split(",") if c.strip()]
    unknown = [c for c in configs if c not in CPU_BATCH]
    if unknown:
        p.error(f"unknown config(s) {unknown}; choose from "
                f"{sorted(CPU_BATCH)}")
    out = {}
    for config in configs:
        bs = args.batch_size or CPU_BATCH[config]
        extra = ["--batch-size", str(bs), "--steps", str(args.steps)]
        if config in DEFAULT_SEQ:
            extra += ["--seq-len", str(args.seq_len or DEFAULT_SEQ[config])]
        ours_extra = list(extra)   # bench.py-only flags stay off the
        if config == "wdl":        # torch script's argv
            # same-semantics comparison: torch's baseline is a PLAIN
            # embedding, so ours must be too; the HET-cache number is
            # measured separately below and reported alongside
            ours_extra += ["--wdl-embed", "dense"]
        # ours measures on the chip in its own process (bench.py refuses
        # any other backend); this parent never touches jax, and the
        # children run one after another — one process per chip
        ours = _run([sys.executable, os.path.join(ROOT, "bench.py"),
                     "--config", config] + ours_extra)
        theirs = _run([sys.executable,
                       os.path.join(ROOT, "examples", "compare",
                                    "torch_baselines.py"),
                       "--config", config] + extra)
        row = {"ours": ours, "torch": theirs}
        if config == "wdl":
            if "error" in ours:
                # the dense run already burnt its budget on a down
                # backend — don't spend another timeout hitting the same
                # wall; stamp the reason instead
                row["ours_het_cache"] = {
                    "error": f"skipped: dense run failed ({ours['error'][:120]})"}
            else:
                row["ours_het_cache"] = _run(
                    [sys.executable, os.path.join(ROOT, "bench.py"),
                     "--config", "wdl"] + extra + ["--wdl-embed", "lru"])
        ov, tv = ours.get("value"), theirs.get("value")
        if ov and tv:
            higher_better = ours.get("unit", "") != "ms/step"
            row["speedup_ours_over_torch"] = round(
                (ov / tv) if higher_better else (tv / ov), 3)
        out[config] = row
    from artifact_schema import provenance
    out["provenance"] = provenance(
        {c: {"batch_size": args.batch_size or CPU_BATCH[c],
             **({"seq_len": args.seq_len or DEFAULT_SEQ[c]}
                if c in DEFAULT_SEQ else {}),
             # wdl measures BOTH embed modes (dense = the comparison row,
             # lru = the HET-cache row) — the hash must say so
             **({"embed": ["dense", "lru"]} if c == "wdl" else {})}
         for c in configs})
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
