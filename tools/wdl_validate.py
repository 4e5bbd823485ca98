"""Wide&Deep cache-on vs cache-off loss-parity validation (BASELINE
config 4's real point: the HET bounded-staleness cache must not change what
the model learns; reference ``examples/embedding/ctr/README.md:33``).

Runs a few hundred WDL steps on Zipf-skewed Criteo-format data twice —
through the direct host store and through the LRU cache — and commits the
curves + AUCs + cache counters to ``artifacts/wdl_validation.json``.
CPU-safe: this validates numerics, not throughput.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "examples", "ctr"))


def main():
    import jax
    # numerics validation, not throughput: any backend will do (run it
    # with JAX_PLATFORMS=cpu off the chip); the artifact names the one used
    import models as ctr

    res = ctr.validate_cache_parity(steps=300, batch_size=512)
    res["backend"] = jax.default_backend()
    ok = (res["auc_cache_off"] > 0.65 and res["auc_cache_on"] > 0.65
          and res["final_divergence"]
          < 0.05 * abs(res["loss_curve_cache_off"][-1]) + 0.01)
    res["ok"] = bool(ok)
    os.makedirs(os.path.join(ROOT, "artifacts"), exist_ok=True)
    path = os.path.join(ROOT, "artifacts", "wdl_validation.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(res, f, indent=1)
    os.replace(tmp, path)
    print(json.dumps({k: v for k, v in res.items()
                      if not k.startswith("loss_curve")}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
