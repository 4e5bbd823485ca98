"""``python benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of ``BENCHMARK.json``.

One process, on the machine it is started on.  The last line of standard
output is the result object; a run that cannot be made (no TPU, too few
chips, an unknown cell, a program that is not there) exits non-zero and
prints none.  ``setup_s`` counts from this file's first line.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
# the repo's one compile-cache rule: jax's variable if set, else a fixed
# directory inside the checkout (the path is part of the cache's key)
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(ROOT, ".jax_cache"))

if __name__ == "__main__":
    from benchmarks import harness
    sys.exit(harness.main(sys.argv[1:], T_START))
