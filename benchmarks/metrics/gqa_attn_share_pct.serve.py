"""Device time of the gated softmax-attention layers (``mix.gqa``:
projections, the append to the KV slab, the one-token kernel's read of it,
gate and output product) as a share of busy time."""
from benchmarks import trace_scopes

MOVES = "serve_tokens_per_s"
SCOPE = "mix.gqa"


def read(run):
    got = trace_scopes.of_run(run, (SCOPE,))
    if not got or got[SCOPE] <= 0 or got["busy"] <= 0:
        return None                    # a program without this scope
    return 100.0 * got[SCOPE] / got["busy"]
