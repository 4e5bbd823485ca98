"""Device time of the latent-attention layers (``mix.mla``: the low-rank
query and key/value projections, the rotation, the append to the latent
cache, the absorbed read of it and the output product) as a share of busy
time."""
from benchmarks import trace_scopes

MOVES = "serve_tokens_per_s"
SCOPE = "mix.mla"


def read(run):
    got = trace_scopes.of_run(run, (SCOPE,))
    if not got or got[SCOPE] <= 0 or got["busy"] <= 0:
        return None                    # a program without this scope
    return 100.0 * got[SCOPE] / got["busy"]
