"""Device time of the linear-attention layers (``mix.kda``: projections,
short convolution, the gated delta rule over the carried state, gate and
output product) as a share of busy time."""
from benchmarks import trace_scopes

MOVES = "serve_tokens_per_s"
SCOPE = "mix.kda"


def read(run):
    got = trace_scopes.of_run(run, (SCOPE,))
    if not got or got[SCOPE] <= 0 or got["busy"] <= 0:
        return None                    # a program without this scope
    return 100.0 * got[SCOPE] / got["busy"]
