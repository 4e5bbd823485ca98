"""Device time of the Lightning linear-attention layers (``mix.lightning``:
the projections, the per-head norms, the rotation, the read and write of the
``(H, D, D)`` state, the output norm, gate and product) as a share of busy
time; nothing for a program without the scope."""
from benchmarks import trace_scopes

MOVES = "serve_tokens_per_s"
SCOPE = "mix.lightning"


def read(run):
    return trace_scopes.share(run, (SCOPE,)) or None
