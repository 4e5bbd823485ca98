"""Device time of the state-space side of the model — the Mamba layers
(``mix.ssm``) and the gated memory units that read their memory
(``mix.gmu``) — as a share of busy time."""
from benchmarks import trace_scopes

MOVES = "serve_tokens_per_s"


def read(run):
    return trace_scopes.share(run, ("mix.ssm", "mix.gmu"))
