"""Device time of the readers of the one shared KV cache — the full
attention layer (``mix.full``) and the cross-attention layers over its keys
and values (``mix.cross``) — as a share of busy time."""
from benchmarks import trace_scopes

MOVES = "serve_tokens_per_s"


def read(run):
    return trace_scopes.share(run, ("mix.full", "mix.cross"))
