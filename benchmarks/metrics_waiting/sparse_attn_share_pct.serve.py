"""Device time of the block-sparse attention layers (``mix.sparse``: the
projections, the per-head norms, the appends to the K, V and compressed-key
slabs, the pooling sums, the indexer, the selected-block read and the output
product) as a share of busy time; nothing for a program without the scope."""
from benchmarks import trace_scopes

MOVES = "serve_tokens_per_s"
SCOPE = "mix.sparse"


def read(run):
    return trace_scopes.share(run, (SCOPE,)) or None
