"""Device time of the Mamba-2 state update and read-out (``ssd.update``: the
decay, the outer product into the ``(H, P, N)`` state, the product with
``C``; inside ``mix.ssm``) as a share of busy time; nothing for a program
without the scope."""
from benchmarks import trace_scopes

MOVES = "serve_tokens_per_s"
SCOPE = "ssd.update"


def read(run):
    return trace_scopes.share(run, (SCOPE,)) or None
