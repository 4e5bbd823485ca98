"""Device time under any token mixer's scope (``mix.*``) as a share of busy
time: what of a decode step is NOT the MLPs and the output head."""
from benchmarks import trace_scopes

MOVES = "serve_tokens_per_s"
SCOPES = ("mix.ssm", "mix.swa", "mix.full", "mix.cross", "mix.gmu")


def read(run):
    return trace_scopes.share(run, SCOPES)
