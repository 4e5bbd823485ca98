"""Device time of the mixture of experts — the router (``moe.route``), the
grouped product over the held experts (``moe.experts``) and the shared
expert (``moe.shared``) — as a share of busy time."""
from benchmarks import trace_scopes

MOVES = "serve_tokens_per_s"
SCOPES = ("moe.route", "moe.experts", "moe.shared")


def read(run):
    got = trace_scopes.of_run(run, SCOPES)
    spent = sum(got[s] for s in SCOPES) if got else 0
    if spent <= 0 or got["busy"] <= 0:
        return None                    # a program without these scopes
    return 100.0 * spent / got["busy"]
