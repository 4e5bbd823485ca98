"""Device time of the cache slabs' copies and in-place updates (operations
named ``copy*`` and ``dynamic-update-slice*``) over device busy time."""
MOVES = "serve_tokens_per_s"
PATTERN = r"^(copy|dynamic-update-slice)"


def read(run):
    from benchmarks import trace_reduce
    if run["trace"] is None:
        return None
    return trace_reduce.share(run["trace"], PATTERN)
