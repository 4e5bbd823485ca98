"""Device time of the decode attention kernels over device busy time: the
serving steps' only Pallas custom calls are the one-token kernel and its
chunked-prefill twin; the reducer names every such call ``pallas:<name>``."""
MOVES = "serve_tokens_per_s"
PATTERN = r"^pallas:"


def read(run):
    from benchmarks import trace_reduce
    if run["trace"] is None:
        return None
    return trace_reduce.share(run["trace"], PATTERN)
