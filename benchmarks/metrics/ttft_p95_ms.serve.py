"""95th percentile of the time to first token, over every request
submitted in the window.  Recorded, judged nowhere: three or four samples
lie beyond it, and across seeds it spreads by 13 % (PERF.md)."""
MOVES = "serve_tokens_per_s"


def read(run):
    from benchmarks.drivers.closed_loop_decode import percentile
    samples = run["window"]["ttft_s"]
    return 1e3 * percentile(samples, 95) if samples else None
