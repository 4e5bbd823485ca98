"""Mean time to first token, submit -> first token on the client's side
of ``DecodeRouter.submit``, over EVERY request submitted in the window.
Not an end-to-end metric: with the order of requests drawn from the seed it
spreads by 7-9 % across seeds at any window the contract allows (PERF.md),
because whether a prompt meets enough other prompts to be ingested in
chunks is a coincidence of phases."""
MOVES = "serve_tokens_per_s"


def read(run):
    samples = run["window"]["ttft_s"]
    return 1e3 * sum(samples) / len(samples) if samples else None
