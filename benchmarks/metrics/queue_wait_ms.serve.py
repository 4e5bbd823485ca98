"""Mean time from ``submit`` to a seat in the batch
(``decode_join_wait_us`` over ``decode_joins``): the queueing share of time
to first token; what is left of it is prompt ingestion."""
MOVES = "serve_tokens_per_s"


def read(run):
    c = run["window"]["counters"]
    if not c.get("decode_joins") or "decode_join_wait_us" not in c:
        return None
    return c["decode_join_wait_us"] / c["decode_joins"] / 1e3
