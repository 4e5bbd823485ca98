"""Share of the KV slabs' key rows that the steps' attention fetched
(``decode_kv_rows_read`` over ``decode_kv_rows_held``, both summed over the
slots of the batch bucket and the steps of the window): 100 where every step
reads its slabs whole (a chunked step, the jnp path), the live key blocks of
each slot where the one-token kernel serves.  A program without the counters
(the parent of PR 30) has nothing to read."""
MOVES = "serve_tokens_per_s"


def read(run):
    c = run["window"]["counters"]
    if not c.get("decode_kv_rows_held") or "decode_kv_rows_read" not in c:
        return None
    return 100.0 * c["decode_kv_rows_read"] / c["decode_kv_rows_held"]
