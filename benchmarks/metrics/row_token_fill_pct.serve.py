"""Share of the row-tokens the steps computed that were not padding: prompt
and generated tokens consumed (``decode_prefill_rows`` +
``decode_generate_rows``) over batch bucket x chunk bucket summed over the
steps (``decode_padded_row_tokens``).  Empty slots and the unused columns of
a chunk are the rest."""
MOVES = "serve_tokens_per_s"


def read(run):
    c = run["window"]["counters"]
    if not c.get("decode_padded_row_tokens"):
        return None
    used = c.get("decode_prefill_rows", 0) + c.get("decode_generate_rows", 0)
    return 100.0 * used / c["decode_padded_row_tokens"]
