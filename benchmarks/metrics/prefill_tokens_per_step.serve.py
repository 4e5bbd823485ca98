"""Prompt tokens ingested per engine step (``decode_prefill_rows`` over
``decode_steps``): what chunked prefill and ``_pick_chunk`` achieve.  A row that ingests a
prompt emits nothing, so the faster prompts go in, the more rows generate."""
MOVES = "serve_tokens_per_s"


def read(run):
    c = run["window"]["counters"]
    if not c.get("decode_steps"):
        return None
    return c.get("decode_prefill_rows", 0) / c["decode_steps"]
