"""Mean chunk bucket of the chunked-prefill steps (``decode_chunk_width``
over ``decode_prefill_steps``): how wide ``_pick_chunk`` lets prompts go in.
Nothing to read in a window without a chunked step."""
MOVES = "serve_tokens_per_s"


def read(run):
    c = run["window"]["counters"]
    if not c.get("decode_prefill_steps") or not c.get("decode_chunk_width"):
        return None
    return c["decode_chunk_width"] / c["decode_prefill_steps"]
