"""Share of the prompt tokens joined in the window that the prefix store
seated (``prefix_cache_hit_rows`` over hit rows + ``decode_prefill_rows`` +
one fed token a join): what of the prompts never went through the model.  A
program without a store has nothing to read."""
MOVES = "serve_tokens_per_s"


def read(run):
    c = run["window"]["counters"]
    hit = c.get("prefix_cache_hit_rows")
    if not hit:
        return None
    prompt = hit + c.get("decode_prefill_rows", 0) + c.get("decode_joins", 0)
    return 100.0 * hit / prompt
