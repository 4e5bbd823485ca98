"""Share of the window's engine steps that were launched while the step
before them was still un-collected (``decode_launches_ahead`` over
``decode_steps``, both counted where a step is collected): how often the
router had the next step on the device before it read the last one back.
Not 100: the first step after an idle engine, a step behind a change of
the batch bucket, a step in flight collected alone.  A program without the
counter (the parent of PR 32) has nothing to read."""
MOVES = "serve_tokens_per_s"


def read(run):
    c = run["window"]["counters"]
    if not c.get("decode_steps") or "decode_launches_ahead" not in c:
        return None
    return 100.0 * c["decode_launches_ahead"] / c["decode_steps"]
