"""Time per engine step that the host spends waiting for the step's logits
to be ready on the device (``decode_step_wait_us`` over ``decode_steps``,
the program's own phase counter): the device's share of a step as the host
sees it.  A step that skips the logits has no such phase."""
MOVES = "itl_p90_ms"


def read(run):
    c = run["window"]["counters"]
    if not c.get("decode_steps") or "decode_step_wait_us" not in c:
        return None
    return c["decode_step_wait_us"] / c["decode_steps"] / 1e3
