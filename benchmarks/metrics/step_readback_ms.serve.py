"""Time per engine step in the read-back of the (batch, vocab) logits from
the device to the host once they are ready (``decode_step_readback_us`` over
``decode_steps``, the program's own phase counter)."""
MOVES = "itl_p90_ms"


def read(run):
    c = run["window"]["counters"]
    if not c.get("decode_steps") or "decode_step_readback_us" not in c:
        return None
    return c["decode_step_readback_us"] / c["decode_steps"] / 1e3
