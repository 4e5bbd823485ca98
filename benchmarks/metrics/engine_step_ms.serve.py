"""Mean of the program's own ``step`` latency histogram over the window
(``DecodeEngine.step``: feeds, the jitted call, the logits read-back and
the host's argmax and bookkeeping)."""
MOVES = "itl_p90_ms"


def read(run):
    c = run["window"]["counters"]
    if not c.get("step_count"):
        return None
    return c["step_us_sum"] / c["step_count"] / 1e3
