"""Time per engine step that the router's loop spends between one step's
return and the next one's entry while rows are seated: the loop's tail under
its lock, taking and seating joins, deadline eviction
(``decode_between_steps_us`` over ``decode_steps``)."""
MOVES = "itl_p90_ms"


def read(run):
    c = run["window"]["counters"]
    if not c.get("decode_steps") or "decode_between_steps_us" not in c:
        return None
    return c["decode_between_steps_us"] / c["decode_steps"] / 1e3
