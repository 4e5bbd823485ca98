"""Host time of one seating from the prefix store, in ms
(``decode_prefix_seat_us`` over ``decode_prefix_seats``): the one donated
call that writes a snapshot's KV rows, index rows and recurrent state into a
slot, as the router's thread pays for it (its device time is in the trace,
queued behind the step in flight).  A program that seats nothing, or has no
such counter, has nothing to read."""
MOVES = "serve_tokens_per_s"


def read(run):
    c = run["window"]["counters"]
    if not c.get("decode_prefix_seats") or "decode_prefix_seat_us" not in c:
        return None
    return c["decode_prefix_seat_us"] / c["decode_prefix_seats"] / 1e3
