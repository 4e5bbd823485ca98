"""Share of the window's engine steps that were chunked-prefill steps
(``decode_prefill_steps`` over ``decode_steps``): every row of such a step
computes the chunk's width and reads its slabs whole, so its gap is the
slow one.  Near 5 the 95th percentile of the gaps falls into the chunked
population on one seed and into the one-token population on the next
(PERF.md section 6, PR 35).  A program that has never counted a chunked
step has nothing to read."""
MOVES = "itl_p90_ms"


def read(run):
    c = run["window"]["counters"]
    if not c.get("decode_steps") or "decode_prefill_steps" not in c:
        return None
    return 100.0 * c["decode_prefill_steps"] / c["decode_steps"]
