"""The one-token selected-block read against its roofline: the least time
the chip could take to read the key blocks the traced steps' queries CHOSE
(``sparse_bytes.chosen_bytes``: K and V of every chosen block of published
rows, over the peak HBM rate), over the device time of the kernel
``pallas:sparse_fwd_q1``.  The traced steps are COUNTED, as
``moe_experts_roofline`` counts them, and each is given the window's mean of
``sparse_blocks_chosen`` a step (summed over rows, sparse layers and key
heads by the program).  The kernel copies every chosen block whole, never
fewer bytes than are counted here, and the compressed keys the indexer
scores are read outside it and left out, so the share cannot pass 100."""
MOVES = "serve_tokens_per_s"
KERNEL = "pallas:sparse_fwd_q1"


def read(run):
    from benchmarks import sparse_bytes
    from benchmarks.metrics.moe_experts_roofline import traced_steps
    counters = run["window"]["counters"]
    chosen, steps = (counters.get(k) for k in ("sparse_blocks_chosen",
                                               "decode_steps"))
    if run["trace"] is None or run["peaks"] is None or not chosen \
            or not steps:
        return None
    spent = run["trace"]["op_s"].get(KERNEL, 0.0)
    traced = traced_steps(run)
    if spent <= 0 or not traced:
        return None
    least = sparse_bytes.chosen_bytes(run["cfg"], chosen / steps * traced) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / spent
