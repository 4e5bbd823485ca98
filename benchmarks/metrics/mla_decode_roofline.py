"""The one-token latent read against its roofline: the least time the chip
could take to read the cache rows the traced steps' sequences HELD
(``mla_bytes.live_bytes``: the published row of every live position, once a
layer, over the peak HBM rate), over the device time of the kernel
``pallas:mla_fwd_q1``.  The traced steps are COUNTED, as
``moe_experts_roofline`` counts them, and each is given the window's mean
of ``decode_kv_rows_live`` a step.  The kernel fetches whole key blocks of
rows padded to whole lane rows, never fewer bytes than are counted here, so
the share cannot pass 100."""
MOVES = "serve_tokens_per_s"
KERNEL = "pallas:mla_fwd_q1"


def read(run):
    from benchmarks import mla_bytes
    from benchmarks.metrics.moe_experts_roofline import traced_steps
    counters = run["window"]["counters"]
    live, steps = (counters.get(k) for k in ("decode_kv_rows_live",
                                             "decode_steps"))
    if run["trace"] is None or run["peaks"] is None or not live or not steps:
        return None
    spent = run["trace"]["op_s"].get(KERNEL, 0.0)
    traced = traced_steps(run)
    if spent <= 0 or not traced:
        return None
    least = mla_bytes.live_bytes(run["cfg"], live / steps * traced) \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / spent
