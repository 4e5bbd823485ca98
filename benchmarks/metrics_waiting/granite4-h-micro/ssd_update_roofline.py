"""The Mamba-2 state update against its roofline: the least time the chip
could take to read and write the states of the batch the traced steps ran
(``ssd_bytes.update_bytes`` of the engine's batch, every Mamba layer, over
the peak HBM rate) over the device time under the scope ``ssd.update``.  The
traced steps are COUNTED, as ``moe_experts_roofline`` counts them; every
step, one-token or chunked, passes over every row's state once.  Read by
SCOPE, not by a kernel's name: whatever implements the update later is held
against the same bytes.  ``x``, ``B``, ``C`` and the output are left out, so
the share cannot pass 100."""
MOVES = "serve_tokens_per_s"
SCOPE = "ssd.update"


def read(run):
    from benchmarks import ssd_bytes, trace_scopes
    from benchmarks.metrics.moe_experts_roofline import traced_steps
    slots = run["window"].get("slots")
    if run["trace"] is None or run["peaks"] is None or not slots \
            or "mamba_n_heads" not in run["cfg"]:
        return None
    got = trace_scopes.of_run(run, (SCOPE,))
    if not got or got[SCOPE] <= 0:
        return None
    traced = traced_steps(run)
    if not traced:
        return None
    least = ssd_bytes.update_bytes(run["cfg"], slots) * traced \
        / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / got[SCOPE]
