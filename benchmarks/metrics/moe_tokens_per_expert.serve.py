"""Tokens a touched expert took, on average: the (token, expert) pairs
whose expert is held here over the held experts that at least one token
chose, both summed over the layers and the steps of the window
(``moe_assignments_held`` / ``moe_experts_touched``).  The deployment's
batch over its experts, rows x k / experts, when every expert is
touched."""
MOVES = "serve_tokens_per_s"


def read(run):
    c = run["window"]["counters"]
    if not c.get("moe_experts_touched") or "moe_assignments_held" not in c:
        return None
    return c["moe_assignments_held"] / c["moe_experts_touched"]
