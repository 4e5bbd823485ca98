"""Bytes an expert layer has to read: what ``moe_experts_roofline`` holds
the device time under ``moe.experts`` against.

A routed expert is three matrices, gate and up ``(d, f)`` and down ``(f,
d)``.  A step reads an expert's matrices if at least one of its tokens
chose it, and need not otherwise, so the least traffic of a step is the
matrices of the experts TOUCHED — the program counts them
(``moe_experts_touched``, summed over the layers).  The activations (a few
rows of ``d`` and ``f`` per touched expert) are three orders of magnitude
below that at decode and are left out, which only lowers the share.
"""

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def expert_bytes(cfg):
    """Bytes of ONE routed expert as the configuration stores it."""
    return (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * ITEMSIZE[cfg["storage"]["weights"]])


def touched_bytes(cfg, experts_touched):
    """Least bytes read for ``experts_touched`` (expert, layer, step)
    triples."""
    return expert_bytes(cfg) * experts_touched
