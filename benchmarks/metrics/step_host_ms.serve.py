"""The engine's own Python per step: the ``plan``, ``feed``, ``dispatch``
and ``host`` phases of ``DecodeEngine.step`` (chunk pick and plan lookup,
host feeds, the jitted call until it returns; argmax, emission, stream
callbacks and bookkeeping) over ``decode_steps``, from the program's phase
counters."""
MOVES = "itl_p90_ms"
PHASES = ("plan", "feed", "dispatch", "host")


def read(run):
    c = run["window"]["counters"]
    kinds = [f"decode_step_{p}_us" for p in PHASES]
    if not c.get("decode_steps") or any(k not in c for k in kinds):
        return None
    return sum(c[k] for k in kinds) / c["decode_steps"] / 1e3
