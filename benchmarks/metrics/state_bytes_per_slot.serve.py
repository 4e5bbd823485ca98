"""Device-resident per-sequence state a slot holds, in MB, all kinds
together (the engine's ``decode_state_bytes_<kind>_hw`` gauges over its
slots): what a step has to be able to read for one more row."""
MOVES = "itl_p90_ms"


def read(run):
    w = run["window"]
    if not w.get("state_bytes") or not w.get("slots"):
        return None
    return sum(w["state_bytes"].values()) / w["slots"] / 1e6
