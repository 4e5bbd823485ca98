"""1 − (union of the device's operation intervals) / traced window."""
MOVES = "train_tokens_per_s"


def read(run):
    red = run["trace"]
    if red is None:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
