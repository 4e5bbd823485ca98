"""Wall time per optimizer step over the whole window (host clock)."""
MOVES = "train_tokens_per_s"


def read(run):
    win = run["window"]
    return 1e3 * win["seconds"] / win["steps"]
