"""Model FLOP utilisation: the operations the step REQUIRES (the
configuration's ``train_flops`` function of ``flops.py``) times steps per
second, over the chip's bf16 peak for this ``device_kind``.  Nothing to
read off a device that is not in ``peaks.json``."""
MOVES = "train_tokens_per_s"


def read(run):
    from benchmarks import flops
    if run["peaks"] is None:
        return None
    win = run["window"]
    per_step = getattr(flops, run["cfg"]["train_flops"])(
        run["cfg"], win["batch"], win["seq_len"], win["masked"])
    return 100.0 * per_step * win["steps"] / win["seconds"] \
        / run["peaks"]["bf16_flops_per_s"]
