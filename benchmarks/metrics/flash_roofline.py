"""The flash-attention calls against their roofline: the least time the
chip could take for the step's attention layers (``flops.flash_fwd_bwd`` at
the cell's batch, heads, sequence and head size in the compute dtype: the
larger of operations over peak FLOP/s and bytes over peak bytes/s), over
the kernels' device time per step.  At (32, 12, 512, 64) bf16 the bound is
compute."""
MOVES = "train_tokens_per_s"
PATTERN = r"^pallas:"


def read(run):
    import re
    from benchmarks import flops
    red, peak = run["trace"], run["peaks"]
    if red is None or peak is None:
        return None
    spent = sum(v for k, v in red["op_s"].items() if re.search(PATTERN, k))
    if spent <= 0:
        return None
    cfg, win = run["cfg"], run["window"]
    heads = cfg["num_attention_heads"]
    ops, nbytes = flops.flash_fwd_bwd(
        win["batch"], heads, win["seq_len"], cfg["hidden_size"] // heads,
        {"bfloat16": 2, "float32": 4}[cfg["compute_dtype"]])
    least, _ = flops.roofline_seconds(ops, nbytes, peak)
    steps_traced = red["window_s"] / (win["seconds"] / win["steps"])
    return 100.0 * least * cfg["num_hidden_layers"] * steps_traced / spent
