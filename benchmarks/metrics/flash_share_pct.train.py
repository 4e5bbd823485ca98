"""Device time of the flash-attention kernels over device busy time.  The
training step's only Pallas custom calls are the flash kernels of
``ops/pallas/flash_attention.py`` (forward under ``jvp``, backward under
``transpose_jvp``); the reducer names every such call ``pallas:<name>``."""
MOVES = "train_tokens_per_s"
PATTERN = r"^pallas:"


def read(run):
    from benchmarks import trace_reduce
    if run["trace"] is None:
        return None
    return trace_reduce.share(run["trace"], PATTERN)
