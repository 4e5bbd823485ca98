"""Bytes a latent-attention read has to fetch: what ``mla_decode_roofline``
holds the device time of the one-token latent kernel against.

A token leaves one cache row a layer, ``[c; k_rope]``: ``kv_lora_rank +
qk_rope_head_dim`` values in the cache's storage type — the PUBLISHED row,
whatever lanes the program pads its stored row to.  A one-token step has to
read every row its sequences hold, in every layer, once: the program counts
those rows a step (``decode_kv_rows_live``).  The query rows, the output
and the weights of the absorbed projections are left out, which only
lowers the share.
"""

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def row_bytes(cfg):
    """Bytes of ONE published cache row (one token, one layer)."""
    return ((cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * ITEMSIZE[cfg["storage"]["cache"]])


def live_bytes(cfg, rows_live):
    """Least bytes read for ``rows_live`` (sequence row, step) pairs, every
    layer reading each once."""
    return row_bytes(cfg) * cfg["num_hidden_layers"] * rows_live
