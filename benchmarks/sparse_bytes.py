"""Bytes a selected-block attention read has to fetch: what
``sparse_read_roofline`` holds the device time of the one-token
selected-block kernel against.

Past ``dense_len`` keys a head group reads ``topk + window / block`` key
blocks of ``block_size`` rows, K and V, whatever the sequence's length; the
program counts the blocks its queries read a step, summed over rows, sparse
layers and key heads (``sparse_blocks_chosen``, folded from the chosen ids it
hands back).  A block is ``block_size`` PUBLISHED rows of one key head: K and
V of ``head_dim`` values in the cache's storage type (over both key heads
1,024 B a position).  The indexer reads the compressed keys beside them — one
row of ``head_dim`` values a key head for every ``kernel_stride`` positions
(512 B over both heads), ``decode_index_rows_live`` rows a slab and step —
but OUTSIDE the kernel (its scores are an XLA product), so
``sparse_read_roofline`` holds the kernel's time against the blocks alone and
``index_bytes`` is reported beside it.  The query rows and the output are
left out, which only lowers the share.
"""

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def sparse_sizes(cfg):
    return cfg["assumed"]["sparse"]["value"]


def block_bytes(cfg):
    """Bytes of ONE chosen block of one key head: K and V rows."""
    return (sparse_sizes(cfg)["block_size"] * 2 * cfg["head_dim"]
            * ITEMSIZE[cfg["storage"]["cache"]])


def chosen_bytes(cfg, blocks):
    """Least bytes read for ``blocks`` (query, sparse layer, key head,
    chosen block) tuples."""
    return block_bytes(cfg) * blocks


def index_bytes(cfg, rows_live):
    """Bytes of compressed keys the indexer scores for ``rows_live``
    (sequence, compressed row) pairs of one slab, every sparse layer and key
    head reading each once."""
    return (rows_live * cfg["mixer_types"].count("minicpm4")
            * cfg["num_key_value_heads"] * cfg["head_dim"]
            * ITEMSIZE[cfg["storage"]["cache"]])
