"""Bytes a Mamba-2 one-token state update has to move: what
``ssd_update_roofline`` holds the device time under ``ssd.update`` against.

A Mamba-2 layer keeps, for every sequence, one float32 matrix a head:
``mamba_n_heads x mamba_d_head x mamba_d_state`` values (64 x 64 x 128 x 4 B =
2 MiB at the published size).  ``S_t = a_t S_{t-1} + Δ_t x_t B_tᵀ`` touches
every one of them: a step READS the state of every row of its batch and
WRITES it back, in every Mamba layer, whatever the sequences' lengths.  The
step's inputs (``x``, ``B``, ``C``, ``Δ``: 17 KB a row and layer) and its
output are left out, which only lowers the share; the convolution's window
(52 KB a row and layer) is shifted outside ``ssd.update``.
"""

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def state_bytes(cfg, rows):
    """Bytes of the Mamba-2 states of ``rows`` sequences, all layers."""
    return (rows * cfg["layer_types"].count("mamba") * cfg["mamba_n_heads"]
            * cfg["mamba_d_head"] * cfg["mamba_d_state"]
            * ITEMSIZE[cfg["storage"]["recurrent"]])


def update_bytes(cfg, rows):
    """Least bytes one step moves for a batch of ``rows``: every state read
    once and written once."""
    return 2 * state_bytes(cfg, rows)
