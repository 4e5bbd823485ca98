"""System under test: Phi-4-mini-flash token-level serving through
``DecodeRouter``.

The only file that knows how the program builds this server: the one-token
and chunked SambaY decode graphs, one ``DecodeEngine`` over both — the
greedy token ids fetched, the logits left on the device — reserved at the
mix's batch and length before the first request, one ``DecodeRouter`` in
front.  Weights arrive as device arrays in the configuration's storage
type and are handed over as they are.  Program constructors, ``submit`` /
``start`` / ``close`` and the program's counters; nothing that starts with
an underscore.
"""
import warnings

# at import, so that a program without this model refuses the cell before
# anything is built (importing the models initialises no backend)
from hetu_tpu.models import (Phi4FlashConfig, phi4flash_decode_chunked_graph,
                             phi4flash_decode_graph)

from . import gpt2_decode


def model_config(cfg, dtypes):
    a = cfg["assumed"]
    return Phi4FlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        sliding_window=cfg["sliding_window"],
        mb_per_layer=cfg["mb_per_layer"],
        layer_norm_eps=cfg["layer_norm_eps"], d_state=a["d_state"],
        d_conv=a["d_conv"], expand=a["d_inner"] // cfg["hidden_size"],
        dt_rank=a["dt_rank"], param_dtype=dtypes["param"],
        cache_dtype=dtypes["cache"], batch_size=1)


def storage(cfg):
    """The storage types the configuration states (``storage``)."""
    import jax.numpy as jnp
    return {"param": jnp.dtype(cfg["storage"]["weights"]),
            "cache": jnp.dtype(cfg["storage"]["cache"])}


class System(gpt2_decode.System):
    kind = "closed_loop_decode"

    def __init__(self, cfg, mix, weights):
        from hetu_tpu.serving import DecodeEngine, DecodeRouter
        max_len, slots = int(mix["max_len"]), int(mix["max_slots"])
        mcfg = model_config(cfg, storage(cfg))
        feeds, logits, states, tokens = phi4flash_decode_graph(mcfg, max_len)
        cf, cl, cs, ctok = phi4flash_decode_chunked_graph(mcfg, max_len)
        with warnings.catch_warnings():
            # a parameter the reference does not name would be served at
            # its initializer's value: the program only warns
            warnings.filterwarnings("error", message="weights source")
            self.engine = DecodeEngine(
                feeds, logits, states, weights=weights, tokens=tokens,
                max_slots=slots, max_len=max_len, seed=0,
                chunked=(cf, cl, cs, ctok), max_chunk=int(mix["max_chunk"]))
        # a server of known size: its long-run buckets from the start
        self.engine.reserve(slots, max_len)
        self.router = DecodeRouter(self.engine, start=False,
                                   queue_limit=4 * slots)
