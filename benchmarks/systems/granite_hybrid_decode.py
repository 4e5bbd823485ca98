"""System under test: Granite-4.0-H token-level serving through
``DecodeRouter``.

The only file that knows how the program builds this server: the one-token
and chunked decode graphs of the whole model, one ``DecodeEngine`` over both
— the greedy token ids fetched, the logits left on the device — reserved at
the mix's batch and length before the first request, one ``DecodeRouter`` in
front.  Weights arrive as device arrays in the configuration's storage type
and are handed over as they are.  Program constructors, ``submit`` /
``start`` / ``close`` and the program's counters; nothing that starts with an
underscore.
"""
import warnings

# at import, so that a program without this model refuses the cell before
# anything is built (importing the models initialises no backend)
from hetu_tpu.models import (GraniteHybridConfig,
                             granite_hybrid_decode_chunked_graph,
                             granite_hybrid_decode_graph)

from . import gpt2_decode
from .phi4flash_decode import storage


def model_config(cfg, dtypes):
    a = cfg["assumed"]
    return GraniteHybridConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        shared_intermediate_size=cfg["shared_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        layer_types=cfg["layer_types"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=a["head_dim"]["value"], mamba_n_heads=cfg["mamba_n_heads"],
        mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_n_groups=cfg["mamba_n_groups"],
        mamba_d_conv=cfg["mamba_d_conv"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=a["weights"]["initializer_range"],
        param_dtype=dtypes["param"], cache_dtype=dtypes["cache"],
        batch_size=1)


class System(gpt2_decode.System):
    kind = "closed_loop_decode"

    def __init__(self, cfg, mix, weights):
        from hetu_tpu.serving import DecodeEngine, DecodeRouter
        max_len, slots = int(mix["max_len"]), int(mix["max_slots"])
        mcfg = model_config(cfg, storage(cfg))
        feeds, logits, states, tokens = granite_hybrid_decode_graph(
            mcfg, max_len)
        cf, cl, cs, ctok = granite_hybrid_decode_chunked_graph(mcfg, max_len)
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
