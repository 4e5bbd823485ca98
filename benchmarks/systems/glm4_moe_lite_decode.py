"""System under test: GLM-4.7-Flash token-level serving through
``DecodeRouter``, as one chip's share of an expert-parallel layer.

The only file that knows how the program builds this server: the one-token
and chunked decode graphs of the share the configuration states (its held
experts of all the router scores; attention, the shared expert, the dense
layer, embedding and head whole), one ``DecodeEngine`` over both — the
greedy token ids and the chosen expert ids fetched, the logits left on the
device — reserved at the mix's batch and length before the first request,
one ``DecodeRouter`` in front.  Program constructors, ``submit`` / ``start``
/ ``close``, ``DecodeStream.aux`` and the program's counters; nothing that
starts with an underscore.
"""
import warnings

import numpy as np

# at import, so that a program without this model refuses the cell before
# anything is built (importing the models initialises no backend)
from hetu_tpu.models import (Glm4MoeLiteConfig,
                             glm4_moe_lite_decode_chunked_graph,
                             glm4_moe_lite_decode_graph)

from . import solar_open2_decode
from .phi4flash_decode import storage
from .solar_open2_decode import CHOICES

#: the stem of the program's checkpoint names; the reference's spec names
#: its leaves under ``reference.STEM``, the one the routed driver asks for
STEM = "glm"


def model_config(cfg, dtypes):
    held = cfg["held_experts"]
    return Glm4MoeLiteConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=cfg["rope_theta"],
        intermediate_size=cfg["intermediate_size"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=held["of"], held=(held["first"], held["count"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["assumed"]["initializer_range"],
        param_dtype=dtypes["param"], cache_dtype=dtypes["cache"],
        batch_size=1)


class System(solar_open2_decode.System):
    kind = "closed_loop_decode"

    def __init__(self, cfg, mix, weights):
        from hetu_tpu.serving import DecodeEngine, DecodeRouter
        max_len, slots = int(mix["max_len"]), int(mix["max_slots"])
        mcfg = model_config(cfg, storage(cfg))
        feeds, logits, states, tokens, chosen = glm4_moe_lite_decode_graph(
            mcfg, max_len, name=STEM)
        cf, cl, cs, ctok, cchosen = glm4_moe_lite_decode_chunked_graph(
            mcfg, max_len, name=STEM)
        weights = {STEM + name[name.index("."):]: w
                   for name, w in weights.items()}
        with warnings.catch_warnings():
            # a parameter the reference does not name would be served at
            # its initializer's value: the program only warns
            warnings.filterwarnings("error", message="weights source")
            self.engine = DecodeEngine(
                feeds, logits, states, weights=weights, tokens=tokens,
                aux={CHOICES: chosen},
                aux_fold={CHOICES: mcfg.choice_counters()},
                max_slots=slots, max_len=max_len, seed=0,
                chunked=(cf, cl, cs, ctok, {CHOICES: cchosen}),
                max_chunk=int(mix["max_chunk"]))
        # a server of known size: its long-run buckets from the start
        self.engine.reserve(slots, max_len)
        self.router = DecodeRouter(self.engine, start=False,
                                   queue_limit=4 * slots)
        #: layers in front of the expert layers, which choose nothing
        self.dense_layers = mcfg.first_k_dense_replace

    def choices(self, stream):
        """The expert ids the program chose at every position ``stream``'s
        sequence consumed, (positions, layers, k): -1 in a dense layer,
        which the reference reads as "nothing to follow"."""
        chosen = stream.aux(CHOICES)
        return np.pad(chosen, ((0, 0), (self.dense_layers, 0), (0, 0)),
                      constant_values=-1)
