"""System under test: Solar-Open2 token-level serving through
``DecodeRouter``, as one chip's share of a layer.

The only file that knows how the program builds this server: the one-token
and chunked decode graphs of the share the configuration states (its
heads, its experts of all the router scores, its slice of the vocabulary),
one ``DecodeEngine`` over both — the greedy token ids and the chosen expert
ids fetched, the logits left on the device — reserved at the mix's batch
and length before the first request, one ``DecodeRouter`` in front.  Program
constructors, ``submit`` / ``start`` / ``close``, ``DecodeStream.aux`` and
the program's counters; nothing that starts with an underscore.
"""
import warnings

# at import, so that a program without this model refuses the cell before
# anything is built (importing the models initialises no backend)
from hetu_tpu.models import (SolarOpen2Config, solar_open2_decode_chunked_graph,
                             solar_open2_decode_graph)

from . import gpt2_decode
from .phi4flash_decode import storage

#: the auxiliary fetch that carries the chosen expert ids
CHOICES = "moe_choices"


def model_config(cfg, dtypes):
    lin, held = cfg["linear_attn_config"], cfg["held_experts"]
    return SolarOpen2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], linear_attn_heads=cfg["linear_attn_heads"],
        linear_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        gate_rank=cfg["assumed"]["gate_rank"],
        gqa_interval=cfg["gqa_interval"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=held["of"], held=(held["first"], held["count"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["assumed"]["initializer_range"],
        param_dtype=dtypes["param"], cache_dtype=dtypes["cache"],
        batch_size=1)


class System(gpt2_decode.System):
    kind = "closed_loop_decode"

    def __init__(self, cfg, mix, weights):
        from hetu_tpu.serving import DecodeEngine, DecodeRouter
        max_len, slots = int(mix["max_len"]), int(mix["max_slots"])
        mcfg = model_config(cfg, storage(cfg))
        feeds, logits, states, tokens, chosen = solar_open2_decode_graph(
            mcfg, max_len)
        cf, cl, cs, ctok, cchosen = solar_open2_decode_chunked_graph(
            mcfg, max_len)
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

    @staticmethod
    def choices(stream):
        """The expert ids the program chose at every position ``stream``'s
        sequence consumed: (positions, layers, k)."""
        return stream.aux(CHOICES)

    @staticmethod
    def counters():
        """As every serving system's, and which expert-layer products the
        program traced (``moe_calls``)."""
        from hetu_tpu.metrics import moe_call_counts
        out = gpt2_decode.System.counters()
        out.update({f"moe_calls:{k}": int(v)
                    for k, v in moe_call_counts().items()})
        return out
