"""System under test: MiniCPM-SALA token-level serving through
``DecodeRouter``, seated from a prefix store.

The only file that knows how the program builds this server: the one-token
and chunked decode graphs of the cut the configuration states, one
``DecodeEngine`` over both — the greedy token ids and the chosen far blocks
fetched, the logits left on the device — reserved at the mix's batch and
length before the first request, a ``PrefixKVStore`` sized from the mix's
documents beside it, one ``DecodeRouter`` in front.  Program constructors,
``submit`` / ``start`` / ``close``, ``DecodeStream.aux`` / ``aux_from`` and
the program's counters; nothing that starts with an underscore.
"""
import warnings

# at import, so that a program without this model refuses the cell before
# anything is built (importing the models initialises no backend)
from hetu_tpu.models import (MiniCPMSALAConfig,
                             minicpm_sala_decode_chunked_graph,
                             minicpm_sala_decode_graph)

from . import gpt2_decode
from .phi4flash_decode import storage

#: the stem of the program's checkpoint names; the reference's spec names
#: its leaves under ``reference.STEM``
STEM = "sala"
#: the auxiliary fetch that carries the chosen far blocks
BLOCKS = "sparse_blocks"


def model_config(cfg, dtypes):
    return MiniCPMSALAConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        mixer_types=cfg["mixer_types"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], lightning_nh=cfg["lightning_nh"],
        lightning_head_dim=cfg["lightning_head_dim"],
        rope_theta=cfg["rope_theta"], rms_norm_eps=cfg["rms_norm_eps"],
        scale_emb=cfg["scale_emb"], scale_depth=cfg["scale_depth"],
        mup_denominator=cfg["mup_denominator"],
        dim_model_base=cfg["dim_model_base"],
        sparse=cfg["assumed"]["sparse"]["value"],
        initializer_range=cfg["assumed"]["initializer_range"],
        param_dtype=dtypes["param"], cache_dtype=dtypes["cache"],
        batch_size=1)


def snapshot_bytes(cfg, dtypes, length):
    """Bytes of one stored prompt of ``length`` tokens: K and V rows and a
    compressed key per ``kernel_stride`` positions in every sparse layer,
    the pooling sums and a Lightning layer's state whole."""
    sparse = cfg["mixer_types"].count("minicpm4")
    row = cfg["num_key_value_heads"] * cfg["head_dim"] * dtypes["cache"].itemsize
    stride = cfg["assumed"]["sparse"]["value"]["kernel_stride"]
    state = cfg["lightning_nh"] * cfg["lightning_head_dim"] ** 2 * 4
    return (sparse * (2 * length + -(-length // stride)) * row
            + sparse * 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 4
            + (len(cfg["mixer_types"]) - sparse) * state)


class System(gpt2_decode.System):
    kind = "closed_loop_decode"

    def __init__(self, cfg, mix, weights):
        from hetu_tpu.serving import DecodeEngine, DecodeRouter, PrefixKVStore
        max_len, slots = int(mix["max_len"]), int(mix["max_slots"])
        dtypes = storage(cfg)
        mcfg = model_config(cfg, dtypes)
        feeds, logits, states, tokens, blocks = minicpm_sala_decode_graph(
            mcfg, max_len, name=STEM)
        cf, cl, cs, ctok, cblocks = minicpm_sala_decode_chunked_graph(
            mcfg, max_len, name=STEM)
        weights = {STEM + name[name.index("."):]: w
                   for name, w in weights.items()}
        documents = mix["lengths"]["document"]["lengths"]
        #: what the documents' snapshots take, and the store's room for them
        self.document_bytes = sum(snapshot_bytes(cfg, dtypes, n)
                                  for n in documents)
        self.store = PrefixKVStore(capacity_bytes=int(
            float(mix["store_capacity_factor"]) * self.document_bytes))
        with warnings.catch_warnings():
            # a parameter the reference does not name would be served at
            # its initializer's value: the program only warns
            warnings.filterwarnings("error", message="weights source")
            self.engine = DecodeEngine(
                feeds, logits, states, weights=weights, tokens=tokens,
                aux={BLOCKS: blocks},
                aux_fold={BLOCKS: mcfg.block_counters()},
                max_slots=slots, max_len=max_len, seed=0,
                chunked=(cf, cl, cs, ctok, {BLOCKS: cblocks}),
                max_chunk=int(mix["max_chunk"]), prefix_store=self.store)
        # a server of known size: its long-run buckets from the start
        self.engine.reserve(slots, max_len)
        self.router = DecodeRouter(self.engine, start=False,
                                   queue_limit=4 * slots)

    def submit(self, prompt, max_new, keep_prefix=False):
        """``keep_prefix``: the client's mark of a prompt worth keeping in
        the store (a document); a question is not."""
        return self.router.submit(prompt, max_new_tokens=max_new,
                                  eos_id=None, keep_prefix=keep_prefix)

    @staticmethod
    def blocks(stream):
        """``(first position, far blocks)`` the program chose at every
        position ``stream``'s sequence consumed, (positions, sparse layers,
        key heads, topk): the first is past 0 for a sequence the store
        seated."""
        return stream.aux_from, stream.aux(BLOCKS)

    @staticmethod
    def counters():
        """As every serving system's, the prefix store's counters, and which
        sparse reads the program traced (``sparse_attn_calls``)."""
        from hetu_tpu.metrics import (prefix_cache_counts,
                                      sparse_attn_call_counts)
        out = gpt2_decode.System.counters()
        out.update({k: int(v) for k, v in prefix_cache_counts().items()})
        out.update({f"sparse_attn_calls:{k}": int(v)
                    for k, v in sparse_attn_call_counts().items()})
        return out

    def close(self):
        super().close()
        if self.store is not None:
            self.store.clear()
        self.store = None
