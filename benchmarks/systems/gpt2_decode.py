"""System under test: GPT-2 token-level serving through ``DecodeRouter``.

The only file that knows how the program builds this server: the one-token
and chunked decode graphs, one ``DecodeEngine`` over both, one
``DecodeRouter`` in front — the path ``chip_smoke.py decode`` proved.  It
uses the program's constructors, ``DecodeRouter.submit``/``start``/``close``
and the program's counters, and nothing that starts with an underscore.
"""
import warnings

import numpy as np


class System:
    kind = "closed_loop_decode"

    def __init__(self, cfg, mix, weights):
        from hetu_tpu.models import (GPT2Config, gpt2_decode_chunked_graph,
                                     gpt2_decode_graph)
        from hetu_tpu.serving import DecodeEngine, DecodeRouter
        max_len = int(mix["max_len"])
        gcfg = GPT2Config(
            vocab_size=cfg["vocab_size"], n_positions=cfg["n_positions"],
            n_embd=cfg["n_embd"], n_layer=cfg["n_layer"],
            n_head=cfg["n_head"],
            layer_norm_epsilon=cfg["layer_norm_epsilon"],
            batch_size=1, seq_len=max_len)
        feeds, logits, caches, _ = gpt2_decode_graph(gcfg, max_len=max_len)
        cf, cl, cc, _ = gpt2_decode_chunked_graph(gcfg, max_len=max_len)
        host = {k: np.asarray(v) for k, v in weights.items()}
        with warnings.catch_warnings():
            # a parameter the reference does not name would be served at
            # its initializer's value: the program only warns
            warnings.filterwarnings("error", message="weights source")
            self.engine = DecodeEngine(
                feeds, logits, caches, weights=host,
                max_slots=int(mix["max_slots"]), max_len=max_len, seed=0,
                chunked=(cf, cl, cc), max_chunk=int(mix["max_chunk"]))
        # not started yet: what is submitted before start() is seated
        # together at the first step
        self.router = DecodeRouter(self.engine, start=False,
                                   queue_limit=4 * int(mix["max_slots"]))

    def start(self):
        self.router.start()

    def submit(self, prompt, max_new):
        return self.router.submit(prompt, max_new_tokens=max_new,
                                  eos_id=None)

    @staticmethod
    def counters():
        """The program's own counters: steps, prefill rows, bucket grows,
        the KV slabs' bytes (a high-water mark, and the slabs never
        shrink), bucket compiles, the ``step`` latency histogram."""
        from hetu_tpu.metrics import (decode_counts, decode_latency_stats,
                                      serve_counts)
        out = {k: int(v) for k, v in decode_counts().items()}
        out["serve_bucket_compiles"] = int(
            serve_counts().get("serve_bucket_compiles", 0))
        step = decode_latency_stats().get("step", {})
        out["step_us_sum"] = float(step.get("sum", 0.0))
        out["step_count"] = int(step.get("count", 0))
        return out

    def close(self):
        if self.router is not None:
            self.router.close(timeout=60)
        self.router = self.engine = None
