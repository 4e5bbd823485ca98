"""System under test: BERT MLM pretraining through ``ht.Executor``.

The only file that knows how the program builds and steps this model: the
graph from ``bert_pretrain_graph``, Adam through ``opt.minimize``, steps
through ``run_steps(sync=False)`` — the path ``chip_smoke.py train`` proved.
"""
import os

import numpy as np

#: steps the executor lets run ahead of the oldest one it waits for, where
#: the mix states none: five seconds of the bert cell's 108 ms steps, so
#: that the chip stays fed while the host stands still (the program's own
#: default, 4, is 0.4 s there: PERF.md section 6, PR 35)
STEPS_IN_FLIGHT = 48


class System:
    kind = "train_steps"

    def __init__(self, cfg, mix, weights):
        import hetu_tpu as ht
        from hetu_tpu.models.bert import BertConfig, bert_pretrain_graph
        self.batch, self.seq_len = int(mix["batch"]), int(mix["seq_len"])
        bcfg = BertConfig(
            vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
            num_hidden_layers=cfg["num_hidden_layers"],
            num_attention_heads=cfg["num_attention_heads"],
            intermediate_size=cfg["intermediate_size"],
            max_position_embeddings=cfg["max_position_embeddings"],
            type_vocab_size=cfg["type_vocab_size"],
            hidden_dropout_prob=cfg["dropout"],
            attention_probs_dropout_prob=cfg["dropout"],
            layer_norm_eps=cfg["layer_norm_eps"],
            batch_size=self.batch, seq_len=self.seq_len)
        self._feeds, loss, _ = bert_pretrain_graph(bcfg)
        o = cfg["optimizer"]
        opt = ht.optim.AdamOptimizer(o["learning_rate"], o["beta1"],
                                     o["beta2"], o["epsilon"])
        # the executor reads its depth from the environment when built
        depth = int(mix.get("steps_in_flight", STEPS_IN_FLIGHT))
        was = os.environ.get("HETU_ASYNC_WINDOW")
        os.environ["HETU_ASYNC_WINDOW"] = str(depth)
        try:
            self.ex = ht.Executor({"train": [loss, opt.minimize(loss)]},
                                  seed=0, compute_dtype=cfg["compute_dtype"])
        finally:
            if was is None:
                del os.environ["HETU_ASYNC_WINDOW"]
            else:
                os.environ["HETU_ASYNC_WINDOW"] = was
        trainable = {name for node, name in self.ex.var_names.items()
                     if getattr(node, "trainable", True)}
        if trainable != set(weights):
            raise ValueError(
                "the reference's parameter names are not the program's: "
                f"{sorted(trainable ^ set(weights))[:6]}")
        self.ex.load_dict({k: np.asarray(v) for k, v in weights.items()})
        self._by_name = {name: node
                         for node, name in self.ex.var_names.items()
                         if name in trainable}

    def feed(self, batch):
        return {self._feeds[k]: v for k, v in batch.items()}

    def run(self, feeder, n):
        """``n`` steps through the pipelined driver; returns its handles."""
        return self.ex.run_steps(feeder, n, name="train", sync=False)

    @staticmethod
    def wait(results):
        import jax
        jax.block_until_ready([r[0].jax() if hasattr(r[0], "jax") else r[0]
                               for r in results])

    @staticmethod
    def loss(result):
        return float(np.asarray(result[0].asnumpy()))

    def params(self):
        return {name: self.ex.var_values[node]
                for name, node in self._by_name.items()}

    def first_moment(self):
        """Adam's ``m`` by parameter name (after one step: 0.1·g₁)."""
        (state,) = self.ex.opt_states.values()
        return {name: state["m"][self.ex._k(node)]
                for name, node in self._by_name.items()}

    def counters(self):
        from hetu_tpu.metrics import flash_fallback_counts, run_plan_counts
        out = {k: int(v) for k, v in run_plan_counts().items()}
        out["flash_fallbacks"] = sum(flash_fallback_counts().values())
        return out

    def close(self):
        self.ex = self._by_name = self._feeds = None
