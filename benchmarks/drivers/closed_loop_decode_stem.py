"""Driver ``closed_loop_decode_stem``: ``closed_loop_decode_large`` with
nothing of a model's names in it.

The clients, the window, the set-up, the graded lone prompts and the sample
are ``closed_loop_decode_large``'s, unchanged.  That driver's walk through
the reference names Phi-4-mini-flash's leaves (``phi4.embed``, a final
LayerNorm's bias, a ``lambda_init`` a layer); here the reference says what
its leaves are called, as ``closed_loop_sessions`` has it:

* ``reference.STEM``      the stem of every parameter name
  (``<stem>.embed``, ``<stem>.l<i>.<leaf>``);
* ``reference.HEAD``      the leaves ``head`` reads, in its order
  (``("embed", "ln_f.scale")`` where the head is the embedding);
* ``reference.embed(table, ids, cfg)``, ``reference.layer(kind, w, x,
  carry, cfg=, precision=) -> (x, carry)``, ``reference.head(*leaves, x,
  cfg=, precision=)``.
"""
import functools

import numpy as np

from .. import weights_by_leaf
from . import closed_loop_decode_large as large


class Driver(large.Driver):

    def _forward(self, ids, precision):
        """The final hidden states ``[(width, d)]`` of the sequences
        ``ids`` (each padded to its width) under the plain reference."""
        import jax
        ref, cfg = self.reference, self.cfg
        stem = ref.STEM
        make = functools.partial(weights_by_leaf.make, self.spec, self.seed,
                                 self.dtype)
        embed = jax.jit(functools.partial(ref.embed, cfg=cfg))
        table = make(only=[f"{stem}.embed"])[f"{stem}.embed"]
        xs = [embed(table, s) for s in ids]
        del table
        carries = [{} for _ in ids]
        step = jax.jit(functools.partial(ref.layer, cfg=cfg,
                                         precision=precision),
                       static_argnums=(0,))
        for i in range(cfg["num_hidden_layers"]):
            prefix = f"{stem}.l{i}."
            w = {k[len(prefix):]: v for k, v in make(
                only=[k for k in self.spec if k.startswith(prefix)]).items()}
            for s in range(len(ids)):
                xs[s], carries[s] = step(ref.layer_kind(cfg, i), w, xs[s],
                                         carries[s])
        return xs

    def gaps(self, precision="highest", served=True, judge="highest"):
        """As ``closed_loop_decode_large.gaps``, the head's leaves by the
        names the reference gives them."""
        import jax
        import jax.numpy as jnp
        ref, cfg = self.reference, self.cfg
        longest = max(len(p) + len(t) for p, t in self.sample)
        ids, rows, put = [], [], []
        for prompt, tokens in self.sample:
            seq = np.concatenate([prompt, tokens[:-1]])
            width = -(-(len(seq) if len(seq) <= 128 else longest) // 128) * 128
            ids.append(jnp.asarray(np.pad(seq, (0, width - len(seq)))))
            first = len(prompt) - 1      # the position that predicts token 0
            rows.append(np.arange(first, first + len(tokens)))
            put.append(tokens)

        def served_rows(precision):
            # kept per sample and precision: a second grading of one
            # sample (the precision witness) follows the reference once
            key = (id(self.sample), precision)
            if key not in self._followed:
                self._followed[key] = jnp.concatenate([x[r] for x, r in zip(
                    self._forward(ids, precision), rows)])
            return self._followed[key]

        hidden = served_rows(judge)
        other = hidden if served else served_rows(precision)
        leaves = tuple(weights_by_leaf.make(
            self.spec, self.seed, self.dtype,
            only=[f"{ref.STEM}.{leaf}" for leaf in ref.HEAD]).values())

        @jax.jit
        def grade(leaves, x, x_other, put):
            head = functools.partial(ref.head, *leaves, cfg=cfg)
            best = head(x, precision=judge)
            if not served:
                put = head(x_other, precision=precision).argmax(-1)
            return best.max(-1) - jnp.take_along_axis(
                best, put[:, None], axis=-1)[:, 0]

        # whole blocks of rows, so that one program grades them all
        block = int(self.mix.get("check_block", 1024))
        put = np.concatenate(put)
        n = len(put)

        def padded(a):
            return jnp.pad(jnp.asarray(a), [(0, -n % block)] + [(0, 0)] * (
                a.ndim - 1))

        hidden, other, put = padded(hidden), padded(other), padded(put)
        return np.concatenate([
            np.asarray(grade(leaves, hidden[at:at + block],
                             other[at:at + block], put[at:at + block]))
            for at in range(0, len(put), block)])[:n]
