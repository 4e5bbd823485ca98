"""Driver ``closed_loop_decode_routed``: ``closed_loop_decode_large`` for a
model whose layers choose experts.

The clients, the window, the set-up and the sample are
``closed_loop_decode_large``'s, unchanged.  What differs is how ``correct``
is decided.  A mixture of experts is DISCONTINUOUS in its input: among some
hundreds of scores the k-th and the (k+1)-th best lie closer than the
rounding of a bfloat16 forward moves them, a flip swaps two experts of
near-equal weight — unrelated functions under seeded weights — and through
keys, values and recurrent state the swap reaches every later position of
the request.  A reference that routed for itself would read a gap ten times
the rounding's on a sound run, and a limit wide enough for that would hide
a lower precision.

So the timed path hands over, with every token, the expert ids it chose
(``System.choices``), for the sampled requests at every position they
consumed.  The plain reference, layer by layer, computes its own float32
scores, measures how far each handed-in choice lies below its own k-th best
(``route_margin``: thousandths for a near-tie, tenths for a wrong expert)
and then FOLLOWS the program's choice with its own float32 weights for it.
What is left between the two is rounding, graded as
``closed_loop_decode_large`` grades it, and ``route_margin_max`` is a third
number with a limit.  The share of token-layers at which the reference
alone would have chosen otherwise is logged, not judged.

The control (``--control 1``) is a forward in the configuration's
``control_precision`` that routes for itself, in the program's place: its
own tokens and its own choices, graded and followed the same way.
"""
import functools

import numpy as np

from .. import weights_by_leaf
from . import closed_loop_decode_large as large


class Driver(large.Driver):

    def setup(self):
        #: id(prompt array of a sampled request) -> (positions, layers, k)
        self._chosen = {}
        self.route = None
        super().setup()

    def _serve_alone(self, prompts, n_new):
        """As ``closed_loop_decode_large``'s, keeping what the program
        chose for each."""
        reqs = [self._submit(p, n_new) for p in prompts]
        if any(r.failed for r in reqs):
            raise RuntimeError(f"set-up request refused: {self.refusals}")
        self.sys.start()
        served = []
        for r in reqs:
            tokens = np.asarray(r.stream.result(timeout=900), np.int32)
            served.append((np.asarray(r.prompt), tokens))
            self._chosen[id(served[-1][0])] = self.sys.choices(r.stream)
        self.requests.clear()
        return served

    def _sample(self, done):
        by_prompt = {id(r.prompt): r for r in done}
        sample = super()._sample(done)
        for prompt, _ in sample:
            if id(prompt) in by_prompt:
                self._chosen[id(prompt)] = self.sys.choices(
                    by_prompt[id(prompt)].stream)
        self._chosen = {id(p): self._chosen[id(p)] for p, _ in sample}
        return sample

    # -- the reference, layer by layer --------------------------------------

    def _follow(self, ids, precision, chosen):
        """The sequences ``ids`` (each padded to its width) through the
        plain reference in ``precision``, following ``chosen`` — per
        sequence (width, layers, k) expert ids, -1 where the reference
        routes for itself — or routing for itself throughout (None).
        Returns ``(final hidden states, ids followed, largest route
        margin, token-layers where its own choice differs)``."""
        import jax
        import jax.numpy as jnp
        ref, cfg = self.reference, self.cfg
        make = functools.partial(weights_by_leaf.make, self.spec, self.seed,
                                 self.dtype)
        embed = jax.jit(ref.embed)
        table = make(only=["solar.embed"])["solar.embed"]
        xs = [embed(table, s) for s in ids]
        del table
        step = jax.jit(functools.partial(ref.layer, cfg=cfg,
                                         precision=precision),
                       static_argnums=(0,))
        followed = [[] for _ in ids]
        margin, differs = 0.0, 0
        for i in range(cfg["num_hidden_layers"]):
            prefix = f"solar.l{i}."
            w = {k[len(prefix):]: v for k, v in make(
                only=[k for k in self.spec if k.startswith(prefix)]).items()}
            for s in range(len(ids)):
                xs[s], _, info = step(
                    ref.layer_kind(cfg, i), w, xs[s], {},
                    choices=None if chosen is None
                    else jnp.asarray(chosen[s][:, i]))
                followed[s].append(info["choices"])
                margin = max(margin, float(info["route_margin"]))
                differs += int(info["differs"])
        return xs, [jnp.stack(f, axis=1) for f in followed], margin, differs

    def gaps(self, precision="highest", served=True, judge="highest"):
        """As ``closed_loop_decode_large.gaps``, the reference that grades
        following the expert ids of what it grades: the program's
        (``served``), or those a forward in ``precision`` chose for itself
        (the control).  Leaves the routing's numbers in ``self.route``."""
        import jax
        import jax.numpy as jnp
        ref, cfg = self.reference, self.cfg
        longest = max(len(p) + len(t) for p, t in self.sample)
        ids, rows, put, handed = [], [], [], []
        for prompt, tokens in self.sample:
            seq = np.concatenate([prompt, tokens[:-1]])
            width = -(-(len(seq) if len(seq) <= 128 else longest) // 128) * 128
            ids.append(jnp.asarray(np.pad(seq, (0, width - len(seq)))))
            first = len(prompt) - 1      # the position that predicts token 0
            rows.append(np.arange(first, first + len(tokens)))
            put.append(tokens)
            chosen = np.asarray(self._chosen[id(prompt)])
            if len(chosen) != len(seq):
                raise RuntimeError(
                    f"a request of {len(seq)} consumed positions was handed "
                    f"the choices of {len(chosen)}")
            handed.append(chosen)
        positions = sum(len(c) for c in handed)

        def run(precision, chosen, tag):
            # kept per sample: a second grading of one sample (the
            # precision witness) follows the reference once
            key = (id(self.sample), precision, tag)
            if key not in self._followed:
                xs, ch, margin, differs = self._follow(ids, precision, chosen)
                self._followed[key] = (
                    jnp.concatenate([x[r] for x, r in zip(xs, rows)]), ch,
                    margin, differs)
            return self._followed[key]

        if served:
            hidden, _, margin, differs = run(judge, [np.pad(
                c, ((0, len(s) - len(c)), (0, 0), (0, 0)), constant_values=-1)
                for c, s in zip(handed, ids)], "served")
            other = hidden
        else:
            # the control routes for itself, and is followed where the
            # sequences are (the padding routes for itself under any judge)
            other, own, _, _ = run(precision, None, "alone")
            hidden, _, margin, differs = run(judge, [
                np.where(np.arange(len(s))[:, None, None] < len(c),
                         np.asarray(o), -1)
                for o, c, s in zip(own, handed, ids)], "control:" + precision)
        self.route = {
            "route_margin_max": margin, "differs": differs,
            "token_layers": positions * cfg["num_hidden_layers"]}
        weight, scale = weights_by_leaf.make(
            self.spec, self.seed, self.dtype,
            only=["solar.lm_head.weight", "solar.ln_f.scale"]).values()

        @jax.jit
        def grade(weight, scale, x, x_other, put):
            head = functools.partial(ref.head, weight, scale, cfg=cfg)
            best = head(x, precision=judge)
            if not served:
                put = head(x_other, precision=precision).argmax(-1)
            return best.max(-1) - jnp.take_along_axis(
                best, put[:, None], axis=-1)[:, 0]

        block = int(self.mix.get("check_block", 1024))
        put = np.concatenate(put)
        n = len(put)

        def padded(a):
            return jnp.pad(jnp.asarray(a), [(0, -n % block)] + [(0, 0)] * (
                a.ndim - 1))

        hidden, other, put = padded(hidden), padded(other), padded(put)
        return np.concatenate([
            np.asarray(grade(weight, scale, hidden[at:at + block],
                             other[at:at + block], put[at:at + block]))
            for at in range(0, len(put), block)])[:n]

    def check(self, control=False):
        numbers = super().check(control)
        route = self.route
        self.log(f"[serve] routing: margin {route['route_margin_max']:.3g}; "
                 f"the reference alone would have chosen otherwise at "
                 f"{route['differs']} of {route['token_layers']} "
                 f"token-layers ({100.0 * route['differs'] / route['token_layers']:.2f} %)")
        numbers["route_margin_max"] = route["route_margin_max"]
        return numbers
