"""Driver ``closed_loop_decode_large``: ``closed_loop_decode`` for a model
too large to be made, or followed by its reference, in one piece.

The clients, the window and what must hold over it are
``closed_loop_decode``'s, unchanged.  What differs:

* **Weights** come leaf by leaf (``weights_by_leaf``), in the storage type
  the configuration states, and go to the system as device arrays.
* **Set-up** walks no ladder: the system reserves the engine at the mix's
  batch and length when it builds it, so ``mix["prime"]`` is one full batch
  of short requests (every slot seated once, the widest chunk run at the
  full batch) and one lone prompt for each chunk width, then the mix itself
  until ``warmup_requests`` completed.
* **The lone prompts are graded.**  With most rows generating, the engine
  takes a new prompt in one token a step (``_pick_chunk``'s floor), so a
  window may hold no chunked step at all and the requests that finish in
  it say nothing of the chunked programs.  Each lone prompt of set-up is
  ``top + w`` tokens long (the widest chunk, then one of width ``w``: the
  state is carried from one chunked program into another), one more ends
  in a chunk that is only partly valid, each generates ``lone_output``
  tokens, and all of them go through the reference with the window's
  sample.
* **A client watches one token at a time.**  ``closed_loop_decode`` hangs
  a callback on every token's future when it submits a request — on the
  server's thread, where the clients live: a few hundred futures for a chat
  answer, but up to 3,840 for a chain of thought, some milliseconds in one
  step of every twenty, which is where the 95th percentile of the gaps
  sits.  Here a token's callback hangs the next token's: the same stamps,
  the cost spread evenly over the steps.
* **The comparison** follows ``mix["check_requests"]`` of the requests that
  finished in the window — the longest, and the rest spread evenly over
  the others — and the lone prompts through the plain reference LAYER BY
  LAYER: a layer's
  weights are made, every sampled sequence goes through that layer, the
  next layer's weights take their place.  Only the served positions reach
  the vocabulary product, in blocks of rows, and only the gaps come back.
"""
import functools
import time

import numpy as np

from .. import traffic, weights_by_leaf
from . import closed_loop_decode as base


class Driver(base.Driver):

    def setup(self):
        t = time.perf_counter()
        self._followed = {}
        self.spec = self.reference.param_spec(self.cfg)
        self.dtype = self.cfg["storage"]["weights"]
        w = weights_by_leaf.make(self.spec, self.seed, self.dtype)
        self.sys = self.system.System(self.cfg, self.mix, w)
        del w
        self.log(f"[serve] built in {time.perf_counter() - t:.1f} s")
        vocab = self.cfg["vocab_size"]
        self.sched = traffic.Schedule(self.mix, vocab, self.seed)
        clients, prime = int(self.mix["clients"]), self.mix["prime"]
        t = time.perf_counter()
        rng = np.random.default_rng([self.seed, 5])
        self._serve_alone(
            [rng.integers(0, vocab, int(prime["prompt"]), dtype=np.int32)
             for _ in range(clients)], int(prime["output"]))
        top, width = int(self.mix["max_chunk"]), 2
        lone = []
        while width <= top:
            lone.append(top + width)
            width *= 2
        lone.append(top + top // 2 + 1)     # ends in a partly valid chunk
        self.lone = []
        for n in lone:
            self.lone += self._serve_alone(
                [rng.integers(0, vocab, n, dtype=np.int32)],
                int(prime["lone_output"]))
        self.log(f"[serve] primed in {time.perf_counter() - t:.1f} s, "
                 f"{self.compiles.hits}/{self.compiles.requests} programs "
                 f"from the cache, counters {self.sys.counters()}")
        self._settle_heap()
        self.submitting = True
        for client in range(clients):
            self._submit_next(client)
        t = time.perf_counter()
        deadline = t + 600
        while self.t0 is None:
            self._sleep_until(time.perf_counter() + 0.01)
            if time.perf_counter() > deadline:
                raise RuntimeError("warm-up did not complete in 600 s")
        self.log(f"[serve] warm-up traffic, {self.mix['warmup_requests']} "
                 f"requests completed, {time.perf_counter() - t:.1f} s")

    def _submit(self, prompt, n_new, client=None, k=None):
        req = base._Request(k, client, prompt, n_new)
        self.requests.append(req)
        try:
            req.stream = self.sys.submit(prompt, n_new)
        except Exception as e:  # noqa: BLE001 - a refusal is a failed request
            self.refusals.append(f"request {k}: {type(e).__name__}: {e}")
            req.failed = True
            return req
        self._watch(req, 0)
        return req

    def _watch(self, req, i):
        req.stream.token(i).add_done_callback(
            functools.partial(self._on_watched, req, i))

    def _on_watched(self, req, i, fut):
        last = i == req.n_new - 1
        self._on_token(req, last, fut)
        if not last and not req.failed:
            self._watch(req, i + 1)

    def window(self, seconds, tracer):
        counters = self.sys.counters
        run = super().window(seconds, tracer)
        gauge = "decode_state_bytes_"
        run["window"]["state_bytes"] = {
            k[len(gauge):-len("_hw")]: v for k, v in counters().items()
            if k.startswith(gauge)}
        run["window"]["slots"] = int(self.mix["max_slots"])
        return run

    def _serve_alone(self, prompts, n_new):
        """As the base's, and returns what was served: ``[(prompt,
        tokens)]``."""
        reqs = [self._submit(p, n_new) for p in prompts]
        if any(r.failed for r in reqs):
            raise RuntimeError(f"set-up request refused: {self.refusals}")
        self.sys.start()
        served = [(np.asarray(r.prompt),
                   np.asarray(r.stream.result(timeout=900), np.int32))
                  for r in reqs]
        self.requests.clear()
        return served

    def _sample(self, done):
        """The longest third of ``check_requests`` among the requests that
        finished in the window, the rest spread evenly over the others
        ranked by length, and set-up's lone prompts."""
        if not done:
            raise RuntimeError("no request finished inside the window")
        done.sort(key=lambda r: (-(len(r.prompt) + r.n_new), r.k))
        n = int(self.mix["check_requests"])
        if len(done) > n:
            head, rest = done[:n // 3], done[n // 3:]
            step = len(rest) / (n - len(head))
            done = head + [rest[int(i * step)] for i in range(n - len(head))]
        # the first full batch of the warm-up traffic went in together, by
        # chunks of the top width; every later prompt a token a step
        first = sum(r.k < int(self.mix["clients"]) for r in done)
        self.log(f"[serve] sample: {len(done)} requests of the window "
                 f"({first} of the warm-up's first batch, taken in by "
                 f"chunks) and {len(self.lone)} lone prompts of set-up, "
                 f"taken in by chunks of every width")
        return base.Driver._sample(done) + self.lone

    # -- the reference, layer by layer --------------------------------------

    def _forward(self, ids, precision):
        """The final hidden states ``[(width, d)]`` of the sequences
        ``ids`` (each padded to its width) under the plain reference."""
        import jax
        ref, cfg = self.reference, self.cfg
        make = functools.partial(weights_by_leaf.make, self.spec, self.seed,
                                 self.dtype)
        embed = jax.jit(ref.embed)
        table = make(only=["phi4.embed"])["phi4.embed"]
        xs = [embed(table, s) for s in ids]
        del table
        carries = [{} for _ in ids]
        step = jax.jit(functools.partial(ref.layer, cfg=cfg,
                                         precision=precision),
                       static_argnums=(0,))
        for i in range(cfg["num_hidden_layers"]):
            prefix = f"phi4.l{i}."
            w = {k[len(prefix):]: v for k, v in make(
                only=[k for k in self.spec if k.startswith(prefix)]).items()}
            for s in range(len(ids)):
                xs[s], carries[s] = step(ref.layer_kind(cfg, i), w, xs[s],
                                         carries[s],
                                         np.float32(ref.lambda_init(i)))
        return xs

    def gaps(self, precision="highest", served=True, judge="highest"):
        """Per served position, how far the logit of a token lies below the
        reference's best: of the SERVED token, or (``served=False``, the
        control) of the token a forward pass in ``precision`` puts first.
        ``judge``: the precision of the reference that grades (always
        ``highest`` where ``correct`` is decided)."""
        import jax
        import jax.numpy as jnp
        ref, cfg = self.reference, self.cfg
        # two widths, so that two programs a layer follow every sequence:
        # one for the lone prompts of set-up, one for the window's requests
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
        table, scale, bias = weights_by_leaf.make(
            self.spec, self.seed, self.dtype,
            only=["phi4.embed", "phi4.ln_f.scale", "phi4.ln_f.bias"]).values()

        @jax.jit
        def grade(table, scale, bias, x, x_other, put):
            head = functools.partial(ref.head, table, scale, bias, cfg=cfg)
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
            np.asarray(grade(table, scale, bias, hidden[at:at + block],
                             other[at:at + block], put[at:at + block]))
            for at in range(0, len(put), block)])[:n]
