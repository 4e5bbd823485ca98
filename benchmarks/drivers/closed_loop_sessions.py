"""Driver ``closed_loop_sessions``: ``closed_loop_decode_large`` for clients
that ask about stored documents, against a server with a prefix store whose
model reads its cache SELECTIVELY.

The clients, the window and what must hold over it are
``closed_loop_decode``'s; weights, the watching of one token at a time and
the graded lone prompts are ``closed_loop_decode_large``'s.  What differs:

* **Documents.**  ``mix["lengths"]["document"]["lengths"]`` documents of
  seeded token ids; client ``c`` asks document ``c mod documents``.  After
  the programs are warm, set-up sends the documents themselves as concurrent
  requests marked ``keep_prefix=True``: their snapshots (KV rows, indexer
  rows, recurrent state) enter the server's store.  Every other request of
  the run says ``False`` — a request is its client's document + a question of
  the table's prompt length -> an answer of the table's output length, and is
  seated from its document's snapshot whole.  The run fails if the store
  evicted anything inside the window.
* **The comparison follows the program's selection.**  A block-sparse layer
  is DISCONTINUOUS in its input (the 64th and the 65th best of some hundred
  block scores lie closer than a bfloat16 forward moves them), as a router
  is: the timed path hands over, with every token, the far blocks its
  queries chose (``System.blocks``), and the plain reference, layer by
  layer, computes its own float32 block scores, measures how far the worst
  block handed in lies below its own topk-th best (``select_margin``) and
  then attends to the blocks handed in.  A sampled request goes through the
  reference over document + question + answer at EVERY position — the
  document's positions with the blocks the set-up request that stored it
  chose, the rest with the request's own: a seated request that agrees with
  a full forward is what proves the snapshot path.  ``select_margin_max`` is
  a third number with a limit.
* **The stem and the layers come from the reference** (``reference.STEM``,
  ``layer_kind``): nothing here names a model.

The control (``--control 1``) is a forward in the configuration's
``control_precision`` that selects for itself, in the program's place: its
own tokens and its own blocks, graded and followed the same way.
"""
import functools

import numpy as np

from .. import weights_by_leaf
from . import closed_loop_decode_large as large


class Driver(large.Driver):

    def setup(self):
        #: id(prompt array of a sampled request) -> (positions, layers, G, k)
        self._chosen = {}
        self.select = None
        self.docs = None
        super().setup()

    # -- set-up: the programs, then the documents ---------------------------

    def _settle_heap(self):
        """Called by the base's set-up once the programs are warm and before
        the mix starts: the documents go in here."""
        lengths = self.mix["lengths"]["document"]["lengths"]
        rng = np.random.default_rng([self.seed, 6])
        self.docs = [rng.integers(0, self.cfg["vocab_size"], int(n),
                                  dtype=np.int32) for n in lengths]
        before = self.sys.counters()
        served = self._serve_alone(
            self.docs, int(self.mix["prime"]["document_output"]), keep=True)
        #: per document, the blocks its own positions chose
        self._doc_blocks = [self._chosen.pop(id(p))[:len(p)]
                            for p, _ in served]
        stored = {k: v - before.get(k, 0)
                  for k, v in self.sys.counters().items()
                  if k.startswith("prefix_cache_")}
        self.log(f"[serve] {len(self.docs)} documents stored: "
                 f"{self.sys.store.nbytes} B of "
                 f"{self.sys.store.capacity_bytes} B, {stored}")
        if stored.get("prefix_cache_inserts", 0) != len(self.docs) \
                or stored.get("prefix_cache_evictions", 0):
            raise RuntimeError(
                f"the store does not hold the documents: {stored}")
        super()._settle_heap()

    def _submit(self, prompt, n_new, client=None, k=None, keep=False):
        req = large.base._Request(k, client, prompt, n_new)
        self.requests.append(req)
        try:
            req.stream = self.sys.submit(prompt, n_new, keep_prefix=keep)
        except Exception as e:  # noqa: BLE001 - a refusal is a failed request
            self.refusals.append(f"request {k}: {type(e).__name__}: {e}")
            req.failed = True
            return req
        self._watch(req, 0)
        return req

    def _submit_next(self, client, after=None):
        question, n_new = self.sched.request(self.next_k)
        prompt = np.concatenate([self.docs[client % len(self.docs)],
                                 question])
        req = self._submit(prompt, n_new, client, self.next_k)
        self.next_k += 1
        if after is not None:
            self.lateness.append(req.t_submit - after)

    def _serve_alone(self, prompts, n_new, keep=False):
        """As ``closed_loop_decode_large``'s, keeping what the program
        chose for each."""
        reqs = [self._submit(p, n_new, keep=keep) for p in prompts]
        if any(r.failed for r in reqs):
            raise RuntimeError(f"set-up request refused: {self.refusals}")
        self.sys.start()
        served = []
        for r in reqs:
            tokens = np.asarray(r.stream.result(timeout=1800), np.int32)
            served.append((np.asarray(r.prompt), tokens))
            self._chosen[id(served[-1][0])] = self._blocks_of(r)
        self.requests.clear()
        return served

    def _blocks_of(self, req):
        """The blocks of every position ``req`` consumed: its own from where
        the store seated it, its document's in front."""
        start, own = self.sys.blocks(req.stream)
        if not start:
            return own
        doc = self._doc_blocks[req.client % len(self.docs)]
        if start != len(doc):
            raise RuntimeError(
                f"a request over a document of {len(doc)} tokens was seated "
                f"with {start} rows")
        return np.concatenate([doc, own])

    def window(self, seconds, tracer):
        run = super().window(seconds, tracer)
        delta = run["window"]["counters"]
        moved = {k: v for k, v in delta.items()
                 if v and (k in ("prefix_cache_evictions",
                                 "prefix_cache_inserts")
                           or k.startswith("decode_state_bytes_"))}
        if moved:
            raise RuntimeError(
                f"the store or the state moved inside the window: {moved}")
        run["window"]["store"] = {
            "document_bytes": self.sys.document_bytes,
            "capacity_bytes": self.sys.store.capacity_bytes}
        return run

    def _sample(self, done):
        by_prompt = {id(r.prompt): r for r in done}
        sample = super()._sample(done)
        for prompt, _ in sample:
            if id(prompt) in by_prompt:
                self._chosen[id(prompt)] = self._blocks_of(
                    by_prompt[id(prompt)])
        self._chosen = {id(p): self._chosen[id(p)] for p, _ in sample}
        return sample

    # -- the reference, layer by layer --------------------------------------

    def _follow(self, ids, precision, chosen):
        """The sequences ``ids`` (each padded to its width) through the
        plain reference in ``precision``, following ``chosen`` — per
        sequence (width, sparse layers, G, topk) block ids, -1 where the
        reference chooses for itself — or choosing for itself throughout
        (None).  Returns ``(final hidden states, ids followed, largest
        select margin, queries where its own choice differs)``."""
        import jax
        import jax.numpy as jnp
        ref, cfg = self.reference, self.cfg
        stem = ref.STEM
        make = functools.partial(weights_by_leaf.make, self.spec, self.seed,
                                 self.dtype)
        embed = jax.jit(functools.partial(ref.embed, cfg=cfg))
        table = make(only=[f"{stem}.embed"])[f"{stem}.embed"]
        xs = [embed(table, s) for s in ids]
        del table
        step = jax.jit(functools.partial(ref.layer, cfg=cfg,
                                         precision=precision),
                       static_argnums=(0,))
        followed = [[] for _ in ids]
        margin, differs, at = 0.0, 0, 0
        for i in range(cfg["num_hidden_layers"]):
            prefix = f"{stem}.l{i}."
            w = {k[len(prefix):]: v for k, v in make(
                only=[k for k in self.spec if k.startswith(prefix)]).items()}
            kind = ref.layer_kind(cfg, i)
            for s in range(len(ids)):
                handed = None
                if kind == "sparse":
                    # always an array, so that one program follows or not
                    handed = jnp.asarray(
                        chosen[s][:, at] if chosen is not None else np.full(
                            (len(ids[s]),) + self._block_shape, -1),
                        jnp.int32)
                xs[s], _, info = step(kind, w, xs[s], {}, blocks=handed)
                if kind == "sparse":
                    followed[s].append(info["blocks"])
                    margin = max(margin, float(info["select_margin"]))
                    differs += int(info["differs"])
            at += kind == "sparse"
        return xs, [jnp.stack(f, axis=1) for f in followed], margin, differs

    def gaps(self, precision="highest", served=True, judge="highest"):
        """As ``closed_loop_decode_large.gaps``, the reference that grades
        following the blocks of what it grades: the program's (``served``),
        or those a forward in ``precision`` chose for itself (the control).
        Leaves the selection's numbers in ``self.select``."""
        import jax
        import jax.numpy as jnp
        ref, cfg = self.reference, self.cfg
        stem = ref.STEM
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
                    f"the blocks of {len(chosen)}")
            handed.append(chosen)
        self._block_shape = handed[0].shape[2:]
        queries = sum(int((c[..., 0] >= 0).sum()) for c in handed)

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

        def padded_blocks(blocks, seqs):
            return [np.pad(np.asarray(c), ((0, len(s) - len(c)),)
                           + ((0, 0),) * 3, constant_values=-1)
                    for c, s in zip(blocks, seqs)]

        if served:
            hidden, _, margin, differs = run(
                judge, padded_blocks(handed, ids), "served")
            other = hidden
        else:
            # the control selects for itself, and is followed where the
            # sequences are (the padding chooses for itself under any judge)
            other, own, _, _ = run(precision, None, "alone")
            hidden, _, margin, differs = run(judge, padded_blocks(
                [np.asarray(o)[:len(c)] for o, c in zip(own, handed)], ids),
                "control:" + precision)
        self.select = {"select_margin_max": margin, "differs": differs,
                       "queries": queries}
        weight, scale = weights_by_leaf.make(
            self.spec, self.seed, self.dtype,
            only=[f"{stem}.lm_head.weight", f"{stem}.ln_f.scale"]).values()

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
        sel = self.select
        self.log(f"[serve] selection: margin {sel['select_margin_max']:.3g}; "
                 f"the reference alone would have chosen otherwise at "
                 f"{sel['differs']} of {sel['queries']} selecting "
                 f"(query, layer, key head) triples")
        numbers["select_margin_max"] = sel["select_margin_max"]
        return numbers
