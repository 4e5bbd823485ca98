"""Driver ``closed_loop_decode``: N clients, each submitting its next
request the moment its last one completes, against a token-level server.

The engine is held at its long-run state before the window opens.  Its
batch and cache-length buckets only ever grow, so a server that has been up
for an hour of this traffic stands at the buckets of the mix's LONGEST
request.  Set-up brings it there through the server's front door alone
(``mix["prime"]``): a full batch of requests as long as the table's longest
is waiting when the server starts, so every slot is seated at once and the
cache grows to its final size within a few dozen steps; then one lone
prompt of every length from 2 to the top chunk, so that whatever chunk
sizes the engine has are each run once at that shape.  It then serves the
mix until ``warmup_requests`` of its requests have completed, so that slots
are at mixed phases when the window opens.  The run fails if a bucket grew,
the cache grew or a program compiled inside the window.

A client is a callback on the last token's future: it runs on the server's
own thread the moment the request completes and submits the client's next
request there and then, so the request joins at the very next step boundary
and the sequence of steps is a function of the seed, not of thread
wake-ups.  Emission times are taken the same way, by a callback on each
token's future.  The seed draws the order of the table's entries (and the
token ids, and the weights): two seeds serve the same multiset of lengths
along different trajectories, and the spread across seeds is what the
bounds are set from.
"""
import bisect
import functools
import gc
import json
import time

import numpy as np

from .. import traffic, weights


class _Request:
    __slots__ = ("k", "client", "prompt", "n_new", "t_submit", "times",
                 "failed", "stream")

    def __init__(self, k, client, prompt, n_new):
        self.k, self.client, self.prompt, self.n_new = k, client, prompt, n_new
        self.t_submit = time.perf_counter()
        self.times = []
        self.failed = False
        self.stream = None


def percentile(values, q):
    """Nearest-rank percentile of a non-empty list."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, int(q / 100.0 * len(ordered)))]


def gap_profile(gaps):
    """Where the gaps between a stream's tokens lie, in ms: the median, the
    tails on both sides of the 95th percentile, the mean of the slowest
    twentieth, and ``slow_pct``, the share of gaps more than a quarter over
    the median.  A window whose steps are of two kinds (one-token, chunked)
    has two populations of gaps, and a percentile is steady from seed to
    seed only where it lies inside one of them on every seed: ``slow_pct``
    says on which side of it the second population begins."""
    ordered = sorted(gaps)
    p50 = percentile(ordered, 50)
    slow = len(ordered) - bisect.bisect_right(ordered, 1.25 * p50)
    tail = ordered[int(0.95 * len(ordered)):]
    out = {f"p{q}": 1e3 * percentile(ordered, q) for q in (50, 90, 95, 99)}
    out["slowest5_mean"] = 1e3 * sum(tail) / len(tail)
    out["slow_pct"] = 100.0 * slow / len(ordered)
    return out


def reduce_window(requests, t0, t1):
    """End-to-end numbers of the window ``[t0, t1)`` from the requests'
    submit and emission times: every token emitted in the window counts,
    from streams finished or not; time to first token covers every request
    submitted in the window; a gap counts when its later token is in the
    window."""
    emitted, ttft, gaps, failed, attempted = 0, [], [], 0, 0
    for r in requests:
        emitted += sum(t0 <= t < t1 for t in r.times)
        gaps += [b - a for a, b in zip(r.times, r.times[1:]) if t0 <= b < t1]
        if t0 <= r.t_submit < t1:
            attempted += 1
            if r.failed or not r.times:
                failed += 1
            else:
                ttft.append(r.times[0] - r.t_submit)
    return {"emitted": emitted, "ttft": ttft, "gaps": gaps,
            "attempted": attempted, "failed": failed}


class Driver:
    def __init__(self, *, cfg, mix, seed, system, reference, compiles, log):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.system, self.reference = system, reference
        self.compiles, self.log = compiles, log
        self.requests = []
        self.next_k = 0
        self.lateness = []
        self.errors, self.refusals = [], []
        self.submitting = self.closing = False
        self.completed = 0
        self.t0 = self.before = None

    # -- the clients -------------------------------------------------------

    def _on_token(self, req, last, fut):
        now = time.perf_counter()
        if self.closing:
            return
        try:
            if fut.cancelled() or fut.exception() is not None:
                done, req.failed = not req.failed, True
            else:
                req.times.append(now)
                done = last
            if done and req.client is not None:
                self.completed += 1
                if self.completed == int(self.mix["warmup_requests"]):
                    self._open_window(now)
                if self.submitting:
                    self._submit_next(req.client, now)
        except BaseException as e:  # noqa: BLE001 - a future swallows it
            self.errors.append(e)

    def _submit(self, prompt, n_new, client=None, k=None):
        req = _Request(k, client, prompt, n_new)
        self.requests.append(req)
        try:
            req.stream = self.sys.submit(prompt, n_new)
        except Exception as e:  # noqa: BLE001 - a refusal is a failed request
            self.refusals.append(f"request {k}: {type(e).__name__}: {e}")
            req.failed = True
            return req
        for i in range(n_new):
            req.stream.token(i).add_done_callback(functools.partial(
                self._on_token, req, i == n_new - 1))
        return req

    def _submit_next(self, client, after=None):
        prompt, n_new = self.sched.request(self.next_k)
        req = self._submit(prompt, n_new, client, self.next_k)
        self.next_k += 1
        if after is not None:
            self.lateness.append(req.t_submit - after)

    def _snapshot(self):
        """What must not move inside the window, and the counters."""
        return {"compiles": self.compiles.requests,
                "counters": self.sys.counters()}

    def _open_window(self, now):
        """The window opens (on the server's thread) the moment the last
        warm-up request completes."""
        self.before = self._snapshot()
        self.t0 = now

    def _sleep_until(self, until):
        """The clients run on the server's thread; this one only waits."""
        while time.perf_counter() < until:
            if self.errors:
                raise self.errors[0]
            time.sleep(min(0.05, max(0.0, until - time.perf_counter())))

    def _settle_heap(self):
        """No full collection from here to ``free``.  Set-up leaves millions
        of tracked objects that live as long as the process, and the run
        adds its own records (a future and a callback for every token, all
        kept for the comparison): a full collection walks them all and
        frees none, a third to half a second at a time with every stream
        waiting, four or five times a window, and longer on a slower host
        (PERF.md section 6, PR 35).  The young generations keep collecting;
        ``free`` gives the old one its threshold back."""
        gc.collect()
        self._gc_thresholds = gc.get_threshold()
        gc.set_threshold(*self._gc_thresholds[:2], 1 << 30)

    def _serve_alone(self, prompts, n_new):
        """Submit ``prompts`` at once and wait for all of them (set-up)."""
        reqs = [self._submit(p, n_new) for p in prompts]
        if any(r.failed for r in reqs):
            raise RuntimeError(f"set-up request refused: {self.refusals}")
        self.sys.start()
        for r in reqs:
            r.stream.result(timeout=900)
        self.requests.clear()

    # -- the run -----------------------------------------------------------

    def setup(self):
        t = time.perf_counter()
        self.spec = self.reference.param_spec(self.cfg)
        w = weights.make(self.spec, self.seed)
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
        for n in range(2, int(self.mix["max_chunk"]) + 1):
            self._serve_alone([rng.integers(0, vocab, n, dtype=np.int32)], 2)
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

    def window(self, seconds, tracer):
        trace_from = seconds - float(self.mix["trace_seconds"])
        before, t0 = self.before, self.t0
        t1 = t0 + seconds
        if tracer is not None:
            self._sleep_until(t0 + max(0.0, trace_from))
            tracer.start()
        self._sleep_until(t1)
        self.submitting = False
        after = self._snapshot()
        if tracer is not None:
            tracer.stop()
        owed = [r for r in self.requests
                if t0 <= r.t_submit < t1 and not r.times and not r.failed]
        deadline = time.perf_counter() + 120
        while any(not r.times and not r.failed for r in owed):
            if time.perf_counter() > deadline:
                raise RuntimeError("first tokens still owed 120 s after "
                                   "the window closed")
            time.sleep(0.005)
        self.closing = True
        red = reduce_window(self.requests, t0, t1)
        delta = {k: v - before["counters"].get(k, 0)
                 for k, v in after["counters"].items()}
        done = [r for r in self.requests if r.k is not None
                and len(r.times) == r.n_new and t0 <= r.times[-1] < t1]
        if not red["ttft"] or not red["gaps"]:
            raise RuntimeError("the window saw no first token or no gap")
        itl = gap_profile(red["gaps"])
        self.log("[serve] " + json.dumps({
            "kv_bytes": after["counters"].get("decode_kv_bytes_hw"),
            "steps": delta.get("decode_steps"),
            "chunked_steps": delta.get("decode_prefill_steps"),
            "prompt_tokens": delta.get("decode_prefill_rows"),
            "chunk_steps_saved": delta.get("decode_prefill_steps_saved"),
            "output_tokens": red["emitted"],
            "itl_ms": {k: round(v, 4) for k, v in itl.items()},
            "submitted": red["attempted"], "first_tokened": len(red["ttft"]),
            "finished_in_window": len(done),
            "late_ms_mean": 1e3 * float(np.mean(self.lateness or [0])),
            "late_ms_max": 1e3 * float(np.max(self.lateness or [0])),
            "requests_so_far": self.next_k, "refused": self.refusals}))
        moved = {k: delta.get(k, 0) for k in (
            "decode_len_grows", "decode_batch_grows", "decode_kv_bytes_hw",
            "serve_bucket_compiles") if delta.get(k, 0)}
        if after["compiles"] != before["compiles"]:
            moved["compile_requests"] = after["compiles"] - before["compiles"]
        if moved:
            raise RuntimeError(
                f"the engine did not hold its state over the window: {moved}")
        self.sample = self._sample(done)
        return {
            "end_to_end": {
                "serve_tokens_per_s": red["emitted"] / seconds,
                # the 90th percentile, not the 95th: it lies inside the
                # one-token steps' gaps on every seed (PERF.md section 2)
                "itl_p90_ms": itl["p90"]},
            "attempted": red["attempted"], "failed": red["failed"],
            "window": {"seconds": seconds, "counters": delta,
                       "ttft_s": red["ttft"], "gaps": len(red["gaps"]),
                       "itl_ms": itl}}

    @staticmethod
    def _sample(done):
        """Every request that finished in the window, the longest first."""
        if not done:
            raise RuntimeError("no request finished inside the window")
        done.sort(key=lambda r: (-(len(r.prompt) + r.n_new), r.k))
        return [(np.asarray(r.prompt), np.asarray(r.stream.result(), np.int32))
                for r in done]

    def free(self):
        self.closing = True
        self.sys.close()
        self.sys = None
        self.requests.clear()
        gc.set_threshold(*self._gc_thresholds)
        gc.collect()

    def gaps(self, precision="highest", served=True):
        """Per served position, how far the logit of a token lies below the
        reference's best: of the SERVED token, or (``served=False``, the
        control) of the token that a forward pass in ``precision`` puts
        first at that position.  Graded on the device: only the gaps come
        back, not the (positions, vocab) logits."""
        import jax
        import jax.numpy as jnp
        w = weights.make(self.spec, self.seed)
        # one program for every request: pad to the table's longest
        width = -(-max(p + o for p, o in self.sched.table) // 128) * 128

        def forward(p):
            return functools.partial(self.reference.logits, cfg=self.cfg,
                                     precision=p)

        @jax.jit
        def grade(w, ids, put):
            ref = forward("highest")(w, ids)
            if not served:
                put = forward(precision)(w, ids).argmax(-1)
            return ref.max(-1) - jnp.take_along_axis(
                ref, put[:, None], axis=-1)[:, 0]

        out = []
        for prompt, tokens in self.sample:
            seq = np.concatenate([prompt, tokens[:-1]])
            first = len(prompt) - 1      # the position that predicts token 0
            ids = np.pad(seq, (0, width - len(seq)))
            put = np.zeros(width, np.int32)
            put[first:first + len(tokens)] = tokens
            out.append(np.asarray(grade(w, jnp.asarray(ids), jnp.asarray(put)))
                       [first:first + len(tokens)])
        return np.concatenate(out)

    def check(self, control=False):
        """``control``: the reference in the program's place, computed in
        the configuration's ``control_precision`` — has to come out as not
        correct."""
        gaps = self.gaps(self.cfg["control_precision"], served=False) \
            if control else self.gaps()
        self.log(f"[serve] compared {len(gaps)} served tokens of "
                 f"{len(self.sample)} requests, "
                 f"{int((gaps > 0).sum())} not the reference's first")
        # the widest gap catches a token altered where it is produced; it
        # cannot tell bfloat16 from float32 (a maximum of a few dozen
        # near-ties swings by its nature).  The mean of the squared gaps
        # can: a logit error ε flips a token where the reference's top two
        # lie within ε and leaves a gap up to ε, so it grows as ε³
        return {"logit_gap_max": float(gaps.max()),
                "logit_gap_sq_mean": float(np.square(gaps).mean())}
