"""Driver ``train_steps``: optimizer steps at a fixed batch shape.

Set-up builds ONE system (the compiled step with its state), drives it from
the seed through its first ``check_steps`` steps by the window's own call
and feed, keeps what the comparison needs (each loss, the first gradient's
norm per leaf out of Adam's first moment, the parameters' change per leaf),
warms up, and hands the same object to the window.  The window dispatches
blocks of steps until ``seconds`` have passed and then waits for the last:
the rate is every token position of every step over the whole of that time.
"""
import gc
import statistics
import time

import numpy as np

from .. import traffic, weights


def leaf_gaps(got, want):
    """Per leaf, |‖got‖ − ‖want‖| against the reference's norm of that leaf
    or of the median leaf, whichever is larger (some gradients are all but
    zero).  Sorted, worst first: ``[(gap, leaf), ...]``."""
    floor = statistics.median(want.values())
    return sorted(((abs(got[k] - want[k]) / max(want[k], floor), k)
                   for k in want), reverse=True)


def angle_gaps(got, want, want_norm):
    """1 − cos of the angle between the program's first gradient and the
    reference's, per leaf; worst leaf first.  Unlike a norm, it is moved by
    every element's rounding (1 − cos ≈ ε²/2 for a relative error ε), so it
    tells bfloat16 from fp8 where the norms cannot.  Leaves whose true
    gradient is (all but) zero have no direction and are left out."""
    import jax
    import jax.numpy as jnp
    floor = 1e-3 * statistics.median(want_norm.values())
    keep = [k for k in want if want_norm[k] > floor]

    def cosines(a, b):
        return {k: jnp.vdot(a[k], b[k]) / jnp.sqrt(
            jnp.vdot(a[k], a[k]) * jnp.vdot(b[k], b[k])) for k in keep}
    cos = jax.jit(cosines)({k: got[k] for k in keep},
                           {k: want[k] for k in keep})
    gaps = sorted(((1.0 - float(v), k) for k, v in cos.items()),
                  reverse=True)
    return gaps[0][0], gaps


class Driver:
    def __init__(self, *, cfg, mix, seed, system, reference, compiles, log):
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.system, self.reference = system, reference
        self.compiles, self.log = compiles, log
        self.cursor = 0

    def _norms(self, tree):
        import jax
        import jax.numpy as jnp
        fn = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(
            v.astype(jnp.float32)))) for k, v in t.items()})
        return {k: float(v) for k, v in fn(tree).items()}

    def _run(self, n):
        base = self.cursor
        self.cursor += n
        return self.sys.run(
            lambda i: self.feeds[(base + i) % len(self.feeds)], n)

    def _watched(self):
        """What must not move inside the window: programs compiled, run
        plans rebuilt, attention calls that left the flash kernel."""
        c = self.sys.counters()
        return {"compile_requests": self.compiles.requests,
                "plan_cache_miss": c.get("plan_cache_miss", 0),
                "flash_fallbacks": c["flash_fallbacks"]}

    def setup(self):
        t = time.perf_counter()
        self.spec = self.reference.param_spec(self.cfg)
        start = weights.make(self.spec, self.seed)
        self.sys = self.system.System(self.cfg, self.mix, start)
        self.batches = traffic.mlm_batches(self.mix, self.cfg["vocab_size"],
                                           self.seed)
        self.feeds = [self.sys.feed(b) for b in self.batches]
        self.log(f"[train] built in {time.perf_counter() - t:.1f} s")
        t = time.perf_counter()
        n_check = int(self.mix["check_steps"])
        first = self._run(1)
        self.sys.wait(first)
        self.log(f"[train] first step (compile) {time.perf_counter() - t:.1f}"
                 f" s, {self.compiles.hits}/{self.compiles.requests} "
                 "programs from the cache")
        b1 = self.cfg["optimizer"]["beta1"]
        # Adam's first moment after one step is (1 − β₁)·g₁: a copy of it
        # is what the timed step produced, kept past the program's state
        moment = {k: v * 1 for k, v in self.sys.first_moment().items()}
        self.got = {
            "first_moment": moment,
            "grad_norm": {k: v / (1 - b1)
                          for k, v in self._norms(moment).items()}}
        rest = self._run(n_check - 1)
        self.sys.wait(rest)
        self.got["losses"] = [self.sys.loss(r) for r in first + rest]
        now = self.sys.params()
        self.got["delta_norm"] = self._norms(
            {k: now[k] - start[k] for k in now})
        del start, now
        self.sys.wait(self._run(int(self.mix["warmup_steps"])))
        self.log(f"[train] first losses {self.got['losses']}")
        # no full collection from here to ``free``: what set-up built
        # lives as long as the run, and walking it frees nothing
        gc.collect()
        self._gc_thresholds = gc.get_threshold()
        gc.set_threshold(*self._gc_thresholds[:2], 1 << 30)

    def window(self, seconds, tracer):
        block = int(self.mix["block_steps"])
        trace_from = seconds - float(self.mix["trace_seconds"])
        before = self._watched()
        steps, last = 0, None
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            if tracer is not None and not tracer.on and now >= trace_from:
                tracer.start()
            last = self._run(block)
            steps += block
        self.sys.wait(last)
        t_end = time.perf_counter()
        if tracer is not None:
            tracer.stop()
        after = self._watched()
        final = self.sys.loss(last[-1])
        self.log(f"[train] window {t_end - t0:.3f} s, {steps} steps, "
                 f"last loss {final}, counters {self.sys.counters()}")
        moved = {k: v - before[k] for k, v in after.items() if v != before[k]}
        if moved:
            raise RuntimeError(f"moved inside the measured window: {moved}")
        b, s = int(self.mix["batch"]), int(self.mix["seq_len"])
        masked = int((self.batches[0]["masked_lm_labels"] >= 0).sum())
        return {
            "end_to_end": {"train_tokens_per_s": steps * b * s / (t_end - t0)},
            "attempted": steps,
            "failed": 0 if np.isfinite(final) else steps,
            "window": {"seconds": t_end - t0, "steps": steps,
                       "batch": b, "seq_len": s, "masked": masked}}

    def free(self):
        self.sys.close()
        self.sys = self.feeds = None
        gc.set_threshold(*self._gc_thresholds)
        gc.collect()

    def check(self, control=False):
        """``control``: the reference in the program's place, its products
        in the configuration's ``control_precision`` — has to come out as
        not correct."""
        n_check = int(self.mix["check_steps"])
        start = weights.make(self.spec, self.seed)
        if control:
            self.got = self.reference.follow(
                start, self.batches[:n_check], self.cfg,
                precision=self.cfg["control_precision"])
            self.got["first_moment"] = self.got["first_grads"]
        want = self.reference.follow(start, self.batches[:n_check], self.cfg)
        numbers = {}
        for i, (g, w) in enumerate(zip(self.got["losses"], want["losses"])):
            numbers[f"loss_gap_step{i + 1}"] = abs(g - w) / abs(w)
        numbers["grad_angle_gap"], angles = angle_gaps(
            self.got["first_moment"], want["first_grads"],
            want["grad_norm"])
        self.log(f"[train] widest angles: {angles[:3]}")
        grad = leaf_gaps(self.got["grad_norm"], want["grad_norm"])
        delta = leaf_gaps(self.got["delta_norm"], want["delta_norm"])
        numbers["grad_norm_gap"] = grad[0][0]
        # the MEDIAN leaf's change, not the worst: the key biases' true
        # gradient is zero (softmax ignores a shift of the keys), so Adam
        # turns their rounding noise into a full-size step on one side only
        numbers["delta_norm_gap"] = statistics.median(g for g, _ in delta)
        self.log(f"[train] worst leaves: grad {grad[:3]}, delta {delta[:3]}")
        self.log(f"[train] reference losses {want['losses']}")
        return numbers
