"""Optimizers (reference ``python/hetu/optimizer.py``: SGD:171, Momentum:229,
AdaGrad:293, Adam:356, AdamW:429, Lamb:493; fused CUDA updates in
``src/ops/Optimizers.cu``).

TPU-native: each optimizer is a pure ``apply(params, grads, state, lr)``
pytree transform executed INSIDE the jitted training step, so the update
fuses with the backward pass (the reference needed hand-fused kernels for
this).  ``OptimizerOp`` keeps the graph-level contract: ``opt.minimize(loss)``
returns a fetchable node, and gradient wrapping for data-parallel happens via
mesh sharding instead of inserted AllReduce ops (``optimizer.py:145-164``).

Layout polymorphism (ZeRO weight-update sharding, ``parallel/zero.py``):
``apply`` never sees graph nodes — just a dict of same-shaped param/grad
arrays — so the sharded update feeds it ``(dp, width)`` bucket SLABS
instead of per-param arrays and the SAME code updates each replica's 1/dp
slice of state.  That only holds while the update is ELEMENTWISE per dict
entry (each output element depends only on that element's p/g/state plus
scalars like ``t``).  An optimizer that couples elements of one parameter
— LAMB's per-parameter trust-ratio norms — must set ``lamb = True``-style
markers so the ZeRO planner packs one param per bucket: a multi-param
slab would blend norms across parameters (the cross-REPLICA half is fine
— the partitioner turns the sharded slab's ``sum(p*p)`` into a partial
sum + all-reduce automatically).  New optimizers with cross-element terms
must do the same or stay off the ZeRO path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..graph.node import Op, PlaceholderOp, topo_sort
from ..graph.gradients import gradients


class OptimizerOp(Op):
    """Graph node that applies ``optimizer`` to its GradientOp inputs."""

    op_type = "OptimizerUpdate"

    def __init__(self, grad_nodes, optimizer, name=None):
        super().__init__(grad_nodes, name=name)
        self.optimizer = optimizer
        self.params = [g.wrt for g in grad_nodes]
        # reference parity: expert-parallel params (name contains 'expert')
        # are excluded from DP grad sync (optimizer.py:150-152); under SPMD
        # the mesh sharding handles this, recorded here for the strategies.
        self.dp_excluded = [p for p in self.params if "expert" in p.name]

    def lower(self, ctx, *vals):  # resolved specially by the executor
        raise RuntimeError("OptimizerOp must be resolved by the executor")


class Optimizer:
    def __init__(self, learning_rate, l2reg=0.0):
        self.lr = learning_rate  # float or LRScheduler
        self.l2reg = l2reg

    # -- graph API --------------------------------------------------------
    def minimize(self, loss, var_list=None):
        if var_list is None:
            var_list = [n for n in topo_sort([loss])
                        if isinstance(n, PlaceholderOp) and n.is_variable
                        and n.trainable]
        grad_nodes = gradients(loss, var_list)
        return OptimizerOp(grad_nodes, self)

    # -- host-side lr -----------------------------------------------------
    def host_lr(self, step):
        from .lr_scheduler import LRScheduler
        if isinstance(self.lr, LRScheduler):
            return float(self.lr.get(step))
        return float(self.lr)

    # -- traced lr (inside the jitted step) -------------------------------
    def traced_lr(self, step):
        """lr as a jax expression of the traced ``step_idx`` scalar, or
        ``None`` when the schedule is data-dependent (the executor then
        computes ``host_lr`` per step and feeds it as a runtime input).
        A constant float lr and every pure step-indexed scheduler trace
        (the per-step Python call and the ``np.asarray(lrs)`` disappear
        from the dispatch path — ``graph/run_plan.py``); the traced
        schedule is baked into the compiled program and hashed into the
        compiled-step cache signature.  ``HETU_TRACED_LR=0`` forces the
        host path everywhere (see :func:`traced_lr_enabled`)."""
        from .lr_scheduler import LRScheduler
        if isinstance(self.lr, LRScheduler):
            return self.lr.traced(step)
        import jax.numpy as jnp
        return jnp.float32(float(self.lr))

    def on_step(self, step):
        from .lr_scheduler import LRScheduler
        if isinstance(self.lr, LRScheduler):
            self.lr.on_step(step)

    # -- pure update ------------------------------------------------------
    def init_state(self, params):
        return {}

    def _reg(self, p, g):
        return g + self.l2reg * p if self.l2reg else g

    def apply(self, params, grads, state, lr):
        raise NotImplementedError


def traced_lr_enabled():
    """Traced-lr gate: ``HETU_TRACED_LR=0`` forces every optimizer onto
    the host ``lrs``-input path (parity debugging; the escape hatch for
    code that mutates a live ``optimizer.lr`` mid-training)."""
    import os
    return os.environ.get("HETU_TRACED_LR", "1") != "0"


def traced_lr_fn(opt):
    """``step -> lr`` callable evaluated inside the jitted step, or
    ``None`` when this optimizer's lr must stay a per-step host input
    (data-dependent schedule, tracing disabled, or a custom ``traced_lr``
    that errors).  Probed EAGERLY with a concrete step so the decision —
    which drives the host ``lrs`` input's shape and the compiled-step
    cache signature (``graph/step_cache.py`` hashes traced schedules) —
    is made before any tracing happens."""
    if not traced_lr_enabled():
        return None
    import jax.numpy as jnp
    try:
        probe = opt.traced_lr(jnp.int32(0))
    except Exception:
        return None
    if probe is None:
        return None
    return opt.traced_lr


class SGDOptimizer(Optimizer):
    def apply(self, params, grads, state, lr):
        new = {k: p - lr * self._reg(p, grads[k]) if k in grads else p
               for k, p in params.items()}
        return new, state


class MomentumOptimizer(Optimizer):
    def __init__(self, learning_rate=0.01, momentum=0.9, nesterov=False, l2reg=0.0):
        super().__init__(learning_rate, l2reg)
        self.momentum = momentum
        self.nesterov = nesterov

    def init_state(self, params):
        return {"v": jax.tree.map(jnp.zeros_like, params)}

    def apply(self, params, grads, state, lr):
        new_p, new_v = {}, {}
        for k, p in params.items():
            if k not in grads:
                new_p[k] = p
                new_v[k] = state["v"][k]
                continue
            g = self._reg(p, grads[k])
            v = self.momentum * state["v"][k] - lr * g
            new_v[k] = v
            new_p[k] = p + (self.momentum * v - lr * g if self.nesterov else v)
        return new_p, {"v": new_v}


class AdaGradOptimizer(Optimizer):
    def __init__(self, learning_rate=0.01, initial_accumulator_value=0.0,
                 eps=1e-7, l2reg=0.0):
        super().__init__(learning_rate, l2reg)
        self.init_acc = initial_accumulator_value
        self.eps = eps

    def init_state(self, params):
        return {"acc": jax.tree.map(
            lambda p: jnp.full_like(p, self.init_acc), params)}

    def apply(self, params, grads, state, lr):
        new_p, new_acc = {}, {}
        for k, p in params.items():
            if k not in grads:
                new_p[k], new_acc[k] = p, state["acc"][k]
                continue
            g = self._reg(p, grads[k])
            acc = state["acc"][k] + g * g
            new_acc[k] = acc
            new_p[k] = p - lr * g / (jnp.sqrt(acc) + self.eps)
        return new_p, {"acc": new_acc}


class AdamOptimizer(Optimizer):
    weight_decay = 0.0
    lamb = False

    def __init__(self, learning_rate=0.01, beta1=0.9, beta2=0.999,
                 epsilon=1e-7, l2reg=0.0, amsgrad=False):
        super().__init__(learning_rate, l2reg)
        self.beta1, self.beta2, self.epsilon = beta1, beta2, epsilon
        self.amsgrad = amsgrad

    def init_state(self, params):
        st = {"m": jax.tree.map(jnp.zeros_like, params),
              "v": jax.tree.map(jnp.zeros_like, params),
              "t": jnp.zeros((), jnp.int32)}
        if self.amsgrad:
            st["vmax"] = jax.tree.map(jnp.zeros_like, params)
        return st

    def apply(self, params, grads, state, lr):
        t = state["t"] + 1
        bc1 = 1 - self.beta1 ** t.astype(jnp.float32)
        bc2 = 1 - self.beta2 ** t.astype(jnp.float32)
        new_p, new_m, new_v, new_vmax = {}, {}, {}, {}
        for k, p in params.items():
            if k not in grads:
                new_p[k], new_m[k], new_v[k] = p, state["m"][k], state["v"][k]
                if self.amsgrad:
                    new_vmax[k] = state["vmax"][k]
                continue
            g = self._reg(p, grads[k])
            # lerp form (m + (1-b1)(g - m)), not b1*m + (1-b1)*g: a sum of
            # TWO products lets the backend contract either one into the
            # FMA, and XLA:CPU picks differently in a loop's vector body
            # and its scalar tail — so the same element rounded
            # differently once ZeRO's slab layout moved it (the 1-ulp
            # drift of ROADMAP D0).  One product per add leaves no choice.
            m0, v0 = state["m"][k], state["v"][k]
            m = m0 + (1 - self.beta1) * (g - m0)
            v = v0 + (1 - self.beta2) * (g * g - v0)
            new_m[k], new_v[k] = m, v
            vhat = v / bc2
            if self.amsgrad:
                vhat = jnp.maximum(state["vmax"][k], vhat)
                new_vmax[k] = vhat
            upd = (m / bc1) / (jnp.sqrt(vhat) + self.epsilon) \
                + self.weight_decay * p
            if self.lamb:
                wn = jnp.sqrt(jnp.sum(p * p))
                un = jnp.sqrt(jnp.sum(upd * upd))
                trust = jnp.where((wn > 0) & (un > 0), wn / un, 1.0)
                upd = trust * upd
            new_p[k] = p - lr * upd
        st = {"m": new_m, "v": new_v, "t": t}
        if self.amsgrad:
            st["vmax"] = new_vmax
        return new_p, st


class AdamWOptimizer(AdamOptimizer):
    def __init__(self, learning_rate=0.01, beta1=0.9, beta2=0.999,
                 epsilon=1e-7, weight_decay=0.0, l2reg=0.0):
        super().__init__(learning_rate, beta1, beta2, epsilon, l2reg)
        self.weight_decay = weight_decay


class LambOptimizer(AdamWOptimizer):
    lamb = True
