"""Graph node (Op) base for the define-then-run frontend.

TPU-native redesign of the reference's ``python/hetu/gpu_ops/Node.py:18`` (class
``Op``): instead of each node dispatching a CUDA kernel at run time, nodes here
are *symbolic*: they record the op kind, inputs and attributes. The executor
(:mod:`hetu_tpu.graph.executor`) topologically lowers an entire fetch subgraph
into ONE pure JAX function and ``jax.jit``-compiles it, so XLA sees the whole
program and can fuse / schedule it (no per-op kernel launches, no streams, no
events — cf. SURVEY.md §3.1).

Each concrete op provides a ``lower(ctx, *jax_vals) -> jax value`` rule, which
maps to ``jax.numpy`` / ``lax`` / Pallas.  Autodiff is NOT per-op ``gradient()``
rules as in the reference (``executor.py:1071``); gradients are taken with
``jax.grad`` over the lowered function (see :mod:`hetu_tpu.graph.gradients`).
"""
from __future__ import annotations

import os
import sys

import numpy as np

# Global monotonically increasing id for deterministic topo-order tie-breaking.
_NODE_COUNTER = 0

#: package root — frames inside it are framework internals, not the user's
#: graph-building code (provenance wants the USER call site)
_PKG_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _creation_site(skip=2, max_depth=25):
    """(filename, lineno, function) of the innermost frame OUTSIDE the
    hetu_tpu package — the user line that created this node.  Captured on
    every ``Op.__init__`` so graph diagnostics (``ht.lint``, executor
    ``validate=``) can say *where* a bad node came from, not just its
    auto-generated name.  A frame walk (no traceback object) keeps this
    cheap enough to run unconditionally."""
    try:
        f = sys._getframe(skip)
    except ValueError:  # pragma: no cover - shallow stack
        return None
    last = None
    for _ in range(max_depth):
        if f is None:
            break
        fn = f.f_code.co_filename
        last = (fn, f.f_lineno, f.f_code.co_name)
        if not fn.startswith(_PKG_DIR):
            return last
        f = f.f_back
    return last


def format_site(site):
    """Human-readable creation site ('file:line in func')."""
    if not site:
        return "<unknown site>"
    fn, line, func = site
    return f"{fn}:{line} in {func}"


def _next_id() -> int:
    global _NODE_COUNTER
    _NODE_COUNTER += 1
    return _NODE_COUNTER


#: the scope nodes are being created under (``name_scope``), or None
_SCOPE = None


class name_scope:
    """``with name_scope("mix.ssm"):`` — every node created inside
    carries the name, and the executors lower it inside
    ``jax.named_scope(name)``, so the operations of one part of a model
    keep that part's name in the compiled program's metadata and in a
    device trace (``benchmarks/trace_scopes.py`` sums device time by it).
    The innermost scope wins; a node created outside any has none and
    lowers exactly as before."""

    def __init__(self, name):
        self.name = str(name)

    def __enter__(self):
        global _SCOPE
        self._outer, _SCOPE = _SCOPE, self.name
        return self

    def __exit__(self, *exc):
        global _SCOPE
        _SCOPE = self._outer


class LowerCtx:
    """Per-build lowering context threaded through ``Op.lower``.

    Carries everything that is *not* part of the dataflow value flow:

    - ``training``: whether we are lowering the train subgraph (enables
      dropout, batch-norm stat updates, ...).
    - ``rng()``: returns a fresh ``jax.random`` key (split from the per-step
      key the executor feeds in), for dropout / stochastic ops.
    - ``state_updates``: side-channel dict ``{variable_node: new_value}`` for
      non-trainable state written during forward (e.g. BN running stats).
      The executor returns these as extra outputs and commits them to the
      variable store after the step (functional state, no mutation in trace).
    - ``mesh`` / ``axis_env``: the active device mesh (if distributed) so comm
      ops can emit sharding constraints or shard_map collectives.
    """

    def __init__(self, training: bool, base_key=None, mesh=None,
                 num_microbatches=None, pipeline=None):
        self.training = training
        self._base_key = base_key
        self._rng_count = 0
        self.state_updates = {}
        self.mesh = mesh
        # executor-level microbatch setting; pipeline_block inherits it
        # when its own n_microbatches is unset
        self.num_microbatches = num_microbatches
        # executor-level schedule choice ('gpipe' | 'pipedream' | 'hetpipe');
        # pipeline_block picks the 1F1B program for 'pipedream'
        self.pipeline = pipeline

    def rng(self):
        if self._base_key is None:
            raise RuntimeError(
                "This subgraph uses randomness (dropout etc.) but the executor "
                "did not thread a PRNG key; pass seed= to Executor.")
        import jax
        key = jax.random.fold_in(self._base_key, self._rng_count)
        self._rng_count += 1
        return key


class Op:
    """Symbolic graph node.

    Mirrors the user-facing surface of the reference ``Op``
    (``gpu_ops/Node.py:48-109`` operator overloads) so that model code written
    against ``ht.*`` ports over unchanged.
    """

    #: subclasses set this; used for naming and debugging
    op_type: str = "Op"

    def __init__(self, inputs, name=None, **attrs):
        self.id = _next_id()
        self.inputs = list(inputs)
        self.attrs = attrs
        self.name = name or f"{self.op_type}_{self.id}"
        # Provenance: the user line that created this node (diagnostics)
        self.creation_site = _creation_site()
        self.scope = _SCOPE   # name_scope the node was created under
        # Placement metadata (DeviceGroup / sharding spec); consumed by the
        # distribution layer, ignored in single-device runs.
        from ..context import current_context
        self.raw_ctx = current_context()
        self.sharding = None  # optional PartitionSpec-like annotation

    # -- lowering ---------------------------------------------------------
    def lower(self, ctx: LowerCtx, *vals):
        raise NotImplementedError(f"{self.op_type} has no lowering rule")

    def infer_shape(self, input_shapes):
        """Static output shape from input shapes.

        Ops without a hand-written rule fall back to the abstract
        interpreter (:mod:`hetu_tpu.analysis.shapes`): ``jax.eval_shape``
        of this node's ``lower`` rule over ``ShapeDtypeStruct``s — zero
        FLOPs, real shapes for EVERY op instead of ``None`` holes.
        Returns ``None`` only when the inputs are unknown or the lowering
        cannot be abstractly evaluated outside its runtime context.
        """
        from ..analysis.shapes import abstract_infer_shape
        return abstract_infer_shape(self, input_shapes)

    # -- python operator sugar (parity with Node.py:48-109) ---------------
    def __add__(self, other):
        from ..ops.arithmetic import add_op, addbyconst_op
        if isinstance(other, Op):
            return add_op(self, other)
        return addbyconst_op(self, const_attr=other)

    __radd__ = __add__

    def __sub__(self, other):
        from ..ops.arithmetic import minus_op, minusbyconst_op
        if isinstance(other, Op):
            return minus_op(self, other)
        return minusbyconst_op(self, const_attr=other)

    def __rsub__(self, other):
        from ..ops.arithmetic import minusbyconst_op, opposite_op
        if isinstance(other, Op):  # pragma: no cover - handled by __sub__
            raise TypeError
        return minusbyconst_op(opposite_op(self), const_attr=-other)

    def __neg__(self):
        from ..ops.arithmetic import opposite_op
        return opposite_op(self)

    def __mul__(self, other):
        from ..ops.arithmetic import mul_op, mulbyconst_op
        if isinstance(other, Op):
            return mul_op(self, other)
        return mulbyconst_op(self, const_attr=other)

    __rmul__ = __mul__

    def __truediv__(self, other):
        from ..ops.arithmetic import div_op, div_const_op
        if isinstance(other, Op):
            return div_op(self, other)
        return div_const_op(self, const_attr=1.0 / other)

    def __rtruediv__(self, other):
        from ..ops.arithmetic import const_div_op
        if isinstance(other, Op):  # pragma: no cover
            raise TypeError
        return const_div_op(self, const_attr=other)

    def __pow__(self, p):
        from ..ops.arithmetic import pow_op
        return pow_op(self, p=p)

    def __matmul__(self, other):
        from ..ops.matmul import matmul_op
        return matmul_op(self, other)

    def __repr__(self):
        return f"<{self.op_type} '{self.name}' id={self.id}>"

    __str__ = __repr__


class PlaceholderOp(Op):
    """A graph input: either a fed value (placeholder) or a Variable.

    Reference: ``gpu_ops/Variable.py:19`` (PlaceholderOp doubles as both).
    """

    op_type = "Placeholder"

    def __init__(self, name, value=None, initializer=None, trainable=False,
                 dtype=None, shape=None, is_embed=False):
        super().__init__([], name=name)
        self.initializer = initializer
        self.trainable = trainable
        self.is_embed = is_embed
        self.dtype = dtype
        self.shape = tuple(shape) if shape is not None else None
        self._value = None
        if value is not None:
            self.set_value(value)

    @property
    def is_variable(self):
        return self.initializer is not None or self._value is not None

    def set_value(self, value):
        value = np.asarray(value)
        self._value = value
        self.shape = value.shape
        if self.dtype is None:
            self.dtype = value.dtype

    def get_init_value(self, seed_key=None):
        """Materialise the initial value as a numpy/jax array."""
        if self._value is not None:
            return self._value
        if self.initializer is not None:
            if hasattr(self.initializer, "materialize"):
                return self.initializer.materialize(self.shape, seed_key)
            return self.initializer(self.shape, seed_key)
        return None

    def lower(self, ctx, *vals):  # never called: executor feeds these
        raise RuntimeError("Placeholder values are supplied by the executor")

    def infer_shape(self, input_shapes):
        return self.shape


def Variable(name, value=None, initializer=None, trainable=True, dtype=None,
             shape=None, is_embed=False):
    """Create a trainable (or stateful) graph variable.

    Parity with ``ht.Variable`` in the reference (``gpu_ops/Variable.py``).
    """
    return PlaceholderOp(name, value=value, initializer=initializer,
                         trainable=trainable, dtype=dtype, shape=shape,
                         is_embed=is_embed)


def placeholder_op(name="placeholder", dtype=np.float32, shape=None):
    return PlaceholderOp(name, dtype=dtype, shape=shape)


def topo_sort(fetches):
    """Deterministic post-order topological sort of the fetch subgraph."""
    visited = set()
    order = []

    def visit(node):
        if node.id in visited:
            return
        visited.add(node.id)
        for inp in node.inputs:
            visit(inp)
        order.append(node)

    for f in fetches:
        visit(f)
    return order


def find_placeholders(topo):
    feeds, variables = [], []
    for n in topo:
        if isinstance(n, PlaceholderOp):
            (variables if n.is_variable else feeds).append(n)
    return feeds, variables
