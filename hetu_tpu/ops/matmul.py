"""Matrix ops — the MXU path.

Reference: ``src/ops/MatrixMult.cu`` (cublasSgemm), ``BatchMatrixMult.cu``,
``Linear.cu``, ``Addmm.cu``, ``Baddbmm.cu``, ``Dot.cu``.  Here they lower to
``jnp.matmul``/``lax.dot_general`` which XLA tiles onto the 128x128 systolic
array.

Dtype discipline: the dot's result dtype follows its operands (bf16 in →
bf16 out).  The MXU accumulates bf16 operands in f32 internally regardless,
so forcing ``preferred_element_type=f32`` buys nothing on the forward — and
it COSTS the backward: an f32 primal output makes every cotangent f32, and
JAX's dot vjp then promotes the bf16 operand, running all dgrad/wgrad dots
as f32×f32 at half MXU throughput (found by tools/hlo_audit.py: 196 of 294
flagship-step dots were f32).  Softmax-feeding contractions that genuinely
need an f32 RESULT (attention scores) opt in locally in ops/attention.py.
Serving graphs whose weights are STORED narrow under a float32 residual
stream opt in per node with ``out_dtype=``: the left operand is cast to the
right one's type and the result comes out in ``out_dtype`` (bfloat16
weights: one MXU pass, f32 accumulation; no backward to pay for).
"""
import jax.numpy as jnp

from .base import def_op


def _mm(c, a, b, trans_A=False, trans_B=False, out_dtype=None):
    if trans_A:
        a = a.T
    if trans_B:
        b = b.T
    if out_dtype is not None:
        return jnp.matmul(a.astype(b.dtype), b,
                          preferred_element_type=out_dtype)
    return jnp.matmul(a, b)


def _mm_shape(a, b, trans_A=False, trans_B=False, out_dtype=None):
    # mirrors the lowering exactly: `.T` REVERSES all axes (not a swap of
    # the trailing two), and jnp.matmul broadcasts leading batch dims /
    # promotes 1-D operands — the old 2-D-only rule was caught wrong on
    # ONNX-imported batched matmuls by the shape-rule-mismatch lint
    import numpy as np
    a = tuple(a)[::-1] if trans_A else tuple(a)
    b = tuple(b)[::-1] if trans_B else tuple(b)
    if len(a) == 1 and len(b) == 1:
        return ()
    if len(b) == 1:
        return a[:-1]
    if len(a) == 1:
        return b[:-2] + (b[-1],)
    batch = np.broadcast_shapes(a[:-2], b[:-2])
    return tuple(batch) + (a[-2], b[-1])


matmul_op = def_op("MatrixMult", _mm, _mm_shape)


def _linear(c, a, b, bias, trans_A=False, trans_B=False):
    return _mm(c, a, b, trans_A, trans_B) + bias


linear_op = def_op("Linear", _linear,
                   lambda a, b, bias, trans_A=False, trans_B=False:
                   _mm_shape(a, b, trans_A, trans_B))


def _bmm(c, a, b, trans_A=False, trans_B=False):
    if trans_A:
        a = jnp.swapaxes(a, -1, -2)
    if trans_B:
        b = jnp.swapaxes(b, -1, -2)
    return jnp.matmul(a, b)


batch_matmul_op = def_op("BatchMatrixMult", _bmm)

addmm_op = def_op(
    "Addmm",
    lambda c, inp, a, b, alpha=1.0, beta=1.0: beta * inp + alpha * _mm(c, a, b))

baddbmm_op = def_op(
    "Baddbmm",
    lambda c, inp, a, b, alpha=1.0, beta=1.0: beta * inp + alpha * _bmm(c, a, b))

matrix_dot_op = def_op("MatrixDot", lambda c, a, b: jnp.sum(a * b))


def einsum_op(subscripts, *nodes, name=None):
    """General einsum node (new; subsumes the reference's special-case batched
    contractions and feeds the MXU directly)."""
    from .base import SimpleOp
    return SimpleOp("Einsum", list(nodes),
                    lambda c, *vals, subscripts=None: jnp.einsum(
                        subscripts, *vals),
                    name=name, subscripts=subscripts)
