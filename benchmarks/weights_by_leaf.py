"""Seeded weights, one leaf at a time.

``weights.make`` draws ONE float32 vector of the total size and cuts it
into the leaves: for a model of billions of parameters that vector alone
does not fit beside the model.  Here every leaf of a spec (``{name: (shape,
mean, std)}``) has a key of its own — ``fold_in(key_of(seed), index of the
leaf in the spec)`` — is drawn in float32 and stored in the type asked for,
so a leaf's bits do not depend on which other leaves are made: the program
under test makes all of them once, the plain reference makes a layer's
leaves when it reaches that layer, and both hold the same values.
"""
import functools

from .weights import key_of


@functools.lru_cache(maxsize=None)
def _draw(shape, dtype):
    import jax
    import jax.numpy as jnp

    def draw(key, mean, std):
        return (jax.random.normal(key, shape, jnp.float32) * std
                + mean).astype(dtype)

    return jax.jit(draw)


def make(spec, seed, dtype, only=None):
    """``{name: device array of dtype}`` for the leaves of ``spec`` (all of
    them, or those named in ``only``)."""
    import jax
    import jax.numpy as jnp
    base = key_of(seed)
    index = {name: i for i, name in enumerate(spec)}
    out = {}
    for name in (spec if only is None else only):
        shape, mean, std = spec[name]
        out[name] = _draw(tuple(shape), jnp.dtype(dtype))(
            jax.random.fold_in(base, index[name]),
            jnp.float32(mean), jnp.float32(std))
    return out
