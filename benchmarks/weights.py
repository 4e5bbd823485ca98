"""Seeded weights, made on the device in one jitted call.

A configuration's reference states its parameters as ``{name: (shape, mean,
std)}``.  One normal draw of the total size is cut into the leaves, so the
compiled program is one random op and N slices whatever the depth.  The
program under test and the plain reference both get their weights from here
(the reference calls it again after the window: same seed, same backend,
same bits) and neither takes anything the other made.
"""
import math


def key_of(seed):
    """A jax PRNG key for any whole-number seed (the driver's go past 2**31)."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make(spec, seed):
    """``{name: float32 device array}`` for ``spec`` = ``{name: (shape, mean,
    std)}``."""
    import jax
    import jax.numpy as jnp
    names = list(spec)
    sizes = [math.prod(spec[n][0]) for n in names]
    total = sum(sizes)

    def draw(key):
        flat = jax.random.normal(key, (total,), jnp.float32)
        out, at = {}, 0
        for name, size in zip(names, sizes):
            shape, mean, std = spec[name]
            out[name] = flat[at:at + size].reshape(shape) * std + mean
            at += size
        return out

    return jax.jit(draw)(key_of(seed))
