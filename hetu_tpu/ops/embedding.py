"""Embedding lookup (reference ``gpu_ops/EmbeddingLookUp.py:10`` +
``src/ops/EmbeddingLookup.cu``).

Dense path: ``jnp.take`` — XLA lowers the backward to a scatter-add, which is
the TPU-native equivalent of the reference's IndexedSlices machinery
(``ndarray.py:507``); no explicit sparse-gradient type is needed under jit.
Huge (HBM-exceeding) tables go through the host-resident embedding store in
:mod:`hetu_tpu.embedding` instead (HET cache semantics, SURVEY.md §5.8).
"""
import jax.numpy as jnp

from .base import def_op



def _lookup(c, table, idx, dtype=None):
    """``dtype``: the rows' type where it is not the table's (a table
    stored in bfloat16 under a float32 residual stream)."""
    rows = jnp.take(table, idx.astype(jnp.int32), axis=0)
    return rows if dtype is None else rows.astype(dtype)


embedding_lookup_op = def_op(
    "EmbeddingLookup", _lookup,
    lambda table, idx, dtype=None: tuple(idx) + (table[1],))
