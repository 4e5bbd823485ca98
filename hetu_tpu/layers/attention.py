"""Multi-head attention layer (new capability; the reference composes this
per-model in ``examples/transformers/*/hetu_bert.py``).

DESIGN NOTE — dropout placement: the reference (and HF) drop attention
*probabilities* inside the softmax (``hetu_bert.py`` attention_probs
dropout).  Here ``dropout`` applies to the attention *output* (after the
o-projection) instead: per-probability dropout is incompatible with the
flash kernel's blockwise online softmax (probabilities never materialise),
and output dropout is the standard flash-attention-era substitute with the
same regularisation strength at equal rate.  Configs named ``attn_pdrop`` /
``attention_probs_dropout_prob`` are therefore REINTERPRETED as
output-dropout rates — loss curves match the reference in expectation, not
step-bitwise, whenever these rates are nonzero."""
from __future__ import annotations

from .base import BaseLayer
from .core import Linear, DropOut
from .. import ops
from ..ops.attention import packed_layout_reason, sdpa_op, sdpa_packed_op


class MultiHeadAttention(BaseLayer):
    def __init__(self, hidden_size, num_heads, dropout=0.0, causal=False,
                 context_parallel=None, name="mha"):
        assert hidden_size % num_heads == 0
        assert context_parallel in (None, "ring", "ulysses")
        self.h = num_heads
        self.dk = hidden_size // num_heads
        self.hidden = hidden_size
        self.causal = causal
        self.context_parallel = context_parallel
        self.q = Linear(hidden_size, hidden_size, name=name + ".q")
        self.k = Linear(hidden_size, hidden_size, name=name + ".k")
        self.v = Linear(hidden_size, hidden_size, name=name + ".v")
        self.o = Linear(hidden_size, hidden_size, name=name + ".o")
        self.drop = DropOut(dropout) if dropout else None

    def _split(self, x, batch, seq):
        x = ops.array_reshape_op(x, output_shape=(batch, seq, self.h, self.dk))
        return ops.transpose_op(x, perm=(0, 2, 1, 3))

    def _head_major_reason(self, mask, bias):
        """Why a call builds the (B, H, S, D) graph — a transpose on each
        side of its attention op — and None where it takes the PACKED one:
        q, k, v stay (B, S, H·D) as the projections leave them and the
        flash kernels read column blocks of heads out of that layout
        (``ops.attention.sdpa_packed_op``), which spares BERT's step 15
        whole-tensor copies a layer (PERF.md §6, PR 44).  Decided by what
        the call can observe and nothing else: the head size and count
        (:func:`~hetu_tpu.ops.attention.packed_layout_reason`), no
        context-parallel schedule (ring and Ulysses slice head-major
        chunks), no bias, and a mask only of the key-padding form
        (B|1, 1, 1, S_kv) — a dense mask or bias is laid out per head."""
        if self.context_parallel is not None:
            return f"context_parallel:{self.context_parallel}"
        if bias is not None:
            return "bias"
        reason = packed_layout_reason(self.h, self.dk)
        if reason is None and mask is not None:
            from ..analysis.shapes import infer_graph
            shape = infer_graph([mask]).shape(mask)
            if shape is None or len(shape) != 4 or shape[1:3] != (1, 1):
                reason = f"mask_shape:{shape}"
        return reason

    def __call__(self, x, batch, seq, kv=None, kv_seq=None, mask=None,
                 bias=None, scale=None):
        """x: (batch*seq, hidden) (reference models flatten); returns same.

        ``kv``: optional (batch*kv_seq, hidden) memory for cross-attention
        (encoder-decoder); ``mask``: optional validity mask node
        broadcastable to (B, H, S_q, S_k) — a (B, 1, 1, S_k) padding mask
        rides the flash kernel's O(S) key-mask strip path, and under
        context parallelism shards over the ring/ulysses schedule; a FULL
        per-query mask (XLNet-style permutation masks) shards its query
        dim over the ring like the bias does (swin stores its shift mask
        (nW, 1, w², w²) and tiles it to the window batch with an
        on-graph Repeat before calling here); ``bias``: optional
        additive logit bias node (T5 relative position bias),
        broadcastable to (B, H, S_q, S_k) — biased attention runs the
        flash kernel on TPU both locally and through the cp ring.

        Sequence lengths need NOT be 128-multiples: the dispatcher
        buckets ragged lengths into the kernel (pad → mask → unpad), so
        ``seq = 384 + r`` stays on the fast path; any genuine fallback
        is counted in ``hetu_tpu.metrics.flash_fallback_counts()``.
        """
        from ..ops.attention import (ring_attention_op, ulysses_attention_op,
                                     ring_attention_masked_op,
                                     ulysses_attention_masked_op,
                                     sdpa_bias_op, sdpa_masked_op,
                                     sdpa_masked_bias_op)
        kv = x if kv is None else kv
        kv_seq = seq if kv_seq is None else kv_seq
        reason = self._head_major_reason(mask, bias)
        if reason is None:
            def rows(t, n):
                return ops.array_reshape_op(
                    t, output_shape=(batch, n, self.hidden))
            o = sdpa_packed_op(
                rows(self.q(x), seq), rows(self.k(kv), kv_seq),
                rows(self.v(kv), kv_seq), *(() if mask is None else (mask,)),
                head_dim=self.dk, causal=self.causal, scale=scale)
            return self._project_out(o, batch, seq)
        from ..metrics import record_flash_head_major
        record_flash_head_major(reason)
        q = self._split(self.q(x), batch, seq)
        k = self._split(self.k(kv), batch, kv_seq)
        v = self._split(self.v(kv), batch, kv_seq)
        cp_attn = {"ring": ring_attention_op,
                   "ulysses": ulysses_attention_op}.get(self.context_parallel)
        cp_masked = {"ring": ring_attention_masked_op,
                     "ulysses": ulysses_attention_masked_op
                     }.get(self.context_parallel)
        if self.context_parallel is not None and cp_attn is None:
            raise ValueError(
                f"unknown context_parallel mode {self.context_parallel!r}")
        if cp_attn is not None and kv_seq != seq:
            # unequal-length cross-attention stays LOCAL (the T5 design,
            # models/t5.py:40): the cp schedules slice key columns by the
            # QUERY chunk size, which is only meaningful for matched
            # lengths — routing it onto the ring would be silently wrong
            cp_attn = cp_masked = None
        if mask is not None:
            if cp_masked is not None:
                # key-padding AND full per-query masks (plus optional
                # bias) shard over the cp schedule
                o = (cp_masked(q, k, v, mask, bias, causal=self.causal,
                               scale=scale) if bias is not None else
                     cp_masked(q, k, v, mask, causal=self.causal,
                               scale=scale))
            elif bias is not None:
                o = sdpa_masked_bias_op(q, k, v, mask, bias,
                                        causal=self.causal, scale=scale)
            else:
                o = sdpa_masked_op(q, k, v, mask, causal=self.causal,
                                   scale=scale)
        elif bias is not None:
            # T5 + context parallelism: the bias node becomes the schedule's
            # 4th input (ring-sliced / head-sharded)
            o = (cp_attn(q, k, v, bias, causal=self.causal, scale=scale)
                 if cp_attn is not None else
                 sdpa_bias_op(q, k, v, bias, causal=self.causal, scale=scale))
        elif cp_attn is not None:
            o = cp_attn(q, k, v, causal=self.causal, scale=scale)
        else:
            o = sdpa_op(q, k, v, causal=self.causal, scale=scale)
        return self._project_out(ops.transpose_op(o, perm=(0, 2, 1, 3)),
                                 batch, seq)

    def _project_out(self, o, batch, seq):
        """(B, S, …) attention output → the (B·S, hidden) stream."""
        o = ops.array_reshape_op(o, output_shape=(batch * seq, self.hidden))
        o = self.o(o)
        if self.drop is not None:
            o = self.drop(o)
        return o
