"""Op library — full surface parity with reference ``gpu_ops/__init__.py``."""
from .base import def_op, SimpleOp, OP_REGISTRY
from .arithmetic import (
    add_op, addbyconst_op, minus_op, minusbyconst_op, minus_byconst_op,
    mul_op, mulbyconst_op, mul_byconst_op, div_op, div_const_op, const_div_op,
    div_handle_zero_op, fmod_op, ne_op, outer_op, const_pow_op, abs_op,
    opposite_op, exp_op, log_op, sqrt_op, rsqrt_op, sigmoid_op, tanh_op,
    erf_op,
    sin_op, cos_op, floor_op, bool_op, pow_op, clamp_op, oneslike_op,
    zeroslike_op, where_op, where_const_op, full_op, full_like_op, eye_op,
    arange_op, rand_op)
from .matmul import (matmul_op, linear_op, batch_matmul_op, addmm_op,
                     baddbmm_op, matrix_dot_op)
from .transform import (
    array_reshape_op, flatten_op, transpose_op, unsqueeze_op, squeeze_op,
    concat_op, concatenate_op, split_op, slice_op, slice_assign_op,
    slice_assign_matrix_op, slice_by_matrix_op, pad_op, broadcastto_op,
    broadcast_shape_op, repeat_op, roll_op, flip_op, gather_op,
    index_select_op, scatter_op, scatter1d_op, scatter1d_grad_op, indexing_op,
    as_strided_op, argmax_op, argsort_op, max_op, min_op, topk_val_op,
    topk_idx_op, one_hot_op, cumsum_with_bias_op, triu_op, tril_op,
    masked_fill_op, interpolate_op, norm_op)
from .reduce import reduce_sum_op, reduce_mean_op, reducesumaxiszero_op, sum_op
from .nn import (relu_op, leaky_relu_op, gelu_op, softmax_op, log_softmax_op,
                 softmax_func, dropout_op, dropout2d_op, conv2d_op,
                 conv2d_add_bias_op, max_pool2d_op, avg_pool2d_op,
                 batch_normalization_op, layer_normalization_op,
                 instance_normalization2d_op, BatchNormOp)
from .losses import (softmaxcrossentropy_op, softmaxcrossentropy_sparse_op,
                     crossentropy_op, crossentropy_sparse_op,
                     binarycrossentropy_op, nll_loss_op)
from .embedding import embedding_lookup_op
from .moe import (topk_gate_op, ktop1_gate_op, sam_gate_op,
                  layout_transform_op, reverse_layout_transform_op,
                  hash_dispatch_op, balance_assignment_op, alltoall_op,
                  halltoall_op, topk_gate_sparse_op, sparse_dispatch_op,
                  sparse_combine_op, moe_route_op, moe_experts_op,
                  moe_choices_op)
from .attention import (sdpa_op, sdpa_masked_op, sdpa_bias_op,
                        sdpa_masked_bias_op, sdpa_varlen_op,
                        sdpa_packed_op,
                        sdpa_decode_op, kv_cache_append_op,
                        kv_slab_placeholder, kv_slab_shape,
                        state_placeholder,
                        sdpa_prefill_op, chunk_positions_op,
                        split_heads_chunk_op, merge_heads_chunk_op,
                        chunk_emit_gather_op,
                        ring_attention_op, ulysses_attention_op)
from .matmul import einsum_op
from .ssm import (swiglu_op, silu_gate_op, greedy_token_op,
                  conv_state_shift_op, ssm_step_op, ssm_chunk_scan_op,
                  ring_append_op, diff_attention_kv_op,
                  diff_attention_ring_op, pair_rows_op, zeros_op)
from .kda import (rms_norm_op, sigmoid_gate_op, kda_chunk_op, kda_out_op,
                  head_norm_op, gqa_rows_op, gqa_attention_kv_op)
from .lightning import lightning_chunk_op
from .ssd import ssd_chunk_op, ssd_step_op
from .sparse_attention import (pool_rows_op, sparse_attention_kv_op,
                               sparse_choices_op)
from .mla import (rope_op, mla_latent_rows_op, mla_attention_kv_op,
                  mla_attention_op)
from .rnn import rnn_op, lstm_op, gru_op
from .transform import clone_op, cumsum_op, group_topk_idx_op

# reference-name aliases
slice_gradient_op = slice_op
array_reshape_gradient_op = array_reshape_op
