"""stf.nn namespace (ref: tensorflow/python/ops/nn.py)."""

from ..ops.nn_ops import (
    relu, relu6, elu, selu, gelu, leaky_relu, swish, silu, crelu,
    softplus, softsign, softmax, log_softmax, l2_loss, bias_add,
    softmax_cross_entropy_with_logits, softmax_cross_entropy_with_logits_v2,
    sparse_softmax_cross_entropy_with_logits,
    sigmoid_cross_entropy_with_logits, weighted_cross_entropy_with_logits,
    conv2d, depthwise_conv2d, depthwise_conv2d_native, separable_conv2d,
    conv3d, conv2d_transpose, conv3d_transpose, atrous_conv2d,
    dilation2d, erosion2d,
    max_pool, avg_pool, max_pool3d, avg_pool3d,
    dropout, local_response_normalization, lrn, in_top_k, top_k,
    xw_plus_b, log_poisson_loss,
    conv1d, convolution, atrous_conv2d_transpose,
    conv2d_backprop_input, conv2d_backprop_filter, max_pool_with_argmax,
    pool, with_space_to_batch, fractional_max_pool, fractional_avg_pool,
    quantized_conv2d, quantized_relu_x, quantized_max_pool,
    quantized_avg_pool, conv3d_backprop_filter_v2,
    depthwise_conv2d_native_backprop_filter,
    depthwise_conv2d_native_backprop_input,
)
from ..ops.nn_impl import (
    moments, weighted_moments, fused_batch_norm, batch_normalization,
    batch_norm_with_global_normalization, l2_normalize, zero_fraction,
    normalize_moments, sufficient_statistics, nce_loss, sampled_softmax_loss,
)
from ..ops.embedding_ops import (
    embedding_lookup, embedding_lookup_sparse, embedding_lookup_fused,
    embedding_bag,
)
from ..ops.math_ops import sigmoid, tanh
from ..ops.rnn import (
    dynamic_rnn, static_rnn, bidirectional_dynamic_rnn, raw_rnn,
)
from ..ops import rnn_cell
from ..ops.fused_ops import (
    fused_attention, fused_bias_dropout_residual, fused_layer_norm,
    fused_softmax_cross_entropy, quantized_matmul,
)
from ..ops.kv_cache_ops import (decode_attention, paged_decode_attention,
                                paged_latent_attention)
from ..ops.moe_ops import routed_ffn_op as routed_ffn
from ..ops.ssm_ops import (
    causal_conv1d_op as causal_conv1d, gated_rms_norm_op as gated_rms_norm,
    short_conv_update_op as short_conv_update,
    ssm_chunk_scan_op as ssm_chunk_scan,
    ssm_state_update_op as ssm_state_update,
)
from ..ops.sparse_attention_ops import (
    indexer_topk_op as indexer_topk, rms_norm_op as rms_norm,
    rotary_embedding_op as rotary_embedding,
    selected_attention_op as selected_attention,
    sparse_block_attention_op as sparse_block_attention,
    pooled_block_choice_op as pooled_block_choice,
    sparse_latent_block_attention_op as sparse_latent_block_attention,
    selected_latent_attention_op as selected_latent_attention,
)
from ..ops.linear_attention_ops import (
    kda_chunk_scan_op as kda_chunk_scan,
    kda_state_update_op as kda_state_update,
)
from ..ops.hyper_connection_ops import (
    hc_pre_op as hyper_connection_pre, hc_post_op as hyper_connection_post,
)
from ..ops.candidate_sampling_ops import (
    uniform_candidate_sampler, log_uniform_candidate_sampler,
    learned_unigram_candidate_sampler, fixed_unigram_candidate_sampler,
    compute_accidental_hits, all_candidate_sampler,
)
from ..ops.ctc_ops import (ctc_loss, ctc_greedy_decoder,
                           ctc_beam_search_decoder)
