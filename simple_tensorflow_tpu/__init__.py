"""simple_tensorflow_tpu (``import simple_tensorflow_tpu as stf``).

A TPU-native framework with the capabilities of the reference stripped
TensorFlow-1.0 tree (DengZhuangSouthRd/simple_tensorflow): deferred graphs,
Sessions, variables, optimizers, distributed training — redesigned for
JAX/XLA/Pallas execution on TPU. See SURVEY.md for the architecture map.

The public namespace mirrors tf-1.x: stf.Session, stf.placeholder,
stf.Variable, stf.matmul, stf.train.AdamOptimizer, stf.nn.softmax, ...
"""

from .version import __version__, VERSION

# framework core
from .framework import dtypes
from .framework.dtypes import (
    DType, as_dtype,
    float16, half, bfloat16, float32, float64, double,
    float8_e4m3fn, float8_e5m2,
    int8, int16, int32, int64, uint8, uint16, uint32, uint64,
    bool_ as bool, complex64, complex128, string,
    qint8, quint8, qint32, qint16, quint16,
)
from .framework.tensor_shape import TensorShape, Dimension
from .framework import errors
from .framework.graph import (
    Graph, Operation, Tensor, GraphKeys, TensorSpec,
    get_default_graph, reset_default_graph,
    name_scope, control_dependencies, device, colocate_with, container,
    add_to_collection, add_to_collections, get_collection, get_collection_ref,
    convert_to_tensor, convert_n_to_tensor,
    register_tensor_conversion_function,
)
from .framework.constant_op import constant
from .framework.random_seed import set_random_seed
from .framework.gradients import gradients, AggregationMethod, GradientTape
from .framework.indexed_slices import IndexedSlices
from .framework.sparse_tensor import SparseTensor, SparseTensorValue
from .framework.config_pb import ConfigProto, GPUOptions, GraphOptions

# ops: import registers lowerings; re-export the tf-1.x flat namespace
from .ops import state_ops
from .ops import variables as _variables_mod
from .ops.variables import (
    Variable, PartitionedVariable, ResourceVariable, is_resource_variable,
    global_variables, all_variables, local_variables, model_variables,
    trainable_variables, moving_average_variables,
    variables_initializer, initialize_variables,
    global_variables_initializer, initialize_all_variables,
    local_variables_initializer, initialize_local_variables,
    is_variable_initialized, assert_variables_initialized,
    report_uninitialized_variables,
)
from .ops import math_ops, array_ops, control_flow_ops, random_ops, init_ops
from .ops import nn_ops, clip_ops, logging_ops, check_ops, functional_ops
from .ops import sparse_ops, linalg_ops, spectral_ops, string_ops
from .ops import variable_scope as _vs

from .ops.math_ops import (
    add, subtract, sub, multiply, mul, divide, div, truediv, realdiv,
    floordiv, mod, floormod, pow, maximum, minimum, squared_difference,
    abs, negative, neg, sign, reciprocal, square, sqrt, rsqrt, exp, expm1,
    log, log1p, sin, cos, tan, asin, acos, atan, atan2, sinh, cosh, tanh,
    asinh, acosh, atanh, sigmoid, erf, erfc, lgamma, digamma, igamma,
    igammac, zeta, polygamma, betainc, floor, ceil, rint, round,
    is_nan, is_inf, is_finite, logical_not, logical_and, logical_or,
    logical_xor, equal, not_equal, less, less_equal, greater, greater_equal,
    cast, to_float, to_double, to_int32, to_int64, to_bfloat16, saturate_cast,
    add_n, accumulate_n, matmul, batch_matmul, tensordot, einsum, cross,
    reduce_sum, reduce_mean, reduce_prod, reduce_max, reduce_min,
    reduce_all, reduce_any, reduce_logsumexp, count_nonzero,
    argmax, argmin, cumsum, cumprod,
    segment_sum, segment_mean, segment_max, segment_min, segment_prod,
    unsorted_segment_sum, unsorted_segment_max, unsorted_segment_min,
    unsorted_segment_prod, bincount, range, linspace, lin_space,
    l2_normalize, scalar_mul, trace, real, imag, conj, angle,
)
from .ops.array_ops import (
    placeholder, placeholder_with_default, identity, stop_gradient,
    check_numerics, shape, shape_n, size, rank, reshape, transpose,
    matrix_transpose, expand_dims, squeeze, zeros, ones, fill, zeros_like,
    ones_like, concat, split, stack, pack, unstack, unpack, pad, tile,
    slice, strided_slice, gather, gather_nd, scatter_nd, one_hot, where,
    select, boolean_mask, reverse, reverse_v2, reverse_sequence,
    sequence_mask, matrix_diag, matrix_diag_part, matrix_set_diag,
    matrix_band_part, diag, diag_part, eye, invert_permutation,
    broadcast_to, space_to_batch_nd, batch_to_space_nd, space_to_depth,
    depth_to_space, extract_image_patches, unique, setdiff1d, meshgrid,
    required_space_to_batch_paddings, edit_distance,
)
from .ops.control_flow_ops import (
    no_op, group, tuple, cond, case, while_loop, with_dependencies,
)
from .ops.random_ops import (
    random_uniform, random_normal, truncated_normal, random_shuffle,
    multinomial, random_gamma, random_poisson, random_crop,
)
from .ops.clip_ops import (
    clip_by_value, clip_by_norm, clip_by_global_norm, clip_by_average_norm,
    global_norm,
)
from .ops.logging_ops import Print, Assert
from .ops.init_ops import (
    zeros_initializer, ones_initializer, constant_initializer,
    random_uniform_initializer, random_normal_initializer,
    truncated_normal_initializer, uniform_unit_scaling_initializer,
    orthogonal_initializer, variance_scaling_initializer,
    glorot_uniform_initializer, glorot_normal_initializer,
)
from .ops.functional_ops import map_fn, scan, foldl, foldr
from .ops.variable_scope import (
    variable_scope, get_variable, get_variable_scope, VariableScope,
    AUTO_REUSE, no_regularizer, variable_op_scope,
)
from .ops.state_ops import (
    assign, assign_add, assign_sub, scatter_update, scatter_add, scatter_sub,
    scatter_mul, scatter_div, scatter_nd_update, count_up_to,
)
from .ops.check_ops import (
    assert_equal, assert_greater, assert_greater_equal, assert_less,
    assert_less_equal, assert_non_negative, assert_non_positive,
    assert_negative, assert_positive, assert_rank, assert_rank_at_least,
    assert_type, assert_integer, assert_scalar,
)
from .ops.template import make_template
from .ops.functional_ops import py_func
from .ops.tensor_array_ops import TensorArray
from .ops import parsing_ops
from .ops.parsing_ops import (
    FixedLenFeature, VarLenFeature, RaggedFeature, parse_example,
    parse_single_example, decode_raw,
)
from .ops import misc_ops
from .ops.misc_ops import (
    confusion_matrix, histogram_fixed_width, bitcast, lbeta,
)
from .ops.numerics import verify_tensor_all_finite, add_check_numerics_ops
from .ops import lookup_ops as lookup
from .ops.lookup_ops import tables_initializer
from .ops import sdca_ops
from .ops.sdca_ops import sdca_optimizer, sdca_shrink_l1, sdca_fprint
from .ops import quantization_ops
from .ops.quantization_ops import (
    quantize_v2, quantize, dequantize,
    fake_quant_with_min_max_args, fake_quant_with_min_max_args_gradient,
    fake_quant_with_min_max_vars, fake_quant_with_min_max_vars_gradient,
    fake_quant_with_min_max_vars_per_channel,
)
from .ops import session_ops
from .ops.session_ops import (
    TensorHandle, get_session_handle, get_session_tensor,
    delete_session_tensor,
)
from .ops import data_flow_ops
from .ops.data_flow_ops import (
    FIFOQueue, RandomShuffleQueue, PaddingFIFOQueue, PriorityQueue,
    QueueBase, StagingArea, Barrier, RecordInput, ConditionalAccumulator,
    SparseConditionalAccumulator, dynamic_partition, dynamic_stitch,
)
from .ops import io_ops
from .ops.io_ops import (
    ReaderBase, WholeFileReader, IdentityReader, TextLineReader,
    TFRecordReader, FixedLengthRecordReader, read_file, write_file,
    matching_files,
)
from .framework.function import Defun, recompute_grad
from .framework import function
from .framework import optimizer as graph_optimizer
from .ops.linalg_ops import (
    cholesky, matrix_determinant, matrix_inverse, matrix_solve,
    matrix_triangular_solve, qr, svd, self_adjoint_eig, self_adjoint_eigvals,
    norm,
)
from .ops.spectral_ops import fft, ifft, fft2d, ifft2d, fft3d, ifft3d

# client
from .client.session import (Session, InteractiveSession,
                             get_default_session, RunOptions, RunMetadata,
                             FetchFuture, ExecutionPlan)

# namespaces (tf.nn, tf.train, tf.layers, tf.summary, ...)
from . import compiler
from . import nn
from .ops import kv_cache_ops  # registers the KV-cache/decode op types
from .ops import moe_ops, sparse_attention_ops  # noqa: F401 — RoutedFFN; sparse-attention serving ops
from . import train
from . import layers
from . import losses
from . import metrics
from . import summary
from . import image
from . import data
from . import parallel
from . import saved_model
from . import serving
from . import estimator
from . import debug
from . import compat
from . import sets
from . import utils
from .utils import nest  # stf.nest (ref: python/util/nest.py)
from .platform import app, flags, tf_logging as logging, resource_loader
from .platform import monitoring
from .platform import test
from .client import device_lib
from .client import timeline

# gradient checker
from .framework.gradient_checker import compute_gradient, compute_gradient_error


# round-4 reference-parity exports (@@-export sweep vs the reference's
# python/{ops,framework,client,training} public names)
from .ops.string_ops import (
    string_join, string_lower, string_upper, string_strip, string_length,
    substr, as_string, string_to_number, string_to_hash_bucket,
    string_to_hash_bucket_fast, string_to_hash_bucket_strong,
    regex_replace, encode_base64, decode_base64, string_split, reduce_join,
)
from .ops.sparse_ops import (
    sparse_to_dense, sparse_tensor_to_dense, sparse_tensor_dense_matmul,
    sparse_add, sparse_reduce_sum, sparse_retain, sparse_reorder,
    sparse_slice, sparse_concat, sparse_placeholder, sparse_mask,
    sparse_reshape, sparse_transpose, sparse_split,
    sparse_fill_empty_rows, sparse_reset_shape, sparse_to_indicator,
    sparse_merge, sparse_softmax, sparse_maximum, sparse_minimum,
    sparse_reduce_sum_sparse,
)
from .ops.array_ops import (
    broadcast_static_shape, broadcast_dynamic_shape, parallel_stack,
    space_to_batch, batch_to_space, unique_with_counts,
)
from .ops.math_ops import (
    floor_div, truncatediv, truncatemod, complex,  # noqa: A004
    sparse_segment_sum, sparse_segment_mean, sparse_segment_sqrt_n,
)
from .ops.check_ops import (
    assert_none_equal, assert_proper_iterable, is_numeric_tensor,
    is_non_decreasing, is_strictly_increasing,
)
from .ops.spectral_ops import rfft, irfft, rfft2d, irfft2d, rfft3d, irfft3d
from .ops.variable_scope import (
    get_local_variable, fixed_size_partitioner,
    variable_axis_size_partitioner, min_max_variable_partitioner,
)
from .ops.state_ops import scatter_nd_add, scatter_nd_sub
from .ops.lookup_ops import initialize_all_tables
from .ops.session_ops import get_session_handle_v2
from .ops.parsing_ops import (
    FixedLenSequenceFeature, SparseFeature, decode_csv, parse_tensor,
    serialize_tensor, decode_json_example,
)
from .ops.misc_ops import remove_squeezable_dimensions
from .ops.linalg_ops import cholesky_solve, matrix_solve_ls
from .ops.quantization_ops import (
    quantized_concat, fake_quant_with_min_max_vars_per_channel_gradient,
)
from .platform.resource_loader import (
    load_op_library, load_file_system_library,
)
from .ops.data_flow_ops import ConditionalAccumulatorBase
from .framework.graph import (
    convert_to_tensor_or_indexed_slices, convert_to_tensor_or_sparse_tensor,
    op_scope,
)
from .framework.graph_io import import_graph_def, import_meta_graph, \
    export_meta_graph, write_graph
from .framework.gradients import (
    RegisterGradient, NotDifferentiable, NoGradient, hessians,
)
from .framework.random_seed import get_seed

# static analysis: graph verifier, variable-hazard detector, lint
# framework (stf.analysis; see docs/ANALYSIS.md)
from . import analysis

# production telemetry plane: HTTP metrics/status server, request
# tracing, flight recorder + watchdog (stf.telemetry;
# docs/OBSERVABILITY.md)
from . import telemetry

# async checkpointing + preemption-safe training (stf.checkpoint;
# docs/CHECKPOINT.md)
from . import checkpoint

# Pallas/XLA kernel routing tier: per-(op, shape, dtype, backend)
# fallback registry with cost-model gating (stf.kernels;
# docs/PERFORMANCE.md "kernel tier")
from . import kernels

newaxis = None
