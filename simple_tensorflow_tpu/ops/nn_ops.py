"""Neural-net ops (ref: tensorflow/python/ops/nn_ops.py,
core/kernels/{conv_ops,maxpooling_op,avgpooling_op,softmax_op,relu_op,
bias_op,xent_op}.cc and their *_gpu.cu.cc CUDA kernels).

TPU-native notes:
- conv2d lowers to lax.conv_general_dilated in NHWC with f32 accumulation —
  XLA tiles it onto the MXU (the reference dispatches to cuDNN). NCHW inputs
  are accepted and transposed once; NHWC is the TPU-preferred layout.
- softmax/log_softmax/xent are jax.nn compositions fused by XLA; a Pallas
  fused softmax-xent for large vocabularies lives in ops/pallas/.
- dropout uses the functional RNG stream (see random_ops) so the same mask
  is replayed in the vjp backward pass.
"""

from __future__ import annotations

import builtins
import numpy as np

import jax
import jax.numpy as jnp

from ..framework import dtypes as dtypes_mod
from ..framework import graph as ops_mod
from ..framework import op_registry
from ..framework import random_seed as random_seed_mod
from ..framework import tensor_shape as shape_mod
from .op_util import make_op, unary

Tensor = ops_mod.Tensor


def _acc32(dtype):
    d = np.dtype(dtype)
    return np.float32 if (d.kind == "f" and d.itemsize <= 2) or str(d) == "bfloat16" \
        else None


# -- registrations -----------------------------------------------------------

op_registry.register_pure("Relu", jax.nn.relu)
op_registry.register_pure("Relu6", jax.nn.relu6)
op_registry.register_pure("Elu", jax.nn.elu)
op_registry.register_pure("Selu", jax.nn.selu)
op_registry.register_pure("Gelu", lambda x, approximate=True: jax.nn.gelu(
    x, approximate=approximate))
op_registry.register_pure("LeakyRelu", lambda x, alpha=0.2: jax.nn.leaky_relu(
    x, negative_slope=alpha))
op_registry.register_pure("Softmax", lambda x, axis=-1: jax.nn.softmax(x, axis=axis))
op_registry.register_pure("LogSoftmax", lambda x, axis=-1: jax.nn.log_softmax(
    x, axis=axis))
op_registry.register_pure("Swish", lambda x: jax.nn.silu(x))
op_registry.register_pure("L2Loss", lambda x: 0.5 * jnp.sum(
    jnp.square(x.astype(jnp.float32))).astype(x.dtype))
op_registry.register_pure("BiasAdd", lambda x, b, data_format="NHWC":
                          x + (b.reshape((1, -1) + (1,) * (x.ndim - 2))
                               if data_format.startswith("NC") and x.ndim > 2
                               else b))


def _softmax_xent(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    loss = -jnp.sum(labels.astype(jnp.float32) * logp, axis=-1)
    return loss.astype(logits.dtype)


def _sparse_softmax_xent(logits, labels):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    loss = -jnp.take_along_axis(logp, labels[..., None].astype(jnp.int32),
                                axis=-1)[..., 0]
    return loss.astype(logits.dtype)


op_registry.register_pure("SoftmaxCrossEntropyWithLogits", _softmax_xent)


def _sparse_xent_pallas(logits, labels):
    """The Pallas streamed-xent route for the composed graph op: same
    contract (per-example loss in the logits dtype)."""
    from .pallas import softmax_cross_entropy

    return softmax_cross_entropy(logits, labels).astype(logits.dtype)


def _sparse_xent_eligible(key):
    # same contract as the FusedSoftmaxXent op — one eligibility
    # implementation (ops/pallas) serves both routes
    from . import pallas as _pallas

    return _pallas._xent_eligible(key)


def _lower_sparse_xent(ctx, op, inputs):
    """nn_ops sparse softmax-xent: routed through stf.kernels — the
    large-vocab Pallas streamed kernel replaces the composed
    log_softmax + gather lowering when the cost gate lets it
    in (ops/pallas/softmax_xent.py); ``off`` mode keeps the composed
    lowering exactly."""
    from ..kernels import registry as _kreg

    logits, labels = inputs
    fn = _kreg.select("SparseSoftmaxCrossEntropyWithLogits",
                      _kreg.aval_key(logits, labels))
    return [fn(logits, labels)]


op_registry.register("SparseSoftmaxCrossEntropyWithLogits",
                     lower=_lower_sparse_xent,
                     pure_fn=_sparse_softmax_xent)


def _register_sparse_xent_kernel():
    from ..kernels import registry as _kreg

    def _gate(key, bk):
        lb_shape, lb_dt = key[0]
        n = 1
        for d in lb_shape:
            n *= int(d)
        try:
            itm = {"bfloat16": 2, "float16": 2}.get(str(lb_dt))
            if itm is None:
                import numpy as _np

                itm = _np.dtype(str(lb_dt)).itemsize
        except TypeError:
            itm = 4
        return _kreg.roofline_gate(5.0 * n, 1.2 * n * itm, 3.0 * n * itm, bk)

    _kreg.register_kernel(
        "SparseSoftmaxCrossEntropyWithLogits",
        impls={"pallas": _sparse_xent_pallas, "xla": _sparse_softmax_xent},
        legacy="xla",
        eligible=_sparse_xent_eligible,
        cost_gate=_gate,
        graph_key=lambda op: _sparse_xent_graph_key(op),
        doc="composed log_softmax+gather vs the Pallas streamed "
            "online-softmax xent kernel")


def _sparse_xent_graph_key(op):
    from . import pallas as _pallas

    return _pallas._simple_graph_key(op)


_register_sparse_xent_kernel()
op_registry.register_pure(
    "SigmoidCrossEntropyWithLogits",
    lambda logits, labels: (jnp.maximum(logits, 0) - logits * labels +
                            jnp.log1p(jnp.exp(-jnp.abs(logits)))))


def _conv2d_impl(x, w, strides=(1, 1, 1, 1), padding="SAME",
                 data_format="NHWC", dilations=(1, 1, 1, 1)):
    if data_format == "NCHW":
        x = jnp.transpose(x, (0, 2, 3, 1))
    sh, sw = strides[1:3] if data_format == "NHWC" else strides[2:4]
    dh, dw = dilations[1:3] if data_format == "NHWC" else dilations[2:4]
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=(sh, sw), padding=padding,
        rhs_dilation=(dh, dw),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    # NOTE: no preferred_element_type here — the MXU accumulates bf16 convs
    # in f32 natively, and an explicit f32 output breaks the vjp transpose
    # (f32 cotangent vs bf16 weights in lax.conv_general_dilated).
    if data_format == "NCHW":
        out = jnp.transpose(out, (0, 3, 1, 2))
    return out


op_registry.register_pure("Conv2D", _conv2d_impl)


def _depthwise_conv2d_impl(x, w, strides=(1, 1, 1, 1), padding="SAME",
                           data_format="NHWC", dilations=(1, 1, 1, 1)):
    if data_format == "NCHW":
        x = jnp.transpose(x, (0, 2, 3, 1))
    c = x.shape[-1]
    kh, kw, cin, mult = w.shape
    w2 = jnp.reshape(jnp.transpose(w, (0, 1, 2, 3)), (kh, kw, 1, cin * mult))
    out = jax.lax.conv_general_dilated(
        x, w2, window_strides=tuple(strides[1:3]), padding=padding,
        rhs_dilation=tuple(dilations[1:3]),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=c)
    if data_format == "NCHW":
        out = jnp.transpose(out, (0, 3, 1, 2))
    return out


op_registry.register_pure("DepthwiseConv2dNative", _depthwise_conv2d_impl)


def _conv3d_impl(x, w, strides=(1, 1, 1, 1, 1), padding="SAME"):
    return jax.lax.conv_general_dilated(
        x, w, window_strides=tuple(strides[1:4]), padding=padding,
        dimension_numbers=("NDHWC", "DHWIO", "NDHWC"))


op_registry.register_pure("Conv3D", _conv3d_impl)


def _conv_transpose_impl(x, w, output_shape, spatial_strides, padding,
                         dim_nums):
    """Transposed conv. Without output_shape: lax.conv_transpose (SAME
    stride-s output = in*s). WITH output_shape, sizes like in*s-1 are
    ambiguous inverses and the pad split differs by parity — so compute
    it definitionally as the vjp of the FORWARD conv over an
    output_shape-sized input (XLA folds the vjp into one conv). TF
    transpose filter layout (…,OUT,IN) read as the fwd conv's I=OUT,
    O=IN filter."""
    if output_shape is None:
        out = jax.lax.conv_transpose(
            x, w, strides=spatial_strides, padding=padding,
            dimension_numbers=dim_nums, transpose_kernel=True)
        return out.astype(x.dtype)
    output_shape = builtins.tuple(int(d) for d in output_shape)

    def fwd(y):
        return jax.lax.conv_general_dilated(
            y, w, window_strides=spatial_strides, padding=padding,
            dimension_numbers=dim_nums)

    primal = jnp.zeros(output_shape, x.dtype)
    out_aval = jax.eval_shape(fwd, primal)
    if out_aval.shape != x.shape:
        raise ValueError(
            f"conv transpose: output_shape {output_shape} is inconsistent "
            f"— the forward conv would produce {out_aval.shape}, but the "
            f"input has shape {x.shape}")
    _, vjp = jax.vjp(fwd, primal)
    (dx,) = vjp(x)
    return dx.astype(x.dtype)


def _conv2d_transpose_impl(x, w, output_shape=None, strides=(1, 1, 1, 1),
                           padding="SAME"):
    return _conv_transpose_impl(
        x, w, output_shape, builtins.tuple(strides[1:3]), padding,
        ("NHWC", "HWIO", "NHWC"))


op_registry.register_pure("Conv2DBackpropInput", _conv2d_transpose_impl)


def _conv3d_transpose_impl(x, w, output_shape=None,
                           strides=(1, 1, 1, 1, 1), padding="SAME"):
    return _conv_transpose_impl(
        x, w, output_shape, builtins.tuple(strides[1:4]), padding,
        ("NDHWC", "DHWIO", "NDHWC"))


op_registry.register_pure("Conv3DBackpropInput", _conv3d_transpose_impl)


def _dilation2d_impl(x, f, strides=(1, 1, 1, 1), rates=(1, 1, 1, 1),
                     padding="SAME"):
    """Grayscale morphological dilation (ref core/kernels/dilation_ops.cc):
    out[b,y,x,c] = max_{i,j}( in[b, y*s+i*r, x*s+j*r, c] + f[i,j,c] ).

    The additive filter makes this not a plain reduce_window; for the
    small morphology kernels it lowers to kh*kw shifted adds + a max
    tree — all static slices, VPU-friendly."""
    kh, kw, _ = f.shape
    sh, sw = builtins.tuple(strides[1:3])
    rh, rw = builtins.tuple(rates[1:3])
    eh, ew = (kh - 1) * rh + 1, (kw - 1) * rw + 1
    n, h, w_dim, c = x.shape
    if padding == "SAME":
        out_h = -(-h // sh)
        out_w = -(-w_dim // sw)
        pad_h = builtins.max((out_h - 1) * sh + eh - h, 0)
        pad_w = builtins.max((out_w - 1) * sw + ew - w_dim, 0)
        pt, pl = pad_h // 2, pad_w // 2
        pb, pr = pad_h - pt, pad_w - pl
    else:
        out_h = (h - eh) // sh + 1
        out_w = (w_dim - ew) // sw + 1
        pt = pl = pb = pr = 0
    # Padded taps are EXCLUDED via a validity mask, not an additive
    # sentinel: adding f to a signed iinfo.min wraps around and a uint
    # "min" of 0 is not neutral — both would corrupt border outputs.
    sentinel = (-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                else jnp.iinfo(x.dtype).min)
    xp = jnp.pad(x, ((0, 0), (pt, pb), (pl, pr), (0, 0)))
    valid = jnp.pad(jnp.ones(x.shape, bool),
                    ((0, 0), (pt, pb), (pl, pr), (0, 0)))
    res = None
    for i in builtins.range(kh):
        for j in builtins.range(kw):
            limits = (n, i * rh + (out_h - 1) * sh + 1,
                      j * rw + (out_w - 1) * sw + 1, c)
            sl = jax.lax.slice(xp, (0, i * rh, j * rw, 0), limits,
                               (1, sh, sw, 1))
            vl = jax.lax.slice(valid, (0, i * rh, j * rw, 0), limits,
                               (1, sh, sw, 1))
            cand = jnp.where(vl, sl + f[i, j, :], sentinel)
            res = cand if res is None else jnp.maximum(res, cand)
    return res


def _erosion2d_impl(x, f, strides=(1, 1, 1, 1), rates=(1, 1, 1, 1),
                    padding="SAME"):
    """erosion2d(v, k) == -dilation2d(-v, flip(k)) (the reference's
    documented duality, ref python/ops/nn_ops.py erosion2d). The duality
    needs a signed domain: unsigned inputs compute in f32 (exact for
    values < 2^24) and cast back."""
    orig = x.dtype
    if jnp.issubdtype(orig, jnp.unsignedinteger):
        x = x.astype(jnp.float32)
        f = f.astype(jnp.float32)
    out = -_dilation2d_impl(-x, jnp.flip(f, axis=(0, 1)),
                            strides=strides, rates=rates, padding=padding)
    return out.astype(orig)


op_registry.register_pure("Dilation2D", _dilation2d_impl)
op_registry.register_pure("Erosion2D", _erosion2d_impl)


def _pool(x, ksize, strides, padding, reducer, init, data_format="NHWC"):
    if data_format == "NCHW":
        x = jnp.transpose(x, (0, 2, 3, 1))
        ksize = (ksize[0], ksize[2], ksize[3], ksize[1])
        strides = (strides[0], strides[2], strides[3], strides[1])
    out = jax.lax.reduce_window(x, init, reducer, tuple(ksize),
                                tuple(strides), padding)
    if data_format == "NCHW":
        out = jnp.transpose(out, (0, 3, 1, 2))
    return out


def _max_pool_impl(x, ksize=None, strides=None, padding="VALID",
                   data_format="NHWC"):
    return _pool(x, ksize, strides, padding, jax.lax.max,
                 -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                 else jnp.iinfo(x.dtype).min, data_format)


def _avg_pool_impl(x, ksize=None, strides=None, padding="VALID",
                   data_format="NHWC"):
    summed = _pool(x.astype(jnp.float32), ksize, strides, padding,
                   jax.lax.add, 0.0, data_format)
    ones = jnp.ones_like(x, dtype=jnp.float32)
    counts = _pool(ones, ksize, strides, padding, jax.lax.add, 0.0, data_format)
    return (summed / counts).astype(x.dtype)


op_registry.register_pure("MaxPool", _max_pool_impl)
op_registry.register_pure("AvgPool", _avg_pool_impl)
op_registry.register_pure("MaxPool3D", lambda x, ksize=None, strides=None,
                          padding="VALID": jax.lax.reduce_window(
                              x, -jnp.inf, jax.lax.max, tuple(ksize),
                              tuple(strides), padding))
op_registry.register_pure("AvgPool3D", lambda x, ksize=None, strides=None,
                          padding="VALID": jax.lax.reduce_window(
                              x.astype(jnp.float32), 0.0, jax.lax.add,
                              tuple(ksize), tuple(strides), padding) /
                          jax.lax.reduce_window(
                              jnp.ones_like(x, dtype=jnp.float32), 0.0,
                              jax.lax.add, tuple(ksize), tuple(strides),
                              padding))


def _lrn_impl(x, depth_radius=5, bias=1.0, alpha=1.0, beta=0.5):
    squares = jnp.square(x.astype(jnp.float32))
    c = x.shape[-1]
    pad = jnp.pad(squares, [(0, 0)] * (x.ndim - 1) + [(depth_radius, depth_radius)])
    windows = [pad[..., i:i + c] for i in builtins.range(2 * depth_radius + 1)]
    norm = bias + alpha * builtins.sum(windows[1:], windows[0])
    return (x.astype(jnp.float32) / jnp.power(norm, beta)).astype(x.dtype)


op_registry.register_pure("LRN", _lrn_impl)


def _dropout_lower(ctx, op, inputs):
    x = inputs[0]
    keep_prob = op.attrs["keep_prob"]
    if keep_prob is None:  # tensor keep_prob (train/eval via placeholder)
        keep_prob = inputs[1]
    key = ctx.rng_for(op)
    noise_shape = op.attrs.get("noise_shape") or x.shape
    u = jax.random.uniform(key, builtins.tuple(noise_shape), dtype=jnp.float32)
    mask = u < keep_prob  # broadcast against x (noise_shape semantics)
    kp = jnp.asarray(keep_prob, x.dtype)
    return [jnp.where(mask, x / kp, jnp.zeros_like(x))]


op_registry.register("Dropout", lower=_dropout_lower,
                     effects=op_registry.Effects(rng=True))

op_registry.register_pure("InTopK", lambda predictions, targets, k=1:
                          _in_top_k_impl(predictions, targets, k))


def _in_top_k_impl(predictions, targets, k):
    target_scores = jnp.take_along_axis(
        predictions, targets[:, None].astype(jnp.int32), axis=1)[:, 0]
    higher = jnp.sum((predictions > target_scores[:, None]).astype(jnp.int32),
                     axis=1)
    finite = jnp.isfinite(target_scores)
    return jnp.logical_and(higher < k, finite)


op_registry.register_pure("TopKV2", lambda x, k=1, sorted=True:
                          list(jax.lax.top_k(x, k)), n_outputs=2)


# -- public API --------------------------------------------------------------

def relu(features, name=None):
    return unary("Relu", features, name)


def relu6(features, name=None):
    return unary("Relu6", features, name)


def elu(features, name=None):
    return unary("Elu", features, name)


def selu(features, name=None):
    return unary("Selu", features, name)


def gelu(features, approximate=True, name=None):
    return unary("Gelu", features, name, attrs={"approximate": approximate})


def crelu(features, axis=-1, name=None):
    """(ref: nn_ops.py ``crelu``): concat(relu(x), relu(-x))."""
    from . import array_ops
    from . import math_ops

    x = ops_mod.convert_to_tensor(features)
    with ops_mod.name_scope(name or "CRelu"):
        return array_ops.concat([relu(x), relu(math_ops.negative(x))],
                                axis=axis)


def leaky_relu(features, alpha=0.2, name=None):
    return unary("LeakyRelu", features, name, attrs={"alpha": alpha})


def swish(features, name=None):
    return unary("Swish", features, name)


silu = swish


def softplus(features, name=None):
    return unary("Softplus", features, name)


def softsign(features, name=None):
    return unary("Softsign", features, name)


def softmax(logits, axis=-1, name=None, dim=None):
    if dim is not None:
        axis = dim
    return unary("Softmax", logits, name, attrs={"axis": int(axis)})


def log_softmax(logits, axis=-1, name=None, dim=None):
    if dim is not None:
        axis = dim
    return unary("LogSoftmax", logits, name, attrs={"axis": int(axis)})


def l2_loss(t, name=None):
    return unary("L2Loss", t, name)


def bias_add(value, bias, data_format="NHWC", name=None):
    value = ops_mod.convert_to_tensor(value)
    bias = ops_mod.convert_to_tensor(bias, dtype=value.dtype.base_dtype)
    return make_op("BiasAdd", [value, bias],
                   attrs={"data_format": data_format or "NHWC"}, name=name)


def softmax_cross_entropy_with_logits(labels=None, logits=None, dim=-1,
                                      name=None, _sentinel=None):
    if _sentinel is not None:
        raise ValueError("Use named arguments for "
                         "softmax_cross_entropy_with_logits")
    logits = ops_mod.convert_to_tensor(logits)
    labels = ops_mod.convert_to_tensor(labels, dtype=logits.dtype.base_dtype)
    return make_op("SoftmaxCrossEntropyWithLogits", [logits, labels], name=name)


softmax_cross_entropy_with_logits_v2 = softmax_cross_entropy_with_logits


def sparse_softmax_cross_entropy_with_logits(labels=None, logits=None,
                                             name=None, _sentinel=None):
    logits = ops_mod.convert_to_tensor(logits)
    labels = ops_mod.convert_to_tensor(labels)
    if not labels.dtype.is_integer:
        raise TypeError("labels must be integer class ids")
    return make_op("SparseSoftmaxCrossEntropyWithLogits", [logits, labels],
                   name=name)


def sigmoid_cross_entropy_with_logits(labels=None, logits=None, name=None,
                                      _sentinel=None):
    logits = ops_mod.convert_to_tensor(logits)
    labels = ops_mod.convert_to_tensor(labels, dtype=logits.dtype.base_dtype)
    return make_op("SigmoidCrossEntropyWithLogits", [logits, labels], name=name)


def weighted_cross_entropy_with_logits(targets, logits, pos_weight, name=None):
    from . import math_ops

    logits = ops_mod.convert_to_tensor(logits)
    targets = ops_mod.convert_to_tensor(targets, dtype=logits.dtype.base_dtype)
    log_weight = 1 + (pos_weight - 1) * targets
    return math_ops.add(
        (1 - targets) * logits,
        log_weight * (math_ops.log1p(math_ops.exp(-math_ops.abs(logits))) +
                      relu(-logits)), name=name)


def conv2d(input, filter=None, strides=None, padding=None, use_cudnn_on_gpu=True,  # noqa: A002
           data_format="NHWC", dilations=None, name=None, filters=None):
    """2-D convolution (ref: nn_ops.py ``conv2d``; CUDA path
    core/kernels/conv_ops.cc) → lax.conv_general_dilated on the MXU."""
    w = filters if filters is not None else filter
    x = ops_mod.convert_to_tensor(input)
    w = ops_mod.convert_to_tensor(w, dtype=x.dtype.base_dtype)
    strides = strides or [1, 1, 1, 1]
    if isinstance(strides, int):
        strides = [1, strides, strides, 1]
    dilations = dilations or [1, 1, 1, 1]
    if isinstance(dilations, int):
        dilations = [1, dilations, dilations, 1]
    return make_op("Conv2D", [x, w],
                   attrs={"strides": builtins.tuple(strides),
                          "padding": padding or "SAME",
                          "data_format": data_format or "NHWC",
                          "dilations": builtins.tuple(dilations)},
                   name=name)


def depthwise_conv2d(input, filter, strides, padding, rate=None, name=None,  # noqa: A002
                     data_format="NHWC"):
    x = ops_mod.convert_to_tensor(input)
    w = ops_mod.convert_to_tensor(filter, dtype=x.dtype.base_dtype)
    dil = [1, 1, 1, 1]
    if rate is not None:
        r = rate if isinstance(rate, (list, tuple)) else [rate, rate]
        dil = [1, r[0], r[1], 1]
    return make_op("DepthwiseConv2dNative", [x, w],
                   attrs={"strides": builtins.tuple(strides),
                          "padding": padding,
                          "data_format": data_format or "NHWC",
                          "dilations": builtins.tuple(dil)},
                   name=name)


depthwise_conv2d_native = depthwise_conv2d


def separable_conv2d(input, depthwise_filter, pointwise_filter, strides,  # noqa: A002
                     padding, rate=None, name=None, data_format="NHWC"):
    dw = depthwise_conv2d(input, depthwise_filter, strides, padding, rate,
                          data_format=data_format)
    return conv2d(dw, pointwise_filter, [1, 1, 1, 1], "VALID",
                  data_format=data_format, name=name)


def conv3d(input, filter=None, strides=None, padding=None, name=None,  # noqa: A002
           filters=None):
    w = filters if filters is not None else filter
    x = ops_mod.convert_to_tensor(input)
    w = ops_mod.convert_to_tensor(w, dtype=x.dtype.base_dtype)
    return make_op("Conv3D", [x, w],
                   attrs={"strides": builtins.tuple(strides),
                          "padding": padding}, name=name)


def _static_output_shape(output_shape):
    if output_shape is None:
        return None
    if isinstance(output_shape, ops_mod.Tensor):
        from ..framework.constant_op import constant_value

        val = constant_value(output_shape)
        if val is None:
            raise NotImplementedError(
                "conv transpose needs a STATIC output_shape (XLA shapes "
                "are compile-time); pass a list/tuple or a constant")
        output_shape = val
    return builtins.tuple(int(d) for d in np.asarray(output_shape).ravel())


def conv2d_transpose(value, filter=None, output_shape=None, strides=None,  # noqa: A002
                     padding="SAME", data_format="NHWC", name=None,
                     filters=None):
    w = filters if filters is not None else filter
    x = ops_mod.convert_to_tensor(value)
    w = ops_mod.convert_to_tensor(w, dtype=x.dtype.base_dtype)
    return make_op("Conv2DBackpropInput", [x, w],
                   attrs={"strides": builtins.tuple(strides),
                          "padding": padding,
                          "output_shape": _static_output_shape(output_shape)},
                   name=name)


def atrous_conv2d(value, filters, rate, padding, name=None):
    return conv2d(value, filters, [1, 1, 1, 1], padding,
                  dilations=[1, rate, rate, 1], name=name)


def conv3d_transpose(value, filter=None, output_shape=None,  # noqa: A002
                     strides=None, padding="SAME", name=None, filters=None):
    w = filters if filters is not None else filter
    x = ops_mod.convert_to_tensor(value)
    w = ops_mod.convert_to_tensor(w, dtype=x.dtype.base_dtype)
    return make_op("Conv3DBackpropInput", [x, w],
                   attrs={"strides": builtins.tuple(strides),
                          "padding": padding,
                          "output_shape": _static_output_shape(output_shape)},
                   name=name)


def dilation2d(input, filter=None, strides=None, rates=None,  # noqa: A002
               padding="SAME", name=None, filters=None):
    """(ref: python/ops/nn_ops.py ``dilation2d``)."""
    f = filters if filters is not None else filter
    x = ops_mod.convert_to_tensor(input)
    f = ops_mod.convert_to_tensor(f, dtype=x.dtype.base_dtype)
    return make_op("Dilation2D", [x, f],
                   attrs={"strides": builtins.tuple(strides or (1, 1, 1, 1)),
                          "rates": builtins.tuple(rates or (1, 1, 1, 1)),
                          "padding": padding}, name=name)


def erosion2d(value, kernel=None, strides=None, rates=None, padding="SAME",
              name=None, filters=None):
    """(ref: python/ops/nn_ops.py ``erosion2d``)."""
    f = filters if filters is not None else kernel
    x = ops_mod.convert_to_tensor(value)
    f = ops_mod.convert_to_tensor(f, dtype=x.dtype.base_dtype)
    return make_op("Erosion2D", [x, f],
                   attrs={"strides": builtins.tuple(strides or (1, 1, 1, 1)),
                          "rates": builtins.tuple(rates or (1, 1, 1, 1)),
                          "padding": padding}, name=name)


def max_pool(value, ksize, strides, padding, data_format="NHWC", name=None):
    x = ops_mod.convert_to_tensor(value)
    return make_op("MaxPool", [x],
                   attrs={"ksize": builtins.tuple(ksize),
                          "strides": builtins.tuple(strides),
                          "padding": padding,
                          "data_format": data_format or "NHWC"}, name=name)


def avg_pool(value, ksize, strides, padding, data_format="NHWC", name=None):
    x = ops_mod.convert_to_tensor(value)
    return make_op("AvgPool", [x],
                   attrs={"ksize": builtins.tuple(ksize),
                          "strides": builtins.tuple(strides),
                          "padding": padding,
                          "data_format": data_format or "NHWC"}, name=name)


def max_pool3d(input, ksize, strides, padding, name=None):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    return make_op("MaxPool3D", [x],
                   attrs={"ksize": builtins.tuple(ksize),
                          "strides": builtins.tuple(strides),
                          "padding": padding}, name=name)


def avg_pool3d(input, ksize, strides, padding, name=None):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    return make_op("AvgPool3D", [x],
                   attrs={"ksize": builtins.tuple(ksize),
                          "strides": builtins.tuple(strides),
                          "padding": padding}, name=name)


def dropout(x, keep_prob=None, noise_shape=None, seed=None, name=None,
            rate=None):
    """(ref: nn_ops.py ``dropout``). Mask drawn from the per-step functional
    RNG; identical mask is replayed in the vjp backward."""
    x = ops_mod.convert_to_tensor(x)
    if rate is not None:
        keep_prob = 1.0 - rate if not isinstance(rate, Tensor) else 1.0 - rate
    if keep_prob is None:
        raise ValueError("dropout: pass keep_prob or rate")
    g = ops_mod.get_default_graph()
    graph_seed, op_seed = random_seed_mod.get_seed(seed)
    ns = None
    if noise_shape is not None:
        from ..framework import constant_op as _const

        if isinstance(noise_shape, Tensor):
            v = _const.constant_value(noise_shape)
            if v is None:
                raise ValueError("noise_shape must be static on TPU")
            noise_shape = v
        ns = builtins.tuple(int(d) for d in np.ravel(np.asarray(noise_shape)))
    inputs = [x]
    if isinstance(keep_prob, Tensor):
        # Placeholder keep_prob (train/eval idiom): passed as a tensor input.
        inputs.append(math_ops_cast_float(keep_prob))
        kp_attr = None
    else:
        kp_attr = float(keep_prob)
        if kp_attr == 1.0:
            return x
    op = g.create_op("Dropout", inputs,
                     attrs={"keep_prob": kp_attr, "noise_shape": ns,
                            "seed": op_seed, "_graph_seed": graph_seed},
                     name=name or "dropout",
                     output_specs=[(x.shape, x.dtype)])
    return op.outputs[0]


def math_ops_cast_float(t):
    from . import math_ops

    return math_ops.cast(t, "float32")


def local_response_normalization(input, depth_radius=5, bias=1.0, alpha=1.0,  # noqa: A002
                                 beta=0.5, name=None):
    x = ops_mod.convert_to_tensor(input)
    return make_op("LRN", [x], attrs={"depth_radius": int(depth_radius),
                                      "bias": float(bias),
                                      "alpha": float(alpha),
                                      "beta": float(beta)}, name=name)


lrn = local_response_normalization


def in_top_k(predictions, targets, k, name=None):
    p = ops_mod.convert_to_tensor(predictions)
    t = ops_mod.convert_to_tensor(targets)
    return make_op("InTopK", [p, t], attrs={"k": int(k)}, name=name)


def top_k(input, k=1, sorted=True, name=None):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    values, indices = make_op("TopKV2", [x], attrs={"k": int(k),
                                                    "sorted": sorted},
                              name=name, n_out=2)
    return values, indices


def xw_plus_b(x, weights, biases, name=None):
    from . import math_ops

    return bias_add(math_ops.matmul(x, weights), biases, name=name)


def log_poisson_loss(targets, log_input, compute_full_loss=False, name=None):
    from . import math_ops

    loss = math_ops.exp(log_input) - log_input * targets
    return loss


# -- round-4 parity fills ----------------------------------------------------

def conv1d(value, filters, stride, padding, use_cudnn_on_gpu=None,
           data_format="NHWC", name=None):
    """(ref: nn_ops.py ``conv1d``): [B, W, C] conv via a height-1 conv2d
    (exactly the reference's implementation strategy)."""
    from . import array_ops

    x = ops_mod.convert_to_tensor(value)
    w = ops_mod.convert_to_tensor(filters, dtype=x.dtype.base_dtype)
    x4 = array_ops.expand_dims(x, 1)            # [B, 1, W, C]
    w4 = array_ops.expand_dims(w, 0)            # [1, K, C, O]
    s = stride if isinstance(stride, int) else stride[1]
    out = conv2d(x4, w4, [1, 1, s, 1], padding, name=name)
    return array_ops.squeeze(out, axis=[1])


def convolution(input, filter, padding, strides=None,  # noqa: A002
                dilation_rate=None, name=None, data_format=None):
    """(ref: nn_ops.py ``convolution``): rank-dispatching wrapper."""
    x = ops_mod.convert_to_tensor(input)
    rank = x.shape.rank
    if rank == 3:
        return conv1d(x, filter, (strides or [1])[0] if strides else 1,
                      padding, name=name)
    if rank == 4:
        s = [1] + list(strides or [1, 1]) + [1]
        d = [1] + list(dilation_rate or [1, 1]) + [1]
        return conv2d(x, filter, s, padding, dilations=d, name=name)
    if rank == 5:
        s = [1] + list(strides or [1, 1, 1]) + [1]
        return conv3d(x, filter, s, padding, name=name)
    raise ValueError(f"convolution: unsupported input rank {rank}")


def atrous_conv2d_transpose(value, filters, output_shape, rate, padding,
                            name=None):
    """(ref: nn_ops.py ``atrous_conv2d_transpose``): the transpose of the
    dilated conv — lax supports rhs_dilation in the backprop, so this is
    conv2d_transpose with a dilated kernel."""
    from . import array_ops

    w = ops_mod.convert_to_tensor(filters)
    if rate > 1:
        # dilate the kernel spatially (zeros between taps)
        kh, kw = int(w.shape[0].value), int(w.shape[1].value)
        eff_h = kh + (kh - 1) * (rate - 1)
        eff_w = kw + (kw - 1) * (rate - 1)
        import numpy as _np

        from ..framework import constant_op

        idx_h = _np.arange(kh) * rate
        idx_w = _np.arange(kw) * rate
        scat = array_ops.scatter_nd(
            constant_op.constant(
                _np.stack(_np.meshgrid(idx_h, idx_w, indexing="ij"),
                          axis=-1).reshape(-1, 2).astype(_np.int32)),
            array_ops.reshape(w, [kh * kw, int(w.shape[2].value),
                                  int(w.shape[3].value)]),
            [eff_h, eff_w, int(w.shape[2].value),
             int(w.shape[3].value)])
        w = scat
    return conv2d_transpose(value, w, output_shape, [1, 1, 1, 1],
                            padding, name=name)


def conv2d_backprop_input(input_sizes, filter, out_backprop, strides,  # noqa: A002
                          padding, use_cudnn_on_gpu=None,
                          data_format="NHWC", name=None):
    """(ref: nn_ops.py ``conv2d_backprop_input``) — the raw gradient op,
    same lowering as conv2d_transpose."""
    return conv2d_transpose(out_backprop, filter,
                            output_shape=input_sizes, strides=strides,
                            padding=padding, name=name)


def conv2d_backprop_filter(input, filter_sizes, out_backprop, strides,  # noqa: A002
                           padding, use_cudnn_on_gpu=None,
                           data_format="NHWC", name=None):
    """(ref: nn_ops.py ``conv2d_backprop_filter``): derived through the
    SAME autodiff that training uses — d(conv)/d(filter) via stf.gradients
    on a throwaway conv with a zero filter of the right shape."""
    from ..framework import gradients as grads_mod
    from ..framework.constant_op import constant_value
    from . import array_ops

    fs = constant_value(ops_mod.convert_to_tensor(filter_sizes))
    if fs is None:
        raise ValueError("conv2d_backprop_filter needs static filter_sizes")
    x = ops_mod.convert_to_tensor(input)
    w0 = array_ops.zeros([int(d) for d in np.ravel(fs)],
                         dtype=x.dtype.base_dtype)
    y = conv2d(x, w0, strides, padding)
    (gw,) = grads_mod.gradients(y, [w0],
                                grad_ys=[ops_mod.convert_to_tensor(
                                    out_backprop)])
    return gw


def _max_pool_argmax_impl(x, ksize=None, strides=None, padding="VALID"):
    """Correct per-window argmax: iterate the (small, static) window
    offsets, tracking best value + FLAT input index (ref flattening
    (y*W + x)*C + c). Handles overlapping windows and SAME padding."""
    b, h, w, c = x.shape
    kh, kw = ksize[1], ksize[2]
    sy, sx = strides[1], strides[2]
    if padding.upper() == "SAME":
        oh = -(-h // sy)
        ow = -(-w // sx)
        pad_h = builtins.max((oh - 1) * sy + kh - h, 0)
        pad_w = builtins.max((ow - 1) * sx + kw - w, 0)
    else:
        oh = (h - kh) // sy + 1
        ow = (w - kw) // sx + 1
        pad_h = pad_w = 0
    neg = (jnp.asarray(-jnp.inf, x.dtype)
           if jnp.issubdtype(x.dtype, jnp.floating)
           else jnp.iinfo(x.dtype).min)
    xp = jnp.pad(x, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)),
                 constant_values=neg)
    flat = ((jnp.arange(h)[:, None, None] * w
             + jnp.arange(w)[None, :, None]) * c
            + jnp.arange(c)[None, None, :]).astype(
                dtypes_mod.narrowed_if_no_x64(dtypes_mod.int64).np_dtype)
    flat = jnp.pad(flat, ((0, pad_h), (0, pad_w), (0, 0)),
                   constant_values=-1)
    best = jnp.full((b, oh, ow, c), neg, x.dtype)
    best_idx = jnp.zeros(
        (b, oh, ow, c),
        dtypes_mod.narrowed_if_no_x64(dtypes_mod.int64).np_dtype)
    ys = jnp.arange(oh) * sy
    xs = jnp.arange(ow) * sx
    for dy in builtins.range(kh):
        for dx in builtins.range(kw):
            v = xp[:, ys + dy][:, :, xs + dx]
            fi = flat[ys + dy][:, xs + dx][None]
            take = v > best
            best = jnp.where(take, v, best)
            best_idx = jnp.where(take, fi, best_idx)
    return [best, best_idx]


op_registry.register_pure("MaxPoolWithArgmax", _max_pool_argmax_impl,
                          n_outputs=2)


def max_pool_with_argmax(input, ksize, strides, padding,  # noqa: A002
                         Targmax=None, name=None):
    """(ref: nn_ops.py ``max_pool_with_argmax``): pooled values plus the
    FLATTENED per-batch index of each max ((y*W + x)*C + c). Correct for
    overlapping windows (the argmax is tracked per window offset)."""
    from ..framework import tensor_shape as shape_mod

    x = ops_mod.convert_to_tensor(input)
    g = ops_mod.get_default_graph()
    b, h, w, c = (d.value for d in x.shape)
    kh, kw = ksize[1], ksize[2]
    sy, sx = strides[1], strides[2]
    if padding.upper() == "SAME":
        oh, ow = -(-h // sy), -(-w // sx)
    else:
        oh, ow = (h - kh) // sy + 1, (w - kw) // sx + 1
    out_shape = shape_mod.TensorShape([b, oh, ow, c])
    op = g.create_op("MaxPoolWithArgmax", [x],
                     attrs={"ksize": builtins.tuple(ksize),
                            "strides": builtins.tuple(strides),
                            "padding": padding},
                     name=name or "MaxPoolWithArgmax",
                     output_specs=[(out_shape, x.dtype),
                                   (out_shape, dtypes_mod.int64)])
    return op.outputs[0], op.outputs[1]


def _pool_v2_impl(x, window_shape=None, pooling_type="MAX",
                  padding="VALID", dilation_rate=None, strides=None):
    dil = builtins.tuple(dilation_rate or [1] * builtins.len(window_shape))
    st = builtins.tuple(strides or [1] * builtins.len(window_shape))
    wd = (1,) + builtins.tuple(window_shape) + (1,)
    ws = (1,) + st + (1,)
    wdil = (1,) + dil + (1,)
    if pooling_type.upper() == "MAX":
        init = (-jnp.inf if jnp.issubdtype(x.dtype, jnp.floating)
                else jnp.iinfo(x.dtype).min)
        return jax.lax.reduce_window(x, init, jax.lax.max, wd, ws,
                                     padding.upper(),
                                     window_dilation=wdil)
    s = jax.lax.reduce_window(x.astype(jnp.float32), 0.0, jax.lax.add,
                              wd, ws, padding.upper(),
                              window_dilation=wdil)
    ones = jnp.ones(x.shape, jnp.float32)
    n = jax.lax.reduce_window(ones, 0.0, jax.lax.add, wd, ws,
                              padding.upper(), window_dilation=wdil)
    return (s / n).astype(x.dtype)


op_registry.register_pure("PoolV2", _pool_v2_impl)


def pool(input, window_shape, pooling_type, padding, dilation_rate=None,  # noqa: A002
         strides=None, name=None, data_format=None):
    """(ref: nn_ops.py ``pool``): generic window pooling WITH dilation —
    lax.reduce_window supports window_dilation natively on TPU."""
    if pooling_type.upper() not in ("MAX", "AVG"):
        raise ValueError(f"pool: unknown pooling_type {pooling_type!r}")
    x = ops_mod.convert_to_tensor(input)
    return make_op("PoolV2", [x],
                   attrs={"window_shape": builtins.tuple(window_shape),
                          "pooling_type": pooling_type.upper(),
                          "padding": padding,
                          "dilation_rate": builtins.tuple(dilation_rate)
                          if dilation_rate else None,
                          "strides": builtins.tuple(strides)
                          if strides else None},
                   name=name)


def with_space_to_batch(input, dilation_rate, padding, op, filter_shape=None,  # noqa: A002
                        spatial_dims=None, data_format=None):
    """(ref: nn_ops.py ``with_space_to_batch``): on TPU, dilated convs are
    native (lax rhs_dilation fuses on the MXU), so the space-to-batch
    dance is unnecessary — this wrapper simply invokes ``op`` with the
    dilation folded in when it is 1, and otherwise applies the reference's
    space-to-batch -> op -> batch-to-space composition."""
    from ..framework.constant_op import constant_value
    from . import array_ops

    rate = np.asarray(constant_value(
        ops_mod.convert_to_tensor(dilation_rate)))
    if (rate == 1).all():
        return op(input, num_spatial_dims=len(rate), padding=padding)
    x = ops_mod.convert_to_tensor(input)
    # pad spatial dims up to multiples of the rate (ref computes this via
    # required_space_to_batch_paddings)
    pads = []
    for d, r in enumerate(rate.ravel()):
        dim = int(x.shape[d + 1].value)
        pads.append([0, (-dim) % int(r)])
    stb = array_ops.space_to_batch_nd(x, list(rate.ravel()), pads)
    y = op(stb, num_spatial_dims=len(rate), padding=padding)
    return array_ops.batch_to_space_nd(y, list(rate.ravel()), pads)


def _fractional_boundaries(n, ratio, seed, pseudo_random):
    """Row boundaries for fractional pooling (ref:
    core/kernels/fractional_pool_common.cc): ~n/ratio output rows with
    window sizes in {floor(ratio), ceil(ratio)}, seeded."""
    out_n = int(n / ratio)
    rng = np.random.RandomState(seed if seed else 0)
    if pseudo_random:
        # a_k = ceil(alpha*(k+u)) (ref pseudorandom sequence)
        u = rng.uniform(0, 1)
        bounds = [0]
        for k in builtins.range(1, out_n):
            bounds.append(builtins.min(int(np.ceil(ratio * (k + u))),
                                       n - 1))
        bounds.append(n)
        return bounds
    # random variant (ref default): shuffle a mix of floor/ceil window
    # sizes that sums to n
    small, big = int(np.floor(ratio)), int(np.ceil(ratio))
    n_big = n - small * out_n
    sizes = [big] * n_big + [small] * (out_n - n_big)
    rng.shuffle(sizes)
    bounds = [0]
    for s in sizes:
        bounds.append(bounds[-1] + s)
    bounds[-1] = n
    return bounds


def _fractional_pool(input, pooling_ratio, kind, pseudo_random,  # noqa: A002
                     overlapping, seed, name):
    from ..framework import constant_op
    from . import array_ops, math_ops

    x = ops_mod.convert_to_tensor(input)
    b, h, w, c = (int(d) for d in x.shape.as_list())
    rh, rw = float(pooling_ratio[1]), float(pooling_ratio[2])
    hb = _fractional_boundaries(h, rh, seed, pseudo_random)
    wb = _fractional_boundaries(w, rw, (seed or 0) + 1, pseudo_random)

    def pool_axis(t, bounds, axis):
        segs = []
        for i in builtins.range(builtins.len(bounds) - 1):
            lo = bounds[i]
            hi = bounds[i + 1] + (1 if overlapping
                                  and bounds[i + 1] < (h if axis == 1
                                                       else w) else 0)
            hi = builtins.max(hi, lo + 1)
            idx = constant_op.constant(
                np.arange(lo, hi, dtype=np.int32))
            sl = array_ops.gather(t, idx, axis=axis)
            red = (math_ops.reduce_max if kind == "max"
                   else math_ops.reduce_mean)
            segs.append(red(sl, axis=axis, keepdims=True))
        return array_ops.concat(segs, axis=axis)

    out = pool_axis(x, hb, 1)
    out = pool_axis(out, wb, 2)
    rs = constant_op.constant(np.asarray(hb, np.int64))
    cs = constant_op.constant(np.asarray(wb, np.int64))
    return out, rs, cs


def fractional_max_pool(value, pooling_ratio, pseudo_random=False,
                        overlapping=False, deterministic=False, seed=0,
                        seed2=0, name=None):
    """(ref: nn_ops.py ``fractional_max_pool``): returns (output,
    row_pooling_sequence, col_pooling_sequence)."""
    return _fractional_pool(value, pooling_ratio, "max", pseudo_random,
                            overlapping, seed, name)


def fractional_avg_pool(value, pooling_ratio, pseudo_random=False,
                        overlapping=False, deterministic=False, seed=0,
                        seed2=0, name=None):
    return _fractional_pool(value, pooling_ratio, "avg", pseudo_random,
                            overlapping, seed, name)


def _requant_range(x):
    from . import math_ops

    return math_ops.reduce_min(x), math_ops.reduce_max(x)


def quantized_conv2d(input, filter, min_input, max_input, min_filter,  # noqa: A002
                     max_filter, strides, padding, out_type=None,
                     name=None):
    """(ref: nn_ops quantized_conv2d, core/kernels/quantized_conv_ops.cc):
    dequantize -> MXU conv -> fresh range. On TPU the int8 fast path is
    the Pallas quantized_matmul (ops/fused_ops.py); this op preserves the
    reference's quantized-graph CONTRACT (value + min/max triple)."""
    from ..ops import quantization_ops as qo

    xf = qo.dequantize(input, min_input, max_input)
    wf = qo.dequantize(filter, min_filter, max_filter)
    y = conv2d(xf, wf, strides, padding, name=name)
    mn, mx = _requant_range(y)
    return y, mn, mx


def quantized_relu_x(features, max_value, min_features, max_features,
                     out_type=None, name=None):
    from ..ops import quantization_ops as qo
    from . import math_ops

    xf = qo.dequantize(features, min_features, max_features)
    y = math_ops.minimum(relu(xf),
                         ops_mod.convert_to_tensor(float(max_value)
                                                   if not isinstance(
                                                       max_value,
                                                       ops_mod.Tensor)
                                                   else max_value))
    mn, mx = _requant_range(y)
    return y, mn, mx


def quantized_max_pool(input, min_input, max_input, ksize, strides,  # noqa: A002
                       padding, name=None):
    from ..ops import quantization_ops as qo

    xf = qo.dequantize(input, min_input, max_input)
    y = max_pool(xf, ksize, strides, padding, name=name)
    mn, mx = _requant_range(y)
    return y, mn, mx


def quantized_avg_pool(input, min_input, max_input, ksize, strides,  # noqa: A002
                       padding, name=None):
    from ..ops import quantization_ops as qo

    xf = qo.dequantize(input, min_input, max_input)
    y = avg_pool(xf, ksize, strides, padding, name=name)
    mn, mx = _requant_range(y)
    return y, mn, mx


def _backprop_filter_via_autodiff(conv_fn, input, filter_sizes,  # noqa: A002
                                  out_backprop, strides, padding):
    from ..framework import gradients as grads_mod
    from ..framework.constant_op import constant_value
    from . import array_ops

    fs = constant_value(ops_mod.convert_to_tensor(filter_sizes))
    if fs is None:
        raise ValueError("backprop_filter needs static filter_sizes")
    x = ops_mod.convert_to_tensor(input)
    w0 = array_ops.zeros([int(d) for d in np.ravel(fs)],
                         dtype=x.dtype.base_dtype)
    y = conv_fn(x, w0, strides, padding)
    (gw,) = grads_mod.gradients(
        y, [w0], grad_ys=[ops_mod.convert_to_tensor(out_backprop)])
    return gw


def conv3d_backprop_filter_v2(input, filter_sizes, out_backprop, strides,  # noqa: A002
                              padding, data_format="NDHWC", name=None):
    """(ref: nn.py ``conv3d_backprop_filter_v2``): derived through the
    same autodiff training uses."""
    return _backprop_filter_via_autodiff(
        lambda x, w, s, p: conv3d(x, w, s, p), input, filter_sizes,
        out_backprop, strides, padding)


def depthwise_conv2d_native_backprop_filter(input, filter_sizes,  # noqa: A002
                                            out_backprop, strides, padding,
                                            data_format="NHWC", name=None):
    return _backprop_filter_via_autodiff(
        lambda x, w, s, p: depthwise_conv2d(x, w, s, p), input,
        filter_sizes, out_backprop, strides, padding)


def depthwise_conv2d_native_backprop_input(input_sizes, filter,  # noqa: A002
                                           out_backprop, strides, padding,
                                           data_format="NHWC", name=None):
    from ..framework import gradients as grads_mod
    from ..framework.constant_op import constant_value
    from . import array_ops

    xs = constant_value(ops_mod.convert_to_tensor(input_sizes))
    if xs is None:
        raise ValueError("backprop_input needs static input_sizes")
    w = ops_mod.convert_to_tensor(filter)
    x0 = array_ops.zeros([int(d) for d in np.ravel(xs)],
                         dtype=w.dtype.base_dtype)
    y = depthwise_conv2d(x0, w, strides, padding)
    (gx,) = grads_mod.gradients(
        y, [x0], grad_ys=[ops_mod.convert_to_tensor(out_backprop)])
    return gx


# ---------------------------------------------------------------------------
# sharding propagation rules (stf.analysis.sharding; ISSUE 6)
# ---------------------------------------------------------------------------

from ..analysis import sharding as _shard  # noqa: E402

_shard.register_rules(_shard.elementwise_rule,
                      "Relu", "Relu6", "Elu", "Selu", "Gelu", "LeakyRelu",
                      "Swish")
_shard.register_rules(_shard.make_softmax_rule("axis"),
                      "Softmax", "LogSoftmax")
_shard.register_rules(_shard.make_last_dim_reduce_rule(),
                      "SoftmaxCrossEntropyWithLogits",
                      "SparseSoftmaxCrossEntropyWithLogits", "InTopK")
_shard.register_rules(_shard.make_conv_rule(2),
                      "Conv2D", "DepthwiseConv2dNative", "Conv2DBackpropInput",
                      "Dilation2D", "Erosion2D")
_shard.register_rules(_shard.make_conv_rule(3), "Conv3D",
                      "Conv3DBackpropInput")
_shard.register_rules(_shard.make_pool_rule(),
                      "MaxPool", "AvgPool", "MaxPool3D", "AvgPool3D",
                      "LRN", "PoolV2", "MaxPoolWithArgmax")
_shard.register_rules(_shard.passthrough_rule, "Dropout")
_shard.register_rules(_shard.make_axis_unsharded_rule("axis", -1),
                      "TopKV2")


def _biasadd_rule(op, in_specs, ctx):
    # the bias aligns with the channel dim (last, or dim 1 under NCHW)
    sx, sb = in_specs[0], in_specs[1] if len(in_specs) > 1 else None
    if sx is None:
        return [None]
    chan = 1 if op.attrs.get("data_format") == "NCHW" else len(sx) - 1
    out = list(sx)
    if sb is not None and len(sb) == 1:
        if sb[0] and not out[chan]:
            out[chan] = sb[0]
        elif sb[0] != out[chan]:
            ctx.require(1, (out[chan],))
    return [_shard._dedupe_axes(tuple(out))]


_shard.register_rules(_biasadd_rule, "BiasAdd")
