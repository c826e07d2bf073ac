"""Array ops (ref: tensorflow/python/ops/array_ops.py, core/kernels/
{concat_op,slice_op,strided_slice_op,pack_op,pad_op,gather_op,one_hot_op,...}.cc).

TPU notes: everything here must keep static shapes for XLA. Ops whose result
shape is data-dependent in the reference (boolean_mask, unique, where with
one arg) are supported only with statically-determinable sizes and raise
actionable errors otherwise — the reference's dynamic-shape behavior does not
exist on TPU hardware either (tf2xla has the same restriction).
"""

from __future__ import annotations

import builtins
import numpy as np

import jax
import jax.numpy as jnp

from ..framework import constant_op
from ..framework import dtypes as dtypes_mod
from ..framework import graph as ops_mod
from ..framework import op_registry
from ..framework import tensor_shape as shape_mod
from .op_util import make_op, unary

Tensor = ops_mod.Tensor
constant = constant_op.constant


# -- registrations -----------------------------------------------------------

op_registry.register_pure("Identity", lambda x: x)
op_registry.register_pure("Snapshot", lambda x: x)
# 64-bit out_types narrow through narrowed_if_no_x64 (one boundary
# warning per process instead of jax's per-callsite truncation warning;
# docs/MIGRATION.md "64-bit dtypes")
op_registry.register_pure("Shape", lambda x, out_type=None: jnp.asarray(
    x.shape, dtype=(dtypes_mod.narrowed_if_no_x64(out_type).np_dtype
                    if out_type else jnp.int32)))
op_registry.register_pure("Size", lambda x, out_type=None: jnp.asarray(
    x.size, dtype=(dtypes_mod.narrowed_if_no_x64(out_type).np_dtype
                   if out_type else jnp.int32)))
op_registry.register_pure("Rank", lambda x: jnp.asarray(x.ndim, dtype=jnp.int32))
op_registry.register_pure("Reshape", lambda x, shape: jnp.reshape(x, shape))
op_registry.register_pure("Transpose", lambda x, perm=None: jnp.transpose(x, perm))
op_registry.register_pure("ConjugateTranspose",
                          lambda x, perm=None: jnp.conj(jnp.transpose(x, perm)))
op_registry.register_pure("ExpandDims", lambda x, axis: jnp.expand_dims(x, axis))
op_registry.register_pure("Squeeze", lambda x, axis=None: jnp.squeeze(x, axis))
op_registry.register_pure("Fill", lambda value, dims=None: jnp.full(dims, value))
op_registry.register_pure("ZerosLike", lambda x: jnp.zeros_like(x))
op_registry.register_pure("OnesLike", lambda x: jnp.ones_like(x))
op_registry.register_pure("Concat", lambda *xs, axis: jnp.concatenate(xs, axis=axis))
op_registry.register_pure("Split", lambda x, num_or_sections, axis=0:
                          jnp.split(x, num_or_sections, axis=axis),
                          n_outputs=None)
op_registry.register_pure("Pack", lambda *xs, axis=0: jnp.stack(xs, axis=axis))
op_registry.register_pure("Unpack", lambda x, num, axis=0:
                          [jnp.squeeze(s, axis) for s in
                           jnp.split(x, num, axis=axis)], n_outputs=None)
op_registry.register_pure(
    "Pad", lambda x, paddings=None, mode="constant", constant_values=0:
    jnp.pad(x, paddings, mode=mode,
            **({"constant_values": constant_values} if mode == "constant" else {})))
op_registry.register_pure("Tile", lambda x, multiples: jnp.tile(x, multiples))
op_registry.register_pure("Slice", lambda x, begin=None, size=None:
                          jax.lax.slice(x, begin,
                                        [b + s for b, s in zip(begin, size)]))
op_registry.register_pure("Gather", lambda params, indices, axis=0:
                          jnp.take(params, indices, axis=axis))
op_registry.register_pure("GatherNd", lambda params, indices: params[
    tuple(indices[..., k] for k in builtins.range(indices.shape[-1]))])
op_registry.register_pure("ScatterNd", lambda indices, updates, shape=None:
                          jnp.zeros(shape, updates.dtype).at[
                              tuple(indices[..., k]
                                    for k in builtins.range(indices.shape[-1]))
                          ].add(updates))
op_registry.register_pure("OneHot", lambda indices, depth=None, on_value=1.0,
                          off_value=0.0, axis=-1, dtype=None:
                          _one_hot_impl(indices, depth, on_value, off_value,
                                        axis, dtype))
op_registry.register_pure("Select", lambda cond, x, y: jnp.where(cond, x, y))
op_registry.register_pure("Reverse", lambda x, axis: jnp.flip(x, axis))
op_registry.register_pure("ReverseSequence",
                          lambda x, seq_lengths, seq_axis=0, batch_axis=0:
                          _reverse_sequence_impl(x, seq_lengths, seq_axis,
                                                 batch_axis))
op_registry.register_pure("MatrixDiag", lambda x: _batched_diag(x))
op_registry.register_pure("MatrixDiagPart",
                          lambda x: jnp.diagonal(x, axis1=-2, axis2=-1))
op_registry.register_pure("MatrixSetDiag", lambda x, diag: _set_diag(x, diag))
op_registry.register_pure("MatrixBandPart",
                          lambda x, num_lower=-1, num_upper=-1:
                          _band_part(x, num_lower, num_upper))
op_registry.register_pure("Diag", lambda x: _tensor_diag(x))
op_registry.register_pure("DiagPart", lambda x: _tensor_diag_part(x))
op_registry.register_pure("InvertPermutation",
                          lambda x: jnp.zeros_like(x).at[x].set(
                              jnp.arange(x.shape[0], dtype=x.dtype)))
op_registry.register_pure("StopGradient", jax.lax.stop_gradient)
op_registry.register_pure("PreventGradient", jax.lax.stop_gradient)
op_registry.register("CheckNumerics",
                     lower=lambda ctx, op, inputs:
                     [_check_numerics_impl(ctx, op, inputs[0])],
                     infer_fn=lambda g, attrs, ins: [(ins[0].shape,
                                                      ins[0].dtype)])
op_registry.register_pure("StridedSlice", lambda x, *dyn, spec: _strided_impl(
    x, dyn, spec))
op_registry.register_pure("BroadcastTo", lambda x, shape: jnp.broadcast_to(x, shape))
op_registry.register_pure("BroadcastArgs", lambda s0, s1: jnp.asarray(
    np.broadcast_shapes(tuple(np.asarray(s0)), tuple(np.asarray(s1))),
    dtype=jnp.int32))
op_registry.register_pure("SpaceToBatchND", lambda x, block_shape, paddings:
                          _space_to_batch_nd(x, block_shape, paddings))
op_registry.register_pure("BatchToSpaceND", lambda x, block_shape, crops:
                          _batch_to_space_nd(x, block_shape, crops))
op_registry.register_pure("SpaceToDepth", lambda x, block_size:
                          _space_to_depth(x, block_size))
op_registry.register_pure("DepthToSpace", lambda x, block_size:
                          _depth_to_space(x, block_size))
op_registry.register_pure("ExtractImagePatches",
                          lambda x, ksizes, strides, rates, padding:
                          _extract_patches(x, ksizes, strides, rates, padding))
op_registry.register_pure("SequenceMask", lambda lengths, maxlen=None, dtype=None:
                          (jnp.arange(maxlen)[None, :] <
                           lengths[..., None]).astype(
                               dtype.np_dtype if dtype else jnp.bool_))


def _one_hot_impl(indices, depth, on_value, off_value, axis, dtype):
    np_dt = dtype.np_dtype if dtype is not None else jnp.float32
    oh = jax.nn.one_hot(indices, depth, axis=axis, dtype=np_dt)
    if on_value != 1.0 or off_value != 0.0:
        oh = oh * (on_value - off_value) + off_value
    return oh.astype(np_dt)


def _reverse_sequence_impl(x, seq_lengths, seq_axis, batch_axis):
    idx = jnp.arange(x.shape[seq_axis])
    # for each batch b: positions i < len reversed: len-1-i else i
    def fix(b_len):
        return jnp.where(idx < b_len, b_len - 1 - idx, idx)

    rev_idx = jax.vmap(fix)(seq_lengths)  # [B, T]
    x_m = jnp.moveaxis(x, (batch_axis, seq_axis), (0, 1))
    out = jax.vmap(lambda xb, ib: jnp.take(xb, ib, axis=0))(x_m, rev_idx)
    return jnp.moveaxis(out, (0, 1), (batch_axis, seq_axis))


def _batched_diag(x):
    eye = jnp.eye(x.shape[-1], dtype=x.dtype)
    return x[..., None] * eye


def _set_diag(x, diag):
    n = builtins.min(x.shape[-2], x.shape[-1])
    eye = jnp.eye(x.shape[-2], x.shape[-1], dtype=bool)
    d = _batched_diag(diag)
    pad = [(0, 0)] * diag.ndim + [(0, x.shape[-1] - diag.shape[-1])]
    dfull = jnp.zeros_like(x).at[..., :n, :n].set(d[..., :n, :n])
    return jnp.where(eye, dfull, x)


def _band_part(x, num_lower, num_upper):
    m, n = x.shape[-2], x.shape[-1]
    i = jnp.arange(m)[:, None]
    j = jnp.arange(n)[None, :]
    keep = jnp.ones((m, n), dtype=bool)
    if num_lower >= 0:
        keep &= (i - j) <= num_lower
    if num_upper >= 0:
        keep &= (j - i) <= num_upper
    return jnp.where(keep, x, jnp.zeros_like(x))


def _tensor_diag(x):
    flat = jnp.ravel(x)
    out = jnp.zeros((flat.size, flat.size), dtype=x.dtype).at[
        jnp.arange(flat.size), jnp.arange(flat.size)].set(flat)
    return jnp.reshape(out, x.shape + x.shape)


def _tensor_diag_part(x):
    k = x.ndim // 2
    lead = x.shape[:k]
    n = int(np.prod(lead))
    flat = jnp.reshape(x, (n, n))
    return jnp.reshape(jnp.diagonal(flat), lead)


def _check_numerics_impl(ctx, op, x):
    # In-graph numeric check (ref core/kernels/check_numerics_op.cc).
    # TPU-native: a hard device assert would stall the pipeline, so the
    # non-finite flag is computed in the compiled step (fuses with the
    # producer) and fetched with the results; the Session raises
    # InvalidArgumentError host-side when a flag is set. Inside lax control
    # flow / shard_map the flag cannot escape the trace — the check is a
    # pass-through there (matches XLA's structured-control-flow limits).
    message = op.attrs.get("message", "")
    if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        return x
    if ctx.host:
        if not np.all(np.isfinite(np.asarray(x, np.float64))):
            from ..framework import errors

            raise errors.InvalidArgumentError(
                None, op, f"{message} : Tensor had NaN/Inf values")
        return x
    if ctx.in_control_flow or ctx.in_shard_map:
        return x
    flag = jnp.logical_not(jnp.all(jnp.isfinite(x)))
    ctx.numeric_checks.append(
        (f"CheckNumerics {op.name}: {message}: Tensor had NaN/Inf "
         "values", flag))
    return x


def _strided_impl(x, dyn_inputs, spec):
    idx = []
    di = iter(dyn_inputs)
    for item in spec:
        kind = item[0]
        if kind == "idx":
            idx.append(item[1])
        elif kind == "tensor_idx":
            idx.append(next(di))
        elif kind == "slice":
            idx.append(builtins.slice(item[1], item[2], item[3]))
        elif kind == "newaxis":
            idx.append(None)
        elif kind == "ellipsis":
            idx.append(Ellipsis)
    return x[tuple(idx)]


def _space_to_batch_nd(x, block_shape, paddings):
    block_shape = list(block_shape)
    pads = [(0, 0)] + [tuple(p) for p in paddings] + [(0, 0)]
    x = jnp.pad(x, pads)
    b = x.shape[0]
    spatial = x.shape[1:1 + len(block_shape)]
    rest = x.shape[1 + len(block_shape):]
    new_shape = [b]
    for s, bs in zip(spatial, block_shape):
        new_shape += [s // bs, bs]
    new_shape += rest
    x = jnp.reshape(x, new_shape)
    perm = []
    for i in builtins.range(len(block_shape)):
        perm.append(2 + 2 * i)
    perm.append(0)
    for i in builtins.range(len(block_shape)):
        perm.append(1 + 2 * i)
    perm += [len(new_shape) - len(rest) + i for i in builtins.range(len(rest))]
    x = jnp.transpose(x, perm)
    out_b = b * int(np.prod(block_shape))
    out_spatial = [s // bs for s, bs in zip(spatial, block_shape)]
    return jnp.reshape(x, [out_b] + out_spatial + list(rest))


def _batch_to_space_nd(x, block_shape, crops):
    block_shape = list(block_shape)
    prod_b = int(np.prod(block_shape))
    b = x.shape[0] // prod_b
    spatial = x.shape[1:1 + len(block_shape)]
    rest = x.shape[1 + len(block_shape):]
    x = jnp.reshape(x, block_shape + [b] + list(spatial) + list(rest))
    nb = len(block_shape)
    perm = [nb]
    for i in builtins.range(nb):
        perm += [nb + 1 + i, i]
    perm += [1 + 2 * nb + i for i in builtins.range(len(rest))]
    x = jnp.transpose(x, perm)
    x = jnp.reshape(x, [b] + [s * bs for s, bs in zip(spatial, block_shape)]
                    + list(rest))
    sl = [builtins.slice(None)]
    for (c0, c1), s, bs in zip([tuple(c) for c in crops], spatial, block_shape):
        sl.append(builtins.slice(c0, s * bs - c1))
    sl += [builtins.slice(None)] * len(rest)
    return x[tuple(sl)]


def _space_to_depth(x, bs):
    b, h, w, c = x.shape
    x = jnp.reshape(x, (b, h // bs, bs, w // bs, bs, c))
    x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
    return jnp.reshape(x, (b, h // bs, w // bs, bs * bs * c))


def _depth_to_space(x, bs):
    b, h, w, c = x.shape
    x = jnp.reshape(x, (b, h, w, bs, bs, c // (bs * bs)))
    x = jnp.transpose(x, (0, 1, 3, 2, 4, 5))
    return jnp.reshape(x, (b, h * bs, w * bs, c // (bs * bs)))


def _extract_patches(x, ksizes, strides, rates, padding):
    _, kh, kw, _ = ksizes
    _, sh, sw, _ = strides
    _, rh, rw, _ = rates
    b, h, w, c = x.shape
    patches = jax.lax.conv_general_dilated_patches(
        jnp.moveaxis(x, -1, 1), (kh, kw), (sh, sw), padding,
        rhs_dilation=(rh, rw))
    # patches: [B, C*kh*kw, H', W'] with channel-major ordering -> TF wants
    # [B, H', W', kh*kw*C] with patch-major ordering.
    bp, ck, hp, wp = patches.shape
    patches = jnp.reshape(patches, (bp, c, kh * kw, hp, wp))
    patches = jnp.transpose(patches, (0, 3, 4, 2, 1))
    return jnp.reshape(patches, (bp, hp, wp, kh * kw * c))


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def placeholder(dtype, shape=None, name=None):
    """(ref: python/ops/array_ops.py:1620 ``placeholder``)."""
    g = ops_mod.get_default_graph()
    dt = dtypes_mod.as_dtype(dtype)
    sh = shape_mod.as_shape(shape) if shape is not None else shape_mod.TensorShape(None)
    op = g.create_op("Placeholder", [], attrs={"dtype": dt, "shape": sh},
                     name=name or "Placeholder",
                     output_specs=[(sh, dt)])
    return op.outputs[0]


def placeholder_with_default(input, shape, name=None):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    op = ops_mod.get_default_graph().create_op(
        "PlaceholderWithDefault", [x], attrs={},
        name=name or "PlaceholderWithDefault",
        output_specs=[(shape_mod.as_shape(shape), x.dtype)])
    return op.outputs[0]


def identity(input, name=None):  # noqa: A002
    return unary("Identity", input, name)


def stop_gradient(input, name=None):  # noqa: A002
    return unary("StopGradient", input, name)


def prevent_gradient(input, message="", name=None):  # noqa: A002
    return unary("PreventGradient", input, name)


def check_numerics(tensor, message="", name=None):
    return unary("CheckNumerics", tensor, name, attrs={"message": message})


def shape(input, name=None, out_type=dtypes_mod.int32):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    return make_op("Shape", [x],
                   attrs={"out_type": dtypes_mod.as_dtype(out_type)}, name=name)


def shape_n(inputs, out_type=dtypes_mod.int32, name=None):
    return [shape(x, out_type=out_type) for x in inputs]


def size(input, name=None, out_type=dtypes_mod.int32):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    return make_op("Size", [x],
                   attrs={"out_type": dtypes_mod.as_dtype(out_type)}, name=name)


def rank(input, name=None):  # noqa: A002
    return unary("Rank", input, name)


def reshape(tensor, shape, name=None):  # noqa: A002
    x = ops_mod.convert_to_tensor(tensor)
    sh = _static_shape_arg(shape, "reshape")
    return make_op("Reshape", [x], attrs={"shape": sh}, name=name)


def _static_shape_arg(shape, what):
    if isinstance(shape, shape_mod.TensorShape):
        return tuple(shape.as_list())
    if isinstance(shape, Tensor):
        v = constant_op.constant_value(shape)
        if v is None:
            raise ValueError(
                f"stf.{what}: target shape must be static on TPU (XLA "
                "requires static shapes); use -1 for one inferred dim.")
        return tuple(int(d) for d in np.ravel(v))
    if isinstance(shape, (int, np.integer)):
        return (int(shape),)
    return tuple(int(d) for d in shape)


def transpose(a, perm=None, name=None, conjugate=False):
    x = ops_mod.convert_to_tensor(a)
    if perm is not None:
        perm = tuple(int(p) for p in
                     (constant_op.constant_value(perm) if isinstance(perm, Tensor)
                      else perm))
    t = "ConjugateTranspose" if conjugate and x.dtype.is_complex else "Transpose"
    return make_op(t, [x], attrs={"perm": perm}, name=name)


def matrix_transpose(a, name=None, conjugate=False):
    x = ops_mod.convert_to_tensor(a)
    r = x.shape.rank
    if r is None:
        raise ValueError("matrix_transpose needs known rank")
    perm = tuple(builtins.range(r - 2)) + (r - 1, r - 2)
    return transpose(x, perm, name=name, conjugate=conjugate)


def expand_dims(input, axis=None, name=None, dim=None):  # noqa: A002
    if dim is not None and axis is None:
        axis = dim
    x = ops_mod.convert_to_tensor(input)
    return make_op("ExpandDims", [x], attrs={"axis": int(axis)}, name=name)


def squeeze(input, axis=None, name=None, squeeze_dims=None):  # noqa: A002
    if squeeze_dims is not None and axis is None:
        axis = squeeze_dims
    x = ops_mod.convert_to_tensor(input)
    if axis is not None and not isinstance(axis, (list, tuple)):
        axis = [axis]
    return make_op("Squeeze", [x],
                   attrs={"axis": tuple(int(a) for a in axis) if axis is not None
                          else None}, name=name)


def zeros(shape, dtype=dtypes_mod.float32, name=None):
    dt = dtypes_mod.as_dtype(dtype)
    sh = _static_shape_arg(shape, "zeros")
    return constant(np.zeros(sh, dtype=dt.np_dtype), name=name or "zeros")


def ones(shape, dtype=dtypes_mod.float32, name=None):
    dt = dtypes_mod.as_dtype(dtype)
    sh = _static_shape_arg(shape, "ones")
    return constant(np.ones(sh, dtype=dt.np_dtype), name=name or "ones")


def fill(dims, value, name=None):
    sh = _static_shape_arg(dims, "fill")
    v = ops_mod.convert_to_tensor(value)
    return make_op("Fill", [v],
                   attrs={"dims": sh},
                   name=name)


op_registry._REGISTRY.pop("Fill", None)
op_registry.register_pure("Fill", lambda value, dims=None: jnp.full(
    dims, value))


def zeros_like(tensor, dtype=None, name=None, optimize=True):
    x = ops_mod.convert_to_tensor(tensor)
    out = unary("ZerosLike", x, name)
    if dtype is not None and dtypes_mod.as_dtype(dtype) != x.dtype.base_dtype:
        from . import math_ops

        out = math_ops.cast(out, dtype)
    return out


def ones_like(tensor, dtype=None, name=None, optimize=True):
    x = ops_mod.convert_to_tensor(tensor)
    out = unary("OnesLike", x, name)
    if dtype is not None and dtypes_mod.as_dtype(dtype) != x.dtype.base_dtype:
        from . import math_ops

        out = math_ops.cast(out, dtype)
    return out


def concat(values, axis, name="concat"):
    if not isinstance(values, (list, tuple)):
        values = [values]
    tensors = [ops_mod.convert_to_tensor(v) for v in values]
    if len(tensors) == 1:
        return identity(tensors[0], name=name)
    if isinstance(axis, Tensor):
        axis = int(constant_op.constant_value(axis))
    return make_op("Concat", tensors, attrs={"axis": int(axis)}, name=name)


def split(value, num_or_size_splits, axis=0, num=None, name="split"):
    x = ops_mod.convert_to_tensor(value)
    if isinstance(num_or_size_splits, Tensor):
        v = constant_op.constant_value(num_or_size_splits)
        if v is None:
            raise ValueError("split sizes must be static on TPU")
        num_or_size_splits = v.tolist() if v.ndim else int(v)
    if isinstance(num_or_size_splits, (list, tuple)):
        sizes = [int(s) for s in num_or_size_splits]
        bounds = np.cumsum(sizes)[:-1].tolist()
        n_out = len(sizes)
        arg = bounds
    else:
        n_out = int(num_or_size_splits)
        arg = n_out
    return make_op("Split", [x], attrs={"num_or_sections": arg,
                                        "axis": int(axis)},
                   name=name, n_out=n_out)


def stack(values, axis=0, name="stack"):
    tensors = [ops_mod.convert_to_tensor(v) for v in values]
    return make_op("Pack", tensors, attrs={"axis": int(axis)}, name=name)


pack = stack


def unstack(value, num=None, axis=0, name="unstack"):
    x = ops_mod.convert_to_tensor(value)
    if num is None:
        if x.shape.rank is None or x.shape[axis].value is None:
            raise ValueError("Cannot infer num from shape; pass num")
        num = x.shape[axis].value
    return make_op("Unpack", [x], attrs={"num": int(num), "axis": int(axis)},
                   name=name, n_out=int(num))


unpack = unstack


def pad(tensor, paddings, mode="CONSTANT", name=None, constant_values=0):
    x = ops_mod.convert_to_tensor(tensor)
    if isinstance(paddings, Tensor):
        v = constant_op.constant_value(paddings)
        if v is None:
            raise ValueError("paddings must be static on TPU")
        paddings = v
    paddings = tuple(tuple(int(p) for p in row) for row in np.asarray(paddings))
    mode_l = {"CONSTANT": "constant", "REFLECT": "reflect",
              "SYMMETRIC": "symmetric"}[mode.upper()]
    return make_op("Pad", [x], attrs={"paddings": paddings, "mode": mode_l,
                                      "constant_values": constant_values},
                   name=name)


op_registry._REGISTRY.pop("Pad", None)
op_registry.register_pure(
    "Pad", lambda x, paddings=None, mode="constant", constant_values=0:
    jnp.pad(x, paddings, mode=mode,
            **({"constant_values": constant_values} if mode == "constant" else {})))


def tile(input, multiples, name=None):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    if isinstance(multiples, Tensor):
        v = constant_op.constant_value(multiples)
        if v is None:
            raise ValueError("multiples must be static on TPU")
        multiples = v
    return make_op("Tile", [x],
                   attrs={"multiples": tuple(int(m) for m in np.ravel(multiples))},
                   name=name)


def slice(input_, begin, size, name=None):  # noqa: A001
    x = ops_mod.convert_to_tensor(input_)
    bv = constant_op.constant_value(ops_mod.convert_to_tensor(begin))
    sv = constant_op.constant_value(ops_mod.convert_to_tensor(size))
    if bv is None or sv is None:
        raise ValueError("stf.slice begin/size must be static on TPU; "
                         "use dynamic_slice via __getitem__ with tensors.")
    begin = [int(b) for b in np.ravel(bv)]
    size = [int(s) for s in np.ravel(sv)]
    size = [x.shape[i].value - begin[i] if s == -1 else s
            for i, s in enumerate(size)]
    return make_op("Slice", [x], attrs={"begin": tuple(begin),
                                        "size": tuple(size)}, name=name)


def strided_slice(input_, begin, end, strides=None, begin_mask=0, end_mask=0,
                  ellipsis_mask=0, new_axis_mask=0, shrink_axis_mask=0,
                  name=None):
    # Reference-compatible entry; builds a python slice spec.
    bv = constant_op.constant_value(ops_mod.convert_to_tensor(begin))
    ev = constant_op.constant_value(ops_mod.convert_to_tensor(end))
    strv = (constant_op.constant_value(ops_mod.convert_to_tensor(strides))
            if strides is not None else np.ones_like(bv))
    if bv is None or ev is None or strv is None:
        raise ValueError("strided_slice bounds must be static on TPU")
    spec = []
    for i, (b, e, s) in enumerate(zip(np.ravel(bv), np.ravel(ev), np.ravel(strv))):
        if shrink_axis_mask & (1 << i):
            spec.append(("idx", int(b)))
        elif new_axis_mask & (1 << i):
            spec.append(("newaxis",))
        elif ellipsis_mask & (1 << i):
            spec.append(("ellipsis",))
        else:
            bb = None if begin_mask & (1 << i) else int(b)
            ee = None if end_mask & (1 << i) else int(e)
            spec.append(("slice", bb, ee, int(s)))
    x = ops_mod.convert_to_tensor(input_)
    return make_op("StridedSlice", [x], attrs={"spec": tuple(spec)}, name=name)


def _slice_helper(tensor, sl):
    """Tensor.__getitem__ (ref: array_ops.py:478 ``_SliceHelper``)."""
    if not isinstance(sl, tuple):
        sl = (sl,)
    spec = []
    dyn = []
    for item in sl:
        if isinstance(item, builtins.slice):
            def stat(v):
                if v is None:
                    return None
                if isinstance(v, Tensor):
                    c = constant_op.constant_value(v)
                    if c is None:
                        raise ValueError(
                            "Slice bounds must be static on TPU; for dynamic "
                            "windows use stf.gather / lax-style dynamic slice.")
                    return int(c)
                return int(v)

            spec.append(("slice", stat(item.start), stat(item.stop),
                         stat(item.step)))
        elif item is Ellipsis:
            spec.append(("ellipsis",))
        elif item is None:
            spec.append(("newaxis",))
        elif isinstance(item, Tensor):
            c = constant_op.constant_value(item)
            if c is not None and c.ndim == 0:
                spec.append(("idx", int(c)))
            else:
                spec.append(("tensor_idx",))
                dyn.append(item)
        else:
            spec.append(("idx", int(item)))
    return make_op("StridedSlice", [tensor] + dyn, attrs={"spec": tuple(spec)})


Tensor.__getitem__ = _slice_helper


def gather(params, indices, validate_indices=None, name=None, axis=0):
    from . import variables as variables_mod

    if isinstance(params, variables_mod.Variable):
        params = params._ref
    params = ops_mod.convert_to_tensor(params)
    indices = ops_mod.convert_to_tensor(indices)
    if isinstance(axis, Tensor):
        axis = int(constant_op.constant_value(axis))
    return make_op("Gather", [params, indices], attrs={"axis": int(axis)},
                   name=name)


def gather_nd(params, indices, name=None):
    params = ops_mod.convert_to_tensor(params)
    indices = ops_mod.convert_to_tensor(indices)
    return make_op("GatherNd", [params, indices], name=name)


def scatter_nd(indices, updates, shape, name=None):
    indices = ops_mod.convert_to_tensor(indices)
    updates = ops_mod.convert_to_tensor(updates)
    sh = _static_shape_arg(shape, "scatter_nd")
    return make_op("ScatterNd", [indices, updates], attrs={"shape": sh},
                   name=name)


def one_hot(indices, depth, on_value=None, off_value=None, axis=None,
            dtype=None, name=None):
    indices = ops_mod.convert_to_tensor(indices)
    if isinstance(depth, Tensor):
        depth = int(constant_op.constant_value(depth))
    dt = dtypes_mod.as_dtype(dtype) if dtype is not None else dtypes_mod.float32
    return make_op("OneHot", [indices],
                   attrs={"depth": int(depth),
                          "on_value": 1.0 if on_value is None else on_value,
                          "off_value": 0.0 if off_value is None else off_value,
                          "axis": -1 if axis is None else int(axis),
                          "dtype": dt},
                   name=name)


def where(condition, x=None, y=None, name=None):
    condition = ops_mod.convert_to_tensor(condition)
    if x is None and y is None:
        cv = constant_op.constant_value(condition)
        if cv is None:
            raise ValueError(
                "stf.where(cond) with one argument has a data-dependent "
                "output shape, which XLA/TPU cannot compile (same limit as "
                "the reference's tf2xla bridge). Use where(cond, x, y) or a "
                "static condition.")
        return constant(np.argwhere(cv).astype(np.int64), name=name or "Where")
    if x is None or y is None:
        raise ValueError("x and y must both be set or both None")
    from .op_util import promote_args

    x, y = promote_args(x, y, "Select")
    return make_op("Select", [condition, x, y], name=name)


select = where


def boolean_mask(tensor, mask, name="boolean_mask", axis=None):
    mv = constant_op.constant_value(ops_mod.convert_to_tensor(mask))
    if mv is None:
        raise ValueError(
            "boolean_mask with a dynamic mask produces a data-dependent "
            "shape, which TPU/XLA cannot compile (the reference's tf2xla "
            "bridge has the same limit). Use stf.where + multiply, or a "
            "static mask.")
    idx = np.nonzero(np.ravel(mv) if axis is None else mv)[0]
    t = ops_mod.convert_to_tensor(tensor)
    if axis is None and mv.ndim > 1:
        lead = int(np.prod(mv.shape))
        t = reshape(t, (lead,) + tuple(t.shape.as_list()[mv.ndim:]))
    return gather(t, constant(idx.astype(np.int32)), axis=axis or 0, name=name)


def reverse(tensor, axis, name=None):
    x = ops_mod.convert_to_tensor(tensor)
    if isinstance(axis, Tensor):
        axis = constant_op.constant_value(axis)
    axis = tuple(int(a) for a in np.ravel(axis))
    return make_op("Reverse", [x], attrs={"axis": axis}, name=name)


def reverse_v2(tensor, axis, name=None):
    return reverse(tensor, axis, name)


def reverse_sequence(input, seq_lengths, seq_axis=None, batch_axis=None,  # noqa: A002
                     name=None, seq_dim=None, batch_dim=None):
    seq_axis = seq_axis if seq_axis is not None else seq_dim
    batch_axis = batch_axis if batch_axis is not None else (batch_dim or 0)
    x = ops_mod.convert_to_tensor(input)
    sl = ops_mod.convert_to_tensor(seq_lengths)
    return make_op("ReverseSequence", [x, sl],
                   attrs={"seq_axis": int(seq_axis),
                          "batch_axis": int(batch_axis)}, name=name)


def sequence_mask(lengths, maxlen=None, dtype=dtypes_mod.bool_, name=None):
    lengths = ops_mod.convert_to_tensor(lengths)
    if maxlen is None:
        v = constant_op.constant_value(lengths)
        if v is None:
            raise ValueError("sequence_mask needs static maxlen on TPU")
        maxlen = int(np.max(v))
    elif isinstance(maxlen, Tensor):
        maxlen = int(constant_op.constant_value(maxlen))
    return make_op("SequenceMask", [lengths],
                   attrs={"maxlen": int(maxlen),
                          "dtype": dtypes_mod.as_dtype(dtype)}, name=name)


def matrix_diag(diagonal, name=None):
    return unary("MatrixDiag", diagonal, name)


def matrix_diag_part(input, name=None):  # noqa: A002
    return unary("MatrixDiagPart", input, name)


def matrix_set_diag(input, diagonal, name=None):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    d = ops_mod.convert_to_tensor(diagonal)
    return make_op("MatrixSetDiag", [x, d], name=name)


def matrix_band_part(input, num_lower, num_upper, name=None):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    return make_op("MatrixBandPart", [x],
                   attrs={"num_lower": int(num_lower),
                          "num_upper": int(num_upper)}, name=name)


def diag(diagonal, name=None):
    return unary("Diag", diagonal, name)


def diag_part(input, name=None):  # noqa: A002
    return unary("DiagPart", input, name)


def eye(num_rows, num_columns=None, batch_shape=None,
        dtype=dtypes_mod.float32, name=None):
    m = np.eye(num_rows, num_columns, dtype=dtypes_mod.as_dtype(dtype).np_dtype)
    if batch_shape:
        m = np.broadcast_to(m, tuple(batch_shape) + m.shape)
    return constant(m, name=name or "eye")


def invert_permutation(x, name=None):
    return unary("InvertPermutation", x, name)


def broadcast_to(input, shape, name=None):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    return make_op("BroadcastTo", [x],
                   attrs={"shape": _static_shape_arg(shape, "broadcast_to")},
                   name=name)


def space_to_batch_nd(input, block_shape, paddings, name=None):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    bs = tuple(int(b) for b in np.ravel(
        constant_op.constant_value(ops_mod.convert_to_tensor(block_shape))))
    pd = tuple(tuple(int(p) for p in row) for row in
               constant_op.constant_value(ops_mod.convert_to_tensor(paddings)))
    return make_op("SpaceToBatchND", [x], attrs={"block_shape": bs,
                                                 "paddings": pd}, name=name)


def batch_to_space_nd(input, block_shape, crops, name=None):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    bs = tuple(int(b) for b in np.ravel(
        constant_op.constant_value(ops_mod.convert_to_tensor(block_shape))))
    cr = tuple(tuple(int(c) for c in row) for row in
               constant_op.constant_value(ops_mod.convert_to_tensor(crops)))
    return make_op("BatchToSpaceND", [x], attrs={"block_shape": bs,
                                                 "crops": cr}, name=name)


def space_to_depth(input, block_size, name=None):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    return make_op("SpaceToDepth", [x], attrs={"block_size": int(block_size)},
                   name=name)


def depth_to_space(input, block_size, name=None):  # noqa: A002
    x = ops_mod.convert_to_tensor(input)
    return make_op("DepthToSpace", [x], attrs={"block_size": int(block_size)},
                   name=name)


def extract_image_patches(images, ksizes, strides, rates, padding, name=None):
    x = ops_mod.convert_to_tensor(images)
    return make_op("ExtractImagePatches", [x],
                   attrs={"ksizes": tuple(ksizes), "strides": tuple(strides),
                          "rates": tuple(rates), "padding": padding},
                   name=name)


def unique(x, out_idx=dtypes_mod.int32, name=None):
    xv = constant_op.constant_value(ops_mod.convert_to_tensor(x))
    if xv is None:
        raise ValueError(
            "stf.unique has a data-dependent output shape; on TPU it is only "
            "supported for statically-known inputs (tf2xla parity).")
    vals, idx = np.unique(xv, return_inverse=True)
    return (constant(vals), constant(idx.astype(
        dtypes_mod.as_dtype(out_idx).np_dtype)))


def setdiff1d(x, y, index_dtype=dtypes_mod.int32, name=None):
    xv = constant_op.constant_value(ops_mod.convert_to_tensor(x))
    yv = constant_op.constant_value(ops_mod.convert_to_tensor(y))
    if xv is None or yv is None:
        raise ValueError("setdiff1d needs static inputs on TPU")
    out = np.setdiff1d(xv, yv, assume_unique=False)
    idx = np.asarray([np.where(xv == o)[0][0] for o in out])
    return constant(out), constant(idx.astype(
        dtypes_mod.as_dtype(index_dtype).np_dtype))


def _levenshtein(a, b):
    if len(a) == 0:
        return len(b)
    if len(b) == 0:
        return len(a)
    prev = list(builtins.range(len(b) + 1))
    for i, ca in enumerate(a, 1):
        cur = [i]
        for j, cb in enumerate(b, 1):
            cur.append(builtins.min(prev[j] + 1, cur[j - 1] + 1,
                                    prev[j - 1] + (ca != cb)))
        prev = cur
    return prev[-1]


def _lower_edit_distance(ctx, op, inputs):
    """Host-stage Levenshtein over COO sequence batches (the reference
    computes this on CPU too — ref core/kernels/edit_distance_op.cc).
    Sequences are grouped by their leading index dims; the last index dim
    is the position within the sequence."""
    h_idx, h_val, h_shape, t_idx, t_val, t_shape = (
        np.asarray(v) for v in inputs)
    normalize = bool(op.attrs.get("normalize", True))
    out_shape = builtins.tuple(
        int(d) for d in np.maximum(h_shape[:-1], t_shape[:-1]))

    def group(idx, val):
        seqs = {}
        order = np.lexsort(idx.T[::-1]) if len(idx) else []
        for r in order:
            key = builtins.tuple(int(x) for x in idx[r][:-1])
            seqs.setdefault(key, []).append(val[r])
        return seqs

    h_seqs = group(h_idx.reshape(-1, builtins.max(1, h_idx.shape[-1])
                                 if h_idx.ndim > 1 else 1), h_val)
    t_seqs = group(t_idx.reshape(-1, builtins.max(1, t_idx.shape[-1])
                                 if t_idx.ndim > 1 else 1), t_val)
    # slots with no entries in EITHER input are 0.0 (reference semantics:
    # edit_distance_op.cc zero-fills and only writes populated groups)
    out = np.zeros(out_shape, np.float32)
    for key in builtins.set(h_seqs) | builtins.set(t_seqs):
        h = h_seqs.get(key, [])
        t = t_seqs.get(key, [])
        d = builtins.float(_levenshtein(h, t))
        if normalize:
            d = d / len(t) if len(t) else (np.inf if len(h) else 0.0)
        out[key] = d
    return [out]


op_registry.register("EditDistance", lower=_lower_edit_distance,
                     runs_on_host=True)


def edit_distance(hypothesis, truth, normalize=True, name="edit_distance"):
    """(ref: python/ops/array_ops.py ``edit_distance``,
    core/kernels/edit_distance_op.cc). Host-stage op: Levenshtein distance
    between corresponding sequences of two SparseTensors with static
    dense_shape ranks; output shape is the leading dims of dense_shape
    (which must be statically known — XLA shapes are compile-time)."""
    from ..framework.sparse_tensor import SparseTensor

    hyp = SparseTensor.from_value(hypothesis)
    tru = SparseTensor.from_value(truth)
    h_shp = constant_op.constant_value(hyp.dense_shape)
    t_shp = constant_op.constant_value(tru.dense_shape)
    if h_shp is None or t_shp is None:
        raise ValueError(
            "edit_distance needs statically-known dense_shapes on TPU "
            "(the output shape is derived from them at graph-build time)")
    out_shape = [int(d) for d in np.maximum(np.asarray(h_shp)[:-1],
                                            np.asarray(t_shp)[:-1])]
    g = ops_mod.get_default_graph()
    op = g.create_op(
        "EditDistance",
        [hyp.indices, hyp.values, hyp.dense_shape,
         tru.indices, tru.values, tru.dense_shape],
        attrs={"normalize": builtins.bool(normalize)}, name=name,
        output_specs=[(shape_mod.TensorShape(out_shape),
                       dtypes_mod.float32)])
    return op.outputs[0]


def meshgrid(*args, **kwargs):
    """(ref: python/ops/array_ops.py ``meshgrid``). Static inputs fold to
    constants; dynamic inputs build via reshape + broadcast (shapes are
    static, only values are runtime — XLA-legal)."""
    indexing = kwargs.get("indexing", "xy")
    if indexing not in ("xy", "ij"):
        raise ValueError(f"indexing must be 'xy' or 'ij': {indexing}")
    tensors = [ops_mod.convert_to_tensor(a) for a in args]
    vals = [constant_op.constant_value(t) for t in tensors]
    if all(v is not None for v in vals):
        grids = np.meshgrid(*vals, indexing=indexing)
        return [constant(g) for g in grids]
    n = len(tensors)
    sizes = []
    for t in tensors:
        dims = t.shape.as_list()
        if len(dims) != 1 or dims[0] is None:
            raise ValueError(
                "meshgrid with runtime values needs 1-D inputs of static "
                f"length on TPU (got shape {t.shape})")
        sizes.append(dims[0])
    order = list(range(n))
    if indexing == "xy" and n >= 2:
        order[0], order[1] = order[1], order[0]
    # grid shape: dimension j of the output varies with input order[j]
    grid_shape = [sizes[i] for i in order]
    outs = []
    for idx, t in enumerate(tensors):
        axis = order.index(idx)
        shp = [1] * n
        shp[axis] = sizes[idx]
        outs.append(broadcast_to(reshape(t, shp), grid_shape))
    return outs


def required_space_to_batch_paddings(input_shape, block_shape,
                                     base_paddings=None):
    """(ref: python/ops/array_ops.py ``required_space_to_batch_paddings``).
    Computes (paddings, crops) so that input + paddings is divisible by
    block_shape; batch_to_space with `crops` undoes the padding. Static
    arithmetic (XLA shapes are compile-time)."""
    ishape = constant_op.constant_value(
        ops_mod.convert_to_tensor(input_shape))
    bshape = constant_op.constant_value(
        ops_mod.convert_to_tensor(block_shape))
    if ishape is None or bshape is None:
        raise ValueError(
            "required_space_to_batch_paddings needs static shapes on TPU")
    ishape = np.asarray(ishape, np.int64).ravel()
    bshape = np.asarray(bshape, np.int64).ravel()
    if base_paddings is None:
        base = np.zeros((len(ishape), 2), np.int64)
    else:
        base = np.asarray(
            constant_op.constant_value(
                ops_mod.convert_to_tensor(base_paddings)),
            np.int64).reshape(len(ishape), 2)
    pad_start = base[:, 0]
    full = ishape + pad_start + base[:, 1]
    rem = (-full) % bshape
    pad_end = base[:, 1] + rem
    paddings = np.stack([pad_start, pad_end], axis=1)
    crops = np.stack([np.zeros_like(rem), rem], axis=1)
    return constant(paddings), constant(crops)


def guarantee_const(input, name=None):  # noqa: A002
    return identity(input, name)


def newaxis():
    return None


# -- round-4 parity fills ----------------------------------------------------

def broadcast_static_shape(shape_x, shape_y):
    """(ref: array_ops.py ``broadcast_static_shape``)."""
    a = shape_mod.as_shape(shape_x)
    b = shape_mod.as_shape(shape_y)
    if a.rank is None or b.rank is None:
        return shape_mod.TensorShape(None)
    out = list(np.broadcast_shapes(
        tuple(1 if d is None else d for d in a.as_list()),
        tuple(1 if d is None else d for d in b.as_list())))
    return shape_mod.TensorShape(out)


def broadcast_dynamic_shape(shape_x, shape_y, name=None):
    """(ref: array_ops.py ``broadcast_dynamic_shape``). Shapes are static
    on TPU, so this folds at construction when both are constants."""
    sx = constant_op.constant_value(ops_mod.convert_to_tensor(shape_x))
    sy = constant_op.constant_value(ops_mod.convert_to_tensor(shape_y))
    if sx is None or sy is None:
        raise ValueError("broadcast_dynamic_shape needs static shape "
                         "tensors on TPU")
    return constant(np.asarray(np.broadcast_shapes(tuple(sx), tuple(sy)),
                               np.int32))


def parallel_stack(values, name=None):
    """(ref: array_ops.py ``parallel_stack``) — the parallel/sequential
    distinction is a CPU-executor scheduling detail; under XLA both
    compile to the same fused concat."""
    return stack(values, axis=0, name=name or "parallel_stack")


def space_to_batch(input, paddings, block_size, name=None):  # noqa: A002
    """2D-specialized wrapper (ref: array_ops.py ``space_to_batch``)."""
    return space_to_batch_nd(input, [block_size, block_size], paddings,
                             name=name)


def batch_to_space(input, crops, block_size, name=None):  # noqa: A002
    return batch_to_space_nd(input, [block_size, block_size], crops,
                             name=name)


def unique_with_counts(x, out_idx=dtypes_mod.int32, name=None):
    """(ref: array_ops.py ``unique_with_counts``) — static inputs only
    (data-dependent output size, tf2xla parity; same rule as unique)."""
    xv = constant_op.constant_value(ops_mod.convert_to_tensor(x))
    if xv is None:
        raise ValueError(
            "stf.unique_with_counts has a data-dependent output shape; on "
            "TPU it is only supported for statically-known inputs.")
    vals, idx, counts = np.unique(xv, return_inverse=True,
                                  return_counts=True)
    np_idx = dtypes_mod.as_dtype(out_idx).np_dtype
    return (constant(vals), constant(idx.astype(np_idx)),
            constant(counts.astype(np_idx)))


# ---------------------------------------------------------------------------
# sharding propagation rules (stf.analysis.sharding; ISSUE 6)
# ---------------------------------------------------------------------------

from ..analysis import sharding as _shard  # noqa: E402

_shard.register_rules(_shard.passthrough_rule,
                      "Identity", "Snapshot", "StopGradient",
                      "PreventGradient", "CheckNumerics", "ZerosLike",
                      "OnesLike")
# shape introspection reads metadata, not data: no gather of the operand
_shard.register_rules(_shard.local_rule, "Shape", "Size", "Rank",
                      "BroadcastArgs", "InvertPermutation",
                      "SequenceMask", "Fill")
_shard.register_rules(_shard.reshape_rule, "Reshape")
_shard.register_rules(_shard.transpose_rule, "Transpose",
                      "ConjugateTranspose")
_shard.register_rules(_shard.expand_dims_rule, "ExpandDims")
_shard.register_rules(_shard.squeeze_rule, "Squeeze")
_shard.register_rules(_shard.make_concat_rule("axis"), "Concat")
_shard.register_rules(_shard.make_stack_rule("axis"), "Pack")
_shard.register_rules(_shard.make_unstack_rule("axis"), "Unpack")
_shard.register_rules(_shard.make_axis_unsharded_rule("axis"), "Split")
_shard.register_rules(_shard.make_slice_rule(),
                      "Slice", "StridedSlice", "Pad", "MirrorPad", "Tile",
                      "Reverse", "ReverseSequence", "BroadcastTo",
                      "MatrixBandPart", "MatrixSetDiag")
_shard.register_rules(_shard.make_gather_rule("axis"), "Gather")
_shard.register_rules(_shard.elementwise_rule, "Select")


def _onehot_rule(op, in_specs, ctx):
    # indices dims pass through; the new class dim is unsharded
    s = in_specs[0]
    r = _shard._out_rank(op)
    if s is None or r is None:
        return [_shard.replicated(r)]
    ax = int(op.attrs.get("axis", -1))
    ax = ax % r
    out = list(s)
    out.insert(ax, ())
    return [tuple(out[:r])]


_shard.register_rules(_onehot_rule, "OneHot")
