"""Int8 quantized matmul (Pallas TPU).

TPU-native counterpart of the reference's quantized matmul kernels
(ref: tensorflow/core/kernels/quantized_matmul_op.cc, quantize_op.cc —
gemmlowp on CPU). The MXU multiplies int8 natively at 2x bf16 rate;
we keep weights pre-quantized per output channel, quantize activations
per row on the fly (dynamic symmetric quantization), accumulate int32,
and dequantize with the outer product of the two scale vectors.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common
from .common import cdiv, pad_dim, round_up

TILE_M = 128
TILE_N = 128


def quantize_rowwise(x):
    """Symmetric per-row int8 quantization: returns (q, scale)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127).astype(jnp.int8)
    return q, scale[..., 0]


def quantize_colwise(w):
    """Symmetric per-output-channel int8 quantization of a (k, n) weight."""
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=0, keepdims=True)
    scale = jnp.where(amax == 0, 1.0, amax / 127.0)
    q = jnp.clip(jnp.round(wf / scale), -127, 127).astype(jnp.int8)
    return q, scale[0]


def _qmm_kernel(n_kb, xq_ref, wq_ref, xs_ref, ws_ref, o_ref, acc_scr):
    # Operands stay s8: Mosaic lowers s8 x s8 -> s32 onto the MXU's native
    # int8 path (2x bf16 rate); widening to i32 first produces an i32
    # matmul Mosaic rejects ("Bad lhs/rhs type: vector<...xi32>").
    # The contraction streams in TILE_K blocks (innermost grid dim) with an
    # int32 VMEM accumulator — full-k strips bust the 16 MB scoped budget
    # for large k.
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    acc_scr[:] += jax.lax.dot_general(
        xq_ref[:], wq_ref[:],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)          # (tm, tn)

    @pl.when(kb == n_kb - 1)
    def _():
        scale = xs_ref[:] * ws_ref[:]              # (tm,1)*(1,tn)->(tm,tn)
        o_ref[:] = (acc_scr[:].astype(jnp.float32)
                    * scale).astype(o_ref.dtype)


TILE_K = 1024


def quant_matmul(x, wq, w_scale, *, out_dtype=None):
    """x @ dequant(wq) with int8 MXU accumulation.

    x: (m, k) float; wq: (k, n) int8; w_scale: (n,) f32.
    """
    if out_dtype is None:
        out_dtype = x.dtype
    m, k = x.shape
    n = wq.shape[1]
    xq, x_scale = quantize_rowwise(x)

    # int8 tiles are (32, 128); pad every dim (zero contraction columns are
    # exact no-ops in the int32 accumulation).
    mp, np_ = round_up(m, TILE_M), round_up(n, TILE_N)
    # k pads to a multiple of tile_k: a ragged final k-block would
    # accumulate out-of-bounds garbage (no in-kernel contraction mask)
    tile_k = min(TILE_K, round_up(k, 8 if common.use_interpret() else 128))
    kp = round_up(k, tile_k)
    xq = pad_dim(pad_dim(xq, 0, mp), 1, kp)
    x_scale = pad_dim(x_scale.reshape(m, 1), 0, mp)
    wq = pad_dim(pad_dim(wq, 0, kp), 1, np_)
    w_scale = pad_dim(w_scale.reshape(1, n), 1, np_)
    k = kp
    n_kb = cdiv(k, tile_k)

    out = pl.pallas_call(
        functools.partial(_qmm_kernel, n_kb),
        grid=(cdiv(mp, TILE_M), cdiv(np_, TILE_N), n_kb),
        in_specs=[
            pl.BlockSpec((TILE_M, tile_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((tile_k, TILE_N), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((TILE_M, 1), lambda i, j, kk: (i, 0)),
            pl.BlockSpec((1, TILE_N), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((TILE_M, TILE_N), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), out_dtype),
        scratch_shapes=[pltpu.VMEM((TILE_M, TILE_N), jnp.int32)],
        cost_estimate=pl.CostEstimate(
            flops=2 * mp * np_ * k,
            # Grid (i, j, kk), kk innermost. A block is re-fetched when its
            # index changes between consecutive iterations: xq (i,kk) cycles
            # per j → mp*k s8 bytes × n_j; wq (kk,j) changes every step →
            # k*np_ × n_i; x_scale (i,0) only on i change → mp f32 once;
            # w_scale (0,j) on j change → np_ f32 × n_i.
            bytes_accessed=(mp * k * cdiv(np_, TILE_N)
                            + k * np_ * cdiv(mp, TILE_M)
                            + mp * 4 + np_ * 4 * cdiv(mp, TILE_M)
                            + mp * np_ * 4),
            transcendentals=0),
        interpret=common.use_interpret(),
        name="stf_quant_matmul_fwd",
    )(xq, wq, x_scale.astype(jnp.float32), w_scale.astype(jnp.float32))
    return out[:m, :n]


@jax.custom_vjp
def quant_matmul_ste(x, wq, w_scale):
    """quant_matmul with a straight-through gradient for x: the rounding in
    the activation quantizer has zero derivative almost everywhere, so
    d/dx is taken through the dequantized matmul x @ (wq * w_scale).
    This is the op the graph registers — differentiable training works."""
    return quant_matmul(x, wq, w_scale)


def _qmm_ste_fwd(x, wq, w_scale):
    return quant_matmul(x, wq, w_scale), (x, wq, w_scale)


def _qmm_ste_bwd(res, g):
    x, wq, w_scale = res
    gf = g.astype(jnp.float32)
    wd = wq.astype(jnp.float32) * w_scale[None, :].astype(jnp.float32)
    dx = (gf @ wd.T).astype(x.dtype)
    d_wq = np.zeros(wq.shape, dtype=jax.dtypes.float0)  # int8: no tangent
    # y[m,n] = acc[m,n] * x_scale[m] * w_scale[n]  (acc = xq @ wq, int32)
    # => d w_scale[n] = sum_m g[m,n] * acc[m,n] * x_scale[m]
    xq, x_scale = quantize_rowwise(x)
    acc = jnp.dot(xq.astype(jnp.int32), wq.astype(jnp.int32))
    d_scale = jnp.sum(gf * acc.astype(jnp.float32)
                      * x_scale[:, None].astype(jnp.float32), axis=0
                      ).astype(w_scale.dtype)
    return dx, d_wq, d_scale


quant_matmul_ste.defvjp(_qmm_ste_fwd, _qmm_ste_bwd)


def quant_matmul_reference(x, wq, w_scale, *, out_dtype=None):
    if out_dtype is None:
        out_dtype = x.dtype
    xq, x_scale = quantize_rowwise(x)
    acc = jnp.dot(xq.astype(jnp.int32), wq.astype(jnp.int32))
    return (acc.astype(jnp.float32)
            * x_scale[:, None] * w_scale[None, :]).astype(out_dtype)


@jax.custom_vjp
def quant_matmul_ste_reference(x, wq, w_scale):
    """The stock-XLA lowering of the QuantMatMul op contract: same
    dynamic row quantization and int32 accumulation as the Pallas
    kernel, as a plain jnp dot (XLA picks the layout), with the
    IDENTICAL straight-through vjp — the kernel registry's fallback."""
    return quant_matmul_reference(x, wq, w_scale)


def _qmm_ref_fwd(x, wq, w_scale):
    return quant_matmul_reference(x, wq, w_scale), (x, wq, w_scale)


quant_matmul_ste_reference.defvjp(_qmm_ref_fwd, _qmm_ste_bwd)
