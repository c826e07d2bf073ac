"""Fused LayerNorm (Pallas TPU), forward + custom-VJP backward.

The reference computes layer norm from unfused mean/var/mul/add graph nodes
(there is no fused LN kernel in TF-1.0; batch-norm has one,
ref: tensorflow/core/kernels/fused_batch_norm_op.cc — this is the layer-norm
analogue done the TPU way). One VMEM-resident pass per row block computes
mean, variance, normalisation and the affine transform; backward fuses the
three reduction terms of d_x and accumulates d_gamma/d_beta into a single
VMEM-resident tile across the sequential TPU grid.

x: (..., features) — flattened to (rows, features). f32 statistics
regardless of input dtype (bf16-safe). Row stats are (rows, 1) tiles
(Mosaic-legal shapes).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import common
from .common import cdiv, pad_dim, round_up

DEFAULT_BLOCK_ROWS = 256


def _fwd_kernel(x_ref, g_ref, b_ref, o_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=-1, keepdims=True)             # (br, 1)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    y = xc * rstd
    o_ref[:] = (y * g_ref[:].astype(jnp.float32)
                + b_ref[:].astype(jnp.float32)).astype(o_ref.dtype)
    mean_ref[:] = mean
    rstd_ref[:] = rstd


def _bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, do_ref,
                dx_ref, dg_ref, db_ref):
    x = x_ref[:].astype(jnp.float32)
    gamma = g_ref[:].astype(jnp.float32)
    do = do_ref[:].astype(jnp.float32)
    mean = mean_ref[:]                                     # (br, 1)
    rstd = rstd_ref[:]

    xhat = (x - mean) * rstd
    wdo = do * gamma
    c1 = jnp.mean(wdo, axis=-1, keepdims=True)
    c2 = jnp.mean(wdo * xhat, axis=-1, keepdims=True)
    dx = (wdo - c1 - xhat * c2) * rstd
    dx_ref[:] = dx.astype(dx_ref.dtype)

    # d_gamma/d_beta accumulate across the sequential grid into one
    # VMEM-resident (1, n) tile (same output block for every program).
    @pl.when(pl.program_id(0) == 0)
    def _():
        dg_ref[:] = jnp.zeros_like(dg_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    dg_ref[:] += jnp.sum(do * xhat, axis=0, keepdims=True)
    db_ref[:] += jnp.sum(do, axis=0, keepdims=True)


def _fwd(x, gamma, beta, eps, block_rows):
    rows, n = x.shape
    grid = (cdiv(rows, block_rows),)
    o, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, n), x.dtype),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        interpret=common.use_interpret(),
        name="stf_layer_norm_fwd",
    )(x, gamma, beta)
    return o, mean, rstd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _layer_norm_2d(x, gamma, beta, eps, block_rows):
    o, _, _ = _fwd(x, gamma, beta, eps, block_rows)
    return o


def _ln_fwd_rule(x, gamma, beta, eps, block_rows):
    o, mean, rstd = _fwd(x, gamma, beta, eps, block_rows)
    return o, (x, gamma, beta, mean, rstd)


def _ln_bwd_rule(eps, block_rows, res, g):
    x, gamma, beta, mean, rstd = res
    rows, n = x.shape
    nblocks = cdiv(rows, block_rows)
    dx, dg, db = pl.pallas_call(
        _bwd_kernel,
        grid=(nblocks,),
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=common.use_interpret(),
        name="stf_layer_norm_bwd",
    )(x, gamma, mean, rstd, g)
    return dx, dg[0].astype(gamma.dtype), db[0].astype(beta.dtype)


_layer_norm_2d.defvjp(_ln_fwd_rule, _ln_bwd_rule)


_VMEM_BLOCK_BUDGET = 4 * 1024 * 1024  # bytes per (block_rows, n) f32 tile


def layer_norm(x, gamma, beta, *, eps=1e-6, block_rows=DEFAULT_BLOCK_ROWS):
    """Fused layer norm over the last axis. gamma/beta: (features,)."""
    orig_shape = x.shape
    n = orig_shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, n)
    # the kernel holds whole (block_rows, n) rows in VMEM (f32 math,
    # double-buffered): shrink block_rows for very wide features so the
    # tile stays inside the ~16 MB scoped budget (n=16384 at the default
    # 256 rows would be a 16 MB tile — the same OOM class the xent kernel
    # hit at BERT vocab width)
    fit = _VMEM_BLOCK_BUDGET // (int(n) * 4)
    block_rows = max(8, min(block_rows, (fit // 8) * 8 or 8))
    block_rows = min(block_rows, round_up(rows, 8))
    rp = round_up(rows, block_rows)
    x2 = pad_dim(x2, 0, rp)
    o = _layer_norm_2d(x2, gamma, beta, float(eps), int(block_rows))
    return o[:rows].reshape(orig_shape)


def layer_norm_reference(x, gamma, beta, *, eps=1e-6):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32)).astype(x.dtype)
