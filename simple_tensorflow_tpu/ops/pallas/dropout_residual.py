"""Fused dropout + bias + residual add (Pallas TPU), fwd + custom VJP.

The transformer block tail ``residual + dropout(x + bias)`` lowers today
as separate bias-add, RNG-mask, scale and add ops — four HBM round
trips over a (B, S, D) activation. This kernel streams the row blocks
once, generating the dropout mask from the same counter-based position
hash the flash-attention kernel uses (common.counter_keep_mask), so

- nothing is materialized for the backward pass (the vjp regenerates
  the mask from the seed), and
- the composed-XLA fallback (``dropout_bias_residual_reference``)
  produces bit-identical output from the same seed — the kernel
  registry can swap implementations without perturbing seeded runs.

x, residual: (rows, n); bias: (n,) or None; seed: int32 (1,).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common
from .common import cdiv, counter_keep_mask, pad_dim, round_up

BLOCK_ROWS = 256
_VMEM_BLOCK_BUDGET = 4 * 1024 * 1024


def _keep(seed, row0, rows, n, keep_prob):
    """(rows, n) keep mask from GLOBAL row indices starting at row0."""
    rr = (row0.astype(jnp.uint32)
          + jax.lax.broadcasted_iota(jnp.uint32, (rows, n), 0))
    cc = jax.lax.broadcasted_iota(jnp.uint32, (rows, n), 1)
    return counter_keep_mask(seed, jnp.uint32(0), rr, cc, keep_prob)


def _kernel(*refs, rate, has_bias, block_rows):
    it = iter(refs)
    x_ref = next(it)
    res_ref = next(it)
    bias_ref = next(it) if has_bias else None
    seed_ref = next(it)
    o_ref = next(it)
    keep_prob = 1.0 - rate
    i = pl.program_id(0)
    x = x_ref[:].astype(jnp.float32)
    if has_bias:
        x = x + bias_ref[:].astype(jnp.float32)
    rows, n = x.shape
    row0 = i * jnp.uint32(block_rows)
    keep = _keep(seed_ref[0], row0, rows, n, keep_prob)
    y = jnp.where(keep, x * (1.0 / keep_prob), 0.0)
    o_ref[:] = (res_ref[:].astype(jnp.float32) + y).astype(o_ref.dtype)


def _fwd(x, residual, bias, seed, rate, block_rows):
    rows, n = x.shape
    grid = (cdiv(rows, block_rows),)
    spec = pl.BlockSpec((block_rows, n), lambda i: (i, 0))
    in_specs = [spec, spec]
    operands = [x, residual]
    if bias is not None:
        in_specs.append(pl.BlockSpec((n,), lambda i: (0,)))
        operands.append(bias)
    in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
    operands.append(seed)
    return pl.pallas_call(
        functools.partial(_kernel, rate=rate, has_bias=bias is not None,
                          block_rows=block_rows),
        grid=grid,
        in_specs=in_specs,
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        cost_estimate=pl.CostEstimate(
            flops=4 * rows * n,
            bytes_accessed=3 * rows * n * x.dtype.itemsize,
            transcendentals=0),
        interpret=common.use_interpret(),
        name="stf_dropout_residual_fwd",
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _dbr_2d(x, residual, bias, seed, rate, block_rows):
    return _fwd(x, residual, bias, seed, rate, block_rows)


def _dbr_fwd_rule(x, residual, bias, seed, rate, block_rows):
    out = _fwd(x, residual, bias, seed, rate, block_rows)
    # zero-size dtype carriers: custom-vjp residuals must be JAX types
    res = (jnp.zeros((0,), x.dtype), jnp.zeros((0,), residual.dtype),
           None if bias is None else jnp.zeros((0,), bias.dtype), seed)
    return out, res


def _dbr_bwd_rule(rate, block_rows, res, g):
    """d/dx = mask/keep ∘ g ; d/dbias = Σ_rows d/dx ; d/dres = g. The
    mask regenerates from (seed, positions) — nothing was saved."""
    x_c, res_c, bias_c, seed = res
    rows, n = g.shape
    keep_prob = 1.0 - rate
    gf = g.astype(jnp.float32)
    keep = _keep_full(seed, rows, n, keep_prob)
    dx_f = jnp.where(keep, gf * (1.0 / keep_prob), 0.0)
    dx = dx_f.astype(x_c.dtype)
    dres = g.astype(res_c.dtype)
    dbias = None if bias_c is None \
        else jnp.sum(dx_f, axis=0).astype(bias_c.dtype)
    import numpy as np

    dseed = np.zeros(seed.shape, dtype=jax.dtypes.float0)
    return dx, dres, dbias, dseed


def _keep_full(seed, rows, n, keep_prob):
    seed0 = jnp.asarray(seed, jnp.int32).reshape((-1,))[0]
    rr = jax.lax.broadcasted_iota(jnp.uint32, (rows, n), 0)
    cc = jax.lax.broadcasted_iota(jnp.uint32, (rows, n), 1)
    return counter_keep_mask(seed0, jnp.uint32(0), rr, cc, keep_prob)


_dbr_2d.defvjp(_dbr_fwd_rule, _dbr_bwd_rule)


def dropout_bias_residual(x, residual, bias=None, *, rate, seed,
                          block_rows=BLOCK_ROWS):
    """Fused ``residual + dropout(x + bias)``. x/residual: (..., n);
    bias (n,) or None; seed: int32 scalar/array. Returns x.dtype."""
    orig = x.shape
    n = orig[-1]
    rows = 1
    for s in orig[:-1]:
        rows *= s
    x2 = x.reshape(rows, n)
    r2 = residual.reshape(rows, n)
    # whole (block_rows, n) f32 rows live in VMEM: shrink for wide n
    fit = _VMEM_BLOCK_BUDGET // (max(int(n), 1) * 4)
    block_rows = max(8, min(block_rows, (fit // 8) * 8 or 8))
    block_rows = min(block_rows, round_up(rows, 8))
    rp = round_up(rows, block_rows)
    x2 = pad_dim(x2, 0, rp)
    r2 = pad_dim(r2, 0, rp)
    seed1 = jnp.asarray(seed, jnp.int32).reshape((-1,))[:1]
    out = _dbr_2d(x2, r2, bias, seed1, float(rate), int(block_rows))
    return out[:rows].reshape(orig)


def dropout_bias_residual_reference(x, residual, bias=None, *, rate, seed,
                                    block_rows=BLOCK_ROWS):
    """The stock composed-XLA lowering: identical math and identical
    counter-based mask — bit-exact with the kernel from the same seed
    (XLA fuses the chain into one elementwise pass; this is the CPU
    lowering and the registry fallback)."""
    orig = x.shape
    n = orig[-1]
    rows = 1
    for s in orig[:-1]:
        rows *= s
    keep_prob = 1.0 - rate
    xf = x.reshape(rows, n).astype(jnp.float32)
    if bias is not None:
        xf = xf + bias.astype(jnp.float32)
    keep = _keep_full(seed, rows, n, keep_prob)
    y = jnp.where(keep, xf * (1.0 / keep_prob), 0.0)
    out = (residual.reshape(rows, n).astype(jnp.float32) + y).astype(x.dtype)
    return out.reshape(orig)
