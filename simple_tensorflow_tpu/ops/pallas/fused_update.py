"""Fused optimizer updates over flattened parameter groups (Pallas TPU).

The reference applies one ApplyAdam/ApplyMomentum kernel per variable
(ref: tensorflow/core/kernels/training_ops.cc) — a long tail of small
launches after every backward pass. Here the optimizer tier concatenates
every same-dtype parameter into ONE flat vector per group and updates
m/v/param in a single blocked elementwise kernel: one pass over four HBM
streams (g, m, v, p) instead of a per-variable chain of a dozen ops
each. The same math is exposed as a plain-jnp "reference" closure — the
stock XLA lowering the kernel registry falls back to (and the CPU path,
where XLA fuses the closure into a few vectorized passes: the fused win
on CPU comes from collapsing the per-variable op tail, not from Pallas).

Math is kept op-for-op identical to the per-variable _apply_dense chains
in train/optimizers.py (same constant formation, same multiply/divide
order), so fused and per-variable training trajectories are bit-exact —
pinned by tests/test_kernel_registry.py.

Inputs are 1-D flat vectors: p (param dtype), m/v/g (update dtype, f32
for low-precision params), plus the traced scalar hyperparameters. The
wrapper pads to (rows, 128) VPU lanes; padded elements compute garbage
that is sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common
from .common import cdiv, pad_dim, round_up

LANES = 128
BLOCK_ROWS = 256


# ---------------------------------------------------------------------------
# Adam: new_m = b1*m + (1-b1)*g ; new_v = b2*v + (1-b2)*g^2 ;
#       new_p = p - (alpha*new_m/(sqrt(new_v)+eps)) cast to p.dtype
# ---------------------------------------------------------------------------

def adam_update_reference(p, m, v, g, alpha, *, beta1, beta2, eps):
    """The fused XLA closure (stock lowering): identical math to the
    per-variable chain, over the flat group."""
    ud = m.dtype
    b1 = jnp.asarray(beta1, ud)
    b2 = jnp.asarray(beta2, ud)
    e = jnp.asarray(eps, ud)
    new_m = b1 * m + (1 - b1) * g
    new_v = b2 * v + (1 - b2) * jnp.square(g)
    upd = alpha.astype(ud) * new_m / (jnp.sqrt(new_v) + e)
    new_p = p - upd.astype(p.dtype)
    return new_p, new_m, new_v


def _adam_kernel(p_ref, m_ref, v_ref, g_ref, alpha_ref,
                 np_ref, nm_ref, nv_ref, *, beta1, beta2, eps):
    ud = m_ref.dtype
    b1 = jnp.asarray(beta1, ud)
    b2 = jnp.asarray(beta2, ud)
    e = jnp.asarray(eps, ud)
    g = g_ref[:]
    new_m = b1 * m_ref[:] + (1 - b1) * g
    new_v = b2 * v_ref[:] + (1 - b2) * jnp.square(g)
    upd = alpha_ref[0].astype(ud) * new_m / (jnp.sqrt(new_v) + e)
    np_ref[:] = p_ref[:] - upd.astype(np_ref.dtype)
    nm_ref[:] = new_m
    nv_ref[:] = new_v


def _flat_2d(x, rows, cols):
    return pad_dim(x, 0, rows * cols).reshape(rows, cols)


def _grid_shapes(n):
    cols = LANES
    rows = cdiv(n, cols)
    block = min(BLOCK_ROWS, round_up(rows, 8))
    rows = round_up(rows, block)
    return rows, cols, block


def adam_update(p, m, v, g, alpha, *, beta1, beta2, eps):
    """Pallas fused Adam over a flat group; one kernel for m/v/param."""
    n = p.shape[0]
    rows, cols, block = _grid_shapes(n)
    p2 = _flat_2d(p, rows, cols)
    m2 = _flat_2d(m, rows, cols)
    v2 = _flat_2d(v, rows, cols)
    g2 = _flat_2d(g, rows, cols)
    alpha1 = jnp.asarray(alpha, m.dtype).reshape((1,))
    spec = pl.BlockSpec((block, cols), lambda i: (i, 0))
    np_, nm, nv = pl.pallas_call(
        functools.partial(_adam_kernel, beta1=float(beta1),
                          beta2=float(beta2), eps=float(eps)),
        grid=(rows // block,),
        in_specs=[spec, spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), p.dtype),
            jax.ShapeDtypeStruct((rows, cols), m.dtype),
            jax.ShapeDtypeStruct((rows, cols), v.dtype),
        ],
        cost_estimate=pl.CostEstimate(
            flops=12 * n,
            bytes_accessed=(p.size * p.dtype.itemsize * 2
                            + 5 * m.size * m.dtype.itemsize),
            transcendentals=n),
        interpret=common.use_interpret(),
        name="stf_fused_update_adam",
    )(p2, m2, v2, g2, alpha1)
    return (np_.reshape(-1)[:n], nm.reshape(-1)[:n], nv.reshape(-1)[:n])


# ---------------------------------------------------------------------------
# Momentum: new_acc = mu*acc + g ;
#           upd = lr*(g + mu*new_acc) (nesterov) | lr*new_acc ;
#           new_p = p - upd cast to p.dtype
# ---------------------------------------------------------------------------

def momentum_update_reference(p, acc, g, lr, mu, *, use_nesterov=False):
    ud = acc.dtype
    new_acc = mu.astype(ud) * acc + g
    if use_nesterov:
        upd = lr.astype(ud) * (g + mu.astype(ud) * new_acc)
    else:
        upd = lr.astype(ud) * new_acc
    new_p = p - upd.astype(p.dtype)
    return new_p, new_acc


def _momentum_kernel(p_ref, acc_ref, g_ref, lr_ref, mu_ref,
                     np_ref, nacc_ref, *, use_nesterov):
    ud = acc_ref.dtype
    g = g_ref[:]
    mu = mu_ref[0].astype(ud)
    new_acc = mu * acc_ref[:] + g
    if use_nesterov:
        upd = lr_ref[0].astype(ud) * (g + mu * new_acc)
    else:
        upd = lr_ref[0].astype(ud) * new_acc
    np_ref[:] = p_ref[:] - upd.astype(np_ref.dtype)
    nacc_ref[:] = new_acc


def momentum_update(p, acc, g, lr, mu, *, use_nesterov=False):
    """Pallas fused Momentum over a flat group."""
    n = p.shape[0]
    rows, cols, block = _grid_shapes(n)
    p2 = _flat_2d(p, rows, cols)
    a2 = _flat_2d(acc, rows, cols)
    g2 = _flat_2d(g, rows, cols)
    lr1 = jnp.asarray(lr, acc.dtype).reshape((1,))
    mu1 = jnp.asarray(mu, acc.dtype).reshape((1,))
    spec = pl.BlockSpec((block, cols), lambda i: (i, 0))
    np_, nacc = pl.pallas_call(
        functools.partial(_momentum_kernel,
                          use_nesterov=bool(use_nesterov)),
        grid=(rows // block,),
        in_specs=[spec, spec, spec,
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((rows, cols), p.dtype),
            jax.ShapeDtypeStruct((rows, cols), acc.dtype),
        ],
        cost_estimate=pl.CostEstimate(
            flops=6 * n,
            bytes_accessed=(p.size * p.dtype.itemsize * 2
                            + 3 * acc.size * acc.dtype.itemsize),
            transcendentals=0),
        interpret=common.use_interpret(),
        name="stf_fused_update_momentum",
    )(p2, a2, g2, lr1, mu1)
    return (np_.reshape(-1)[:n], nacc.reshape(-1)[:n])
