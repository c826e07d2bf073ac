"""A hyper-connected residual (mHC) for serving: ``n`` residual streams a
token, mixed around every sublayer by a doubly stochastic matrix made per
token.

(ref: the reference's residual is ``x + f(x)``; the mechanism is
manifold-constrained hyper-connections, arXiv:2512.24880, after
Hyper-Connections, arXiv:2409.19606.) The stream ``X (n, C)``; around a
sublayer ``F``::

    x~     = RMSNorm(vec X)                            (n C wide, no gain)
    H_pre  = sigmoid(a_pre x~ phi_pre + b_pre)         (1 x n)
    H_post = 2 sigmoid(a_post x~ phi_post + b_post)    (1 x n)
    H_res  = Sinkhorn(exp(a_res mat(x~ phi_res) + b_res))     (n x n)
    X     <- H_res X + H_post^T F(H_pre X)

``phi = [phi_pre | phi_post | phi_res]`` is ``(n C, 2 n + n^2)``, ``a``
three scalars, ``b`` ``2 n + n^2`` values. Sinkhorn alternates a row and a
column normalisation ``iters`` times (each sum ``+ eps``): the columns sum
to 1 exactly after the last, the rows to within its convergence. Every
coefficient is float32 (the projection at the highest matmul precision);
the streams keep their dtype, mixed in float32.

Two graph ops, both plain XLA: ``HyperConnectionPre`` (the coefficients
and the sublayer's input ``H_pre X``) and ``HyperConnectionPost`` (the new
streams). Serving-only (no gradients).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework import graph as ops_mod
from ..framework import op_registry
from . import op_util

_HI = jax.lax.Precision.HIGHEST


def sinkhorn(m, *, iters, eps):
    """``m (..., n, n)`` positive -> doubly stochastic: ``iters`` times a
    row then a column normalisation."""
    def one(_, m):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=-2, keepdims=True) + eps)

    return jax.lax.fori_loop(0, int(iters), one, m)


def hc_coefficients(x, phi, scale, bias, *, iters, eps, norm_eps):
    """``x (R, n, C)`` -> ``(pre (R, n), post (R, n), res (R, n, n))``
    float32 (module docstring)."""
    r, n, c = x.shape
    xf = x.reshape(r, n * c).astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                            + norm_eps)
    proj = jnp.dot(xf, phi.astype(jnp.float32), precision=_HI)
    scale = scale.astype(jnp.float32)
    bias = bias.astype(jnp.float32)
    pre = jax.nn.sigmoid(scale[0] * proj[:, :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(scale[1] * proj[:, n:2 * n]
                                + bias[n:2 * n])
    logits = (scale[2] * proj[:, 2 * n:] + bias[2 * n:]).reshape(r, n, n)
    return pre, post, sinkhorn(jnp.exp(logits), iters=iters, eps=eps)


def hc_pre(x, phi, scale, bias, *, iters, eps, norm_eps):
    """``(H_pre X (R, C)`` in x's dtype, ``post``, ``res)``."""
    pre, post, res = hc_coefficients(x, phi, scale, bias, iters=iters,
                                     eps=eps, norm_eps=norm_eps)
    h = jnp.einsum("rn,rnc->rc", pre, x.astype(jnp.float32), precision=_HI)
    return h.astype(x.dtype), post, res


def hc_post(x, f, post, res):
    """``H_res X + H_post^T F``: ``x (R, n, C)``, ``f (R, C)``."""
    y = (jnp.einsum("rij,rjc->ric", res, x.astype(jnp.float32),
                    precision=_HI)
         + post[:, :, None] * f.astype(jnp.float32)[:, None, :])
    return y.astype(x.dtype)


op_registry.register_pure(
    "HyperConnectionPre",
    lambda x, phi, scale, bias, iters=20, eps=1e-6, norm_eps=1e-6: hc_pre(
        x, phi, scale, bias, iters=iters, eps=eps, norm_eps=norm_eps),
    n_outputs=3)
op_registry.register_pure("HyperConnectionPost", hc_post)


def _make(op_type, inputs, attrs, name, n_out=1):
    return op_util.make_op(op_type,
                           [ops_mod.convert_to_tensor(t) for t in inputs],
                           attrs=attrs, name=name, n_out=n_out)


def hc_pre_op(x, phi, scale, bias, *, iters, eps, norm_eps, name=None):
    """Returns ``(H_pre X, post, res)`` (:func:`hc_pre`)."""
    return _make("HyperConnectionPre", [x, phi, scale, bias],
                 {"iters": int(iters), "eps": float(eps),
                  "norm_eps": float(norm_eps)},
                 name or "hc_pre", n_out=3)


def hc_post_op(x, f, post, res, name=None):
    return _make("HyperConnectionPost", [x, f, post, res], {},
                 name or "hc_post")
