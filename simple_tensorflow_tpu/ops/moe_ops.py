"""Routed feed-forward (mixture of experts) for serving: every token
goes to its ``top_k`` experts, none is dropped.

(ref: the reference has no routed layer.) One graph op, ``RoutedFFN``:

  router     ``p = softmax(x . Wr)`` over all experts, float32 (the
             product at the highest matmul precision: a token whose
             8th and 9th probabilities are close must not change
             experts on a rounding); ``lax.top_k`` (ties to the lower
             expert); gates ``p_e / sum_top p`` when ``norm_topk``.
  dispatch   the ``T * top_k`` (token, expert) pairs sorted by expert
             (stable), token rows gathered in that order: each expert's
             rows are contiguous, ``group_sizes`` counts them. Dropless:
             the row count is static (``T * top_k``), only the split
             between experts moves.
  experts    two grouped matmuls, ``jax.lax.ragged_dot`` — on a TPU the
             compiler's own grouped-matmul kernel, 2*m*k*n FLOPs
             whatever the split — around ``silu(gate) * up``.
  combine    rows back in pair order, weighted by the gates, summed per
             token in float32.

Every expert is held by the caller: there is no code for absent experts
or their exchange. The op also returns how many LIVE rows each expert
got (``row_mask`` leaves a bucket's padding rows out of the count).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework import graph as ops_mod
from ..framework import op_registry
from . import op_util


def route(x, w_router, *, top_k, norm_topk):
    """``(experts (T, k) int32, gates (T, k) float32)``."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    top_p, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), top_p


def routed_ffn(x, w_router, w_gate_up, w_down, row_mask=None, *, top_k,
               norm_topk=True):
    """``x (T, H)``; ``w_router (H, E)``; ``w_gate_up (E, H, 2*I)`` (gate
    columns first); ``w_down (E, I, H)``. Returns ``(y (T, H)`` in
    ``x``'s dtype, ``counts (E,)`` int32``)``."""
    t, _ = x.shape
    num_experts, width = w_down.shape[0], w_down.shape[1]
    experts, gates = route(x, w_router, top_k=top_k, norm_topk=norm_topk)
    flat = experts.reshape(-1)                               # (T*k,)
    order = jnp.argsort(flat, stable=True)
    one_hot = flat[:, None] == jnp.arange(num_experts, dtype=jnp.int32)
    group_sizes = jnp.sum(one_hot, axis=0, dtype=jnp.int32)
    rows = x[order // top_k].astype(w_gate_up.dtype)
    h = jax.lax.ragged_dot(rows, w_gate_up, group_sizes,
                           preferred_element_type=jnp.float32)
    h = (jax.nn.silu(h[:, :width]) * h[:, width:]).astype(w_down.dtype)
    y = jax.lax.ragged_dot(h, w_down, group_sizes,
                           preferred_element_type=jnp.float32)
    y = y[jnp.argsort(order)].reshape(t, top_k, -1)          # pair order
    y = jnp.sum(y * gates[:, :, None], axis=1).astype(x.dtype)
    if row_mask is None:
        counts = group_sizes
    else:
        live = jnp.repeat(row_mask.astype(jnp.int32), top_k)
        counts = jnp.sum(one_hot * live[:, None], axis=0, dtype=jnp.int32)
    return y, counts


op_registry.register_pure(
    "RoutedFFN",
    lambda x, w_router, w_gate_up, w_down, row_mask=None, top_k=1,
    norm_topk=True: routed_ffn(x, w_router, w_gate_up, w_down, row_mask,
                               top_k=top_k, norm_topk=norm_topk),
    n_outputs=2)


def routed_ffn_op(x, w_router, w_gate_up, w_down, row_mask=None, *, top_k,
                  norm_topk=True, name=None):
    """Graph op over ``x (T, H)``; see :func:`routed_ffn`. Returns
    ``(y, counts)``."""
    inputs = [x, w_router, w_gate_up, w_down]
    if row_mask is not None:
        inputs.append(row_mask)
    inputs = [ops_mod.convert_to_tensor(t) for t in inputs]
    return op_util.make_op("RoutedFFN", inputs,
                           attrs={"top_k": int(top_k),
                                  "norm_topk": bool(norm_topk)},
                           name=name or "routed_ffn", n_out=2)
