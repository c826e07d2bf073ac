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

The op also returns how many LIVE rows each expert got (``row_mask``
leaves a bucket's padding rows out of the count).

Attributes beyond the default block (each leaves the default lowering as
it was when it is not given):

  score      ``"softmax"`` (default) or ``"sigmoid"``: ``s = sigmoid(x .
             Wr)``, the gates ``s_e / (sum_selected s + 1e-20)``.
  bias       an input, ``(E,)`` float32: added to the scores to CHOOSE
             the ``top_k`` experts and to nothing else — the gates weigh
             with the scores as they were.
  gate_scale a factor on the gates after normalisation.
  held       ``(first, count)``: this chip holds experts ``[first, first
             + count)`` of the ``E`` the router scores — ``w_gate_up`` and
             ``w_down`` carry ``count`` experts. The router still scores
             all ``E`` and picks ``top_k``; only the pairs whose expert is
             held are dispatched, and ``y`` is this chip's PART of the
             sum. ``counts`` is over the held experts.
  activation ``"swiglu"`` (default) or ``"relu2"``: an expert is
             ``relu(x . w_up)^2 . w_down`` — NO gate matrix: the third
             input is ``w_up (E, H, I)``, not ``(E, H, 2*I)``.

The held dispatch follows the pairs that land here, not ``T * top_k``:
the pairs are sorted held-experts-first and taken in windows of
``held_window(T * top_k, count, E)`` rows — twice what an even router
sends here — one window a pass of a ``while`` loop that runs ``ceil(
landed / window)`` times. Most calls make one pass; a call on which
more land makes a second, none is dropped, and one on which none land
makes none. There is no code for the absent experts or their exchange.

THE DENSE FORM (relu2 held calls of at most ``_DENSE_MAX_ROWS`` rows).
The TPU's grouped matmul costs a fixed time a GROUP whatever rows it
gets: at 64 held experts of 2688 x 1920 one ``ragged_dot`` took 8.1 ms
at 256 rows and 11.0 ms at 12,288 (0.8 ms by its bytes), so a decode
step of 256 rows spent 18 ms a routed layer, 64 % of its device time, on
12 rows an expert. Every held expert over every row — two batched
matmuls and a gate-weighed sum, 21 times the needed FLOPs on an MXU that
a decode step leaves idle — took 2.4 ms at 256 rows and 16.0 ms at 2048
(my chip runs, PR 35): the same numbers but for the order of a sum. It
is taken where its float32 ``(held, rows, H)`` output stays small (352 MB
at 512 rows); longer calls keep the grouped form. The swiglu form keeps
the grouped matmul at every size in this PR (a 12-expert, 32-row call
would take the dense form by the same argument: its own pair of runs
decides).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework import graph as ops_mod
from ..framework import op_registry
from . import op_util


def route(x, w_router, *, top_k, norm_topk, score="softmax", bias=None,
          gate_scale=1.0):
    """``(experts (T, k) int32, gates (T, k) float32)``."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if score == "softmax" and bias is None:
        top_p, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1),
                                       top_k)
        norm = jnp.sum(top_p, axis=-1, keepdims=True)
    else:
        scores = (jax.nn.sigmoid(logits) if score == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        # the bias chooses, it does not weigh
        choice = scores if bias is None else scores + bias.astype(
            jnp.float32)
        _, experts = jax.lax.top_k(choice, top_k)
        top_p = jnp.take_along_axis(scores, experts, axis=-1)
        norm = jnp.sum(top_p, axis=-1, keepdims=True) + 1e-20
    if norm_topk:
        top_p = top_p / norm
    if gate_scale != 1.0:
        top_p = top_p * gate_scale
    return experts.astype(jnp.int32), top_p


def held_window(pairs, count, num_experts):
    """Rows one pass of the held dispatch takes: twice the pairs an even
    router sends to ``count`` of ``num_experts`` experts, a multiple of
    8, never more than the pairs rounded up to one (a call of one token
    and six experts a token takes 8 rows, two of them padding: the TPU's
    grouped matmul returned wrong rows for a 6-row operand; my chip run,
    PR 35)."""
    even = -(-pairs * count // num_experts)
    return min(-(-pairs // 8) * 8, max(8, -(-2 * even // 8) * 8))


def _experts(rows, w_gate_up, w_down, group_sizes, activation="swiglu"):
    """The two grouped matmuls around ``silu(gate) * up`` — or, with
    ``relu2``, around ``relu(up)^2`` — float32 out."""
    width = w_down.shape[1]
    h = jax.lax.ragged_dot(rows, w_gate_up, group_sizes,
                           preferred_element_type=jnp.float32)
    if activation == "relu2":
        h = jnp.square(jax.nn.relu(h)).astype(w_down.dtype)
    else:
        h = (jax.nn.silu(h[:, :width]) * h[:, width:]).astype(w_down.dtype)
    return jax.lax.ragged_dot(h, w_down, group_sizes,
                              preferred_element_type=jnp.float32)


def _live_counts(one_hot, group_sizes, row_mask, top_k):
    """Rows per expert, a bucket's padding rows (``row_mask`` False) left
    out; ``one_hot`` is (pairs, experts)."""
    if row_mask is None:
        return group_sizes
    live = jnp.repeat(row_mask.astype(jnp.int32), top_k)
    return jnp.sum(one_hot * live[:, None], axis=0, dtype=jnp.int32)


# the most rows a relu2 held call takes EVERY held expert over (module
# docstring, "THE DENSE FORM")
_DENSE_MAX_ROWS = 512


def _held_dense(x, experts, gates, w_up, w_down, row_mask, held):
    """The relu2 held call's dense form: every held expert over every row,
    two batched matmuls, each row's outputs weighed by its gates (zero for
    an expert it did not choose) in float32."""
    t, top_k = experts.shape
    first, count = held
    local = experts - first                                   # (T, k)
    one_hot = local[:, :, None] == jnp.arange(count, dtype=jnp.int32)
    weigh = jnp.sum(jnp.where(one_hot, gates[:, :, None], 0.0), axis=1)
    h = jnp.einsum("th,ehi->eti", x.astype(w_up.dtype), w_up,
                   preferred_element_type=jnp.float32)
    h = jnp.square(jax.nn.relu(h)).astype(w_down.dtype)
    out = jnp.einsum("eti,eih->eth", h, w_down,
                     preferred_element_type=jnp.float32)
    y = jnp.einsum("eth,te->th", out, weigh)
    pairs = one_hot.reshape(t * top_k, count)
    return y.astype(x.dtype), _live_counts(
        pairs, jnp.sum(pairs, axis=0, dtype=jnp.int32), row_mask, top_k)


def _held_ffn(x, experts, gates, w_gate_up, w_down, row_mask, held,
              num_experts, activation="swiglu"):
    """This chip's part of the routed sum (module docstring, ``held``)."""
    t, top_k = experts.shape
    first, count = held
    if activation == "relu2" and t <= _DENSE_MAX_ROWS:
        return _held_dense(x, experts, gates, w_gate_up, w_down, row_mask,
                           held)
    pairs = t * top_k
    window = held_window(pairs, count, num_experts)
    local = experts.reshape(-1) - first                      # (T*k,)
    local = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(local, stable=True)      # held first, by expert
    one_hot = local[:, None] == jnp.arange(count, dtype=jnp.int32)
    group_sizes = jnp.sum(one_hot, axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(group_sizes)
    starts, landed = ends - group_sizes, ends[-1]
    # a window past the last pair reads padding, never another pass's rows
    order = jnp.concatenate([order, jnp.zeros((window,), order.dtype)])
    flat_gates = gates.reshape(-1)
    lane = jnp.arange(window, dtype=jnp.int32)

    def one_pass(i, y):
        lo = i * window
        pair = jax.lax.dynamic_slice_in_dim(order, lo, window)
        sizes = jnp.clip(jnp.minimum(ends, lo + window)
                         - jnp.maximum(starts, lo), 0, window)
        token = pair // top_k
        out = _experts(x[token].astype(w_gate_up.dtype), w_gate_up,
                       w_down, sizes, activation)
        # rows past the landed pairs belong to no group: their product
        # is not defined, so they are selected away, not multiplied
        out = jnp.where((lo + lane < landed)[:, None],
                        out * flat_gates[pair][:, None], 0.0)
        return y.at[token].add(out)

    y = jax.lax.fori_loop(0, -(-landed // window), one_pass,
                          jnp.zeros((t, w_down.shape[-1]), jnp.float32))
    return y.astype(x.dtype), _live_counts(one_hot, group_sizes, row_mask,
                                           top_k)


def routed_ffn(x, w_router, w_gate_up, w_down, row_mask=None, *, top_k,
               norm_topk=True, score="softmax", bias=None, gate_scale=1.0,
               held=None, activation="swiglu"):
    """``x (T, H)``; ``w_router (H, E)``; ``w_gate_up (E, H, 2*I)`` (gate
    columns first; ``(E, H, I)``, the up projection alone, with
    ``activation="relu2"``); ``w_down (E, I, H)`` — with ``held = (first,
    count)`` the two carry ``count`` experts. Returns ``(y (T, H)`` in
    ``x``'s dtype, ``counts`` int32 over the experts held``)``."""
    t, _ = x.shape
    experts, gates = route(x, w_router, top_k=top_k, norm_topk=norm_topk,
                           score=score, bias=bias, gate_scale=gate_scale)
    if held is not None:
        return _held_ffn(x, experts, gates, w_gate_up, w_down, row_mask,
                         tuple(int(v) for v in held), w_router.shape[1],
                         activation)
    num_experts = w_down.shape[0]
    flat = experts.reshape(-1)                               # (T*k,)
    order = jnp.argsort(flat, stable=True)
    one_hot = flat[:, None] == jnp.arange(num_experts, dtype=jnp.int32)
    group_sizes = jnp.sum(one_hot, axis=0, dtype=jnp.int32)
    rows = x[order // top_k].astype(w_gate_up.dtype)
    y = _experts(rows, w_gate_up, w_down, group_sizes, activation)
    y = y[jnp.argsort(order)].reshape(t, top_k, -1)          # pair order
    y = jnp.sum(y * gates[:, :, None], axis=1).astype(x.dtype)
    return y, _live_counts(one_hot, group_sizes, row_mask, top_k)


def _lower(x, w_router, w_gate_up, w_down, *rest, top_k=1, norm_topk=True,
           score="softmax", gate_scale=1.0, held=None, has_bias=False,
           activation="swiglu"):
    # optional inputs, in the order ``routed_ffn_op`` appends them
    rest = list(rest)
    bias = rest.pop() if has_bias else None
    row_mask = rest.pop() if rest else None
    return routed_ffn(x, w_router, w_gate_up, w_down, row_mask, top_k=top_k,
                      norm_topk=norm_topk, score=score, bias=bias,
                      gate_scale=gate_scale, held=held, activation=activation)


op_registry.register_pure("RoutedFFN", _lower, n_outputs=2)


def routed_ffn_op(x, w_router, w_gate_up, w_down, row_mask=None, *, top_k,
                  norm_topk=True, score="softmax", bias=None,
                  gate_scale=1.0, held=None, activation="swiglu", name=None):
    """Graph op over ``x (T, H)``; see :func:`routed_ffn`. Returns
    ``(y, counts)``."""
    if score not in ("softmax", "sigmoid"):
        raise ValueError(f"score must be softmax or sigmoid, got {score!r}")
    if activation not in ("swiglu", "relu2"):
        raise ValueError("activation must be swiglu or relu2, got "
                         f"{activation!r}")
    inputs = [x, w_router, w_gate_up, w_down]
    attrs = {"top_k": int(top_k), "norm_topk": bool(norm_topk)}
    if row_mask is not None:
        inputs.append(row_mask)
    # what the default block does not use stays off its op
    if bias is not None:
        inputs.append(bias)
        attrs["has_bias"] = True
    if score != "softmax":
        attrs["score"] = score
    if gate_scale != 1.0:
        attrs["gate_scale"] = float(gate_scale)
    if held is not None:
        attrs["held"] = (int(held[0]), int(held[1]))
    if activation != "swiglu":
        attrs["activation"] = activation
    inputs = [ops_mod.convert_to_tensor(t) for t in inputs]
    return op_util.make_op("RoutedFFN", inputs, attrs=attrs,
                           name=name or "routed_ffn", n_out=2)
