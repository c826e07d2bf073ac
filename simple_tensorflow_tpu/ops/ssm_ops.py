"""Selective state-space (Mamba-2) layer ops for serving: the recurrence
over a prompt chunk and over one decode token, its causal convolution with
a carried window, and the gated group norm behind it.

(ref: the reference has no recurrent-state serving path; the mechanism is
the state-space duality layer of recent hybrid decoders.) For one head
with scalar ``A < 0``, step sizes ``dt_t > 0`` and a group's ``B_t, C_t
(state,)``::

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t     h: (head_dim, state)
    y_t = h_t C_t + D x_t

A sequence's ``h`` and the last ``taps - 1`` inputs of its convolution are
NOT rows addressed by position: they live in pools addressed by SLOT
(``kv_cache_ops.StatePool``), and every op here reads a row's state from
its slot, advances it and writes it back — one op, declared as a
read-modify-write of the store entry, the pool donated and updated in
place.

  CausalConv1D   depthwise causal convolution along a chunk ``(rows, L,
                 C)`` — each row's entering window from its slot, its
                 leaving window (the inputs behind its last REAL token)
                 back to it — or one token a row ``(rows, C)``.
  SSMChunkScan   PREFILL: the recurrence over a chunk a row, in blocks of
                 ``chunk`` tokens: inside a block the quadratic form (``C
                 B^T`` masked by the cumulative decay, times ``dt x``),
                 between blocks and between ROWS the state.
  SSMStateUpdate DECODE: one token a row; routed through stf.kernels
                 (``ops/pallas/ssm_state_update.py`` in place, or the
                 gather-update-scatter composition).
  GatedRMSNorm   ``RMSNorm_groups(y * silu(z)) * gamma``: the gate first,
                 then the norm, over groups of the last axis.

ROWS OF ONE CALL. A prefill call's rows are page chunks in the order the
engine gives them — by ``(base, slot)``, so a slot's rows come in order of
``base``. The chunk ops walk the rows IN THAT ORDER: a row's entering
state is zero when ``fresh`` (its ``base`` is 0: a slot is re-used) and
otherwise what the pool holds for its slot — left there by an earlier row
of this call or by an earlier call — and its leaving state goes back to
the pool before the next row reads. ``lens`` is the real tokens of each
row: past them ``dt = 0`` (decay 1, no input), so the state and the
carried window are those after the LAST REAL token whatever pads the
chunk. The walk touches states only (elementwise over ``rows`` small
steps); every matmul of the chunk runs over all rows at once.

Serving-only (no gradients). Decays, cumulative sums and states are
float32; matmul operands keep their dtype with float32 accumulation (the
highest precision when they are float32).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework import dtypes as dtypes_mod
from ..framework import graph as ops_mod
from ..framework import op_registry
from ..framework import tensor_shape as shape_mod
from ..kernels import registry as _kreg
from . import op_util
from .pallas.ssm_state_update import from_pool_layout, to_pool_layout

_HI = jax.lax.Precision.HIGHEST
_NEG_INF = float("-inf")


# ---------------------------------------------------------------------------
# jax-level functions
# ---------------------------------------------------------------------------

def gated_rms_norm(y, z, gamma, *, groups, eps):
    """``y, z (..., W)``; ``W % groups == 0``. Float32 inside, ``y``'s
    dtype out."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    lead, w = g.shape[:-1], g.shape[-1]
    g = g.reshape(lead + (groups, w // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return (g.reshape(lead + (w,)) * gamma.astype(jnp.float32)).astype(
        y.dtype)


def _entering(held, leaving, slots, fresh, i):
    """Row ``i``'s entering state: zero when ``fresh``; else what the
    latest EARLIER row of this call with the same slot leaves (``leaving``
    holds rows ``< i``); else what the pool held (``held[i]``)."""
    h = held[i]
    for j, left in enumerate(leaving):
        h = jnp.where(slots[j] == slots[i], left, h)
    return jnp.where(fresh[i], jnp.zeros_like(h), h)


def _write_back(pool, slots, leaving):
    """The rows' leaving states into the pool in ONE scatter: of the rows
    that share a slot the last one's (the others go to the scratch slot,
    the pool's last row, which every row that uses it starts ``fresh``).
    The rows are gathered once and scattered once — how ``KVCacheAppend``
    updates its donated pool in place; a chain of row-sized slices and
    updates made the TPU compiler carry the whole pool through the chain
    in another layout, two pool-sized copies a call (described-chip
    compile, PR 35)."""
    rows = slots.shape[0]
    later = (slots[:, None] == slots[None, :]) & (
        jnp.arange(rows)[:, None] < jnp.arange(rows)[None, :])
    index = jnp.where(jnp.any(later, axis=1), pool.shape[0] - 1, slots)
    return pool.at[index].set(leaving.astype(pool.dtype))


def causal_conv1d(pool, x, w, bias, slots, fresh, lens=None):
    """Depthwise causal convolution with the window carried in ``pool
    (slots, (taps - 1) * C)`` — a slot's last ``taps - 1`` inputs, oldest
    first, flat on one lane-dense axis (a ``(slots, 3, C)`` pool is relaid
    by the TPU compiler at every call: 3 rows fill no sublane tile). ``w
    (taps, C)``: ``out[t] = bias + sum_k w[k] in[t - (taps - 1) + k]`` (the
    last tap meets the current token). ``x (rows, L, C)`` with ``lens
    (rows,)`` real tokens a row, or one token a row ``x (rows, C)``.
    Returns ``(out`` like ``x``, the pool``)``."""
    taps, c = w.shape
    wf, bf = w.astype(jnp.float32), bias.astype(jnp.float32)
    slots = jnp.asarray(slots, jnp.int32)
    fresh = jnp.asarray(fresh, bool)
    if x.ndim == 2:
        window = jnp.where(fresh[:, None], 0, pool[slots]).reshape(
            -1, taps - 1, c)
        ext = jnp.concatenate([window, x[:, None].astype(pool.dtype)], 1)
        out = bf + jnp.einsum("bkc,kc->bc", ext.astype(jnp.float32), wf)
        return out.astype(x.dtype), pool.at[slots].set(
            ext[:, 1:].reshape(-1, (taps - 1) * c))
    rows, length, _ = x.shape
    lens = jnp.asarray(lens, jnp.int32)
    xs = x.astype(pool.dtype)
    held = pool[slots].reshape(rows, taps - 1, c)
    entering, leaving = [], []
    # the rows in the order given: a row's window may be what an earlier
    # row of this call leaves
    for i in range(rows):
        window = _entering(held, leaving, slots, fresh, i)
        entering.append(window)
        leaving.append(jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([window, xs[i]], 0), lens[i], taps - 1, 0))
    pool = _write_back(pool, slots, jnp.stack(leaving).reshape(rows, -1))
    ext = jnp.concatenate([jnp.stack(entering), xs], 1).astype(jnp.float32)
    out = bf + sum(wf[k] * ext[:, k:k + length] for k in range(taps))
    return out.astype(x.dtype), pool


def ssm_chunk_scan(pool, x, dt, a, bm, cm, d, slots, fresh, lens, *, chunk):
    """The recurrence over a chunk a row (module docstring). pool:
    ``(slots, J, N, W)`` float32 in ``pallas/ssm_state_update``'s layout;
    ``x (R, L, H, P)``; ``dt (R, L, H)`` positive; ``a, d (H,)``; ``bm,
    cm (R, L, G, N)``; ``slots, fresh, lens (R,)``. ``L`` is a multiple of
    ``min(chunk, L)``. Returns ``(y (R, L, H, P)`` in ``x``'s dtype, the
    pool``)``."""
    r, length, heads, p = x.shape
    groups, n = bm.shape[2], bm.shape[3]
    q = min(int(chunk), length)
    if length % q:
        raise ValueError(f"chunk {q} does not divide the rows' {length}")
    nc, k = length // q, heads // groups
    pack = pool.shape[-1] // p
    f32 = jnp.float32
    hi = _HI if x.dtype == f32 else None
    slots = jnp.asarray(slots, jnp.int32)
    fresh = jnp.asarray(fresh, bool)
    live = jnp.arange(length)[None, :] < jnp.asarray(lens, jnp.int32)[:, None]
    dtm = jnp.where(live[:, :, None], dt.astype(f32), 0.0)
    dtc = dtm.reshape(r, nc, q, groups, k)
    cum = jnp.cumsum(dtc * a.astype(f32).reshape(groups, k), axis=2)
    xc = x.reshape(r, nc, q, groups, k, p)
    bc = bm.reshape(r, nc, q, groups, n)
    cc = cm.reshape(r, nc, q, groups, n)

    # inside a block: y[t] = sum_{s <= t} (C_t . B_s) exp(cum_t - cum_s)
    # dt_s x_s
    gmat = jnp.einsum("rcqgn,rcsgn->rcgqs", cc, bc, precision=hi,
                      preferred_element_type=f32)
    seg = (jnp.moveaxis(cum, 2, -1)[..., :, None]
           - jnp.moveaxis(cum, 2, -1)[..., None, :])        # (R,nc,G,K,q,s)
    seen = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    weights = (gmat[:, :, :, None] * jnp.exp(jnp.where(seen, seg, _NEG_INF))
               * jnp.moveaxis(dtc, 2, -1)[..., None, :])
    y = jnp.einsum("rcgkqs,rcsgkp->rcqgkp", weights.astype(x.dtype), xc,
                   precision=hi, preferred_element_type=f32)

    # a block's own state (from zero) and its whole decay
    to_end = jnp.exp(cum[:, :, -1:] - cum) * dtc            # (R,nc,q,G,K)
    state = jnp.einsum("rcsgkp,rcsgn->rcgkpn",
                       (to_end[..., None] * xc.astype(f32)).astype(x.dtype),
                       bc, precision=hi, preferred_element_type=f32)
    decay = jnp.exp(cum[:, :, -1])                          # (R,nc,G,K)

    # between blocks and between rows: the state, in the order given, in
    # the POOL'S layout (a slot's row is scaled and added to as it lies)
    state = to_pool_layout(state.reshape(r, nc, heads, p, n), pack)
    decay = jnp.broadcast_to(
        decay.reshape(r, nc, heads, 1), (r, nc, heads, p)).reshape(
        r, nc, heads // pack, 1, pack * p)
    held = pool[slots]
    entering, leaving = [], []
    for i in range(r):
        h = _entering(held, leaving, slots, fresh, i)
        for c in range(nc):
            entering.append(h)
            h = decay[i, c] * h + state[i, c]
        leaving.append(h)
    pool = _write_back(pool, slots, jnp.stack(leaving))
    entering = from_pool_layout(jnp.stack(entering), pack).reshape(
        r, nc, groups, k, p, n)
    y = y + jnp.exp(cum)[..., None] * jnp.einsum(
        "rcqgn,rcgkpn->rcqgkp", cc.astype(f32), entering, precision=hi,
        preferred_element_type=f32)
    y = y.reshape(r, length, heads, p) + (
        d.astype(f32)[:, None] * x.astype(f32))
    return y.astype(x.dtype), pool


# ---------------------------------------------------------------------------
# graph ops
# ---------------------------------------------------------------------------

op_registry.register_pure(
    "GatedRMSNorm",
    lambda y, z, gamma, groups=1, eps=1e-5: gated_rms_norm(
        y, z, gamma, groups=groups, eps=eps))


def gated_rms_norm_op(y, z, gamma, *, groups, eps, name=None):
    """``RMSNorm_groups(y * silu(z)) * gamma`` over ``groups`` groups of
    the last axis (:func:`gated_rms_norm`)."""
    return op_util.make_op(
        "GatedRMSNorm", [ops_mod.convert_to_tensor(t) for t in (y, z, gamma)],
        attrs={"groups": int(groups), "eps": float(eps)},
        name=name or "gated_rms_norm")


def _pool_update_op(op_type, pool, inputs, out, name, **attrs):
    """One op that advances ``pool``'s rows in place and returns ``out
    (shape, dtype)``; the pool is named in its attributes."""
    op = ops_mod.get_default_graph().create_op(
        op_type, [ops_mod.convert_to_tensor(t) for t in inputs],
        attrs={**pool._attrs(), **attrs}, name=name,
        output_specs=[(shape_mod.TensorShape(out[0]), out[1])])
    return op


def _ints(t):
    return ops_mod.convert_to_tensor(t, dtype=dtypes_mod.int32)


def _flags(t):
    return ops_mod.convert_to_tensor(t, dtype=dtypes_mod.bool_)


def causal_conv1d_op(x, w, bias, pool, slots, fresh, lens=None, name=None):
    """:func:`causal_conv1d` against the window pool ``pool`` (a
    ``StatePool`` of inner shape ``((taps - 1) * C,)``). Returns the
    convolved tensor; its op is what later reads of the pool order
    after."""
    x = ops_mod.convert_to_tensor(x)
    inputs = [x, w, bias, _ints(slots), _flags(fresh)]
    if x.shape.rank == 3:
        if lens is None:
            raise ValueError("a chunk (rows, L, C) needs lens")
        inputs.append(_ints(lens))
    op = _pool_update_op("CausalConv1D", pool, inputs,
                         (x.shape.as_list(), x.dtype),
                         name or "causal_conv1d")
    return op.outputs[0]


def _lower_causal_conv1d(ctx, op, inputs):
    name = op.attrs["var_name"]
    out, pool = causal_conv1d(ctx.read_var(name, op), *inputs)
    ctx.write_var(name, pool)
    return [out]


def ssm_chunk_scan_op(x, dt, a, bm, cm, d, pool, slots, fresh, lens, *,
                      chunk, name=None):
    """:func:`ssm_chunk_scan` against the state pool ``pool``."""
    x = ops_mod.convert_to_tensor(x)
    op = _pool_update_op(
        "SSMChunkScan", pool,
        [x, dt, a, bm, cm, d, _ints(slots), _flags(fresh),
         _ints(lens)],
        (x.shape.as_list(), x.dtype), name or "ssm_chunk_scan",
        chunk=int(chunk))
    return op.outputs[0]


def _lower_ssm_chunk_scan(ctx, op, inputs):
    name = op.attrs["var_name"]
    y, pool = ssm_chunk_scan(ctx.read_var(name, op), *inputs,
                             chunk=op.attrs["chunk"])
    ctx.write_var(name, pool)
    return [y]


def ssm_state_update_op(x, dt, a, bm, cm, d, pool, slots, fresh, name=None):
    """One token a row: ``x (B, H, P)``, ``dt (B, H)``, ``bm, cm (B, G,
    N)`` against the state pool ``pool``, updated in place
    (``pallas/ssm_state_update.py``). Routed through stf.kernels."""
    x = ops_mod.convert_to_tensor(x)
    op = _pool_update_op(
        "SSMStateUpdate", pool,
        [x, dt, a, bm, cm, d, _ints(slots), _flags(fresh)],
        (x.shape.as_list(), x.dtype), name or "ssm_state_update")
    return op.outputs[0]


def _lower_ssm_state_update(ctx, op, inputs):
    name = op.attrs["var_name"]
    pool = ctx.read_var(name, op)
    x, _, _, bm = inputs[:4]
    fn = _kreg.select("SSMStateUpdate", _kreg.aval_key(x, pool, bm))
    y, pool = fn(pool, *inputs)
    ctx.write_var(name, pool)
    return [y]


for _type, _lower in (("CausalConv1D", _lower_causal_conv1d),
                      ("SSMChunkScan", _lower_ssm_chunk_scan),
                      ("SSMStateUpdate", _lower_ssm_state_update)):
    op_registry.register(
        _type, lower=_lower,
        effects=op_registry.Effects(writes=("var_name",), update="update"))
