"""Gated delta-rule linear attention (KDA) for serving: the recurrence over
a prompt chunk and over one decode token, each row's matrix state in a
pool addressed by slot.

(ref: the reference has no linear-attention layer; the mechanism is Kimi
Delta Attention, arXiv:2510.26692.) For one head with unit-norm ``q_t,
k_t (d_k,)``, ``v_t (d_v,)``, ``beta_t`` in (0, 1) and a log-decay a KEY
channel ``g_t (d_k,) <= 0``::

    S'_t = Diag(exp g_t) S_{t-1}          S: (d_k, d_v), float32
    u_t  = beta_t (v_t - S'_t^T k_t)
    S_t  = S'_t + k_t u_t^T   = (I - beta k k^T) Diag(exp g) S + beta k v^T
    o_t  = S_t^T q_t * scale

A sequence's ``S`` is not a row addressed by position: it lives in a
``kv_cache_ops.StatePool`` addressed by SLOT, as a Mamba-2 layer's state
does (``ops/ssm_ops.py``, whose conventions hold here: ``fresh`` rows start
from zero, a call's rows are walked IN THE ORDER GIVEN, past ``lens`` the
step is the identity — ``g = 0``, ``beta = 0`` — so the state left is the
one after the last real token).

  KDAChunkScan    PREFILL: the recurrence over a chunk a row, in blocks of
                  ``chunk`` tokens (below), between blocks and between
                  ROWS the state.
  KDAStateUpdate  DECODE: one token a row; routed through stf.kernels
                  (``ops/pallas/kda_state_update.py`` in place, or the
                  gather-update-scatter composition).

THE CHUNKED FORM, exact to the recurrence. Inside a block of ``C`` tokens
let ``G_t = sum_{s <= t} g_s`` (per channel) and ``S_0`` the entering
state. Unrolled, ``S_t = e^{G_t} (.) S_0 + sum_{s <= t} Diag(e^{G_t - G_s})
k_s u_s^T``, so ``u = T (beta V - W S_0)`` with ``W_t = beta_t k_t (.)
e^{G_t}`` and ``T = (I + A)^-1``, ``A_ts = beta_t sum_c k_t[c] k_s[c]
e^{G_t[c] - G_s[c]}`` (s < t); the output is ``o = (Q_g S_0 + B u) *
scale`` with ``Q_g = q (.) e^G`` and ``B_ts = sum_c q_t[c] k_s[c] e^{G_t[c] -
G_s[c]}`` (s <= t); the leaving state ``e^{G_C} (.) S_0 + K_end^T u`` with
``K_end = k (.) e^{G_C - G}``. Every decay ratio is ``exp(G_i - G_j)`` with
``i >= j`` — never ``exp(-G_j)`` alone: over 64 tokens at the gate's bound
of -5 the cumulative log-decay reaches -320, and ``e^{320}`` is no float32.
``A`` and ``B`` are a per-channel product a PAIR of positions (a sum over
``c`` of an elementwise product, not a matmul) inside a sub-block of 16,
and between sub-blocks one matmul through the earlier sub-block's last
position (``_pair_products``); ``T [beta V | W]`` is solved by blocks of
16, each diagonal block inverted a row a step. Then a block is the
affine map ``S_0 -> M S_0 + N`` (``M = Diag(e^{G_C}) - K_end^T T W``) and
the walk over blocks and rows is one ``(d_k, d_k) x (d_k, d_v)`` product a
block and head. States, decays and every product are float32 at the
highest matmul precision.

Serving-only (no gradients).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework import dtypes as dtypes_mod
from ..framework import graph as ops_mod
from ..framework import op_registry
from ..kernels import registry as _kreg
from .ssm_ops import (_entering, _flags, _ints, _pool_update_op,
                      _write_back)

_HI = jax.lax.Precision.HIGHEST


def _ein(spec, *ops):
    return jnp.einsum(spec, *ops, precision=_HI,
                      preferred_element_type=jnp.float32)


# positions of a SUB-block: the pair products within one are made channel
# by channel; between two, by a matmul through a reference position
_SUB = 16


def _pair_products(q, k, cum):
    """``A_ts / beta_t`` (s < t) and ``B_ts`` (s <= t) of a block of ``C``
    positions: ``q, k, cum (n, C, d_k)`` -> two ``(n, C, C)``.

    Within a sub-block of ``_SUB`` positions a pair's decay is made
    channel by channel, ``exp(G_t - G_s)``, and summed at once. Between
    sub-block ``a`` and a later row ``t`` it factors through ``r``, the
    last position of ``a``: ``exp(G_t - G_s) = exp(G_t - G_r) exp(G_r -
    G_s)`` with ``t > r >= s``, both factors a ratio of a later position
    over an earlier one (at most 1; where the first underflows the pair's
    own decay is below it), so the block is ONE matmul of ``(k_t or q_t)
    (.) exp(G_t - G_r)`` with ``k_s (.) exp(G_r - G_s)`` — a quarter of
    the elementwise work of a whole block's pairs, the rest on the MXU."""
    n, c, dk = q.shape
    sub = min(_SUB, c)
    m = c // sub
    pos = jnp.arange(c)

    def within(x, y):
        xs, ys, gs = (t.reshape(n, m, sub, dk) for t in (x, y, cum))
        seen = jnp.arange(sub)[:, None] >= jnp.arange(sub)[None, :]
        decay = jnp.exp(jnp.where(seen[:, :, None],
                                  gs[:, :, :, None, :] - gs[:, :, None],
                                  -jnp.inf))
        blocks = jnp.sum(xs[:, :, :, None] * ys[:, :, None] * decay, -1)
        # (n, m, sub, sub) on the block diagonal of (n, C, C)
        return jnp.einsum("nats,ab->natbs", blocks,
                          jnp.eye(m, dtype=blocks.dtype)).reshape(n, c, c)

    def between(x, y):
        if m == 1:
            return jnp.zeros((n, c, c), jnp.float32)
        ref = jnp.arange(m - 1) * sub + sub - 1                 # r_a
        g_ref = cum[:, ref]                                     # (n, a, K)
        later = (pos[None, :] > ref[:, None])[None, :, :, None]
        rows = x[:, None] * jnp.exp(jnp.where(
            later, cum[:, None] - g_ref[:, :, None], -jnp.inf))  # (n,a,C,K)
        cols = y.reshape(n, m, sub, dk)[:, :m - 1] * jnp.exp(
            g_ref[:, :, None] - cum.reshape(n, m, sub, dk)[:, :m - 1])
        out = _ein("natk,nask->ntas", rows, cols).reshape(n, c, c - sub)
        return jnp.concatenate(
            [out, jnp.zeros((n, c, sub), jnp.float32)], axis=-1)

    kk = within(k, k) + between(k, k)
    qk = within(q, k) + between(q, k)
    return jnp.where(pos[:, None] > pos[None, :], kk, 0.0), qk


def _unit_lower_solve(a, rhs):
    """``(I + a)^-1 rhs`` for strictly lower ``a (n, C, C)`` by blocks of
    ``_SUB``: each diagonal block inverted by forward substitution (a row
    a step, all blocks at once), then the blocks of rows in order, each a
    matmul with the rows above it."""
    n, c, _ = a.shape
    sub = min(_SUB, c)
    m = c // sub
    diag = jnp.stack([a[:, i * sub:(i + 1) * sub, i * sub:(i + 1) * sub]
                      for i in range(m)], 1)                # (n, m, s, s)
    eye = jnp.broadcast_to(jnp.eye(sub, dtype=a.dtype), diag.shape)

    def row(t, inv):
        return inv.at[:, :, t].set(
            eye[:, :, t] - _ein("nms,nmsj->nmj", diag[:, :, t], inv))

    inv = jax.lax.fori_loop(0, sub, row, jnp.zeros_like(diag))
    out = []
    for i in range(m):
        r = rhs[:, i * sub:(i + 1) * sub]
        if i:
            r = r - _ein("nts,nsv->ntv", a[:, i * sub:(i + 1) * sub,
                                           :i * sub],
                         jnp.concatenate(out, axis=1))
        out.append(_ein("nts,nsv->ntv", inv[:, i], r))
    return jnp.concatenate(out, axis=1)


def kda_chunk_scan(pool, q, k, v, g, beta, slots, fresh, lens, *, chunk,
                   scale):
    """The recurrence over a chunk a row (module docstring). pool:
    ``(slots, H, d_k, d_v)`` float32; ``q, k, g (R, L, H, d_k)``; ``v (R,
    L, H, d_v)``; ``beta (R, L, H)``; ``slots, fresh, lens (R,)``. ``L`` is
    a multiple of ``min(chunk, L)``. Returns ``(o (R, L, H, d_v)``
    float32, the pool``)``."""
    r, length, heads, dk = q.shape
    dv = v.shape[-1]
    c = min(int(chunk), length)
    if length % c:
        raise ValueError(f"chunk {c} does not divide the rows' {length}")
    nc = length // c
    f32 = jnp.float32
    slots = jnp.asarray(slots, jnp.int32)
    fresh = jnp.asarray(fresh, bool)
    live = jnp.arange(length)[None, :] < jnp.asarray(lens, jnp.int32)[:, None]
    # past the real tokens the step is the identity: no decay, no update
    g = jnp.where(live[:, :, None, None], g.astype(f32), 0.0)
    beta = jnp.where(live[:, :, None], beta.astype(f32), 0.0)

    def blocks(x):              # (R, L, H, X) -> (R * nc * H, C, X)
        x = x.astype(f32).reshape(r, nc, c, heads, -1)
        return jnp.moveaxis(x, 3, 2).reshape(r * nc * heads, c, -1)

    qb, kb, vb, gb = blocks(q), blocks(k), blocks(v), blocks(g)
    bb = blocks(beta[..., None])                        # (n, C, 1)
    cum = jnp.cumsum(gb, axis=1)                        # G, <= 0
    kk, qk = _pair_products(qb, kb, cum)
    # T [beta V | W] with T = (I + A)^-1
    u = _unit_lower_solve(bb * kk, jnp.concatenate(
        [bb * vb, bb * kb * jnp.exp(cum)], axis=-1))
    u0, uw = u[..., :dv], u[..., dv:]
    last = cum[:, -1:, :]                               # G_C
    k_end = kb * jnp.exp(last - cum)
    m = (jnp.eye(dk, dtype=f32) * jnp.exp(last)
         - _ein("nsi,nsj->nij", k_end, uw))             # (n, K, K)
    nn_ = _ein("nsi,nsv->niv", k_end, u0)               # (n, K, V)
    p = qb * jnp.exp(cum) - _ein("nts,nsk->ntk", qk, uw)
    o0 = _ein("nts,nsv->ntv", qk, u0)

    # between blocks and between rows: the state, in the order given
    m = m.reshape(r, nc, heads, dk, dk)
    nn_ = nn_.reshape(r, nc, heads, dk, dv)
    held = pool[slots]
    entering, leaving = [], []
    for i in range(r):
        s = _entering(held, leaving, slots, fresh, i)
        for b in range(nc):
            entering.append(s)
            s = _ein("hij,hjv->hiv", m[i, b], s) + nn_[i, b]
        leaving.append(s)
    pool = _write_back(pool, slots, jnp.stack(leaving))
    s0 = jnp.stack(entering).reshape(-1, dk, dv)        # (n, K, V)
    o = (_ein("ntk,nkv->ntv", p, s0) + o0) * scale
    o = jnp.moveaxis(o.reshape(r, nc, heads, c, dv), 2, 3)
    return o.reshape(r, length, heads, dv), pool


# ---------------------------------------------------------------------------
# graph ops
# ---------------------------------------------------------------------------

def kda_chunk_scan_op(q, k, v, g, beta, pool, slots, fresh, lens, *, chunk,
                      scale, name=None):
    """:func:`kda_chunk_scan` against the state pool ``pool`` (a
    ``StatePool`` of inner shape ``(H, d_k, d_v)``, float32)."""
    v = ops_mod.convert_to_tensor(v)
    out = v.shape.as_list()
    op = _pool_update_op(
        "KDAChunkScan", pool,
        [q, k, v, g, beta, _ints(slots), _flags(fresh), _ints(lens)],
        (out, dtypes_mod.float32), name or "kda_chunk_scan",
        chunk=int(chunk), scale=float(scale))
    return op.outputs[0]


def _lower_kda_chunk_scan(ctx, op, inputs):
    name = op.attrs["var_name"]
    o, pool = kda_chunk_scan(ctx.read_var(name, op), *inputs,
                             chunk=op.attrs["chunk"],
                             scale=op.attrs["scale"])
    ctx.write_var(name, pool)
    return [o]


def kda_state_update_op(q, k, v, g, beta, pool, slots, fresh, *, scale,
                        name=None):
    """One token a row: ``q, k, g (B, H, d_k)``, ``v (B, H, d_v)``, ``beta
    (B, H)`` against the state pool ``pool``, updated in place
    (``pallas/kda_state_update.py``). Routed through stf.kernels."""
    v = ops_mod.convert_to_tensor(v)
    op = _pool_update_op(
        "KDAStateUpdate", pool,
        [q, k, v, g, beta, _ints(slots), _flags(fresh)],
        (v.shape.as_list(), dtypes_mod.float32), name or "kda_state_update",
        scale=float(scale))
    return op.outputs[0]


def _lower_kda_state_update(ctx, op, inputs):
    name = op.attrs["var_name"]
    pool = ctx.read_var(name, op)
    q, v = inputs[0], inputs[2]
    fn = _kreg.select("KDAStateUpdate", _kreg.aval_key(q, pool, v))
    o, pool = fn(pool, *inputs, scale=op.attrs["scale"])
    ctx.write_var(name, pool)
    return [o]


for _type, _lower in (("KDAChunkScan", _lower_kda_chunk_scan),
                      ("KDAStateUpdate", _lower_kda_state_update)):
    op_registry.register(
        _type, lower=_lower,
        effects=op_registry.Effects(writes=("var_name",), update="update"))
