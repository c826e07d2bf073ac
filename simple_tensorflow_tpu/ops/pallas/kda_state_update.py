"""One decode step of a gated delta-rule (KDA) linear-attention layer for
TPU (Pallas): every row's matrix state read and written IN PLACE in a pool
addressed by slot.

A KDA layer keeps, a sequence, one ``(d_k, d_v)`` float32 matrix a head —
4 MB at 64 heads x 128 x 128 — and a decode step touches all of it::

    S' = Diag(alpha) S          (alpha: a decay a KEY channel, in (0, 1))
    u  = v - S'^T k             S = S' + (beta k) u^T        o = S^T q

As a gather of the rows' states, the update and a scatter back, a step
moves every row's state three times; this kernel moves it twice, the least
there is, as ``ssm_state_update.py`` does for Mamba-2: the slot ids are
scalar-prefetch operands, the pool block's index map reads them, and the
pool is aliased input -> output. A row whose ``fresh`` flag is set
(position 0: a slot another sequence left) starts from zero.

THE LAYOUT. A head's state is stored ``(d_k, d_v)``: the key channels on
the sublanes, the value channels on the lanes. Then what varies along the
KEY axis — ``alpha``, ``k``, ``beta k``, ``q`` — is a COLUMN, sliced out of
a ``(d_k, heads)`` tile the caller lays out in XLA, and what varies along
the value axis — ``v`` and the result — is a row, as it is stored;
``S'^T k`` and ``S^T q`` are sublane reductions whose results are rows.
``beta`` is folded into the column that makes the outer product, so no
scalar a head is needed.

:func:`kda_state_update_xla` is the composition — gather, update, scatter —
that runs where Mosaic does not (the CPU, a mesh, mode ``off``).
Inference-only: no gradient.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common

_HI = jax.lax.Precision.HIGHEST
# the most bytes of one state block (read and written, each
# double-buffered: four of them in VMEM beside the step's temporaries)
_BLOCK_BYTES = 1 << 20


def _columns(q, k, g, beta):
    """``alpha``, ``k``, ``beta k`` and ``q`` (float32, ``(B, H, d_k)``)."""
    f32 = jnp.float32
    k = k.astype(f32)
    return (jnp.exp(g.astype(f32)), k, beta.astype(f32)[..., None] * k,
            q.astype(f32))


def kda_state_update_xla(pool, q, k, v, g, beta, slots, fresh, *, scale):
    """The composition: the rows' states gathered, updated and scattered
    back. Same arguments and results as :func:`kda_state_update`."""
    slots = jnp.asarray(slots, jnp.int32)
    s = jnp.where(jnp.asarray(fresh, bool)[:, None, None, None], 0.0,
                  pool[slots])                          # (B, H, K, V)
    alpha, kf, kb, qf = _columns(q, k, g, beta)
    s = alpha[..., None] * s
    ks = jnp.einsum("bhk,bhkv->bhv", kf, s, precision=_HI)
    s = s + kb[..., None] * (v.astype(jnp.float32) - ks)[..., None, :]
    o = jnp.einsum("bhk,bhkv->bhv", qf, s, precision=_HI) * scale
    return o, pool.at[slots].set(s)


def _update_kernel(slot_ref, fresh_ref, a_ref, k_ref, kb_ref, q_ref, v_ref,
                   s_ref, o_ref, so_ref, *, scale):
    del slot_ref                        # read by the index maps only
    fresh = fresh_ref[pl.program_id(0)] != 0
    # one body a head: (d_k, d_v) = 16 vregs at 128 x 128
    for j in range(s_ref.shape[0]):
        h = jnp.where(fresh, 0.0, s_ref[j])              # (K, V)
        h = a_ref[:, j:j + 1] * h
        ks = jnp.sum(k_ref[:, j:j + 1] * h, axis=0, keepdims=True)
        h = h + kb_ref[:, j:j + 1] * (v_ref[j:j + 1, :] - ks)
        so_ref[j] = h
        o_ref[j:j + 1, :] = jnp.sum(q_ref[:, j:j + 1] * h, axis=0,
                                    keepdims=True) * scale


def heads_per_block(heads, dk, dv):
    """Heads one grid step updates: as many as keep a state block within
    ``_BLOCK_BYTES``, a divisor of ``heads`` and a whole number of 8-row
    tiles of the row operands (or all of them)."""
    best = heads
    for hb in range(heads, 0, -1):
        if heads % hb or (hb != heads and hb % 8):
            continue
        best = hb
        if hb * dk * dv * 4 <= _BLOCK_BYTES:
            break
    return best


def kda_state_update(pool, q, k, v, g, beta, slots, fresh, *, scale):
    """One token a row through the recurrence, the pool updated in place.

    pool: ``(slots, H, d_k, d_v)`` float32; q, k: ``(B, H, d_k)`` (already
    normalised); v: ``(B, H, d_v)``; g: ``(B, H, d_k)`` the log-decays (<=
    0); beta: ``(B, H)``; slots: ``(B,)`` int32 pool rows; fresh: ``(B,)``
    rows that start from zero state. Returns ``(o (B, H, d_v)`` float32 —
    ``S^T q * scale`` — the pool``)``; rows that name the same slot (a
    bucket's padding rows, all on the scratch slot) leave it holding one
    of their states."""
    return _update_call(pool, q, k, v, g, beta, slots, fresh,
                        scale=float(scale), interpret=common.use_interpret())


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _update_call(pool, q, k, v, g, beta, slots, fresh, *, scale, interpret):
    b, heads, dk = q.shape
    dv = v.shape[-1]
    assert pool.shape[1:] == (heads, dk, dv), (pool.shape, q.shape, v.shape)
    hb = heads_per_block(heads, dk, dv)
    n_hb = heads // hb

    def tiles(c):                       # (B, H, K) -> (B, n_hb, K, hb)
        return jnp.moveaxis(c.reshape(b, n_hb, hb, dk), 3, 2)

    cols = [tiles(c) for c in _columns(q, k, g, beta)]
    col = pl.BlockSpec((None, None, dk, hb), lambda i, j, s, f: (i, j, 0, 0))
    row = pl.BlockSpec((None, hb, dv), lambda i, j, s, f: (i, j, 0))
    state = pl.BlockSpec((None, hb, dk, dv),
                         lambda i, j, s, f: (s[i], j, 0, 0))
    o, pool = pl.pallas_call(
        functools.partial(_update_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_hb),
            in_specs=[col, col, col, col, row, state],
            out_specs=[row, state]),
        out_shape=[jax.ShapeDtypeStruct((b, heads, dv), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 7 (after the two prefetched) is the pool: output 1
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(7 * b * heads * dk * dv),
            bytes_accessed=int(2 * b * heads * dk * dv * 4),
            transcendentals=0),
        interpret=interpret,
        name=f"stf_kda_state_update_b{b}",
    )(jnp.asarray(slots, jnp.int32), jnp.asarray(fresh, jnp.int32),
      *cols, v.astype(jnp.float32), pool)
    return o, pool
