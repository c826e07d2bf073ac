"""One decode step of a selective state-space layer for TPU (Pallas): the
state of every row read and written IN PLACE in a pool addressed by slot.

A Mamba-2 layer keeps, a sequence, ``h (heads, head_dim, state)`` in
float32 — 2.1 MB at 64 x 64 x 128 — and a decode step touches all of it::

    h <- exp(dt A) h + (dt x) (x) B        y = h C + D x

As a gather of the rows' states, the update and a scatter back, a step
moves every row's state three times and holds two copies beside the pool.
This kernel moves it twice, the least there is: the slot ids are
scalar-prefetch operands, the pool block's index map reads them, and the
pool is aliased input -> output, so a row's state comes into VMEM from
where it lies, is updated there, and goes back to the same place. A row
whose ``fresh`` flag is set (position 0: a slot that another sequence
left) starts from zero whatever the pool holds.

THE POOL'S LAYOUT. A state is stored ``(heads / pack, state, pack *
head_dim)``: ``pack`` heads of one B/C group side by side on the lane axis
(2 at head_dim 64: a whole 128-lane tile), the state dimension on the
sublanes (:func:`state_pack`, :func:`to_pool_layout`). With ``state`` on
the lanes instead, ``x (x) B`` needs every ``x[p]`` as a column and ``h C``
a lane reduction a head: 4,096 columns a row. This way what multiplies
along the lanes is a ROW the caller makes in XLA — ``exp(dt A)`` and ``dt
x``, each repeated over its head's lanes, 16 KB a row — and only ``B`` and
``C``, one column a GROUP (8 a row), are sliced out of a ``(state,
groups)`` tile and broadcast over the lanes; ``h C`` is a sublane
reduction whose result is a row, as it is stored.

:func:`ssm_state_update_xla` is the composition — gather, update, scatter —
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

# the most bytes of one state block (a block is read and written, each
# double-buffered: four of them in VMEM beside the step's temporaries)
_BLOCK_BYTES = 1 << 20


def state_pack(num_heads, head_dim, num_groups):
    """Heads stored side by side on the lane axis: as many as fill a
    128-lane tile, all of one B/C group (they share ``B`` and ``C``)."""
    per_group = num_heads // num_groups
    pack = max(1, min(128 // head_dim if head_dim <= 128 else 1, per_group))
    while per_group % pack:
        pack -= 1
    return pack


def pool_inner_shape(num_heads, head_dim, state, num_groups):
    """Inner shape of the state pool: ``(heads / pack, state, pack *
    head_dim)``."""
    pack = state_pack(num_heads, head_dim, num_groups)
    return (num_heads // pack, state, pack * head_dim)


def to_pool_layout(h, pack):
    """``h (..., heads, head_dim, state)`` -> ``(..., heads / pack, state,
    pack * head_dim)``."""
    *lead, heads, p, n = h.shape
    h = h.reshape(*lead, heads // pack, pack, p, n)
    h = jnp.moveaxis(h, -1, -3)                 # (..., J, N, pack, P)
    return h.reshape(*lead, heads // pack, n, pack * p)


def from_pool_layout(h, pack):
    """The inverse of :func:`to_pool_layout`."""
    *lead, j, n, w = h.shape
    h = h.reshape(*lead, j, n, pack, w // pack)
    h = jnp.moveaxis(h, -3, -1)                 # (..., J, pack, P, N)
    return h.reshape(*lead, j * pack, w // pack, n)


def _rows(x, dt, a, d, bm, cm, pack):
    """What the update multiplies along the lanes, as rows of the pool's
    layout: ``decay`` and ``dtx (B, J, W)`` float32, ``B`` and ``C`` as
    ``(B, N, G)`` columns, and the skip term ``D x``."""
    b, heads, p = x.shape
    xf = x.astype(jnp.float32)
    dt = dt.astype(jnp.float32)
    decay = jnp.exp(dt * a.astype(jnp.float32))
    lanes = (b, heads // pack, pack * p)
    decay = jnp.broadcast_to(decay[:, :, None], xf.shape).reshape(lanes)
    dtx = (dt[:, :, None] * xf).reshape(lanes)
    skip = d.astype(jnp.float32)[None, :, None] * xf
    return (decay, dtx, jnp.swapaxes(bm.astype(jnp.float32), 1, 2),
            jnp.swapaxes(cm.astype(jnp.float32), 1, 2), skip)


def ssm_state_update_xla(pool, x, dt, a, bm, cm, d, slots, fresh):
    """The composition: the rows' states gathered, updated and scattered
    back. Same arguments and results as :func:`ssm_state_update`."""
    b, heads, p = x.shape
    groups = bm.shape[1]
    pack = pool.shape[-1] // p
    slots = jnp.asarray(slots, jnp.int32)
    h = jnp.where(jnp.asarray(fresh, bool)[:, None, None, None], 0.0,
                  pool[slots])                          # (B, J, N, W)
    decay, dtx, bt, ct, skip = _rows(x, dt, a, d, bm, cm, pack)
    per_group = heads // groups // pack                 # packs a group
    bcol = jnp.repeat(jnp.moveaxis(bt, 2, 1), per_group, axis=1)
    ccol = jnp.repeat(jnp.moveaxis(ct, 2, 1), per_group, axis=1)  # (B,J,N)
    h = decay[:, :, None, :] * h + bcol[..., None] * dtx[:, :, None, :]
    y = jnp.sum(h * ccol[..., None], axis=2).reshape(b, heads, p) + skip
    return y.astype(x.dtype), pool.at[slots].set(h)


def _update_kernel(slot_ref, fresh_ref, decay_ref, dtx_ref, bt_ref, ct_ref,
                   h_ref, y_ref, ho_ref, *, packs_per_group):
    del slot_ref                        # read by the index maps only
    fresh = fresh_ref[pl.program_id(0)] != 0
    # one body a pack of heads: (state, lanes) = 16 vregs at 128 x 128
    for j in range(h_ref.shape[0]):
        g = j // packs_per_group
        h = jnp.where(fresh, 0.0, h_ref[j])              # (N, W)
        h = (decay_ref[j:j + 1, :] * h
             + bt_ref[:, g:g + 1] * dtx_ref[j:j + 1, :])
        ho_ref[j] = h
        y_ref[j:j + 1, :] = jnp.sum(h * ct_ref[:, g:g + 1], axis=0,
                                    keepdims=True)


def packs_per_block(packs, packs_per_group, state, lanes):
    """Packs of heads one grid step updates, from the shapes alone: whole
    groups, as many as keep a state block within ``_BLOCK_BYTES``, and a
    whole number of 8-row tiles of the row operands (or all of them)."""
    best = packs
    for jb in range(packs, 0, -1):
        if packs % jb or jb % packs_per_group:
            continue
        if jb != packs and jb % 8:
            continue
        best = jb
        if jb * state * lanes * 4 <= _BLOCK_BYTES:
            break
    return best


def ssm_state_update(pool, x, dt, a, bm, cm, d, slots, fresh):
    """One token a row through the recurrence, the pool updated in place.

    pool: ``(slots, J, N, W)`` float32 (:func:`pool_inner_shape`); x:
    ``(B, heads, head_dim)``; dt: ``(B, heads)`` the step size, already
    positive (softplus applied); a, d: ``(heads,)`` (``A`` negative); bm,
    cm: ``(B, groups, state)``; slots: ``(B,)`` int32 pool rows; fresh:
    ``(B,)`` rows that start from zero state. Returns ``(y (B, heads,
    head_dim)`` in ``x``'s dtype, the pool``)``; rows that name the same
    slot (a bucket's padding rows, all on the scratch slot) leave it
    holding one of their states."""
    return _update_call(pool, x, dt, a, bm, cm, d, slots, fresh,
                        interpret=common.use_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_call(pool, x, dt, a, bm, cm, d, slots, fresh, *, interpret):
    b, heads, p = x.shape
    groups, n = bm.shape[1], bm.shape[2]
    _, packs, n_pool, lanes = pool.shape
    pack = lanes // p
    assert n_pool == n and packs * pack == heads and heads % groups == 0, (
        pool.shape, x.shape, bm.shape)
    per_group = heads // groups // pack
    jb = packs_per_block(packs, per_group, n, lanes)
    n_jb, gb = packs // jb, jb // per_group
    decay, dtx, bt, ct, skip = _rows(x, dt, a, d, bm, cm, pack)
    # a block's own groups on the lanes of its own (N, gb) tile
    bt = jnp.moveaxis(bt.reshape(b, n, n_jb, gb), 2, 1)
    ct = jnp.moveaxis(ct.reshape(b, n, n_jb, gb), 2, 1)

    row = pl.BlockSpec((None, jb, lanes), lambda i, j, s, f: (i, j, 0))
    col = pl.BlockSpec((None, None, n, gb), lambda i, j, s, f: (i, j, 0, 0))
    state = pl.BlockSpec((None, jb, n, lanes),
                         lambda i, j, s, f: (s[i], j, 0, 0))
    y, pool = pl.pallas_call(
        functools.partial(_update_kernel, packs_per_group=per_group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_jb),
            in_specs=[row, row, col, col, state],
            out_specs=[row, state]),
        out_shape=[jax.ShapeDtypeStruct((b, packs, lanes), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 6 (after the two prefetched) is the pool: output 1
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(6 * b * heads * p * n),
            bytes_accessed=int(2 * b * packs * n * lanes * 4),
            transcendentals=0),
        interpret=interpret,
        name=f"stf_ssm_state_update_b{b}",
    )(jnp.asarray(slots, jnp.int32), jnp.asarray(fresh, jnp.int32),
      decay, dtx, bt, ct, pool)
    y = y.reshape(b, heads, p) + skip
    return y.astype(x.dtype), pool
