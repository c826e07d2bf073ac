"""One decode step of a gated short-convolution layer (LFM2's) for TPU
(Pallas): every row's carried window read and written IN PLACE in a pool
addressed by slot.

A gated short convolution takes the in-projection's ``[B | C | x~]`` (three
``C``-wide thirds a row) and, a token::

    u = B * x~                                    (the convolution's input)
    v = sum_k w[k] window_k + w[taps - 1] u       (depthwise, causal, no bias)
    y = C * v

A sequence's state is its window, the last ``taps - 1`` rows of ``u``
(oldest first). As a gather of the rows' windows, the update and a scatter
back, the TPU compiler writes the pool a ROW at a time in a loop (a trace of
``causal_conv1d``'s decode path at 128 rows on a v5e: a ``while`` over the
rows and a pool-sized ``dynamic-update-slice`` a layer). Here the slot ids are
scalar-prefetch operands, the pool stays in HBM aliased input -> output,
and a grid step copies its block of rows' windows in by hand, computes, and
copies the shifted windows back: the next block's windows are copied in,
and the last block's copied out, while a block computes.

THE LAYOUT. A slot's window is stored as whole ``(rows, 128)`` tiles,
``window_shape(taps, width)``: ``(taps - 1) * width / 128`` rows of 128
lanes, the ``k``-th oldest row of ``u`` in rows ``[k * width / 128, (k + 1)
* width / 128)``. One slot is then one contiguous run of tiles that a copy
moves whole, where a lane-dense ``(slots, (taps - 1) * width)`` pool puts 16
slots' pieces in every bfloat16 tile. The ``[B | C | x~]`` rows and the
output go through the kernel in the same ``(width / 128, 128)`` form a
third.

Rows of one call that name the same slot each read what the pool held
before the call, and the LATER row's window is what the slot keeps (the
earlier rows' go to the scratch slot, the pool's last): every slot but the
scratch one is written once, after its last read. A row whose ``fresh``
flag is set (position 0: a slot another sequence left) starts from zeros.

:func:`short_conv_update_xla` is the composition — gather, update, scatter
— that runs where Mosaic does not (the CPU, a mesh, mode ``off``).
Inference-only: no gradient.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common

_LANES = 128
# the most rows a grid step takes: a block's windows in and out (two
# buffers each) and its [B | C | x~] rows stay within a few MB of VMEM
_MAX_ROWS = 32


def window_shape(taps, width):
    """Inner shape of a layer's window pool (module docstring, "THE
    LAYOUT"); a width that is no whole number of lane tiles keeps the flat
    ``((taps - 1) * width,)`` row."""
    if width % _LANES:
        return ((taps - 1) * width,)
    return ((taps - 1) * width // _LANES, _LANES)


def last_writer(slots, scratch):
    """``(B,)`` the slot each row's leaving window goes to: its own, or the
    scratch slot where a later row of the call names the same one."""
    rows = slots.shape[0]
    order = jnp.arange(rows)
    later = (slots[:, None] == slots[None, :]) & (
        order[:, None] < order[None, :])
    return jnp.where(jnp.any(later, axis=1), scratch, slots)


def _conv(window, u, w, out_dtype):
    """``window (..., taps - 1, W)`` (already zero where fresh), ``u (...,
    W)``, ``w (taps, W)`` -> ``C``'s factor ``v`` in ``out_dtype``: the taps
    summed oldest first in float32."""
    wf = w.astype(jnp.float32)
    v = wf[0] * window[..., 0, :].astype(jnp.float32)
    for k in range(1, w.shape[0] - 1):
        v = v + wf[k] * window[..., k, :].astype(jnp.float32)
    return (v + wf[-1] * u.astype(jnp.float32)).astype(out_dtype)


def short_conv_update_xla(pool, bcx, w, slots, fresh):
    """The composition: the rows' windows gathered, the step, the shifted
    windows scattered back. Same arguments and results as
    :func:`short_conv_update`."""
    taps, width = w.shape
    rows = bcx.shape[0]
    slots = jnp.asarray(slots, jnp.int32)
    b, c, x = (bcx[:, j * width:(j + 1) * width] for j in range(3))
    u = (b * x).astype(pool.dtype)
    window = jnp.where(jnp.asarray(fresh, bool)[:, None, None], 0,
                       pool[slots].reshape(rows, taps - 1, width))
    v = _conv(window, u, w, bcx.dtype)
    y = (c.astype(jnp.float32) * v.astype(jnp.float32)).astype(bcx.dtype)
    ext = jnp.concatenate([window, u[:, None]], 1)[:, 1:]
    return y, pool.at[last_writer(slots, pool.shape[0] - 1)].set(
        ext.reshape((rows,) + pool.shape[1:]))


def rows_per_step(rows):
    """Rows one grid step takes: the largest of 32 and 16 that divides
    ``rows`` (a whole number of bfloat16 sublane tiles), else all of
    them."""
    for r in (_MAX_ROWS, 16):
        if rows % r == 0:
            return r
    return rows


def _update_kernel(slot_ref, dest_ref, keep_ref, bcx_ref, w_ref, pool_in,
                   y_ref, pool_out, win, new, rsem, wsem):
    i, n = pl.program_id(0), pl.num_programs(0)
    r_blk, s = win.shape[1], y_ref.shape[1]
    taps = w_ref.shape[0]

    def copies(step, reading, start):
        """Start (or wait for) the copies of block ``step``'s windows: in
        from each row's slot, or out to the slot it leaves its window to."""
        buf = step % 2

        def one(r, carry):
            if reading:
                cp = pltpu.make_async_copy(
                    pool_in.at[slot_ref[step * r_blk + r]], win.at[buf, r],
                    rsem.at[buf])
            else:
                cp = pltpu.make_async_copy(
                    new.at[buf, r], pool_out.at[dest_ref[step * r_blk + r]],
                    wsem.at[buf])
            if start:
                cp.start()
            else:
                cp.wait()
            return carry

        jax.lax.fori_loop(0, r_blk, one, 0)

    @pl.when(i == 0)
    def _():
        copies(0, True, True)

    @pl.when(i + 1 < n)
    def _():
        copies(i + 1, True, True)

    copies(i, True, False)
    buf = i % 2
    f32 = jnp.float32
    keep = keep_ref[...] > 0                               # (R, 1, 128)
    old = jnp.where(keep, win[buf], 0)                     # (R, (T-1)s, 128)
    u = (bcx_ref[:, 0:s].astype(f32) * bcx_ref[:, 2 * s:3 * s].astype(f32)
         ).astype(win.dtype)
    w = w_ref[...].astype(f32)                             # (T, s, 128)
    v = w[0] * old[:, 0:s].astype(f32)
    for k in range(1, taps - 1):
        v = v + w[k] * old[:, k * s:(k + 1) * s].astype(f32)
    v = (v + w[taps - 1] * u.astype(f32)).astype(y_ref.dtype)
    y_ref[...] = (bcx_ref[:, s:2 * s].astype(f32) * v.astype(f32)).astype(
        y_ref.dtype)
    if taps > 2:
        new[buf, :, 0:(taps - 2) * s] = old[:, s:]
    new[buf, :, (taps - 2) * s:] = u
    copies(i, False, True)

    # the other buffer's copies out (the block before) are done before
    # this block's are waited for or that buffer is filled again
    @pl.when(i > 0)
    def _():
        copies(i - 1, False, False)

    @pl.when(i == n - 1)
    def _():
        copies(i, False, False)


def short_conv_update(pool, bcx, w, slots, fresh):
    """One token a row through the gated short convolution, the pool
    updated in place.

    pool: ``(slots, *window_shape(taps, W))`` in the window's dtype; bcx:
    ``(B, 3 W)`` the in-projection ``[B | C | x~]``; w: ``(taps, W)``;
    slots: ``(B,)`` int32 pool rows; fresh: ``(B,)`` rows that start from a
    zero window. Returns ``(y (B, W)`` in ``bcx``'s dtype — ``C * v`` — the
    pool``)``."""
    return _update_call(pool, bcx, w, slots, fresh,
                        interpret=common.use_interpret())


@functools.partial(jax.jit, static_argnames=("interpret",))
def _update_call(pool, bcx, w, slots, fresh, *, interpret):
    taps, width = w.shape
    rows = bcx.shape[0]
    s = width // _LANES
    assert bcx.shape[1] == 3 * width and pool.shape[1:] == window_shape(
        taps, width) and len(pool.shape) == 3, (pool.shape, bcx.shape,
                                                w.shape)
    r_blk = rows_per_step(rows)
    slots = jnp.asarray(slots, jnp.int32)
    dest = last_writer(slots, pool.shape[0] - 1)
    keep = jnp.broadcast_to(
        (~jnp.asarray(fresh, bool)).astype(jnp.float32)[:, None, None],
        (rows, 1, _LANES))
    block = functools.partial(pl.BlockSpec,
                              index_map=lambda i, sl, de: (i, 0, 0))
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    itm = jnp.dtype(pool.dtype).itemsize
    window = pltpu.VMEM((2, r_blk) + pool.shape[1:], pool.dtype)
    y, pool = pl.pallas_call(
        _update_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // r_blk,),
            in_specs=[block((r_blk, 1, _LANES)),
                      block((r_blk, 3 * s, _LANES)),
                      pl.BlockSpec((taps, s, _LANES),
                                   lambda i, sl, de: (0, 0, 0)),
                      in_hbm],
            out_specs=[block((r_blk, s, _LANES)), in_hbm],
            scratch_shapes=[window, window, pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=[jax.ShapeDtypeStruct((rows, s, _LANES), bcx.dtype),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        # operand 5 (after the two prefetched) is the pool: output 1
        input_output_aliases={5: 1},
        # a block's copies run ahead into the next: the grid is walked in
        # order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        cost_estimate=pl.CostEstimate(
            flops=int(rows * width * (2 * taps + 1)),
            bytes_accessed=int(rows * (2 * (taps - 1) + 4) * width * itm
                               + taps * width * w.dtype.itemsize),
            transcendentals=0),
        interpret=interpret,
        name=f"stf_short_conv_update_b{rows}",
    )(slots, dest, keep, bcx.reshape(rows, 3 * s, _LANES),
      w.reshape(taps, s, _LANES), pool)
    return y.reshape(rows, width), pool
