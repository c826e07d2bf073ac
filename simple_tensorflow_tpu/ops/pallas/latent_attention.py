"""Paged latent attention for TPU (Pallas): absorbed multi-head latent
attention over a pool of LATENT rows read in place through the page table.

A latent-attention layer (``models/latent_moe_lm.py``) caches ONE row a
position, ``[c_kv ; k_rope]``, that every head reads: with each head's
query absorbed into the latent space (``[q_nope . W^K ; q_rope]``) the
whole row is the key, and its first ``value_dim`` lanes are the value
(``W^V`` is applied to the result by the caller). Nothing per head is
ever cached, and the per-head keys and values are never built.

This is ``decode_attention.paged_decode_attention``'s machinery —
``_live_pages``, the table and the lengths as scalar-prefetch operands,
an index map that repeats the last live page for dead entries (an
unchanged block index issues no DMA), float32 online softmax in VMEM
scratch, the jitted body traced once a program — over one pool instead
of two and with the heads on the SUBLANE axis: a tile is ``(heads x Kq,
W)`` queries against a page's ``(page_len, W)`` rows, so one DMA a page
serves the score product and the value product. It lives in a file of
its own so that the K/V kernels' traced bodies (their source locations
are part of a compiled program's cache key) stay as they are.

The pool's minor dimension is a whole number of 128-lane tiles: a
576-wide pool (512 latent + 64 rope) is laid out positions-minor by the
TPU compiler (``bf16[1201,512,576]{1,2,0}``), and every call then pays a
pool-sized relayout copy (787 MB at the benchmark's pool; described-chip
compile, PR 33) — the fault PR 26 found in the K/V pools. The model pads
the row to 640 lanes, which the tiled layout would occupy anyway.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common
from .common import NEG_INF, pad_dim, round_up
from .decode_attention import _HI, _live_pages

# the most query rows one tile of the latent kernel holds
_LATENT_MAX_ROWS = 512


def latent_heads_per_tile(kq, num_heads):
    """Heads whose ``kq`` queries share one tile of the latent kernel:
    all of them at a decode step (64 rows), one at a 512-query prefill
    block — the largest divisor of ``num_heads`` that keeps a tile within
    ``_LATENT_MAX_ROWS`` rows (the float32 accumulator is rows x
    value_dim)."""
    return max(g for g in range(1, num_heads + 1)
               if num_heads % g == 0 and (g * kq <= _LATENT_MAX_ROWS
                                          or g == 1))


def _latent_kernel(tbl_ref, len_ref, q_ref, c_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, sm_scale, page_len, n_blocks, kq, value_dim,
                   causal_offset):
    """One grid step is one table entry of one tile of heads, as in
    ``decode_attention._paged_kernel``; the page's rows are keys whole and values in
    their first ``value_dim`` lanes, so one DMA serves both products."""
    del tbl_ref                      # read by the index map only
    b, page = pl.program_id(0), pl.program_id(2)
    rows = q_ref.shape[0]
    hi = _HI if q_ref.dtype == jnp.float32 else None

    @pl.when(page == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(page < _live_pages(length, kq, page_len, n_blocks,
                                causal_offset))
    def _():
        c = c_ref[:]                                   # (page_len, width)
        s = jax.lax.dot_general(
            q_ref[:], c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=hi) * sm_scale                   # (rows, page_len)
        span = page * page_len + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_len), 1)
        if causal_offset:
            # row = head * kq + query
            jrow = jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_len), 0) % kq
            allowed = length + jrow + 1
        else:
            allowed = length
        s = jnp.where(span < allowed, s, NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = alpha * l_scr[:] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(c.dtype), c[:, :value_dim], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32, precision=hi)

    @pl.when(page == pl.num_programs(2) - 1)
    def _():
        l = l_scr[:]
        o_ref[:] = (acc_scr[:] / jnp.where(l == 0.0, 1.0, l)
                    ).astype(o_ref.dtype)


def paged_latent_attention(q, pool, page_tables, lengths, *, value_dim,
                           sm_scale, causal_offset=False):
    """Absorbed latent attention that reads the paged LATENT pool in
    place.

    q: (B, H, W) — one absorbed query a head a sequence — or a
    (B, Kq, H, W) block; pool: the cache as stored, ``(pages, page_len,
    W)``, ONE row a position that every head reads: the whole row is the
    key, its first ``value_dim`` lanes are the value. page_tables,
    lengths, ``causal_offset``: as ``decode_attention.paged_decode_
    attention``, whose machinery this is (table and lengths in scalar prefetch, the index
    map that skips dead entries, float32 online softmax). Returns
    ``q.shape[:-1] + (value_dim,)`` in q's dtype."""
    return _latent_call(q, pool, page_tables, lengths,
                        value_dim=int(value_dim), sm_scale=float(sm_scale),
                        causal_offset=bool(causal_offset),
                        interpret=common.use_interpret())


@functools.partial(jax.jit, static_argnames=(
    "value_dim", "sm_scale", "causal_offset", "interpret"))
def _latent_call(q, pool, page_tables, lengths, *, value_dim, sm_scale,
                 causal_offset, interpret):
    if q.ndim == 3:
        return _latent_call(
            q[:, None], pool, page_tables, lengths, value_dim=value_dim,
            sm_scale=sm_scale, causal_offset=False,
            interpret=interpret)[:, 0]
    b, kq, h, w = q.shape
    _, page_len, width = pool.shape
    n_blocks = page_tables.shape[1]
    assert width == w and value_dim <= w, (q.shape, pool.shape, value_dim)
    g = latent_heads_per_tile(kq, h)
    n_g = h // g
    rows = g * kq if interpret else round_up(g * kq, 8)

    # a tile's rows are head-major: row = head_in_tile * kq + query
    qt = jnp.transpose(q, (0, 2, 1, 3)).reshape(b, n_g, g * kq, w)
    qt = pad_dim(qt, 2, rows).astype(pool.dtype)

    def page_of(bi, gi, page, tbl, lens):
        last = _live_pages(lens[bi], kq, page_len, n_blocks,
                           causal_offset) - 1
        entry = jnp.maximum(jnp.minimum(page, last), 0)
        return (tbl[bi * n_blocks + entry], 0, 0)

    kernel = functools.partial(
        _latent_kernel, sm_scale=sm_scale, page_len=page_len,
        n_blocks=n_blocks, kq=kq, value_dim=value_dim,
        causal_offset=causal_offset)
    itm = jnp.dtype(pool.dtype).itemsize
    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_g, n_blocks),
            in_specs=[pl.BlockSpec((None, None, rows, w),
                                   lambda bi, gi, page, tbl, lens:
                                   (bi, gi, 0, 0)),
                      pl.BlockSpec((None, page_len, w), page_of)],
            out_specs=pl.BlockSpec((None, None, rows, value_dim),
                                   lambda bi, gi, page, tbl, lens:
                                   (bi, gi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, value_dim), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, n_g, rows, value_dim), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(2 * b * kq * h * n_blocks * page_len
                      * (w + value_dim)),
            bytes_accessed=int(b * n_g * n_blocks * page_len * w * itm),
            transcendentals=int(b * kq * h * n_blocks * page_len)),
        interpret=interpret,
        name=f"stf_latent_attention_q{kq}_paged",
    )(jnp.asarray(page_tables, jnp.int32).reshape(-1),
      jnp.asarray(lengths, jnp.int32), qt, pool)
    o = o[:, :, :g * kq].reshape(b, n_g, g, kq, value_dim)
    return jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(b, kq, h, value_dim)


def paged_latent_attention_xla(q, pool, page_tables, lengths, *, value_dim,
                               sm_scale, causal_offset=False):
    """The composition that runs where Mosaic does not (the CPU, a mesh,
    mode ``off``): gather the logical view of the latent rows through
    the page table, then the same masked float32 softmax."""
    tables = jnp.asarray(page_tables, jnp.int32)
    b, nb = tables.shape
    view = pool[tables].reshape(b, nb * pool.shape[1], pool.shape[2])
    block = q if q.ndim == 4 else q[:, None]
    kq = block.shape[1]
    s = jnp.einsum("bqhw,blw->bqhl", block.astype(jnp.float32),
                   view.astype(jnp.float32), precision=_HI) * sm_scale
    span = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    allowed = jnp.asarray(lengths, jnp.int32)[:, None, None, None]
    if causal_offset:
        allowed = allowed + 1 + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
    s = jnp.where(span < allowed, s, NEG_INF)
    p = jnp.exp(s - jax.scipy.special.logsumexp(s, axis=-1, keepdims=True))
    o = jnp.einsum("bqhl,blv->bqhv", p,
                   view[..., :value_dim].astype(jnp.float32),
                   precision=_HI).astype(q.dtype)
    return o if q.ndim == 4 else o[:, 0]
