"""Paged-cache decode attention for TPU (Pallas): query length 1 or a
small query BLOCK (speculative verify / paged block prefill).

The generative-inference hot loop (docs/PERFORMANCE.md "decode
anatomy") attends ONE new query position per sequence against that
sequence's gathered KV-cache rows. The training-side flash kernel is
the wrong tool here: its q-block tiling amortizes over many query rows,
and a (1, d) query block wastes the whole MXU pass. This kernel keeps
the HEADS on the sublane axis instead — grid (batch, kv_blocks), one
(H, D) query tile per sequence, K/V streamed in (block_l, H, D) tiles
straight from the paged-cache layout (slots, max_len, heads, head_dim)
that :mod:`..kv_cache_ops` gathers — so no (B, H, 1, L) score tensor
ever reaches HBM and the cache rows are read exactly once.

Masking is per-sequence by LENGTH (cache positions >= lengths[b] are
dead slots/future positions) plus an optional additive key bias
(B, kv_len) — the padding-mask shape cross-attention feeds. Online
softmax (m, l, acc) lives in VMEM scratch across the kv-block walk,
exactly like flash_attention.py.

Decode is inference-only: no custom VJP (the op is registered without
a gradient; training uses the flash kernel).

Layout: q (B, H, D); k/v (B, L, H, D); lengths (B,) int32 in SMEM.
Heads pad to the f32 sublane tile (8), head_dim to a half lane tile
(64) off-interpret — dead head rows are sliced off on return.

Query-block variant (PR 16): q (B, Kq, H, D) — Kq consecutive
positions per sequence, the shape of a speculative VERIFY step (the
target re-scores the draft's K proposals in one pass) and of the
causal-LM page-block prefill. With ``causal_offset=True`` ``lengths``
is the committed prefix BEFORE the block and query j attends
positions < lengths[b] + j + 1 (the block's own K/V were appended at
lengths[b]..lengths[b]+Kq-1 just before this op); with False every
query sees positions < lengths[b] (cross-attention over a fixed
source). The kernel walks the same (batch, kv_blocks) grid with the
query block riding the sublane axis next to heads — tiles (H, Kq, D),
scores (H, Kq, block_l) — so the Kq=4-ish verify widths never touch
HBM either.

Two kernels, by what they read (PR 30):

- :func:`decode_attention` reads a GATHERED cache ``(B, L, H, D)``: one
  dense row a sequence. The slot caches run it (the translation
  model's decode and speculative verify, ``transformer._SlotCaches``;
  cross-attention with its key bias; the cached beam search).
- :func:`paged_decode_attention` reads the PAGED pool in place,
  ``(pages, page_len, H*D)`` as stored, through a page table: the
  decode step and the page-chunk prefill of the paged causal LM
  (``causal_lm._PagedCaches``). No logical view is gathered and none is
  relaid: at lm-big's sizes the view and its relayout were 58 % of the
  serving benchmark's device time.
  :func:`paged_decode_attention_xla` — the gathered view plus
  :func:`decode_attention_xla` — is its composition for the CPU, a mesh
  and mode ``off``.

GROUPED QUERIES (PR 35). Both paged lowerings take ``q`` with MORE heads
than the pools hold, ``H % H_kv == 0``: query head ``h`` reads key-value
head ``h // (H / H_kv)``. The kernel then needs no block-diagonal
lay-out: a key-value head's ``H / H_kv`` query heads are ROWS of one query
tile over that head's own lanes (``_paged_grouped_kernel``; head_dim a
whole number of 128-lane tiles; its call site is named
``stf_decode_attention_q<Kq>_paged_gqa``). At ``H == H_kv`` the kernel is
the one above, tile for tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common
from .common import NEG_INF, cdiv, pad_dim, round_up

DEFAULT_BLOCK_L = 128
_HI = jax.lax.Precision.HIGHEST


def _decode_block_kernel(q_ref, k_ref, v_ref, len_ref, bias_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, sm_scale, block_l,
                         num_lb, kq, has_bias, causal_offset):
    b = pl.program_id(0)
    lb = pl.program_id(1)

    @pl.when(lb == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[b]
    # the furthest position ANY query in the block may read
    horizon = length + (kq if causal_offset else 0)
    live = lb * block_l < horizon

    @pl.when(live)
    def _():
        q = q_ref[:]                                   # (H, Kq, D)
        k = k_ref[:]                                   # (block_l, H, D)
        v = v_ref[:]
        # batch dim H, contract D -> (H, Kq, block_l)
        s = jax.lax.dot_general(
            q, k, dimension_numbers=(((2,), (2,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
            precision=_HI if q.dtype == jnp.float32 else None) * sm_scale
        if has_bias:
            s = s + bias_ref[:].reshape(1, 1, block_l)
        span = lb * block_l + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 2)
        if causal_offset:
            jrow = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            allowed = length + jrow + 1
        else:
            allowed = length
        s = jnp.where(span < allowed, s, NEG_INF)

        m_prev, l_prev = m_scr[:], l_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                         # (H, Kq, block_l)
        alpha = jnp.exp(m_prev - m_new)                # (H, Kq, 1)
        m_scr[:] = m_new
        l_scr[:] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        # P·V with batch dim H: (H, Kq, block_l) x (block_l, H, D)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v,
            dimension_numbers=(((2,), (0,)), ((0,), (1,))),
            preferred_element_type=jnp.float32,
            precision=_HI if v.dtype == jnp.float32 else None)

    @pl.when(lb == num_lb - 1)
    def _():
        l_safe = jnp.where(l_scr[:] == 0.0, 1.0, l_scr[:])
        o_ref[:] = (acc_scr[:] / l_safe).astype(o_ref.dtype)


def _decode_attention_block(q, k_cache, v_cache, lengths, *, bias,
                            sm_scale, block_l, causal_offset):
    """Query-block path: q (B, Kq, H, D) -> (B, Kq, H, D)."""
    b, kq, h, d = q.shape
    max_len = k_cache.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    lengths = jnp.asarray(lengths, jnp.int32)

    align = 8 if common.use_interpret() else 128
    block_l = min(block_l, round_up(max_len, align))
    lp = round_up(max_len, block_l)
    hp = h if common.use_interpret() else round_up(h, 8)
    kqp = kq if common.use_interpret() else round_up(kq, 8)
    dp = d if common.use_interpret() else round_up(d, 64)

    # ride the query block on the sublane axis next to heads
    qt = jnp.transpose(q, (0, 2, 1, 3))                # (B, H, Kq, D)
    qq = pad_dim(pad_dim(pad_dim(qt, 1, hp), 2, kqp), 3, dp)
    kk = pad_dim(pad_dim(pad_dim(k_cache, 1, lp), 2, hp), 3, dp)
    vv = pad_dim(pad_dim(pad_dim(v_cache, 1, lp), 2, hp), 3, dp)
    num_lb = cdiv(lp, block_l)

    has_bias = bias is not None
    in_specs = [
        pl.BlockSpec((None, hp, kqp, dp), lambda i, j: (i, 0, 0, 0)),
        pl.BlockSpec((None, block_l, hp, dp), lambda i, j: (i, j, 0, 0)),
        pl.BlockSpec((None, block_l, hp, dp), lambda i, j: (i, j, 0, 0)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]
    operands = [qq, kk, vv, lengths]
    if has_bias:
        bb = jax.lax.stop_gradient(
            jnp.asarray(bias, jnp.float32).reshape(b, max_len))
        bb = pad_dim(bb, 1, lp, value=NEG_INF).reshape(b, 1, lp)
        in_specs.append(pl.BlockSpec((None, 1, block_l),
                                     lambda i, j: (i, 0, j)))
        operands.append(bb)
    else:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(jnp.zeros((1,), jnp.float32))

    kernel = functools.partial(
        _decode_block_kernel, sm_scale=float(sm_scale), block_l=block_l,
        num_lb=num_lb, kq=kq, has_bias=has_bias,
        causal_offset=causal_offset)
    o = pl.pallas_call(
        kernel,
        grid=(b, num_lb),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, hp, kqp, dp),
                               lambda i, j: (i, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hp, kqp, dp), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((hp, kqp, 1), jnp.float32),
            pltpu.VMEM((hp, kqp, 1), jnp.float32),
            pltpu.VMEM((hp, kqp, dp), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=int(4 * b * kq * h * max_len * d),
            bytes_accessed=(kk.size + vv.size + qq.size) * q.dtype.itemsize,
            transcendentals=b * kq * h * max_len),
        interpret=common.use_interpret(),
        name=f"stf_decode_attention_q{kq}",
    )(*operands)
    return jnp.transpose(o[:, :h, :kq, :d], (0, 2, 1, 3))


def decode_attention(q, k_cache, v_cache, lengths, *, bias=None,
                     sm_scale=None, block_l=DEFAULT_BLOCK_L,
                     causal_offset=False):
    """One-position attention against a gathered paged cache.

    q: (batch, heads, head_dim) — the single new query per sequence —
    or a (batch, Kq, heads, head_dim) query block (module docstring).
    k_cache/v_cache: (batch, max_len, heads, head_dim) gathered cache
    rows (the :func:`..kv_cache_ops.kv_cache` layout). lengths: (batch,)
    int32 live prefix per sequence — positions >= lengths[b] are masked.
    bias: optional additive (batch, max_len) f32 key bias (padding
    masks for cross-attention); constant under differentiation (the op
    has no gradient — decode is inference-only). Returns q's shape in
    q.dtype.
    """
    if q.ndim == 4:
        return _decode_attention_block(
            q, k_cache, v_cache, lengths, bias=bias, sm_scale=sm_scale,
            block_l=block_l, causal_offset=bool(causal_offset))
    # the single query is a query block of one: Mosaic refuses the
    # (H, D) x (block_l, H, D) contraction a dedicated 1-row kernel
    # needs (no non-contracting lhs dimension), and with Kq = 1 the
    # causal_offset=False mask is exactly "positions < lengths[b]"
    return _decode_attention_block(
        q[:, None], k_cache, v_cache, lengths, bias=bias,
        sm_scale=sm_scale, block_l=block_l, causal_offset=False)[:, 0]


# ---------------------------------------------------------------------------
# Paged variant: K and V read from the STORED pool through the page table
# ---------------------------------------------------------------------------

# paged_heads_per_group's two measured constants: the rows a score tile
# is widened to, and the most lane groups (unrolled bodies) a kernel has
_PAGED_MIN_ROWS = 16
_PAGED_MAX_GROUPS = 4


def paged_heads_per_group(kq, num_heads, head_dim):
    """Heads the paged kernel takes in one block-diagonal query tile,
    from the shapes alone (no knob; ``flash_attention.tiles`` is the
    pattern).

    A page arrives as stored, ``(page_len, heads * head_dim)``: heads on
    the lane axis. The kernel never splits lanes into (H, D). It takes
    the lanes in GROUPS of whole 128-lane tiles (static, tile-aligned
    slices cost nothing) and lays the group's queries out
    block-diagonally — head ``h`` of the group in rows ``[h*Kq,
    (h+1)*Kq)`` and lanes ``[h*D, (h+1)*D)``, zeros elsewhere — so
    ``Q_bd . K_g^T`` is each head's own scores and row-block ``h`` of
    ``P . V_g`` holds head ``h``'s output in its own lanes. A group of G
    heads spends G times the MXU flops on zeros: the smallest group is
    the heads of one lane tile (2 at head_dim 64, 1 at 128), and it is
    widened until a score tile has ``_PAGED_MIN_ROWS`` rows — the
    single-query decode step takes all 16 heads in one (16, 1024) tile
    (1.48 ms a call at lm-big's 96 rows against 1.89 at 8 heads a tile
    and 2.8 at 2 or 4) — and until the groups, each an unrolled body
    that every program lowers at every process start (~0.035 s on the
    benchmark's host), are at most ``_PAGED_MAX_GROUPS``: a 64-query
    prefill block takes 4 heads in a (256, 256) tile, which at the 1-4
    rows admission uses reads what head pairs read (0.208 against
    0.210 ms a call; 1.77 against 1.08 at 32 rows, where the gathered
    view read 3.23; my chip runs, PR 30)."""
    h, d = int(num_heads), int(head_dim)
    if 128 % d == 0:
        g = min(128 // d, h)
    elif d % 128 == 0:
        g = 1
    else:
        g = h            # lanes of a head are no whole tiles: one group
    if h % g:
        g = h
    while g < h and h % (2 * g) == 0 and (
            g * kq < _PAGED_MIN_ROWS or h // g > _PAGED_MAX_GROUPS):
        g *= 2
    return g


def paged_vmem_bytes(kq, num_heads, head_dim, page_len, dtype):
    """Upper estimate of the paged kernel's VMEM: double-buffered page,
    query and output blocks, the float32 accumulator and softmax
    statistics (lane-padded), and a step's largest temporaries."""
    g = paged_heads_per_group(kq, num_heads, head_dim)
    itm = jnp.dtype(dtype).itemsize
    hd = num_heads * head_dim
    rows, n_g, w = round_up(g * kq, 8), num_heads // g, g * head_dim
    kv = 4 * page_len * hd * itm
    q_o = 2 * n_g * rows * w * itm + 2 * round_up(kq, 8) * hd * itm
    scratch = n_g * rows * (w + 2 * 128) * 4
    temps = rows * (2 * w + 4 * round_up(page_len, 128)) * 4
    return kv + q_o + scratch + temps


def _live_pages(length, kq, page_len, n_blocks, causal_offset):
    """Table entries a row reads: up to the furthest position ANY of
    its queries may see (the kernel body and the index map agree)."""
    horizon = length + (kq if causal_offset else 0)
    return jnp.minimum(cdiv(horizon, page_len), n_blocks)


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_scr,
                  l_scr, acc_scr, *, sm_scale, page_len, n_blocks, kq,
                  head_dim, causal_offset):
    """One grid step is ONE table entry: at lm-big's decode call 1, 2,
    4, 8 and 16 entries a step read 1.51, 1.48, 1.46, 1.48 and 1.48 ms
    (a page's two matmuls set the time, not the grid step; my chip runs,
    PR 30), while every further entry is one more operand and one more
    unrolled body to lower at every process start — 0.35 s a program on
    the benchmark's host at two entries, which a model of thirteen
    programs pays as set-up."""
    del tbl_ref                      # read by the index map only
    b, page = pl.program_id(0), pl.program_id(1)
    n_g, rows, w = q_ref.shape
    heads_per_group = w // head_dim
    hi = _HI if q_ref.dtype == jnp.float32 else None
    # a group's lanes are a static, tile-aligned slice, which costs
    # nothing (a fori_loop over the groups with a dynamic lane offset
    # lowers less and ran the 64-query call at half the speed: 2.05
    # against 1.08 ms at 32 rows, my chip run, PR 30)
    groups = [(g, slice(g * w, (g + 1) * w)) for g in range(n_g)]

    @pl.when(page == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    # a dead entry is neither read (its index map repeats the last live
    # page) nor used
    @pl.when(page < _live_pages(length, kq, page_len, n_blocks,
                                causal_offset))
    def _():
        span = page * page_len + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_len), 1)
        if causal_offset:
            jrow = jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_len), 0) % kq
            allowed = length + jrow + 1
        else:
            allowed = length
        visible = span < allowed
        for g, lanes in groups:
            k = k_ref[:, lanes]                        # (page_len, W)
            v = v_ref[:, lanes]
            s = jax.lax.dot_general(
                q_ref[g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=hi) * sm_scale               # (rows, page_len)
            s = jnp.where(visible, s, NEG_INF)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_scr[g] = m_new
            l_scr[g] = alpha * l_scr[g] + jnp.sum(p, axis=-1,
                                                  keepdims=True)
            acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=hi)

    @pl.when(page == pl.num_programs(1) - 1)
    def _():
        # row-block h keeps its own head's lanes; the blocks then sum
        # to the (Kq, W) output of the group
        own = (jax.lax.broadcasted_iota(jnp.int32, (rows, w), 0) // kq
               == jax.lax.broadcasted_iota(jnp.int32, (rows, w), 1)
               // head_dim)
        for g, lanes in groups:
            l = l_scr[g]
            o = acc_scr[g] / jnp.where(l == 0.0, 1.0, l)
            if heads_per_group > 1:
                o = jnp.where(own, o, 0.0)
                if kq == 1:
                    o = jnp.sum(o, axis=0, keepdims=True)
                else:
                    o = sum(o[h * kq:(h + 1) * kq]
                            for h in range(heads_per_group))
            else:
                o = o[:kq]
            o_ref[:, lanes] = o.astype(o_ref.dtype)


# the most query rows one tile of the grouped kernel holds: a (rows,
# page_len) float32 score tile and its exponentials are the step's
# largest temporaries (1 MB each at 1024 x 256)
_GROUPED_MAX_ROWS = 1024


def grouped_heads_per_tile(kq, rep):
    """Query heads of ONE key-value head whose ``kq`` queries share a
    tile of the grouped kernel, from the shapes alone: the largest
    divisor of ``rep = H / H_kv`` that keeps a tile within
    ``_GROUPED_MAX_ROWS`` rows — all 16 at a decode step (16 rows), 4 at
    a 256-query prefill block (1024 rows, four tiles a key-value head)."""
    return max(r for r in range(1, rep + 1)
               if rep % r == 0 and (r * kq <= _GROUPED_MAX_ROWS or r == 1))


def _paged_grouped_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                          m_scr, l_scr, acc_scr, *, sm_scale, page_len,
                          n_blocks, kq, causal_offset):
    """:func:`_paged_kernel` for grouped queries: grid (row, tile of
    query heads, table entry); ``q_ref (H_kv, rows, D)`` holds, a
    key-value head, ``rows / Kq`` of its query heads' queries — head-major
    — which meet that head's own lanes of the page. No zeros are
    multiplied and the output leaves in the tile's own lay-out."""
    del tbl_ref
    b, page = pl.program_id(0), pl.program_id(2)
    n_kv, rows, d = q_ref.shape
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
        span = page * page_len + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_len), 1)
        if causal_offset:
            jrow = jax.lax.broadcasted_iota(
                jnp.int32, (rows, page_len), 0) % kq
            allowed = length + jrow + 1
        else:
            allowed = length
        visible = span < allowed
        for g in range(n_kv):
            lanes = slice(g * d, (g + 1) * d)
            k = k_ref[:, lanes]                        # (page_len, D)
            v = v_ref[:, lanes]
            s = jax.lax.dot_general(
                q_ref[g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=hi) * sm_scale               # (rows, page_len)
            s = jnp.where(visible, s, NEG_INF)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            m_scr[g] = m_new
            l_scr[g] = alpha * l_scr[g] + jnp.sum(p, axis=-1,
                                                  keepdims=True)
            acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32, precision=hi)

    @pl.when(page == pl.num_programs(2) - 1)
    def _():
        for g in range(n_kv):
            l = l_scr[g]
            o_ref[g] = (acc_scr[g] / jnp.where(l == 0.0, 1.0, l)).astype(
                o_ref.dtype)


def _paged_grouped_call(q, k_pool, v_pool, page_tables, lengths, *,
                        sm_scale, causal_offset, interpret):
    b, kq, h, d = q.shape
    _, page_len, hd = k_pool.shape
    n_blocks = page_tables.shape[1]
    n_kv = hd // d
    rep = h // n_kv
    r_blk = grouped_heads_per_tile(kq, rep)
    n_tiles = rep // r_blk
    real = r_blk * kq
    rows = real if interpret else round_up(real, 8)

    # (B, H_kv, tiles, r_blk * Kq, D): a tile's query heads head-major
    qt = jnp.transpose(q.reshape(b, kq, n_kv, n_tiles, r_blk, d),
                       (0, 2, 3, 4, 1, 5)).reshape(b, n_kv, n_tiles, real, d)
    qt = pad_dim(qt, 3, rows).astype(k_pool.dtype)

    def page_of(bi, ti, page, tbl, lens):
        last = _live_pages(lens[bi], kq, page_len, n_blocks,
                           causal_offset) - 1
        entry = jnp.maximum(jnp.minimum(page, last), 0)
        return (tbl[bi * n_blocks + entry], 0, 0)

    page_spec = pl.BlockSpec((None, page_len, hd), page_of)
    tile_spec = pl.BlockSpec((None, n_kv, None, rows, d),
                             lambda bi, ti, page, tbl, lens: (bi, 0, ti, 0, 0))
    kernel = functools.partial(
        _paged_grouped_kernel, sm_scale=float(sm_scale), page_len=page_len,
        n_blocks=n_blocks, kq=kq, causal_offset=causal_offset)
    itm = jnp.dtype(k_pool.dtype).itemsize
    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_tiles, n_blocks),
            in_specs=[tile_spec, page_spec, page_spec],
            out_specs=tile_spec,
            scratch_shapes=[
                pltpu.VMEM((n_kv, rows, 1), jnp.float32),
                pltpu.VMEM((n_kv, rows, 1), jnp.float32),
                pltpu.VMEM((n_kv, rows, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, n_tiles, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * b * kq * h * n_blocks * page_len * d),
            bytes_accessed=int(2 * b * n_tiles * n_blocks * page_len * hd
                               * itm),
            transcendentals=int(b * kq * h * n_blocks * page_len)),
        interpret=interpret,
        name=f"stf_decode_attention_q{kq}_paged_gqa",
    )(jnp.asarray(page_tables, jnp.int32).reshape(-1),
      jnp.asarray(lengths, jnp.int32), qt, k_pool, v_pool)
    o = o[:, :, :, :real].reshape(b, n_kv, n_tiles, r_blk, kq, d)
    return jnp.transpose(o, (0, 4, 1, 2, 3, 5)).reshape(b, kq, h, d)


def paged_decode_attention(q, k_pool, v_pool, page_tables, lengths, *,
                           sm_scale=None, causal_offset=False):
    """Decode attention that reads the paged pool IN PLACE.

    q: (B, H, D) — one query a sequence — or a (B, Kq, H, D) block;
    k_pool/v_pool: the caches as STORED, ``(pages, page_len, H_kv*D)``
    (:func:`..kv_cache_ops.stored_shape`), ``H % H_kv == 0`` (module
    docstring, "GROUPED QUERIES"); page_tables: (B, n_blocks)
    int32 physical pages in logical order; lengths and ``causal_offset``
    as :func:`decode_attention`. Same float32 online softmax, same
    masks, same result as :func:`decode_attention` over the gathered
    logical view — but the view is never built: the table and the
    lengths are scalar-prefetch operands, the K/V block's index map
    reads the table, and a page comes into VMEM lane-dense as it lies in
    HBM. Entries past ``ceil(horizon / page_len)`` are never read: their
    index map repeats the last live page (an unchanged block index
    issues no DMA) and their body is skipped.
    """
    # one trace and one lowering for every layer of a program: the
    # layers call with the same shapes, so the jitted body is traced
    # once and the program calls it once a layer. use_interpret() is
    # still asked at every call: it is part of the key
    return _paged_call(q, k_pool, v_pool, page_tables, lengths,
                       sm_scale=sm_scale, causal_offset=bool(causal_offset),
                       interpret=common.use_interpret())


@functools.partial(jax.jit, static_argnames=("sm_scale", "causal_offset",
                                             "interpret"))
def _paged_call(q, k_pool, v_pool, page_tables, lengths, *, sm_scale,
                causal_offset, interpret):
    if q.ndim == 3:
        return _paged_call(
            q[:, None], k_pool, v_pool, page_tables, lengths,
            sm_scale=sm_scale, causal_offset=False,
            interpret=interpret)[:, 0]
    b, kq, h, d = q.shape
    _, page_len, hd = k_pool.shape
    n_blocks = page_tables.shape[1]
    assert hd % d == 0 and (h * d) % hd == 0 \
        and v_pool.shape == k_pool.shape, (q.shape, k_pool.shape,
                                           v_pool.shape)
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if hd != h * d:
        return _paged_grouped_call(
            q, k_pool, v_pool, page_tables, lengths, sm_scale=sm_scale,
            causal_offset=causal_offset, interpret=interpret)
    g = paged_heads_per_group(kq, h, d)
    n_g, w = h // g, g * d
    rows = g * kq if interpret else round_up(g * kq, 8)

    # block-diagonal queries: (B, n_g, G*Kq, G*D), head hh of a group in
    # rows [hh*Kq, (hh+1)*Kq) and lanes [hh*D, (hh+1)*D)
    qg = jnp.transpose(q.reshape(b, kq, n_g, g, d), (0, 2, 3, 1, 4))
    eye = jnp.eye(g, dtype=q.dtype)
    qbd = (qg[:, :, :, :, None, :] * eye[None, None, :, None, :, None]
           ).reshape(b, n_g, g * kq, w)
    qbd = pad_dim(qbd, 2, rows).astype(k_pool.dtype)

    def page_of(bi, page, tbl, lens):
        last = _live_pages(lens[bi], kq, page_len, n_blocks,
                           causal_offset) - 1
        entry = jnp.maximum(jnp.minimum(page, last), 0)
        return (tbl[bi * n_blocks + entry], 0, 0)

    page_spec = pl.BlockSpec((None, page_len, hd), page_of)
    kernel = functools.partial(
        _paged_kernel, sm_scale=float(sm_scale), page_len=page_len,
        n_blocks=n_blocks, kq=kq, head_dim=d, causal_offset=causal_offset)
    itm = jnp.dtype(k_pool.dtype).itemsize
    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_blocks),
            in_specs=[pl.BlockSpec((None, n_g, rows, w),
                                   lambda bi, page, tbl, lens: (bi, 0, 0, 0)),
                      page_spec, page_spec],
            out_specs=pl.BlockSpec((None, kq, hd),
                                   lambda bi, page, tbl, lens: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((n_g, rows, 1), jnp.float32),
                pltpu.VMEM((n_g, rows, 1), jnp.float32),
                pltpu.VMEM((n_g, rows, w), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kq, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * g * b * kq * h * n_blocks * page_len * d),
            bytes_accessed=int(2 * b * n_blocks * page_len * hd * itm),
            transcendentals=int(b * kq * h * n_blocks * page_len)),
        interpret=interpret,
        name=f"stf_decode_attention_q{kq}_paged",
    )(jnp.asarray(page_tables, jnp.int32).reshape(-1),
      jnp.asarray(lengths, jnp.int32), qbd, k_pool, v_pool)
    return o.reshape(b, kq, h, d)


def paged_decode_attention_xla(q, k_pool, v_pool, page_tables, lengths, *,
                               sm_scale=None, causal_offset=False):
    """The composition the paged programs ran until PR 30, and what
    runs where Mosaic does not (the CPU, a mesh, mode ``off``): gather
    the logical view ``(B, n_blocks * page_len, H, D)`` through the
    page table, then :func:`decode_attention_xla` over it."""
    tables = jnp.asarray(page_tables, jnp.int32)
    b, nb = tables.shape

    def view(pool):
        rows = pool[tables].reshape((b, nb * pool.shape[1], -1))
        # the leading inner dim is inferred, so a head shard (its own
        # heads/tp whole heads on the minor axis) reshapes the same way
        rows = rows.reshape(rows.shape[:-1] + (-1, q.shape[-1]))
        # grouped queries: query head h reads key-value head h // rep
        rep = q.shape[-2] // rows.shape[-2]
        return rows if rep == 1 else jnp.repeat(rows, rep, axis=2)

    return decode_attention_xla(q, view(k_pool), view(v_pool), lengths,
                                sm_scale=sm_scale,
                                causal_offset=causal_offset)


def decode_attention_xla(q, k_cache, v_cache, lengths, *, bias=None,
                         sm_scale=None, block_l=DEFAULT_BLOCK_L,
                         causal_offset=False):
    """Composed-XLA lowering of the DecodeAttention op contract — the
    registry fallback (and the only implementation the cost gate picks
    off-TPU, where Pallas runs in interpret mode). Materializes the
    (B, H, L) f32 score tensor; numerically the same f32 logsumexp
    softmax as :func:`attention_xla`, so the cached decode step matches
    the naive re-forward search to float round-off."""
    if q.ndim == 4:
        b, kq, h, d = q.shape
        max_len = k_cache.shape[1]
        if sm_scale is None:
            sm_scale = 1.0 / (d ** 0.5)
        s = jnp.einsum("bqhd,blhd->bqhl", q.astype(jnp.float32),
                       k_cache.astype(jnp.float32),
                       precision=_HI) * sm_scale
        if bias is not None:
            bb = jax.lax.stop_gradient(
                jnp.asarray(bias, jnp.float32).reshape(b, max_len))
            s = s + bb[:, None, None, :]
        span = jax.lax.broadcasted_iota(
            jnp.int32, (b, kq, h, max_len), 3)
        allowed = jnp.asarray(lengths, jnp.int32)[:, None, None, None]
        if causal_offset:
            allowed = allowed + 1 + jax.lax.broadcasted_iota(
                jnp.int32, (b, kq, h, max_len), 1)
        s = jnp.where(span < allowed, s, NEG_INF)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        p = jnp.exp(s - lse[..., None])
        o = jnp.einsum("bqhl,blhd->bqhd", p,
                       v_cache.astype(jnp.float32), precision=_HI)
        return o.astype(q.dtype)
    b, h, d = q.shape
    max_len = k_cache.shape[1]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("bhd,blhd->bhl", q.astype(jnp.float32),
                   k_cache.astype(jnp.float32), precision=_HI) * sm_scale
    if bias is not None:
        bb = jax.lax.stop_gradient(
            jnp.asarray(bias, jnp.float32).reshape(b, max_len))
        s = s + bb[:, None, :]
    span = jax.lax.broadcasted_iota(jnp.int32, (b, h, max_len), 2)
    s = jnp.where(span < jnp.asarray(lengths, jnp.int32)[:, None, None],
                  s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("bhl,blhd->bhd", p, v_cache.astype(jnp.float32),
                   precision=_HI)
    return o.astype(q.dtype)
