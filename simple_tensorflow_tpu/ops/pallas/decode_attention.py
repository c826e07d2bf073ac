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
  serving benchmark's device time. A grid step takes a BLOCK of table
  entries (PR 38, :func:`paged_pages_per_block`): the pools stay in HBM,
  the block's live pages are copied by hand into one contiguous K and
  one V tile (double-buffered, the next block's copies in flight during
  this one's compute), and one QK dot, one online-softmax update and
  one PV dot serve the whole block — at lm-big's single-query tile 8
  pages a step (1.37 -> 0.62 ms a call, 40 -> 88 % of the bytes' time),
  at the 64-query prefill tile one.
  :func:`paged_decode_attention_xla` — the gathered view plus
  :func:`decode_attention_xla` — is its composition for the CPU, a mesh
  and mode ``off``.

GROUPED QUERIES (PR 35). Both paged lowerings take ``q`` with MORE heads
than the pools hold, ``H % H_kv == 0``: query head ``h`` reads key-value
head ``h // (H / H_kv)``. The kernel then needs no block-diagonal
lay-out: a key-value head's ``H / H_kv`` query heads are ROWS of one query
tile over that head's own lanes (``_paged_grouped_kernel``; its call site
is named ``stf_decode_attention_q<Kq>_paged_gqa``). At ``H == H_kv`` the
kernel is the one above, tile for tile. A head_dim below one lane tile
(64) puts ``128 / head_dim`` key-value heads in a tile
(:func:`kv_heads_per_lane_tile`): their query rows are stacked in one
query tile, each row over its own head's lanes and zero on the others', so
the kernel still slices whole tiles and row-block ``j`` of the result keeps
head ``j``'s lanes (taken out after the call).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...kernels import registry as _kreg
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
# paged_pages_per_block's: a score tile of this many rows already pays a
# page's fixed costs back (the 64-query prefill block's 256), the most
# pages a block takes, and the VMEM its K and V buffers (two each) may fill
_PAGED_BLOCK_ROWS = 128
_PAGED_MAX_PAGES = 8
_PAGED_KV_VMEM = 8 * 2 ** 20


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
    and 2.8 at 2 or 4, one page a grid step) — and until the groups, each
    an unrolled body that every program lowers at every process start
    (~0.035 s on the benchmark's host), are at most
    ``_PAGED_MAX_GROUPS``: a 64-query prefill block takes 4 heads in a
    (256, 256) tile, which at the 1-4 rows admission uses reads what head
    pairs read (0.208 against 0.210 ms a call; 1.77 against 1.08 at 32
    rows, where the gathered view read 3.23; my chip runs, PR 30)."""
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


def paged_pages_per_block(kq, num_heads, head_dim, page_len, n_blocks,
                          dtype):
    """Table entries ONE grid step of the paged kernel takes, from the
    shapes alone (no knob; :func:`paged_heads_per_group` is the pattern).

    A step copies its block's live pages into one contiguous
    ``(P * page_len, H * D)`` buffer for K and one for V, and then runs
    ONE QK matmul, ONE online-softmax update and ONE PV matmul over all
    of them: a page's fixed costs — two small dots, each a 64-deep half
    of the MXU's weight tile, and a rescale of the float32 accumulator —
    are paid once a block. The grid step itself is not the cost: one
    page a step read 1.51, 1.48, 1.46, 1.48 and 1.48 ms a call at 1, 2,
    4, 8 and 16 entries a grid step (my chip runs, PR 30). At lm-big's
    decode call (96 rows, 16 heads x 64 in one 16-row tile, 1,732 live
    pages) a call read 1.371 ms with one page a step (0.79 us a page),
    and 1.135, 0.759, 0.631, 0.621 and 0.633 ms (0.66, 0.44, 0.36, 0.36,
    0.37 us) at P = 1, 2, 4, 8 and 16 — against 0.55 ms (0.32 us) for
    its bytes at 819 GB/s (my chip runs, PR 38): ``_PAGED_MAX_PAGES`` is
    8. A score tile of ``_PAGED_BLOCK_ROWS`` rows or more has a page's
    fixed costs paid back already and takes one page a step: the
    64-query prefill call (4 heads, 256 rows) read 1.591 against 1.691
    ms at 32 rows (my chip run, PR 38). Otherwise ``P`` is the most
    pages that ``_PAGED_MAX_PAGES`` and the table allow and whose two K
    and two V buffers fit ``_PAGED_KV_VMEM``."""
    g = paged_heads_per_group(kq, num_heads, head_dim)
    if g * kq >= _PAGED_BLOCK_ROWS:
        return 1
    per_page = 4 * page_len * num_heads * head_dim * jnp.dtype(dtype).itemsize
    return max(1, min(_PAGED_MAX_PAGES, n_blocks, _PAGED_KV_VMEM // per_page))


def paged_vmem_bytes(kq, num_heads, head_dim, page_len, n_blocks, dtype):
    """Upper estimate of the paged kernel's VMEM: the two K and two V
    block buffers, double-buffered query and output blocks, the float32
    accumulator and softmax statistics (lane-padded), and a step's
    largest temporaries (the score tile's, and one block loaded whole)."""
    g = paged_heads_per_group(kq, num_heads, head_dim)
    per_block = paged_pages_per_block(kq, num_heads, head_dim, page_len,
                                      n_blocks, dtype)
    itm = jnp.dtype(dtype).itemsize
    hd = num_heads * head_dim
    rows, n_g, w = round_up(g * kq, 8), num_heads // g, g * head_dim
    span = per_block * page_len
    kv = 4 * span * hd * itm
    q_o = 2 * n_g * rows * w * itm + 2 * round_up(kq, 8) * hd * itm
    scratch = n_g * rows * (w + 2 * 128) * 4
    temps = rows * (2 * w + 4 * round_up(span, 128)) * 4 + span * hd * itm
    return kv + q_o + scratch + temps


def _live_pages(length, kq, page_len, n_blocks, causal_offset):
    """Table entries a row reads: up to the furthest position ANY of
    its queries may see (the kernel body and the index map agree)."""
    horizon = length + (kq if causal_offset else 0)
    return jnp.minimum(cdiv(horizon, page_len), n_blocks)


def _copy_plan(lengths, kq, page_len, n_blocks, per_block, causal_offset):
    """What :func:`_paged_kernel` copies, one entry a grid step (rows
    major, blocks minor; ``S`` steps) and one more, ``S``, that copies
    nothing: four ``(S + 1,)`` int32 rows, flat — the table index of the
    step's first entry, its live pages, the next step that has any (``S``
    if none; entry ``S`` holds the first) and the buffer it fills (live
    steps take turns). Worked out here, once a call, so that the kernel
    body holds as few scalar operations as it can, and in ``lax`` terms:
    each operation is traced and lowered by every program at every
    process start, and ``jnp``'s own (``jnp.roll``, ``jnp.cumsum``,
    ``%``) trace several (PERF.md Findings PR 38)."""
    lax = jax.lax
    b = lengths.shape[0]
    n_steps = cdiv(n_blocks, per_block)
    steps = b * n_steps
    live = _live_pages(lengths, kq, page_len, n_blocks, causal_offset)
    block = np.arange(n_steps, dtype=np.int32) * per_block
    first = np.append(np.arange(b)[:, None] * n_blocks + block, 0)
    count = lax.clamp(0, lax.broadcast_in_dim(live, (b, n_steps), (0,))
                      - block, per_block)
    count = lax.pad(count.reshape(steps), np.int32(0), [(0, 1, 0)])
    live_step = lax.convert_element_type(count > 0, np.int32)
    on = lax.select(live_step > 0, np.arange(steps + 1, dtype=np.int32),
                    np.full(steps + 1, steps, np.int32))
    # the first live step after each step, wrapping round at the end
    ahead = lax.cummin(on, reverse=True)
    nxt = lax.concatenate([lax.slice(ahead, (1,), (steps + 1,)),
                           lax.slice(ahead, (0,), (1,))], 0)
    slot = lax.cumsum(live_step) & 1
    return lax.concatenate([first.astype(np.int32), count, nxt, slot], 0)


def _paged_kernel(tbl_ref, len_ref, plan_ref, q_ref, k_hbm, v_hbm, o_ref,
                  m_scr, l_scr, acc_scr, k_buf, v_buf, sems, *, sm_scale,
                  page_len, n_blocks, kq, head_dim, causal_offset):
    """One grid step is one BLOCK of :func:`paged_pages_per_block`
    table entries of one row. Its live pages are copied by hand from the
    pools (left in HBM) into one of two K and two V buffers — the next
    live block's copies, this row's or a later row's, start before this
    block's compute (:func:`_copy_plan`) — and the block is attended as
    one ``(P * page_len)``-key tile. A dead block is neither copied nor
    computed. A block, not a page, because a page's two small dots set
    the time where the grid step does not (1 to 16 entries a step read
    alike, PR 30): 8 pages a block read 0.36 us a page where one read
    0.79 (my chip runs, PR 38; the readings are
    :func:`paged_pages_per_block`'s)."""
    b, blk = pl.program_id(0), pl.program_id(1)
    n_g, rows, w = q_ref.shape
    span = k_buf.shape[1]
    per_block = span // page_len
    steps = plan_ref.shape[0] // 4          # the grid's steps + 1
    heads_per_group = w // head_dim
    hi = _HI if q_ref.dtype == jnp.float32 else None
    # a group's lanes are a static, tile-aligned slice, which costs
    # nothing (a fori_loop over the groups with a dynamic lane offset
    # lowers less and ran the 64-query call at half the speed: 2.05
    # against 1.08 ms at 32 rows, my chip run, PR 30)
    groups = [(g, slice(g * w, (g + 1) * w)) for g in range(n_g)]

    def copies(step, start):
        """Start (or wait for) the copies of ``step``'s live pages, K and
        V, into its buffer: one page each, on that buffer's semaphore."""
        first, slot = plan_ref[step], plan_ref[3 * steps + step]

        def one(i, carry):
            page = tbl_ref[first + i]
            for pool, buf in ((k_hbm, k_buf), (v_hbm, v_buf)):
                cp = pltpu.make_async_copy(
                    pool.at[page], buf.at[slot, pl.ds(i * page_len,
                                                      page_len)],
                    sems.at[slot])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        jax.lax.fori_loop(0, plan_ref[steps + step], one, 0)

    step = b * pl.num_programs(1) + blk

    @pl.when(step == 0)
    def _():
        # a block's dead pages keep what the buffer held: pages that were
        # live before, or these zeros — never VMEM's NaN, which a zero
        # probability does not cancel in P . V
        v_buf[...] = jnp.zeros_like(v_buf)
        copies(plan_ref[3 * steps - 1], True)      # the first live step

    @pl.when(blk == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(plan_ref[steps + step] > 0)
    def _():
        copies(plan_ref[2 * steps + step], True)   # the next live step
        copies(step, False)
        slot = plan_ref[3 * steps + step]

        pos = blk * span + jax.lax.broadcasted_iota(
            jnp.int32, (rows, span), 1)
        if causal_offset:
            jrow = jax.lax.broadcasted_iota(jnp.int32, (rows, span), 0) % kq
            allowed = len_ref[b] + jrow + 1
        else:
            allowed = len_ref[b]
        if n_blocks % per_block:
            # the last block runs past the table: those positions do not
            # exist, whatever the length says
            allowed = jnp.minimum(allowed, n_blocks * page_len)
        visible = pos < allowed
        for g, lanes in groups:
            k = k_buf[slot, :, lanes]                  # (span, W)
            v = v_buf[slot, :, lanes]
            s = jax.lax.dot_general(
                q_ref[g], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=hi) * sm_scale               # (rows, span)
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

    @pl.when(blk == pl.num_programs(1) - 1)
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


def grouped_heads_per_tile(kq, rep, pack=1):
    """Query heads of ONE key-value head whose ``kq`` queries share a
    tile of the grouped kernel, from the shapes alone: the largest
    divisor of ``rep = H / H_kv`` that keeps a tile — ``pack`` key-value
    heads' rows at a head_dim below a lane tile — within
    ``_GROUPED_MAX_ROWS`` rows: all 16 at a decode step (16 rows), 4 at a
    256-query prefill block (1024 rows, four tiles a key-value head)."""
    return max(r for r in range(1, rep + 1)
               if rep % r == 0
               and (pack * r * kq <= _GROUPED_MAX_ROWS or r == 1))


def kv_heads_per_lane_tile(head_dim, kv_heads):
    """Key-value heads whose lanes share one 128-lane tile in the grouped
    kernel: ``128 / head_dim`` below a tile (2 at 64) where the heads fill
    whole tiles, else 1."""
    d = int(head_dim)
    pack = 128 // d if d < 128 and 128 % d == 0 else 1
    return pack if int(kv_heads) % pack == 0 else 1


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
    pack = kv_heads_per_lane_tile(d, n_kv)
    r_blk = grouped_heads_per_tile(kq, rep, pack)
    n_tiles = rep // r_blk
    real = r_blk * kq

    if pack == 1:
        rows = real if interpret else round_up(real, 8)
        # (B, H_kv, tiles, r_blk * Kq, D): a tile's query heads head-major
        qt = jnp.transpose(q.reshape(b, kq, n_kv, n_tiles, r_blk, d),
                           (0, 2, 3, 4, 1, 5)).reshape(b, n_kv, n_tiles,
                                                       real, d)
    else:
        # (B, H_kv / pack, tiles, pack * r_blk * Kq, pack * D): key-value
        # head j of a lane tile in rows [j * real, (j + 1) * real) and
        # lanes [j * D, (j + 1) * D), zeros elsewhere
        n_kv, d = n_kv // pack, pack * d
        rows = pack * real if interpret else round_up(pack * real, 8)
        qg = jnp.transpose(
            q.reshape(b, kq, n_kv, pack, n_tiles, r_blk, d // pack),
            (0, 2, 4, 3, 5, 1, 6)).reshape(b, n_kv, n_tiles, pack, real,
                                           1, d // pack)
        eye = jnp.eye(pack, dtype=q.dtype)[:, None, :, None]
        qt = (qg * eye).reshape(b, n_kv, n_tiles, pack * real, d)
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
    if pack > 1:
        # row-block j keeps key-value head j's lanes
        d //= pack
        o = o[:, :, :, :pack * real].reshape(b, n_kv, n_tiles, pack, real,
                                             pack, d)
        n_kv *= pack
        o = jnp.stack([o[:, :, :, j, :, j] for j in range(pack)],
                      2).reshape(b, n_kv, n_tiles, real, d)
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
    lengths are scalar-prefetch operands, the pools stay in HBM, and a
    grid step copies the pages of its block of table entries
    (:func:`paged_pages_per_block`) into VMEM lane-dense as they lie in
    HBM. Entries past ``ceil(horizon / page_len)`` are never read: a
    block copies only its live pages, and a block with none is skipped.
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
    per_block = paged_pages_per_block(kq, h, d, page_len, n_blocks,
                                      k_pool.dtype)
    _kreg.metric_paged_tiles.get_cell(
        str(kq), str(g), str(per_block)).increase_by(1)
    n_g, w = h // g, g * d
    rows = g * kq if interpret else round_up(g * kq, 8)

    # block-diagonal queries: (B, n_g, G*Kq, G*D), head hh of a group in
    # rows [hh*Kq, (hh+1)*Kq) and lanes [hh*D, (hh+1)*D)
    qg = jnp.transpose(q.reshape(b, kq, n_g, g, d), (0, 2, 3, 1, 4))
    eye = jnp.eye(g, dtype=q.dtype)
    qbd = (qg[:, :, :, :, None, :] * eye[None, None, :, None, :, None]
           ).reshape(b, n_g, g * kq, w)
    qbd = pad_dim(qbd, 2, rows).astype(k_pool.dtype)

    lengths = jnp.asarray(lengths, jnp.int32)
    kernel = functools.partial(
        _paged_kernel, sm_scale=float(sm_scale), page_len=page_len,
        n_blocks=n_blocks, kq=kq, head_dim=d, causal_offset=causal_offset)
    itm = jnp.dtype(k_pool.dtype).itemsize
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    block_buf = pltpu.VMEM((2, per_block * page_len, hd), k_pool.dtype)
    o = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, cdiv(n_blocks, per_block)),
            in_specs=[pl.BlockSpec((None, n_g, rows, w),
                                   lambda bi, blk, *_: (bi, 0, 0, 0)),
                      in_hbm, in_hbm],
            out_specs=pl.BlockSpec((None, kq, hd),
                                   lambda bi, blk, *_: (bi, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((n_g, rows, 1), jnp.float32),
                pltpu.VMEM((n_g, rows, 1), jnp.float32),
                pltpu.VMEM((n_g, rows, w), jnp.float32),
                block_buf, block_buf,
                pltpu.SemaphoreType.DMA((2,)),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kq, hd), q.dtype),
        # a block's copies run ahead into a later row: the grid is
        # walked in order on one core
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=int(4 * g * b * kq * h * n_blocks * page_len * d),
            bytes_accessed=int(2 * b * n_blocks * page_len * hd * itm),
            transcendentals=int(b * kq * h * n_blocks * page_len)),
        interpret=interpret,
        name=f"stf_decode_attention_q{kq}_paged",
    )(jnp.asarray(page_tables, jnp.int32).reshape(-1), lengths,
      _copy_plan(lengths, kq, page_len, n_blocks, per_block, causal_offset),
      qbd, k_pool, v_pool)
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
