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
