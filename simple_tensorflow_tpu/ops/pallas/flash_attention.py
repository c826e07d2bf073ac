"""Flash attention for TPU (Pallas), forward + custom-VJP backward.

Replaces the reference's attention-as-composed-matmuls path (the reference
has no fused attention; BERT-style models there materialise the [B,H,S,S]
score matrix through batch_matmul + softmax kernels,
ref: tensorflow/core/kernels/{batch_matmul_op,softmax_op}.cc). On TPU the
materialised scores blow HBM bandwidth at long sequence, so we compute
attention with the FlashAttention-2 online-softmax recurrence, tiled to the
MXU.

K/V genuinely stream: the grid's innermost dimension walks K/V blocks (TPU
grids execute sequentially per core), the online-softmax state (m, l, acc)
lives in VMEM scratch across those iterations, and the output block flushes
on the last one. VMEM per program is O(block_q*d + block_k*d) independent of
sequence length. Causally-dead blocks are predicated off with pl.when.

Matmul policy: operands stay in the input dtype (bf16 runs the MXU at
native rate), accumulation is f32 via preferred_element_type, and
Precision.HIGHEST stops XLA from demoting f32 operands to bf16 passes.
The probability matrix is cast back to the input dtype for the P·V and
dS-type matmuls (standard FlashAttention practice).

Layout: (batch, heads, seq, head_dim), bf16/f32 in, f32 accumulation.
The wrapper pads seq to the block size; head_dim stays UNPADDED for the
common 64/128 sizes (Mosaic accepts a half-tile minor dim — padding d=64
to the 128-lane width in HBM doubled every attention tensor, ~11 GB/step
on BERT-base), with only odd sizes rounded up to the next half tile.
Padded keys are masked in-kernel against the true KV length (static), so
softmax stays NaN-free. Per-row stats (m, l, lse, delta) are kept as
(rows, 1) tiles — Mosaic requires sublane×lane-legal block shapes.

Backward follows FlashAttention-2: recompute P block-wise from (Q,K,lse),
dV = P^T dO, dP = dO V^T, dS = P * (dP - delta), dQ = dS K, dK = dS^T Q,
with delta = rowsum(dO * O) precomputed.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common
from .common import (NEG_INF, cdiv, counter_keep_mask, mix32, pad_dim,
                     round_up)

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, contract):
    """dot_general with f32 accumulation. contract=((a_dims),(b_dims)).
    f32 operands get Precision.HIGHEST (stops XLA demoting them to bf16
    MXU passes); bf16 operands run the MXU natively — Mosaic rejects an
    fp32 contract precision on bf16 inputs."""
    precision = _HI if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a, b, dimension_numbers=(contract, ((), ())),
        preferred_element_type=jnp.float32, precision=precision)


def _score_mask(s, qi, kb, block_q, block_k, kv_true, causal):
    """Apply KV-length and causal masking to a (block_q, block_k) score
    tile for Q block qi / K block kb. Single source of truth for fwd+bwd."""
    shape = (s.shape[0], s.shape[1])
    span_q = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    span_k = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    mask = span_k < kv_true
    if causal:
        mask = mask & (span_q >= span_k)
    return jnp.where(mask, s, NEG_INF)


_mix32 = mix32  # moved to common.py (shared with the fused dropout kernel)


def _keep_mask(seed, bh, qi, kb, block_q, block_k, keep_prob):
    """Deterministic dropout keep-mask for score tile (qi, kb) of head bh.

    Counter-based on GLOBAL (row, col) score indices (common.py
    counter_keep_mask) — regenerated bit-identically in the backward
    kernels regardless of grid order AND by the composed-XLA fallback
    lowering (attention_xla), so swapping implementations through the
    kernel registry preserves seeded runs exactly. No mask tensor is
    ever materialized in HBM."""
    shape = (block_q, block_k)
    rows = (qi.astype(jnp.uint32) * jnp.uint32(block_q) +
            jax.lax.broadcasted_iota(jnp.uint32, shape, 0))
    cols = (kb.astype(jnp.uint32) * jnp.uint32(block_k) +
            jax.lax.broadcasted_iota(jnp.uint32, shape, 1))
    return counter_keep_mask(seed, bh, rows, cols, keep_prob)


# ---------------------------------------------------------------------------
# Forward kernel: grid (bh, q_blocks, k_blocks), innermost streams K/V
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, sm_scale, causal, block_q, block_k, kv_true, num_kb,
                has_bias, dropout_rate):
    it = iter(refs)
    q_ref, k_ref, v_ref = next(it), next(it), next(it)
    bias_ref = next(it) if has_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = it
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # A block contributes unless it is wholly above the causal diagonal.
    live = ((qi + 1) * block_q - 1 >= kb * block_k) if causal else True

    @pl.when(live)
    def _():
        q = q_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        s = _dot(q, k, ((1,), (1,))) * sm_scale        # (block_q, block_k)
        if has_bias:
            s = s + bias_ref[:]                        # (1, block_k) f32
        s = _score_mask(s, qi, kb, block_q, block_k, kv_true, causal)

        m_prev, l_prev = m_scr[:], l_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)                # (block_q, 1)
        m_scr[:] = m_new
        # denominator accumulates the UN-dropped sum: dropout scales
        # normalized probs, and elementwise 0/(1/keep) commutes with the
        # final per-row division by l.
        l_scr[:] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_rate > 0.0:
            keep_prob = 1.0 - dropout_rate
            keep = _keep_mask(seed_ref[0], bh, qi, kb, block_q, block_k,
                              keep_prob)
            p = jnp.where(keep, p * (1.0 / keep_prob), 0.0)
        acc_scr[:] = acc_scr[:] * alpha + _dot(
            p.astype(v.dtype), v, ((1,), (0,)))

    @pl.when(kb == num_kb - 1)
    def _():
        l_safe = jnp.where(l_scr[:] == 0.0, 1.0, l_scr[:])
        o_ref[:] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[:] = m_scr[:] + jnp.log(l_safe)


def _fwd(q, k, v, bias, seed, sm_scale, causal, block_q, block_k, kv_true,
         dropout_rate, num_heads):
    bh, q_len, d = q.shape
    kv_pad_len = k.shape[1]
    num_kb = cdiv(kv_pad_len, block_k)
    has_bias = bias is not None
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               kv_true=kv_true, num_kb=num_kb,
                               has_bias=has_bias, dropout_rate=dropout_rate)
    in_specs = [
        pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
        pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
    ]
    operands = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec(
            (None, 1, block_k),
            lambda b, i, j, nh=num_heads: (b // nh, 0, j)))
        operands.append(bias)
    if dropout_rate > 0.0:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        operands.append(seed)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, cdiv(q_len, block_q), num_kb),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, q_len, d), q.dtype),
            jax.ShapeDtypeStruct((bh, q_len, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=int(4 * bh * q_len * kv_true * d * (0.5 if causal else 1.0)),
            bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
            transcendentals=bh * q_len * kv_true),
        interpret=common.use_interpret(),
        name="stf_flash_attention_fwd",
    )(*operands)
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dkdv_kernel(*refs, sm_scale, causal, block_q, block_k, kv_true,
                     num_qb, has_bias, dropout_rate):
    # grid (bh, k_blocks, q_blocks): one K/V block, streaming Q/dO blocks.
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (
        next(it), next(it), next(it), next(it), next(it), next(it))
    bias_ref = next(it) if has_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    dk_ref, dv_ref, dk_scr, dv_scr = it
    bh = pl.program_id(0)
    ki = pl.program_id(1)
    qb = pl.program_id(2)

    @pl.when(qb == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    live = ((qb + 1) * block_q - 1 >= ki * block_k) if causal else True

    @pl.when(live)
    def _():
        k = k_ref[:]
        v = v_ref[:]
        q = q_ref[:]
        do = do_ref[:]
        lse = lse_ref[:]                               # (bq, 1)
        delta = delta_ref[:]
        s = _dot(q, k, ((1,), (1,))) * sm_scale
        if has_bias:
            s = s + bias_ref[:]
        s = _score_mask(s, qb, ki, block_q, block_k, kv_true, causal)
        p = jnp.exp(s - lse)                           # (bq, bk) f32
        dp = _dot(do, v, ((1,), (1,)))                 # (bq, bk)
        if dropout_rate > 0.0:
            keep_prob = 1.0 - dropout_rate
            # NOTE (qb, ki) order: the mask is keyed on (q-block, k-block)
            # exactly as in the forward, though this grid iterates k outer.
            keep = _keep_mask(seed_ref[0], bh, qb, ki, block_q, block_k,
                              keep_prob)
            pc = jnp.where(keep, p * (1.0 / keep_prob), 0.0).astype(do.dtype)
            dp = jnp.where(keep, dp * (1.0 / keep_prob), 0.0)
        else:
            pc = p.astype(do.dtype)
        dv_scr[:] += _dot(pc, do, ((0,), (0,)))        # (bk, d)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_scr[:] += _dot(ds, q, ((0,), (0,)))         # (bk, d)

    @pl.when(qb == num_qb - 1)
    def _():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, sm_scale, causal, block_q, block_k, kv_true,
                   num_kb, has_bias, dropout_rate):
    # grid (bh, q_blocks, k_blocks): one Q block, streaming K/V blocks.
    it = iter(refs)
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref = (
        next(it), next(it), next(it), next(it), next(it), next(it))
    bias_ref = next(it) if has_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    dq_ref, dq_scr = it
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = ((qi + 1) * block_q - 1 >= kb * block_k) if causal else True

    @pl.when(live)
    def _():
        q = q_ref[:]
        do = do_ref[:]
        lse = lse_ref[:]
        delta = delta_ref[:]
        k = k_ref[:]
        v = v_ref[:]
        s = _dot(q, k, ((1,), (1,))) * sm_scale
        if has_bias:
            s = s + bias_ref[:]
        s = _score_mask(s, qi, kb, block_q, block_k, kv_true, causal)
        p = jnp.exp(s - lse)
        dp = _dot(do, v, ((1,), (1,)))
        if dropout_rate > 0.0:
            keep_prob = 1.0 - dropout_rate
            keep = _keep_mask(seed_ref[0], bh, qi, kb, block_q, block_k,
                              keep_prob)
            dp = jnp.where(keep, dp * (1.0 / keep_prob), 0.0)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        dq_scr[:] += _dot(ds, k, ((1,), (0,)))

    @pl.when(kb == num_kb - 1)
    def _():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, kv_true, dropout_rate,
         num_heads, res, g):
    q, k, v, bias, seed, o, lse = res
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1,
                    keepdims=True)                          # (bh, q_len, 1)
    return _bwd_with_delta(sm_scale, causal, block_q, block_k, kv_true,
                           dropout_rate, num_heads,
                           (q, k, v, bias, seed, lse), g, delta)


def _bwd_with_delta(sm_scale, causal, block_q, block_k, kv_true,
                    dropout_rate, num_heads, res, g, delta):
    """Kernel plumbing shared by the plain vjp (delta = rowsum(dO∘O)) and
    the (o, lse) vjp (delta shifted by −dlse)."""
    q, k, v, bias, seed, lse = res
    bh, q_len, d = q.shape
    kv_pad_len = k.shape[1]
    has_bias = bias is not None
    num_qb = cdiv(q_len, block_q)
    num_kb = cdiv(kv_pad_len, block_k)

    def aux(kb_index_map):
        """Optional bias/seed specs+operands; kb_index_map maps grid ids to
        the k-block index (differs between the two bwd grids)."""
        specs, ops = [], []
        if has_bias:
            specs.append(pl.BlockSpec(
                (None, 1, block_k),
                lambda b, i, j, nh=num_heads: (b // nh, 0,
                                               kb_index_map(i, j))))
            ops.append(bias)
        if dropout_rate > 0.0:
            specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
            ops.append(seed)
        return specs, ops

    dkdv = functools.partial(_bwd_dkdv_kernel, sm_scale=sm_scale,
                             causal=causal, block_q=block_q, block_k=block_k,
                             kv_true=kv_true, num_qb=num_qb,
                             has_bias=has_bias, dropout_rate=dropout_rate)
    aux_specs, aux_ops = aux(lambda i, j: i)  # grid (bh, kb, qb)
    dk, dv = pl.pallas_call(
        dkdv,
        grid=(bh, num_kb, num_qb),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, j, 0)),
        ] + aux_specs,
        out_specs=[
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, kv_pad_len, d), k.dtype),
            jax.ShapeDtypeStruct((bh, kv_pad_len, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=common.use_interpret(),
        name="stf_flash_attention_bwd_dkv",
    )(q, k, v, g, lse, delta, *aux_ops)

    dqk = functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                            block_q=block_q, block_k=block_k,
                            kv_true=kv_true, num_kb=num_kb,
                            has_bias=has_bias, dropout_rate=dropout_rate)
    aux_specs, aux_ops = aux(lambda i, j: j)  # grid (bh, qb, kb)
    dq = pl.pallas_call(
        dqk,
        grid=(bh, num_qb, num_kb),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0)),
        ] + aux_specs,
        out_specs=pl.BlockSpec((None, block_q, d),
                               lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, q_len, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=common.use_interpret(),
        name="stf_flash_attention_bwd_dq",
    )(q, k, v, g, lse, delta, *aux_ops)
    grads = [dq, dk, dv]
    # bias is a constant mask under differentiation (stop_gradient'd in the
    # wrapper); seed is integer-typed. Both get symbolic-zero cotangents.
    if has_bias:
        grads.append(jnp.zeros_like(bias))
    else:
        grads.append(None)
    if seed is not None:
        grads.append(np.zeros(seed.shape, dtype=jax.dtypes.float0))
    else:
        grads.append(None)
    return tuple(grads)


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_bhsd(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                kv_true, dropout_rate, num_heads):
    o, _ = _fwd(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                kv_true, dropout_rate, num_heads)
    return o


def _flash_fwd_rule(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                    kv_true, dropout_rate, num_heads):
    o, lse = _fwd(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                  kv_true, dropout_rate, num_heads)
    return o, (q, k, v, bias, seed, o, lse)


_flash_bhsd.defvjp(_flash_fwd_rule, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_bhsd_lse(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                    kv_true, dropout_rate, num_heads):
    """Variant returning (o, lse) — ring attention merges per-block
    partials through the log-sum-exp."""
    return _fwd(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                kv_true, dropout_rate, num_heads)


def _flash_lse_fwd_rule(q, k, v, bias, seed, sm_scale, causal, block_q,
                        block_k, kv_true, dropout_rate, num_heads):
    o, lse = _fwd(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                  kv_true, dropout_rate, num_heads)
    return (o, lse), (q, k, v, bias, seed, o, lse)


def _bwd_lse(sm_scale, causal, block_q, block_k, kv_true, dropout_rate,
             num_heads, res, gs):
    """The lse cotangent folds into the existing kernels: with
    L = f(O, LSE), dS = P∘(dP − delta + dlse) since ∂LSE/∂S = P — i.e.
    run the standard backward with delta' = rowsum(dO∘O) − dlse."""
    g_o, g_lse = gs
    q, k, v, bias, seed, o, lse = res
    do = g_o.astype(jnp.float32)
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1, keepdims=True) \
        - g_lse.astype(jnp.float32)
    return _bwd_with_delta(sm_scale, causal, block_q, block_k, kv_true,
                           dropout_rate, num_heads,
                           (q, k, v, bias, seed, lse), g_o, delta)


_flash_bhsd_lse.defvjp(_flash_lse_fwd_rule, _bwd_lse)


def flash_attention(q, k, v, *, causal=False, sm_scale=None, bias=None,
                    dropout_rate=0.0, dropout_seed=None, return_lse=False,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """Fused attention. q,k,v: (batch, heads, seq, head_dim) (kv seq may
    differ for cross-attention; causal requires equal lengths). Returns
    (batch, heads, q_seq, head_dim) in q.dtype.

    bias: optional additive score bias, broadcast over heads and query
    positions — shape (batch, kv_seq) or any (batch, 1, 1, kv_seq)-style
    squeezable form. This is the padding-mask shape (0 attendable / -1e9
    padded); it is treated as a CONSTANT under differentiation
    (stop_gradient) — per-head trainable biases must use the XLA
    composed-attention path.

    dropout_rate: attention-probability dropout (applied after softmax
    normalization, inverted scaling). Requires dropout_seed, an int32
    scalar/array; the mask is counter-based on (head, row, col) so the
    backward pass regenerates it exactly — nothing is materialized.

    return_lse: also return the per-row log-sum-exp (batch, heads,
    q_seq) in f32 — the merge key for composing partial attentions
    (ring attention); differentiable (the lse cotangent folds into the
    backward's delta term).
    """
    b, h, q_len, d = q.shape
    kv_len = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if causal and q_len != kv_len:
        raise ValueError("causal flash attention needs q_len == kv_len")
    if dropout_rate < 0.0 or dropout_rate >= 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1): {dropout_rate}")
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("flash attention dropout needs dropout_seed")

    align = 8 if common.use_interpret() else 128
    block_q = min(block_q, round_up(q_len, align))
    block_k = min(block_k, round_up(kv_len, align))
    qp_len = round_up(q_len, block_q)
    kp_len = round_up(kv_len, block_k)
    # head_dim 64 stays unpadded: Mosaic accepts a half-tile minor dim, and
    # padding to the 128-lane width in HBM doubles every attention tensor
    # (q/k/v/o and all three gradients) — measured as ~11 GB/step of pure
    # padding traffic on BERT-base. Only odd sizes pad, to the next half
    # tile.
    dp = d if common.use_interpret() else round_up(d, 64)

    qq = pad_dim(pad_dim(q.reshape(b * h, q_len, d), 1, qp_len), 2, dp)
    kk = pad_dim(pad_dim(k.reshape(b * h, kv_len, d), 1, kp_len), 2, dp)
    vv = pad_dim(pad_dim(v.reshape(b * h, kv_len, d), 1, kp_len), 2, dp)

    bb = None
    if bias is not None:
        bb = jnp.asarray(bias, jnp.float32)
        # squeeze broadcast dims down to (batch, kv_seq)
        while bb.ndim > 2:
            sq = next((i for i in range(1, bb.ndim - 1) if bb.shape[i] == 1),
                      None)
            if sq is None:
                raise NotImplementedError(
                    "flash attention bias must broadcast over heads and "
                    f"query positions (got shape {bias.shape}); use the "
                    "XLA composed-attention path for per-head/per-query "
                    "biases")
            bb = jnp.squeeze(bb, axis=sq)
        if bb.shape != (b, kv_len):
            raise ValueError(
                f"flash attention bias: expected (batch, kv_seq)="
                f"({b}, {kv_len}) after squeezing, got {bb.shape}")
        bb = jax.lax.stop_gradient(pad_dim(bb, 1, kp_len))
        bb = bb.reshape(b, 1, kp_len)

    ss = None
    if dropout_rate > 0.0:
        ss = jnp.asarray(dropout_seed, jnp.int32).reshape((1,))

    args = (qq, kk, vv, bb, ss, float(sm_scale), bool(causal),
            int(block_q), int(block_k), int(kv_len),
            float(dropout_rate), int(h))
    if return_lse:
        o, lse = _flash_bhsd_lse(*args)
        o = o[:, :q_len, :d].reshape(b, h, q_len, d)
        lse = lse[:, :q_len, 0].reshape(b, h, q_len)
        return o, lse
    o = _flash_bhsd(*args)
    o = o[:, :q_len, :d].reshape(b, h, q_len, d)
    return o


def attention_xla(q, k, v, *, causal=False, sm_scale=None, bias=None,
                  dropout_rate=0.0, dropout_seed=None, return_lse=False,
                  block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K):
    """The stock composed-XLA lowering of the FlashAttention op contract
    (batch_matmul → softmax → batch_matmul, the reference's attention
    path; ref core/kernels/{batch_matmul_op,softmax_op}.cc) — the
    registry's fallback when the Pallas kernel is ineligible or the
    cost model/autotune prices the fused kernel slower (tiny shapes;
    every shape off-TPU, where Pallas runs in interpret mode).

    Call-compatible with :func:`flash_attention` including in-kernel
    probability dropout: the keep mask is the same counter-based hash
    of (head, row, col) positions, so a seeded run is bit-identically
    reproducible whichever implementation the registry picks. The
    score matrix IS materialized ((B, H, Sq, Sk) f32) — that HBM
    traffic is exactly what the cost-model gate prices against the
    streamed kernel. ``bias`` additionally accepts any
    attention-broadcastable shape (per-head/per-query biases the fused
    kernel rejects)."""
    b, h, q_len, d = q.shape
    kv_len = k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    if causal and q_len != kv_len:
        raise ValueError("causal attention needs q_len == kv_len")
    if dropout_rate < 0.0 or dropout_rate >= 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1): {dropout_rate}")
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("attention dropout needs dropout_seed")
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32), precision=_HI) * sm_scale
    if bias is not None:
        bb = jax.lax.stop_gradient(jnp.asarray(bias, jnp.float32))
        if bb.ndim == 2:                       # (batch, kv_seq) key bias
            bb = bb[:, None, None, :]
        s = s + bb
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (q_len, kv_len), 1)
        s = jnp.where((rows >= cols)[None, None], s, NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)          # (b, h, q) f32
    p = jnp.exp(s - lse[..., None])
    if dropout_rate > 0.0:
        keep_prob = 1.0 - dropout_rate
        seed = jnp.asarray(dropout_seed, jnp.int32).reshape((-1,))[0]
        bh = jax.lax.broadcasted_iota(jnp.uint32,
                                      (b * h, q_len, kv_len), 0)
        rr = jax.lax.broadcasted_iota(jnp.uint32,
                                      (b * h, q_len, kv_len), 1)
        cc = jax.lax.broadcasted_iota(jnp.uint32,
                                      (b * h, q_len, kv_len), 2)
        keep = counter_keep_mask(seed, bh, rr, cc,
                                 keep_prob).reshape(b, h, q_len, kv_len)
        p = jnp.where(keep, p * (1.0 / keep_prob), 0.0)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                   precision=_HI).astype(q.dtype)
    if return_lse:
        return o, lse
    return o


def mha_reference(q, k, v, *, causal=False, sm_scale=None, bias=None):
    """Naive attention in jnp — the numeric reference for tests.
    bias: additive (batch, kv_seq) or (batch, 1, 1, kv_seq) score bias."""
    d = q.shape[-1]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32) * sm_scale,
                   precision=_HI)
    if bias is not None:
        bias = jnp.asarray(bias, jnp.float32)
        if bias.ndim == 2:
            bias = bias[:, None, None, :]
        s = s + bias
    if causal:
        q_len, k_len = s.shape[-2:]
        mask = jnp.tril(jnp.ones((q_len, k_len), bool))
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                      precision=_HI).astype(q.dtype)
