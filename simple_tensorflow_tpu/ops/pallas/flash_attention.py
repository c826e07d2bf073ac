"""Flash attention for TPU (Pallas), forward + custom-VJP backward.

Replaces the reference's attention-as-composed-matmuls path (the reference
has no fused attention; BERT-style models there materialise the [B,H,S,S]
score matrix through batch_matmul + softmax kernels,
ref: tensorflow/core/kernels/{batch_matmul_op,softmax_op}.cc). On TPU the
materialised scores blow HBM bandwidth at long sequence, so scores live
only in VMEM, a tile at a time.

Tiles are sized from the shapes (:func:`tiles`, the one place they come
from): the largest whose grid step fits :data:`VMEM_BUDGET` by
:func:`vmem_bytes`. A grid step costs ~0.35 us before it computes
anything, so at 128 x 128 tiles (the fixed size until PR 28) a s512 head
was 16 steps of 10 ns of MXU each and the kernels ran at 3 % of their
roofline. Two regimes follow from the rule, and what each makes static is
taken out at trace time:

* **single pass** — the whole key range is ONE tile (s <= 1024 at
  head_dim 64/128 in bfloat16). Softmax is computed outright: no running
  max/sum, no rescale, no scratch. The backward recomputes P and dS once
  and ONE call emits dQ, dK and dV (five matmuls, not seven). Where one
  tile covers a whole head, a step walks as many heads of a batch row as
  fit the budget. VMEM grows with the sequence: a (512, 512) float32
  score tile is 1 MB, and the backward holds three.
* **streamed** — longer sequences (ring attention's blocks, 2048-8192):
  FlashAttention-2's online softmax. The grid's innermost dimension walks
  K/V tiles (TPU grids execute sequentially per core), the state
  (m, l, acc) lives in VMEM scratch across those steps, the output block
  flushes on the last one, and the backward is two calls (dK/dV with the
  K/V tile resident, dQ with the Q tile resident). VMEM per step is
  O(block_q * block_k), independent of the sequence. Causally-dead tiles
  are predicated off with pl.when.

In both, the key-length mask is emitted only when the key range was
padded and the causal mask only when asked for. Which regime and tiles a
trace took is counted on ``/stf/kernels/flash_tiles``.

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

Backward follows FlashAttention-2: recompute P tile-wise from (Q,K,lse),
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

from ...kernels import registry as _kreg
from . import common
from .common import NEG_INF, cdiv, counter_keep_mask, pad_dim, round_up

_HI = jax.lax.Precision.HIGHEST

# ---------------------------------------------------------------------------
# The tile rule
# ---------------------------------------------------------------------------

# A grid step's blocks, scratch and live score tiles are sized to this
# much VMEM, under Mosaic's 16 MiB scoped default (v5e). The estimate is
# an upper one (every score-sized value counted live at once), so the
# rest is room for what it cannot see: relayouts, the transposes of the
# backward's P and dS. Measured at (48, 12, 512, 64) bfloat16: the
# backward at four heads a step (estimated 15.8 MiB) still compiled, at
# six (21.3) Mosaic ran out of VMEM.
VMEM_BUDGET = 14 * 1024 * 1024
_LANES = 128
# (query rows, keys) a step may hold at most, tried in order: the
# largest sides that measured faster than the next smaller (v5e, bf16,
# head_dim 64: s2048 forward 0.64 ms at 512x1024, 0.99 at 512x512, 2.05
# at 256x256, 4.1 at 128x128; 1024x1024 no faster than 512x1024).
_STREAMED = ((512, 1024), (512, 512), (256, 256), (128, 128))
_SINGLE_PASS_MIN_Q = 256  # fewer query rows a step: the step count is back


def _split(length, cap, align):
    """Block size that covers ``length`` in the fewest blocks of at most
    ``cap``, evenly sized (padding stays under one ``align`` a block)."""
    padded = round_up(length, align)
    return round_up(cdiv(padded, cdiv(padded, cap)), align)


def vmem_bytes(block_q, block_k, head_dim, dtype, backward, heads=1):
    """Upper estimate of one grid step's VMEM, from the shapes alone:
    every pipelined block of its ``heads`` heads twice (double
    buffering), the float32 accumulators, and the score-sized values of
    the one head at work live at once. A minor dimension occupies whole
    128-lane tiles, so a (rows, 1) statistics column costs as much as a
    (rows, 128) block and head_dim 64 as much as 128."""
    item = jnp.dtype(dtype).itemsize
    lanes = round_up(head_dim, _LANES)
    q_blk, k_blk = block_q * lanes * item, block_k * lanes * item
    q_acc, k_acc = block_q * lanes * 4, block_k * lanes * 4
    column = block_q * _LANES * 4
    bias = 2 * 8 * block_k * 4
    tile = block_q * block_k
    if backward:
        # q, dO, dQ; k, v, dK, dV; lse, delta | dK, dV, dQ accumulators |
        # P, dP, dS in float32 and P, dS cast for their matmuls
        return (2 * heads * (3 * q_blk + 4 * k_blk + 2 * column) + bias
                + 2 * k_acc + q_acc + tile * (3 * 4 + 2 * item))
    # q, o; k, v; lse | acc, m, l | S, P in float32 and P cast
    return (2 * heads * (2 * q_blk + 2 * k_blk + column) + bias
            + q_acc + 2 * column + tile * (2 * 4 + item))


def _fits(block_q, block_k, head_dim, dtype, heads=1):
    """Whether the backward step — the larger of the two — fits."""
    return vmem_bytes(block_q, block_k, head_dim, dtype, True,
                      heads) <= VMEM_BUDGET


def _heads_that_fit(num_heads, block_q, block_k, head_dim, dtype):
    """Heads a whole-head step (one tile covers a head) may hold: the
    largest divisor of a batch row's heads (they share its bias row)
    that fits the budget. Fewer, longer steps: at (48, 12, 512, 64) a
    forward call measured 1.35 ms at one head a step, 1.27 at two, 1.22
    at four; at (192, 12, 128, 64) 1.91, 1.48, 1.28 and 1.19 at twelve
    (v5e, bf16)."""
    return max(g for g in range(1, num_heads + 1)
               if num_heads % g == 0
               and (g == 1 or _fits(block_q, block_k, head_dim, dtype, g)))


def tiles(q_len, kv_len, head_dim, dtype, causal=False, num_heads=1,
          align=_LANES):
    """(block_q, block_k, heads a step) for these shapes — the one place
    tile sizes come from. The largest whose backward step fits
    :data:`VMEM_BUDGET`:

    * the whole key range in ONE tile whenever that fits with at least
      ``_SINGLE_PASS_MIN_Q`` query rows a step (or all of them) — the
      *single-pass* regime: softmax is computed outright, no running
      statistics, and one backward call emits dQ, dK and dV. Where one
      tile covers a whole head, a step takes as many heads of a batch
      row as fit (:func:`_heads_that_fit`);
    * else the first of ``_STREAMED`` that fits — the *streamed* regime
      (FlashAttention-2's online softmax over key tiles), one head a
      step.

    Depends on shapes and dtype alone. ``causal`` does not change the
    choice: at (8, 16, 512, 64) the single pass measured 0.23 ms forward
    against 0.51 (256-wide tiles) and 0.84 (128-wide) although it
    computes the masked half too, and streamed tiles skip dead blocks
    whatever their size.
    """
    del causal
    whole_k = round_up(kv_len, align)
    for cap in (_STREAMED[0][0], _SINGLE_PASS_MIN_Q):
        block_q = _split(q_len, cap, align)
        if _fits(block_q, whole_k, head_dim, dtype):
            heads = 1
            if block_q >= q_len:
                heads = _heads_that_fit(num_heads, block_q, whole_k,
                                        head_dim, dtype)
            return block_q, whole_k, heads
    for cap_q, cap_k in _STREAMED:
        block_q, block_k = _split(q_len, cap_q, align), _split(kv_len, cap_k,
                                                               align)
        if _fits(block_q, block_k, head_dim, dtype):
            break
    return block_q, block_k, 1


def _dot(a, b, contract):
    """dot_general with f32 accumulation. contract=((a_dims),(b_dims)).
    f32 operands get Precision.HIGHEST (stops XLA demoting them to bf16
    MXU passes); bf16 operands run the MXU natively — Mosaic rejects an
    fp32 contract precision on bf16 inputs."""
    precision = _HI if a.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a, b, dimension_numbers=(contract, ((), ())),
        preferred_element_type=jnp.float32, precision=precision)


_NT = ((1,), (1,))   # a @ b.T
_NN = ((1,), (0,))   # a @ b
_TN = ((0,), (0,))   # a.T @ b


def _score_mask(s, row0, col0, kv_limit, causal):
    """KV-length and causal masking of a score tile whose first element
    is global (row0, col0). Single source of truth for fwd+bwd.
    ``kv_limit`` is None when the key range holds no padded key: with
    ``causal`` off nothing is emitted at all."""
    mask = None
    if kv_limit is not None or causal:
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if kv_limit is not None:
        mask = cols < kv_limit
    if causal:
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        mask = (rows >= cols) if mask is None else mask & (rows >= cols)
    return s if mask is None else jnp.where(mask, s, NEG_INF)


def _scores(q, k, bias, row0, col0, *, sm_scale, kv_limit, causal):
    """Masked float32 scores of the tile at global (row0, col0)."""
    s = _dot(q, k, _NT) * sm_scale                     # (rows, cols) f32
    if bias is not None:
        s = s + bias                                   # (1, cols) f32
    return _score_mask(s, row0, col0, kv_limit, causal)


def _keep_mask(seed, bh, row0, col0, shape, keep_prob):
    """Deterministic dropout keep-mask for the score tile of head bh whose
    first element is global (row0, col0).

    Counter-based on GLOBAL (row, col) score indices (common.py
    counter_keep_mask) — regenerated bit-identically in the backward
    kernels regardless of tile sizes and grid order AND by the
    composed-XLA fallback lowering (attention_xla), so swapping
    implementations through the kernel registry preserves seeded runs
    exactly. No mask tensor is ever materialized in HBM."""
    rows = (jnp.asarray(row0).astype(jnp.uint32) +
            jax.lax.broadcasted_iota(jnp.uint32, shape, 0))
    cols = (jnp.asarray(col0).astype(jnp.uint32) +
            jax.lax.broadcasted_iota(jnp.uint32, shape, 1))
    return counter_keep_mask(seed, bh, rows, cols, keep_prob)


def _dropped(x, seed_ref, bh, row0, col0, dropout_rate):
    """``x`` (a score-shaped tile at global (row0, col0)) with this
    head's dropout applied: kept entries scaled by 1/keep_prob."""
    if dropout_rate == 0.0:
        return x
    keep_prob = 1.0 - dropout_rate
    keep = _keep_mask(seed_ref[0], bh, row0, col0, x.shape, keep_prob)
    return jnp.where(keep, x * (1.0 / keep_prob), 0.0)


def _unpack(refs, n_main, has_bias, dropout_rate):
    """(main input refs, bias_ref, seed_ref, the rest) of a kernel's
    positional refs: optional bias and seed follow the main inputs."""
    it = iter(refs)
    main = [next(it) for _ in range(n_main)]
    bias_ref = next(it) if has_bias else None
    seed_ref = next(it) if dropout_rate > 0.0 else None
    return main, bias_ref, seed_ref, list(it)


# ---------------------------------------------------------------------------
# Forward kernels
# ---------------------------------------------------------------------------

def _fwd_single_kernel(*refs, sm_scale, causal, block_q, kv_limit, heads,
                       has_bias, dropout_rate):
    # single pass, grid (bh / heads, q_blocks): every key of a row is in
    # this tile, so softmax is computed outright — no running max/sum,
    # no rescale, no scratch. Blocks hold ``heads`` heads of one batch
    # row, walked in turn.
    (q_ref, k_ref, v_ref), bias_ref, seed_ref, (o_ref, lse_ref) = _unpack(
        refs, 3, has_bias, dropout_rate)
    row0 = pl.program_id(1) * block_q
    bias = bias_ref[:] if has_bias else None
    for g in range(heads):
        bh = pl.program_id(0) * heads + g
        v = v_ref[g]
        s = _scores(q_ref[g], k_ref[g], bias, row0, 0, sm_scale=sm_scale,
                    kv_limit=kv_limit, causal=causal)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)         # >= 1: exp(0) at m
        # the denominator is the UN-dropped sum: dropout scales
        # normalized probs, and elementwise 0/(1/keep) commutes with
        # the per-row division by l.
        p = _dropped(p, seed_ref, bh, row0, 0, dropout_rate)
        o_ref[g] = (_dot(p.astype(v.dtype), v, _NN) / l).astype(o_ref.dtype)
        lse_ref[g] = m + jnp.log(l)


def _fwd_kernel(*refs, sm_scale, causal, block_q, block_k, kv_limit, num_kb,
                has_bias, dropout_rate):
    # streamed, grid (bh, q_blocks, k_blocks): the innermost dimension
    # walks K/V tiles, the online-softmax state lives in scratch.
    (q_ref, k_ref, v_ref), bias_ref, seed_ref, rest = _unpack(
        refs, 3, has_bias, dropout_rate)
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
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
        row0, col0 = qi * block_q, kb * block_k
        v = v_ref[:]
        s = _scores(q_ref[:], k_ref[:], bias_ref[:] if has_bias else None,
                    row0, col0, sm_scale=sm_scale, kv_limit=kv_limit,
                    causal=causal)
        m_prev, l_prev = m_scr[:], l_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)                # (block_q, 1)
        m_scr[:] = m_new
        # un-dropped denominator, as in the single pass
        l_scr[:] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        p = _dropped(p, seed_ref, bh, row0, col0, dropout_rate)
        acc_scr[:] = acc_scr[:] * alpha + _dot(p.astype(v.dtype), v, _NN)

    @pl.when(kb == num_kb - 1)
    def _():
        l_safe = jnp.where(l_scr[:] == 0.0, 1.0, l_scr[:])
        o_ref[:] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[:] = m_scr[:] + jnp.log(l_safe)


def _aux(bias, seed, block_k, batch_of, kb_of):
    """Specs and operands of the optional key bias and dropout seed.
    ``batch_of`` maps a step's leading grid id to its batch row,
    ``kb_of`` its inner grid ids to its key block."""
    specs, ops = [], []
    if bias is not None:
        specs.append(pl.BlockSpec(
            (None, 1, block_k),
            lambda b, *ij: (batch_of(b), 0, kb_of(*ij))))
        ops.append(bias)
    if seed is not None:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        ops.append(seed)
    return specs, ops


def _fwd(q, k, v, bias, seed, sm_scale, causal, block_q, block_k, kv_true,
         dropout_rate, num_heads, heads):
    bh, q_len, d = q.shape
    kv_pad_len = k.shape[1]
    num_qb, num_kb = cdiv(q_len, block_q), cdiv(kv_pad_len, block_k)
    static = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  kv_limit=None if kv_true == kv_pad_len else kv_true,
                  has_bias=bias is not None, dropout_rate=dropout_rate)
    if num_kb == 1:
        kernel = functools.partial(_fwd_single_kernel, heads=heads, **static)
        grid = (bh // heads, num_qb)
        aux_specs, aux_ops = _aux(bias, seed, block_k,
                                  lambda b: b * heads // num_heads,
                                  lambda i: 0)
        lead, q_map, k_map = heads, (lambda b, i: (b, i, 0)), (
            lambda b, i: (b, 0, 0))
        scratch = []
    else:
        kernel = functools.partial(_fwd_kernel, block_k=block_k,
                                   num_kb=num_kb, **static)
        grid = (bh, num_qb, num_kb)
        aux_specs, aux_ops = _aux(bias, seed, block_k,
                                  lambda b: b // num_heads, lambda i, j: j)
        lead, q_map, k_map = None, (lambda b, i, j: (b, i, 0)), (
            lambda b, i, j: (b, j, 0))
        scratch = [
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((lead, block_q, d), q_map),
            pl.BlockSpec((lead, block_k, d), k_map),
            pl.BlockSpec((lead, block_k, d), k_map),
        ] + aux_specs,
        out_specs=[
            pl.BlockSpec((lead, block_q, d), q_map),
            pl.BlockSpec((lead, block_q, 1), q_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, q_len, d), q.dtype),
            jax.ShapeDtypeStruct((bh, q_len, 1), jnp.float32),
        ],
        scratch_shapes=scratch,
        cost_estimate=pl.CostEstimate(
            flops=int(4 * bh * q_len * kv_true * d * (0.5 if causal else 1.0)),
            bytes_accessed=(q.size + k.size + v.size) * q.dtype.itemsize,
            transcendentals=bh * q_len * kv_true),
        interpret=common.use_interpret(),
        name="stf_flash_attention_fwd",
    )(q, k, v, *aux_ops)
    return o, lse


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_tile(q, k, v, do, lse, delta, bias, seed_ref, bh, row0, col0, *,
              sm_scale, kv_limit, causal, dropout_rate, want_p):
    """(P as dV's matmul takes it, or None; dS) of one score tile:
    recompute P from (Q, K, lse), dP = dO V^T, dS = P∘(dP − delta)."""
    s = _scores(q, k, bias, row0, col0, sm_scale=sm_scale,
                kv_limit=kv_limit, causal=causal)
    p = jnp.exp(s - lse)                               # (rows, cols) f32
    dp = _dropped(_dot(do, v, _NT), seed_ref, bh, row0, col0, dropout_rate)
    ds = p * (dp - delta) * sm_scale
    pc = None
    if want_p:
        pc = _dropped(p, seed_ref, bh, row0, col0,
                      dropout_rate).astype(do.dtype)
    return pc, ds.astype(q.dtype)


def _bwd_single_kernel(*refs, sm_scale, causal, block_q, kv_limit, num_qb,
                       heads, has_bias, dropout_rate):
    # single pass, grid (bh / heads, q_blocks): the whole key range is
    # one tile, so the tile a dK/dV step recomputes is the very tile dQ
    # needs — one recompute of P and dS feeds all three gradients.
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), bias_ref, seed_ref, \
        rest = _unpack(refs, 6, has_bias, dropout_rate)
    dq_ref, dk_ref, dv_ref, *scratch = rest
    qb = pl.program_id(1)
    bias = bias_ref[:] if has_bias else None

    def gradients(g):
        q, k, do = q_ref[g], k_ref[g], do_ref[g]
        pc, ds = _bwd_tile(
            q, k, v_ref[g], do, lse_ref[g], delta_ref[g], bias, seed_ref,
            pl.program_id(0) * heads + g, qb * block_q, 0,
            sm_scale=sm_scale, kv_limit=kv_limit, causal=causal,
            dropout_rate=dropout_rate, want_p=True)
        dq_ref[g] = _dot(ds, k, _NN).astype(dq_ref.dtype)
        return _dot(ds, q, _TN), _dot(pc, do, _TN)     # dK, dV: (keys, d)

    if not scratch:            # a head is one step: nothing accumulates
        for g in range(heads):
            dk, dv = gradients(g)
            dk_ref[g] = dk.astype(dk_ref.dtype)
            dv_ref[g] = dv.astype(dv_ref.dtype)
        return

    dk_scr, dv_scr = scratch

    @pl.when(qb == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    dk, dv = gradients(0)
    dk_scr[:] += dk
    dv_scr[:] += dv

    @pl.when(qb == num_qb - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dkdv_kernel(*refs, sm_scale, causal, block_q, block_k, kv_limit,
                     num_qb, has_bias, dropout_rate):
    # streamed, grid (bh, k_blocks, q_blocks): one K/V block, streaming
    # Q/dO blocks.
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), bias_ref, seed_ref, \
        rest = _unpack(refs, 6, has_bias, dropout_rate)
    dk_ref, dv_ref, dk_scr, dv_scr = rest
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
        q, do = q_ref[:], do_ref[:]
        # the mask is keyed on global (row, col) exactly as in the
        # forward, though this grid iterates k outer.
        pc, ds = _bwd_tile(
            q, k_ref[:], v_ref[:], do, lse_ref[:], delta_ref[:],
            bias_ref[:] if has_bias else None, seed_ref, bh,
            qb * block_q, ki * block_k, sm_scale=sm_scale,
            kv_limit=kv_limit, causal=causal, dropout_rate=dropout_rate,
            want_p=True)
        dv_scr[:] += _dot(pc, do, _TN)                 # (bk, d)
        dk_scr[:] += _dot(ds, q, _TN)                  # (bk, d)

    @pl.when(qb == num_qb - 1)
    def _():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, sm_scale, causal, block_q, block_k, kv_limit,
                   num_kb, has_bias, dropout_rate):
    # streamed, grid (bh, q_blocks, k_blocks): one Q block, streaming
    # K/V blocks.
    (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref), bias_ref, seed_ref, \
        rest = _unpack(refs, 6, has_bias, dropout_rate)
    dq_ref, dq_scr = rest
    bh = pl.program_id(0)
    qi = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    live = ((qi + 1) * block_q - 1 >= kb * block_k) if causal else True

    @pl.when(live)
    def _():
        k = k_ref[:]
        _, ds = _bwd_tile(
            q_ref[:], k, v_ref[:], do_ref[:], lse_ref[:], delta_ref[:],
            bias_ref[:] if has_bias else None, seed_ref, bh,
            qi * block_q, kb * block_k, sm_scale=sm_scale,
            kv_limit=kv_limit, causal=causal, dropout_rate=dropout_rate,
            want_p=False)
        dq_scr[:] += _dot(ds, k, _NN)

    @pl.when(kb == num_kb - 1)
    def _():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _bwd(sm_scale, causal, block_q, block_k, kv_true, dropout_rate,
         num_heads, heads, res, g):
    q, k, v, bias, seed, o, lse = res
    do = g.astype(jnp.float32)
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1,
                    keepdims=True)                          # (bh, q_len, 1)
    return _bwd_with_delta(sm_scale, causal, block_q, block_k, kv_true,
                           dropout_rate, num_heads, heads,
                           (q, k, v, bias, seed, lse), g, delta)


def _bwd_with_delta(sm_scale, causal, block_q, block_k, kv_true,
                    dropout_rate, num_heads, heads, res, g, delta):
    """Kernel plumbing shared by the plain vjp (delta = rowsum(dO∘O)) and
    the (o, lse) vjp (delta shifted by −dlse)."""
    q, k, v, bias, seed, lse = res
    bh, q_len, d = q.shape
    kv_pad_len = k.shape[1]
    num_qb = cdiv(q_len, block_q)
    num_kb = cdiv(kv_pad_len, block_k)
    static = dict(sm_scale=sm_scale, causal=causal, block_q=block_q,
                  kv_limit=None if kv_true == kv_pad_len else kv_true,
                  has_bias=bias is not None, dropout_rate=dropout_rate)
    operands = (q, k, v, g, lse, delta)
    q_shape = jax.ShapeDtypeStruct((bh, q_len, d), q.dtype)
    k_shape = jax.ShapeDtypeStruct((bh, kv_pad_len, d), k.dtype)

    def specs(lead, qb_of, kb_of):
        """(in_specs of the six operands, a dQ-shaped and a dK-shaped
        out_spec) for a grid whose inner ids map to (q block, k block)."""
        def q_spec(last=d):
            return pl.BlockSpec((lead, block_q, last),
                                lambda b, *ij: (b, qb_of(*ij), 0))
        k_spec = pl.BlockSpec((lead, block_k, d),
                              lambda b, *ij: (b, kb_of(*ij), 0))
        return ([q_spec(), k_spec, k_spec, q_spec(), q_spec(1), q_spec(1)],
                q_spec(), k_spec)

    if num_kb == 1:
        in_specs, dq_spec, dk_spec = specs(heads, lambda i: i, lambda i: 0)
        aux_specs, aux_ops = _aux(bias, seed, block_k,
                                  lambda b: b * heads // num_heads,
                                  lambda i: 0)
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_single_kernel, num_qb=num_qb, heads=heads,
                              **static),
            grid=(bh // heads, num_qb),
            in_specs=in_specs + aux_specs,
            out_specs=[dq_spec, dk_spec, dk_spec],
            out_shape=[q_shape, k_shape, k_shape],
            scratch_shapes=[] if num_qb == 1 else [
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
            interpret=common.use_interpret(),
            name="stf_flash_attention_bwd",
        )(*operands, *aux_ops)
    else:
        static["block_k"] = block_k
        batch_of = lambda b: b // num_heads
        # grid (bh, kb, qb)
        in_specs, _, dk_spec = specs(None, lambda i, j: j, lambda i, j: i)
        aux_specs, aux_ops = _aux(bias, seed, block_k, batch_of,
                                  lambda i, j: i)
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkdv_kernel, num_qb=num_qb, **static),
            grid=(bh, num_kb, num_qb),
            in_specs=in_specs + aux_specs,
            out_specs=[dk_spec, dk_spec],
            out_shape=[k_shape, k_shape],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
            interpret=common.use_interpret(),
            name="stf_flash_attention_bwd_dkv",
        )(*operands, *aux_ops)

        # grid (bh, qb, kb)
        in_specs, dq_spec, _ = specs(None, lambda i, j: i, lambda i, j: j)
        aux_specs, aux_ops = _aux(bias, seed, block_k, batch_of,
                                  lambda i, j: j)
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, num_kb=num_kb, **static),
            grid=(bh, num_qb, num_kb),
            in_specs=in_specs + aux_specs,
            out_specs=dq_spec,
            out_shape=q_shape,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            interpret=common.use_interpret(),
            name="stf_flash_attention_bwd_dq",
        )(*operands, *aux_ops)
    grads = [dq, dk, dv]
    # bias is a constant mask under differentiation (stop_gradient'd in the
    # wrapper); seed is integer-typed. Both get symbolic-zero cotangents.
    if bias is not None:
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

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash_bhsd(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                kv_true, dropout_rate, num_heads, heads):
    o, _ = _fwd(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                kv_true, dropout_rate, num_heads, heads)
    return o


def _flash_fwd_rule(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                    kv_true, dropout_rate, num_heads, heads):
    o, lse = _fwd(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                  kv_true, dropout_rate, num_heads, heads)
    return o, (q, k, v, bias, seed, o, lse)


_flash_bhsd.defvjp(_flash_fwd_rule, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash_bhsd_lse(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                    kv_true, dropout_rate, num_heads, heads):
    """Variant returning (o, lse) — ring attention merges per-block
    partials through the log-sum-exp."""
    return _fwd(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                kv_true, dropout_rate, num_heads, heads)


def _flash_lse_fwd_rule(q, k, v, bias, seed, sm_scale, causal, block_q,
                        block_k, kv_true, dropout_rate, num_heads, heads):
    o, lse = _fwd(q, k, v, bias, seed, sm_scale, causal, block_q, block_k,
                  kv_true, dropout_rate, num_heads, heads)
    return (o, lse), (q, k, v, bias, seed, o, lse)


def _bwd_lse(sm_scale, causal, block_q, block_k, kv_true, dropout_rate,
             num_heads, heads, res, gs):
    """The lse cotangent folds into the existing kernels: with
    L = f(O, LSE), dS = P∘(dP − delta + dlse) since ∂LSE/∂S = P — i.e.
    run the standard backward with delta' = rowsum(dO∘O) − dlse."""
    g_o, g_lse = gs
    q, k, v, bias, seed, o, lse = res
    do = g_o.astype(jnp.float32)
    delta = jnp.sum(do * o.astype(jnp.float32), axis=-1, keepdims=True) \
        - g_lse.astype(jnp.float32)
    return _bwd_with_delta(sm_scale, causal, block_q, block_k, kv_true,
                           dropout_rate, num_heads, heads,
                           (q, k, v, bias, seed, lse), g_o, delta)


_flash_bhsd_lse.defvjp(_flash_lse_fwd_rule, _bwd_lse)


def flash_attention(q, k, v, *, causal=False, sm_scale=None, bias=None,
                    dropout_rate=0.0, dropout_seed=None, return_lse=False,
                    block_q=None, block_k=None):
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

    align = 8 if common.use_interpret() else _LANES
    rule_q, rule_k, heads = tiles(q_len, kv_len, d, q.dtype, causal, h, align)
    if block_q is None and block_k is None:
        block_q, block_k = rule_q, rule_k
    else:
        # the caller's tiles (tests): clipped to the sequence, and the
        # heads a step by the same arithmetic as the rule's
        block_q = min(block_q or rule_q, round_up(q_len, align))
        block_k = min(block_k or rule_k, round_up(kv_len, align))
        whole_head = block_q >= q_len and block_k >= kv_len
        heads = _heads_that_fit(h, block_q, block_k, d,
                                q.dtype) if whole_head else 1
    regime = "single_pass" if block_k >= kv_len else "streamed"
    _kreg.metric_flash_tiles.get_cell(
        regime, str(block_q), str(block_k), str(heads)).increase_by(1)
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
            float(dropout_rate), int(h), int(heads))
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
                  block_q=None, block_k=None):
    """The stock composed-XLA lowering of the FlashAttention op contract
    (batch_matmul → softmax → batch_matmul, the reference's attention
    path; ref core/kernels/{batch_matmul_op,softmax_op}.cc) — the
    registry's fallback when the Pallas kernel is ineligible or the
    cost gate prices the fused kernel slower (tiny shapes;
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
