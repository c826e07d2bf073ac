"""Fused sparse softmax cross-entropy (Pallas TPU), fwd + custom VJP.

Replaces the reference's two-kernel softmax→xent chain
(ref: tensorflow/core/kernels/xent_op.cc, softmax_op.cc). For LM/BERT-size
vocabularies the [batch, vocab] logits tensor dominates HBM traffic; this
kernel streams each row once, vocab-block by vocab-block, maintaining the
online-softmax running (max, sumexp) plus the label logit, so VMEM holds
only a (block_rows, block_vocab) tile regardless of vocabulary size (a
full-row tile at 128×30522×f32 double-buffered is 30 MB — twice the 16 MB
scoped-VMEM budget). The backward emits (softmax - onehot) * g blockwise
from the saved logsumexp without re-reading intermediates.

logits: (rows, vocab) any float dtype; labels: (rows,) int32 (carried as
(rows, 1) tiles — Mosaic-legal shapes). Returns per-row loss, f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import common
from .common import NEG_INF, cdiv, pad_dim, round_up

DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_VOCAB = 2048


def _fwd_kernel(vocab, n_vblocks, smoothing, logits_ref, labels_ref,
                loss_ref, lse_ref, m_ref, s_ref, ll_ref, sx_ref):
    j = pl.program_id(1)
    x = logits_ref[:].astype(jnp.float32)           # (br, bv)
    labels = labels_ref[:]                          # (br, 1)
    bv = x.shape[1]
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = cols < vocab
    x = jnp.where(valid, x, NEG_INF)                # mask the ragged edge

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        s_ref[:] = jnp.zeros(s_ref.shape, jnp.float32)
        ll_ref[:] = jnp.zeros(ll_ref.shape, jnp.float32)
        if smoothing > 0.0:
            sx_ref[:] = jnp.zeros(sx_ref.shape, jnp.float32)

    m_prev = m_ref[:]
    m_blk = jnp.max(x, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    s_ref[:] = s_ref[:] * jnp.exp(m_prev - m_new) + jnp.sum(
        jnp.exp(x - m_new), axis=-1, keepdims=True)
    m_ref[:] = m_new
    ll_ref[:] = ll_ref[:] + jnp.sum(
        jnp.where(cols == labels, x, 0.0), axis=-1, keepdims=True)
    if smoothing > 0.0:
        sx_ref[:] = sx_ref[:] + jnp.sum(jnp.where(valid, x, 0.0),
                                        axis=-1, keepdims=True)

    @pl.when(j == n_vblocks - 1)
    def _finish():
        lse = m_ref[:] + jnp.log(s_ref[:])
        if smoothing > 0.0:
            # soft targets q = low + (conf - low)*onehot with
            # conf = 1 - smoothing, low = smoothing/(V-1); since sum(q)=1:
            # loss = lse - conf*x_label - low*(sum_x - x_label)
            conf = 1.0 - smoothing
            low = smoothing / (vocab - 1)
            loss_ref[:] = (lse - conf * ll_ref[:]
                           - low * (sx_ref[:] - ll_ref[:]))
        else:
            loss_ref[:] = lse - ll_ref[:]
        lse_ref[:] = lse


def _bwd_kernel(vocab, smoothing, logits_ref, labels_ref, lse_ref, g_ref,
                dx_ref):
    j = pl.program_id(1)
    x = logits_ref[:].astype(jnp.float32)
    labels = labels_ref[:]                          # (br, 1)
    lse = lse_ref[:]                                # (br, 1)
    g = g_ref[:]                                    # (br, 1)
    p = jnp.exp(x - lse)
    bv = x.shape[1]
    cols = j * bv + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    onehot = (cols == labels).astype(jnp.float32)
    if smoothing > 0.0:
        conf = 1.0 - smoothing
        low = smoothing / (vocab - 1)
        q = low + (conf - low) * onehot             # dL/dx = p - q
    else:
        q = onehot
    dx = jnp.where(cols < vocab, (p - q) * g, 0.0)
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _block_sizes(vocab, block_vocab):
    # No padding: both kernels mask loads past `vocab` (cols < vocab), so a
    # ragged final block is fine and the [rows, vocab] tensor — the whole
    # reason this kernel exists — is never copied just to round its shape.
    bv = min(block_vocab, round_up(vocab, 128))
    return bv, cdiv(vocab, bv)


def _fwd(logits, labels, block_rows, block_vocab, smoothing):
    rows, vocab = logits.shape
    bv, nv = _block_sizes(vocab, block_vocab)
    loss, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, vocab, nv, smoothing),
        grid=(cdiv(rows, block_rows), nv),
        in_specs=[
            pl.BlockSpec((block_rows, bv), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
            pltpu.VMEM((block_rows, 1), jnp.float32),
        ],
        interpret=common.use_interpret(),
        name="stf_softmax_xent_fwd",
    )(logits, labels)
    return loss, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _xent_2d(logits, labels, block_rows, block_vocab, smoothing):
    loss, _ = _fwd(logits, labels, block_rows, block_vocab, smoothing)
    return loss


def _xent_fwd_rule(logits, labels, block_rows, block_vocab, smoothing):
    loss, lse = _fwd(logits, labels, block_rows, block_vocab, smoothing)
    return loss, (logits, labels, lse)


def _xent_bwd_rule(block_rows, block_vocab, smoothing, res, g):
    logits, labels, lse = res
    rows, vocab = logits.shape
    bv, nv = _block_sizes(vocab, block_vocab)
    dx = pl.pallas_call(
        functools.partial(_bwd_kernel, vocab, smoothing),
        grid=(cdiv(rows, block_rows), nv),
        in_specs=[
            pl.BlockSpec((block_rows, bv), lambda i, j: (i, j)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((rows, vocab), logits.dtype),
        interpret=common.use_interpret(),
        name="stf_softmax_xent_bwd",
    )(logits, labels, lse, g)
    return dx, None


_xent_2d.defvjp(_xent_fwd_rule, _xent_bwd_rule)


def softmax_cross_entropy(logits, labels, *, label_smoothing=0.0,
                          block_rows=DEFAULT_BLOCK_ROWS,
                          block_vocab=DEFAULT_BLOCK_VOCAB):
    """Per-example sparse softmax xent. logits: (..., vocab),
    labels: (...,) int. Returns f32 loss of shape (...).

    label_smoothing > 0 trains against soft targets
    q = smoothing/(V-1) + (1 - smoothing - smoothing/(V-1))*onehot, fused
    into the same streamed pass (the composed form materializes log_softmax
    AND a dense one-hot at [rows, vocab] — two extra vocab-sized tensors)."""
    orig = logits.shape
    vocab = orig[-1]
    rows = 1
    for s in orig[:-1]:
        rows *= s
    l2 = logits.reshape(rows, vocab)
    lab = labels.reshape(rows, 1).astype(jnp.int32)
    block_rows = min(block_rows, round_up(rows, 8))
    rp = round_up(rows, block_rows)
    l2 = pad_dim(l2, 0, rp)
    lab = pad_dim(lab, 0, rp)
    loss = _xent_2d(l2, lab, int(block_rows), int(block_vocab),
                    float(label_smoothing))
    return loss[:rows, 0].reshape(orig[:-1])


def softmax_cross_entropy_reference(logits, labels, *, label_smoothing=0.0):
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(
        logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
    if label_smoothing == 0.0:
        return nll
    vocab = logits.shape[-1]
    conf = 1.0 - label_smoothing
    low = label_smoothing / (vocab - 1)
    return conf * nll - low * (jnp.sum(logp, axis=-1) + nll)
