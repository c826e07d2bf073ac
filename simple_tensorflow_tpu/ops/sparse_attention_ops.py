"""Learned sparse attention for serving: an indexer picks, for every
query position, the ``topk`` cached positions it attends to.

(ref: the reference has neither; the mechanism is the lightning-indexer
sparse attention of recent open decoders: a small many-head, one-key-head
scorer ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])`` ranks the
context, and softmax attention runs over the ``topk`` best positions
``s <= t`` only — all of them while ``t < topk``; a tie goes to the lower
position.)

Serving-only graph ops (no gradients), all lowered to plain XLA:

  RMSNorm            ``x * rsqrt(mean(x^2) + eps) * gamma`` in float32.
  RotaryEmbedding    rotate-half RoPE from absolute positions; with
                     ``yarn`` YaRN's blended frequencies.
  IndexerTopK        DECODE: one query per sequence scores the gathered
                     indexer keys of its context and returns the selected
                     positions, best first, and how many are real.
  SelectedAttention  DECODE: grouped-query attention of one query per
                     sequence over the selected K/V rows
                     (``KVCache.gather_rows``), never the whole view.
  SparseBlockAttention  PREFILL: a page-aligned block of queries against
                     the gathered views. The same selection, computed as
                     a MASK: indexer scores are written tile by tile into
                     one ``(B, S, L)`` float32 buffer (never ``[S, L,
                     heads]``), each row's ``topk``-th largest is found by
                     bisection over the scores' bit patterns (32 counting
                     passes; a sort of ``(512, 33792)`` costs 25 ms on a
                     v5e, the bisection 1.3), and attention walks the key
                     tiles with an online softmax. Both walks stop at the
                     last tile any query of the block can see, so the
                     cost follows the context, not ``pages_per_seq``.

Indexer scores, softmax statistics and RMSNorm are float32; matmul
operands keep their (bfloat16) dtype with float32 accumulation.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..framework import graph as ops_mod
from ..framework import op_registry
from . import op_util

_NEG_INF = float("-inf")


# ---------------------------------------------------------------------------
# jax-level functions
# ---------------------------------------------------------------------------

def rms_norm(x, gamma, *, eps, out_dtype=None):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = y * gamma.astype(jnp.float32)
    return y.astype(out_dtype or x.dtype)


def yarn_inv_freq(dim, theta, *, factor, original_len, beta_fast,
                  beta_slow):
    """YaRN's blended frequencies for a ``dim``-wide rotation: ``f_i =
    theta^(-2i/dim)`` where a dimension turns more than ``beta_fast``
    times within ``original_len`` positions, ``f_i / factor`` where it
    turns fewer than ``beta_slow`` times, a linear ramp between. Static:
    the same frequencies at every length."""
    def turns_dim(n):
        return dim * math.log(original_len / (2 * math.pi * n)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), dim - 1)
    freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / factor * ramp


def rotary_embedding(x, positions, *, theta, yarn=None, amplitude=1.0):
    """``x (..., H, D)`` rotated by ``positions`` (shape ``x.shape[:-2]``):
    the rotate-half convention, angles ``pos * theta^(-2i/D)`` in float32.
    ``yarn = (factor, original_len, beta_fast, beta_slow)`` takes
    :func:`yarn_inv_freq`'s frequencies instead; ``amplitude`` scales cos
    and sin (YaRN's ``mscale / mscale_all_dim``)."""
    d = x.shape[-1]
    if yarn is None:
        inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    else:
        factor, original_len, beta_fast, beta_slow = yarn
        inv_freq = yarn_inv_freq(d, theta, factor=factor,
                                 original_len=original_len,
                                 beta_fast=beta_fast, beta_slow=beta_slow)
    ang = positions.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if amplitude != 1.0:
        cos, sin = cos * amplitude, sin * amplitude
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :d // 2], xf[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def indexer_scores(q_idx, weights, k_idx):
    """``q_idx (..., Hi, Di)``, ``weights (..., Hi)`` float32, ``k_idx
    (B, T, Di)`` -> ``(..., T)`` float32 (leading dim of ``...`` is B)."""
    s = jnp.einsum("b...hd,btd->b...ht", q_idx, k_idx,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("b...ht,b...h->b...t", jax.nn.relu(s),
                      weights.astype(jnp.float32))


def indexer_topk(q_idx, weights, k_idx, lengths, *, topk):
    """DECODE selection. ``q_idx (B, Hi, Di)``, ``weights (B, Hi)``,
    ``k_idx (B, L, Di)`` the gathered indexer keys, ``lengths (B,)`` the
    live context (this position included). Returns ``positions (B, k)``
    int32, best first (``lax.top_k``: equal scores keep the lower
    position first), and ``n_valid (B,) = min(lengths, k)``: the entries
    past it point at dead positions."""
    big_l = k_idx.shape[1]
    k = min(int(topk), big_l)
    scores = indexer_scores(q_idx, weights, k_idx)
    live = jnp.arange(big_l, dtype=jnp.int32)[None, :] < lengths[:, None]
    _, positions = jax.lax.top_k(jnp.where(live, scores, _NEG_INF), k)
    return (positions.astype(jnp.int32),
            jnp.minimum(lengths, k).astype(jnp.int32))


def selected_attention(q, k_sel, v_sel, n_valid):
    """``q (B, Hq, D)`` over ``k_sel/v_sel (B, K, Hkv, D)``, rows
    ``>= n_valid[b]`` dead; query head h reads KV head ``h // (Hq/Hkv)``."""
    b, hq, d = q.shape
    kk, hkv = k_sel.shape[1], k_sel.shape[2]
    scale = d ** -0.5
    qg = q.reshape(b, hkv, hq // hkv, d)
    s = jnp.einsum("bgrd,bkgd->bgrk", qg, k_sel,
                   preferred_element_type=jnp.float32) * scale
    live = jnp.arange(kk, dtype=jnp.int32)[None, :] < n_valid[:, None]
    s = jnp.where(live[:, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bgrk,bkgd->bgrd", p.astype(v_sel.dtype), v_sel,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, hq, d).astype(q.dtype)


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    bits = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return bits.astype(jnp.uint32) ^ jnp.uint32(0x80000000)


def kth_largest_bits(scores, k):
    """Per row of ``scores (R, L)`` float32: the ordered bit pattern of
    its k-th largest entry, built bit by bit from the top — the largest
    prefix that at least ``k`` entries reach."""
    bits = _ordered_bits(scores)

    def body(i, prefix):
        cand = prefix | (jnp.uint32(0x80000000) >> i.astype(jnp.uint32))
        reach = jnp.sum((bits >= cand[:, None]).astype(jnp.int32), axis=-1)
        return jnp.where(reach >= k, cand, prefix)

    return bits, jax.lax.fori_loop(
        0, 32, body, jnp.zeros(scores.shape[:1], jnp.uint32))


def sparse_block_attention(q, q_idx, weights, k_view, v_view, k_idx_view,
                           base, *, topk, tile):
    """PREFILL. ``q (B, S, Hq, D)`` at positions ``base[b] + [0, S)``;
    ``q_idx (B, S, Hi, Di)``, ``weights (B, S, Hi)``; ``k_view/v_view (B,
    L, Hkv, D)`` and ``k_idx_view (B, L, Di)`` the gathered logical views
    (the block's own rows already appended). Query t attends the ``topk``
    best-scored positions ``s <= t`` (module docstring). ``tile`` keys a
    step; ``L % tile == 0``."""
    b, s_len, hq, d = q.shape
    big_l, hkv = k_view.shape[1], k_view.shape[2]
    if big_l % tile:
        raise ValueError(f"tile {tile} does not divide the view {big_l}")
    rep = hq // hkv
    scale = d ** -0.5
    topk = int(topk)
    base = base.astype(jnp.int32)
    t_pos = base[:, None] + jnp.arange(s_len, dtype=jnp.int32)[None, :]
    # tiles any query of the block can see
    needed = (jnp.max(base) + s_len + tile - 1) // tile
    tile_pos = jnp.arange(tile, dtype=jnp.int32)

    def score_tile(j, buf):
        k_t = jax.lax.dynamic_slice_in_dim(k_idx_view, j * tile, tile, 1)
        sc = indexer_scores(q_idx, weights, k_t)            # (B, S, T)
        seen = (j * tile + tile_pos)[None, None, :] <= t_pos[:, :, None]
        return jax.lax.dynamic_update_slice_in_dim(
            buf, jnp.where(seen, sc, _NEG_INF), j * tile, 2)

    scores = jax.lax.fori_loop(
        0, needed, score_tile,
        jnp.full((b, s_len, big_l), _NEG_INF, jnp.float32))

    # the topk-th largest of every row, and how many of the entries
    # EQUAL to it are taken (the lowest positions first). The 32 counting
    # passes read the scores the block can see, rounded up to one of a
    # few widths (every entry past them is -inf and changes no count): a
    # pass over all 33,792 positions of (2048, L) is 0.37 ms on a v5e
    k = min(topk, big_l)

    def threshold_over(width):
        def threshold(scores):
            flat = scores[:, :, :width].reshape(b * s_len, width)
            bits, kth = kth_largest_bits(flat, k)
            above = jnp.sum((bits > kth[:, None]).astype(jnp.int32), axis=-1)
            return kth.reshape(b, s_len), (k - above).reshape(b, s_len)
        return threshold

    def everything(scores):
        # no query of the block has more than topk positions behind it
        return (jnp.zeros((b, s_len), jnp.uint32),
                jnp.full((b, s_len), big_l, jnp.int32))

    n_tiles = big_l // tile
    widths = sorted({tile * -(-n_tiles * step // 6) for step in range(1, 7)})
    widths = [w for w in widths if w >= k] or [big_l]
    branch = jnp.where(jnp.max(base) + s_len <= topk, 0,
                       1 + jnp.searchsorted(jnp.asarray(widths), needed * tile))
    kth, take_equal = jax.lax.switch(
        jnp.minimum(branch, len(widths)),
        [everything] + [threshold_over(w) for w in widths], scores)

    qg = q.reshape(b, s_len, hkv, rep, d)

    def attend_tile(j, carry):
        m, l, acc, equal_before = carry
        k_t = jax.lax.dynamic_slice_in_dim(k_view, j * tile, tile, 1)
        v_t = jax.lax.dynamic_slice_in_dim(v_view, j * tile, tile, 1)
        sc = jax.lax.dynamic_slice_in_dim(scores, j * tile, tile, 2)
        bits = _ordered_bits(sc)
        equal = bits == kth[:, :, None]
        rank = equal_before[:, :, None] + jnp.cumsum(
            equal.astype(jnp.int32), axis=-1)
        chosen = ((bits > kth[:, :, None])
                  | (equal & (rank <= take_equal[:, :, None])))
        chosen = chosen & (sc > _NEG_INF)                    # s <= t
        logits = jnp.einsum("bsgrd,btgd->bgrst", qg, k_t,
                            preferred_element_type=jnp.float32) * scale
        logits = jnp.where(chosen[:, None, None], logits, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        # a row that has seen nothing yet keeps m = -inf: shift by 0
        shift = jnp.where(m_new > _NEG_INF, m_new, 0.0)
        p = jnp.exp(logits - shift[..., None])
        alpha = jnp.exp(jnp.where(m > _NEG_INF, m - shift, _NEG_INF))
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bgrst,btgd->bgrsd", p.astype(v_t.dtype), v_t,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new,
                equal_before + jnp.sum(equal.astype(jnp.int32), axis=-1))

    stat = (b, hkv, rep, s_len)
    _, l, acc, _ = jax.lax.fori_loop(
        0, needed, attend_tile,
        (jnp.full(stat, _NEG_INF, jnp.float32),
         jnp.zeros(stat, jnp.float32),
         jnp.zeros(stat + (d,), jnp.float32),
         jnp.zeros((b, s_len), jnp.int32)))
    out = acc / l[..., None]                    # every query sees itself
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s_len, hq, d).astype(
        q.dtype)


# ---------------------------------------------------------------------------
# graph ops
# ---------------------------------------------------------------------------

op_registry.register_pure(
    "RMSNorm",
    lambda x, gamma, eps=1e-6, out_dtype=None: rms_norm(
        x, gamma, eps=eps,
        out_dtype=jnp.dtype(out_dtype) if out_dtype else None))
op_registry.register_pure(
    "RotaryEmbedding",
    lambda x, positions, theta=10000.0, yarn=None, amplitude=1.0:
    rotary_embedding(x, positions, theta=theta, yarn=yarn,
                     amplitude=amplitude))
op_registry.register_pure(
    "IndexerTopK",
    lambda q_idx, weights, k_idx, lengths, topk=0: indexer_topk(
        q_idx, weights, k_idx, lengths, topk=topk),
    n_outputs=2)
op_registry.register_pure(
    "SelectedAttention",
    selected_attention)
op_registry.register_pure(
    "SparseBlockAttention",
    lambda q, q_idx, weights, k_view, v_view, k_idx_view, base, topk=0,
    tile=0: sparse_block_attention(
        q, q_idx, weights, k_view, v_view, k_idx_view, base, topk=topk,
        tile=tile))


def _make(op_type, inputs, attrs, name, n_out=1):
    """``make_op`` over inputs that may be Variables."""
    return op_util.make_op(op_type,
                           [ops_mod.convert_to_tensor(t) for t in inputs],
                           attrs=attrs, name=name, n_out=n_out)


def rms_norm_op(x, gamma, eps=1e-6, out_dtype=None, name=None):
    """RMSNorm over the last axis in float32; ``out_dtype`` (a numpy
    dtype name) defaults to ``x``'s."""
    return _make("RMSNorm", [x, gamma],
                 {"eps": float(eps), "out_dtype": out_dtype},
                 name or "rms_norm")


def rotary_embedding_op(x, positions, theta, yarn=None, amplitude=1.0,
                        name=None):
    """``yarn`` = ``(factor, original_len, beta_fast, beta_slow)`` or
    None for plain RoPE (:func:`rotary_embedding`)."""
    attrs = {"theta": float(theta)}
    # what plain RoPE does not use stays off its op
    if yarn is not None:
        attrs["yarn"] = tuple(float(v) for v in yarn)
    if amplitude != 1.0:
        attrs["amplitude"] = float(amplitude)
    return _make("RotaryEmbedding", [x, positions], attrs,
                 name or "rotary_embedding")


def indexer_topk_op(q_idx, weights, k_idx, lengths, topk, name=None):
    return _make("IndexerTopK", [q_idx, weights, k_idx, lengths],
                 {"topk": int(topk)}, name or "indexer_topk", n_out=2)


def selected_attention_op(q, k_sel, v_sel, n_valid, name=None):
    return _make("SelectedAttention", [q, k_sel, v_sel, n_valid], {},
                 name or "selected_attention")


def sparse_block_attention_op(q, q_idx, weights, k_view, v_view,
                              k_idx_view, base, topk, tile, name=None):
    return _make(
        "SparseBlockAttention",
        [q, q_idx, weights, k_view, v_view, k_idx_view, base],
        {"topk": int(topk), "tile": int(tile)},
        name or "sparse_block_attention")
