"""Analytic operation and byte counts of the hybrid linear-attention
routed-FFN decoder (GLM-5.3-Flash's block) AS ONE CHIP'S SHARE: what the
mathematics this chip is given needs, from shapes (``work.py``'s rules: a
multiply-add is 2 FLOPs, nothing recomputed is counted). ``spec`` is the
configuration's ``reference.spec``.

Per position and per layer, 2 FLOPs per matmul weight the token meets, by
the layer's mixer and feed-forward:

- every sublayer's hyper-connection: the ``n d x (2 n + n^2)`` projection
  and the mixing of the n streams (``(n^2 + 2 n) d``).
- ``K``: the q/k/v, beta, the two rank-``gate_rank`` gates and the output
  projections, the convolution's taps, and the delta rule at 7 FLOPs a
  state element (decay multiply, ``S^T k``, ``k u^T`` and ``S^T q``
  multiply-adds: ``heads x d_k x d_v`` of them). The chunked form the
  prefill program runs does more (the pair products inside a block): not
  credited.
- ``D``: the query and latent projections, the ABSORBED attention's per
  head products with ``W_kvb`` (what both programs run), the output
  projection, the indexer's projections; over the context, the indexer's
  scores against every complete pooled block before the tail (``2 Hi Di``
  a block) and attention over the positions read (the tail's and the
  chosen blocks', ``2 x heads x 2 kv_rank`` a position: score and value
  over the whole 512-wide row).
- ``d``: a SwiGLU of ``dense_width``; ``s``: the router, the shared expert
  and the HELD pairs only, ``experts_per_token x held / experts`` SwiGLUs.

The head is over the sliced vocabulary.
"""

from __future__ import annotations

import numpy as np


def count(spec, kind):
    """Layers whose mixer or feed-forward is ``kind`` (K, D, d or s)."""
    return (spec["mixers"] + spec["mlps"]).count(kind)


def _state_elements(spec):
    return spec["heads"] * spec["head_dim"] * spec["head_dim"]


def hyper_params(spec):
    """One sublayer's hyper-connection, per position: the projection and
    the mixing of the streams."""
    n, d = spec["hc_mult"], spec["hidden"]
    return n * d * (2 * n + n * n) + (n * n + 2 * n) * d


def kda_params(spec):
    d, h, dk, r = (spec["hidden"], spec["heads"], spec["head_dim"],
                   spec["gate_rank"])
    w = h * dk
    return (d * 3 * w + spec["conv_taps"] * 3 * w + d * h
            + 2 * (d * r + r * w) + w * d)


def dsa_params(spec):
    """The absorbed form's weights a position meets, the context aside."""
    d, h = spec["hidden"], spec["mla_heads"]
    nope, vd, qr, kr = (spec["nope_dim"], spec["v_dim"], spec["q_rank"],
                        spec["kv_rank"])
    hi, di = spec["idx_heads"], spec["idx_dim"]
    return (d * qr + qr * h * nope + d * kr + h * nope * kr + h * kr * vd
            + h * vd * d + qr * hi * di + d * di + d * hi)


def ffn_params(spec, kind):
    d = spec["hidden"]
    if kind == "d":
        return 3 * d * spec["dense_width"]
    held_pairs = spec["experts_per_token"] * spec["held"][1] / spec["experts"]
    return (d * spec["experts"] + 3 * d * spec["shared_width"]
            + held_pairs * 3 * d * spec["expert_width"])


def kda_update_flops(spec):
    """One KDA layer's delta rule for one token: what
    ``kda_update_roofline`` counts a row of a decode call at."""
    return 7 * _state_elements(spec)


def kda_update_bytes(spec):
    """One KDA layer's state-update kernel for one row of a decode call:
    the row's float32 state in and out, the alpha, k, beta k and q
    columns and the v row in, the o row out (all float32)."""
    h, dk = spec["heads"], spec["head_dim"]
    return 2 * 4 * _state_elements(spec) + 4 * (4 * h * dk + 2 * h * dk)


def read_positions(spec, context):
    """Positions a query at ``context - 1`` reads: its tail's and the
    chosen blocks' (all of them while the context is within the
    budget)."""
    context = np.asarray(context, np.int64)
    pool = spec["idx_pool"]
    return np.minimum(context, spec["idx_topk"] - pool + 1
                      + (context - 1) % pool)


def _per_context(spec, context):
    """FLOPs of one DSA layer over the context: indexer scores of the
    complete blocks before the tail, attention over what is read."""
    context = np.asarray(context, np.int64)
    blocks = (context - 1) // spec["idx_pool"]
    return (2 * spec["idx_heads"] * (spec["idx_dim"] + 1) * blocks
            + 4 * spec["mla_heads"] * spec["kv_rank"]
            * read_positions(spec, context))


def _matmul_flops(spec):
    """Per position, all layers, without the context."""
    layers = len(spec["mixers"])
    return (2 * layers * 2 * hyper_params(spec)
            + count(spec, "K") * (2 * kda_params(spec)
                                  + kda_update_flops(spec))
            + count(spec, "D") * 2 * dsa_params(spec)
            + sum(2 * ffn_params(spec, k) for k in spec["mlps"]))


def decode_flops(spec, context):
    """One decode position over ``context`` positions (itself included),
    with the head over the vocabulary slice."""
    return float(_matmul_flops(spec)
                 + count(spec, "D") * _per_context(spec, context)
                 + 2 * spec["hidden"] * spec["vocab"])


def prompt_flops(spec, prompt_len):
    """A prompt of ``prompt_len`` tokens processed causally (no logits):
    position j has context j + 1."""
    n = int(prompt_len)
    return float(_matmul_flops(spec) * n + count(spec, "D") * _per_context(
        spec, np.arange(1, n + 1)).sum())


def latent_attention_work(spec, context):
    """``[flops, bytes]`` of one decode row of the paged latent kernel
    over its SELECTED rows (``sparse_attention_ops.selected_latent_
    attention``): score and value over the 512-wide row a position read,
    the rows' fake pages whole as the kernel reads them, the absorbed
    queries in and the latent-space result out."""
    h, w = spec["mla_heads"], spec["kv_rank"]
    read = int(read_positions(spec, context))
    budget = spec["idx_topk"]
    page = max(p for p in range(1, min(budget, 512) + 1) if budget % p == 0)
    pages = -(-read // page)
    return [2.0 * h * 2 * w * read, 2.0 * (pages * page * w + 2 * h * w)]
