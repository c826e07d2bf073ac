"""Analytic operation counts of the sparse-attention routed-FFN decoder:
what the algorithm needs, from shapes (``work.py``'s rules: a multiply-add
is 2 FLOPs, nothing recomputed is counted). ``spec`` is the
configuration's ``reference.spec``.

Per position at context ``c`` (itself included) and per layer: 2 FLOPs per
matmul weight the token meets — attention's four projections, the
indexer's three, the router, and ``experts_per_token`` experts of three
matrices — plus the indexer's scores over the whole context (2 x
indexer_heads x indexer_dim x c) and attention over the SELECTED positions
only (4 x heads x head_dim x min(c, topk)). What a dense-masked prefill
does beyond min(c, topk) is not credited, nor are the experts a token is
not routed to.
"""

from __future__ import annotations


def layer_matmul_params(spec):
    """Matmul weights one token meets in one layer."""
    d = spec["hidden"]
    q_width = spec["heads"] * spec["head_dim"]
    kv_width = spec["kv_heads"] * spec["head_dim"]
    attention = 2 * d * q_width + 2 * d * kv_width
    indexer = d * (spec["indexer_heads"] * spec["indexer_dim"]
                   + spec["indexer_dim"] + spec["indexer_heads"])
    router = d * spec["experts"]
    experts = spec["experts_per_token"] * 3 * d * spec["expert_width"]
    return attention + indexer + router + experts


def _context_flops(spec, context, selected):
    """The two context-dependent terms of one layer, given the summed
    context and the summed selected positions."""
    return (2 * spec["indexer_heads"] * spec["indexer_dim"] * context
            + 4 * spec["heads"] * spec["head_dim"] * selected)


def decode_flops(spec, context):
    """One decode position over ``context`` cached positions, with the
    untied head."""
    per_layer = (2 * layer_matmul_params(spec)
                 + _context_flops(spec, context,
                                  min(context, spec["topk"])))
    return spec["layers"] * per_layer + 2 * spec["hidden"] * spec["vocab"]


def prompt_flops(spec, prompt_len):
    """A prompt of ``prompt_len`` tokens processed causally (no logits:
    the last position's are counted with the decode token it yields):
    position j has context j + 1."""
    n, k = prompt_len, spec["topk"]
    context = n * (n + 1) / 2
    selected = context if n <= k else k * (k + 1) / 2 + (n - k) * k
    return spec["layers"] * (2 * layer_matmul_params(spec) * n
                             + _context_flops(spec, context, selected))


def cache_bytes_per_token(spec, dtype_bytes=2):
    """K, V and the indexer key of every layer for one cached token."""
    return spec["layers"] * dtype_bytes * (
        2 * spec["kv_heads"] * spec["head_dim"] + spec["indexer_dim"])
