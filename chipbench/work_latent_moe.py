"""Analytic operation and byte counts of the latent-attention routed-FFN
decoder AS ONE CHIP'S SHARE: what the mathematics this chip is given needs,
from shapes (``work.py``'s rules: a multiply-add is 2 FLOPs, nothing
recomputed is counted). ``spec`` is the configuration's ``reference.spec``.

Per position and per layer, 2 FLOPs per matmul weight the token meets:
attention's four projections outside the per-head up-projection, and the
dense feed-forward, or the router, the shared expert and the HELD pairs
only: ``experts_per_token * held / experts`` experts of three matrices (a
token's other pairs land on other chips and are nobody's work here). The
head is over the sliced vocabulary.

Attention is counted in the form the mathematics needs in each phase, and
no more. DECODE (one query over ``c`` cached rows, absorbed form): the
query's absorption into the latent space and the result's way out (2 x
heads x kv_rank x (qk_nope_dim + v_dim)) and 2 x heads x (kv_rank +
qk_rope_dim + kv_rank) x c for scores and the weighted sum of latents.
PREFILL (plain form): each position's latent up-projected ONCE (2 x kv_rank
x heads x (qk_nope_dim + v_dim)) and per-head attention over its context, 2
x heads x (qk_nope_dim + qk_rope_dim + v_dim) x c. What the program's
absorbed prefill does beyond that is not credited.
"""

from __future__ import annotations


def _attention_params(spec):
    """Matmul weights of attention a token meets whatever the form: q_a,
    q_b, kv_a and the output projection."""
    d, h = spec["hidden"], spec["heads"]
    q_width = h * (spec["qk_nope_dim"] + spec["qk_rope_dim"])
    return (d * spec["q_rank"] + spec["q_rank"] * q_width
            + d * (spec["kv_rank"] + spec["qk_rope_dim"])
            + h * spec["v_dim"] * d)


def _up_projection_flops(spec):
    """2 FLOPs per weight of W_kvb: a position's keys and values made from
    its latent (prefill), or a query absorbed and its result brought out
    (decode) — the same count."""
    return 2 * spec["kv_rank"] * spec["heads"] * (spec["qk_nope_dim"]
                                                  + spec["v_dim"])


def ffn_params(spec, dense):
    """Feed-forward matmul weights a token meets in one layer HERE."""
    d = spec["hidden"]
    if dense:
        return 3 * d * spec["dense_width"]
    held_pairs = spec["experts_per_token"] * spec["held"][1] / spec["experts"]
    return (d * spec["experts"] + 3 * d * spec["shared_width"]
            + held_pairs * 3 * d * spec["expert_width"])


def _matmul_flops(spec):
    """Per position, all layers, without attention over the context."""
    dense, layers = spec["dense_layers"], spec["layers"]
    return (layers * (2 * _attention_params(spec)
                      + _up_projection_flops(spec))
            + 2 * dense * ffn_params(spec, True)
            + 2 * (layers - dense) * ffn_params(spec, False))


def decode_attention_flops(spec, context):
    """One layer's absorbed attention of one query over ``context`` rows:
    what ``latent_attn_roofline`` counts a call's row at."""
    return 2 * spec["heads"] * (2 * spec["kv_rank"]
                                + spec["qk_rope_dim"]) * context


def decode_flops(spec, context):
    """One decode position over ``context`` cached rows, with the head
    over the vocabulary slice."""
    return (_matmul_flops(spec)
            + spec["layers"] * decode_attention_flops(spec, context)
            + 2 * spec["hidden"] * spec["vocab"])


def prompt_flops(spec, prompt_len):
    """A prompt of ``prompt_len`` tokens processed causally in the plain
    form (no logits): position j has context j + 1."""
    n = prompt_len
    per_key = 2 * spec["heads"] * (spec["qk_nope_dim"] + spec["qk_rope_dim"]
                                   + spec["v_dim"])
    return (_matmul_flops(spec) * n
            + spec["layers"] * per_key * n * (n + 1) / 2)


def page_bytes(spec, page_len, dtype_bytes=2):
    """One stored page of one layer's latent pool."""
    return page_len * spec["latent_row"] * dtype_bytes


def decode_attention_bytes(spec, context, page_len, dtype_bytes=2):
    """One layer's latent kernel for one row of a decode call: the live
    pages whole (the kernel reads whole pages), the absorbed queries in
    and the latent-space result out."""
    live_pages = -(-context // page_len)
    return (live_pages * page_bytes(spec, page_len, dtype_bytes)
            + spec["heads"] * (spec["latent_row"] + spec["kv_rank"])
            * dtype_bytes)


def cache_bytes_per_token(spec, dtype_bytes=2):
    """The stored latent row of every layer for one cached token."""
    return spec["layers"] * spec["latent_row"] * dtype_bytes
