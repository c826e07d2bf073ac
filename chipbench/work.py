"""Analytic operation and byte counts: what the algorithm needs, from shapes.

Kept with the benchmark so that no later PR can change the yardstick. A
multiply-add is 2 FLOPs. No recomputation is counted anywhere: the model
step's numbers are the forward (and, for training, backward = 2 x forward)
passes the mathematics requires, whatever the program re-does.
"""

from __future__ import annotations


def _block_matmul_params(hidden, ffn):
    """Matmul weights of one post-LN block: Q, K, V, O and the two FFN
    matrices (biases and LayerNorm are not matmuls)."""
    return 4 * hidden * hidden + 2 * hidden * ffn


def transformer_forward_flops_per_token(hidden, ffn, layers, context):
    """Forward FLOPs of the block stack for ONE token that attends over
    ``context`` keys: 2 per matmul weight, plus scores and the weighted
    sum (2 * 2 * context * hidden per layer)."""
    return layers * (2 * _block_matmul_params(hidden, ffn)
                     + 4 * context * hidden)


def bert_train_flops_per_token(spec, seq_len, masked_per_row):
    """Forward + backward FLOPs per input token of BERT pretraining.

    The masked-LM head (transform + tied vocabulary projection) runs on
    ``masked_per_row`` of every ``seq_len`` positions only; the pooler and
    the next-sentence head run once a row. Backward is twice forward."""
    h, v = spec["hidden"], spec["vocab"]
    stack = transformer_forward_flops_per_token(
        h, spec["ffn"], spec["layers"], seq_len)
    mlm = (masked_per_row / seq_len) * (2 * h * h + 2 * h * v)
    row_heads = (2 * h * h + 2 * h * 2) / seq_len
    return 3.0 * (stack + mlm + row_heads)


def causal_lm_prompt_flops(spec, prompt_len):
    """Forward FLOPs to process a prompt of ``prompt_len`` tokens causally
    (no logits: only the last position's are needed, counted with the
    decode token it yields)."""
    h = spec["hidden"]
    per_tok = 2 * _block_matmul_params(h, spec["ffn"]) * spec["layers"]
    # token j attends over j+1 keys
    attn = 4 * h * spec["layers"] * prompt_len * (prompt_len + 1) / 2
    return per_tok * prompt_len + attn


def causal_lm_decode_flops(spec, context):
    """Forward FLOPs of one decode position over ``context`` cached keys,
    with the tied output head."""
    h = spec["hidden"]
    return (transformer_forward_flops_per_token(
        h, spec["ffn"], spec["layers"], context) + 2 * h * spec["vocab"])


def kv_cache_bytes_per_token(spec, dtype_bytes=2):
    """K and V of every layer for one cached token."""
    return 2 * spec["layers"] * spec["hidden"] * dtype_bytes


def flash_attention_work(batch, heads, seq_q, seq_k, head_dim, *,
                         backward, dtype_bytes=2):
    """(flops, bytes) of one flash-attention call over full (non-causal)
    scores. Forward: QK^T and PV. Backward: the score recompute, dV, dP,
    dQ, dK — five matmuls of the same size. Bytes: each operand and result
    moved once (Q, K, V, O forward; Q, K, V, O, dO in and dQ, dK, dV out
    backward); scores never touch HBM."""
    mm = 2 * batch * heads * seq_q * seq_k * head_dim
    q_bytes = batch * heads * seq_q * head_dim * dtype_bytes
    k_bytes = batch * heads * seq_k * head_dim * dtype_bytes
    if backward:
        return 5 * mm, 4 * q_bytes + 4 * k_bytes
    return 2 * mm, 2 * q_bytes + 2 * k_bytes


def roofline_seconds(flops, bytes_, peak):
    """The least time the chip could take, and which bound sets it."""
    t_flops = flops / peak["flops_per_s_bf16"]
    t_bytes = bytes_ / peak["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
