"""Analytic operation and byte counts of the short-convolution routed-FFN
decoder (LFM2's block): what the mathematics needs, from shapes
(``work.py``'s rules: a multiply-add is 2 FLOPs, nothing recomputed is
counted). ``spec`` is the configuration's ``reference.spec``.

Per position and per layer, 2 FLOPs per matmul weight the token meets:

- ``conv``: the in-projection (``d x 3 d``), the out-projection (``d x
  d``) and the convolution's taps (``taps x d``).
- ``full_attention``: q, k, v and output projections; over the context,
  scores and values (``2 x heads x head_dim`` a position each).
- the dense feed-forward, a SwiGLU of ``dense_width``; the routed one, the
  router and ``experts_per_token`` SwiGLUs of ``expert_width``.

The head is the tied embedding over the whole vocabulary.
"""

from __future__ import annotations

import numpy as np

CONV = "conv"


def count(spec, kind):
    """Layers whose mixer is ``kind`` (``conv`` or ``full_attention``)."""
    return list(spec["layer_types"]).count(kind)


def conv_params(spec):
    d = spec["hidden"]
    return 3 * d * d + d * d + spec["conv_taps"] * d


def attention_params(spec):
    d, hd = spec["hidden"], spec["head_dim"]
    return d * hd * (2 * spec["heads"] + 2 * spec["kv_heads"])


def ffn_params(spec, dense):
    d = spec["hidden"]
    if dense:
        return 3 * d * spec["dense_width"]
    return (d * spec["experts"]
            + spec["experts_per_token"] * 3 * d * spec["expert_width"])


def _matmul_flops(spec):
    """Per position, all layers, without the context."""
    total = 0
    for i, kind in enumerate(spec["layer_types"]):
        mixer = conv_params(spec) if kind == CONV else attention_params(spec)
        total += 2 * (mixer + ffn_params(spec, i < spec["dense_layers"]))
    return total


def _per_context_row(spec):
    """An attention layer's FLOPs a context position: score and value."""
    return 4 * spec["heads"] * spec["head_dim"]


def decode_flops(spec, context):
    """One decode position over ``context`` positions (itself included),
    with the tied head over the vocabulary."""
    return float(_matmul_flops(spec)
                 + count(spec, "full_attention") * _per_context_row(spec)
                 * context + 2 * spec["hidden"] * spec["vocab"])


def prompt_flops(spec, prompt_len):
    """A prompt of ``prompt_len`` tokens processed causally (no logits):
    position j has context j + 1."""
    n = int(prompt_len)
    return float(_matmul_flops(spec) * n
                 + count(spec, "full_attention") * _per_context_row(spec)
                 * np.arange(1, n + 1).sum())


def short_conv_update_flops(spec):
    """One conv layer's decode step for one row: ``u = B x~`` (1 a
    channel), the taps' multiply-adds (``2 taps - 1``) and ``C v`` (1)."""
    return (2 * spec["conv_taps"] + 1) * spec["hidden"]


def short_conv_update_bytes(spec, itemsize=2):
    """One conv layer's update kernel for one row of a decode call: the
    window in and out, the ``[B | C | x~]`` row in and ``y`` out, in the
    compute dtype. A call's taps (``taps x d``, 12,288 B at 2048 wide) are
    left out: 0.15 % of a 256-row call's bytes."""
    d = spec["hidden"]
    return itemsize * d * (2 * (spec["conv_taps"] - 1) + 3 + 1)
