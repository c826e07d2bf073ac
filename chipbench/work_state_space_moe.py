"""Analytic operation and byte counts of the hybrid state-space routed-FFN
decoder AS ONE CHIP'S SHARE: what the mathematics this chip is given needs,
from shapes (``work.py``'s rules: a multiply-add is 2 FLOPs, nothing
recomputed is counted). ``spec`` is the configuration's ``reference.spec``.

Per position and per layer, by the layer's KIND, 2 FLOPs per matmul weight
the token meets:

- ``M``: the in and out projections, the convolution's taps, and the
  recurrence at 6 FLOPs a state element (``exp(dt A) h`` a multiply, ``+ dt
  x (x) B`` a multiply-add, ``h C`` a multiply-add: ``2 x 3`` a element, 64 x
  64 x 128 of them). The chunked form the prefill program runs does more
  (the quadratic form inside a block): not credited.
- ``E``: the router, the shared expert and the HELD pairs only:
  ``experts_per_token x held / experts`` experts of two matrices (a token's
  other pairs land on the other chip and are nobody's work here).
- ``*``: the four projections, and attention over the context, 2 x heads x
  2 x head_dim FLOPs a cached row.

The head is over the sliced vocabulary.
"""

from __future__ import annotations


def count(spec, kind):
    """Layers of ``kind`` in the pattern."""
    return spec["pattern"].count(kind)


def _state_elements(spec):
    return spec["mamba_heads"] * spec["mamba_head_dim"] * spec["state"]


def mamba_params(spec):
    """Matmul weights of one state-space layer a token meets: in and out
    projections and the convolution's taps."""
    d = spec["hidden"]
    di = spec["mamba_heads"] * spec["mamba_head_dim"]
    conv = di + 2 * spec["groups"] * spec["state"]
    return (d * (di + conv + spec["mamba_heads"]) + di * d
            + spec["conv_taps"] * conv)


def state_update_flops(spec):
    """One layer's recurrence for one token: what ``ssm_update_roofline``
    counts a row of a decode call at."""
    return 6 * _state_elements(spec)


def state_update_bytes(spec):
    """One layer's state-update kernel for one row of a decode call: the
    row's float32 state in and out, the rows of decay and ``dt x`` and the
    ``B`` and ``C`` columns in, ``y`` out (all float32)."""
    lanes = spec["mamba_heads"] * spec["mamba_head_dim"]
    return (2 * 4 * _state_elements(spec)
            + 4 * (3 * lanes + 2 * spec["groups"] * spec["state"]))


def ffn_params(spec):
    """Feed-forward matmul weights a token meets in one routed layer
    HERE."""
    d = spec["hidden"]
    held_pairs = spec["experts_per_token"] * spec["held"][1] / spec["experts"]
    return (d * spec["experts"] + 2 * d * spec["shared_width"]
            + held_pairs * 2 * d * spec["expert_width"])


def attention_params(spec):
    d, hd = spec["hidden"], spec["head_dim"]
    return d * hd * (2 * spec["heads"] + 2 * spec["kv_heads"])


def _per_context_row(spec):
    """FLOPs of one attention layer's scores and weighted sum a cached
    row."""
    return 4 * spec["heads"] * spec["head_dim"]


def _matmul_flops(spec):
    """Per position, all layers, without attention over the context."""
    return (count(spec, "M") * (2 * mamba_params(spec)
                                + state_update_flops(spec))
            + count(spec, "E") * 2 * ffn_params(spec)
            + count(spec, "*") * 2 * attention_params(spec))


def decode_flops(spec, context):
    """One decode position over ``context`` cached rows, with the head
    over the vocabulary slice."""
    return (_matmul_flops(spec)
            + count(spec, "*") * _per_context_row(spec) * context
            + 2 * spec["hidden"] * spec["vocab"])


def prompt_flops(spec, prompt_len):
    """A prompt of ``prompt_len`` tokens processed causally (no logits):
    position j has context j + 1."""
    n = prompt_len
    return (_matmul_flops(spec) * n
            + count(spec, "*") * _per_context_row(spec) * n * (n + 1) / 2)
