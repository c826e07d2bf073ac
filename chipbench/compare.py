"""The comparison that decides ``correct``: numbers, each beside its limit.

Training (``train_numbers``), by the worst step or leaf:

``loss_gap``     |program loss - reference loss| / |reference loss|, the
                 worst of the followed steps.
``grad1_gap``    the first gradient as Adam got it, per leaf: the gap between
                 the program's norm and the reference's norm (not the norm of
                 their difference), against the reference's norm of that leaf
                 or of the median leaf, whichever is larger.
``grad1_diff``   the same first gradient, by direction: the norm of the
                 DIFFERENCE between the program's and the reference's, at
                 the MEDIAN leaf, against the reference's gradient norm of
                 the LARGEST leaf (the most parameters). Rounding noise is
                 zero-mean and all but cancels in a norm, so the two gaps of
                 norms do not tell bfloat16 from float8 (PERF.md); this one
                 does. The difference reads alike on every seed, while most
                 leaves' own norms swing 4x with how far the random
                 next-sentence head leans to one class; the largest leaf,
                 the word embedding, is set by the masked-LM loss and reads
                 alike on every seed (PERF.md), so it is the scale.
``change_gap``   the same for the parameters' change after the followed
                 steps, over the leaves whose reference gradient is at least
                 a thousandth of the median leaf's: the others (a key's bias
                 under softmax) move under Adam by round-off alone.

Serving (``serve_numbers``): ``logit_gap`` is the widest gap by which a
served token's logit lies below the reference's best, over the sampled
requests' served tokens; ``logprob_gap`` the widest distance between the
log-probability the server streamed with a token and the reference's of
that token; ``missing`` counts sampled answers that are cut short or never
came.

A limit of ``None`` in the cell's limits file means the number is printed
but not compared (PERF.md says which and why).
"""

from __future__ import annotations

import statistics

import numpy as np


def _worst_leaf_gap(got, want, keep=None):
    median = statistics.median(want.values())
    worst, worst_leaf = 0.0, None
    for leaf, ref_norm in want.items():
        if keep is not None and leaf not in keep:
            continue
        gap = abs(got[leaf] - ref_norm) / max(ref_norm, median, 1e-30)
        if gap >= worst:
            worst, worst_leaf = gap, leaf
    return worst, worst_leaf


def train_numbers(got, want):
    """The three readings, with the leaf each worst one sits on."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], want["losses"]))
    g_ref = want["grad1_norms"]
    g_median = statistics.median(g_ref.values())
    moved = {k for k, g in g_ref.items() if g >= 1e-3 * g_median}
    grad_gap, grad_leaf = _worst_leaf_gap(got["grad1_norms"], g_ref)
    change_gap, change_leaf = _worst_leaf_gap(
        got["change_norms"], want["change_norms"], keep=moved)
    numbers = {"loss_gap": loss_gap, "grad1_gap": grad_gap,
               "change_gap": change_gap}
    leaves = {"grad1_leaf": grad_leaf, "change_leaf": change_leaf,
              "leaves_left_out": sorted(set(g_ref) - moved)}
    largest = max(g_ref, key=lambda k: want["grad1"][k].size)
    diffs = sorted(float(np.linalg.norm(got["grad1"][k] - want["grad1"][k]))
                   for k in g_ref)
    numbers["grad1_diff"] = diffs[len(diffs) // 2] / g_ref[largest]
    leaves.update(grad1_diff_scale_leaf=largest,
                  grad1_diff_scale=g_ref[largest],
                  grad1_median_norm=g_median)
    return numbers, leaves


def against(numbers, limits):
    return {name: (value, limits[name]["limit"])
            for name, value in numbers.items()
            if limits.get(name, {}).get("limit") is not None}


def serve_numbers(rows, missing, control=False):
    """From the reference's rows over the sampled requests (see
    ``served_token_gaps``): the widest logit gap and the widest distance
    between a served log-probability and the reference's of the same
    token. ``control``: read the control's columns instead."""
    gap_key = "control_gap" if control else "gap"
    lp_key = "control_logprob" if control else "served_logprob"
    gaps = [float(r[gap_key].max()) for r in rows if len(r[gap_key])]
    lps = [float(abs(r[lp_key] - r["logprob"]).max()) for r in rows
           if len(r["logprob"])]
    return {"logit_gap": max(gaps, default=0.0),
            "logprob_gap": max(lps, default=0.0),
            "missing": float(missing)}


def is_correct(compared):
    return bool(compared) and all(value <= limit
                                  for value, limit in compared.values())
