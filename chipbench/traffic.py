"""The one general generator: inputs from a traffic file and ``--seed``.

A traffic mix is a data file under traffic/ — lengths, rates, arrival
process, sharing — and this module is the only code that reads it. The
same seed gives the same inputs; the program receives only what is
generated here.

Every seed carries the SAME sizes (fixed by the file and its
``shape_seed``), in another order, with other token ids: so the work a run
is offered does not depend on the seed, only its order does.
"""

from __future__ import annotations

import math

import numpy as np


def _rng(seed, stream):
    return np.random.default_rng([int(seed), stream])


# -- training jobs -------------------------------------------------------------

def row_lengths(job):
    """The real (unpadded) length of each row of one batch, before the
    seed orders them: google-research/bert create_pretraining_data.py
    fills a row to ``max_seq_length`` unless, with ``short_seq_prob``, it
    draws a target uniformly from what is shorter. So of ``batch`` rows
    ``round(short_seq_prob * batch)`` (at least one) are short, at the
    evenly spaced quantiles of [``short_min_tokens``, ``seq_len``], and
    the rest are full: every batch of every seed holds the same lengths."""
    b, s = job["batch"], job["seq_len"]
    n_short = max(1, round(job["short_seq_prob"] * b))
    u = (np.arange(n_short) + 0.5) / n_short
    lo = job["short_min_tokens"]
    short = np.round(lo + u * (s - lo)).astype(np.int64)
    return np.concatenate([short, np.full(b - n_short, s, np.int64)])


def train_batches(job, spec, seed):
    """``pool_batches`` distinct BERT pretraining batches (host numpy),
    cycled by the runner. Every batch holds the lengths of
    :func:`row_lengths` and exactly half next-sentence positives, in an
    order drawn from the seed: with random labels the next-sentence
    gradient's size, and with it every relative error, swung 4x from seed
    to seed (PERF.md). A row predicts ``masked_lm_prob`` of its real
    tokens, at most ``masked_per_row``, at distinct positions of its real
    part; the predictions a short row does not fill carry weight 0,
    position 0 and id 0, as create_pretraining_data.py pads them. Every
    row differs."""
    b, s, p = job["batch"], job["seq_len"], job["masked_per_row"]
    vocab = spec["vocab"]
    rng = _rng(seed, 1)
    lengths = row_lengths(job)
    out = []
    for _ in range(job["pool_batches"]):
        real = rng.permutation(lengths)
        pos = np.arange(s)[None, :]
        mask = (pos < real[:, None]).astype(np.int32)
        split = (real * rng.uniform(0.3, 0.7, size=b)).astype(np.int64)
        ids = rng.integers(0, vocab, size=(b, s)).astype(np.int32) * mask
        n_pred = np.clip(np.round(real * job["masked_lm_prob"]), 1,
                         p).astype(np.int64)
        weights = (np.arange(p)[None, :] < n_pred[:, None])
        mlm_pos = np.zeros((b, p), np.int32)
        for i, (r, n) in enumerate(zip(real, n_pred)):
            mlm_pos[i, :n] = np.sort(rng.choice(r, size=n, replace=False))
        out.append({
            "input_ids": ids,
            "token_type_ids": ((pos >= split[:, None]) & (mask > 0)
                               ).astype(np.int32),
            "input_mask": mask,
            "mlm_positions": mlm_pos,
            "mlm_ids": (rng.integers(0, vocab, size=(b, p)) * weights
                        ).astype(np.int32),
            "mlm_weights": weights.astype(np.float32),
            "nsp_labels": rng.permutation(np.arange(b) % 2).astype(np.int32),
        })
    return out


# -- request traffic -------------------------------------------------------------

def _quantiles(dist, u):
    """The distribution's values at the quantiles ``u`` (stratified: the
    block's sizes are spread evenly over the distribution)."""
    if dist["dist"] == "loguniform":
        lo, hi = math.log(dist["min"]), math.log(dist["max"])
        return np.clip(np.exp(lo + u * (hi - lo)).round(),
                       dist["min"], dist["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist['dist']!r}")


def request_count(mix, seconds):
    """Requests generated for a window of ``seconds``: more than the
    window can finish (``max_rate_per_s``) and the slots it leaves full
    (``extra``), a whole number of blocks."""
    n = int(math.ceil(mix["max_rate_per_s"] * seconds)) + mix["extra"]
    block = mix["block"]
    return -(-n // block) * block


def requests(mix, vocab, seed, seconds):
    """[{due, prompt, max_new_tokens}] in the order they are offered, all
    due at the window's start (``arrival: "backlog"``, the one arrival
    process there is; another kind is new code here, which only a
    benchmark PR may add).

    Sizes come in blocks of ``mix["block"]`` requests: one block's
    (prompt, answer) length pairs are the distributions' evenly spaced
    quantiles, paired by a permutation drawn once from ``shape_seed``;
    every block of every seed holds exactly those pairs, and the seed
    shuffles each block (but for the queue's head, below) and draws the
    token ids."""
    if mix["arrival"] != "backlog":
        raise ValueError(f"unknown arrival process {mix['arrival']!r}")
    n = request_count(mix, seconds)
    block = mix["block"]
    u = (np.arange(block) + 0.5) / block
    prompt_len = _quantiles(mix["prompt_len"], u)
    output_len = _quantiles(mix["output_len"],
                            _rng(mix["shape_seed"], 2).permutation(u))
    rng = _rng(seed, 3)
    order = np.concatenate([rng.permutation(block)
                            for _ in range(n // block)])
    # The queue's head is the block's middle pair on every seed: an idle
    # engine admits its first request alone, a page chunk at a time, so a
    # head drawn from the seed moved the whole window by up to 1.4 s, 3.9 %
    # of the rate (PERF.md).
    head = int(np.flatnonzero(order[:block] == block // 2)[0])
    order[[0, head]] = order[[head, 0]]
    out = []
    for j in order:
        # ids from [2, vocab): 0 pads and 1 ends a sequence
        prompt = rng.integers(2, vocab, size=int(prompt_len[j]))
        out.append({"due": 0.0, "prompt": prompt.astype(np.int32),
                    "max_new_tokens": int(output_len[j])})
    return out
