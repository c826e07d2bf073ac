"""The benchmark's traffic, guarded by tier-1 (PERF.md section 7 left it
for the first PR that may add a file under tests/): every case of
``chipbench/tests/test_traffic.py`` — the generator, the one order of every
block, the sleeping window and its log — run here by import, and the
``repo-backlog`` mix of ``kimi-k2.7-code.repo-backlog`` and the
``reasoning-backlog`` mix of ``nemotron-3-nano-30b-a3b.reasoning-backlog``
beside them."""

import collections
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import traffic  # noqa: E402
from chipbench.runners import serve  # noqa: E402
from chipbench.tests import test_traffic as _cases  # noqa: E402
from chipbench.tests.test_traffic import *  # noqa: E402,F401,F403
from chipbench.tests.test_traffic import (  # noqa: E402,F401
    SEEDS, load, manifest, sleeps)

# every case the module has is run here: a new one there is one more here
assert {n for n in dir(_cases) if n.startswith("test_")} <= set(globals())

MIX = "repo-backlog"
CELL = "kimi-k2.7-code.repo-backlog"
VOCAB = 20480


def _kimi():
    return load("configs", "kimi-k2.7-code.json")


def test_repo_backlog_offers_three_times_what_a_window_takes_up(manifest):
    """192 requests in 12 blocks of 16 at the manifest's window, under the
    configuration's queue: a window takes up 32 slots and what retires,
    under 64 requests (PERF.md section 5)."""
    mix, config = load("traffic", MIX + ".json"), _kimi()
    cell = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert cell["traffic"] == MIX and cell["chips"] == 1
    offered = traffic.request_count(mix, manifest["run_seconds"])
    assert offered == 192 == 12 * mix["block"]
    assert offered <= config["program"]["max_queue_depth"]
    assert offered >= 3 * 2 * config["program"]["model_kwargs"]["max_live"]
    assert mix["arrival"] == "backlog" and mix["kind"] == "requests"


def test_repo_backlog_holds_the_same_sizes_and_head_on_every_seed():
    mix = load("traffic", MIX + ".json")
    block, seconds = mix["block"], 3.0
    sizes, heads, orders = [], [], []
    for seed in SEEDS:
        reqs = traffic.requests(mix, VOCAB, seed, seconds)
        pairs = [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]
        for b in range(0, len(pairs), block):
            sizes.append(collections.Counter(pairs[b:b + block]))
        heads.append(pairs[0])
        orders.append(tuple(pairs))
        assert all(r["due"] == 0.0 for r in reqs)
        # ids from the vocabulary SLICE this chip holds; 0 pads, 1 ends
        assert min(int(r["prompt"].min()) for r in reqs) >= 2
        assert max(int(r["prompt"].max()) for r in reqs) < VOCAB
    assert all(s == sizes[0] for s in sizes)
    assert sum(sizes[0].values()) == block
    assert len(set(heads)) == 1 and len(set(orders)) == len(SEEDS)
    prompts = sorted(p for p, _ in sizes[0].elements())
    answers = sorted(a for _, a in sizes[0].elements())
    assert 2048 <= prompts[0] < prompts[-1] <= 16384
    assert 256 <= answers[0] < answers[-1] <= 2048
    # log-uniform: mean about 6.9k in, 860 out
    assert 6500 < sum(prompts) / block < 7300
    assert 800 < sum(answers) / block < 920


def test_repo_backlogs_longest_request_fits_a_sequence():
    mix, kw = load("traffic", MIX + ".json"), _kimi()["program"][
        "model_kwargs"]
    longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    assert (kw["page_len"], kw["pages_per_seq"]) == (512, 37)
    assert longest + 1 <= kw["page_len"] * kw["pages_per_seq"]
    # a pool that holds every slot's longest sequence, and the scratch page
    assert kw["num_pages"] >= kw["max_live"] * kw["pages_per_seq"]


def test_repo_backlog_is_offered_in_one_order(manifest):
    mix = load("traffic", MIX + ".json")

    def offered(seed):
        made = [serve._Request(r) for r in traffic.requests(
            mix, VOCAB, seed, manifest["run_seconds"])]
        return made, serve.in_one_order(made, mix)

    (made_a, a), (_, b) = offered(SEEDS[2]), offered(SEEDS[3])
    sizes = [(len(r.prompt), r.budget) for r in a]
    assert sizes == [(len(r.prompt), r.budget) for r in b]
    assert sorted(map(id, a)) == sorted(map(id, made_a))
    assert sizes[0] == sorted(sizes[:16])[8]       # the middle pair heads
    assert sizes[:16] != sizes[16:32]
    assert any((x.prompt[:8] != y.prompt[:8]).any() for x, y in zip(a, b))


# -- reasoning-backlog (nemotron-3-nano-30b-a3b.reasoning-backlog) --------------

REASONING = "reasoning-backlog"
REASONING_CELL = "nemotron-3-nano-30b-a3b.reasoning-backlog"
REASONING_VOCAB = 65536


def _nemotron():
    return load("configs", "nemotron-3-nano-30b-a3b.json")


def test_reasoning_backlog_offers_1536(manifest):
    """96 blocks of 16 at the manifest's window, under the configuration's
    queue: over three times the 256 slots and what retires in a window."""
    mix, config = load("traffic", REASONING + ".json"), _nemotron()
    cell = [w for w in manifest["workloads"]
            if w["name"] == REASONING_CELL][0]
    assert cell["traffic"] == REASONING and cell["chips"] == 1
    offered = traffic.request_count(mix, manifest["run_seconds"])
    assert offered == 1536 == 96 * mix["block"]
    assert offered <= config["program"]["max_queue_depth"]
    assert offered >= 3 * config["program"]["model_kwargs"]["max_live"]
    assert mix["arrival"] == "backlog" and mix["kind"] == "requests"
    assert (mix["trace_seconds"], mix["trace_start_seconds"]) == (10, 20)
    assert mix["check_requests"] == 4 and mix["drain_seconds"] == 8.0


def test_reasoning_backlog_holds_the_same_sizes_and_head_on_every_seed():
    mix = load("traffic", REASONING + ".json")
    block, seconds = mix["block"], 1.0
    sizes, heads, orders = [], [], []
    for seed in SEEDS:
        reqs = traffic.requests(mix, REASONING_VOCAB, seed, seconds)
        pairs = [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]
        for b in range(0, len(pairs), block):
            sizes.append(collections.Counter(pairs[b:b + block]))
        heads.append(pairs[0])
        orders.append(tuple(pairs))
        assert all(r["due"] == 0.0 for r in reqs)
        # ids from the vocabulary SLICE this chip holds; 0 pads, 1 ends
        assert min(int(r["prompt"].min()) for r in reqs) >= 2
        assert max(int(r["prompt"].max()) for r in reqs) < REASONING_VOCAB
    assert all(s == sizes[0] for s in sizes)
    assert sum(sizes[0].values()) == block
    assert len(set(heads)) == 1 and len(set(orders)) == len(SEEDS)
    prompts = sorted(p for p, _ in sizes[0].elements())
    answers = sorted(a for _, a in sizes[0].elements())
    assert 128 <= prompts[0] < prompts[-1] <= 1024
    assert 256 <= answers[0] < answers[-1] <= 2048
    # log-uniform: mean about 431 in, 862 out
    assert 400 < sum(prompts) / block < 460
    assert 800 < sum(answers) / block < 920


def test_reasoning_backlogs_longest_request_fits_13_pages():
    mix, kw = load("traffic", REASONING + ".json"), _nemotron()["program"][
        "model_kwargs"]
    longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    assert (kw["page_len"], kw["pages_per_seq"]) == (256, 13)
    assert longest + 1 <= kw["page_len"] * kw["pages_per_seq"]
    # a pool that holds every slot's longest sequence, and the scratch page
    assert kw["num_pages"] >= kw["max_live"] * kw["pages_per_seq"]
    assert kw["decode_bucket_sizes"] == [1, kw["max_live"]] == [1, 256]


def test_reasoning_backlog_is_offered_in_one_order(manifest):
    from chipbench.runners import serve_state_space_moe

    mix = load("traffic", REASONING + ".json")
    assert serve_state_space_moe.in_one_order is serve.in_one_order

    def offered(seed):
        made = [serve._Request(r) for r in traffic.requests(
            mix, REASONING_VOCAB, seed, 1.0)]
        return made, serve.in_one_order(made, mix)

    (made_a, a), (_, b) = offered(SEEDS[2]), offered(SEEDS[3])
    sizes = [(len(r.prompt), r.budget) for r in a]
    assert sizes == [(len(r.prompt), r.budget) for r in b]
    assert sorted(map(id, a)) == sorted(map(id, made_a))
    assert sizes[0] == sorted(sizes[:16])[8]       # the middle pair heads
    assert sizes[:16] != sizes[16:32]
    assert any((x.prompt[:8] != y.prompt[:8]).any() for x, y in zip(a, b))
