"""The benchmark's rehearsal of ``lfm2-8b-a1b.gen-backlog`` guarded by
tier-1, as ``tests/test_chipbench_traffic.py`` guards the traffic: every
case of ``chipbench/tests/test_short_conv_moe.py`` — the runner through
``run.measure()`` on the CPU at tiny sizes, ``correct`` true for the program
and false for the float8 control and the planted token, the counters, the
metric patterns, the work file — run here by import, and the
``gen-backlog`` mix beside them."""

import collections
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import traffic  # noqa: E402
from chipbench.runners import serve  # noqa: E402
from chipbench.tests import test_short_conv_moe as _cases  # noqa: E402
from chipbench.tests.test_short_conv_moe import *  # noqa: E402,F401,F403
from chipbench.tests.test_short_conv_moe import sound  # noqa: E402,F401
from chipbench.tests.test_traffic import (  # noqa: E402,F401
    SEEDS, load, manifest)

# every case the module has is run here: a new one there is one more here
assert {n for n in dir(_cases) if n.startswith("test_")} <= set(globals())

MIX = "gen-backlog"
CELL = "lfm2-8b-a1b.gen-backlog"
VOCAB = 65536


def _lfm2():
    return load("configs", "lfm2-8b-a1b.json")


def test_gen_backlog_offers_over_three_windows(manifest):
    """96 blocks of 16 at the manifest's window, under the configuration's
    queue: over three times what a window takes up (458 requests in the
    cell's first traced run on the chip)."""
    mix, config = load("traffic", MIX + ".json"), _lfm2()
    cell = [w for w in manifest["workloads"] if w["name"] == CELL][0]
    assert cell["traffic"] == MIX and cell["chips"] == 1
    offered = traffic.request_count(mix, manifest["run_seconds"])
    assert offered == 1536 == 96 * mix["block"]
    assert offered >= 3 * 458
    assert offered <= config["program"]["max_queue_depth"]
    assert offered >= 3 * config["program"]["model_kwargs"]["max_live"]
    assert mix["arrival"] == "backlog" and mix["kind"] == "requests"


def test_gen_backlog_holds_the_same_sizes_on_every_seed():
    mix = load("traffic", MIX + ".json")
    block = mix["block"]
    sizes = []
    for seed in SEEDS:
        reqs = traffic.requests(mix, VOCAB, seed, 1.0)
        pairs = [(len(r["prompt"]), r["max_new_tokens"]) for r in reqs]
        for b in range(0, len(pairs), block):
            sizes.append(collections.Counter(pairs[b:b + block]))
        assert all(r["due"] == 0.0 for r in reqs)
        assert min(int(r["prompt"].min()) for r in reqs) >= 2
        assert max(int(r["prompt"].max()) for r in reqs) < VOCAB
    assert all(s == sizes[0] for s in sizes)
    prompts = sorted(p for p, _ in sizes[0].elements())
    answers = sorted(a for _, a in sizes[0].elements())
    assert 128 <= prompts[0] < prompts[-1] <= 1024
    assert 256 <= answers[0] < answers[-1] <= 2048
    # log-uniform: mean about 431 in, 862 out
    assert 400 < sum(prompts) / block < 460
    assert 800 < sum(answers) / block < 920


def test_gen_backlogs_longest_request_fits_13_pages():
    mix, kw = load("traffic", MIX + ".json"), _lfm2()["program"][
        "model_kwargs"]
    longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    assert (kw["page_len"], kw["pages_per_seq"]) == (256, 13)
    assert longest + 1 <= kw["page_len"] * kw["pages_per_seq"]
    assert kw["num_pages"] >= kw["max_live"] * kw["pages_per_seq"]
    assert kw["decode_bucket_sizes"] == [1, kw["max_live"]] == [1, 256]
    assert kw["prefill_bucket_sizes"] == [1, 2, 4, 8]


def test_gen_backlog_is_offered_in_one_order():
    from chipbench.runners import serve_with_reference

    mix = load("traffic", MIX + ".json")
    assert serve_with_reference.in_one_order is serve.in_one_order

    def offered(seed):
        made = [serve._Request(r) for r in traffic.requests(
            mix, VOCAB, seed, 1.0)]
        return serve.in_one_order(made, mix)

    a, b = offered(SEEDS[2]), offered(SEEDS[3])
    sizes = [(len(r.prompt), r.budget) for r in a]
    assert sizes == [(len(r.prompt), r.budget) for r in b]
    assert sizes[0] == sorted(sizes[:16])[8]       # the middle pair heads
