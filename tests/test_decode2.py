"""Decode throughput II (ISSUE 16): shared-prefix prompt cache
(refcount trie, CoW divergence, churn fuzz with reconcile drift 0),
speculative decoding (greedy token-exact vs the PR 11 cached decode
path, through a checkpoint round trip), sampling decode determinism
under a fixed seed, KVCachePageCopy / copy_pages conformance,
query-block decode-attention parity, the paged causal-LM serving path,
the new serving-decode-cache lint branches, and the new
/stf/serving/{prefix_cache_*,spec_*} metrics."""

import dataclasses
import os
import tempfile
import threading

import numpy as np
import pytest

import simple_tensorflow_tpu as stf
from simple_tensorflow_tpu import analysis, serving
from simple_tensorflow_tpu.models import causal_lm as clm
from simple_tensorflow_tpu.models import transformer as tr
from simple_tensorflow_tpu.ops import kv_cache_ops as kvc
from simple_tensorflow_tpu.platform import monitoring
from simple_tensorflow_tpu.serving.prefix_cache import (
    PagesExhaustedError, PrefixCache)


@pytest.fixture(autouse=True)
def _fresh_graph():
    stf.reset_default_graph()
    yield
    stf.reset_default_graph()


# ---------------------------------------------------------------------------
# KVCachePageCopy op conformance (copy_pages: the CoW primitive)
# ---------------------------------------------------------------------------

class TestKVCachePageCopy:
    def test_copy_pages_duplicates_rows(self):
        c = kvc.kv_cache("pc_cow", num_slots=4, max_len=3,
                         inner_shape=(2,), dtype=stf.float32, paged=True)
        alloc = c.alloc()
        val = stf.placeholder(stf.float32, [1, 3, 2], "cow_val")
        one = stf.constant(np.array([1], np.int32))
        zero = stf.constant(np.array([0], np.int32))
        appended = c.append(val, one, zero)
        copied = c.copy_pages(stf.constant(np.array([2], np.int32)), one)
        slots = stf.placeholder(stf.int32, [2], "cow_slots")
        g = c.gather(slots)
        with stf.Session() as sess:
            sess.run(alloc.op)
            v = np.arange(6, dtype=np.float32).reshape(1, 3, 2)
            sess.run(appended.op, {val: v})
            sess.run(copied.op)
            out = sess.run(g, {slots: np.array([1, 2], np.int32)})
            # dst page is a byte-identical duplicate of src
            assert np.array_equal(out[0], out[1])
            assert np.array_equal(out[1], v[0])
            # an un-copied page is untouched
            out0 = sess.run(g, {slots: np.array([0, 3], np.int32)})
            assert (out0 == 0).all()

    def test_copy_then_diverge_leaves_src_intact(self):
        # the CoW contract: appends into the copy never write through
        # to the shared source page
        c = kvc.kv_cache("pc_div", num_slots=3, max_len=4,
                         inner_shape=(), dtype=stf.float32, paged=True)
        alloc = c.alloc()
        val = stf.placeholder(stf.float32, [1, 2], "div_val")
        s0 = stf.constant(np.array([0], np.int32))
        s1 = stf.constant(np.array([1], np.int32))
        zero = stf.constant(np.array([0], np.int32))
        two = stf.constant(np.array([2], np.int32))
        fill_src = c.append(val, s0, zero)
        cow = c.copy_pages(s1, s0)
        val1 = stf.placeholder(stf.float32, [1, 1], "div_val1")
        diverge = c.append(val1, s1, two)
        slots = stf.placeholder(stf.int32, [2], "div_slots")
        g = c.gather(slots)
        with stf.Session() as sess:
            sess.run(alloc.op)
            sess.run(fill_src.op, {val: np.array([[5., 6.]], np.float32)})
            sess.run(cow.op)
            sess.run(diverge.op, {val1: np.array([[9.]], np.float32)})
            out = sess.run(g, {slots: np.array([0, 1], np.int32)})
            assert np.array_equal(out[0], [5., 6., 0., 0.])   # src intact
            assert np.array_equal(out[1], [5., 6., 9., 0.])   # copy diverged

    def test_effects_declared(self):
        from simple_tensorflow_tpu.framework import op_registry

        c = kvc.kv_cache("pc_eff", 2, 2, (), stf.float32, paged=True)
        t = c.copy_pages(stf.constant(np.array([0], np.int32)),
                         stf.constant(np.array([1], np.int32)))
        eff = op_registry.get("KVCachePageCopy").effects
        assert eff.resolved_writes(t.op) == {"var_name=pc_eff"}
        assert t.op.attrs.get(kvc.PAGED_ATTR) is True


# ---------------------------------------------------------------------------
# PrefixCache: trie, refcounts, CoW probe, eviction, reconcile
# ---------------------------------------------------------------------------

class TestPrefixCacheUnit:
    def test_full_chunk_hit_and_miss_accounting(self):
        pc = PrefixCache(num_pages=8, page_len=4)
        p1 = pc.acquire(list(range(8)))
        assert len(p1.fill) == 2 and not p1.reused_pages
        assert p1.tail_page is None and pc.miss_pages == 2
        p2 = pc.acquire(list(range(8)))
        assert p2.reused_pages == p1.pages and not p2.fill
        assert pc.hit_pages == 2 and pc.shared_pages == 2
        # both sequences hold refs on the same chain
        assert p2.node is p1.node and p2.node.refs == 2

    def test_partial_tail_is_trie_resident_with_cow(self):
        pc = PrefixCache(num_pages=8, page_len=4)
        pa = pc.acquire(list(range(8)))
        pb = pc.acquire(list(range(6)))     # chunk [0:4] + tail [4, 5]
        assert pb.reused_pages == [pa.pages[0]]
        # tail [4, 5] is a proper prefix of A's second chunk (4,5,6,7):
        # served by page copy, not prefill
        assert pb.cow_src == pa.pages[1]
        assert pb.tail_page is not None
        assert pb.tail_page not in pa.pages
        assert pc.cow_hits == 1
        # the tail is TRIE-RESIDENT (ISSUE 20): a leaf node keyed on
        # the partial chunk joins the two full-chunk nodes
        assert pc.shared_pages == 3
        assert pb.node.chunk == (4, 5)
        assert pb.node.page == pb.tail_page
        assert not pb.tail_ready
        assert np.array_equal(pb.tail, [4, 5])
        assert pb.cached_len == 6
        # an identical tail later is an exact-hit: zero prefill, zero
        # copy (tail_ready), sharing the same node/page
        pb2 = pc.acquire(list(range(6)))
        assert pb2.tail_ready and pb2.tail_page == pb.tail_page
        assert pb2.cow_src is None and pb2.node is pb.node
        assert pb.node.refs == 2

    def test_tail_without_extending_child_prefills(self):
        pc = PrefixCache(num_pages=8, page_len=4)
        pc.acquire(list(range(8)))
        pb = pc.acquire([0, 1, 2, 3, 99, 98])   # tail diverges
        assert pb.cow_src is None and pb.tail_page is not None
        assert pc.cow_hits == 0

    def test_release_keeps_pages_resident_until_eviction(self):
        pc = PrefixCache(num_pages=2, page_len=4)
        pa = pc.acquire(list(range(8)))
        pc.release(pa.node)
        # refs dropped to 0 but the pages stay cached (that IS the cache)
        assert pc.shared_pages == 2 and pc.free_count == 0
        # a hit on the released chain revives it with zero prefill
        pb = pc.acquire(list(range(8)))
        assert pb.reused_pages == pa.pages and pc.hit_pages == 2
        pc.release(pb.node)
        # a disjoint admission now EVICTS (LRU refs-0 leaves)
        pcd = pc.acquire([50, 51, 52, 53])
        assert pc.evictions >= 1 and len(pcd.fill) == 1
        assert pc.reconcile([]) == 0

    def test_eviction_is_leaf_first(self):
        pc = PrefixCache(num_pages=2, page_len=2)
        pa = pc.acquire([1, 2, 3, 4])       # chain of two nodes
        pc.release(pa.node)
        pc.acquire([9, 8])                  # needs one page: evicts
        # the LEAF (deeper node) went first; its parent survives
        assert pc.evictions == 1
        resident = {n.chunk for n in pc._iter_nodes()}
        assert (1, 2) in resident and (3, 4) not in resident

    def test_exhaustion_raises_and_rolls_back(self):
        pc = PrefixCache(num_pages=2, page_len=4)
        held = pc.acquire(list(range(8)))   # both pages, refs=1
        before = pc.statusz_info()
        with pytest.raises(PagesExhaustedError):
            pc.acquire([90, 91, 92, 93, 94])
        # full rollback: no leaked refs, pages, or trie nodes
        assert pc.reconcile([]) == 0
        assert pc.shared_pages == before["shared_pages"]
        assert held.node.refs == 1

    def test_reconcile_detects_drift(self):
        pc = PrefixCache(num_pages=4, page_len=4)
        plan = pc.acquire(list(range(4)))
        assert pc.reconcile([]) == 0
        # manufacture a double-owned page: reconcile must flag it
        pc.free_page(plan.pages[0])
        assert pc.reconcile([]) > 0


class TestPrefixChurnFuzz:
    def test_refcount_fuzz_12_request_churn_drift_zero(self):
        # 12 concurrently-live requests churning over a small pool:
        # shared prefixes, CoW tails, private decode pages, eviction
        # pressure. After EVERY transition the three page populations
        # (free / trie / private) must reconcile with drift 0.
        rng = np.random.RandomState(1234)
        pc = PrefixCache(num_pages=24, page_len=4)
        prefixes = [list(rng.randint(2, 64, rng.randint(2, 13)))
                    for _ in range(5)]
        live = []       # (node, private_pages)

        def _reconcile():
            private = [p for _, priv in live for p in priv]
            assert pc.reconcile(private) == 0

        for step in range(300):
            if live and (len(live) >= 12 or rng.rand() < 0.4):
                node, priv = live.pop(rng.randint(len(live)))
                pc.release(node)
                for pg in priv:
                    pc.free_page(pg)
                _reconcile()
                continue
            toks = list(prefixes[rng.randint(len(prefixes))])
            toks += list(rng.randint(2, 64, rng.randint(0, 6)))
            try:
                plan = pc.acquire(toks)
            except PagesExhaustedError:
                _reconcile()
                continue
            priv = []
            if len(plan.tail):
                # the tail page is trie-resident: the first decode
                # append into it copies-on-write into a private page
                try:
                    priv.append(pc.alloc_page())
                except PagesExhaustedError:
                    pass
            # a few decode-time page-fault allocations
            for _ in range(rng.randint(0, 3)):
                try:
                    priv.append(pc.alloc_page())
                except PagesExhaustedError:
                    break
            live.append((plan.node, priv))
            _reconcile()
        # drain everything: the pool must come back whole
        for node, priv in live:
            pc.release(node)
            for pg in priv:
                pc.free_page(pg)
        assert pc.reconcile([]) == 0
        assert pc.hit_pages > 0 and pc.miss_pages > 0


# ---------------------------------------------------------------------------
# The victim order is KEPT (a heap), the policy is the walk's: an oracle
# names the victim from the whole trie at every allocation from a dry
# pool; and the step path never iterates the trie
# ---------------------------------------------------------------------------

def _walk_victim(pc, pin=()):
    """The policy as a walk of the whole trie: the least-recently-touched
    unpinned refs-0 leaf, None when every page is live."""
    victim = None
    for n in pc._iter_nodes():
        if n.refs == 0 and not n.children and n.page not in pin:
            if victim is None or n.last_use < victim.last_use:
                victim = n
    return victim


def _refs_by_node(pc):
    return {n: n.refs for n in pc._iter_nodes()}


def _walked_count(pc):
    return sum(1 for _ in pc._iter_nodes())


def _private(live):
    return [p for _, priv in live for p in priv]


def _retire(pc, node, priv):
    pc.release(node)
    for pg in priv:
        pc.free_page(pg)


def _no_walk(*_):
    raise AssertionError("the step path walked the trie")


class _OracleCache(PrefixCache):
    """Holds every eviction to the walk, and the bookkeeping to the trie
    as walked, at the moment a page is wanted from a dry pool."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.live = []          # (node, private pages), the churn's
        self.evicted = []
        self.refused = 0
        self.pin_decided = 0

    def _evict_one(self, pin):
        assert self.reconcile(_private(self.live)) == 0
        assert self.shared_pages == _walked_count(self)
        want = _walk_victim(self, pin)
        if want is None:
            self.refused += 1
            return super()._evict_one(pin)      # raises, as the walk did
        if want is not _walk_victim(self):
            self.pin_decided += 1
        got = super()._evict_one(pin)
        assert got is want
        self.evicted.append(got)
        return got


def _churn(pc, rng, steps, heads, suffix, max_faults):
    """Shared heads (each cut at one of its four places, inside a page
    more often than at its end, so that tails find copy-on-write sources
    and a retired tail is hit again), decode page faults with and
    without a pinned page, releases, and admissions until the pool
    refuses: every eviction goes through ``_OracleCache._evict_one``.
    Admits only until the pool first refuses, then ``steps`` steps in
    stretches of 20, mostly retirements and mostly admissions in turn:
    what a stretch retires is resident at refs 0 for the next to hit."""
    live = pc.live
    rolled_back = 0
    while steps:
        if rolled_back:
            steps -= 1
        if live and rolled_back and \
                rng.rand() < (0.75 if steps // 20 % 2 else 0.3):
            _retire(pc, *live.pop(rng.randint(len(live))))
            continue
        head, cuts = heads[rng.randint(len(heads))]
        toks = list(head[:cuts[rng.randint(len(cuts))]])
        if rng.rand() < 0.7:
            toks += list(rng.randint(2, 1000, rng.randint(*suffix)))
        before = _refs_by_node(pc)
        n_evicted = len(pc.evicted)
        try:
            plan = pc.acquire(toks)
        except PagesExhaustedError:
            # roll-back: the trie is what it was, less what was evicted
            rolled_back += 1
            for nd in pc.evicted[n_evicted:]:
                assert before.pop(nd) == 0
            assert _refs_by_node(pc) == before
            continue
        priv = []
        live.append((plan.node, priv))
        try:
            if len(plan.tail) and rng.rand() < 0.6:
                # first decode append into the shared tail page
                priv.append(pc.alloc_page({plan.tail_page}))
            for _ in range(rng.randint(0, max_faults + 1)):
                priv.append(pc.alloc_page())
        except PagesExhaustedError:
            pass
    assert pc.reconcile(_private(live)) == 0
    assert pc.shared_pages == _walked_count(pc)
    return rolled_back


_GEOMETRIES = {
    # pool, page_len, steps, heads (count, tokens), suffix tokens, faults
    "small": (24, 4, 400, (5, (2, 13)), (0, 6), 2),
    "cell": (3072, 64, 320, (40, (200, 700)), (300, 1200), 40),
}


class TestVictimOrderIsTheWalks:
    @pytest.mark.parametrize("seed", range(8))
    @pytest.mark.parametrize("geometry", sorted(_GEOMETRIES))
    def test_every_eviction_is_the_oracles(self, geometry, seed):
        pool, pl, steps, (n_heads, head_len), suffix, faults = \
            _GEOMETRIES[geometry]
        rng = np.random.RandomState(3600 + seed)
        pc = _OracleCache(num_pages=pool, page_len=pl)
        heads = [list(rng.randint(2, 1000, rng.randint(*head_len)))
                 for _ in range(n_heads)]
        heads = [(h, rng.randint(1, len(h) + 1, 4)) for h in heads]
        rolled_back = _churn(pc, rng, steps, heads, suffix, faults)
        assert len(pc.evicted) == pc.evictions > 0
        assert pc.refused > 0 and rolled_back > 0
        assert pc.hit_pages > 0 and pc.cow_hits > 0
        for node, priv in pc.live:
            _retire(pc, node, priv)
        assert pc.reconcile([]) == 0

    def test_pinned_victim_is_skipped_and_put_back(self):
        pc = _OracleCache(num_pages=3, page_len=2)
        old = pc.acquire([1, 2])
        mid = pc.acquire([3, 4])
        new = pc.acquire([5, 6])
        for plan in (old, mid, new):
            pc.release(plan.node)
        # the oldest leaf is pinned: the next oldest goes, and the
        # pinned one is still the first to go once nothing pins it
        private = []
        pc.live.append((None, private))
        private.append(pc.alloc_page({old.pages[0]}))
        assert private == mid.pages and pc.pin_decided == 1
        private.append(pc.alloc_page())
        assert private == mid.pages + old.pages
        with pytest.raises(PagesExhaustedError):
            pc.alloc_page(set(new.pages))
        private.append(pc.alloc_page())
        with pytest.raises(PagesExhaustedError):
            pc.alloc_page()
        assert pc.reconcile(private) == 0 and pc.shared_pages == 0

    def test_roll_back_keeps_a_matched_leaf_evictable(self):
        pc = _OracleCache(num_pages=3, page_len=2)
        cached = pc.acquire([1, 2])
        pc.release(cached.node)
        held = pc.acquire([7, 8, 9, 10])
        # hits the cached leaf (its queued entry goes stale), then finds
        # no page for the rest: rolled back, the leaf is refs 0 again
        with pytest.raises(PagesExhaustedError):
            pc.acquire([1, 2, 3, 4])
        assert cached.node.refs == 0 and pc.shared_pages == 3
        assert pc.alloc_page() == cached.pages[0]
        assert pc.stale_discarded == 1
        assert pc.reconcile(cached.pages) == 0 and held.node.refs == 1

    def test_only_pinned_leaves_left_raises_and_keeps_them(self):
        pc = _OracleCache(num_pages=1, page_len=2)
        only = pc.acquire([1, 2])
        pc.release(only.node)
        with pytest.raises(PagesExhaustedError):
            pc.alloc_page({only.pages[0]})
        assert pc.statusz_info()["victim_entries"] == 1
        assert pc.alloc_page() == only.pages[0]


def _cell_prompt(rng):
    """`lm-big.backlog`'s prompts: log-uniform 600-1728, none shared."""
    n = int(np.exp(rng.uniform(np.log(600), np.log(1728))))
    return rng.randint(2, 32000, n)


class TestStepPathNeverWalksTheTrie:
    def test_dry_pool_admissions_and_retirements_never_iterate(
            self, monkeypatch):
        rng = np.random.RandomState(36)
        pc = PrefixCache(num_pages=3072, page_len=64)
        live = []

        def _admit():
            plan = pc.acquire(_cell_prompt(rng)[:-1])
            priv = [pc.alloc_page({plan.tail_page})] if len(plan.tail) \
                else []
            live.append((plan.node, priv))

        def _retire_one():
            _retire(pc, *live.pop(rng.randint(len(live))))

        while pc.evictions < 100:            # fill until the pool is dry
            _admit()
            if len(live) > 96:
                _retire_one()
        monkeypatch.setattr(pc, "_iter_nodes", _no_walk)
        before = pc.evictions
        for _ in range(300):
            _admit()
            live[-1][1].append(pc.alloc_page())      # a decode page fault
            _retire_one()
            info = pc.statusz_info()
            assert info["shared_pages"] == pc.shared_pages > 2000
        # every page handed out was evicted from the trie, and the
        # structure that names the victim holds only what is evictable
        assert pc.evictions - before > 3000
        assert info["victim_entries"] <= info["shared_pages"]
        monkeypatch.undo()
        assert pc.shared_pages == _walked_count(pc)
        assert pc.reconcile(_private(live)) == 0

    def test_hits_without_evictions_leave_the_structure_bounded(self):
        # a hot shared prefix hit and released with free pages left:
        # nothing is ever evicted, every hit strands an entry
        pc = PrefixCache(num_pages=64, page_len=4)
        prompts = [list(range(100 * k, 100 * k + 10)) for k in range(3)]
        for i in range(5000):
            pc.release(pc.acquire(prompts[i % 3]).node)
            info = pc.statusz_info()
            assert info["victim_entries"] <= 2 * info["shared_pages"] + 17
        assert pc.evictions == 0 and pc.free_count == 64 - 9
        assert info["shared_pages"] == 9
        assert info["stale_discarded"] > 4000
        # and the three tails are still the evictable ones, oldest first
        oldest = _walk_victim(pc)
        assert oldest.chunk == (208, 209)
        assert pc._evict_one(set()) is oldest


# ---------------------------------------------------------------------------
# Speculative decoding: greedy token-exact through a checkpoint
# ---------------------------------------------------------------------------

def _save_ckpt(model, tmp):
    ckpt = os.path.join(tmp, "model")
    with model.graph.as_default():
        saver = stf.train.Saver()
        saver.save(model.session, ckpt)
    return ckpt


def _run_engine(model, prompts, draft=None, max_new_tokens=6,
                num_slots=4, max_decode_len=8, name="eng"):
    pol = serving.DecodePolicy(num_slots=num_slots,
                               max_decode_len=max_decode_len,
                               max_new_tokens=max_new_tokens)
    with serving.GenerativeEngine(name, model, pol, draft=draft) as eng:
        futs = [eng.generate(p) for p in prompts]
        out = [f.result(timeout=120) for f in futs]
        stats = eng.statusz_info()
    return out, stats


class TestSpeculativeTokenExact:
    SRC_LEN, L = 8, 8

    def _target(self, cfg, ckpt, **kw):
        return tr.TransformerGenerativeModel(
            cfg, self.SRC_LEN, num_slots=4, max_decode_len=self.L,
            checkpoint=ckpt, aot_warmup=False, **kw)

    def test_greedy_token_exact_vs_cached_decode(self):
        # target + SAME-WEIGHTS draft: every proposal agrees, yet the
        # emitted stream must equal plain cached decode exactly (every
        # committed token is the target's own pick by construction)
        cfg = tr.TransformerConfig.tiny()
        tmp = tempfile.mkdtemp(prefix="stf_spec_")
        base_model = tr.TransformerGenerativeModel(
            cfg, self.SRC_LEN, num_slots=4, max_decode_len=self.L,
            init_fresh=True, aot_warmup=False, seed=7)
        ckpt = _save_ckpt(base_model, tmp)
        batch = tr.synthetic_wmt_batch(5, self.SRC_LEN, self.L,
                                       vocab_size=cfg.vocab_size)
        prompts = [batch["src_ids"][i] for i in range(5)]
        base_out, _ = _run_engine(base_model, prompts, name="spec_base")
        base_model.close()

        target = self._target(cfg, ckpt, speculative_k=3)
        draft = self._target(cfg, ckpt, draft_steps=2)
        spec_out, stats = _run_engine(target, prompts, draft=draft,
                                      name="spec_eng")
        target.close()
        draft.close()
        for b, s in zip(base_out, spec_out):
            assert list(b["tokens"]) == list(s["tokens"])
            assert b["outcome"] == s["outcome"]
        spec = stats["speculative"]
        assert spec["proposed_tokens"] > 0
        # identical draft weights: proposals mostly accepted
        assert spec["acceptance_rate"] >= 0.5

    def test_token_exact_even_with_garbage_draft(self):
        # a draft with UNRELATED weights proposes junk; acceptance
        # collapses but the output stream is still bit-exact (the
        # verify step commits only target-agreeing prefixes)
        cfg = tr.TransformerConfig.tiny()
        tmp = tempfile.mkdtemp(prefix="stf_spec_bad_")
        base_model = tr.TransformerGenerativeModel(
            cfg, self.SRC_LEN, num_slots=4, max_decode_len=self.L,
            init_fresh=True, aot_warmup=False, seed=7)
        ckpt = _save_ckpt(base_model, tmp)
        batch = tr.synthetic_wmt_batch(3, self.SRC_LEN, self.L,
                                       vocab_size=cfg.vocab_size, seed=5)
        prompts = [batch["src_ids"][i] for i in range(3)]
        base_out, _ = _run_engine(base_model, prompts, name="specb_base")
        base_model.close()

        target = self._target(cfg, ckpt, speculative_k=3)
        draft = tr.TransformerGenerativeModel(
            cfg, self.SRC_LEN, num_slots=4, max_decode_len=self.L,
            init_fresh=True, aot_warmup=False, seed=999, draft_steps=2)
        spec_out, _ = _run_engine(target, prompts, draft=draft,
                                  name="specb_eng")
        target.close()
        draft.close()
        for b, s in zip(base_out, spec_out):
            assert list(b["tokens"]) == list(s["tokens"])

    def test_draft_target_geometry_validated(self):
        cfg = tr.TransformerConfig.tiny()
        target = tr.TransformerGenerativeModel(
            cfg, self.SRC_LEN, num_slots=4, max_decode_len=self.L,
            init_fresh=True, aot_warmup=False, speculative_k=3)
        draft = tr.TransformerGenerativeModel(
            cfg, self.SRC_LEN, num_slots=4, max_decode_len=self.L,
            init_fresh=True, aot_warmup=False, draft_steps=3)  # k+1 != 3
        pol = serving.DecodePolicy(num_slots=4, max_decode_len=self.L)
        try:
            with pytest.raises(ValueError, match="draft_steps"):
                serving.GenerativeEngine("geom", target, pol, draft=draft)
        finally:
            target.close()
            draft.close()

    def test_verify_matches_chained_single_steps(self):
        # the ONE batched re-score must equal K chained decode() calls
        cfg = tr.TransformerConfig.tiny()
        model = tr.TransformerGenerativeModel(
            cfg, self.SRC_LEN, num_slots=2, max_decode_len=self.L,
            init_fresh=True, aot_warmup=False, seed=3, speculative_k=3)
        try:
            batch = tr.synthetic_wmt_batch(1, self.SRC_LEN, self.L,
                                           vocab_size=cfg.vocab_size)
            model.prefill(batch["src_ids"], [0])
            # chained reference on slot 0
            tok = np.array([cfg.eos_id], np.int32)
            chain = []
            for t in range(3):
                nxt, _lp, _b = model.decode(tok, [t], [0])
                chain.append(int(nxt[0]))
                tok = nxt
            # fresh slot 1, same prompt: verify the SAME block in one go
            model.prefill(batch["src_ids"], [1])
            blk = np.array([[cfg.eos_id, chain[0], chain[1]]], np.int32)
            toks, logps, _b = model.verify(blk, [0], [1])
            assert list(toks[0]) == chain
            assert np.all(logps <= 0.0)
        finally:
            model.close()


# ---------------------------------------------------------------------------
# Sampling decode: seeded determinism
# ---------------------------------------------------------------------------

class TestSamplingDecode:
    def _decode_seq(self, model, src, steps):
        model.prefill(src[None, :], [0])
        tok = np.array([model.eos_id], np.int32)
        out = []
        for t in range(steps):
            nxt, lp, _b = model.decode(tok, [t], [0])
            out.append(int(nxt[0]))
            assert lp[0] <= 0.0
            tok = nxt
        return out

    def test_fixed_seed_reproduces_across_rebuilds(self):
        cfg = tr.TransformerConfig.tiny()
        sampling = {"temperature": 0.8, "top_k": 8, "top_p": 0.95,
                    "seed": 123}
        batch = tr.synthetic_wmt_batch(1, 8, 8,
                                       vocab_size=cfg.vocab_size)
        runs = []
        for _ in range(2):
            model = tr.TransformerGenerativeModel(
                cfg, 8, num_slots=2, max_decode_len=6, init_fresh=True,
                aot_warmup=False, seed=11, sampling=sampling)
            try:
                runs.append(self._decode_seq(model, batch["src_ids"][0],
                                             5))
            finally:
                model.close()
        assert runs[0] == runs[1]

    def test_top_k_one_is_greedy(self):
        # top_k=1 keeps only the argmax token: the sampled stream must
        # equal greedy decode from the same checkpoint
        cfg = tr.TransformerConfig.tiny()
        tmp = tempfile.mkdtemp(prefix="stf_samp_")
        greedy_model = tr.TransformerGenerativeModel(
            cfg, 8, num_slots=2, max_decode_len=6, init_fresh=True,
            aot_warmup=False, seed=11)
        ckpt = _save_ckpt(greedy_model, tmp)
        batch = tr.synthetic_wmt_batch(1, 8, 8,
                                       vocab_size=cfg.vocab_size)
        src = batch["src_ids"][0]
        greedy = self._decode_seq(greedy_model, src, 5)
        greedy_model.close()
        samp_model = tr.TransformerGenerativeModel(
            cfg, 8, num_slots=2, max_decode_len=6, checkpoint=ckpt,
            aot_warmup=False, sampling={"top_k": 1, "seed": 0})
        try:
            sampled = self._decode_seq(samp_model, src, 5)
        finally:
            samp_model.close()
        assert sampled == greedy

    def test_sample_token_respects_top_k_support(self):
        from simple_tensorflow_tpu.ops import sampling_ops

        stf.set_random_seed(0)
        logits_np = np.zeros((4, 16), np.float32)
        logits_np[:, 3] = 5.0
        logits_np[:, 7] = 4.0
        logits = stf.constant(logits_np)
        tok, logp = sampling_ops.sample_token(
            logits, temperature=1.0, top_k=2, seed=42)
        with stf.Session() as sess:
            for _ in range(5):
                t, lp = sess.run([tok, logp])
                assert set(np.asarray(t).tolist()) <= {3, 7}
                assert np.all(np.asarray(lp) <= 0.0)

    def test_unknown_sampling_knob_rejected(self):
        cfg = tr.TransformerConfig.tiny()
        with pytest.raises(ValueError, match="sampling"):
            tr.build_generative_program(
                cfg, 8, num_slots=2, max_decode_len=6,
                sampling={"nucleus": 0.9})


# ---------------------------------------------------------------------------
# Query-block decode attention (causal_offset)
# ---------------------------------------------------------------------------

class TestBlockDecodeAttentionParity:
    def test_rank4_block_equals_per_position_loop(self):
        B, L, H, D, K = 2, 8, 2, 4, 3
        rng = np.random.RandomState(0)
        q_np = rng.randn(B, K, H, D).astype(np.float32)
        k_np = rng.randn(B, L, H, D).astype(np.float32)
        v_np = rng.randn(B, L, H, D).astype(np.float32)
        len_np = np.array([3, 5], np.int32)   # committed prefix lens
        q4 = stf.placeholder(stf.float32, [B, K, H, D], "q4")
        kc = stf.placeholder(stf.float32, [B, L, H, D], "kc")
        vc = stf.placeholder(stf.float32, [B, L, H, D], "vc")
        ln = stf.placeholder(stf.int32, [B], "ln")
        blk = kvc.decode_attention(q4, kc, vc, ln, causal_offset=True)
        q3 = stf.placeholder(stf.float32, [B, H, D], "q3")
        one = kvc.decode_attention(q3, kc, vc, ln)
        with stf.Session() as sess:
            out_blk = sess.run(blk, {q4: q_np, kc: k_np, vc: v_np,
                                     ln: len_np})
            assert out_blk.shape == (B, K, H, D)
            for j in range(K):
                # block query j sees exactly lengths + j + 1 positions
                ref = sess.run(one, {q3: q_np[:, j], kc: k_np,
                                     vc: v_np, ln: len_np + j + 1})
                np.testing.assert_allclose(out_blk[:, j], ref,
                                           rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Paged causal LM: parity, CoW divergence, engine end-to-end
# ---------------------------------------------------------------------------

PAGE_LEN, PAGES_PER_SEQ, NUM_PAGES, MAX_LIVE = 4, 4, 16, 4


def _clm_model(cfg, **kw):
    kw.setdefault("init_fresh", True)
    return clm.CausalLMGenerativeModel(
        cfg, page_len=PAGE_LEN, pages_per_seq=PAGES_PER_SEQ,
        num_pages=NUM_PAGES, max_live=MAX_LIVE, aot_warmup=False,
        seed=kw.pop("seed", 11), **kw)


def _naive_causal_greedy(sess, ids_ph, logits_t, prompt, steps, pad_id):
    """Full re-forward per emitted token — the reference stream."""
    L = int(ids_ph.shape[1])
    seq = list(prompt)
    out = []
    for _ in range(steps):
        row = np.full((1, L), pad_id, np.int32)
        row[0, :len(seq)] = seq
        logits = sess.run(logits_t, {ids_ph: row})
        tok = int(np.argmax(logits[0, len(seq) - 1]))
        out.append(tok)
        seq.append(tok)
        if len(seq) >= L:
            break
    return out


class TestPagedCausalLM:
    def _naive_handles(self, cfg, ckpt, L):
        g = stf.Graph()
        with g.as_default():
            ids = stf.placeholder(stf.int32, [1, L], "ids")
            logits = clm.causal_lm_logits(ids, cfg, training=False,
                                          compute_dtype=stf.float32)
            sess = stf.Session(graph=g)
            saver = stf.train.Saver()
            saver.restore(sess, ckpt)
        return sess, ids, logits

    def test_engine_matches_naive_reforward_with_shared_prefixes(self):
        cfg = tr.TransformerConfig.tiny()
        tmp = tempfile.mkdtemp(prefix="stf_clm_")
        model = _clm_model(cfg)
        ckpt = _save_ckpt(model, tmp)
        L = model.max_seq_len
        nsess, ids, logits = self._naive_handles(cfg, ckpt, L)
        rng = np.random.RandomState(4)
        shared = list(rng.randint(2, cfg.vocab_size, 6))
        prompts = [shared + list(rng.randint(2, cfg.vocab_size, 3))
                   for _ in range(4)]
        pol = serving.DecodePolicy(num_slots=MAX_LIVE, max_decode_len=L,
                                   bucket_sizes=[1, MAX_LIVE],
                                   max_new_tokens=5)
        with serving.GenerativeEngine("paged_eng", model, pol) as eng:
            futs = [eng.generate(p, max_new_tokens=5) for p in prompts]
            results = [f.result(timeout=120) for f in futs]
            stats = eng.statusz_info()
            drift = eng._prefix.reconcile([])    # all retired: no private
        model.close()
        try:
            for p, r in zip(prompts, results):
                budget = min(5, L - len(p))
                naive = _naive_causal_greedy(nsess, ids, logits, p,
                                             budget, cfg.pad_id)
                got = list(r["tokens"])
                if r["outcome"] == "eos":
                    assert got == naive[:len(got)]
                else:
                    assert got == naive
        finally:
            nsess.close()
        assert drift == 0
        pc = stats["prefix_cache"]
        # 4 prompts sharing a 6-token prefix: later admissions hit the
        # first one's resident chunk
        assert pc["hit_pages"] >= 3

    def test_cow_divergence_bit_exact(self):
        # B's cached span ends INSIDE A's second page: the tail page is
        # built by KVCachePageCopy (copy_pages) of A's page, then B
        # diverges in place — stream must equal a from-scratch decode
        cfg = tr.TransformerConfig.tiny()
        tmp = tempfile.mkdtemp(prefix="stf_cow_")
        model = _clm_model(cfg)
        ckpt = _save_ckpt(model, tmp)
        L = model.max_seq_len
        nsess, ids, logits = self._naive_handles(cfg, ckpt, L)
        rng = np.random.RandomState(9)
        base = list(rng.randint(2, cfg.vocab_size, 9))
        prompt_a = base                       # cached 8 = 2 full pages
        prompt_b = base[:6] + [int(rng.randint(2, cfg.vocab_size))]
        # cached(B) = base[:6] = page [0:4] hit + tail [4:6], a proper
        # prefix of A's second chunk base[4:8] -> CoW
        pol = serving.DecodePolicy(num_slots=MAX_LIVE, max_decode_len=L,
                                   bucket_sizes=[1, MAX_LIVE],
                                   max_new_tokens=4)
        with serving.GenerativeEngine("cow_eng", model, pol) as eng:
            ra = eng.generate(prompt_a, max_new_tokens=4).result(120)
            rb = eng.generate(prompt_b, max_new_tokens=4).result(120)
            pc = eng.statusz_info()["prefix_cache"]
        model.close()
        try:
            for p, r in zip((prompt_a, prompt_b), (ra, rb)):
                naive = _naive_causal_greedy(nsess, ids, logits, p, 4,
                                             cfg.pad_id)
                got = list(r["tokens"])
                if r["outcome"] == "eos":
                    assert got == naive[:len(got)]
                else:
                    assert got == naive
        finally:
            nsess.close()
        assert pc["cow_hits"] == 1
        assert pc["hit_pages"] >= 1

    def test_churn_reconciles_and_rejects_oversize(self):
        cfg = tr.TransformerConfig.tiny()
        model = _clm_model(cfg)
        L = model.max_seq_len
        rng = np.random.RandomState(7)
        shared = list(rng.randint(2, cfg.vocab_size, 4))
        pol = serving.DecodePolicy(num_slots=MAX_LIVE, max_decode_len=L,
                                   bucket_sizes=[1, MAX_LIVE],
                                   max_new_tokens=3)
        with serving.GenerativeEngine("churn_eng", model, pol) as eng:
            # oversize prompt: leaves no decode position
            from simple_tensorflow_tpu.framework import errors
            bad = eng.generate(list(range(2, 2 + L)))
            with pytest.raises(errors.InvalidArgumentError):
                bad.result(timeout=10)
            # 12 requests over 4 live slots / 16 pages
            prompts = [shared + list(rng.randint(2, cfg.vocab_size,
                                                 1 + (i % 4)))
                       for i in range(12)]
            futs = [eng.generate(p, max_new_tokens=3) for p in prompts]
            results = [f.result(timeout=240) for f in futs]
            drift = eng._prefix.reconcile([])
            stats = eng.statusz_info()
        model.close()
        assert drift == 0
        assert all(r["outcome"] in ("eos", "length") for r in results)
        assert all(len(r["tokens"]) >= 1 for r in results)
        assert stats["prefix_cache"]["hit_pages"] > 0

    def test_engine_step_path_never_walks_the_trie(self, monkeypatch):
        # admissions, page faults, retirements and /statusz over a pool
        # that runs dry, with the trie's iterator taken away
        cfg = tr.TransformerConfig.tiny()
        model = _clm_model(cfg)
        L = model.max_seq_len
        rng = np.random.RandomState(36)
        pol = serving.DecodePolicy(num_slots=MAX_LIVE, max_decode_len=L,
                                   bucket_sizes=[1, MAX_LIVE],
                                   max_new_tokens=3)
        with serving.GenerativeEngine("nowalk_eng", model, pol) as eng:
            monkeypatch.setattr(PrefixCache, "_iter_nodes", _no_walk)
            prompts = [list(rng.randint(2, cfg.vocab_size, 6 + (i % 4)))
                       for i in range(12)]
            futs = [eng.generate(p, max_new_tokens=3) for p in prompts]
            results = [f.result(timeout=240) for f in futs]
            stats = eng.statusz_info()["prefix_cache"]
            monkeypatch.undo()
            walked = _walked_count(eng._prefix)
            drift = eng._prefix.reconcile([])
        model.close()
        assert all(r["outcome"] in ("eos", "length") for r in results)
        assert stats["evictions"] > 0 and drift == 0
        assert stats["shared_pages"] == walked
        assert stats["victim_entries"] <= 2 * walked + 17
        gauge = monitoring.export()[
            "/stf/serving/prefix_cache_shared_pages"]["cells"]
        assert walked in [v for k, v in gauge.items() if "nowalk_eng" in k]

    def test_prefix_and_spec_metrics_exported(self):
        exported = monitoring.export()
        for name in ("/stf/serving/prefix_cache_hits",
                     "/stf/serving/prefix_cache_evictions",
                     "/stf/serving/prefix_cache_shared_pages",
                     "/stf/serving/spec_proposed_tokens",
                     "/stf/serving/spec_accepted_tokens",
                     "/stf/serving/spec_acceptance_rate_pct"):
            assert name in exported, name
        hits = exported["/stf/serving/prefix_cache_hits"]["cells"]
        assert any(v > 0 for v in hits.values())


# ---------------------------------------------------------------------------
# Admission hands a prompt's page chunks to the model as the ROWS of one
# prefill_chunk: calls by the model's prefill buckets, rows by base
# ---------------------------------------------------------------------------

def _call_rows(label):
    v = monitoring.get_metric("/stf/serving/prefill_call_rows") \
        .get_cell(label).value()
    return v["count"], v["sum"]


class TestAdmissionPacksRows:
    PAGES = 12

    def _model(self, label, buckets, **kw):
        cfg = dataclasses.replace(tr.TransformerConfig.tiny(),
                                  max_len=PAGE_LEN * self.PAGES)
        kw.setdefault("aot_warmup", False)
        return cfg, clm.CausalLMGenerativeModel(
            cfg, page_len=PAGE_LEN, pages_per_seq=self.PAGES, num_pages=40,
            max_live=MAX_LIVE, prefill_bucket_sizes=buckets,
            init_fresh=True, seed=11, metrics_label=label, **kw)

    def _engine(self, name, model, steps):
        return serving.GenerativeEngine(name, model, serving.DecodePolicy(
            num_slots=MAX_LIVE, max_decode_len=model.max_seq_len,
            bucket_sizes=[1, MAX_LIVE], max_new_tokens=steps))

    def _prompt(self, rng, cfg, chunks, head=()):
        """``chunks`` page chunks of cached span, the last one partial."""
        n = PAGE_LEN * (chunks - 1) + 2 + 1 - len(head)
        return list(head) + list(rng.randint(2, cfg.vocab_size, n))

    def _assert_naive(self, cfg, model, prompts, results, steps):
        ckpt = _save_ckpt(model, tempfile.mkdtemp(prefix="stf_rows_"))
        nsess, ids, logits = TestPagedCausalLM()._naive_handles(
            cfg, ckpt, model.max_seq_len)
        try:
            for p, r in zip(prompts, results):
                naive = _naive_causal_greedy(nsess, ids, logits, p, steps,
                                             cfg.pad_id)
                got = list(r["tokens"])
                assert got == naive[:len(got)]
                assert r["outcome"] == "eos" or got == naive
        finally:
            nsess.close()

    def _one_batch(self, model, name, prompts, steps=3):
        """Serve ``prompts`` admitted in ONE batch: a first answer's
        ``on_token`` holds the engine's thread while they are queued,
        so the next step's joiners are all of them. Returns the answers,
        every ``prefill_chunk`` the batch made as (bases, tables), the
        program calls and rows ``/stf/serving/prefill_call_rows{name}``
        counted for them, and the trie's drift once all have retired."""
        seen, entered, gate = [], threading.Event(), threading.Event()
        chunk = model.prefill_chunk

        def recording(tok, bases, tables, dst):
            seen.append((list(bases), np.asarray(tables)))
            return chunk(tok, bases, tables, dst)

        def hold(tok, lp):
            entered.set()
            assert gate.wait(60)

        model.prefill_chunk = recording
        with self._engine(name, model, steps) as eng:
            first = eng.generate([5, 6, 7], max_new_tokens=2, on_token=hold)
            assert entered.wait(60)
            del seen[:]
            before = _call_rows(name)
            futs = [eng.generate(p, max_new_tokens=steps) for p in prompts]
            gate.set()
            results = [f.result(timeout=120) for f in futs]
            first.result(timeout=120)
            drift = eng._prefix.reconcile([])
            stats = eng.statusz_info()
        counted = tuple(np.subtract(_call_rows(name), before))
        return results, seen, counted, drift, stats

    @pytest.mark.parametrize("chunks, calls", [(7, 1), (11, 2)])
    def test_a_prompt_is_the_rows_of_one_call(self, chunks, calls):
        label = f"rows_{chunks}"
        cfg, model = self._model(label, [1, 2, 4, 8])
        prompt = self._prompt(np.random.RandomState(chunks), cfg, chunks)
        with self._engine(label, model, 4) as eng:
            result = eng.generate(prompt, max_new_tokens=4).result(120)
            row = model.statusz_info()
        assert _call_rows(label) == (calls, chunks)
        assert row["prefill_rows_per_call"] == chunks / calls
        self._assert_naive(cfg, model, [prompt], [result], 4)
        model.close()

    @pytest.mark.parametrize("chunks", [6, 11],
                             ids=["one-call", "two-calls"])
    def test_chunks_as_rows_equal_one_call_a_chunk(self, chunks):
        """Rows j = 0..n-1 of one prefill call may be chunks j of ONE
        prompt: a layer appends every row before any attends, so row j
        reads pages 0..j as n chained bucket-1 calls do. Bucket 8 holds
        6 rows and two pad rows; 11 rows are a call of 8 and a call of
        3 in bucket 4. Bit-equal decode logits."""
        cfg, model = self._model(f"rows_eq_{chunks}", [1, 4, 8])
        body = np.random.RandomState(chunks).randint(
            2, cfg.vocab_size, (chunks, PAGE_LEN)).astype(np.int32)
        bases = PAGE_LEN * np.arange(chunks)
        logits = []
        for pages, one_call in ((np.arange(chunks), False),
                                (20 + np.arange(chunks), True)):
            table = model._scratch_tables(1)
            # one page more: the decode position's
            table[0, :chunks + 1] = list(pages) + [pages[-1] + 1]
            tables = np.repeat(table, chunks, axis=0)
            if one_call:
                assert model.prefill_chunk(body, bases, tables, pages) == \
                    -(-chunks // 8)
            else:
                for j in range(chunks):
                    model.prefill_chunk(body[j:j + 1], bases[j:j + 1],
                                        table, pages[j:j + 1])
            _, p = model._decode_plans[1]
            pos = chunks * PAGE_LEN
            logits.append(model.session.run(p["logits"], {
                p["tok"]: [7], p["pos"]: [pos], p["tables"]: table,
                p["dst"]: table[:, pos // PAGE_LEN], p["off"]: [0],
                p["slots"]: [model.scratch_slot_row]}))
        model.close()
        np.testing.assert_array_equal(logits[1], logits[0])

    def test_shared_prefix_in_one_batch_splits_over_calls(self):
        """B reuses the two pages A fills in the SAME batch; with the
        largest bucket 2 the six rows take three calls, and B's rows
        read A's pages from an earlier call of the same admission."""
        cfg, model = self._model("rows_shared", [1, 2])
        rng = np.random.RandomState(3)
        shared = list(rng.randint(2, cfg.vocab_size, 2 * PAGE_LEN))
        prompts = [self._prompt(rng, cfg, 4, shared),
                   self._prompt(rng, cfg, 4, shared)]
        results, seen, counted, drift, stats = self._one_batch(
            model, "rows_shared", prompts)
        assert len(seen) == 1 and len(seen[0][0]) == 6     # 4 + 2 rows
        assert counted == (3, 6)
        assert drift == 0
        assert stats["prefix_cache"]["hit_pages"] >= 2
        self._assert_naive(cfg, model, prompts, results, 3)
        model.close()

    def test_rows_reach_the_model_ordered_by_base(self):
        cfg, model = self._model("rows_order", [1, 2, 4, 8])
        rng = np.random.RandomState(5)
        prompts = [self._prompt(rng, cfg, c) for c in (3, 5, 2)]
        results, seen, counted, drift, _ = self._one_batch(
            model, "rows_order", prompts)
        (bases, tables), = seen
        assert counted == (2, 10)                          # 8 + 2 rows
        assert bases == sorted(bases) and len(bases) == 10
        assert bases[:4] == [0, 0, 0, PAGE_LEN]
        # a prompt's rows carry its own table: three tables at base 0
        assert len({t.tobytes() for t in tables[:3]}) == 3
        assert drift == 0
        self._assert_naive(cfg, model, prompts, results, 3)
        model.close()

    def test_aot_warmup_runs_every_prefill_bucket_on_scratch_rows(
            self, monkeypatch):
        ran = []
        call = clm.CausalLMGenerativeModel._prefill_call

        def recording(self, pb, tok, bases, tables, dst):
            ran.append((pb, len(dst)))
            return call(self, pb, tok, bases, tables, dst)

        monkeypatch.setattr(clm.CausalLMGenerativeModel, "_prefill_call",
                            recording)
        _, model = self._model("rows_warm", [1, 2, 4], aot_warmup=True)
        assert ran == [(1, 0), (2, 0), (4, 0)]
        assert _call_rows("rows_warm") == (0, 0)
        values = model.session._variable_store.values
        for group in model._prog["caches"]:
            for cache in group:
                pool = np.asarray(values[cache.name])
                assert not pool[:model.scratch_page].any()
                assert pool[model.scratch_page].any()
        model.close()


# ---------------------------------------------------------------------------
# Lint: shared-page host-sink reachability + unguarded verify writes
# ---------------------------------------------------------------------------

class TestDecode2Lint:
    RULE = ["lint/serving-decode-cache"]

    def test_paged_transitive_host_sink_is_error(self):
        c = kvc.kv_cache("lp1", 2, 4, (2,), stf.float32, paged=True)
        g = c.gather(stf.placeholder(stf.int32, [1], "lp1_s"))
        h = stf.reduce_sum(g)                 # one device hop
        stf.Print(h, [h], "leak:")
        diags = analysis.lint_graph(purpose="serving", rules=self.RULE)
        assert any("shared-page" in d.message and
                   d.severity == "error" for d in diags)

    def test_unpaged_transitive_sink_not_flagged(self):
        # the reachability contract is the PAGED tightening; per-slot
        # caches only error on DIRECT host sinks (fetch derived scalars
        # is the documented idiom)
        c = kvc.kv_cache("lp2", 2, 4, (2,), stf.float32)
        g = c.gather(stf.placeholder(stf.int32, [1], "lp2_s"))
        h = stf.reduce_sum(g)
        stf.Print(h, [h], "ok:")
        diags = analysis.lint_graph(purpose="serving", rules=self.RULE)
        assert not diags

    def test_paged_clean_decode_graph_passes(self):
        c = kvc.kv_cache("lp3", 2, 4, (2,), stf.float32, paged=True)
        g = c.gather(stf.placeholder(stf.int32, [1], "lp3_s"))
        _ = stf.reduce_sum(g)
        assert not analysis.lint_graph(purpose="serving",
                                       rules=self.RULE)

    def test_unguarded_verify_write_is_error(self):
        c = kvc.kv_cache("lv1", 2, 4, (2,), stf.float32)
        val = stf.placeholder(stf.float32, [1, 1, 2], "lv1_v")
        s = stf.constant(np.array([0], np.int32))
        c.append(val, s, s, verify_plan=True)   # refcount_guarded=False
        diags = analysis.lint_graph(purpose="serving", rules=self.RULE)
        assert any("refcount-guarded" in d.message and
                   d.severity == "error" for d in diags)

    def test_guarded_verify_write_passes(self):
        c = kvc.kv_cache("lv2", 2, 4, (2,), stf.float32)
        val = stf.placeholder(stf.float32, [1, 1, 2], "lv2_v")
        s = stf.constant(np.array([0], np.int32))
        c.append(val, s, s, verify_plan=True, refcount_guarded=True)
        assert not analysis.lint_graph(purpose="serving",
                                       rules=self.RULE)

    def test_shipped_verify_programs_lint_clean(self):
        # the transformer VERIFY programs stamp their cache writes
        # refcount_guarded=True: the rule must pass the real thing
        cfg = tr.TransformerConfig.tiny()
        model = tr.TransformerGenerativeModel(
            cfg, 8, num_slots=2, max_decode_len=6, init_fresh=True,
            aot_warmup=False, speculative_k=2)
        try:
            with model.graph.as_default():
                diags = analysis.lint_graph(purpose="serving",
                                            rules=self.RULE)
            assert not [d for d in diags if d.severity == "error"]
        finally:
            model.close()
