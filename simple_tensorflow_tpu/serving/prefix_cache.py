"""Shared-prefix prompt cache: refcounted page pool + token-chunk trie.

(ref: vLLM-style prefix caching / RadixAttention, rebuilt host-side for
the stf paged causal-LM serving path.)

Chat and agent workloads resend the same system prompt / few-shot
header in front of every request; re-running prefill over that shared
prefix burns FLOPs recomputing K/V state that is BYTE-IDENTICAL across
requests (K/V at position p depends only on tokens <= p). This module
dedups it at PAGE granularity:

- the device caches are paged: ``(num_pages + 1, page_len, H, hd)``
  per layer (models/causal_lm.py), a sequence's state is its ordered
  page table, attention reads through the page-table gather;
- a trie keyed on FULL ``page_len``-token chunks maps prompt prefixes
  to physical pages. Admission walks the trie: every matched chunk
  reuses the existing page (refcount + 1, ZERO prefill), unmatched full
  chunks prefill into fresh pages that are inserted into the trie for
  the next request;
- partial tail chunks are trie-resident too: the tail gets its own
  (always-leaf) trie node keyed on the partial chunk, so an identical
  tail in a later prompt is a ZERO-work hit. When a trie child's chunk
  EXTENDS the tail (tail is a proper prefix of a full chunk or of a
  longer resident tail), the tail page is built by COPY-ON-WRITE
  (``KVCachePageCopy`` of the child's page) instead of prefill: rows
  ``0..len(tail)-1`` of the copied page are exactly the tail's K/V,
  the rows past it are dead (attention masks by committed length).
  Because the tail page is SHARED, a sequence's first decode append
  into that page copies it out first (engine-side CoW,
  ``generative._step_paged``) — the resident tail stays pristine for
  the next hit;
- retirement walks the sequence's trie chain decrementing refcounts;
  pages at refcount 0 STAY resident (that's the cache) until the free
  list runs dry, then :meth:`PrefixCache._evict_one` reclaims the
  least-recently-touched refs-0 LEAF (leaf-first keeps the trie
  consistent: an inner node's page can't outlive its children's).
- the next victim is KEPT, not searched: a binary heap of
  ``(last_use, n, node)`` holds every evictable node (refs 0, no
  children, resident), pushed at the only places a node becomes
  evictable — :meth:`PrefixCache.release` (a leaf's count reaching 0),
  ``_evict_one`` (the victim's parent left a refs-0 leaf) and
  ``acquire``'s roll-back. Nothing is deleted from the middle: a later
  hit, child or eviction leaves the entry STALE, and an entry is
  checked when popped (still resident, refs 0, childless, ``last_use``
  the entry's). ``last_use`` ticks are unique, so the first valid
  unpinned entry IS the node a walk of the whole trie would choose. A
  pinned victim is skipped and put back. Where nothing is evicted (a
  hot prefix hit and released with free pages left) stale entries are
  swept once the heap holds more than twice the resident nodes, so it
  stays bounded; ``shared_pages`` is a count kept at insert and
  remove. No call of the engine's step path iterates the trie
  (``_iter_nodes`` is ``reconcile``'s and the tests').

A model with STATE OUTSIDE ITS PAGES (a recurrent layer's, addressed by
slot: ``models/state_space_moe_lm.py``) cannot take a trie hit: the hit
hands over pages of K/V whose recurrent state nobody kept. For such a
model the engine builds the cache with ``share=False``: ``acquire``
matches nothing, inserts nothing and hands out fresh pages that the
sequence owns and frees at retirement (``AdmitPlan.node`` is None); the
pool, the free list and ``reconcile`` are the same. Prefix reuse by state
snapshot is open (ROADMAP X6).

Single-threaded by design: the engine's scheduler thread owns the
instance (same ownership contract as ``generative.CacheSlotPool``).
:meth:`PrefixCache.reconcile` cross-checks the three page populations
(free list, trie-resident, sequence-private) against the pool size —
the churn fuzz test drives 12 requests through admit/retire/evict and
asserts drift stays 0.
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class PagesExhaustedError(RuntimeError):
    """No free page and nothing evictable (every page is referenced by
    a live sequence or privately owned). The engine holds the request
    back and re-tries admission after the next retirement."""


class _TrieNode:
    __slots__ = ("chunk", "page", "refs", "children", "parent",
                 "last_use")

    def __init__(self, chunk: Optional[Tuple[int, ...]],
                 page: Optional[int], parent: "Optional[_TrieNode]"):
        self.chunk = chunk
        self.page = page
        self.refs = 0
        self.children: Dict[Tuple[int, ...], "_TrieNode"] = {}
        self.parent = parent
        self.last_use = 0


class AdmitPlan:
    """One admission's resolved page program (see
    :meth:`PrefixCache.acquire`): everything the engine must DO is in
    ``fill`` (prefill these chunks into these pages) and ``cow_src``
    (copy that page into ``tail_page`` first); everything already done
    is in ``reused_pages`` and — when ``tail_ready`` — the tail page
    itself (an exact trie hit on the partial chunk: no prefill, no
    copy)."""

    __slots__ = ("reused_pages", "fill", "tail", "tail_page", "cow_src",
                 "node", "cached_len", "tail_ready")

    def __init__(self, reused_pages, fill, tail, tail_page, cow_src,
                 node, cached_len, tail_ready=False):
        self.reused_pages: List[int] = reused_pages
        self.fill: List[Tuple[int, np.ndarray, int]] = fill
        self.tail: np.ndarray = tail
        self.tail_page: Optional[int] = tail_page
        self.cow_src: Optional[int] = cow_src
        self.node: _TrieNode = node
        self.cached_len: int = cached_len
        self.tail_ready: bool = tail_ready

    @property
    def pages(self) -> List[int]:
        """The page-table prefix, in sequence order."""
        out = list(self.reused_pages) + [pg for pg, _, _ in self.fill]
        if self.tail_page is not None:
            out.append(self.tail_page)
        return out


class PrefixCache:
    """Refcounted page pool + shared-prefix trie (module docstring)."""

    def __init__(self, num_pages: int, page_len: int, share: bool = True):
        self.num_pages = int(num_pages)
        self.page_len = int(page_len)
        # False: every admission gets fresh pages of its own (module
        # docstring, "state outside its pages")
        self.share = bool(share)
        self._free: List[int] = list(range(self.num_pages))[::-1]
        self._root = _TrieNode(None, None, None)
        self._tick = 0
        self._resident = 0        # trie nodes (a page each)
        # evictable nodes by last_use, entries checked when popped
        # (module docstring); a removed node's parent is None
        self._victims: List[Tuple[int, int, _TrieNode]] = []
        self._pushes = 0
        self.stale_discarded = 0
        # counters the engine maps into /stf/serving/prefix_cache_*
        self.hit_pages = 0        # full chunks served with zero prefill
        self.cow_hits = 0         # tails served by page copy, not prefill
        self.miss_pages = 0       # full chunks that had to prefill
        self.evictions = 0

    # -- introspection -------------------------------------------------------
    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def shared_pages(self) -> int:
        """Trie-resident page count (refs > 0 or cached at refs 0)."""
        return self._resident

    def _iter_nodes(self):
        stack = list(self._root.children.values())
        while stack:
            n = stack.pop()
            yield n
            stack.extend(n.children.values())

    # -- page pool -----------------------------------------------------------
    def _touch(self, node: _TrieNode):
        self._tick += 1
        node.last_use = self._tick

    def alloc_page(self, _pin: Optional[set] = None) -> int:
        """Take a page off the free list, evicting a refs-0 trie leaf
        if it is dry. Raises :class:`PagesExhaustedError` when every
        page is live."""
        if not self._free:
            self._evict_one(_pin or set())
        return self._free.pop()

    def free_page(self, page: int):
        self._free.append(page)

    def _insert(self, chunk, page: int, parent: _TrieNode) -> _TrieNode:
        node = _TrieNode(chunk, page, parent)
        node.refs = 1
        self._touch(node)
        parent.children[chunk] = node
        self._resident += 1
        return node

    def _remove(self, node: _TrieNode):
        del node.parent.children[node.chunk]
        node.parent = None
        self._resident -= 1

    @staticmethod
    def _evictable(node: _TrieNode) -> bool:
        return (node.parent is not None and node.refs == 0
                and not node.children)

    def _stale(self, entry) -> bool:
        """The entry's node was hit again, got a child or left the trie
        since it was queued."""
        return entry[2].last_use != entry[0] or \
            not self._evictable(entry[2])

    def _offer(self, node: _TrieNode):
        """``node`` may have become evictable: queue it if so."""
        if not self._evictable(node):       # the root's parent is None
            return
        heap = self._victims
        if len(heap) > 2 * self._resident + 16:
            # more stale than live (hits and releases with nothing
            # evicted): sweep, so the heap is bounded by the trie
            live = [e for e in heap if not self._stale(e)]
            self.stale_discarded += len(heap) - len(live)
            heap[:] = live
            heapq.heapify(heap)
        self._pushes += 1
        heapq.heappush(heap, (node.last_use, self._pushes, node))

    def _evict_one(self, pin: set) -> _TrieNode:
        """Evict the least-recently-touched refs-0 leaf whose page is
        not in ``pin``; returns it."""
        heap = self._victims
        pinned = []
        victim = None
        while heap:
            entry = heapq.heappop(heap)
            if self._stale(entry):
                self.stale_discarded += 1
            elif entry[2].page in pin:
                pinned.append(entry)
            else:
                victim = entry[2]
                break
        for entry in pinned:
            heapq.heappush(heap, entry)
        if victim is None:
            raise PagesExhaustedError(
                f"all {self.num_pages} pages live (no refs-0 leaf to "
                "evict)")
        parent = victim.parent
        self._remove(victim)
        self._offer(parent)
        self._free.append(victim.page)
        self.evictions += 1
        return victim

    # -- admission / retirement ----------------------------------------------
    def acquire(self, cached_tokens: Sequence[int]) -> AdmitPlan:
        """Resolve the page program for one admission.

        ``cached_tokens`` is the prompt span the engine caches —
        ``prompt[:-1]`` (the final prompt token is fed through the
        first decode step, which produces the first emitted token).
        Matched full chunks are refcounted in place; unmatched full
        chunks get fresh pages AND trie nodes (refs=1, shareable by the
        next request before this one even retires); a partial tail is
        trie-resident too — an exact partial-chunk hit reuses the node
        with ZERO work (``tail_ready``), otherwise a fresh page + leaf
        node are inserted and populated by CoW when a resident chunk
        extends the tail, by prefill when none does. On allocation
        failure everything is rolled back and
        :class:`PagesExhaustedError` propagates."""
        toks = [int(t) for t in cached_tokens]
        pl = self.page_len
        n_full = len(toks) // pl
        tail = np.asarray(toks[n_full * pl:], np.int32)
        if not self.share:
            return self._acquire_unshared(toks, n_full, tail)

        node = self._root
        reused: List[int] = []
        matched: List[_TrieNode] = []
        i = 0
        while i < n_full:
            chunk = tuple(toks[i * pl:(i + 1) * pl])
            child = node.children.get(chunk)
            if child is None:
                break
            child.refs += 1
            self._touch(child)
            matched.append(child)
            reused.append(child.page)
            node = child
            i += 1
        self.hit_pages += len(reused)

        fill: List[Tuple[int, np.ndarray, int]] = []
        inserted: List[_TrieNode] = []
        allocated: List[int] = []
        pin = set(reused)

        def _rollback():
            for nd in inserted:
                self._remove(nd)
            for m in matched:
                m.refs -= 1
                self._offer(m)
            for pg in allocated:
                self._free.append(pg)

        try:
            while i < n_full:
                chunk = tuple(toks[i * pl:(i + 1) * pl])
                pg = self.alloc_page(pin)
                allocated.append(pg)
                pin.add(pg)
                child = self._insert(chunk, pg, node)
                inserted.append(child)
                fill.append((pg, np.asarray(chunk, np.int32), i * pl))
                self.miss_pages += 1
                node = child
                i += 1

            tail_page = None
            cow_src = None
            tail_ready = False
            if len(tail):
                tkey = tuple(int(t) for t in tail)
                exact = node.children.get(tkey)
                if exact is not None:
                    # exact partial-chunk hit: the resident tail page
                    # already holds these rows — zero prefill, zero copy
                    exact.refs += 1
                    self._touch(exact)
                    matched.append(exact)
                    tail_page = exact.page
                    tail_ready = True
                    self.hit_pages += 1
                    node = exact
                else:
                    # CoW probe: a resident chunk (full, or a longer
                    # partial tail) that EXTENDS this tail already holds
                    # its K/V rows
                    for chunk, child in node.children.items():
                        if len(chunk) > len(tkey) and \
                                chunk[:len(tkey)] == tkey:
                            cow_src = child.page
                            break
                    if cow_src is not None:
                        pin.add(cow_src)
                    tail_page = self.alloc_page(pin)
                    allocated.append(tail_page)
                    tail_node = self._insert(tkey, tail_page, node)
                    inserted.append(tail_node)
                    if cow_src is not None:
                        self.cow_hits += 1
                    else:
                        self.miss_pages += 1
                    node = tail_node
        except PagesExhaustedError:
            _rollback()
            raise
        return AdmitPlan(reused, fill, tail, tail_page, cow_src, node,
                         len(toks), tail_ready=tail_ready)

    def _acquire_unshared(self, toks, n_full, tail) -> AdmitPlan:
        """Fresh pages for every chunk and the tail, in no trie: the
        sequence owns them (``node`` None) and frees them when it
        retires."""
        pl = self.page_len
        pages: List[int] = []
        try:
            for _ in range(n_full + bool(len(tail))):
                pages.append(self.alloc_page())
        except PagesExhaustedError:
            self._free.extend(pages)
            raise
        fill = [(pages[i], np.asarray(toks[i * pl:(i + 1) * pl], np.int32),
                 i * pl) for i in range(n_full)]
        self.miss_pages += len(pages)
        return AdmitPlan([], fill, tail, pages[n_full] if len(tail) else None,
                         None, None, len(toks))

    def release(self, node: Optional[_TrieNode]):
        """Retire one sequence's hold on its trie chain (deepest node
        first; pages stay cached at refs 0 until evicted)."""
        while node is not None and node is not self._root:
            node.refs -= 1
            assert node.refs >= 0, "prefix-cache refcount underflow"
            self._offer(node)
            node = node.parent

    # -- invariant check -----------------------------------------------------
    def reconcile(self, private_pages: Sequence[int]) -> int:
        """Cross-check the three page populations. Returns the drift
        (0 when consistent): every page is in exactly one of {free
        list, trie, private}, and they sum to ``num_pages``."""
        free = list(self._free)
        trie = [n.page for n in self._iter_nodes()]
        private = list(private_pages)
        drift = 0
        allp = free + trie + private
        drift += len(allp) - len(set(allp))          # double-owned
        drift += abs(len(allp) - self.num_pages)     # leaked / lost
        drift += sum(1 for p in allp
                     if not 0 <= p < self.num_pages)  # out of range
        return drift

    def statusz_info(self):
        return {"num_pages": self.num_pages, "page_len": self.page_len,
                "free": self.free_count,
                "shared_pages": self.shared_pages,
                "hit_pages": self.hit_pages, "cow_hits": self.cow_hits,
                "miss_pages": self.miss_pages,
                "evictions": self.evictions,
                "victim_entries": len(self._victims),
                "stale_discarded": self.stale_discarded}
