"""Token-level continuous batching: the stf.serving generative engine.

(ref: tensorflow_serving batches per REQUEST — a generative workload
decodes hundreds of steps per request, so request-level batching either
serializes sequences or pads every batch to the slowest member. This
engine schedules per TOKEN, the continuous-batching design of modern
LLM servers, on top of the PR 7 batching machinery.)

One :class:`GenerativeEngine` owns one decode-capable model (e.g.
``models.transformer.TransformerGenerativeModel``) and runs a single
scheduler thread:

- requests enqueue on the same bounded admission RingBuffer the
  request batcher uses (backpressure, deadlines, close semantics);
- a joining request takes a CACHE SLOT from the free-list, pays one
  PREFILL (encoder forward + cross-K/V projection scattered into its
  slot's cache rows), and rides the next decode step — mid-decode, no
  barrier with the sequences already running;
- every engine step runs ONE decode program over the live set, bucketed
  to the smallest :class:`~.policy.DecodePolicy` bucket (padding rows
  target the model's scratch slot, never a live cache row);
- a sequence RETIRES the step it emits EOS, exhausts its token budget,
  or blows its deadline — its slot returns to the free-list and the
  batch keeps going without it. Deadlines are re-checked every token.
  The paged path enqueues step k + 1 before it fetches step k (the
  device keeps each slot's last token, so step k + 1 needs no token
  from the host), so there an EOS is seen one step late and that step's
  token for it is dropped; budgets are exact (``_step_paged``).

Because the decode program is static per bucket and every row reads
only its own slot's cache, a sequence's tokens are BIT-IDENTICAL
whether it decodes alone or rides a churning batch (pinned by
tests/test_generative.py).

Model interface (duck-typed): ``prefill(src_rows, slots)``,
``decode(tokens, positions, slots) -> (next_tok, logp, bucket)``,
``close()``, attrs ``eos_id / pad_id / num_slots / max_decode_len /
src_len``. A paged model (``page_len``) gives ``prefill_chunk`` and
``copy_page`` instead of ``prefill``, and ``decode_async(tokens,
positions, page_tables, slots=)`` -> a handle with ``bucket``, whose
``collect(handle)`` -> ``(next_tok, logp, bucket)``; a token < 0 is the
one the device holds for the slot.

Two decode-throughput extensions ride the same scheduler loop:

- SPECULATIVE DECODING (``draft=`` model): each engine step runs the
  draft model ``draft_steps`` greedy positions ahead in ONE dispatch
  (``decode_k``), then the target re-scores the ``spec_k``-token block
  in ONE batched pass (``verify``, query-block DecodeAttention) and
  commits the longest prefix of draft proposals that MATCH the
  target's own choices, plus one bonus target token. Every emitted
  token is the target's own pick, so greedy output is token-exact vs
  plain decode; per step a sequence advances 1..spec_k tokens for two
  dispatches instead of up to spec_k.

- SHARED-PREFIX PROMPT CACHE (paged models, e.g.
  ``models.causal_lm.CausalLMGenerativeModel``): admission consults a
  prefix trie keyed on page-sized token chunks
  (serving/prefix_cache.py) — matched prompt chunks reuse refcounted
  shared cache pages with ZERO prefill, divergence inside a page is
  copy-on-write, and retirement decrefs the chain (pages stay cached
  at refs 0 until LRU eviction). Admissions that run out of pages
  hold back and retry after the next retirement.

- STATE OUTSIDE THE PAGES (a paged model that declares
  ``state_outside_pages``, e.g.
  ``models.state_space_moe_lm.StateSpaceMoEGenerativeModel``): beside
  its K/V pages a sequence has recurrent state in pools addressed by
  SLOT. The engine has a slot for every sequence and tells such a model:
  ``prefill_chunk(..., slots=, lens=)`` — each page-chunk row's slot and
  REAL token count (pad tokens must not run a recurrence on) — and
  ``decode(..., slots=)``. Rows go in order of ``(base, slot)`` as for
  every paged model, so a slot's rows come in order of ``base`` and the
  model walks them in the order given. Such a model gets no prefix hit
  (``PrefixCache(share=False)``: fresh pages, freed at retirement) and
  no ``draft=``. The model's declaration decides: there is no knob.

Metrics: the ``/stf/serving/decode_*`` / ``prefix_cache_*`` /
``spec_*`` families (docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import threading
import time
import weakref
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from ..data.pipeline import _DONE, RingBuffer
from ..framework import errors
from ..platform import monitoring
from ..telemetry import recorder as _flight_mod
from ..telemetry import tracing as _req_tracing
from .batcher import _QueueStats, record_queue_wait

# ---------------------------------------------------------------------------
# metrics (process-global; registration is idempotent)
# ---------------------------------------------------------------------------

_metric_tokens = monitoring.Counter(
    "/stf/serving/decode_tokens",
    "Tokens emitted by the generative engine", "model")
_metric_tokens_per_sec = monitoring.IntGauge(
    "/stf/serving/decode_tokens_per_sec",
    "Tokens emitted per second over a trailing 10 s window", "model")
_metric_step_seconds = monitoring.Sampler(
    "/stf/serving/decode_step_seconds",
    monitoring.ExponentialBuckets(1e-5, 2.0, 22),
    "Per-engine-step seconds (one decode position for every live "
    "sequence)", "model")
_metric_per_token = monitoring.Sampler(
    "/stf/serving/decode_per_token_seconds",
    monitoring.ExponentialBuckets(1e-5, 2.0, 22),
    "Per-sequence seconds per emitted token (prefill done -> "
    "retirement, / tokens)", "model")
_metric_prefill_seconds = monitoring.Sampler(
    "/stf/serving/decode_prefill_seconds",
    monitoring.ExponentialBuckets(1e-5, 2.0, 22),
    "Seconds encoding joining prompts into their cache slots", "model")
_metric_overlap = monitoring.Sampler(
    "/stf/serving/decode_overlap",
    monitoring.ExplicitBuckets([0.5]),
    "Per decode step of the paged path: 1 when it was enqueued while the "
    "engine's previous decode step was still unfetched, else 0 (the "
    "mean is the share of steps the host prepared behind the device)",
    "model")
_metric_fill = monitoring.Sampler(
    "/stf/serving/decode_fill",
    monitoring.ExplicitBuckets(
        [0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0]),
    "Live-sequence fraction of the decode bucket each engine step ran "
    "at (1.0 = no padding waste)", "model")
_metric_slots = monitoring.IntGauge(
    "/stf/serving/decode_slots_active",
    "Cache slots currently owned by live sequences", "model")
_metric_sequences = monitoring.Counter(
    "/stf/serving/decode_sequences",
    "Generative sequences finished, by outcome (eos | length | "
    "deadline_exceeded | error | cancelled | rejected)", "model",
    "outcome")
_metric_prefix_hits = monitoring.Counter(
    "/stf/serving/prefix_cache_hits",
    "Prompt pages served from the shared-prefix cache (full-chunk trie "
    "hits + copy-on-write tails) — each is one page of prefill FLOPs "
    "avoided", "model")
_metric_prefix_evictions = monitoring.Counter(
    "/stf/serving/prefix_cache_evictions",
    "Refs-0 prefix-cache pages reclaimed by LRU eviction to satisfy an "
    "allocation", "model")
_metric_prefix_shared = monitoring.IntGauge(
    "/stf/serving/prefix_cache_shared_pages",
    "Cache pages currently resident in the shared-prefix trie "
    "(referenced or cached at refs 0)", "model")
_metric_spec_proposed = monitoring.Counter(
    "/stf/serving/spec_proposed_tokens",
    "Draft-model tokens proposed to speculative verification", "model")
_metric_spec_accepted = monitoring.Counter(
    "/stf/serving/spec_accepted_tokens",
    "Draft proposals accepted (matched the target's own choice)",
    "model")
_metric_spec_acceptance = monitoring.IntGauge(
    "/stf/serving/spec_acceptance_rate_pct",
    "Lifetime speculative acceptance rate, percent "
    "(accepted / proposed)", "model")
_metric_tp_degree = monitoring.IntGauge(
    "/stf/serving/tp_degree",
    "Decode tensor-parallel degree the model was built at (1 = "
    "single-device decode)", "model")
_metric_tp_cache_bytes = monitoring.IntGauge(
    "/stf/serving/tp_cache_bytes_per_device",
    "Per-device KV-cache bytes under the committed decode-TP layout "
    "(the replicated footprint divided over the tp axis)", "model")
_metric_tp_collective = monitoring.IntGauge(
    "/stf/serving/tp_collective_bytes_per_token",
    "Predicted per-token collective bytes of the decode-TP layout "
    "(embedding all-reduce + per-sublayer context all-gathers + the "
    "logits all-gather; 0 at tp=1)", "model")

# every constructed GenerativeEngine, while alive (test leak hygiene:
# tests/conftest.py asserts these are all closed after each module)
live_engines: "weakref.WeakSet" = weakref.WeakSet()


class CacheSlotPool:
    """Free-list over the model's cache slots (pages). Single-threaded
    (the engine thread owns it); exists as a class so tests can pin
    reuse behavior."""

    def __init__(self, num_slots: int):
        self._free: List[int] = list(range(num_slots))[::-1]
        self.num_slots = num_slots

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def active_count(self) -> int:
        return self.num_slots - len(self._free)

    def acquire(self) -> Optional[int]:
        return self._free.pop() if self._free else None

    def release(self, slot: int) -> None:
        self._free.append(slot)


class GenerateFuture:
    """Async handle for one generative request. ``result()`` blocks for
    the full sequence: ``{"tokens", "logprobs", "outcome"}``; streaming
    consumers pass ``on_token`` to :meth:`GenerativeEngine.generate`
    instead (called from the engine thread per emitted token)."""

    __slots__ = ("_event", "_result", "_exc", "_model", "trace_id")

    def __init__(self, model: str, trace_id: Optional[str] = None):
        self._event = threading.Event()
        self._result: Optional[Dict[str, Any]] = None
        self._exc: Optional[BaseException] = None
        self._model = model
        self.trace_id = trace_id

    def _set_result(self, result: Dict[str, Any]):
        self._result = result
        self._event.set()

    def _set_exception(self, exc: BaseException):
        self._exc = exc
        self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def exception(self, timeout: Optional[float] = None):
        if not self._event.wait(timeout):
            raise errors.DeadlineExceededError(
                None, None,
                f"generation for model {self._model!r} not done within "
                f"{timeout}s")
        return self._exc

    def result(self, timeout: Optional[float] = None) -> Dict[str, Any]:
        exc = self.exception(timeout)
        if exc is not None:
            raise exc
        return self._result

    def __repr__(self):
        state = ("pending" if not self.done()
                 else "failed" if self._exc is not None else "done")
        return f"<GenerateFuture {self._model} {state}>"


class GenerateRequest:
    __slots__ = ("src", "max_new_tokens", "future", "deadline",
                 "on_token", "t_enqueue", "trace_id")

    def __init__(self, src, max_new_tokens, future,
                 deadline: Optional[float] = None,
                 on_token: Optional[Callable[[int, float], None]] = None,
                 trace_id: Optional[str] = None):
        self.src = src
        self.max_new_tokens = max_new_tokens
        self.future = future
        self.deadline = deadline
        self.on_token = on_token
        self.t_enqueue = time.perf_counter()
        self.trace_id = trace_id

    def expired(self, now: Optional[float] = None) -> bool:
        return self.deadline is not None and \
            (now if now is not None else time.perf_counter()) > self.deadline


class _Sequence:
    """One live decoding sequence: its slot, emission state, budget.
    On the paged (prefix-cache) path it also carries its page table,
    its deepest trie node (released at retirement), and the private
    pages it owns (tail + decode pages, freed at retirement)."""

    __slots__ = ("req", "slot", "tokens", "logps", "pos", "last_tok",
                 "budget", "t_start", "pages", "node", "private",
                 "cow_blk", "asked", "done")

    def __init__(self, req: GenerateRequest, slot: int, first_tok: int,
                 budget: int):
        self.req = req
        self.slot = slot
        self.tokens: List[int] = []
        self.logps: List[float] = []
        self.pos = 0
        self.last_tok = first_tok
        self.budget = budget
        self.t_start = time.perf_counter()
        self.pages: Optional[np.ndarray] = None
        self.node = None
        self.private: List[int] = []
        # page-table block holding the trie-resident (shared) tail
        # page: the first decode append into it copies-on-write
        self.cow_blk: Optional[int] = None
        # decode steps enqueued for it whose tokens are not yet delivered
        # (``pos`` is the position the NEXT step feeds), and whether it
        # has retired — a step already enqueued may still carry it
        self.asked = 0
        self.done = False


class _Enqueued:
    """The paged path's decode step in flight: the model's handle, the
    sequences that ride it in row order, and when it was enqueued."""

    __slots__ = ("call", "rows", "t0")

    def __init__(self, call, rows, t0):
        self.call, self.rows, self.t0 = call, rows, t0


class GenerativeEngine:
    """Scheduler thread + slot pool for one generative model (see the
    module docstring). Constructed by ``ModelServer.load_generative``;
    usable standalone (tests, bench)."""

    def __init__(self, name: str, model, policy, draft=None):
        self.name = name
        self._model = model
        self._policy = policy
        self._draft = draft
        self._spec_enabled = (draft is not None
                              and getattr(policy, "speculative", True))
        # paged models (page_len attr) route through the shared-prefix
        # prompt cache; slot models through per-sequence cache rows
        self._paged = getattr(model, "page_len", None) is not None
        # recurrent state in pools addressed by slot, beside the pages
        self._by_slot = bool(getattr(model, "state_outside_pages", False))
        if draft is not None and self._by_slot:
            raise ValueError(
                f"model {name!r} keeps recurrent state outside its cache "
                "pages: a draft's rejected proposals would have advanced "
                "that state and it cannot be rolled back, so speculative "
                "decoding (draft=) is not supported for it")
        self._prefix = None
        self._holdback: List[GenerateRequest] = []
        if self._paged and getattr(policy, "use_prefix_cache", True):
            from .prefix_cache import PrefixCache

            self._prefix = PrefixCache(model.num_pages, model.page_len,
                                       share=not self._by_slot)
        elif self._paged:
            raise ValueError(
                "paged models require the prefix cache "
                "(DecodePolicy.use_prefix_cache=False unsupported)")
        if self._spec_enabled:
            if self._paged:
                raise ValueError(
                    "speculative decoding is not supported on the "
                    "paged (prefix-cache) path")
            spec_k = getattr(model, "spec_k", 0)
            kd = getattr(draft, "draft_steps", 0)
            if spec_k < 2 or kd < 1:
                raise ValueError(
                    f"speculative decoding needs a target built with "
                    f"speculative_k >= 2 (got {spec_k}) and a draft "
                    f"built with draft_steps >= 1 (got {kd})")
            if spec_k != kd + 1:
                raise ValueError(
                    f"target speculative_k={spec_k} must equal draft "
                    f"draft_steps+1={kd + 1} (one bonus target token "
                    "per verified block)")
            for attr in ("src_len", "eos_id", "pad_id"):
                if getattr(draft, attr) != getattr(model, attr):
                    raise ValueError(
                        f"draft/target {attr} mismatch: "
                        f"{getattr(draft, attr)} != "
                        f"{getattr(model, attr)}")
            if draft.num_slots < policy.num_slots:
                raise ValueError(
                    f"draft has {draft.num_slots} slots < "
                    f"policy.num_slots={policy.num_slots}")
            if draft.max_decode_len < model.max_decode_len:
                raise ValueError(
                    f"draft max_decode_len={draft.max_decode_len} < "
                    f"target's {model.max_decode_len}")
        if policy.num_slots > model.num_slots:
            raise ValueError(
                f"policy.num_slots={policy.num_slots} exceeds the "
                f"model's {model.num_slots} cache slots")
        # the POLICY owns bucketing (bucket_for, per token): when the
        # model declares which decode buckets it compiled plans for,
        # every policy bucket must have one — a silent mismatch would
        # re-bucket inside the model and make DecodePolicy.bucket_sizes
        # a dead knob
        model_buckets = getattr(model, "decode_buckets", None)
        self._scratch_slot = getattr(model, "scratch_slot", None)
        if model_buckets is not None:
            missing = [b for b in policy.bucket_sizes
                       if b not in model_buckets]
            if missing:
                raise ValueError(
                    f"DecodePolicy.bucket_sizes {policy.bucket_sizes} "
                    f"include buckets the model has no decode plan for "
                    f"({missing}; model compiled {model_buckets}); "
                    "align decode_bucket_sizes at model build with the "
                    "policy")
        # device-memory admission (stf.telemetry.memory): a model whose
        # resident footprint (weights + cache pages, already ledgered
        # under its store owner) exceeds the session's budget is
        # refused here — before the scheduler thread ever starts
        msess = getattr(model, "session", None)
        if msess is not None and getattr(msess, "_memory_budget", 0):
            from ..telemetry import memory as _memory_mod

            _memory_mod.check_budget(
                msess._memory_budget, 0, "generative_engine",
                owner=msess._variable_store.owner,
                detail=f"engine {name!r}: {policy.num_slots} slots")
        self._pool = CacheSlotPool(policy.num_slots)
        self._queue = RingBuffer(policy.max_queue_depth,
                                 stats=_QueueStats(name))
        self._active: List[_Sequence] = []
        self._rate = monitoring.WindowedRate(10.0)
        self._rate_gauge = _metric_tokens_per_sec.get_cell(name)
        self._tokens = _metric_tokens.get_cell(name)
        self._step_s = _metric_step_seconds.get_cell(name)
        self._prefill_s = _metric_prefill_seconds.get_cell(name)
        self._fill = _metric_fill.get_cell(name)
        self._overlap = _metric_overlap.get_cell(name)
        self._slots_gauge = _metric_slots.get_cell(name)
        self._per_token = _metric_per_token.get_cell(name)
        self._prefix_hits = _metric_prefix_hits.get_cell(name)
        self._prefix_evictions = _metric_prefix_evictions.get_cell(name)
        self._prefix_shared = _metric_prefix_shared.get_cell(name)
        self._spec_proposed = _metric_spec_proposed.get_cell(name)
        self._spec_accepted = _metric_spec_accepted.get_cell(name)
        self._spec_acceptance = _metric_spec_acceptance.get_cell(name)
        # decode-TP telemetry: models built over a mesh report their
        # committed layout facts once (gauges; the layout is static)
        tp_info = getattr(model, "tp_info", None)
        self._tp_info = tp_info() if callable(tp_info) else None
        if self._tp_info is not None:
            _metric_tp_degree.get_cell(name).set(
                int(self._tp_info["tp_degree"]))
            _metric_tp_cache_bytes.get_cell(name).set(
                int(self._tp_info["cache_bytes_per_device"]))
            _metric_tp_collective.get_cell(name).set(
                int(self._tp_info["per_token_collective_bytes"]))
        self._spec_counts = [0, 0]        # lifetime [proposed, accepted]
        # the paged path's decode step enqueued and not yet fetched, and
        # when the last step was fetched
        self._inflight: Optional[_Enqueued] = None
        self._t_collect = 0.0
        self._prefix_seen = [0, 0]        # last synced [hits, evictions]
        self._closed = False
        self._thread = threading.Thread(
            target=self._loop, name=f"stf_serving_decode_{name}",
            daemon=True)
        self._thread.start()
        live_engines.add(self)

    # -- submission ----------------------------------------------------------
    @property
    def closed(self) -> bool:
        return self._closed

    def queue_depth(self) -> int:
        return len(self._queue)

    def active_count(self) -> int:
        return len(self._active)

    def refresh_rate(self) -> int:
        rate = int(self._rate.rate())
        self._rate_gauge.set(rate)
        return rate

    def generate(self, src, max_new_tokens: Optional[int] = None,
                 timeout_ms: Optional[float] = None,
                 on_token: Optional[Callable[[int, float], None]] = None,
                 trace_id: Optional[str] = None) -> GenerateFuture:
        """Submit one prompt. ``src``: (src_len,) int32 token row
        (shorter rows pad with the model's pad id). ``on_token(token,
        logprob)`` streams from the engine thread. Returns a
        :class:`GenerateFuture`."""
        from .. import telemetry

        if trace_id is None:
            trace_id = telemetry.current_trace_id() or \
                telemetry.new_trace_id()
        fut = GenerateFuture(self.name, trace_id=trace_id)
        src = np.asarray(src, np.int32).reshape(-1)
        if self._paged:
            # prompt rides unpadded (the page program is sized per
            # request); it must leave at least one decode position
            limit = self._model.max_seq_len - 1
            if not 1 <= len(src) <= limit:
                fut._set_exception(errors.InvalidArgumentError(
                    None, None,
                    f"prompt length {len(src)} outside [1, {limit}] "
                    f"(max_seq_len {self._model.max_seq_len} minus one "
                    "decode position)"))
                _metric_sequences.get_cell(
                    self.name, "rejected").increase_by(1)
                return fut
            row = src
        else:
            if len(src) > self._model.src_len:
                fut._set_exception(errors.InvalidArgumentError(
                    None, None,
                    f"prompt length {len(src)} exceeds the model's "
                    f"src_len {self._model.src_len}"))
                _metric_sequences.get_cell(
                    self.name, "rejected").increase_by(1)
                return fut
            row = np.full((self._model.src_len,), self._model.pad_id,
                          np.int32)
            row[:len(src)] = src
        if timeout_ms is None and self._policy.default_timeout_ms > 0:
            timeout_ms = self._policy.default_timeout_ms
        deadline = (time.perf_counter() + float(timeout_ms) / 1000.0
                    if timeout_ms else None)
        if max_new_tokens is None:
            max_new_tokens = self._policy.max_new_tokens
        if int(max_new_tokens) < 0:
            fut._set_exception(errors.InvalidArgumentError(
                None, None,
                f"max_new_tokens must be >= 0, got {max_new_tokens}"))
            _metric_sequences.get_cell(self.name, "rejected").increase_by(1)
            return fut
        budget = min(int(max_new_tokens), self._model.max_decode_len)
        if self._paged:
            # emitted tokens occupy positions len(src)..max_seq_len-1
            budget = min(budget, self._model.max_seq_len - len(src))
        if budget == 0:
            # a zero budget never needs a slot or a prefill
            fut._set_result({"tokens": np.zeros(0, np.int32),
                             "logprobs": np.zeros(0, np.float32),
                             "outcome": "length"})
            _metric_sequences.get_cell(self.name, "length").increase_by(1)
            return fut
        req = GenerateRequest(row, budget, fut, deadline,
                              on_token=on_token, trace_id=trace_id)
        if self._closed:
            self._reject(req, "cancelled", errors.UnavailableError(
                None, None, f"model {self.name!r}: engine is shut down"))
            return fut
        timeout = None
        if deadline is not None:
            timeout = max(deadline - time.perf_counter(), 0.0)
        if not self._queue.put(req, timeout=timeout):
            if self._queue.closed:
                self._reject(req, "cancelled", errors.UnavailableError(
                    None, None,
                    f"model {self.name!r}: engine is shut down"))
            else:
                self._reject(req, "rejected", errors.DeadlineExceededError(
                    None, None,
                    f"model {self.name!r}: deadline expired waiting for "
                    "admission (queue full — backpressure)"))
        return fut

    def _reject(self, req: GenerateRequest, outcome: str,
                exc: BaseException):
        _metric_sequences.get_cell(self.name, outcome).increase_by(1)
        req.future._set_exception(exc)

    # -- scheduler loop ------------------------------------------------------
    def _loop(self):
        while True:
            if self._holdback:
                # page-starved admissions retry once per engine step;
                # when nothing is live (nothing will ever retire) the
                # retry inside _admit_batch rejects instead of looping
                hb, self._holdback = self._holdback, []
                self._admit_batch(hb)
            if not self._active and self._inflight is None:
                with monitoring.traceme("engine/wait"):
                    item = self._queue.get()
                if item is _DONE:
                    # closed AND drained: queued requests admitted before
                    # the close marker have all run to completion
                    return
                self._admit_batch([item])
            # joiners ride the next step: burst-drain up to the free slots
            if self._pool.free_count:
                joiners = self._queue.get_available(self._pool.free_count)
                if joiners:
                    self._admit_batch(joiners)
            if self._active or self._inflight is not None:
                try:
                    self._step()
                except BaseException as e:  # noqa: BLE001 — deliver, never die
                    _flight_mod.get_recorder().on_error(
                        e, where="serving_decode_step", model=self.name)
                    # the live rows of the step in flight and of the one
                    # being enqueued or fetched: all of them are here
                    for s in self._active:
                        self._retire(s, "error", exc=e)
                    self._active = []
                    self._inflight = None
                    self._slots_gauge.set(0)

    def _admit_batch(self, items):
        had = len(self._active)
        with monitoring.traceme("engine/admit") as sp:
            self._admit(items)
            sp.set_meta(joined=len(self._active) - had,
                        held_back=len(self._holdback))

    def _admit(self, items):
        now = time.perf_counter()
        live: List[GenerateRequest] = []
        for req in items:
            if req is _DONE:
                continue
            if req.expired(now):
                self._reject(req, "deadline_exceeded",
                             errors.DeadlineExceededError(
                                 None, None,
                                 f"model {self.name!r}: deadline expired "
                                 "after "
                                 f"{now - req.t_enqueue:.3f}s in the "
                                 "admission queue"))
                continue
            live.append(req)
        if not live:
            return
        if self._paged:
            self._admit_paged(live, now)
            return
        slots = []
        for req in live:
            slot = self._pool.acquire()
            assert slot is not None, "admission exceeded free slots"
            slots.append(slot)
            record_queue_wait(self.name, req, now)
        try:
            with self._prefill_span(
                    live, calls=2 if self._spec_enabled else 1,
                    rows=len(live)) as sp:
                self._model.prefill(np.stack([r.src for r in live]),
                                    np.asarray(slots, np.int32))
                if self._spec_enabled:
                    # the draft keeps its own caches: it needs the same
                    # prompts resident to propose from
                    self._draft.prefill(np.stack([r.src for r in live]),
                                        np.asarray(slots, np.int32))
        except BaseException as e:  # noqa: BLE001
            _flight_mod.get_recorder().on_error(
                e, where="serving_decode_prefill", model=self.name)
            for req, slot in zip(live, slots):
                self._pool.release(slot)
                self._reject(req, "error", e)
            return
        self._prefill_s.add(sp.dur_s)
        eos = self._model.eos_id
        for req, slot in zip(live, slots):
            # decoder seeds with EOS at position 0, like beam search
            self._active.append(_Sequence(req, slot, eos,
                                          req.max_new_tokens))
        self._slots_gauge.set(len(self._active))

    def _prefill_span(self, requests, **meta):
        """``engine/prefill``: the model calls that put the joining
        prompts into the cache (``calls`` program calls carrying
        ``rows`` prompt rows or page chunks between them); the ring
        knows it as ``serving_decode_prefill``."""
        return _req_tracing.span(
            "engine/prefill", ring="serving_decode_prefill",
            trace_ids=[r.trace_id for r in requests if r.trace_id],
            model=self.name, joined=len(requests), **meta)

    def _sync_prefix_metrics(self):
        pc = self._prefix
        hits = pc.hit_pages + pc.cow_hits
        if hits > self._prefix_seen[0]:
            self._prefix_hits.increase_by(hits - self._prefix_seen[0])
            self._prefix_seen[0] = hits
        if pc.evictions > self._prefix_seen[1]:
            self._prefix_evictions.increase_by(
                pc.evictions - self._prefix_seen[1])
            self._prefix_seen[1] = pc.evictions
        self._prefix_shared.set(pc.shared_pages)

    def _admit_paged(self, live, now):
        """Prefix-cache admission: resolve each prompt's page program
        (trie hits reuse shared pages, misses prefill fresh ones, a
        partial tail copies-on-write when a cached page extends it),
        then hand the model ALL their page chunks as the rows of one
        ``prefill_chunk``, ordered by absolute start (``base``), ties
        by slot. A row reads pages of lower ``base`` only — its own
        prompt's, and those of a shared prefix that another prompt of
        this batch fills — and the model cuts the rows into program
        calls in the order given, each layer of a call appending all
        its rows before any attends: every page a row reads is written
        by its own call or an earlier one. Rows of like ``base`` share
        a call, so a call's furthest row is no further than a
        lock-step fill's."""
        from .prefix_cache import PagesExhaustedError

        admitted = []          # (req, slot, plan)
        for req in live:
            slot = self._pool.acquire()
            if slot is None:
                self._holdback.append(req)
                continue
            try:
                plan = self._prefix.acquire(req.src[:-1])
            except PagesExhaustedError as e:
                self._pool.release(slot)
                if self._active or admitted:
                    # something live will retire and free pages: retry
                    self._holdback.append(req)
                else:
                    self._reject(req, "rejected",
                                 errors.ResourceExhaustedError(
                                     None, None,
                                     f"model {self.name!r}: prompt "
                                     f"needs more cache pages than "
                                     f"exist ({e})"))
                continue
            admitted.append((req, slot, plan))
            record_queue_wait(self.name, req, now)
        if not admitted:
            self._sync_prefix_metrics()
            return
        pl = self._model.page_len
        pps = self._model.pages_per_seq
        scratch = self._model.scratch_page
        try:
            # one row a page chunk: (base, slot, page, tokens, real
            # tokens); the prefilled tail is a prompt's last chunk when
            # it wasn't served by CoW
            tables = {}
            rows = []
            for req, slot, plan in admitted:
                table = np.full((pps,), scratch, np.int32)
                pages = plan.pages
                table[:len(pages)] = pages
                tables[slot] = table
                rows += [(base, slot, page, tok, pl)
                         for page, tok, base in plan.fill]
                if len(plan.tail) and plan.cow_src is None and \
                        not plan.tail_ready:
                    tok = np.full((pl,), self._model.pad_id, np.int32)
                    tok[:len(plan.tail)] = plan.tail
                    rows.append((plan.cached_len - len(plan.tail), slot,
                                 plan.tail_page, tok, len(plan.tail)))
            rows.sort(key=lambda r: r[:2])
            # a model with state outside its pages is told each row's
            # slot and how many of its tokens are real
            by_slot = {"slots": [r[1] for r in rows],
                       "lens": [r[4] for r in rows]} if self._by_slot else {}
            with self._prefill_span([r for r, _, _ in admitted],
                                    rows=len(rows)) as sp:
                # copy-on-write first: a CoW'd tail page must be
                # populated before any decode step reads through it
                for _, _, plan in admitted:
                    if plan.cow_src is not None:
                        self._model.copy_page(plan.tail_page, plan.cow_src)
                sp.set_meta(calls=self._model.prefill_chunk(
                    [r[3] for r in rows], [r[0] for r in rows],
                    [tables[r[1]] for r in rows], [r[2] for r in rows],
                    **by_slot))
        except BaseException as e:  # noqa: BLE001
            _flight_mod.get_recorder().on_error(
                e, where="serving_decode_prefill", model=self.name)
            for req, slot, plan in admitted:
                # the tail page (when any) is trie-resident: release of
                # the node chain covers it, nothing to free directly
                self._prefix.release(plan.node)
                if plan.node is None:       # unshared: the plan's own pages
                    for pg in plan.pages:
                        self._prefix.free_page(pg)
                self._pool.release(slot)
                self._reject(req, "error", e)
            self._sync_prefix_metrics()
            return
        self._prefill_s.add(sp.dur_s)
        for req, slot, plan in admitted:
            # the first decode step feeds the LAST prompt token at
            # position plen-1 — its output is the first emitted token
            s = _Sequence(req, slot, int(req.src[-1]),
                          req.max_new_tokens)
            s.pos = len(req.src) - 1
            s.pages = tables[slot]
            s.node = plan.node
            if plan.node is None:
                # unshared pages (a model with state outside its pages):
                # the sequence's own from the start, freed when it retires
                s.private = list(plan.pages)
            # the tail page is trie-owned (shared): the sequence owns
            # no private pages yet — its first decode append into the
            # tail block copies-on-write (see _step_paged)
            elif len(plan.tail):
                s.cow_blk = plan.cached_len // pl
            self._active.append(s)
        self._sync_prefix_metrics()
        self._slots_gauge.set(len(self._active))

    def _step(self):
        with monitoring.traceme("engine/step"):
            self._advance()

    def _advance(self):
        if self._paged:
            self._step_paged()
            return
        self._expire()
        if not self._active:
            return
        if self._spec_enabled:
            self._step_speculative()
            return
        n = len(self._active)
        tokens = [s.last_tok for s in self._active]
        positions = [s.pos for s in self._active]
        slots = [s.slot for s in self._active]
        if self._scratch_slot is not None:
            # POLICY-driven bucketing: pad the live set to the policy's
            # bucket with rows targeting the model's scratch slot (a
            # live slot id here would corrupt that sequence's cache)
            bucket = self._policy.bucket_for(n)
            pad = bucket - n
            if pad:
                tokens = tokens + [self._model.pad_id] * pad
                positions = positions + [0] * pad
                slots = slots + [self._scratch_slot] * pad
        self._decode_step(n, tokens, positions, slots)

    def _expire(self):
        """Per-token deadline check: an expired sequence retires NOW — it
        never stalls or rides another step (a step in flight that carries
        it drops its token)."""
        now = time.perf_counter()
        still = []
        for s in self._active:
            if s.req.expired(now):
                self._retire(s, "deadline_exceeded")
            else:
                still.append(s)
        self._active = still
        self._slots_gauge.set(len(still))

    def _wants(self, s: _Sequence) -> bool:
        """Whether ``s`` rides the next step: tokens of its budget not yet
        asked for, and a position left. Known before the tokens of the
        steps in flight are, so a sequence at its budget never rides a
        step it has no use for."""
        return (len(s.tokens) + s.asked < s.budget
                and s.pos < self._model.max_decode_len)

    def _decode_step(self, n, tokens, positions, slots):
        """One decode position for the ``n`` live sequences of the slot
        path, computed and fetched at once, then the commit the paged path
        shares. ``engine/decode`` spans what
        ``/stf/serving/decode_step_seconds`` samples."""
        for s in self._active:
            s.pos += 1
            s.asked += 1
        t0 = time.perf_counter()
        with monitoring.traceme("engine/decode", live=n) as sp:
            next_tok, logp, bucket = self._model.decode(tokens, positions,
                                                        slots)
            sp.set_meta(bucket=bucket)
        dur = time.perf_counter() - t0
        self._step_s.add(dur)
        self._fill.add(n / max(bucket, 1))
        self._commit(self._active, next_tok, logp, bucket, dur)

    def _commit(self, rows, next_tok, logp, bucket, dur):
        """A fetched step's tokens to the sequences that rode it, in row
        order: metrics, streaming, EOS/budget retirement. A row whose
        sequence retired after the step was enqueued (an end token seen
        one step late, a deadline, an error) is past its end: its token
        is dropped."""
        eos = self._model.eos_id
        n = 0
        with monitoring.traceme("engine/deliver"):
            for i, s in enumerate(rows):
                if s.done:
                    continue
                s.asked -= 1
                tok = int(next_tok[i])
                lp = float(logp[i])
                s.tokens.append(tok)
                s.logps.append(lp)
                s.last_tok = tok
                n += 1
                if s.req.on_token is not None:
                    try:
                        s.req.on_token(tok, lp)
                    except Exception:  # noqa: BLE001 — client cb must not kill the engine
                        pass
                if tok == eos:
                    self._retire(s, "eos")
                elif not s.asked and not self._wants(s):
                    self._retire(s, "length")
            self._active = [s for s in self._active if not s.done]
        self._slots_gauge.set(len(self._active))
        self._tokens.increase_by(n)
        self._rate.add(n)
        self._rate_gauge.set(int(self._rate.rate()))
        rec = _flight_mod.get_recorder()
        if rec.enabled:
            rec.record("decode_step", model=self.name, live=n,
                       bucket=bucket, step_s=round(dur, 6))

    def _step_paged(self):
        """One step of the paged path, PIPELINED: the rows of step k + 1
        — every sequence with tokens left to ask for, which the host
        knows before it has step k's tokens — get their write pages
        (private decode pages allocated lazily, page-fault style), step
        k + 1 is enqueued, its tokens left for the device to take from
        what step k writes (``decode_async``: -1 for every sequence but
        at its first step), and only then is step k fetched and
        delivered; admission then runs while step k + 1 does. So an end
        token is seen one step late: the sequence already rides step
        k + 1, whose token for it is dropped; its slot and pages go back
        at once, and only work enqueued after step k + 1 reuses them. A
        sequence whose budget the step enqueued spends gives them back
        then, so the joiners admitted next fill its row of step k + 2.
        With nothing to enqueue, the step in flight is fetched at once."""
        self._expire()
        prev = self._inflight
        with monitoring.traceme("engine/page_faults"):
            ride = self._page_faults(
                [s for s in self._active if self._wants(s)])
        t0 = time.perf_counter()
        with monitoring.traceme("engine/decode", live=len(ride)) as sp:
            self._inflight = None
            if ride:
                call = self._model.decode_async(
                    [-1 if s.asked else s.last_tok for s in ride],
                    [s.pos for s in ride],
                    np.stack([s.pages for s in ride]),
                    slots=[s.slot for s in ride])
                self._overlap.add(float(prev is not None))
                self._fill.add(len(ride) / max(call.bucket, 1))
                for s in ride:
                    s.pos += 1
                    s.asked += 1
                    if not self._wants(s):
                        # its last step: the slot and pages go to the
                        # joiners admitted next, which ride step k + 2
                        self._free(s)
                self._inflight = _Enqueued(call, ride, t0)
                sp.set_meta(bucket=call.bucket)
            if prev is not None:
                next_tok, logp, bucket = self._model.collect(prev.call)
        if prev is not None:
            now = time.perf_counter()
            # a step on the wall: from the fetch before (or, after an
            # idle stretch, its own enqueue) to its own fetch
            dur = now - max(self._t_collect, prev.t0)
            self._t_collect = now
            self._step_s.add(dur)
            self._commit(prev.rows, next_tok, logp, bucket, dur)

    def _page_faults(self, rows):
        """Make sure each of ``rows`` has the page its next position
        writes; a sequence that cannot get one retires with an error.
        Returns the rows that can ride."""
        from .prefix_cache import PagesExhaustedError

        pl = self._model.page_len
        scratch = self._model.scratch_page
        for s in rows:
            blk = s.pos // pl
            if s.pages[blk] == scratch:
                try:
                    pg = self._prefix.alloc_page()
                except PagesExhaustedError as e:
                    # every page is held by live sequences: this one
                    # cannot advance — fail it rather than stall all
                    self._retire(s, "error",
                                 exc=errors.ResourceExhaustedError(
                                     None, None,
                                     f"model {self.name!r}: out of "
                                     f"cache pages mid-decode ({e})"))
                    continue
                s.pages[blk] = pg
                s.private.append(pg)
            elif s.cow_blk is not None and blk == s.cow_blk:
                # first decode append into the trie-resident tail page:
                # copy-on-write so the shared rows stay pristine for
                # the next exact-tail hit
                shared = int(s.pages[blk])
                try:
                    pg = self._prefix.alloc_page({shared})
                except PagesExhaustedError as e:
                    self._retire(s, "error",
                                 exc=errors.ResourceExhaustedError(
                                     None, None,
                                     f"model {self.name!r}: out of "
                                     f"cache pages mid-decode ({e})"))
                    continue
                self._model.copy_page(pg, shared)
                s.pages[blk] = pg
                s.private.append(pg)
                s.cow_blk = None
        self._active = [s for s in self._active if not s.done]
        self._slots_gauge.set(len(self._active))
        return [s for s in rows if not s.done]

    def _step_speculative(self):
        """One speculative cycle: the draft proposes ``draft_steps``
        greedy tokens in one dispatch, the target verifies the
        ``spec_k``-token block in one batched re-score, and each
        sequence commits the longest matching prefix plus one bonus
        target token. Every committed token is the target's own
        choice, so greedy output is token-exact vs plain decode;
        rejected-suffix cache rows are dead (length-masked) until the
        next cycle overwrites them."""
        n = len(self._active)
        tokens = [s.last_tok for s in self._active]
        positions = [s.pos for s in self._active]
        slots = [s.slot for s in self._active]
        kd = self._draft.draft_steps
        t0 = time.perf_counter()
        with monitoring.traceme("engine/decode", live=n) as sp:
            props, _ = self._draft.decode_k(tokens, positions, slots)
            blk = np.concatenate(
                [np.asarray(tokens, np.int32).reshape(n, 1), props],
                axis=1)
            tgt, lps, bucket = self._model.verify(blk, positions, slots)
            sp.set_meta(bucket=bucket)
        dur = time.perf_counter() - t0
        self._step_s.add(dur)
        self._fill.add(n / max(bucket, 1))
        rec = _flight_mod.get_recorder()
        eos = self._model.eos_id
        max_pos = self._model.max_decode_len - 1
        emitted_total = 0
        accepted_total = 0
        still = []
        with monitoring.traceme("engine/deliver"):
            for i, s in enumerate(self._active):
                a = 0
                while a < kd and int(props[i, a]) == int(tgt[i, a]):
                    a += 1
                accepted_total += a
                outcome = None
                for j in range(a + 1):
                    tok = int(tgt[i, j])
                    lp = float(lps[i, j])
                    s.tokens.append(tok)
                    s.logps.append(lp)
                    s.pos += 1
                    s.last_tok = tok
                    emitted_total += 1
                    if s.req.on_token is not None:
                        try:
                            s.req.on_token(tok, lp)
                        except Exception:  # noqa: BLE001
                            pass
                    if tok == eos:
                        outcome = "eos"
                        break
                    if len(s.tokens) >= s.budget or s.pos > max_pos:
                        outcome = "length"
                        break
                if outcome is not None:
                    self._retire(s, outcome)
                else:
                    still.append(s)
        self._active = still
        self._slots_gauge.set(len(still))
        self._tokens.increase_by(emitted_total)
        self._rate.add(emitted_total)
        self._rate_gauge.set(int(self._rate.rate()))
        self._spec_proposed.increase_by(kd * n)
        self._spec_accepted.increase_by(accepted_total)
        self._spec_counts[0] += kd * n
        self._spec_counts[1] += accepted_total
        if self._spec_counts[0]:
            self._spec_acceptance.set(
                int(100 * self._spec_counts[1] / self._spec_counts[0]))
        if rec.enabled:
            rec.record("decode_step", model=self.name, live=n,
                       bucket=bucket, step_s=round(dur, 6),
                       spec_emitted=emitted_total)

    def _free(self, s: _Sequence):
        """Give ``s``'s slot and pages back: at retirement, or on the paged
        path as soon as the last step it rides is enqueued — only work
        enqueued after that step (a joiner's prefill, its decode steps)
        reuses them, and the device runs it after."""
        if s.slot is None:
            return
        if s.pages is not None:
            # decref the shared trie chain (pages stay cached at refs
            # 0 for future prefix hits) and free the private pages
            if s.node is not None:
                self._prefix.release(s.node)
            for pg in s.private:
                self._prefix.free_page(pg)
            s.private = []
            s.node = None
            s.pages = None
            self._sync_prefix_metrics()
        self._pool.release(s.slot)
        s.slot = None

    def _retire(self, s: _Sequence, outcome: str,
                exc: Optional[BaseException] = None):
        s.done = True
        self._free(s)
        _metric_sequences.get_cell(self.name, outcome).increase_by(1)
        if s.tokens:
            self._per_token.add(
                (time.perf_counter() - s.t_start) / len(s.tokens))
        if outcome in ("eos", "length"):
            s.req.future._set_result({
                "tokens": np.asarray(s.tokens, np.int32),
                "logprobs": np.asarray(s.logps, np.float32),
                "outcome": outcome,
            })
        elif exc is not None:
            s.req.future._set_exception(exc)
        else:
            s.req.future._set_exception(errors.DeadlineExceededError(
                None, None,
                f"model {self.name!r}: per-token deadline expired after "
                f"{len(s.tokens)} emitted tokens"))

    # -- introspection / lifecycle -------------------------------------------
    def statusz_info(self) -> Dict[str, Any]:
        info = {"model": self.name, "kind": "generative",
                "num_slots": self._pool.num_slots,
                "slots_active": self._pool.active_count,
                "queue_depth": self.queue_depth(),
                "tokens_per_sec": self.refresh_rate()}
        model_info = getattr(self._model, "statusz_info", None)
        if callable(model_info):
            info.update(model_info())
        if self._prefix is not None:
            info["prefix_cache"] = self._prefix.statusz_info()
            info["holdback"] = len(self._holdback)
        if self._spec_enabled:
            prop, acc = self._spec_counts
            info["speculative"] = {
                "spec_k": self._model.spec_k,
                "draft_steps": self._draft.draft_steps,
                "proposed_tokens": prop, "accepted_tokens": acc,
                "acceptance_rate": (acc / prop) if prop else 0.0}
        return info

    def close(self, timeout: float = 30.0):
        """Close admission and drain: new submits fail Unavailable;
        already-queued requests and ACTIVE sequences run to completion
        (the ContinuousBatcher drain contract); then the model's
        session closes with the engine thread."""
        self._closed = True
        self._queue.close()
        if self._thread.is_alive() and \
                threading.current_thread() is not self._thread:
            # checked: a wedged engine thread must be LOUD (flight
            # `wedge` event with its stack + held locks), not silently
            # leaked past close()
            _flight_mod.checked_join(self._thread, timeout,
                                     f"GenerativeEngine.close({self.name})")
        self._model.close()
        if self._draft is not None:
            self._draft.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
