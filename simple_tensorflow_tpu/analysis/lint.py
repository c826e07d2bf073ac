"""Extensible lint framework over the Graph IR.

A :class:`LintRule` is a named check with a default severity; rules
registered through :func:`register_lint_rule` run under
:func:`lint_graph` (whole graph or a fetch-pruned op list) and yield
:class:`~.diagnostics.Diagnostic` objects with op + user-source
attribution. Severities are per-run configurable
(``lint_graph(severities={"lint/unseeded-rng": "error"})``) so a CI
gate can promote any smell to a failure without code changes.

Built-in catalog (see docs/ANALYSIS.md for the worked examples):

  lint/int-div-float     integer division truncates, then the truncated
                         result feeds a float computation (WARNING)
  lint/narrow-64bit      a 64-bit tensor is declared while the runtime
                         narrows to 32-bit (jax_enable_x64 off): the
                         site that will silently lose precision (NOTE)
  lint/unseeded-rng      an RNG-effect op with neither graph nor op
                         seed: irreproducible across processes under
                         jit (WARNING)
  lint/const-fetch       a fetch is entirely constant-foldable — it is
                         recomputed (or at best re-fetched) every step
                         (NOTE)
  lint/transpose-pair    adjacent mutually inverse transposes survive
                         where the layout pass cannot cancel them
                         (control deps / multi-consumer boundaries)
                         (WARNING)
  lint/serving-incompatible
                         ops that make an exported inference graph
                         unservable under the stf.serving continuous
                         batcher: host-stage ops, host-observable io
                         effects (Print/logging), unseeded stateful
                         RNG. Active only for purpose="serving" runs
                         (``lint_graph(purpose="serving")`` /
                         ``graph_lint --serving``) (WARNING)
  lint/serving-decode-cache
                         generative decode-plan shape: KV-cache ops
                         missing a committed-sharding declaration, a
                         cache tensor escaping to host (fetched, or
                         feeding a host-stage op), a SHARED-page cache
                         tensor (paged prefix cache) transitively
                         REACHING a host sink, or a speculative-verify
                         cache write that is not refcount-guarded.
                         Active only for purpose="serving" runs (ERROR)
  lint/kernel-routing    per-op Pallas/XLA routing verdicts from the
                         stf.kernels registry (routed / fallback+reason).
                         Active only for purpose="kernels"
                         runs (``graph_lint --kernels``) (NOTE)
  lint/embedding-replicated-table
                         an embedding table at/over the byte budget
                         (``--budget`` or 128 MiB default) that
                         resolves REPLICATED on a >1-device mesh —
                         every device holds a full copy of a table
                         that only fits because vocab sharding divides
                         it. Active only for purpose="embeddings" runs
                         (``graph_lint --embeddings``) (ERROR)
  lint/memory-budget     the static cost model's predicted peak device
                         memory for a fetch closure exceeds the
                         configured budget (``graph_lint --memory
                         --budget BYTES``; ctx.memory_budget). Active
                         only for purpose="memory" runs (ERROR)
  lint/numeric-risk      statically visible NaN/Inf seeds, the offline
                         half of the stf.debug.numerics runtime plane:
                         unguarded domain-restricted ops (Log/Rsqrt/
                         Reciprocal on an unclamped operand, Div with
                         an unguarded denominator, Exp with no upper
                         clamp or max-subtraction) and bf16/f16
                         long-axis reductions whose low-mantissa
                         accumulator drifts. Active only for
                         purpose="numerics" runs (``graph_lint
                         --numerics``) (WARNING)
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence

from ..framework import dtypes as dtypes_mod
from ..framework import graph as ops_mod
from ..framework import op_registry
from . import diagnostics as diag_mod
from .diagnostics import ERROR, NOTE, WARNING, Diagnostic
from .effects import op_effects


class LintContext:
    """What one lint run sees: the op list (graph order), the owning
    graph, the optional fetch set, and — when the sharding analyzer ran
    — its :class:`~.sharding.ShardingReport` (the sharding lint rules
    consult it and yield nothing without one). ``purpose`` scopes
    purpose-gated rules: "serving" activates the
    serving-incompatibility checks an exported inference graph must
    pass (a training graph legitimately fails them — dropout,
    summaries — so they never fire by default)."""

    def __init__(self, graph, ops: Sequence[Any],
                 fetches: Optional[Sequence[Any]] = None,
                 sharding_report: Optional[Any] = None,
                 purpose: Optional[str] = None,
                 memory_budget: Optional[int] = None):
        self.graph = graph
        self.ops = list(ops)
        self.fetches = list(fetches or [])
        self.sharding_report = sharding_report
        self.purpose = purpose
        # device-memory budget in bytes for the lint/memory-budget rule
        # (graph_lint --memory --budget; purpose="memory" runs)
        self.memory_budget = memory_budget
        self._x64 = None

    @property
    def x64_enabled(self) -> bool:
        if self._x64 is None:
            import jax

            self._x64 = bool(jax.config.jax_enable_x64)
        return self._x64


class LintRule:
    """One registered rule. ``check(ctx)`` yields (op, message) pairs —
    severity/code attachment and counting happen in the driver."""

    def __init__(self, code: str, default_severity: str,
                 check: Callable[[LintContext], Iterable],
                 doc: str = ""):
        if not code.startswith("lint/"):
            code = "lint/" + code
        self.code = code
        self.default_severity = default_severity
        self.check = check
        self.doc = doc or (check.__doc__ or "").strip()

    def __repr__(self):
        return f"<LintRule {self.code} ({self.default_severity})>"


_RULES: Dict[str, LintRule] = {}


def register_lint_rule(code: str, default_severity: str = WARNING,
                       doc: str = ""):
    """Decorator: register ``fn(ctx) -> iterable of (op, message)`` as a
    lint rule. Re-registration replaces (rules are module-reloadable)."""
    def deco(fn):
        rule = LintRule(code, default_severity, fn, doc)
        _RULES[rule.code] = rule
        return fn

    return deco


def registered_rules() -> List[LintRule]:
    return [_RULES[k] for k in sorted(_RULES)]


def lint_graph(graph=None, ops: Optional[Sequence[Any]] = None,
               fetches: Optional[Sequence[Any]] = None,
               severities: Optional[Dict[str, str]] = None,
               rules: Optional[Sequence[str]] = None,
               sharding_report: Optional[Any] = None,
               purpose: Optional[str] = None,
               memory_budget: Optional[int] = None) -> List[Diagnostic]:
    """Run the registered rules. ``severities`` overrides per-code
    severity ("off" disables a rule); ``rules`` restricts to a subset;
    ``sharding_report`` feeds the sharding rules (analyze_sharding
    passes its own report through here); ``purpose="serving"``
    activates the serving-compatibility rules (ModelServer.load and
    ``graph_lint --serving`` pass it); ``purpose="memory"`` +
    ``memory_budget`` activates the device-memory budget rule
    (``graph_lint --memory --budget``)."""
    if graph is None and ops is None:
        graph = ops_mod.get_default_graph()
    if ops is None:
        ops = graph.get_operations()
    ctx = LintContext(graph, ops, fetches, sharding_report=sharding_report,
                      purpose=purpose, memory_budget=memory_budget)
    severities = severities or {}
    diags: List[Diagnostic] = []
    for rule in registered_rules():
        if rules is not None and rule.code not in rules \
                and rule.code[len("lint/"):] not in rules:
            continue
        sev = severities.get(rule.code,
                             severities.get(rule.code[len("lint/"):],
                                            rule.default_severity))
        if sev == "off":
            continue
        for op, message in rule.check(ctx):
            diag_mod.report(diags, sev, rule.code, message, op=op)
    return diags


# ---------------------------------------------------------------------------
# built-in rules
# ---------------------------------------------------------------------------

_INT_DIV_TYPES = ("Div", "FloorDiv")


@register_lint_rule("int-div-float", WARNING)
def _rule_int_div_float(ctx):
    """Integer division truncates; feeding the truncated quotient into a
    float computation is almost always a missing cast on the operands
    (classic: ``mean = total / count`` with int tensors)."""
    for op in ctx.ops:
        if op.type not in _INT_DIV_TYPES or not op.outputs:
            continue
        out = op.outputs[0]
        if not out.dtype.base_dtype.is_integer:
            continue
        for consumer in out.consumers():
            floaty = False
            if consumer.type == "Cast":
                to = consumer.attrs.get("dtype")
                floaty = to is not None and \
                    dtypes_mod.as_dtype(to).is_floating
            else:
                floaty = any(
                    t is not out and t.dtype.base_dtype.is_floating
                    for t in consumer.inputs) or any(
                    t.dtype.base_dtype.is_floating
                    for t in consumer.outputs)
            if floaty:
                yield (op,
                       f"integer division {op.name!r} truncates before "
                       f"feeding float computation {consumer.name!r} "
                       f"({consumer.type}); cast the operands to float "
                       "first (or use stf.truediv)")
                break


_WIDE_DTYPES = ("int64", "uint64", "float64")
# op types whose 64-bit output is a deliberate API contract, narrowed
# once at the session boundary (see docs/MIGRATION.md): re-flagging
# every op in between would bury the signal
_NARROW_SOURCE_TYPES = ("Placeholder", "PlaceholderWithDefault",
                        "VariableV2", "Const")


@register_lint_rule("narrow-64bit", NOTE)
def _rule_narrow_64bit(ctx):
    """64-bit tensors silently narrow to 32-bit on TPU (jax x64 off).
    Flags the *source* sites (placeholders, variables, constants) where
    the narrowing enters the graph."""
    if ctx.x64_enabled:
        return
    for op in ctx.ops:
        if op.type not in _NARROW_SOURCE_TYPES:
            continue
        for out in op.outputs:
            if out.dtype.base_dtype.name in _WIDE_DTYPES:
                yield (op,
                       f"{op.type} {op.name!r} declares "
                       f"{out.dtype.base_dtype.name}, which narrows to "
                       f"{dtypes_mod.narrowed_if_no_x64(out.dtype.base_dtype).name}"
                       " on this runtime (jax_enable_x64 off); declare "
                       "the 32-bit dtype to make the precision explicit")
                break


@register_lint_rule("unseeded-rng", WARNING)
def _rule_unseeded_rng(ctx):
    """An RNG op with neither a graph seed nor an op seed draws from a
    different stream every process start — irreproducible under jit.
    Set stf.set_random_seed(...) or pass seed= at the op."""
    for op in ctx.ops:
        eff = op_effects(op)
        if not eff.rng:
            continue
        if op.attrs.get("seed") is None \
                and op.attrs.get("_graph_seed") is None \
                and (op.graph.seed is None):
            yield (op,
                   f"RNG op {op.name!r} ({op.type}) has no seed and the "
                   "graph seed is unset: draws are irreproducible "
                   "across process restarts")


@register_lint_rule("const-fetch", NOTE)
def _rule_const_fetch(ctx):
    """A fetch whose whole ancestry is constant re-evaluates (at best
    re-fetches) an invariant value every step; fold it at build time or
    fetch it once."""
    if not ctx.fetches:
        return
    cache: Dict[Any, bool] = {}

    def const_only(op) -> bool:
        if op in cache:
            return cache[op]
        cache[op] = False  # cycle guard
        try:
            od = op_registry.get(op.type)
        except KeyError:
            return False
        if op.type == "Const":
            cache[op] = True
            return True
        if od.is_stateful or od.runs_on_host or od.pure_fn is None \
                or not op.inputs:
            return False
        ok = all(const_only(t.op) for t in op.inputs) \
            and not op.control_inputs
        cache[op] = ok
        return ok

    for f in ctx.fetches:
        op = f if isinstance(f, ops_mod.Operation) else f.op
        if op.type != "Const" and const_only(op):
            yield (op,
                   f"fetch {op.name!r} is entirely constant-foldable; "
                   "its value never changes across steps")


def _perm_of(op):
    p = op.attrs.get("perm")
    return tuple(p) if p is not None else None


@register_lint_rule("transpose-pair", WARNING)
def _rule_transpose_pair(ctx):
    """Adjacent mutually inverse transposes that survive into the final
    graph (the layout pass cancels clean pairs; pairs split by control
    dependencies or consumed by name stay) — pure data-movement cost on
    every step."""
    for op in ctx.ops:
        if op.type != "Transpose" or not op.inputs:
            continue
        p1 = _perm_of(op)
        src = op.inputs[0].op
        if src.type != "Transpose" or op.inputs[0].value_index != 0 \
                or not src.inputs:
            continue
        p2 = _perm_of(src)
        if not p1 or not p2 or len(p1) != len(p2):
            continue
        if tuple(p2[i] for i in p1) == tuple(range(len(p1))):
            yield (op,
                   f"transpose pair {src.name!r} -> {op.name!r} composes "
                   "to identity but was not cancelled (control deps or "
                   "by-name fetches pin it); restructure so the layout "
                   "pass can cancel it")


# op types that are pure graph inputs/values — never serving hazards
# even though Placeholder is formally "fed on host"
_SERVING_BENIGN_TYPES = ("Placeholder", "PlaceholderWithDefault", "Const",
                         "NoOp")


@register_lint_rule("serving-incompatible", WARNING)
def _rule_serving_incompatible(ctx):
    """Ops an exported inference graph must not contain to serve under
    the stf.serving continuous batcher (active only for
    ``purpose="serving"`` runs):

    - host-stage ops (queues, readers, iterators, summaries, py_func):
      each one forces a Python host stage around every coalesced batch
      — ModelServer refuses such plans outright;
    - host-observable io effects (``Print``, logging): they fire once
      per BATCH, not per request, and serialize the device dispatch;
    - stateful RNG without an op seed: responses become dependent on
      batch composition and request arrival order (and irreproducible
      across server restarts) — seed the op, or export an inference
      graph without sampling (e.g. dropout at keep_prob=1 folded out).
    """
    if ctx.purpose != "serving":
        return
    ops = ctx.ops
    if ctx.fetches:
        from ..framework import lowering as lowering_mod

        targets = [f if isinstance(f, ops_mod.Operation) else f.op
                   for f in ctx.fetches]
        # narrow to the fetch ancestry, but never WIDEN past the op set
        # the caller scoped the run to: ModelServer passes the closure
        # already pruned at the signature-INPUT boundary, and ops
        # upstream of a fed input are not part of the serving plan
        scoped = set(ctx.ops)
        ops = [op for op in lowering_mod.prune(targets, set())
               if op in scoped]
    for op in ops:
        if op.type in _SERVING_BENIGN_TYPES:
            continue
        if op.op_def.runs_on_host:
            yield (op,
                   f"host-stage op {op.name!r} ({op.type}) in the "
                   "inference closure: every request batch would pay a "
                   "Python host stage; export a pure device inference "
                   "graph")
            continue
        eff = op_effects(op)
        if eff.io:
            yield (op,
                   f"op {op.name!r} ({op.type}) has a host-observable "
                   "io effect: under batching it fires once per batch, "
                   "not per request, and blocks async dispatch; strip "
                   "logging/Print from the exported inference graph")
        if eff.rng and op.attrs.get("seed") is None \
                and op.attrs.get("_graph_seed") is None \
                and op.graph.seed is None:
            yield (op,
                   f"unseeded stateful RNG {op.name!r} ({op.type}) in "
                   "the inference closure: responses depend on batch "
                   "composition/request order and do not reproduce "
                   "across restarts; seed it, or export without "
                   "sampling ops")


@register_lint_rule("serving-decode-cache", ERROR)
def _rule_serving_decode_cache(ctx):
    """Decode-plan shape checks for generative serving (active only for
    ``purpose="serving"`` runs — ``graph_lint --serving``). The
    KV-cache contract (ops/kv_cache_ops.py) is that cache state lives
    device-resident with a COMMITTED sharding and never leaves HBM
    between decode steps; this rule makes both halves statically
    checkable:

    - a cache op (KVCacheAlloc/Append/Gather, a paged attention
      reading its pools in place, or a state pool's alloc and its
      in-place updates) whose committed-sharding
      declaration is missing would commit at whatever layout the first
      write happened to produce — resharding every subsequent step;
    - a cache tensor ESCAPING TO HOST (a host-stage op consuming a
      cache op's output, or a cache op's output fetched directly) pays
      a device→host transfer of the whole cache page set per decode
      step — the exact traffic the cache exists to avoid. Slice a
      device-side view instead, or fetch derived scalars;
    - a SHARED page (paged prefix cache, ``PAGED_ATTR``) holds K/V rows
      other live sequences read through their page tables, so the
      host-sink contract tightens from "direct consumer" to
      REACHABILITY: any path from a paged cache tensor to a host sink
      leaks refcounted shared state off-device (and a host round-trip
      in the decode loop serializes every sequence sharing the page);
    - a cache write inside a speculative VERIFY plan (``VERIFY_ATTR``)
      lands K rows of which only the accepted prefix is committed; the
      write must be stamped ``refcount_guarded=True`` (``GUARD_ATTR``)
      to assert the engine masks the rejected suffix by committed
      length — an unguarded verify write could expose uncommitted
      draft rows to a sequence sharing the page;
    - decode tensor parallelism (``"<axis>:heads"`` declarations): a
      head-sharded cache whose gathered pages are immediately
      re-sharded to a head-replicated layout pays a per-token
      all-gather of the whole cache read — the traffic the TP layout
      exists to avoid (DecodeAttention runs per-shard over heads); and
      a ``KVCachePageCopy`` that declares a DIFFERENT sharding than
      the cache's committed one would re-commit the store entry at the
      new layout on the first CoW, resharding every subsequent decode
      step.
    """
    if ctx.purpose != "serving":
        return
    from ..ops import kv_cache_ops as _kvc

    # committed declarations per cache var (from the non-PageCopy ops:
    # alloc/append/gather all stamp the kv_cache handle's declaration)
    committed_decls = {}
    for op in ctx.ops:
        if _kvc.is_cache_op(op) and op.type != "KVCachePageCopy":
            decl = op.attrs.get(_kvc.SHARDING_ATTR)
            for vn in _kvc.cache_names(op) if decl else ():
                committed_decls.setdefault(vn, set()).add(str(decl))

    fetched = set()
    for f in ctx.fetches:
        if not isinstance(f, ops_mod.Operation):
            fetched.add(f)

    def _is_host_sink(consumer):
        return consumer.op_def.runs_on_host or op_effects(consumer).io

    # transitive host-sink search for the shared-page branch; memoized
    # per consumer op so the sweep stays linear in graph size
    _reach_memo = {}

    def _reaches_host(op):
        """First host-observable op reachable downstream of ``op``
        (following data edges), or None."""
        if op in _reach_memo:
            return _reach_memo[op]
        _reach_memo[op] = None  # cycle guard (graphs are acyclic)
        found = None
        for out in op.outputs:
            for consumer in out.consumers():
                if _is_host_sink(consumer):
                    found = consumer
                    break
                found = _reaches_host(consumer)
                if found is not None:
                    break
            if found is not None:
                break
        _reach_memo[op] = found
        return found

    for op in ctx.ops:
        if not _kvc.is_cache_op(op):
            continue
        if not op.attrs.get(_kvc.SHARDING_ATTR):
            yield (op,
                   f"cache op {op.name!r} ({op.type}) on "
                   f"{op.attrs.get('var_name')!r} has no committed "
                   "sharding declaration; declare it at kv_cache(..., "
                   "sharding=...) so the store commits a stable layout")
        if op.attrs.get(_kvc.VERIFY_ATTR) \
                and not op.attrs.get(_kvc.GUARD_ATTR):
            yield (op,
                   f"verify-plan cache write {op.name!r} on "
                   f"{op.attrs.get('var_name')!r} is not refcount-"
                   "guarded: a speculative VERIFY append lands rows "
                   "the engine may reject; stamp it "
                   "refcount_guarded=True (append(..., "
                   "verify_plan=True, refcount_guarded=True)) to "
                   "assert only the accepted prefix is committed")
        decl = str(op.attrs.get(_kvc.SHARDING_ATTR) or "")
        head_sharded = decl.endswith(_kvc.HEAD_SHARD_SUFFIX)
        if op.type == "KVCachePageCopy":
            others = committed_decls.get(op.attrs.get("var_name"), set())
            head_committed = any(
                d.endswith(_kvc.HEAD_SHARD_SUFFIX) for d in others)
            if head_committed and decl not in others:
                yield (op,
                       f"page copy {op.name!r} on "
                       f"{op.attrs.get('var_name')!r} declares sharding "
                       f"{decl or None!r} but the cache committed "
                       f"{sorted(others)}: the CoW would re-commit the "
                       "store entry at the new layout and reshard every "
                       "subsequent decode step; stamp the copy with the "
                       "cache's own declaration (build it from the same "
                       "kv_cache handle)")
        if op.type == "KVCacheGather" and head_sharded:
            axis = decl[: -len(_kvc.HEAD_SHARD_SUFFIX)]
            for out in op.outputs:
                for consumer in out.consumers():
                    if consumer.type != "ShardingConstraint":
                        continue
                    spec = tuple(consumer.attrs.get("spec") or ())
                    entry = (spec[_kvc.HEAD_DIM]
                             if len(spec) > _kvc.HEAD_DIM else None)
                    axes = (tuple(entry) if isinstance(entry,
                                                       (tuple, list))
                            else (entry,) if entry else ())
                    if axis not in axes:
                        yield (op,
                               f"head-sharded cache gather {op.name!r} "
                               f"({op.attrs.get('var_name')!r}, "
                               f"sharding {decl!r}) is re-sharded to a "
                               f"head-replicated layout by "
                               f"{consumer.name!r}: the decode plan "
                               "all-gathers the full head dim of every "
                               "gathered page per token; feed the "
                               "gathered pages to DecodeAttention "
                               "per-shard instead (heads are "
                               "embarrassingly parallel)")
        paged = bool(op.attrs.get(_kvc.PAGED_ATTR))
        # a paged attention's output is attention, not pages — and a
        # state-pool update's is its layer's, not the pool: what it
        # REACHES counts, as it did through the gather that fed the
        # kernel before the pool was read in place
        pages_out = op.type not in _kvc.IN_PLACE_OP_TYPES
        for out in op.outputs:
            if pages_out and out in fetched:
                yield (op,
                       f"cache tensor {out.name!r} is fetched — the "
                       "whole cache page set would transfer "
                       "device->host every decode step; fetch derived "
                       "values instead")
            direct_sink = False
            for consumer in out.consumers() if pages_out else ():
                if _is_host_sink(consumer):
                    direct_sink = True
                    yield (op,
                           f"cache tensor {out.name!r} feeds host-"
                           f"observable op {consumer.name!r} "
                           f"({consumer.type}): the cache must stay "
                           "device-resident across decode steps "
                           "(host-sink on a cache tensor)")
            if paged and not direct_sink:
                sink = _reaches_host(op)
                if sink is not None:
                    yield (op,
                           f"shared-page cache tensor {out.name!r} "
                           f"(paged prefix cache) reaches host-"
                           f"observable op {sink.name!r} "
                           f"({sink.type}): shared pages are "
                           "refcounted device state read by every "
                           "sequence whose page table maps them; no "
                           "path from a paged cache tensor may leave "
                           "the device")
                    break


@register_lint_rule("memory-budget", ERROR)
def _rule_memory_budget(ctx):
    """A fetch closure whose statically predicted peak device memory
    (framework/cost_model: resident variables + transient liveness
    sweep) exceeds the configured budget (active only for
    ``purpose="memory"`` runs with ``ctx.memory_budget`` set —
    ``graph_lint --memory --budget BYTES``). The offline half of the
    ``ConfigProto(device_memory_budget_bytes=)`` admission check: a
    plan a budgeted Session would refuse at load fails CI here, before
    any deploy. Without fetches, the whole graph's terminal ops are
    the plan (one diagnostic)."""
    if ctx.purpose != "memory" or not ctx.memory_budget:
        return
    from ..framework import cost_model

    budget = int(ctx.memory_budget)
    plans = plan_fetch_groups(ctx)
    for label, fetches, anchor in plans:
        try:
            est = cost_model.estimate(fetches)
        except Exception:  # noqa: BLE001 — un-costable plan: skip
            continue
        if est.peak_bytes > budget:
            yield (anchor,
                   f"plan {label!r}: predicted peak device memory "
                   f"{int(est.peak_bytes)} B (resident "
                   f"{int(est.resident_bytes)} B + transient "
                   f"{int(est.peak_bytes - est.resident_bytes)} B) "
                   f"exceeds the budget {budget} B "
                   "(ConfigProto.device_memory_budget_bytes); a "
                   "budgeted Session refuses this plan at admission")


def plan_fetch_groups(ctx):
    """(label, fetches, anchor_op) groups the memory rules treat as
    one plan each: every explicit fetch is its own plan; with no
    fetches, the graph's terminal ops (no consumed outputs) form one
    whole-graph plan."""
    groups = []
    if ctx.fetches:
        for f in ctx.fetches:
            op = f if isinstance(f, ops_mod.Operation) else f.op
            groups.append((getattr(f, "name", op.name), [f], op))
        return groups
    consumed = set()
    for op in ctx.ops:
        for t in op.inputs:
            consumed.add(t)
    terminals = [op for op in ctx.ops
                 if op.outputs and not any(o in consumed
                                           for o in op.outputs)]
    if terminals:
        groups.append(("(whole graph)",
                       [o for op in terminals for o in op.outputs],
                       terminals[0]))
    return groups


@register_lint_rule("kernel-routing", NOTE)
def _rule_kernel_routing(ctx):
    """Per-op Pallas/XLA routing verdicts from the stf.kernels registry
    (active only for ``purpose="kernels"`` runs: ``graph_lint
    --kernels`` and the zoo routing gate). One NOTE per op whose type
    has a registered kernel pair, naming the verdict the registry would
    reach — ``routed`` (Pallas) or ``fallback`` + reason. Op types
    without a kernel are summarized by the CLI, not flagged per op."""
    if ctx.purpose != "kernels":
        return
    from ..kernels import registry as kreg

    mode = kreg.current_mode()
    bk = kreg.backend()
    for op in ctx.ops:
        if not kreg.has_kernel(op.type):
            continue
        rec = kreg.routing_report([op], mode=mode)[0]
        reason = rec.get("reason")
        detail = f" ({reason})" if reason and rec["verdict"] != "routed" \
            else ""
        yield (op,
               f"kernel routing [{mode}/{bk}]: {op.type} -> "
               f"{rec['verdict']}{detail}")


# ---------------------------------------------------------------------------
# numeric-risk (purpose="numerics") — the static half of the
# stf.debug.numerics runtime health plane (docs/DEBUG.md)
# ---------------------------------------------------------------------------

# ops that constrain their operand's range: a guard anywhere on the
# plumbing path between a value and a risky consumer means the author
# already handled the edge case
_NUMERIC_GUARD_TYPES = frozenset((
    "Maximum", "Minimum", "ClipByValue", "Abs", "Square", "Exp",
    "Sigmoid", "Softmax", "Softplus", "Relu", "Relu6",
))
# Exp overflows at the TOP of the range, so its guards differ: an upper
# clamp, a negation, or the log-sum-exp ``x - max(x)`` subtraction
_NUMERIC_EXP_GUARD_TYPES = frozenset((
    "Minimum", "ClipByValue", "Neg", "Sub", "LogSoftmax", "Softplus",
    "Sigmoid", "Softmax",
))
# pure shape/dtype plumbing the guard search walks through
_NUMERIC_PASSTHROUGH_TYPES = frozenset((
    "Identity", "Reshape", "Cast", "StopGradient", "Squeeze",
    "ExpandDims", "Transpose",
))
# risky op type -> (operand index to inspect, failure mode)
_NUMERIC_RISK_OPS = {
    "Log":        (0, "log of a zero/negative value is -inf/nan"),
    "Rsqrt":      (0, "rsqrt of zero is inf, of a negative value nan"),
    "Reciprocal": (0, "1/0 is inf"),
    "Div":        (1, "a zero denominator is inf (0/0 is nan)"),
    "TrueDiv":    (1, "a zero denominator is inf (0/0 is nan)"),
    "RealDiv":    (1, "a zero denominator is inf (0/0 is nan)"),
    "Exp":        (0, "exp overflows to inf past ~88 in float32 "
                      "(~11 in float16)"),
}
_NUMERIC_RISK_GUARD_HINT = {
    "Log":        "clamp with maximum(x, eps) or use log1p",
    "Rsqrt":      "add an epsilon (rsqrt(x + eps))",
    "Reciprocal": "add an epsilon or clamp the operand",
    "Div":        "add an epsilon to the denominator or use div_no_nan",
    "TrueDiv":    "add an epsilon to the denominator or use div_no_nan",
    "RealDiv":    "add an epsilon to the denominator or use div_no_nan",
    "Exp":        "subtract the row max first (log-sum-exp) or clamp",
}
_NUMERIC_REDUCE_TYPES = ("Sum", "Mean", "Prod")
_NUMERIC_LOW_MANTISSA = ("bfloat16", "float16")
# elements folded into one low-mantissa accumulator before the lost
# bits (~log2(n) of bf16's 8) start to matter
_NUMERIC_LONG_AXIS = 1024


def _numeric_guarded(tensor, guard_types) -> bool:
    """True when ``tensor`` is visibly range-restricted: produced by a
    guard op (possibly through shape/dtype plumbing), by the
    ``x + eps`` idiom (Add with a Const operand), or a literal Const.
    A conservative single-path walk — branches in the plumbing stop the
    search, so the rule under- rather than over-silences."""
    t = tensor
    for _ in range(8):
        op = t.op
        if op.type in guard_types:
            return True
        if op.type == "Const":
            return True
        if op.type in ("Add", "AddV2") and any(
                i.op.type == "Const" for i in op.inputs):
            return True  # the x + eps idiom
        if op.type in _NUMERIC_PASSTHROUGH_TYPES and op.inputs:
            t = op.inputs[0]
            continue
        return False
    return False


def _numeric_reduced_elements(op):
    """Statically known element count folded per output element by a
    reduce op, or None when any reduced dim is unknown."""
    if not op.inputs:
        return None
    shape = op.inputs[0].shape
    if shape.rank is None:
        return None
    dims = [d.value for d in shape.dims]
    axis = op.attrs.get("axis")
    if axis is None:
        reduced = dims
    else:
        axes = axis if isinstance(axis, (tuple, list)) else (axis,)
        try:
            reduced = [dims[int(a)] for a in axes]
        except IndexError:
            return None
    n = 1
    for d in reduced:
        if d is None:
            return None
        n *= int(d)
    return n


@register_lint_rule("numeric-risk", WARNING)
def _rule_numeric_risk(ctx):
    """Statically visible NaN/Inf seeds — the offline counterpart of the
    stf.debug.numerics runtime plane (active only for
    ``purpose="numerics"`` runs: ``graph_lint --numerics``):

    - a domain-restricted op (Log/Rsqrt/Reciprocal/Div/Exp) whose
      operand shows no guard on its producer path — no clamp, no
      ``x + eps``, no max-subtraction for Exp;
    - a Sum/Mean/Prod reduction over a bfloat16/float16 input folding
      >= 1024 statically known elements into one low-mantissa
      accumulator — cast up to float32 before reducing.

    Heuristic by design: a guard hidden behind a multi-input op is not
    seen (false positive), and a clamp to a still-bad range is trusted
    (false negative). The runtime plane catches what this misses."""
    if ctx.purpose != "numerics":
        return
    for op in ctx.ops:
        risk = _NUMERIC_RISK_OPS.get(op.type)
        if risk is not None and op.outputs \
                and op.outputs[0].dtype.base_dtype.is_floating:
            idx, hazard = risk
            guards = _NUMERIC_EXP_GUARD_TYPES if op.type == "Exp" \
                else _NUMERIC_GUARD_TYPES
            if idx < len(op.inputs) and not _numeric_guarded(
                    op.inputs[idx], guards):
                operand = "denominator" if idx == 1 else "operand"
                yield (op,
                       f"unguarded {op.type} {op.name!r}: {hazard}; "
                       f"no clamp/epsilon found on the {operand} "
                       f"({op.inputs[idx].op.name!r}) — "
                       f"{_NUMERIC_RISK_GUARD_HINT[op.type]}")
            continue
        if op.type in _NUMERIC_REDUCE_TYPES and op.inputs:
            dt = op.inputs[0].dtype.base_dtype.name
            if dt not in _NUMERIC_LOW_MANTISSA:
                continue
            n = _numeric_reduced_elements(op)
            if n is not None and n >= _NUMERIC_LONG_AXIS:
                yield (op,
                       f"{op.type} {op.name!r} folds {n} {dt} elements "
                       "into one low-mantissa accumulator; precision "
                       f"drifts by ~log2({n}) of its ~8 mantissa bits "
                       "— cast to float32 before the reduction")
