"""Graph → JAX lowering: the TPU-native "executor".

This replaces the reference's per-node dynamic executor
(ref: tensorflow/core/common_runtime/executor.cc ``ExecutorState::Process``,
direct_session.cc ``DirectSession::Run``). Instead of dispatching one kernel
at a time off a ready queue, we:

  1. prune the graph to the ancestors of the fetches, stopping at fed
     tensors (ref: core/graph/subgraph.cc ``RewriteGraphForExecution``),
  2. topologically order the pruned ops (data + control edges),
  3. *trace* them in order inside one function — each op's lowering rule
     emits jax/lax calls — producing a single pure function
     ``f(feeds, state, rng) -> (fetches, state')``,
  4. hand that function to jax.jit, so XLA compiles and fuses the whole step
     for the MXU (this is the tf2xla "cluster" model, ref
     tensorflow/compiler/tf2xla, promoted to the only execution path).

Statefulness is functionalized: variable reads pull from ``ctx.state``,
writes replace entries and are returned as outputs; random ops derive
per-op PRNG keys from a per-step root key (see random_seed.py).
Control-dependency ordering is preserved because lowering walks ops in
topological order over data+control edges; effects on the same variable are
thus ordered exactly when the graph orders them (the reference has the same
contract, enforced dynamically).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax

from . import graph as ops_mod
from . import op_registry
from .errors import FailedPreconditionError, InvalidArgumentError

Operation = ops_mod.Operation
Tensor = ops_mod.Tensor


# ---------------------------------------------------------------------------
# Pruning / ordering
# ---------------------------------------------------------------------------

_NATIVE_PRUNE_MIN_NODES = 512  # below this, ctypes marshalling beats C DFS


def _ancestor_set(target_ops, fed_tensors):
    """Unordered dependency closure of targets (cheap BFS; O(|ancestors|),
    independent of total graph size)."""
    seen = set()
    work = list(target_ops)
    while work:
        op = work.pop()
        if op in seen:
            continue
        seen.add(op)
        for t in op.inputs:
            if t not in fed_tensors and t.op not in seen:
                work.append(t.op)
        for c in op.control_inputs:
            if c not in seen:
                work.append(c)
    return seen


def prune(target_ops: Sequence[Operation],
          fed_tensors: Set[Tensor]) -> List[Operation]:
    """Ops needed to compute ``target_ops`` given ``fed_tensors`` are
    supplied externally. Returns a deterministic topological order
    (data + control edges). Large fetch subgraphs go through the native
    C++ pruner (runtime_cc/graph.cc); this Python DFS is the fallback and
    the cycle-error path. Gating keys on the *ancestor* count, not total
    graph size, so a small fetch in a huge graph stays O(|ancestors|)."""
    if target_ops:
        anc = _ancestor_set(target_ops, fed_tensors)
        if len(anc) >= _NATIVE_PRUNE_MIN_NODES:
            native_order = _prune_native(anc, target_ops, fed_tensors)
            if native_order is not None:
                return native_order
    order: List[Operation] = []
    state: Dict[Operation, int] = {}  # 0=visiting, 1=done

    def deps(op: Operation):
        for t in op.inputs:
            if t not in fed_tensors:
                yield t.op
        yield from op.control_inputs

    # Iterative DFS postorder for deep graphs.
    for root in target_ops:
        if state.get(root) == 1:
            continue
        stack: List[Tuple[Operation, Any]] = [(root, None)]
        while stack:
            op, it = stack[-1]
            if it is None:
                if state.get(op) == 1:
                    stack.pop()
                    continue
                if state.get(op) == 0:
                    stack.pop()
                    continue
                state[op] = 0
                it = iter(list(deps(op)))
                stack[-1] = (op, it)
            advanced = False
            for d in it:
                if state.get(d) is None:
                    stack.append((d, None))
                    advanced = True
                    break
                if state.get(d) == 0 and d is not op:
                    cycle = " -> ".join(o.name for o, _ in stack[-5:])
                    raise InvalidArgumentError(
                        None, op, f"Graph cycle detected near: {cycle}")
            if not advanced:
                state[op] = 1
                order.append(op)
                stack.pop()
    return order


def _prune_native(ancestors, target_ops, fed_tensors):
    """Flat-array edge list over the ancestor region -> runtime_cc
    StfPruneToposort. Returns None (falling back to the Python DFS) when
    the native library is absent or reports a cycle — the Python path
    raises the contextful error."""
    try:
        from ..runtime import native
    except Exception:
        return None
    if not native.available():
        return None
    import numpy as np

    # deterministic node order: graph insertion order via op id
    region = sorted(ancestors, key=lambda op: op._id)
    ids = {op: i for i, op in enumerate(region)}
    edges = []
    for op, i in ids.items():
        for t in op.inputs:
            if t not in fed_tensors:
                edges.append((ids[t.op], i))
        for c in op.control_inputs:
            edges.append((ids[c], i))
    edge_arr = (np.asarray(edges, dtype=np.int32)
                if edges else np.empty((0, 2), np.int32))
    order = native.prune_toposort(
        len(region), edge_arr, [ids[op] for op in target_ops])
    if order is None:
        return None
    return [region[i] for i in order]


def ancestors_between(xs: Sequence[Tensor], ys: Sequence[Tensor]
                      ) -> Tuple[List[Operation], Set[Tensor]]:
    """Ops on a data path from any x to any y, in topological order, plus the
    subset of ``xs`` actually connected to ``ys``. Used by the symbolic
    gradient lowering to re-trace just the differentiated slice (everything
    off-path is captured from the already-lowered environment; XLA CSEs the
    replayed on-path ops against the originals)."""
    xset = set(xs)
    desc: Set[Operation] = set()
    work: List[Operation] = []
    for t in xs:
        work.extend(t.consumers())
    while work:
        op = work.pop()
        if op in desc:
            continue
        desc.add(op)
        for out in op.outputs:
            work.extend(out.consumers())
    anc_order = prune([y.op for y in ys], fed_tensors=xset)
    path = [op for op in anc_order if op in desc]
    path_set = set(path)
    connected = {x for x in xs
                 if any(y is x for y in ys)
                 or any(c in path_set for c in x.consumers())}
    return path, connected


# ---------------------------------------------------------------------------
# Lowering context
# ---------------------------------------------------------------------------

class LoweringContext:
    """Carries the functionalized state while tracing a pruned subgraph.

    state:  var name -> current jax value (mutated as Assign ops lower).
    written: var names assigned during this step (become donated outputs).
    rng_root: per-step PRNG key; ops derive theirs via fold_in.
    env:    Tensor -> traced jax value.
    host:   True when executing the host stage (no jax tracing).
    """

    def __init__(self, state: Dict[str, Any], rng_root, feeds=None,
                 host=False, session=None):
        self.state = state
        self.written: Set[str] = set()
        self.var_metadata: Dict[str, Any] = {}
        self.rng_root = rng_root
        self.env: Dict[Tensor, Any] = dict(feeds or {})
        self.host = host
        self.session = session
        # kernel-registry routing mode for ops traced under this context
        # (stf.kernels): ConfigProto(kernel_registry=...) when the
        # session set one, else None = the process default. execute_ops
        # activates it thread-locally around the trace loop, so every
        # registry decision inside this plan (including FuncGraph bodies,
        # shard_map'd jax helpers, and SymbolicGradient replays) sees the
        # session's mode.
        self.kernel_mode = None
        if session is not None:
            cfg = getattr(session, "_config", None)
            self.kernel_mode = getattr(cfg, "kernel_registry", None) \
                if cfg is not None else None
        self.sharding_env = None  # set by parallel lowering
        self.in_control_flow = False
        self.in_shard_map = False
        # True while tracing the SymbolicGradient forward replay: op
        # lowerings may pick a differentiable form (e.g. a bounded While
        # lowers to a masked lax.scan instead of lax.while_loop)
        self.differentiable = False
        # CSE alias map from the plan-time optimizer: duplicate tensor ->
        # canonical tensor; consulted on every input lookup
        self.alias: Dict[Tensor, Tensor] = {}
        # per-plan FuncGraph body plans (optimizer._plan_function_bodies):
        # fg -> (op_list, const_env, alias). Scoped to THIS compiled
        # plan — never stashed on the FuncGraph, because which captures
        # are constant depends on the plan's feed set.
        self.func_plans: Dict[Any, Any] = {}
        self._rng_cache: Dict[int, Any] = {}
        # CheckNumerics flags gathered during trace: [(message, bool value)];
        # the Session fetches them with the step and raises host-side
        self.numeric_checks: List[Tuple[str, Any]] = []

    def child(self, env: Dict[Tensor, Any],
              in_control_flow: Optional[bool] = None) -> "LoweringContext":
        c = LoweringContext.__new__(LoweringContext)
        c.kernel_mode = self.kernel_mode
        c.state = self.state
        c.written = self.written
        c.var_metadata = self.var_metadata
        c.rng_root = self.rng_root
        c.env = env
        c.host = self.host
        c.session = self.session
        c.sharding_env = self.sharding_env
        c.in_control_flow = (self.in_control_flow if in_control_flow is None
                             else in_control_flow)
        c.in_shard_map = self.in_shard_map
        c.differentiable = self.differentiable
        c.alias = self.alias
        c.func_plans = self.func_plans
        c._rng_cache = self._rng_cache
        c.numeric_checks = self.numeric_checks
        return c

    # -- state ---------------------------------------------------------------
    def read_var(self, name: str, op=None):
        if name not in self.state:
            raise FailedPreconditionError(
                None, op,
                f"Attempting to use uninitialized variable {name!r}. "
                "Run stf.global_variables_initializer() first.")
        return self.state[name]

    def write_var(self, name: str, value):
        if self.in_control_flow:
            raise InvalidArgumentError(
                None, None,
                f"Variable {name!r} is assigned inside a cond/while/scan "
                "body. XLA structured control flow cannot write cross-step "
                "state from a branch; carry the value as a loop variable and "
                "assign it after the loop (TPU-native pattern).")
        self.state[name] = value
        self.written.add(name)

    def var_exists(self, name: str) -> bool:
        return name in self.state

    # -- rng -----------------------------------------------------------------
    def rng_for(self, op: Operation):
        """Per-op key: deterministic within a step, so jax.vjp forward replay
        reuses the same stream (dropout masks match fwd/bwd) and XLA CSEs the
        replayed ops."""
        from . import random_seed

        fold = random_seed.fold_in_value(op)
        if fold not in self._rng_cache:
            import jax

            self._rng_cache[fold] = jax.random.fold_in(self.rng_root, fold)
        return self._rng_cache[fold]

    # -- values --------------------------------------------------------------
    def value_of(self, tensor: Tensor):
        tensor = self.alias.get(tensor, tensor)
        if tensor in self.env:
            return self.env[tensor]
        raise InternalLoweringError(
            f"Tensor {tensor.name} has no value in the lowering env — "
            "pruning/ordering bug.")


class InternalLoweringError(Exception):
    pass


def check_step_read_write_races(
        op_list: Sequence[Operation],
        alias: Optional[Dict[Tensor, Tensor]] = None) -> None:
    """SURVEY §5 ordering detector — now a thin wrapper over the
    stf.analysis variable-hazard engine (analysis/hazards.py), which
    generalizes the original read-your-write check to full RAW/WAR/WAW
    detection over the op registry's declared effect sets and adds the
    warn/auto_deps modes. Kept for direct callers: raises
    InvalidArgumentError on any enforceable unordered hazard, exactly as
    before. Bare-fetch reads stay exempt (observations with documented
    topological-position semantics, see state_ops.py ReadVariable)."""
    from ..analysis import hazards

    hazards.check_plan(op_list, alias, mode="raise")


def execute_ops(ctx: LoweringContext, op_list: Sequence[Operation],
                fed: Optional[Set[Tensor]] = None):
    """Trace ops in topological order, populating ctx.env.

    The kernel-registry mode (stf.kernels) is activated thread-locally
    for the duration of the trace: op lowerings — and any jax-level
    helpers they call under shard_map/scan/vjp — route Pallas vs XLA
    under the session's ConfigProto(kernel_registry=...) (or the
    process default when the context carries None).

    ``fed`` is accepted for call-site compatibility only: fed-tensor
    pruning happened in prune(), and every fed tensor is already bound
    in ctx.env before the trace starts."""
    from ..kernels import registry as _kernels
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    # GSPMD partitions whatever traces under a multi-device mesh
    # outside shard_map — and cannot partition a Mosaic kernel
    auto = (mesh is not None and mesh.size > 1
            and not getattr(ctx, "in_shard_map", False))
    with _kernels.activate(ctx.kernel_mode, auto_partitioned=auto):
        _execute_ops_inner(ctx, op_list)


def _execute_ops_inner(ctx: LoweringContext,
                       op_list: Sequence[Operation]):
    for op in op_list:
        already = all(o in ctx.env for o in op.outputs) and op.outputs
        # CapturedInput/FuncArg are bound values, not effects: when a branch
        # returns a capture directly, its op is a prune target but its value
        # is already in env — skip despite the stateful registration.
        if already and (not op.op_def.is_stateful
                        or op.type in ("CapturedInput", "FuncArg")):
            continue
        input_vals = []
        for t in op.inputs:
            t = ctx.alias.get(t, t)
            input_vals.append(ctx.env[t] if t in ctx.env else ctx.value_of(t))
        if ctx.host:
            outputs = op.op_def.lower(ctx, op, input_vals)
        else:
            # the XLA ops this traces carry the stf op that made them
            # (HLO metadata op_name; read in a profiler trace by hand)
            with jax.named_scope(op.name):
                outputs = op.op_def.lower(ctx, op, input_vals)
        if len(outputs) != len(op.outputs):
            raise InternalLoweringError(
                f"Op {op.name} ({op.type}) lowered to {len(outputs)} outputs, "
                f"graph says {len(op.outputs)}")
        for t, v in zip(op.outputs, outputs):
            ctx.env[t] = v


def lower_func_graph(ctx: LoweringContext, fg: "ops_mod.FuncGraph",
                     arg_values: Sequence[Any],
                     capture_values: Sequence[Any]) -> List[Any]:
    """Lower a FuncGraph body given values for its declared inputs and its
    captures; returns values for fg.outputs. Used by cond/while/scan/function
    lowering.

    When the plan-time optimizer recorded an optimized plan for this
    body in ctx.func_plans (optimizer._plan_function_bodies), that plan
    drives the trace instead of a fresh prune: constant-folded interior
    values seed the env as host constants, CSE-duplicate tensors resolve
    through the body's alias map, and DCE'd ops never trace — so
    in-body fold/CSE wins apply on EVERY iteration of a while/scan
    body."""
    env: Dict[Tensor, Any] = {}
    if len(arg_values) != len(fg.inputs):
        raise InternalLoweringError(
            f"FuncGraph {fg.func_name}: {len(arg_values)} args for "
            f"{len(fg.inputs)} inputs")
    for t, v in zip(fg.inputs, arg_values):
        env[t] = v
    for (outer, inner), v in zip(fg.captures, capture_values):
        env[inner] = v
    child = ctx.child(env, in_control_flow=True)
    plan = ctx.func_plans.get(fg)
    if plan is not None:
        needed, body_consts, body_alias = plan
        if body_alias:
            # replace (never mutate) the shared alias dict
            merged = dict(child.alias)
            merged.update(body_alias)
            child.alias = merged
        for t, v in body_consts.items():
            env.setdefault(t, v)  # bound args/captures win over seeds
    else:
        needed = prune([t.op for t in fg.outputs],
                       fed_tensors=set(env.keys()))
    execute_ops(child, needed, fed=set(env.keys()))
    return [child.value_of(t) for t in fg.outputs]


def capture_values_for(ctx: LoweringContext, fg: "ops_mod.FuncGraph") -> List[Any]:
    """Resolve a FuncGraph's captured outer tensors against the current env."""
    vals = []
    for outer, _ in fg.captures:
        vals.append(ctx.value_of(outer))
    return vals
