"""Graph optimizer passes: constant folding, CSE, DCE, layout, LICM —
driven by a function-aware PassManager
(ref: tensorflow/core/common_runtime/constant_folding.cc,
core/graph/optimizer_cse.cc, core/grappler/ — grappler's
meta_optimizer.cc processes FunctionDef bodies; stf's passes recurse the
same way into the FuncGraphs that cond/while/scan/defun store in node
attrs).

On TPU most of this work belongs to XLA — the whole pruned subgraph
compiles as one program and XLA constant-folds/CSEs/fuses HLO. These
passes run *before tracing* on the GraphDef level, where they still pay:
- smaller graphs trace faster (Session compile latency),
- exported GraphDefs / SavedModels shrink,
- AOT keys stabilize (CSE canonicalizes),
- layout conversions around NCHW image ops cancel — including inside
  cond branches and while/scan bodies, where a per-op transpose is paid
  once per LOOP ITERATION if left in place.
They operate on the GraphDef-JSON dict (framework/graph_io.py), returning
a new dict — the Graph IR itself is immutable-append by design.

Function-op anatomy (who declares what): ops that embed FuncGraph bodies
register a FunctionOpSpec via ``register_function_op`` (see
ops/control_flow_ops.py Cond/While, ops/functional_ops.py
MapFn/Scan/Foldl, framework/function.py GraphFunctionCall /
RecomputeGradCall). The spec names each body attr, locates the body's
captured inputs inside the op's input list, and says whether the body
re-executes per iteration (→ loop-invariant code motion is profitable)
— the single place future rewrites (quantize_weights, fuse_convolutions)
plug into. Rewritten bodies always keep their signature: same
inputs/outputs arity and dtypes, captures only ever APPENDED (LICM), so
importers, Session executable-cache keys, and framework/lowering.py stay
valid.
"""

from __future__ import annotations

import copy
import functools
import time
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

import numpy as np

from . import dtypes as dtypes_mod
from . import op_registry
from . import tensor_shape as shape_mod
from ..platform import monitoring

# per-pass observability (ref: grappler's meta_optimizer logs
# per-optimizer wall time and "graph rewritten" counts the same way)
_metric_pass_seconds = monitoring.Sampler(
    "/stf/graph/optimizer/pass_seconds",
    monitoring.ExponentialBuckets(1e-6, 4.0, 16),
    "wall seconds per PassManager pass invocation", "pass")
_metric_pass_runs = monitoring.Counter(
    "/stf/graph/optimizer/pass_runs",
    "PassManager pass invocations", "pass")
_metric_pass_rewrites = monitoring.Counter(
    "/stf/graph/optimizer/pass_rewrites",
    "PassManager pass invocations that changed the graph", "pass")

_metric_folded_ops = monitoring.Counter(
    "/stf/graph/optimizer/plan_folded_ops",
    "ops Session plans evaluated at plan time instead of lowering")


@functools.lru_cache(None)
def _fold_device():
    """The CPU device constant folding evaluates on, whatever the
    default backend: folded values are NumPy constants of the plan, so
    the chip must run the same graph the CPU tests pin. Asked for once;
    a process with no CPU backend raises here, not at every fold."""
    import jax

    return jax.devices("cpu")[0]


def _fold(od, args, attrs):
    """``od.pure_fn`` on constant (NumPy) inputs, or None where the op
    cannot be evaluated from such values — it then stays in the plan
    and lowers like any other op. A device, compile or runtime failure
    is not such a case and propagates."""
    import jax

    with jax.default_device(_fold_device()):
        try:
            return od.pure_fn(*args, **attrs)
        except jax.errors.JaxRuntimeError:
            raise
        except Exception:  # noqa: BLE001 — the op's own argument errors
            return None


_FOLDABLE_BLOCKLIST = {"Placeholder", "PlaceholderWithDefault", "Const",
                       "VariableV2", "VarRead", "Assign"}


def _tensor_ref(name: str) -> Tuple[str, int]:
    if ":" in name:
        node, idx = name.rsplit(":", 1)
        return node, int(idx)
    return name, 0


def _is_pure(node) -> bool:
    try:
        od = op_registry.get(node["op"])
    except KeyError:
        return False
    return od.pure_fn is not None and not od.is_stateful


# ---------------------------------------------------------------------------
# function-aware pass infrastructure
# ---------------------------------------------------------------------------

class FunctionOpSpec:
    """How an op type embeds FuncGraph bodies (registered by the op's
    module via ``register_function_op``).

    ``bodies(attrs, n_inputs)`` returns one descriptor per body graph:
      attr:       node attr holding the FuncGraph,
      start:      index in the op's input list where this body's captured
                  inputs begin,
      count:      how many captured inputs belong to this body,
      hoist:      True when the body re-executes per iteration (while
                  cond/body, scan/map/fold fns) so hoisting
                  loop-invariant subexpressions out pays,
      count_attr: node attr counting this body's captures — bumped when
                  LICM appends one; None when the captures are the
                  trailing inputs (count is implicit).

    ``mode`` drives cost attribution (framework/cost_model.py):
      "loop"   — every body runs ``trip(attrs, inputs)`` times,
      "branch" — exactly one body runs per execution,
      "call"   — bodies run once, inline.
    """

    __slots__ = ("op_type", "bodies", "mode", "trip")

    def __init__(self, op_type, bodies, mode="call", trip=None):
        self.op_type = op_type
        self.bodies = bodies
        self.mode = mode
        self.trip = trip


_FUNCTION_OPS: Dict[str, FunctionOpSpec] = {}


def register_function_op(op_type: str, bodies: Callable, mode: str = "call",
                         trip: Optional[Callable] = None) -> FunctionOpSpec:
    spec = FunctionOpSpec(op_type, bodies, mode=mode, trip=trip)
    _FUNCTION_OPS[op_type] = spec
    return spec


def function_op_spec(op_type: str) -> Optional[FunctionOpSpec]:
    return _FUNCTION_OPS.get(op_type)


def _node_bodies(node: Dict) -> List[Tuple[Dict, Dict]]:
    """(descriptor, body_graph_dict) per FuncGraph attr of a GraphDef
    node. Body dicts are graph_io._funcgraph_to_dict shaped: the pass
    functions treat them as GraphDefs with extra inputs/outputs/captures
    keys (all preserved by the deepcopy-and-replace-"node" idiom; each
    recursion level re-deepcopies its bodies — accepted cost, since body
    dicts are small and nesting is shallow in practice)."""
    spec = _FUNCTION_OPS.get(node.get("op"))
    if spec is None:
        return []
    attrs = node.get("attr", {})
    try:
        descs = spec.bodies(attrs, len(node.get("input", [])))
    except (KeyError, TypeError):
        return []
    out = []
    for d in descs:
        enc = attrs.get(d["attr"])
        if isinstance(enc, dict) and enc.get("__kind__") == "funcgraph":
            out.append((d, enc["v"]))
    return out


def _body_keep(body: Dict) -> List[str]:
    """The body's signature: its output refs plus every FuncArg /
    CapturedInput node — lowering binds them positionally, so no pass may
    drop or rename them."""
    keep = list(body.get("outputs", []))
    keep += [n["name"] for n in body.get("node", [])
             if n.get("op") in ("FuncArg", "CapturedInput")]
    return keep


def _signature_broken(old: Dict, new: Dict) -> bool:
    """A rewritten body must keep its calling convention: identical input
    refs, same output arity, the old captures as a prefix of the new
    (LICM appends), and every signature ref still resolvable."""
    if list(old.get("inputs", [])) != list(new.get("inputs", [])):
        return True
    if len(old.get("outputs", [])) != len(new.get("outputs", [])):
        return True
    old_inner = [c[1] for c in old.get("captures", [])]
    new_inner = [c[1] for c in new.get("captures", [])]
    if new_inner[:len(old_inner)] != old_inner:
        return True
    names = {n["name"] for n in new.get("node", [])}
    need = {_tensor_ref(r)[0] for r in
            list(new.get("inputs", [])) + list(new.get("outputs", []))
            + new_inner}
    return not need <= names


def _set_body(node: Dict, desc: Dict, new_body: Dict,
              old_body: Optional[Dict] = None) -> None:
    if old_body is not None and _signature_broken(old_body, new_body):
        return  # defensive: a signature-breaking rewrite is discarded
    node["attr"][desc["attr"]] = {"__kind__": "funcgraph", "v": new_body}


def _uniq_in(used: Set[str], base: str) -> str:
    name = base
    k = 1
    while name in used:
        name = f"{base}_{k}"
        k += 1
    used.add(name)
    return name


def dead_code_elimination(graph_def: Dict, keep: List[str]) -> Dict:
    """Drop nodes not reachable (as dependencies) from ``keep`` node/tensor
    names (ref: core/graph/algorithm.cc PruneForReverseReachability).
    Recurses into FuncGraph bodies of surviving nodes, keeping each
    body's signature (inputs/captures/outputs) alive."""
    nodes = {n["name"]: n for n in graph_def["node"]}
    work = [_tensor_ref(k)[0] for k in keep]
    live: Set[str] = set()
    while work:
        name = work.pop()
        if name in live or name not in nodes:
            continue
        live.add(name)
        n = nodes[name]
        work.extend(_tensor_ref(i)[0] for i in n.get("input", []))
        work.extend(n.get("control_input", []))
    out = copy.deepcopy(graph_def)
    out["node"] = [n for n in out["node"] if n["name"] in live]
    for n in out["node"]:
        for d, b in _node_bodies(n):
            _set_body(n, d, dead_code_elimination(b, _body_keep(b)), b)
    return out


def common_subexpression_elimination(graph_def: Dict,
                                     keep: Optional[List[str]] = None) -> Dict:
    """Merge pure nodes with identical (op, inputs, attrs)
    (ref: core/graph/optimizer_cse.cc). Nodes named in ``keep`` are never
    merged away — callers fetch them by name after import. FuncGraph
    bodies are CSE'd recursively with their signature kept — duplicate
    subexpressions inside while/scan bodies cost once per ITERATION, so
    this is where CSE pays most."""
    keep_names: Set[str] = {_tensor_ref(k)[0] for k in (keep or [])}
    out = copy.deepcopy(graph_def)
    replace: Dict[str, str] = {}  # old node name -> canonical node name
    seen: Dict[str, str] = {}  # signature -> canonical name
    kept = []
    for n in out["node"]:
        for d, b in _node_bodies(n):
            _set_body(n, d, common_subexpression_elimination(
                b, keep=_body_keep(b)), b)
        # rewrite inputs through earlier merges first
        n["input"] = [_rewrite(i, replace) for i in n.get("input", [])]
        n["control_input"] = [replace.get(c, c)
                              for c in n.get("control_input", [])]
        if not _is_pure(n) or n.get("control_input"):
            kept.append(n)
            continue
        sig = repr((n["op"], n["input"],
                    sorted((k, repr(v)) for k, v in
                           n.get("attr", {}).items())))
        if sig in seen and n["name"] not in keep_names:
            replace[n["name"]] = seen[sig]
        else:
            if sig not in seen:
                seen[sig] = n["name"]
            kept.append(n)
    out["node"] = kept
    return out


def _rewrite(tensor_name: str, replace: Dict[str, str]) -> str:
    node, idx = _tensor_ref(tensor_name)
    if node in replace:
        return f"{replace[node]}:{idx}"
    return tensor_name


_SHAPE_OPS = {"Shape", "Size", "Rank"}


def constant_folding(graph_def: Dict,
                     seed_values: Optional[Dict[str, Any]] = None) -> Dict:
    """Evaluate pure nodes whose inputs are all Consts, replacing them with
    Const nodes (ref: core/common_runtime/constant_folding.cc). Uses each
    op's registered jax pure_fn on host numpy values — the same semantics
    the compiled program would have. Shape/Size/Rank of statically-shaped
    producers fold from the shape alone (grappler's
    shape-materialization), without needing a constant input value.

    Recurses into FuncGraph bodies with cross-boundary constant
    propagation: a constant captured by a cond branch / while body is
    seeded into the body's fold via ``seed_values`` (node name → value
    for that node's output 0 — captures are loop-invariant, so the seed
    holds on every iteration). Seeded CapturedInput nodes are never
    themselves replaced (the body signature must survive), only their
    consumers fold."""
    from . import graph_io

    out = copy.deepcopy(graph_def)
    values: Dict[str, List[Any]] = {}  # node name -> output values
    for name, v in (seed_values or {}).items():
        values[name] = [np.asarray(v)]
    specs_by_name: Dict[str, Any] = {n["name"]: n.get("output_specs")
                                     for n in out["node"]}
    for n in out["node"]:
        if n["op"] == "Const":
            v = graph_io._decode_attr(n.get("attr", {}).get("value"))
            if v is not None:
                values[n["name"]] = [np.asarray(v)]
    new_nodes = []
    for n in out["node"]:
        name = n["name"]
        bodies = _node_bodies(n)
        if bodies:
            # cross-boundary propagation: captures whose outer producer
            # already has a known value seed the body's fold
            for d, b in bodies:
                inner_seeds: Dict[str, Any] = {}
                for i, cap in enumerate(b.get("captures", [])):
                    idx = d["start"] + i
                    if idx >= len(n.get("input", [])):
                        break
                    src, k = _tensor_ref(n["input"][idx])
                    if src in values and k < len(values[src]):
                        inner_seeds[_tensor_ref(cap[1])[0]] = values[src][k]
                _set_body(n, d, constant_folding(b, seed_values=inner_seeds),
                          b)
            new_nodes.append(n)
            continue
        if n["op"] == "Const" or not _is_pure(n) or n.get("control_input"):
            new_nodes.append(n)
            continue
        if n["op"] in _SHAPE_OPS and n.get("input"):
            src, idx = _tensor_ref(n["input"][0])
            specs = specs_by_name.get(src)
            sh = (specs[idx][0] if specs and idx < len(specs) else None)
            if isinstance(sh, list) and all(
                    isinstance(d, int) for d in sh):
                from . import graph_io

                ot = graph_io._decode_attr(
                    n.get("attr", {}).get("out_type"))
                # out_type through the 64-bit narrowing: a folded Shape
                # must carry the dtype the runtime path computes
                np_dt = (dtypes_mod.narrowed_if_no_x64(ot).np_dtype
                         if ot is not None else np.int32)
                if n["op"] == "Shape":
                    arr = np.asarray(sh, np_dt)
                elif n["op"] == "Size":
                    arr = np.asarray(int(np.prod(sh)) if sh else 1,
                                     np_dt)
                else:
                    arr = np.asarray(len(sh), np.int32)  # Rank: int32
                values[name] = [arr]
                new_nodes.append({
                    "name": name, "op": "Const", "input": [],
                    "control_input": [], "device": n.get("device", ""),
                    "attr": {"value": graph_io._encode_attr(arr),
                             "dtype": graph_io._encode_attr(
                                 dtypes_mod.as_dtype(str(arr.dtype)))},
                    "output_specs": [[list(arr.shape), str(arr.dtype)]],
                })
                continue
        in_refs = [_tensor_ref(i) for i in n.get("input", [])]
        if not in_refs or not all(r[0] in values for r in in_refs):
            new_nodes.append(n)
            continue
        od = op_registry.get(n["op"])
        attrs = {k: graph_io._decode_attr(v)
                 for k, v in n.get("attr", {}).items()
                 if not k.startswith("_") and k != "dtype"}
        result = _fold(od, [values[r[0]][r[1]] for r in in_refs], attrs)
        if result is None:
            new_nodes.append(n)  # not foldable: the node stays
            continue
        outs = (list(result) if isinstance(result, (list, tuple))
                else [result])
        outs = [np.asarray(o) for o in outs]
        values[name] = outs
        if len(outs) == 1:  # replace with a Const node
            spec = n.get("output_specs") or [[list(outs[0].shape),
                                              str(outs[0].dtype)]]
            folded = {
                "name": name, "op": "Const", "input": [],
                "control_input": [], "device": n.get("device", ""),
                "attr": {"value": graph_io._encode_attr(outs[0]),
                         "dtype": graph_io._encode_attr(
                             dtypes_mod.as_dtype(spec[0][1]))},
                "output_specs": spec,
            }
            new_nodes.append(folded)
        else:
            new_nodes.append(n)
    out["node"] = new_nodes
    return out


# ---------------------------------------------------------------------------
# layout optimization (ref: core/grappler/optimizers/layout_optimizer.cc)
# ---------------------------------------------------------------------------

_NCHW_TO_NHWC = (0, 2, 3, 1)
_NHWC_TO_NCHW = (0, 3, 1, 2)

# image ops that carry a data_format attr; "vec" attrs are per-dimension
# 4-vectors (strides/ksize/dilations) permuted along with the layout
_LAYOUT_OPS = {
    "Conv2D": ("strides", "dilations"),
    "DepthwiseConv2dNative": ("strides", "dilations"),
    "MaxPool": ("strides", "ksize"),
    "AvgPool": ("strides", "ksize"),
    "FusedBatchNorm": (),
    "BiasAdd": (),
}

# rank-preserving elementwise ops a transpose can move through unchanged
_LAYOUT_AGNOSTIC = {
    "Relu", "Relu6", "Elu", "Selu", "LeakyRelu", "Tanh", "Sigmoid",
    "Softplus", "Abs", "Neg", "Square", "Sqrt", "Rsqrt", "Exp", "Log",
    "Identity", "Add", "AddV2", "Sub", "Mul", "RealDiv", "Maximum",
    "Minimum", "SquaredDifference",
}


def _compose_perm(p2, p1):
    """perm of transpose(transpose(x, p2), p1)."""
    return tuple(p2[i] for i in p1)


def layout_optimization(graph_def: Dict,
                        keep: Optional[List[str]] = None) -> Dict:
    """Rewrite NCHW image ops to NHWC globally (ref: grappler
    layout_optimizer.cc). TPU rationale: the per-op lowering honors NCHW
    by transposing around EVERY conv/pool/bn call; this pass instead
    converts the ops once and pushes the layout conversions to the
    subgraph boundary, cancelling interior transpose pairs — an NCHW
    ResNet block lowers with exactly two transposes (one in, one out).

    Three phases: (1) convert each NCHW op to NHWC with explicit
    boundary transposes; (2) push NHWC→NCHW transposes down through
    rank-preserving elementwise ops (so pairs become adjacent);
    (3) cancel adjacent inverse pairs, then DCE.
    Touched nodes drop their output_specs — the importer's shape
    inference recomputes them in the new layout.
    """
    from . import graph_io

    out = copy.deepcopy(graph_def)
    nodes: List[Dict] = out["node"]
    by_name = {n["name"]: n for n in nodes}

    def _uniq(base):
        name = base
        k = 1
        while name in by_name:
            name = f"{base}_{k}"
            k += 1
        return name

    def _attr(n, key, default=None):
        v = n.get("attr", {}).get(key)
        return default if v is None else graph_io._decode_attr(v)

    def _perm_of(n):
        p = _attr(n, "perm")
        return tuple(p) if p is not None else ()

    enc = graph_io._encode_attr

    # ---- phase 0: recurse into FuncGraph bodies (cond branches, while
    # bodies, scan/map fns, defun bodies). Signature preserved: the
    # name-swap trick keeps every body-internal AND boundary ref meaning
    # NCHW data, so loop-carried vars keep their layout — interior
    # transpose pairs cancel per iteration, and push_loop_layout (run
    # after this pass) moves the remaining boundary pair out of while
    # loops whose body provably maps NHWC→NHWC.
    for n in nodes:
        for d, b in _node_bodies(n):
            _set_body(n, d, layout_optimization(b, keep=_body_keep(b)), b)

    # ---- phase 1: per-op conversion (in topo order, so a converted
    # producer's boundary transpose is visible to later converts).
    # NAME SWAP: the converted op is renamed "<name>/nhwc" and the
    # inverse output transpose takes the ORIGINAL name, so every
    # existing reference — graph edges AND by-name fetches — still sees
    # NCHW data without any rewiring. Extra outputs (FusedBatchNorm's
    # per-channel mean/var) are layout-free and rewired to the renamed
    # node directly — but only graph-INTERNAL edges can be rewired, so a
    # multi-output op with an externally visible ":k" (k>0) ref in
    # ``keep`` is left unconverted (":0" keeps work: the shim serves
    # them — this is what lets a FusedBatchNorm that IS a cond-branch
    # output still convert).
    keep_names = {_tensor_ref(k)[0] for k in (keep or [])}
    keep_extra_out = {_tensor_ref(k)[0] for k in (keep or [])
                      if _tensor_ref(k)[1] > 0}
    new_nodes: List[Dict] = []
    rewire: Dict[str, str] = {}  # "orig:k" (k>0) -> "<orig>/nhwc:k"
    converted = []
    for n in nodes:
        if n["op"] not in _LAYOUT_OPS or _attr(n, "data_format") != "NCHW":
            new_nodes.append(n)
            continue
        if len(n.get("output_specs") or []) > 1 \
                and n["name"] in keep_extra_out:
            # a by-name fetch references output k>0, which the
            # single-output transpose shim cannot serve
            new_nodes.append(n)
            continue
        orig = n["name"]
        vec_attrs = _LAYOUT_OPS[n["op"]]
        n["attr"]["data_format"] = "NHWC"
        for va in vec_attrs:
            v = _attr(n, va)
            if isinstance(v, (list, tuple)) and len(v) == 4:
                n["attr"][va] = enc(tuple((v[0], v[2], v[3], v[1])))
        n_specs = len(n.get("output_specs") or [])
        n.pop("output_specs", None)
        del by_name[orig]
        n["name"] = _uniq(orig + "/nhwc")
        by_name[n["name"]] = n
        for k in range(1, n_specs):
            rewire[f"{orig}:{k}"] = f"{n['name']}:{k}"
        # transpose the data input (input 0 for every op here); chained
        # converted producers resolve automatically: their original name
        # now names their inverse transpose
        t_in = {
            "name": _uniq(orig + "/nchw_to_nhwc"),
            "op": "Transpose", "input": [n["input"][0]],
            "control_input": [], "device": n.get("device", ""),
            "attr": {"perm": enc(_NCHW_TO_NHWC)},
        }
        by_name[t_in["name"]] = t_in
        new_nodes.append(t_in)
        n["input"] = [t_in["name"] + ":0"] + list(n["input"][1:])
        new_nodes.append(n)
        # inverse transpose under the ORIGINAL name serves consumers
        t_out = {
            "name": orig,
            "op": "Transpose", "input": [n["name"] + ":0"],
            "control_input": [], "device": n.get("device", ""),
            "attr": {"perm": enc(_NHWC_TO_NCHW)},
        }
        by_name[orig] = t_out
        new_nodes.append(t_out)
        converted.append(orig)
    if rewire:
        conv_set = set(converted)
        for n in new_nodes:
            if n["name"] in conv_set:  # the t_out shims keep ":0" inputs
                continue
            n["input"] = [rewire.get(i, i) for i in n.get("input", [])]
    nodes = new_nodes
    by_name = {n["name"]: n for n in nodes}

    # ---- phase 2: push NHWC->NCHW transposes through elementwise ----
    def _is_inv_transpose(ref):
        node, idx = _tensor_ref(ref)
        m = by_name.get(node)
        return (m is not None and m["op"] == "Transpose" and idx == 0
                and _perm_of(m) == _NHWC_TO_NCHW)

    def _rank4_ref(ref):
        """Producer output spec says rank 4 (safe to forward-transpose)."""
        node, idx = _tensor_ref(ref)
        m = by_name.get(node)
        specs = (m or {}).get("output_specs")
        if not specs or idx >= len(specs):
            return False
        sh = specs[idx][0]
        return isinstance(sh, list) and len(sh) == 4

    changed = True
    while changed:
        changed = False
        addenda = []
        for n in nodes:
            if n["op"] not in _LAYOUT_AGNOSTIC or n.get("control_input"):
                continue
            ins = n.get("input", [])
            # every input must be pushable: already NHWC behind an inverse
            # transpose, or a rank-4 tensor we can forward-transpose here
            # (identity shortcuts: Add(bn_out, x) — the x transpose then
            # CSEs with the first conv's input transpose). Same-rank
            # inputs only: broadcasting scalars would change meaning.
            if not ins or not any(_is_inv_transpose(i) for i in ins):
                continue
            if not all(_is_inv_transpose(i) or _rank4_ref(i)
                       for i in ins):
                continue
            if any(k in n.get("attr", {}) for k in ("data_format",)):
                continue
            # consume the transposes' NHWC inputs directly; forward-
            # transpose the NCHW stragglers
            new_ins = []
            for i in ins:
                if _is_inv_transpose(i):
                    new_ins.append(by_name[_tensor_ref(i)[0]]["input"][0])
                else:
                    t_f = {
                        "name": _uniq(_tensor_ref(i)[0] +
                                      "/nchw_to_nhwc"),
                        "op": "Transpose", "input": [i],
                        "control_input": [],
                        "device": n.get("device", ""),
                        "attr": {"perm": enc(_NCHW_TO_NHWC)},
                    }
                    by_name[t_f["name"]] = t_f
                    addenda.append((_tensor_ref(i)[0], t_f))
                    new_ins.append(t_f["name"] + ":0")
            n["input"] = new_ins
            n.pop("output_specs", None)
            # name swap (as in phase 1): this op becomes "<name>/nhwc",
            # an inverse transpose under the ORIGINAL name serves every
            # existing reference unchanged
            orig = n["name"]
            del by_name[orig]
            n["name"] = _uniq(orig + "/nhwc")
            by_name[n["name"]] = n
            t_out = {
                "name": orig,
                "op": "Transpose", "input": [n["name"] + ":0"],
                "control_input": [], "device": n.get("device", ""),
                "attr": {"perm": enc(_NHWC_TO_NCHW)},
            }
            by_name[orig] = t_out
            addenda.append((n["name"], t_out))
            changed = True
        # splice each new transpose right after its producer
        for prod_name, t_out in addenda:
            idx = next(i for i, m in enumerate(nodes)
                       if m["name"] == prod_name)
            nodes.insert(idx + 1, t_out)

    # ---- phase 3: cancel adjacent inverse pairs ---------------------
    alias: Dict[str, str] = {}
    for n in nodes:
        n["input"] = [alias.get(i, i) for i in n.get("input", [])]
        if n["op"] != "Transpose":
            continue
        p1 = _perm_of(n)
        src_name, src_idx = _tensor_ref(n["input"][0])
        src = by_name.get(src_name)
        if (src is not None and src["op"] == "Transpose" and src_idx == 0):
            p2 = _perm_of(src)
            if len(p1) == len(p2) and \
                    _compose_perm(p2, p1) == tuple(range(len(p1))):
                alias[n["name"] + ":0"] = src["input"][0]
    for n in nodes:
        n["input"] = [alias.get(i, i) for i in n.get("input", [])]

    out["node"] = nodes
    if keep:
        out = dead_code_elimination(out, keep)
    return out


# ---------------------------------------------------------------------------
# loop-invariant code motion (ref: grappler/optimizers/loop_optimizer.cc
# LoopInvariantNodeMotionOptimizer)
# ---------------------------------------------------------------------------

def loop_invariant_code_motion(graph_def: Dict,
                               keep: Optional[List[str]] = None) -> Dict:
    """Hoist pure body subexpressions that depend only on captures/consts
    out of while/scan/map bodies (descriptors with hoist=True) into the
    enclosing graph. The hoisted value re-enters the body as a new
    APPENDED capture, so the body signature (inputs/outputs, existing
    captures) is untouched; the op's input list grows at the body's
    capture slot and the relevant count attr is bumped. Runs bottom-up,
    so an expression nested two bodies deep migrates one level per graph
    and reaches the outermost invariant scope in one pipeline run."""
    out = copy.deepcopy(graph_def)
    used = {n["name"] for n in out["node"]}
    result: List[Dict] = []
    for node in out["node"]:
        for d, b in _node_bodies(node):
            _set_body(node, d, loop_invariant_code_motion(b), b)
        # trailing-captures body first: its inserts don't shift the
        # earlier slices, and earlier inserts bump their count attr so
        # later recomputation stays consistent
        for d, b in sorted(_node_bodies(node),
                           key=lambda db: -db[0]["start"]):
            if d.get("hoist"):
                result.extend(_hoist_from_body(node, d, b, used))
        result.append(node)
    out["node"] = result
    return out


def _hoist_from_body(node: Dict, desc: Dict, body: Dict,
                     used: Set[str]) -> List[Dict]:
    """Hoist invariant pure ops from one body; returns the new outer
    nodes (placed before ``node``). Mutates node inputs / body nodes /
    body captures in place."""
    from . import graph_io

    nodes_b = body["node"]
    start = desc["start"]
    appended_from = len(body.get("captures", []))
    hoisted: List[Dict] = []
    const_copies: Dict[str, str] = {}
    changed = True
    while changed:
        changed = False
        by_name = {n["name"]: n for n in nodes_b}
        cap_outer = {}  # inner CapturedInput node name -> outer input ref
        for i, cap in enumerate(body.get("captures", [])):
            idx = start + i
            if idx < len(node.get("input", [])):
                cap_outer[_tensor_ref(cap[1])[0]] = node["input"][idx]
        for bn in list(nodes_b):
            if bn["op"] in ("CapturedInput", "FuncArg", "Const"):
                continue
            if not _is_pure(bn) or bn.get("control_input"):
                continue
            specs = bn.get("output_specs")
            if not specs or len(specs) != 1:
                continue  # CapturedInput replacement is single-output
            ins = bn.get("input", [])
            if not ins:
                continue
            invariant = True
            has_capture_dep = False
            for r in ins:
                p = by_name.get(_tensor_ref(r)[0])
                if p is None:
                    invariant = False
                    break
                if p["op"] == "CapturedInput":
                    if _tensor_ref(r)[0] not in cap_outer:
                        invariant = False  # LICM-orphan (imported body)
                        break
                    has_capture_dep = True
                elif p["op"] != "Const":
                    invariant = False
                    break
            if not invariant or not has_capture_dep:
                # all-const chains are constant folding's job, not LICM's
                continue
            outer_name = _uniq_in(
                used, node["name"] + "/licm/" + bn["name"].replace("/", "_"))
            new_inputs = []
            for r in ins:
                pn, pi = _tensor_ref(r)
                p = by_name[pn]
                if p["op"] == "CapturedInput":
                    new_inputs.append(cap_outer[pn])
                else:  # Const: copy into the outer graph once per body
                    cn = const_copies.get(pn)
                    if cn is None:
                        cn = _uniq_in(used, node["name"] + "/licm/"
                                      + pn.replace("/", "_"))
                        cc = copy.deepcopy(p)
                        cc["name"] = cn
                        hoisted.append(cc)
                        const_copies[pn] = cn
                    new_inputs.append(f"{cn}:{pi}")
            hn = copy.deepcopy(bn)
            hn["name"] = outer_name
            hn["input"] = new_inputs
            hn["control_input"] = []
            hoisted.append(hn)
            # the body-side residue: a CapturedInput bound to the hoisted
            # op, keeping bn's NAME so body refs need no rewriting —
            # consumers of bn become hoist candidates on the next sweep
            sh, dt = specs[0]
            nodes_b[nodes_b.index(bn)] = {
                "name": bn["name"], "op": "CapturedInput", "input": [],
                "control_input": [], "device": bn.get("device", ""),
                "attr": {"dtype": graph_io._encode_attr(
                             dtypes_mod.as_dtype(dt)),
                         "shape": graph_io._encode_attr(
                             shape_mod.TensorShape(sh))},
                "output_specs": [[sh, dt]],
            }
            body.setdefault("captures", []).append(
                [f"{outer_name}:0", f"{bn['name']}:0"])
            node["input"].insert(start + len(body["captures"]) - 1,
                                 f"{outer_name}:0")
            if desc.get("count_attr"):
                node["attr"][desc["count_attr"]] = \
                    int(node["attr"][desc["count_attr"]]) + 1
            changed = True
    # Hoisting a k-op chain leaves k-1 intermediate CapturedInput
    # residues whose only consumer hoisted out on the next sweep — dead,
    # but body-signature DCE protects every CapturedInput. These were
    # appended by THIS call (never part of the original signature), so
    # drop the unconsumed ones along with their capture entry and the
    # matching op input; the orphaned outer intermediates fall to the
    # pipeline's final DCE.
    for i in range(len(body.get("captures", [])) - 1, appended_from - 1,
                   -1):
        inner_nm = _tensor_ref(body["captures"][i][1])[0]
        consumed = any(
            _tensor_ref(r)[0] == inner_nm
            for n2 in nodes_b for r in n2.get("input", [])) or any(
            _tensor_ref(r)[0] == inner_nm for r in body.get("outputs", []))
        if consumed:
            continue
        del body["captures"][i]
        del node["input"][start + i]
        body["node"] = nodes_b = [n2 for n2 in nodes_b
                                  if n2["name"] != inner_nm]
        if desc.get("count_attr"):
            node["attr"][desc["count_attr"]] = \
                int(node["attr"][desc["count_attr"]]) - 1
    return hoisted


# ---------------------------------------------------------------------------
# loop-carried layout push (the while-specific half of layout
# optimization: ref grappler layout_optimizer + loop_optimizer interplay)
# ---------------------------------------------------------------------------

def push_loop_layout(graph_def: Dict,
                     keep: Optional[List[str]] = None) -> Dict:
    """Push the boundary layout conversions of a layout-optimized while
    body ACROSS the loop. Sound only when the layout is invariant across
    an iteration — i.e. the body maps NHWC→NHWC for that loop var —
    which is verified structurally: the var must enter the body only
    through NCHW→NHWC transposes and exit through an NHWC→NCHW
    transpose (the shims layout_optimization leaves). Such a var is
    re-carried in NHWC: zero transposes execute per iteration; one
    conversion pair runs once, outside the loop. The While op keeps its
    name, arity, and dtypes (shapes permute); external consumers are
    rewired through a restoring transpose, so a While named in ``keep``
    (fetched by name) is skipped entirely."""
    out = copy.deepcopy(graph_def)
    keep_names = {_tensor_ref(k)[0] for k in (keep or [])}
    used = {n["name"] for n in out["node"]}
    rewire: Dict[str, str] = {}
    shim_names: Set[str] = set()
    new_nodes: List[Dict] = []
    for node in out["node"]:
        if rewire and node["name"] not in shim_names:
            node["input"] = [rewire.get(r, r)
                             for r in node.get("input", [])]
        for d, b in _node_bodies(node):
            _set_body(node, d, push_loop_layout(b, keep=_body_keep(b)), b)
        if node["op"] == "While" and node["name"] not in keep_names:
            pre, post = _push_while_vars(node, used, rewire, shim_names)
            new_nodes.extend(pre)
            new_nodes.append(node)
            new_nodes.extend(post)
        else:
            new_nodes.append(node)
    out["node"] = new_nodes
    return out


def _push_while_vars(node: Dict, used: Set[str], rewire: Dict[str, str],
                     shim_names: Set[str]) -> Tuple[List[Dict], List[Dict]]:
    from . import graph_io

    enc = graph_io._encode_attr
    dec = graph_io._decode_attr

    bodies = {d["attr"]: b for d, b in _node_bodies(node)}
    body = bodies.get("body_graph")
    cond = bodies.get("cond_graph")
    if body is None or cond is None:
        return [], []
    n_vars = int(node["attr"].get("n_vars", 0))
    by_name = {n["name"]: n for n in body["node"]}

    def _perm(nd):
        p = dec(nd.get("attr", {}).get("perm"))
        return tuple(p) if p is not None else ()

    def _perm_shape(sh):
        return [sh[i] for i in _NCHW_TO_NHWC] if isinstance(sh, list) \
            and len(sh) == 4 else sh

    pre: List[Dict] = []
    post: List[Dict] = []
    for i in range(min(n_vars, len(body.get("outputs", [])),
                       len(body.get("inputs", [])))):
        onm, oi = _tensor_ref(body["outputs"][i])
        t_out = by_name.get(onm)
        if (t_out is None or t_out["op"] != "Transpose" or oi != 0
                or _perm(t_out) != _NHWC_TO_NCHW):
            continue
        arg_ref = body["inputs"][i]
        anm = _tensor_ref(arg_ref)[0]
        arg_node = by_name.get(anm)
        if arg_node is None or arg_node["op"] != "FuncArg":
            continue
        if any(_tensor_ref(r)[0] == anm for r in body["outputs"]):
            continue  # var also passed through unconverted
        consumers = [n2 for n2 in body["node"]
                     if any(r == arg_ref for r in n2.get("input", []))]
        if not consumers or any(
                n2["op"] != "Transpose" or _perm(n2) != _NCHW_TO_NHWC
                or len(n2.get("input", [])) != 1 for n2 in consumers):
            continue  # body does NOT map this var NHWC→NHWC: unsound
        spec = arg_node.get("output_specs")
        if (not spec or not isinstance(spec[0][0], list)
                or len(spec[0][0]) != 4):
            continue
        # ---- the var provably carries NHWC-invariant layout: flip it --
        dt = spec[0][1]
        nhwc_shape = _perm_shape(spec[0][0])
        arg_node["output_specs"] = [[nhwc_shape, dt]]
        arg_node.setdefault("attr", {})["shape"] = enc(
            shape_mod.TensorShape(nhwc_shape))
        # entry: consumers read the NHWC arg directly
        dead = {n2["name"] for n2 in consumers}
        for n2 in body["node"]:
            n2["input"] = [arg_ref if _tensor_ref(r)[0] in dead else r
                           for r in n2.get("input", [])]
        body["outputs"] = [arg_ref if _tensor_ref(r)[0] in dead else r
                           for r in body["outputs"]]
        body["node"] = [n2 for n2 in body["node"]
                        if n2["name"] not in dead]
        # exit: emit the NHWC value; the old shim stays only if consumed
        body["outputs"][i] = t_out["input"][0]
        # cond graph sees the var NHWC; restore NCHW for its uses
        c_ref = cond["inputs"][i]
        cnm = _tensor_ref(c_ref)[0]
        c_by_name = {n2["name"]: n2 for n2 in cond["node"]}
        c_arg = c_by_name.get(cnm)
        if c_arg is not None:
            c_spec = c_arg.get("output_specs")
            if c_spec:
                c_arg["output_specs"] = [[_perm_shape(c_spec[0][0]),
                                          c_spec[0][1]]]
            c_arg.setdefault("attr", {})["shape"] = enc(
                shape_mod.TensorShape(nhwc_shape))
            c_users = [n2 for n2 in cond["node"]
                       if any(r == c_ref for r in n2.get("input", []))]
            if c_users:
                tc_name = _uniq_in({n2["name"] for n2 in cond["node"]},
                                   cnm + "/to_nchw")
                tc = {"name": tc_name, "op": "Transpose",
                      "input": [c_ref], "control_input": [],
                      "device": c_arg.get("device", ""),
                      "attr": {"perm": enc(_NHWC_TO_NCHW)},
                      "output_specs": [[spec[0][0], dt]]}
                for n2 in c_users:
                    n2["input"] = [tc_name + ":0" if r == c_ref else r
                                   for r in n2.get("input", [])]
                cond["node"].insert(
                    cond["node"].index(c_arg) + 1, tc)
        # outer: convert the init value in, restore for consumers
        tin_name = _uniq_in(used, f"{node['name']}/v{i}_to_nhwc")
        pre.append({"name": tin_name, "op": "Transpose",
                    "input": [node["input"][i]], "control_input": [],
                    "device": node.get("device", ""),
                    "attr": {"perm": enc(_NCHW_TO_NHWC)},
                    "output_specs": [[nhwc_shape, dt]]})
        node["input"][i] = tin_name + ":0"
        old_spec_i = node["output_specs"][i]
        node["output_specs"][i] = [_perm_shape(old_spec_i[0]),
                                   old_spec_i[1]]
        tb_name = _uniq_in(used, f"{node['name']}/v{i}_to_nchw")
        post.append({"name": tb_name, "op": "Transpose",
                     "input": [f"{node['name']}:{i}"],
                     "control_input": [], "device": node.get("device", ""),
                     "attr": {"perm": enc(_NHWC_TO_NCHW)},
                     "output_specs": [old_spec_i]})
        shim_names.add(tb_name)
        rewire[f"{node['name']}:{i}"] = tb_name + ":0"
    return pre, post


# ---------------------------------------------------------------------------
# the PassManager
# ---------------------------------------------------------------------------

class GraphPass:
    """One named GraphDef rewrite. ``fn(graph_def, keep) -> graph_def``;
    every built-in pass is function-aware (recurses into FuncGraph
    bodies itself). ``signature_safe`` marks passes that never change a
    body's captures or an op's input arity — the only ones
    ``optimize_graph_functions`` may run on live graphs."""

    def __init__(self, name: str, fn: Callable, signature_safe: bool = True):
        self.name = name
        self.fn = fn
        self.signature_safe = signature_safe

    def run(self, graph_def: Dict, keep: List[str]) -> Dict:
        return self.fn(graph_def, keep)

    def __repr__(self):
        return f"<GraphPass {self.name}>"


LAYOUT_PASS = GraphPass(
    "layout", lambda gd, keep: layout_optimization(gd, keep=keep))
PUSH_LOOP_LAYOUT_PASS = GraphPass(
    "push_loop_layout", push_loop_layout, signature_safe=False)
FOLD_PASS = GraphPass("fold", lambda gd, keep: constant_folding(gd))
LICM_PASS = GraphPass("licm", loop_invariant_code_motion,
                      signature_safe=False)
CSE_PASS = GraphPass(
    "cse", lambda gd, keep: common_subexpression_elimination(gd, keep=keep))
DCE_PASS = GraphPass(
    "dce", lambda gd, keep: dead_code_elimination(gd, keep) if keep else gd)


def default_passes(layout: bool = True,
                   signature_safe_only: bool = False) -> List[GraphPass]:
    passes = []
    if layout:
        passes.append(LAYOUT_PASS)
        if not signature_safe_only:
            passes.append(PUSH_LOOP_LAYOUT_PASS)
    passes.append(FOLD_PASS)
    if not signature_safe_only:
        passes.append(LICM_PASS)
    passes += [CSE_PASS, DCE_PASS]
    return passes


class PassManager:
    """Unified driver for the GraphDef-level passes (the grappler
    meta_optimizer slot). Every registered pass is function-aware: it
    recurses into the FuncGraph bodies declared via
    ``register_function_op`` (cond branches, while cond/body, scan/map
    fns, defun bodies), preserving each body's signature so Session
    executable-cache keys and the lowering stay valid.

    ``verify``: run the stf.analysis GraphDef verifier as a pre/post
    invariant around every pass — a pass that *introduces* a structural
    error (dangling ref, broken body signature, cycle) raises
    InternalError naming the pass, instead of the error surfacing later
    as an opaque import/lowering failure. Pre-existing errors in the
    input graph are attributed to the input, not to a pass. Default
    from env ``STF_VERIFY_PASSES`` (off unless "1": verification is
    O(graph) per pass, the optimizer hot path is per-plan)."""

    def __init__(self, passes: Optional[List[GraphPass]] = None,
                 verify: Optional[bool] = None):
        self.passes = list(passes if passes is not None
                           else default_passes())
        if verify is None:
            import os

            verify = os.environ.get("STF_VERIFY_PASSES", "0") == "1"
        self.verify = bool(verify)

    @staticmethod
    def _error_keys(gd: Dict) -> set:
        from ..analysis import verifier as verifier_mod

        return {(d.code, d.op_name)
                for d in verifier_mod.verify_graphdef(gd) if d.is_error}

    def run(self, graph_def: Dict, keep: Optional[List[str]] = None) -> Dict:
        gd = graph_def
        baseline = self._error_keys(gd) if self.verify else None
        for p in self.passes:
            t0 = time.perf_counter()
            with monitoring.traceme(f"optimizer/graph_pass:{p.name}",
                                    n_nodes=len(gd.get("node", ()))):
                new = p.run(gd, list(keep or []))
            _metric_pass_seconds.get_cell(p.name).add(
                time.perf_counter() - t0)
            _metric_pass_runs.get_cell(p.name).increase_by(1)
            # rewrite detection is a deep dict compare — O(graph bytes),
            # paid once per (fetches, feeds) plan; identical-object
            # returns skip it
            if new is not gd and new != gd:
                _metric_pass_rewrites.get_cell(p.name).increase_by(1)
                if baseline is not None:
                    introduced = self._error_keys(new) - baseline
                    if introduced:
                        from .errors import InternalError

                        detail = "; ".join(
                            f"{code} at {name}" for code, name
                            in sorted(introduced,
                                      key=lambda k: (k[0], str(k[1]))))
                        raise InternalError(
                            None, None,
                            f"graph pass {p.name!r} broke the graph: "
                            f"{detail} (pre/post invariant check, "
                            "stf.analysis.verify_graphdef)")
            gd = new
        return gd


def optimize(graph_def: Dict, keep: Optional[List[str]] = None,
             layout: bool = True) -> Dict:
    """grappler-equivalent pipeline:
    layout -> push_loop_layout -> fold -> licm -> CSE -> DCE,
    each pass recursing into cond/while/scan/defun bodies."""
    return PassManager(default_passes(layout=layout)).run(graph_def,
                                                          keep=keep)


def optimize_graph_functions(graph, layout: bool = True,
                             passes: Optional[List[GraphPass]] = None) -> int:
    """Rewrite the FuncGraph bodies of a LIVE graph in place.

    Runs the signature-safe pipeline (layout / fold / CSE / DCE — no
    LICM or loop push: a live op's input tuple is immutable, so captures
    must stay put) on each body, rebuilds it, and swaps it into the op's
    attr. Outputs/arity/dtypes/captures are preserved, so every existing
    by-name and positional reference stays valid. Bumps the graph's
    rewrite version so Session executable caches keyed on it invalidate
    and the next run() re-plans against the rewritten bodies. Returns
    the number of bodies rewritten."""
    from . import graph as ops_mod
    from . import graph_io

    if passes is None:
        passes = default_passes(layout=layout, signature_safe_only=True)
    if any(not p.signature_safe for p in passes):
        raise ValueError(
            "optimize_graph_functions: only signature-safe passes may "
            "rewrite live graphs (got "
            f"{[p.name for p in passes if not p.signature_safe]})")
    pm = PassManager(passes)
    changed = 0
    for op in graph.get_operations():
        spec = _FUNCTION_OPS.get(op.type)
        if spec is None:
            continue
        try:
            descs = spec.bodies(op.attrs, len(op.inputs))
        except (KeyError, TypeError):
            continue
        for desc in descs:
            fg = op.attrs.get(desc["attr"])
            if not isinstance(fg, ops_mod.FuncGraph):
                continue
            body = graph_io._funcgraph_to_dict(fg)
            opt = pm.run(body, keep=_body_keep(body))
            if opt == body:
                continue
            if (_signature_broken(body, opt)
                    or len(opt.get("captures", []))
                    != len(fg.captures)):
                continue  # defensive: never swap in a broken body
            new_fg = graph_io.rebuild_funcgraph(opt, fg.outer_graph)
            # rebind the original outer capture tensors positionally
            new_fg.captures = [
                (outer, inner2) for (outer, _), (_, inner2)
                in zip(fg.captures, new_fg.captures)]
            op.attrs[desc["attr"]] = new_fg
            changed += 1
    if changed:
        graph._rewrite_version += 1
    return changed


# ---------------------------------------------------------------------------
# IR-level passes (the Session's hot path)
# ---------------------------------------------------------------------------

_FOLD_MAX_BYTES = 1 << 20  # don't materialize folded constants above 1 MiB


def optimize_pruned(op_list, fed_tensors, keep_tensors, const_seed=None,
                    func_plans=None):
    """Fold/CSE/DCE over a pruned, topo-ordered Operation list — the pass
    Session._plan runs before lowering (ref grappler's role ahead of the
    executor; core/common_runtime/constant_folding.cc).

    Works WITHOUT mutating the graph (the IR is immutable-append):
    returns ``(new_op_list, const_env, alias)`` where
      const_env: Tensor -> np.ndarray — outputs computed at plan time;
        the Session seeds them into the lowering env, so the ops that
        produced them never trace,
      alias: Tensor -> Tensor — CSE-duplicate output -> canonical output;
        consulted at every input lookup during lowering.

    Function-aware: ops carrying FuncGraph bodies (cond/while/scan/defun)
    get each body optimized recursively at plan time — fold (seeded with
    the values of constant captures: cross-boundary constant
    propagation), CSE, and DCE run over the body's pruned op list. The
    results land in ``func_plans`` (FuncGraph -> (op_list, const_env,
    alias)), which the caller threads into the LoweringContext so
    lowering.lower_func_graph consumes them on every trace of that body.
    A duplicate subexpression inside a while/scan body therefore lowers
    ONCE per iteration instead of twice, without mutating the graph.
    Body plans belong to THIS plan, not the FuncGraph: a capture's value
    may be constant under one feed set and fed under another, so plans
    are never shared across (fetches, feeds) signatures.

    ``const_seed``: Tensor -> np value bindings known constant in this
    scope (the recursive calls pass capture constants through it).
    ``func_plans``: optional dict collecting the per-FuncGraph body
    plans (shared with recursive calls); pass it to each
    LoweringContext that will trace these ops.

    Ops are foldable/CSE-able only via ``pure_fn`` (stateless by
    construction: RNG, variables, placeholders, host IO all register with
    ``lower=`` and/or ``is_stateful`` and are excluded)."""
    const_env: Dict[Any, Any] = dict(const_seed or {})
    alias: Dict[Any, Any] = {}
    sigs: Dict[str, Any] = {}  # signature -> canonical op
    new_list = []
    for op in op_list:
        od = op.op_def
        if op.type in _FUNCTION_OPS and func_plans is not None:
            _plan_function_bodies(op, const_env, alias, fed_tensors,
                                  func_plans)
        if op.type == "Const":
            v = op.attrs.get("value")
            if v is not None and op.outputs:
                const_env[op.outputs[0]] = np.asarray(v)
            new_list.append(op)  # kept for host-stage consumers; DCE'd below
            continue
        pure = (od.pure_fn is not None and not od.is_stateful
                and not od.runs_on_host and not op.control_inputs
                and op.type not in _FOLDABLE_BLOCKLIST)
        resolved_ins = [alias.get(t, t) for t in op.inputs]
        if (pure and op.type in _SHAPE_OPS and op.inputs
                and op.inputs[0].shape.is_fully_defined()):
            # shape materialization: static shape -> constant, no value
            # needed (grappler does the same before its folding pass);
            # out_type honored through the 64-bit narrowing so a folded
            # Shape returns the same dtype the runtime path computes
            sh = op.inputs[0].shape.as_list()
            ot = op.attrs.get("out_type")
            np_dt = (dtypes_mod.narrowed_if_no_x64(ot).np_dtype
                     if ot is not None else np.int32)
            if op.type == "Shape":
                val = np.asarray(sh, np_dt)
            elif op.type == "Size":
                val = np.asarray(int(np.prod(sh)) if sh else 1, np_dt)
            else:
                val = np.asarray(len(sh), np.int32)  # Rank: int32
            if op.outputs:
                const_env[op.outputs[0]] = val
                continue
        if pure and resolved_ins and all(t in const_env
                                         for t in resolved_ins):
            attrs = {k: v for k, v in op.attrs.items()
                     if not k.startswith("_")}
            out = _fold(od, [const_env[t] for t in resolved_ins], attrs)
            if out is not None:
                outs = (list(out) if isinstance(out, (list, tuple))
                        else [out])
                outs = [np.asarray(o) for o in outs]
                if (len(outs) == len(op.outputs) and
                        sum(o.nbytes for o in outs) <= _FOLD_MAX_BYTES):
                    for t, v in zip(op.outputs, outs):
                        const_env[t] = v
                    _metric_folded_ops.get_cell().increase_by(1)
                    continue  # folded: op never lowers
        if pure:
            sig = repr((op.type,
                        tuple(id(t) for t in resolved_ins),
                        sorted((k, repr(v)) for k, v in op.attrs.items()
                               if not k.startswith("_"))))
            canon = sigs.get(sig)
            if canon is not None:
                for dup_out, canon_out in zip(op.outputs, canon.outputs):
                    alias[dup_out] = alias.get(canon_out, canon_out)
                continue  # CSE'd: op never lowers
            sigs[sig] = op
        new_list.append(op)

    # DCE (reverse walk): effects stay; pure ops stay only if some kept op
    # or fetch consumes an output (through aliases), and folded consumers
    # are gone already.
    needed = set()
    for t in keep_tensors:
        t = alias.get(t, t)
        if t not in const_env:
            needed.add(t)
    kept_rev = []
    for op in reversed(new_list):
        od = op.op_def
        effectful = od.is_stateful or od.runs_on_host or not op.outputs
        wanted = effectful or any(o in needed for o in op.outputs)
        if not wanted:
            continue
        kept_rev.append(op)
        for t in op.inputs:
            t = alias.get(t, t)
            if t not in const_env and t not in fed_tensors:
                needed.add(t)
        for c in op.control_inputs:
            # output-less control deps are effectful and kept by the rule
            # above; tensor-producing ones are kept via their outputs
            needed.update(c.outputs)
    return list(reversed(kept_rev)), const_env, alias


def _plan_function_bodies(op, const_env, alias, fed_tensors, func_plans):
    """Optimize the FuncGraph bodies of one op at plan time, recording
    each result in ``func_plans`` as fg -> (op_list, const_env, alias)
    (consumed by lowering.lower_func_graph through the
    LoweringContext). Seeds the body fold with captures whose outer
    producer is a plan-time constant AND not fed in this plan — sound
    because captures are invariant across iterations/branches, and a
    fed tensor (even a fed Const: feeding overrides any node) must
    never be baked in. Defensive: a failure here must never break the
    session plan."""
    spec = _FUNCTION_OPS.get(op.type)
    if spec is None:
        return
    try:
        descs = spec.bodies(op.attrs, len(op.inputs))
    except (KeyError, TypeError):
        return
    from . import lowering as lowering_mod

    for d in descs:
        fg = op.attrs.get(d["attr"])
        if fg is None or not hasattr(fg, "captures"):
            continue
        if fg in func_plans:
            continue
        seeds: Dict[Any, Any] = {}
        for outer, inner in fg.captures:
            if outer is None:
                continue  # imported body: outer refs re-bound by caller
            r = alias.get(outer, outer)
            if outer in fed_tensors or r in fed_tensors:
                continue  # fed value wins over any graph constant
            if r in const_env:
                seeds[inner] = const_env[r]
            elif r.op.type == "Const":
                v = r.op.attrs.get("value")
                if v is not None:
                    seeds[inner] = np.asarray(v)
        fed = set(fg.inputs) | {inner for _, inner in fg.captures}
        try:
            plan = lowering_mod.prune([t.op for t in fg.outputs], fed)
            body_plan = optimize_pruned(plan, fed, list(fg.outputs),
                                        const_seed=seeds,
                                        func_plans=func_plans)
        except Exception:
            continue
        func_plans[fg] = body_plan
