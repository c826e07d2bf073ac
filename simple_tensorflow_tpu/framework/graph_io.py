"""Graph serialization: GraphDef-equivalent JSON + MetaGraph
(ref: tensorflow/python/framework/{graph_io,importer,meta_graph}.py,
core/framework/graph.proto).

The wire format is JSON (attrs hold numpy constants base64-encoded) rather
than GraphDef protobuf — the reference's proto schema is tied to its op
registry; ours captures the same information (nodes, inputs, control deps,
attrs, collections, versions) for export/import round-trips.
"""

from __future__ import annotations

import base64
import io as _io
import json
import os

import numpy as np

from . import dtypes as dtypes_mod
from . import graph as ops_mod
from . import tensor_shape as shape_mod


def _encode_attr(v):
    if isinstance(v, np.ndarray):
        buf = _io.BytesIO()
        if v.dtype == object:
            return {"__kind__": "strlist",
                    "v": [str(s) for s in np.ravel(v)],
                    "shape": list(v.shape)}
        np.save(buf, v, allow_pickle=False)
        return {"__kind__": "ndarray",
                "v": base64.b64encode(buf.getvalue()).decode()}
    if isinstance(v, dtypes_mod.DType):
        return {"__kind__": "dtype", "v": v.name}
    if isinstance(v, shape_mod.TensorShape):
        return {"__kind__": "shape",
                "v": v.as_list() if v.rank is not None else None}
    if isinstance(v, ops_mod.FuncGraph):
        return {"__kind__": "funcgraph", "v": _funcgraph_to_dict(v)}
    if isinstance(v, tuple):
        return {"__kind__": "tuple", "v": [_encode_attr(x) for x in v]}
    if isinstance(v, (int, float, str, bool)) or v is None:
        return v
    if isinstance(v, list):
        return {"__kind__": "tuple", "v": [_encode_attr(x) for x in v]}
    return {"__kind__": "repr", "v": repr(v)}


def _decode_attr(v):
    if isinstance(v, dict) and "__kind__" in v:
        kind = v["__kind__"]
        if kind == "ndarray":
            return np.load(_io.BytesIO(base64.b64decode(v["v"])),
                           allow_pickle=False)
        if kind == "strlist":
            return np.asarray(v["v"], dtype=object).reshape(v["shape"])
        if kind == "dtype":
            return dtypes_mod.as_dtype(v["v"])
        if kind == "shape":
            return shape_mod.TensorShape(v["v"])
        if kind == "tuple":
            return tuple(_decode_attr(x) for x in v["v"])
        if kind == "funcgraph":
            return v  # rebuilt lazily by importer
        if kind == "repr":
            return v["v"]
    return v


def _node_to_dict(op: ops_mod.Operation):
    d = {
        "name": op.name,
        "op": op.type,
        "input": [t.name for t in op.inputs],
        "control_input": [c.name for c in op.control_inputs],
        "device": op.device,
        "attr": {k: _encode_attr(v) for k, v in op.attrs.items()},
        "output_specs": [
            [o.shape.as_list() if o.shape.rank is not None else None,
             o.dtype.name] for o in op.outputs],
    }
    if op.traceback:
        # innermost user frame only: enough for stf.analysis diagnostics
        # on re-imported graphs to point at the original creation site
        f, ln, fn = op.traceback[0]
        d["source"] = [f, ln, fn]
    return d


def _funcgraph_to_dict(fg: ops_mod.FuncGraph):
    return {
        "name": fg.func_name,
        "node": [_node_to_dict(op) for op in fg.get_operations()],
        "inputs": [t.name for t in fg.inputs],
        "outputs": [t.name for t in fg.outputs],
        # an imported FuncGraph has outer=None captures (re-bound by the
        # caller through the op's input list) — serialize those as None
        "captures": [[outer.name if outer is not None else None,
                      inner.name] for outer, inner in fg.captures],
    }


def graph_to_graphdef(graph: ops_mod.Graph, from_version=None):
    """(ref: Graph.as_graph_def, core/framework/graph.proto)."""
    return {
        "versions": {"producer": 1},
        "node": [_node_to_dict(op) for op in graph.get_operations()],
    }


def write_graph(graph_or_graph_def, logdir, name, as_text=True):
    """(ref: python/framework/graph_io.py:28 ``write_graph``)."""
    if isinstance(graph_or_graph_def, ops_mod.Graph):
        gd = graph_to_graphdef(graph_or_graph_def)
    else:
        gd = graph_or_graph_def
    os.makedirs(logdir, exist_ok=True)
    path = os.path.join(logdir, name)
    with open(path, "w") as f:
        json.dump(gd, f, indent=1 if as_text else None)
    return path


def _build_nodes_into(target_graph, nodes, tensor_env, scope_prefix,
                      input_map=None):
    """Rebuild GraphDef node dicts into ``target_graph`` (shared by
    import_graph_def and rebuild_funcgraph)."""
    input_map = input_map or {}
    for node in nodes:
        attrs = {k: _decode_attr(v)
                 for k, v in (node.get("attr") or {}).items()}
        # Scoped imports get their own VariableStore namespace: rewrite
        # var_name attrs so an imported 'w' cannot alias an existing
        # variable 'w' in this graph (store keys come from these attrs).
        if scope_prefix:
            if isinstance(attrs.get("var_name"), str):
                attrs["var_name"] = f"{scope_prefix}/{attrs['var_name']}"
            elif isinstance(attrs.get("var_name"), (list, tuple)):
                # an op over several store entries (a fused update, a
                # paged attention's K and V pools)
                attrs["var_name"] = type(attrs["var_name"])(
                    f"{scope_prefix}/{n}" for n in attrs["var_name"])
            if isinstance(attrs.get("var_names"), tuple):
                attrs["var_names"] = tuple(
                    f"{scope_prefix}/{n}" for n in attrs["var_names"])
        # rebuild nested funcgraphs
        for k, v in list(attrs.items()):
            if isinstance(v, dict) and v.get("__kind__") == "funcgraph":
                attrs[k] = rebuild_funcgraph(v["v"], target_graph)
        inputs = []
        for ref in node["input"]:
            if ref in input_map:
                inputs.append(input_map[ref])
            else:
                inputs.append(tensor_env[ref])
        ctrl = [tensor_env["(op)" + c]
                for c in node.get("control_input", ())
                if "(op)" + c in tensor_env]
        # A producer that doesn't know output shapes (e.g. the C client
        # building math ops) omits output_specs; the op registry's
        # shape inference fills them in, mirroring the reference's
        # shape_refiner on import (ref: common_runtime/shape_refiner.cc).
        specs_raw = node.get("output_specs")
        specs = None if specs_raw is None else [
            (shape_mod.TensorShape(sh), dtypes_mod.as_dtype(dt))
            for sh, dt in specs_raw]
        new_name = f"{scope_prefix}/{node['name']}" if scope_prefix \
            else node["name"]
        op = target_graph.create_op(
            node["op"], inputs, attrs=attrs, name=new_name + "/",
            output_specs=specs, control_inputs=ctrl)
        src = node.get("source")
        if src and len(src) == 3:
            # restore the original creation site (the capture above only
            # recorded the import call) for analysis diagnostics
            op._traceback = ((str(src[0]), int(src[1]), str(src[2])),)
        tensor_env["(op)" + node["name"]] = op
        for i, out in enumerate(op.outputs):
            tensor_env[f"{node['name']}:{i}"] = out
    return tensor_env


def rebuild_funcgraph(fg_dict, outer):
    """Rebuild a serialized FuncGraph dict into a live FuncGraph of
    ``outer``. Captures keep their inner placeholders with outer refs
    None — resolving outers by name is not possible here; the caller
    (the function-op's lowering via op inputs, or
    optimizer.optimize_graph_functions) re-binds them."""
    fg = ops_mod.FuncGraph(fg_dict["name"], outer_graph=outer)
    env = {}
    with ops_mod._as_current(fg):
        _build_nodes_into(fg, fg_dict["node"], env, "")
    fg.inputs = [env[n] for n in fg_dict["inputs"]]
    fg.outputs = [env[n] for n in fg_dict["outputs"]]
    fg.captures = [(None, env[inner])
                   for _, inner in fg_dict["captures"]]
    return fg


def import_graph_def(graph_def, input_map=None, return_elements=None,
                     name=None, op_dict=None, producer_op_list=None):
    """(ref: python/framework/importer.py:156 ``import_graph_def``).

    Rebuilds nodes into the current default graph. FuncGraph attrs are
    rebuilt recursively.
    """
    if isinstance(graph_def, (str, bytes)):
        graph_def = json.loads(graph_def)
    g = ops_mod.get_default_graph()
    # TF semantics: default prefix "import"; explicit "" means no prefix
    prefix = "import" if name is None else name
    input_map = {k: v for k, v in (input_map or {}).items()}
    tensors = {}

    _build_nodes_into(g, graph_def["node"], tensors, prefix,
                      input_map=input_map)
    if return_elements:
        out = []
        for r in return_elements:
            key = f"{r}" if ":" in r else "(op)" + r
            out.append(tensors[key] if key in tensors
                       else tensors[f"{r}:0"])
        return out
    return None


def export_meta_graph(filename=None, graph=None, collection_list=None,
                      **kwargs):
    """(ref: python/framework/meta_graph.py ``export_scoped_meta_graph``)."""
    graph = graph or ops_mod.get_default_graph()
    meta = {
        "graph_def": graph_to_graphdef(graph),
        "collections": {},
        "meta_info": {"stf_version": "1.0.0-tpu"},
    }
    for key in (collection_list or graph.get_all_collection_keys()):
        items = graph.get_collection(key)
        names = []
        for it in items:
            if isinstance(it, ops_mod.Tensor):
                names.append({"tensor": it.name})
            elif isinstance(it, ops_mod.Operation):
                names.append({"op": it.name})
            elif hasattr(it, "to_proto"):
                try:
                    names.append({"proto": it.to_proto()})
                except Exception:
                    continue
        if names:
            meta["collections"][key] = names
    if filename:
        with open(filename, "w") as f:
            json.dump(meta, f)
    return meta


def import_meta_graph(meta_graph_or_file, clear_devices=False,
                      import_scope=None):
    if isinstance(meta_graph_or_file, str):
        with open(meta_graph_or_file) as f:
            meta = json.load(f)
    else:
        meta = meta_graph_or_file
    import_graph_def(meta["graph_def"], name=import_scope or "")
    _rebuild_collections(meta, import_scope)
    return meta


def _rebuild_collections(meta, import_scope=None):
    """Restore graph collections from a MetaGraph, reconstructing Variable
    wrappers from their serialized protos (ref: python/framework/
    meta_graph.py ``import_scoped_meta_graph`` — without this, Saver finds
    no variables after import and restore is a silent no-op)."""
    g = ops_mod.get_default_graph()
    rebuilt_vars = {}  # variable_name -> Variable (shared across collections)

    def _scoped(name):
        return f"{import_scope}/{name}" if import_scope else name

    for key, items in meta.get("collections", {}).items():
        for it in items:
            if "tensor" in it or "op" in it:
                ref, as_tensor = ((it["tensor"], True) if "tensor" in it
                                  else (it["op"], False))
                try:
                    g.add_to_collection(key, g.as_graph_element(
                        _scoped(ref), allow_tensor=as_tensor,
                        allow_operation=not as_tensor))
                except (KeyError, ValueError):
                    continue  # item not present in the imported subgraph
            elif "proto" in it:
                proto = it["proto"]
                if isinstance(proto, dict) and "variable_name" in proto:
                    vname = proto["variable_name"]
                    if vname not in rebuilt_vars:
                        from ..ops.variables import Variable

                        try:
                            rebuilt_vars[vname] = Variable.from_proto(
                                proto, import_scope=import_scope, graph=g)
                        except (KeyError, ValueError) as e:
                            # a dropped variable means Saver.restore would
                            # silently skip it — that must be loud
                            from ..platform import tf_logging as logging

                            logging.warning(
                                "import_meta_graph: could not rebuild "
                                "variable %s from collection %s (%s); it "
                                "will NOT be restored by Saver.", vname,
                                key, e)
                            continue
                    g.add_to_collection(key, rebuilt_vars[vname])
                # other proto kinds (e.g. SaverDef) are advisory: the
                # caller constructs a fresh Saver over the rebuilt vars
