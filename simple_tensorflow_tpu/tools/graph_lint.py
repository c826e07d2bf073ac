"""Offline graph verifier + linter for serialized GraphDef JSON.

    python -m simple_tensorflow_tpu.tools.graph_lint graphdef.json \
        [--fetch op_or_tensor ...] [--severity code=level ...] \
        [--level structural|full] [--json] [--serving] \
        [--kernels [off|auto|force]] \
        [--memory [--budget BYTES]] [--numerics] \
        [--embeddings [--budget BYTES]] \
        [--mesh 8|2x4|dp=2,tp=4] [--rules rules.json] \
        [--autoshard [--emit-rules out.json] [--budget BYTES]] \
        [--max-severity note|warning|error]

Runs the stf.analysis stack over a GraphDef written by
``stf.train.write_graph`` / ``graph_io.write_graph``:

  1. ``verify_graphdef`` — structural wire-format invariants (dangling
     refs, duplicate names, unregistered ops, cycles, FuncGraph body
     signatures). Errors here stop the run: the graph cannot be
     imported.
  2. import into a fresh Graph, then ``analyze`` — live verifier (full
     level by default, including abstract-eval shape/dtype re-checks),
     per-fetch variable-hazard detection, and the lint rule catalog.
  3. with ``--mesh``, the sharding analyzer (stf.analysis.sharding)
     runs over an ABSTRACT mesh — no devices needed, so a dp8 graph
     lints on a 1-CPU CI box. ``--rules rules.json`` seeds variable
     shardings from regex partition rules (the
     ``match_partition_rules`` format: ``[[pattern, [spec...]], ...]``
     with null = replicate a dim), letting a rule set be checked
     BEFORE paying a compile.

Diagnostics carry the op's original creation site when the GraphDef
recorded one (graph_io serializes the innermost user frame). Exit code
1 when any diagnostic reaches ``--max-severity`` (default: error), so
CI can gate at warning level for sharding hygiene. ``--json`` emits one
JSON object per diagnostic plus a trailing ``summary`` record
(collective bytes by kind, per-shard peak HBM) for machine consumption.
"""

from __future__ import annotations

import argparse
import json
import sys


def kernel_routing_summary(graph, mode=None):
    """Aggregate per-op routing verdicts over a graph: {op_type:
    {verdict_or_reason: count}} plus a ``no-kernel`` op-type count —
    the ``graph_lint --kernels`` table (stf.kernels.routing_report)."""
    from ..kernels import registry as kreg

    table = {}
    no_kernel = 0
    for rec in kreg.routing_report(graph.get_operations(), mode=mode):
        if rec["verdict"] == "no-kernel":
            no_kernel += rec.get("count", 1)
            continue
        key = rec["verdict"]
        if rec["verdict"] == "fallback" and rec.get("reason"):
            key = f"fallback:{rec['reason']}"
        per = table.setdefault(rec["type"], {})
        per[key] = per.get(key, 0) + 1
    return {"mode": mode or kreg.current_mode(),
            "backend": kreg.backend(),
            "by_op_type": table, "no_kernel_ops": no_kernel}


def memory_summary(graph, fetch_names=None, fetches=None, budget=None):
    """Per-plan peak-estimate rows for ``graph_lint --memory``: one row
    per fetch (or one whole-graph row), with the static cost model's
    predicted peak/resident/transient bytes and — when a budget is
    given — whether the plan fits (stf.telemetry.memory offline
    half)."""
    from ..analysis import lint as lint_mod
    from ..framework import cost_model

    ctx = lint_mod.LintContext(graph, graph.get_operations(),
                               fetches=fetches)
    rows = []
    for label, plan_fetches, _anchor in lint_mod.plan_fetch_groups(ctx):
        try:
            est = cost_model.estimate(plan_fetches)
        except Exception as e:  # noqa: BLE001 — un-costable plan
            rows.append({"plan": label, "error": str(e)})
            continue
        row = {"plan": label,
               "predicted_peak_bytes": int(est.peak_bytes),
               "resident_bytes": int(est.resident_bytes),
               "transient_bytes": int(est.peak_bytes
                                      - est.resident_bytes)}
        if budget:
            row["budget_bytes"] = int(budget)
            row["within_budget"] = bool(est.peak_bytes <= int(budget))
        rows.append(row)
    return rows


def embedding_summary(graph, report, budget=None):
    """Per-table verdict rows for ``graph_lint --embeddings``: every
    variable consumed as an embedding table, its resolved spec on the
    analyzed mesh, and a verdict — ``vocab-sharded`` (dim 0 carries a
    mesh axis: the fused all-to-all route), ``dim-sharded`` (sharded,
    but the lookup must reshard the table), or ``replicated`` (flagged
    over-budget at/over the byte bar)."""
    from ..analysis import sharding as sharding_mod

    budget = int(budget or sharding_mod.EMBEDDING_TABLE_BUDGET_BYTES)
    tables = sharding_mod.embedding_tables_of(graph.get_operations(),
                                              report.variables)
    rows = []
    def _axes_of(entry):
        if entry is None:
            return ()
        return tuple(entry) if isinstance(entry, (tuple, list)) \
            else (entry,)

    for name, (vop, nbytes, spec, lookups) in sorted(tables.items()):
        spec_t = sharding_mod.to_partition_spec(spec) or ()
        if spec_t and _axes_of(spec_t[0]):
            verdict = "vocab-sharded"
        elif any(_axes_of(e) for e in spec_t):
            verdict = "dim-sharded"
        else:
            verdict = "replicated"
        rows.append({"table": name, "bytes": int(nbytes),
                     "spec": [e for e in spec_t],
                     "lookups": sorted(set(lookups)),
                     "verdict": verdict,
                     "over_budget": bool(verdict == "replicated"
                                         and nbytes >= budget)})
    return rows


def autoshard_summary(graph, mesh, fetches=None, partition_rules=None,
                      budget=None):
    """``graph_lint --autoshard``: run the PartitionSpec search offline
    on an imported GraphDef (stf.analysis.autoshard) and return the
    result — per-group chosen specs, predicted collective bytes vs the
    replicated baseline, per-shard peak vs ``budget``. Pure analysis:
    nothing is applied."""
    from ..analysis import autoshard as autoshard_mod

    return autoshard_mod.search_sharding(
        graph=graph, mesh=mesh, fetches=fetches or None,
        rules=partition_rules, budget_bytes=budget)


def run_lint(graph_def: dict, fetch_names=None, severities=None,
             level: str = "full", mesh=None, partition_rules=None,
             purpose=None, memory_budget=None):
    """Programmatic entry: returns (diagnostics, imported_graph|None,
    sharding_report|None)."""
    from .. import analysis
    from ..framework import graph as graph_mod
    from ..framework import graph_io

    diags = analysis.verify_graphdef(graph_def)
    if analysis.errors(diags):
        return diags, None, None
    graph = graph_mod.Graph()
    with graph.as_default():
        graph_io.import_graph_def(graph_def, name="")
    fetches = []
    for name in fetch_names or []:
        try:
            fetches.append(graph.as_graph_element(
                name, allow_tensor=True, allow_operation=True))
        except (KeyError, ValueError) as e:
            from ..analysis.diagnostics import ERROR, report

            report(diags, ERROR, "lint-cli/bad-fetch",
                   f"--fetch {name!r}: {e}")
    diags.extend(analysis.analyze(graph, fetches=fetches or None,
                                  level=level, severities=severities,
                                  purpose=purpose,
                                  memory_budget=memory_budget))
    report_obj = None
    if mesh:
        seeds = None
        if partition_rules:
            from ..parallel.api import match_partition_rules

            # an imported GraphDef has VariableV2 OPS, not Variable
            # objects: match over the ops' output tensors (shape is all
            # the matcher needs; seeds feed the analyzer by store name)
            store = {op.attrs.get("var_name", op.name): op.outputs[0]
                     for op in graph.get_operations()
                     if op.type == "VariableV2" and op.outputs}
            seeds = match_partition_rules(partition_rules, store)
        report_obj = analysis.analyze_sharding(
            graph=graph, mesh=mesh, seed_specs=seeds,
            fetches=fetches or None, with_peak=bool(fetches),
            severities=severities, purpose=purpose,
            memory_budget=memory_budget)
        diags.extend(report_obj.diagnostics)
    return diags, graph, report_obj


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m simple_tensorflow_tpu.tools.graph_lint",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("graphdef", help="GraphDef JSON file (graph_io format)")
    ap.add_argument("--fetch", action="append", default=[],
                    help="op/tensor name treated as a fetch (enables "
                         "hazard + unreachable-stateful + const-fetch "
                         "checks); repeatable")
    ap.add_argument("--severity", action="append", default=[],
                    metavar="CODE=LEVEL",
                    help="override a rule severity, e.g. "
                         "lint/unseeded-rng=error or narrow-64bit=off")
    ap.add_argument("--level", choices=["structural", "full"],
                    default="full", help="verifier depth (default full)")
    ap.add_argument("--json", action="store_true",
                    help="emit diagnostics as JSON lines (+ a trailing "
                         "summary record)")
    ap.add_argument("--mesh", default=None, metavar="SPEC",
                    help="run the sharding analyzer over an abstract "
                         "mesh: '8' (dp=8), '2x4' (dp=2,tp=4), or "
                         "'dp=2,tp=4'")
    ap.add_argument("--rules", default=None, metavar="RULES_JSON",
                    help="partition-rule file: JSON [[pattern, "
                         "[spec entries]], ...]; seeds variable "
                         "shardings for --mesh analysis "
                         "(match_partition_rules format)")
    ap.add_argument("--kernels", nargs="?", const="auto", default=None,
                    choices=["off", "auto", "force"], metavar="MODE",
                    help="report per-op Pallas/XLA kernel-routing "
                         "verdicts (stf.kernels) under MODE (default "
                         "auto): activates the lint/kernel-routing "
                         "rule and prints a per-op-type verdict "
                         "summary (routed / fallback+reason / "
                         "no-kernel)")
    ap.add_argument("--autoshard", action="store_true",
                    help="run the auto-sharding search "
                         "(stf.analysis.autoshard) over the graph on "
                         "the --mesh: prints the per-group chosen "
                         "PartitionSpecs and predicted collective "
                         "bytes vs the replicated baseline; --rules "
                         "seeds the search; with --budget, exit 1 "
                         "when the winning layout's predicted "
                         "per-shard peak HBM exceeds it")
    ap.add_argument("--emit-rules", default=None, metavar="OUT_JSON",
                    help="write the winning rule set (the --rules / "
                         "match_partition_rules format) to OUT_JSON "
                         "for review/snapshotting (requires "
                         "--autoshard)")
    ap.add_argument("--memory", action="store_true",
                    help="print the per-plan predicted peak device-"
                         "memory table (static cost model over each "
                         "--fetch closure, or the whole graph) and "
                         "activate the lint/memory-budget rule; with "
                         "--budget, exit 1 when any plan's predicted "
                         "peak exceeds it (the offline half of "
                         "ConfigProto(device_memory_budget_bytes=))")
    ap.add_argument("--budget", type=int, default=None, metavar="BYTES",
                    help="device-memory budget in bytes for --memory")
    ap.add_argument("--serving", action="store_true",
                    help="lint as an exported inference graph: activate "
                         "the serving-compatibility rules "
                         "(lint/serving-incompatible — host stages, "
                         "Print/logging io, unseeded RNG in the fetch "
                         "closure — and lint/serving-decode-cache: "
                         "KV-cache ops missing committed shardings, or "
                         "a cache tensor escaping to host)")
    ap.add_argument("--embeddings", action="store_true",
                    help="lint the sparse-embedding plane (requires "
                         "--mesh): activate the lint/embedding-"
                         "replicated-table ERROR (a table at/over "
                         "--budget bytes — default 128 MiB — that "
                         "resolves replicated on a >1-device mesh) and "
                         "print a per-table verdict column "
                         "(vocab-sharded / dim-sharded / replicated)")
    ap.add_argument("--numerics", action="store_true",
                    help="lint for statically visible NaN/Inf seeds: "
                         "activate the lint/numeric-risk rule "
                         "(unguarded Log/Rsqrt/Reciprocal/Div/Exp "
                         "operands, bf16/f16 long-axis reductions) — "
                         "the offline half of the stf.debug.numerics "
                         "runtime health plane (STF_NUMERICS)")
    ap.add_argument("--max-severity", default="error",
                    choices=["note", "warning", "error"],
                    help="exit nonzero when any diagnostic reaches this "
                         "severity (default: error)")
    args = ap.parse_args(argv)

    from ..analysis.diagnostics import SEVERITIES

    severities = {}
    for kv in args.severity:
        if "=" not in kv:
            ap.error(f"--severity needs CODE=LEVEL, got {kv!r}")
        k, v = kv.split("=", 1)
        if v not in SEVERITIES + ("off",):
            ap.error(f"--severity {k}: level must be one of "
                     f"{SEVERITIES + ('off',)}, got {v!r}")
        severities[k] = v

    mesh = None
    if args.mesh:
        from ..analysis.sharding import parse_mesh_arg

        try:
            mesh = parse_mesh_arg(args.mesh)
        except (ValueError, TypeError) as e:
            ap.error(f"--mesh {args.mesh!r}: {e}")
    partition_rules = None
    if args.rules:
        if not mesh:
            ap.error("--rules requires --mesh")
        with open(args.rules) as f:
            raw = json.load(f)
        partition_rules = [(pat, tuple(spec)) for pat, spec in raw]

    with open(args.graphdef) as f:
        gd = json.load(f)

    from .. import analysis

    if sum(bool(x) for x in (args.kernels, args.serving, args.memory,
                             args.numerics, args.autoshard,
                             args.embeddings)) > 1:
        ap.error("--kernels, --serving, --memory, --numerics, "
                 "--autoshard, and --embeddings are separate lint "
                 "purposes; run them as separate invocations")
    if args.budget is not None and not (args.memory or args.autoshard
                                        or args.embeddings):
        ap.error("--budget requires --memory, --autoshard, or "
                 "--embeddings")
    if args.embeddings and not mesh:
        ap.error("--embeddings requires --mesh (the verdicts are the "
                 "RESOLVED table shardings on that mesh)")
    if args.autoshard and not mesh:
        ap.error("--autoshard requires --mesh")
    if args.emit_rules and not args.autoshard:
        ap.error("--emit-rules requires --autoshard")
    purpose = "serving" if args.serving else (
        "kernels" if args.kernels else (
            "memory" if args.memory else (
                "numerics" if args.numerics else (
                    "embeddings" if args.embeddings else None))))
    from ..kernels import registry as _kreg

    with _kreg.activate(args.kernels):
        diags, _graph, report = run_lint(gd, fetch_names=args.fetch,
                                         severities=severities,
                                         level=args.level, mesh=mesh,
                                         partition_rules=partition_rules,
                                         purpose=purpose,
                                         memory_budget=args.budget)
        kernel_summary = None
        if args.kernels and _graph is not None:
            kernel_summary = kernel_routing_summary(_graph,
                                                    mode=args.kernels)
        embedding_rows = None
        if args.embeddings and _graph is not None and report is not None:
            embedding_rows = embedding_summary(_graph, report,
                                               budget=args.budget)
        memory_rows = None
        if args.memory and _graph is not None:
            fetches = []
            for name in args.fetch:
                try:
                    fetches.append(_graph.as_graph_element(
                        name, allow_tensor=True, allow_operation=True))
                except (KeyError, ValueError):
                    pass
            memory_rows = memory_summary(_graph, fetches=fetches,
                                         budget=args.budget)
        autoshard_result = None
        if args.autoshard and _graph is not None:
            fetches = []
            for name in args.fetch:
                try:
                    fetches.append(_graph.as_graph_element(
                        name, allow_tensor=True, allow_operation=True))
                except (KeyError, ValueError):
                    pass
            if args.budget is not None and not fetches:
                # per-shard peak is priced over the fetch closure; with
                # nothing resolved the budget gate would pass vacuously
                ap.error("--autoshard --budget needs a resolvable "
                         f"--fetch (got {args.fetch!r}) — the per-shard "
                         "peak it gates is priced over the fetch closure")
            autoshard_result = autoshard_summary(
                _graph, mesh, fetches=fetches,
                partition_rules=partition_rules, budget=args.budget)
            if args.emit_rules:
                with open(args.emit_rules, "w") as f:
                    json.dump(autoshard_result.rules(), f, indent=1)
    if args.json:
        for d in diags:
            print(json.dumps(d.to_dict()))
        if kernel_summary is not None:
            print(json.dumps({"kernel_routing": kernel_summary}))
        if memory_rows is not None:
            print(json.dumps({"memory": memory_rows}))
        if embedding_rows is not None:
            print(json.dumps({"embeddings": embedding_rows}))
        if autoshard_result is not None:
            print(json.dumps(
                {"autoshard": json.loads(autoshard_result.to_json())}))
        if report is not None:
            print(json.dumps({"summary": report.summary()}))
    else:
        print(analysis.format_report(
            diags, header=f"graph_lint {args.graphdef}:"))
        if memory_rows is not None:
            hdr = "plan" + " " * 28 + "peak_bytes   resident   transient"
            print(f"memory ({len(memory_rows)} plan(s)"
                  + (f", budget {args.budget} B" if args.budget else "")
                  + f"):\n  {hdr}")
            for r in memory_rows:
                if "error" in r:
                    print(f"  {r['plan'][:30]:<32}(uncostable: "
                          f"{r['error'][:40]})")
                    continue
                mark = "" if r.get("within_budget", True) \
                    else "  OVER BUDGET"
                print(f"  {r['plan'][:30]:<32}"
                      f"{r['predicted_peak_bytes']:>10} "
                      f"{r['resident_bytes']:>10} "
                      f"{r['transient_bytes']:>10}{mark}")
        if embedding_rows is not None:
            print(f"embeddings ({len(embedding_rows)} table(s)):")
            for r in embedding_rows:
                spec = ", ".join("None" if e is None else str(e)
                                 for e in r["spec"]) or "-"
                mark = "  OVER BUDGET" if r["over_budget"] else ""
                print(f"  {r['table'][:38]:<40}{r['bytes']:>12} B  "
                      f"P({spec})  {r['verdict']}{mark}")
        if kernel_summary is not None:
            print(f"kernel routing [{kernel_summary['mode']}/"
                  f"{kernel_summary['backend']}]: "
                  f"{kernel_summary['no_kernel_ops']} op(s) with no "
                  "registered kernel")
            for t, verdicts in sorted(
                    kernel_summary["by_op_type"].items()):
                row = ", ".join(f"{k}={v}"
                                for k, v in sorted(verdicts.items()))
                print(f"  {t}: {row}")
        if autoshard_result is not None:
            r = autoshard_result
            print(f"autoshard ({len(r.groups)} group(s), "
                  f"{r.candidates_priced} candidate(s), "
                  f"{r.search_seconds:.3f}s):")
            for g in sorted(r.groups, key=lambda g: -g["bytes"]):
                spec = ", ".join("None" if e is None else str(e)
                                 for e in g["spec"]) or "-"
                print(f"  [{g['kind']}] {g['pattern'][:40]:<42}"
                      f"P({spec})  {int(g['bytes'])} B")
            print(f"  predicted collective bytes/step: "
                  f"{int(r.predicted['collective_bytes'])} searched vs "
                  f"{int(r.baseline['collective_bytes'])} replicated")
            if r.predicted.get("per_shard_peak_bytes") is not None:
                over = " OVER BUDGET" if r.predicted["over_budget"] \
                    else ""
                print(f"  per-shard peak "
                      f"{int(r.predicted['per_shard_peak_bytes'])} B"
                      + (f" (budget {args.budget} B){over}"
                         if args.budget else ""))
        if report is not None:
            s = report.summary()
            print(f"sharding: {s['n_collective_edges']} collective "
                  f"edge(s), {int(s['total_collective_bytes'])} "
                  f"predicted bytes/step "
                  f"{s['bytes_by_kind']}"
                  + (f", per-shard peak "
                     f"{int(s['per_shard_peak_bytes'])} bytes"
                     if s.get("per_shard_peak_bytes") else ""))
    order = {s: i for i, s in enumerate(SEVERITIES)}
    threshold = order[args.max_severity]
    worst = max((order.get(d.severity, 0) for d in diags), default=-1)
    if autoshard_result is not None and args.budget \
            and autoshard_result.predicted.get("over_budget"):
        return 1
    return 1 if worst >= threshold else 0


if __name__ == "__main__":
    sys.exit(main())
