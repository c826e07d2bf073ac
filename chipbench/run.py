"""chipbench: one run of one cell of BENCHMARK.json, on the chip.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, on the machine it is started on. Finds the cell's
configuration and traffic files by name, hands them to the runner the
configuration names, and prints as its last line of standard output

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"], "compared"}

with the cell's end-to-end metrics (``--trace 0``) or its per-layer metrics
(``--trace 1``). Exits non-zero, printing no result, without a TPU, on a
``device_kind`` that peaks.json does not hold, or with fewer chips than the
cell asks for. There is no size or platform switch: tests rehearse the
runners at tiny sizes through ``measure()``.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import compare, harness, trace_reduce  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def measure(args, manifest, cell, config, mix, limits, devices, peak,
            t_start):
    """Everything after the look for a chip: run the cell's runner, read
    the per-layer metrics, decide ``correct``. Returns the result line as
    a dict (tests call this with tiny files and the CPU's devices)."""
    specs = harness.layer_metric_specs(manifest, cell["name"])
    model_name = config.get("model_name", config["name"])
    watched = {}
    for _, spec in specs:
        params = spec.get("params", {})
        if "metric" in params:
            watched[params["metric"]] = [
                lab.format(model=model_name) for lab in params["labels"]]

    def snapshot_counters():
        return {name: harness.read_counter(name, labels)
                for name, labels in watched.items()}

    ctx = {
        "args": args, "cell": cell, "config": config, "traffic": mix,
        "limits": limits, "devices": devices, "peak": peak,
        "t_start": t_start, "spans": harness.Spans(),
        "clock": harness.CompileClock(),
        "tracer": harness.Tracer(args.trace, mix.get("trace_seconds", 5),
                                 mix.get("trace_start_seconds", 0)),
        "snapshot_counters": snapshot_counters, "model_name": model_name,
    }
    runner = importlib.import_module("chipbench.runners." + config["runner"])
    result = runner.run(ctx)
    harness.log(info=result["info"])

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": compare.is_correct(result["compared"]),
            "attempted": result["attempted"], "failed": result["failed"]}
    if args.trace:
        trace = ctx["tracer"].reduce(cell["chips"])
        facts = dict(result["facts"], trace=trace, peak=peak,
                     chips=cell["chips"])
        line["metrics"] = harness.read_layer_metrics(specs, facts)
        device.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = trace["breakdown"]
        harness.log(kernel_patterns={
            entry["name"]: trace_reduce.pattern_seconds(
                trace["ops"], spec["params"]["pattern"], trace["window"])
            for entry, spec in specs if "pattern" in spec.get("params", {})})
        harness.log(trace={"bytes": trace["trace_bytes"],
                           "device_events": len(trace["ops"]),
                           "work": result["facts"].get("work"),
                           "lines": trace["lines"],
                           "heaviest": trace_reduce.top_ops(
                               trace["ops"], trace["window"], n=25),
                           "custom_calls": trace_reduce.top_ops(
                               trace["ops"], trace["window"], n=25,
                               operands=True, limit=400,
                               only=" custom-call(")})
    else:
        units = {m["name"]: m["unit"] for m in harness.cell_metrics(
            manifest, "end_to_end", cell["name"])}
        missing = set(units) - set(result["end_to_end"])
        if missing:
            raise RuntimeError(f"runner did not report {sorted(missing)}")
        line["metrics"] = {k: {"value": float(result["end_to_end"][k]),
                               "unit": unit} for k, unit in units.items()}
    line["device"] = device
    line["compared"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in result["compared"].items()}
    return line, result


def main(argv=None):
    args = parse_args(argv)
    manifest = harness.load_manifest()
    cell, entry = harness.find_cell(manifest, args.workload)
    config = harness.load_json(harness.ROOT, entry["file"])
    mix = harness.load_json(harness.HERE, "traffic", cell["traffic"] + ".json")
    limits = harness.load_json(harness.HERE, "limits", cell["name"] + ".json")

    devices, peak = harness.require_device(cell["chips"])
    cache_dir = harness.enable_compile_cache()
    harness.log(start={"workload": cell["name"], "seed": args.seed,
                       "seconds": args.seconds, "trace": args.trace,
                       "device_kind": devices[0].device_kind,
                       "cache_dir": cache_dir,
                       "cache_before": harness.cache_stats(cache_dir)})
    line, result = measure(args, manifest, cell, config, mix, limits,
                           devices, peak, _T_START)
    harness.log(cache_after=harness.cache_stats(cache_dir),
                total_s=time.perf_counter() - _T_START)
    harness.print_compared(result["compared"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
