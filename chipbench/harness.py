"""What every runner shares: the manifest, the device, spans, the trace.

The harness is driven by data. A cell of BENCHMARK.json names a
configuration and a traffic mix; this module finds their files by those
names and hands them to the runner the configuration names. Per-layer
metrics are files under layer_metrics/ read by the reader each names.
Nothing here knows a cell, a configuration or a metric by name.
"""

from __future__ import annotations

import contextlib
import importlib
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_manifest():
    return load_json(ROOT, "BENCHMARK.json")


def find_cell(manifest, workload):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(f"chipbench: no workload {workload!r} in "
                         f"BENCHMARK.json (have {sorted(cells)})")
    cell = cells[workload]
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    return cell, entry


def cell_metrics(manifest, section, workload):
    """The metrics of ``section`` this cell reports: those that list it
    under ``workloads``, and those that list nothing (every cell)."""
    return [m for m in manifest[section]
            if workload in m.get("workloads", [workload])]


def log(**facts):
    """One JSON line of facts on standard output (never the last)."""
    print(json.dumps(facts, default=str), flush=True)


# -- device -------------------------------------------------------------------

def require_device(chips):
    """The chip this run measures, or exit non-zero with no result."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    peaks = load_json(HERE, "peaks.json")
    if dev.platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, found {dev.platform!r}; "
                         "nothing is measured off the chip")
    if dev.device_kind not in peaks:
        raise SystemExit(f"chipbench: device_kind {dev.device_kind!r} is not "
                         f"in peaks.json ({sorted(peaks)})")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: cell needs {chips} chip(s), "
                         f"found {len(devices)}")
    return devices[:chips], peaks[dev.device_kind]


def memory_peak_bytes(devices, program_temp_bytes=0):
    """Peak bytes on the fullest chip: the allocator's
    ``peak_bytes_in_use`` plus ``program_temp_bytes``, the temporaries of
    the largest compiled program the window drove (its
    ``memory_analysis().temp_size_in_bytes``). The v5e runtime does not
    count a running program's temporaries in its allocator statistics
    (PERF.md Findings: a program with 3.2 GB of temporaries moved the
    peak by nothing), so without them a training step would read as its
    weights and optimizer state alone."""
    stats = [d.memory_stats() or {} for d in devices]
    log(program_temp_bytes=int(program_temp_bytes))
    log(memory_stats=[{k: s[k] for k in ("peak_bytes_in_use", "bytes_in_use",
                                         "bytes_limit", "largest_alloc_size")
                       if k in s} for s in stats])
    return (max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
            + int(program_temp_bytes))


def enable_compile_cache():
    """The program's one rule for where the cache lives
    (compiler/aot.enable_persistent_cache: JAX_COMPILATION_CACHE_DIR if
    set, else <checkout>/.jax_cache), and every program kept, however
    fast it compiled, so that a second run compiles nothing."""
    import jax
    from simple_tensorflow_tpu.compiler import aot

    cache_dir = aot.enable_persistent_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


def cache_stats(cache_dir):
    try:
        files = os.listdir(cache_dir)
    except OSError:
        return {"entries": 0, "bytes": 0}
    return {"entries": len(files),
            "bytes": sum(os.path.getsize(os.path.join(cache_dir, f))
                         for f in files)}


class CompileClock:
    """Backend compiles (persistent-cache reads included) and cache hits,
    from jax.monitoring: what ran inside the window must be zero."""

    def __init__(self):
        import jax.monitoring as jm

        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        jm.register_event_duration_secs_listener(self._on_duration)
        jm.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return (self.compiles, self.compile_s, self.hits)

    def since(self, mark):
        return {"compiles": self.compiles - mark[0],
                "compile_s": round(self.compile_s - mark[1], 3),
                "cache_hits": self.hits - mark[2]}


# -- kernel routing -----------------------------------------------------------

def set_kernel_mode(config):
    """The kernel-routing mode the configuration states
    (``program.kernel_mode``: off | auto | force) through the program's
    ``stf.kernels.set_mode``, before anything is traced; a configuration
    that states none gets the program's default back. A routing decision
    is a pure function of op, shapes, backend, mesh and mode, so two
    checkouts route alike in every mode."""
    import simple_tensorflow_tpu as stf

    stf.kernels.set_mode(config["program"].get("kernel_mode"))


def kernel_routing():
    """What the program's kernel registry decided in this process: the
    mode, calls routed to Pallas and to XLA by reason, and the tiles the
    flash-attention rule chose from the shapes it was given."""
    import simple_tensorflow_tpu as stf

    snap = stf.kernels.snapshot()
    return {k: snap[k] for k in ("mode", "routed", "fallback",
                                 "flash_tiles")}


# -- weights ------------------------------------------------------------------

def map_variables(config, trainable):
    """{reference leaf (per layer: layers.<i>.<name>): program Variable}
    through the configuration's name map; every trainable variable of the
    program has to be named by it."""
    by_name = {v.name.split(":")[0]: v for v in trainable}
    layers = config["reference"]["spec"]["layers"]
    out = {}
    for leaf, template in config["variables"].items():
        if leaf.startswith("layers."):
            for i in range(layers):
                out[f"layers.{i}.{leaf.split('.', 1)[1]}"] = \
                    by_name.pop(template.format(i=i))
        else:
            out[leaf] = by_name.pop(template)
    if by_name:
        raise RuntimeError("trainable variables the configuration's name "
                           f"map does not cover: {sorted(by_name)}")
    return out


def load_variables(sess, variables, params):
    """The reference's seeded weights (``init_params``) into the
    program's variables. Returns the host copies (float32) by leaf."""
    import numpy as np
    from chipbench.reference import postln_transformer as ref

    host = {k: np.asarray(v, np.float32)
            for k, v in ref.split_leaves(params).items()}
    for leaf, var in variables.items():
        if tuple(var.shape.as_list()) != host[leaf].shape:
            raise RuntimeError(f"{leaf}: program shape {var.shape} != "
                               f"reference shape {host[leaf].shape}")
        var.load(host[leaf], sess)
    return host


# -- program counters ----------------------------------------------------------

def read_counter(name, labels):
    """Current value of one cell of a metric the program registered, or
    None where the program has no such metric. Counters give a number,
    samplers ``{"count", "sum", ...}``."""
    from simple_tensorflow_tpu.platform import monitoring

    metric = monitoring.get_metric(name)
    if metric is None:
        return None
    return metric.get_cell(*labels).value()


# -- spans and the trace --------------------------------------------------------

class Spans:
    """The harness's own spans around its calls into the program: kept in
    memory on the host clock, and written into the profiler's trace as
    ``chipbench:<label>`` so that idle gaps can be labelled by them."""

    def __init__(self):
        self.records = []  # (label, start_s, end_s) on time.perf_counter

    @contextlib.contextmanager
    def span(self, label):
        import jax.profiler

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench:" + label):
            yield
        self.records.append((label, t0, time.perf_counter()))

    def within(self, label, lo, hi):
        return [(a, b) for name, a, b in self.records
                if name == label and a >= lo and b <= hi]


class Tracer:
    """Traces ``seconds`` of the window, from ``start_after`` seconds into
    it, when asked to. The runner calls :meth:`arm` as the window opens
    and :meth:`poll` when the trace is due to start (``t_open`` +
    ``start_after``) and to stop (``t0`` + ``seconds``): a serving runner
    sleeps in between, a training runner polls between its steps."""

    def __init__(self, enabled, seconds, start_after=0.0):
        self.enabled = bool(enabled)
        self.seconds = float(seconds)
        self.start_after = float(start_after)
        self.dir = None
        self.t_open = self.t0 = self.t1 = None
        self._window = None

    def arm(self):
        self.t_open = time.perf_counter()
        self.poll()

    def poll(self):
        if not self.enabled or self.t_open is None:
            return
        now = time.perf_counter()
        if self._window is not None:
            if now - self.t0 >= self.seconds:
                self.stop()
        elif self.t0 is None and now - self.t_open >= self.start_after:
            self._start()

    def _start(self):
        import jax.profiler

        self.dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._window = jax.profiler.TraceAnnotation("chipbench:window")
        self._window.__enter__()
        self.t0 = time.perf_counter()

    def stop(self):
        """Close the traced part; safe to call when not tracing."""
        if self._window is None:
            return
        import jax.profiler

        self.t1 = time.perf_counter()
        self._window.__exit__(None, None, None)
        self._window = None
        jax.profiler.stop_trace()

    def reduce(self, chips):
        from chipbench import trace_reduce

        if self.dir is None:
            raise RuntimeError("the window closed before the trace began")
        try:
            trace = trace_reduce.load_xplane(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        reduced = trace_reduce.reduce(trace, chips)
        reduced["trace_bytes"] = trace["bytes"]
        return reduced


# -- per-layer metrics -----------------------------------------------------------

def layer_metric_specs(manifest, workload):
    """[(manifest entry, the metric's own file)] for this cell."""
    return [(m, load_json(HERE, "layer_metrics", m["name"] + ".json"))
            for m in cell_metrics(manifest, "per_layer", workload)]


def read_layer_metrics(specs, facts):
    """Each metric through the reader its file names. A reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for entry, spec in specs:
        reader = importlib.import_module(
            "chipbench.readers." + spec["reader"])
        value = reader.read(spec.get("params", {}), facts)
        if value is not None:
            out[entry["name"]] = {"value": float(value),
                                  "unit": entry["unit"]}
    return out


def import_attr(path):
    """``package.module:attr`` -> the attribute."""
    module, attr = path.split(":")
    return getattr(importlib.import_module(module), attr)


def print_compared(compared):
    """The numbers compared, each beside its limit, as the last lines on
    standard error."""
    for name, (value, limit) in compared.items():
        print(f"chipbench compared {name} = {value!r} limit {limit!r} "
              f"{'ok' if value <= limit else 'OVER'}", file=sys.stderr)
    sys.stderr.flush()
