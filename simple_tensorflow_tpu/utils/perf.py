"""Performance tracing: step timing, MFU, roofline estimates
(ref role: tensorflow/core/common_runtime/step_stats_collector.cc + the
timeline tooling; TPU-native it reads XLA cost analysis + jax.profiler).

- StepTimer: wall-per-step ring buffer with percentile summary.
- mfu(): achieved FLOP/s over the chip's bf16 peak from the compiled
  executable's XLA cost analysis (flops) + measured step time.
- roofline(): bytes-accessed/flops arithmetic intensity vs the chip's
  HBM bandwidth — says whether a step is compute- or bandwidth-bound.
- trace(): context manager around jax.profiler for chrome://tracing dumps.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional

import numpy as np

# Published per-chip figures, keyed by the ``device_kind`` JAX reports:
# (peak bf16 FLOP/s, HBM bytes/s, HBM bytes). The one table of the
# repo — the kernel cost gate reads it too. An accelerator that is not
# in it is an error, never a default.
#   "TPU v5 lite" (v5e): Google Cloud documentation, "TPU v5e" —
#   197 TFLOP/s bf16, 819 GB/s and 16 GB of HBM per chip.
CHIP_TABLE = {
    "TPU v5 lite": (197e12, 819e9, 16e9),
}
# The CPU has no published peak. These are NOMINAL figures so the
# planning maths (cost gate, remat, microbatch sizing) has something to
# divide by under tier-1; no utilization is ever computed from them
# (mfu/roofline refuse the CPU).
_CPU_NOMINAL = (1e12, 100e9, 4e9)


def published_chip(device=None):
    """(peak_flops, peak_hbm_bw, hbm_bytes) of an accelerator in
    CHIP_TABLE; any other device, the CPU included, raises."""
    import jax

    d = device or jax.devices()[0]
    kind = getattr(d, "device_kind", "")
    if kind not in CHIP_TABLE:
        raise ValueError(
            f"no published peak for device kind {kind!r} (platform "
            f"{d.platform!r}): add it to utils.perf.CHIP_TABLE with its "
            "source")
    return CHIP_TABLE[kind]


def has_peak(device=None) -> bool:
    """Whether a utilization may be computed on this device: False on
    the CPU (nominal figures only). An accelerator answers True even
    when it is missing from CHIP_TABLE — asking for its peak then
    raises, which is the point."""
    import jax

    return (device or jax.devices()[0]).platform != "cpu"


def _planning_chip(device=None):
    import jax

    d = device or jax.devices()[0]
    return _CPU_NOMINAL if d.platform == "cpu" else published_chip(d)


def chip_spec(device=None):
    """(peak_flops, peak_hbm_bw) for planning maths on the attached
    device — published for an accelerator, nominal for the CPU."""
    return _planning_chip(device)[:2]


def chip_hbm_bytes(device=None):
    """Per-chip HBM capacity for the attached device (memory-planning
    inputs: remat decisions, pipeline microbatch sizing) — published
    for an accelerator, nominal for the CPU."""
    return _planning_chip(device)[2]


class StepTimer:
    """Wall-clock per-step stats; call mark() after each synced step."""

    def __init__(self, window=200):
        self._times: List[float] = []
        self._window = window
        self._last: Optional[float] = None

    def start(self):
        self._last = time.perf_counter()

    def mark(self) -> float:
        now = time.perf_counter()
        dt = now - (self._last if self._last is not None else now)
        self._last = now
        self._times.append(dt)
        if len(self._times) > self._window:
            self._times.pop(0)
        return dt

    @property
    def steps(self) -> int:
        return len(self._times)

    def summary(self) -> Dict[str, float]:
        if not self._times:
            return {}
        a = np.asarray(self._times)
        return {"mean_s": float(a.mean()),
                "p50_s": float(np.percentile(a, 50)),
                "p90_s": float(np.percentile(a, 90)),
                "steps_per_sec": float(1.0 / a.mean())}


def cost_of(compiled) -> Dict[str, float]:
    """Normalize jax cost analysis across versions: {flops, bytes}."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not ca:
        return {}
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def _aval_bytes(avals) -> int:
    """Sum of abstract-shape byte sizes over a (nested) aval pytree."""
    total = 0
    stack = [avals]
    while stack:
        a = stack.pop()
        if a is None:
            continue
        if isinstance(a, (list, tuple)):
            stack.extend(a)
            continue
        if isinstance(a, dict):
            stack.extend(a.values())
            continue
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is None or dtype is None:
            continue
        n = 1
        for d in shape:
            try:
                n *= int(d)
            except (TypeError, ValueError):
                n = 0
                break
        total += n * int(np.dtype(dtype).itemsize)
    return total


def memory_of(compiled, lowered=None) -> Dict[str, int]:
    """Normalize jax ``Compiled.memory_analysis()`` across versions:
    {argument_bytes, output_bytes, temp_bytes, alias_bytes,
    generated_code_bytes, peak_bytes} (peak ≈ arguments + outputs + XLA
    temp allocation, minus aliased/donated buffers counted twice).

    Backends that expose no (or an all-zero) ``memory_analysis`` fall
    back to summing the XLA cost-analysis byte components plus
    abstract-shape sizes from the executable's avals (ISSUE 13
    satellite: tier-1 CPU runs must still produce peak/argument/output
    stats so the memory-accounting plane is testable without TPU).
    Fallback results carry ``"estimated": 1``."""
    ma = None
    try:
        ma = compiled.memory_analysis()
    except Exception:
        ma = None
    if ma is not None:
        out = {
            "argument_bytes": int(getattr(ma, "argument_size_in_bytes",
                                          0)),
            "output_bytes": int(getattr(ma, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(ma, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(ma, "alias_size_in_bytes", 0)),
            "generated_code_bytes": int(
                getattr(ma, "generated_code_size_in_bytes", 0)),
        }
        if any(out.values()):
            # aliased (donated) buffers are counted in both argument
            # and output sizes but exist once on device — subtract
            # them from the peak
            out["peak_bytes"] = (out["argument_bytes"]
                                 + out["output_bytes"]
                                 + out["temp_bytes"]
                                 - out["alias_bytes"])
            return out
    # -- fallback: cost-analysis components + aval sizes ---------------------
    arg_bytes = 0
    out_bytes = 0
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        ca = ca or {}
        out_bytes = int(ca.get("bytes accessedout{}", 0))
        arg_bytes = int(sum(
            v for k, v in ca.items()
            if k.startswith("bytes accessed") and k != "bytes accessed"
            and k != "bytes accessedout{}"))
    except Exception:
        ca = {}
    if not arg_bytes:
        for src in (compiled, lowered):
            avals = getattr(src, "in_avals", None) if src is not None \
                else None
            if avals:
                arg_bytes = _aval_bytes(avals)
                break
    if not out_bytes and lowered is not None:
        out_bytes = _aval_bytes(getattr(lowered, "out_info", None))
    if not arg_bytes and not out_bytes:
        return {}
    out = {
        "argument_bytes": arg_bytes,
        "output_bytes": out_bytes,
        "temp_bytes": 0,
        "alias_bytes": 0,
        "generated_code_bytes": 0,
        "estimated": 1,
    }
    out["peak_bytes"] = arg_bytes + out_bytes
    return out


_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

# `%name = SHAPE all-reduce(...)` (async variants emit -start/-done
# pairs; only -start carries the payload — -done's trailing "(" will not
# match the pattern, so pairs count once)
_HLO_COLLECTIVE_RE = None


def collective_bytes_of(compiled) -> Dict[str, float]:
    """Per-kind payload bytes of the collective instructions in a
    compiled executable's (partitioned) HLO — the machine-checkable
    comparator for the sharding analyzer's predicted collective bytes
    (stf.analysis.sharding; the bench asserts the two within 25%).

    Sums the RESULT shape bytes of every all-reduce / all-gather /
    all-to-all / collective-permute / reduce-scatter instruction.
    Sync tuple-shaped results (variadic collectives) sum their leaves;
    an async ``-start``'s tuple is (operand, result[, u32 contexts]),
    so only the result leaf counts — summing it whole would tally the
    payload twice. Returns {} when the backend exposes no HLO text."""
    import re

    global _HLO_COLLECTIVE_RE
    if _HLO_COLLECTIVE_RE is None:
        _HLO_COLLECTIVE_RE = re.compile(
            r"=\s+((?:\([^)]*\))|(?:\S+))\s+"
            r"(all-reduce|all-gather|all-to-all|collective-permute|"
            r"reduce-scatter)(-start)?\(")
    texts = []
    try:
        mods = compiled.hlo_modules()
        texts = [m.to_string() for m in mods]
    except Exception:
        try:
            texts = [compiled.as_text()]
        except Exception:
            return {}
    shape_re = re.compile(r"([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
    out: Dict[str, float] = {}
    for text in texts:
        for m in _HLO_COLLECTIVE_RE.finditer(text):
            shape_txt, kind, is_start = (m.group(1), m.group(2),
                                         m.group(3))
            leaves = []
            for sm in shape_re.finditer(shape_txt):
                dt = _HLO_DTYPE_BYTES.get(sm.group(1))
                if dt is None:
                    continue
                n = 1
                dims = sm.group(2)
                if dims:
                    for d in dims.split(","):
                        n *= int(d)
                leaves.append(n * dt)
            if is_start and len(leaves) >= 2:
                nbytes = float(leaves[1])
            else:
                nbytes = float(sum(leaves))
            if nbytes:
                out[kind] = out.get(kind, 0.0) + nbytes
    if out:
        out["total"] = sum(out.values())
    return out


def mfu(step_flops: float, step_seconds: float, device=None) -> float:
    """Model FLOPs Utilization: achieved/peak. Only against a published
    peak: on the CPU (or an accelerator missing from CHIP_TABLE) this
    raises instead of dividing by a nominal figure."""
    peak = published_chip(device)[0]
    if step_seconds <= 0:
        return 0.0
    return step_flops / step_seconds / peak


def roofline(step_flops: float, step_bytes: float, device=None
             ) -> Dict[str, float]:
    """Arithmetic intensity vs the machine ridge point: intensity >
    ridge -> compute-bound (good: MXU busy); below -> HBM-bound (fuse
    more / recompute instead of re-reading). Published peaks only, like
    :func:`mfu`."""
    peak_flops, peak_bw, _ = published_chip(device)
    intensity = step_flops / step_bytes if step_bytes else float("inf")
    ridge = peak_flops / peak_bw
    attainable = min(peak_flops, intensity * peak_bw)
    return {"intensity_flops_per_byte": intensity,
            "ridge_point": ridge,
            "compute_bound": intensity >= ridge,
            "attainable_flops": attainable,
            "roofline_fraction_of_peak": attainable / peak_flops}


class PerfReport:
    """Combines a compiled step's cost analysis with measured wall time."""

    def __init__(self, compiled=None, flops_per_step: Optional[float] = None,
                 device=None):
        self._cost = cost_of(compiled) if compiled is not None else {}
        if flops_per_step is not None:
            self._cost["flops"] = flops_per_step
        self._device = device
        self.timer = StepTimer()

    def step_done(self):
        return self.timer.mark()

    def report(self) -> Dict[str, Any]:
        s = self.timer.summary()
        if not s:
            return {}
        out = dict(s)
        flops = self._cost.get("flops")
        if flops:
            out["achieved_tflops"] = flops / s["mean_s"] / 1e12
        # utilizations only against a published peak: none on the CPU
        if not has_peak(self._device):
            return out
        if flops:
            out["mfu"] = mfu(flops, s["mean_s"], self._device)
        if self._cost.get("bytes"):
            out.update(roofline(self._cost.get("flops", 0.0),
                                self._cost["bytes"], self._device))
        return out
