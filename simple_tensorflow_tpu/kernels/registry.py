"""Process-global kernel registry: Pallas vs XLA routing at lowering time.

The Pallas kernels (ops/pallas/) and their stock-XLA lowerings are two
implementations of the same op contract. This registry is the single
place that decides, per (op type, shapes, dtypes, backend), which one a
lowering emits — the TPU-native analogue of the reference's per-device
kernel registry (ref: tensorflow/core/framework/op_kernel.cc kernel
dispatch by KernelDef priority), upgraded with the cost-model gating the
TPU-v3 MLPerf submissions used to decide hand-tuned kernel vs compiler
output (1909.09756 §"performance optimizations").

Three modes (``STF_PALLAS`` env / ``stf.kernels.set_mode`` /
``ConfigProto(kernel_registry=...)``):

  off    the registry is inert — every op lowers exactly as it did
         before the registry existed (the fused graph ops keep their
         Pallas kernels, composed ops keep their jnp lowerings, the
         optimizer tail stays per-variable assigns).
  auto   (default) eligibility checks, then a static cost-model gate
         (roofline pricing of both lowerings, framework/cost_model.py
         accounting), then — for shapes the gate cannot confidently
         price, or always under ``STF_KERNEL_AUTOTUNE=1`` — a measured
         micro-autotune: the first call on an ungated shape times both
         lowerings and persists the verdict alongside the persistent
         compile cache (compiler.aot.enable_persistent_cache). A
         measured verdict always overrides the static gate: auto mode
         never picks a lowering the autotune measured slower.
  force  the Pallas implementation for every eligible op (interpret
         mode off-TPU, so the whole tier runs under tier-1 CPU tests).

Every decision increments exactly one of ``/stf/kernels/routed{op}``
(Pallas chosen) or ``/stf/kernels/fallback{op, reason}`` (XLA chosen),
so the counters explain every non-routed call. Decisions are cached per
(op, key, mode, backend) — a given executable always retraces to the
same routing.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..platform import monitoring
from ..platform import sync as _sync

MODES = ("off", "auto", "force")

metric_routed = monitoring.Counter(
    "/stf/kernels/routed",
    "lowering decisions that chose the Pallas kernel", "op")
metric_fallback = monitoring.Counter(
    "/stf/kernels/fallback",
    "lowering decisions that chose the stock XLA lowering", "op", "reason")
metric_autotune_runs = monitoring.Counter(
    "/stf/kernels/autotune_runs",
    "micro-autotune measurements (both lowerings timed once per "
    "ungated (op, shape, dtype, backend) key)", "op")
metric_flash_tiles = monitoring.Counter(
    "/stf/kernels/flash_tiles",
    "flash-attention kernel traces by the regime and tiles the shape "
    "rule chose (ops/pallas/flash_attention.tiles)",
    "regime", "block_q", "block_k", "heads_per_step")

# -- mode ---------------------------------------------------------------------

_state = threading.local()          # per-thread activation (Session lowering)
_mode_override: Optional[str] = None
_lock = _sync.RLock("kernels/registry", rank=_sync.RANK_STATE)


def _env_mode() -> str:
    """Resolve the process-default mode from the environment.

    STF_PALLAS=0 is the documented kill switch (registry inert, pre-PR
    lowerings); STF_PALLAS=force pins every eligible op to Pallas;
    anything else (or unset) is auto. STF_KERNELS=off|auto|force is the
    explicit spelling of the same knob and wins when both are set.
    """
    v = os.environ.get("STF_KERNELS")
    if v in MODES:
        return v
    p = os.environ.get("STF_PALLAS")
    if p is not None:
        p = p.strip().lower()
        if p in ("0", "off", "false", "no"):
            return "off"
        if p == "force":
            return "force"
    return "auto"


def set_mode(mode: Optional[str]) -> None:
    """Set the process-default routing mode (None = back to the env
    default). Affects decisions made by FUTURE traces only: an
    already-compiled executable keeps the routing it was traced with."""
    global _mode_override
    if mode is not None and mode not in MODES:
        raise ValueError(f"kernel registry mode must be one of {MODES}, "
                         f"got {mode!r}")
    _mode_override = mode


def default_mode() -> str:
    return _mode_override if _mode_override is not None else _env_mode()


def current_mode() -> str:
    """The mode in effect for decisions on this thread: an active
    lowering's ConfigProto(kernel_registry=...) scope if one is open,
    else the process default."""
    m = getattr(_state, "mode", None)
    return m if m is not None else default_mode()


class activate:
    """Context manager: pin the decision mode for this thread while a
    Session lowers (framework/lowering.py execute_ops wraps its trace
    loop in one, carrying ConfigProto(kernel_registry=...)). ``None``
    leaves the current/default mode in effect. Re-entrant.

    ``auto_partitioned``: the ops being traced lower under a
    multi-device mesh OUTSIDE shard_map, i.e. GSPMD partitions them.
    Mosaic kernels cannot be partitioned automatically, so on a TPU
    every decision in this scope takes the XLA lowering (reason
    ``mesh_auto_partitioned``); inside a shard_map body the nested
    scope clears the flag and per-shard kernels route normally."""

    def __init__(self, mode: Optional[str], auto_partitioned: bool = False):
        if mode is not None and mode not in MODES:
            raise ValueError(f"kernel registry mode must be one of {MODES}, "
                             f"got {mode!r}")
        self._mode = mode
        self._auto = bool(auto_partitioned)
        self._prev = None

    def __enter__(self):
        self._prev = (getattr(_state, "mode", None),
                      getattr(_state, "auto_partitioned", False))
        if self._mode is not None:
            _state.mode = self._mode
        _state.auto_partitioned = self._auto
        return self

    def __exit__(self, *exc):
        _state.mode, _state.auto_partitioned = self._prev
        return False


def backend() -> str:
    import jax

    return jax.default_backend()


def device_kind() -> str:
    import jax

    return jax.devices()[0].device_kind


# -- kernel definitions -------------------------------------------------------

class KernelDef:
    """One routable op type.

    impls: {"pallas": fn, "xla": fn} — call-compatible implementations
      (same positional arrays, same static kwargs, same outputs).
    legacy: which impl the op lowered through BEFORE the registry
      existed; ``off`` mode always picks it.
    eligible(key) -> None (Pallas-capable) or a fallback reason string
      (``ineligible_*``). Force mode still honors ineligibility — an
      implementation that cannot express the call cannot be forced.
    cost_gate(key, backend) -> (verdict|None, reason): the static gate.
      None verdict = uncertain, measure (auto mode).
    make_case(key) -> (args, kwargs): representative concrete inputs
      for the micro-autotune (never called for ineligible keys).
    """

    __slots__ = ("op_type", "impls", "legacy", "eligible", "cost_gate",
                 "make_case", "graph_key", "doc")

    def __init__(self, op_type, impls, legacy, eligible=None,
                 cost_gate=None, make_case=None, graph_key=None, doc=""):
        assert legacy in ("pallas", "xla")
        self.op_type = op_type
        self.impls = dict(impls)
        self.legacy = legacy
        self.eligible = eligible or (lambda key: None)
        self.cost_gate = cost_gate or (lambda key, backend: (None, "unpriced"))
        self.make_case = make_case
        self.graph_key = graph_key
        self.doc = doc


_KERNELS: Dict[str, KernelDef] = {}


def register_kernel(op_type: str, **kw) -> KernelDef:
    kd = KernelDef(op_type, **kw)
    _KERNELS[op_type] = kd
    return kd


def kernel_types() -> List[str]:
    return sorted(_KERNELS)


def has_kernel(op_type: str) -> bool:
    return op_type in _KERNELS


# -- keys ---------------------------------------------------------------------

def aval_key(*arrays, **statics) -> Tuple:
    """Canonical decision key: (shape, dtype) per array (None entries
    skipped) + sorted perf-relevant statics. Works on tracers, jax
    arrays, numpy arrays, and ShapeDtypeStructs alike."""
    parts: List[Any] = []
    for a in arrays:
        if a is None:
            parts.append(None)
        else:
            parts.append((tuple(getattr(a, "shape", ())),
                          str(getattr(a, "dtype", "?"))))
    for k in sorted(statics):
        parts.append((k, statics[k]))
    return tuple(parts)


# -- autotune cache -----------------------------------------------------------

# (op_type, key, backend, device_kind) -> {"verdict", "pallas_s",
# "xla_s"}: a verdict timed on one device is never replayed on another
_measured: Dict[Tuple, Dict[str, Any]] = {}
_measured_loaded_from: Optional[str] = None
_AUTOTUNE_FILE = "stf_kernel_autotune.json"


def _autotune_forced() -> bool:
    return os.environ.get("STF_KERNEL_AUTOTUNE", "") == "1"


def _cache_file() -> Optional[str]:
    """Persist verdicts alongside the persistent compile cache (PR 5):
    the same directory that makes process restarts disk-hit their XLA
    compiles makes them skip re-measuring."""
    from ..compiler import aot

    d = aot.persistent_cache_dir()
    if not d:
        return None
    return os.path.join(d, _AUTOTUNE_FILE)


def _load_persisted() -> None:
    global _measured_loaded_from
    path = _cache_file()
    if path is None or path == _measured_loaded_from:
        return
    _measured_loaded_from = path
    try:
        with open(path) as f:
            raw = json.load(f)
    except (OSError, ValueError):
        return
    def _tuplify(x):
        if isinstance(x, list):
            return tuple(_tuplify(v) for v in x)
        return x

    for rec in raw.get("verdicts", []):
        try:
            k = (rec["op"], _tuplify(rec["key"]), rec["backend"],
                 rec["device_kind"])
            _measured.setdefault(k, {
                "verdict": rec["verdict"],
                "pallas_s": rec.get("pallas_s"),
                "xla_s": rec.get("xla_s"),
            })
        except (KeyError, TypeError):
            continue


def _persist() -> None:
    path = _cache_file()
    if path is None:
        return
    recs = []
    for (op, key, bk, kind), v in _measured.items():
        recs.append({"op": op, "key": _jsonable(key), "backend": bk,
                     "device_kind": kind, "verdict": v["verdict"],
                     "pallas_s": v.get("pallas_s"),
                     "xla_s": v.get("xla_s")})
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "w") as f:
            json.dump({"verdicts": recs}, f)
        os.replace(tmp, path)
    except OSError:
        try:
            os.remove(tmp)
        except OSError:
            pass


def _jsonable(part):
    if isinstance(part, tuple):
        return [_jsonable(x) for x in part]
    return part


def _time_thunk(fn, args, kwargs) -> float:
    """Best-of-N wall time of ``fn(*args, **kwargs)`` under jit (the
    first call pays trace+compile and is excluded)."""
    import jax

    jfn = jax.jit(lambda *a: fn(*a, **kwargs))
    jax.block_until_ready(jfn(*args))  # compile + warm
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(jfn(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def _measure(kd: KernelDef, key, bk: str) -> str:
    """Micro-autotune: time both lowerings on representative inputs,
    persist the verdict. Called at most once per (op, key, backend,
    device kind). A lowering that fails to compile or run here would
    fail the same way in the plan being traced, so the failure
    propagates, naming what was being timed — it is never a verdict."""
    cache_key = (kd.op_type, key, bk, device_kind())
    hit = _measured.get(cache_key)
    if hit is not None:
        return hit["verdict"]
    if kd.make_case is None:
        # nothing to measure with: defer to the static gate's lean
        v, _ = kd.cost_gate(key, bk)
        return v or ("xla" if bk != "tpu" else "pallas")
    metric_autotune_runs.get_cell(kd.op_type).increase_by(1)
    args, kwargs = kd.make_case(key)
    times = {}
    for impl in ("pallas", "xla"):
        try:
            times[impl] = _time_thunk(kd.impls[impl], args, kwargs)
        except Exception as e:
            raise RuntimeError(
                f"kernel autotune: timing the {impl!r} lowering of "
                f"{kd.op_type} failed on backend {bk!r} for key "
                f"{key!r}") from e
    t_p, t_x = times["pallas"], times["xla"]
    verdict = "pallas" if t_p <= t_x else "xla"
    _measured[cache_key] = {"verdict": verdict, "pallas_s": t_p,
                            "xla_s": t_x}
    _persist()
    return verdict


def measured_verdicts() -> Dict[Tuple, Dict[str, Any]]:
    """The in-process autotune cache (bench/introspection)."""
    return dict(_measured)


def record_measurement(op_type: str, key, pallas_s: float,
                       xla_s: float) -> str:
    """Feed an externally-timed (pallas, xla) pair into the autotune
    cache — the bench row records its per-kernel timings through this,
    so auto-mode decisions afterwards follow the measurement (the
    'never pick a lowering the autotune measured slower' contract).
    Returns the resulting verdict. Cached decisions are invalidated for
    this op so the next decide() re-reads the cache."""
    verdict = "pallas" if pallas_s <= xla_s else "xla"
    _measured[(op_type, key, backend(), device_kind())] = {
        "verdict": verdict, "pallas_s": float(pallas_s),
        "xla_s": float(xla_s)}
    _persist()
    with _lock:
        for k in [k for k in _decisions if k[0] == op_type and k[1] == key]:
            del _decisions[k]
    return verdict


def clear_measurements() -> None:
    _measured.clear()


# -- decisions ----------------------------------------------------------------

# (op_type, key, mode, backend, auto_partitioned) -> (impl_name, reason):
# the same trace signature always routes the same way within a process
_decisions: Dict[Tuple, Tuple[str, str]] = {}


def decide(op_type: str, key, mode: Optional[str] = None,
           count: bool = True) -> Tuple[str, str]:
    """Route one call: returns (impl_name, reason) with impl_name in
    {"pallas", "xla"}. Increments exactly one routed/fallback counter
    per call (``count=False`` for offline reports)."""
    kd = _KERNELS[op_type]
    mode = mode or current_mode()
    bk = backend()
    auto = bk == "tpu" and getattr(_state, "auto_partitioned", False)
    cache_key = (op_type, key, mode, bk, auto)
    with _lock:
        hit = _decisions.get(cache_key)
    if hit is None:
        # compute OUTSIDE the lock: the uncached path may run the
        # micro-autotune (two compiles + timed executions) and must not
        # stall every other thread's routing decisions; racing threads
        # at worst measure redundantly, and first-publish wins so the
        # cached decision stays stable
        computed = (("xla", "mesh_auto_partitioned") if auto
                    else _decide_uncached(kd, key, mode, bk))
        with _lock:
            hit = _decisions.setdefault(cache_key, computed)
    impl, reason = hit
    if count:
        if impl == "pallas":
            metric_routed.get_cell(op_type).increase_by(1)
        else:
            metric_fallback.get_cell(op_type, reason).increase_by(1)
    return hit


def _decide_uncached(kd: KernelDef, key, mode: str, bk: str):
    if mode == "off":
        return (kd.legacy, "mode_off")
    inel = kd.eligible(key)
    if inel:
        return ("xla", inel)
    if mode == "force":
        return ("pallas", "forced")
    # auto: measured verdict wins over everything else
    _load_persisted()
    m = _measured.get((kd.op_type, key, bk, device_kind()))
    if m is not None:
        return (m["verdict"], "autotune")
    verdict, reason = kd.cost_gate(key, bk)
    if verdict is None or _autotune_forced():
        return (_measure(kd, key, bk), "autotune")
    return (verdict, reason)


def select(op_type: str, key, mode: Optional[str] = None) -> Callable:
    """decide() and hand back the chosen implementation callable."""
    impl, _ = decide(op_type, key, mode=mode)
    return _KERNELS[op_type].impls[impl]


def decisions_snapshot() -> List[Dict[str, Any]]:
    with _lock:
        return [{"op": op, "key": repr(key), "mode": mode,
                 "backend": bk, "impl": impl, "reason": reason}
                for (op, key, mode, bk, _auto), (impl, reason)
                in sorted(_decisions.items(), key=lambda kv: kv[0][0])]


def clear_decisions() -> None:
    """Forget cached routing decisions (tests / after set_mode). Does
    NOT retrace already-compiled executables."""
    with _lock:
        _decisions.clear()


def _backend_if_initialized() -> Optional[str]:
    """The jax backend WITHOUT triggering backend init (a /statusz
    scrape must never be what first brings a TPU runtime up)."""
    import sys

    jax = sys.modules.get("jax")
    if jax is None:
        return None
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return None
    return jax.default_backend()


def snapshot() -> Dict[str, Any]:
    """Registry state for /statusz and bench artifacts."""
    routed = {labels[0]: cell.value()
              for labels, cell in metric_routed.cells().items()}
    fallback = {f"{labels[0]}:{labels[1]}": cell.value()
                for labels, cell in metric_fallback.cells().items()}
    autotune = {labels[0]: cell.value()
                for labels, cell in metric_autotune_runs.cells().items()}
    flash_tiles = {"{}:{}x{}x{}".format(*labels): cell.value()
                   for labels, cell in metric_flash_tiles.cells().items()}
    return {
        "mode": default_mode(),
        "backend": _backend_if_initialized(),
        "kernels": kernel_types(),
        "routed": routed,
        "fallback": fallback,
        "autotune_runs": autotune,
        "flash_tiles": flash_tiles,
        "measured": {f"{op}|{bk}|{kind}": v["verdict"]
                     for (op, _k, bk, kind), v in _measured.items()},
    }


# -- offline routing report (graph_lint --kernels; zoo gate) ------------------

def routing_report(ops, mode: Optional[str] = None,
                   backend_name: Optional[str] = None) -> List[Dict[str, Any]]:
    """Static per-op routing verdicts for a (possibly imported) graph:
    one record per op whose type has a registered kernel —
    ``verdict`` in {"routed", "fallback", "autotune"} — plus aggregate
    ``no-kernel`` counts for everything else. Never measures: keys the
    static gate cannot price report verdict "autotune" (decided on
    first live call)."""
    mode = mode or current_mode()
    bk = backend_name or backend()
    records: List[Dict[str, Any]] = []
    no_kernel: Dict[str, int] = {}
    for op in ops:
        kd = _KERNELS.get(op.type)
        if kd is None:
            no_kernel[op.type] = no_kernel.get(op.type, 0) + 1
            continue
        if kd.graph_key is None:
            records.append({"op": op.name, "type": op.type,
                            "verdict": "fallback",
                            "reason": "no_graph_key"})
            continue
        try:
            key = kd.graph_key(op)
        except Exception:  # noqa: BLE001 — report, don't raise
            key = None
        if key is None:
            records.append({"op": op.name, "type": op.type,
                            "verdict": "fallback",
                            "reason": "unknown_shape"})
            continue
        if mode == "off":
            impl, reason = kd.legacy, "mode_off"
        else:
            inel = kd.eligible(key)
            if inel:
                impl, reason = "xla", inel
            elif mode == "force":
                impl, reason = "pallas", "forced"
            else:
                m = _measured.get((kd.op_type, key, bk, device_kind()))
                if m is not None:
                    impl, reason = m["verdict"], "autotune"
                else:
                    impl, reason = kd.cost_gate(key, bk)
                    if impl is None:
                        records.append({"op": op.name, "type": op.type,
                                        "verdict": "autotune",
                                        "reason": "unmeasured"})
                        continue
        records.append({"op": op.name, "type": op.type,
                        "verdict": "routed" if impl == "pallas"
                        else "fallback", "reason": reason})
    for t, n in sorted(no_kernel.items()):
        records.append({"type": t, "verdict": "no-kernel", "count": n})
    return records


# -- shared gating helpers ----------------------------------------------------

def roofline_gate(flops: float, pallas_bytes: float, xla_bytes: float,
                  bk: str, margin: float = 1.25) -> Tuple[Optional[str], str]:
    """Price both lowerings with the PR 1 cost-model roofline (seconds =
    max(flops/peak_flops, bytes/peak_bw), utils/perf chip numbers) and
    pick the clearly-faster one; within ``margin`` the gate abstains and
    the micro-autotune decides.

    Off-TPU the Pallas kernels run in interpret mode — each grid program
    executes as traced jnp calls, orders of magnitude off the roofline —
    so the gate confidently falls back (reason ``interpret_backend``);
    a measured verdict still overrides (decide() consults the autotune
    cache first)."""
    if bk != "tpu":
        return ("xla", "interpret_backend")
    from ..utils import perf

    peak_flops, peak_bw = perf.chip_spec()
    t_pallas = max(flops / max(peak_flops, 1.0),
                   pallas_bytes / max(peak_bw, 1.0))
    t_xla = max(flops / max(peak_flops, 1.0),
                xla_bytes / max(peak_bw, 1.0))
    if t_xla > margin * t_pallas:
        return ("pallas", "cost_model")
    if t_pallas > margin * t_xla:
        return ("xla", "cost_model")
    return (None, "cost_model_uncertain")
