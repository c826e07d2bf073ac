"""Process-global kernel registry: Pallas vs XLA routing at lowering time.

The Pallas kernels (ops/pallas/) and their stock-XLA lowerings are two
implementations of the same op contract. This registry is the single
place that decides, per (op type, shapes, dtypes, backend), which one a
lowering emits — the TPU-native analogue of the reference's per-device
kernel registry (ref: tensorflow/core/framework/op_kernel.cc kernel
dispatch by KernelDef priority), upgraded with the cost-model gating the
TPU-v3 MLPerf submissions used to decide hand-tuned kernel vs compiler
output (1909.09756 §"performance optimizations").

A decision is a pure function of (op, shapes and dtypes, backend, mesh,
mode): it traces, compiles, times and stores nothing. The rule, whole
(``_route``):

  1. a TPU under a multi-device mesh outside shard_map -> ``xla``
     (``mesh_auto_partitioned``: GSPMD cannot partition a Mosaic kernel),
     in every mode;
  2. mode ``off`` -> the kernel's ``legacy`` lowering: every op lowers
     exactly as it did before the registry existed (the fused graph ops
     keep their Pallas kernels, composed ops keep their jnp lowerings,
     the optimizer tail stays per-variable assigns);
  3. a call the kernel cannot express -> ``xla`` with its
     ``ineligible_*`` reason;
  4. mode ``force`` -> ``pallas`` (interpret mode off-TPU, so the whole
     tier runs under tier-1 CPU tests);
  5. mode ``auto`` (the default) -> the static cost gate's verdict
     (roofline pricing of both lowerings, framework/cost_model.py
     accounting); where the gate abstains (``cost_model_uncertain``,
     ``unpriced``) ``pallas`` on a TPU and ``xla`` elsewhere.

The mode is set process-wide by ``stf.kernels.set_mode`` and per Session
by ``ConfigProto(kernel_registry=...)``.

Every decision increments exactly one of ``/stf/kernels/routed{op}``
(Pallas chosen) or ``/stf/kernels/fallback{op, reason}`` (XLA chosen),
so the counters explain every non-routed call. Decisions are recorded
per (op, key, mode, backend) for ``decisions_snapshot()``.
"""

from __future__ import annotations

import threading
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
metric_flash_tiles = monitoring.Counter(
    "/stf/kernels/flash_tiles",
    "flash-attention kernel traces by the regime and tiles the shape "
    "rule chose (ops/pallas/flash_attention.tiles)",
    "regime", "block_q", "block_k", "heads_per_step")

# -- mode ---------------------------------------------------------------------

_state = threading.local()          # per-thread activation (Session lowering)
_default_mode = "auto"
_lock = _sync.RLock("kernels/registry", rank=_sync.RANK_STATE)


def set_mode(mode: Optional[str]) -> None:
    """Set the process-default routing mode (None = back to ``auto``).
    Affects decisions made by FUTURE traces only: an
    already-compiled executable keeps the routing it was traced with."""
    global _default_mode
    if mode is not None and mode not in MODES:
        raise ValueError(f"kernel registry mode must be one of {MODES}, "
                         f"got {mode!r}")
    _default_mode = mode or "auto"


def default_mode() -> str:
    return _default_mode


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
      None verdict = the gate abstains; auto mode then takes the kernel
      on a TPU and the XLA lowering elsewhere.
    """

    __slots__ = ("op_type", "impls", "legacy", "eligible", "cost_gate",
                 "graph_key", "doc")

    def __init__(self, op_type, impls, legacy, eligible=None,
                 cost_gate=None, graph_key=None, doc=""):
        assert legacy in ("pallas", "xla")
        self.op_type = op_type
        self.impls = dict(impls)
        self.legacy = legacy
        self.eligible = eligible or (lambda key: None)
        self.cost_gate = cost_gate or (lambda key, backend: (None, "unpriced"))
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


# -- decisions ----------------------------------------------------------------

# (op_type, key, mode, backend, auto_partitioned) -> (impl_name, reason):
# what was decided in this process (decisions_snapshot)
_decisions: Dict[Tuple, Tuple[str, str]] = {}


def _route(kd: KernelDef, key, mode: str, bk: str,
           auto_partitioned: bool = False) -> Tuple[str, str]:
    """The routing rule (module docstring): a pure function of its
    arguments. It runs the kernel's eligibility check and cost gate —
    shape arithmetic — and never traces, compiles or runs a lowering."""
    if auto_partitioned and bk == "tpu":
        return ("xla", "mesh_auto_partitioned")
    if mode == "off":
        return (kd.legacy, "mode_off")
    inel = kd.eligible(key)
    if inel:
        return ("xla", inel)
    if mode == "force":
        return ("pallas", "forced")
    verdict, reason = kd.cost_gate(key, bk)
    if verdict is None:
        # the gate abstains: the kernel on a TPU (the side the one chip
        # measurement of an abstention came down on, PERF.md Findings
        # (e)), the stock lowering where Pallas is interpreted
        verdict = "pallas" if bk == "tpu" else "xla"
    return (verdict, reason)


def decide(op_type: str, key, mode: Optional[str] = None,
           count: bool = True) -> Tuple[str, str]:
    """Route one call: returns (impl_name, reason) with impl_name in
    {"pallas", "xla"}. Increments exactly one routed/fallback counter
    per call (``count=False`` for offline reports)."""
    kd = _KERNELS[op_type]
    mode = mode or current_mode()
    bk = backend()
    auto = getattr(_state, "auto_partitioned", False)
    hit = _route(kd, key, mode, bk, auto)
    with _lock:
        _decisions[(op_type, key, mode, bk, auto)] = hit
    impl, reason = hit
    if count:
        if impl == "pallas":
            metric_routed.get_cell(op_type).increase_by(1)
        else:
            metric_fallback.get_cell(op_type, reason).increase_by(1)
    return hit


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
    """Forget the recorded routing decisions (tests). Does NOT retrace
    already-compiled executables."""
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
    """Registry state for /statusz and the benchmark's kernel_routing."""
    routed = {labels[0]: cell.value()
              for labels, cell in metric_routed.cells().items()}
    fallback = {f"{labels[0]}:{labels[1]}": cell.value()
                for labels, cell in metric_fallback.cells().items()}
    flash_tiles = {"{}:{}x{}x{}".format(*labels): cell.value()
                   for labels, cell in metric_flash_tiles.cells().items()}
    return {
        "mode": default_mode(),
        "backend": _backend_if_initialized(),
        "kernels": kernel_types(),
        "routed": routed,
        "fallback": fallback,
        # nothing is timed any more; the key stays, empty, for its readers
        # chipbench/harness.kernel_routing and chipbench/tests/
        # test_manifest.py until a benchmark PR drops it (PERF.md Open
        # questions (q))
        "autotune_runs": {},
        "flash_tiles": flash_tiles,
    }


# -- offline routing report (graph_lint --kernels; zoo gate) ------------------

def routing_report(ops, mode: Optional[str] = None,
                   backend_name: Optional[str] = None) -> List[Dict[str, Any]]:
    """Static per-op routing verdicts for a (possibly imported) graph:
    one record per op whose type has a registered kernel —
    ``verdict`` in {"routed", "fallback"}, decided by the same rule as a
    live call (``_route``) — plus aggregate ``no-kernel`` counts for
    everything else."""
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
        impl, reason = _route(kd, key, mode, bk)
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
    pick the clearly-faster one; within ``margin`` the gate abstains
    (``_route`` then takes the kernel on a TPU).

    Off-TPU the Pallas kernels run in interpret mode — each grid program
    executes as traced jnp calls, orders of magnitude off the roofline —
    so the gate confidently falls back (reason ``interpret_backend``)."""
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
