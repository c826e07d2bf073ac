"""stf.kernels — the Pallas/XLA kernel routing tier.

Infrastructure lives in :mod:`.registry`; the actual kernel
registrations live next to the op lowerings that use them (the same
placement contract as sharding rules and effects): ops/pallas/__init__
registers the fused attention/layer-norm/xent/quant-matmul pairs,
ops/nn_ops.py the composed softmax-xent route, train/optimizers.py the
fused optimizer updates.

Quick reference (docs/PERFORMANCE.md "kernel tier"):

    stf.kernels.set_mode("force")          # pin Pallas everywhere
    stf.kernels.set_mode("off")            # kill switch: pre-registry
                                           # lowerings exactly
    ConfigProto(kernel_registry="auto")    # per-Session mode
    /stf/kernels/{routed,fallback}         # counters
"""

from .registry import (MODES, activate, aval_key, backend, clear_decisions,
                       current_mode, decide, decisions_snapshot,
                       default_mode, has_kernel, kernel_types,
                       metric_fallback, metric_routed, register_kernel,
                       roofline_gate, routing_report, select, set_mode,
                       snapshot)

__all__ = [
    "MODES", "activate", "aval_key", "backend", "clear_decisions",
    "current_mode", "decide", "decisions_snapshot", "default_mode",
    "has_kernel", "kernel_types", "register_kernel", "roofline_gate", "routing_report", "select",
    "set_mode", "snapshot",
]
