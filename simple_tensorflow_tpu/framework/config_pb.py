"""Session configuration (ref: tensorflow/core/protos/config.proto
``ConfigProto`` and python/client usage ``tf.Session(config=...)``).

Thread-pool and GPU knobs from the reference are accepted for API
compatibility but are advisory here — XLA owns scheduling on TPU. The
TPU-meaningful additions are the L0 transfer guards: per-step host↔device
transfers are the classic silent TPU performance killer (feeding numpy
every step instead of staging via data.prefetch_to_device; fetching big
activations to host), so the Session can log or reject implicit
transfers above a threshold on the hot path.
"""

from __future__ import annotations


class GPUOptions:
    """(ref: config.proto ``GPUOptions``) — accepted, advisory on TPU."""

    def __init__(self, per_process_gpu_memory_fraction=0.0,
                 allow_growth=False, allocator_type="",
                 visible_device_list=""):
        self.per_process_gpu_memory_fraction = per_process_gpu_memory_fraction
        self.allow_growth = allow_growth
        self.allocator_type = allocator_type
        self.visible_device_list = visible_device_list


class GraphOptions:
    """(ref: config.proto ``GraphOptions``)."""

    def __init__(self, enable_recv_scheduling=False, build_cost_model=0,
                 infer_shapes=False, place_pruned_graph=False,
                 optimizer_options=None):
        self.enable_recv_scheduling = enable_recv_scheduling
        self.build_cost_model = build_cost_model
        self.infer_shapes = infer_shapes
        self.place_pruned_graph = place_pruned_graph
        self.optimizer_options = optimizer_options


class ConfigProto:
    """(ref: config.proto ``ConfigProto``).

    transfer_guard: "allow" (default) | "log" | "disallow" — applied by
    Session.run on the HOT path (after the step is compiled and warm) to
    host-numpy feeds and host fetches larger than
    ``transfer_guard_threshold_bytes``. "log" warns once per tensor;
    "disallow" raises InvalidArgumentError with staging guidance.

    graph_analysis: "off" (default) | "warn" | "strict" — stf.analysis
    graph verification. "strict" verifies the whole graph at Session
    construction (ERROR diagnostics raise InvalidArgumentError) and
    re-verifies every new run plan; "warn" logs instead of raising.
    Per-plan results are cached by plan signature (verification runs
    only on executable-cache misses).

    variable_hazard_mode: None (process default, see
    stf.analysis.set_hazard_mode / STF_HAZARD_MODE) | "off" | "warn" |
    "raise" | "auto_deps" — unordered same-variable read/write policy
    per run plan (RAW/WAR/WAW; docs/ANALYSIS.md).

    loop_fusion_steps: default multi-step window for
    ``Session.run_steps(n=None)`` and the transparent
    MonitoredSession/hook driving (docs/PERFORMANCE.md): N > 1 compiles
    N training steps into one device loop, amortizing host dispatch
    1/N. 1 (default) disables transparent fusion.

    compile_cache_dir: directory for the persistent XLA executable
    cache (``compiler.aot.enable_persistent_cache``); a second process
    compiling the same HLO hits the disk cache instead of paying the
    full compile again. None (default) falls back to the
    ``STF_COMPILE_CACHE`` environment variable; empty/unset leaves
    persistent caching off.
    Where ``JAX_COMPILATION_CACHE_DIR`` is set, that directory is used
    and both of these are ignored.
    PROCESS-GLOBAL: the underlying jax compilation-cache directory is
    process-wide state — the first Session that sets it points every
    later compile in the process (including Sessions constructed with
    compile_cache_dir=None) at that directory until it is explicitly
    changed; it is not reverted on Session.close().

    async_fetches: True makes steady-state ``Session.run`` return
    device-produced fetches as lazy ``stf.FetchFuture`` objects that
    ride JAX async dispatch — ``device_get`` happens only when the
    caller materializes (np.asarray/float/.result()), so step N+1's
    staging overlaps step N's device execution. Default False keeps
    the eager-numpy return contract.

    kernel_registry: None (process default: ``stf.kernels.set_mode``,
    "auto" unless set) | "off" | "auto" | "force" — the Pallas
    kernel-routing mode for programs this Session lowers
    (docs/PERFORMANCE.md "kernel tier"). "off" restores the
    pre-registry lowerings exactly; "auto" routes per (op, shape,
    dtype, backend) through the cost-model gate, taking the kernel
    on a TPU where the gate abstains; "force" pins every eligible op
    to the Pallas kernel (interpret mode off-TPU — the tier-1 testing
    mode). Applies at TRACE time: executables already compiled by this Session keep the
    routing they were traced with. NOTE: the fused optimizer tail is a
    GRAPH-BUILD decision — a graph built while the process default was
    not "off" already contains the fused update op and flat slot
    layout; this session-scoped "off" only picks its composed lowering.
    To restore the per-variable assign tail (and its per-variable slot
    checkpoint layout) call stf.kernels.set_mode("off")
    BEFORE building the optimizer.

    auto_shard: False (default) | True — prescriptive sharding
    (stf.analysis.autoshard; docs/ANALYSIS.md "Auto-sharding"). When a
    >1-device mesh is active at plan time, the FIRST fed (step-shaped)
    plan runs the PartitionSpec search over its pruned op list and
    commits the winner BEFORE compile: variable shardings (already-
    committed state is re-placed immediately), feed shardings, and
    committing ShardingConstraint ops at the searched cut points.
    Explicit user-placed specs are kept as fixed seeds, never
    overridden; the search result is applied once per graph. The
    searched layout then feeds the PR 6 per-plan analyzer, so
    /statusz and RunMetadata predicted-collectives report the CHOSEN
    layout. device_memory_budget_bytes (below), when set, doubles as
    the search's per-shard peak-HBM feasibility budget.

    device_memory_budget_bytes: device-memory admission budget for this
    Session (stf.telemetry.memory; docs/OBSERVABILITY.md "Device
    memory"). When set, every plan is admission-checked at plan time
    (static cost-model peak vs the process HBM ledger's live set),
    every AOT bucket at compile time (XLA memory_analysis), and
    ModelServer.load / GenerativeEngine construction refuse servables
    that cannot fit — all with errors.ResourceExhaustedError naming
    the top owners by bytes plus a flight-recorder oom dump, BEFORE
    anything launches. None/0 (default) disables the check (and its
    plan-time cost estimate entirely).

    telemetry_port: start the process's stf.telemetry HTTP server
    (``/metrics`` Prometheus scrape, ``/healthz``, ``/statusz``,
    ``/tracez``, ``/flightz``, ``/trainz``; docs/OBSERVABILITY.md) when
    the Session is constructed. 0 binds an ephemeral port
    (``stf.telemetry.get_server().port``); None (default) starts
    nothing. PROCESS-GLOBAL like compile_cache_dir: the server outlives
    the Session (one process, one telemetry plane) — constructing a
    second Session with the same (or None) port is a no-op, a
    different fixed port raises.

    numerics: None (process default, see
    stf.debug.numerics.set_numerics_mode / STF_NUMERICS) | "off" |
    "metrics" | "raise" | "dump" — the training numerics-health plane
    (stf.debug.numerics; docs/DEBUG.md). Training-shaped plans are
    auto-instrumented with device-side NumericSummary taps (gradients,
    optimizer updates, loss, plus activations matched by
    ``numerics_taps``); the packed health tensor rides fused windows.
    "metrics" feeds /stf/train/* + /trainz; "raise" additionally raises
    InvalidArgumentError naming the first nonfinite tap and its
    creation site; "dump" additionally re-executes the failing plan in
    checked mode, localizes the first bad op, and writes a tfdbg-style
    dump directory (STF_NUMERICS_DUMP_ROOT or a tmp dir).

    numerics_taps: optional list of name-pattern regexes (the
    match_partition_rules idiom) selecting EXTRA tensors to tap by op
    name, on top of the automatic gradient/update/loss selection.
    """

    def __init__(self, device_count=None, intra_op_parallelism_threads=0,
                 inter_op_parallelism_threads=0, use_per_session_threads=False,
                 session_inter_op_thread_pool=None, placement_period=0,
                 device_filters=None, gpu_options=None,
                 allow_soft_placement=False, log_device_placement=False,
                 graph_options=None, operation_timeout_in_ms=0,
                 transfer_guard="allow",
                 transfer_guard_threshold_bytes=1 << 20,
                 graph_analysis="off", variable_hazard_mode=None,
                 loop_fusion_steps=1, async_fetches=False,
                 compile_cache_dir=None, telemetry_port=None,
                 kernel_registry=None, device_memory_budget_bytes=None,
                 auto_shard=False, numerics=None, numerics_taps=None):
        self.device_count = dict(device_count or {})
        self.intra_op_parallelism_threads = intra_op_parallelism_threads
        self.inter_op_parallelism_threads = inter_op_parallelism_threads
        self.use_per_session_threads = use_per_session_threads
        self.session_inter_op_thread_pool = session_inter_op_thread_pool
        self.placement_period = placement_period
        self.device_filters = list(device_filters or [])
        self.gpu_options = gpu_options or GPUOptions()
        self.allow_soft_placement = allow_soft_placement
        self.log_device_placement = log_device_placement
        self.graph_options = graph_options or GraphOptions()
        self.operation_timeout_in_ms = operation_timeout_in_ms
        if transfer_guard not in ("allow", "log", "disallow"):
            raise ValueError(
                f"transfer_guard must be allow|log|disallow, "
                f"got {transfer_guard!r}")
        self.transfer_guard = transfer_guard
        self.transfer_guard_threshold_bytes = transfer_guard_threshold_bytes
        if graph_analysis not in ("off", "warn", "strict"):
            raise ValueError(
                f"graph_analysis must be off|warn|strict, "
                f"got {graph_analysis!r}")
        self.graph_analysis = graph_analysis
        if variable_hazard_mode is not None and variable_hazard_mode \
                not in ("off", "warn", "raise", "auto_deps"):
            raise ValueError(
                "variable_hazard_mode must be None|off|warn|raise|"
                f"auto_deps, got {variable_hazard_mode!r}")
        self.variable_hazard_mode = variable_hazard_mode
        loop_fusion_steps = int(loop_fusion_steps)
        if loop_fusion_steps < 1:
            raise ValueError(
                f"loop_fusion_steps must be >= 1, got {loop_fusion_steps}")
        self.loop_fusion_steps = loop_fusion_steps
        self.async_fetches = bool(async_fetches)
        self.compile_cache_dir = compile_cache_dir
        if kernel_registry is not None and kernel_registry not in (
                "off", "auto", "force"):
            raise ValueError(
                f"kernel_registry must be None|off|auto|force, "
                f"got {kernel_registry!r}")
        self.kernel_registry = kernel_registry
        if device_memory_budget_bytes is not None:
            device_memory_budget_bytes = int(device_memory_budget_bytes)
            if device_memory_budget_bytes < 0:
                raise ValueError(
                    "device_memory_budget_bytes must be >= 0 or None, "
                    f"got {device_memory_budget_bytes}")
        self.device_memory_budget_bytes = device_memory_budget_bytes
        self.auto_shard = bool(auto_shard)
        if numerics is not None and numerics not in (
                "off", "metrics", "raise", "dump"):
            raise ValueError(
                f"numerics must be None|off|metrics|raise|dump, "
                f"got {numerics!r}")
        self.numerics = numerics
        self.numerics_taps = list(numerics_taps or [])
        if telemetry_port is not None:
            telemetry_port = int(telemetry_port)
            if telemetry_port < 0 or telemetry_port > 65535:
                raise ValueError(
                    f"telemetry_port must be 0..65535 or None, "
                    f"got {telemetry_port}")
        self.telemetry_port = telemetry_port
