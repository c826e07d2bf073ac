"""Pipeline parallelism: GPipe and 1F1B microbatch schedules over a mesh axis.

The reference pipelines by placing layer subsets on different workers with
``tf.device`` and letting grpc Send/Recv stream activations
(ref: core/distributed_runtime partition + core/kernels/sendrecv_ops.cc);
there is no microbatch schedule, so utilisation collapses with depth. The
TPU version runs the schedule *inside one SPMD program*: every chip along
the 'pp' axis executes the same scan; at step t chip s processes microbatch
t-s (a skew of the GPipe schedule), and ``lax.ppermute`` hands activations
to the next stage over ICI. Bubble fraction is (n_stages-1)/(n_micro +
n_stages-1); XLA overlaps the permute with the next microbatch's compute.

Two schedules:
- ``pipeline_p``: GPipe forward; jax.vjp differentiates through the scan
  (activation memory O(n_micro) — fine for inference/short pipelines).
- ``pipeline_1f1b_p``: combined forward+backward 1F1B training step in ONE
  scan. The loss is computed in-pipeline at the last stage, cotangents
  ppermute backwards while later microbatches still flow forward, and the
  backward recomputes each stage from a ring buffer of saved stage INPUTS
  — activation memory O(n_stages), independent of n_micro (the reason
  1F1B exists). Returns (mean loss, per-stage param grads) directly.

Heterogeneous stages: both schedules accept a LIST of per-stage functions,
lowered to ``lax.switch`` on the stage index — each chip executes only its
own branch, so per-stage computation (and per-stage params, padded to a
common stacked shape) may differ as long as the carried activation shape
is uniform across stage boundaries.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework import graph as ops_mod
from ..framework import op_registry
from ..framework import tensor_shape as shape_mod
from ..framework import lowering as lowering_mod
from .mesh import current_mesh, get_shard_map


def _as_stage_fn(fn, stage):
    """Normalize fn-or-list-of-fns to one fn dispatching on stage index.
    A list lowers to lax.switch: each chip runs only its own branch."""
    if not isinstance(fn, (list, tuple)):
        return fn
    fns = list(fn)
    return lambda p, x: jax.lax.switch(
        stage, [lambda pp, xx, f=f: f(pp, xx) for f in fns], p, x)


def pipeline_p(fn, stage_params, microbatches, axis_name):
    """Per-shard GPipe schedule, for use inside ``shard_map``.

    fn(stage_params, x) -> y with y.shape == x.shape — or a list of
    n_stages such fns for heterogeneous stages.
    stage_params: this stage's param pytree (stage dim already sliced off).
    microbatches: (n_micro, mb, ...) — replicated across the pp axis.
    Returns (n_micro, mb, ...), identical on every chip (psum broadcast of
    the last stage's outputs).
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    fn = _as_stage_fn(fn, stage)
    n_micro = microbatches.shape[0]
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def step(carry, t):
        state, outputs = carry
        inject = microbatches[jnp.minimum(t, n_micro - 1)]
        state = jnp.where(stage == 0, inject, state)
        y = fn(stage_params, state)
        out_idx = t - (n_stages - 1)
        is_out = (stage == n_stages - 1) & (out_idx >= 0)
        outputs = jax.lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(is_out, y, jax.lax.dynamic_index_in_dim(
                outputs, jnp.maximum(out_idx, 0), 0, keepdims=False)),
            jnp.maximum(out_idx, 0), 0)
        state = jax.lax.ppermute(y, axis_name, perm)
        return (state, outputs), None

    state0 = jnp.zeros_like(microbatches[0])
    out0 = jnp.zeros_like(microbatches)
    (state, outputs), _ = jax.lax.scan(
        step, (state0, out0), jnp.arange(n_micro + n_stages - 1))
    # Only the last stage holds real outputs; broadcast them to all chips.
    outputs = jax.lax.psum(
        jnp.where(stage == n_stages - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)
    return outputs


def pipeline_1f1b_p(fn, loss_fn, stage_params, microbatches, targets,
                    axis_name):
    """Per-shard 1F1B training schedule, for use inside ``shard_map``.

    One scan interleaves forward and backward: at step t, stage s runs the
    forward for microbatch ``t - s`` and the backward for microbatch
    ``t - (2S-2-s)``. The last stage seeds the backward from the loss vjp
    of the microbatch it JUST forwarded (forward and backward indices
    coincide there), so cotangents start flowing after S-1 steps instead
    of after all n_micro forwards — in-flight activations are bounded by
    2(S-1-s) per stage, independent of n_micro. The backward recomputes
    the stage from its saved INPUT (rematerialization), the standard
    1F1B-with-remat memory/compute trade.

    fn(stage_params, x) -> y (or a list of per-stage fns, see
    ``_as_stage_fn``); loss_fn(y, target) -> scalar (summed over the
    microbatch — applied at the last stage only).
    Returns (loss_sum / n_micro, grad pytree like stage_params): loss
    replicated on every chip, grads local to each stage's chip.
    """
    n_stages = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    fn = _as_stage_fn(fn, stage)
    n_micro = microbatches.shape[0]
    is_last = stage == n_stages - 1
    fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    bwd_perm = [(i, (i - 1) % n_stages) for i in range(n_stages)]
    # Ring buffer of saved stage inputs: the fwd->bwd gap for one microbatch
    # at stage s is 2(S-1-s) steps, so 2S-1 slots can never collide.
    ring = 2 * n_stages - 1

    def step(carry, t):
        fwd_state, bwd_state, x_buf, grad_acc, loss_acc = carry
        f = t - stage                      # fwd microbatch index
        b = t - (2 * n_stages - 2 - stage)  # bwd microbatch index
        fwd_valid = (f >= 0) & (f < n_micro)
        bwd_valid = (b >= 0) & (b < n_micro)

        # ---- forward: one microbatch through this stage ----
        inject = microbatches[jnp.clip(f, 0, n_micro - 1)]
        x_in = jnp.where(stage == 0, inject, fwd_state)
        y = fn(stage_params, x_in)
        slot_f = jnp.mod(jnp.clip(f, 0, n_micro - 1), ring)
        x_buf = jnp.where(
            fwd_valid,
            jax.lax.dynamic_update_index_in_dim(x_buf, x_in, slot_f, 0),
            x_buf)

        # ---- backward: recompute from the saved input, pull cotangent ----
        slot_b = jnp.mod(jnp.clip(b, 0, n_micro - 1), ring)
        x_saved = jax.lax.dynamic_index_in_dim(x_buf, slot_b, 0,
                                               keepdims=False)
        y_re, stage_vjp = jax.vjp(fn, stage_params, x_saved)
        # last stage: cotangent comes from the loss of microbatch b == f.
        # lax.cond so the S-1 non-last stages skip the loss fwd+vjp at
        # runtime instead of computing and discarding it every step.
        target_b = targets[jnp.clip(b, 0, n_micro - 1)]

        def _loss_branch(args):
            y_b, t_b = args
            loss_v, loss_vjp = jax.vjp(loss_fn, y_b, t_b)
            dy_v, _ = loss_vjp(jnp.ones_like(loss_v))
            return loss_v.astype(jnp.float32), dy_v.astype(y_b.dtype)

        def _skip_branch(args):
            y_b, _ = args
            return jnp.zeros((), jnp.float32), jnp.zeros_like(y_b)

        loss_b, dy_from_loss = jax.lax.cond(
            is_last, _loss_branch, _skip_branch, (y_re, target_b))
        dy = jnp.where(is_last, dy_from_loss, bwd_state)
        dparams, dx = stage_vjp(dy.astype(y_re.dtype))
        grad_acc = jax.tree.map(
            lambda acc, g: acc + jnp.where(bwd_valid, g, 0.0).astype(acc.dtype),
            grad_acc, dparams)
        loss_acc = loss_acc + jnp.where(
            is_last & bwd_valid, loss_b.astype(loss_acc.dtype), 0.0)

        fwd_state = jax.lax.ppermute(y, axis_name, fwd_perm)
        bwd_state = jax.lax.ppermute(dx, axis_name, bwd_perm)
        return (fwd_state, bwd_state, x_buf, grad_acc, loss_acc), None

    mb_shape = microbatches.shape[1:]
    dtype = microbatches.dtype
    carry0 = (
        jnp.zeros(mb_shape, dtype),
        jnp.zeros(mb_shape, dtype),  # cotangents carry the activation dtype
        jnp.zeros((ring,) + mb_shape, dtype),
        jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), stage_params),
        jnp.zeros((), jnp.float32),
    )
    n_steps = n_micro + 2 * n_stages - 2
    (_, _, _, grads, loss_sum), _ = jax.lax.scan(
        step, carry0, jnp.arange(n_steps))
    # only the last stage accumulated loss; broadcast it everywhere
    loss = jax.lax.psum(loss_sum, axis_name) / n_micro
    grads = jax.tree.map(lambda g: g / n_micro, grads)
    return loss, grads


# ---------------------------------------------------------------------------
# Graph op
# ---------------------------------------------------------------------------

def _lower_pipeline(ctx, op, inputs):
    mesh = current_mesh()
    axis = op.attrs["axis"]
    n_micro = op.attrs["n_microbatches"]
    fg = op.attrs["body"]
    n_params = op.attrs["n_params"]
    params = inputs[:n_params]
    x = inputs[n_params]
    caps = list(inputs[n_params + 1:])

    if mesh is None or axis not in mesh.shape:
        raise ValueError(f"pipeline requires a Mesh with axis {axis!r}")
    n_stages = mesh.axis_size(axis)

    batch = x.shape[0]
    if batch % n_micro != 0:
        raise ValueError(f"batch {batch} not divisible by "
                         f"n_microbatches {n_micro}")
    mb = batch // n_micro
    x_micro = x.reshape((n_micro, mb) + x.shape[1:])

    def body_fn(stage_params, state):
        outs = lowering_mod.lower_func_graph(
            ctx, fg, list(stage_params) + [state], caps)
        return outs[0]

    def shard_fn(*args):
        ps = [jnp.squeeze(p, 0) for p in args[:n_params]]
        return pipeline_p(lambda sp, s: body_fn(sp, s), ps, args[n_params],
                          axis)

    from jax.sharding import PartitionSpec as JP

    _shard_map = get_shard_map()
    in_specs = tuple(JP(axis) for _ in range(n_params)) + (JP(),)
    fn = _shard_map(shard_fn, mesh=mesh.jax_mesh, in_specs=in_specs,
                    out_specs=JP(), check_vma=False)
    out = fn(*params, x_micro)
    return [out.reshape((batch,) + out.shape[2:])]


op_registry.register("Pipeline", lower=_lower_pipeline)


def _lower_pipeline_train(ctx, op, inputs):
    mesh = current_mesh()
    axis = op.attrs["axis"]
    n_micro = op.attrs["n_microbatches"]
    body_fgs = op.attrs["bodies"]          # list: 1 (uniform) or n_stages
    loss_fg = op.attrs["loss_body"]
    n_params = op.attrs["n_params"]
    n_body_caps = op.attrs["n_body_caps"]  # per-fg capture counts
    params = inputs[:n_params]
    x = inputs[n_params]
    targets = inputs[n_params + 1]
    caps = list(inputs[n_params + 2:])
    body_caps, off = [], 0
    for n in n_body_caps:
        body_caps.append(caps[off:off + n])
        off += n
    loss_caps = caps[off:]

    if mesh is None or axis not in mesh.shape:
        raise ValueError(f"pipeline requires a Mesh with axis {axis!r}")
    n_stages = mesh.axis_size(axis)

    batch = x.shape[0]
    if batch % n_micro != 0:
        raise ValueError(f"batch {batch} not divisible by "
                         f"n_microbatches {n_micro}")
    mb = batch // n_micro
    x_micro = x.reshape((n_micro, mb) + x.shape[1:])
    t_micro = targets.reshape((n_micro, mb) + targets.shape[1:])

    def make_body(fg, fg_caps):
        def body_fn(stage_params, state):
            outs = lowering_mod.lower_func_graph(
                ctx, fg, list(stage_params) + [state], fg_caps)
            return outs[0]
        return body_fn

    bodies = [make_body(fg, c) for fg, c in zip(body_fgs, body_caps)]
    stage_fn = bodies[0] if len(bodies) == 1 else bodies

    def loss_fn(y, t):
        outs = lowering_mod.lower_func_graph(ctx, loss_fg, [y, t], loss_caps)
        return outs[0]

    def shard_fn(*args):
        ps = [jnp.squeeze(p, 0) for p in args[:n_params]]
        loss, grads = pipeline_1f1b_p(
            stage_fn, loss_fn, tuple(ps), args[n_params],
            args[n_params + 1], axis)
        return (loss,) + tuple(g[None] for g in grads)

    from jax.sharding import PartitionSpec as JP

    _shard_map = get_shard_map()
    in_specs = tuple(JP(axis) for _ in range(n_params)) + (JP(), JP())
    out_specs = (JP(),) + tuple(JP(axis) for _ in range(n_params))
    fn = _shard_map(shard_fn, mesh=mesh.jax_mesh, in_specs=in_specs,
                    out_specs=out_specs, check_vma=False)
    outs = fn(*params, x_micro, t_micro)
    return list(outs)


op_registry.register("PipelineTrain", lower=_lower_pipeline_train)


def _device_memory_budget(frac=0.6):
    """Usable HBM for activation stashes: memory_stats when the backend
    reports it, else the chip table's capacity (utils/perf: published
    for a known accelerator, nominal for the CPU, an error for an
    unknown one), scaled by ``frac`` (params, optimizer state, and XLA
    scratch own the rest). Every device of a mesh is the same chip, so
    the first one speaks for all."""
    import jax

    from ..utils import perf

    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    return frac * float(limit or perf.chip_hbm_bytes())


def pipeline_train(stage_fn, loss_fn, params, x, targets, *,
                   n_microbatches, axis="pp", name=None):
    """Graph op: 1F1B-scheduled pipelined TRAINING step over mesh axis
    ``axis``. Returns ``(loss, grads)`` — the mean per-microbatch loss and
    one gradient tensor per stacked param, sharded like the params.

    Unlike ``pipeline`` + ``stf.gradients`` (GPipe forward, autodiff
    backward, O(n_micro) live activations), this runs the combined
    1F1B forward/backward schedule inside one scan with O(n_stages)
    activation memory; apply the returned grads with
    ``optimizer.apply_gradients(zip(grads, vars))``.

    stage_fn(*stage_params, state) -> state' builds one stage as graph ops
    — or a LIST of n_stages such fns for heterogeneous pipelines (stage
    widths may then differ internally; pad per-stage params to a common
    stacked shape and slice inside each fn). loss_fn(y, target) -> scalar
    (summed over a microbatch). ``params`` are stacked (n_stages, ...)
    tensors sharded over ``axis``; ``x``/``targets``: (batch, ...) with
    batch divisible by n_microbatches.

    ``n_microbatches="auto"`` sizes the microbatch count from the static
    cost model (framework/cost_model.py): smallest count whose 1F1B
    activation stash fits the per-device HBM budget, clamped to
    [n_stages, batch] and to a divisor of the batch.
    """
    from ..ops.functional_ops import _build_fn_graph

    mesh = current_mesh()
    if mesh is None or axis not in mesh.shape:
        raise ValueError(f"pipeline requires a Mesh with axis {axis!r}")
    n_stages = mesh.axis_size(axis)

    params = [ops_mod.convert_to_tensor(p) for p in params]
    x = ops_mod.convert_to_tensor(x)
    targets = ops_mod.convert_to_tensor(targets)
    for p in params:
        if p.shape.rank is None or p.shape[0].value != n_stages:
            raise ValueError(
                f"stacked param {p} must have leading dim == n_stages "
                f"({n_stages})")

    if n_microbatches == "auto":
        # cost-model-driven choice (ref: grappler graph_memory.cc role):
        # the inter-stage state (x-shaped, per microbatch) is the 1F1B
        # activation stash; fit it in a fraction of per-device HBM, then
        # clamp to the batch.
        from ..framework import cost_model as cost_model_mod

        state_bytes = 1
        for d in x.shape.dims:
            state_bytes *= d.value or 1
        state_bytes *= x.dtype.base_dtype.size
        budget = _device_memory_budget()
        n_microbatches = cost_model_mod.suggest_microbatches(
            float(state_bytes), n_stages, budget, schedule="1f1b")
        # more microbatches than batch rows is meaningless; also keep the
        # bubble fraction sane (>= n_stages microbatches when possible)
        batch_rows = x.shape[0].value
        n_microbatches = max(min(n_microbatches, batch_rows),
                             min(n_stages, batch_rows))
        # round UP to a divisor of the batch: fewer microbatches would
        # mean BIGGER stashes and blow the budget the count was fitted to
        # (batch_rows divides itself, so this terminates)
        while batch_rows % n_microbatches:
            n_microbatches += 1

    mb = x.shape[0].value // n_microbatches
    arg_specs = ([(p.shape.as_list()[1:], p.dtype) for p in params]
                 + [([mb] + x.shape.as_list()[1:], x.dtype)])
    stage_fns = (list(stage_fn) if isinstance(stage_fn, (list, tuple))
                 else [stage_fn])
    if len(stage_fns) not in (1, n_stages):
        raise ValueError(f"need 1 or {n_stages} stage fns, "
                         f"got {len(stage_fns)}")
    fgs, all_caps, n_body_caps = [], [], []
    for i, fn in enumerate(stage_fns):
        fg, _ = _build_fn_graph(lambda *a, f=fn: f(*a), arg_specs,
                                f"pipeline_stage_{i}")
        fgs.append(fg)
        fg_caps = [outer for outer, _ in fg.captures]
        all_caps.extend(fg_caps)
        n_body_caps.append(len(fg_caps))

    y_spec = ([mb] + x.shape.as_list()[1:], x.dtype)
    t_spec = ([mb] + targets.shape.as_list()[1:], targets.dtype)
    loss_fg, _ = _build_fn_graph(lambda y, t: loss_fn(y, t),
                                 [y_spec, t_spec], "pipeline_loss")
    loss_caps = [outer for outer, _ in loss_fg.captures]

    from ..framework import dtypes as dtypes_mod

    g = ops_mod.get_default_graph()
    out_specs = ([(shape_mod.TensorShape([]), dtypes_mod.float32)]
                 + [(p.shape, dtypes_mod.float32) for p in params])
    node = g.create_op(
        "PipelineTrain", params + [x, targets] + all_caps + loss_caps,
        attrs={"bodies": fgs, "loss_body": loss_fg, "axis": axis,
               "n_microbatches": int(n_microbatches),
               "n_params": len(params), "n_body_caps": n_body_caps},
        name=name or "pipeline_train", output_specs=out_specs)
    return node.outputs[0], list(node.outputs[1:])


def pipeline(stage_fn, params, x, *, n_microbatches, axis="pp", name=None):
    """Graph op: run ``stage_fn`` as an n_stage pipeline over mesh axis
    ``axis`` with the GPipe microbatch schedule.

    stage_fn(*stage_params, x) -> y builds the per-stage computation as
    graph ops (y.shape == x.shape). ``params`` are tensors/variables whose
    leading dim is n_stages (stacked per-stage weights, sharded over the
    axis). ``x``: (batch, ...) with batch divisible by n_microbatches.
    """
    from ..ops.functional_ops import _build_fn_graph

    mesh = current_mesh()
    if mesh is None or axis not in mesh.shape:
        raise ValueError(f"pipeline requires a Mesh with axis {axis!r}")

    params = [ops_mod.convert_to_tensor(p) for p in params]
    x = ops_mod.convert_to_tensor(x)
    for p in params:
        if p.shape.rank is None or p.shape[0].value != mesh.axis_size(axis):
            raise ValueError(
                f"stacked param {p} must have leading dim == n_stages "
                f"({mesh.axis_size(axis)})")

    arg_specs = ([(p.shape.as_list()[1:], p.dtype) for p in params]
                 + [([x.shape[0].value // n_microbatches]
                     + x.shape.as_list()[1:], x.dtype)])
    fg, _ = _build_fn_graph(lambda *a: stage_fn(*a), arg_specs,
                            "pipeline_stage")
    caps = [outer for outer, _ in fg.captures]
    g = ops_mod.get_default_graph()
    node = g.create_op(
        "Pipeline", params + [x] + caps,
        attrs={"body": fg, "axis": axis, "n_microbatches": int(n_microbatches),
               "n_params": len(params)},
        name=name or "pipeline", output_specs=[(x.shape, x.dtype)])
    return node.outputs[0]
