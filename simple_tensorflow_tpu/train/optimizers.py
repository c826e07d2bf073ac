"""Concrete optimizers (ref: tensorflow/python/training/{gradient_descent,
momentum,adam,adagrad,adagrad_da,adadelta,rmsprop,ftrl,proximal_*}.py and
core/kernels/training_ops.cc Apply* kernels).

Each _apply_dense builds assign ops whose lowerings fuse into the step's XLA
program — there are no per-optimizer kernels to hand-tune on TPU; XLA fuses
the whole update chain (m/v/param) into a few HBM passes.

Mixed precision: for low-precision float params the slots live in f32
(slot_creator.update_dtype) and ALL update math runs in f32 — grads
upcast on entry, only the final new-value/delta rounds back to the param
dtype. bf16 Adam second moments (8-bit mantissa) would otherwise destroy
the effective step size; for f32 params every cast below is a no-op.
"""

from __future__ import annotations

from ..framework import graph as ops_mod
from ..framework import op_registry
from ..ops import array_ops, control_flow_ops, math_ops, state_ops
from ..ops import variables as variables_mod
from .optimizer import Optimizer, _var_key
from .slot_creator import update_dtype as _ud


def _c(value, var):
    """Hyperparameter in the var's UPDATE dtype (f32 for bf16 params)."""
    return ops_mod.convert_to_tensor(value, dtype=_ud(var))


# ---------------------------------------------------------------------------
# Fused optimizer tail (stf.kernels; docs/PERFORMANCE.md "kernel tier").
#
# Every training step used to end with a TAIL of per-variable update
# chains — for Adam, ~10 ops per variable (two slot assigns, the alpha
# arithmetic, the param assign-sub) over 2N slot arrays. The fused path
# collapses them into ONE graph op per optimizer whose optimizer state
# lives FLAT: one (n_total,) slot variable per (param dtype, update
# dtype) group, updated together with every group's flattened params in
# a single batched pass — a Pallas kernel on TPU, one fused XLA closure
# on CPU (the registry decides; ops/pallas/fused_update.py holds both).
# Keeping m/v flat ACROSS steps is the perf point: the per-variable
# layout would force a gather/scatter of every slot every step, and the
# Session's state dict shrinks from O(3N) to O(N + groups) arrays —
# which is most of the per-step tail cost at small-variable counts.
#
# The flat slots are ordinary Variables (saved/restored by Saver,
# initialized by global_variables_initializer); get_slot() returns
# per-variable VIEW tensors slicing them, so introspection and tests
# see the same shapes/values as the per-variable layout. The flat math
# is kept op-for-op identical to the per-variable chains, so fused and
# unfused trajectories are bit-exact (tests/test_kernel_registry.py).
# Kill switch: kernel-registry mode "off" (stf.kernels.set_mode("off"))
# at graph-construction time rebuilds the per-variable assigns exactly as
# before (note: the checkpoint layout of optimizer slots differs
# between modes — resume in the mode you saved in).
# ---------------------------------------------------------------------------

def _store_name(var):
    """The variable's store name (the resource the Assign ops declare)."""
    return var._ref.op.attrs["var_name"]


def _fusion_wanted() -> bool:
    from .. import kernels

    return kernels.current_mode() != "off"


def _static_float(*hypers):
    """True when every hyper is a plain python number (foldable into
    the fused kernel); a Tensor/callable hyper falls back per-var."""
    return all(not isinstance(h, ops_mod.Tensor) and not callable(h)
               for h in hypers)


def _build_groups(pairs):
    """Ordered {(param dtype, update dtype): [(grad, var), ...]} by
    first occurrence — one flat slot set and one fused update per
    group. Static: dtypes are graph-build-time knowledge."""
    groups = {}
    for grad, var in pairs:
        key = (var.dtype.base_dtype, _ud(var))
        groups.setdefault(key, []).append((grad, var))
    return groups


def _flat_slot_layout(self, slot_names, groups):
    """Create (or reuse) the per-group flat slot variables and the
    per-variable view tensors. Returns {slot_name: [flat var names in
    group order]} plus per-group (param names, sizes, shapes)."""
    layout = {sn: [] for sn in slot_names}
    group_params = []
    for gi, ((pdt, ud), pairs) in enumerate(groups.items()):
        sizes = [int(np_prod(v.shape.as_list())) for _, v in pairs]
        n = sum(sizes)
        for sn in slot_names:
            cache = self._flat_slot_cache
            ck = (sn, gi, n, ud.name,
                  tuple(_var_key(v) for _, v in pairs))
            flat = cache.get(ck)
            if flat is None:
                flat = variables_mod.Variable(
                    array_ops.zeros([n], dtype=ud), trainable=False,
                    name=f"{self._name}/fused_{sn}_g{gi}")
                # HBM-ledger class marker (stf.telemetry.memory): the
                # flat slot layout is optimizer state like its per-var
                # siblings
                flat._mem_class = "optimizer_slots"
                cache[ck] = flat
                self._fused_slot_vars.append(flat)
                # per-variable views: same shape/dtype/values the
                # per-variable slot would hold (sliced on read)
                off = 0
                views = self._slot_views.setdefault(sn, {})
                for (_, v), sz in zip(pairs, sizes):
                    view = array_ops.reshape(
                        array_ops.slice(flat._ref, [off], [sz]),
                        [int(d) for d in v.shape.as_list()])
                    views[_var_key(v)] = view
                    off += sz
            layout[sn].append(_store_name(flat))
        group_params.append(tuple(_store_name(v) for _, v in pairs))
    return layout, group_params


def np_prod(xs):
    n = 1
    for x in xs:
        n *= int(x)
    return n


def _fused_hypers(groups, *values):
    """Per-group hyper tensors, converted exactly like the per-variable
    ``_c`` would (python floats convert directly, tensors cast) — one
    input per (hyper, group)."""
    out = []
    for value in values:
        for (_pdt, ud) in groups:
            out.append(ops_mod.convert_to_tensor(value, dtype=ud))
    return out


def _concat_flat(vals):
    import jax.numpy as jnp

    flats = [v.reshape(-1) for v in vals]
    return flats[0] if len(flats) == 1 else jnp.concatenate(flats)


def _group_lowering_io(ctx, op, gi, grads, grad_offsets):
    """Read one group's params + grads from the lowering state:
    returns (param names, param values, shapes, offsets, g_flat)."""
    import jax.numpy as jnp  # noqa: F401

    pnames = op.attrs["group_params"][gi]
    udt = op.attrs["group_ud"][gi]
    pvals = [ctx.read_var(p, op) for p in pnames]
    shapes = [p.shape for p in pvals]
    offsets = []
    off = 0
    for p in pvals:
        offsets.append((off, off + p.size))
        off += p.size
    lo, hi = grad_offsets[gi]
    gs = grads[lo:hi]
    g_flat = _concat_flat([g.astype(udt) if str(g.dtype) != udt else g
                           for g in gs])
    return pnames, pvals, shapes, offsets, g_flat


def _grad_offsets(op):
    counts = [len(p) for p in op.attrs["group_params"]]
    offs = []
    lo = 0
    for c in counts:
        offs.append((lo, lo + c))
        lo += c
    return offs


def _split_write_params(ctx, flat, names, shapes, offsets):
    for name, shape, (lo, hi) in zip(names, shapes, offsets):
        ctx.write_var(name, flat[lo:hi].reshape(shape))


def _lower_fused_adam(ctx, op, inputs):
    import jax.numpy as jnp

    from ..kernels import registry as _kreg
    from ..ops.pallas import flat_group_key

    attrs = op.attrs
    n_groups = len(attrs["group_params"])
    lrs = inputs[:n_groups]
    grads = inputs[n_groups:]
    beta1, beta2, eps = attrs["beta1"], attrs["beta2"], attrs["epsilon"]
    b1p = ctx.read_var(attrs["beta1_power"], op)
    b2p = ctx.read_var(attrs["beta2_power"], op)
    offs = _grad_offsets(op)
    for gi in range(n_groups):
        udt = attrs["group_ud"][gi]
        # the alpha arithmetic is the per-variable chain verbatim:
        # cast the CURRENT beta powers to the update dtype, then
        # lr * sqrt(1 - b2p) / (1 - b1p)
        b1p_c = b1p.astype(udt)
        b2p_c = b2p.astype(udt)
        alpha = lrs[gi] * jnp.sqrt(1 - b2p_c) / (1 - b1p_c)
        pnames, pvals, shapes, offsets, g_flat = _group_lowering_io(
            ctx, op, gi, grads, offs)
        p_flat = _concat_flat(pvals)
        m_name = attrs["group_m"][gi]
        v_name = attrs["group_v"][gi]
        m_flat = ctx.read_var(m_name, op)
        v_flat = ctx.read_var(v_name, op)
        fn = _kreg.select(
            "FusedAdamUpdate",
            flat_group_key(p_flat.size, str(p_flat.dtype), udt))
        new_p, new_m, new_v = fn(p_flat, m_flat, v_flat, g_flat, alpha,
                                 beta1=beta1, beta2=beta2, eps=eps)
        ctx.write_var(m_name, new_m)
        ctx.write_var(v_name, new_v)
        _split_write_params(ctx, new_p, pnames, shapes, offsets)
    # beta-power decay, exactly as AdamOptimizer._finish orders it:
    # after every group's update, from the pre-update power values
    ctx.write_var(attrs["beta1_power"],
                  b1p * jnp.asarray(beta1, b1p.dtype))
    ctx.write_var(attrs["beta2_power"],
                  b2p * jnp.asarray(beta2, b2p.dtype))
    return []


op_registry.register(
    "FusedAdamUpdate", lower=_lower_fused_adam, n_outputs=0,
    effects=op_registry.Effects(reads=("var_name",),
                                writes=("var_name",)))


def _lower_fused_momentum(ctx, op, inputs):
    from ..kernels import registry as _kreg
    from ..ops.pallas import flat_group_key

    attrs = op.attrs
    n_groups = len(attrs["group_params"])
    lrs = inputs[:n_groups]
    mus = inputs[n_groups:2 * n_groups]
    grads = inputs[2 * n_groups:]
    nesterov = bool(attrs.get("use_nesterov", False))
    offs = _grad_offsets(op)
    for gi in range(n_groups):
        udt = attrs["group_ud"][gi]
        pnames, pvals, shapes, offsets, g_flat = _group_lowering_io(
            ctx, op, gi, grads, offs)
        p_flat = _concat_flat(pvals)
        a_name = attrs["group_momentum"][gi]
        a_flat = ctx.read_var(a_name, op)
        fn = _kreg.select(
            "FusedMomentumUpdate",
            flat_group_key(p_flat.size, str(p_flat.dtype), udt))
        new_p, new_a = fn(p_flat, a_flat, g_flat, lrs[gi], mus[gi],
                          use_nesterov=nesterov)
        ctx.write_var(a_name, new_a)
        _split_write_params(ctx, new_p, pnames, shapes, offsets)
    return []


op_registry.register(
    "FusedMomentumUpdate", lower=_lower_fused_momentum, n_outputs=0,
    effects=op_registry.Effects(reads=("var_name",),
                                writes=("var_name",)))


def _g(grad, var):
    """Gradient upcast to the update dtype."""
    ud = _ud(var)
    return math_ops.cast(grad, ud) if grad.dtype.base_dtype != ud else grad


def _vread(var):
    """Current param value in the update dtype."""
    ud = _ud(var)
    r = var._ref
    return math_ops.cast(r, ud) if var.dtype.base_dtype != ud else r


def _back(x, var):
    """Round a new value / delta back to the param dtype for the assign."""
    d = var.dtype.base_dtype
    return math_ops.cast(x, d) if x.dtype.base_dtype != d else x


class GradientDescentOptimizer(Optimizer):
    """(ref: python/training/gradient_descent.py)."""

    def __init__(self, learning_rate, use_locking=False,
                 name="GradientDescent"):
        super().__init__(use_locking, name)
        self._learning_rate = learning_rate

    def _apply_dense(self, grad, var):
        grad = _g(grad, var)
        lr = _c(self._call_if_callable(self._learning_rate), var)
        return state_ops.assign_sub(var._ref, _back(lr * grad, var)).op

    def _apply_sparse(self, grad, var):
        lr = _c(self._call_if_callable(self._learning_rate), var)
        vals = _g(grad.values, var)
        return state_ops.scatter_sub(var._ref, grad.indices,
                                     _back(lr * vals, var)).op


class MomentumOptimizer(Optimizer):
    """(ref: python/training/momentum.py)."""

    def __init__(self, learning_rate, momentum, use_locking=False,
                 name="Momentum", use_nesterov=False):
        super().__init__(use_locking, name)
        self._learning_rate = learning_rate
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _create_slots(self, var_list):
        for v in var_list:
            self._zeros_slot(v, "momentum", self._name)

    def _apply_dense(self, grad, var):
        grad = _g(grad, var)
        mom = self.get_slot(var, "momentum")
        lr = _c(self._call_if_callable(self._learning_rate), var)
        mu = _c(self._call_if_callable(self._momentum), var)
        new_acc = state_ops.assign(mom._ref, mu * mom._ref + grad)
        if self._use_nesterov:
            update = lr * (grad + mu * new_acc)
        else:
            update = lr * new_acc
        return state_ops.assign_sub(var._ref, _back(update, var)).op

    def _maybe_build_fused_update(self, grads_and_vars):
        if not _fusion_wanted() \
                or type(self)._apply_dense is not MomentumOptimizer._apply_dense:
            return None
        pairs = self._densified(grads_and_vars)
        if not pairs:
            return None
        groups = _build_groups(pairs)
        layout, group_params = _flat_slot_layout(self, ("momentum",),
                                                 groups)
        lr_val = self._call_if_callable(self._learning_rate)
        mu_val = self._call_if_callable(self._momentum)
        inputs = (_fused_hypers(groups, lr_val, mu_val)
                  + [g for pairs_g in groups.values()
                     for g, _ in pairs_g])
        all_params = [p for grp in group_params for p in grp]
        g = ops_mod.get_default_graph()
        return g.create_op(
            "FusedMomentumUpdate", inputs,
            attrs={"var_name": all_params + layout["momentum"],
                   "group_params": tuple(group_params),
                   "group_momentum": tuple(layout["momentum"]),
                   "group_ud": tuple(ud.name for (_p, ud) in groups),
                   "use_nesterov": bool(self._use_nesterov)},
            name="fused_momentum_update", output_specs=[])


class AdamOptimizer(Optimizer):
    """(ref: python/training/adam.py; kernel core/kernels/training_ops.cc
    ``ApplyAdam``)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, use_locking=False, name="Adam"):
        super().__init__(use_locking, name)
        self._lr = learning_rate
        self._beta1 = beta1
        self._beta2 = beta2
        self._epsilon = epsilon
        self._beta1_power = None
        self._beta2_power = None

    def _create_slots(self, var_list):
        if self._beta1_power is None:
            self._beta1_power = variables_mod.Variable(
                float(self._beta1), trainable=False,
                name=self._name + "/beta1_power")
            self._beta2_power = variables_mod.Variable(
                float(self._beta2), trainable=False,
                name=self._name + "/beta2_power")
        for v in var_list:
            self._zeros_slot(v, "m", self._name)
            self._zeros_slot(v, "v", self._name)

    def _apply_dense(self, grad, var):
        grad = _g(grad, var)
        m = self.get_slot(var, "m")
        v = self.get_slot(var, "v")
        lr = _c(self._call_if_callable(self._lr), var)
        b1 = _c(self._beta1, var)
        b2 = _c(self._beta2, var)
        eps = _c(self._epsilon, var)
        b1p = math_ops.cast(self._beta1_power._ref, _ud(var))
        b2p = math_ops.cast(self._beta2_power._ref, _ud(var))
        alpha = lr * math_ops.sqrt(1 - b2p) / (1 - b1p)
        new_m = state_ops.assign(m._ref, b1 * m._ref + (1 - b1) * grad)
        new_v = state_ops.assign(v._ref, b2 * v._ref +
                                 (1 - b2) * math_ops.square(grad))
        update = alpha * new_m / (math_ops.sqrt(new_v) + eps)
        return state_ops.assign_sub(var._ref, _back(update, var)).op

    def _finish(self, update_ops, name_scope):
        g = ops_mod.get_default_graph()
        with g.control_dependencies(update_ops):
            b1_up = state_ops.assign(self._beta1_power._ref,
                                     self._beta1_power._ref *
                                     _c(self._beta1, self._beta1_power)).op
            b2_up = state_ops.assign(self._beta2_power._ref,
                                     self._beta2_power._ref *
                                     _c(self._beta2, self._beta2_power)).op
        return control_flow_ops.group(*(update_ops + [b1_up, b2_up]),
                                      name=name_scope)

    def _maybe_build_fused_update(self, grads_and_vars):
        if not _fusion_wanted() \
                or type(self)._apply_dense is not AdamOptimizer._apply_dense:
            return None
        if not _static_float(self._beta1, self._beta2, self._epsilon):
            return None
        pairs = self._densified(grads_and_vars)
        if not pairs:
            return None
        # beta-power variables exactly as _create_slots makes them
        if self._beta1_power is None:
            self._beta1_power = variables_mod.Variable(
                float(self._beta1), trainable=False,
                name=self._name + "/beta1_power")
            self._beta2_power = variables_mod.Variable(
                float(self._beta2), trainable=False,
                name=self._name + "/beta2_power")
        groups = _build_groups(pairs)
        layout, group_params = _flat_slot_layout(self, ("m", "v"), groups)
        lr_val = self._call_if_callable(self._lr)
        inputs = (_fused_hypers(groups, lr_val)
                  + [g for pairs_g in groups.values()
                     for g, _ in pairs_g])
        all_params = [p for grp in group_params for p in grp]
        b1p = _store_name(self._beta1_power)
        b2p = _store_name(self._beta2_power)
        g = ops_mod.get_default_graph()
        return g.create_op(
            "FusedAdamUpdate", inputs,
            attrs={"var_name": (all_params + layout["m"] + layout["v"]
                                + [b1p, b2p]),
                   "group_params": tuple(group_params),
                   "group_m": tuple(layout["m"]),
                   "group_v": tuple(layout["v"]),
                   "group_ud": tuple(ud.name for (_p, ud) in groups),
                   "beta1_power": b1p, "beta2_power": b2p,
                   "beta1": float(self._beta1), "beta2": float(self._beta2),
                   "epsilon": float(self._epsilon)},
            name="fused_adam_update", output_specs=[])


class AdagradOptimizer(Optimizer):
    """(ref: python/training/adagrad.py)."""

    def __init__(self, learning_rate, initial_accumulator_value=0.1,
                 use_locking=False, name="Adagrad"):
        super().__init__(use_locking, name)
        self._learning_rate = learning_rate
        self._init_acc = initial_accumulator_value

    def _create_slots(self, var_list):
        for v in var_list:
            self._get_or_make_slot(
                v, array_ops.fill([int(d) for d in v.shape.as_list()],
                                  ops_mod.convert_to_tensor(
                                      self._init_acc, dtype=_ud(v))),
                "accumulator", self._name)

    def _apply_dense(self, grad, var):
        grad = _g(grad, var)
        acc = self.get_slot(var, "accumulator")
        lr = _c(self._call_if_callable(self._learning_rate), var)
        new_acc = state_ops.assign_add(acc._ref, math_ops.square(grad))
        return state_ops.assign_sub(
            var._ref, _back(lr * grad * math_ops.rsqrt(new_acc), var)).op

    def _apply_sparse(self, grad, var):
        acc = self.get_slot(var, "accumulator")
        lr = _c(self._call_if_callable(self._learning_rate), var)
        vals = _g(grad.values, var)
        new_acc = state_ops.scatter_add(acc._ref, grad.indices,
                                        math_ops.square(vals))
        from ..ops import array_ops as ao

        acc_slice = ao.gather(new_acc, grad.indices)
        return state_ops.scatter_sub(
            var._ref, grad.indices,
            _back(lr * vals * math_ops.rsqrt(acc_slice), var)).op


class AdadeltaOptimizer(Optimizer):
    """(ref: python/training/adadelta.py)."""

    def __init__(self, learning_rate=0.001, rho=0.95, epsilon=1e-8,
                 use_locking=False, name="Adadelta"):
        super().__init__(use_locking, name)
        self._lr = learning_rate
        self._rho = rho
        self._epsilon = epsilon

    def _create_slots(self, var_list):
        for v in var_list:
            self._zeros_slot(v, "accum", self._name)
            self._zeros_slot(v, "accum_update", self._name)

    def _apply_dense(self, grad, var):
        grad = _g(grad, var)
        accum = self.get_slot(var, "accum")
        accum_update = self.get_slot(var, "accum_update")
        lr = _c(self._call_if_callable(self._lr), var)
        rho = _c(self._rho, var)
        eps = _c(self._epsilon, var)
        new_accum = state_ops.assign(
            accum._ref, rho * accum._ref + (1 - rho) * math_ops.square(grad))
        update = (math_ops.sqrt(accum_update._ref + eps) *
                  math_ops.rsqrt(new_accum + eps) * grad)
        new_accum_update = state_ops.assign(
            accum_update._ref,
            rho * accum_update._ref + (1 - rho) * math_ops.square(update))
        with ops_mod.get_default_graph().control_dependencies(
                [new_accum_update.op]):
            return state_ops.assign_sub(var._ref,
                                        _back(lr * update, var)).op


class RMSPropOptimizer(Optimizer):
    """(ref: python/training/rmsprop.py)."""

    def __init__(self, learning_rate, decay=0.9, momentum=0.0, epsilon=1e-10,
                 use_locking=False, centered=False, name="RMSProp"):
        super().__init__(use_locking, name)
        self._lr = learning_rate
        self._decay = decay
        self._momentum = momentum
        self._epsilon = epsilon
        self._centered = centered

    def _create_slots(self, var_list):
        for v in var_list:
            self._get_or_make_slot(
                v, array_ops.ones([int(d) for d in v.shape.as_list()],
                                  dtype=_ud(v)), "rms", self._name)
            self._zeros_slot(v, "momentum", self._name)
            if self._centered:
                self._zeros_slot(v, "mg", self._name)

    def _apply_dense(self, grad, var):
        grad = _g(grad, var)
        rms = self.get_slot(var, "rms")
        mom = self.get_slot(var, "momentum")
        lr = _c(self._call_if_callable(self._lr), var)
        decay = _c(self._decay, var)
        momentum = _c(self._momentum, var)
        eps = _c(self._epsilon, var)
        new_rms = state_ops.assign(
            rms._ref, decay * rms._ref + (1 - decay) * math_ops.square(grad))
        denom = new_rms
        if self._centered:
            mg = self.get_slot(var, "mg")
            new_mg = state_ops.assign(mg._ref,
                                      decay * mg._ref + (1 - decay) * grad)
            denom = new_rms - math_ops.square(new_mg)
        new_mom = state_ops.assign(
            mom._ref, momentum * mom._ref +
            lr * grad * math_ops.rsqrt(denom + eps))
        return state_ops.assign_sub(var._ref, _back(new_mom, var)).op


class FtrlOptimizer(Optimizer):
    """(ref: python/training/ftrl.py)."""

    def __init__(self, learning_rate, learning_rate_power=-0.5,
                 initial_accumulator_value=0.1, l1_regularization_strength=0.0,
                 l2_regularization_strength=0.0, use_locking=False,
                 name="Ftrl", l2_shrinkage_regularization_strength=0.0):
        super().__init__(use_locking, name)
        self._lr = learning_rate
        self._lr_power = learning_rate_power
        self._init_acc = initial_accumulator_value
        self._l1 = l1_regularization_strength
        self._l2 = l2_regularization_strength

    def _create_slots(self, var_list):
        for v in var_list:
            self._get_or_make_slot(
                v, array_ops.fill([int(d) for d in v.shape.as_list()],
                                  ops_mod.convert_to_tensor(
                                      self._init_acc, dtype=_ud(v))),
                "accum", self._name)
            self._zeros_slot(v, "linear", self._name)

    def _apply_dense(self, grad, var):
        grad = _g(grad, var)
        accum = self.get_slot(var, "accum")
        linear = self.get_slot(var, "linear")
        lr = _c(self._call_if_callable(self._lr), var)
        lr_power = _c(self._lr_power, var)
        l1 = _c(self._l1, var)
        l2 = _c(self._l2, var)
        new_accum = accum._ref + math_ops.square(grad)
        sigma = (math_ops.pow(new_accum, -lr_power) -
                 math_ops.pow(accum._ref, -lr_power)) / lr
        new_linear = state_ops.assign(
            linear._ref, linear._ref + grad - sigma * _vread(var))
        upd_accum = state_ops.assign(accum._ref, new_accum)
        quadratic = math_ops.pow(new_accum, -lr_power) / lr + 2 * l2
        pre = math_ops.sign(new_linear) * l1 - new_linear
        new_var = array_ops.where(
            math_ops.greater(math_ops.abs(new_linear), l1),
            pre / quadratic, array_ops.zeros_like(new_linear))
        with ops_mod.get_default_graph().control_dependencies([upd_accum.op]):
            return state_ops.assign(var._ref, _back(new_var, var)).op


class AdagradDAOptimizer(Optimizer):
    """(ref: python/training/adagrad_da.py)."""

    def __init__(self, learning_rate, global_step,
                 initial_gradient_squared_accumulator_value=0.1,
                 l1_regularization_strength=0.0,
                 l2_regularization_strength=0.0, use_locking=False,
                 name="AdagradDA"):
        super().__init__(use_locking, name)
        self._lr = learning_rate
        self._global_step = global_step
        self._init_gg = initial_gradient_squared_accumulator_value
        self._l1 = l1_regularization_strength
        self._l2 = l2_regularization_strength

    def _create_slots(self, var_list):
        for v in var_list:
            self._zeros_slot(v, "gradient_accumulator", self._name)
            self._get_or_make_slot(
                v, array_ops.fill([int(d) for d in v.shape.as_list()],
                                  ops_mod.convert_to_tensor(
                                      self._init_gg, dtype=_ud(v))),
                "gradient_squared_accumulator", self._name)

    def _apply_dense(self, grad, var):
        grad = _g(grad, var)
        g_acc = self.get_slot(var, "gradient_accumulator")
        gg_acc = self.get_slot(var, "gradient_squared_accumulator")
        lr = _c(self._call_if_callable(self._lr), var)
        l1 = _c(self._l1, var)
        l2 = _c(self._l2, var)
        gstep = math_ops.cast(
            self._global_step._ref if hasattr(self._global_step, "_ref")
            else self._global_step, _ud(var)) + 1
        new_g = state_ops.assign_add(g_acc._ref, grad)
        new_gg = state_ops.assign_add(gg_acc._ref, math_ops.square(grad))
        sign = math_ops.sign(new_g)
        pruned = sign * math_ops.maximum(
            math_ops.abs(new_g) - l1 * gstep, array_ops.zeros_like(new_g))
        denom = math_ops.sqrt(new_gg) + lr * l2 * gstep
        new_var = -lr * pruned / denom
        return state_ops.assign(var._ref, _back(new_var, var)).op


class ProximalGradientDescentOptimizer(GradientDescentOptimizer):
    """(ref: python/training/proximal_gradient_descent.py) — l1/l2 proximal
    step after the gradient step."""

    def __init__(self, learning_rate, l1_regularization_strength=0.0,
                 l2_regularization_strength=0.0, use_locking=False,
                 name="ProximalGradientDescent"):
        super().__init__(learning_rate, use_locking, name)
        self._l1 = l1_regularization_strength
        self._l2 = l2_regularization_strength

    def _apply_dense(self, grad, var):
        grad = _g(grad, var)
        lr = _c(self._call_if_callable(self._learning_rate), var)
        l1 = _c(self._l1, var)
        l2 = _c(self._l2, var)
        prox = _vread(var) - lr * grad
        soft = math_ops.sign(prox) * math_ops.maximum(
            math_ops.abs(prox) - lr * l1, array_ops.zeros_like(prox))
        return state_ops.assign(var._ref,
                                _back(soft / (1 + lr * l2), var)).op


class ProximalAdagradOptimizer(AdagradOptimizer):
    """(ref: python/training/proximal_adagrad.py)."""

    def __init__(self, learning_rate, initial_accumulator_value=0.1,
                 l1_regularization_strength=0.0,
                 l2_regularization_strength=0.0, use_locking=False,
                 name="ProximalAdagrad"):
        super().__init__(learning_rate, initial_accumulator_value,
                         use_locking, name)
        self._l1 = l1_regularization_strength
        self._l2 = l2_regularization_strength

    def _apply_dense(self, grad, var):
        grad = _g(grad, var)
        acc = self.get_slot(var, "accumulator")
        lr = _c(self._call_if_callable(self._learning_rate), var)
        l1 = _c(self._l1, var)
        l2 = _c(self._l2, var)
        new_acc = state_ops.assign_add(acc._ref, math_ops.square(grad))
        adjusted_lr = lr * math_ops.rsqrt(new_acc)
        prox = _vread(var) - adjusted_lr * grad
        soft = math_ops.sign(prox) * math_ops.maximum(
            math_ops.abs(prox) - adjusted_lr * l1, array_ops.zeros_like(prox))
        return state_ops.assign(var._ref,
                                _back(soft / (1 + adjusted_lr * l2), var)).op
