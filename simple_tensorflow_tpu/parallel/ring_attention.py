"""Ring attention: sequence/context parallelism over a mesh axis.

The reference scales long sequences only by adding workers and partitioning
the graph (ref: core/distributed_runtime graph partitioning + Send/Recv,
core/kernels/sendrecv_ops.cc); attention itself never exceeds one device's
memory. TPU-native long context shards the *sequence* dimension across a
mesh axis ('sp'): each chip keeps its Q shard resident and the K/V shards
rotate around the ICI ring via ``lax.ppermute``, one hop per step, while an
online-softmax accumulator (m, l, acc) merges each visiting block — the
FlashAttention recurrence lifted to the mesh level (Liu et al., Ring
Attention; see PAPERS.md). Memory per chip is O(S/n), compute overlaps the
ppermute because XLA schedules the collective-permute concurrently with the
local block matmuls.

Causal masking is done per (q-chunk, kv-chunk) pair from the global chunk
offsets; chunks entirely in the future contribute nothing (their rows are
masked, adding exp(-inf)=0 terms).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework import graph as ops_mod
from ..framework import op_registry
from ..ops.pallas.common import NEG_INF
from .mesh import current_mesh, get_shard_map


def _block_attn(q, k, v, sm_scale, mask):
    """Unnormalised attention of one KV block: returns (m, l, acc) in f32.
    q,k,v: (B, H, Sq, D)/(B, H, Sk, D); mask: (Sq, Sk) True=keep."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return m, l, acc


def _merge(m1, l1, a1, m2, l2, a2):
    """Merge two online-softmax partial states."""
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    l = l1 * c1 + l2 * c2
    a = a1 * c1[..., None] + a2 * c2[..., None]
    return m, l, a


def _select_block_attention(q, k, v, *, causal):
    """Registry-routed attention for one ring block (stf.kernels):
    Pallas flash kernel or the composed-XLA lowering, decided per
    (shard shape, dtype, backend) under the active mode."""
    from ..kernels import registry as _kreg

    return _kreg.select(
        "FlashAttention",
        _kreg.aval_key(q, k, v, None, causal=bool(causal), dropout=False,
                       ring_block=True))


def ring_attention_p(q, k, v, axis_name, *, causal=False, sm_scale=None,
                     use_flash=True):
    """Per-shard ring attention, for use inside ``shard_map`` where the
    sequence dim (2) of q/k/v is sharded over ``axis_name``.

    q, k, v: (B, H, S_local, D) local shards. Returns the local O shard.
    Differentiable (ppermute transposes to the reverse permute; jax.vjp of
    the scan replays the ring backwards).

    use_flash (default): each visiting KV block runs the Pallas flash
    kernel (O(block) VMEM) and partials merge through the returned
    log-sum-exp — the naive per-block path materializes an f32
    (S/n, S/n) score matrix per (b, h), which defeats ring attention's
    memory point at real context lengths. Three block cases under
    lax.switch: wholly-future (causal) blocks contribute an empty
    partial, the diagonal block runs the causal kernel, past blocks the
    full kernel.
    """
    b, h, s_local, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    if use_flash:
        # the per-block attention routes through the kernel registry
        # exactly like the single-device FlashAttention op: the Pallas
        # streamed kernel when gated in (TPU / force), the composed-XLA
        # lowering otherwise — both merge through the returned lse
        _attn_causal = _select_block_attention(q, k, v, causal=True)
        _attn_full = _select_block_attention(q, k, v, causal=False)

        def step(carry, t):
            k_t, v_t, lse_acc, o_acc = carry
            src = (idx - t) % n

            def _empty(args):
                qq, _, _ = args
                return (jnp.zeros_like(o_acc),
                        jnp.full((b, h, s_local), NEG_INF, jnp.float32))

            def _diag(args):
                qq, kk, vv = args
                o2, lse2 = _attn_causal(qq, kk, vv, causal=True,
                                        sm_scale=sm_scale,
                                        return_lse=True)
                return o2.astype(jnp.float32), lse2

            def _full(args):
                qq, kk, vv = args
                o2, lse2 = _attn_full(qq, kk, vv, causal=False,
                                      sm_scale=sm_scale,
                                      return_lse=True)
                return o2.astype(jnp.float32), lse2

            if causal:
                case = jnp.where(src > idx, 0, jnp.where(src == idx, 1, 2))
            else:
                case = jnp.full((), 2, jnp.int32)
            o2, lse2 = jax.lax.switch(case, [_empty, _diag, _full],
                                      (q, k_t, v_t))
            # merge two normalized partials through their lse
            lse_new = jnp.logaddexp(lse_acc, lse2)
            c1 = jnp.exp(lse_acc - lse_new)[..., None]
            c2 = jnp.exp(lse2 - lse_new)[..., None]
            o_acc = o_acc * c1 + o2 * c2
            k_t = jax.lax.ppermute(k_t, axis_name, perm)
            v_t = jax.lax.ppermute(v_t, axis_name, perm)
            return (k_t, v_t, lse_new, o_acc), None

        lse0 = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
        o0 = jnp.zeros((b, h, s_local, d), jnp.float32)
        (k, v, lse, o), _ = jax.lax.scan(step, (k, v, lse0, o0),
                                         jnp.arange(n))
        return o.astype(q.dtype)

    q_pos = idx * s_local + jnp.arange(s_local)

    def step(carry, t):
        k_t, v_t, m, l, acc = carry
        # After t forward rotations, this device holds the chunk that
        # originated on device (idx - t) mod n.
        src = (idx - t) % n
        k_pos = src * s_local + jnp.arange(s_local)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = jnp.ones((s_local, s_local), bool)
        m2, l2, a2 = _block_attn(q, k_t, v_t, sm_scale, mask)
        m, l, acc = _merge(m, l, acc, m2, l2, a2)
        k_t = jax.lax.ppermute(k_t, axis_name, perm)
        v_t = jax.lax.ppermute(v_t, axis_name, perm)
        return (k_t, v_t, m, l, acc), None

    m0 = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    a0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    (k, v, m, l, acc), _ = jax.lax.scan(
        step, (k, v, m0, l0, a0), jnp.arange(n))
    l = jnp.where(l == 0.0, 1.0, l)
    return (acc / l[..., None]).astype(q.dtype)


# ---------------------------------------------------------------------------
# Graph op: shard_maps the per-shard kernel over the mesh axis.
# ---------------------------------------------------------------------------

def _lower_ring_attention(ctx, op, inputs):
    mesh = current_mesh()
    axis = op.attrs["axis"]
    causal = op.attrs["causal"]
    sm_scale = op.attrs["sm_scale"]
    q, k, v = inputs
    if ctx.in_shard_map:
        return [ring_attention_p(q, k, v, axis, causal=causal,
                                 sm_scale=sm_scale)]
    if mesh is None or axis not in mesh.shape or mesh.axis_size(axis) == 1:
        # No sequence axis to ring over: plain single-device attention,
        # routed Pallas/XLA through the kernel registry like the
        # FlashAttention op itself.
        from ..kernels import registry as _kreg

        fn = _kreg.select(
            "FlashAttention",
            _kreg.aval_key(q, k, v, None, causal=bool(causal),
                           dropout=False))
        return [fn(q, k, v, causal=causal, sm_scale=sm_scale)]

    from jax.sharding import PartitionSpec as JP

    _shard_map = get_shard_map()
    spec = JP(None, None, axis, None)
    fn = _shard_map(
        functools.partial(ring_attention_p, axis_name=axis, causal=causal,
                          sm_scale=sm_scale),
        mesh=mesh.jax_mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return [fn(q, k, v)]


op_registry.register("RingAttention", lower=_lower_ring_attention)


def _register_ring_kernel():
    """Kernel-registry entry for RingAttention: the ring op's inner
    per-block attention is what routes (see _select_block_attention),
    but the offline routing report (graph_lint --kernels; the zoo force
    gate) wants a per-op verdict for the graph node itself — priced and
    gated exactly like FlashAttention on the (possibly sharded) block
    shapes."""
    from ..kernels import registry as _kreg
    from ..ops import pallas as _p
    from ..ops.pallas.flash_attention import attention_xla, flash_attention

    def _graph_key(op):
        avals = [_p._tensor_aval(t) for t in op.inputs[:3]]
        if len(avals) < 3 or any(a is None for a in avals):
            return None
        return _kreg.aval_key(
            *[_p._Aval(*a) for a in avals], None,
            causal=bool(op.attrs.get("causal", False)), dropout=False)

    _kreg.register_kernel(
        "RingAttention",
        impls={"pallas": flash_attention, "xla": attention_xla},
        legacy="pallas",
        eligible=_p._flash_eligible,
        cost_gate=_p._flash_gate,
        graph_key=_graph_key,
        doc="sequence-parallel ring attention; the per-block kernel "
            "routes like FlashAttention")


_register_ring_kernel()


def ring_attention(q, k, v, *, axis="sp", causal=False, sm_scale=None,
                   name=None):
    """Graph op: sequence-parallel attention over mesh axis ``axis``.
    q, k, v: (B, H, S, D) global tensors (S sharded over the axis at
    runtime). Falls back to single-device flash attention when the mesh has
    no such axis."""
    q = ops_mod.convert_to_tensor(q)
    k = ops_mod.convert_to_tensor(k)
    v = ops_mod.convert_to_tensor(v)
    g = ops_mod.get_default_graph()
    node = g.create_op(
        "RingAttention", [q, k, v],
        attrs={"axis": axis, "causal": bool(causal),
               "sm_scale": None if sm_scale is None else float(sm_scale)},
        name=name or "ring_attention", output_specs=[(q.shape, q.dtype)])
    return node.outputs[0]


# ---------------------------------------------------------------------------
# sharding propagation rule (stf.analysis.sharding; ISSUE 6): the op IS
# the sequence-parallel path — q/k/v stay S-sharded over ``axis`` and
# the kernel rings k/v shards with collective-permutes (one per ring
# step; the HLO while body materializes the instruction once, so the
# comparable payload is one shard of k plus one of v).
# ---------------------------------------------------------------------------

from ..analysis import sharding as _shard  # noqa: E402


def _ring_attention_rule(op, in_specs, ctx):
    axis = op.attrs.get("axis", "sp")
    n = ctx.axis_size(axis)
    sq = in_specs[0]
    if n > 1:
        kb = _shard.tensor_bytes(op.inputs[1]) if len(op.inputs) > 1 else 0
        vb = _shard.tensor_bytes(op.inputs[2]) if len(op.inputs) > 2 else 0
        ctx.collective("collective-permute", (axis,), (kb + vb) / n,
                       note="ring k/v shard rotation",
                       tensor_name=op.outputs[0].name)
        # q/k/v ride S-sharded over the ring axis (B, H, S, D)
        if sq is not None and len(sq) == 4:
            want = tuple(((axis,) if d == 2 else e)
                         for d, e in enumerate(sq))
            for i in range(min(3, len(in_specs))):
                if in_specs[i] is not None and in_specs[i] != want:
                    ctx.require(i, want)
            return [want]
    return [sq]


_shard.register_rules(_ring_attention_rule, "RingAttention")
