"""KV-cache graph ops: device-resident paged decode caches.

(ref: the reference has no KV cache — its serving path re-runs the full
forward per emitted token, tensorflow_serving/servables/tensorflow/.
This module is the TPU-native incremental-decode substrate the
generative engine (stf.serving.generative) and the cached beam search
(models/transformer.py) run on.)

A cache is an entry in the Session's device-resident VariableStore —
the SAME store that holds model weights and optimizer slots — declared
``(num_slots, max_len, *inner)``. Slots are PAGES: each live sequence
owns one row, a free-list (serving/generative.py CacheSlotPool) hands
rows to joining sequences and reclaims them at EOS, so a retiring
sequence never compacts or copies its neighbors' cache. The store's
values are donated into every step exactly like optimizer state, and
the cache NEVER moves device→host between decode steps (the
``lint/serving-decode-cache`` rule makes a host-sink on a cache tensor
a hard error).

Stored layout. The store entry is ``(num_slots, max_len,
prod(inner))`` whenever ``inner`` has two or more dimensions
(:func:`stored_shape`; scalar and rank-1 inners are stored as
declared). The ops keep the declared contract: an append takes
``(B, P, *inner)``, a gather returns ``(B, L, *inner)``; only the
lowerings reshape, on the way in and on the way out, and graph-level
shapes, attrs, lint and the memory ledger see the declared shape (the
bytes are the same). Why: an attention cache's minor dimension is
``head_dim``, typically 64 — half a 128-lane tile. The TPU compiler
then gives the donated parameter, and the aliased result, a layout with
the PAGES on the lane axis, which neither the scatter nor the gather
can use: every append paid three relayout copies of the whole pool
(403 MB each at 3073 pages x 64 x 16 x 64 bfloat16 — 78 % of the
serving benchmark's device time before PR 26). With heads x head_dim
merged into one lane-dense axis the compiler leaves the parameter's
layout alone and the scatter updates the donated buffer in place. That
an append is in place is ASSERTED, not stated:
``tests/test_tpu_aot_compile.py`` compiles the real decode and prefill
programs for a described v5e chip and finds every pool aliased, no
pool-sized ``copy`` and one pool-shaped fusion (the scatter) per append.

Seven ops over paged pools, registered with declared Effects so the hazard engine orders
them like any other variable access (append = read-modify-write on the
cache resource, gather and paged attention = read):

  KVCacheAlloc   zero-fill the cache storage (engine start / slot-pool
                 reset); also the op that carries the cache's committed
                 sharding declaration (``_cache_sharding`` attr).
  KVCacheAppend  write ``value (B, P, *inner)`` at rows ``slots (B,)``,
                 positions ``positions[b] + [0, P)`` — P is 1 on the
                 decode path, the prompt length on the prefill path.
  KVCacheGather  read rows ``slots (B,)`` → ``(B, max_len, *inner)``,
                 or through a page table ``(B, n_blocks)`` →
                 ``(B, n_blocks * max_len, *inner)``; feeds
                 DecodeAttention.
  KVCacheGatherRows  read SELECTED token rows through a page table:
                 ``tables (B, n_blocks)``, logical ``positions (B, K)``
                 → ``(B, K, *inner)``. What sparse attention reads: K
                 rows, never the sequence's whole logical view.
  KVCachePageCopy  ``cache[dst] = cache[src]`` over whole rows: the
                 prefix cache's copy-on-write.
  PagedDecodeAttention  attention of ``q`` over a K and a V cache read
                 IN PLACE through a page table (:func:`paged_decode_
                 attention`): no view is gathered.
  PagedLatentAttention  absorbed latent attention of ``q`` over ONE cache
                 of latent rows (inner shape ``(W,)``: a position's row
                 is the key of every head, its first ``value_dim`` lanes
                 their value) read in place through a page table
                 (:func:`paged_latent_attention`).

A second KIND of pool, :class:`StatePool` ``(num_slots, *inner)``, is
addressed by SLOT, not by page and position: what a recurrent layer
carries a sequence (``StatePoolAlloc`` here; the ops that advance a row
in place are ``ops/ssm_ops.py``'s ``CausalConv1D``, ``SSMChunkScan`` and
``SSMStateUpdate``). It is allocated, donated and linted with the paged
pools and is in no copy-on-write: a sequence's state is never shared.

Who reads the pool in place and who still gathers (PR 30). The paged
programs of the dense causal LM (``models/causal_lm._PagedCaches``:
decode step and page-chunk prefill) attend through
``PagedDecodeAttention``, whose kernel takes pages from the stored pool
by the table; the latent-attention model (``models/latent_moe_lm.py``)
does the same over its ONE pool of latent rows a layer
(``PagedLatentAttention``, PR 33). Still gathered: the slot caches of the
translation model and speculative verify (``transformer._SlotCaches``: ``KVCacheGather``
of one dense row a sequence, then ``DecodeAttention``), the sparse
model's indexer view and selected rows (``KVCacheGather`` /
``KVCacheGatherRows``), and ``PagedDecodeAttention``'s own ``xla``
lowering — the gathered view plus the composed softmax — which is what
runs on the CPU, under a mesh and in mode ``off``.

Ordering note: a gather or a paged attention has no data edge from the
appends that must precede it; build it under
``stf.control_dependencies([append])`` (the :class:`KVCache` helper and
the models' cache accessors do) — the hazard detector (mode ``raise``)
rejects the unordered RAW otherwise.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import numpy as np

from ..framework import dtypes as dtypes_mod
from ..framework import graph as ops_mod
from ..framework import op_registry
from ..framework import tensor_shape as shape_mod
from ..kernels import registry as _kreg

# collection-style registry attr markers consumed by the
# lint/serving-decode-cache rule (analysis/lint.py)
CACHE_ATTR = "_kv_cache"
SHARDING_ATTR = "_cache_sharding"
# head-dim sharding declaration suffix: ``"tp:heads"`` shards the
# cache's HEAD dim (dim 2 of (slots, len, heads, head_dim)) over mesh
# axis ``tp`` — the decode-time tensor-parallel layout. A bare axis
# name keeps the legacy meaning (slot-dim sharding); "replicated"/None
# keeps the cache whole on every device.
HEAD_SHARD_SUFFIX = ":heads"
# dim index of the head dim in the canonical cache layout
# (slots, positions, heads, head_dim) — and in the STORED layout
# (slots, positions, heads*head_dim), where sharding dim 2 over ``tp``
# gives each device the same contiguous heads/tp whole heads (heads is
# the major factor of the merged axis), so one index serves the graph's
# specs and the store's NamedSharding alike
HEAD_DIM = 2
# shared-page layer markers (PR 16): PAGED_ATTR tags ops against a
# cache whose rows are REFCOUNTED shared pages (prefix cache) — a
# host-sink on one leaks another request's prompt state off device;
# VERIFY_ATTR tags cache writes inside a speculative VERIFY plan, which
# must carry GUARD_ATTR (the engine commits only the accepted prefix —
# an unguarded verify write would publish unverified draft state)
PAGED_ATTR = "_kv_paged"
VERIFY_ATTR = "_verify_plan"
GUARD_ATTR = "_refcount_guarded"

# a pool ADDRESSED BY SLOT (:class:`StatePool`): state that no position
# addresses — a recurrence's — beside the paged pools
STATE_ATTR = "_state_pool"

# attention read IN PLACE: the output is attention, not pages
PAGED_ATTENTION_OP_TYPES = ("PagedDecodeAttention", "PagedLatentAttention")
# a state pool's rows read, advanced and written back by ONE op
# (ops/ssm_ops.py): the output is the layer's, not the pool
STATE_UPDATE_OP_TYPES = ("CausalConv1D", "SSMChunkScan", "SSMStateUpdate")
# ops whose output is computed FROM a pool and is not the pool's rows
IN_PLACE_OP_TYPES = PAGED_ATTENTION_OP_TYPES + STATE_UPDATE_OP_TYPES
_CACHE_OP_TYPES = ("KVCacheAlloc", "KVCacheAppend", "KVCacheGather",
                   "KVCacheGatherRows", "KVCachePageCopy", "StatePoolAlloc"
                   ) + IN_PLACE_OP_TYPES


# ---------------------------------------------------------------------------
# lowerings
# ---------------------------------------------------------------------------

def _np_dtype(op):
    return dtypes_mod.as_dtype(op.attrs["dtype"]).np_dtype


def parse_cache_sharding(decl) -> Tuple[Optional[int], Optional[str]]:
    """Split a ``_cache_sharding`` declaration into ``(dim, axis)``.

    ``None``/``"replicated"`` -> ``(None, None)``; a bare mesh-axis name
    shards the SLOT dim (legacy form) -> ``(0, axis)``; ``"axis:heads"``
    shards the HEAD dim -> ``(HEAD_DIM, axis)`` — the decode
    tensor-parallel layout (each device owns heads/tp of every slot,
    so slot/page-table gathers stay shard-local)."""
    if not decl or decl == "replicated":
        return None, None
    decl = str(decl)
    if decl.endswith(HEAD_SHARD_SUFFIX):
        return HEAD_DIM, decl[:-len(HEAD_SHARD_SUFFIX)]
    if ":" in decl:
        raise ValueError(
            f"unknown cache sharding declaration {decl!r} "
            f"(want 'replicated', '<axis>', or '<axis>{HEAD_SHARD_SUFFIX}')")
    return 0, decl


def cache_named_sharding(decl, rank, mesh=None):
    """NamedSharding for a cache declared ``decl`` under the active (or
    given) mesh, or None when the declaration stays replicated / the
    mesh lacks the axis / the dim is out of range for ``rank``."""
    from ..parallel.mesh import current_mesh

    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    dim, axis = parse_cache_sharding(decl)
    if axis is None or dim is None or dim >= rank \
            or mesh.shape.get(axis, 1) <= 1:
        return None
    spec = [None] * rank
    spec[dim] = axis
    return mesh.named_sharding(*spec)


def stored_shape(shape) -> Tuple[int, ...]:
    """The shape a cache declared ``(num_slots, max_len, *inner)`` has
    in the VariableStore: an inner shape of rank >= 2 is stored
    flattened into ONE lane-dense minor axis (module docstring,
    "Stored layout"); scalar and rank-1 inners are stored as declared."""
    shape = tuple(int(d) for d in shape)
    if len(shape) < 4:
        return shape
    return shape[:2] + (int(np.prod(shape[2:])),)


def _logical_view(op, stored):
    """``stored (..., prod(inner))`` seen with the declared inner dims
    again: the leading inner dim is inferred, so a head shard (its own
    ``heads/tp`` whole heads on the minor axis) reshapes the same way."""
    inner = tuple(int(d) for d in op.attrs["shape"][2:])
    if len(inner) < 2:
        return stored
    return stored.reshape(stored.shape[:-1] + (-1,) + inner[1:])


def _hint_cache_class(ctx, op):
    """Tag the cache's store entry for the HBM ledger (trace-time
    Python side effect — stf.telemetry.memory classifies the store
    name as kv_cache instead of generic state)."""
    sess = getattr(ctx, "session", None)
    if sess is not None:
        try:
            sess._variable_store.classes[op.attrs["var_name"]] = \
                "kv_cache"
        except Exception:  # noqa: BLE001 — accounting only
            pass


def _lower_kv_alloc(ctx, op, inputs):
    import jax.numpy as jnp

    _hint_cache_class(ctx, op)
    shape = stored_shape(op.attrs["shape"])
    val = jnp.zeros(shape, _np_dtype(op))
    ns = None
    if not getattr(ctx, "host", False) \
            and not getattr(ctx, "in_shard_map", False):
        try:
            ns = cache_named_sharding(op.attrs.get(SHARDING_ATTR),
                                      len(shape))
        except ValueError:
            ns = None
    if ns is not None:
        import jax

        # commit the declared layout at birth: the zeros leave the
        # alloc step already sharded, every later step's donated cache
        # input inherits it, and registering the NamedSharding in the
        # store makes checkpoint restore (VariableStore.load) re-place
        # the restored cache at the same layout
        val = jax.lax.with_sharding_constraint(val, ns)
        sess = getattr(ctx, "session", None)
        if sess is not None:
            try:
                sess._variable_store.shardings.setdefault(
                    op.attrs["var_name"], ns)
            except Exception:  # noqa: BLE001 — placement hint only
                pass
    ctx.write_var(op.attrs["var_name"], val)
    return [_logical_view(op, val)]


def _lower_kv_append(ctx, op, inputs):
    import jax.numpy as jnp

    name = op.attrs["var_name"]
    value, slots, positions = inputs
    cache = ctx.read_var(name, op)
    if value.dtype != cache.dtype:
        value = value.astype(cache.dtype)
    # (B, P, *inner) -> the stored rank: inner dims merge into one axis
    value = value.reshape(value.shape[:2] + cache.shape[2:])
    p = value.shape[1]
    p_idx = jnp.asarray(positions, jnp.int32)[:, None] + jnp.arange(
        p, dtype=jnp.int32)[None, :]
    new = cache.at[jnp.asarray(slots, jnp.int32)[:, None], p_idx].set(value)
    ctx.write_var(name, new)
    return [_logical_view(op, new)]


def _lower_kv_gather(ctx, op, inputs):
    import jax.numpy as jnp

    cache = ctx.read_var(op.attrs["var_name"], op)
    idx = jnp.asarray(inputs[0], jnp.int32)
    rows = cache[idx]
    if idx.ndim == 2:
        # page-table gather: slots (B, n_blocks) -> the LOGICAL cache
        # view (B, n_blocks * page_len, *inner) — block b's pages
        # concatenated in table order, so downstream DecodeAttention
        # sees one contiguous per-sequence cache exactly like the 1-D
        # slot path (lengths mask in logical coordinates)
        b, nb = idx.shape
        rows = rows.reshape((b, nb * cache.shape[1]) + cache.shape[2:])
    return [_logical_view(op, rows)]


def _lower_kv_gather_rows(ctx, op, inputs):
    import jax.numpy as jnp

    cache = ctx.read_var(op.attrs["var_name"], op)
    tables = jnp.asarray(inputs[0], jnp.int32)
    pos = jnp.asarray(inputs[1], jnp.int32)
    page_len = cache.shape[1]
    # logical position -> (physical page, in-page offset); only the K
    # selected rows move, (B, K, prod(inner)) out of the whole pool
    pages = jnp.take_along_axis(tables, pos // page_len, axis=1)
    return [_logical_view(op, cache[pages, pos % page_len])]


def _lower_kv_page_copy(ctx, op, inputs):
    import jax.numpy as jnp

    name = op.attrs["var_name"]
    dst, src = inputs
    cache = ctx.read_var(name, op)
    rows = cache[jnp.asarray(src, jnp.int32)]
    new = cache.at[jnp.asarray(dst, jnp.int32)].set(rows)
    ctx.write_var(name, new)
    return [_logical_view(op, new)]


op_registry.register(
    "KVCacheAlloc", lower=_lower_kv_alloc,
    effects=op_registry.Effects(writes=("var_name",)))
op_registry.register(
    "KVCacheAppend", lower=_lower_kv_append,
    effects=op_registry.Effects(writes=("var_name",), update="update"))
op_registry.register(
    "KVCacheGather", lower=_lower_kv_gather,
    effects=op_registry.Effects(reads=("var_name",)))
op_registry.register(
    "KVCacheGatherRows", lower=_lower_kv_gather_rows,
    effects=op_registry.Effects(reads=("var_name",)))
op_registry.register(
    "KVCachePageCopy", lower=_lower_kv_page_copy,
    effects=op_registry.Effects(writes=("var_name",), update="update"))


# ---------------------------------------------------------------------------
# public handle
# ---------------------------------------------------------------------------

class KVCache:
    """Handle to one paged cache in the VariableStore.

    Build-time only (holds no device state): methods emit graph ops
    against the default graph. The cache value itself lives in the
    session's store under ``name`` once the :meth:`alloc` op has run.
    """

    def __init__(self, name: str, num_slots: int, max_len: int,
                 inner_shape: Sequence[int], dtype,
                 sharding: Optional[str] = None, paged: bool = False):
        self.name = name
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.inner_shape = tuple(int(d) for d in inner_shape)
        self.dtype = dtypes_mod.as_dtype(dtype)
        # committed-sharding declaration: cache state commits at this
        # layout in the store ("replicated", a mesh-axis name the slot
        # dim shards over, or "<axis>:heads" — the decode
        # tensor-parallel layout sharding the HEAD dim so each device
        # owns heads/tp of every slot); recorded on every cache op so
        # offline lint (graph_lint --serving) can check it without a
        # session
        self.sharding = sharding or "replicated"
        parse_cache_sharding(self.sharding)  # validate the declaration
        # paged=True: rows are refcounted shared pages (prefix cache) —
        # every op carries PAGED_ATTR so lint can hold the shared-page
        # layer to the stricter host-sink contract
        self.paged = bool(paged)

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.num_slots, self.max_len) + self.inner_shape

    @property
    def stored_shape(self) -> Tuple[int, ...]:
        """Shape of the store entry (:func:`stored_shape`)."""
        return stored_shape(self.shape)

    def _attrs(self):
        a = {"var_name": self.name, "shape": list(self.shape),
             "dtype": self.dtype.name, CACHE_ATTR: True,
             SHARDING_ATTR: self.sharding}
        if self.paged:
            a[PAGED_ATTR] = True
        return a

    def alloc(self, name=None):
        """Zero-fill the cache storage (returns the cache tensor; fetch
        the op — not the tensor — to keep the cache on device)."""
        g = ops_mod.get_default_graph()
        op = g.create_op(
            "KVCacheAlloc", [], attrs=self._attrs(),
            name=name or f"{self.name}_alloc",
            output_specs=[(shape_mod.TensorShape(list(self.shape)),
                           self.dtype)])
        return op.outputs[0]

    def append(self, value, slots, positions, name=None,
               verify_plan=False, refcount_guarded=False):
        """Write ``value (B, P, *inner)`` at ``slots (B,)`` int32 rows,
        positions ``positions (B,) + [0, P)``. Returns the updated cache
        tensor (use it for control deps, never as a fetch).

        ``verify_plan=True`` marks a write inside a speculative VERIFY
        program; it must also set ``refcount_guarded=True`` (the engine
        commits only the accepted prefix) or the
        ``lint/serving-decode-cache`` rule errors."""
        g = ops_mod.get_default_graph()
        value = ops_mod.convert_to_tensor(value, dtype=self.dtype)
        slots = ops_mod.convert_to_tensor(slots, dtype=dtypes_mod.int32)
        positions = ops_mod.convert_to_tensor(positions,
                                              dtype=dtypes_mod.int32)
        attrs = self._attrs()
        if verify_plan:
            attrs[VERIFY_ATTR] = True
            attrs[GUARD_ATTR] = bool(refcount_guarded)
        op = g.create_op(
            "KVCacheAppend", [value, slots, positions], attrs=attrs,
            name=name or f"{self.name}_append",
            output_specs=[(shape_mod.TensorShape(list(self.shape)),
                           self.dtype)])
        return op.outputs[0]

    def gather(self, slots, name=None):
        """Read rows ``slots (B,)`` → ``(B, max_len, *inner)``; or a
        page-table gather ``slots (B, n_blocks)`` → the logical view
        ``(B, n_blocks * max_len, *inner)`` (pages concatenated in
        table order)."""
        g = ops_mod.get_default_graph()
        slots = ops_mod.convert_to_tensor(slots, dtype=dtypes_mod.int32)
        if slots.shape.rank == 2:
            b = slots.shape[0].value
            nb = int(slots.shape[1].value)
            out_shape = [b, nb * self.max_len] + list(self.inner_shape)
        else:
            b = slots.shape[0] if slots.shape.rank == 1 else None
            out_shape = [b, self.max_len] + list(self.inner_shape)
        op = g.create_op(
            "KVCacheGather", [slots], attrs=self._attrs(),
            name=name or f"{self.name}_gather",
            output_specs=[(shape_mod.TensorShape(out_shape), self.dtype)])
        return op.outputs[0]

    def gather_rows(self, page_tables, positions, name=None):
        """Read selected token rows of a paged cache: ``page_tables
        (B, n_blocks)`` and LOGICAL ``positions (B, K)`` (token index in
        the sequence, ``< n_blocks * max_len``) → ``(B, K, *inner)``.
        Like :meth:`gather` it has no data edge from the appends it must
        follow: build it under their control dependency."""
        g = ops_mod.get_default_graph()
        page_tables = ops_mod.convert_to_tensor(page_tables,
                                                dtype=dtypes_mod.int32)
        positions = ops_mod.convert_to_tensor(positions,
                                              dtype=dtypes_mod.int32)
        out_shape = ([positions.shape[0].value, positions.shape[1].value]
                     + list(self.inner_shape))
        op = g.create_op(
            "KVCacheGatherRows", [page_tables, positions],
            attrs=self._attrs(), name=name or f"{self.name}_gather_rows",
            output_specs=[(shape_mod.TensorShape(out_shape), self.dtype)])
        return op.outputs[0]

    def copy_pages(self, dst, src, name=None):
        """Copy whole rows ``cache[dst] = cache[src]`` (``dst``/``src``
        (M,) int32) — the prefix cache's copy-on-write primitive: a
        request diverging inside a shared page copies it before its own
        appends. Returns the updated cache tensor (control deps)."""
        g = ops_mod.get_default_graph()
        dst = ops_mod.convert_to_tensor(dst, dtype=dtypes_mod.int32)
        src = ops_mod.convert_to_tensor(src, dtype=dtypes_mod.int32)
        op = g.create_op(
            "KVCachePageCopy", [dst, src], attrs=self._attrs(),
            name=name or f"{self.name}_page_copy",
            output_specs=[(shape_mod.TensorShape(list(self.shape)),
                           self.dtype)])
        return op.outputs[0]

    def append_and_gather(self, value, slots, positions, name=None,
                          verify_plan=False, refcount_guarded=False):
        """The decode-step idiom: append, then gather the SAME rows
        under a control dependency so the RAW on the cache resource is
        graph-ordered (the hazard engine enforces this)."""
        appended = self.append(value, slots, positions, name=name,
                               verify_plan=verify_plan,
                               refcount_guarded=refcount_guarded)
        with ops_mod.get_default_graph().control_dependencies(
                [appended.op]):
            return self.gather(slots,
                               name=(name + "_gather") if name else None)

    def __repr__(self):
        return (f"KVCache({self.name!r}, slots={self.num_slots}, "
                f"max_len={self.max_len}, inner={self.inner_shape}, "
                f"dtype={self.dtype.name}, sharding={self.sharding!r})")


def kv_cache(name, num_slots, max_len, inner_shape, dtype,
             sharding: Optional[str] = None, paged: bool = False) -> KVCache:
    """Declare one paged KV cache (see module docstring for layout)."""
    return KVCache(name, num_slots, max_len, inner_shape, dtype,
                   sharding=sharding, paged=paged)


class StatePool:
    """Handle to one pool of per-sequence state ADDRESSED BY SLOT:
    ``(num_slots, *inner)`` in the VariableStore, stored as declared.

    What a paged cache cannot hold: state that is not a row a position —
    a state-space layer's ``h`` and the last rows its convolution saw. A
    live sequence's state is the row of its SLOT (the engine's
    ``CacheSlotPool`` id); the last row is the scratch slot a bucket's
    padding rows use. Allocated with the paged pools, donated into every
    step and updated in place like them; never shared between sequences
    (no ``PAGED_ATTR``) and never copied on write. The ops that advance
    it (``ops/ssm_ops.py``) read and write a row in ONE op, declared as a
    read-modify-write of the store entry."""

    def __init__(self, name: str, num_slots: int,
                 inner_shape: Sequence[int], dtype,
                 sharding: Optional[str] = None):
        self.name = name
        self.num_slots = int(num_slots)
        self.inner_shape = tuple(int(d) for d in inner_shape)
        self.dtype = dtypes_mod.as_dtype(dtype)
        self.sharding = sharding or "replicated"
        if self.sharding != "replicated":
            raise ValueError("a state pool is kept whole on every device; "
                             f"got sharding {sharding!r}")
        self.paged = False

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.num_slots,) + self.inner_shape

    stored_shape = shape

    @property
    def scratch_slot(self) -> int:
        return self.num_slots - 1

    def _attrs(self):
        return {"var_name": self.name, "shape": list(self.shape),
                "dtype": self.dtype.name, CACHE_ATTR: True,
                STATE_ATTR: True, SHARDING_ATTR: self.sharding}

    def alloc(self, name=None):
        """Zero-fill the pool (fetch the op, not the tensor)."""
        g = ops_mod.get_default_graph()
        op = g.create_op(
            "StatePoolAlloc", [], attrs=self._attrs(),
            name=name or f"{self.name}_alloc",
            output_specs=[(shape_mod.TensorShape(list(self.shape)),
                           self.dtype)])
        return op.outputs[0]

    def __repr__(self):
        return (f"StatePool({self.name!r}, slots={self.num_slots}, "
                f"inner={self.inner_shape}, dtype={self.dtype.name})")


def state_pool(name, num_slots, inner_shape, dtype) -> StatePool:
    """Declare one pool of per-sequence state addressed by slot."""
    return StatePool(name, num_slots, inner_shape, dtype)


def _lower_state_alloc(ctx, op, inputs):
    import jax.numpy as jnp

    _hint_cache_class(ctx, op)
    val = jnp.zeros(tuple(op.attrs["shape"]), _np_dtype(op))
    ctx.write_var(op.attrs["var_name"], val)
    return [val]


op_registry.register(
    "StatePoolAlloc", lower=_lower_state_alloc,
    effects=op_registry.Effects(writes=("var_name",)))


def is_cache_op(op) -> bool:
    return op.type in _CACHE_OP_TYPES


def cache_names(op) -> Tuple[str, ...]:
    """Store names of the caches a cache op touches: one, or the K and
    the V cache of a ``PagedDecodeAttention``."""
    vn = op.attrs.get("var_name")
    if vn is None:
        return ()
    return tuple(vn) if isinstance(vn, (list, tuple)) else (vn,)


# ---------------------------------------------------------------------------
# DecodeAttention graph op (the paged-cache decode kernel's entry)
# ---------------------------------------------------------------------------

def decode_attention(q, k_cache, v_cache, lengths, *, bias=None,
                     sm_scale=None, causal_offset=False, name=None):
    """Attention for one query position — or a query BLOCK — against
    gathered cache rows.

    q: (B, heads, head_dim) single new query per sequence, or
    (B, Kq, heads, head_dim) a block of Kq query positions (speculative
    verify / block prefill); k_cache/v_cache: (B, max_len, heads,
    head_dim) — the :class:`KVCache` gather layout; lengths: (B,) int32
    live prefix per sequence; bias: optional additive (B, max_len) key
    bias (cross-attention padding masks). With a query block,
    ``causal_offset=True`` means ``lengths`` is the committed prefix
    BEFORE the block and query j attends positions < lengths[b]+j+1
    (the block's own K/V already appended at lengths[b]..+Kq-1);
    ``causal_offset=False`` means every query sees exactly
    positions < lengths[b] (cross-attention over a fixed source).
    Routed Pallas vs composed-XLA through stf.kernels like every fused
    op. Inference-only: no registered gradient.
    """
    g = ops_mod.get_default_graph()
    q = ops_mod.convert_to_tensor(q)
    k_cache = ops_mod.convert_to_tensor(k_cache)
    v_cache = ops_mod.convert_to_tensor(v_cache)
    lengths = ops_mod.convert_to_tensor(lengths, dtype=dtypes_mod.int32)
    if causal_offset and q.shape.rank != 4:
        raise ValueError("causal_offset=True requires a query block "
                         f"(B, Kq, H, D); got q rank {q.shape.rank}")
    inputs = [q, k_cache, v_cache, lengths]
    if bias is not None:
        inputs.append(ops_mod.convert_to_tensor(bias))
    op = g.create_op("DecodeAttention", inputs,
                     attrs={"sm_scale": sm_scale,
                            "causal_offset": bool(causal_offset)},
                     name=name or "decode_attention",
                     output_specs=[(q.shape, q.dtype)])
    return op.outputs[0]


def _lower_decode_attention(ctx, op, input_values):
    q, k, v, lengths = input_values[:4]
    bias = input_values[4] if len(input_values) > 4 else None
    fn = _kreg.select(
        "DecodeAttention",
        _kreg.aval_key(q, k, v, bias, has_bias=bias is not None))
    kw = {}
    if op.attrs.get("causal_offset"):
        # only block-query verify/prefill plans set this; keeping the
        # kwarg conditional preserves every pre-existing impl signature
        kw["causal_offset"] = True
    return [fn(q, k, v, lengths, bias=bias,
               sm_scale=op.attrs.get("sm_scale"), **kw)]


op_registry.register("DecodeAttention", lower=_lower_decode_attention)


# ---------------------------------------------------------------------------
# PagedDecodeAttention graph op: attention straight off the paged pool
# ---------------------------------------------------------------------------

def paged_decode_attention(q, k_cache: KVCache, v_cache: KVCache,
                           page_tables, lengths, *, sm_scale=None,
                           causal_offset=False, name=None):
    """:func:`decode_attention` of ``q`` over the logical view of two
    PAGED caches, without the view: K and V are read from the stored
    pools through ``page_tables (B, n_blocks)``.

    q, lengths, ``causal_offset``: as :func:`decode_attention` (no key
    bias: a paged self-attention cache has no padding inside its
    length). GROUPED QUERIES: ``q`` may carry more heads than the caches'
    inner shape ``(H_kv, D)``, ``H % H_kv == 0``: query head ``h`` reads
    key-value head ``h // (H / H_kv)``, in both lowerings. The caches are named in the op's attributes and declared
    as READS of both store entries, so the hazard engine orders the op
    after the layer's appends exactly as it orders a ``KVCacheGather``:
    build it under their control dependency. Routed through stf.kernels:
    ``pallas`` is :func:`..pallas.decode_attention.paged_decode_attention`
    (pages arrive lane-dense as stored, entries past the row's length
    are never read), ``xla`` the gathered view and the composed softmax.
    Inference-only: no registered gradient."""
    if k_cache.shape != v_cache.shape or k_cache.dtype != v_cache.dtype \
            or k_cache.sharding != v_cache.sharding \
            or len(k_cache.inner_shape) != 2:
        raise ValueError(
            "paged_decode_attention wants a K and a V cache declared "
            f"alike with inner shape (heads, head_dim); got {k_cache!r} "
            f"and {v_cache!r}")
    g = ops_mod.get_default_graph()
    q = ops_mod.convert_to_tensor(q)
    page_tables = ops_mod.convert_to_tensor(page_tables,
                                            dtype=dtypes_mod.int32)
    lengths = ops_mod.convert_to_tensor(lengths, dtype=dtypes_mod.int32)
    if causal_offset and q.shape.rank != 4:
        raise ValueError("causal_offset=True requires a query block "
                         f"(B, Kq, H, D); got q rank {q.shape.rank}")
    h_kv, d = k_cache.inner_shape
    if q.shape[-1].value != d or (q.shape[-2].value or 0) % h_kv:
        raise ValueError(
            f"q {q.shape} does not go with caches of inner shape "
            f"{k_cache.inner_shape}: head_dim alike and the query heads a "
            "multiple of the key-value heads")
    attrs = k_cache._attrs()
    attrs.update(var_name=[k_cache.name, v_cache.name], sm_scale=sm_scale,
                 causal_offset=bool(causal_offset))
    op = g.create_op("PagedDecodeAttention", [q, page_tables, lengths],
                     attrs=attrs, name=name or "paged_decode_attention",
                     output_specs=[(q.shape, q.dtype)])
    return op.outputs[0]


def _lower_paged_decode_attention(ctx, op, input_values):
    q, tables, lengths = input_values
    k_pool, v_pool = (ctx.read_var(n, op) for n in op.attrs["var_name"])
    fn = _kreg.select("PagedDecodeAttention",
                      _kreg.aval_key(q, k_pool, tables))
    return [fn(q, k_pool, v_pool, tables, lengths,
               sm_scale=op.attrs.get("sm_scale"),
               causal_offset=bool(op.attrs.get("causal_offset")))]


op_registry.register(
    "PagedDecodeAttention", lower=_lower_paged_decode_attention,
    effects=op_registry.Effects(reads=("var_name",)))


# ---------------------------------------------------------------------------
# PagedLatentAttention graph op: absorbed latent attention off the pool
# ---------------------------------------------------------------------------

def paged_latent_attention(q, cache: KVCache, page_tables, lengths, *,
                           value_dim, sm_scale, causal_offset=False,
                           name=None):
    """Absorbed multi-head latent attention over ONE paged cache of
    latent rows, read in place through ``page_tables (B, n_blocks)``.

    cache: declared with inner shape ``(W,)`` — a position's row is
    ``[c_kv ; k_rope]``, shared by every head; q: ``(B, H, W)`` or a
    block ``(B, Kq, H, W)``, each head's query already absorbed into the
    latent space (``[q_nope . W^K ; q_rope]``). ``score = sm_scale * q .
    row``; the value of a position is its row's first ``value_dim``
    lanes, so the result is ``q.shape[:-1] + (value_dim,)``, still in the
    latent space (the caller applies ``W^V``). lengths and
    ``causal_offset`` as :func:`decode_attention`. Ordered after the
    layer's append like :func:`paged_decode_attention`: build it under
    that control dependency. Routed through stf.kernels: ``pallas`` is
    :func:`..pallas.latent_attention.paged_latent_attention`, ``xla`` the
    gathered view and the composed softmax. Inference-only."""
    if len(cache.inner_shape) != 1 or not 0 < value_dim <= cache.inner_shape[0]:
        raise ValueError(
            "paged_latent_attention wants a cache of latent rows (inner "
            f"shape (W,)) and 0 < value_dim <= W; got {cache!r}, "
            f"value_dim {value_dim}")
    g = ops_mod.get_default_graph()
    q = ops_mod.convert_to_tensor(q)
    page_tables = ops_mod.convert_to_tensor(page_tables,
                                            dtype=dtypes_mod.int32)
    lengths = ops_mod.convert_to_tensor(lengths, dtype=dtypes_mod.int32)
    if causal_offset and q.shape.rank != 4:
        raise ValueError("causal_offset=True requires a query block "
                         f"(B, Kq, H, W); got q rank {q.shape.rank}")
    attrs = cache._attrs()
    attrs.update(value_dim=int(value_dim), sm_scale=float(sm_scale),
                 causal_offset=bool(causal_offset))
    out_shape = q.shape.as_list()[:-1] + [int(value_dim)]
    op = g.create_op("PagedLatentAttention", [q, page_tables, lengths],
                     attrs=attrs, name=name or "paged_latent_attention",
                     output_specs=[(shape_mod.TensorShape(out_shape),
                                    q.dtype)])
    return op.outputs[0]


def _lower_paged_latent_attention(ctx, op, input_values):
    q, tables, lengths = input_values
    pool = ctx.read_var(op.attrs["var_name"], op)
    value_dim = int(op.attrs["value_dim"])
    fn = _kreg.select("PagedLatentAttention",
                      _kreg.aval_key(q, pool, tables, value_dim=value_dim))
    return [fn(q, pool, tables, lengths, value_dim=value_dim,
               sm_scale=op.attrs["sm_scale"],
               causal_offset=bool(op.attrs.get("causal_offset")))]


op_registry.register(
    "PagedLatentAttention", lower=_lower_paged_latent_attention,
    effects=op_registry.Effects(reads=("var_name",)))


# ---------------------------------------------------------------------------
# sharding propagation rules (stf.analysis.sharding)
#
# Cache state commits at the layout declared on the cache (slot dim
# shardable; positions/features replicated per shard) — the same
# contract as optimizer slots: the STORE owns the committed sharding,
# data edges adapt to it.
# ---------------------------------------------------------------------------

from ..analysis import sharding as _shard  # noqa: E402


def _cache_spec(op, ctx, rank):
    try:
        dim, axis = parse_cache_sharding(op.attrs.get(SHARDING_ATTR))
    except ValueError:
        dim, axis = None, None
    spec = [()] * rank
    if axis is not None and dim is not None and dim < rank \
            and ctx.mesh_axes.get(axis, 1) > 1:
        spec[dim] = (axis,)
    return tuple(spec)


def _kv_alloc_rule(op, in_specs, ctx):
    return [_cache_spec(op, ctx, len(op.attrs["shape"]))]


def _kv_append_rule(op, in_specs, ctx):
    # the committed cache layout wins; a differently-sharded value
    # reshards on the way in (slot-indexed scatter stays local when the
    # batch rides the same axis as the slot dim)
    spec = _cache_spec(op, ctx, len(op.attrs["shape"]))
    if in_specs and in_specs[0] is not None \
            and len(in_specs[0]) == len(spec) and in_specs[0] != spec:
        ctx.require(0, spec)
    return [spec]


def _kv_gather_rule(op, in_specs, ctx):
    # gather-by-slot over a slot-sharded cache is an all-gather of the
    # touched rows; over a replicated cache it is local. A HEAD-sharded
    # cache (tensor-parallel decode) is ALSO local: slot/page-table
    # indexing never crosses the head dim, each shard gathers its own
    # heads, and the output keeps the committed head sharding (dim 2 of
    # (B, L, heads, head_dim) — same inner dims as the cache).
    rank = len(op.attrs["shape"])
    cache = _cache_spec(op, ctx, rank)
    out_t = op.outputs[0]
    out_rank = rank if out_t.shape.rank is None else out_t.shape.rank
    out = [()] * out_rank
    if cache[0]:
        ctx.collective(
            "all-gather", cache[0],
            _shard.tensor_bytes(out_t) / ctx.shard_factor(cache),
            note="KVCacheGather over slot-sharded cache",
            tensor_name=out_t.name)
    else:
        for d in range(2, min(rank, out_rank)):
            out[d] = cache[d]
    return [tuple(out)]


def _kv_page_copy_rule(op, in_specs, ctx):
    # whole-row copy inside the committed cache layout: stays local on
    # a replicated OR head-sharded cache (each shard copies its own
    # heads of the row); over a slot-sharded cache the rows move
    # between shards (all-to-all of the touched rows) — priced like the
    # gather's collective but over M rows only
    return [_cache_spec(op, ctx, len(op.attrs["shape"]))]


_shard.register_rules(_kv_alloc_rule, "KVCacheAlloc")
_shard.register_rules(_kv_append_rule, "KVCacheAppend")
_shard.register_rules(_kv_gather_rule, "KVCacheGather")
_shard.register_rules(_kv_gather_rule, "KVCacheGatherRows")
_shard.register_rules(_kv_page_copy_rule, "KVCachePageCopy")


def _decode_attention_rule(op, in_specs, ctx):
    # (B, H, D) q — or a (B, Kq, H, D) query block: batch/head sharding
    # flows through exactly like FlashAttention (attention is
    # embarrassingly parallel over heads — the tensor-parallel decode
    # layout runs per-shard with ZERO collectives here); a sharded
    # cache length would need ring traffic the kernel does not do —
    # consumed gathered. Kq (block position axis) and head_dim never
    # shard.
    sq = in_specs[0]
    if sq is None:
        return [None]
    if len(sq) == 4:
        return [(sq[0], (), sq[2], ())]
    if len(sq) == 3:
        return [(sq[0], sq[1], ())]
    return [sq]


_shard.register_rules(_decode_attention_rule, "DecodeAttention")


def _paged_attention_rule(op, in_specs, ctx):
    # a cache READ like the gather: local over a replicated or a
    # head-sharded pool (each shard attends with its own heads; a latent
    # pool has no head dim and every head reads the same rows); over a
    # slot-sharded pool the pages the tables address move to the rows
    # that read them, priced as the gather's all-gather of every pool's
    # view (K and V, or the one pool of latent rows)
    cache = _cache_spec(op, ctx, len(op.attrs["shape"]))
    if cache[0]:
        shape = op.attrs["shape"]
        page = float(np.prod(shape[1:])) * op.outputs[0].dtype.base_dtype.size
        entries = _shard._nelems(op.inputs[1].shape) or 0
        ctx.collective(
            "all-gather", cache[0],
            len(cache_names(op)) * entries * page / ctx.shard_factor(cache),
            note=f"{op.type} over slot-sharded caches",
            tensor_name=op.outputs[0].name)
    return _decode_attention_rule(op, in_specs, ctx)


_shard.register_rules(_paged_attention_rule, *PAGED_ATTENTION_OP_TYPES)
