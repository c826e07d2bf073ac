"""AOT compilation + executable cache
(ref: tensorflow/compiler/aot — tfcompile turns a frozen subgraph into a
standalone object file).

TPU-native, AOT = lower the fetch subgraph to one XLA program ahead of
Session.run and persist the compiled executable, so process restart skips
the (20-40s) TPU compile. Two layers:
- ``compile_fetches``: graph -> pure fn -> jax.jit(...).lower().compile(),
  returning an AotExecutable with HLO text, cost analysis, and a stable
  cache key.
- ``compile_step``: AOT-compile an already-planned Session step for ONE
  concrete feed-shape bucket (state avals from the live variable store),
  returning an AotStepExecutable the session's device dispatch calls in
  place of the jit path. ``stf.serving.ModelServer`` warms one per batch
  bucket at load so the first request of every bucket shape skips the
  trace+compile (ref: the reference's Servable warmup,
  tensorflow_serving/servables).
- ``enable_persistent_cache``: turns on jax's compilation cache directory,
  the PJRT-level equivalent of tfcompile's ahead-of-time object files —
  keyed by HLO, shared across processes.
"""

from __future__ import annotations

import hashlib
import os
from typing import Any, Dict, List, Optional, Sequence

from ..framework import graph as ops_mod
from ..framework import lowering as lowering_mod


_persistent_cache_dir: Optional[str] = None
_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_persistent_cache(cache_dir: Optional[str] = None) -> str:
    """Persist compiled executables across processes (subsequent
    compiles of the same HLO are disk hits). The one rule for where:

    - ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own handling of it
      stands — the directory and its thresholds. ``cache_dir`` is
      ignored and nothing here touches the cache's configuration.
      (Whoever placed the cache may also have capped its size: on the
      chip tool's machine the cap is 192 MiB, and writing every
      sub-second compile into it made one smoke run's entries outgrow
      the cap, so a second run found none of them — PR 21.)
    - unset: ``cache_dir`` if given, else ``<checkout>/.jax_cache`` (a
      fixed path — the path is part of the cache key, so a directory
      that moves never hits); everything is cached, however fast the
      compile was.

    Returns the directory in effect."""
    import jax

    global _persistent_cache_dir
    env_dir = os.environ.get(_CACHE_ENV)
    if env_dir:
        return env_dir
    _persistent_cache_dir = cache_dir or _CHECKOUT_CACHE
    os.makedirs(_persistent_cache_dir, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _persistent_cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return _persistent_cache_dir


def persistent_cache_dir() -> Optional[str]:
    """The persistent-cache directory in effect, or None."""
    return os.environ.get(_CACHE_ENV) or _persistent_cache_dir


class _CompiledBundle:
    """Shared introspection over a (lowered, compiled) XLA pair."""

    def __init__(self, compiled, lowered, key):
        self._compiled = compiled
        self._lowered = lowered
        self.cache_key = key

    @property
    def hlo_text(self) -> str:
        return self._lowered.as_text()

    def cost_analysis(self) -> Dict[str, Any]:
        """XLA's estimate: flops, bytes accessed — feeds stf.utils.perf."""
        ca = self._compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0]
        return dict(ca) if ca else {}

    def memory_analysis(self):
        return self._compiled.memory_analysis()


class AotExecutable(_CompiledBundle):
    """A compiled fetch subgraph: call with feed values in declared order."""

    def __init__(self, compiled, lowered, feed_tensors, fetch_tensors, key):
        super().__init__(compiled, lowered, key)
        self.feed_tensors = list(feed_tensors)
        self.fetch_tensors = list(fetch_tensors)

    def __call__(self, *feed_values):
        if len(feed_values) != len(self.feed_tensors):
            raise ValueError(
                f"expected {len(self.feed_tensors)} feeds "
                f"({[t.name for t in self.feed_tensors]}), "
                f"got {len(feed_values)}")
        out = self._compiled(*feed_values)
        return out


def feed_signature(feed_args: Dict[str, Any]):
    """Stable key for one concrete feed-shape bucket: sorted (name,
    shape, dtype) triples. ``feed_args`` values may be numpy arrays,
    jax.Arrays, or ShapeDtypeStructs — anything with .shape/.dtype
    (never forces a device transfer)."""
    return tuple(sorted(
        (name, tuple(getattr(v, "shape", ())),
         str(getattr(v, "dtype", type(v).__name__)))
        for name, v in feed_args.items()))


class AotStepExecutable(_CompiledBundle):
    """An already-planned Session step, AOT-compiled for one feed-shape
    bucket. Call-compatible with the step's jitted function
    (``(state, feed_args, rng_key, rng_ctr)``), so the session's device
    dispatch (client/session.py ``_call_step_executable``) uses it
    transparently when the execution's ``feed_signature`` matches.
    State is donated exactly like the jit path — the caller commits the
    returned state dict back to the variable store."""

    def __init__(self, compiled, lowered, feed_avals, key):
        super().__init__(compiled, lowered, key)
        self.feed_avals = dict(feed_avals)
        self.feed_signature = feed_signature(feed_avals)

    def __call__(self, state, feed_args, rng_key, rng_ctr):
        return self._compiled(state, feed_args, rng_key, rng_ctr)


def compile_step(jitted, state: Dict[str, Any],
                 feed_avals: Dict[str, Any], rng_key,
                 rng_ctr) -> AotStepExecutable:
    """AOT-compile a planned step for one feed-shape bucket.

    ``jitted`` is the step's jax.jit function; ``state`` the CURRENT
    variable store (concrete arrays — only their avals matter, nothing
    executes); ``feed_avals`` maps feed tensor name ->
    jax.ShapeDtypeStruct of the bucket shape. With a persistent compile
    cache enabled (``enable_persistent_cache`` /
    ConfigProto(compile_cache_dir=...)), process restarts disk-hit
    these compiles — AOT warmup after the first deploy costs reads,
    not compiles."""
    lowered = jitted.lower(dict(state), dict(feed_avals), rng_key, rng_ctr)
    key = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
    compiled = lowered.compile()
    return AotStepExecutable(compiled, lowered, feed_avals, key)


def compile_fetches(fetches, feeds: Sequence[ops_mod.Tensor],
                    graph: Optional[ops_mod.Graph] = None,
                    static_args: Optional[Dict] = None) -> AotExecutable:
    """AOT-compile ``fetches`` as a pure function of ``feeds``.

    Variables are baked at their initializer values are NOT supported here —
    AOT programs are pure (the tfcompile model: frozen graphs). Feed every
    runtime input explicitly.
    """
    import jax

    fetch_list = fetches if isinstance(fetches, (list, tuple)) else [fetches]
    g = graph or fetch_list[0].graph
    feed_list = list(feeds)
    fed_set = set(feed_list)
    target_ops = [t.op for t in fetch_list]
    pruned = lowering_mod.prune(target_ops, fed_set)
    for op in pruned:
        if op.op_def.is_stateful and op.type not in ("Placeholder",):
            raise ValueError(
                f"AOT subgraph contains stateful op {op.name} ({op.type}); "
                "AOT programs must be pure — freeze variables first "
                "(ref tfcompile freezes the graph)")

    def fn(*feed_values):
        ctx = lowering_mod.LoweringContext(state={}, rng_root=None)
        for t, v in zip(feed_list, feed_values):
            ctx.env[t] = v
        lowering_mod.execute_ops(ctx, pruned, fed=fed_set)
        return tuple(ctx.env[t] for t in fetch_list)

    for t in feed_list:
        # Validate BEFORE building ShapeDtypeStructs: unknown-rank shapes
        # would crash in as_list() with an unfriendly error, and a static
        # scalar (as_list() == []) is perfectly valid.
        if t.shape.rank is None or any(d is None for d in t.shape.as_list()):
            raise ValueError(
                f"AOT feed {t.name} has unknown shape {t.shape}; XLA AOT "
                "needs fully static shapes")
    args = [jax.ShapeDtypeStruct(
        tuple(t.shape.as_list()), t.dtype.as_numpy_dtype)
        for t in feed_list]
    lowered = jax.jit(fn).lower(*args)
    key = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
    compiled = lowered.compile()
    return AotExecutable(compiled, lowered, feed_list, fetch_list, key)
