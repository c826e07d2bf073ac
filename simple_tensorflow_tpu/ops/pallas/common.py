"""Shared helpers for the Pallas TPU kernels.

These kernels replace the reference's hand-written CUDA kernels
(ref: tensorflow/core/kernels/*_gpu.cu.cc) with Mosaic/Pallas programs tiled
for the MXU/VPU. On non-TPU backends (the CPU test mesh) every kernel runs
in interpret mode (:func:`use_interpret`), so numerics tests are
backend-independent.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def use_interpret() -> bool:
    """Whether a kernel traced now must run in Pallas interpret mode.

    Mosaic compiles for the TPU only, so this follows the platform the
    enclosing computation lowers for: the ``jax.default_device`` scope
    if one is open, else the default backend. Asked at every trace,
    never cached — a process is not frozen into whatever the first call
    saw. The kernel modules call it as ``common.use_interpret()`` so a
    test that compiles for a described (unattached) TPU steers all of
    them by patching this one attribute.

    Interpreting while the default backend is a TPU means the kernel
    silently left the chip: an error, not a mode."""
    dev = jax.config.jax_default_device
    platform = getattr(dev, "platform", dev) or jax.default_backend()
    if platform == "tpu":
        return False
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"Pallas kernel traced for platform {platform!r} while the "
            "default backend is a TPU: it would run in interpret mode")
    return True


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


def pad_dim(x, dim: int, target: int, value=0.0):
    """Zero-pad dimension ``dim`` of x up to ``target`` (no-op if equal)."""
    cur = x.shape[dim]
    if cur == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[dim] = (0, target - cur)
    return jnp.pad(x, pads, constant_values=value)


NEG_INF = -1e30  # finite "minus infinity" — avoids NaN from (-inf) - (-inf)


def mix32(h):
    """murmur3 finalizer: avalanche a uint32 value (vectorized)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def keep_threshold(keep_prob: float):
    """uint32 compare threshold for a counter-based keep mask."""
    return jnp.uint32(min(int(keep_prob * 4294967296.0), 4294967295))


def counter_keep_mask(seed, salt, rows, cols, keep_prob):
    """Deterministic dropout keep-mask from GLOBAL (row, col) indices.

    Counter-based: hash(seed, salt, row, col) — the mask is a pure
    function of positions, so a blocked Pallas kernel and a composed
    XLA lowering regenerate it bit-identically from the same seed (the
    kernel-registry swap contract), and backward passes replay it
    without materializing anything in HBM. Plain uint32 arithmetic (not
    pltpu.prng_*) so interpret mode runs the identical code path.

    seed/salt: uint32-castable scalars; rows/cols: broadcastable uint32
    index arrays.
    """
    # every term stays uint32 explicitly: mixing in an int32 scalar would
    # silently promote-then-clamp the whole chain back to int32 (x64 off),
    # and an int32 < uint32 compare wraps the threshold negative.
    h0 = mix32(seed.astype(jnp.uint32)
               ^ (salt.astype(jnp.uint32) * jnp.uint32(0x9E3779B9)))
    h = mix32(h0 ^ rows.astype(jnp.uint32))
    h = mix32(h ^ cols.astype(jnp.uint32))
    return h.astype(jnp.uint32) < keep_threshold(keep_prob)
