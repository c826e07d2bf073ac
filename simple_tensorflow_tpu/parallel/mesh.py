"""Device mesh (replaces ref: tensorflow/core/distributed_runtime +
third_party/nccl.BUILD NCCL rings).

The reference scales by partitioning the graph across grpc workers and
inserting Send/Recv + NcclAllReduce. TPU-native scaling is SPMD: ONE global
program, a named device mesh, shardings on arrays — XLA GSPMD inserts the
collectives over ICI/DCN. `Mesh` wraps jax.sharding.Mesh with the canonical
training axis names:

  dp    data parallel (batch split, params replicated)
  fsdp  fully-sharded data parallel (batch + params split)
  tp    tensor/model parallel (Megatron-style)
  pp    pipeline parallel (layer stages)
  sp    sequence/context parallel (ring attention)
  ep    expert parallel (MoE)

Multi-host: jax.distributed (stf.train.Server) makes jax.devices() span all
hosts; the same Mesh code then spans the pod — ICI within a slice, DCN
across slices (put dp/fsdp outermost so its collectives ride DCN).
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

CANONICAL_AXES = ("dp", "fsdp", "pp", "tp", "sp", "ep")

_mesh_stack = threading.local()


def _stack() -> list:
    if not hasattr(_mesh_stack, "stack"):
        _mesh_stack.stack = []
    return _mesh_stack.stack


class Mesh:
    """Named device mesh. ``Mesh({"dp": 2, "tp": 4})`` or
    ``Mesh(axis_names=("dp","tp"), shape=(2,4))``."""

    def __init__(self, axes: Optional[Dict[str, int]] = None,
                 devices=None, axis_names: Optional[Sequence[str]] = None,
                 shape: Optional[Sequence[int]] = None):
        import jax

        if axes is not None:
            axis_names = tuple(axes.keys())
            shape = tuple(int(v) for v in axes.values())
        elif axis_names is not None:
            axis_names = tuple(axis_names)
            shape = tuple(int(s) for s in (shape or ()))
        else:
            raise ValueError("Mesh needs axes={name: size}")
        if devices is None:
            devices = jax.devices()
        n = int(np.prod(shape)) if shape else 1
        if len(devices) < n:
            raise ValueError(
                f"Mesh {dict(zip(axis_names, shape))} needs {n} devices, "
                f"have {len(devices)}")
        dev_array = np.asarray(devices[:n]).reshape(shape)
        self._jax_mesh = jax.sharding.Mesh(dev_array, axis_names)
        self.axis_names = axis_names
        self.shape = dict(zip(axis_names, shape))

    @property
    def jax_mesh(self):
        return self._jax_mesh

    @property
    def devices(self):
        return list(self._jax_mesh.devices.flat)

    @property
    def size(self) -> int:
        return int(np.prod(list(self.shape.values())))

    def axis_size(self, name: str) -> int:
        return self.shape[name]

    def named_sharding(self, *spec):
        import jax

        return jax.sharding.NamedSharding(self._jax_mesh,
                                          jax.sharding.PartitionSpec(*spec))

    def __enter__(self):
        _stack().append(self)
        return self

    def __exit__(self, *exc):
        _stack().pop()
        return False

    def __repr__(self):
        return f"stf.parallel.Mesh({self.shape})"


def current_mesh() -> Optional[Mesh]:
    st = _stack()
    return st[-1] if st else None


def get_shard_map():
    """``jax.shard_map`` (callers pass ``check_vma``)."""
    from jax import shard_map

    return shard_map


def make_mesh(axes: Dict[str, int], devices=None) -> Mesh:
    return Mesh(axes, devices=devices)


class PartitionSpec(tuple):
    """Thin alias of jax.sharding.PartitionSpec semantics, constructible
    without jax imported at module scope."""

    def __new__(cls, *parts):
        return super().__new__(cls, parts)

    def to_jax(self):
        import jax

        return jax.sharding.PartitionSpec(*self)


P = PartitionSpec
