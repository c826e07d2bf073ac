"""DType system for simple_tensorflow_tpu.

TPU-native rework of the reference dtype registry
(ref: tensorflow/python/framework/dtypes.py): the set of user-visible dtypes
matches the reference, but the backing representation is a numpy/ml_dtypes
dtype that JAX understands directly — no proto enum, no quantized side-band
types (int8/uint8 + scale factors are plain tensors here, as XLA wants them).
bfloat16 is a first-class citizen (it's the TPU MXU's native input type).
"""

from __future__ import annotations

import builtins
import dataclasses
from typing import Any, Optional

import numpy as np

try:  # ml_dtypes ships with jax
    import ml_dtypes as _mld

    _BFLOAT16_NP = np.dtype(_mld.bfloat16)
    _FP8_E4M3_NP = np.dtype(_mld.float8_e4m3fn)
    _FP8_E5M2_NP = np.dtype(_mld.float8_e5m2)
except Exception:  # pragma: no cover - ml_dtypes is always present with jax
    _BFLOAT16_NP = np.dtype(np.float32)
    _FP8_E4M3_NP = np.dtype(np.float32)
    _FP8_E5M2_NP = np.dtype(np.float32)


@dataclasses.dataclass(frozen=True)
class DType:
    """A tensor element type.

    Thin, hashable wrapper over a numpy dtype with the reference API surface:
    ``is_floating``, ``is_integer``, ``min``/``max``, ``base_dtype``,
    ``as_numpy_dtype`` etc. (ref: python/framework/dtypes.py:31 ``class DType``).
    ``_is_ref`` mirrors the reference's ``*_ref`` variants used for variable
    endpoints; on TPU variables are functional state so refs only matter for
    API fidelity.
    """

    name: str
    np_dtype: np.dtype
    _is_ref: bool = False

    # -- classification ------------------------------------------------------
    @property
    def is_floating(self) -> bool:
        return self.np_dtype.kind == "f" or self.name.startswith(("bfloat", "float8"))

    @property
    def is_integer(self) -> bool:
        return self.np_dtype.kind in ("i", "u")

    @property
    def is_unsigned(self) -> bool:
        return self.np_dtype.kind == "u"

    @property
    def is_complex(self) -> bool:
        return self.np_dtype.kind == "c"

    @property
    def is_bool(self) -> bool:
        return self.np_dtype.kind == "b"

    @property
    def is_numpy_compatible(self) -> bool:
        return True

    @property
    def is_quantized(self) -> bool:
        return self.name.startswith("q")

    # -- conversion ----------------------------------------------------------
    @property
    def as_numpy_dtype(self):
        return self.np_dtype.type

    @property
    def base_dtype(self) -> "DType":
        if self._is_ref:
            return DType(self.name[: -len("_ref")], self.np_dtype)
        return self

    @property
    def real_dtype(self) -> "DType":
        if self.name == "complex64":
            return float32
        if self.name == "complex128":
            return float64
        return self

    @property
    def is_ref_dtype(self) -> bool:
        return self._is_ref

    @property
    def _ref(self) -> "DType":
        if self._is_ref:
            return self
        return DType(self.name + "_ref", self.np_dtype, True)

    # -- limits --------------------------------------------------------------
    @property
    def min(self):
        if self.is_bool:
            return False
        if self.name == "bfloat16":
            return float(_mld.finfo(_mld.bfloat16).min)
        if self.is_floating:
            return float(np.finfo(self.np_dtype).min)
        return int(np.iinfo(self.np_dtype).min)

    @property
    def max(self):
        if self.is_bool:
            return True
        if self.name == "bfloat16":
            return float(_mld.finfo(_mld.bfloat16).max)
        if self.is_floating:
            return float(np.finfo(self.np_dtype).max)
        return int(np.iinfo(self.np_dtype).max)

    @property
    def limits(self):
        return (self.min, self.max)

    @property
    def size(self) -> int:
        return self.np_dtype.itemsize

    def is_compatible_with(self, other) -> bool:
        other = as_dtype(other)
        return self.base_dtype == other.base_dtype

    def __str__(self):
        return f"<dtype: '{self.name}'>"

    def __repr__(self):
        return f"stf.{self.name}"

    def __eq__(self, other):
        if other is None:
            return False
        try:
            other = as_dtype(other)
        except TypeError:
            return NotImplemented
        return self.name == other.name

    def __ne__(self, other):
        eq = self.__eq__(other)
        if eq is NotImplemented:
            return eq
        return not eq

    def __hash__(self):
        return hash(self.name)


# Registry -------------------------------------------------------------------

float16 = DType("float16", np.dtype(np.float16))
half = float16
bfloat16 = DType("bfloat16", _BFLOAT16_NP)
float32 = DType("float32", np.dtype(np.float32))
float64 = DType("float64", np.dtype(np.float64))
double = float64
float8_e4m3fn = DType("float8_e4m3fn", _FP8_E4M3_NP)
float8_e5m2 = DType("float8_e5m2", _FP8_E5M2_NP)
int8 = DType("int8", np.dtype(np.int8))
int16 = DType("int16", np.dtype(np.int16))
int32 = DType("int32", np.dtype(np.int32))
int64 = DType("int64", np.dtype(np.int64))
uint8 = DType("uint8", np.dtype(np.uint8))
uint16 = DType("uint16", np.dtype(np.uint16))
uint32 = DType("uint32", np.dtype(np.uint32))
uint64 = DType("uint64", np.dtype(np.uint64))
bool_ = DType("bool", np.dtype(np.bool_))
complex64 = DType("complex64", np.dtype(np.complex64))
complex128 = DType("complex128", np.dtype(np.complex128))
# Strings are host-side only (parsing, filenames); represented as numpy object
# arrays and never shipped to the TPU.
string = DType("string", np.dtype(object))
# Quantized dtypes (ref: framework/types.h DT_QINT8 etc.). On TPU the MXU
# consumes plain s8/u8/s32 with separate scale tensors, so these are
# distinct *names* over the native widths — exactly how the int8 Pallas
# quant_matmul wants its operands.
qint8 = DType("qint8", np.dtype(np.int8))
quint8 = DType("quint8", np.dtype(np.uint8))
qint32 = DType("qint32", np.dtype(np.int32))
qint16 = DType("qint16", np.dtype(np.int16))
quint16 = DType("quint16", np.dtype(np.uint16))

_ALL = [
    float16, bfloat16, float32, float64, float8_e4m3fn, float8_e5m2,
    int8, int16, int32, int64, uint8, uint16, uint32, uint64,
    bool_, complex64, complex128, string,
    qint8, quint8, qint32, qint16, quint16,
]
_BY_NAME = {d.name: d for d in _ALL}
_BY_NAME.update({d.name + "_ref": d._ref for d in _ALL})
_BY_NAME["bool"] = bool_
_BY_NAME["half"] = float16
_BY_NAME["double"] = float64

_NP_TO_DTYPE = {}
for _d in _ALL:
    if _d.name == "string":
        continue
    _NP_TO_DTYPE.setdefault(_d.np_dtype, _d)
# Python scalar defaults: int -> int32 (TPU-friendly; jax default), float -> float32.
_PY_DEFAULTS = {builtins.int: int32, builtins.float: float32, builtins.bool: bool_,
                builtins.complex: complex64, builtins.str: string, bytes: string}


def as_dtype(value) -> DType:
    """Convert ``value`` (DType, string, numpy dtype, python type, jax dtype)
    to a DType. (ref: python/framework/dtypes.py:580 ``as_dtype``)."""
    if isinstance(value, DType):
        return value
    if value is None:
        raise TypeError("Cannot convert None to DType")
    if isinstance(value, str):
        if value in _BY_NAME:
            return _BY_NAME[value]
        raise TypeError(f"Cannot convert {value!r} to a DType")
    if value in _PY_DEFAULTS:
        return _PY_DEFAULTS[value]
    try:
        np_dt = np.dtype(value)
    except TypeError:
        raise TypeError(f"Cannot convert {value!r} to a DType")
    if np_dt in _NP_TO_DTYPE:
        return _NP_TO_DTYPE[np_dt]
    if np_dt.kind in ("U", "S", "O"):
        return string
    raise TypeError(f"Cannot convert {value!r} to a DType")


# -- 64-bit narrowing --------------------------------------
#
# TPUs have no int64/float64 datapath; with jax_enable_x64 off (the
# default), 64-bit requests compute in 32 bits. The divergence is
# documented loudly in docs/MIGRATION.md; at runtime it surfaces as ONE
# warning at the session/feed boundary — never a per-op warning storm.

_64BIT_NARROWING = {"int64": "int32", "uint64": "uint32",
                    "float64": "float32"}
_narrowing_warned = [False]


def narrowed_if_no_x64(dtype) -> DType:
    """The dtype 64-bit requests actually compute with: narrowed to its
    32-bit sibling when jax_enable_x64 is off, unchanged otherwise. Op
    lowerings that honor an explicit 64-bit out_type route through this
    so jax never emits its per-callsite truncation warning."""
    d = as_dtype(dtype)
    base = d.base_dtype.name
    if base not in _64BIT_NARROWING:
        return d
    import jax

    if jax.config.jax_enable_x64:
        return d
    return as_dtype(_64BIT_NARROWING[base])


def warn_64bit_narrowing_once(where: str) -> None:
    """Emit the single process-wide 64-bit narrowing notice (the
    session/feed boundary calls this when a 64-bit tensor first crosses
    it). Replaces the per-op jax truncation warnings."""
    if _narrowing_warned[0]:
        return
    import jax

    if jax.config.jax_enable_x64:
        return
    _narrowing_warned[0] = True
    import warnings

    warnings.warn(
        f"stf: {where} uses a 64-bit dtype, but TPU (and this runtime "
        "with jax_enable_x64 off) computes int64/uint64/float64 as "
        "32-bit. Values past 2**31 or needing f64 precision will be "
        "WRONG, not an error. See docs/MIGRATION.md '64-bit dtypes' "
        "for details and JAX_ENABLE_X64=1 for CPU-only full-width "
        "runs. (This warning is emitted once per process.)",
        UserWarning, stacklevel=3)


def infer_dtype(value) -> DType:
    """Infer the stf dtype of a concrete python/numpy/jax value."""
    import jax

    if isinstance(value, (jax.Array, np.ndarray, np.generic)):
        return as_dtype(value.dtype)
    if isinstance(value, builtins.bool):
        return bool_
    if isinstance(value, builtins.int):
        return int32
    if isinstance(value, builtins.float):
        return float32
    if isinstance(value, builtins.complex):
        return complex64
    if isinstance(value, (builtins.str, bytes)):
        return string
    if isinstance(value, (list, tuple)):
        arr = np.asarray(value)
        return as_dtype(arr.dtype) if arr.dtype.kind not in "USO" else string
    raise TypeError(f"Cannot infer dtype of {type(value)}")
