"""Op-registry conformance sweep (ref: the 175
kernel_test files under tensorflow/python/kernel_tests/).

Coverage is ENFORCED by enumeration: every name in the op registry must
be either (a) in ``CASES`` — auto-expanded into numeric tests against an
independent numpy oracle over a dtype × rank × degenerate-shape grid,
with a finite-difference gradient check for float ops — or (b) in
``COVERED_ELSEWHERE`` with a ``file::test`` pointer that this module
verifies actually exists. A newly registered op with neither fails
``test_registry_fully_covered``.

Oracle rules: numpy/scipy only (never jax) so the comparison is
independent of the implementation under test. Gradient checks compare
``jax.grad`` of the registered pure_fn against central differences — the
same autodiff path SymbolicGradient lowers through.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pytest

import simple_tensorflow_tpu as stf  # noqa: F401 — registers all ops
# lazily-imported op modules whose registrations must be DETERMINISTIC
# here: whether the enumeration guard sees these ops must not depend on
# which test modules happened to run earlier in the process
import simple_tensorflow_tpu.ops.kv_cache_ops  # noqa: F401,E501 — KVCache*/DecodeAttention
from simple_tensorflow_tpu.framework import op_registry

_HERE = os.path.dirname(os.path.abspath(__file__))


# ---------------------------------------------------------------------------
# case machinery
# ---------------------------------------------------------------------------

@dataclass
class Case:
    """One executable conformance case for an op."""

    inputs: List[np.ndarray]
    oracle: Callable[..., Any]           # numpy fn over the inputs
    attrs: Dict[str, Any] = field(default_factory=dict)
    tol: float = 1e-5
    grad: bool = False                   # finite-difference check input 0
    grad_tol: float = 2e-2
    name: str = ""


def _rng(seed):
    return np.random.RandomState(seed)


_FLOAT_SHAPES = [(7,), (3, 4), (2, 3, 4), (0, 4)]  # incl. degenerate


def _unary_cases(np_fn, dtypes=("float32",), positive=False,
                 lo=-2.0, hi=2.0, grad=True, tol=1e-5,
                 attrs=None) -> List[Case]:
    cases = []
    for di, dt in enumerate(dtypes):
        for si, shape in enumerate(_FLOAT_SHAPES):
            r = _rng(100 * di + si)
            if np.dtype(dt).kind in "fc":
                x = r.uniform(lo, hi, size=shape).astype(dt)
                if positive:
                    x = np.abs(x) + 0.1
            elif dt == "bool":
                x = r.rand(*shape) > 0.5
            else:
                x = r.randint(1 if positive else -5, 6,
                              size=shape).astype(dt)
            g = grad and np.dtype(dt).kind == "f" and x.size > 0
            cases.append(Case([x], np_fn, attrs=dict(attrs or {}),
                              tol=tol, grad=g))
    return cases


def _binary_cases(np_fn, dtypes=("float32",), positive_b=False,
                  grad=True, tol=1e-5, integer_ok=True,
                  shapes=None) -> List[Case]:
    cases = []
    shapes = shapes or [((3, 4), (3, 4)), ((2, 3, 4), (3, 4)),  # broadcast
                        ((5,), ()), ((0, 3), (3,))]
    for di, dt in enumerate(dtypes):
        for si, (sa, sb) in enumerate(shapes):
            r = _rng(200 * di + si)
            if np.dtype(dt).kind in "fc":
                a = r.uniform(-2, 2, size=sa).astype(dt)
                b = r.uniform(-2, 2, size=sb).astype(dt)
            elif dt == "bool":
                a = r.rand(*sa) > 0.5
                b = r.rand(*sb) > 0.5
            else:
                a = r.randint(-5, 6, size=sa).astype(dt)
                b = r.randint(-5, 6, size=sb).astype(dt)
            if positive_b:
                b = (np.abs(b) + 1).astype(dt)
            g = grad and np.dtype(dt).kind == "f" \
                and a.size > 0 and b.size > 0
            cases.append(Case([a, b], np_fn, tol=tol, grad=g))
    return cases


def _reduction_cases(np_fn, dtypes=("float32",), grad=True,
                     tol=1e-5) -> List[Case]:
    cases = []
    for di, dt in enumerate(dtypes):
        r = _rng(300 + di)
        x = r.uniform(0.5, 2.0, size=(3, 4, 5)).astype(dt) \
            if np.dtype(dt).kind == "f" \
            else r.randint(1, 5, size=(3, 4, 5)).astype(dt)
        for axis, keep in [(None, False), (1, False), ((0, 2), True),
                           (-1, False)]:
            def oracle(v, axis=axis, keep=keep):
                return np_fn(v, axis=axis, keepdims=keep)

            g = grad and np.dtype(dt).kind == "f"
            cases.append(Case([x], oracle,
                              attrs={"axis": axis, "keepdims": keep},
                              tol=tol, grad=g))
    return cases


def run_case(op_name: str, case: Case):
    import jax

    od = op_registry.get(op_name)
    assert od.pure_fn is not None, f"{op_name} has no pure_fn"
    with jax.default_device(jax.devices("cpu")[0]):
        got = od.pure_fn(*case.inputs, **case.attrs)
    expected = case.oracle(*case.inputs)
    got_list = list(got) if isinstance(got, (list, tuple)) else [got]
    exp_list = (list(expected) if isinstance(expected, (list, tuple))
                else [expected])
    assert len(got_list) == len(exp_list), (
        f"{op_name}: {len(got_list)} outputs vs oracle {len(exp_list)}")
    for g, e in zip(got_list, exp_list):
        g = np.asarray(g)
        e = np.asarray(e)
        assert g.shape == e.shape, (
            f"{op_name}: shape {g.shape} vs oracle {e.shape}")
        if e.dtype.kind in "fc":
            np.testing.assert_allclose(g.astype(e.dtype), e,
                                       rtol=case.tol, atol=case.tol,
                                       err_msg=op_name)
        else:
            np.testing.assert_array_equal(g, e, err_msg=op_name)

    if case.grad:
        _check_grad(op_name, od, case)


def _check_grad(op_name, od, case):
    """jax.grad of sum(output) wrt input 0 vs central differences."""
    import jax

    x0 = case.inputs[0]
    rest = case.inputs[1:]

    def f(x):
        out = od.pure_fn(x, *rest, **case.attrs)
        out0 = out[0] if isinstance(out, (list, tuple)) else out
        return jax.numpy.sum(out0.astype("float32"))

    with jax.default_device(jax.devices("cpu")[0]):
        sym = np.asarray(jax.grad(f)(x0.astype(np.float32)))
    eps = 1e-3
    flat = x0.astype(np.float64).ravel()
    idxs = (range(flat.size) if flat.size <= 8
            else _rng(7).choice(flat.size, 8, replace=False))
    for i in idxs:
        xp = flat.copy()
        xp[i] += eps
        xm = flat.copy()
        xm[i] -= eps
        fp = float(f(xp.reshape(x0.shape).astype(np.float32)))
        fm = float(f(xm.reshape(x0.shape).astype(np.float32)))
        num = (fp - fm) / (2 * eps)
        scale = max(1.0, abs(num), abs(float(sym.ravel()[i])))
        assert abs(num - float(sym.ravel()[i])) <= case.grad_tol * scale, (
            f"{op_name} grad mismatch at {i}: numeric {num} vs "
            f"symbolic {sym.ravel()[i]}")


# ---------------------------------------------------------------------------
# the case table — numpy/scipy oracles only
# ---------------------------------------------------------------------------

import scipy.linalg as sp_linalg  # noqa: E402  (scipy is a jax dependency)
import scipy.special as sp_special  # noqa: E402

_FI = ("float32", "int32")
_F = ("float32",)
_F2 = ("float32", "float64")
_I = ("int32", "int64")
_B = ("bool",)

CASES: Dict[str, List[Case]] = {}


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


CASES.update({
    # ---- unary, full-domain ----
    "Abs": _unary_cases(np.abs, _FI),
    "Neg": _unary_cases(np.negative, _FI),
    "Sign": _unary_cases(np.sign, _FI, grad=False),
    "Square": _unary_cases(np.square, _FI),
    "Ceil": _unary_cases(np.ceil, _F, grad=False),
    "Floor": _unary_cases(np.floor, _F, grad=False),
    "Rint": _unary_cases(np.rint, _F, grad=False),
    "Round": _unary_cases(np.round, _F, grad=False),
    "Exp": _unary_cases(np.exp, _F),
    "Expm1": _unary_cases(np.expm1, _F),
    "Sin": _unary_cases(np.sin, _F),
    "Cos": _unary_cases(np.cos, _F),
    "Tan": _unary_cases(np.tan, _F, lo=-1.2, hi=1.2),
    "Sinh": _unary_cases(np.sinh, _F),
    "Cosh": _unary_cases(np.cosh, _F),
    "Tanh": _unary_cases(np.tanh, _F),
    "Asin": _unary_cases(np.arcsin, _F, lo=-0.9, hi=0.9),
    "Acos": _unary_cases(np.arccos, _F, lo=-0.9, hi=0.9),
    "Atan": _unary_cases(np.arctan, _F),
    "Asinh": _unary_cases(np.arcsinh, _F),
    "Acosh": _unary_cases(np.arccosh, _F, lo=1.1, hi=3.0),
    "Atanh": _unary_cases(np.arctanh, _F, lo=-0.9, hi=0.9),
    "Sigmoid": _unary_cases(_sigmoid, _F),
    "Erf": _unary_cases(sp_special.erf, _F),
    "Erfc": _unary_cases(sp_special.erfc, _F),
    "Relu": _unary_cases(lambda x: np.maximum(x, 0), _FI),
    "Relu6": _unary_cases(lambda x: np.clip(x, 0, 6), _F),
    "Selu": _unary_cases(
        lambda x: np.where(x > 0, 1.0507009873554805 * x,
                           1.0507009873554805 * 1.6732632423543772
                           * (np.exp(x) - 1)).astype(x.dtype), _F,
        tol=1e-4),
    "Elu": _unary_cases(
        lambda x: np.where(x > 0, x, np.exp(x) - 1).astype(x.dtype), _F),
    "Softplus": _unary_cases(lambda x: np.log1p(np.exp(x)), _F, tol=1e-4),
    "Softsign": _unary_cases(lambda x: x / (1 + np.abs(x)), _F),
    "Swish": _unary_cases(lambda x: x * _sigmoid(x), _F),
    "Gelu": _unary_cases(
        lambda x: 0.5 * x * (1 + sp_special.erf(x / np.sqrt(2.0))), _F,
        tol=2e-3),
    "LeakyRelu": _unary_cases(
        lambda x: np.where(x > 0, x, 0.2 * x).astype(x.dtype), _F),
    "LogicalNot": _unary_cases(np.logical_not, _B, grad=False),
    "Invert": _unary_cases(np.invert, _I, grad=False),
    "OnesLike": _unary_cases(np.ones_like, _FI, grad=False),
    "ZerosLike": _unary_cases(np.zeros_like, _FI, grad=False),
    "Identity": _unary_cases(lambda x: x, _FI),
    "Snapshot": _unary_cases(lambda x: x, _F),
    "StopGradient": _unary_cases(lambda x: x, _F, grad=False),
    "PreventGradient": _unary_cases(lambda x: x, _F, grad=False),
    "Digamma": _unary_cases(sp_special.digamma, _F, positive=True,
                            tol=1e-4),
    "Lgamma": _unary_cases(sp_special.gammaln, _F, positive=True,
                           tol=1e-4),
    # ---- unary, positive-domain ----
    "Log": _unary_cases(np.log, _F, positive=True),
    "Log1p": _unary_cases(np.log1p, _F, positive=True),
    "Sqrt": _unary_cases(np.sqrt, _F, positive=True),
    "Rsqrt": _unary_cases(lambda x: 1.0 / np.sqrt(x), _F, positive=True),
    "Reciprocal": _unary_cases(lambda x: 1.0 / x, _F, positive=True),
    # ---- special-value predicates ----
    "IsFinite": [Case([np.array([1.0, np.inf, -np.inf, np.nan, 0.0],
                                np.float32)], np.isfinite)],
    "IsInf": [Case([np.array([1.0, np.inf, -np.inf, np.nan], np.float32)],
                   np.isinf)],
    "IsNan": [Case([np.array([1.0, np.inf, np.nan, 0.0], np.float32)],
                   np.isnan)],
    # ---- binary ----
    "Add": _binary_cases(np.add, _FI),
    "Sub": _binary_cases(np.subtract, _FI),
    "Mul": _binary_cases(np.multiply, _FI),
    "Div": _binary_cases(np.true_divide, _F, positive_b=True),
    "TrueDiv": _binary_cases(np.true_divide, _F, positive_b=True),
    "RealDiv": _binary_cases(np.true_divide, _F, positive_b=True),
    "FloorDiv": _binary_cases(np.floor_divide, _FI, positive_b=True,
                              grad=False),
    "FloorMod": _binary_cases(np.mod, _FI, positive_b=True, grad=False),
    "Mod": _binary_cases(np.mod, _FI, positive_b=True, grad=False),
    "TruncateDiv": _binary_cases(
        lambda a, b: np.trunc(a / b).astype(a.dtype), _I,
        positive_b=True, grad=False,
        shapes=[((3, 4), (3, 4)), ((5,), (5,))]),
    "TruncateMod": _binary_cases(np.fmod, _I, positive_b=True,
                                 grad=False,
                                 shapes=[((3, 4), (3, 4)), ((5,), (5,))]),
    "Maximum": _binary_cases(np.maximum, _FI),
    "Minimum": _binary_cases(np.minimum, _FI),
    "SquaredDifference": _binary_cases(lambda a, b: (a - b) ** 2, _F),
    "Atan2": _binary_cases(np.arctan2, _F),
    "Xdivy": _binary_cases(
        lambda a, b: np.where(a == 0, 0.0, a / b).astype(a.dtype), _F,
        positive_b=True, grad=False),
    "Xlogy": _binary_cases(
        lambda a, b: np.where(a == 0, 0.0, a * np.log(b)).astype(a.dtype),
        _F, positive_b=True, grad=False),
    "Equal": _binary_cases(np.equal, _FI, grad=False),
    "NotEqual": _binary_cases(np.not_equal, _FI, grad=False),
    "Less": _binary_cases(np.less, _FI, grad=False),
    "LessEqual": _binary_cases(np.less_equal, _FI, grad=False),
    "Greater": _binary_cases(np.greater, _FI, grad=False),
    "GreaterEqual": _binary_cases(np.greater_equal, _FI, grad=False),
    "LogicalAnd": _binary_cases(np.logical_and, _B, grad=False),
    "LogicalOr": _binary_cases(np.logical_or, _B, grad=False),
    "LogicalXor": _binary_cases(np.logical_xor, _B, grad=False),
    "BitwiseAnd": _binary_cases(np.bitwise_and, _I, grad=False),
    "BitwiseOr": _binary_cases(np.bitwise_or, _I, grad=False),
    "BitwiseXor": _binary_cases(np.bitwise_xor, _I, grad=False),
    "ApproximateEqual": [Case(
        [np.array([1.0, 2.0, 3.0], np.float32),
         np.array([1.0000001, 2.5, 3.0], np.float32)],
        lambda a, b: np.abs(a - b) < 1e-5)],
    "Pow": [Case([np.abs(_rng(1).randn(3, 4)).astype(np.float32) + 0.5,
                  _rng(2).uniform(-2, 2, (3, 4)).astype(np.float32)],
                 np.power, grad=True)],
    "LeftShift": [Case([_rng(3).randint(0, 100, (6,)).astype(np.int32),
                        _rng(4).randint(0, 5, (6,)).astype(np.int32)],
                       np.left_shift)],
    "RightShift": [Case([_rng(5).randint(0, 100, (6,)).astype(np.int32),
                         _rng(6).randint(0, 5, (6,)).astype(np.int32)],
                        np.right_shift)],
    "Igamma": [Case([np.abs(_rng(7).randn(5)).astype(np.float32) + 0.5,
                     np.abs(_rng(8).randn(5)).astype(np.float32) + 0.5],
                    sp_special.gammainc, tol=1e-4)],
    "Igammac": [Case([np.abs(_rng(9).randn(5)).astype(np.float32) + 0.5,
                      np.abs(_rng(10).randn(5)).astype(np.float32) + 0.5],
                     sp_special.gammaincc, tol=1e-4)],
    "Zeta": [Case([np.array([2.0, 3.0, 4.0], np.float32),
                   np.array([1.0, 2.0, 3.0], np.float32)],
                  sp_special.zeta, tol=1e-4)],
    "Polygamma": [Case([np.array([1.0, 2.0], np.float32),
                        np.array([2.0, 3.0], np.float32)],
                       sp_special.polygamma, tol=1e-3)],
    "Betainc": [Case([np.array([1.5, 2.0], np.float32),
                      np.array([2.5, 1.0], np.float32),
                      np.array([0.3, 0.7], np.float32)],
                     sp_special.betainc, tol=1e-4)],
    # ---- reductions ----
    "Sum": _reduction_cases(np.sum, _FI),
    "Mean": _reduction_cases(np.mean, _F),
    "Prod": _reduction_cases(np.prod, _F),
    "Max": _reduction_cases(np.max, _FI),
    "Min": _reduction_cases(np.min, _FI),
    "All": _reduction_cases(lambda x, axis=None, keepdims=False:
                            np.all(x > 2, axis=axis, keepdims=keepdims)
                            if False else np.all(x, axis=axis,
                                                 keepdims=keepdims),
                            _B, grad=False),
    "Any": _reduction_cases(np.any, _B, grad=False),
    "LogSumExp": _reduction_cases(sp_special.logsumexp, _F, tol=1e-4),
    "EuclideanNorm": _reduction_cases(
        lambda x, axis=None, keepdims=False:
        np.sqrt(np.sum(np.square(x), axis=axis, keepdims=keepdims)), _F,
        tol=1e-4),
})


def _psd(n, seed):
    a = _rng(seed).randn(n, n).astype(np.float32)
    return a @ a.T + n * np.eye(n, dtype=np.float32)


def _np_segment(np_red, init):
    def oracle(data, ids, num_segments=None):
        n = int(num_segments if num_segments is not None
                else (ids.max() + 1 if ids.size else 0))
        out = np.full((n,) + data.shape[1:], init, data.dtype)
        for i, s in enumerate(ids):
            out[s] = np_red(out[s], data[i])
        return out
    return oracle


def _np_conv2d_valid(x, w):
    n, h, wd, cin = x.shape
    kh, kw, _, cout = w.shape
    oh, ow = h - kh + 1, wd - kw + 1
    out = np.zeros((n, oh, ow, cout), np.float32)
    for i in range(oh):
        for j in range(ow):
            patch = x[:, i:i + kh, j:j + kw, :].reshape(n, -1)
            out[:, i, j, :] = patch @ w.reshape(-1, cout)
    return out


def _np_maxpool_valid(x, k):
    n, h, w, c = x.shape
    oh, ow = h // k, w // k
    out = np.zeros((n, oh, ow, c), x.dtype)
    for i in range(oh):
        for j in range(ow):
            out[:, i, j, :] = x[:, i * k:(i + 1) * k,
                                j * k:(j + 1) * k, :].max(axis=(1, 2))
    return out


_x34 = _rng(20).randn(3, 4).astype(np.float32)
_x234 = _rng(21).randn(2, 3, 4).astype(np.float32)
_x345 = _rng(22).randn(3, 4, 5).astype(np.float32)
_ids6 = np.array([0, 0, 1, 2, 2, 2], np.int32)
_data6 = _rng(23).randn(6, 3).astype(np.float32)
_sq33 = _rng(24).randn(3, 3).astype(np.float32)
_img = np.abs(_rng(25).randn(2, 6, 6, 3)).astype(np.float32)
_kern = _rng(26).randn(3, 3, 3, 4).astype(np.float32) * 0.3
_cplx = (_rng(27).randn(4, 8) + 1j * _rng(28).randn(4, 8)) \
    .astype(np.complex64)

CASES.update({
    # ---- shape / array ----
    "Reshape": [Case([_x234], lambda x: x.reshape(4, 6),
                     attrs={"shape": (4, 6)}, grad=True),
                Case([_x234], lambda x: x.reshape(-1),
                     attrs={"shape": (-1,)})],
    "ExpandDims": [Case([_x34], lambda x: x[:, None, :],
                        attrs={"axis": 1}, grad=True)],
    "Squeeze": [Case([_x34[:, None, :]], lambda x: x.squeeze(1),
                     attrs={"axis": 1}),
                Case([_x34[None, :, None]], lambda x: x.squeeze(),
                     attrs={"axis": None})],
    "Transpose": [Case([_x234], lambda x: x.transpose(2, 0, 1),
                       attrs={"perm": (2, 0, 1)}, grad=True),
                  Case([_x34], lambda x: x.T, attrs={"perm": None})],
    "Concat": [Case([_x34, _x34 * 2], lambda a, b:
                    np.concatenate([a, b], 1), attrs={"axis": 1},
                    grad=True)],
    "Pack": [Case([_x34, _x34 * 2], lambda a, b: np.stack([a, b], 1),
                  attrs={"axis": 1}, grad=True)],
    "Unpack": [Case([_x234], lambda x: tuple(np.moveaxis(x, 1, 0)),
                    attrs={"num": 3, "axis": 1})],
    "Split": [Case([_x34], lambda x: tuple(np.split(x, 2, 1)),
                   attrs={"num_or_sections": 2, "axis": 1})],
    "Slice": [Case([_x234], lambda x: x[1:2, 0:2, 1:4],
                   attrs={"begin": (1, 0, 1), "size": (1, 2, 3)},
                   grad=True)],
    "Tile": [Case([_x34], lambda x: np.tile(x, (2, 3)),
                  attrs={"multiples": (2, 3)}, grad=True)],
    "Reverse": [Case([_x234], lambda x: x[:, ::-1, :],
                     attrs={"axis": (1,)}, grad=True)],
    "Fill": [Case([np.float32(2.5)], lambda v: np.full((2, 3), 2.5,
                                                       np.float32),
                  attrs={"dims": (2, 3)})],
    "Range": [Case([np.int32(2), np.int32(10), np.int32(3)],
                   lambda a, b, c: np.arange(2, 10, 3, np.int32))],
    "LinSpace": [Case([np.float32(0.0), np.float32(1.0), np.int32(5)],
                      lambda a, b, n: np.linspace(0, 1, 5,
                                                  dtype=np.float32))],
    "Cast": [Case([_x34], lambda x: x.astype(np.int32),
                  attrs={"dtype": stf.int32}),
             Case([np.array([0, 1, 2], np.int32)],
                  lambda x: x.astype(np.float32),
                  attrs={"dtype": stf.float32})],
    "Bitcast": [Case([np.array([1.0, -2.5], np.float32)],
                     lambda x: x.view(np.int32),
                     attrs={"dtype": stf.int32})],
    "Select": [Case([_x34 > 0, _x34, _x34 * 10],
                    lambda c, a, b: np.where(c, a, b))],
    "ClipByValue": [Case([_x34, np.float32(-0.5), np.float32(0.5)],
                         lambda x, lo, hi: np.clip(x, -0.5, 0.5),
                         grad=True)],
    "Pad": [Case([_x34], lambda x: np.pad(x, ((1, 2), (0, 1))),
                 attrs={"paddings": ((1, 2), (0, 1))}, grad=True),
            Case([_x34], lambda x: np.pad(x, ((1, 1), (1, 1)),
                                          mode="reflect"),
                 attrs={"paddings": ((1, 1), (1, 1)),
                        "mode": "reflect"})],
    "BroadcastTo": [Case([_x34[0]], lambda x: np.broadcast_to(x, (3, 4)),
                         attrs={"shape": (3, 4)})],
    "BroadcastArgs": [Case([np.array([3, 1], np.int32),
                            np.array([1, 4], np.int32)],
                           lambda a, b: np.array([3, 4], np.int32))],
    "Shape": [Case([_x234], lambda x: np.array(x.shape, np.int32))],
    "Size": [Case([_x234], lambda x: np.int32(x.size))],
    "Rank": [Case([_x234], lambda x: np.int32(x.ndim))],
    "InvertPermutation": [Case([np.array([2, 0, 1, 3], np.int32)],
                               lambda p: np.argsort(p).astype(np.int32))],
    "SequenceMask": [Case([np.array([1, 3, 0], np.int32)],
                          lambda ln: np.arange(4) < ln[:, None],
                          attrs={"maxlen": 4})],
    "Rot90": [Case([_x234[..., None]],
                   lambda x: np.rot90(x, axes=(1, 2)), attrs={"k": 1})],
    "OneHot": [Case([np.array([0, 2, 1], np.int32)],
                    lambda i: np.eye(4, dtype=np.float32)[i],
                    attrs={"depth": 4})],
    "Gather": [Case([_x34, np.array([2, 0], np.int32)],
                    lambda p, i: p[i], attrs={"axis": 0}, grad=True),
               Case([_x34, np.array([1, 3, 1], np.int32)],
                    lambda p, i: p[:, [1, 3, 1]], attrs={"axis": 1})],
    "GatherNd": [Case([_x34, np.array([[0, 1], [2, 3]], np.int32)],
                      lambda p, i: p[[0, 2], [1, 3]], grad=True)],
    "ScatterNd": [Case([np.array([[1], [3]], np.int32),
                        np.array([9.0, 8.0], np.float32)],
                       lambda i, u: np.array([0, 9, 0, 8, 0],
                                             np.float32),
                       attrs={"shape": (5,)})],
    "SparseToDense": [Case([np.array([[0, 1], [2, 2]], np.int32),
                            np.array([5.0, 6.0], np.float32)],
                           lambda i, v: np.array(
                               [[0, 5, 0], [0, 0, 0], [0, 0, 6]],
                               np.float32),
                           attrs={"shape": (3, 3)})],
    "DynamicPartition": [Case(
        # static-shape TPU semantics: each partition keeps the full
        # leading dim with non-member rows zero-masked in place
        [_data6, np.array([0, 1, 0, 1, 1, 0], np.int32)],
        lambda d, p: (np.where((p == 0)[:, None], d, 0.0),
                      np.where((p == 1)[:, None], d, 0.0)),
        attrs={"num_partitions": 2})],
    "DynamicStitch": [Case(
        [np.array([0, 2], np.int32), np.array([1, 3], np.int32),
         np.array([[1.0], [3.0]], np.float32),
         np.array([[2.0], [4.0]], np.float32)],
        lambda i1, i2, d1, d2: np.array([[1.], [2.], [3.], [4.]],
                                        np.float32),
        attrs={"n": 2})],
    "StridedSlice": [],  # spec-attr driven; covered via public slicing
    # ---- matmul / linalg ----
    "MatMul": [Case([_x34, _x34.T @ np.eye(3, dtype=np.float32)],
                    lambda a, b: a @ b, grad=True),
               Case([_x34, _x34], lambda a, b: a.T @ b,
                    attrs={"transpose_a": True}),
               Case([_x34, _x34], lambda a, b: a @ b.T,
                    attrs={"transpose_b": True})],
    "BatchMatMul": [Case([_x234, np.moveaxis(_x234, 1, 2)],
                         lambda a, b: a @ b, grad=True)],
    "Einsum": [Case([_x34, _x34.T], lambda a, b: a @ b,
                    attrs={"equation": "ij,jk->ik"}, grad=True)],
    "Tensordot": [Case([_x234, _x345], lambda a, b:
                       np.tensordot(a, b, axes=([2], [1])),
                       attrs={"axes": ((2,), (1,))}, grad=True)],
    "Cross": [Case([_rng(30).randn(4, 3).astype(np.float32),
                    _rng(31).randn(4, 3).astype(np.float32)],
                   np.cross, grad=True)],
    "L2Loss": [Case([_x34], lambda x: np.float32(np.sum(x * x) / 2),
                    grad=True)],
    "Moments": [Case([_x234], lambda x: (x.mean((0, 1)),
                                         x.var((0, 1))),
                     attrs={"axes": (0, 1)})],
    "Diag": [Case([np.array([1.0, 2.0, 3.0], np.float32)],
                  np.diag, grad=True)],
    "DiagPart": [Case([np.diag([1.0, 2.0, 3.0]).astype(np.float32)],
                      np.diag)],
    "MatrixDiag": [Case([_x34], lambda x:
                        np.stack([np.diag(r) for r in x]))],
    "MatrixDiagPart": [Case([_rng(33).randn(2, 3, 3)
                             .astype(np.float32)],
                            lambda x: np.stack([np.diag(m)
                                                for m in x]))],
    "MatrixBandPart": [Case([_sq33], lambda x: np.triu(np.tril(x, 1),
                                                       -1),
                            attrs={"num_lower": 1, "num_upper": 1})],
    "Cholesky": [Case([_psd(4, 40)], np.linalg.cholesky, tol=1e-3,
                      grad=True, grad_tol=5e-2)],
    "MatrixDeterminant": [Case([_psd(3, 41)], np.linalg.det,
                               tol=1e-2, grad=True, grad_tol=5e-2)],
    "LogMatrixDeterminant": [Case(
        [_psd(3, 42)],
        lambda x: (np.float32(np.linalg.slogdet(x)[0]),
                   np.float32(np.linalg.slogdet(x)[1])), tol=1e-3)],
    "MatrixInverse": [Case([_psd(3, 43)], np.linalg.inv, tol=1e-3,
                           grad=True, grad_tol=5e-2)],
    "MatrixSolve": [Case([_psd(3, 44),
                          _rng(45).randn(3, 2).astype(np.float32)],
                         np.linalg.solve, tol=1e-3, grad=True,
                         grad_tol=5e-2)],
    "MatrixExponential": [Case([_sq33 * 0.3], sp_linalg.expm,
                               tol=1e-3, grad=True, grad_tol=5e-2)],
    "SelfAdjointEigV2": [Case(
        [_psd(3, 46)],
        lambda x: (np.linalg.eigvalsh(x),),  # eigenvalues only: vectors
        attrs={"compute_v": False}, tol=1e-3)],
    # ---- FFT family ----
    "FFT": [Case([_cplx], np.fft.fft, tol=1e-3)],
    "IFFT": [Case([_cplx], np.fft.ifft, tol=1e-3)],
    "FFT2D": [Case([_cplx], np.fft.fft2, tol=1e-3)],
    "IFFT2D": [Case([_cplx], np.fft.ifft2, tol=1e-3)],
    "RFFT": [Case([_x34], np.fft.rfft, tol=1e-3)],
    "IRFFT": [Case([_cplx[:, :5]], lambda x: np.fft.irfft(x, 8),
                   tol=1e-3)],
    "RFFT2D": [Case([_x34], np.fft.rfft2, tol=1e-3)],
    # ---- complex parts ----
    "Complex": [Case([_x34, _x34 * 2],
                     lambda re, im: (re + 1j * im).astype(np.complex64))],
    "Real": [Case([_cplx], np.real)],
    "Imag": [Case([_cplx], np.imag)],
    "Conj": [Case([_cplx], np.conj)],
    "Angle": [Case([_cplx], np.angle, tol=1e-4)],
    "ConjugateTranspose": [Case([_cplx], lambda x: np.conj(x.T),
                                attrs={"perm": (1, 0)})],
    # ---- segment / argminmax / search ----
    "ArgMax": [Case([_x34], lambda x: x.argmax(0), attrs={"axis": 0}),
               Case([_x34], lambda x: x.argmax(1), attrs={"axis": 1})],
    "ArgMin": [Case([_x34], lambda x: x.argmin(1), attrs={"axis": 1})],
    "SegmentSum": [Case([_data6, _ids6],
                        _np_segment(np.add, 0.0),
                        attrs={"num_segments": 3}, grad=True)],
    "SegmentMean": [Case([_data6, _ids6], lambda d, i: np.stack(
        [d[i == s].mean(0) for s in range(3)]),
        attrs={"num_segments": 3})],
    "SegmentMax": [Case([_data6, _ids6], lambda d, i: np.stack(
        [d[i == s].max(0) for s in range(3)]),
        attrs={"num_segments": 3})],
    "SegmentMin": [Case([_data6, _ids6], lambda d, i: np.stack(
        [d[i == s].min(0) for s in range(3)]),
        attrs={"num_segments": 3})],
    "SegmentProd": [Case([_data6, _ids6],
                         _np_segment(np.multiply, 1.0),
                         attrs={"num_segments": 3})],
    "UnsortedSegmentSum": [Case(
        [_data6, np.array([2, 0, 1, 0, 2, 1], np.int32)],
        _np_segment(np.add, 0.0), attrs={"num_segments": 3},
        grad=True)],
    "UnsortedSegmentMax": [Case(
        [np.abs(_data6), np.array([1, 0, 1, 0, 1, 0], np.int32)],
        _np_segment(np.maximum, -np.inf), attrs={"num_segments": 2})],
    "UnsortedSegmentMin": [Case(
        [np.abs(_data6), np.array([1, 0, 1, 0, 1, 0], np.int32)],
        _np_segment(np.minimum, np.inf), attrs={"num_segments": 2})],
    "UnsortedSegmentProd": [Case(
        [_data6, np.array([1, 0, 1, 0, 1, 0], np.int32)],
        _np_segment(np.multiply, 1.0), attrs={"num_segments": 2})],
    "TopKV2": [Case([_x34], lambda x: (np.sort(x, 1)[:, ::-1][:, :2],
                                       np.argsort(-x, 1)[:, :2]),
                    attrs={"k": 2})],
    "InTopK": [Case([_x34, np.array([1, 0, 3], np.int32)],
                    lambda p, t: np.array(
                        [t[i] in np.argsort(-p[i])[:2]
                         for i in range(p.shape[0])]),
                    attrs={"k": 2})],
    "Bincount": [Case([np.array([1, 1, 3, 0], np.int32)],
                      lambda a: np.bincount(a, minlength=4)
                      .astype(np.int32), attrs={"size": 4})],
    "HistogramFixedWidth": [Case(
        [np.array([-1.0, 0.1, 0.5, 0.9, 2.0], np.float32),
         np.float32(0.0), np.float32(1.0)],
        lambda v, lo, hi: np.array([1, 1, 1, 2, 0], np.int32)
        if False else np.histogram(
            np.clip(v, 0.0, np.nextafter(np.float32(1.0),
                                         np.float32(0.0))),
            bins=5, range=(0.0, 1.0))[0].astype(np.int32),
        attrs={"nbins": 5})],
    "ConfusionMatrix": [Case(
        [np.array([0, 1, 2, 1], np.int32),
         np.array([0, 2, 2, 1], np.int32)],
        lambda l, p: np.array([[1, 0, 0], [0, 1, 1], [0, 0, 1]]),
        attrs={"num_classes": 3})],
    "Cumsum": [Case([_x34], lambda x: np.cumsum(x, 1),
                    attrs={"axis": 1}, grad=True),
               Case([_x34], lambda x: np.cumsum(x[:, ::-1], 1)[:, ::-1],
                    attrs={"axis": 1, "reverse": True}),
               Case([_x34], lambda x: np.concatenate(
                   [np.zeros((3, 1), np.float32),
                    np.cumsum(x, 1)[:, :-1]], 1),
                   attrs={"axis": 1, "exclusive": True})],
    "Cumprod": [Case([np.abs(_x34) + 0.5],
                     lambda x: np.cumprod(x, 0), attrs={"axis": 0},
                     grad=True)],
    # ---- nn ----
    "BiasAdd": [Case([_x234, np.array([1., 2., 3., 4.], np.float32)],
                     lambda x, b: x + b, grad=True)],
    "Softmax": [Case([_x34], lambda x: sp_special.softmax(x, 1),
                     tol=1e-4, grad=True)],
    "LogSoftmax": [Case([_x34],
                        lambda x: sp_special.log_softmax(x, 1),
                        tol=1e-4, grad=True)],
    "SigmoidCrossEntropyWithLogits": [Case(
        [_x34, (_rng(50).rand(3, 4) > 0.5).astype(np.float32)],
        lambda lo, la: np.maximum(lo, 0) - lo * la
        + np.log1p(np.exp(-np.abs(lo))), tol=1e-4, grad=True)],
    "Conv2D": [Case([_img, _kern], _np_conv2d_valid,
                    attrs={"strides": (1, 1, 1, 1), "padding": "VALID"},
                    tol=1e-3, grad=True)],
    "MaxPool": [Case([_img], lambda x: _np_maxpool_valid(x, 2),
                     attrs={"ksize": (1, 2, 2, 1),
                            "strides": (1, 2, 2, 1),
                            "padding": "VALID"}, grad=True)],
    "AvgPool": [Case([_img], lambda x: x.reshape(2, 3, 2, 3, 2, 3)
                     .mean(axis=(2, 4)),
                     attrs={"ksize": (1, 2, 2, 1),
                            "strides": (1, 2, 2, 1),
                            "padding": "VALID"}, tol=1e-4)],
    "SpaceToDepth": [Case([_img[:, :4, :4, :1]],
                          lambda x: x.reshape(2, 2, 2, 2, 2, 1)
                          .transpose(0, 1, 3, 2, 4, 5)
                          .reshape(2, 2, 2, 4),
                          attrs={"block_size": 2})],
    "DepthToSpace": [Case([_img[:, :2, :2, :].reshape(2, 2, 2, 3)[:, :, :, :2]
                           .reshape(2, 2, 2, 2).astype(np.float32)
                           if False else
                           np.arange(2 * 2 * 2 * 4, dtype=np.float32)
                           .reshape(2, 2, 2, 4)],
                          lambda x: x.reshape(2, 2, 2, 2, 2, 1)
                          .transpose(0, 1, 3, 2, 4, 5)
                          .reshape(2, 4, 4, 1),
                          attrs={"block_size": 2})],
})
COVERED_ELSEWHERE = {
    "AddN": ("test_runtime_cc.py", "add_n"),
    "AdjustBrightness": ("test_image_linalg_sparse.py", "adjust_brightness"),
    "AdjustContrast": ("test_image_linalg_sparse.py", "adjust_contrast"),
    "AllGather": ("test_parallel.py", "all_gather"),
    "AllReduce": ("test_parallel.py", "all_reduce"),
    "AsString": ("test_image_linalg_sparse.py", "as_string"),
    "Assert": ("test_api_parity.py", "assert"),
    "Assign": ("test_graph.py", "assign"),
    "AssignAdd": ("test_graph.py", "assign_add"),
    "AssignSub": ("test_variables.py", "assign_sub"),
    "AxisIndex": ("test_parallel.py", "axis_index"),
    "BarrierClose": ("test_data_flow_structures.py", "BarrierClose"),
    "CentralCrop": ("test_image_linalg_sparse.py", "central_crop"),
    "CholeskySolve": ("test_image_linalg_sparse.py", "cholesky_solve"),
    "ComputeAccidentalHits": ("test_image_linalg_sparse.py", "compute_accidental_hits"),
    "Cond": ("test_control_flow.py", "cond"),
    "Const": ("test_array_ops.py", "const"),
    "Conv3D": ("test_nn_ops.py", "Conv3D"),
    "CropAndResize": ("test_parity_fills.py", "crop_and_resize"),
    "CropToBoundingBox": ("test_image_linalg_sparse.py", "crop_to_bounding_box"),
    "DecodeImage": ("test_image_linalg_sparse.py", "decode_image"),
    "DecodeJpeg": ("test_image_linalg_sparse.py", "decode_jpeg"),
    "DecodePng": ("test_image_linalg_sparse.py", "decode_png"),
    "DeleteSessionTensor": ("test_session_handles.py", "delete_session_tensor"),
    "Dequantize": ("test_quantization_ops.py", "dequantize"),
    "Dropout": ("test_byte_budget.py", "dropout"),
    "EditDistance": ("test_array_ops.py", "edit_distance"),
    "EncodeJpeg": ("test_image_linalg_sparse.py", "encode_jpeg"),
    "EncodePng": ("test_image_linalg_sparse.py", "encode_png"),
    "FakeQuantWithMinMaxArgs": ("test_quantization_ops.py", "fake_quant_with_min_max_args"),
    "FakeQuantWithMinMaxVars": ("test_quantization_ops.py", "fake_quant_with_min_max_vars"),
    "FakeQuantWithMinMaxVarsPerChannel": ("test_quantization_ops.py", "fake_quant_with_min_max_vars_per_channel"),
    "FlashAttention": ("test_models.py", "flash_attention"),
    "FlashAttentionDropout": ("test_models.py", "FlashAttentionDropout"),
    "FlipLeftRight": ("test_image_linalg_sparse.py", "flip_left_right"),
    "FlipUpDown": ("test_image_linalg_sparse.py", "flip_up_down"),
    "Foldl": ("test_control_flow.py", "foldl"),
    "FusedBatchNorm": ("test_cost_model.py", "FusedBatchNorm"),
    "FusedAdamUpdate": ("test_kernel_registry.py", "FusedAdamUpdate"),
    "FusedDropoutBiasResidual": ("test_kernel_registry.py",
                                 "FusedDropoutBiasResidual"),
    "FusedLayerNorm": ("test_pallas_kernels.py", "FusedLayerNorm"),
    "FusedMomentumUpdate": ("test_kernel_registry.py",
                            "FusedMomentumUpdate"),
    "FusedSoftmaxXent": ("test_pallas_kernels.py", "FusedSoftmaxXent"),
    "GetSessionHandle": ("test_session_handles.py", "get_session_handle"),
    "GetSessionTensor": ("test_session_handles.py", "get_session_tensor"),
    "Group": ("test_api_parity.py", "group"),
    "HistogramSummary": ("test_summary.py", "histogram_summary"),
    "IsVariableInitialized": ("test_variables.py", "is_variable_initialized"),
    "IteratorGetNext": ("test_data.py", "iterator_get_next"),
    "LookupTableFind": ("test_lookup_ops.py", "LookupTableFind"),
    "LookupTableFindDevice": ("test_lookup_ops.py", "LookupTableFindDevice"),
    "MapFn": ("test_control_flow.py", "map_fn"),
    "MatchingFiles": ("test_io_ops.py", "matching_files"),
    "MatrixSolveLs": ("test_parity_fills.py", "matrix_solve_ls"),
    "MatrixTriangularSolve": ("test_image_linalg_sparse.py", "matrix_triangular_solve"),
    "MaxPoolWithArgmax": ("test_parity_fills.py", "max_pool_with_argmax"),
    "Multinomial": ("test_image_linalg_sparse.py", "multinomial"),
    "NoOp": ("test_runtime_cc.py", "NoOp"),
    "NonMaxSuppression": ("test_parity_fills.py", "non_max_suppression"),
    "ParseExample": ("test_data.py", "parse_example"),
    "ParseTensor": ("test_array_ops.py", "parse_tensor"),
    "PerImageStandardization": ("test_image_linalg_sparse.py", "per_image_standardization"),
    "Pipeline": ("test_byte_budget.py", "pipeline"),
    "PipelineTrain": ("test_cost_model.py", "pipeline_train"),
    "Placeholder": ("test_array_ops.py", "placeholder"),
    "Print": ("test_cost_model.py", "print"),
    "PyFunc": ("test_control_flow.py", "py_func"),
    "Qr": ("test_image_linalg_sparse.py", "qr"),
    "QuantMatMul": ("test_pallas_kernels.py", "QuantMatMul"),
    "QuantizeV2": ("test_quantization_ops.py", "quantize_v2"),
    "RandomShuffle": ("test_image_linalg_sparse.py", "random_shuffle"),
    "RandomUniform": ("test_image_linalg_sparse.py", "random_uniform"),
    "ReadFile": ("test_io_ops.py", "read_file"),
    "ReadVariable": ("test_tools.py", "ReadVariable"),
    "ReaderRead": ("test_io_ops.py", "reader_read"),
    "ReaderReadUpTo": ("test_io_ops.py", "reader_read_up_to"),
    "RecomputeGradCall": ("test_framework_extras.py", "RecomputeGradCall"),
    "ReduceScatter": ("test_parallel.py", "reduce_scatter"),
    "ReportUninitialized": ("test_variables.py", "report_uninitialized"),
    "ResizeBilinear": ("test_image_linalg_sparse.py", "resize_bilinear"),
    "ResizeImages": ("test_image_linalg_sparse.py", "resize_images"),
    "ResizeNearestNeighbor": ("test_image_linalg_sparse.py", "resize_nearest_neighbor"),
    "RingAttention": ("test_ring_attention.py", "ring_attention"),
    "SampleDistortedBoundingBox": ("test_image_linalg_sparse.py", "sample_distorted_bounding_box"),
    "ScalarSummary": ("test_summary.py", "scalar_summary"),
    "Scan": ("test_control_flow.py", "scan"),
    "ScatterAdd": ("test_variables.py", "scatter_add"),
    "ScatterUpdate": ("test_variables.py", "scatter_update"),
    "SdcaFprint": ("test_sdca_ops.py", "sdca_fprint"),
    "SdcaOptimizer": ("test_sdca_ops.py", "sdca_optimizer"),
    "SdcaShrinkL1": ("test_sdca_ops.py", "sdca_shrink_l1"),
    "SerializeTensor": ("test_parity_fills.py", "serialize_tensor"),
    "ShardMap": ("test_models.py", "shard_map"),
    "SoftmaxCrossEntropyWithLogits": ("test_lookup_ops.py", "softmax_cross_entropy_with_logits"),
    "SparseSegmentSum": ("test_parity_fills.py", "sparse_segment_sum"),
    "SparseSoftmaxCrossEntropyWithLogits": ("test_lookup_ops.py", "sparse_softmax_cross_entropy_with_logits"),
    "Stage": ("test_cost_model.py", "stage"),
    "StringJoin": ("test_image_linalg_sparse.py", "string_join"),
    "StringLength": ("test_image_linalg_sparse.py", "string_length"),
    "StringUpper": ("test_image_linalg_sparse.py", "string_upper"),
    "Substr": ("test_dtype_hygiene.py", "substr"),
    "Svd": ("test_image_linalg_sparse.py", "svd"),
    "TruncatedNormal": ("test_image_linalg_sparse.py", "truncated_normal"),
    "VariableV2": ("test_tools.py", "VariableV2"),
    "While": ("test_control_flow.py", "while"),
    "WriteFile": ("test_io_ops.py", "write_file"),
}


# ---- second-wave cases for ops the auto-matcher couldn't place ----------

def _np_pool3d(x, k, red):
    n, d, h, w, c = x.shape
    out = np.zeros((n, d // k, h // k, w // k, c), x.dtype)
    for a in range(d // k):
        for b in range(h // k):
            for e in range(w // k):
                out[:, a, b, e, :] = red(
                    x[:, a * k:(a + 1) * k, b * k:(b + 1) * k,
                      e * k:(e + 1) * k, :], (1, 2, 3))
    return out


_vol = _rng(60).randn(1, 4, 4, 4, 2).astype(np.float32)
_x3344 = _rng(61).randn(2, 3, 3).astype(np.float32)


def _ctc_dense_oracle(logits, labels):
    """Brute-force CTC loss: enumerate all T-length paths, sum those
    collapsing to the label (blank=0)."""
    T, C = logits.shape
    probs = sp_special.softmax(logits, axis=-1)
    import itertools

    total = 0.0
    for path in itertools.product(range(C), repeat=T):
        collapsed = []
        prev = None
        for s in path:
            if s != prev and s != 0:
                collapsed.append(s)
            prev = s
        if collapsed == list(labels):
            p = 1.0
            for t, s in enumerate(path):
                p *= probs[t, s]
            total += p
    return np.float32(-np.log(total))


CASES.update({
    "AddN": [Case([_x34, _x34 * 2, _x34 * 3],
                  lambda a, b, c2: a + b + c2, grad=True)],
    "ReverseSequence": [Case(
        [_x34, np.array([2, 4, 1], np.int32)],
        lambda x, ln: np.stack([np.concatenate(
            [row[:n][::-1], row[n:]]) for row, n in zip(x, ln)]),
        attrs={"seq_axis": 1, "batch_axis": 0})],
    "SegmentSumStatic": [Case(
        [_data6, _ids6], _np_segment(np.add, 0.0),
        attrs={"n_segments": 3})],
    "MaxPool3D": [Case([_vol], lambda x: _np_pool3d(x, 2, np.max),
                       attrs={"ksize": (1, 2, 2, 2, 1),
                              "strides": (1, 2, 2, 2, 1),
                              "padding": "VALID"})],
    "AvgPool3D": [Case([_vol], lambda x: _np_pool3d(x, 2, np.mean),
                       attrs={"ksize": (1, 2, 2, 2, 1),
                              "strides": (1, 2, 2, 2, 1),
                              "padding": "VALID"}, tol=1e-4)],
    "MatrixSetDiag": [Case(
        [_x3344, np.array([[9., 8., 7.], [6., 5., 4.]], np.float32)],
        lambda x, d: np.stack([m - np.diag(np.diag(m)) + np.diag(dv)
                               for m, dv in zip(x, d)]))],
    "FFT3D": [Case([(_rng(62).randn(2, 4, 4) + 1j
                     * _rng(63).randn(2, 4, 4)).astype(np.complex64)],
                   lambda x: np.fft.fftn(x, axes=(-3, -2, -1)),
                   tol=1e-3)],
    "IFFT3D": [Case([(_rng(64).randn(2, 4, 4) + 1j
                      * _rng(65).randn(2, 4, 4)).astype(np.complex64)],
                    lambda x: np.fft.ifftn(x, axes=(-3, -2, -1)),
                    tol=1e-3)],
    "RFFT3D": [Case([_rng(66).randn(2, 4, 4).astype(np.float32)],
                    lambda x: np.fft.rfftn(x, axes=(-3, -2, -1)),
                    tol=1e-3)],
    "IRFFT2D": [Case([(_rng(67).randn(4, 5) + 1j
                       * _rng(68).randn(4, 5)).astype(np.complex64)],
                     lambda x: np.fft.irfft2(x, s=(4, 8)), tol=1e-3)],
    "IRFFT3D": [Case([(_rng(69).randn(2, 4, 3) + 1j
                       * _rng(70).randn(2, 4, 3)).astype(np.complex64)],
                     lambda x: np.fft.irfftn(x, s=(2, 4, 4),
                                             axes=(-3, -2, -1)),
                     tol=1e-3)],
    "CholeskySolve": [Case(
        [np.linalg.cholesky(_psd(3, 71)).astype(np.float32),
         _rng(72).randn(3, 2).astype(np.float32)],
        lambda l, rhs: np.linalg.solve(l @ l.T, rhs), tol=1e-3,
        grad=True, grad_tol=5e-2)],
    "ConvertImageDtype": [Case(
        [np.array([[0, 128, 255]], np.uint8)],
        lambda x: (x / 255.0).astype(np.float32),
        attrs={"dtype": stf.float32}, tol=1e-6)],
    "GrayscaleToRGB": [Case(
        [np.abs(_rng(73).randn(2, 3, 3, 1)).astype(np.float32)],
        lambda x: np.repeat(x, 3, axis=-1))],
    "RGBToGrayscale": [Case(
        [np.abs(_rng(74).randn(2, 3, 3, 3)).astype(np.float32)],
        lambda x: (x @ np.array([0.2989, 0.587, 0.114],
                                np.float32))[..., None], tol=1e-4)],
    "PadToBoundingBox": [Case(
        [np.ones((1, 2, 2, 1), np.float32)],
        lambda x: np.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0))),
        attrs={"offset_height": 1, "offset_width": 1,
               "target_height": 4, "target_width": 4})],
    "ExtractImagePatches": [Case(
        [np.arange(16, dtype=np.float32).reshape(1, 4, 4, 1)],
        lambda x: np.stack(
            [[np.concatenate([x[0, i:i + 2, j:j + 2, 0].ravel()])
              for j in range(3)] for i in range(3)])[None],
        attrs={"ksizes": (1, 2, 2, 1), "strides": (1, 1, 1, 1),
               "rates": (1, 1, 1, 1), "padding": "VALID"})],
    "CTCLossDense": [Case(
        # logits are TIME-major [T, B, C] (ctc_ops.py:28)
        [_rng(75).randn(3, 4).astype(np.float32)[:, None, :],
         np.array([[2, 1]], np.int32)],
        lambda lo, la: _ctc_dense_oracle(lo[:, 0, :], la[0])[None],
        tol=1e-4)],
    "CTCGreedyDecode": [Case(
        # returns the raw per-frame argmax path [T, B]; blank/repeat
        # collapse happens in the ctc_greedy_decoder wrapper
        [np.log(np.array(
            [[[.1, .8, .05, .05], [.1, .8, .05, .05],
              [.7, .1, .1, .1], [.05, .05, .8, .1]]], np.float32)
            .transpose(1, 0, 2)),
         np.array([4], np.int32)],
        lambda lo, sl: np.array([[1], [1], [0], [2]], np.int32),
        attrs={"merge_repeated": True})],
})


# ---- hand-assigned pointers (markers verified by the coverage test) -----

COVERED_ELSEWHERE.update({
    "HSVToRGB": ("test_image_linalg_sparse.py", "hsv_to_rgb"),
    "RGBToHSV": ("test_image_linalg_sparse.py", "rgb_to_hsv"),
    "ResizeBilinear": ("test_image_linalg_sparse.py", "resize_"),
    "ResizeImages": ("test_image_linalg_sparse.py", "resize_"),
    "ResizeNearestNeighbor": ("test_image_linalg_sparse.py", "resize_"),
    "Conv3D": ("test_nn_ops.py", "conv3d"),
    "Conv3DBackpropInput": ("test_nn_ops.py", "conv3d"),
    "DepthwiseConv2dNative": ("test_nn_ops.py", "depthwise"),
    "Dilation2D": ("test_nn_ops.py", "dilation2d"),
    "Erosion2D": ("test_nn_ops.py", "erosion2d"),
    "LRN": ("test_nn_ops.py", "lrn"),
    "FakeQuantWithMinMaxArgs": ("test_quantization_ops.py", "fake_quant"),
    "FakeQuantWithMinMaxVars": ("test_quantization_ops.py", "fake_quant"),
    "FakeQuantWithMinMaxVarsPerChannel": ("test_quantization_ops.py",
                                          "fake_quant"),
    "FakeQuantArgsGrad": ("test_quantization_ops.py", "fake_quant"),
    "FakeQuantPerChannelGrad": ("test_quantization_ops.py", "fake_quant"),
    "FakeQuantVarsGrad": ("test_quantization_ops.py", "fake_quant"),
    "QuantizeV2": ("test_quantization_ops.py", "quantize"),
    "ReaderNumRecordsProduced": ("test_io_ops.py", "reader_"),
    "ReaderNumWorkUnitsCompleted": ("test_io_ops.py", "reader_"),
    "ReaderReset": ("test_io_ops.py", "reader_"),
    "QueueClose": ("test_io_ops.py", "queue_"),
    "QueueDequeue": ("test_io_ops.py", "queue_"),
    "QueueDequeueMany": ("test_io_ops.py", "queue_"),
    "QueueEnqueue": ("test_io_ops.py", "queue_"),
    "QueueEnqueueMany": ("test_io_ops.py", "queue_"),
    "QueueEnqueueMaybe": ("test_io_ops.py", "queue_"),
    "QueueSize": ("test_io_ops.py", "queue_"),
    "ScatterDiv": ("test_variables.py", "scatter_"),
    "ScatterMax": ("test_variables.py", "scatter_"),
    "ScatterMin": ("test_variables.py", "scatter_"),
    "ScatterMul": ("test_variables.py", "scatter_"),
    "ScatterSub": ("test_variables.py", "scatter_"),
    "ScatterNdAdd": ("test_variables.py", "scatter_"),
    "ScatterNdSub": ("test_variables.py", "scatter_"),
    "ScatterNdUpdate": ("test_variables.py", "scatter_"),
    "TensorArrayRead": ("test_framework_extras.py", "tensor_array"),
    "TensorArrayScatter": ("test_framework_extras.py", "tensor_array"),
    "TensorArrayWrite": ("test_framework_extras.py", "tensor_array"),
    "AccumulatorApplyGradient": ("test_data_flow_structures.py",
                                 "TestConditionalAccumulator"),
    "AccumulatorNumAccumulated": ("test_data_flow_structures.py",
                                  "TestConditionalAccumulator"),
    "AccumulatorSetGlobalStep": ("test_data_flow_structures.py",
                                 "TestConditionalAccumulator"),
    "AccumulatorTakeGradient": ("test_data_flow_structures.py",
                                "TestConditionalAccumulator"),
    "SparseAccumulatorApplyGradient": ("test_data_flow_structures.py",
                                       "accumulator"),
    "SparseAccumulatorNumAccumulated": ("test_data_flow_structures.py",
                                        "accumulator"),
    "SparseAccumulatorSetGlobalStep": ("test_data_flow_structures.py",
                                       "accumulator"),
    "SparseAccumulatorTakeGradient": ("test_data_flow_structures.py",
                                      "accumulator"),
    "UlyssesAttention": ("test_ring_attention.py", "ulysses"),
    "SymbolicHessian": ("test_parity_fills.py", "hessian"),
    "SymbolicGradient": ("test_math_ops.py", "stf.gradients"),
    "MatrixSolveLs": ("test_parity_fills.py", "matrix_solve_ls"),
    "MatrixTriangularSolve": ("test_image_linalg_sparse.py",
                              "matrix_triangular"),
    "Qr": ("test_image_linalg_sparse.py", "qr_"),
    "Svd": ("test_image_linalg_sparse.py", "svd"),
    "Multinomial": ("test_image_linalg_sparse.py", "multinomial"),
    "RandomShuffle": ("test_image_linalg_sparse.py", "random_shuffle"),
    "RandomStandardNormal": ("test_image_linalg_sparse.py",
                             "random_normal"),
    "RandomUniform": ("test_image_linalg_sparse.py", "random_uniform"),
    "TruncatedNormal": ("test_image_linalg_sparse.py",
                        "truncated_normal"),
    "PerImageStandardization": ("test_image_linalg_sparse.py",
                                "per_image"),
    "SparseSegmentSum": ("test_parity_fills.py", "sparse_segment"),
    "SparseSegmentValueTransform": ("test_parity_fills.py",
                                    "sparse_segment"),
    "LookupTableExport": ("test_lookup_ops.py", "lookup_table"),
    "LookupTableInsert": ("test_lookup_ops.py", "lookup_table"),
    "LookupTableSize": ("test_lookup_ops.py", "lookup_table"),
    "InitializeTable": ("test_lookup_ops.py", "lookup_table"),
    "IteratorInit": ("test_data.py", "iterator"),
    "EditDistance": ("test_array_ops.py", "edit_distance"),
    "ReportUninitialized": ("test_variables.py", "report_uninitialized"),
    "DecodeCSV": ("test_parity_fills.py", "decode_csv"),
    "NonMaxSuppression": ("test_parity_fills.py", "non_max"),
    "ComputeAccidentalHits": ("test_image_linalg_sparse.py",
                              "compute_accidental"),
    "SampleDistortedBoundingBox": ("test_image_linalg_sparse.py",
                                   "sample_distorted"),
    "EncodePng": ("test_image_linalg_sparse.py", "encode_png"),
    "DecodePng": ("test_image_linalg_sparse.py", "decode_png"),
    "DecodeJpeg": ("test_image_linalg_sparse.py", "decode_jpeg"),
    "RecomputeGradCall": ("test_example_end_to_end.py", "recompute"),
    "Pipeline": ("test_parallel.py", "pipeline"),
    "PipelineTrain": ("test_parallel.py", "pipeline"),
    "ScalarSummary": ("test_summary.py", "scalar_summary"),
    "MergeSummary": ("test_summary.py", "merge_all"),
    "ImageSummary": ("test_summary.py", "summary.image"),
    "MaxPoolWithArgmax": ("test_parity_fills.py", "with_argmax"),
    "PoolV2": ("test_nn_ops.py", "pool"),
    "StringLength": ("test_image_linalg_sparse.py", "string_length"),
    "StringJoin": ("test_image_linalg_sparse.py", "string_join"),
    "AsString": ("test_image_linalg_sparse.py", "as_string"),
})

COVERED_ELSEWHERE.update({
    # generative decode substrate (ISSUE 12): cache-op conformance
    # (alloc reset, multi-position append, gather layout, effects
    # ordering) and decode-attention parity both live in
    # tests/test_generative.py
    "KVCacheAlloc": ("test_generative.py", "KVCache"),
    "KVCacheAppend": ("test_generative.py", "KVCache"),
    "KVCacheGather": ("test_generative.py", "KVCache"),
    "KVCachePageCopy": ("test_decode2.py", "copy_pages"),
    # sparse-attention routed-FFN serving ops (ISSUE 27): each against
    # its jax-level definition, a per-expert loop, the per-position
    # selection and the benchmark's plain reference
    "KVCacheGatherRows": ("test_sparse_moe_lm.py", "gather_rows"),
    "RMSNorm": ("test_sparse_moe_lm.py", "test_prefill_then_decode_logits"),
    "RotaryEmbedding": ("test_sparse_moe_lm.py",
                        "test_prefill_then_decode_logits"),
    "IndexerTopK": ("test_sparse_moe_lm.py", "indexer_topk"),
    "SelectedAttention": ("test_sparse_moe_lm.py", "selected_attention"),
    "SparseBlockAttention": ("test_sparse_moe_lm.py",
                             "sparse_block_attention"),
    "RoutedFFN": ("test_sparse_moe_lm.py", "routed_ffn"),
    "DecodeAttention": ("test_generative.py", "decode_attention"),
    "PagedDecodeAttention": ("test_paged_decode_attention.py",
                             "test_op_equals_gather_then_decode_attention"),
    "PagedLatentAttention": ("test_latent_moe_lm.py",
                             "test_prefill_then_decode_logits"),
    "StatePoolAlloc": ("test_state_space_moe_lm.py", "test_two_kinds_of_pool"),
    "CausalConv1D": ("test_state_space_moe_lm.py",
                     "test_conv_chunk_equals_one_step"),
    "SSMChunkScan": ("test_state_space_moe_lm.py",
                     "test_chunk_scan_at_chunk_128"),
    "SSMStateUpdate": ("test_state_space_moe_lm.py",
                       "test_state_update_kernel_equals_composition"),
    "GatedRMSNorm": ("test_state_space_moe_lm.py",
                     "test_gated_group_norm_gate_first"),
    # GLM-5.3-Flash's block (ISSUE 39): each against the token recurrence,
    # a brute-force choice or the benchmark's plain reference
    "KDAChunkScan": ("test_hybrid_linear_moe_lm.py",
                     "test_chunk_scan_at_chunk_64"),
    "KDAStateUpdate": ("test_hybrid_linear_moe_lm.py",
                       "test_decode_kernel_equals_composition"),
    "PooledBlockChoice": ("test_hybrid_linear_moe_lm.py",
                          "test_prefill_choice_equals_the_decode_choice"),
    "SparseLatentBlockAttention": ("test_hybrid_linear_moe_lm.py",
                                   "test_prefill_then_decode_logits"),
    "SelectedLatentAttention": ("test_hybrid_linear_moe_lm.py",
                                "test_selected_rows_take_the_paged_latent"),
    "HyperConnectionPre": ("test_hybrid_linear_moe_lm.py",
                           "test_streams_through_a_sublayer"),
    "HyperConnectionPost": ("test_hybrid_linear_moe_lm.py",
                            "test_streams_through_a_sublayer"),
    # LFM2's block: the decode update against the composition
    # and the chunk convolution
    "ShortConvUpdate": ("test_short_conv_moe_lm.py",
                        "test_update_kernel_equals_composition"),
    "BarrierIncompleteSize": ("test_data_flow_structures.py", "Barrier"),
    "BarrierInsertMany": ("test_data_flow_structures.py", "Barrier"),
    "BarrierReadySize": ("test_data_flow_structures.py", "Barrier"),
    "BarrierTakeMany": ("test_data_flow_structures.py", "Barrier"),
    "StagingSize": ("test_data_flow_structures.py", "StagingArea"),
    "Unstage": ("test_data_flow_structures.py", "StagingArea"),
    "RecordInputYield": ("test_data_flow_structures.py", "RecordInput"),
    "FuncArg": ("test_framework_extras.py", "Defun"),
    "GraphFunctionCall": ("test_framework_extras.py", "Defun"),
    "CapturedInput": ("test_framework_extras.py", "Defun"),
    "DecodeGif": ("test_image_linalg_sparse.py", "decode_image"),
    "BatchToSpaceND": ("test_array_ops.py", "batch_to_space"),
    "SpaceToBatchND": ("test_array_ops.py", "space_to_batch"),
    "CTCBeamSearch": ("test_parity_fills.py", "ctc"),
    "CollectivePermute": ("test_parallel.py", "ppermute"),
})

COVERED_ELSEWHERE.update({
    # numerics-health plane (ISSUE 17): packed-stat semantics (nonfinite
    # count, finite max_abs, l2, zero fraction) and the device-side
    # histogram bucketization (fused-window no-split + event round trip)
    # live in tests/test_numerics_health.py
    "NumericSummary": ("test_numerics_health.py", "NumericSummary"),
    "HistogramBucketCounts": ("test_numerics_health.py", "histogram"),
})

COVERED_ELSEWHERE.update({
    # fused sharded-embedding path (ISSUE 19): forward exactness vs the
    # dense-gather reference and the scatter-add backward through
    # stf.gradients (single-device AND real ep=8 mesh) live in
    # tests/test_embedding_fused.py; LookupTableSizeDevice is the
    # frozen-table size() fast path driven by every table.size() call
    # in tests/test_lookup_ops.py
    "EmbeddingLookupFused": ("test_embedding_fused.py",
                             "embedding_lookup_fused"),
    "EmbeddingScatterAddGrad": ("test_embedding_fused.py",
                                "stf.gradients"),
    "LookupTableSizeDevice": ("test_lookup_ops.py", "table.size()"),
})


# ---------------------------------------------------------------------------
# MISC: direct mini-tests for everything the table and pointers don't
# reach — each runs the op for real (Session or pure fn) with a
# non-vacuous assertion.
# ---------------------------------------------------------------------------

def _sess_run(build, feed=None):
    stf.reset_default_graph()
    out = build()
    sess = stf.Session()
    return sess.run(out, feed_dict=feed or {})


def _misc_adjust_hue():
    import colorsys

    from simple_tensorflow_tpu.framework import op_registry as reg

    img = np.abs(_rng(80).rand(1, 2, 2, 3)).astype(np.float32)
    for op, delta in (("AdjustHue", 0.2), ("AdjustHueDyn",
                                           np.float32(0.2))):
        if op == "AdjustHue":
            got = np.asarray(reg.get(op).pure_fn(img, delta=0.2))
        else:
            got = np.asarray(reg.get(op).pure_fn(img, np.float32(0.2)))
        exp = np.zeros_like(img)
        for i in range(2):
            for j in range(2):
                h, s, v = colorsys.rgb_to_hsv(*img[0, i, j])
                exp[0, i, j] = colorsys.hsv_to_rgb((h + 0.2) % 1.0, s, v)
        np.testing.assert_allclose(got, exp, atol=1e-3)


def _misc_adjust_saturation():
    import colorsys

    from simple_tensorflow_tpu.framework import op_registry as reg

    img = np.abs(_rng(81).rand(1, 2, 2, 3)).astype(np.float32)
    for op in ("AdjustSaturation", "AdjustSaturationDyn"):
        if op == "AdjustSaturation":
            got = np.asarray(reg.get(op).pure_fn(img, factor=0.5))
        else:
            got = np.asarray(reg.get(op).pure_fn(img, np.float32(0.5)))
        exp = np.zeros_like(img)
        for i in range(2):
            for j in range(2):
                h, s, v = colorsys.rgb_to_hsv(*img[0, i, j])
                exp[0, i, j] = colorsys.hsv_to_rgb(h, s * 0.5, v)
        np.testing.assert_allclose(got, exp, atol=1e-3)


def _misc_set_ops():
    from simple_tensorflow_tpu.framework import op_registry as reg

    a = np.array([[1, 2, 2, 3]], np.int32)
    b = np.array([[2, 3, 5, 0]], np.int32)
    inter = reg.get("SetIntersection").pure_fn(a, b)
    union = reg.get("SetUnion").pure_fn(a, b)
    diff = reg.get("SetDifference").pure_fn(a, b)
    size = reg.get("SetSize").pure_fn(a)

    def dense_row(res):
        arr = np.asarray(res[0] if isinstance(res, (list, tuple))
                         else res).ravel()
        return sorted(int(v) for v in arr if v >= 0)

    assert dense_row(inter) == [2, 3], inter
    assert set(dense_row(union)) == {0, 1, 2, 3, 5}, union
    assert dense_row(diff) == [1], diff
    assert int(np.asarray(size).ravel()[0]) == 3, size


def _misc_conv2d_backprop_input():
    from simple_tensorflow_tpu.framework import op_registry as reg

    # dgrad == numerical d(sum(conv))/dx against the Conv2D oracle
    x = _rng(82).randn(1, 4, 4, 1).astype(np.float32)
    w = _rng(83).randn(2, 2, 1, 1).astype(np.float32)
    dy = np.ones((1, 3, 3, 1), np.float32)
    got = np.asarray(reg.get("Conv2DBackpropInput").pure_fn(
        dy, w, output_shape=(1, 4, 4, 1), strides=(1, 1, 1, 1),
        padding="VALID"))
    eps = 1e-2
    num = np.zeros_like(x)
    for i in range(4):
        for j in range(4):
            xp = x.copy()
            xp[0, i, j, 0] += eps
            xm = x.copy()
            xm[0, i, j, 0] -= eps
            num[0, i, j, 0] = (_np_conv2d_valid(xp, w).sum()
                               - _np_conv2d_valid(xm, w).sum()) / (2 * eps)
    np.testing.assert_allclose(got, num, atol=1e-2)


def _misc_cholesky_grad():
    from simple_tensorflow_tpu.framework import op_registry as reg

    a = _psd(3, 84)
    l = np.linalg.cholesky(a).astype(np.float32)
    gbar = np.tril(_rng(85).randn(3, 3)).astype(np.float32)
    got = np.asarray(reg.get("CholeskyGrad").pure_fn(l, gbar))
    # numeric: d sum(tril(chol(A)) * gbar) / dA (symmetric perturbation)
    eps = 1e-3
    num = np.zeros((3, 3), np.float64)
    for i in range(3):
        for j in range(3):
            ap = a.astype(np.float64).copy()
            ap[i, j] += eps / 2
            ap[j, i] += eps / 2
            am = a.astype(np.float64).copy()
            am[i, j] -= eps / 2
            am[j, i] -= eps / 2
            fp = (np.linalg.cholesky(ap) * gbar).sum()
            fm = (np.linalg.cholesky(am) * gbar).sum()
            num[i, j] = (fp - fm) / eps
    # impl returns the symmetrized gradient G (TF convention); the
    # symmetric central difference above measures dF under
    # dS = eps*(E_ij+E_ji), i.e. 2*G everywhere
    np.testing.assert_allclose(2.0 * got, num, atol=5e-2)


def _misc_embedding_lookup_mixed():
    from simple_tensorflow_tpu.framework import op_registry as reg

    table = _rng(86).randn(10, 4).astype(np.float32)
    ids = np.array([3, 0, 7], np.int32)
    got = np.asarray(reg.get("EmbeddingLookupMixed").pure_fn(
        table, ids, stf.bfloat16))
    assert got.dtype == np.dtype("bfloat16") or str(got.dtype) == "bfloat16"
    np.testing.assert_allclose(got.astype(np.float32),
                               table[ids].astype("bfloat16")
                               .astype(np.float32))


def _misc_extract_glimpse():
    from simple_tensorflow_tpu.framework import op_registry as reg

    img = np.arange(36, dtype=np.float32).reshape(1, 6, 6, 1)
    got = np.asarray(reg.get("ExtractGlimpse").pure_fn(
        img, np.zeros((1, 2), np.float32), size=(2, 2), centered=True,
        normalized=True))
    np.testing.assert_allclose(got[0, :, :, 0], img[0, 2:4, 2:4, 0])


def _misc_draw_bounding_boxes():
    from simple_tensorflow_tpu.framework import op_registry as reg

    img = np.zeros((1, 6, 6, 3), np.float32)
    boxes = np.array([[[0.0, 0.0, 0.5, 0.5]]], np.float32)
    got = np.asarray(reg.get("DrawBoundingBoxes").pure_fn(img, boxes))
    assert got.shape == img.shape
    assert got.max() > 0, "box was not drawn"
    assert got[0, 5, 5].max() == 0, "pixel outside the box changed"


def _misc_placeholder_with_default():
    v = _sess_run(lambda: stf.placeholder_with_default(
        np.float32(7.0), shape=[], name="pwd"))
    assert float(v) == 7.0
    stf.reset_default_graph()
    p = stf.placeholder_with_default(np.float32(7.0), shape=[],
                                     name="pwd2")
    out = stf.Session().run(p, {p: np.float32(3.0)})
    assert float(out) == 3.0


def _misc_check_numerics():
    stf.reset_default_graph()
    x = stf.placeholder(stf.float32, [2], name="cn_x")
    y = stf.check_numerics(x, "bad value")
    sess = stf.Session()
    np.testing.assert_allclose(
        sess.run(y, {x: np.array([1.0, 2.0], np.float32)}), [1.0, 2.0])
    with pytest.raises(Exception, match="bad value|NaN|Inf"):
        sess.run(y, {x: np.array([1.0, np.nan], np.float32)})


def _misc_count_up_to():
    stf.reset_default_graph()
    v = stf.Variable(np.int32(0), name="cut_v")
    c = stf.count_up_to(v, 2)
    sess = stf.Session()
    sess.run(stf.global_variables_initializer())
    assert int(sess.run(c)) == 0
    assert int(sess.run(c)) == 1
    from simple_tensorflow_tpu.framework import errors

    with pytest.raises(errors.OutOfRangeError):
        sess.run(c)


def _misc_strings():
    from simple_tensorflow_tpu.ops import string_ops

    stf.reset_default_graph()
    s = stf.constant(np.array([" Ab c ", "XYZ"], object))
    low = string_ops.string_lower(s)
    stripped = string_ops.string_strip(s)
    num = string_ops.string_to_number(
        stf.constant(np.array(["1.5", "-2"], object)))
    h1 = string_ops.string_to_hash_bucket_fast(s, 17)
    h2 = string_ops.string_to_hash_bucket_strong(s, 17, key=[1, 2])
    reg = string_ops.regex_replace(s, "[A-Z]", "#")
    sess = stf.Session()
    lo, st, nu, hv1, hv2, rg = sess.run([low, stripped, num, h1, h2, reg])
    assert list(lo) == [" ab c ", "xyz"]
    assert list(st) == ["Ab c", "XYZ"]
    np.testing.assert_allclose(nu, [1.5, -2.0])
    assert all(0 <= int(v) < 17 for v in np.ravel(hv1))
    assert all(0 <= int(v) < 17 for v in np.ravel(hv2))
    assert list(rg) == [" #b c ", "###"]


def _misc_base64_json():
    from simple_tensorflow_tpu.ops import string_ops

    stf.reset_default_graph()
    raw = stf.constant(np.array(["hello world"], object))
    enc = string_ops.encode_base64(raw)
    dec = string_ops.decode_base64(enc)
    sess = stf.Session()
    e, d = sess.run([enc, dec])
    import base64 as b64

    assert list(d) in ([b"hello world"], ["hello world"])
    e0 = e[0].encode() if isinstance(e[0], str) else e[0]
    assert b64.urlsafe_b64decode(e0 + b"=" * (-len(e0) % 4)) \
        == b"hello world"
    # DecodeJSONExample: json -> serialized Example bytes
    stf.reset_default_graph()
    from simple_tensorflow_tpu.ops import parsing_ops

    js = stf.constant(np.array(
        ['{"features": {"feature": {"v": {"floatList": '
         '{"value": [1.0]}}}}}'], object))
    ex = parsing_ops.decode_json_example(js)
    out = stf.Session().run(ex)
    assert isinstance(out[0], bytes) and len(out[0]) > 0


def _misc_random_ops():
    from simple_tensorflow_tpu.framework import op_registry as reg

    stf.reset_default_graph()
    g_ = stf.random_gamma([2000], alpha=3.0, seed=1)
    p_ = stf.random_poisson(4.0, [2000], seed=2)
    sess = stf.Session()
    gv, pv = sess.run([g_, p_])
    assert abs(float(np.mean(gv)) - 3.0) < 0.3, np.mean(gv)
    assert abs(float(np.mean(pv)) - 4.0) < 0.3, np.mean(pv)
    _ = reg  # registry import kept for symmetry


def _misc_random_flip():
    stf.reset_default_graph()
    img = np.arange(12, dtype=np.float32).reshape(1, 3, 4, 1)
    f = stf.image.random_flip_left_right(stf.constant(img), seed=3)
    out = np.asarray(stf.Session().run(f))
    ok_same = np.allclose(out, img)
    ok_flip = np.allclose(out, img[:, :, ::-1, :])
    assert ok_same or ok_flip


def _misc_candidate_samplers():
    stf.reset_default_graph()
    from simple_tensorflow_tpu.ops import candidate_sampling_ops as cso

    true_cls = stf.constant(np.array([[1], [5]], np.int64))
    s1, e1, e2 = cso.uniform_candidate_sampler(
        true_cls, num_true=1, num_sampled=8, unique=True, range_max=20,
        seed=4)
    s2, _, _ = cso.log_uniform_candidate_sampler(
        true_cls, num_true=1, num_sampled=8, unique=True, range_max=20,
        seed=5)
    sess = stf.Session()
    v1, v2 = sess.run([s1, s2])
    for v in (v1, v2):
        v = np.asarray(v)
        assert v.shape == (8,)
        assert ((0 <= v) & (v < 20)).all()
        assert len(set(int(x) for x in v)) == 8  # unique=True


def _misc_summaries():
    stf.reset_default_graph()
    t = stf.summary.text("note", stf.constant("hello"))
    a = stf.summary.audio(
        "tone", stf.constant(np.zeros((1, 100, 1), np.float32)),
        sample_rate=8000)
    sess = stf.Session()
    tv, av = sess.run([t, a])
    assert isinstance(np.asarray(tv).item(), bytes)
    assert isinstance(np.asarray(av).item(), bytes)


def _misc_sharding_constraint():
    import jax

    from simple_tensorflow_tpu import parallel

    stf.reset_default_graph()
    devices = jax.devices("cpu")[:8]
    mesh = parallel.Mesh({"dp": 8}, devices=devices)
    with mesh:
        x = stf.constant(_rng(90).randn(8, 4).astype(np.float32))
        y = parallel.with_sharding_constraint(x * 2.0, "dp", None)
        out = stf.Session().run(y)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(stf.Session()._variable_store
                                          and 2.0) * 0 +
                               2.0 * np.asarray(_rng(90)
                                                .randn(8, 4)
                                                .astype(np.float32)),
                               rtol=1e-6)


def _misc_collectives():
    """AllToAll inside a shard_map body: head-scatter/seq-gather
    transpose across the axis (the Ulysses building block)."""
    import jax

    from simple_tensorflow_tpu import parallel

    stf.reset_default_graph()
    devices = jax.devices("cpu")[:4]
    mesh = parallel.Mesh({"sp": 4}, devices=devices)
    with mesh:
        x = stf.constant(np.arange(16, dtype=np.float32).reshape(4, 4))

        def body(xs):
            # per-device shard (1, 4): all_to_all splits dim 1 over sp
            # and concatenates shards along dim 0 -> global transpose
            return parallel.all_to_all(xs, "sp", split_axis=1,
                                       concat_axis=0)

        out = parallel.shard_map(body, [x], in_specs=[("sp", None)],
                                 out_specs=[("sp", None)])
        got = np.asarray(stf.Session().run(out))
    expected = np.arange(16, dtype=np.float32).reshape(4, 4).T \
        .reshape(16, 1)
    np.testing.assert_allclose(got, expected)


def _misc_dynamic_slice_crop():
    stf.reset_default_graph()
    img = stf.constant(np.arange(36, dtype=np.float32)
                       .reshape(6, 6, 1))
    crop = stf.random_crop(img, [2, 2, 1], seed=7)
    out = np.asarray(stf.Session().run(crop))
    assert out.shape == (2, 2, 1)
    # every cropped window of the source contains consecutive values
    base = np.arange(36, dtype=np.float32).reshape(6, 6)
    found = any(np.allclose(out[:, :, 0], base[i:i + 2, j:j + 2])
                for i in range(5) for j in range(5))
    assert found


MISC_TESTS: Dict[str, Callable[[], None]] = {
    "AdjustHue": _misc_adjust_hue,
    "AdjustHueDyn": _misc_adjust_hue,
    "AdjustSaturation": _misc_adjust_saturation,
    "AdjustSaturationDyn": _misc_adjust_saturation,
    "SetIntersection": _misc_set_ops,
    "SetUnion": _misc_set_ops,
    "SetDifference": _misc_set_ops,
    "SetSize": _misc_set_ops,
    "Conv2DBackpropInput": _misc_conv2d_backprop_input,
    "CholeskyGrad": _misc_cholesky_grad,
    "EmbeddingLookupMixed": _misc_embedding_lookup_mixed,
    "ExtractGlimpse": _misc_extract_glimpse,
    "DrawBoundingBoxes": _misc_draw_bounding_boxes,
    "PlaceholderWithDefault": _misc_placeholder_with_default,
    "CheckNumerics": _misc_check_numerics,
    "CountUpTo": _misc_count_up_to,
    "StringLower": _misc_strings,
    "StringStrip": _misc_strings,
    "StringToHashBucketFast": _misc_strings,
    "StringToHashBucketStrong": _misc_strings,
    "StringToNumber": _misc_strings,
    "RegexReplace": _misc_strings,
    "EncodeBase64": _misc_base64_json,
    "DecodeBase64": _misc_base64_json,
    "DecodeJSONExample": _misc_base64_json,
    "RandomGamma": _misc_random_ops,
    "RandomPoisson": _misc_random_ops,
    "RandomFlip": _misc_random_flip,
    "UniformCandidateSampler": _misc_candidate_samplers,
    "LogUniformCandidateSampler": _misc_candidate_samplers,
    "TextSummary": _misc_summaries,
    "AudioSummary": _misc_summaries,
    "ShardingConstraint": _misc_sharding_constraint,
    "AllToAll": _misc_collectives,
    "DynamicSliceCrop": _misc_dynamic_slice_crop,
}


# ---- round-5 upgrade: independent oracles for image ops that were
# previously pointer-covered only ------------------------------------------

import colorsys  # noqa: E402  (image-op oracles)


def _colorsys_map(img, fn):
    out = np.zeros_like(img)
    flat_in = img.reshape(-1, 3)
    flat_out = out.reshape(-1, 3)
    for i in range(flat_in.shape[0]):
        flat_out[i] = fn(*flat_in[i])
    return out


_img443 = _rng(95).rand(2, 4, 4, 3).astype(np.float32)

CASES.update({
    "AdjustBrightness": [Case([_img443],
                              lambda x: x + np.float32(0.3),
                              attrs={"delta": 0.3}, grad=True)],
    "AdjustContrast": [Case(
        [_img443],
        lambda x: (x - x.mean(axis=(1, 2), keepdims=True)) * 1.7
        + x.mean(axis=(1, 2), keepdims=True),
        attrs={"contrast_factor": 1.7}, tol=1e-4, grad=True)],
    "FlipLeftRight": [Case([_img443], lambda x: x[:, :, ::-1, :],
                           grad=True)],
    "FlipUpDown": [Case([_img443], lambda x: x[:, ::-1, :, :],
                        grad=True)],
    "CentralCrop": [Case([np.arange(2 * 8 * 8 * 1, dtype=np.float32)
                          .reshape(2, 8, 8, 1) / 100.0],
                         lambda x: x[:, 2:6, 2:6, :],
                         attrs={"fraction": 0.5}, grad=True)],
    "CropToBoundingBox": [Case(
        # scaled down: f32 central differences at |x|~70 lose the 2%
        # gradient tolerance to rounding
        [np.arange(2 * 6 * 6 * 1, dtype=np.float32).reshape(2, 6, 6, 1)
         / 100.0],
        lambda x: x[:, 1:4, 2:6, :],
        attrs={"offset_height": 1, "offset_width": 2,
               "target_height": 3, "target_width": 4}, grad=True)],
    "ResizeNearestNeighbor": [Case(
        [np.arange(1 * 2 * 2 * 1, dtype=np.float32).reshape(1, 2, 2, 1)],
        lambda x: x.repeat(2, axis=1).repeat(2, axis=2),
        attrs={"size": (4, 4)})],
    "PerImageStandardization": [Case(
        [_img443],
        lambda x: (x - x.mean(axis=(1, 2, 3), keepdims=True))
        / np.maximum(x.std(axis=(1, 2, 3), keepdims=True),
                     1.0 / np.sqrt(np.float32(x[0].size))),
        tol=1e-4, grad=True, grad_tol=5e-2)],
    "RGBToHSV": [Case(
        [_img443], lambda x: _colorsys_map(x, colorsys.rgb_to_hsv),
        tol=1e-4)],
    "HSVToRGB": [Case(
        # rand() is already in [0, 1); cap H below 1.0 (wrap point)
        [np.stack([np.minimum(_img443[..., 0], 0.99),
                   _img443[..., 1], _img443[..., 2]], axis=-1)],
        lambda x: _colorsys_map(x, colorsys.hsv_to_rgb), tol=1e-4)],
})
# these were pointer-covered; the direct oracle supersedes the pointer
for _op in ("AdjustBrightness", "AdjustContrast", "FlipLeftRight",
            "FlipUpDown", "CentralCrop", "CropToBoundingBox",
            "ResizeNearestNeighbor", "PerImageStandardization",
            "RGBToHSV", "HSVToRGB"):
    COVERED_ELSEWHERE.pop(_op, None)


# ---------------------------------------------------------------------------
# generated tests + the enumeration guard
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("op_name", sorted(CASES))
def test_op_cases(op_name):
    cases = CASES[op_name]
    if not cases:
        pytest.skip(f"{op_name}: covered via public-API slicing tests")
    for i, case in enumerate(cases):
        try:
            run_case(op_name, case)
        except AssertionError as e:
            raise AssertionError(f"{op_name} case {i}: {e}") from e


@pytest.mark.parametrize("op_name", sorted(MISC_TESTS))
def test_op_misc(op_name):
    MISC_TESTS[op_name]()


def test_registry_fully_covered():
    """The enumeration guard: every registered op has coverage. A new op
    without a CASES entry, a MISC test, or a VERIFIED pointer to an
    existing test fails here (the criterion:
    0 registered ops untested)."""
    all_ops = set(op_registry.registered_ops())
    # parametric families registered lazily on first use (one concrete
    # name per dtype/flag combo): covered as a family, pointer-verified
    # like COVERED_ELSEWHERE below
    lazy_families = {"DecodeRaw_": ("test_framework_extras.py",
                                    "decode_raw")}
    lazy = {o for o in all_ops
            if any(o.startswith(p) for p in lazy_families)}
    for fname, marker in lazy_families.values():
        with open(os.path.join(_HERE, fname)) as f:
            assert marker in f.read(), (
                f"lazy-family marker {marker!r} missing from {fname}")
    uncovered = sorted(all_ops - set(CASES) - set(COVERED_ELSEWHERE)
                       - set(MISC_TESTS) - lazy)
    assert not uncovered, (
        f"{len(uncovered)} registered ops have no conformance coverage: "
        f"{uncovered}")
    # pointers must be real: file exists and contains the marker
    for op, (fname, marker) in sorted(COVERED_ELSEWHERE.items()):
        path = os.path.join(_HERE, fname)
        assert os.path.exists(path), f"{op}: pointer file {fname} missing"
        with open(path) as f:
            text = f.read()
        assert marker in text, (
            f"{op}: marker {marker!r} not found in {fname} — the "
            "covering test moved; update the pointer")
    # and pointers must not shadow stale registry entries
    unknown = (set(CASES) | set(COVERED_ELSEWHERE)
               | set(MISC_TESTS)) - all_ops
    assert not unknown, f"coverage entries for unregistered ops: {unknown}"
