"""Registry-driven operator case table (model: the per-op tests of
tests/python/unittest/test_operator.py compressed into data).

Each entry maps a REGISTERED op name to one or more Cases. A Case drives
up to four checks in test_op_sweep.py:
  1. forward numpy cross-check (when ``ref`` is given),
  2. numeric-gradient check (autograd vs central differences) for
     differentiable ops with float inputs,
  3. dtype sweep (f32 result vs f16/bf16/f64 runs, loose tolerance),
  4. edge shapes (size-0 / 1-element) for elementwise-classed ops.

COVERED_ELSEWHERE lists registry ops whose fwd+bwd behavior is exercised
by a dedicated test file instead (kept exact: the coverage test greps the
file to prove the claim). test_op_coverage.py emits OP_COVERAGE.json.
"""
from __future__ import annotations

import numpy as np

__all__ = ["Case", "CASES", "COVERED_ELSEWHERE"]


class Case:
    def __init__(self, inputs, params=None, ref=None, grad=None,
                 rtol=1e-4, atol=1e-5, grad_rtol=2e-2, grad_atol=2e-3,
                 dtype_sweep=False, edge=False, out_index=0,
                 grad_only=None):
        self.inputs = inputs          # tuple of np arrays
        self.params = params or {}
        self.ref = ref                # callable(*inputs, **params) or None
        self.grad = grad              # None = auto (differentiable + float)
        self.rtol, self.atol = rtol, atol
        self.grad_rtol, self.grad_atol = grad_rtol, grad_atol
        self.dtype_sweep = dtype_sweep
        self.edge = edge              # also run on size-0 / scalar-ish input
        self.out_index = out_index    # which output the ref describes
        # indices of inputs to differentiate (None = all); index-like
        # inputs (lengths, positions) have no meaningful finite-difference
        self.grad_only = grad_only


def U(lo, hi, shape=(3, 4), seed=0, dtype=np.float32):
    return np.random.RandomState(seed).uniform(lo, hi, shape).astype(dtype)


def N(shape=(3, 4), seed=0, scale=1.0, dtype=np.float32):
    return (np.random.RandomState(seed).randn(*shape) * scale).astype(dtype)


def I(hi, shape=(3, 4), seed=0, dtype=np.int32):
    return np.random.RandomState(seed).randint(0, hi, shape).astype(dtype)


CASES = {}


def case(name, *cs):
    CASES[name] = list(cs)


# --------------------------------------------------------------------------
# elemwise: unary math
# --------------------------------------------------------------------------
import scipy.special as _sp

_UNARY = [
    # (name, numpy ref, (lo, hi), kwargs)
    ("abs", np.abs, (-2, 2), {}),
    ("arccos", np.arccos, (-0.9, 0.9), {}),
    ("arccosh", np.arccosh, (1.1, 3), {}),
    ("arcsin", np.arcsin, (-0.9, 0.9), {}),
    ("arcsinh", np.arcsinh, (-2, 2), {}),
    ("arctan", np.arctan, (-2, 2), {}),
    ("arctanh", np.arctanh, (-0.9, 0.9), {}),
    ("cbrt", np.cbrt, (0.2, 3), {}),
    ("ceil", np.ceil, (-3, 3), {"grad": False}),
    ("cos", np.cos, (-3, 3), {}),
    ("cosh", np.cosh, (-2, 2), {}),
    ("degrees", np.degrees, (-3, 3), {}),
    ("erf", _sp.erf, (-2, 2), {}),
    ("erfinv", _sp.erfinv, (-0.9, 0.9), {"grad_rtol": 5e-2}),
    ("exp", np.exp, (-1, 1), {}),
    ("expm1", np.expm1, (-1, 1), {}),
    ("fix", np.fix, (-3, 3), {"grad": False}),
    ("floor", np.floor, (-3, 3), {"grad": False}),
    ("gamma", _sp.gamma, (0.5, 3), {"grad_atol": 5e-3}),
    ("gammaln", _sp.gammaln, (0.5, 3), {"grad_atol": 5e-3}),
    ("log", np.log, (0.1, 3), {}),
    ("log10", np.log10, (0.1, 3), {}),
    ("log1p", np.log1p, (-0.5, 3), {}),
    ("log2", np.log2, (0.1, 3), {}),
    ("logical_not", lambda x: (x == 0).astype(np.float32), (-1, 1),
     {"grad": False}),
    ("negative", np.negative, (-2, 2), {}),
    ("radians", np.radians, (-90, 90), {}),
    ("rcbrt", lambda x: 1 / np.cbrt(x), (0.3, 3), {}),
    ("reciprocal", np.reciprocal, (0.3, 3), {}),
    ("relu", lambda x: np.maximum(x, 0), (-2, 2), {}),
    ("rint", np.rint, (-3, 3), {"grad": False}),
    ("round", lambda x: np.floor(x + 0.5) * (x >= 0) +
     np.ceil(x - 0.5) * (x < 0), (-3, 3), {"grad": False}),
    ("rsqrt", lambda x: 1 / np.sqrt(x), (0.3, 3), {}),
    ("sigmoid", lambda x: 1 / (1 + np.exp(-x)), (-3, 3), {}),
    ("sign", np.sign, (-2, 2), {"grad": False}),
    ("sin", np.sin, (-3, 3), {}),
    ("sinh", np.sinh, (-2, 2), {}),
    ("softsign", lambda x: x / (1 + np.abs(x)), (-2, 2), {}),
    ("sqrt", np.sqrt, (0.1, 4), {}),
    ("square", np.square, (-2, 2), {}),
    ("tan", np.tan, (-1, 1), {}),
    ("tanh", np.tanh, (-2, 2), {}),
    ("trunc", np.trunc, (-3, 3), {"grad": False}),
]

# rounding-family ops produce discrete outputs: a value that lands on a
# different side of an integer boundary after a low-precision cast changes
# the result by 1.0, so the close-to-f32 dtype sweep does not apply
_DISCRETE = {"ceil", "floor", "rint", "round", "trunc", "fix", "sign",
             "logical_not"}
for _name, _ref, _rng, _kw in _UNARY:
    case(_name, Case((U(_rng[0], _rng[1], seed=hash(_name) % 1000),),
                     ref=_ref, dtype_sweep=_name not in _DISCRETE,
                     edge=True, **_kw))

case("hard_sigmoid",
     Case((N(seed=3),), {"alpha": 0.2, "beta": 0.5},
          ref=lambda x, alpha, beta: np.clip(alpha * x + beta, 0, 1)))
case("smooth_l1",
     Case((N(seed=4),), {"scalar": 1.0},
          ref=lambda x, scalar: np.where(
              np.abs(x) < 1.0 / scalar**2,
              0.5 * (scalar * x) ** 2,
              np.abs(x) - 0.5 / scalar**2)))
case("BlockGrad", Case((N(seed=5),), ref=lambda x: x, grad=False))
case("_copy", Case((N(seed=6),), ref=lambda x: x, edge=True))
case("make_loss", Case((N(seed=7),), ref=lambda x: x))
case("ones_like", Case((N(seed=8),), ref=np.ones_like, grad=False))
case("zeros_like", Case((N(seed=9),), ref=np.zeros_like, grad=False))
case("shape_array",
     Case((N((2, 5), seed=10),), ref=lambda x: np.array([2, 5]),
          grad=False))
case("size_array",
     Case((N((2, 5), seed=11),), ref=lambda x: np.array([10]), grad=False))
case("Cast",
     Case((N(seed=12),), {"dtype": "float64"},
          ref=lambda x, dtype: x.astype(np.float64), grad=False))
case("amp_cast",
     Case((N(seed=13),), {"dtype": "float32"},
          ref=lambda x, dtype: x, grad=False))
case("gamma_sample_grad_dummy", Case((U(0.5, 2, seed=14),),
                                     ref=lambda x: x, grad=False))

# binary elemwise (also the operator aliases _plus/_minus/...)
_BIN = [
    ("_add", np.add, (0.5, 2)),
    ("_minus", np.subtract, (0.5, 2)),
    ("_mul", np.multiply, (0.5, 2)),
    ("_div", np.divide, (0.5, 2)),
    ("_mod", np.mod, (1.0, 5)),
    ("_power", np.power, (0.5, 2)),
    ("_hypot", np.hypot, (0.5, 2)),
    ("_maximum", np.maximum, (-2, 2)),
    ("_minimum", np.minimum, (-2, 2)),
    ("_scatter_elemwise_div", np.divide, (0.5, 2)),
]
for _name, _ref, _rng in _BIN:
    case(_name, Case((U(*_rng, seed=20), U(*_rng, seed=21)), ref=_ref,
                     dtype_sweep=True, edge=True,
                     grad=(None if _name != "_mod" else False)))

_CMP = [
    ("_equal", np.equal), ("_not_equal", np.not_equal),
    ("_greater", np.greater), ("_greater_equal", np.greater_equal),
    ("_lesser", np.less), ("_lesser_equal", np.less_equal),
    ("_logical_and", np.logical_and), ("_logical_or", np.logical_or),
    ("_logical_xor", np.logical_xor),
]
for _name, _ref in _CMP:
    _a, _b = I(3, seed=22).astype(np.float32), I(3, seed=23).astype(np.float32)
    case(_name, Case((_a, _b),
                     ref=lambda a, b, _f=_ref: _f(a, b).astype(np.float32),
                     grad=False))

# scalar forms incl. reverse variants
_SCALAR = [
    ("_plus_scalar", lambda x, s: x + s, (0.5, 2)),
    ("_minus_scalar", lambda x, s: x - s, (0.5, 2)),
    ("_rminus_scalar", lambda x, s: s - x, (0.5, 2)),
    ("_mul_scalar", lambda x, s: x * s, (0.5, 2)),
    ("_div_scalar", lambda x, s: x / s, (0.5, 2)),
    ("_rdiv_scalar", lambda x, s: s / x, (0.5, 2)),
    ("_mod_scalar", lambda x, s: np.mod(x, s), (1, 5)),
    ("_rmod_scalar", lambda x, s: np.mod(s, x), (1, 5)),
    ("_power_scalar", lambda x, s: x ** s, (0.5, 2)),
    ("_rpower_scalar", lambda x, s: s ** x, (0.5, 2)),
    ("_maximum_scalar", lambda x, s: np.maximum(x, s), (-2, 2)),
    ("_minimum_scalar", lambda x, s: np.minimum(x, s), (-2, 2)),
    ("_hypot_scalar", lambda x, s: np.hypot(x, s), (0.5, 2)),
    ("_scatter_plus_scalar", lambda x, s: x + s, (0.5, 2)),
    ("_scatter_minus_scalar", lambda x, s: x - s, (0.5, 2)),
]
for _name, _ref, _rng in _SCALAR:
    _grad = None if "_mod" not in _name else False
    case(_name, Case((U(*_rng, seed=25),), {"scalar": 1.5},
                     ref=lambda x, scalar, _f=_ref: _f(x, scalar),
                     grad=_grad))

_SCALAR_CMP = [
    ("_equal_scalar", np.equal), ("_not_equal_scalar", np.not_equal),
    ("_greater_scalar", np.greater),
    ("_greater_equal_scalar", np.greater_equal),
    ("_lesser_scalar", np.less), ("_lesser_equal_scalar", np.less_equal),
    ("_logical_and_scalar", np.logical_and),
    ("_logical_or_scalar", np.logical_or),
    ("_logical_xor_scalar", np.logical_xor),
]
for _name, _ref in _SCALAR_CMP:
    case(_name, Case((I(3, seed=26).astype(np.float32),), {"scalar": 1.0},
                     ref=lambda x, scalar, _f=_ref:
                     _f(x, scalar).astype(np.float32), grad=False))

case("amp_multicast",
     Case((N(seed=27), N(seed=28)), {"num_outputs": 2},
          ref=lambda a, b, num_outputs: a, grad=False))

# --------------------------------------------------------------------------
# broadcast
# --------------------------------------------------------------------------
_BCAST = [
    ("broadcast_add", np.add), ("broadcast_plus", np.add),
    ("broadcast_sub", np.subtract), ("broadcast_minus", np.subtract),
    ("broadcast_mul", np.multiply), ("broadcast_div", np.divide),
    ("broadcast_mod", np.mod), ("broadcast_power", np.power),
    ("broadcast_hypot", np.hypot),
    ("broadcast_maximum", np.maximum), ("broadcast_minimum", np.minimum),
]
for _name, _ref in _BCAST:
    _grad = None if _name != "broadcast_mod" else False
    case(_name, Case((U(0.5, 2, (2, 1, 4), seed=30),
                      U(0.5, 2, (1, 3, 4), seed=31)),
                     ref=_ref, dtype_sweep=True, grad=_grad))

_BCAST_CMP = [
    ("broadcast_equal", np.equal), ("broadcast_not_equal", np.not_equal),
    ("broadcast_greater", np.greater),
    ("broadcast_greater_equal", np.greater_equal),
    ("broadcast_lesser", np.less), ("broadcast_lesser_equal", np.less_equal),
    ("broadcast_logical_and", np.logical_and),
    ("broadcast_logical_or", np.logical_or),
    ("broadcast_logical_xor", np.logical_xor),
]
for _name, _ref in _BCAST_CMP:
    case(_name, Case((I(3, (2, 1), seed=32).astype(np.float32),
                      I(3, (2, 4), seed=33).astype(np.float32)),
                     ref=lambda a, b, _f=_ref: _f(a, b).astype(np.float32),
                     grad=False))

case("broadcast_axes",
     Case((N((2, 1, 3), seed=34),), {"axis": (1,), "size": (4,)},
          ref=lambda x, axis, size: np.broadcast_to(x, (2, 4, 3))))
case("broadcast_to",
     Case((N((2, 1, 3), seed=35),), {"shape": (2, 4, 3)},
          ref=lambda x, shape: np.broadcast_to(x, shape)))
case("broadcast_like",
     Case((N((2, 1), seed=36), N((2, 5), seed=37)),
          ref=lambda x, y: np.broadcast_to(x, y.shape)))

# --------------------------------------------------------------------------
# reduce
# --------------------------------------------------------------------------
case("sum",
     Case((N((3, 4), seed=40),), {"axis": (1,)},
          ref=lambda x, axis: x.sum(axis=axis), dtype_sweep=True,
          edge=True),
     Case((N((3, 4), seed=41),), {"axis": (0, 1), "keepdims": True},
          ref=lambda x, axis, keepdims: x.sum(axis=axis, keepdims=True)),
     Case((N((2, 3, 4), seed=42),), {"axis": (1,), "exclude": True},
          ref=lambda x, axis, exclude: x.sum(axis=(0, 2))))
case("mean",
     Case((N((3, 4), seed=43),), {"axis": (0,)},
          ref=lambda x, axis: x.mean(axis=axis)))
case("prod",
     Case((U(0.5, 1.5, (3, 4), seed=44),), {"axis": (1,)},
          ref=lambda x, axis: x.prod(axis=axis)))
case("nansum",
     Case((np.where(N((3, 4), seed=45) > 1, np.nan,
                    N((3, 4), seed=45)).astype(np.float32),),
          {"axis": (1,)}, ref=lambda x, axis: np.nansum(x, axis=axis),
          grad=False))
case("nanprod",
     Case((np.where(N((3, 4), seed=46) > 1, np.nan,
                    U(0.5, 1.5, (3, 4), seed=46)).astype(np.float32),),
          {"axis": (1,)}, ref=lambda x, axis: np.nanprod(x, axis=axis),
          grad=False))
case("max", Case((N((3, 4), seed=47),), {"axis": (1,)},
                 ref=lambda x, axis: x.max(axis=axis)))
case("min", Case((N((3, 4), seed=48),), {"axis": (1,)},
                 ref=lambda x, axis: x.min(axis=axis)))
case("norm",
     Case((N((3, 4), seed=49),), {},
          ref=lambda x: np.array(np.sqrt((x ** 2).sum()))),
     Case((N((3, 4), seed=50),), {"ord": 1, "axis": 1},
          ref=lambda x, ord, axis: np.abs(x).sum(axis=1)))
case("argmax",
     Case((N((3, 4), seed=51),), {"axis": 1},
          ref=lambda x, axis: x.argmax(axis=1).astype(np.float32),
          grad=False))
case("argmin",
     Case((N((3, 4), seed=52),), {"axis": 1},
          ref=lambda x, axis: x.argmin(axis=1).astype(np.float32),
          grad=False))
case("argmax_channel",
     Case((N((3, 4), seed=53),),
          ref=lambda x: x.argmax(axis=1).astype(np.float32), grad=False))

# --------------------------------------------------------------------------
# matrix
# --------------------------------------------------------------------------
case("dot",
     Case((N((3, 4), seed=60), N((4, 5), seed=61)),
          ref=lambda a, b: a @ b, dtype_sweep=True),
     Case((N((4, 3), seed=62), N((4, 5), seed=63)), {"transpose_a": True},
          ref=lambda a, b, transpose_a: a.T @ b))
case("batch_dot",
     Case((N((2, 3, 4), seed=64), N((2, 4, 5), seed=65)),
          ref=lambda a, b: np.einsum("bij,bjk->bik", a, b)))
case("matmul", Case((N((2, 3, 4), seed=66), N((4, 5), seed=67)),
                    ref=lambda a, b: a @ b))
case("Flatten", Case((N((2, 3, 4), seed=68),),
                     ref=lambda x: x.reshape(2, 12)))
case("Reshape",
     Case((N((2, 6), seed=69),), {"shape": (3, 4)},
          ref=lambda x, shape: x.reshape(shape)),
     Case((N((2, 6), seed=70),), {"shape": (-1, 3)},
          ref=lambda x, shape: x.reshape(-1, 3)))
case("transpose",
     Case((N((2, 3, 4), seed=71),), {"axes": (2, 0, 1)},
          ref=lambda x, axes: x.transpose(axes)),
     Case((N((2, 3), seed=72),), {}, ref=lambda x: x.T))
case("expand_dims", Case((N((2, 3), seed=73),), {"axis": 1},
                         ref=lambda x, axis: x[:, None, :]))
case("squeeze", Case((N((2, 1, 3), seed=74),), {"axis": 1},
                     ref=lambda x, axis: x.squeeze(1)))
case("Concat",
     Case((N((2, 3), seed=75), N((2, 4), seed=76)), {"dim": 1},
          ref=lambda a, b, dim: np.concatenate([a, b], axis=1)))
case("stack",
     Case((N((2, 3), seed=77), N((2, 3), seed=78)), {"axis": 1},
          ref=lambda a, b, axis: np.stack([a, b], axis=1)))
case("SliceChannel",
     Case((N((2, 6), seed=79),), {"num_outputs": 3, "axis": 1},
          ref=lambda x, num_outputs, axis: x[:, 0:2]))
case("_split_v2",
     Case((N((2, 6), seed=80),), {"sections": 2, "axis": 1},
          ref=lambda x, sections, axis: x[:, :3]))
case("slice_axis",
     Case((N((3, 6), seed=81),), {"axis": 1, "begin": 1, "end": 4},
          ref=lambda x, axis, begin, end: x[:, 1:4]))
case("crop",
     Case((N((3, 6), seed=82),), {"begin": (0, 1), "end": (2, 5)},
          ref=lambda x, begin, end: x[0:2, 1:5]))
case("slice_like",
     Case((N((4, 6), seed=83), N((2, 3), seed=84)),
          ref=lambda x, y: x[:2, :3]))
case("take",
     Case((N((5, 3), seed=85), np.array([0, 2, 4], np.int32)),
          ref=lambda x, i: x[i], dtype_sweep=True),
     # clip mode clamps out-of-range; wrap mode wraps negative/overflow
     Case((N((4, 2), seed=217), np.array([-1, 5, 3], np.int32)),
          {"mode": "clip"},
          ref=lambda x, i, mode: x[np.clip(i, 0, 3)]),
     Case((N((4, 2), seed=218), np.array([-1, 5, 3], np.int32)),
          {"mode": "wrap"},
          ref=lambda x, i, mode: x[i % 4]))
case("batch_take",
     Case((N((3, 4), seed=86), np.array([0, 2, 1], np.int32)),
          ref=lambda a, i: a[np.arange(3), i]))
case("pick",
     Case((N((3, 4), seed=87), np.array([0, 2, 1], np.float32)),
          {"axis": 1},
          ref=lambda x, i, axis: x[np.arange(3), i.astype(int)],
          grad_only=(0,)))
case("gather_nd",
     Case((N((3, 4), seed=88), np.array([[0, 2], [1, 3]], np.int32)),
          ref=lambda x, idx: x[idx[0], idx[1]]))
case("scatter_nd",
     Case((np.array([1.0, 2.0], np.float32),
           np.array([[0, 2], [1, 3]], np.int32)),
          {"shape": (3, 4)},
          ref=lambda d, idx, shape: _scatter_ref(d, idx, shape)))


def _scatter_ref(d, idx, shape):
    out = np.zeros(shape, np.float32)
    out[idx[0], idx[1]] = d
    return out


case("_scatter_set_nd",
     Case((np.zeros((3, 4), np.float32), np.array([1.0, 2.0], np.float32),
           np.array([[0, 2], [1, 3]], np.int32)),
          {"shape": (3, 4)},
          ref=lambda lhs, d, idx, shape: _scatter_ref(d, idx, shape),
          grad=False))
case("tile", Case((N((2, 3), seed=89),), {"reps": (2, 2)},
                  ref=lambda x, reps: np.tile(x, reps)))
case("repeat",
     Case((N((2, 3), seed=90),), {"repeats": 2, "axis": 1},
          ref=lambda x, repeats, axis: np.repeat(x, repeats, axis=1)),
     Case((N((2, 3), seed=91),), {"repeats": 2},
          ref=lambda x, repeats: np.repeat(x.reshape(-1), 2)))
case("flip", Case((N((2, 3), seed=92),), {"axis": 1},
                  ref=lambda x, axis: x[:, ::-1]))
case("reverse", Case((N((2, 3), seed=93),), {"axis": 1},
                     ref=lambda x, axis: x[:, ::-1]))
case("SwapAxis", Case((N((2, 3, 4), seed=94),), {"dim1": 0, "dim2": 2},
                      ref=lambda x, dim1, dim2: x.swapaxes(0, 2)))
case("moveaxis", Case((N((2, 3, 4), seed=95),),
                      {"source": 0, "destination": 2},
                      ref=lambda x, source, destination:
                      np.moveaxis(x, 0, 2)))
case("diag",
     Case((N((4, 4), seed=96),), {}, ref=lambda x: np.diag(x)),
     Case((np.arange(3, dtype=np.float32),), {},
          ref=lambda x: np.diag(x)))
case("one_hot",
     Case((np.array([0, 2, 1], np.int32),), {"depth": 4},
          ref=lambda i, depth: np.eye(4, dtype=np.float32)[i], grad=False))
case("where",
     Case((np.array([1, 0, 1], np.float32), N((3,), seed=97),
           N((3,), seed=98)),
          ref=lambda c, a, b: np.where(c != 0, a, b)))
case("clip",
     Case((N((3, 4), seed=99),), {"a_min": -0.5, "a_max": 0.5},
          ref=lambda x, a_min, a_max: np.clip(x, a_min, a_max)))
case("Pad",
     Case((N((2, 3, 4, 5), seed=100),),
          {"mode": "constant", "pad_width": (0, 0, 0, 0, 1, 1, 2, 2),
           "constant_value": 0.0},
          ref=lambda x, mode, pad_width, constant_value:
          np.pad(x, ((0, 0), (0, 0), (1, 1), (2, 2)))))
case("depth_to_space",
     Case((N((1, 4, 2, 3), seed=101),), {"block_size": 2},
          ref=lambda x, block_size: _d2s_ref(x, 2)))
case("space_to_depth",
     Case((N((1, 1, 4, 6), seed=102),), {"block_size": 2},
          ref=lambda x, block_size: _s2d_ref(x, 2)))


def _d2s_ref(x, b):
    n, c, h, w = x.shape
    y = x.reshape(n, b, b, c // (b * b), h, w)
    return y.transpose(0, 3, 4, 1, 5, 2).reshape(n, c // (b * b),
                                                 h * b, w * b)


def _s2d_ref(x, b):
    n, c, h, w = x.shape
    y = x.reshape(n, c, h // b, b, w // b, b)
    return y.transpose(0, 3, 5, 1, 2, 4).reshape(n, c * b * b,
                                                 h // b, w // b)


case("ravel_multi_index",
     Case((np.array([[0, 1], [2, 0]], np.float32),), {"shape": (3, 4)},
          ref=lambda d, shape: np.array([2.0, 4.0], np.float32),
          grad=False))
case("unravel_index",
     Case((np.array([2, 4], np.float32),), {"shape": (3, 4)},
          ref=lambda d, shape: np.array([[0, 1], [2, 0]], np.float32),
          grad=False))
case("reshape_like",
     Case((N((2, 6), seed=103), N((3, 4), seed=104)),
          ref=lambda x, y: x.reshape(3, 4)))
case("khatri_rao",
     Case((N((2, 3), seed=105), N((4, 3), seed=106)),
          ref=lambda a, b: np.vstack([np.kron(a[:, k], b[:, k])
                                      for k in range(3)]).T))

# --------------------------------------------------------------------------
# ordering
# --------------------------------------------------------------------------
case("sort", Case((N((3, 5), seed=110),), {"axis": 1},
                  ref=lambda x, axis: np.sort(x, axis=1)))
case("argsort",
     Case((N((3, 5), seed=111),), {"axis": 1},
          ref=lambda x, axis: np.argsort(x, axis=1).astype(np.float32),
          grad=False))
case("topk",
     Case((N((3, 5), seed=112),), {"axis": 1, "k": 2, "ret_typ": "value"},
          ref=lambda x, axis, k, ret_typ: np.sort(x, axis=1)[:, ::-1][:, :2],
          grad=False))

# --------------------------------------------------------------------------
# nn
# --------------------------------------------------------------------------
case("Activation",
     Case((N(seed=120),), {"act_type": "relu"},
          ref=lambda x, act_type: np.maximum(x, 0)),
     Case((N(seed=121),), {"act_type": "softrelu"},
          ref=lambda x, act_type: np.log1p(np.exp(x))))
case("LeakyReLU",
     Case((N(seed=122),), {"act_type": "leaky", "slope": 0.1},
          ref=lambda x, act_type, slope: np.where(x > 0, x, 0.1 * x)),
     Case((N(seed=123),), {"act_type": "elu", "slope": 1.0},
          ref=lambda x, act_type, slope: np.where(x > 0, x,
                                                  np.expm1(x))),
     # prelu: learned per-channel (here scalar) negative slope input
     Case((N((2, 4), seed=229), np.full((1,), 0.2, np.float32)),
          {"act_type": "prelu"},
          ref=lambda x, g, act_type: np.where(x > 0, x, 0.2 * x)))
case("FullyConnected",
     Case((N((4, 6), seed=124), N((3, 6), seed=125), N((3,), seed=126)),
          {"num_hidden": 3},
          ref=lambda x, w, b, num_hidden: x @ w.T + b, dtype_sweep=True))
case("Convolution",
     Case((N((2, 2, 5, 5), seed=127), N((3, 2, 3, 3), seed=128)),
          {"kernel": (3, 3), "num_filter": 3, "no_bias": True},
          ref=lambda x, w, **kw: _conv2d_ref(x, w), grad_rtol=4e-2),
     # stride 2 + padding 1
     Case((N((1, 2, 6, 6), seed=219), N((4, 2, 3, 3), seed=220)),
          {"kernel": (3, 3), "num_filter": 4, "no_bias": True,
           "stride": (2, 2), "pad": (1, 1)},
          ref=lambda x, w, **kw: _conv2d_ref(x, w, stride=2, pad=1),
          grad_rtol=4e-2),
     # grouped convolution (num_group=2)
     Case((N((1, 4, 5, 5), seed=221), N((4, 2, 3, 3), seed=222)),
          {"kernel": (3, 3), "num_filter": 4, "no_bias": True,
           "num_group": 2},
          ref=lambda x, w, **kw: np.concatenate(
              [_conv2d_ref(x[:, :2], w[:2]),
               _conv2d_ref(x[:, 2:], w[2:])], axis=1),
          grad_rtol=4e-2),
     # with bias
     Case((N((1, 2, 4, 4), seed=223), N((3, 2, 3, 3), seed=224),
           N((3,), seed=225)),
          {"kernel": (3, 3), "num_filter": 3},
          ref=lambda x, w, b, **kw:
          _conv2d_ref(x, w) + b.reshape(1, -1, 1, 1), grad_rtol=4e-2))


def _conv2d_ref(x, w, stride=1, pad=0):
    if pad:
        x = np.pad(x, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    n, c, h, wd = x.shape
    f, _, kh, kw = w.shape
    oh = (h - kh) // stride + 1
    ow = (wd - kw) // stride + 1
    out = np.zeros((n, f, oh, ow), np.float32)
    for i in range(oh):
        for j in range(ow):
            patch = x[:, :, i * stride:i * stride + kh,
                      j * stride:j * stride + kw]
            out[:, :, i, j] = np.einsum("nchw,fchw->nf", patch, w)
    return out


case("Deconvolution",
     Case((N((1, 2, 3, 3), seed=129), N((2, 2, 2, 2), seed=130)),
          {"kernel": (2, 2), "num_filter": 2, "no_bias": True},
          ref=lambda x, w, **kw: _deconv2d_ref(x, w), grad_rtol=4e-2))


def _deconv2d_ref(x, w):
    # transposed convolution, stride 1: scatter each input pixel through
    # the kernel (w layout: (in_ch, out_ch, kh, kw))
    n, ci, h, wd = x.shape
    _, co, kh, kw = w.shape
    out = np.zeros((n, co, h + kh - 1, wd + kw - 1), np.float32)
    for i in range(h):
        for j in range(wd):
            out[:, :, i:i + kh, j:j + kw] += np.einsum(
                "nc,cfhw->nfhw", x[:, :, i, j], w)
    return out
case("Pooling",
     Case((N((2, 2, 4, 4), seed=131),),
          {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max"},
          ref=lambda x, **kw: x.reshape(2, 2, 2, 2, 2, 2).max((3, 5))),
     Case((N((2, 2, 4, 4), seed=132),),
          {"kernel": (2, 2), "stride": (2, 2), "pool_type": "avg"},
          ref=lambda x, **kw: x.reshape(2, 2, 2, 2, 2, 2).mean((3, 5))),
     # global pooling ignores kernel
     Case((N((2, 3, 5, 5), seed=226),),
          {"kernel": (2, 2), "pool_type": "avg", "global_pool": True},
          ref=lambda x, **kw: x.mean((2, 3), keepdims=True)),
     # 'full' convention rounds the output size UP (ref: pooling-inl.h
     # pooling_convention=full)
     Case((N((1, 1, 5, 5), seed=227),),
          {"kernel": (2, 2), "stride": (2, 2), "pool_type": "max",
           "pooling_convention": "full"},
          ref=lambda x, **kw: _pool_full_ref(x)),
     # sum pooling
     Case((N((1, 2, 4, 4), seed=228),),
          {"kernel": (2, 2), "stride": (2, 2), "pool_type": "sum"},
          ref=lambda x, **kw: x.reshape(1, 2, 2, 2, 2, 2).sum((3, 5))))


def _pool_full_ref(x):
    # 5x5, kernel 2, stride 2, full: out 3x3 (last window partial)
    out = np.full((1, 1, 3, 3), -np.inf, np.float32)
    for i in range(3):
        for j in range(3):
            out[0, 0, i, j] = x[0, 0, 2 * i:2 * i + 2,
                                2 * j:2 * j + 2].max()
    return out
case("softmax",
     Case((N((3, 5), seed=133),), {"axis": -1},
          ref=lambda x, axis: _softmax_ref(x), dtype_sweep=True),
     # masked softmax: positions >= length get exactly 0, a length-0 row
     # is all zeros (ref: softmax-inl.h use_length path)
     # float32 lengths so the numeric-gradient leg runs (grad_only skips
     # perturbing the length input; _length_mask casts internally)
     Case((N((3, 5), seed=230), np.array([3, 5, 0], np.float32)),
          {"axis": -1, "use_length": True},
          ref=lambda x, l, axis, use_length: _masked_softmax_ref(x, l),
          grad_only=(0,)),
     Case((N((3, 5), seed=231),), {"temperature": 2.0},
          ref=lambda x, temperature: _softmax_ref(x / 2.0)))
case("log_softmax",
     Case((N((3, 5), seed=134),), {"axis": -1},
          ref=lambda x, axis: np.log(_softmax_ref(x))))
case("softmin",
     Case((N((3, 5), seed=135),), {"axis": -1},
          ref=lambda x, axis: _softmax_ref(-x)))


def _masked_softmax_ref(x, lengths):
    out = np.zeros_like(x)
    for i, L in enumerate(lengths.astype(int)):
        if L > 0:
            out[i, :L] = _softmax_ref(x[i, :L].reshape(1, -1))
    return out
case("SoftmaxActivation",
     Case((N((3, 5), seed=136),), ref=lambda x: _softmax_ref(x)))


def _softmax_ref(x, axis=-1):
    e = np.exp(x - x.max(axis=axis, keepdims=True))
    return e / e.sum(axis=axis, keepdims=True)


# SoftmaxOutput: forward is softmax; backward is the fused (p - onehot)
# loss gradient by design (ref: softmax_output.cc), so finite differences
# of the forward do NOT apply.
case("Softmax",
     Case((N((4, 5), seed=137), np.array([0, 1, 2, 3], np.float32)),
          ref=lambda x, y: _softmax_ref(x), grad=False))
case("softmax_cross_entropy",
     Case((N((4, 5), seed=138), np.array([0, 1, 2, 3], np.float32)),
          ref=lambda x, y: np.array(
              -np.log(_softmax_ref(x))[np.arange(4),
                                       y.astype(int)].sum()),
          grad=False))
case("LayerNorm",
     Case((N((3, 5), seed=139), np.ones(5, np.float32),
           np.zeros(5, np.float32)),
          {"axis": -1},
          ref=lambda x, g, b, axis: (x - x.mean(-1, keepdims=True)) /
          np.sqrt(x.var(-1, keepdims=True) + 1e-5)))
case("InstanceNorm",
     Case((N((2, 3, 4), seed=140), np.ones(3, np.float32),
           np.zeros(3, np.float32)),
          ref=lambda x, g, b: (x - x.mean(-1, keepdims=True)) /
          np.sqrt(x.var(-1, keepdims=True) + 1e-3)))
case("L2Normalization",
     Case((N((3, 5), seed=141),),
          ref=lambda x: x / np.sqrt((x ** 2).sum(
              axis=tuple(range(1, x.ndim)), keepdims=True) + 1e-10)))
case("LRN", Case((N((2, 6, 3, 3), seed=142),), {"nsize": 3},
                 ref=lambda x, nsize: _lrn_ref(x, nsize),
                 grad_rtol=4e-2))


def _lrn_ref(x, nsize, alpha=1e-4, beta=0.75, knorm=2.0):
    # cross-channel local response norm; alpha is divided by nsize
    # (ref: lrn-inl.h  tmp_norm = knorm + alpha/nsize * sum(sq))
    n, c, h, w = x.shape
    half = nsize // 2
    sq = x ** 2
    denom = np.zeros_like(x)
    for ch in range(c):
        lo, hi = max(0, ch - half), min(c, ch + half + 1)
        denom[:, ch] = sq[:, lo:hi].sum(axis=1)
    return x / (knorm + alpha / nsize * denom) ** beta
case("Embedding",
     Case((np.array([0, 2, 1], np.int32), N((4, 5), seed=143)),
          {"input_dim": 4, "output_dim": 5},
          ref=lambda i, w, **kw: w[i]))
# Dropout is an rng op (takes a PRNG key input) — exercised through the
# gluon layer in tests/test_gluon.py instead of direct registry invoke
case("GridGenerator",
     Case((N((1, 6), seed=145),),
          {"transform_type": "affine", "target_shape": (2, 3)},
          grad=False))
case("UpSampling",
     Case((N((1, 2, 2, 2), seed=146),),
          {"scale": 2, "sample_type": "nearest"},
          ref=lambda x, **kw: x.repeat(2, 2).repeat(2, 3)))
case("SequenceMask",
     Case((N((4, 2, 3), seed=147), np.array([2, 4], np.float32)),
          {"use_sequence_length": True},
          ref=lambda x, l, **kw: _seqmask_ref(x, l), grad_only=(0,)))


def _seqmask_ref(x, lens, value=0.0):
    out = x.copy()
    for b, L in enumerate(lens.astype(int)):
        out[L:, b] = value
    return out


case("SequenceLast",
     Case((N((4, 2, 3), seed=148), np.array([2, 4], np.float32)),
          {"use_sequence_length": True},
          ref=lambda x, l, **kw: x[l.astype(int) - 1, np.arange(2)],
          grad_only=(0,)))
case("SequenceReverse",
     Case((N((4, 2, 3), seed=149), np.array([2, 4], np.float32)),
          {"use_sequence_length": True},
          ref=lambda x, l, **kw: _seqrev_ref(x, l), grad_only=(0,)))


def _seqrev_ref(x, lens):
    out = x.copy()
    for b, L in enumerate(lens.astype(int)):
        out[:L, b] = x[:L, b][::-1]
    return out


case("LinearRegressionOutput",
     Case((N((3, 4), seed=150), N((3, 4), seed=151)),
          ref=lambda x, y: x, grad=False))
case("MAERegressionOutput",
     Case((N((3, 4), seed=152), N((3, 4), seed=153)),
          ref=lambda x, y: x, grad=False))
case("LogisticRegressionOutput",
     Case((N((3, 4), seed=154), N((3, 4), seed=155)),
          ref=lambda x, y: 1 / (1 + np.exp(-x)), grad=False))
case("SVMOutput",
     Case((N((3, 4), seed=156), np.array([0, 1, 2], np.float32)),
          ref=lambda x, y: x, grad=False))
case("MakeLoss", Case((U(0.5, 2, seed=157),), ref=lambda x: x))
case("IdentityAttachKLSparseReg",
     Case((U(0.1, 0.9, seed=158),), ref=lambda x: x))
case("ElementWiseSum",
     Case((N(seed=159), N(seed=160), N(seed=161)),
          ref=lambda *xs: sum(xs)))
case("_rnn_param_concat",
     Case((N((2, 3), seed=162), N((4, 3), seed=163)), {"dim": 0},
          ref=lambda a, b, dim: np.concatenate(
              [a.reshape(-1), b.reshape(-1)])))
case("Crop",
     Case((N((1, 2, 5, 5), seed=164),), {"h_w": (3, 3)},
          ref=lambda x, h_w: x[:, :, :3, :3]))
case("_CrossDeviceCopy", Case((N(seed=165),), ref=lambda x: x))
case("_identity_with_attr_like_rhs",
     Case((N(seed=166), N(seed=167)), ref=lambda a, b: a))
case("_slice_assign",
     Case((np.zeros((3, 4), np.float32), np.ones((2, 2), np.float32)),
          {"begin": (0, 1), "end": (2, 3)},
          ref=lambda l, r, begin, end: _sa_ref(l, r)))


def _sa_ref(l, r):
    out = l.copy()
    out[0:2, 1:3] = r
    return out


case("_slice_assign_scalar",
     Case((np.zeros((3, 4), np.float32),),
          {"scalar": 5.0, "begin": (0, 1), "end": (2, 3)},
          ref=lambda l, scalar, begin, end: _sas_ref(l, 5.0)))


def _sas_ref(l, v):
    out = l.copy()
    out[0:2, 1:3] = v
    return out


case("BatchNorm",
     Case((N((4, 3, 2, 2), seed=168), np.ones(3, np.float32),
           np.zeros(3, np.float32), np.zeros(3, np.float32),
           np.ones(3, np.float32)),
          {"fix_gamma": False, "use_global_stats": True},
          ref=lambda x, g, b, mm, mv, **kw: x / np.sqrt(1 + 1e-3),
          grad=False),
     # train-mode stats on data with mean >> std: the shifted single-pass
     # variance must not cancel catastrophically (f32 E[x^2]-mean^2 would
     # return exactly 0 here)
     Case((N((64, 3, 4, 4), seed=169, scale=1.0).astype(np.float32)
           + 10000.0, np.ones(3, np.float32), np.zeros(3, np.float32),
           np.zeros(3, np.float32), np.ones(3, np.float32)),
          {"fix_gamma": False, "_training": True},
          ref=lambda x, g, b, mm, mv, **kw:
          x.var(axis=(0, 2, 3)).astype(np.float32),
          out_index=2, rtol=1e-2, atol=1e-3, grad=False))

# --------------------------------------------------------------------------
# linalg
# --------------------------------------------------------------------------
def _spd(n, seed):
    a = np.random.RandomState(seed).randn(n, n).astype(np.float32)
    return (a @ a.T + n * np.eye(n)).astype(np.float32)


case("_linalg_gemm",
     Case((N((3, 4), seed=170), N((4, 5), seed=171), N((3, 5), seed=172)),
          {"alpha": 2.0, "beta": 0.5},
          ref=lambda a, b, c, alpha, beta: alpha * (a @ b) + beta * c))
case("_linalg_gemm2",
     Case((N((3, 4), seed=173), N((4, 5), seed=174)), {"alpha": 1.5},
          ref=lambda a, b, alpha: alpha * (a @ b)))
case("_linalg_syrk",
     Case((N((3, 4), seed=175),), {"alpha": 1.0},
          ref=lambda a, alpha: a @ a.T))
case("_linalg_det",
     Case((_spd(3, 176),), ref=lambda a: np.array(np.linalg.det(a)),
          rtol=1e-3, grad_rtol=4e-2))
case("_linalg_slogdet",
     Case((_spd(3, 177),),
          ref=lambda a: np.array(np.linalg.slogdet(a)[0]), grad=False))
case("_linalg_inverse",
     Case((_spd(3, 178),), ref=np.linalg.inv, rtol=1e-3,
          grad_rtol=4e-2))
case("_linalg_potrf",
     Case((_spd(3, 179),), ref=np.linalg.cholesky, rtol=1e-3,
          grad_rtol=4e-2))
case("_linalg_potri",
     Case((np.linalg.cholesky(_spd(3, 180)).astype(np.float32),),
          ref=lambda l: np.linalg.inv(l @ l.T), rtol=1e-2,
          grad=False))
case("_linalg_trmm",
     Case((np.tril(N((3, 3), seed=181)).astype(np.float32),
           N((3, 4), seed=182)),
          ref=lambda a, b: a @ b))
case("_linalg_trsm",
     Case((np.tril(N((3, 3), seed=183) + 3 * np.eye(3,
                                                    dtype=np.float32)),
           N((3, 4), seed=184)),
          ref=lambda a, b: np.linalg.solve(a, b), rtol=1e-3))
case("_linalg_sumlogdiag",
     Case((_spd(3, 185),),
          ref=lambda a: np.array(np.log(np.diag(a)).sum())))
case("_linalg_extractdiag",
     Case((N((3, 3), seed=186),), ref=lambda a: np.diag(a)))
case("_linalg_makediag",
     Case((N((3,), seed=187),), ref=lambda a: np.diag(a)))
case("_linalg_extracttrian",
     Case((N((3, 3), seed=188),),
          ref=lambda a: a[np.tril_indices(3)]))
case("_linalg_maketrian",
     Case((N((6,), seed=189),), ref=lambda a: _maketrian_ref(a)))


def _maketrian_ref(a):
    out = np.zeros((3, 3), np.float32)
    out[np.tril_indices(3)] = a
    return out


case("_linalg_syevd", Case((_spd(3, 190),), grad=False))
case("_linalg_gelqf", Case((N((2, 4), seed=191),), grad=False))
case("histogram",
     Case((U(0, 10, (20,), seed=192),), {"bin_cnt": 5, "range": (0, 10)},
          ref=lambda x, bin_cnt, range:
          np.histogram(x, bins=5, range=(0, 10))[0].astype(np.float32),
          grad=False))
case("moments",
     Case((N((3, 4), seed=193),), {"axes": (1,)},
          ref=lambda x, axes: x.mean(axis=1)))

# --------------------------------------------------------------------------
# contrib
# --------------------------------------------------------------------------
case("_contrib_quadratic",
     Case((N(seed=200),), {"a": 2.0, "b": 3.0, "c": 1.0},
          ref=lambda x, a, b, c: a * x * x + b * x + c))
# gradientmultiplier: identity forward, backward scales the gradient by
# `scalar` ON PURPOSE — finite differences of the forward do not apply
case("_contrib_gradientmultiplier",
     Case((N(seed=201),), {"scalar": 2.0},
          ref=lambda x, scalar: x, grad=False))
case("_contrib_index_array",
     Case((N((2, 3), seed=202),),
          ref=lambda x: np.stack(np.meshgrid(np.arange(2), np.arange(3),
                                             indexing="ij"),
                                 -1).astype(np.int64),
          grad=False))
case("_contrib_index_copy",
     Case((np.zeros((4, 3), np.float32), np.array([1, 3], np.int32),
           np.ones((2, 3), np.float32)),
          ref=lambda o, i, n: _idxcopy_ref(o, i, n), grad=False))


def _idxcopy_ref(o, i, n):
    out = o.copy()
    out[i] = n
    return out


case("_contrib_boolean_mask",
     Case((N((4, 3), seed=203), np.array([1, 0, 1, 0], np.float32)),
          grad=False))
case("_contrib_box_iou",
     Case((np.array([[0, 0, 2, 2]], np.float32),
           np.array([[1, 1, 3, 3]], np.float32)),
          ref=lambda a, b, **kw: np.array([[1.0 / 7.0]], np.float32),
          grad=False))
case("_contrib_arange_like",
     Case((N((2, 3), seed=204),),
          ref=lambda x: np.arange(6, dtype=np.float32).reshape(2, 3),
          grad=False))
case("_contrib_count_sketch",
     Case((N((2, 8), seed=205), U(0, 4, (8,), seed=206),
           np.sign(N((8,), seed=207)).astype(np.float32)),
          {"out_dim": 4}, grad=False))
case("AdaptiveAvgPooling2D",
     Case((N((1, 2, 4, 4), seed=208),), {"output_size": (2, 2)},
          ref=lambda x, output_size:
          x.reshape(1, 2, 2, 2, 2, 2).mean((3, 5))))
case("BilinearResize2D",
     Case((N((1, 2, 3, 3), seed=209),), {"height": 6, "width": 6},
          grad_rtol=4e-2))
# _quantized_fc_static and the _contrib_quantized_* family are covered by
# tests/test_quantization_ops.py (int8 pipeline roundtrips)

case("ROIAlign",
     # one ROI covering the full 4x4 map, 2x2 output, aligned sampling:
     # gradient flows through bilinear weights (rois not differentiable)
     Case((N((1, 2, 4, 4), seed=216),
           np.array([[0, 0, 0, 3, 3]], np.float32)),
          {"pooled_size": (2, 2), "spatial_scale": 1.0,
           "sample_ratio": 1},
          grad_only=(0,), grad_rtol=4e-2))
case("MultiBoxPrior",
     Case((N((1, 3, 2, 2), seed=213),),
          {"sizes": (0.5,), "ratios": (1.0,)},
          ref=lambda x, sizes, ratios: _mbprior_ref(2, 2, 0.5),
          grad=False))


def _mbprior_ref(h, w, size):
    out = []
    for i in range(h):
        for j in range(w):
            cy, cx = (i + 0.5) / h, (j + 0.5) / w
            out.append([cx - size / 2, cy - size / 2,
                        cx + size / 2, cy + size / 2])
    return np.array(out, np.float32)[None]


case("_contrib_box_nms",
     Case((np.array([[[0, 0.9, 0, 0, 2, 2],
                      [0, 0.8, 0.1, 0.1, 2, 2],
                      [0, 0.7, 5, 5, 7, 7]]], np.float32),),
          {"overlap_thresh": 0.5},
          ref=lambda d, overlap_thresh: _nms_ref(d), grad=False))


def _nms_ref(d):
    # box 1 overlaps box 0 (IoU > 0.5) -> suppressed: the whole entry is
    # overwritten with -1 (ref: box_nms forward marks all fields)
    out = d.copy()
    out[0, 1, :] = -1
    return out


case("_rnn_state_zeros",
     Case((N((5, 2, 3), seed=214),), {"num_states": 1, "state_size": 4},
          ref=lambda x, num_states, state_size:
          np.zeros((1, 2, 4), np.float32), grad=False))
case("_state_zeros",
     Case((N((2, 5, 3), seed=215),), {"num_hidden": 4, "batch_axis": 0},
          ref=lambda x, num_hidden, batch_axis:
          np.zeros((2, 4), np.float32), grad=False))

# --------------------------------------------------------------------------
# creation / internal (forward-only sanity)
# --------------------------------------------------------------------------
case("_zeros_without_dtype",
     Case((), {"shape": (2, 3)},
          ref=lambda shape: np.zeros((2, 3), np.float32), grad=False))


# --------------------------------------------------------------------------
# ops proven by dedicated test files (file must mention the op)
# --------------------------------------------------------------------------
COVERED_ELSEWHERE = {
    # optimizer kernels: tests/test_optimizer_rules.py exercises every rule
    **{op: "tests/test_optimizer_rules.py" for op in [
        "sgd_update", "sgd_mom_update", "mp_sgd_update",
        "mp_sgd_mom_update", "adam_update", "nag_mom_update",
        "rmsprop_update", "rmspropalex_update", "ftrl_update",
        "ftml_update", "signsgd_update", "signum_update",
        "multi_sgd_update", "multi_sgd_mom_update", "multi_mp_sgd_update",
        "multi_mp_sgd_mom_update", "_adamw_update", "_mp_adamw_update",
        "_sparse_adagrad_update", "_contrib_group_adagrad_update"]},
    # aggregated multi-tensor update family (beyond SGD): parity vs the
    # single-tensor kernels in the aggregation suite
    **{op: "tests/test_optimizer_aggregation.py" for op in [
        "multi_adam_update", "multi_nag_mom_update",
        "multi_rmsprop_update"]},
    # random samplers: distribution tests
    **{op: "tests/test_operator_extended.py" for op in [
        "_random_uniform", "_random_normal", "_random_gamma",
        "_random_exponential", "_random_poisson",
        "_random_negative_binomial",
        "_random_generalized_negative_binomial", "_random_randint",
        "_sample_uniform", "_sample_normal", "_sample_gamma",
        "_sample_exponential", "_sample_poisson", "_sample_multinomial",
        "_shuffle", "sample_unique_zipfian"]},
    # image ops
    **{op: "tests/test_image_ops.py" for op in [
        "_image_adjust_lighting", "_image_flip_left_right",
        "_image_flip_top_bottom", "_image_normalize",
        "_image_random_brightness", "_image_random_color_jitter",
        "_image_random_contrast", "_image_random_flip_left_right",
        "_image_random_flip_top_bottom", "_image_random_hue",
        "_image_random_lighting", "_image_random_saturation",
        "_image_resize", "_image_to_tensor"]},
    # rcnn / detection
    **{op: "tests/test_rcnn_ops.py" for op in [
        "Proposal", "MultiProposal", "PSROIPooling",
        "DeformablePSROIPooling", "_contrib_bipartite_matching"]},
    # vision extras
    **{op: "tests/test_vision_ops.py" for op in [
        "Correlation", "DeformableConvolution", "_contrib_fft",
        "_contrib_ifft", "_contrib_count_sketch",
        "MultiBoxTarget", "MultiBoxDetection"]},
    "ROIPooling": "tests/test_rcnn_ops.py",
    "SpatialTransformer": "tests/test_operator_extended.py",
    "BilinearSampler": "tests/test_operator_extended.py",
    # rnn stack
    "RNN": "tests/test_rnn.py",
    # quantization
    **{op: "tests/test_quantization_ops.py" for op in [
        "_contrib_quantize", "_contrib_quantize_v2", "_contrib_dequantize",
        "_contrib_requantize", "_contrib_quantized_conv",
        "_contrib_quantized_fully_connected", "_contrib_quantized_pooling",
        "_contrib_quantized_concat", "_contrib_quantized_flatten",
        "_quantized_fc_static", "_quantize_static", "_quantized_conv_v2",
        "_quantized_dense_v2"]},
    # pallas attention kernels
    **{op: "tests/test_pallas_ops.py" for op in [
        "_contrib_flash_attention", "_contrib_interleaved_matmul_selfatt_qk",
        "_contrib_interleaved_matmul_selfatt_valatt"]},
    # the sparse latent-attention language model's ops (ops/lm_ops.py)
    **{op: "tests/test_glm_moe_lite.py" for op in [
        "_contrib_rms_norm", "_contrib_swiglu_ffn", "_contrib_mla_attention",
        "_contrib_dropless_moe", "_contrib_blocked_attention"]},
    # the nemotron_h family's mixers (ops/lm_ops.py)
    **{op: "tests/test_nemotron_h.py" for op in [
        "_contrib_mamba2_mixer", "_contrib_gqa_attention"]},
    # the olmo_hybrid family's mixers (ops/lm_ops.py)
    **{op: "tests/test_olmo_hybrid.py" for op in [
        "_contrib_gated_deltanet_mixer", "_contrib_qk_norm_attention"]},
    # the mimo_v2 family's attention (ops/lm_ops.py)
    "_contrib_fused_qkv_attention": "tests/test_mimo_v2.py",
    # pallas fused conv epilogues (fwd+grad parity, fallback, fold)
    **{op: "tests/test_fused_epilogue.py" for op in [
        "_contrib_fused_bn_relu", "_contrib_fused_bn_add_relu"]},
    # symbolic control flow + graph-level sparse ops
    **{op: "tests/test_symbol_control_flow.py" for op in [
        "_foreach", "_while_loop", "_cond", "cast_storage",
        "sparse_retain", "_square_sum"]},
    # DGL graph-sampling family (host-side csr algorithms)
    **{op: "tests/test_graph_ops.py" for op in [
        "_contrib_dgl_adjacency", "_contrib_dgl_subgraph",
        "_contrib_dgl_csr_neighbor_uniform_sample",
        "_contrib_dgl_csr_neighbor_non_uniform_sample",
        "_contrib_dgl_graph_compact", "_contrib_edge_id"]},
    # round-4 tail closure: init ops, sampler-_like family, lazy sparse
    # updates, sparse containers (VERDICT r3 directive #3)
    **{op: "tests/test_op_tail.py" for op in [
        "_zeros", "_ones", "_full", "_eye", "_arange", "_grad_add",
        "_contrib_div_sqrt_dim", "_random_uniform_like",
        "_random_normal_like", "_random_exponential_like",
        "_random_gamma_like", "_random_poisson_like",
        "_random_negative_binomial_like",
        "_random_generalized_negative_binomial_like",
        "_sparse_sgd_update", "_sparse_sgd_mom_update",
        "_sparse_adam_update", "_sparse_retain", "_contrib_getnnz"]},
    # misc dedicated files
    "CTCLoss": "tests/test_ctc.py",
    "Custom": "tests/test_custom_op.py",
    "_subgraph": "tests/test_subgraph.py",
    "_index": "tests/test_ndarray.py",
    "_index_assign": "tests/test_ndarray.py",
    "_index_assign_scalar": "tests/test_ndarray.py",
    "SyncBatchNorm": "tests/test_gluon_contrib.py",
    "Dropout": "tests/test_gluon.py",
}
