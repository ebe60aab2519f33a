"""Registry-wide operator sweep (the reference's test_operator.py
discipline: forward goldens vs numpy for nearly every op, numeric-gradient
checks for the differentiable core, torch-cpu as the conv/pool/norm
oracle, plus a coverage gate so new ops must bring tests).
"""
import math

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops.registry import list_ops
from mxnet_tpu.test_utils import assert_almost_equal, check_numeric_gradient

RS = onp.random.RandomState(42)

# ---------------------------------------------------------------------------
# forward golden specs: op -> (input arrays, attrs, numpy reference fn)
# ---------------------------------------------------------------------------

POS = RS.uniform(0.5, 2.0, (3, 4)).astype(onp.float32)      # strictly +
SYM = RS.uniform(-1.0, 1.0, (3, 4)).astype(onp.float32)     # (-1, 1)
GT1 = RS.uniform(1.5, 3.0, (3, 4)).astype(onp.float32)      # > 1
ANY = RS.normal(0, 2, (3, 4)).astype(onp.float32)
B = RS.normal(0, 2, (3, 4)).astype(onp.float32)
ROW = RS.normal(0, 1, (1, 4)).astype(onp.float32)
INT = RS.randint(0, 3, (3, 4)).astype(onp.float32)
BOOL = (RS.rand(3, 4) > 0.5).astype(onp.float32)
BOOL2 = (RS.rand(3, 4) > 0.5).astype(onp.float32)

_erf = onp.vectorize(math.erf, otypes=[onp.float32])
_gamma_np = onp.vectorize(math.gamma, otypes=[onp.float32])
_lgamma = onp.vectorize(math.lgamma, otypes=[onp.float32])

UNARY = {
    "abs": (ANY, onp.abs),
    "arccos": (SYM, onp.arccos),
    "arccosh": (GT1, onp.arccosh),
    "arcsin": (SYM, onp.arcsin),
    "arcsinh": (ANY, onp.arcsinh),
    "arctan": (ANY, onp.arctan),
    "arctanh": (SYM * 0.9, onp.arctanh),
    "cbrt": (ANY, onp.cbrt),
    "ceil": (ANY, onp.ceil),
    "cos": (ANY, onp.cos),
    "cosh": (ANY, onp.cosh),
    "degrees": (ANY, onp.degrees),
    "erf": (ANY, _erf),
    "exp": (SYM, onp.exp),
    "expm1": (SYM, onp.expm1),
    "fix": (ANY, onp.fix),
    "floor": (ANY, onp.floor),
    "gamma": (POS, _gamma_np),
    "gammaln": (POS, _lgamma),
    "identity": (ANY, lambda x: x),
    "log": (POS, onp.log),
    "log10": (POS, onp.log10),
    "log1p": (POS, onp.log1p),
    "log2": (POS, onp.log2),
    "logical_not": (BOOL, lambda x: (x == 0).astype(onp.float32)),
    "negative": (ANY, onp.negative),
    "radians": (ANY, onp.radians),
    "reciprocal": (POS, onp.reciprocal),
    "relu": (ANY, lambda x: onp.maximum(x, 0)),
    "rint": (ANY, onp.rint),
    "rsqrt": (POS, lambda x: 1 / onp.sqrt(x)),
    "rcbrt": (POS, lambda x: 1 / onp.cbrt(x)),
    "sigmoid": (ANY, lambda x: 1 / (1 + onp.exp(-x))),
    "sign": (ANY, onp.sign),
    "sin": (ANY, onp.sin),
    "sinh": (ANY, onp.sinh),
    "softsign": (ANY, lambda x: x / (1 + onp.abs(x))),
    "sqrt": (POS, onp.sqrt),
    "square": (ANY, onp.square),
    "tan": (SYM, onp.tan),
    "tanh": (ANY, onp.tanh),
    "trunc": (ANY, onp.trunc),
    "erfinv": (SYM * 0.9, None),  # checked via erf(erfinv(x)) == x
    "zeros_like": (ANY, onp.zeros_like),
    "ones_like": (ANY, onp.ones_like),
}

BINARY = {
    "broadcast_add": ((ANY, ROW), onp.add),
    "broadcast_plus": ((ANY, ROW), onp.add),
    "broadcast_sub": ((ANY, ROW), onp.subtract),
    "broadcast_minus": ((ANY, ROW), onp.subtract),
    "broadcast_mul": ((ANY, ROW), onp.multiply),
    "broadcast_div": ((ANY, POS[:1]), onp.divide),
    "broadcast_power": ((POS, ROW), onp.power),
    "broadcast_maximum": ((ANY, ROW), onp.maximum),
    "broadcast_minimum": ((ANY, ROW), onp.minimum),
    "broadcast_mod": ((POS * 10, POS[:1]), onp.mod),
    "broadcast_hypot": ((ANY, ROW), onp.hypot),
    "broadcast_equal": ((INT, INT[:1]), lambda a, b: (a == b).astype("f")),
    "broadcast_not_equal": ((INT, INT[:1]),
                            lambda a, b: (a != b).astype("f")),
    "broadcast_greater": ((INT, INT[:1]), lambda a, b: (a > b).astype("f")),
    "broadcast_greater_equal": ((INT, INT[:1]),
                                lambda a, b: (a >= b).astype("f")),
    "broadcast_lesser": ((INT, INT[:1]), lambda a, b: (a < b).astype("f")),
    "broadcast_lesser_equal": ((INT, INT[:1]),
                               lambda a, b: (a <= b).astype("f")),
    "broadcast_logical_and": ((BOOL, BOOL2),
                              lambda a, b: ((a != 0) & (b != 0)).astype("f")),
    "broadcast_logical_or": ((BOOL, BOOL2),
                             lambda a, b: ((a != 0) | (b != 0)).astype("f")),
    "broadcast_logical_xor": ((BOOL, BOOL2),
                              lambda a, b: ((a != 0) ^ (b != 0)).astype("f")),
    "elemwise_add": ((ANY, B), onp.add),
    "elemwise_sub": ((ANY, B), onp.subtract),
    "elemwise_mul": ((ANY, B), onp.multiply),
    "elemwise_div": ((ANY, POS), onp.divide),
    "maximum": ((ANY, B), onp.maximum),
    "minimum": ((ANY, B), onp.minimum),
    "hypot": ((ANY, B), onp.hypot),
    "arctan2": ((ANY, POS), onp.arctan2),
    "ldexp": ((ANY, SYM), lambda a, b: a * onp.power(2.0, b)),
    "power": ((POS, B), onp.power),
    "mod": ((POS * 10, POS), onp.mod),
    "equal": ((INT, INT.T.reshape(3, 4)), lambda a, b: (a == b).astype("f")),
    "not_equal": ((INT, INT.T.reshape(3, 4)),
                  lambda a, b: (a != b).astype("f")),
    "greater": ((INT, INT.T.reshape(3, 4)), lambda a, b: (a > b).astype("f")),
    "greater_equal": ((INT, INT.T.reshape(3, 4)),
                      lambda a, b: (a >= b).astype("f")),
    "lesser": ((INT, INT.T.reshape(3, 4)), lambda a, b: (a < b).astype("f")),
    "lesser_equal": ((INT, INT.T.reshape(3, 4)),
                     lambda a, b: (a <= b).astype("f")),
    "logical_and": ((BOOL, BOOL2),
                    lambda a, b: ((a != 0) & (b != 0)).astype("f")),
    "logical_or": ((BOOL, BOOL2),
                   lambda a, b: ((a != 0) | (b != 0)).astype("f")),
    "logical_xor": ((BOOL, BOOL2),
                    lambda a, b: ((a != 0) ^ (b != 0)).astype("f")),
    "_add": ((ANY, B), onp.add),
    "_plus": ((ANY, B), onp.add),
    "_sub": ((ANY, B), onp.subtract),
    "_minus": ((ANY, B), onp.subtract),
    "_mul": ((ANY, B), onp.multiply),
    "_div": ((ANY, POS), onp.divide),
    "_mod": ((POS * 10, POS), onp.mod),
    "_power": ((POS, B), onp.power),
}

SCALAR = {  # op -> (input, scalar, numpy fn)
    "_plus_scalar": (ANY, 1.5, lambda x, s: x + s),
    "_minus_scalar": (ANY, 1.5, lambda x, s: x - s),
    "_rminus_scalar": (ANY, 1.5, lambda x, s: s - x),
    "_mul_scalar": (ANY, 1.5, lambda x, s: x * s),
    "_div_scalar": (ANY, 1.5, lambda x, s: x / s),
    "_rdiv_scalar": (POS, 1.5, lambda x, s: s / x),
    "_mod_scalar": (POS * 10, 1.5, lambda x, s: onp.mod(x, s)),
    "_rmod_scalar": (POS, 7.0, lambda x, s: onp.mod(s, x)),
    "_power_scalar": (POS, 2.0, lambda x, s: onp.power(x, s)),
    "_rpower_scalar": (SYM, 2.0, lambda x, s: onp.power(s, x)),
    "_maximum_scalar": (ANY, 0.5, lambda x, s: onp.maximum(x, s)),
    "_minimum_scalar": (ANY, 0.5, lambda x, s: onp.minimum(x, s)),
    "_hypot_scalar": (ANY, 1.5, lambda x, s: onp.hypot(x, s)),
    "_equal_scalar": (INT, 1.0, lambda x, s: (x == s).astype("f")),
    "_not_equal_scalar": (INT, 1.0, lambda x, s: (x != s).astype("f")),
    "_greater_scalar": (INT, 1.0, lambda x, s: (x > s).astype("f")),
    "_greater_equal_scalar": (INT, 1.0, lambda x, s: (x >= s).astype("f")),
    "_lesser_scalar": (INT, 1.0, lambda x, s: (x < s).astype("f")),
    "_lesser_equal_scalar": (INT, 1.0, lambda x, s: (x <= s).astype("f")),
}

REDUCE = {
    "sum": onp.sum, "mean": onp.mean, "prod": onp.prod,
    "max": onp.max, "min": onp.min,
    "nansum": onp.nansum, "nanprod": onp.nanprod,
}


@pytest.mark.parametrize("op_name", sorted(UNARY))
def test_unary_forward(op_name):
    x, ref = UNARY[op_name]
    out = getattr(nd, op_name)(mx.nd.array(x)).asnumpy()
    if op_name == "erfinv":
        assert_almost_equal(_erf(out), x, rtol=1e-4, atol=1e-5)
        return
    assert_almost_equal(out, ref(x).astype(onp.float32), rtol=1e-4,
                        atol=1e-5)


@pytest.mark.parametrize("op_name", sorted(BINARY))
def test_binary_forward(op_name):
    (a, b), ref = BINARY[op_name]
    out = getattr(nd, op_name)(mx.nd.array(a), mx.nd.array(b)).asnumpy()
    assert_almost_equal(out, ref(a, b).astype(onp.float32), rtol=1e-4,
                        atol=1e-5)


@pytest.mark.parametrize("op_name", sorted(SCALAR))
def test_scalar_forward(op_name):
    x, s, ref = SCALAR[op_name]
    out = getattr(nd, op_name)(mx.nd.array(x), scalar=s).asnumpy()
    assert_almost_equal(out, ref(x, s).astype(onp.float32), rtol=1e-4,
                        atol=1e-5)


@pytest.mark.parametrize("op_name", sorted(REDUCE))
@pytest.mark.parametrize("axis,keepdims", [(None, False), (0, False),
                                           (1, True)])
def test_reduce_forward(op_name, axis, keepdims):
    x = ANY
    kw = {"keepdims": keepdims}
    if axis is not None:
        kw["axis"] = axis
    out = getattr(nd, op_name)(mx.nd.array(x), **kw).asnumpy()
    ref = REDUCE[op_name](x, axis=axis, keepdims=keepdims)
    assert_almost_equal(out, onp.asarray(ref, onp.float32), rtol=1e-4,
                        atol=1e-5)


def test_shape_ops_forward():
    x = RS.normal(0, 1, (2, 3, 4)).astype(onp.float32)
    a = mx.nd.array(x)
    assert_almost_equal(nd.transpose(a, axes=(2, 0, 1)).asnumpy(),
                        x.transpose(2, 0, 1))
    assert_almost_equal(nd.swapaxes(a, dim1=0, dim2=2).asnumpy(),
                        x.swapaxes(0, 2))
    assert_almost_equal(nd.expand_dims(a, axis=1).asnumpy(),
                        x[:, None])
    assert_almost_equal(nd.flip(a, axis=1).asnumpy() if hasattr(nd, "flip")
                        else nd.reverse(a, axis=(1,)).asnumpy(),
                        x[:, ::-1])
    assert_almost_equal(nd.tile(a, reps=(2, 1, 1)).asnumpy(),
                        onp.tile(x, (2, 1, 1)))
    assert_almost_equal(nd.repeat(a, repeats=2, axis=1).asnumpy(),
                        onp.repeat(x, 2, axis=1))
    assert_almost_equal(nd.slice(a, begin=(0, 1, 0), end=(2, 3, 2)).asnumpy(),
                        x[0:2, 1:3, 0:2])
    assert_almost_equal(nd.slice_axis(a, axis=2, begin=1, end=3).asnumpy(),
                        x[:, :, 1:3])
    assert_almost_equal(nd.clip(a, a_min=-0.5, a_max=0.5).asnumpy(),
                        onp.clip(x, -0.5, 0.5))
    assert_almost_equal(nd.broadcast_to(mx.nd.array(x[:1]),
                                        shape=(2, 3, 4)).asnumpy(),
                        onp.broadcast_to(x[:1], (2, 3, 4)))
    assert_almost_equal(nd.broadcast_like(mx.nd.array(x[:1]), a).asnumpy(),
                        onp.broadcast_to(x[:1], (2, 3, 4)))
    assert_almost_equal(nd.flatten(a).asnumpy(), x.reshape(2, -1))
    assert_almost_equal(nd.Reshape(a, shape=(-1, 4)).asnumpy(),
                        x.reshape(-1, 4))
    assert_almost_equal(nd.squeeze(nd.expand_dims(a, axis=0)).asnumpy(), x)


def test_index_ops_forward():
    x = RS.normal(0, 1, (5, 4)).astype(onp.float32)
    idx = onp.array([0, 2, 4], onp.float32)
    a = mx.nd.array(x)
    assert_almost_equal(nd.take(a, mx.nd.array(idx)).asnumpy(),
                        x[idx.astype(int)])
    pick_i = onp.array([0, 1, 2, 3, 0], onp.float32)
    assert_almost_equal(
        nd.pick(a, mx.nd.array(pick_i), axis=1).asnumpy(),
        x[onp.arange(5), pick_i.astype(int)])
    assert_almost_equal(
        nd.one_hot(mx.nd.array(idx), depth=5).asnumpy(),
        onp.eye(5, dtype=onp.float32)[idx.astype(int)])
    ind = onp.array([[0, 1], [2, 3]], onp.float32)  # gather_nd indices
    assert_almost_equal(
        nd.gather_nd(a, mx.nd.array(ind)).asnumpy(),
        x[ind[0].astype(int), ind[1].astype(int)])
    assert_almost_equal(nd.diag(a).asnumpy(), onp.diag(x))
    assert_almost_equal(nd.tril(a).asnumpy(), onp.tril(x))
    srt = nd.sort(a, axis=1).asnumpy()
    assert_almost_equal(srt, onp.sort(x, axis=1))
    ags = nd.argsort(a, axis=1).asnumpy()
    assert_almost_equal(ags, onp.argsort(x, axis=1).astype(onp.float32))
    assert_almost_equal(nd.argmax(a, axis=1).asnumpy(),
                        onp.argmax(x, axis=1).astype(onp.float32))
    assert_almost_equal(nd.argmin(a, axis=1).asnumpy(),
                        onp.argmin(x, axis=1).astype(onp.float32))
    mask = onp.array([1, 0, 1, 0, 1], onp.float32)
    assert_almost_equal(nd.boolean_mask(a, mx.nd.array(mask)).asnumpy(),
                        x[mask.astype(bool)])
    assert_almost_equal(
        nd.where(mx.nd.array(BOOL), mx.nd.array(ANY),
                 mx.nd.array(B)).asnumpy(),
        onp.where(BOOL != 0, ANY, B))


def test_linalg_ops_forward():
    a = RS.normal(0, 1, (4, 4)).astype(onp.float32)
    spd = (a @ a.T + 4 * onp.eye(4)).astype(onp.float32)
    A = mx.nd.array(spd)
    assert_almost_equal(nd.linalg_potrf(A).asnumpy(),
                        onp.linalg.cholesky(spd), rtol=1e-4, atol=1e-4)
    assert_almost_equal(nd.linalg_inverse(A).asnumpy(),
                        onp.linalg.inv(spd), rtol=1e-3, atol=1e-4)
    assert_almost_equal(nd.linalg_det(A).asnumpy(),
                        onp.linalg.det(spd), rtol=1e-3, atol=1e-3)
    B_ = RS.normal(0, 1, (4, 3)).astype(onp.float32)
    assert_almost_equal(
        nd.linalg_gemm2(A, mx.nd.array(B_)).asnumpy(), spd @ B_,
        rtol=1e-4, atol=1e-4)
    assert_almost_equal(nd.dot(A, mx.nd.array(B_)).asnumpy(), spd @ B_,
                        rtol=1e-4, atol=1e-4)
    bx = RS.normal(0, 1, (2, 3, 4)).astype(onp.float32)
    by = RS.normal(0, 1, (2, 4, 5)).astype(onp.float32)
    assert_almost_equal(nd.batch_dot(mx.nd.array(bx),
                                     mx.nd.array(by)).asnumpy(),
                        onp.einsum("bij,bjk->bik", bx, by),
                        rtol=1e-4, atol=1e-4)


def test_linalg_long_tail():
    a = RS.normal(0, 1, (4, 4)).astype(onp.float32)
    spd = (a @ a.T + 4 * onp.eye(4)).astype(onp.float32)
    L = onp.linalg.cholesky(spd)
    A = mx.nd.array(spd)
    Lnd = mx.nd.array(L)
    B_ = RS.normal(0, 1, (4, 3)).astype(onp.float32)
    # potri: inverse from cholesky factor
    assert_almost_equal(nd.linalg_potri(Lnd).asnumpy(),
                        onp.linalg.inv(spd), rtol=1e-3, atol=1e-3)
    # trmm: triangular matmul L @ B
    assert_almost_equal(nd.linalg_trmm(Lnd, mx.nd.array(B_)).asnumpy(),
                        L @ B_, rtol=1e-4, atol=1e-4)
    # trsm: solve L X = B
    X = nd.linalg_trsm(Lnd, mx.nd.array(B_)).asnumpy()
    assert_almost_equal(L @ X, B_, rtol=1e-3, atol=1e-3)
    # syrk: A @ A.T
    assert_almost_equal(nd.linalg_syrk(A).asnumpy(), spd @ spd.T,
                        rtol=1e-3, atol=1e-3)
    # slogdet / sumlogdiag
    sign, logdet = onp.linalg.slogdet(spd)
    s_out = nd.linalg_slogdet(A)
    assert_almost_equal(s_out[0].asnumpy(), sign, rtol=1e-4, atol=1e-4)
    assert_almost_equal(s_out[1].asnumpy(), logdet, rtol=1e-4, atol=1e-4)
    assert_almost_equal(nd.linalg_sumlogdiag(Lnd).asnumpy(),
                        onp.log(onp.diag(L)).sum(), rtol=1e-4, atol=1e-4)
    # extractdiag / makediag
    assert_almost_equal(nd.linalg_extractdiag(A).asnumpy(), onp.diag(spd))
    v = RS.normal(0, 1, (4,)).astype(onp.float32)
    assert_almost_equal(nd.linalg_makediag(mx.nd.array(v)).asnumpy(),
                        onp.diag(v))


def test_misc_ops_forward():
    x = RS.normal(0, 1, (2, 3, 4, 4)).astype(onp.float32)
    a = mx.nd.array(x)
    # smooth_l1
    y = RS.normal(0, 2, (3, 4)).astype(onp.float32)
    s = nd.smooth_l1(mx.nd.array(y), scalar=1.0).asnumpy()
    ref = onp.where(onp.abs(y) < 1, 0.5 * y * y, onp.abs(y) - 0.5)
    assert_almost_equal(s, ref, rtol=1e-5, atol=1e-6)
    # hard_sigmoid
    h = nd.hard_sigmoid(mx.nd.array(y)).asnumpy()
    assert_almost_equal(h, onp.clip(0.2 * y + 0.5, 0, 1), rtol=1e-5,
                        atol=1e-6)
    # slice_like
    big = mx.nd.array(RS.normal(0, 1, (4, 6)).astype("f"))
    small = mx.nd.array(onp.zeros((2, 3), "f"))
    assert nd.slice_like(big, small).shape == (2, 3)
    # histogram
    data = onp.array([0.1, 0.4, 0.6, 0.9, 0.2], "f")
    cnt, edges = nd.histogram(mx.nd.array(data), bin_cnt=2, range=(0., 1.))
    assert_almost_equal(cnt.asnumpy(), onp.array([3., 2.], "f"))
    # scatter_nd
    idx = mx.nd.array(onp.array([[0, 1], [1, 0]], "f"))
    vals = mx.nd.array(onp.array([9., 8.], "f"))
    out = nd.scatter_nd(vals, idx, shape=(2, 2)).asnumpy()
    assert out[0, 1] == 9.0 and out[1, 0] == 8.0
    # depth_to_space / space_to_depth roundtrip
    d = mx.nd.array(RS.normal(0, 1, (1, 8, 2, 2)).astype("f"))
    rt = nd.space_to_depth(nd.depth_to_space(d, block_size=2),
                           block_size=2)
    assert_almost_equal(rt.asnumpy(), d.asnumpy())
    # shape_array / size_array
    assert list(nd.shape_array(a).asnumpy()) == [2, 3, 4, 4]
    assert int(nd.size_array(a).asnumpy()[0]) == 96
    # argmax_channel
    am = nd.argmax_channel(mx.nd.array(y)).asnumpy()
    assert_almost_equal(am, onp.argmax(y, axis=1).astype("f"))
    # broadcast_axis
    one = mx.nd.array(onp.ones((1, 3), "f"))
    assert nd.broadcast_axis(one, axis=0, size=4).shape == (4, 3)
    # topk values
    tk = nd.topk(mx.nd.array(y), k=2, ret_typ="value", axis=1).asnumpy()
    ref_tk = -onp.sort(-y, axis=1)[:, :2]
    assert_almost_equal(tk, ref_tk)
    # Pad
    p = nd.Pad(a, mode="constant",
               pad_width=(0, 0, 0, 0, 1, 1, 1, 1)).asnumpy()
    assert p.shape == (2, 3, 6, 6) and p[0, 0, 0, 0] == 0
    # UpSampling
    up = nd.UpSampling(a, scale=2, sample_type="nearest").asnumpy()
    assert up.shape == (2, 3, 8, 8)
    assert_almost_equal(up[:, :, ::2, ::2], x)
    # moments
    mean, var = nd.moments(mx.nd.array(y), axes=(0,))
    assert_almost_equal(mean.asnumpy(), y.mean(axis=0), rtol=1e-5,
                        atol=1e-6)
    assert_almost_equal(var.asnumpy(), y.var(axis=0), rtol=1e-4,
                        atol=1e-5)
    # L2Normalization
    l2 = nd.L2Normalization(mx.nd.array(y)).asnumpy()
    ref_l2 = y / onp.sqrt((y * y).sum(axis=1, keepdims=True) + 1e-10)
    assert_almost_equal(l2, ref_l2, rtol=1e-4, atol=1e-5)


def test_sample_ops_forward():
    """Per-distribution-parameter sampling (sample_* take array params)."""
    mx.random.seed(11)
    mu = mx.nd.array(onp.array([0.0, 10.0], "f"))
    sg = mx.nd.array(onp.array([1.0, 2.0], "f"))
    s = nd.sample_normal(mu, sg, shape=(20000,)).asnumpy()
    assert s.shape == (2, 20000)
    assert abs(s[0].mean()) < 0.1 and abs(s[1].mean() - 10) < 0.1
    al = mx.nd.array(onp.array([2.0, 6.0], "f"))
    be = mx.nd.array(onp.array([1.0, 0.5], "f"))
    g = nd.sample_gamma(al, be, shape=(20000,)).asnumpy()
    assert abs(g[0].mean() - 2.0) < 0.1 and abs(g[1].mean() - 3.0) < 0.1
    lo = mx.nd.array(onp.array([0.0], "f"))
    hi = mx.nd.array(onp.array([4.0], "f"))
    u = nd.sample_uniform(lo, hi, shape=(20000,)).asnumpy()
    assert abs(u.mean() - 2.0) < 0.1
    nb = nd.random_negative_binomial(k=5, p=0.5, shape=(20000,)).asnumpy()
    assert abs(nb.mean() - 5.0) < 0.2  # mean = k(1-p)/p
    gnb = nd.random_generalized_negative_binomial(
        mu=3.0, alpha=0.2, shape=(20000,)).asnumpy()
    assert abs(gnb.mean() - 3.0) < 0.2


# ---------------------------------------------------------------------------
# torch-cpu oracle for NN core ops
# ---------------------------------------------------------------------------

def test_convolution_vs_torch():
    import torch
    import torch.nn.functional as F
    x = RS.normal(0, 1, (2, 3, 8, 8)).astype(onp.float32)
    w = RS.normal(0, 0.5, (5, 3, 3, 3)).astype(onp.float32)
    b = RS.normal(0, 0.5, (5,)).astype(onp.float32)
    out = nd.Convolution(mx.nd.array(x), mx.nd.array(w), mx.nd.array(b),
                         kernel=(3, 3), num_filter=5, stride=(2, 2),
                         pad=(1, 1)).asnumpy()
    ref = F.conv2d(torch.from_numpy(x), torch.from_numpy(w),
                   torch.from_numpy(b), stride=2, padding=1).numpy()
    assert_almost_equal(out, ref, rtol=1e-3, atol=1e-4)


def test_pooling_vs_torch():
    import torch
    import torch.nn.functional as F
    x = RS.normal(0, 1, (2, 3, 8, 8)).astype(onp.float32)
    out = nd.Pooling(mx.nd.array(x), kernel=(2, 2), stride=(2, 2),
                     pool_type="max").asnumpy()
    ref = F.max_pool2d(torch.from_numpy(x), 2, 2).numpy()
    assert_almost_equal(out, ref, rtol=1e-5, atol=1e-6)
    out = nd.Pooling(mx.nd.array(x), kernel=(2, 2), stride=(2, 2),
                     pool_type="avg").asnumpy()
    ref = F.avg_pool2d(torch.from_numpy(x), 2, 2).numpy()
    assert_almost_equal(out, ref, rtol=1e-5, atol=1e-6)


def test_batchnorm_vs_torch():
    import torch
    import torch.nn.functional as F
    x = RS.normal(0, 1, (4, 3, 5, 5)).astype(onp.float32)
    g = RS.uniform(0.5, 1.5, (3,)).astype(onp.float32)
    be = RS.normal(0, 0.5, (3,)).astype(onp.float32)
    out, _, _ = nd.BatchNorm(mx.nd.array(x), mx.nd.array(g),
                             mx.nd.array(be), mx.nd.zeros((3,)),
                             mx.nd.ones((3,)), fix_gamma=False,
                             training=True, eps=1e-5)
    ref = F.batch_norm(torch.from_numpy(x), torch.zeros(3), torch.ones(3),
                       torch.from_numpy(g), torch.from_numpy(be),
                       training=True, eps=1e-5).numpy()
    assert_almost_equal(out.asnumpy(), ref, rtol=1e-3, atol=1e-4)


def test_layernorm_vs_torch():
    import torch
    import torch.nn.functional as F
    x = RS.normal(0, 1, (4, 6)).astype(onp.float32)
    g = RS.uniform(0.5, 1.5, (6,)).astype(onp.float32)
    be = RS.normal(0, 0.5, (6,)).astype(onp.float32)
    out = nd.LayerNorm(mx.nd.array(x), mx.nd.array(g),
                       mx.nd.array(be), eps=1e-5).asnumpy()
    ref = F.layer_norm(torch.from_numpy(x), (6,), torch.from_numpy(g),
                       torch.from_numpy(be), eps=1e-5).numpy()
    assert_almost_equal(out, ref, rtol=1e-4, atol=1e-5)
    # fp32 keeps the two-pass stats: a large common offset must not
    # cancel the variance (the one-pass E[x^2]-E[x]^2 form is reserved
    # for bf16, whose fp32 accumulator has the mantissa headroom)
    xo = (x + 1e4).astype(onp.float32)
    out = nd.LayerNorm(mx.nd.array(xo), mx.nd.array(g),
                       mx.nd.array(be), eps=1e-5).asnumpy()
    ref = F.layer_norm(torch.from_numpy(xo), (6,), torch.from_numpy(g),
                       torch.from_numpy(be), eps=1e-5).numpy()
    assert_almost_equal(out, ref, rtol=1e-3, atol=2e-3)


def test_softmax_family_vs_torch():
    import torch
    import torch.nn.functional as F
    x = RS.normal(0, 2, (4, 6)).astype(onp.float32)
    t = torch.from_numpy(x)
    assert_almost_equal(nd.softmax(mx.nd.array(x)).asnumpy(),
                        F.softmax(t, dim=-1).numpy(), rtol=1e-5, atol=1e-6)
    assert_almost_equal(nd.log_softmax(mx.nd.array(x)).asnumpy(),
                        F.log_softmax(t, dim=-1).numpy(), rtol=1e-4,
                        atol=1e-5)
    assert_almost_equal(nd.softmin(mx.nd.array(x)).asnumpy(),
                        F.softmin(t, dim=-1).numpy(), rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# numeric-gradient checks (the differentiable core)
# ---------------------------------------------------------------------------

GRAD_UNARY = ["exp", "log", "sqrt", "square", "tanh", "sigmoid", "sin",
              "cos", "arctan", "cbrt", "softsign", "rsqrt", "reciprocal",
              "expm1", "log1p", "arcsinh", "erf"]


@pytest.mark.parametrize("op_name", GRAD_UNARY)
def test_unary_numeric_grad(op_name):
    x = RS.uniform(0.5, 1.5, (2, 3)).astype(onp.float32)
    fn = getattr(nd, op_name)
    check_numeric_gradient(lambda a: fn(a), [x])


@pytest.mark.parametrize("op_name", ["broadcast_add", "broadcast_mul",
                                     "broadcast_div", "elemwise_sub",
                                     "maximum", "hypot"])
def test_binary_numeric_grad(op_name):
    a = RS.uniform(0.5, 1.5, (2, 3)).astype(onp.float32)
    b = RS.uniform(0.5, 1.5, (1, 3)).astype(onp.float32)
    if op_name in ("elemwise_sub", "maximum", "hypot"):
        b = RS.uniform(0.5, 1.5, (2, 3)).astype(onp.float32)
    fn = getattr(nd, op_name)
    check_numeric_gradient(lambda x, y: fn(x, y), [a, b])


def test_matmul_numeric_grad():
    a = RS.uniform(-1, 1, (3, 4)).astype(onp.float32)
    b = RS.uniform(-1, 1, (4, 2)).astype(onp.float32)
    check_numeric_gradient(lambda x, y: nd.dot(x, y), [a, b])


def test_softmax_numeric_grad():
    x = RS.uniform(-1, 1, (3, 4)).astype(onp.float32)
    check_numeric_gradient(
        lambda a: (nd.softmax(a) * mx.nd.array(POS)).sum(), [x],
        rtol=2e-2, atol=1e-3)


def test_reduce_numeric_grad():
    x = RS.uniform(0.5, 1.5, (3, 4)).astype(onp.float32)
    check_numeric_gradient(lambda a: nd.sum(a, axis=1), [x])
    check_numeric_gradient(lambda a: nd.mean(a), [x])
    check_numeric_gradient(lambda a: nd.norm(a), [x])


def test_conv_numeric_grad():
    x = RS.uniform(-1, 1, (1, 2, 5, 5)).astype(onp.float32)
    w = RS.uniform(-1, 1, (3, 2, 3, 3)).astype(onp.float32)
    check_numeric_gradient(
        lambda a, b: nd.Convolution(a, b, kernel=(3, 3), num_filter=3,
                                    no_bias=True),
        [x, w], rtol=2e-2, atol=1e-3)


# ---------------------------------------------------------------------------
# random ops: statistical smoke
# ---------------------------------------------------------------------------

def test_random_ops_statistics():
    mx.random.seed(7)
    n = 50_000
    u = nd.random_uniform(low=0.0, high=2.0, shape=(n,)).asnumpy()
    assert 0.95 < u.mean() < 1.05 and u.min() >= 0 and u.max() <= 2
    g = nd.random_normal(loc=1.0, scale=2.0, shape=(n,)).asnumpy()
    assert abs(g.mean() - 1.0) < 0.05 and abs(g.std() - 2.0) < 0.05
    p = nd.random_poisson(lam=4.0, shape=(n,)).asnumpy()
    assert abs(p.mean() - 4.0) < 0.1
    e = nd.random_exponential(lam=2.0, shape=(n,)).asnumpy()
    assert abs(e.mean() - 0.5) < 0.02
    r = nd.random_randint(low=0, high=10, shape=(n,)).asnumpy()
    assert r.min() >= 0 and r.max() <= 9 and abs(r.mean() - 4.5) < 0.1
    gm = nd.random_gamma(alpha=3.0, beta=2.0, shape=(n,)).asnumpy()
    assert abs(gm.mean() - 6.0) < 0.15
    s = nd.shuffle(mx.nd.array(onp.arange(100, dtype="f"))).asnumpy()
    assert sorted(s.tolist()) == list(range(100))
    m = nd.multinomial(mx.nd.array(onp.array([[0.1, 0.9]], "f")),
                       shape=1000).asnumpy()
    assert 850 < (m == 1).sum() < 950


# ---------------------------------------------------------------------------
# coverage gate: every registry op must be exercised somewhere in tests/
# ---------------------------------------------------------------------------

COVERED_ELSEWHERE = {
    # the decoder-block ops of PR 26 (RMS norm, partial rotary, causal
    # convolutions over the sequence, CCA's q/k/v, the sparse experts, the
    # blocked tied cross-entropy): tests/test_zaya.py, each against the
    # plain float32 reference or a dense composition
    "RMSNorm", "rms_norm", "l2_normalize", "rotary_embedding",
    "causal_conv1d", "_contrib_cca_qkv", "cca_qkv",
    "_contrib_sparse_experts", "sparse_experts",
    "tied_softmax_cross_entropy",
    # the chunked state-space scan of PR 30: tests/test_nemotron_h.py,
    # value and every gradient against the step-by-step recurrence
    "_contrib_ssd_chunk_scan", "ssd_chunk_scan",
    # the tile count of a mask given as data (PR 33): tests/test_sdar.py,
    # against the kernels' own summary at the planned blocks
    "_contrib_attention_mask_tiles", "attention_mask_tiles",
    # exercised by dedicated test files: test_operator.py (NN core),
    # test_rnn.py (RNN), test_gluon.py (layers), test_symbol.py /
    # test_module.py (output ops), test_amp.py (amp_cast), test_loss.py,
    # test_autograd.py (BlockGrad/stop_gradient), test_control_flow.py
    # BatchNormAddRelu: fused BN->add->ReLU epilogue, fwd+bwd covered by
    # tests/test_fused_bn_epilogue.py
    "BatchNormAddRelu", "_contrib_BatchNormAddRelu",
    "Activation", "BatchNorm", "BatchNorm_v1", "BlockGrad",
    "BlockGrad_inner", "Cast", "Convolution", "Convolution_v1",
    "Deconvolution", "Dropout", "Embedding", "Flatten", "FullyConnected",
    "GroupNorm", "InstanceNorm", "LRN", "LayerNorm",
    "LeakyReLU", "LinearRegressionOutput", "LogisticRegressionOutput",
    "MAERegressionOutput", "Pooling", "Pooling_v1", "RNN",
    "Reshape", "SequenceLast", "SequenceMask", "SequenceReverse",
    "SliceChannel", "Softmax", "SoftmaxActivation", "SoftmaxOutput",
    "SwapAxis", "amp_cast", "make_loss",
    "softmax_output", "softmax_cross_entropy", "stop_gradient",
    "stop_gradient_identity", "_copy", "cast",
    "norm", "pow", "slice_channel", "broadcast_axes",
    # tested in this file via their canonical names (see the dedicated
    # forward tests above)
    "L2Normalization", "Pad", "UpSampling", "moments", "smooth_l1",
    "hard_sigmoid", "pad", "histogram", "scatter_nd", "topk",
    "argmax_channel", "broadcast_axis", "slice_like",
    "depth_to_space", "space_to_depth", "shape_array", "size_array",
    "linalg_extractdiag", "linalg_makediag", "linalg_potri",
    "linalg_slogdet", "linalg_sumlogdiag", "linalg_syrk", "linalg_trmm",
    "linalg_trsm",
    "_sample_gamma", "_sample_multinomial", "_sample_normal",
    "_sample_uniform", "sample_gamma", "sample_multinomial",
    "sample_normal", "sample_uniform", "normal", "uniform", "randint",
    "_random_exponential", "_random_gamma", "_random_normal",
    "_random_poisson", "_random_randint", "_random_uniform", "_shuffle",
    "_random_negative_binomial",
    "_random_generalized_negative_binomial",
    "random_negative_binomial", "random_generalized_negative_binomial",
    "multinomial", "shuffle",
    # tested in tests/test_quantization.py (golden-value checks vs numpy
    # quantization math and the float ops)
    "quantize", "_contrib_quantize", "quantize_v2", "_contrib_quantize_v2",
    "dequantize", "_contrib_dequantize", "requantize", "_contrib_requantize",
    "quantized_conv", "_contrib_quantized_conv",
    "quantized_fully_connected", "_contrib_quantized_fully_connected",
    "quantized_pooling", "_contrib_quantized_pooling",
    "quantized_flatten", "_contrib_quantized_flatten",
    "quantized_elemwise_add", "_contrib_quantized_elemwise_add",
    "quantized_act", "_contrib_quantized_act",
    # tested in tests/test_flash_attention.py (kernel + op + vjp)
    "flash_attention", "_contrib_flash_attention",
    # BSHD layout variant: tests/test_flash_attention.py (bshd kernels)
    "flash_attention_bshd", "_contrib_flash_attention_bshd",
    # tests/test_transformer.py::test_gather_positions_op
    "gather_positions", "_contrib_gather_positions",
    # tested in tests/test_round5_ops.py (reference-oracle checks)
    "SVMOutput", "svm_output", "IdentityAttachKLSparseReg",
    "identity_attach_KL_sparse_reg", "linalg_gelqf",
    "_ravel_multi_index", "ravel_multi_index", "_unravel_index",
    "unravel_index",
    # tested in tests/test_custom_op.py (imperative/gluon/module paths)
    "Custom", "custom",
    # tested in tests/test_contrib_extras.py (numpy-oracle checks)
    "khatri_rao", "_contrib_krprod",
    "_contrib_arange_like", "arange_like",
    "_contrib_allclose", "allclose",
    "_contrib_boolean_mask", "boolean_mask",
    "_contrib_hawkesll", "hawkesll",
    # tested in tests/test_detection_ops.py (value + SSD training checks)
    "_contrib_MultiBoxTarget", "MultiBoxTarget",
    "_contrib_MultiBoxDetection", "MultiBoxDetection",
    "_contrib_Proposal", "Proposal",
    "_contrib_MultiProposal", "MultiProposal",
    "_contrib_PSROIPooling", "PSROIPooling",
    # tested in tests/test_transformer.py (numpy-oracle value checks)
    "_contrib_div_sqrt_dim", "div_sqrt_dim",
    "_contrib_interleaved_matmul_selfatt_qk",
    "interleaved_matmul_selfatt_qk",
    "_contrib_interleaved_matmul_selfatt_valatt",
    "interleaved_matmul_selfatt_valatt",
    "_contrib_interleaved_matmul_encdec_qk",
    "interleaved_matmul_encdec_qk",
    "_contrib_interleaved_matmul_encdec_valatt",
    "interleaved_matmul_encdec_valatt",
    # tested in tests/test_gluon_contrib.py (layer-level value checks)
    "_contrib_SyncBatchNorm", "SyncBatchNorm",
    "_contrib_DeformableConvolution", "DeformableConvolution",
    # tested in tests/test_vision_ops.py (golden-value checks)
    "BilinearSampler", "bilinear_sampler", "GridGenerator",
    "grid_generator", "SpatialTransformer", "spatial_transformer",
    "ROIPooling", "roi_pooling", "_contrib_ROIAlign", "ROIAlign",
    "_contrib_BilinearResize2D", "BilinearResize2D",
    "_contrib_AdaptiveAvgPooling2D", "AdaptiveAvgPooling2D",
    "_contrib_box_iou", "box_iou", "_contrib_box_nms", "box_nms",
    "_contrib_bipartite_matching", "bipartite_matching",
    "_contrib_MultiBoxPrior", "MultiBoxPrior", "Correlation", "correlation",
    "_contrib_div_sqrt_dim", "div_sqrt_dim", "_contrib_quadratic",
    "quadratic", "_contrib_index_array", "index_array",
    "_contrib_index_copy", "index_copy", "_contrib_fft", "fft",
    "_contrib_ifft", "ifft", "_contrib_count_sketch", "count_sketch",
    "_contrib_gradient_multiplier", "gradient_multiplier",
    "all_finite", "multi_all_finite",
    # aliases of tested canonical ops
    "activation", "batch_norm", "convolution", "deconvolution", "dropout",
    "fully_connected", "layer_norm", "linear_regression_output",
    "logistic_regression_output", "lrn", "pooling", "flatten", "reshape",
    "reverse", "flip", "swapaxes", "transpose", "squeeze", "expand_dims",
    "slice", "slice_axis", "tile", "repeat", "clip", "broadcast_to",
    "broadcast_like", "take", "pick", "one_hot", "gather_nd", "diag",
    "tril", "sort", "argsort", "argmax", "argmin",
    "where", "dot", "batch_dot", "linalg_det", "linalg_gemm",
    "linalg_gemm2", "linalg_inverse", "linalg_potrf", "max_axis",
    "min_axis", "sum_axis", "log_softmax", "softmin", "softmax",
    "random_exponential", "random_gamma", "random_normal",
    "random_poisson", "random_randint", "random_uniform",
}


def test_registry_coverage():
    """Every registered op is exercised by the sweep or a dedicated test.
    Adding an op without a test fails here (reference test_operator.py
    covers 'nearly every op')."""
    tested = (set(UNARY) | set(BINARY) | set(SCALAR) | set(REDUCE)
              | COVERED_ELSEWHERE)
    missing = [op for op in list_ops() if op not in tested]
    assert not missing, "untested registry ops: %r" % missing
