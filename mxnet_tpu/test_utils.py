"""Test helpers (reference: python/mxnet/test_utils.py — assert_almost_equal,
check_numeric_gradient finite differences, check_consistency cpu-vs-device,
rand_ndarray, default_context)."""
from __future__ import annotations

import numpy as _np

from .context import Context, cpu, current_context
from .ndarray import NDArray, array
from . import ndarray as nd
from . import autograd

__all__ = ["default_context", "set_default_context", "assert_almost_equal",
           "almost_equal", "same", "rand_ndarray", "rand_shape_2d",
           "rand_shape_3d", "rand_shape_nd", "check_numeric_gradient",
           "check_consistency", "simple_forward"]


def default_context():
    """Context under test: the process default (context.py)."""
    return current_context()


def set_default_context(ctx):
    Context._default_ctx.value = ctx


def same(a, b):
    return _np.array_equal(a, b)


def almost_equal(a, b, rtol=None, atol=None, equal_nan=False):
    rtol = 1e-5 if rtol is None else rtol
    atol = 1e-20 if atol is None else atol
    return _np.allclose(a, b, rtol=rtol, atol=atol, equal_nan=equal_nan)


def assert_almost_equal(a, b, rtol=None, atol=None, names=("a", "b"),
                        equal_nan=False):
    if isinstance(a, NDArray):
        a = a.asnumpy()
    if isinstance(b, NDArray):
        b = b.asnumpy()
    rtol = 1e-5 if rtol is None else rtol
    atol = 1e-6 if atol is None else atol
    if not _np.allclose(_np.asarray(a, dtype=_np.float64),
                        _np.asarray(b, dtype=_np.float64),
                        rtol=rtol, atol=atol, equal_nan=equal_nan):
        err = _np.max(_np.abs(_np.asarray(a, dtype=_np.float64)
                              - _np.asarray(b, dtype=_np.float64)))
        raise AssertionError("%s and %s differ: max abs err %g (rtol=%g atol=%g)\n%s\n%s"
                             % (names[0], names[1], err, rtol, atol, a, b))


# The rand_* helpers below deliberately stay on numpy's global RNG: they
# are TEST-support entropy, and the suite's conftest seeds np.random per
# test (the @with_seed contract), while the framework stream must keep an
# undisturbed draw sequence for mx.random.seed reproducibility tests.
def rand_shape_2d(dim0=10, dim1=10):
    return (_np.random.randint(1, dim0 + 1),  # mxlint: disable=RNG001
            _np.random.randint(1, dim1 + 1))  # mxlint: disable=RNG001


def rand_shape_3d(dim0=10, dim1=10, dim2=10):
    return (_np.random.randint(1, dim0 + 1),  # mxlint: disable=RNG001
            _np.random.randint(1, dim1 + 1),  # mxlint: disable=RNG001
            _np.random.randint(1, dim2 + 1))  # mxlint: disable=RNG001


def rand_shape_nd(num_dim, dim=10):
    return tuple(_np.random.randint(1, dim + 1, size=num_dim))  # mxlint: disable=RNG001


def rand_ndarray(shape, stype="default", density=None, dtype=None, ctx=None):
    if stype == "default":
        return array(_np.random.uniform(-1, 1, shape),  # mxlint: disable=RNG001
                     ctx=ctx, dtype=dtype or _np.float32)
    from .ndarray import sparse
    return sparse.rand_sparse_ndarray(shape, stype, density=density, dtype=dtype)[0]


def simple_forward(sym, ctx=None, is_train=False, **inputs):
    outputs = sym.eval(ctx, **{k: array(v) for k, v in inputs.items()})
    outputs = [o.asnumpy() for o in outputs]
    if len(outputs) == 1:
        outputs = outputs[0]
    return outputs


def numeric_grad(executor_fn, inputs, eps=1e-4):
    """Central finite differences of sum(f(inputs)) w.r.t. each input."""
    grads = []
    for i, x in enumerate(inputs):
        g = _np.zeros_like(x)
        flat = x.reshape(-1)
        gflat = g.reshape(-1)
        for j in range(flat.size):
            old = flat[j]
            flat[j] = old + eps
            fp = float(executor_fn(inputs))
            flat[j] = old - eps
            fm = float(executor_fn(inputs))
            flat[j] = old
            gflat[j] = (fp - fm) / (2 * eps)
        grads.append(g)
    return grads


def check_numeric_gradient(fn, locations, rtol=1e-2, atol=1e-4, eps=1e-3):
    """Compare autograd gradients of ``fn`` against finite differences.

    fn: callable(*NDArrays) -> NDArray (scalar-reduced internally).
    locations: list of numpy arrays (float64 recommended positions)."""
    nds = [array(x.astype(_np.float32)) for x in locations]
    for x in nds:
        x.attach_grad()
    with autograd.record():
        out = fn(*nds)
        loss = out.sum()
    loss.backward()
    ag_grads = [x.grad.asnumpy() for x in nds]

    def f(np_inputs):
        vals = [array(v.astype(_np.float32)) for v in np_inputs]
        return fn(*vals).sum().asscalar()

    num_grads = numeric_grad(f, [x.copy() for x in locations], eps=eps)
    for i, (a, n) in enumerate(zip(ag_grads, num_grads)):
        assert_almost_equal(a, n, rtol=rtol, atol=atol,
                            names=("autograd[%d]" % i, "numeric[%d]" % i))


def check_consistency(fn, inputs, ctx_list=None, rtol=1e-5, atol=1e-6):
    """Run fn on several contexts and compare results (reference
    check_consistency runs a sym on cpu+gpu)."""
    ctx_list = ctx_list or [cpu(0), default_context()]
    results = []
    for ctx in ctx_list:
        vals = [array(x, ctx=ctx) for x in inputs]
        out = fn(*vals)
        results.append(out.asnumpy())
    for r in results[1:]:
        assert_almost_equal(results[0], r, rtol=rtol, atol=atol)
    return results
