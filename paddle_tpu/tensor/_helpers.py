"""Shared machinery for the eager op surface.

Reference analogue: the Phi kernel library + dispatch
(paddle/phi/kernels/, paddle/phi/core/kernel_factory.cc).  TPU-native: every
op is a jnp/lax lambda run through the autograd tape (`call_op`); XLA is the
kernel library, so there is no per-backend registry — one definition serves
CPU and TPU, eager and traced.
"""
import numpy as np
import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..framework.autograd import call_op
from ..framework import dtypes


def ensure_tensor(x, ref_dtype=None):
    if isinstance(x, Tensor):
        return x
    if isinstance(x, (int, float, bool, complex)):
        # keep python scalars weakly typed via closure-free asarray
        return Tensor(jnp.asarray(x))
    if isinstance(x, (jax.Array, jax.core.Tracer)):
        # raw jax values (incl. tracers inside lax control flow, which
        # np.asarray would try to concretize) wrap directly
        return Tensor(x)
    arr = np.asarray(x)
    if arr.dtype == np.float64:
        arr = arr.astype(dtypes.get_default_dtype())
    return Tensor(arr)


def unary_op(fn):
    def op(x, name=None):
        return call_op(fn, ensure_tensor(x))
    return op


def binary_op(fn):
    """Python-scalar operands stay host-side closure constants instead of
    device arrays: device-materializing a scalar costs an HBM upload, and
    ops that inspect static ints (e.g. jnp.power's integer-exponent path
    calling __index__) would otherwise force a blocking device READBACK
    per call.  Weak
    scalar typing is also the correct jnp promotion (a float scalar must
    not upcast a bf16 tensor)."""
    def op(x, y, name=None):
        y_scalar = isinstance(y, (int, float, complex)) \
            and not isinstance(y, bool)
        x_scalar = isinstance(x, (int, float, complex)) \
            and not isinstance(x, bool)
        if y_scalar and not x_scalar:
            return call_op(lambda v: fn(v, y), ensure_tensor(x))
        if x_scalar and not y_scalar:
            return call_op(lambda v: fn(x, v), ensure_tensor(y))
        return call_op(fn, ensure_tensor(x), ensure_tensor(y))
    return op


def reduce_op(fn):
    def op(x, axis=None, keepdim=False, name=None, dtype=None):
        x = ensure_tensor(x)
        if isinstance(axis, (list, tuple)):
            axis = tuple(int(a) for a in axis)
        elif axis is not None and not isinstance(axis, int):
            axis = int(axis)
        kw = dict(axis=axis, keepdims=keepdim)
        if dtype is not None:
            kw["dtype"] = dtypes.convert_dtype(dtype)
        return call_op(lambda v: fn(v, **kw), x)
    return op


def raw(x):
    """Underlying jax array of a Tensor (or pass-through)."""
    return x._value if isinstance(x, Tensor) else x
