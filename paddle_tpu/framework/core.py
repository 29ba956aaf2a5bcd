"""Tensor facade and device handling.

The reference's tensor stack is ``phi::DenseTensor`` + eager ``Tensor`` with
``AutogradMeta`` (reference: paddle/phi/core/dense_tensor.cc,
paddle/fluid/pybind/eager.cc).  TPU-native design: a ``Tensor`` is a thin
Python wrapper over a ``jax.Array`` — PJRT owns memory, layout, and device
placement, so there is no allocator or DeviceContext to build.  Autograd
metadata (``stop_gradient``, tape node, accumulated ``grad``) lives on the
wrapper; the tape itself is in ``autograd.py``.
"""
import numpy as np
import jax
import jax.numpy as jnp

from . import dtypes
from . import autograd as _ag

__all__ = ["Tensor", "to_tensor", "set_device", "get_device", "is_tensor",
           "set_default_dtype", "get_default_dtype", "set_printoptions"]

# repr formatting knobs (reference: paddle.set_printoptions)
_PRINT_OPTS = {"precision": 8, "threshold": 1000, "edgeitems": 3,
               "max_line_width": 80, "sci_mode": False}


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """reference: paddle.set_printoptions — configure Tensor repr."""
    if precision is not None:
        _PRINT_OPTS["precision"] = int(precision)
    if threshold is not None:
        _PRINT_OPTS["threshold"] = int(threshold)
    if edgeitems is not None:
        _PRINT_OPTS["edgeitems"] = int(edgeitems)
    if linewidth is not None:
        _PRINT_OPTS["max_line_width"] = int(linewidth)
    if sci_mode is not None:
        _PRINT_OPTS["sci_mode"] = bool(sci_mode)

set_default_dtype = dtypes.set_default_dtype
get_default_dtype = dtypes.get_default_dtype

_CURRENT_DEVICE = [None]  # None → jax default


def _parse_device(spec):
    """'cpu' / 'tpu' / 'tpu:N' (gpu/cuda alias tpu) -> the jax device.
    A platform that is not present or an index past the last device
    raises: a run asked onto the chip must never continue on the CPU."""
    if spec is None:
        return None
    name, _, idx = spec.partition(":")
    idx = int(idx) if idx else 0
    name = {"gpu": "tpu", "cuda": "tpu"}.get(name, name)  # no GPUs here
    if name == "cpu":
        devs = jax.devices("cpu")
    else:
        devs = [d for d in jax.devices() if d.platform == name]
    if not devs:
        raise RuntimeError(
            f"device {spec!r} requested but JAX has no {name!r} device "
            f"(backend: {jax.default_backend()})")
    if not 0 <= idx < len(devs):
        raise ValueError(
            f"device {spec!r} out of range: {len(devs)} {name} "
            "device(s) visible")
    return devs[idx]


def set_device(device):
    """paddle.set_device — 'cpu', 'tpu', 'tpu:0' (gpu aliases map to tpu)."""
    dev = _parse_device(device)
    _CURRENT_DEVICE[0] = dev
    if dev is not None:
        jax.config.update("jax_default_device", dev)
    return dev


def get_device():
    d = _CURRENT_DEVICE[0]
    if d is None:
        d = jax.devices()[0]
    return f"{d.platform}:{getattr(d, 'id', 0)}"


def current_jax_device():
    return _CURRENT_DEVICE[0]


class Tensor:
    """Eager tensor: wraps a jax.Array + autograd metadata.

    Mutation model: methods never mutate the underlying array (XLA arrays are
    immutable); in-place-looking APIs (``set_value``, optimizer updates)
    rebind ``_value``.  Parameter identity is therefore the wrapper object.
    """
    __slots__ = ("_value", "stop_gradient", "_grad", "_node", "_out_idx",
                 "_hooks", "_retain_grads", "name", "persistable", "trainable",
                 "__weakref__", "__dict__")

    def __init__(self, value, stop_gradient=True, name=None):
        if isinstance(value, Tensor):
            value = value._value
        if not isinstance(value, jax.Array):
            value = jnp.asarray(value)
        self._value = value
        self.stop_gradient = stop_gradient
        self._grad = None
        self._node = None
        self._out_idx = 0
        self._hooks = []
        self._retain_grads = False
        self.name = name
        self.persistable = False
        self.trainable = not stop_gradient

    def __deepcopy__(self, memo):
        """Deep copy shares the immutable jax.Array value but detaches from
        the tape (fresh wrapper identity, no node/grad)."""
        new = Tensor(self._value, stop_gradient=self.stop_gradient,
                     name=self.name)
        memo[id(self)] = new
        new.persistable = self.persistable
        new.trainable = self.trainable
        new.__dict__.update(self.__dict__)
        return new

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self):
        return list(self._value.shape)

    @property
    def dtype(self):
        return self._value.dtype

    @property
    def ndim(self):
        return self._value.ndim

    dim = ndim

    @property
    def size(self):
        return int(np.prod(self._value.shape)) if self._value.shape else 1

    @property
    def place(self):
        devs = getattr(self._value, "devices", None)
        try:
            dev = next(iter(self._value.devices()))
            return f"{dev.platform}:{dev.id}"
        except Exception:
            return "cpu"

    @property
    def grad(self):
        if self._grad is None:
            return None
        return Tensor(self._grad, stop_gradient=True)

    @grad.setter
    def grad(self, value):
        self._grad = None if value is None else (
            value._value if isinstance(value, Tensor) else jnp.asarray(value))

    def _wrap_grad(self, g):
        return Tensor(g, stop_gradient=True)

    @property
    def is_leaf(self):
        return self._node is None

    # -- conversion ---------------------------------------------------------
    # each host readback reports to the jit.sot journal (when active):
    # concretizations are the graph-break boundaries block-level SOT
    # splits compiled segments around
    def numpy(self):
        v = np.asarray(self._value)
        _ag.journal_sync(self, v)
        return v

    def item(self, *args):
        v = self._value.item(*args)
        _ag.journal_sync(self, v)
        return v

    def tolist(self):
        return self.numpy().tolist()

    def __array__(self, dtype=None):
        a = np.asarray(self._value)
        _ag.journal_sync(self, a)
        return a.astype(dtype) if dtype is not None else a

    def __float__(self):
        v = float(self._value)
        _ag.journal_sync(self, v)
        return v

    def __int__(self):
        v = int(self._value)
        _ag.journal_sync(self, v)
        return v

    def __index__(self):
        # lets a concrete integer scalar Tensor drive range()/slicing
        # (reference parity); traced values raise jax's concretization
        # error, which the to_static graph-break machinery handles
        import jax.numpy as _jnp
        if not _jnp.issubdtype(self._value.dtype, _jnp.integer):
            raise TypeError(
                f"only integer tensors can be used as an index, got "
                f"{self._value.dtype}")
        v = int(self._value)
        _ag.journal_sync(self, v)
        return v

    def __bool__(self):
        v = bool(self._value)
        _ag.journal_sync(self, v)
        return v

    def __len__(self):
        if not self._value.shape:
            raise TypeError("len() of a 0-d tensor")
        return self._value.shape[0]

    def __repr__(self):
        import numpy as _np
        opts = dict(_PRINT_OPTS)
        sci = opts.pop("sci_mode")
        prec = opts["precision"]
        body = _np.array2string(
            _np.asarray(self._value),
            formatter={"float_kind": (lambda v: f"{v:.{prec}e}")
                       if sci else None},
            **opts)
        return (f"Tensor(shape={self.shape}, dtype={self._value.dtype}, "
                f"stop_gradient={self.stop_gradient},\n{body})")

    def __hash__(self):
        return id(self)

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        _ag.backward(self, grad_tensor, retain_graph)

    def clear_grad(self):
        self._grad = None

    clear_gradient = clear_grad

    def retain_grads(self):
        self._retain_grads = True

    def register_hook(self, hook):
        self._hooks.append(hook)

        class _Handle:
            def remove(_self):
                try:
                    self._hooks.remove(hook)
                except ValueError:
                    pass
        return _Handle()

    def detach(self):
        t = Tensor(self._value, stop_gradient=True, name=self.name)
        # static recording: the detached copy must stay linked to its
        # producer in the Program op tape (ops like embedding/CE detach
        # their index inputs; without this link a fed placeholder's
        # detached view would replay as a frozen constant).  No autograd
        # node — detach still blocks gradients.
        rec = _ag._STATIC_RECORDER[0]
        if rec is not None and not _ag._TAPE_SUSPENDED[0]:
            rec.record(lambda v: v, (self,), (t,))
        return t

    def clone(self):
        return _ag.call_op(lambda v: v + 0, self)

    def set_value(self, value):
        if _ag._JOURNAL[0] is not None:
            _ag._JOURNAL[0].unsupported = "Tensor.set_value in forward"
        if isinstance(value, Tensor):
            value = value._value
        value = jnp.asarray(value)
        if tuple(value.shape) != tuple(self._value.shape):
            raise ValueError(
                f"set_value shape mismatch {value.shape} vs {self._value.shape}")
        # copy-in semantics: never alias the source's buffer (a shared
        # buffer would be deleted under the other owner when a jitted step
        # donates this parameter)
        self._value = jnp.array(value, dtype=self._value.dtype, copy=True)

    def _replace(self, value):
        """Internal: rebind the raw array (optimizer updates)."""
        self._value = value

    # -- dtype/device movement ---------------------------------------------
    def astype(self, dtype):
        d = dtypes.convert_dtype(dtype)
        return _ag.call_op(lambda v: v.astype(d), self)

    def cast(self, dtype):
        return self.astype(dtype)

    def to(self, *args, **kwargs):
        t = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and (a.split(":")[0] in
                                       ("cpu", "tpu", "gpu", "cuda")):
                dev = _parse_device(a)
                t = Tensor(jax.device_put(t._value, dev),
                           stop_gradient=t.stop_gradient, name=t.name)
            else:
                t = t.astype(a)
        return t

    def cpu(self):
        return self.to("cpu")

    def cuda(self, *a):
        return self.to("tpu")

    def pin_memory(self):
        return self

    # -- indexing -----------------------------------------------------------
    def __getitem__(self, idx):
        if isinstance(idx, Tensor):
            idx = idx._value
        elif isinstance(idx, tuple):
            idx = tuple(i._value if isinstance(i, Tensor) else i for i in idx)
        return _ag.call_op(lambda v: v[idx], self)

    def __setitem__(self, idx, value):
        # Functional scatter: rebinds _value.  Not differentiable through the
        # assignment (matches dygraph in-place semantics on leaf tensors).
        if isinstance(idx, Tensor):
            idx = idx._value
        elif isinstance(idx, tuple):
            idx = tuple(i._value if isinstance(i, Tensor) else i for i in idx)
        v = value._value if isinstance(value, Tensor) else value
        self._value = self._value.at[idx].set(v)

    @property
    def T(self):
        return _ag.call_op(lambda v: v.T, self)

    # Arithmetic dunders are attached by paddle_tpu.tensor (method patching,
    # mirroring the reference's monkey-patch of math ops onto Tensor).


def is_tensor(x):
    return isinstance(x, Tensor)


def to_tensor(data, dtype=None, place=None, stop_gradient=True):
    """paddle.to_tensor."""
    if isinstance(data, Tensor):
        v = data._value
    elif isinstance(data, jax.Array):
        v = data
    else:
        arr = np.asarray(data)
        if dtype is None and arr.dtype == np.float64:
            arr = arr.astype(dtypes.get_default_dtype())
        v = jnp.asarray(arr)
    d = dtypes.convert_dtype(dtype)
    if d is not None and v.dtype != d:
        v = v.astype(d)
    if place is not None:
        v = jax.device_put(v, _parse_device(place))
    return Tensor(v, stop_gradient=stop_gradient)
