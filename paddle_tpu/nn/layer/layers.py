"""Layer base class (reference: python/paddle/nn/layer/layers.py).

Mutable module tree holding Parameter Tensors — same ergonomics as the
reference's ``paddle.nn.Layer`` (sublayers, state_dict, hooks, train/eval).
TPU-native twist: a Layer doubles as the *state boundary* for compiled
execution — ``named_parameters``/``named_buffers`` define a deterministic
pytree order that functional.swap_params uses to run forwards as pure
functions under jit/pjit.
"""
from collections import OrderedDict

import numpy as np
import jax.numpy as jnp

from ...framework.core import Tensor
from ...framework import dtypes
from ...framework.autograd import no_grad
from ..initializer import _apply_initializer

# paddle.LazyGuard state (see paddle_tpu/__init__.py)
_LAZY_INIT = [False]

__all__ = ["Layer", "LayerList", "Sequential", "ParameterList", "LayerDict"]


class HookRemoveHelper:
    def __init__(self, hooks, idx):
        self._hooks, self._idx = hooks, idx

    def remove(self):
        self._hooks.pop(self._idx, None)


# Counts every assignment of a layer's ``training`` flag in the
# process.  A caller that put a network into one mode and remembers the
# stamp knows, while the stamp stands, that no layer has left that mode
# (``hapi.Model`` skips its per-step ``network.train()`` walk by it).
_MODE_STAMP = [0]


def mode_stamp():
    return _MODE_STAMP[0]


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        object.__setattr__(self, "_non_persistable_buffer_names", set())
        self.training = True
        self._dtype = dtypes.convert_dtype(dtype)
        self._forward_pre_hooks = OrderedDict()
        self._forward_post_hooks = OrderedDict()
        self._hook_id = 0
        self._name_scope = name_scope or type(self).__name__.lower()

    # -- attribute routing --------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        subs = self.__dict__.get("_sub_layers")
        bufs = self.__dict__.get("_buffers")
        if isinstance(value, Tensor) and (
                not value.stop_gradient or
                getattr(value, "is_parameter", False)):
            if params is None:
                raise RuntimeError("call Layer.__init__ first")
            for d in (subs, bufs):
                d.pop(name, None)
            params[name] = value
        elif isinstance(value, Layer):
            for d in (params, bufs):
                if d is not None:
                    d.pop(name, None)
            subs[name] = value
        elif params is not None and name in params:
            if value is None:
                del params[name]
                object.__setattr__(self, name, value)
            else:
                params[name] = value
        elif bufs is not None and name in bufs:
            bufs[name] = value
        else:
            if name == "training":
                _MODE_STAMP[0] += 1
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    # -- construction helpers ----------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        d = dtypes.convert_dtype(dtype) or self._dtype
        init = default_initializer
        name = None
        if attr is not None and attr is not False:
            init = getattr(attr, "initializer", None) or init
            name = getattr(attr, "name", None)
        if _LAZY_INIT[0]:
            # paddle.LazyGuard: defer the initializer; zeros hold the
            # shape/dtype until param.initialize() materializes
            import jax.numpy as _jnp
            p = Tensor(_jnp.zeros(tuple(int(s) for s in shape), d),
                       stop_gradient=False, name=name)
            _shape, _init, _bias = tuple(int(s) for s in shape), init, \
                is_bias

            def _materialize(_p=p, _s=_shape, _i=_init, _b=_bias, _d=d):
                _p._value = _apply_initializer(_i, _s, _d, _b)
                return _p
            p.initialize = _materialize
            p.persistable = True
            p.is_parameter = True
            return p
        value = _apply_initializer(init, tuple(int(s) for s in shape), d,
                                   is_bias)
        p = Tensor(value, stop_gradient=False, name=name)
        p.persistable = True
        p.is_parameter = True
        if attr is not None and getattr(attr, "trainable", True) is False:
            p.stop_gradient = True
            p.trainable = False
        return p

    def add_parameter(self, name, parameter):
        if parameter is None:
            self._parameters.pop(name, None)
        else:
            self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # -- traversal ----------------------------------------------------------
    def children(self):
        for _, l in self.named_children():
            yield l

    def named_children(self):
        seen = set()
        for name, l in self._sub_layers.items():
            if l is not None and id(l) not in seen:
                seen.add(id(l))
                yield name, l

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def named_sublayers(self, prefix="", include_self=False, layers_set=None):
        if layers_set is None:
            layers_set = set()
        if id(self) in layers_set:
            return
        layers_set.add(id(self))
        if include_self:
            yield prefix, self
        for name, l in self.named_children():
            sub_prefix = prefix + ("." if prefix else "") + name
            yield from l.named_sublayers(prefix=sub_prefix, include_self=True,
                                         layers_set=layers_set)

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        layers = self.named_sublayers(prefix=prefix, include_self=True) \
            if include_sublayers else [(prefix, self)]
        for lp, layer in layers:
            for name, p in layer._parameters.items():
                if p is None or id(p) in seen:
                    continue
                seen.add(id(p))
                yield (lp + ("." if lp else "") + name, p)

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        layers = self.named_sublayers(prefix=prefix, include_self=True) \
            if include_sublayers else [(prefix, self)]
        for lp, layer in layers:
            for name, b in layer._buffers.items():
                if b is None or id(b) in seen:
                    continue
                seen.add(id(b))
                yield (lp + ("." if lp else "") + name, b)

    def apply(self, fn):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # -- mode ---------------------------------------------------------------
    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    # -- hooks --------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # -- call ---------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in self._forward_pre_hooks.values():
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in self._forward_post_hooks.values():
            out = hook(self, inputs, outputs)
            if out is not None:
                outputs = out
        return outputs

    # -- state dict ---------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = OrderedDict() if destination is None else destination
        for name, p in self.named_parameters(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers):
            dest[name] = p
        # persistable buffers only
        np_names = set()
        for lp, layer in self.named_sublayers(include_self=True):
            for bn in layer._non_persistable_buffer_names:
                np_names.add(lp + ("." if lp else "") + bn)
        for name, b in self.named_buffers(
                prefix=structured_name_prefix.rstrip("."),
                include_sublayers=include_sublayers):
            if name not in np_names:
                dest[name] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for name, t in own.items():
            if name in state_dict:
                v = state_dict[name]
                if isinstance(v, Tensor):
                    v = v._value
                v = jnp.asarray(np.asarray(v))
                if tuple(v.shape) != tuple(t._value.shape):
                    raise ValueError(
                        f"shape mismatch for {name}: "
                        f"{v.shape} vs {t._value.shape}")
                t._value = v.astype(t._value.dtype)
            else:
                missing.append(name)
        for k in state_dict:
            if k not in own:
                unexpected.append(k)
        return missing, unexpected

    load_dict = set_state_dict

    # -- dtype/device movement ---------------------------------------------
    @no_grad()
    def to(self, device=None, dtype=None, blocking=None):
        d = dtypes.convert_dtype(dtype) if dtype is not None else None
        for t in list(self.parameters()) + list(self.buffers()):
            v = t._value
            if d is not None and dtypes.is_floating_dtype(v.dtype):
                v = v.astype(d)
            if device is not None:
                import jax
                from ...framework.core import _parse_device
                v = jax.device_put(v, _parse_device(device))
            t._value = v
        if d is not None:
            self._dtype = d
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def half(self):
        return self.to(dtype="float16")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    def full_name(self):
        return self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [extra] if extra else []
        for name, l in self.named_children():
            mod_str = repr(l)
            mod_str = "\n".join("  " + line for line in mod_str.split("\n"))
            lines.append(f"({name}): " + mod_str.lstrip())
        main = type(self).__name__
        if not lines:
            return f"{main}({extra})"
        return main + "(\n  " + "\n  ".join(lines) + "\n)"


class Sequential(Layer):
    def __init__(self, *layers):
        super().__init__()
        if len(layers) == 1 and isinstance(layers[0], OrderedDict):
            for name, l in layers[0].items():
                self.add_sublayer(name, l)
        else:
            for i, l in enumerate(layers):
                if isinstance(l, tuple):
                    self.add_sublayer(l[0], l[1])
                else:
                    self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        items = list(self._sub_layers.values())
        if isinstance(idx, slice):
            return Sequential(*items[idx])
        return items[idx]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def forward(self, input):
        for l in self._sub_layers.values():
            input = l(input)
        return input


class LayerList(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            for i, l in enumerate(sublayers):
                self.add_sublayer(str(i), l)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return LayerList(list(self._sub_layers.values())[idx])
        n = len(self._sub_layers)
        if idx < 0:
            idx += n
        return self._sub_layers[str(idx)]

    def __setitem__(self, idx, layer):
        self._sub_layers[str(idx)] = layer

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers.values())

    def append(self, layer):
        self.add_sublayer(str(len(self._sub_layers)), layer)
        return self

    def insert(self, index, layer):
        layers = list(self._sub_layers.values())
        layers.insert(index, layer)
        self._sub_layers.clear()
        for i, l in enumerate(layers):
            self._sub_layers[str(i)] = l

    def extend(self, layers):
        for l in layers:
            self.append(l)
        return self


class ParameterList(Layer):
    def __init__(self, parameters=None):
        super().__init__()
        if parameters is not None:
            for i, p in enumerate(parameters):
                self.add_parameter(str(i), p)

    def __getitem__(self, idx):
        return self._parameters[str(idx)]

    def __len__(self):
        return len(self._parameters)

    def __iter__(self):
        return iter(self._parameters.values())

    def append(self, parameter):
        self.add_parameter(str(len(self._parameters)), parameter)
        return self


class LayerDict(Layer):
    def __init__(self, sublayers=None):
        super().__init__()
        if sublayers is not None:
            self.update(sublayers)

    def __getitem__(self, key):
        return self._sub_layers[key]

    def __setitem__(self, key, layer):
        self.add_sublayer(key, layer)

    def __delitem__(self, key):
        del self._sub_layers[key]

    def __len__(self):
        return len(self._sub_layers)

    def __iter__(self):
        return iter(self._sub_layers)

    def __contains__(self, key):
        return key in self._sub_layers

    def keys(self):
        return self._sub_layers.keys()

    def values(self):
        return self._sub_layers.values()

    def items(self):
        return self._sub_layers.items()

    def update(self, sublayers):
        items = sublayers.items() if hasattr(sublayers, "items") else sublayers
        for k, v in items:
            self.add_sublayer(k, v)

    def clear(self):
        self._sub_layers.clear()

    def pop(self, key):
        return self._sub_layers.pop(key)
