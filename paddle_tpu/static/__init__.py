"""Static-graph API (reference: python/paddle/static/ — Program/Executor
define-and-run over ProgramDesc + the C++ interpreters in
paddle/fluid/framework/new_executor/).

TPU-native design: the "Program" is a recorded op tape — while static mode
is on, every eager op appends its primal jnp function + tensor wiring to
the active Program (the analogue of OpDesc insertion).  ``Executor.run``
replays the tape as ONE pure function of (feeds, parameters) and compiles
it with ``jax.jit`` keyed by feed shapes — XLA is the InterpreterCore:
dependency analysis, stream scheduling, fusion, and memory planning all
happen in the compiler instead of a hand-built C++ interpreter.
Parameters are passed as runtime arguments, so optimizer updates between
``run`` calls are visible without retracing.
"""
import numpy as np

from ..framework import dtypes

__all__ = ["InputSpec", "enable_static", "disable_static", "Program",
           "program_guard", "default_main_program", "default_startup_program",
           "name_scope", "data", "Executor", "save_inference_model",
           "load_inference_model", "global_scope", "scope_guard",
           "cpu_places", "cuda_places"]

_static_mode = [False]


class InputSpec:
    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        self.shape = tuple(None if s in (-1, None) else int(s)
                           for s in shape)
        self.dtype = dtypes.convert_dtype(dtype)
        self.name = name
        self.stop_gradient = stop_gradient

    def __repr__(self):
        return (f"InputSpec(shape={self.shape}, dtype={self.dtype}, "
                f"name={self.name})")

    @classmethod
    def from_tensor(cls, tensor, name=None):
        return cls(tuple(tensor.shape), tensor.dtype, name)

    @classmethod
    def from_numpy(cls, ndarray, name=None):
        return cls(ndarray.shape, ndarray.dtype, name)

    def batch(self, batch_size):
        return InputSpec((batch_size,) + self.shape, self.dtype, self.name)

    def unbatch(self):
        return InputSpec(self.shape[1:], self.dtype, self.name)


class Program:
    """Recorded op tape: [(fn, input Tensors, output Tensors)] + the feed
    placeholders created by ``data()`` while this program was active."""

    def __init__(self):
        self._ops = []                 # (fn, inputs tuple, outputs tuple)
        self._placeholders = {}        # name -> Tensor

    # recorder protocol (installed into framework.autograd._STATIC_RECORDER)
    def record(self, fn, inputs, outputs):
        self._ops.append((fn, tuple(inputs), tuple(outputs)))

    # -- program surface ----------------------------------------------------
    def global_block(self):
        return self

    def clone(self, for_test=False):
        p = Program()
        p._ops = list(self._ops)
        p._placeholders = dict(self._placeholders)
        return p

    @property
    def num_ops(self):
        return len(self._ops)

    def __repr__(self):
        return (f"Program(ops={len(self._ops)}, "
                f"feeds={list(self._placeholders)})")

    # -- replay -------------------------------------------------------------
    def _leaf_inputs(self):
        """Tensors consumed but never produced and not placeholders —
        parameters/constants, passed as runtime args at run()."""
        produced = set()
        ph_ids = {id(t) for t in self._placeholders.values()}
        leaves, seen = [], set()
        for _, inputs, outputs in self._ops:
            for t in inputs:
                if id(t) not in produced and id(t) not in ph_ids and \
                        id(t) not in seen:
                    seen.add(id(t))
                    leaves.append(t)
            for t in outputs:
                produced.add(id(t))
        return leaves

    def _prune_to(self, fetch_list):
        """Backward slice: only ops in the fetch cone (the reference's
        inference-program prune)."""
        needed = {id(t) for t in fetch_list}
        kept = []
        for fn, inputs, outputs in reversed(self._ops):
            if any(id(t) in needed for t in outputs):
                kept.append((fn, inputs, outputs))
                needed.update(id(t) for t in inputs)
        kept.reverse()
        return kept, needed

    def _build_pure(self, fetch_list, feed_names=None):
        """Pure (feed_vals, leaf_vals) -> fetch vals replay function over
        the fetch cone.  ``feed_names`` restricts which placeholders become
        feed arguments (the rest must be dead after pruning)."""
        ops, needed = self._prune_to(fetch_list)
        ph_items = sorted((n, t) for n, t in self._placeholders.items()
                          if feed_names is None or n in feed_names)
        # leaves restricted to the pruned cone
        produced = set()
        ph_ids_all = {id(t) for t in self._placeholders.values()}
        leaves, seen = [], set()
        for _, inputs, outputs in ops:
            for t in inputs:
                if id(t) not in produced and id(t) not in ph_ids_all and \
                        id(t) not in seen:
                    seen.add(id(t))
                    leaves.append(t)
            produced.update(id(t) for t in outputs)
        live_ph = {id(t) for _, inputs, _ in ops for t in inputs} & ph_ids_all
        fed_ids = {id(t) for _, t in ph_items}
        unfed = live_ph - fed_ids
        if unfed:
            names = [n for n, t in self._placeholders.items()
                     if id(t) in unfed]
            raise ValueError(
                f"placeholders {names} are live in the fetch cone but not "
                "listed as feeds")
        leaf_ids = [id(t) for t in leaves]
        ph_ids = [id(t) for _, t in ph_items]
        fetch_ids = [id(t) for t in fetch_list]
        fetchable = produced | set(ph_ids) | set(leaf_ids)
        bad = [i for i, t in enumerate(fetch_list)
               if id(t) not in fetchable]
        if bad and self._ops:
            raise ValueError(
                f"fetch targets at positions {bad} were not produced by "
                "this program (was static mode enabled while building?)")
        fallback = {id(t): t for t in fetch_list}

        def pure(feed_vals, leaf_vals):
            env = dict(zip(ph_ids, feed_vals))
            env.update(zip(leaf_ids, leaf_vals))
            for fn, inputs, outputs in ops:
                vals = [env[id(t)] if id(t) in env else t._value
                        for t in inputs]
                out = fn(*vals)
                outs = out if isinstance(out, (tuple, list)) else (out,)
                for t, v in zip(outputs, outs):
                    env[id(t)] = v
            return [env[i] if i in env else fallback[i]._value
                    for i in fetch_ids]
        return pure, ph_items, leaves


_default_main = [Program()]
_default_startup = [Program()]


def default_main_program():
    return _default_main[0]


def default_startup_program():
    return _default_startup[0]


def _set_recording(program):
    from ..framework import autograd as _ag
    _ag._STATIC_RECORDER[0] = program


def enable_static():
    _static_mode[0] = True
    _set_recording(_default_main[0])


def disable_static(place=None):
    _static_mode[0] = False
    _set_recording(None)


from contextlib import contextmanager  # noqa: E402


@contextmanager
def program_guard(main_program, startup_program=None):
    prev_main = _default_main[0]
    _default_main[0] = main_program
    if startup_program is not None:
        prev_start = _default_startup[0]
        _default_startup[0] = startup_program
    if _static_mode[0]:
        _set_recording(main_program)
    try:
        yield
    finally:
        _default_main[0] = prev_main
        if startup_program is not None:
            _default_startup[0] = prev_start
        if _static_mode[0]:
            _set_recording(prev_main)


@contextmanager
def name_scope(prefix=None):
    yield


def data(name, shape, dtype="float32", lod_level=0):
    """Feed placeholder (reference: paddle.static.data).  Returns a Tensor
    whose value is a zeros stand-in; Executor.run substitutes the feed."""
    import jax.numpy as jnp
    from ..framework.core import Tensor
    from ..framework import autograd as _ag
    d = dtypes.convert_dtype(dtype)
    concrete = tuple(1 if s in (-1, None) else int(s) for s in shape)
    with _ag.suspend_tape():
        t = Tensor(jnp.zeros(concrete, d), name=name)
    t.is_placeholder = True
    t._declared_shape = tuple(shape)    # keeps None/-1 dims visible
    t.stop_gradient = True
    _default_main[0]._placeholders[name] = t
    return t


class _Scope:
    def __init__(self):
        self.vars = {}


_global_scope = _Scope()


def global_scope():
    return _global_scope


@contextmanager
def scope_guard(scope):
    yield scope


def cpu_places(device_count=None):
    return ["cpu"] * (device_count or 1)


def cuda_places(device_ids=None):
    ids = device_ids if device_ids is not None else [0]
    return [f"tpu:{i}" for i in ids]


class Executor:
    """Replay-compile-run (reference: python/paddle/base/executor.py over
    StandaloneExecutor).  Compiled executables are cached per
    (program, fetch ids, feed shapes/dtypes)."""

    def __init__(self, place=None):
        self.place = place
        self._cache = {}

    def run(self, program=None, feed=None, fetch_list=None,
            return_numpy=True):
        import jax
        import jax.numpy as jnp
        program = program or _default_main[0]
        feed = feed or {}
        fetch_list = list(fetch_list or [])
        if not fetch_list:
            return []
        # stop recording while executing (replay must not re-record)
        from ..framework import autograd as _ag
        prev = _ag._STATIC_RECORDER[0]
        _ag._STATIC_RECORDER[0] = None
        try:
            feed_arrs = {n: np.asarray(v) for n, v in feed.items()}
            key_shapes = tuple(sorted(
                (n, a.shape, str(a.dtype)) for n, a in feed_arrs.items()))
            key = (id(program), tuple(id(t) for t in fetch_list),
                   key_shapes, len(program._ops))
            if key not in self._cache:
                pure, ph_items, leaves = program._build_pure(fetch_list)
                missing = [n for n, _ in ph_items if n not in feed_arrs]
                if missing:
                    raise ValueError(f"missing feeds: {missing}")
                self._cache[key] = (jax.jit(pure), ph_items, leaves)
            fn, ph_items, leaves = self._cache[key]
            feed_vals = [jnp.asarray(feed_arrs[n]) for n, _ in ph_items]
            leaf_vals = [t._value for t in leaves]
            outs = fn(feed_vals, leaf_vals)
        finally:
            _ag._STATIC_RECORDER[0] = prev
        if return_numpy:
            return [np.asarray(o) for o in outs]
        return outs

    def close(self):
        self._cache.clear()


def save_inference_model(path_prefix, feed_vars, fetch_vars, executor=None,
                         program=None, **kwargs):
    """Serialize the pruned feed→fetch subgraph as a portable jax.export
    artifact + params (reference: python/paddle/static/io.py)."""
    import pickle
    import os
    import jax
    program = program or _default_main[0]
    feed_vars = feed_vars if isinstance(feed_vars, (list, tuple)) \
        else [feed_vars]
    fetch_vars = fetch_vars if isinstance(fetch_vars, (list, tuple)) \
        else [fetch_vars]
    feed_names = [getattr(v, "name", None) for v in feed_vars]
    pure, ph_items, leaves = program._build_pure(list(fetch_vars),
                                                 feed_names=feed_names)
    arg_shapes = [jax.ShapeDtypeStruct(tuple(t.shape), t.dtype)
                  for _, t in ph_items]
    leaf_vals = [t._value for t in leaves]
    from jax import export as _jax_export
    exported = _jax_export.export(
        jax.jit(pure), platforms=("cpu", "tpu"))(arg_shapes, leaf_vals)
    os.makedirs(os.path.dirname(path_prefix) or ".", exist_ok=True)
    with open(path_prefix + ".pdmodel", "w") as f:
        f.write(exported.mlir_module())
    meta = {
        "exported": bytes(exported.serialize()),
        "feed_names": [n for n, _ in ph_items],
        "leaves": [np.asarray(v) for v in leaf_vals],
        "n_fetch": len(fetch_vars),
    }
    with open(path_prefix + ".pdiparams", "wb") as f:
        pickle.dump(meta, f, protocol=4)
    return path_prefix


def load_inference_model(path_prefix, executor=None, **kwargs):
    """Returns (runner, feed_names, fetch_indices); ``runner.run(feed)``
    executes the loaded artifact and returns numpy outputs."""
    import pickle
    import jax
    import jax.numpy as jnp
    with open(path_prefix + ".pdiparams", "rb") as f:
        meta = pickle.load(f)
    from jax import export as _jax_export
    exported = _jax_export.deserialize(bytearray(meta["exported"]))
    leaves = [jnp.asarray(a) for a in meta["leaves"]]
    feed_names = meta["feed_names"]

    class _LoadedProgram:
        def run(self, feed):
            vals = [jnp.asarray(feed[n]) for n in feed_names]
            outs = exported.call(vals, leaves)
            return [np.asarray(o) for o in outs]

    return _LoadedProgram(), feed_names, list(range(meta["n_fetch"]))


# imported last: static.nn pulls in jit.dy2static, which imports back into
# this (by then fully-populated) module for InputSpec
from . import nn  # noqa: E402
from . import amp  # noqa: E402


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """reference: paddle.static.gradients — static autodiff from targets
    to inputs.  TPU-native: the Program is an op tape over jax.vjp
    nodes, so static gradients ARE the eager tape's gradients — delegate
    to autograd.grad on the recorded tensors (the reference's
    append_backward grad-op construction is jax.vjp here)."""
    from ..autograd import grad as _grad
    targets = targets if isinstance(targets, (list, tuple)) else [targets]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    gt = target_gradients
    if gt is not None and not isinstance(gt, (list, tuple)):
        gt = [gt]
    outs = _grad(targets, inputs, grad_outputs=gt, allow_unused=True,
                 retain_graph=True)
    return list(outs) if isinstance(outs, (list, tuple)) else [outs]


def append_backward(loss, parameter_list=None, no_grad_set=None,
                    callbacks=None, checkpoints=None):
    """reference: paddle.static.append_backward — build grads for every
    trainable param reachable from ``loss`` and return (param, grad)
    pairs.  Delegates to the tape (see gradients())."""
    prog = default_main_program()
    if parameter_list is None:
        seen, parameter_list = set(), []
        for op in getattr(prog, "ops", []):
            for t in op[1]:
                if getattr(t, "is_parameter", False) and \
                        not t.stop_gradient and id(t) not in seen:
                    seen.add(id(t))
                    parameter_list.append(t)
    if not parameter_list:
        return []
    gs = gradients([loss], list(parameter_list))
    return [(p, g) for p, g in zip(parameter_list, gs) if g is not None]


def py_func(func, x, out, backward_func=None,
            skip_vars_in_backward_input=None):
    """reference: paddle.static.py_func — host-side python op inside the
    graph.  TPU-native: jax.pure_callback (runs on host, shape-checked
    against ``out``).  ``backward_func(*inputs, *outputs, *out_grads) ->
    in_grads`` (the reference contract) registers a custom vjp (also a
    host callback); inputs listed in ``skip_vars_in_backward_input`` are
    omitted from the backward CALL ONLY — backward_func still returns
    one gradient per forward input, in forward order, skipped or not
    (the reference's contract: its docs' tanh example skips x from the
    backward input yet tanh_grad returns dx).  Without backward_func the
    op is non-differentiable (pure_callback has no autodiff rule)."""
    import jax
    import numpy as np
    import jax.numpy as jnp
    from ..framework.core import Tensor
    from ..framework.autograd import call_op
    xs = x if isinstance(x, (list, tuple)) else [x]
    xs = [t if isinstance(t, Tensor) else Tensor(jnp.asarray(t))
          for t in xs]
    outs = out if isinstance(out, (list, tuple)) else [out]
    shapes = [jax.ShapeDtypeStruct(tuple(o.shape), jnp.dtype(
        o.dtype if isinstance(o.dtype, str) else o._value.dtype))
        for o in outs]
    single = not isinstance(out, (list, tuple))

    def _host(*vals):
        res = func(*[np.asarray(v) for v in vals])
        res = res if isinstance(res, (list, tuple)) else [res]
        return [np.asarray(r) for r in res]

    def _fwd_impl(*vals):
        res = jax.pure_callback(
            _host, shapes if not single else shapes[:1], *vals)
        return res[0] if single else tuple(res)

    if backward_func is None:
        return call_op(_fwd_impl, *xs)

    in_shapes = [jax.ShapeDtypeStruct(tuple(t._value.shape),
                                      t._value.dtype) for t in xs]
    skip = skip_vars_in_backward_input or []
    skip = skip if isinstance(skip, (list, tuple)) else [skip]
    skip_ids = {id(s) for s in skip}
    # match against BOTH the wrapped tensors and the caller's original
    # objects (non-Tensor inputs get wrapped in fresh facades above)
    originals = x if isinstance(x, (list, tuple)) else [x]
    keep = [i for i, (t, o) in enumerate(zip(xs, originals))
            if id(t) not in skip_ids and id(o) not in skip_ids]

    @jax.custom_vjp
    def _op(*vals):
        return _fwd_impl(*vals)

    def _op_fwd(*vals):
        out_vals = _fwd_impl(*vals)
        return out_vals, (vals, out_vals)

    def _op_bwd(res, g):
        in_vals, out_vals = res
        outs_list = [out_vals] if single else list(out_vals)
        gs = [g] if single else list(g)
        kept_ins = [in_vals[i] for i in keep]

        def _host_bwd(*args):
            arrs = [np.asarray(v) for v in args]
            grads = backward_func(*arrs)
            grads = grads if isinstance(grads, (list, tuple)) else [grads]
            return [np.asarray(gr) for gr in grads]
        in_grads = jax.pure_callback(_host_bwd, in_shapes,
                                     *kept_ins, *outs_list, *gs)
        return tuple(in_grads)

    _op.defvjp(_op_fwd, _op_bwd)
    return call_op(lambda *vals: _op(*vals), *xs)


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    """reference: paddle.static.create_parameter."""
    from ..framework.core import Tensor
    from ..framework import dtypes as _dt
    from ..nn.initializer import XavierUniform, Constant
    init = default_initializer
    if attr is not None and attr is not False:
        init = getattr(attr, "initializer", None) or init
        name = getattr(attr, "name", None) or name
    if init is None:
        init = Constant(0.0) if is_bias else XavierUniform()
    d = _dt.convert_dtype(dtype)
    value = init(tuple(int(s) for s in shape), d)
    p = Tensor(value, stop_gradient=False)
    p.is_parameter = True
    p.name = name
    return p


class ExponentialMovingAverage:
    """reference: paddle.static.ExponentialMovingAverage — shadow
    parameters theta_ema = decay * theta_ema + (1 - decay) * theta with
    apply()/restore() swap (the evaluation-time EMA trick)."""

    def __init__(self, decay=0.999, thres_steps=None, name=None):
        self._decay = float(decay)
        self._ema = {}
        self._backup = None
        self._params = []

    def update(self, parameters=None):
        import jax.numpy as jnp
        if parameters is not None:
            self._params = list(parameters)
        for p in self._params:
            k = id(p)
            prev = self._ema.get(k)
            self._ema[k] = p._value if prev is None else \
                self._decay * prev + (1.0 - self._decay) * p._value
        return self

    def apply(self, executor=None, need_restore=True):
        from ..incubate.optimizer import _SwapCtx, _apply_swap
        _apply_swap(self, self._params, lambda p: self._ema.get(id(p)))
        if not need_restore:
            self._backup = None
        return _SwapCtx(self)

    def restore(self, executor=None):
        from ..incubate.optimizer import _restore_swap
        _restore_swap(self, self._params)


from contextlib import contextmanager as _ctxmgr


@_ctxmgr
def device_guard(device=None):
    """reference: paddle.static.device_guard — op device placement hint.
    XLA owns placement on TPU (one device per program shard); the guard
    is accepted and ignored."""
    yield


class WeightNormParamAttr:
    """reference: paddle.static.WeightNormParamAttr — ParamAttr marking
    a weight for weight normalization; layers consume it by wrapping
    themselves with nn.utils.weight_norm."""

    def __init__(self, dim=None, name=None, initializer=None,
                 learning_rate=1.0, regularizer=None, trainable=True,
                 do_model_average=False, need_clip=True):
        self.dim = dim
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip


__all__ += ["gradients", "append_backward", "py_func", "create_parameter",
            "ExponentialMovingAverage", "device_guard",
            "WeightNormParamAttr"]


# -- Variable / global vars / program state (reference: paddle.static) ------

# In the reference a static ``Variable`` is the graph symbol distinct from
# an eager Tensor; our tape records real Tensors, so the symbol type IS the
# Tensor facade (reference: python/paddle/base/framework.py Variable).
from ..framework.core import Tensor as Variable  # noqa: E402,F401


def create_global_var(shape, value, dtype, persistable=False,
                      force_cpu=False, name=None):
    """reference: paddle.static.create_global_var."""
    import jax.numpy as jnp
    from ..framework.core import Tensor
    from ..framework import dtypes as _dt
    d = _dt.convert_dtype(dtype)
    t = Tensor(jnp.full(tuple(int(s) for s in shape), value, d), name=name)
    t.persistable = persistable
    t.stop_gradient = True
    global_scope().vars[name or f"global_var_{id(t)}"] = t
    return t


def _program_parameters(program):
    """Named parameter/persistable leaves of a program's op tape."""
    out = {}
    for t in program._leaf_inputs():
        if getattr(t, "is_parameter", False) or \
                getattr(t, "persistable", False):
            nm = getattr(t, "name", None) or f"param_{len(out)}"
            out[nm] = t
    return out


def set_program_state(program, state_dict):
    """reference: paddle.static.set_program_state — assign numpy state
    into a program's parameters by name."""
    import jax.numpy as jnp
    params = _program_parameters(program)
    for nm, val in state_dict.items():
        if nm in params:
            params[nm]._value = jnp.asarray(val)


def save(program, path_prefix, protocol=4):
    """reference: paddle.static.save — writes ``.pdparams`` (named
    parameter state).  Optimizer state lives with the optimizer object in
    this framework (documented envelope)."""
    import pickle
    import numpy as np
    state = {nm: np.asarray(t._value)
             for nm, t in _program_parameters(program).items()}
    with open(path_prefix + ".pdparams", "wb") as f:
        pickle.dump(state, f, protocol=protocol)


def load(program, path_prefix, executor=None, var_list=None):
    """reference: paddle.static.load — restore ``.pdparams`` into the
    program's parameters."""
    import pickle
    with open(path_prefix + ".pdparams", "rb") as f:
        state = pickle.load(f)
    set_program_state(program, state)


def accuracy(input, label, k=1, correct=None, total=None, name=None):
    """reference: paddle.static.accuracy — top-k accuracy tensor."""
    import jax.numpy as jnp
    from ..framework.autograd import call_op
    from ..tensor._helpers import ensure_tensor
    input, label = ensure_tensor(input), ensure_tensor(label)

    def _acc(p, l):
        kk = min(int(k), p.shape[-1])
        top = jnp.argsort(-p, axis=-1)[..., :kk]
        hit = jnp.any(top == l.reshape(-1, 1), axis=-1)
        return jnp.mean(hit.astype(jnp.float32))
    return call_op(_acc, input.detach(), label.detach())


def auc(input, label, curve="ROC", num_thresholds=4095, topk=1,
        slide_steps=1, name=None):
    """reference: paddle.static.auc — returns (auc_out, batch_auc_out,
    states).  Computed exactly (ROC: Mann-Whitney with mid-ranks for
    ties; PR: trapezoid over the precision-recall curve) instead of the
    reference's thresholded histogram approximation."""
    import jax.numpy as jnp
    from ..framework.autograd import call_op
    from ..tensor._helpers import ensure_tensor
    if curve not in ("ROC", "PR"):
        raise ValueError(f"auc: unknown curve {curve!r}")
    input, label = ensure_tensor(input), ensure_tensor(label)

    def _roc(p, l):
        score = (p[..., 1] if p.ndim == 2 else p).reshape(-1)
        lab = l.reshape(-1).astype(jnp.float32)
        srt = jnp.sort(score)
        # mid-rank: average of 1-based left/right insertion positions
        ranks = (jnp.searchsorted(srt, score, side="left")
                 + jnp.searchsorted(srt, score, side="right")
                 + 1).astype(jnp.float32) / 2.0
        npos = jnp.sum(lab)
        nneg = lab.size - npos
        pos_rank_sum = jnp.sum(jnp.where(lab > 0, ranks, 0.0))
        denom = jnp.maximum(npos * nneg, 1.0)
        return (pos_rank_sum - npos * (npos + 1) / 2.0) / denom

    def _pr(p, l):
        score = (p[..., 1] if p.ndim == 2 else p).reshape(-1)
        lab = l.reshape(-1).astype(jnp.float32)
        order = jnp.argsort(-score)
        lab_sorted = lab[order]
        tp = jnp.cumsum(lab_sorted)
        fp = jnp.cumsum(1.0 - lab_sorted)
        npos = jnp.maximum(jnp.sum(lab), 1.0)
        precision = tp / jnp.maximum(tp + fp, 1.0)
        recall = tp / npos
        prec = jnp.concatenate([jnp.ones((1,)), precision])
        rec = jnp.concatenate([jnp.zeros((1,)), recall])
        return jnp.sum((rec[1:] - rec[:-1]) * (prec[1:] + prec[:-1]) / 2.0)

    out = call_op(_roc if curve == "ROC" else _pr,
                  input.detach(), label.detach())
    # states tuple: the reference returns four histogram stat tensors
    # [batch_stat_pos, batch_stat_neg, stat_pos, stat_neg] that callers
    # commonly unpack/index; the exact (non-histogram) computation here
    # does not need them, so they are zero-filled placeholders keeping
    # the unpacking contract (ADVICE r4 #4)
    from .. import zeros as _zeros
    states = [_zeros([1, num_thresholds + 1], dtype="int64")
              for _ in range(4)]
    return out, out, states


__all__ += ["Variable", "create_global_var", "set_program_state", "save",
            "load", "accuracy", "auc"]
