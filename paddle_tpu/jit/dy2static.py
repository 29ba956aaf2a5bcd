"""Data-dependent control flow for dygraph→static (reference:
python/paddle/jit/dy2static/ — ~25 AST transformers + convert_operators
rewriting Python if/while/and/or/not into conditional_block / while ops).

TPU-native: one AST pass rewrites ``if``/``while``/``and``/``or``/``not``
into calls to runtime converters that dispatch at execution time — a
concrete (eager) predicate keeps exact Python semantics, a traced
predicate lowers to ``lax.cond`` / ``lax.while_loop`` so the branch
becomes real compiled control flow instead of a tracer error.  This is
the reference's convert_ifelse/convert_while_loop design
(python/paddle/jit/dy2static/convert_operators.py) collapsed onto XLA's
structured control-flow primitives.

Supported rewrites (the rest of the function is left untouched and keeps
plain tracing semantics):
- ``if``/``elif``/``else`` whose branches assign local variables, or
  whose branches both end in ``return``.
- ``while`` whose body assigns its loop-carried variables (no
  ``break``/``continue``/``return`` inside — XLA has no early exit).
- ``for`` with a single-name target: ``range(tensor_n)`` lowers to
  ``lax.fori_loop``, iterating a traced Tensor lowers to ``lax.scan``
  over its leading axis, anything else keeps plain Python iteration;
  ``break``/``continue`` inside a tensor-bounded ``for`` raises a clear
  error (the loop var is not visible after a converted loop).
- ``and``/``or``/``not`` (short-circuit preserved when operands are
  concrete; ``logical_and/or/not`` when traced).

Gradients flow through converted ``if`` (lax.cond is reverse-mode
differentiable) and through any loop given a static trip-count bound:
under ``bounded_loops(N)`` a tensor-bounded ``for``/``while`` lowers to a
masked ``lax.scan`` of length N (reverse-mode differentiable — the scan
saves per-iteration residuals; iterations past the dynamic trip count
take a ``lax.cond`` identity branch, so the body never runs on the
terminal carry and cannot emit inf/NaN Jacobians).  Without a bound the loop lowers to
``lax.fori_loop``/``lax.while_loop``, which XLA cannot transpose
(dynamic trip count ⇒ unbounded residual storage); reverse AD through
one raises a clear error pointing at ``bounded_loops``.  This mirrors
the reference's while_grad op (python/paddle/static/nn/control_flow.py)
under XLA's static-shape constraint.

Variables assigned only inside a branch/loop that are unbound before it
ride an ``_UNDEF`` sentinel: they stay "unbound" (erroring on use) unless
the executed path binds them — mirroring Python.
"""
import ast
import functools
import inspect
import textwrap
import threading
import warnings

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.core import Tensor

__all__ = ["convert_ifelse", "convert_while_loop", "convert_logical_and",
           "convert_logical_or", "convert_logical_not",
           "transform_function", "bounded_loops"]

_LOOP_BOUND = threading.local()


class bounded_loops:
    """Give tensor-bounded converted loops a static max trip count.

    Inside this context a dy2static-converted ``for range(tensor_n)`` or
    ``while`` lowers to a masked ``lax.scan`` of length ``max_iters``
    instead of ``lax.fori_loop``/``lax.while_loop`` — making the loop
    reverse-mode differentiable (scan records residuals; iterations past
    the dynamic trip count keep the carry unchanged, so their cotangent
    contribution is exactly zero).  If the dynamic trip count exceeds
    ``max_iters`` the loop is truncated and a RuntimeWarning is emitted
    from a ``jax.debug.callback``.

    Usage::

        with paddle.jit.bounded_loops(64):
            loss = static_fn(x, n)   # n a traced step count <= 64
            loss.backward()
    """

    def __init__(self, max_iters):
        if not isinstance(max_iters, (int, jnp.integer)):
            raise TypeError(
                "bounded_loops: max_iters must be a concrete Python int "
                f"(the static scan length), got {type(max_iters).__name__}")
        self.max_iters = int(max_iters)
        if self.max_iters <= 0:
            raise ValueError("bounded_loops: max_iters must be positive")

    def __enter__(self):
        stack = getattr(_LOOP_BOUND, "stack", None)
        if stack is None:
            stack = _LOOP_BOUND.stack = []
        stack.append(self.max_iters)
        return self

    def __exit__(self, *exc):
        _LOOP_BOUND.stack.pop()
        return False


def active_loop_bound():
    stack = getattr(_LOOP_BOUND, "stack", None)
    return stack[-1] if stack else None


def _overflow_warn(flag, kind, bound):
    if flag:
        warnings.warn(
            f"dy2static bounded_loops({bound}): a converted {kind} loop "
            f"needed more than {bound} iterations and was truncated; "
            f"raise the bound", RuntimeWarning, stacklevel=2)


def _bounded_scan(step_masked, carry0, bound, overflow_flag_fn, kind):
    """Masked scan of static length ``bound``; the truncation warning
    rides a ``jax.debug.callback``."""
    final, _ = lax.scan(step_masked, carry0, jnp.arange(bound))
    jax.debug.callback(
        functools.partial(_overflow_warn, kind=kind, bound=bound),
        jnp.asarray(overflow_flag_fn(final)))
    return final


class _Undef:
    """Placeholder for a name unbound at the control-flow entry."""

    def __repr__(self):
        return "<dy2static undefined>"

    def __bool__(self):
        raise NameError("variable is unbound on this control-flow path "
                        "(dy2static)")


_UNDEF = _Undef()


def _val(x):
    return x._value if isinstance(x, Tensor) else x


def _is_traced(x):
    return isinstance(_val(x), jax.core.Tracer)


def _load(thunk):
    """Read a possibly-unbound outer local."""
    try:
        return thunk()
    except NameError:
        return _UNDEF


def _unwrap_tree(out):
    return jax.tree.map(lambda o: _val(o), out,
                        is_leaf=lambda o: isinstance(o, Tensor))


def _wrap_tree(vals):
    return jax.tree.map(lambda v: Tensor(v), vals)


def convert_ifelse(pred, true_fn, false_fn, init=()):
    """if/else over a possibly-traced predicate.

    init: current values of the variables either branch assigns (so a
    read-before-write inside a branch sees the outer value instead of
    hitting UnboundLocalError).  Concrete pred -> exact Python dispatch;
    traced pred -> ``lax.cond`` with both branches traced.
    """
    p = _val(pred)
    if not isinstance(p, jax.core.Tracer):
        return true_fn(*init) if bool(p) else false_fn(*init)
    t = lambda: _unwrap_tree(true_fn(*init))
    f = lambda: _unwrap_tree(false_fn(*init))
    return _wrap_tree(lax.cond(p, t, f))


def convert_while_loop(cond_fn, body_fn, init):
    """while over a possibly-traced condition.

    init: tuple of loop-carried values (entries may be ``_UNDEF`` for
    names unbound before the loop — those are treated as body-local
    temporaries and not carried).  Traced -> ``lax.while_loop``.
    """
    init = tuple(init)
    p0 = cond_fn(*init)
    if not isinstance(_val(p0), jax.core.Tracer) \
            and not any(_is_traced(v) for v in init):
        out = init
        while bool(_val(cond_fn(*out))):
            out = tuple(body_fn(*out))
        return out

    live = [i for i, v in enumerate(init) if v is not _UNDEF]
    if not live:
        raise NotImplementedError(
            "dy2static while: no loop-carried variable is bound before "
            "the loop; initialize the loop state first (lax.while_loop "
            "needs concrete initial shapes)")
    wrap_t = [isinstance(init[i], Tensor) for i in live]

    def full(carry):
        args = list(init)
        for j, i in enumerate(live):
            args[i] = Tensor(carry[j]) if wrap_t[j] else carry[j]
        return args

    def c(carry):
        return _val(cond_fn(*full(carry)))

    def b(carry):
        out = tuple(body_fn(*full(carry)))
        return tuple(jnp.asarray(_val(out[i])) for i in live)

    carry0 = tuple(jnp.asarray(_val(init[i])) for i in live)
    bound = active_loop_bound()
    if bound is not None:
        # masked scan: differentiable bounded while (see bounded_loops)
        def step(carry, _):
            # lax.cond, not where: post-termination iterations must not
            # execute the body at all — a body that divides/gathers on
            # the frozen carry could emit inf/NaN Jacobian entries, and
            # 0-cotangent × inf = NaN would poison the scan transpose
            return lax.cond(jnp.asarray(c(carry)), b,
                            lambda cr: cr, carry), None

        final = _bounded_scan(step, carry0, bound,
                              lambda fin: c(fin), "while")
    else:
        final = lax.while_loop(c, b, carry0)
    out = list(init)
    for j, i in enumerate(live):
        out[i] = Tensor(final[j]) if wrap_t[j] else final[j]
    return tuple(out)


class _TracedRange:
    """range() whose bounds are traced tensors — consumed by
    ``convert_for`` (lowered to lax.fori_loop)."""

    def __init__(self, *args):
        vals = [jnp.asarray(_val(a)) for a in args]
        if len(vals) == 1:
            self.lower, self.upper, self.step = 0, vals[0], 1
        elif len(vals) == 2:
            self.lower, self.upper, self.step = vals[0], vals[1], 1
        else:
            self.lower, self.upper, self.step = vals

    def __iter__(self):
        raise NotImplementedError(
            "dy2static: a tensor-bounded range() can only drive a "
            "converted for loop (no break/continue/return inside)")


def convert_range(*args):
    """range over possibly-traced bounds."""
    if any(_is_traced(a) for a in args):
        return _TracedRange(*args)
    return range(*(int(_val(a)) for a in args))


def convert_range_guard(*args):
    """range at a non-convertible ``for`` site (break/continue/return in
    the body): concrete bounds keep Python semantics; traced bounds get
    a clear error instead of a silent mistrace."""
    if any(_is_traced(a) for a in args):
        raise NotImplementedError(
            "dy2static: break/continue/return inside a tensor-bounded "
            "for loop is not supported (XLA control flow has no early "
            "exit); hoist the exit into a mask or a while_loop condition")
    return range(*(int(_val(a)) for a in args))


def convert_for(iterable, body_fn, init):
    """for over a possibly-traced iterable.

    ``body_fn(loop_var, *carried) -> tuple(carried)``.  Dispatch:
    - ``_TracedRange`` -> masked ``lax.scan`` under ``bounded_loops``
      (reverse-mode differentiable), else ``lax.fori_loop`` (forward
      only — dynamic trip count has no transpose)
    - traced Tensor -> ``lax.scan`` over the leading axis (reverse-mode
      differentiable)
    - anything else -> plain Python iteration (exact semantics)

    The loop variable is NOT visible after the loop (unlike Python);
    carried entries may be ``_UNDEF`` like convert_while_loop.
    """
    init = tuple(init)
    traced_tensor = isinstance(iterable, Tensor) and _is_traced(iterable)
    if not isinstance(iterable, _TracedRange) and not traced_tensor:
        out = init
        for item in iterable:
            out = tuple(body_fn(item, *out))
        return out

    live = [i for i, v in enumerate(init) if v is not _UNDEF]
    if not live:
        raise NotImplementedError(
            "dy2static for: no loop-carried variable is bound before the "
            "loop; initialize the state first (XLA loops need concrete "
            "initial shapes)")
    wrap_t = [isinstance(init[i], Tensor) for i in live]

    def full(carry):
        args = list(init)
        for j, i in enumerate(live):
            args[i] = Tensor(carry[j]) if wrap_t[j] else carry[j]
        return args

    carry0 = tuple(jnp.asarray(_val(init[i])) for i in live)

    if isinstance(iterable, _TracedRange):
        lower, upper, step = iterable.lower, iterable.upper, iterable.step
        n_iters = jnp.maximum(
            (upper - lower + step - jnp.sign(step)) // step, 0)

        def b(k, carry):
            i = lower + k * step
            out = tuple(body_fn(Tensor(i), *full(carry)))
            return tuple(jnp.asarray(_val(out[j])) for j in live)

        bound = active_loop_bound()
        if bound is not None:
            # masked scan: differentiable bounded fori (see bounded_loops)
            def sbody(carry, k):
                # cond, not where — see the while lowering above
                return lax.cond(k < n_iters,
                                lambda cr: b(k, cr),
                                lambda cr: cr, carry), None

            final = _bounded_scan(sbody, carry0, bound,
                                  lambda fin: n_iters > bound, "for")
        else:
            final = lax.fori_loop(0, n_iters, b, carry0)
    else:
        def f(carry, x):
            out = tuple(body_fn(Tensor(x), *full(carry)))
            return tuple(jnp.asarray(_val(out[j])) for j in live), None

        final, _ = lax.scan(f, carry0, _val(iterable))

    out = list(init)
    for j, i in enumerate(live):
        out[i] = Tensor(final[j]) if wrap_t[j] else final[j]
    return tuple(out)


def convert_logical_and(a_fn, b_fn):
    a = a_fn()
    if _is_traced(a):
        return Tensor(jnp.logical_and(_val(a), _val(b_fn())))
    return a and b_fn()


def convert_logical_or(a_fn, b_fn):
    a = a_fn()
    if _is_traced(a):
        return Tensor(jnp.logical_or(_val(a), _val(b_fn())))
    return a or b_fn()


def convert_logical_not(a):
    if _is_traced(a):
        return Tensor(jnp.logical_not(_val(a)))
    return not a


_RUNTIME = {
    "__pt_ifelse__": convert_ifelse,
    "__pt_while__": convert_while_loop,
    "__pt_for__": convert_for,
    "__pt_range__": convert_range,
    "__pt_range_guard__": convert_range_guard,
    "__pt_and__": convert_logical_and,
    "__pt_or__": convert_logical_or,
    "__pt_not__": convert_logical_not,
    "__pt_ld__": _load,
}


# -- static analysis helpers -------------------------------------------------
_SCOPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda, ast.ClassDef,
           ast.ListComp, ast.SetComp, ast.DictComp, ast.GeneratorExp)


def _walk_scope(node):
    """Walk statements without descending into nested scopes."""
    stack = list(node) if isinstance(node, list) else [node]
    while stack:
        n = stack.pop()
        yield n
        for child in ast.iter_child_nodes(n):
            if not isinstance(child, _SCOPES):
                stack.append(child)


def _target_names(target, names, ok):
    if isinstance(target, ast.Name):
        names.add(target.id)
    elif isinstance(target, (ast.Tuple, ast.List)):
        for elt in target.elts:
            _target_names(elt, names, ok)
    elif isinstance(target, ast.Starred):
        _target_names(target.value, names, ok)
    else:
        # attribute/subscript stores are side effects a traced branch
        # cannot replay — caller must leave this construct untransformed
        ok[0] = False


def _assigned_names(stmts):
    """(names, transformable) assigned by a statement list."""
    names, ok = set(), [True]
    for n in _walk_scope(stmts):
        if isinstance(n, ast.Assign):
            for t in n.targets:
                _target_names(t, names, ok)
        elif isinstance(n, (ast.AugAssign, ast.AnnAssign, ast.For)):
            _target_names(n.target, names, ok)
        elif isinstance(n, ast.withitem) and n.optional_vars is not None:
            _target_names(n.optional_vars, names, ok)
        elif isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            names.add(n.name)
        elif isinstance(n, (ast.Delete, ast.Global, ast.Nonlocal)):
            ok[0] = False
    return names, ok[0]


def _loop_level_break(stmts):
    """break/continue belonging to THIS loop (not a nested one)."""
    stack = list(stmts)
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.Break, ast.Continue)):
            return True
        for child in ast.iter_child_nodes(n):
            if not isinstance(child, _SCOPES + (ast.For, ast.While)):
                stack.append(child)
    return False


def _count_returns(stmts):
    return sum(1 for n in _walk_scope(stmts) if isinstance(n, ast.Return))


def _name(id_, ctx=None):
    return ast.Name(id=id_, ctx=ctx or ast.Load())


def _ld_tuple(names):
    """(__pt_ld__(lambda: v1), __pt_ld__(lambda: v2), ...)"""
    elts = [ast.Call(func=_name("__pt_ld__"),
                     args=[ast.Lambda(
                         args=ast.arguments(posonlyargs=[], args=[],
                                            kwonlyargs=[], kw_defaults=[],
                                            defaults=[]),
                         body=_name(v))],
                     keywords=[]) for v in names]
    return ast.Tuple(elts=elts, ctx=ast.Load())


def _fn_def(fname, params, body):
    return ast.FunctionDef(
        name=fname,
        args=ast.arguments(
            posonlyargs=[],
            args=[ast.arg(arg=p) for p in params],
            kwonlyargs=[], kw_defaults=[], defaults=[]),
        body=body, decorator_list=[], returns=None, type_comment=None,
        type_params=[])


class _CtrlFlowTransformer(ast.NodeTransformer):
    def __init__(self):
        self.changed = False
        self._n = 0

    def _uid(self):
        self._n += 1
        return self._n

    # -- boolean ops ---------------------------------------------------------
    def visit_BoolOp(self, node):
        node = self.generic_visit(node)
        conv = "__pt_and__" if isinstance(node.op, ast.And) else "__pt_or__"
        out = node.values[0]
        for rhs in node.values[1:]:
            thunk_l = ast.Lambda(
                args=ast.arguments(posonlyargs=[], args=[], kwonlyargs=[],
                                   kw_defaults=[], defaults=[]),
                body=out)
            thunk_r = ast.Lambda(
                args=ast.arguments(posonlyargs=[], args=[], kwonlyargs=[],
                                   kw_defaults=[], defaults=[]),
                body=rhs)
            out = ast.Call(func=_name(conv), args=[thunk_l, thunk_r],
                           keywords=[])
        self.changed = True
        return out

    def visit_UnaryOp(self, node):
        node = self.generic_visit(node)
        if isinstance(node.op, ast.Not):
            self.changed = True
            return ast.Call(func=_name("__pt_not__"), args=[node.operand],
                            keywords=[])
        return node

    # -- if ------------------------------------------------------------------
    def visit_If(self, node):
        node = self.generic_visit(node)
        n = self._uid()
        t_ret = _count_returns(node.body)
        f_ret = _count_returns(node.orelse)
        t_names, t_ok = _assigned_names(node.body)
        f_names, f_ok = _assigned_names(node.orelse)

        if t_ret == 0 and f_ret == 0 and t_ok and f_ok:
            out = sorted(t_names | f_names)
            if not out:
                return node  # side-effect-only branches: keep Python
            ret = ast.Return(value=ast.Tuple(
                elts=[_name(v) for v in out], ctx=ast.Load()))
            tfn = _fn_def(f"_pt_true_{n}", out, node.body + [ret])
            ffn = _fn_def(f"_pt_false_{n}", out,
                          (node.orelse or [ast.Pass()]) + [ret])
            call = ast.Call(
                func=_name("__pt_ifelse__"),
                args=[node.test, _name(f"_pt_true_{n}"),
                      _name(f"_pt_false_{n}"), _ld_tuple(out)],
                keywords=[])
            unpack = ast.Assign(
                targets=[ast.Tuple(elts=[_name(v, ast.Store()) for v in out],
                                   ctx=ast.Store())],
                value=call)
            self.changed = True
            return [tfn, ffn, unpack]

        # both branches end in their single return -> return the cond value
        if (t_ret == 1 and f_ret == 1 and node.orelse
                and isinstance(node.body[-1], ast.Return)
                and isinstance(node.orelse[-1], ast.Return)
                and t_ok and f_ok):
            out = sorted(t_names | f_names)
            tfn = _fn_def(f"_pt_true_{n}", out, node.body)
            ffn = _fn_def(f"_pt_false_{n}", out, node.orelse)
            call = ast.Call(
                func=_name("__pt_ifelse__"),
                args=[node.test, _name(f"_pt_true_{n}"),
                      _name(f"_pt_false_{n}"), _ld_tuple(out)],
                keywords=[])
            self.changed = True
            return [tfn, ffn, ast.Return(value=call)]

        return node  # early-return / side-effect shapes: keep Python

    # -- for -----------------------------------------------------------------
    @staticmethod
    def _is_range_call(e):
        return (isinstance(e, ast.Call) and isinstance(e.func, ast.Name)
                and e.func.id == "range" and not e.keywords)

    def visit_For(self, node):
        node = self.generic_visit(node)
        is_range = self._is_range_call(node.iter)

        def guarded():
            # non-convertible shape: keep Python, but a range() iter gets
            # the runtime guard so traced bounds error clearly
            if is_range:
                node.iter = ast.Call(func=_name("__pt_range_guard__"),
                                     args=node.iter.args, keywords=[])
                self.changed = True
            return node

        if node.orelse or not isinstance(node.target, ast.Name) \
                or _loop_level_break(node.body) or _count_returns(node.body):
            return guarded()
        names, ok = _assigned_names(node.body)
        names.discard(node.target.id)   # loop var is a body param
        if not names or not ok:
            return guarded()
        n = self._uid()
        out = sorted(names)
        ret = ast.Return(value=ast.Tuple(
            elts=[_name(v) for v in out], ctx=ast.Load()))
        bfn = _fn_def(f"_pt_fbody_{n}", [node.target.id] + out,
                      node.body + [ret])
        it = ast.Call(func=_name("__pt_range__"), args=node.iter.args,
                      keywords=[]) if is_range else node.iter
        call = ast.Call(
            func=_name("__pt_for__"),
            args=[it, _name(f"_pt_fbody_{n}"), _ld_tuple(out)],
            keywords=[])
        unpack = ast.Assign(
            targets=[ast.Tuple(elts=[_name(v, ast.Store()) for v in out],
                               ctx=ast.Store())],
            value=call)
        self.changed = True
        return [bfn, unpack]

    # -- while ---------------------------------------------------------------
    def visit_While(self, node):
        node = self.generic_visit(node)
        if node.orelse or _loop_level_break(node.body) \
                or _count_returns(node.body):
            return node
        names, ok = _assigned_names(node.body)
        if not names or not ok:
            return node
        n = self._uid()
        out = sorted(names)
        cfn = _fn_def(f"_pt_wcond_{n}", out,
                      [ast.Return(value=node.test)])
        ret = ast.Return(value=ast.Tuple(
            elts=[_name(v) for v in out], ctx=ast.Load()))
        bfn = _fn_def(f"_pt_wbody_{n}", out, node.body + [ret])
        call = ast.Call(
            func=_name("__pt_while__"),
            args=[_name(f"_pt_wcond_{n}"), _name(f"_pt_wbody_{n}"),
                  _ld_tuple(out)],
            keywords=[])
        unpack = ast.Assign(
            targets=[ast.Tuple(elts=[_name(v, ast.Store()) for v in out],
                               ctx=ast.Store())],
            value=call)
        self.changed = True
        return [cfn, bfn, unpack]


def transform_function(fn):
    """AST-rewrite a function's tensor control flow.  Returns
    (function, changed); on any unsupported shape the original function
    is returned unchanged (plain tracing semantics)."""
    try:
        src = textwrap.dedent(inspect.getsource(fn))
    except (OSError, TypeError):
        return fn, False
    if "super(" in src:
        # zero-arg super() needs the __class__ cell, which a recompiled
        # function body does not carry
        return fn, False
    try:
        tree = ast.parse(src)
    except SyntaxError:
        return fn, False
    fdef = tree.body[0]
    if not isinstance(fdef, ast.FunctionDef):
        return fn, False
    fdef.decorator_list = []
    tr = _CtrlFlowTransformer()
    tree = tr.visit(tree)
    if not tr.changed:
        return fn, False
    ast.fix_missing_locations(tree)
    try:
        code = compile(tree, filename=f"<dy2static {fn.__qualname__}>",
                       mode="exec")
    except (SyntaxError, ValueError):
        return fn, False
    glb = dict(fn.__globals__)
    if fn.__closure__:
        glb.update({name: cell.cell_contents
                    for name, cell in zip(fn.__code__.co_freevars,
                                          fn.__closure__)})
    glb.update(_RUNTIME)
    ns = {}
    exec(code, glb, ns)
    new_fn = functools.wraps(fn)(ns[fdef.name])
    return new_fn, True
