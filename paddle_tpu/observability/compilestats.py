"""Compile telemetry: what every jit surface *costs*, and when it
silently recompiled.

The PR 5 telemetry layer sees the runtime — step latency, chunk counts,
wire bytes — but nothing about the compiled surfaces themselves: how
often a surface compiled, what one dispatch of it analytically costs
(FLOPs, bytes accessed, HBM footprint), or that a shape/dtype drift
quietly retraced a hot executable (the jit cache-miss class of perf bug:
a minority-dtype param slipping through ``refresh_weights`` retraces the
whole decode program; a non-bucketed prompt length compiles a prefill
per request).  This module closes that gap:

- :func:`wrap` takes an already-``jax.jit``-ed callable and a *surface*
  name (matching the ``analysis.jit_surface`` registry vocabulary) and
  returns a :class:`CompiledSurface` that calls it and keeps one record
  per shape signature.  After a call that compiled it walks the
  signature, records the lowering's ``cost_analysis()`` (FLOPs / bytes
  accessed), the compiled ``memory_analysis()`` footprint and the
  compile wall time — ONE compile per signature; a call JAX's own
  dispatch knows walks nothing (``pt_compile_dispatch_total``);
- every record lands in the ``pt_compile_*`` metrics (labels:
  ``surface``) and in a module registry :func:`snapshot` the roofline
  arithmetic joins against measured latency (``roofline_from_stats``);
- the **retrace sentinel**: each wrapper declares a compile *budget* —
  the number of distinct signatures the surface legitimately needs in
  its lifetime (1 for a chunked decode loop; ``len(buckets)`` for a
  bucket-compiled prefill family).  Compiling past the budget emits the
  guardian ``compile_retrace`` event carrying the old-vs-new signature
  diff, turning silent recompilation into a machine-checked event.

Zero new host syncs: everything here is host-side bookkeeping around
the dispatch (trace/lower/compile are host work jax does anyway); no
device value is ever read back.  The module sits in
``analysis.allowlist.MONITORED_MODULES`` with zero budgeted sync
entries, and the PR 5 A/B device-transfer test is extended to cover it
(``tests/test_compile_tracing.py``).

The grad_comm reducer closures have no executable of their own — they
are traced *into* the ``hapi.train_step_comm`` stepper, so their cost
shows up in that surface's row.
"""
import json
import os
import re
import threading
import time
import weakref

from . import metrics as _metrics

__all__ = ["wrap", "CompiledSurface", "signature", "signature_diff",
           "snapshot", "reset", "surfaces", "retrace_total",
           "hlo_op_names", "op_names", "write_op_names", "OP_NAMES_FILE"]


# -- shape signatures -------------------------------------------------------

def signature(args):
    """Canonical (hashable) shape/dtype signature of one positional
    argument tuple: array leaves become ``(shape, dtype, weak)``
    triples, scalars keep their python type, and the (hashable)
    pytree treedef rides along so a ``None``-vs-array cache split is
    part of the key (mirroring jax's own dispatch key closely enough
    that one signature == one executable)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(args)
    sig = []
    for x in leaves:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is not None and dtype is not None:
            sig.append((tuple(shape), str(dtype),
                        bool(getattr(x, "weak_type", False))))
        else:
            sig.append((type(x).__name__,))
    return (treedef, tuple(sig))


def _fmt_leaf(leaf):
    if len(leaf) == 1:
        return leaf[0]
    shape, dtype, weak = leaf
    return f"{dtype}[{','.join(str(d) for d in shape)}]" + \
        ("~" if weak else "")


def signature_diff(old, new):
    """Human-readable old-vs-new diff for the retrace event: leaf
    positions whose shape/dtype changed, plus a structure note when the
    pytrees differ."""
    if old is None:
        return "first compile"
    parts = []
    if old[0] != new[0]:
        parts.append("pytree structure changed")
    o, n = old[1], new[1]
    if len(o) != len(n):
        parts.append(f"leaf count {len(o)} -> {len(n)}")
    for i, (a, b) in enumerate(zip(o, n)):
        if a != b:
            parts.append(f"arg[{i}]: {_fmt_leaf(a)} -> {_fmt_leaf(b)}")
    return "; ".join(parts[:8]) if parts else "identical signature"


# -- module registry --------------------------------------------------------
#
# Per-surface cumulative stats, independent of wrapper lifetimes (an
# engine rebuild makes a fresh CompiledSurface, but the surface's cost
# story is one story).  Budget enforcement is deliberately
# per-*wrapper*: a rebuilt engine legitimately re-pays its compiles,
# while one wrapper compiling twice IS the retrace bug.

_LOCK = threading.Lock()
_SURFACES = {}     # surface -> {"compiles", "retraces", "wall_ms",
#                                "sigs": {sig: rec}, "last": rec}


def _record(surface, sig, wall_ms, cost, mem, kinds=None):
    rec = {"signature": [_fmt_leaf(l) for l in sig[1]],
           "compile_ms": round(wall_ms, 3),
           "flops": cost.get("flops") if cost else None,
           "bytes_accessed": cost.get("bytes accessed") if cost else None,
           "memory_bytes": mem}
    with _LOCK:
        st = _SURFACES.setdefault(
            surface, {"compiles": 0, "retraces": 0, "wall_ms": 0.0,
                      "sigs": {}, "last": None})
        st["compiles"] += 1
        st["wall_ms"] += wall_ms
        st["sigs"][sig] = rec
        st["last"] = rec
    if _metrics.enabled():
        _metrics.inc("pt_compile_compiles_total", surface=surface)
        _metrics.observe("pt_compile_wall_ms", wall_ms, surface=surface)
        if rec["flops"] is not None:
            _metrics.set_gauge("pt_compile_flops", rec["flops"],
                               surface=surface)
        if rec["bytes_accessed"] is not None:
            _metrics.set_gauge("pt_compile_bytes_accessed",
                               rec["bytes_accessed"], surface=surface)
        if mem is not None:
            _metrics.set_gauge("pt_compile_memory_bytes", mem,
                               surface=surface)
    # hand the full memory_analysis breakdown to the HBM ledger (it
    # books pt_memory_static_bytes{surface,kind}, runs the envelope
    # budget check, and feeds memory.json) — even an all-None
    # breakdown lands a ledger row, so "surface compiled but backend
    # reported nothing" is visible rather than absent
    from . import memory as _memory
    _memory.record_static(surface, kinds or {}, cost)
    return rec


def surfaces():
    """Names of every surface that compiled at least once."""
    with _LOCK:
        return sorted(_SURFACES)


def snapshot():
    """Per-surface cumulative compile stats (the roofline view's
    analytical half): ``{surface: {compiles, retraces, wall_ms,
    signatures, flops, bytes_accessed, memory_bytes}}`` where the cost
    numbers are the LAST compiled signature's (documented: a
    multi-signature family reports its most recent member)."""
    out = {}
    with _LOCK:
        for name, st in sorted(_SURFACES.items()):
            last = st["last"] or {}
            out[name] = {
                "compiles": st["compiles"],
                "retraces": st["retraces"],
                "compile_wall_ms": round(st["wall_ms"], 3),
                "signatures": len(st["sigs"]),
                "flops": last.get("flops"),
                "bytes_accessed": last.get("bytes_accessed"),
                "memory_bytes": last.get("memory_bytes"),
            }
    return out


def retrace_total():
    """Cumulative over-budget recompiles across all surfaces — one
    lock, one sum, no dict building (the SLO watchdog polls this per
    flight sample, so it must stay cheap)."""
    with _LOCK:
        return sum(st["retraces"] for st in _SURFACES.values())


def reset():
    """Drop all per-surface stats (test isolation / bench per-run
    snapshots).  Wrapper-local executable caches are untouched —
    compiled programs stay warm."""
    with _LOCK:
        _SURFACES.clear()


def _count_retrace(surface):
    with _LOCK:
        st = _SURFACES.get(surface)
        if st is not None:
            st["retraces"] += 1
    if _metrics.enabled():
        _metrics.inc("pt_compile_retraces_total", surface=surface)


# -- op_name maps for the device view ---------------------------------------
#
# libtpu 0.0.34 names a device event by its HLO instruction text and puts
# no ``op_name`` on it, so ``report --device`` joins the event's
# instruction name and program to the ``metadata={op_name=...}`` of the
# executables the wrappers below hold.  Nothing here runs unless asked:
# ``as_text()`` of a whole train step is megabytes.

OP_NAMES_FILE = "op_names.json"     # sidecar beside a trace's plugins/
_WRAPPERS = weakref.WeakSet()       # live CompiledSurface objects
_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')


def hlo_op_names(text):
    """(module name, {instruction name: op_name}) of one executable's
    HLO text.  Instruction names are unique in a module, so one flat map
    serves the entry computation, loop bodies and fused computations."""
    module, names = None, {}
    for line in text.splitlines():
        if module is None:
            m = _HLO_MODULE.match(line)
            if m:
                module = m.group(1)
            continue
        m = _HLO_INSTR.match(line)
        if m:
            op = _HLO_OP_NAME.search(line)
            if op:
                names[m.group(1)] = op.group(1)
    return module, names


def op_names():
    """{program name: {instruction name: op_name}} over every executable
    the live wrappers hold.  Programs that share a name (the prefill
    buckets are all ``jit_paged_prefill``) share one map; an instruction
    they disagree on maps to None and the device view books it under its
    HLO name."""
    out = {}
    for w in list(_WRAPPERS):
        for entry in list(w._cache.values()):
            module, names = hlo_op_names(entry.as_text())
            have = out.setdefault(module, {})
            for instr, op in names.items():
                if have.setdefault(instr, op) != op:
                    have[instr] = None
    return out


def write_op_names(trace_dir):
    """Write :func:`op_names` beside a profiler trace
    (``<trace_dir>/op_names.json``) for ``report --device`` to join;
    returns the path, or None where no wrapper holds an executable."""
    table = op_names()
    if not table:
        return None
    os.makedirs(trace_dir, exist_ok=True)
    path = os.path.join(trace_dir, OP_NAMES_FILE)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(table, f)
    os.replace(tmp, path)
    return path


# -- the wrapper ------------------------------------------------------------

class CompiledSurface:
    """Tracks the executables of one jit surface; dispatch is the jitted
    callable's own.  JAX's C++ dispatch decides, by the pytree and avals
    it keys its cache on, whether a call is known, and a known call
    costs what ``jax.jit``'s cached call costs.  Only after a call that
    added an entry to that cache is the Python :func:`signature` walked;
    a new signature is lowered again for its analysis (lowering and
    executable come out of JAX's caches: ONE backend compile each),
    recorded, and held against the budget.  No fallback: a trace,
    compile or dispatch error propagates (only the *analysis* readouts
    are best-effort).
    """

    def __init__(self, fn, surface, budget=None):
        self._fn = fn
        self.surface = surface
        self.budget = budget
        self._cache = {}       # sig -> AOT executable (analysis, HLO text)
        self._last_sig = None
        self._lock = threading.Lock()
        _WRAPPERS.add(self)

    @property
    def compiles(self):
        return len(self._cache)

    def __call__(self, *args):
        fn = self._fn
        known = fn._cache_size()
        t0 = time.perf_counter()
        out = fn(*args)
        new = fn._cache_size() != known
        _metrics.inc("pt_compile_dispatch_total", surface=self.surface,
                     path="signature" if new else "fast")
        if new:
            # donated arguments are deleted by now; shapes and dtypes stay
            sig = signature(args)
            if sig not in self._cache:
                self._compiled(sig, args, t0)
        return out

    def _compiled(self, sig, args, t0):
        with self._lock:
            if sig in self._cache:
                return
            cost = mem = None
            kinds = {}
            lowered = self._fn.lower(*args)
            try:
                cost = lowered.cost_analysis()
            except Exception:
                cost = None
            entry = lowered.compile()
            if not cost:
                # XLA:TPU answers only for the compiled executable
                try:
                    cost = entry.cost_analysis()
                except Exception:
                    cost = None
            try:
                ma = entry.memory_analysis()
                # getattr-guard every field: XLA:CPU under-reports
                # (temp/generated-code often absent) — the ledger
                # keeps whatever the backend does expose
                for kind, attr in (
                        ("argument", "argument_size_in_bytes"),
                        ("output", "output_size_in_bytes"),
                        ("temp", "temp_size_in_bytes"),
                        ("generated_code",
                         "generated_code_size_in_bytes")):
                    v = getattr(ma, attr, None)
                    if v is not None:
                        kinds[kind] = int(v)
                known = [kinds.get(k) for k in
                         ("argument", "output", "temp")]
                if any(v is not None for v in known):
                    mem = sum(v for v in known if v is not None)
            except Exception:
                mem = None
            wall_ms = (time.perf_counter() - t0) * 1e3
            _record(self.surface, sig, wall_ms, cost, mem, kinds=kinds)
            n = len(self._cache) + 1
            if self.budget is not None and n > self.budget:
                self._retrace(sig, n)
            self._last_sig = sig
            self._cache[sig] = entry

    def _retrace(self, sig, n):
        diff = signature_diff(self._last_sig, sig)
        _count_retrace(self.surface)
        from ..framework import guardian
        guardian.emit("compile_retrace", surface=self.surface,
                      compiles=n, budget=self.budget, diff=diff)


def wrap(fn, surface, budget=None):
    """Wrap an already-jitted callable as a tracked
    :class:`CompiledSurface`.  ``surface`` names the jit surface
    (``hapi.train_step``, ``serving.decode_chunk``, ...); ``budget`` is
    the number of legitimate compiles in this wrapper's lifetime (None
    = no retrace sentinel, count-only)."""
    return CompiledSurface(fn, surface, budget=budget)
