"""Platform-aware kernel registry (the ``_use_pallas`` replacement).

Before this module, every fused kernel carried its own ad-hoc gate
(``attention._use_pallas``, ``fused_xent``'s backend check, per-file
env knobs) and none of them agreed on how a kernel is selected, forced,
or attributed.  The registry centralizes the *policy*:

- **per-platform impl selection** — each kernel registers one or more
  implementations with the platforms they run on (``tpu`` for Pallas
  kernels, ``*`` for the XLA reference paths).  ``choose()`` picks the
  first implementation matching the active backend, so TPU trains
  through the Pallas hot path while CPU/GPU keep the XLA lowering with
  identical math.
- **opt-in interpret mode** (``PADDLE_TPU_KERNEL_INTERPRET=1``) — the
  dispatch behaves exactly as on TPU but every Pallas kernel runs in
  interpreter mode, so CI exercises the *selected* kernels (including
  their custom VJPs) on the CPU backend.  This is how the train-step
  parity suite machine-checks flash-vs-dense gradients.  It is a
  CPU-only knob: set on a TPU backend it is an error, never a chip run
  that quietly interprets its kernels.
- **overrides** — ``force(kernel, impl)`` (the ``sdp_kernel`` context
  manager hook) and the env knob ``PADDLE_TPU_KERNEL_<KERNEL>=<impl>``.
  Overrides are read at TRACE time: a cached executable keeps the impl
  it was traced with (the shape-keyed stepper cache contract); sweeps
  that flip impls build fresh steppers.  An unknown impl name raises,
  and so does a forced impl that cannot run on a TPU backend; no
  registered impl for the platform raises too.
- **flash block sizes** — :func:`flash_blocks` is one static rule on
  the (padded) sequence length; nothing outside the arguments changes
  what gets compiled.
- **roofline attribution** — kernels registered here are dispatched
  through :class:`TrackedKernel`, which wraps standalone (non-traced)
  calls in ``observability.compilestats.wrap`` so ``roofline_from_stats``
  attributes per-kernel FLOPs / bytes / dispatch latency under the
  ``kernel.*`` surface names below.  Calls made *inside* an outer jit
  trace (the hapi train stepper) inline into the caller's surface and
  are attributed there, exactly like the grad_comm reducers.

Selection decisions are recorded in the ``pt_kernel_*`` metrics
(catalog.py; docs/kernels.md documents the dispatch rules).
"""
import functools
import os
import threading
from collections import namedtuple

import jax

__all__ = [
    "register", "choose", "impl_fn", "force", "interpret_enabled",
    "record_select", "record_fallback", "TrackedKernel", "flash_blocks",
    "Selection", "partitioned", "current_partition",
]

# -- compile-surface vocabulary --------------------------------------------
#
# One constant per tracked kernel surface; the ``*_SURFACE`` spelling is
# collected by the graph-discipline vocabulary lint exactly like a
# compilestats.wrap literal, and analysis.allowlist.COMPILE_SURFACES
# mirrors these names (tests/test_graph_discipline.py cross-references
# both directions).
FLASH_FWD_SURFACE = "kernel.flash_fwd"
FLASH_FWD_LSE_SURFACE = "kernel.flash_fwd_lse"
FLASH_BWD_SURFACE = "kernel.flash_bwd"
XENT_FWD_SURFACE = "kernel.xent_fwd"
XENT_BWD_SURFACE = "kernel.xent_bwd"
QUANT_MATMUL_SURFACE = "kernel.quant_matmul"

_INTERPRET_ENV = "PADDLE_TPU_KERNEL_INTERPRET"

_LOCK = threading.Lock()
_IMPLS = {}      # kernel -> [(impl_name, fn, platforms)]  (registration order)
_FORCED = {}     # kernel -> impl_name (force() context overrides)

Selection = namedtuple("Selection", ["impl", "forced", "interpret"])


def _metrics():
    from ..observability import metrics
    return metrics


def register(kernel, impl, fn=None, platforms=("tpu",)):
    """Register ``impl`` (e.g. ``"pallas"``) for ``kernel`` (e.g.
    ``"attention"``).  ``platforms`` lists backends the impl runs
    compiled on (``"*"`` = everywhere); Pallas impls additionally become
    selectable off-TPU when interpret mode is on.  Re-registering the
    same (kernel, impl) replaces the entry (module reloads in tests)."""
    with _LOCK:
        entries = _IMPLS.setdefault(kernel, [])
        entries[:] = [e for e in entries if e[0] != impl]
        entries.append((impl, fn, tuple(platforms)))


def impl_fn(kernel, impl):
    """The registered callable for (kernel, impl); None when the impl
    keeps its dispatch at the call site (attention's in-module paths)."""
    with _LOCK:
        for name, fn, _ in _IMPLS.get(kernel, ()):
            if name == impl:
                return fn
    raise KeyError(f"kernel {kernel!r} has no impl {impl!r}")


def _ensure_defaults(kernel):
    """Lazy-import the module that registers ``kernel``'s default impls
    (a bare ``choose()`` before the kernel module loaded must still see
    the catalog; the imports are cycles-safe because registration runs
    at module top level and ``choose`` at call time)."""
    with _LOCK:
        present = kernel in _IMPLS
    if present:
        return
    if kernel == "attention":
        from ..nn.functional import attention  # noqa: F401 (registers)
    elif kernel == "xent":
        from .pallas import fused_xent         # noqa: F401 (registers)
    elif kernel == "quant_matmul":
        from . import quant_dispatch           # noqa: F401 (registers)
    elif kernel == "grouped_matmul":
        from . import grouped_matmul           # noqa: F401 (registers)
    elif kernel == "paged_attention":
        from .pallas import paged_attention    # noqa: F401 (registers)


def interpret_enabled():
    """CI-parity knob: treat the platform as TPU and run every selected
    Pallas kernel in interpreter mode.  CPU-only: on a TPU backend the
    variable is an error (a chip run must never interpret its kernels
    because of a stray environment variable)."""
    on = os.environ.get(_INTERPRET_ENV, "") not in ("", "0", "false")
    if on and jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{_INTERPRET_ENV} is set on a TPU backend: Pallas kernels "
            "would run interpreted on the chip; unset it")
    return on


def choose(kernel, platform=None, book=True):
    """Pick the implementation for ``kernel`` on ``platform`` (default:
    the active jax backend).  Order: ``force()`` context > env override
    > first registered impl whose platform matches.  Returns
    ``Selection(impl, forced, interpret)``; ``interpret`` is True when
    the pick is a Pallas impl running off-platform under interpret
    mode.  The selection is counted in ``pt_kernel_selects_total``;
    a dispatch site that tells variants of one impl apart (attention's
    ``pallas_transposed``) passes ``book=False`` and books the label
    itself through :func:`record_select`."""
    plat = platform or jax.default_backend()
    interp = interpret_enabled()
    _ensure_defaults(kernel)
    with _LOCK:
        entries = list(_IMPLS.get(kernel, ()))
        forced_name = _FORCED.get(kernel)
    if not entries:
        raise KeyError(f"unknown kernel {kernel!r}")
    forced = forced_name or os.environ.get(
        f"PADDLE_TPU_KERNEL_{kernel.upper()}")
    sel = None
    if forced:
        plats = next((pl_ for name, _fn, pl_ in entries if name == forced),
                     None)
        if plats is None:
            raise ValueError(
                f"kernel {kernel!r} has no impl {forced!r} (known: "
                f"{[e[0] for e in entries]}); check force() / "
                f"PADDLE_TPU_KERNEL_{kernel.upper()}")
        on_plat = "*" in plats or plat in plats
        if on_plat or interp:
            sel = Selection(forced, True, bool(not on_plat and interp))
        elif plat == "tpu":
            raise RuntimeError(
                f"kernel {kernel!r}: forced impl {forced!r} runs on "
                f"{plats}, not on the TPU backend")
        # off the chip, a forced TPU-only impl without interpret mode
        # keeps the platform default: a script written for the chip
        # still runs its math on the CPU mesh
    if sel is None:
        for name, _fn, plats in entries:
            if "*" in plats or plat in plats or ("tpu" in plats and interp):
                sel = Selection(name, False,
                                bool(plat not in plats and "*" not in plats
                                     and interp))
                break
    if sel is None:
        raise RuntimeError(
            f"kernel {kernel!r} has no impl for platform {plat!r} "
            f"(registered: {[(e[0], e[2]) for e in entries]})")
    if book:
        record_select(kernel, sel.impl)
    return sel


def record_select(kernel, impl):
    """Book one dispatch decision in ``pt_kernel_selects_total``."""
    m = _metrics()
    if m.enabled():
        m.inc("pt_kernel_selects_total", kernel=kernel, impl=impl)


def record_fallback(kernel, reason):
    """Book a constraint fallback: the platform policy picked a Pallas
    impl but a kernel-specific contract (mask shape, non-default scale,
    dropout, VMEM cap) routed this call to the XLA path instead.  The
    reasons surface in ``pt_kernel_fallbacks_total`` so a silently
    dense-running config is visible in telemetry."""
    m = _metrics()
    if m.enabled():
        m.inc("pt_kernel_fallbacks_total", kernel=kernel, reason=reason)


class force:
    """Context manager forcing ``kernel`` to ``impl`` (the ``sdp_kernel``
    hook).  Nestable; restores the previous override on exit."""

    def __init__(self, kernel, impl):
        self.kernel = kernel
        self.impl = impl
        self._prev = None
        self._had = False

    def __enter__(self):
        with _LOCK:
            self._had = self.kernel in _FORCED
            self._prev = _FORCED.get(self.kernel)
            _FORCED[self.kernel] = self.impl
        return self

    def __exit__(self, *exc):
        with _LOCK:
            if self._had:
                _FORCED[self.kernel] = self._prev
            else:
                _FORCED.pop(self.kernel, None)
        return False


# -- multi-device traces ----------------------------------------------------
#
# XLA cannot partition a Mosaic kernel: under a jit whose operands live
# on several devices the lowering raises "Mosaic kernels cannot be
# automatically partitioned. Please wrap the call in a shard_map" (first
# seen on four v5e chips, PR 21 — the CPU mesh never lowers Mosaic).  So
# the steppers that trace under a mesh (hapi's placement plan, the fleet
# pipeline stepper, models/gpt_hybrid) enter ``partitioned`` for the
# trace, and the kernel dispatch sites wrap their Pallas calls in a
# ``jax.shard_map`` that is manual over every mesh axis: batch rows split
# over the batch axes, heads over the tensor-parallel axis, everything
# else replicated.

_PARTITION = threading.local()


class partitioned:
    """Trace-time context: kernels dispatched inside run per shard of
    ``mesh`` — the batch dim over ``batch_axes``, the head dim over
    ``head_axis`` (axes absent from the mesh or of size 1 are dropped)."""

    def __init__(self, mesh, batch_axes=(), head_axis=None):
        self.mesh = mesh
        self.batch_axes = tuple(
            a for a in (batch_axes or ())
            if a in mesh.axis_names and mesh.shape[a] > 1)
        self.head_axis = head_axis if head_axis in mesh.axis_names \
            and mesh.shape[head_axis] > 1 else None

    def __enter__(self):
        stack = getattr(_PARTITION, "stack", None)
        if stack is None:
            stack = _PARTITION.stack = []
        stack.append(self)
        return self

    def __exit__(self, *exc):
        _PARTITION.stack.pop()
        return False

    def batch(self, n):
        """PartitionSpec entry for a dim of ``n`` batch rows (None when
        the batch axes do not divide it: rows then stay whole)."""
        size = 1
        for a in self.batch_axes:
            size *= self.mesh.shape[a]
        if size == 1 or n % size:
            return None
        return self.batch_axes if len(self.batch_axes) > 1 \
            else self.batch_axes[0]

    def heads(self, *counts):
        """PartitionSpec entry for a head dim (None unless the
        tensor-parallel axis divides every head count given)."""
        if self.head_axis is None or any(
                c % self.mesh.shape[self.head_axis] for c in counts):
            return None
        return self.head_axis

    def shard_map(self, fn, in_specs, out_specs):
        """``fn`` per shard, manual over every mesh axis that an
        enclosing shard_map (the pipeline's ``pipe``) has not already
        made manual — Mosaic lowers only when all axes are."""
        outer = frozenset(jax.sharding.get_abstract_mesh().manual_axes)
        # nested: the context mesh (with its manual axes) is the mesh
        return jax.shard_map(
            fn, mesh=None if outer else self.mesh, in_specs=in_specs,
            out_specs=out_specs,
            axis_names=frozenset(self.mesh.axis_names) - outer,
            check_vma=False)


def current_partition():
    """The innermost :class:`partitioned` context of a multi-device
    mesh, or None (single-device traces call the kernels directly)."""
    stack = getattr(_PARTITION, "stack", None)
    if not stack or stack[-1].mesh.size == 1:
        return None
    return stack[-1]


# -- compilestats tracking --------------------------------------------------

def _tracing(args):
    return any(isinstance(l, jax.core.Tracer)
               for l in jax.tree_util.tree_leaves(args))


class TrackedKernel:
    """compilestats registration for a jitted kernel entry.

    Standalone (eager) dispatches go through one
    ``compilestats.wrap``-ed AOT surface per static-kwarg config, so the
    roofline CLI attributes per-kernel FLOPs/bytes under the
    ``kernel.*`` surface.
    Calls with tracer operands are *being traced into a larger surface*
    (the hapi train stepper): they pass straight through to the jitted
    callable, inline, and are attributed to the caller — the same
    contract the grad_comm reducers document.  No budget: a kernel
    legitimately compiles once per shape, so the retrace sentinel stays
    with the steppers that own the shape contract.
    """

    def __init__(self, fn, surface):
        self.fn = fn
        self.surface = surface
        self._tracked = {}
        self._lock = threading.Lock()

    def __call__(self, *args, **statics):
        if _tracing(args):
            return self.fn(*args, **statics)
        key = tuple(sorted(statics.items()))
        cs = self._tracked.get(key)
        if cs is None:
            with self._lock:
                cs = self._tracked.get(key)
                if cs is None:
                    from ..observability import compilestats
                    cs = compilestats.wrap(
                        jax.jit(functools.partial(self.fn, **statics)),
                        self.surface)
                    self._tracked[key] = cs
        return cs(*args)


# -- flash block sizes --------------------------------------------------------

def flash_blocks(S, D, heads=None):
    """(block_q, block_k) for the flash kernels at sequence ``S`` /
    head_dim ``D`` / ``heads`` (batch*heads of the folded layout).
    Every answer divides ``S`` (callers pad S to the 256 granule
    first).  The pairs are the v5e picks of the round 3-4 sweeps at
    D=64: (512,512) at S=4096; 256/256 below it for the head-folded
    kernel (smaller unrolled stack, better VPU/MXU overlap).  A sweep
    on the chip passes ``block_q`` / ``block_k`` to the kernels
    directly; a winner this rule does not give becomes a branch on its
    ``(S, D, heads)`` here."""
    if S >= 4096 and S % 512 == 0:
        return (512, 512)
    if S % 256 == 0:
        return (256, 256)
    # last resort MUST still divide S (the kernels size their loops as
    # S // block — a non-dividing answer silently drops the key tail
    # and leaves output rows unwritten).  Direct callers can land here
    # with any S % 128 == 0 shape (incubate flash_attention's gate);
    # a truly unaligned S degrades to one whole-sequence block, which
    # is correct wherever it compiles.
    if S % 128 == 0:
        return (128, 128)
    return (S, S)


def _reset_for_tests():
    """Drop force overrides (test isolation)."""
    with _LOCK:
        _FORCED.clear()
