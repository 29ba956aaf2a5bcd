"""Grouped matmul: rows sorted by group, one weight matrix a group.

``grouped_matmul(lhs, rhs, group_sizes)`` computes, for each group ``g``
in order, ``lhs[start_g:start_g + group_sizes[g]] @ rhs[g]`` into the
same rows of the result; ``lhs`` is ``(m, k)``, ``rhs`` is
``(groups, k, n)``, and ``group_sizes`` (int32, traced) may sum to less
than ``m``: the rows past the last group belong to no group and come
back as ZEROS.  Shapes are static, the sizes are data: this is what a
dropless expert layer needs (``incubate/distributed/models/moe/
dropless.py``), where a step's rows per expert are whatever the router
chose.

Two implementations behind the kernel registry (``"grouped_matmul"``):

- ``megablox`` (TPU; interpreted under ``PADDLE_TPU_KERNEL_INTERPRET``):
  JAX's Pallas kernel ``pallas.ops.tpu.megablox.gmm``.  It walks the row
  tiles that hold rows of some group and nothing else — a group with no
  row is never visited and its weights are never read, and the tiles
  past the last group's rows are never computed — which is what makes a
  decode step read only the experts it touched.  ``m`` is padded up to
  the row tile.  Measured against ``ragged_dot`` on a v5e (PERF.md, PR
  35): 0.65 against 0.75 ms for a decode step's gate+up, 3.2 against 5.4
  ms for a 4,096-token prefill's.
- ``ragged_dot`` (everywhere): ``jax.lax.ragged_dot``, XLA's own
  lowering; the CPU path of the tests.

Both run inside ONE jitted entry, :func:`_expert_grouped_matmul`, so that
the device trace names the work the same whichever runs.
"""
import functools
import importlib

import jax
import jax.numpy as jnp

from . import registry as kreg

__all__ = ["grouped_matmul", "row_tile"]

KERNEL = "grouped_matmul"


def row_tile(m):
    """The row tile for ``m`` rows: 256 where a prefill's rows fill it,
    128 under that, 64 for a decode step's handful."""
    return 256 if m >= 4096 else 128 if m > 64 else 64


def _tiling(m, k, n):
    """(tm, tk, tn) as swept on a v5e at 32 groups of 4096 x 4096 and
    2048 x 4096 (PERF.md, PR 35): the whole contraction in one tile (no k
    loop, up to 4096) and weight tiles of 4 MB; a decode step's 16 rows
    over 13 groups then run at 76-82% of the HBM roofline."""
    tk = min(k, 4096)
    return row_tile(m), tk, min(n, 512 if tk >= 4096 else 1024)


def _zero_tail(out, group_sizes):
    rows = jnp.arange(out.shape[0])[:, None]
    return jnp.where(rows < group_sizes.sum(), out, jnp.zeros((), out.dtype))


@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def _expert_grouped_matmul(lhs, rhs, group_sizes, *, impl, interpret=False):
    m, k = lhs.shape
    n = rhs.shape[2]
    if impl == "megablox":
        # the kernel's own module (the package's ``gmm`` is its custom_vjp)
        _gmm = importlib.import_module(
            "jax.experimental.pallas.ops.tpu.megablox.gmm")
        tm, tk, tn = _tiling(m, k, n)
        pad = (-m) % tm
        padded = jnp.pad(lhs, ((0, pad), (0, 0))) if pad else lhs
        out = _gmm.gmm(padded, rhs, group_sizes,
                       preferred_element_type=lhs.dtype,
                       tiling=(tm, tk, tn), interpret=interpret)[:m]
    else:
        out = jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                 preferred_element_type=lhs.dtype)
    # what the kernel never visited is uninitialised memory
    return _zero_tail(out, group_sizes)


def grouped_matmul(lhs, rhs, group_sizes):
    """See the module docstring.  The implementation is the registry's
    pick for this backend (``registry.force("grouped_matmul", ...)`` or
    ``PADDLE_TPU_KERNEL_GROUPED_MATMUL`` overrides it at trace time)."""
    sel = kreg.choose(KERNEL)
    return _expert_grouped_matmul(lhs, rhs, group_sizes.astype(jnp.int32),
                                  impl=sel.impl, interpret=sel.interpret)


kreg.register(KERNEL, "megablox", platforms=("tpu",))
kreg.register(KERNEL, "ragged_dot", platforms=("*",))
