"""Segment-parallel ("sep") long-context attention utilities.

Reference analogue: the ``sep`` mesh axis in
python/paddle/distributed/fleet/base/topology.py — the reference's in-core
support is the axis + alltoall reshard (Ulysses); ring attention is made
first-class here per SURVEY.md §5.7/§7.

Two modes over the same seq-sharded activations (B, S/sep, H, D):
- ``sep_attention(..., mode="ulysses")`` — all_to_all head<->seq reshard
  around dense/flash attention (needs sep | num_heads).
- ``sep_attention(..., mode="ring")`` — ppermute KV rotation with online
  softmax (any head count, O(S/sep) activation memory).

These are Tensor-level and autograd-aware (jax differentiates through
ppermute/all_to_all); they must run inside a sep-axis shard_map — the
`RingFlashAttention` / `sep` paths of the hybrid engine arrange that.
"""
import jax

from ....framework.core import Tensor
from ....framework.autograd import call_op
from ....ops.ring_attention import ring_flash_attention, ulysses_attention

__all__ = ["sep_attention", "ring_attention", "split_inputs_sequence_dim",
           "RingFlashAttention", "set_sep_mesh"]

_SEP_AXIS = "sep"
_AMBIENT_MESH = [None]


def set_sep_mesh(mesh):
    """Register the jax Mesh carrying the sep axis.  sep_attention called
    OUTSIDE a shard_map (e.g. under the auto-parallel Engine's GSPMD
    stepper) wraps itself in a shard_map over this mesh; inside one it
    uses the ambient manual axis directly."""
    _AMBIENT_MESH[0] = mesh


def _in_manual_axis(axis):
    """True when tracing inside a shard_map/pmap that binds `axis`."""
    from ...collective import _in_named_trace
    return _in_named_trace(axis)


def sep_attention(query, key, value, is_causal=False, mode="ring",
                  sep_axis=_SEP_AXIS, scale=None):
    """Sequence-parallel scaled-dot-product attention on seq-sharded
    (B, S_local, H, D) tensors; full-softmax-exact over the global S.

    Inside a sep-axis shard_map (fleet hybrid engine) the collective
    rides the ambient manual axis.  Outside one, with a mesh registered
    via ``set_sep_mesh`` (the auto-parallel Engine does this when
    Strategy.sep_degree > 1), the call wraps itself in a shard_map that
    shards batch on the data axis and sequence on the sep axis."""
    q, k, v = [t if isinstance(t, Tensor) else Tensor(t)
               for t in (query, key, value)]
    if mode == "ring":
        fn = lambda a, b, c: ring_flash_attention(
            a, b, c, sep_axis, causal=bool(is_causal), scale=scale)
    elif mode == "ulysses":
        fn = lambda a, b, c: ulysses_attention(
            a, b, c, sep_axis, causal=bool(is_causal), scale=scale)
    else:
        raise ValueError(f"unknown sep attention mode {mode!r}")
    if _in_manual_axis(sep_axis):
        return call_op(fn, q, k, v)
    mesh = _AMBIENT_MESH[0]
    if mesh is None or sep_axis not in mesh.axis_names:
        raise RuntimeError(
            "sep_attention: not inside a shard_map over the sep axis and "
            "no sep mesh registered — run under the fleet hybrid engine, "
            "an explicit shard_map, or an Engine with sep_degree > 1 "
            "(which calls set_sep_mesh)")
    from jax.sharding import PartitionSpec as P
    batch = tuple(a for a in ("data", "sharding")
                  if a in mesh.axis_names and mesh.shape[a] > 1) or None
    spec = P(batch, sep_axis, None, None)
    wrapped = jax.shard_map(fn, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec)
    return call_op(wrapped, q, k, v)


def ring_attention(query, key, value, is_causal=False, sep_axis=_SEP_AXIS):
    return sep_attention(query, key, value, is_causal, "ring", sep_axis)


def split_inputs_sequence_dim(inputs, rank, degree, axis=1):
    """Shard a full-sequence batch for this sep rank (the reference splits
    inputs along seq before feeding sep-parallel models)."""
    from ....tensor.manipulation import split
    if degree <= 1:
        return inputs
    return split(inputs, degree, axis=axis)[rank]


class RingFlashAttention:
    """PyLayer-shaped facade matching the reference-era custom-op API."""

    @staticmethod
    def apply(q, k, v, causal=False, sep_axis=_SEP_AXIS):
        return sep_attention(q, k, v, is_causal=causal, mode="ring",
                             sep_axis=sep_axis)
