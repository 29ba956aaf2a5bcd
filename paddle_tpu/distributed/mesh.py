"""Auto-parallel mesh + placement API (reference:
python/paddle/distributed/auto_parallel/ — ProcessMesh, shard_tensor,
Placement(Shard/Replicate/Partial), completion/partition/reshard).

TPU-native: this maps 1:1 onto GSPMD.  ``ProcessMesh`` wraps
``jax.sharding.Mesh``; ``shard_tensor`` attaches a ``NamedSharding``; the
reference's completion/partition/reshard passes are XLA's SPMD partitioner
— we only annotate.  ``dtensor_from_fn``/``reshard`` are thin wrappers over
``jax.device_put`` with a new sharding.
"""
import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..framework.core import Tensor

__all__ = ["ProcessMesh", "get_mesh", "set_mesh", "auto_mesh",
           "shard_tensor", "shard_op", "reshard", "Shard", "Replicate",
           "Partial", "dtensor_from_fn"]

_GLOBAL_MESH = [None]


class Shard:
    def __init__(self, dim):
        self.dim = dim

    def __repr__(self):
        return f"Shard(dim={self.dim})"

    def is_shard(self, dim=None):
        return dim is None or dim == self.dim

    def is_replicated(self):
        return False

    def is_partial(self):
        return False


class Replicate:
    def __repr__(self):
        return "Replicate()"

    def is_shard(self, dim=None):
        return False

    def is_replicated(self):
        return True

    def is_partial(self):
        return False


class Partial:
    def __init__(self, reduce_type="sum"):
        self.reduce_type = reduce_type

    def is_shard(self, dim=None):
        return False

    def is_replicated(self):
        return False

    def is_partial(self):
        return True


class ProcessMesh:
    """N-D logical mesh over devices.

    ``mesh``: nested list of process/device ids (reference layout) or a
    shape tuple; ``dim_names``: axis names (dp/mp/pp/...).
    """

    def __init__(self, mesh=None, dim_names=None, shape=None,
                 process_ids=None):
        if mesh is not None:
            arr = np.asarray(mesh)
        else:
            arr = np.arange(int(np.prod(shape))).reshape(shape)
        self._ids = arr
        self._shape = tuple(arr.shape)
        self._dim_names = list(dim_names) if dim_names else \
            [f"d{i}" for i in range(arr.ndim)]
        devices = jax.devices()
        ids = arr.reshape(-1)
        if ids.size and (int(ids.max()) >= len(devices)
                         or int(ids.min()) < 0):
            # a mesh larger than the host must fail, not wrap onto the
            # same chips (a "4-way" run on one device measures nothing)
            raise ValueError(
                f"ProcessMesh asks for device id {int(ids.max())} but "
                f"only {len(devices)} {devices[0].platform} device(s) "
                "are visible")
        dev_arr = np.asarray([devices[i] for i in ids],
                             dtype=object).reshape(arr.shape)
        self._jax_mesh = Mesh(dev_arr, tuple(self._dim_names))

    @property
    def shape(self):
        return list(self._shape)

    @property
    def dim_names(self):
        return list(self._dim_names)

    @property
    def process_ids(self):
        return self._ids.reshape(-1).tolist()

    @property
    def mesh(self):
        return self._ids

    @property
    def jax_mesh(self):
        return self._jax_mesh

    def get_dim_size(self, name):
        return self._shape[self._dim_names.index(name)]

    def get_rank_by_dim_and_process_id(self, dim, pid):
        idx = np.argwhere(self._ids == pid)
        if idx.size == 0:
            return -1
        return int(idx[0][self._dim_names.index(dim)])

    def __enter__(self):
        self._prev = _GLOBAL_MESH[0]
        _GLOBAL_MESH[0] = self
        return self

    def __exit__(self, *exc):
        _GLOBAL_MESH[0] = self._prev
        return False

    def __eq__(self, other):
        return (isinstance(other, ProcessMesh) and
                self._shape == other._shape and
                self._dim_names == other._dim_names)

    def __repr__(self):
        return f"ProcessMesh(shape={self._shape}, dims={self._dim_names})"


def set_mesh(mesh):
    _GLOBAL_MESH[0] = mesh


def get_mesh():
    return _GLOBAL_MESH[0]


def auto_mesh(dim_names=("dp",), shape=None):
    """Build a mesh over all visible devices with the given axis names."""
    n = jax.device_count()
    if shape is None:
        shape = (n,) + (1,) * (len(dim_names) - 1)
    return ProcessMesh(shape=shape, dim_names=dim_names)


def _placements_to_spec(placements, ndim):
    spec = [None] * ndim
    for mesh_dim, pl in enumerate(placements):
        if isinstance(pl, Shard):
            spec[pl.dim] = mesh_dim  # temp: mesh axis index
    return spec


def shard_tensor(data, mesh, placements, dtype=None, stop_gradient=None):
    """Place a tensor on the mesh with the given per-mesh-axis placements.

    Returns a Tensor whose jax.Array carries the NamedSharding — XLA's SPMD
    partitioner (the reference's Partitioner+Reshard passes) takes over
    from there.
    """
    t = data if isinstance(data, Tensor) else Tensor(data)
    ndim = t.ndim
    axis_names = mesh.dim_names
    spec = [None] * ndim
    for mesh_dim, pl in enumerate(placements):
        if isinstance(pl, Shard):
            cur = spec[pl.dim]
            if cur is None:
                spec[pl.dim] = axis_names[mesh_dim]
            elif isinstance(cur, tuple):
                spec[pl.dim] = cur + (axis_names[mesh_dim],)
            else:
                spec[pl.dim] = (cur, axis_names[mesh_dim])
    ns = NamedSharding(mesh.jax_mesh, P(*spec))
    val = jax.device_put(t._value, ns)
    out = Tensor(val, stop_gradient=t.stop_gradient
                 if stop_gradient is None else stop_gradient, name=t.name)
    out.process_mesh = mesh
    out.placements = list(placements)
    if getattr(t, "is_parameter", False):
        out.is_parameter = True
    return out


def reshard(x, mesh, placements):
    return shard_tensor(x, mesh, placements)


def dtensor_from_fn(fn, mesh, placements, *args, **kwargs):
    return shard_tensor(fn(*args, **kwargs), mesh, placements)


def shard_op(op, mesh=None, in_placements=None, out_placements=None):
    """Annotate an op's outputs with shardings (semi-auto).  With GSPMD the
    input annotations propagate, so this is mostly an assertion point."""
    def wrapper(*args, **kwargs):
        out = op(*args, **kwargs)
        if out_placements is not None and mesh is not None:
            if isinstance(out, Tensor):
                return shard_tensor(out, mesh, out_placements)
        return out
    return wrapper


def shard_layer(layer, process_mesh, shard_fn=None, input_fn=None,
                output_fn=None):
    """reference: paddle.distributed.shard_layer — convert a Layer's
    parameters to distributed tensors on ``process_mesh``.

    ``shard_fn(name, layer, process_mesh)`` shards one sublayer's params
    in place; default replicates every parameter.  ``input_fn``/
    ``output_fn`` wrap forward to reshard activations at the boundary.
    """
    if shard_fn is None:
        def shard_fn(name, sublayer, mesh):
            for pname, p in list(sublayer._parameters.items()):
                if p is not None:
                    sublayer._parameters[pname] = shard_tensor(
                        p, mesh, [Replicate()] * p.ndim)

    for name, sub in layer.named_sublayers(include_self=True):
        shard_fn(name, sub, process_mesh)

    if input_fn is not None:
        layer.register_forward_pre_hook(
            lambda lay, inputs: input_fn(inputs, process_mesh))
    if output_fn is not None:
        layer.register_forward_post_hook(
            lambda lay, inputs, outputs: output_fn(outputs, process_mesh))
    return layer
