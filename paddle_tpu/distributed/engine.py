"""Placement engine: DistributedStrategy → GSPMD shardings.

The reference implements each parallelism as a separate runtime protocol
(C++ Reducer for DP, GroupSharded hooks for ZeRO, program rewrites for
static graph: paddle/fluid/imperative/reducer.cc,
fleet/meta_parallel/sharding/*).  TPU-native, every one of them is a
*placement* of the same compiled train step over a named mesh:

- DP        → batch sharded on the "data" axis; params replicated; XLA
              inserts the gradient psum (this is the Reducer, for free).
- ZeRO-1/2  → optimizer state (and with os_g the grad reduce) sharded on
              the "sharding" axis: moments get a NamedSharding along that
              axis, so XLA reduce-scatters grads into the update and
              all-gathers fresh params — exactly GroupShardedStage2's
              wire pattern, chosen by the SPMD partitioner.
- ZeRO-3    → parameters themselves sharded on "sharding"; XLA all-gathers
              per use site (= stage-3 re-gather on forward/backward).
- TP        → layers annotate weights with a ``pspec`` (mp_layers set
              e.g. ("model", None)); activations follow by propagation.
- sep (M5)  → sequence dim of activations sharded; attention reshards
              head↔seq with all_to_all inside the layer.

One PlacementPlan holds the mesh + the rules; the hapi stepper consumes it
to device_put state and to set in/out shardings on the jitted step.
"""
import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["PlacementPlan", "make_data_parallel_plan", "plan_from_hcg"]


def _divisible_dim(shape, k, prefer_largest=True):
    """First/largest dim index divisible by k, else None."""
    cands = [i for i, s in enumerate(shape) if s % k == 0 and s >= k]
    if not cands:
        return None
    if prefer_largest:
        return max(cands, key=lambda i: shape[i])
    return cands[0]


class PlacementPlan:
    """Mesh + placement rules for params / optimizer state / batch."""

    def __init__(self, mesh, batch_axes=("data", "sharding"),
                 level=None, fsdp_axis="sharding", mp_axis="model",
                 sep_axis="sep", grad_comm=None):
        self.mesh = mesh
        # GradCommConfig for the explicit bucketed/quantized reducer
        # (hapi stepper's shard_map path); None = GSPMD inserts the
        # gradient all-reduce as before
        self.grad_comm = grad_comm if grad_comm is not None and \
            getattr(grad_comm, "enabled", False) else None
        self.batch_axes = tuple(a for a in batch_axes
                                if a in mesh.axis_names and
                                mesh.shape[a] > 1) or None
        self.level = level          # None | 'os' | 'os_g' | 'p_g_os'
        self.fsdp_axis = fsdp_axis if fsdp_axis in mesh.axis_names else None
        self.mp_axis = mp_axis if mp_axis in mesh.axis_names else None
        self.sep_axis = sep_axis if sep_axis in mesh.axis_names else None

    # -- specs ---------------------------------------------------------------
    @property
    def fsdp_size(self):
        return self.mesh.shape[self.fsdp_axis] if self.fsdp_axis else 1

    def param_pspec(self, tensor_or_shape, name=None, pspec=None):
        """PartitionSpec for a parameter.

        Priority: explicit ``pspec`` attribute (TP layers / shard_tensor)
        > ZeRO-3 sharding on the fsdp axis > replicated.
        """
        explicit = pspec if pspec is not None else \
            getattr(tensor_or_shape, "pspec", None)
        if explicit is not None:
            return P(*explicit)
        shape = tensor_or_shape if isinstance(tensor_or_shape, (tuple, list)) \
            else tuple(tensor_or_shape.shape)
        if self.level == "p_g_os" and self.fsdp_size > 1:
            dim = _divisible_dim(shape, self.fsdp_size)
            if dim is not None:
                spec = [None] * len(shape)
                spec[dim] = self.fsdp_axis
                return P(*spec)
        return P()

    def opt_pspec(self, param_spec, shape):
        """Spec for a param-shaped optimizer moment.  ZeRO-1/2/3: ensure it
        is sharded on the fsdp axis (stage-3 moments inherit the param's
        sharding, which already contains it)."""
        if self.level in ("os", "os_g", "p_g_os") and self.fsdp_size > 1:
            if self.fsdp_axis not in (param_spec or ()):
                dim = _divisible_dim(shape, self.fsdp_size)
                if dim is not None:
                    spec = list(param_spec) + \
                        [None] * (len(shape) - len(param_spec))
                    if spec[dim] is None:
                        spec[dim] = self.fsdp_axis
                        return P(*spec)
        return param_spec

    def input_pspec(self, ndim, batch_dim=0):
        if not self.batch_axes or ndim == 0:
            return P()
        spec = [None] * ndim
        spec[batch_dim] = self.batch_axes if len(self.batch_axes) > 1 \
            else self.batch_axes[0]
        return P(*spec)

    # -- shardings -----------------------------------------------------------
    def sharding(self, pspec):
        return NamedSharding(self.mesh, pspec)

    def replicated(self):
        return NamedSharding(self.mesh, P())

    def param_sharding(self, tensor, name=None):
        return self.sharding(self.param_pspec(tensor, name))

    def input_sharding(self, ndim, batch_dim=0):
        return self.sharding(self.input_pspec(ndim, batch_dim))

    def opt_state_shardings(self, opt_state, param_specs, param_shapes):
        """Map the optimizer state pytree (list-per-param of {name: arr})
        to shardings: param-shaped leaves get opt_pspec, scalars
        replicated."""
        out = []
        for st, pspec, shape in zip(opt_state, param_specs, param_shapes):
            mapped = {}
            for k, v in st.items():
                if tuple(np.shape(v)) == tuple(shape):
                    mapped[k] = self.sharding(self.opt_pspec(pspec, shape))
                else:
                    mapped[k] = self.replicated()
            out.append(mapped)
        return out

    def kernel_partition(self):
        """Trace-time context for the steppers that jit under this plan:
        Pallas kernels run per shard of the mesh (batch over the batch
        axes, heads over the tensor-parallel axis) — XLA cannot
        partition a Mosaic kernel itself (ops/registry.py)."""
        from ..ops import registry as kreg
        return kreg.partitioned(self.mesh, self.batch_axes or (),
                                self.mp_axis)

    def describe(self):
        return (f"PlacementPlan(mesh={dict(self.mesh.shape)}, "
                f"batch_axes={self.batch_axes}, level={self.level})")


def make_data_parallel_plan(devices=None, level=None, grad_comm=None):
    """All visible devices on one 'data' axis (optionally ZeRO 'sharding'
    semantics on the same axis — reference: pure-DP GroupSharded uses the
    world group).  ``grad_comm.zero1`` is the strategy-flag spelling of
    ``level="os"``: shard the weight update across the replicas
    themselves (PAPERS.md "Automatic Cross-Replica Sharding of Weight
    Update in Data-Parallel Training")."""
    devs = np.asarray(devices if devices is not None else jax.devices())
    if grad_comm is not None and grad_comm.zero1 and level is None:
        level = "os"
    if level in ("os", "os_g", "p_g_os"):
        mesh = Mesh(devs.reshape(1, -1), ("data", "sharding"))
    else:
        mesh = Mesh(devs, ("data",))
    return PlacementPlan(mesh, level=level, grad_comm=grad_comm)


def plan_from_hcg(hcg, level=None, grad_comm=None):
    """Build the plan from a HybridCommunicateGroup (fleet.init output).

    With ``grad_comm.zero1`` on a topology whose dedicated sharding axis
    is degenerate (sharding_degree == 1), the *data* axis becomes the
    fsdp axis: the optimizer state shards across replicas and GSPMD
    emits the reduce-scatter-into-update + all-gather wire pattern."""
    fsdp_axis = "sharding"
    if grad_comm is not None and grad_comm.zero1:
        if level is None:
            level = "os"
        shape = dict(hcg.jax_mesh.shape)
        if shape.get("sharding", 1) <= 1 and shape.get("data", 1) > 1:
            fsdp_axis = "data"
    return PlacementPlan(hcg.jax_mesh, level=level, fsdp_axis=fsdp_axis,
                         grad_comm=grad_comm)
