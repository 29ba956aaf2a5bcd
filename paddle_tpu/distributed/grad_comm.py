"""Communication-efficient gradient reduction for data-parallel steps.

The GSPMD data-parallel path lets XLA insert one gradient all-reduce per
parameter wherever its scheduler likes.  This module is the explicit
twin used by the hapi compiled stepper's shard_map path; it implements:

- **Bucketed, backward-overlapped all-reduce** (PAPERS.md "T3"): the
  grad tree is partitioned into size-targeted buckets in *reverse*
  parameter order — backward produces the last layers' gradients first,
  so the first buckets' reduces depend only on values available early
  in backward and the latency-hiding scheduler can run them under the
  remaining backward compute.  The final bucket (first layers' grads)
  completes with backward itself and cannot overlap; the structural
  ``pt_collective_overlap_fraction`` gauge reports the overlap-eligible
  byte share.
- **Opt-in quantized all-reduce** (PAPERS.md "EQuARX"): ``bf16`` casts
  the bucket for the wire; ``int8``/``fp8`` run the two-phase scheme —
  chunkwise absmax-scaled quantize → ``all_to_all`` (each rank receives
  its shard from every peer in the narrow dtype) → dequantized fp32
  partial sums → requantize → ``all_gather``.  The wire never carries a
  partially-summed narrow value, so there is no int8 overflow and the
  documented error is pure quantization error (see
  docs/DISTRIBUTED.md, "accuracy contract").
- **ZeRO-1 as a flag** (PAPERS.md "Automatic Cross-Replica Sharding of
  Weight Update"): ``grad_comm_configs={"zero1": True}`` does NOT use
  this module's reducer — it routes the PlacementPlan to
  ``level="os"`` with the *data* axis as the fsdp axis, so the existing
  plan-based stepper shards the optimizer state across replicas and
  GSPMD emits the reduce-scatter + all-gather wire pattern.

Bytes on the wire flow into the PR 5 ``pt_collective_*`` counters from
static shape/dtype metadata (per *tracing* inside jit, like every other
traced collective — the catalog documents that honestly).
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .. import observability as _obs
from ..analysis import jit_surface, register_jit_surface
from .collective import _telemetry

__all__ = ["GradCommConfig", "BucketPlan", "plan_buckets",
           "build_grad_reducer"]

# the traced reducers are nested defs a decorator can't reach; mirrored
# in analysis.allowlist.EXTRA_JIT_SURFACES
for _qual in ("build_grad_reducer.reduce",
              "_build_quant_reduce.quant_reduce"):
    register_jit_surface(__name__, _qual)

_QUANT_MODES = (None, "bf16", "int8", "fp8")
_FP8_DTYPE = jnp.float8_e4m3fn


class GradCommConfig:
    """Normalized ``DistributedStrategy.grad_comm_configs``.

    ``enabled`` turns on the explicit bucketed reducer (shard_map
    stepper path); ``zero1`` instead reroutes the plan-based path.  The
    two are mutually exclusive: the explicit reducer assumes replicated
    optimizer state, ZeRO-1 shards it — combining them would reduce
    every gradient twice.
    """

    def __init__(self, enabled=True, bucket_mb=32.0, overlap=True,
                 quantize=None, quant_chunk=65536, zero1=False):
        if quantize not in _QUANT_MODES:
            raise ValueError(
                f"grad_comm: unknown quantize mode {quantize!r} "
                f"(choose from {_QUANT_MODES})")
        if enabled and zero1:
            raise ValueError(
                "grad_comm: zero1 and the bucketed/quantized explicit "
                "reducer are mutually exclusive — zero1 shards the "
                "weight update on the plan-based (GSPMD) path while the "
                "reducer assumes a replicated update; enable one or the "
                "other")
        self.enabled = bool(enabled)
        self.bucket_mb = float(bucket_mb)
        self.overlap = bool(overlap)
        self.quantize = quantize
        self.quant_chunk = max(int(quant_chunk), 1)
        self.zero1 = bool(zero1)

    @classmethod
    def from_strategy(cls, strategy):
        """None unless the strategy asks for grad_comm or zero1."""
        if strategy is None:
            return None
        on = bool(getattr(strategy, "grad_comm", False))
        cfgs = dict(getattr(strategy, "grad_comm_configs", None) or {})
        zero1 = bool(cfgs.get("zero1", False))
        if not on and not zero1:
            return None
        bucket_mb = cfgs.get("bucket_mb")
        if bucket_mb is None:
            bucket_mb = getattr(strategy, "fuse_grad_size_in_MB", 32)
        return cls(enabled=on, bucket_mb=bucket_mb,
                   overlap=cfgs.get("overlap", True),
                   quantize=cfgs.get("quantize"),
                   quant_chunk=cfgs.get("quant_chunk", 65536),
                   zero1=zero1)

    def describe(self):
        return (f"GradCommConfig(enabled={self.enabled}, "
                f"bucket_mb={self.bucket_mb}, overlap={self.overlap}, "
                f"quantize={self.quantize}, zero1={self.zero1})")


class BucketPlan:
    """Size-targeted partition of the grad list (reverse param order)."""

    def __init__(self, buckets, nbytes):
        self.buckets = buckets          # list of index lists
        self.nbytes = nbytes            # bytes per bucket
        self.total_bytes = sum(nbytes)

    @property
    def overlap_fraction(self):
        """Byte share whose reduce can hide under remaining backward
        compute: everything but the final bucket, which completes with
        backward itself.  Structural (from the plan), not measured."""
        if len(self.buckets) <= 1 or self.total_bytes == 0:
            return 0.0
        return 1.0 - self.nbytes[-1] / self.total_bytes

    def __repr__(self):
        return (f"BucketPlan(n={len(self.buckets)}, "
                f"bytes={self.nbytes})")


def plan_buckets(shapes, dtypes, bucket_bytes):
    """Greedy partition in reverse parameter order: walk params from the
    last (whose grads backward produces first), close a bucket once it
    reaches ``bucket_bytes``.  A single oversized tensor gets its own
    bucket rather than splitting (splitting one array across reduces
    buys nothing — its grad materializes all at once)."""
    buckets, nbytes = [], []
    cur, cur_b = [], 0
    for i in reversed(range(len(shapes))):
        b = int(np.prod(shapes[i], dtype=np.int64) or 1) \
            * jnp.dtype(dtypes[i]).itemsize
        cur.append(i)
        cur_b += b
        if cur_b >= bucket_bytes:
            buckets.append(cur)
            nbytes.append(cur_b)
            cur, cur_b = [], 0
    if cur:
        buckets.append(cur)
        nbytes.append(cur_b)
    return BucketPlan(buckets, nbytes)


def _to_narrow(x, mode):
    """Quantize a pre-scaled fp32 array onto the wire dtype."""
    if mode == "int8":
        return jnp.clip(jnp.round(x), -127.0, 127.0).astype(jnp.int8)
    return jnp.clip(x, -448.0, 448.0).astype(_FP8_DTYPE)


def _quant_qmax(mode):
    return 127.0 if mode == "int8" else 448.0


def _build_quant_reduce(axis_name, world, chunk, mode):
    """Build the EQuARX-pattern two-phase quantized all-reduce of a flat
    fp32 vector, with topology (``world``), chunking and wire mode fixed
    at build time (trace-time constants — every rank traces the same
    collective sequence).  Phase 1: chunkwise absmax-quantize the
    per-destination shards and exchange them with ONE narrow-dtype
    ``all_to_all``; the receiver dequantizes and sums in fp32, so no
    narrow value ever holds a partial sum (no int8 overflow at any world
    size).  Phase 2: requantize the reduced shard and ``all_gather`` it
    back.  Scales ride as fp32 sidecars (1 per ``chunk`` elements).
    Returns the SUM (caller applies the 1/world mean)."""
    qmax = _quant_qmax(mode)

    def quant_reduce(vec):
        n = vec.shape[0]
        per = -(-n // world)    # ceil: elements destined per rank
        # ``chunk`` caps the scale-group size; the shard is split into
        # equal groups of at most that, NOT rounded up to a chunk
        # multiple — rounding pads a 69k-element shard to 2 full 64k
        # chunks (88% dead wire bytes; a 256KB bucket even came out
        # LARGER than its fp32 psum before this)
        g = -(-per // min(chunk, per))
        c = -(-per // g)
        shard = g * c
        total = shard * world
        if total > n:           # static: shape metadata + build consts
            vec = jnp.concatenate(
                [vec, jnp.zeros((total - n,), vec.dtype)])
        x = vec.reshape(world, shard // c, c)
        amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        scale = jnp.maximum(amax, 1e-30) / qmax
        q = _to_narrow(x / scale, mode)
        _telemetry("grad_quant_all_to_all", (q, scale))
        q_t = lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                             tiled=True)
        s_t = lax.all_to_all(scale, axis_name, split_axis=0,
                             concat_axis=0, tiled=True)
        partial = jnp.sum(q_t.astype(jnp.float32) * s_t, axis=0)
        amax2 = jnp.max(jnp.abs(partial), axis=-1, keepdims=True)
        scale2 = jnp.maximum(amax2, 1e-30) / qmax
        q2 = _to_narrow(partial / scale2, mode)
        _telemetry("grad_quant_all_gather", (q2, scale2))
        q2_all = lax.all_gather(q2, axis_name)
        s2_all = lax.all_gather(scale2, axis_name)
        out = (q2_all.astype(jnp.float32) * s2_all).reshape(total)
        return out[:n]

    return quant_reduce


@jit_surface
def _psum_reduce(vec, axis_name):
    _telemetry("grad_bucket_psum", vec)
    return lax.psum(vec, axis_name)


@jit_surface
def _bf16_reduce(vec, axis_name):
    """Half-width wire: cast the bucket to bf16 for the reduce.  The
    accumulation itself happens in bf16 (XLA's psum dtype follows the
    operand) — cheapest mode, loosest contract."""
    w = vec.astype(jnp.bfloat16)
    _telemetry("grad_bucket_psum_bf16", w)
    return lax.psum(w, axis_name).astype(vec.dtype)


def build_grad_reducer(shapes, dtypes, cfg, axis_name, world):
    """Build the traced ``reduce(grads) -> mean_grads`` closure for one
    parameter list (trainable order).  All partitioning/dispatch
    decisions happen HERE at build time from static shapes and config —
    the traced body contains no mode conditionals, so every rank traces
    the identical collective sequence (collective-order lint clean by
    construction).  Returns ``(reduce, plan)``."""
    bucket_bytes = max(int(cfg.bucket_mb * (1 << 20)), 1)
    if not cfg.overlap:
        bucket_bytes = 1 << 62          # one monolithic bucket
    plan = plan_buckets(shapes, dtypes, bucket_bytes)
    mode = cfg.quantize
    chunk = cfg.quant_chunk
    if _obs.enabled():
        _obs.set_gauge("pt_collective_grad_buckets", len(plan.buckets))
        _obs.set_gauge("pt_collective_overlap_fraction",
                       plan.overlap_fraction)
        # analytical bytes ONE step puts on the wire under this plan
        # (static shapes + wire mode — no readback): quantized modes
        # carry ~1 byte/element plus one fp32 scale per quant chunk;
        # joined against compile-telemetry FLOPs by `roofline_from_stats`
        n_elts = sum(int(np.prod(s, dtype=np.int64) or 1)
                     for s in shapes)
        item = {"int8": 1, "fp8": 1, "bf16": 2}.get(mode, 4)
        wire = n_elts * item
        if mode in ("int8", "fp8"):
            wire += -(-n_elts // max(chunk, 1)) * 4
        _obs.set_gauge("pt_collective_wire_bytes_per_step", wire)
    inv_world = 1.0 / float(world)
    if mode in ("int8", "fp8"):
        reduce_vec = _build_quant_reduce(axis_name, world, chunk, mode)
    elif mode == "bf16":
        def reduce_vec(v):
            return _bf16_reduce(v, axis_name)
    else:
        def reduce_vec(v):
            return _psum_reduce(v, axis_name)
    meta = []
    for idxs in plan.buckets:
        sizes = [int(np.prod(shapes[i], dtype=np.int64) or 1)
                 for i in idxs]
        rdtype = jnp.result_type(*[dtypes[i] for i in idxs]) \
            if len(idxs) > 1 else jnp.dtype(dtypes[idxs[0]])
        if mode in ("int8", "fp8"):
            rdtype = jnp.promote_types(rdtype, jnp.float32)
        meta.append((idxs, sizes, rdtype))

    def reduce(grads):
        out = list(grads)
        for idxs, sizes, rdtype in meta:
            vec = jnp.concatenate(
                [jnp.ravel(grads[i]).astype(rdtype) for i in idxs]) \
                if len(idxs) > 1 else \
                jnp.ravel(grads[idxs[0]]).astype(rdtype)
            vec = reduce_vec(vec) * inv_world   # ring-sum -> DP mean
            off = 0
            for i, sz in zip(idxs, sizes):
                out[i] = vec[off:off + sz].reshape(
                    tuple(shapes[i])).astype(jnp.dtype(dtypes[i]))
                off += sz
        return out

    return reduce, plan
