"""SPMD pipeline parallelism (reference: fleet/meta_parallel/
pipeline_parallel.py + pp_utils/p2p_communication.py — per-rank processes
exchanging activations via send_v2/recv_v2 under a 1F1B schedule, plus the
C++ FleetExecutor interceptor runtime for static graphs).

TPU-native design: ONE SPMD program.  The homogeneous transformer blocks
are stacked on a leading layer dim, sharded over the "pipe" mesh axis
(each device holds its stage's blocks); a `lax.scan` over ticks rotates
micro-batch activations stage→stage with `lax.ppermute` (the ICI-native
send/recv).  The classic fill/steady/drain schedule emerges from the scan:
tick t runs stage s on micro-batch (t-s) — exactly GPipe's wavefront; with
jax.checkpoint on the block, backward replays per (stage, microbatch) and
XLA's liveness keeps ~one microbatch of activations per stage live at a
time, giving 1F1B's memory profile without a hand-written scheduler.
Embedding/head run outside the loop (they are not stage-homogeneous).
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

__all__ = ["spmd_pipeline", "stack_block_params", "PipelineStagedModule"]


def _shard_map(fn, mesh, in_specs, out_specs, axis):
    # manual only over the pipe axis: other mesh axes (data/model/...)
    # stay under GSPMD so dp/tp compose with the pipeline
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names=frozenset({axis}))


def stack_block_params(param_lists):
    """[[block0 params...], [block1 params...]] → list of stacked arrays
    with leading dim L (blocks must be structurally identical)."""
    n = len(param_lists[0])
    return [jnp.stack([pl[i] for pl in param_lists], axis=0)
            for i in range(n)]


def spmd_pipeline(block_apply, stacked_params, x, mesh, axis="pipe",
                  remat=True, n_virtual=1):
    """Run L stacked blocks as an S-stage pipeline over micro-batches.

    block_apply(params_list, h) -> h'  — one block, pure.
    stacked_params: list of arrays with leading dim L (L % (S*V) == 0).
    x: (M, mb, ...) micro-batched activations, replicated on `axis`.
    Returns (M, mb, ...) outputs.

    ``n_virtual`` > 1 is the interleaved virtual-pipeline schedule
    (reference: PipelineParallelWithInterleave): physical stage s hosts
    the V non-contiguous logical stages {s, s+S, ..., s+(V-1)S}, and each
    activation makes V trips around the ppermute ring (a v counter rides
    the rotation).  Injection is continuous: micro-batch m enters stage 0
    at tick (m//S)·SV + (m%S) — exactly the slot where an activation that
    finished its last trip leaves the ring — so consecutive waves overlap
    with no inter-ring drain.  Per tick a stage runs L/(SV) layers, and
    the whole schedule takes ((M-1)//S)·SV + (M-1)%S + SV ticks: for
    M ≤ S that is (S-1) idle ticks spread over M·V+S-1 — the reference
    interleave's bubble shrink — without a hand-written scheduler.  The
    V=1 case reduces to the plain GPipe wavefront (M+S-1 ticks).
    """
    S = mesh.shape[axis]
    M = x.shape[0]
    V = int(n_virtual or 1)
    L = stacked_params[0].shape[0]
    assert L % (S * V) == 0, \
        f"layers {L} not divisible by stages*virtual {S}*{V}"
    per = L // (S * V)
    SV = S * V
    # logical stage l = v*S + s owns layers [l*per, (l+1)*per): reshape to
    # (V, S, per, ...) then put the physical-stage dim first for sharding
    params_s = [jnp.moveaxis(p.reshape(V, S, per, *p.shape[1:]), 1, 0)
                for p in stacked_params]

    if remat:
        block_apply = jax.checkpoint(block_apply)

    p_specs = [P(axis, *([None] * (p.ndim - 1))) for p in params_s]
    x_spec = P(*([None] * x.ndim))

    def run(params_l, xl):
        s_idx = lax.axis_index(axis)
        my_params = [p[0] for p in params_l]   # (V, per, ...)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def stage_compute(h, v):
            chunk = [lax.dynamic_index_in_dim(p, jnp.clip(v, 0, V - 1), 0,
                                              keepdims=False)
                     for p in my_params]        # (per, ...)

            def body(carry, blk):
                return block_apply(blk, carry), None
            h, _ = lax.scan(body, h, chunk)
            return h

        state0 = jnp.zeros_like(xl[0])
        out0 = jnp.zeros_like(xl)
        v0 = jnp.zeros((), jnp.int32)

        def tick(carry, t):
            state, v, outputs = carry
            # stage 0 injects micro-batch m at tick (m//S)*SV + (m%S);
            # live wrap-arounds land on phases >= S, dead ones (v == V)
            # land exactly on the injection phases and are replaced
            phase = t % SV
            m_in = (t // SV) * S + phase
            inject = (s_idx == 0) & (phase < S) & (m_in < M)
            mb_in = lax.dynamic_index_in_dim(
                xl, jnp.clip(m_in, 0, M - 1), 0, keepdims=False)
            inp = jnp.where(inject, mb_in, state)
            v_cur = jnp.where(inject, 0, v)
            out = stage_compute(inp, v_cur)
            # micro-batch m completes at its inject tick + SV - 1
            u = t - (SV - 1)
            uphase = u % SV
            m_out = (u // SV) * S + uphase
            write = (s_idx == S - 1) & (v_cur == V - 1) & (u >= 0) \
                & (uphase < S) & (m_out < M)
            out_idx = jnp.clip(m_out, 0, M - 1)
            cur = lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                           keepdims=False)
            outputs = lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, out, cur), out_idx, 0)
            state = lax.ppermute(out, axis, perm)
            # the v counter rides the ring; +1 on the S-1 → 0 wrap
            v = lax.ppermute(
                v_cur + (s_idx == S - 1).astype(jnp.int32), axis, perm)
            return (state, v, outputs), None

        n_ticks = ((M - 1) // S) * SV + (M - 1) % S + SV
        (_, _, outputs), _ = lax.scan(tick, (state0, v0, out0),
                                      jnp.arange(n_ticks))
        # only the last stage holds real outputs; replicate via psum
        outputs = jnp.where(s_idx == S - 1, outputs, 0)
        return lax.psum(outputs, axis)

    fn = _shard_map(run, mesh, in_specs=(p_specs, x_spec),
                    out_specs=x_spec, axis=axis)
    return fn(params_s, x)


class PipelineStagedModule:
    """Bridge from a Layer holding N identical blocks to spmd_pipeline.

    Captures the blocks' parameters (functional seam), stacks them, and
    exposes ``apply(stacked_values, x_microbatches)``.
    """

    def __init__(self, blocks, mesh, axis="pipe", remat=True, n_virtual=1):
        from ..framework.core import Tensor
        from ..framework import autograd as _ag
        self.blocks = list(blocks)
        self.mesh = mesh
        self.axis = axis
        self.remat = remat
        self.n_virtual = int(n_virtual or 1)
        self.template = self.blocks[0]
        self.t_params = [p for _, p in self.template.named_parameters()]
        self.param_lists = [[p._value for _, p in b.named_parameters()]
                            for b in self.blocks]
        self.stacked = stack_block_params(self.param_lists)

        template, t_params = self.template, self.t_params

        def block_apply(blk_values, h):
            olds = [p._value for p in t_params]
            for p, v in zip(t_params, blk_values):
                p._value = v
            try:
                with _ag.suspend_tape():
                    return template(Tensor(h))._value
            finally:
                for p, v in zip(t_params, olds):
                    p._value = v
        self.block_apply = block_apply

    def apply(self, stacked_values, x_mb):
        return spmd_pipeline(self.block_apply, stacked_values, x_mb,
                             self.mesh, self.axis, remat=self.remat,
                             n_virtual=self.n_virtual)
