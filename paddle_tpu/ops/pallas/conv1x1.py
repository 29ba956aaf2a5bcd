"""Fused 1x1-conv + BN-apply + ReLU (+ residual) Pallas kernel (TPU).

Reference analogue: paddle/phi/kernels/fusion/gpu conv+bn+act fusions
(cudnn fused conv epilogues) used by ResNet-style bottlenecks.

TPU-native rationale (ResNet analysis, VERDICT r3 #6): a 1x1
conv IS a (B*H*W, Cin) @ (Cin, Cout) matmul with arithmetic intensity
~Cin*Cout/(Cin+Cout) flops/byte — HBM-bound at ResNet bottleneck shapes
(~21-26%-of-peak roofline on v5e), while the XLA conv emitter measured
only 8-11%.  This kernel runs the matmul form with the BN scale/shift
and ReLU (and optional residual add) applied in the SAME VMEM epilogue,
so the output crosses HBM exactly once and the input exactly once.

BN folding: y = relu(conv(x) * scale + shift [+ residual]) with
scale = gamma / sqrt(var + eps), shift = beta - mean * scale — the
inference/frozen-stats form; train-mode stats ride the usual fused
E[x]/E[x^2] pass outside.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _conv1x1_kernel(x_ref, w_ref, sc_ref, sh_ref, res_ref, o_ref, acc,
                    *, n_k, relu, with_res):
    """grid (M/bm, N/bn, K/bk); f32 VMEM accumulator; epilogue on the
    last K step applies scale/shift (+residual) + ReLU in-register."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _zero():
        acc[:] = jnp.zeros_like(acc)

    acc[:] += jnp.dot(x_ref[:], w_ref[:],
                      preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _epilogue():
        y = acc[:] * sc_ref[0, :][None, :] + sh_ref[0, :][None, :]
        if with_res:
            y = y + res_ref[:].astype(jnp.float32)
        if relu:
            y = jnp.maximum(y, 0.0)
        o_ref[:] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("relu", "block_m", "block_n",
                                              "block_k", "interpret"))
def conv1x1_bn_act(x2d, w, scale, shift, residual=None, relu=True,
                   block_m=256, block_n=256, block_k=256,
                   interpret=False):
    """relu((x2d @ w) * scale + shift [+ residual]) in one HBM pass.

    x2d: (M, K) — the NHWC activation collapsed to (B*H*W, Cin);
    w: (K, N); scale/shift: (N,) f32 (BN folded); residual: (M, N) or
    None.  M is padded to block_m internally; K and N must divide by
    block_k/block_n (ResNet channel counts are powers of two >= 64, and
    the wrapper clamps blocks to the dims).
    """
    M, K = x2d.shape
    N = w.shape[1]
    bm = min(block_m, M)
    bn = min(block_n, N)
    bk = min(block_k, K)
    if K % bk or N % bn:
        raise ValueError(f"conv1x1_bn_act: K={K} N={N} must divide "
                         f"block_k={bk} / block_n={bn}")
    pad = (-M) % bm
    if pad:
        x2d = jnp.pad(x2d, ((0, pad), (0, 0)))
        if residual is not None:
            residual = jnp.pad(residual, ((0, pad), (0, 0)))
    Mp = x2d.shape[0]
    with_res = residual is not None
    if residual is None:
        residual = jnp.zeros((bm, bn), x2d.dtype)   # dummy, never read
        res_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (0, 0))
    else:
        res_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    out = pl.pallas_call(
        functools.partial(_conv1x1_kernel, n_k=K // bk, relu=relu,
                          with_res=with_res),
        grid=(Mp // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
            res_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), x2d.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x2d, w, scale.astype(jnp.float32).reshape(1, N),
      shift.astype(jnp.float32).reshape(1, N), residual)
    return out[:M] if pad else out


def conv1x1_bn_act_nhwc(x, w, scale, shift, residual=None, relu=True,
                        interpret=False):
    """NHWC convenience wrapper: x (B, H, W, Cin), w (Cin, Cout)."""
    B, H, W, C = x.shape
    r2d = None if residual is None else residual.reshape(B * H * W, -1)
    out = conv1x1_bn_act(x.reshape(B * H * W, C), w, scale, shift,
                         residual=r2d, relu=relu, interpret=interpret)
    return out.reshape(B, H, W, -1)
