"""Int8 matmul Pallas kernel with fused quantize/dequant epilogue (TPU).

Reference analogue: paddle/phi/kernels/fusion/gpu quant GEMM epilogues
(fused int8 matmul + dequant in cutlass), SURVEY §7.1 "int8 matmul
epilogue" row.  The MXU executes int8×int8→int32 natively; this kernel
fuses the activation quantization (round/clip to int8 at the tile), the
int32-accumulating matmul, and the per-output-channel dequant epilogue
into one pass, so the int8 activations and int32 accumulator never
round-trip HBM.

``int8_matmul(x, w_int, w_scale, act_scale, ...)`` matches the deploy
semantics of quantization.QuantizedLinear: xq = clip(round(x/act_scale
* bnd)); out = (xq @ w_int) * (act_scale/bnd) * (w_scale/bnd).

The wrapper always runs the kernel: compiled by default, interpreted
only when the caller passes ``interpret=True`` (CPU tests; the registry
passes its own interpret-mode decision).  Platform selection between
this kernel and the XLA dot_general reference lives in
``ops/quant_dispatch.py``.

Measured (4096^3, v5e): 47.5 TOPS vs 50.2 for the XLA dot_general path —
parity; both are bound by the fp32 activation-quantize VPU pass, not the
MXU.  The kernel's fusion win (int8/int32 never touch HBM) matters most
at small/medium shapes where the separate quantize pass is a full extra
HBM round trip.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["int8_matmul", "fp8_matmul", "fp8_quantize_weight"]

_BM, _BK, _BN = 256, 512, 256


def _qmm_kernel(x_ref, w_ref, ws_ref, sc_ref, o_ref, acc_ref, *, n_k, bnd):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    a_s = sc_ref[0, 0]
    xq = jnp.clip(jnp.round(x_ref[:].astype(jnp.float32) / a_s * bnd),
                  -bnd - 1, bnd).astype(jnp.int8)
    acc_ref[:] += jnp.dot(xq, w_ref[:], preferred_element_type=jnp.int32)

    @pl.when(k == n_k - 1)
    def _epilogue():
        scale = (a_s / bnd) * (ws_ref[0, :].astype(jnp.float32) / bnd)
        o_ref[:] = (acc_ref[:].astype(jnp.float32)
                    * scale[None, :]).astype(o_ref.dtype)


def int8_matmul(x, w_int, w_scale, act_scale, bit_length=8,
                out_dtype=jnp.float32, interpret=False):
    """x: (..., K) float; w_int: (K, N) int8; w_scale: (N,) fp32;
    act_scale: python float or 0-d array.  Returns (..., N) out_dtype."""
    bnd = float(2 ** (bit_length - 1) - 1)
    lead = x.shape[:-1]
    K = x.shape[-1]
    N = w_int.shape[1]
    x2 = x.reshape(-1, K)
    M = x2.shape[0]
    if M <= 64:
        # decode-style serving: weight-streaming-bound, not MXU-bound.
        # Fat K/N tiles amortize per-grid-step overhead (measured r5:
        # 32/4096/1024 beats the training-shape tiles by ~2.3x at M=32)
        bm, bk, bn = M, min(4096, K), min(1024, N)
    else:
        bm, bk, bn = min(_BM, M), min(_BK, K), min(_BN, N)
    pm, pk, pn = (-M) % bm, (-K) % bk, (-N) % bn
    xp = jnp.pad(x2, ((0, pm), (0, pk))) if pm or pk else x2
    wp = jnp.pad(w_int, ((0, pk), (0, pn))) if pk or pn else w_int
    wsp = jnp.pad(w_scale, (0, pn)) if pn else w_scale
    Mp, Kp, Np = M + pm, K + pk, N + pn
    n_k = Kp // bk
    sc = jnp.asarray(act_scale, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        functools.partial(_qmm_kernel, n_k=n_k, bnd=bnd),
        grid=(Mp // bm, Np // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda m, n, k: (m, k)),
            pl.BlockSpec((bk, bn), lambda m, n, k: (k, n)),
            pl.BlockSpec((1, bn), lambda m, n, k: (0, n)),
            pl.BlockSpec((1, 1), lambda m, n, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n, k: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xp, wp, wsp.reshape(1, -1), sc)
    return out[:M, :N].reshape(*lead, N)


# ---------------------------------------------------------------------------
# fp8 matmul epilogue (SURVEY §7.1 "int8/fp8 matmul epilogues" row)
# ---------------------------------------------------------------------------

_F8_MAX = 448.0      # float8_e4m3fn max finite value


def fp8_quantize_weight(w):
    """Per-output-channel fp8 (e4m3) quantization of a (K, N) weight.

    Returns (w_fp8 (K, N), w_scale (N,) fp32) with w ≈ w_fp8 * w_scale.
    """
    wf = jnp.asarray(w, jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=0)
    scale = jnp.maximum(amax / _F8_MAX, 1e-12)
    return (wf / scale[None, :]).astype(jnp.float8_e4m3fn), scale


def fp8_matmul(x, w_fp8, w_scale, act_scale=None, out_dtype=jnp.float32):
    """fp8(e4m3) weight-quantized matmul with fused dequant epilogue.

    x: (..., K) float; w_fp8: (K, N) float8_e4m3fn; w_scale: (N,) fp32.

    act_scale:
      * None (default) — WEIGHT-ONLY fp8: activations stay bf16 and only
        the weight is fp8.  This is the TPU-native deploy mode — see
        physics below.
      * "dynamic" — also quantize activations to e4m3 with a per-call
        amax scale (numerical parity with reference fp8 recipes that
        quantize both sides; adds a serializing global amax reduce).
      * python float / 0-d array — static activation scale.

    v5e physics (re-measured r5, scan-chained + dispatch latency
    subtracted — the r4 numbers in both directions were latency
    noise): the MXU has no fp8 arithmetic, XLA upconverts the weight
    to bf16 on the fly *inside* its matmul pipeline.  In the weight-
    bandwidth-bound serving regime (M=32, K=N=4096, 32-layer chain)
    this measured 1.46 ms/pass bf16 (733 GB/s
    weight stream) vs 0.88 ms/pass fp8 (609 GB/s of half-size
    weights) = **1.66x** — the memory-bandwidth win is real and XLA's
    own streaming beats every Pallas upconvert kernel we tried
    (bit-twiddle, packed-int32; see tools/fp8_tune.py), so there is
    deliberately no Pallas kernel here.  At large M the dot is
    MXU-bound and fp8 ~ties bf16.  Quantizing activations too
    (act_scale="dynamic") costs ~15% and only loses accuracy on this
    chip — hence weight-only default.
    """
    xf = jnp.asarray(x)
    if xf.dtype not in (jnp.bfloat16, jnp.float32):
        xf = xf.astype(jnp.float32)
    lead, K = xf.shape[:-1], xf.shape[-1]
    x2 = xf.reshape(-1, K)
    if act_scale is None:
        # weight-only: upconvert w lazily; XLA fuses the convert + scale
        # into the dot's weight-streaming loop
        acc = lax.dot_general(x2.astype(jnp.bfloat16),
                              w_fp8.astype(jnp.bfloat16),
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        out = acc * w_scale.astype(jnp.float32)[None, :]
        return out.astype(out_dtype).reshape(*lead, w_fp8.shape[1])
    if isinstance(act_scale, str):
        if act_scale != "dynamic":
            raise ValueError(f"act_scale must be None, 'dynamic' or a "
                             f"number, got {act_scale!r}")
        act_scale = jnp.maximum(
            jnp.max(jnp.abs(x2.astype(jnp.float32))) / _F8_MAX, 1e-12)
    else:
        act_scale = jnp.asarray(act_scale, jnp.float32)
    xq = (x2.astype(jnp.float32) / act_scale).astype(jnp.float8_e4m3fn)
    acc = lax.dot_general(xq, w_fp8, (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    out = acc * act_scale * w_scale.astype(jnp.float32)[None, :]
    return out.astype(out_dtype).reshape(*lead, w_fp8.shape[1])
