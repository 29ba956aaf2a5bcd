"""paddle.nn.quant (reference: python/paddle/nn/quant — quant layer
variants, weight-only quantization helpers, llm.int8 linear).

TPU-native layout decision: quantized weights keep the framework's
(in_features, out_features) = (K, N) Linear layout with a per-output
-channel fp32 scale (N,), mapping 1:1 onto the Pallas int8 epilogue
kernel (ops/pallas/quant_matmul.py) — no arch-specific repacking like
the reference's cutlass layouts.
"""
import jax
import jax.numpy as jnp

from ...framework.core import Tensor
from ...framework.autograd import call_op
from ...tensor._helpers import ensure_tensor
from ..layer.layers import Layer
from ...quantization import (  # noqa: F401 (re-export, reference parity)
    QuantedLinear, QuantedConv2D, FakeQuanterWithAbsMaxObserver,
    FakeQuanterChannelWiseAbsMaxObserver, quant_linear)

__all__ = ["Stub", "weight_quantize", "weight_dequantize",
           "weight_only_linear", "llm_int8_linear", "QuantedLinear",
           "QuantedConv2D", "quant_linear"]

_I8_BND = 127.0


class Stub(Layer):
    """reference: paddle.nn.quant.Stub — placeholder the QAT pass swaps
    for a quanter; identity until converted."""

    def __init__(self, observer=None):
        super().__init__()
        self._observer = observer

    def forward(self, x):
        return x


def weight_quantize(x, algo="weight_only_int8", group_size=-1):
    """(K, N) float weight -> (quantized tensor, (N,) fp32 scale).

    ``algo``:
      * weight_only_int8 | llm.int8 — (K, N) int8, scale = absmax/127.
      * weight_only_int4 — (K/2, N) int8 holding two nibbles per byte
        (even K rows in the low nibble, odd in the high; K must be
        even), scale = absmax/7.  v5e reality: XLA's int4 dtype is
        stored unpacked (1 byte/element) and the VPU nibble-unpack
        costs more than fp8's upconvert, so int4 on this chip is a
        CAPACITY feature (4x smaller checkpoints / HBM weights than
        fp32, 2x vs int8-or-fp8), not a latency one — the serving
        latency path is fp8 or int8-MXU.
    """
    if algo not in ("weight_only_int8", "llm.int8", "weight_only_int4"):
        raise ValueError(f"unsupported algo {algo}")
    if group_size != -1:
        raise NotImplementedError(
            "group-wise quantization (group_size != -1) is not "
            "implemented; only per-output-channel scales")
    w = ensure_tensor(x)

    if algo == "weight_only_int4":
        if int(w.shape[0]) % 2:
            raise ValueError(
                "weight_only_int4 packs two K rows per byte: K must "
                f"be even, got {int(w.shape[0])}")

        def _q4(v):
            vf = v.astype(jnp.float32)
            scale = jnp.maximum(jnp.max(jnp.abs(vf), axis=0) / 7.0,
                                1e-10)
            q = jnp.clip(jnp.round(vf / scale), -8, 7).astype(jnp.int32)
            lo = q[0::2] & 0xF
            hi = (q[1::2] & 0xF) << 4
            return (lo | hi).astype(jnp.int8), scale
        out = call_op(_q4, w.detach())
        return out[0], out[1]

    def _q(v):
        # reference scale convention: scale = absmax / 127, dequant =
        # q * scale — (q, scale) pairs interoperate with externally
        # quantized weights
        scale = jnp.max(jnp.abs(v.astype(jnp.float32)), axis=0) / _I8_BND
        scale = jnp.maximum(scale, 1e-10)
        q = jnp.clip(jnp.round(v.astype(jnp.float32) / scale),
                     -128, 127).astype(jnp.int8)
        return q, scale
    out = call_op(_q, w.detach())
    return out[0], out[1]


def _unpack_int4(q):
    """(K/2, N) packed nibbles -> (K, N) int8 in [-8, 7]."""
    qi = q.astype(jnp.int32)
    lo = qi & 0xF
    hi = (qi >> 4) & 0xF
    # sign-extend 4-bit two's complement
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    K2, N = q.shape
    # one fused interleave (row 2i = lo[i], row 2i+1 = hi[i])
    return jnp.stack([lo, hi], axis=1).reshape(K2 * 2, N) \
        .astype(jnp.int8)


def weight_dequantize(x, scale, algo="weight_only_int8",
                      out_dtype="float32"):
    w, s = ensure_tensor(x), ensure_tensor(scale)
    if algo == "weight_only_int4":
        return call_op(
            lambda q, sc: (_unpack_int4(q).astype(jnp.float32)
                           * sc).astype(out_dtype), w, s)
    return call_op(
        lambda q, sc: (q.astype(jnp.float32) * sc).astype(out_dtype),
        w, s)


def weight_only_linear(x, weight, bias=None, weight_scale=None,
                       weight_dtype="int8", group_size=-1, name=None):
    """reference: paddle.nn.quant.weight_only_linear — weight stays
    int8 (or nibble-packed int4, weight_dtype="int4") in HBM; dequant
    happens in the matmul epilogue which XLA fuses, activations stay in
    their float dtype (no activation quantization).  int4 on v5e is a
    capacity feature (see weight_quantize docstring): the unpack runs
    before the dot, so at small M it is slower than fp8/int8 serving.
    """
    if weight_dtype not in ("int8", "int4"):
        raise NotImplementedError(
            "weight_only_linear: int8 and int4 only")
    if group_size != -1:
        raise NotImplementedError(
            "weight_only_linear: group-wise scales (group_size != -1) "
            "are not implemented")
    x = ensure_tensor(x)
    w, s = ensure_tensor(weight), ensure_tensor(weight_scale)
    ts = [x, w.detach(), s.detach()]
    if bias is not None:
        ts.append(ensure_tensor(bias))
    int4 = weight_dtype == "int4"

    def _wol(a, q, sc, *b):
        if int4:
            q = _unpack_int4(q)
        acc = jnp.matmul(a, q.astype(a.dtype))
        out = acc * sc.astype(a.dtype)
        return out + b[0] if b else out
    return call_op(_wol, *ts)


def llm_int8_linear(x, weight, bias=None, weight_scale=None,
                    threshold=6.0, name=None):
    """reference: paddle.nn.quant.llm_int8_linear — LLM.int8 outlier
    decomposition: activation columns whose absmax exceeds ``threshold``
    run in float against dequantized weight rows; the rest runs int8x
    int8.  Static shapes (outliers are where-masked, not gathered) so
    the whole thing jits."""
    x = ensure_tensor(x)
    w, s = ensure_tensor(weight), ensure_tensor(weight_scale)
    ts = [x, w.detach(), s.detach()]
    if bias is not None:
        ts.append(ensure_tensor(bias))

    def _l8(a, q, sc, *b):
        af = a.astype(jnp.float32)
        lead = af.shape[:-1]
        a2 = af.reshape(-1, af.shape[-1])
        col_out = jnp.max(jnp.abs(a2), axis=0) > threshold      # (K,)
        # float path: outlier columns only
        wf = q.astype(jnp.float32) * sc
        fp_part = jnp.matmul(jnp.where(col_out[None, :], a2, 0.0), wf)
        # int8 path: remaining columns, per-tensor activation scale
        a_in = jnp.where(col_out[None, :], 0.0, a2)
        act_scale = jnp.maximum(jnp.max(jnp.abs(a_in)), 1e-8)
        aq = jnp.clip(jnp.round(a_in / act_scale * _I8_BND),
                      -128, 127).astype(jnp.int8)
        acc = jax.lax.dot_general(aq, q, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        int_part = acc.astype(jnp.float32) * (act_scale / _I8_BND) * sc
        out = (fp_part + int_part).reshape(*lead, q.shape[1])
        out = out.astype(a.dtype)
        return out + b[0] if b else out
    return call_op(_l8, *ts)
