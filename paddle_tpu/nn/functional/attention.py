"""Attention functionals (reference:
python/paddle/nn/functional/flash_attention.py — cutlass flash-attn;
paddle/phi/kernels/fusion/gpu/fused_attention — fused QKV attention).

TPU-native: one `scaled_dot_product_attention` entry.  On TPU, long
sequences run the Pallas flash kernels forward AND backward (blockwise
online softmax; the backward recomputes P blockwise from the saved
log-sum-exp rows), reading and writing (B, S, H, D) in place
(ops/pallas/flash_attention.py).  The XLA path (which the compiler
already fuses into two MXU matmuls + softmax, with a recompute-based
pullback) serves short sequences, the CPU, and every shape the kernel
constraint ladder below turns away.
"""
import math
from collections import namedtuple
from functools import partial

import jax
import jax.numpy as jnp

from ...framework.core import Tensor
from ...framework.autograd import call_op
from ...ops import registry as kreg
from ...ops.pallas import flash_attention as _fa

__all__ = ["scaled_dot_product_attention", "flash_attention",
           "flash_attn_unpadded", "sdp_kernel", "sparse_attention"]

# Pallas kernel pays off past this seq length on TPU (short seqs fit XLA's
# fused softmax just fine and avoid kernel-launch overhead); forcing the
# impl (sdp_kernel / PADDLE_TPU_KERNEL_ATTENTION=pallas) skips the floor
_PALLAS_MIN_SEQ = 1024
# sequences pad up to this granule so S need not be a multiple of 512
# (256 divides every block pair registry.flash_blocks answers for it)
_PAD_GRANULE = 256


def _xla_attention(q, k, v, mask=None, causal=False, scale=None,
                   dropout_p=0.0, key=None):
    """(B, S, H, D) reference attention — fp32 softmax accumulation."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    Hk = k.shape[2]
    if Hk != H:  # MQA/GQA
        k = jnp.repeat(k, H // Hk, axis=2)
        v = jnp.repeat(v, H // Hk, axis=2)
    # (B,H,Sq,Sk)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        qi = jnp.arange(Sq)[:, None]
        ki = jnp.arange(Sk)[None, :]
        s = jnp.where(qi >= ki, s, -1e30)
    if mask is not None:
        s = s + mask.astype(s.dtype)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_p), 0.0)
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
    return o.astype(q.dtype)


# -- kernel-registry dispatch ----------------------------------------------
#
# The registry owns the platform/override/interpret policy; the
# constraint ladder below encodes what the Pallas kernels can express
# (docs/kernels.md "Dispatch rules" is the table form of this code).
# The XLA path is registered as the everywhere-fallback with identical
# math.

kreg.register("attention", "pallas", _fa.flash_attention_fwd,
              platforms=("tpu",))
kreg.register("attention", "xla", _xla_attention, platforms=("*",))

# standalone (eager) flash dispatches are compilestats-tracked under the
# kernel.* surfaces so `roofline_from_stats` attributes per-kernel
# FLOPs/bytes; traced calls inline into the caller's surface
_flash_fwd = kreg.TrackedKernel(_fa.flash_attention_fwd,
                                kreg.FLASH_FWD_SURFACE)
_flash_fwd_lse = kreg.TrackedKernel(_fa.flash_attention_fwd_lse,
                                    kreg.FLASH_FWD_LSE_SURFACE)
_flash_bwd = kreg.TrackedKernel(_fa.flash_attention_bwd,
                                kreg.FLASH_BWD_SURFACE)

_Flash = namedtuple("_Flash", ["use", "interpret"])
_NO_FLASH = _Flash(False, False)


# Under a multi-device trace (kreg.partitioned) XLA cannot partition the
# Mosaic kernels, so the three flash entries run per shard: batch over
# the batch axes, heads over the tensor-parallel axis (the lse residual
# is (B, H, S)).  Each shard's own head count decides whether its
# kernels read the layout in place (_fa.lane_tiled).

def _flash_specs(part, q, k):
    P = jax.sharding.PartitionSpec
    b = part.batch(q.shape[0])
    h = part.heads(q.shape[2], k.shape[2])
    return P(b, None, h, None), P(b, None), P(b, h, None)


def _run_flash_fwd(q, k, v, bias, causal, interpret, with_lse):
    part = kreg.current_partition()
    entry = _flash_fwd_lse if with_lse else _flash_fwd
    if part is None:
        return entry(q, k, v, bias, causal=causal, interpret=interpret)
    s4, s2, s3 = _flash_specs(part, q, k)

    def local(q_, k_, v_, *b_):
        return entry(q_, k_, v_, b_[0] if b_ else None, causal=causal,
                     interpret=interpret)
    with_bias = () if bias is None else (bias,)
    return part.shard_map(
        local, (s4, s4, s4) + (s2,) * len(with_bias),
        (s4, s3) if with_lse else s4)(q, k, v, *with_bias)


def _run_flash_bwd(q, k, v, o, lse, g, bias, causal, interpret):
    part = kreg.current_partition()
    if part is None:
        return _flash_bwd(q, k, v, o, lse, g, bias, causal=causal,
                          interpret=interpret)
    s4, s2, s3 = _flash_specs(part, q, k)

    def local(q_, k_, v_, o_, lse_, g_, *b_):
        return _flash_bwd(q_, k_, v_, o_, lse_, g_, b_[0] if b_ else None,
                          causal=causal, interpret=interpret)
    with_bias = () if bias is None else (bias,)
    return part.shard_map(
        local, (s4, s4, s4, s4, s3, s4) + (s2,) * len(with_bias),
        (s4, s4, s4))(q, k, v, o, lse, g, *with_bias)


def _local_heads(H, Hk):
    """The query-head count one shard's kernels see."""
    part = kreg.current_partition()
    if part is None or part.heads(H, Hk) is None:
        return H
    return H // part.mesh.shape[part.head_axis]


def _select_flash(S, Sk, D, causal, has_mask, mask_is_keybias, scale,
                  dropout_p=0.0, *, heads):
    """The dispatch decision for one attention call, made on static
    shapes at trace time (``heads`` = (H, H_kv)).  Platform/override
    policy comes from the registry; the constraint ladder maps what the
    kernels support, and every constraint fallback is booked in
    pt_kernel_fallbacks_total (a silently dense-running config must be
    visible in telemetry).  A shape the flash kernels take only through
    a transposed copy (the lane-tile rule, per shard) is booked in
    pt_kernel_selects_total as ``pallas_transposed``."""
    sel = kreg.choose("attention", book=False)
    if sel.impl != "pallas":
        kreg.record_select("attention", sel.impl)
        return _NO_FLASH
    pad = (-S) % _PAD_GRANULE
    spad = S + pad
    need_bias = bool(has_mask and mask_is_keybias) or \
        bool(pad and not causal)
    reason = None
    if dropout_p and dropout_p > 0.0:
        reason = "dropout"
    elif scale is not None:
        reason = "scale"
    elif Sk != S:
        reason = "cross-seq"
    elif has_mask and not mask_is_keybias:
        reason = "mask"
    elif need_bias and spad * D > _fa._MH_BWD_MAX_SD:
        # the key-bias path lives in the head-folded kernels; past their
        # VMEM cap a masked (or padded non-causal) shape has no kernel
        reason = "mask-large" if has_mask else "pad-noncausal"
    elif not sel.forced and S < _PALLAS_MIN_SEQ:
        reason = "short-seq"
    transposed = reason is None and not _fa.lane_tiled(
        _local_heads(*heads), D)
    kreg.record_select("attention",
                       "pallas_transposed" if transposed else "pallas")
    if reason is not None:
        kreg.record_fallback("attention", reason)
        return _NO_FLASH
    return _Flash(True, sel.interpret)


def _pad_qkv(q, k, v, bias, causal):
    """Pad S up to the 256 granule.  Causal needs no key masking (real
    queries never attend the appended keys); non-causal folds the pad
    drop into the additive key bias.  Returns (q, k, v, bias, S)."""
    S = q.shape[1]
    pad = (-S) % _PAD_GRANULE
    if not pad:
        return q, k, v, bias, S
    pw = ((0, 0), (0, pad), (0, 0), (0, 0))
    q, k, v = jnp.pad(q, pw), jnp.pad(k, pw), jnp.pad(v, pw)
    if not causal or bias is not None:
        B = q.shape[0]
        if bias is None:
            bias = jnp.zeros((B, S), jnp.float32)
        bias = jnp.pad(bias.astype(jnp.float32), ((0, 0), (0, pad)),
                       constant_values=-1e30)
    return q, k, v, bias, S


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _attention_core(q, k, v, causal, scale, flash):
    if flash.use:
        qp, kp, vp, bias, S = _pad_qkv(q, k, v, None, causal)
        o = _run_flash_fwd(qp, kp, vp, bias, causal, flash.interpret,
                           with_lse=False)
        return o[:, :S] if o.shape[1] != S else o
    return _xla_attention(q, k, v, causal=causal, scale=scale)


def _attn_fwd(q, k, v, causal, scale, flash):
    if flash.use:
        qp, kp, vp, bias, S = _pad_qkv(q, k, v, None, causal)
        o, lse = _run_flash_fwd(qp, kp, vp, bias, causal, flash.interpret,
                                with_lse=True)
        return (o[:, :S] if o.shape[1] != S else o), \
            (qp, kp, vp, bias, o, lse)
    return _xla_attention(q, k, v, causal=causal, scale=scale), \
        (q, k, v, None, None, None)


def _attn_bwd(causal, scale, flash, res, g):
    q, k, v, bias, o, lse = res
    if lse is not None:
        # pallas flash backward: recompute P blockwise from saved lse —
        # no S×S materialization (the reference's flash_attn_bwd)
        S = g.shape[1]
        if o.shape[1] != S:   # padded: pad the cotangent, slice grads
            g = jnp.pad(g, ((0, 0), (0, o.shape[1] - S), (0, 0), (0, 0)))
        dq, dk, dv = _run_flash_bwd(q, k, v, o, lse, g, bias, causal,
                                    flash.interpret)
        return dq[:, :S], dk[:, :S], dv[:, :S]
    # recompute-based pullback at the XLA level (flash-bwd strategy)
    _, vjp = jax.vjp(lambda q_, k_, v_: _xla_attention(
        q_, k_, v_, causal=causal, scale=scale), q, k, v)
    return vjp(g)


_attention_core.defvjp(_attn_fwd, _attn_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _attention_core_bias(q, k, v, bias, causal, flash):
    """Masked flash path: ``bias`` is a (B, Sk) additive per-key mask
    (the reduced (B, 1, 1, Sk) attention mask).  Only entered when
    ``_select_flash`` accepted the shape; the mask gets zero cotangent
    (masks are data, matching the dense path's detached-mask
    contract)."""
    qp, kp, vp, bp, S = _pad_qkv(q, k, v, bias, causal)
    o = _run_flash_fwd(qp, kp, vp, bp, causal, flash.interpret,
                       with_lse=False)
    return o[:, :S] if o.shape[1] != S else o


def _attn_bias_fwd(q, k, v, bias, causal, flash):
    qp, kp, vp, bp, S = _pad_qkv(q, k, v, bias, causal)
    o, lse = _run_flash_fwd(qp, kp, vp, bp, causal, flash.interpret,
                            with_lse=True)
    return (o[:, :S] if o.shape[1] != S else o), \
        (qp, kp, vp, bp, o, lse, bias)


def _attn_bias_bwd(causal, flash, res, g):
    q, k, v, bp, o, lse, bias0 = res
    S = g.shape[1]
    if o.shape[1] != S:
        g = jnp.pad(g, ((0, 0), (0, o.shape[1] - S), (0, 0), (0, 0)))
    dq, dk, dv = _run_flash_bwd(q, k, v, o, lse, g, bp, causal,
                                flash.interpret)
    return dq[:, :S], dk[:, :S], dv[:, :S], jnp.zeros_like(bias0)


_attention_core_bias.defvjp(_attn_bias_fwd, _attn_bias_bwd)


def _as_key_bias(m, B, Sk):
    """Reduce an additive attention mask to the kernels' per-key (B, Sk)
    bias when it is constant over heads and queries — the key-padding
    shape (B|1, 1, 1, Sk).  Returns None when the mask genuinely varies
    per query/head (the XLA path keeps full generality)."""
    if m is None:
        return None
    shape = tuple(getattr(m, "shape", ()))
    if len(shape) == 4 and shape[1] == 1 and shape[2] == 1 \
            and shape[3] == Sk and shape[0] in (1, B):
        return lambda mv: jnp.broadcast_to(
            mv[:, 0, 0, :].astype(jnp.float32), (B, Sk))
    return None


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    """paddle.nn.functional.scaled_dot_product_attention — (B, S, H, D).

    Dispatch (ops/registry.py policy + the kernel constraint ladder):
    TPU (or interpret mode) routes through the Pallas flash kernels —
    including masked calls whose mask reduces to a per-key bias (the
    key-padding shape) and sequences that are not a multiple of 512
    (padded to the 256 granule) — everything else through the XLA
    attention with identical math."""
    from ...framework.random import next_key
    tensors = [query, key, value]
    q, k, v = [t if isinstance(t, Tensor) else Tensor(t) for t in tensors]
    B, S, H, D = q.shape
    Sk = k.shape[1]
    causal = bool(is_causal)
    drop = dropout_p if training else 0.0
    m = attn_mask._value if isinstance(attn_mask, Tensor) else attn_mask
    reduce = _as_key_bias(m, B, Sk) if attn_mask is not None else None
    flash = _select_flash(S, Sk, D, causal,
                          has_mask=attn_mask is not None,
                          mask_is_keybias=reduce is not None,
                          scale=None, dropout_p=drop,
                          heads=(H, k.shape[2]))
    if flash.use:
        if attn_mask is None:
            return call_op(lambda a, b, c: _attention_core(
                a, b, c, causal, None, flash), q, k, v)
        return call_op(lambda a, b, c: _attention_core_bias(
            a, b, c, reduce(m), causal, flash), q, k, v)
    if attn_mask is None and drop == 0.0:
        return call_op(lambda a, b, c: _attention_core(
            a, b, c, causal, None, _NO_FLASH), q, k, v)
    rng = next_key() if (drop > 0.0) else None
    return call_op(lambda a, b, c: _xla_attention(
        a, b, c, mask=m, causal=causal,
        dropout_p=drop, key=rng), q, k, v)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """paddle.nn.functional.flash_attention.flash_attention parity."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    return (out, None) if return_softmax else (out, None)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale=None, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="", training=True,
                        name=None):
    """paddle.nn.functional.flash_attention.flash_attn_unpadded parity:
    packed (total, H, D) q/k/v with (B+1,) cu_seqlens prefix sums.
    TPU-native: segment-id-masked Pallas flash kernel (see
    ops/pallas/flash_attention_varlen.py)."""
    from ...ops.pallas.flash_attention_varlen import (
        flash_attn_unpadded as _raw)
    q, k, v = [t if isinstance(t, Tensor) else Tensor(t)
               for t in (query, key, value)]
    cu_q = cu_seqlens_q._value if isinstance(cu_seqlens_q, Tensor) \
        else jnp.asarray(cu_seqlens_q, jnp.int32)
    cu_k = cu_seqlens_k._value if isinstance(cu_seqlens_k, Tensor) \
        else jnp.asarray(cu_seqlens_k, jnp.int32)
    drop = dropout if training else 0.0
    from ...framework.random import next_key
    dkey = next_key() if drop and drop > 0.0 else None
    if return_softmax:
        # debug mode: dense path materializes the probabilities
        out, p = call_op(
            lambda a, b, c: _raw(a, b, c, cu_q, cu_k, max_seqlen_q,
                                 max_seqlen_k, scale=scale, dropout=drop,
                                 causal=bool(causal), dropout_key=dkey,
                                 return_softmax=True),
            q, k, v)
        return out, p
    out = call_op(
        lambda a, b, c: _raw(a, b, c, cu_q, cu_k, max_seqlen_q,
                             max_seqlen_k, scale=scale, dropout=drop,
                             causal=bool(causal), dropout_key=dkey)[0],
        q, k, v)
    return out, None


class sdp_kernel:
    """Context manager selecting attention backends (torch-compat shim
    the reference also exposes), now wired to the kernel registry:
    ``enable_flash=False`` forces the XLA path, ``enable_math=False``
    (with flash enabled) forces the Pallas kernel — the same override
    rail as ``PADDLE_TPU_KERNEL_ATTENTION``.
    With both enabled (the default) the dispatch stays automatic."""

    def __init__(self, enable_flash=True, enable_math=True,
                 enable_mem_efficient=True, **kwargs):
        self._force = None
        if not enable_flash:
            self._force = kreg.force("attention", "xla")
        elif not enable_math:
            self._force = kreg.force("attention", "pallas")

    def __enter__(self):
        if self._force is not None:
            self._force.__enter__()
        return self

    def __exit__(self, *exc):
        if self._force is not None:
            self._force.__exit__(*exc)
        return False


def sparse_attention(query, key, value, sparse_csr_offset,
                     sparse_csr_columns, key_padding_mask=None,
                     attn_mask=None, name=None):
    """reference: paddle.nn.functional.sparse_attention — attention
    restricted to a per-(batch, head) CSR sparsity pattern.

    q/k/v: (B, H, T, D); offset: (B, H, T+1) int; columns: (B, H, nnz).
    TPU-native lowering: the CSR pattern becomes a dense (T, T) boolean
    mask built with one scatter (nnz is static under jit; row ids come
    from searchsorted over the offsets), then the masked softmax rides
    the regular fused attention path — on TPU the MXU prefers the dense
    masked form over gather/scatter per row unless sparsity is extreme.
    """
    from ...tensor._helpers import ensure_tensor
    q = ensure_tensor(query)
    k = ensure_tensor(key)
    v = ensure_tensor(value)
    off = ensure_tensor(sparse_csr_offset).detach()
    cols = ensure_tensor(sparse_csr_columns).detach()
    ts = [q, k, v, off, cols]
    if key_padding_mask is not None:
        ts.append(ensure_tensor(key_padding_mask).detach())
    if attn_mask is not None:
        ts.append(ensure_tensor(attn_mask).detach())

    def _sa(qv, kv, vv, offv, colv, *masks):
        B, H, T, D = qv.shape
        nnz = colv.shape[-1]
        # row index of every nnz entry, per (B, H)
        ar = jnp.arange(nnz)

        def rows_of(o):            # o: (T+1,)
            return jnp.searchsorted(o, ar, side="right") - 1
        rows = jax.vmap(jax.vmap(rows_of))(offv)          # (B, H, nnz)
        mask = jnp.zeros((B, H, T, T), bool)
        bidx = jnp.arange(B)[:, None, None]
        hidx = jnp.arange(H)[None, :, None]
        mask = mask.at[bidx, hidx, rows, colv].set(True)
        scale = 1.0 / math.sqrt(D)
        scores = jnp.einsum("bhtd,bhsd->bhts", qv, kv) * scale
        neg = jnp.asarray(-1e9, scores.dtype)
        scores = jnp.where(mask, scores, neg)
        mi = 0
        if key_padding_mask is not None:
            kpm = masks[mi]
            mi += 1
            scores = jnp.where(kpm[:, None, None, :] != 0, scores, neg)
        if attn_mask is not None:
            scores = scores + masks[mi].astype(scores.dtype)
        probs = jax.nn.softmax(scores, axis=-1)
        # rows with no live key (possible via padding) emit zeros
        live = jnp.any(scores > neg / 2, axis=-1, keepdims=True)
        probs = jnp.where(live, probs, 0.0)
        return jnp.einsum("bhts,bhsd->bhtd", probs, vv)
    return call_op(_sa, *ts)
