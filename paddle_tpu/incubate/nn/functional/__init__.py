"""Incubate functionals (reference: python/paddle/incubate/nn/functional/
— fused_multi_head_attention, flash_attention wrapper over the cutlass
submodule).

TPU-native: flash attention dispatches to the Pallas kernel (M3) when on
TPU with compatible shapes, falling back to the XLA softmax composition
(which XLA fuses well on its own).
"""
import math

import numpy as np
import jax
import jax.numpy as jnp

from ....framework.core import Tensor
from ....framework.autograd import call_op
from ....tensor._helpers import ensure_tensor

__all__ = ["flash_attention", "scaled_dot_product_attention",
           "fused_multi_head_attention", "flash_attn_unpadded"]


def _sdpa(q, k, v, mask=None, dropout=0.0, causal=False, scale=None):
    """q,k,v: (B, S, H, D) paddle flash-attention layout."""
    d = q.shape[-1]
    s = scale or (1.0 / math.sqrt(d))
    # -> (B,H,S,D)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhsd,bhtd->bhst", qt, kt) * s
    if causal:
        S, T = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((S, T), bool))
        logits = jnp.where(cm, logits, -1e30)
    if mask is not None:
        logits = logits + mask
    probs = jax.nn.softmax(logits.astype(jnp.float32),
                           axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vt)
    return jnp.swapaxes(out, 1, 2)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """paddle.nn.functional.flash_attention layout: (B, S, H, D)."""
    q, k, v = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    use_pallas = _pallas_ok(q)
    if use_pallas:
        from ....ops.pallas.flash_attention import flash_attention_fwd
        out = call_op(lambda a, b, c: flash_attention_fwd(
            a, b, c, causal=causal), q, k, v)
    else:
        out = call_op(lambda a, b, c: _sdpa(a, b, c, causal=causal), q, k, v)
    if return_softmax:
        return out, None
    return out, None


def _pallas_ok(q):
    """Pallas flash on a TPU backend when the shape fits the kernel; a
    TPU call the shape sends to the XLA composition is a counted
    fallback, never a silent one."""
    if jax.default_backend() != "tpu":
        return False
    B, S, H, D = q.shape
    if S % 128 == 0 and D in (64, 128, 256):
        return True
    from ....ops import registry as kreg
    kreg.record_fallback("attention", "incubate-shape")
    return False


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    q, k, v = ensure_tensor(query), ensure_tensor(key), ensure_tensor(value)
    if attn_mask is not None:
        m = ensure_tensor(attn_mask)
        return call_op(lambda a, b, c, mm: _sdpa(a, b, c, mask=mm,
                                                 causal=is_causal),
                       q, k, v, m)
    return call_op(lambda a, b, c: _sdpa(a, b, c, causal=is_causal), q, k, v)


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False, name=None):
    """Varlen flash attention (reference: paddle.incubate varlen entry);
    delegates to the segment-id-masked Pallas kernel."""
    from ...nn.functional.attention import flash_attn_unpadded as _fa
    return _fa(query, key, value, cu_seqlens_q, cu_seqlens_k,
               max_seqlen_q, max_seqlen_k, scale=scale, dropout=dropout,
               causal=causal, return_softmax=return_softmax)


def fused_multi_head_attention(
        x, qkv_weight, linear_weight, pre_layer_norm=False,
        pre_ln_scale=None, pre_ln_bias=None, ln_scale=None, ln_bias=None,
        pre_ln_epsilon=1e-5, qkv_bias=None, linear_bias=None,
        cache_kv=None, attn_mask=None, dropout_rate=0.5,
        attn_dropout_rate=0.5, ln_epsilon=1e-5, training=True,
        mode="upscale_in_train", ring_id=-1, add_residual=True,
        num_heads=-1, transpose_qkv_wb=False, name=None):
    """reference: incubate.nn.functional.fused_multi_head_attention —
    residual + (pre|post)-LN self-attention with the qkv projection as
    one packed GEMM (one MXU pass; XLA fuses the epilogues).

    qkv_weight layouts: (3, H, Dh, C) reference-native, or (C, 3C) with
    transpose_qkv_wb=True.  cache_kv / tensor-parallel ring_id are not
    supported here (use the fleet TP layers / mmha for decode).
    """
    if cache_kv is not None:
        raise NotImplementedError(
            "fused_multi_head_attention: cache_kv decode path is not "
            "supported; use masked_multihead_attention")
    if ring_id != -1:
        raise NotImplementedError(
            "fused_multi_head_attention: tensor-parallel ring_id is not "
            "supported; use fleet meta_parallel TP layers")
    from ....framework.random import next_key
    xt = ensure_tensor(x)
    qkv_w = ensure_tensor(qkv_weight)
    lin_w = ensure_tensor(linear_weight)
    if transpose_qkv_wb:
        C = qkv_w.shape[0]
        H = num_heads
        if H <= 0:
            raise ValueError("transpose_qkv_wb=True needs num_heads")
        Dh = C // H
    else:
        _, H, Dh, C = qkv_w.shape
    if mode not in ("upscale_in_train", "downscale_in_infer"):
        raise ValueError(f"unknown dropout mode {mode!r}")
    attn_p = attn_dropout_rate if training else 0.0
    out_p = dropout_rate if training else 0.0
    # downscale_in_infer: train drops WITHOUT upscaling; infer scales
    # the activations by (1-p) instead
    upscale = mode == "upscale_in_train"
    infer_scale_attn = (1.0 - attn_dropout_rate) \
        if (not upscale and not training) else 1.0
    infer_scale_out = (1.0 - dropout_rate) \
        if (not upscale and not training) else 1.0
    rng = next_key() if (attn_p > 0.0 or out_p > 0.0) else None
    pre = bool(pre_layer_norm)

    opt = {"qkv_b": qkv_bias, "lin_b": linear_bias,
           "pls": pre_ln_scale, "plb": pre_ln_bias,
           "lns": ln_scale, "lnb": ln_bias,
           "mask": attn_mask}
    names = [k for k, v in opt.items() if v is not None]
    ts = [xt, qkv_w, lin_w] + [ensure_tensor(opt[k]) for k in names]

    def impl(xv, wq, wl, *rest):
        vals = dict(zip(names, rest))

        def _lnorm(h, sc, bi, eps):
            mu = jnp.mean(h, -1, keepdims=True)
            var = jnp.var(h, -1, keepdims=True)
            out = (h - mu) * jax.lax.rsqrt(var + eps)
            if sc is not None:
                out = out * sc
            if bi is not None:
                out = out + bi
            return out

        residual = xv
        h = xv
        if pre:
            h = _lnorm(h, vals.get("pls"), vals.get("plb"), pre_ln_epsilon)
        B, S, _ = h.shape
        if transpose_qkv_wb:
            qkv = h @ wq                                  # (B, S, 3C)
            if "qkv_b" in vals:
                qkv = qkv + vals["qkv_b"]
            qkv = qkv.reshape(B, S, 3, H, Dh)
        else:
            # (3, H, Dh, C) reference layout: one einsum GEMM
            qkv = jnp.einsum("bsc,thdc->bsthd", h, wq)
            if "qkv_b" in vals:
                qkv = qkv + vals["qkv_b"].reshape(1, 1, 3, H, Dh)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       preferred_element_type=jnp.float32)             / math.sqrt(Dh)
        if "mask" in vals:
            mv = vals["mask"]
            if jnp.issubdtype(mv.dtype, jnp.floating):
                s = s + mv.astype(s.dtype)
            else:
                # bool/int mask: nonzero = attend, zero = masked
                s = jnp.where(mv != 0, s, jnp.asarray(-1e9, s.dtype))
        p = jax.nn.softmax(s, axis=-1)
        if attn_p > 0.0:
            keep = jax.random.bernoulli(jax.random.fold_in(rng, 0),
                                        1.0 - attn_p, p.shape)
            p = jnp.where(keep, p / (1.0 - attn_p) if upscale else p, 0.0)
        elif infer_scale_attn != 1.0:
            p = p * infer_scale_attn
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)
        o = o.reshape(B, S, H * Dh) @ wl
        if "lin_b" in vals:
            o = o + vals["lin_b"]
        if out_p > 0.0:
            keep = jax.random.bernoulli(jax.random.fold_in(rng, 1),
                                        1.0 - out_p, o.shape)
            o = jnp.where(keep, o / (1.0 - out_p) if upscale else o, 0.0)
        elif infer_scale_out != 1.0:
            o = o * infer_scale_out
        out = residual + o if add_residual else o
        if not pre:
            out = _lnorm(out, vals.get("lns"), vals.get("lnb"),
                         ln_epsilon)
        return out
    return call_op(impl, *ts)


# -- fused norm / rotary / activation surface (reference:
# python/paddle/incubate/nn/functional/{fused_layer_norm,fused_rms_norm,
# fused_rotary_position_embedding,swiglu,fused_dropout_add}.py) ------------

def fused_rms_norm(x, norm_weight, norm_bias=None, epsilon=1e-6,
                   begin_norm_axis=-1, bias=None, residual=None,
                   quant_scale=-1, **kw):
    """RMSNorm over the last axis via the Pallas one-pass kernel
    (ops/pallas/fused_norm.py; CPU fallback identical numerics).
    Optional pre-norm residual-add (returns (out, residual_out) then,
    reference signature).  norm_bias adds after scaling."""
    from ....ops.pallas.fused_norm import fused_rms_norm as _kernel
    xt = ensure_tensor(x)
    ts = [xt, ensure_tensor(norm_weight)]
    has_res = residual is not None
    has_bias = bias is not None
    has_nb = norm_bias is not None
    if has_res:
        ts.append(ensure_tensor(residual))
    if has_bias:
        ts.append(ensure_tensor(bias))
    if has_nb:
        ts.append(ensure_tensor(norm_bias))

    def impl(xv, gv, *rest):
        i = 0
        rv = rest[i] if has_res else None
        i += has_res
        bv = rest[i] if has_bias else None
        i += has_bias
        nb = rest[i] if has_nb else None
        pre = xv
        if bv is not None:
            pre = pre + bv
        if rv is not None:
            pre = pre + rv
        out = _kernel(pre, gv, eps=epsilon)
        if nb is not None:
            out = out + nb
        return (out, pre) if has_res else out
    return call_op(impl, *ts)


def fused_layer_norm(x, norm_weight, norm_bias, epsilon=1e-5,
                     begin_norm_axis=-1, bias=None, residual=None, **kw):
    """LayerNorm via the Pallas one-pass kernel, with the reference's
    optional residual/bias pre-adds."""
    from ....ops.pallas.fused_norm import fused_layer_norm as _kernel
    xt = ensure_tensor(x)
    ts = [xt, ensure_tensor(norm_weight), ensure_tensor(norm_bias)]
    has_res = residual is not None
    has_bias = bias is not None
    if has_res:
        ts.append(ensure_tensor(residual))
    if has_bias:
        ts.append(ensure_tensor(bias))

    def impl(xv, gv, bv, *rest):
        i = 0
        rv = rest[i] if has_res else None
        i += has_res
        pb = rest[i] if has_bias else None
        pre = xv
        if pb is not None:
            pre = pre + pb
        if rv is not None:
            pre = pre + rv
        out = _kernel(pre, gv, bv, eps=epsilon)
        return (out, pre) if has_res else out
    return call_op(impl, *ts)


def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True,
                                    time_major=False, rotary_emb_base=10000.0):
    """RoPE applied to q/k (v passes through untouched when given) —
    reference: incubate/nn/functional/fused_rotary_position_embedding.py.
    (B, S, H, D) layout.  With use_neox_rotary_style the rotation pairs
    (x_i, x_{i+D/2}); otherwise interleaved (x_{2i}, x_{2i+1})."""
    outs = []

    def rope_one(xv, sin_v, cos_v):
        B, S, H, D = xv.shape
        if sin_v is None:
            pos = jnp.arange(S) if position_ids is None else position_ids
            freqs = 1.0 / (rotary_emb_base ** (
                jnp.arange(0, D, 2, dtype=jnp.float32) / D))
            ang = pos[:, None].astype(jnp.float32) * freqs[None, :]
            cos_a = jnp.cos(ang)[None, :, None, :]
            sin_a = jnp.sin(ang)[None, :, None, :]
        else:
            # accepted shapes (B?, S, 1?, D) carrying duplicated halves —
            # take the leading D/2 columns
            sin_a = jnp.asarray(sin_v, jnp.float32).reshape(1, S, 1, -1)[..., :D // 2]
            cos_a = jnp.asarray(cos_v, jnp.float32).reshape(1, S, 1, -1)[..., :D // 2]
        xf = xv.astype(jnp.float32)
        if use_neox_rotary_style:
            x1, x2 = xf[..., :D // 2], xf[..., D // 2:]
            r1 = x1 * cos_a - x2 * sin_a
            r2 = x2 * cos_a + x1 * sin_a
            out = jnp.concatenate([r1, r2], axis=-1)
        else:
            x1, x2 = xf[..., ::2], xf[..., 1::2]
            r1 = x1 * cos_a - x2 * sin_a
            r2 = x2 * cos_a + x1 * sin_a
            out = jnp.stack([r1, r2], axis=-1).reshape(B, S, H, D)
        return out.astype(xv.dtype)

    sv = sin._value if isinstance(sin, Tensor) else sin
    cv = cos._value if isinstance(cos, Tensor) else cos
    for t in (q, k):
        if t is None:
            outs.append(None)
            continue
        tt = ensure_tensor(t)
        outs.append(call_op(lambda xv: rope_one(xv, sv, cv), tt))
    outs.append(ensure_tensor(v) if v is not None else None)
    return tuple(outs)


def swiglu(x, y=None, name=None):
    """silu(x) * y; with y=None x splits in half on the last axis
    (reference: incubate/nn/functional/swiglu.py)."""
    xt = ensure_tensor(x)
    if y is None:
        def impl(v):
            a, b = jnp.split(v, 2, axis=-1)
            return jax.nn.silu(a) * b
        return call_op(impl, xt)
    return call_op(lambda a, b: jax.nn.silu(a) * b, xt, ensure_tensor(y))


def fused_dropout_add(x, y, p=0.0, training=True, mode="upscale_in_train",
                      name=None):
    """dropout(x) + y in one op (reference:
    incubate/nn/functional/fused_dropout_add.py); XLA fuses the mask and
    add into one kernel."""
    from ....nn import functional as _F
    xt, yt = ensure_tensor(x), ensure_tensor(y)
    dropped = _F.dropout(xt, p=p, training=training, mode=mode)
    return call_op(lambda a, b: a + b, dropped, yt)


__all__ += ["fused_rms_norm", "fused_layer_norm",
            "fused_rotary_position_embedding", "swiglu",
            "fused_dropout_add"]


def _ln_apply(h, scale, bias, eps):
    """Shared last-axis layer norm body for the fused ops below."""
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.var(h, axis=-1, keepdims=True)
    out = (h - mu) / jnp.sqrt(var + eps)
    if scale is not None:
        out = out * scale
    if bias is not None:
        out = out + bias
    return out


def _drop_apply(h, key, rate, mode):
    """Shared dropout body (reference mode semantics, matching
    nn.functional.dropout): upscale_in_train scales kept values at
    train time; downscale_in_infer scales ALL values at eval time."""
    if key is not None:
        keep = jax.random.bernoulli(key, 1.0 - rate, h.shape)
        if mode == "upscale_in_train":
            return jnp.where(keep, h / (1.0 - rate), 0.0)
        return jnp.where(keep, h, 0.0)
    if mode == "downscale_in_infer" and rate > 0.0:
        return h * (1.0 - rate)
    return h


def fused_linear(x, weight, bias=None, transpose_weight=False, name=None):
    """reference: incubate.nn.functional.fused_linear — matmul + bias in
    one call (XLA fuses the epilogue; the reference fuses via cublasLt)."""
    x = ensure_tensor(x)
    weight = ensure_tensor(weight)
    args = [x, weight] + ([ensure_tensor(bias)] if bias is not None else [])

    def _fl(xv, wv, *b):
        w = wv.T if transpose_weight else wv
        out = jnp.dot(xv, w, preferred_element_type=jnp.float32)
        if b:
            out = out + b[0]
        return out.astype(xv.dtype)
    return call_op(_fl, *args)


def fused_linear_activation(x, y, bias, trans_x=False, trans_y=False,
                            activation="gelu", name=None):
    """reference: incubate.nn.functional.fused_linear_activation —
    matmul + bias + activation epilogue."""
    x = ensure_tensor(x)
    y = ensure_tensor(y)
    bias = ensure_tensor(bias)
    act = {"gelu": jax.nn.gelu, "relu": jax.nn.relu,
           "none": lambda v: v}[activation]

    def _fla(xv, yv, bv):
        a = xv.T if trans_x else xv
        b = yv.T if trans_y else yv
        out = jnp.dot(a, b, preferred_element_type=jnp.float32) + bv
        return act(out).astype(xv.dtype)
    return call_op(_fla, x, y, bias)


def fused_bias_dropout_residual_layer_norm(
        x, residual, bias=None, ln_scale=None, ln_bias=None,
        dropout_rate=0.5, ln_epsilon=1e-5, training=True,
        mode="upscale_in_train", name=None):
    """reference: incubate.nn.functional.fused_bias_dropout_residual_
    layer_norm — LN(residual + dropout(x + bias)); one fused region
    under XLA."""
    from ....framework.random import next_key
    x = ensure_tensor(x)
    residual = ensure_tensor(residual)
    opt = [t for t in (bias, ln_scale, ln_bias) if t is not None]
    has = [t is not None for t in (bias, ln_scale, ln_bias)]
    key = next_key() if (training and dropout_rate > 0.0) else None

    def _f(xv, rv, *rest):
        it = iter(rest)
        bv = next(it) if has[0] else None
        sv = next(it) if has[1] else None
        lbv = next(it) if has[2] else None
        h = xv if bv is None else xv + bv
        h = _drop_apply(h, key, dropout_rate, mode)
        h = h + rv
        out = _ln_apply(h, sv, lbv, ln_epsilon)
        return out.astype(xv.dtype)
    return call_op(_f, x, residual, *[ensure_tensor(t) for t in opt])


def fused_feedforward(x, linear1_weight, linear2_weight,
                      linear1_bias=None, linear2_bias=None,
                      ln1_scale=None, ln1_bias=None, ln2_scale=None,
                      ln2_bias=None, dropout1_rate=0.5, dropout2_rate=0.5,
                      activation="relu", ln1_epsilon=1e-5,
                      ln2_epsilon=1e-5, pre_layer_norm=False,
                      training=True, mode="upscale_in_train", name=None):
    """reference: incubate.nn.functional.fused_feedforward — the full
    transformer FFN block: residual + LN around
    linear2(dropout1(act(linear1(x))))."""
    from ....framework.random import next_key
    x = ensure_tensor(x)
    tensors = {"w1": ensure_tensor(linear1_weight),
               "w2": ensure_tensor(linear2_weight)}
    for nm, t in (("b1", linear1_bias), ("b2", linear2_bias),
                  ("s1", ln1_scale), ("lb1", ln1_bias),
                  ("s2", ln2_scale), ("lb2", ln2_bias)):
        if t is not None:
            tensors[nm] = ensure_tensor(t)
    names = list(tensors)
    act = {"relu": jax.nn.relu, "gelu": jax.nn.gelu}[activation]
    k1 = next_key() if (training and dropout1_rate > 0.0) else None
    k2 = next_key() if (training and dropout2_rate > 0.0) else None

    def _ff(xv, *vals):
        d = dict(zip(names, vals))
        h = xv
        if pre_layer_norm:
            h = _ln_apply(h, d.get("s1"), d.get("lb1"), ln1_epsilon)
        h = jnp.dot(h, d["w1"], preferred_element_type=jnp.float32)
        if "b1" in d:
            h = h + d["b1"]
        h = _drop_apply(act(h), k1, dropout1_rate, mode)
        h = jnp.dot(h, d["w2"], preferred_element_type=jnp.float32)
        if "b2" in d:
            h = h + d["b2"]
        h = xv + _drop_apply(h, k2, dropout2_rate, mode).astype(xv.dtype)
        if not pre_layer_norm:
            # post-LN applies the ln2 params only (reference contract)
            h = _ln_apply(h, d.get("s2"), d.get("lb2"), ln2_epsilon)
        return h.astype(xv.dtype)
    return call_op(_ff, x, *tensors.values())


def variable_length_memory_efficient_attention(
        query, key, value, seq_lens, kv_seq_lens, mask=None, scale=None,
        causal=False, pre_cache_length=0, name=None):
    """reference: incubate.nn.functional.variable_length_memory_
    efficient_attention — (B, H, S, D) attention with per-batch valid
    lengths.  TPU-native: length masks folded into one XLA softmax
    region (the reference's cutlass memory-efficient kernel's job is
    done by not materializing fp32 probs in HBM — XLA keeps the
    block-softmax in registers)."""
    q, k, v = (ensure_tensor(t) for t in (query, key, value))
    sl = ensure_tensor(seq_lens)._value.reshape(-1).astype(jnp.int32)
    kvl = ensure_tensor(kv_seq_lens)._value.reshape(-1).astype(jnp.int32)
    m = None if mask is None else ensure_tensor(mask)._value

    def _vl(qv, kv_, vv):
        B, H, S, D = qv.shape
        T = kv_.shape[2]
        sc = scale or 1.0 / math.sqrt(D)
        logits = jnp.einsum("bhsd,bhtd->bhst", qv.astype(jnp.float32),
                            kv_.astype(jnp.float32)) * sc
        q_live = jnp.arange(S)[None, :] < sl[:, None]          # (B, S)
        k_live = jnp.arange(T)[None, :] < kvl[:, None]         # (B, T)
        live = q_live[:, None, :, None] & k_live[:, None, None, :]
        if causal:
            # pre_cache_length offsets the causal diagonal: query i may
            # attend keys [0, pre_cache_length + i]
            live = live & jnp.tril(jnp.ones((S, T), bool),
                                   k=int(pre_cache_length))[None, None]
        logits = jnp.where(live, logits, -1e30)
        if m is not None:
            logits = logits + m
        p = jax.nn.softmax(logits, axis=-1)
        # rows with no live keys (query past kv_seq_len): exact zeros
        p = jnp.where(jnp.any(live, -1, keepdims=True), p, 0.0)
        return jnp.einsum("bhst,bhtd->bhsd", p, vv.astype(jnp.float32)
                          ).astype(qv.dtype)
    return call_op(_vl, q, k, v)


__all__ += ["fused_linear", "fused_linear_activation",
            "fused_bias_dropout_residual_layer_norm",
            "fused_feedforward",
            "variable_length_memory_efficient_attention"]


def masked_multihead_attention(x, cache_kv=None, bias=None, src_mask=None,
                               cum_offsets=None, sequence_lengths=None,
                               rotary_tensor=None, beam_cache_offset=None,
                               seq_len=1, rotary_emb_dims=0,
                               use_neox_rotary_style=False,
                               compute_dtype="default", out_scale=-1,
                               quant_round_type=1, quant_max_bound=127.0,
                               quant_min_bound=-127.0, name=None):
    """reference: incubate.nn.functional.masked_multihead_attention —
    single-step decoder attention over a KV cache.

    Core contract (the serving path): x (B, 3*H*D) fused qkv for ONE new
    token; cache_kv (2, B, H, T_max, D); sequence_lengths (B,) = tokens
    already cached.  The new k/v are written at each batch row's length,
    attention runs over the valid prefix + the new token, and the
    UPDATED cache is returned alongside the (B, H*D) output.  Quant /
    beam-search / neox-rotary knobs of the reference CUDA kernel are not
    supported here and raise."""
    if beam_cache_offset is not None or rotary_emb_dims:
        raise NotImplementedError(
            "masked_multihead_attention: beam_cache_offset / rotary "
            "embedding application is not supported; apply rotary to x "
            "before the call")
    if out_scale > 0 or use_neox_rotary_style or \
            compute_dtype not in ("default",):
        raise NotImplementedError(
            "masked_multihead_attention: quantized output (out_scale>0), "
            "neox rotary style, and compute_dtype overrides are not "
            "supported")
    if cache_kv is None:
        raise ValueError("masked_multihead_attention needs cache_kv")
    x = ensure_tensor(x)
    cache = ensure_tensor(cache_kv)
    args = [x, cache]
    if bias is not None:
        args.append(ensure_tensor(bias))
    has_bias = bias is not None
    mask_v = None if src_mask is None else ensure_tensor(src_mask)._value
    _, B, H, T, D = cache.shape
    if sequence_lengths is None:
        raise ValueError(
            "masked_multihead_attention: sequence_lengths is required "
            "(static shapes need the explicit cache fill level)")
    lens = ensure_tensor(sequence_lengths)._value.reshape(-1) \
        .astype(jnp.int32)
    if not isinstance(lens, jax.core.Tracer) and bool((lens >= T).any()):
        raise ValueError(
            f"masked_multihead_attention: KV cache full (capacity {T}, "
            f"lengths {np.asarray(lens).tolist()}) — the scatter for the "
            "new token would be dropped silently")

    def _mmha(xv, cachev, *rest):
        qkv = xv + rest[0] if has_bias else xv
        qkv = qkv.reshape(B, 3, H, D)
        q, k_new, v_new = qkv[:, 0], qkv[:, 1], qkv[:, 2]   # (B, H, D)
        bi = jnp.arange(B)
        k_cache = cachev[0].at[bi, :, lens, :].set(k_new)
        v_cache = cachev[1].at[bi, :, lens, :].set(v_new)
        sc = 1.0 / math.sqrt(D)
        logits = jnp.einsum("bhd,bhtd->bht", q.astype(jnp.float32),
                            k_cache.astype(jnp.float32)) * sc
        live = jnp.arange(T)[None, :] <= lens[:, None]      # (B, T)
        logits = jnp.where(live[:, None, :], logits, -1e30)
        if mask_v is not None:
            logits = logits + mask_v.reshape(B, 1, -1)[..., :T]
        p = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bht,bhtd->bhd", p,
                         v_cache.astype(jnp.float32))
        return (out.reshape(B, H * D).astype(xv.dtype),
                jnp.stack([k_cache, v_cache]))
    return call_op(_mmha, *args)


__all__ += ["masked_multihead_attention"]


def fused_matmul_bias(x, y, bias=None, transpose_x=False,
                      transpose_y=False, name=None):
    """reference: incubate.nn.functional.fused_matmul_bias (cublasLt
    epilogue); XLA fuses the bias add into the GEMM."""
    x, y = ensure_tensor(x), ensure_tensor(y)
    args = [x, y] + ([ensure_tensor(bias)] if bias is not None else [])

    def _fmb(xv, yv, *b):
        a = jnp.swapaxes(xv, -1, -2) if transpose_x else xv
        w = jnp.swapaxes(yv, -1, -2) if transpose_y else yv
        out = jnp.dot(a, w, preferred_element_type=jnp.float32)
        if b:
            out = out + b[0]
        return out.astype(xv.dtype)
    return call_op(_fmb, *args)


__all__ += ["fused_matmul_bias"]


def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size,
                     name=None):
    """reference: incubate.nn.functional.blha_get_max_len — max
    encoder/decoder lengths feeding block_multihead_attention's
    scheduling."""
    enc = ensure_tensor(seq_lens_encoder).detach()
    dec = ensure_tensor(seq_lens_decoder).detach()
    mx = lambda v: jnp.max(v.reshape(-1)) if v.size else jnp.asarray(0)
    return (call_op(mx, enc), call_op(mx, dec))


def block_multihead_attention(
        qkv, key_cache, value_cache, seq_lens_encoder, seq_lens_decoder,
        seq_lens_this_time, padding_offsets=None, cum_offsets=None,
        cu_seqlens_q=None, cu_seqlens_k=None, block_tables=None,
        pre_key_cache=None, pre_value_cache=None, rope_emb=None,
        mask=None, tgt_mask=None, max_seq_len=-1, block_size=64,
        use_neox_style=False, name=None, **unsupported):
    """reference: incubate.nn.functional.block_multihead_attention —
    mixed prefill/decode attention over a PAGED (block) KV cache.

    Contract implemented: qkv (total_tokens, 3*H*D) packs every batch
    row's tokens this step; row b is a PREFILL of seq_lens_encoder[b]
    tokens or a DECODE of one token over seq_lens_decoder[b] cached
    ones; block_tables (B, max_blocks) maps logical KV positions into
    key/value_cache (num_blocks, H, block_size, D).  Returns
    (out, qkv, key_cache, value_cache) with the caches UPDATED.

    Envelope: host-scheduled per-request attention (correctness-level
    paged cache; the TPU fast paths are
    variable_length_memory_efficient_attention for prefill and
    masked_multihead_attention for decode).  Rope / neox / quant-cache
    knobs raise.
    """
    if rope_emb is not None or use_neox_style:
        raise NotImplementedError(
            "block_multihead_attention: apply rotary embeddings to qkv "
            "before the call")
    extra = {k: v for k, v in unsupported.items() if v is not None}
    if pre_key_cache is not None or extra:
        raise NotImplementedError(
            "block_multihead_attention: unsupported arguments "
            f"{['pre_key_cache'] if pre_key_cache is not None else []}"
            f"{sorted(extra)} (pre-cache / quantized-cache / scale knobs "
            "are not implemented)")
    if block_tables is None:
        raise ValueError("block_multihead_attention needs block_tables")

    qkv_t = ensure_tensor(qkv)
    kc = ensure_tensor(key_cache)
    vc = ensure_tensor(value_cache)
    enc = np.asarray(ensure_tensor(seq_lens_encoder)._value).reshape(-1)
    dec = np.asarray(ensure_tensor(seq_lens_decoder)._value).reshape(-1)
    this = np.asarray(ensure_tensor(seq_lens_this_time)._value).reshape(-1)
    bt = np.asarray(ensure_tensor(block_tables)._value)
    B = bt.shape[0]
    n_blocks, H, bs, D = kc.shape
    mask_t = ensure_tensor(mask).detach() if mask is not None else None

    def _run(qkv_v, kc_v, vc_v, *maybe_mask):
        total = qkv_v.shape[0]
        q3 = qkv_v.reshape(total, 3, H, D)
        outs = []
        tok = 0
        kc_new, vc_new = kc_v, vc_v
        for b in range(B):
            n_this = int(this[b])
            if n_this == 0:
                continue
            qb = q3[tok:tok + n_this, 0]          # (n, H, D)
            kb = q3[tok:tok + n_this, 1]
            vb = q3[tok:tok + n_this, 2]
            start = 0 if int(enc[b]) else int(dec[b])
            # write new k/v into the paged cache at [start, start+n):
            # ONE batched scatter (per-token .at updates would be O(L)
            # dispatches)
            new_pos = np.arange(start, start + n_this)
            if (new_pos // bs).max() >= bt.shape[1] or \
                    (bt[b, new_pos // bs] < 0).any():
                raise ValueError(
                    f"block_multihead_attention: request {b} needs cache "
                    f"positions up to {int(new_pos.max())} but its "
                    "block_tables row has no allocated block there "
                    "(-1/out of range) — the scatter would silently "
                    "corrupt another request's blocks")
            nblk = jnp.asarray(bt[b, new_pos // bs].astype(np.int32))
            noff = jnp.asarray((new_pos % bs).astype(np.int32))
            kc_new = kc_new.at[nblk, :, noff, :].set(kb)
            vc_new = vc_new.at[nblk, :, noff, :].set(vb)
            # gather the full valid prefix [0, start+n) back out — one
            # fancy-index gather
            L = start + n_this
            all_pos = np.arange(L)
            blks = jnp.asarray(bt[b, all_pos // bs].astype(np.int32))
            offs = jnp.asarray((all_pos % bs).astype(np.int32))
            keys = kc_new[blks, :, offs, :]                    # (L, H, D)
            vals = vc_new[blks, :, offs, :]
            scores = jnp.einsum("nhd,lhd->hnl", qb, keys) \
                / math.sqrt(D)
            # causal within this request: query i may see [0, start+i]
            qpos = start + jnp.arange(n_this)[None, :, None]
            kpos = jnp.arange(L)[None, None, :]
            cm = kpos <= qpos
            scores = jnp.where(cm, scores, -1e9)
            if maybe_mask:
                mv = maybe_mask[0]
                if mv.ndim != 4:
                    raise ValueError(
                        "block_multihead_attention: mask must be "
                        "(B, H|1, max_q, max_kv) additive")
                scores = scores + mv[b, :, :n_this, :L].astype(
                    scores.dtype)
            probs = jax.nn.softmax(scores, axis=-1)
            ob = jnp.einsum("hnl,lhd->nhd", probs, vals)
            outs.append(ob.reshape(n_this, H * D))
            tok += n_this
        out = jnp.concatenate(outs, 0) if outs else \
            jnp.zeros((0, H * D), qkv_v.dtype)
        return out.astype(qkv_v.dtype), kc_new, vc_new

    args = [qkv_t, kc.detach(), vc.detach()]
    if mask_t is not None:
        args.append(mask_t)
    res = call_op(_run, *args)
    out, kc_out, vc_out = res
    return out, qkv_t, kc_out, vc_out


__all__ += ["blha_get_max_len", "block_multihead_attention"]


def softmax_mask_fuse(x, mask, name=None):
    """reference: paddle.incubate.softmax_mask_fuse — softmax(x + mask)
    over the last axis in one pass (paddle/phi/kernels/fusion/gpu/
    fused_softmax_mask_kernel.cu).  TPU-native: XLA fuses the add into
    the softmax's streaming pass, so this is the jnp composition —
    the fusion the CUDA kernel hand-writes is the compiler's default
    here.  x: (B, H, S, S) scores; mask: additive, broadcastable
    (typically (B, 1, S, S))."""
    from ....framework.autograd import call_op
    from ....tensor._helpers import ensure_tensor
    import jax
    x, mask = ensure_tensor(x), ensure_tensor(mask)
    return call_op(
        lambda v, m: jax.nn.softmax(
            v.astype(jnp.float32) + m.astype(jnp.float32),
            axis=-1).astype(v.dtype), x, mask)


def softmax_mask_fuse_upper_triangle(x, name=None):
    """reference: paddle.incubate.softmax_mask_fuse_upper_triangle —
    causal (lower-triangular-visible) masked softmax of (B, H, S, S)
    attention scores without materializing the mask tensor."""
    from ....framework.autograd import call_op
    from ....tensor._helpers import ensure_tensor
    import jax
    x = ensure_tensor(x)

    def _f(v):
        S = v.shape[-1]
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal, v.astype(jnp.float32), -1e30)
        return jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return call_op(_f, x)


__all__ += ["softmax_mask_fuse", "softmax_mask_fuse_upper_triangle"]
