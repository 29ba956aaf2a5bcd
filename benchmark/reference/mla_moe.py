"""Plain reference of the ``mla_moe`` family (``sarvam_mla``: latent
attention, a dropless sigmoid-routed expert layer with a shared expert):
float32 ``jax.numpy`` at matmul precision "highest", no kernel, no cache,
no batching, every held expert applied to every token and masked.

The equations, one row ``ids`` [T] at a time.  Pre-norm residual blocks,
RMSNorm (eps, weight) before attention and before the FFN, a final
RMSNorm, an untied output head.

*Latent attention*, hidden x, nH heads: ``q = W_q x`` in nH x (nope +
rope), an RMSNorm over each head's whole query (one weight), split into
``q_nope`` and ``q_rope``; ``[c_raw ; k_rope_raw] = W_kva x``; ``c =
RMSNorm(c_raw)``; rotary positions on ``q_rope`` (per head) and
``k_rope_raw`` (one for all heads) with the ``deepseek_yarn``
frequencies (the linear ramp between the two correction dimensions
blends ``1/(factor f)`` with ``1/f``; ``mscale == mscale_all_dim`` so the
rotation carries no extra scale); ``[k_nope_h ; v_h] = W_kvb,h c``;
``score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s))
x (nope + rope)^-1/2 x m^2``, ``m = 0.1 ln(factor) + 1``; causal softmax;
``o = W_o concat_h sum_s p_h(t, s) v_h(s)``.  Always this expanded form:
the program's absorbed decode is the same function.

*Expert layer* (layers ``first_k_dense_replace``..): ``s = sigmoid(W_r
h)`` over the router's full width in float32; the chosen are the top-k
of ``s + b`` (the selection bias picks and never weighs); ``g_i =
scaling x s_i / sum_{j chosen} s_j``; ``y = sum_{i chosen and held} g_i
E_i(h) + E_shared(h)``, ``E(h) = W_down (silu(W_gate h) * W_up h)``.  The
layers before it are a SwiGLU FFN at ``intermediate_size``.

Departures from the published model, each also in the configuration's
``assumed``:
- ``use_qk_norm`` is read as the latent's RMSNorm plus the per-head query
  norm above (the config gives the flag, not where it acts);
- one routing group, sigmoid scores, ``norm_topk_prob`` true;
- the rotation pairs dimension i with i + rope/2 (the checkpoints
  interleave the pairs: a fixed permutation of W_q's and W_kva's columns
  that seeded weights do not see);
- THE CUT: the chip holds ``num_experts`` of the router's
  ``router_num_experts`` experts (ids ``first_expert_held``..) and a
  slice of the vocabulary; what the absent experts would add is left
  out, here as in the program, and that partial result goes on to the
  next layer.

The weights arrive as the bfloat16 values the program was given
(``weights_mla_moe.make_stacked``) and are lifted to float32 a layer at
a time (an expert at a time inside the expert layer); attention works a
block of heads at a time: the same mathematics in less memory.

``precision`` is "highest" for the reference itself.  "bf16" rounds
every activation to bfloat16 (sums inside a matmul, a norm or a softmax
stay float32, and so do the router's scores, as the configuration
states): the witness.  "int8" also rounds the operands of every linear
layer, of the experts and of the output head to an int8 grid (per row of
the activations, per output column of the weights): the control that
``correct`` has to refuse.  A serving cell needs no ``train_steps``:
there is none here.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PRECISIONS = ("highest", "bf16", "int8")
HEAD_BLOCK = 4


def _int8_grid(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _act(x, precision):
    """Round an activation to what ``precision`` carries."""
    if precision == "highest":
        return x
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _dot(x, w, precision):
    """x [.., K] @ w [K, N] (w lifted from bfloat16), summed in float32."""
    x, w = _act(x, precision), w.astype(jnp.float32)
    if precision == "int8":
        x, w = _int8_grid(x, -1), _int8_grid(w, 0)
    return _act(jnp.matmul(x, w, precision=HIGHEST), precision)


def _rms_norm(x, weight, eps, precision):
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return _act(x * weight.astype(jnp.float32), precision)


def _swiglu(h, gate_up, down, precision):
    """``W_down (silu(W_gate h) * W_up h)``; the leaf holds ``[W_gate |
    W_up]`` side by side along its output axis."""
    gu = _dot(h, gate_up, precision)
    half = gu.shape[-1] // 2
    a = _act(jax.nn.silu(gu[..., :half]) * gu[..., half:], precision)
    return _dot(a, down, precision)


def yarn_inv_freq(dim, theta, scaling):
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    plain = 1.0 / (theta ** exponent)
    factor = scaling["factor"]
    original = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))
    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return plain / factor * ramp + plain * (1.0 - ramp)


def _rotate(x, inv_freq):
    """x [T, ..., rope] at positions 0..T-1, rotate-half."""
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq
    while angle.ndim < x.ndim:
        angle = angle[:, None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(h, lw, m, precision, scale_fault):
    T = h.shape[0]
    nH, nope, rope, vd, C = (m["num_attention_heads"], m["qk_nope_head_dim"],
                             m["qk_rope_head_dim"], m["v_head_dim"],
                             m["kv_lora_rank"])
    eps = m["rms_norm_eps"]
    inv_freq = yarn_inv_freq(rope, m["rope_theta"], m["rope_scaling"])
    q = _dot(h, lw["attn.q"], precision).reshape(T, nH, nope + rope)
    if m.get("use_qk_norm", True):
        q = _rms_norm(q, lw["attn.q_norm"], eps, "highest")
    q_nope = _act(q[..., :nope], precision)
    q_rope = _act(_rotate(q[..., nope:], inv_freq), precision)
    kva = _dot(h, lw["attn.kv_a"], precision)
    c = _rms_norm(kva[:, :C], lw["attn.kv_norm"], eps, precision)
    k_rope = _act(_rotate(kva[:, C:], inv_freq), precision)
    kv = _dot(c, lw["attn.kv_b"], precision).reshape(T, nH, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + rope) ** -0.5
    if not scale_fault:       # a test's fault: m^2 left out
        mscale = 0.1 * m["rope_scaling"]["mscale_all_dim"] \
            * math.log(m["rope_scaling"]["factor"]) + 1.0
        scale *= mscale * mscale
    causal = jnp.tril(jnp.ones((T, T), bool))

    def heads(args):                      # a block of heads at a time
        qn, qr, kn, vv = args             # [T, hb, .]
        s = (jnp.einsum("qhd,khd->hqk", qn, kn, precision=HIGHEST)
             + jnp.einsum("qhd,kd->hqk", qr, k_rope, precision=HIGHEST))
        p = jax.nn.softmax(jnp.where(causal, s * scale, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", _act(p, precision), vv,
                          precision=HIGHEST)
    hb = HEAD_BLOCK if nH % HEAD_BLOCK == 0 else 1

    def split(a):
        return jnp.moveaxis(a.reshape(T, nH // hb, hb, a.shape[-1]), 1, 0)
    ctx = lax.map(heads, (split(q_nope), split(q_rope), split(k_nope),
                          split(v)))
    ctx = _act(jnp.moveaxis(ctx, 0, 1).reshape(T, nH * vd), precision)
    return _dot(ctx, lw["attn.o"], precision)


def _experts(h, lw, m, precision, bias_fault):
    first, held = m.get("first_expert_held", 0), m["num_experts"]
    k = m["num_experts_per_tok"]
    # the router: float32 whatever the precision carries elsewhere
    s = jax.nn.sigmoid(jnp.matmul(h, lw["moe.router"].astype(jnp.float32),
                                  precision=HIGHEST))
    bias = lw["moe.bias"].astype(jnp.float32)
    _, picks = lax.top_k(s if bias_fault else s + bias, k)
    chosen = jnp.take_along_axis(s, picks, -1)
    gates = m["routed_scaling_factor"] * chosen \
        / chosen.sum(-1, keepdims=True)

    def one(total, expert):
        e, gate_up, down = expert
        weight = jnp.sum(jnp.where(picks == first + e, gates, 0.0), -1)
        y = _swiglu(h, gate_up, down, precision)      # every token
        return total + weight[:, None] * y, None
    routed, _ = lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(held), lw["moe.experts.gate_up"],
        lw["moe.experts.down"]))
    shared = _swiglu(h, lw["moe.shared.gate_up"], lw["moe.shared.down"],
                     precision)
    return _act(routed + shared, precision)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _layer(x, lw, static, precision, scale_fault, bias_fault):
    m = _unfreeze(static)
    eps = m["rms_norm_eps"]
    h = _rms_norm(x, lw["ln1.weight"], eps, precision)
    x = _act(x + _attention(h, lw, m, precision, scale_fault), precision)
    h = _rms_norm(x, lw["ln2.weight"], eps, precision)
    if "moe.router" in lw:
        y = _experts(h, lw, m, precision, bias_fault)
    else:
        y = _swiglu(h, lw["mlp.gate_up"], lw["mlp.down"], precision)
    return _act(x + y, precision)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed(g, ids, static, precision):
    return _act(g["embed"].astype(jnp.float32)[ids], precision)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(g, x, static, precision):
    m = _unfreeze(static)
    x = _rms_norm(x, g["norm.weight"], m["rms_norm_eps"], precision)
    return _dot(x, g["head"], precision)


def _freeze(model):
    return tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in model.items()))


def _unfreeze(static):
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in static}


def row_logits(model, w, ids, precision="highest", fault=None):
    """ids [T] -> logits [T, V], a layer at a time.  ``fault`` (tests):
    "scale" leaves ``m^2`` out of the softmax scale, "bias" picks without
    the selection bias."""
    static = _freeze(model)
    ids = jnp.asarray(ids, jnp.int32)
    x = _embed(w["globals"], ids, static, precision)
    for lw in w["layers"]:
        x = _layer(x, lw, static, precision, fault == "scale",
                   fault == "bias")
    return _head(w["globals"], x, static, precision)


@jax.jit
def _gaps(logits, targets):
    return jnp.max(logits, -1) - jnp.take_along_axis(
        logits, targets[:, None], axis=-1)[:, 0]


def next_token_gaps(model, w, ids, targets):
    """For a row ``ids`` [T] and the token ``targets`` [T] that followed
    each position: how far the reference's logit of that token lies below
    the reference's best logit there (0 where it is the best)."""
    return _gaps(row_logits(model, w, ids),
                 jnp.asarray(targets, jnp.int32))


def best_next_tokens(model, w, ids, precision):
    """The token that ``precision`` puts first after each position."""
    return jnp.argmax(row_logits(model, w, ids, precision), -1)
