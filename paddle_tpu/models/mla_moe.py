"""Latent-attention, sparse-expert decoder (the ``sarvam_mla`` /
DeepSeek-V2 line of architectures).

Pre-norm residual blocks, RMSNorm before attention and before the FFN, a
final RMSNorm and an untied output head.

**Latent attention** (hidden ``x``, ``nH`` heads).  ``q = W_q x`` in
``nH x (nope + rope)``, RMSNorm over each head's whole query (one weight;
``use_qk_norm``), split into ``q_nope`` and ``q_rope``;
``[c_raw ; k_rope_raw] = W_kva x`` in ``latent + rope``;
``c = RMSNorm(c_raw)``; rotary positions (YaRN frequencies,
:func:`yarn_inv_freq`) on ``q_rope`` per head and on ``k_rope_raw``, one
for all heads; ``[k_nope_h ; v_h] = W_kvb,h c``;
``score_h(t, s) = (q_nope_h(t) . k_nope_h(s) + q_rope_h(t) . k_rope(s))
x scale``, ``scale = (nope + rope)^-1/2 x m^2``, ``m = 0.1 ln(factor) + 1``
(``mscale_all_dim``); causal softmax in float32;
``o = W_o concat_h sum_s p_h(t, s) v_h(s)``.

The cache holds, a token a layer, the ``latent + rope`` values
``[c ; k_rope]`` (``kv_cache_spec`` says ``LatentCacheSpec``), in pages
(``inference.kvcache.LatentCacheView``).  Two forms of the same function:

- *expanded* (a prefill that starts at position 0): keys and values by
  head from the prompt's own latent rows, through the flash attention
  dispatch.  The kernels take one width for q, k and v and scale by its
  root, so q/k (``nope + rope`` = 192) and v (128) are zero-padded to the
  next multiple of 128 and q carries the scale's correction; the MXU's
  passes over a contraction of 192 are those of 256, the PV matmul pays
  256 columns for 128 (PERF.md gives the share).
- *absorbed* (decode, and a prefill behind a cached prefix):
  ``q'_h = W_kvb,h^K^T q_nope_h`` (latent wide),
  ``score = q'_h . c(s) + q_rope_h . k_rope(s)``,
  ``out_h = W_kvb,h^V sum_s p_h(s) c(s)`` over the slot's gathered latent
  rows, plain ``jnp`` einsums, queries a block of 128 at a time.

**FFN.**  The first ``first_k_dense_replace`` layers are SwiGLU at
``intermediate_size``; the rest are
``incubate.distributed.models.moe.DroplessMoELayer`` (sigmoid router with
a selection bias, top-k, a shared expert, ``experts_held``).

The rotation pairs dimension ``i`` with ``i + rope/2`` (rotate-half); the
published checkpoints interleave the pairs, a fixed permutation of the
columns of ``W_q`` and ``W_kva`` that random weights do not see.

Parameters are created in ``config.dtype``: a model built in bfloat16
holds one copy of its weights and ``ServingEngine(dtype="bfloat16")``
copies nothing.  Served by ``ServingEngine(kv_mode="paged")``; the dense
engine, ``generate()``, int8 KV, weight quantization and speculative
decoding raise on a latent layer.
"""
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from ..framework.autograd import call_op
from .. import nn
from ..nn import functional as F
from ..nn.initializer import Constant, Normal
from ..incubate.distributed.models.moe.dropless import (DroplessMoELayer,
                                                        swiglu)
from ..observability.tracing import scope as _scope
from .generation import GenerationMixin, LatentCacheSpec

__all__ = ["MLAMoEConfig", "MLAMoEModel", "MLAMoEForCausalLM",
           "mla_moe_tiny", "yarn_inv_freq", "softmax_scale"]

_QUERY_BLOCK = 128        # the absorbed form's block of queries
_LANES = 128


@dataclass
class MLAMoEConfig:
    vocab_size: int = 262144
    hidden_size: int = 4096
    num_hidden_layers: int = 32
    num_attention_heads: int = 64
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    use_qk_norm: bool = True
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    first_k_dense_replace: int = 1
    num_experts: int = 128            # the router's width
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    experts_held: tuple = None        # (first, count); None: all
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=lambda: {
        "type": "deepseek_yarn", "factor": 40, "beta_fast": 32,
        "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 4096})
    dtype: str = None                 # parameters' dtype (None: default)
    init_std: float = 0.02

    @property
    def q_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def held_range(self):
        if self.experts_held is None:
            return range(self.num_experts)
        first, count = self.experts_held
        return range(first, first + count)


def mla_moe_tiny(**kw):
    """A test-sized model of the same code: 3 layers, 4 heads of 24 + 8
    with values of 16, latent 32, 8 experts top-2."""
    base = dict(vocab_size=512, hidden_size=64, num_hidden_layers=3,
                num_attention_heads=4, qk_nope_head_dim=24,
                qk_rope_head_dim=8, v_head_dim=16, kv_lora_rank=32,
                intermediate_size=128, moe_intermediate_size=32,
                num_experts=8, num_experts_per_tok=2,
                max_position_embeddings=512,
                rope_scaling={"type": "deepseek_yarn", "factor": 4,
                              "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                              "mscale_all_dim": 1,
                              "original_max_position_embeddings": 64})
    base.update(kw)
    return MLAMoEConfig(**base)


# -- rotary positions ---------------------------------------------------------

def yarn_inv_freq(dim, theta, scaling):
    """The ``deepseek_yarn`` inverse frequencies (dim/2,), float32: below
    the ``beta_fast`` correction dimension the plain ``1/f``, above the
    ``beta_slow`` one the interpolated ``1/(factor f)``, a linear ramp
    between.  ``scaling`` None: plain rotary frequencies."""
    exponent = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    plain = 1.0 / (theta ** exponent)
    if not scaling:
        return plain
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


def softmax_scale(q_head_dim, scaling):
    """``q_head_dim^-1/2 x m^2``, ``m`` the YaRN attention factor of
    ``mscale_all_dim`` (1 where there is no scaling)."""
    scale = q_head_dim ** -0.5
    if scaling and scaling.get("mscale_all_dim"):
        m = 0.1 * scaling["mscale_all_dim"] * math.log(scaling["factor"]) + 1
        scale *= m * m
    return scale


def _rotate(x, positions, inv_freq):
    """Rotate-half rotary of ``x`` (B, S, ..., rope) at ``positions``
    (B, S), in float32."""
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    while angle.ndim < x.ndim:
        angle = angle[..., None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _rms(x, weight, eps):
    xf = x.astype(jnp.float32)
    xf = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return xf * weight.astype(jnp.float32)


def _positions(pos, B, S):
    """(B, S) absolute positions of this call's tokens: ``pos`` is a
    scalar (every row starts there) or a per-row (B,) vector."""
    pos = pos.astype(jnp.int32)
    start = pos[:, None] if pos.ndim else pos
    return jnp.broadcast_to(start + jnp.arange(S), (B, S))


# -- latent attention ---------------------------------------------------------

def project(x, w_q, q_norm, w_kva, kv_norm, positions, cfg):
    """Queries and this call's cache rows.  Returns ``q_nope``
    (B, S, nH, nope), ``q_rope`` (B, S, nH, rope), both float32, and
    ``rows`` (B, S, latent + rope) in x's dtype: ``[c ; k_rope]``."""
    B, S, _ = x.shape
    nH, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    inv_freq = yarn_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta,
                             cfg.rope_scaling)
    with _scope("attention.qkv"):
        q = jnp.dot(x, w_q).reshape(B, S, nH, cfg.q_head_dim)
        q = _rms(q, q_norm, cfg.rms_norm_eps) if cfg.use_qk_norm \
            else q.astype(jnp.float32)
        q_nope = q[..., :nope]
        q_rope = _rotate(q[..., nope:], positions, inv_freq)
    with _scope("attention.latent_kv"):
        kva = jnp.dot(x, w_kva)
        c = _rms(kva[..., :cfg.kv_lora_rank], kv_norm, cfg.rms_norm_eps)
        k_rope = _rotate(kva[..., cfg.kv_lora_rank:].astype(jnp.float32),
                         positions, inv_freq)
        rows = jnp.concatenate([c, k_rope], -1).astype(x.dtype)
    return q_nope, q_rope, rows


def expanded_attention(q_nope, q_rope, rows, w_kvb, cfg, dtype):
    """Causal attention of a whole prompt over its own rows, keys and
    values expanded by head: (B, S, nH x v) in ``dtype``."""
    B, S, nH, nope = q_nope.shape
    rope, v_dim = cfg.qk_rope_head_dim, cfg.v_head_dim
    width = -(-max(nope + rope, v_dim) // _LANES) * _LANES
    scale = softmax_scale(nope + rope, cfg.rope_scaling)
    with _scope("attention.latent_kv"):
        c, k_rope = rows[..., :cfg.kv_lora_rank], rows[..., cfg.kv_lora_rank:]
        kv = jnp.dot(c, w_kvb).reshape(B, S, nH, nope + v_dim)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope[:, :, None, :], (B, S, nH, rope)),
             jnp.zeros((B, S, nH, width - nope - rope), dtype)], -1)
        v = jnp.pad(kv[..., nope:],
                    ((0, 0), (0, 0), (0, 0), (0, width - v_dim)))
        # the kernels scale by width^-1/2: q carries the correction
        q = jnp.concatenate(
            [q_nope, q_rope,
             jnp.zeros((B, S, nH, width - nope - rope), jnp.float32)], -1)
        q = (q * (scale * math.sqrt(width))).astype(dtype)
    with _scope("attention.core"):
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=False)._value
    return out[..., :v_dim].reshape(B, S, nH * v_dim)


def absorbed_attention(q_nope, q_rope, cached, positions, w_kvb, cfg, dtype):
    """Attention of the queries at ``positions`` (B, S) over the cache
    rows ``cached`` (B, MAX, latent + rope), keys at or before each
    query's position, with ``W_kvb`` absorbed into the query and the
    output: (B, S, nH x v) in ``dtype``."""
    B, S, nH, nope = q_nope.shape
    latent, v_dim = cfg.kv_lora_rank, cfg.v_head_dim
    scale = softmax_scale(nope + cfg.qk_rope_head_dim, cfg.rope_scaling)
    w = w_kvb.reshape(latent, nH, nope + v_dim)
    w_k, w_v = w[..., :nope], w[..., nope:]
    c, k_rope = cached[..., :latent], cached[..., latent:]
    key_pos = jnp.arange(cached.shape[1])

    def block(args):
        qn, qr, at = args                       # (B, s, nH, .), (B, s)
        with _scope("attention.absorb"):
            q_lat = jnp.einsum("bshd,chd->bshc", qn.astype(dtype), w_k)
        with _scope("attention.core"):
            score = jnp.einsum("bshc,btc->bhst", q_lat, c,
                               preferred_element_type=jnp.float32) \
                + jnp.einsum("bshr,btr->bhst", qr.astype(dtype), k_rope,
                             preferred_element_type=jnp.float32)
            seen = key_pos[None, None, None, :] <= at[:, None, :, None]
            p = jax.nn.softmax(jnp.where(seen, score * scale, -1e30), -1)
            ctx = jnp.einsum("bhst,btc->bshc", p.astype(dtype), c)
        with _scope("attention.absorb"):
            return jnp.einsum("bshc,chd->bshd", ctx, w_v)

    if S <= _QUERY_BLOCK:
        out = block((q_nope, q_rope, positions))
    else:
        n = S // _QUERY_BLOCK      # buckets are powers of two

        def split(a):
            return jnp.moveaxis(
                a.reshape((B, n, _QUERY_BLOCK) + a.shape[2:]), 1, 0)
        out = jax.lax.map(block, (split(q_nope), split(q_rope),
                                  split(positions)))
        out = jnp.moveaxis(out, 0, 1).reshape(B, S, nH, v_dim)
    return out.reshape(B, S, nH * v_dim)


def _cached_attention(x, w_q, q_norm, w_kva, kv_norm, w_kvb, w_o, pages,
                      table, pos, *, cfg):
    """One cached call of the layer: write this call's rows into the
    slot's pages, attend, project out.  Returns (out, pages)."""
    from ..inference import kvcache as _kvc
    B, S, _ = x.shape
    positions = _positions(pos, B, S)
    q_nope, q_rope, rows = project(x, w_q, q_norm, w_kva, kv_norm,
                                   positions, cfg)
    pages = _kvc.scatter_latent(pages, rows, table, pos)

    def over_cache():
        cached = _kvc.gather_latent(pages, table)
        return absorbed_attention(q_nope, q_rope, cached, positions, w_kvb,
                                  cfg, x.dtype)
    if S > 1 and not pos.ndim:
        # a prefill: from position 0 the prompt's own rows are all it
        # sees; behind a cached prefix it reads the slot's pages
        out = jax.lax.cond(
            pos == 0,
            lambda: expanded_attention(q_nope, q_rope, rows, w_kvb, cfg,
                                       x.dtype),
            over_cache)
    else:
        out = over_cache()
    with _scope("attention.out"):
        return jnp.dot(out, w_o), pages


def _full_attention(x, w_q, q_norm, w_kva, kv_norm, w_kvb, w_o, *, cfg):
    """The layer with no cache (training-style forward): expanded."""
    B, S, _ = x.shape
    positions = _positions(jnp.zeros((), jnp.int32), B, S)
    q_nope, q_rope, rows = project(x, w_q, q_norm, w_kva, kv_norm,
                                   positions, cfg)
    out = expanded_attention(q_nope, q_rope, rows, w_kvb, cfg, x.dtype)
    with _scope("attention.out"):
        return jnp.dot(out, w_o)


class LatentAttention(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = c = config
        H, nH = c.hidden_size, c.num_attention_heads
        init = Normal(0.0, c.init_std)

        def param(shape, initializer=init):
            return self.create_parameter(shape, dtype=c.dtype,
                                         default_initializer=initializer)
        self.q_proj = param((H, nH * c.q_head_dim))
        self.q_norm = param((c.q_head_dim,), Constant(1.0))
        self.kv_a_proj = param((H, c.kv_lora_rank + c.qk_rope_head_dim))
        self.kv_norm = param((c.kv_lora_rank,), Constant(1.0))
        self.kv_b_proj = param(
            (c.kv_lora_rank, nH * (c.qk_nope_head_dim + c.v_head_dim)))
        self.o_proj = param((nH * c.v_head_dim, H))

    def _weights(self):
        return (self.q_proj, self.q_norm, self.kv_a_proj, self.kv_norm,
                self.kv_b_proj, self.o_proj)

    def forward(self, x, cache=None, pos=None):
        if pos is None:
            return call_op(_full_attention, x, *self._weights(),
                           cfg=self.config)
        if not hasattr(cache, "pages"):
            raise ValueError(
                "a latent attention layer keeps a paged latent cache "
                "(inference.kvcache.LatentCacheView): serve it through "
                "ServingEngine(kv_mode='paged')")
        out, pages = call_op(_cached_attention, x, *self._weights(),
                             cache.pages, cache.table, pos,
                             cfg=self.config)
        return out, cache._replace(pages=pages)


# -- blocks -------------------------------------------------------------------

class SwiGLU(nn.Layer):
    def __init__(self, config):
        super().__init__()
        H, I = config.hidden_size, config.intermediate_size
        init = Normal(0.0, config.init_std)
        self.gate_up = self.create_parameter(
            (H, 2 * I), dtype=config.dtype, default_initializer=init)
        self.down = self.create_parameter(
            (I, H), dtype=config.dtype, default_initializer=init)

    def forward(self, x):
        return call_op(swiglu, x, self.gate_up, self.down)


class _RMSNorm(nn.Layer):
    """RMSNorm whose weight is created in the configuration's dtype."""

    def __init__(self, config):
        super().__init__()
        self.eps = config.rms_norm_eps
        self.weight = self.create_parameter(
            (config.hidden_size,), dtype=config.dtype,
            default_initializer=Constant(1.0))

    def forward(self, x):
        return call_op(lambda v, w: _rms(v, w, self.eps).astype(v.dtype),
                       x, self.weight)


class MLAMoEDecoderLayer(nn.Layer):
    def __init__(self, config, index):
        super().__init__()
        c = config
        self.input_layernorm = _RMSNorm(c)
        self.self_attn = LatentAttention(c)
        self.post_attention_layernorm = _RMSNorm(c)
        self.sparse = index >= c.first_k_dense_replace
        if self.sparse:
            self.mlp = DroplessMoELayer(
                c.hidden_size, c.moe_intermediate_size, c.num_experts,
                c.num_experts_per_tok, experts_held=c.held_range,
                d_shared=c.num_shared_experts * c.moe_intermediate_size,
                scaling=c.routed_scaling_factor, dtype=c.dtype,
                init_std=c.init_std)
        else:
            self.mlp = SwiGLU(c)

    def forward(self, x, cache=None, pos=None):
        with _scope("norm"):
            h = self.input_layernorm(x)
        if pos is None:
            a = self.self_attn(h)
        else:
            a, cache = self.self_attn(h, cache=cache, pos=pos)
        with _scope("attention.out"):
            x = x + a
        with _scope("norm"):
            h = self.post_attention_layernorm(x)
        if self.sparse:
            x = x + self.mlp(h)        # the layer sets its moe.* scopes
        else:
            with _scope("mlp"):
                x = x + self.mlp(h)
        return x if pos is None else (x, cache)


class MLAMoEModel(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embed_tokens = self.create_parameter(
            (config.vocab_size, config.hidden_size), dtype=config.dtype,
            default_initializer=Normal(0.0, config.init_std))
        self.layers = nn.LayerList(
            [MLAMoEDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = _RMSNorm(config)

    def forward(self, input_ids, caches=None, pos=None):
        with _scope("embed"):
            x = call_op(lambda ids, table: table[ids], input_ids,
                        self.embed_tokens)
        if pos is None:
            for blk in self.layers:
                x = blk(x)
            with _scope("norm"):
                return self.norm(x)
        new = []
        for blk, cache in zip(self.layers, caches):
            x, cache = blk(x, cache=cache, pos=pos)
            new.append(cache)
        with _scope("norm"):
            return self.norm(x), new


class MLAMoEForCausalLM(nn.Layer, GenerationMixin):
    # ``forward(..., last=)`` applies the head to that one position: a
    # prefill over a 4,096 bucket never forms 4,096 rows of logits
    forward_takes_last = True
    device_counters = DroplessMoELayer.device_counters

    def __init__(self, config):
        super().__init__()
        self.model = MLAMoEModel(config)
        self.lm_head = self.create_parameter(
            (config.hidden_size, config.vocab_size), dtype=config.dtype,
            default_initializer=Normal(0.0, config.init_std))

    def kv_cache_spec(self):
        c = self.model.config
        return [LatentCacheSpec(c.kv_lora_rank + c.qk_rope_head_dim)] * \
            c.num_hidden_layers

    def _head(self, x):
        with _scope("lm_head"):
            return call_op(
                lambda h, w: jnp.dot(h, w,
                                     preferred_element_type=jnp.float32),
                x, self.lm_head)

    def forward(self, input_ids, caches=None, pos=None, attn_mask=None,
                last=None):
        if attn_mask is not None:
            raise ValueError("MLAMoEForCausalLM takes no attn_mask "
                             "(right-padded prompts need none)")
        if pos is None:
            return self._head(self.model(input_ids))
        x, caches = self.model(input_ids, caches=caches, pos=pos)
        if last is not None:
            x = call_op(lambda h, at: jax.lax.dynamic_slice_in_dim(
                h, at.astype(jnp.int32), 1, axis=1), x, last)
        return self._head(x), caches
