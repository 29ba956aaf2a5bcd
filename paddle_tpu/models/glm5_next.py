"""Hybrid linear / sparse-latent decoder with a hyper-connected residual
and sparse experts (the ``glm5_next_text`` line of architectures).

Every block has two sublayers, attention and an FFN, each read from and
written to ``hc_mult`` residual streams through its own hyper-connection.

**Hyper-connections (mHC).**  The streams ``X`` (n x hidden) of a token
are flattened and RMS-normed (no weight): ``m = alpha * (RMSNorm(vec X)
phi) + b``, split into ``n``, ``n`` and ``n x n``; ``H_pre = sigmoid(.)``,
``H_post = 2 sigmoid(.)``, ``H_res = SinkhornKnopp(exp(.))`` over
``hc_sinkhorn_iters`` rounds of row then column normalisation (the first
row step a softmax; each step adds ``hc_eps``).  The sublayer ``F``
(pre-norm included) sees ``H_pre X`` and ``X' = H_res X + H_post^T F(H_pre
X)``.  The streams
start as copies of the embedding and are summed before the final norm.

**Linear attention (KDA, gated delta rule with a decay a channel).**
``q, k, v``: ``W x`` (heads x head_dim each), a causal depthwise
convolution of ``short_conv_kernel_size``, SiLU; q and k L2-normed per
head, q scaled by ``head_dim^-1/2``.  ``beta = sigmoid(W_b x)`` a head;
``log alpha = gate_lower_bound * sigmoid(exp(A_log) * (W_fb W_fa x +
dt_bias))`` a channel, so ``log alpha`` lies in (``gate_lower_bound``, 0).
State ``S`` (key_dim x value_dim a head, float32)::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

then an RMSNorm per head gated by ``sigmoid(W_gb W_ga x)`` and
``W_o``.  A prefill runs the chunked form (:func:`kda_chunked`: within a
chunk of ``KDA_CHUNK`` tokens the WY form solves the delta rule's
triangular system, across chunks the state is carried; the decay bound
keeps ``exp`` of a chunk's cumulated log-decay inside float32); a decode
step runs the recurrence once (:func:`kda_step`).  The layer's cache is
its state a slot (``StateCacheSpec``): the matrices and the short
convolutions' last inputs, carried by the engine as device state.

**Sparse latent attention (DSA over MLA, no rotary part).**
``q = W_qb RMSNorm(W_qa x)`` (heads of ``qk_head_dim``), latent ``c =
RMSNorm(W_kva x)``, ``k_h = W_kb,h c``, ``v_h = W_vb,h c``; score scale
``qk_head_dim^-1/2``.  The indexer: ``q^I = W_Iq RMSNorm(W_qa x)``
(``index_n_heads`` of ``index_head_dim``), ``k^I = LayerNorm(W_Ik x)``,
both rotated on their first ``index_rope_dim`` dims with interleaved
pairs; ``w = W_Iw x / sqrt(index_n_heads index_head_dim)``; keys are
mean-pooled over blocks of ``index_kpool`` positions and a query scores a
block ``I(t, b) = sum_j w_j ReLU(q^I_j . kbar_b)``.  The query attends the
keys of the ``index_topk / index_kpool`` best blocks before its own, and
its own block (the tail, always picked) up to itself: every key while
``index_topk`` or fewer are live.  The cache holds a token's latent and
its index key side by side (``LatentCacheSpec(kv_lora_rank +
index_head_dim)``); attention is the absorbed form over the picked rows
(``q'_h = W_kb,h^T q_h``, ``out_h = W_vb,h sum_s p_h(s) c(s)``).

**FFN.**  A ``dense`` layer is SwiGLU at ``intermediate_size``; a
``sparse`` one is ``DroplessMoELayer`` (sigmoid router over
``n_routed_experts`` with a selection bias, top-k normalised and scaled,
one shared expert, ``experts_held``, the ``swiglu_limit`` clamp).

Served by ``ServingEngine(kv_mode="paged")``: a prompt longer than
``prefill_piece`` is prefilled in pieces that carry the state; the
multi-token prediction layer and the vision tower are not part of this
module.  Device counters: the expert layer's, ``dsa_keys_attended`` /
``dsa_keys_live`` (keys a real query attended / could have) and
``prefill_pieces``.
"""
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular

from ..framework.autograd import call_op
from .. import nn
from ..nn.initializer import Constant, Normal
from ..incubate.distributed.models.moe.dropless import DroplessMoELayer
from ..observability import devcounters as _devc
from ..observability.tracing import scope as _scope
from .generation import GenerationMixin, LatentCacheSpec, StateCacheSpec
from .mla_moe import SwiGLU, _RMSNorm, _positions, _rms

__all__ = ["GLM5NextConfig", "GLM5NextModel", "GLM5NextForCausalLM",
           "glm5_next_tiny", "kda_chunked", "kda_step", "sinkhorn",
           "LINEAR", "SPARSE"]

LINEAR, SPARSE = "linear_attention", "deepseek_sparse_attention"
DSA_COUNTERS = ("dsa_keys_attended", "dsa_keys_live", "prefill_pieces")
_QUERY_BLOCK = 128          # the sparse attention's block of queries
# the chunked delta rule's chunk: e^{-G} of a chunk's cumulated log-decay
# (at most 16 x 5 = 80) stays inside float32
KDA_CHUNK = 16
_HIGHEST = jax.lax.Precision.HIGHEST


def _published_layer_types():
    return tuple(SPARSE if i % 4 == 3 else LINEAR for i in range(45))


@dataclass
class GLM5NextConfig:
    vocab_size: int = 154880
    hidden_size: int = 4096
    # one entry a layer, as run
    layer_types: tuple = field(default_factory=_published_layer_types)
    mlp_layer_types: tuple = field(
        default_factory=lambda: ("dense",) * 3 + ("sparse",) * 42)
    # linear attention
    linear_num_heads: int = 64
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    gate_lower_bound: float = -5.0
    linear_gate_rank: int = 128
    # sparse latent attention
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    qk_head_dim: int = 256
    v_head_dim: int = 256
    kv_lora_rank: int = 512
    index_n_heads: int = 32
    index_head_dim: int = 128
    index_topk: int = 2048
    index_kpool: int = 4
    index_rope_dim: int = 64
    index_rope_theta: float = 10000.0
    # hyper-connections
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    # FFN
    intermediate_size: int = 12288
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 288         # the router's width
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    swiglu_limit: float = 10.0
    experts_held: tuple = None          # (first, count); None: all
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    prefill_piece: int = 4096           # the longest prefill program
    dtype: str = None
    init_std: float = 0.02

    @property
    def num_hidden_layers(self):
        return len(self.layer_types)

    @property
    def held_range(self):
        if self.experts_held is None:
            return range(self.n_routed_experts)
        first, count = self.experts_held
        return range(first, first + count)


def glm5_next_tiny(**kw):
    """A test-sized model of the same code: a dense and three expert
    layers (linear, linear, sparse, linear), 4 streams, linear heads 4 x
    16, sparse heads 4 x 16 over a latent of 24, an indexer that picks 4
    blocks of 4 keys, 16 experts top-4."""
    base = dict(vocab_size=512, hidden_size=64,
                layer_types=(LINEAR, LINEAR, SPARSE, LINEAR),
                mlp_layer_types=("dense", "sparse", "sparse", "sparse"),
                linear_num_heads=4, linear_head_dim=16, linear_gate_rank=8,
                num_attention_heads=4, q_lora_rank=32,
                qk_head_dim=16, v_head_dim=16, kv_lora_rank=24,
                index_n_heads=2, index_head_dim=16, index_topk=16,
                index_rope_dim=8, intermediate_size=128,
                moe_intermediate_size=32, n_routed_experts=16,
                num_experts_per_tok=4, max_position_embeddings=1024,
                prefill_piece=32)
    base.update(kw)
    return GLM5NextConfig(**base)


def _f32(x):
    return x.astype(jnp.float32)


# -- hyper-connections --------------------------------------------------------

def sinkhorn(logits, iters, eps):
    """Sinkhorn-Knopp of ``exp(logits)`` over the last two axes: a row
    softmax plus ``eps`` and a column normalisation, then ``iters - 1``
    rounds of row then column normalisation, each sum plus ``eps``."""
    m = jax.nn.softmax(logits, -1) + eps
    m = m / (m.sum(-2, keepdims=True) + eps)
    for _ in range(iters - 1):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def hc_maps(X, phi, bias, alpha, *, n, iters, eps, norm_eps):
    """(pre (B,S,n), post (B,S,n), res (B,S,n,n)) of streams ``X``
    (B, S, n, H), float32."""
    B, S = X.shape[:2]
    flat = _f32(X).reshape(B, S, -1)
    flat = flat * jax.lax.rsqrt(jnp.mean(flat * flat, -1, keepdims=True)
                                + norm_eps)
    m = jnp.dot(flat.astype(X.dtype), phi,
                preferred_element_type=jnp.float32)
    a = _f32(alpha)
    b = _f32(bias)
    pre = jax.nn.sigmoid(a[0] * m[..., :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * m[..., n:2 * n] + b[n:2 * n])
    res = (a[2] * m[..., 2 * n:] + b[2 * n:]).reshape(B, S, n, n)
    return pre, post, sinkhorn(res, iters, eps)


def hc_read(X, pre):
    """``H_pre X``: the sublayer's input (B, S, H) in X's dtype."""
    return jnp.einsum("bsn,bsnh->bsh", pre, _f32(X)).astype(X.dtype)


def hc_write(X, res, post, y):
    """``H_res X + H_post^T y`` in X's dtype."""
    out = jnp.einsum("bsnm,bsmh->bsnh", res, _f32(X)) \
        + post[..., None] * _f32(y)[:, :, None, :]
    return out.astype(X.dtype)


class HyperConnection(nn.Layer):
    def __init__(self, config):
        super().__init__()
        c, n = config, config.hc_mult
        self.config = c
        self.phi = self.create_parameter(
            (n * c.hidden_size, 2 * n + n * n), dtype=c.dtype,
            default_initializer=Normal(0.0, c.init_std))
        self.bias = self.create_parameter(
            (2 * n + n * n,), dtype=c.dtype, default_initializer=Constant(0.0))
        self.alpha = self.create_parameter(
            (3,), dtype=c.dtype, default_initializer=Constant(1.0))

    def maps(self, X):
        c = self.config
        with _scope("residual.hc"):
            return call_op(hc_maps, X, self.phi, self.bias, self.alpha,
                           n=c.hc_mult, iters=c.hc_sinkhorn_iters,
                           eps=c.hc_eps, norm_eps=c.rms_norm_eps)


# -- linear attention (KDA) ----------------------------------------------------

def _l2norm(x, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def kda_step(q, k, v, g, beta, S):
    """One token of the recurrence for each row: ``q, k`` (B, H, Dk),
    ``v`` (B, H, Dv), ``g`` = log alpha (B, H, Dk), ``beta`` (B, H), ``S``
    (B, H, Dk, Dv); returns (o (B, H, Dv), S)."""
    S = S * jnp.exp(g)[..., None]
    u = v - jnp.einsum("bhkv,bhk->bhv", S, k, precision=_HIGHEST)
    S = S + (k * beta[..., None])[..., None] * u[..., None, :]
    return jnp.einsum("bhkv,bhk->bhv", S, q, precision=_HIGHEST), S


def kda_chunked(q, k, v, g, beta, S, chunk):
    """The recurrence over ``T`` tokens of one row in chunks: ``q, k``
    (T, H, Dk), ``v`` (T, H, Dv), ``g`` (T, H, Dk), ``beta`` (T, H), ``S``
    (H, Dk, Dv); returns (o (T, H, Dv), S), all float32.

    Within a chunk, with ``G`` the cumulated log-decay, ``kt = k e^G``,
    ``kh = k e^-G``, ``qt = q e^G``, the pseudo-values ``u_t = v_t - kt_t S0
    - sum_{i<t} beta_i (kt_t . kh_i) u_i`` solve one unit lower-triangular
    system (``u = ubar - w S0``); ``o_t = qt_t S0 + sum_{i<=t} beta_i (qt_t .
    kh_i) u_i`` and ``S = e^{G_C} S0 + sum_i beta_i e^{G_C - G_i} k_i u_i^T``."""
    T, H, Dk = k.shape
    pad = -T % chunk
    if pad:
        q, k, v, g = (jnp.pad(a, ((0, pad), (0, 0), (0, 0)))
                      for a in (q, k, v, g))
        beta = jnp.pad(beta, ((0, pad), (0, 0)))
    n = (T + pad) // chunk

    def blocks(a):                        # (T, H, D) -> (n, H, C, D)
        return a.reshape(n, chunk, H, -1).transpose(0, 2, 1, 3)
    q, k, v, g = map(blocks, (q, k, v, g))
    beta = beta.reshape(n, chunk, H).transpose(0, 2, 1)     # (n, H, C)
    G = jnp.cumsum(g, axis=2)
    kt, kh, qt = k * jnp.exp(G), k * jnp.exp(-G), q * jnp.exp(G)

    def mm(spec, a, b):
        return jnp.einsum(spec, a, b, precision=_HIGHEST)
    below = jnp.tril(jnp.ones((chunk, chunk), bool), -1)
    upto = jnp.tril(jnp.ones((chunk, chunk), bool))
    A = jnp.where(below, mm("nhid,nhjd->nhij", kt, kh) * beta[..., None, :],
                  0.0)
    L = jnp.eye(chunk, dtype=jnp.float32) + A
    ubar = solve_triangular(L, v, lower=True, unit_diagonal=True)
    w = solve_triangular(L, kt, lower=True, unit_diagonal=True)
    aqk = jnp.where(upto, mm("nhid,nhjd->nhij", qt, kh) * beta[..., None, :],
                    0.0)
    decay = jnp.exp(G[:, :, -1])                            # (n, H, Dk)
    kd = k * jnp.exp(G[:, :, -1:] - G) * beta[..., None]

    def step(S, c):
        ub, w_, qt_, aqk_, decay_, kd_ = c
        u = ub - mm("hcd,hde->hce", w_, S)
        o = mm("hcd,hde->hce", qt_, S) + mm("hcj,hje->hce", aqk_, u)
        return decay_[..., None] * S + mm("hcd,hce->hde", kd_, u), o
    S, o = jax.lax.scan(step, S, (ubar, w, qt, aqk, decay, kd))
    return o.transpose(0, 2, 1, 3).reshape(n * chunk, H, -1)[:T], S


def _short_conv(x, tail, weight, length):
    """Causal depthwise convolution of this call's inputs ``x`` (B, S, C)
    behind the previous ``tail`` (B, K-1, C), then SiLU, float32; and the
    new tail: the last K-1 inputs up to ``length`` real tokens."""
    K, S = weight.shape[0], x.shape[1]
    full = jnp.concatenate([tail.astype(x.dtype), x], 1)
    w = _f32(weight)
    y = sum(_f32(full[:, j:j + S]) * w[j] for j in range(K))
    new_tail = jax.vmap(lambda r: jax.lax.dynamic_slice_in_dim(
        r, length, K - 1, axis=0))(full)
    return jax.nn.silu(y), new_tail


def _kda_cached(x, w_qkv, w_conv, w_gate_a, w_f_b, w_g_b, a_log, dt_bias,
                o_norm, w_o, state, conv, *rest, cfg):
    """One cached call of the layer.  A prefill (``rest`` = slot, length,
    pos; one row) reads the slot's state (zeros where the call starts at
    position 0), runs the chunked form over its real tokens and writes
    the state back; a decode step (``rest`` = live; one token a row) runs
    the recurrence and keeps the state of rows that are not live.
    Returns (out, state, conv)."""
    B, S, _ = x.shape
    H, D, R = cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_gate_rank
    prefill = len(rest) == 3
    if prefill:
        slot, length, pos = rest
        fresh = pos == 0
        st = jnp.where(fresh, 0.0, jax.lax.dynamic_index_in_dim(
            state, slot, 0, keepdims=False))
        tail = jnp.where(fresh, jnp.zeros((), conv.dtype),
                         jax.lax.dynamic_slice_in_dim(conv, slot, 1, 0))
    else:
        (live,), length = rest, 1
        tail = conv
    with _scope("attention.qkv"):
        qkv, new_tail = _short_conv(jnp.dot(x, w_qkv), tail, w_conv, length)
        q, k, v = (a.reshape(B, S, H, D) for a in jnp.split(qkv, 3, -1))
        q = _l2norm(q) * D ** -0.5
        k = _l2norm(k)
    with _scope("attention.kda"):
        ga = jnp.dot(x, w_gate_a, preferred_element_type=jnp.float32)
        f = jnp.dot(ga[..., :R].astype(x.dtype), w_f_b,
                    preferred_element_type=jnp.float32)
        decay = jnp.exp(_f32(a_log))[:, None]                   # (H, 1)
        g = cfg.gate_lower_bound * jax.nn.sigmoid(
            decay * (f + _f32(dt_bias)).reshape(B, S, H, D))
        beta = jax.nn.sigmoid(ga[..., 2 * R:])                  # (B, S, H)
        gate = jax.nn.sigmoid(jnp.dot(ga[..., R:2 * R].astype(x.dtype),
                                      w_g_b,
                                      preferred_element_type=jnp.float32))
        if prefill:
            # padding past the real tokens neither decays nor writes
            real = (jnp.arange(S) < length)[None, :]
            beta = jnp.where(real[..., None], beta, 0.0)
            g = jnp.where(real[..., None, None], g, 0.0)
            o, st = kda_chunked(q[0], k[0], v[0], g[0], beta[0], st,
                                KDA_CHUNK)
            o = o[None]
            state = jax.lax.dynamic_update_index_in_dim(state, st, slot, 0)
            conv = jax.lax.dynamic_update_slice_in_dim(
                conv, new_tail.astype(conv.dtype), slot, 0)
        else:
            o, st = kda_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                             beta[:, 0], state)
            o = o[:, None]
            state = jnp.where(live[:, None, None, None], st, state)
            conv = jnp.where(live[:, None, None],
                             new_tail.astype(conv.dtype), conv)
        o = _rms(o, o_norm, cfg.rms_norm_eps).reshape(B, S, H * D) * gate
    with _scope("attention.out"):
        return jnp.dot(o.astype(x.dtype), w_o), state, conv


class KimiDeltaAttention(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = c = config
        H, C = c.hidden_size, c.linear_num_heads * c.linear_head_dim
        R, init = c.linear_gate_rank, Normal(0.0, c.init_std)

        def param(shape, initializer=init):
            return self.create_parameter(shape, dtype=c.dtype,
                                         default_initializer=initializer)
        self.qkv_proj = param((H, 3 * C))
        self.conv = param((c.short_conv_kernel_size, 3 * C))
        # [W_fa | W_ga | W_b] side by side: one product with x
        self.gate_a = param((H, 2 * R + c.linear_num_heads))
        self.f_b = param((R, C))
        self.g_b = param((R, C))
        self.a_log = param((c.linear_num_heads,), Constant(0.0))
        self.dt_bias = param((C,), Constant(0.0))
        self.o_norm = param((c.linear_head_dim,), Constant(1.0))
        self.o_proj = param((C, H))

    def cache_spec(self):
        c = self.config
        return StateCacheSpec(c.linear_num_heads, c.linear_head_dim,
                              c.linear_head_dim,
                              3 * c.linear_num_heads * c.linear_head_dim,
                              c.short_conv_kernel_size - 1)

    def forward(self, x, cache, pos):
        if not hasattr(cache, "state"):
            raise ValueError(
                "a linear attention layer keeps a recurrent state a slot "
                "(inference.kvcache.StateCacheView): serve it through "
                "ServingEngine(kv_mode='paged')")
        weights = (self.qkv_proj, self.conv, self.gate_a, self.f_b,
                   self.g_b, self.a_log, self.dt_bias, self.o_norm,
                   self.o_proj)
        rest = (cache.live,) if cache.slot is None \
            else (cache.slot, cache.length, pos)
        out, state, conv = call_op(_kda_cached, x, *weights, cache.state,
                                   cache.conv, *rest, cfg=self.config)
        return out, cache._replace(state=state, conv=conv)


# -- sparse latent attention (DSA) --------------------------------------------

def _rope_interleaved(x, positions, dim, theta):
    """Rotate the first ``dim`` of ``x``'s last axis at ``positions``
    (B, S), pairs (2i, 2i + 1); ``x`` is (B, S, ..., D) float32."""
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = positions[..., None].astype(jnp.float32) * inv
    while angle.ndim < x.ndim:
        angle = angle[..., None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., 0:dim:2], x[..., 1:dim:2]
    rot = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot.reshape(x.shape[:-1] + (dim,)),
                            x[..., dim:]], -1)


def _layer_norm(x, weight, bias, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * _f32(weight) + _f32(bias)


def sparse_attend(q_lat, q_idx, w_idx, positions, cached, cfg):
    """Each query's attention over the keys its indexer picks.
    ``q_lat`` (B, S, nH, C) the absorbed queries, ``q_idx`` (B, S, nI, dI)
    and ``w_idx`` (B, S, nI) the indexer's, ``positions`` (B, S),
    ``cached`` (B, MAX, C + dI) the slot's rows.  Returns the context
    (B, S, nH, C) float32 and the keys each query attended (B, S)."""
    B, S, nH, C = q_lat.shape
    kp = cfg.index_kpool
    nb = cached.shape[1] // kp
    pick_n = min(cfg.index_topk // kp, nb)
    latent = cached[..., :C].reshape(B, nb, kp, C)
    with _scope("attention.indexer"):
        pooled = _f32(cached[..., C:]).reshape(B, nb, kp, -1).mean(2)
    scale = cfg.qk_head_dim ** -0.5
    blocks = jnp.arange(nb)
    rows_b = jnp.arange(B)[:, None, None]

    def block(args):
        ql, qi, wi, at = args               # (B, s, ...), at (B, s)
        with _scope("attention.indexer"):
            score = jnp.einsum("bsjd,bnd->bsjn", qi, pooled,
                               preferred_element_type=jnp.float32)
            score = jnp.einsum("bsjn,bsj->bsn", jax.nn.relu(score), wi)
            own = (at // kp)[..., None]
            score = jnp.where(blocks == own, jnp.inf,
                              jnp.where(blocks < own, score, -jnp.inf))
            _, pick = jax.lax.top_k(score, pick_n)          # (B, s, K)
        with _scope("attention.sparse"):
            rows = latent[rows_b, pick].reshape(B, at.shape[1], -1, C)
            key_pos = (pick[..., None] * kp + jnp.arange(kp)).reshape(
                B, at.shape[1], -1)
            seen = key_pos <= at[..., None]
            s = jnp.einsum("bshc,bskc->bshk", ql.astype(cached.dtype), rows,
                           preferred_element_type=jnp.float32) * scale
            p = jax.nn.softmax(jnp.where(seen[:, :, None], s, -1e30), -1)
            ctx = jnp.einsum("bshk,bskc->bshc", p.astype(cached.dtype), rows,
                             preferred_element_type=jnp.float32)
        return ctx, seen.sum(-1)

    if S <= _QUERY_BLOCK:
        return block((q_lat, q_idx, w_idx, positions))
    n = S // _QUERY_BLOCK                 # buckets are powers of two

    def split(a):
        return jnp.moveaxis(a.reshape((B, n, _QUERY_BLOCK) + a.shape[2:]),
                            1, 0)
    ctx, seen = jax.lax.map(block, tuple(map(split, (q_lat, q_idx, w_idx,
                                                     positions))))
    return (jnp.moveaxis(ctx, 0, 1).reshape(B, S, nH, C),
            jnp.moveaxis(seen, 0, 1).reshape(B, S))


def _dsa_cached(x, w_qa, q_a_norm, w_qb, w_kva, kv_norm, w_kvb, w_o, w_iq,
                w_ik, ik_norm, ik_bias, w_iw, pages, table, pos, *, cfg):
    """One cached call: write this call's rows (latent and index key)
    into the slot's pages, pick and attend, project out.  Returns (out,
    pages)."""
    from ..inference import kvcache as _kvc
    B, S, _ = x.shape
    nH, dq, dv = cfg.num_attention_heads, cfg.qk_head_dim, cfg.v_head_dim
    nI, dI, C = cfg.index_n_heads, cfg.index_head_dim, cfg.kv_lora_rank
    eps = cfg.rms_norm_eps
    positions = _positions(pos, B, S)
    with _scope("attention.qkv"):
        qa = _rms(jnp.dot(x, w_qa), q_a_norm, eps).astype(x.dtype)
        q = jnp.dot(qa, w_qb).reshape(B, S, nH, dq)
    with _scope("attention.latent_kv"):
        c = _rms(jnp.dot(x, w_kva), kv_norm, eps)
    with _scope("attention.indexer"):
        qi = jnp.dot(qa, w_iq, preferred_element_type=jnp.float32)
        qi = _rope_interleaved(qi.reshape(B, S, nI, dI), positions,
                               cfg.index_rope_dim, cfg.index_rope_theta)
        ki = _layer_norm(jnp.dot(x, w_ik, preferred_element_type=jnp.float32),
                         ik_norm, ik_bias, eps)
        ki = _rope_interleaved(ki, positions, cfg.index_rope_dim,
                               cfg.index_rope_theta)
        wi = jnp.dot(x, w_iw, preferred_element_type=jnp.float32) \
            * (nI * dI) ** -0.5
    rows = jnp.concatenate([c, ki], -1).astype(x.dtype)
    pages = _kvc.scatter_latent(pages, rows, table, pos)
    cached = _kvc.gather_latent(pages, table)
    w = w_kvb.reshape(C, nH, dq + dv)
    with _scope("attention.absorb"):
        q_lat = jnp.einsum("bshd,chd->bshc", q, w[..., :dq])
    ctx, seen = sparse_attend(q_lat, qi.astype(x.dtype), wi, positions,
                              cached, cfg)
    bag = _devc.current()
    if bag is not None:
        real = jnp.ones((B, S), bool) if bag.rows is None \
            else jnp.broadcast_to(bag.rows, (B, S))
        bag.add("dsa_keys_attended", jnp.where(real, seen, 0).sum())
        bag.add("dsa_keys_live", jnp.where(real, positions + 1, 0).sum())
    with _scope("attention.absorb"):
        out = jnp.einsum("bshc,chd->bshd", ctx.astype(x.dtype), w[..., dq:])
    with _scope("attention.out"):
        return jnp.dot(out.reshape(B, S, nH * dv), w_o), pages


class SparseLatentAttention(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = c = config
        H, nH = c.hidden_size, c.num_attention_heads
        init = Normal(0.0, c.init_std)

        def param(shape, initializer=init):
            return self.create_parameter(shape, dtype=c.dtype,
                                         default_initializer=initializer)
        self.q_a_proj = param((H, c.q_lora_rank))
        self.q_a_norm = param((c.q_lora_rank,), Constant(1.0))
        self.q_b_proj = param((c.q_lora_rank, nH * c.qk_head_dim))
        self.kv_a_proj = param((H, c.kv_lora_rank))
        self.kv_norm = param((c.kv_lora_rank,), Constant(1.0))
        self.kv_b_proj = param(
            (c.kv_lora_rank, nH * (c.qk_head_dim + c.v_head_dim)))
        self.o_proj = param((nH * c.v_head_dim, H))
        self.index_q = param((c.q_lora_rank,
                              c.index_n_heads * c.index_head_dim))
        self.index_k = param((H, c.index_head_dim))
        self.index_k_norm = param((c.index_head_dim,), Constant(1.0))
        self.index_k_bias = param((c.index_head_dim,), Constant(0.0))
        self.index_w = param((H, c.index_n_heads))

    def cache_spec(self):
        c = self.config
        return LatentCacheSpec(c.kv_lora_rank + c.index_head_dim)

    def forward(self, x, cache, pos):
        if not hasattr(cache, "pages"):
            raise ValueError(
                "a sparse latent attention layer keeps a paged latent "
                "cache (inference.kvcache.LatentCacheView): serve it "
                "through ServingEngine(kv_mode='paged')")
        out, pages = call_op(
            _dsa_cached, x, self.q_a_proj, self.q_a_norm, self.q_b_proj,
            self.kv_a_proj, self.kv_norm, self.kv_b_proj, self.o_proj,
            self.index_q, self.index_k, self.index_k_norm,
            self.index_k_bias, self.index_w, cache.pages, cache.table, pos,
            cfg=self.config)
        return out, cache._replace(pages=pages)


# -- blocks -------------------------------------------------------------------

class GLM5NextDecoderLayer(nn.Layer):
    def __init__(self, config, index):
        super().__init__()
        c = config
        self.attn_hc = HyperConnection(c)
        self.input_layernorm = _RMSNorm(c)
        self.self_attn = KimiDeltaAttention(c) \
            if c.layer_types[index] == LINEAR else SparseLatentAttention(c)
        self.ffn_hc = HyperConnection(c)
        self.post_attention_layernorm = _RMSNorm(c)
        self.sparse = c.mlp_layer_types[index] == "sparse"
        if self.sparse:
            self.mlp = DroplessMoELayer(
                c.hidden_size, c.moe_intermediate_size, c.n_routed_experts,
                c.num_experts_per_tok, experts_held=c.held_range,
                d_shared=c.n_shared_experts * c.moe_intermediate_size,
                scaling=c.routed_scaling_factor, dtype=c.dtype,
                init_std=c.init_std, swiglu_limit=c.swiglu_limit)
        else:
            self.mlp = SwiGLU(c)

    def _sublayer(self, X, hc, norm, fn):
        pre, post, res = hc.maps(X)
        with _scope("residual.hc"):
            h = call_op(hc_read, X, pre)
        with _scope("norm"):
            h = norm(h)
        y, extra = fn(h)
        with _scope("residual.hc"):
            return call_op(hc_write, X, res, post, y), extra

    def forward(self, X, cache, pos):
        X, cache = self._sublayer(
            X, self.attn_hc, self.input_layernorm,
            lambda h: self.self_attn(h, cache, pos))

        def ffn(h):
            if self.sparse:
                return self.mlp(h), None     # the layer sets its moe.* scopes
            with _scope("mlp"):
                return self.mlp(h), None
        X, _ = self._sublayer(X, self.ffn_hc, self.post_attention_layernorm,
                              ffn)
        return X, cache


class GLM5NextModel(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embed_tokens = self.create_parameter(
            (config.vocab_size, config.hidden_size), dtype=config.dtype,
            default_initializer=Normal(0.0, config.init_std))
        self.layers = nn.LayerList(
            [GLM5NextDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = _RMSNorm(config)

    def forward(self, input_ids, caches, pos):
        n = self.config.hc_mult
        with _scope("embed"):
            X = call_op(lambda ids, table: jnp.repeat(
                table[ids][:, :, None, :], n, axis=2),
                input_ids, self.embed_tokens)
        bag = _devc.current()
        if bag is not None and bag.phase == "prefill":
            bag.add("prefill_pieces", 1)
        new = []
        for blk, cache in zip(self.layers, caches):
            X, cache = blk(X, cache, pos)
            new.append(cache)
        with _scope("residual.hc"):
            x = call_op(lambda v: jnp.sum(_f32(v), 2).astype(v.dtype), X)
        with _scope("norm"):
            return self.norm(x), new


class GLM5NextForCausalLM(nn.Layer, GenerationMixin):
    # ``forward(..., last=)`` applies the head to that one position
    forward_takes_last = True
    device_counters = DroplessMoELayer.device_counters + DSA_COUNTERS

    def __init__(self, config):
        super().__init__()
        self.model = GLM5NextModel(config)
        self.lm_head = self.create_parameter(
            (config.hidden_size, config.vocab_size), dtype=config.dtype,
            default_initializer=Normal(0.0, config.init_std))

    @property
    def prefill_piece(self):
        """The longest prefill the engine runs in one program."""
        return self.model.config.prefill_piece

    def kv_cache_spec(self):
        return [blk.self_attn.cache_spec() for blk in self.model.layers]

    def forward(self, input_ids, caches=None, pos=None, attn_mask=None,
                last=None):
        if pos is None or attn_mask is not None:
            raise ValueError(
                "GLM5NextForCausalLM runs cached, with no attn_mask: serve "
                "it through ServingEngine(kv_mode='paged')")
        x, caches = self.model(input_ids, caches, pos)
        if last is not None:
            x = call_op(lambda h, at: jax.lax.dynamic_slice_in_dim(
                h, at.astype(jnp.int32), 1, axis=1), x, last)
        with _scope("lm_head"):
            logits = call_op(
                lambda h, w: jnp.dot(h, w,
                                     preferred_element_type=jnp.float32),
                x, self.lm_head)
        return logits, caches
