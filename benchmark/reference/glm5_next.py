"""Plain reference of the ``glm5_next`` family (``glm5_next_text``: linear
attention by the gated delta rule, sparse latent attention over the keys
an indexer picks, a hyper-connected residual of ``hc_mult`` streams, a
dropless sigmoid-routed expert layer with a shared expert): float32
``jax.numpy`` at matmul precision "highest", no kernel, no cache, no
batching.  One row ``ids`` [T] at a time, a layer at a time.

*Residual (mHC)*: the streams X [T, n, H] start as n copies of the
embedding.  Each sublayer F (attention, then the FFN) reads and writes
them through its own maps, from the weightless RMSNorm of the flattened
streams x~ [n H]: ``m = x~ phi``; ``H_pre = sigmoid(a0 m_pre + b_pre)``,
``H_post = 2 sigmoid(a1 m_post + b_post)``, ``H_res = Sinkhorn(a2 m_res +
b_res)`` (n x n; a row softmax plus eps, a column normalisation, then
``hc_sinkhorn_iters - 1`` more rounds of row and column normalisation,
each sum plus eps); ``X' = H_res X + H_post^T F(RMSNorm(H_pre X))``.
After the last layer the streams are summed, RMS-normed and multiplied
by the untied head.

*Linear attention (KDA)*, a layer of ``linear_num_heads`` heads of
``linear_head_dim``: ``[q; k; v] = SiLU(causal depthwise conv of W_qkv h)``
(the conv's taps over the previous ``short_conv_kernel_size`` inputs,
zeros before the first); q and k L2-normed a head, q scaled by
``head_dim^-1/2``; ``beta = sigmoid(W_b h)`` a head; ``log alpha =
gate_lower_bound sigmoid(exp(A_log) (W_fb W_fa h + dt_bias))`` a channel.
Token by token, state S [heads, dk, dv] float32 from zero::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

then an RMSNorm a head, times ``sigmoid(W_gb W_ga h)``, and ``W_o``.

*Sparse latent attention (DSA)*: ``qa = RMSNorm(W_qa h)``, ``q = W_qb qa``
(heads of ``qk_head_dim``, no rotary part), ``c = RMSNorm(W_kva h)``,
``[k_h; v_h] = W_kvb,h c``, scores scaled by ``qk_head_dim^-1/2``.  The
indexer: ``q^I = W_Iq qa`` (``index_n_heads`` of ``index_head_dim``),
``k^I = LayerNorm(W_Ik h)``, both rotated on their first
``index_rope_dim`` dims in interleaved pairs; ``w = W_Iw h / sqrt(nI
dI)``; the keys mean-pooled over blocks of ``index_kpool`` positions;
a query at t scores each block b before its own ``sum_j w_j
ReLU(q^I_j . kbar_b)`` and keeps its own block and the best
``index_topk / index_kpool - 1`` earlier ones; it attends the keys of the
kept blocks up to t.  Here the pick is a mask on causal attention over
every key (the same function).

*FFN*: SwiGLU; a ``dense`` layer at ``intermediate_size``; a ``sparse``
one (its routed and shared experts with the gate clamped to at most
``swiglu_limit`` and up to [-limit, limit]): ``s = sigmoid(W_r h)`` over the router's full width in float32, the
chosen are the top-k of ``s + bias`` (the bias picks and never weighs),
``g_i = scaling s_i / sum_{j chosen} s_j``, ``y = sum_{i chosen and held}
g_i E_i(h) + E_shared(h)``; every held expert applied to every token and
masked.

Departures from the published model, each also in the configuration's
``assumed``: the readings of the indexer's pooling and tail, of the
Sinkhorn normalisation, of the first-stream initialisation and the final
sum, of the decay gate and the clamp; THE CUT: the chip holds
``n_routed_experts`` of the router's ``router_num_experts`` experts and a
slice of the vocabulary; what the absent experts would add is left out,
here as in the program.  The multi-token prediction layer is not part of
the next-token path and is not here.

The weights arrive as the bfloat16 values the program was given
(``weights_glm5_next.make_stacked``: a layer drawn when asked for) and are
lifted to float32 where they are used.  To fit 33,792 positions the
row-wise parts run a block of rows at a time, the recurrence a block of
tokens at a time (token by token inside it), the attention a block of
heads and queries at a time: the same mathematics in less memory.

``precision`` is "highest" for the reference itself.  "bf16" rounds every
activation to bfloat16 (sums inside a matmul, a norm, a softmax, the
router and the recurrence's state stay float32, as the configuration
states): the witness.  "int8" also rounds the operands of every linear
layer, of the experts and of the head to an int8 grid (per row of the
activations, per output column of the weights): the control that
``correct`` has to refuse.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PRECISIONS = ("highest", "bf16", "int8")
LINEAR = "linear_attention"
ROWS = 512          # rows a block in the row-wise parts and the recurrence
QUERIES = 256       # queries a block in the indexer and the attention
HEADS = 8           # heads a block in the attention


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


def _f32(w):
    return w.astype(jnp.float32)


def _rms(x, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)


def _rms_norm(x, weight, eps, precision):
    return _act(_rms(x, eps) * _f32(weight), precision)


def _blocks(x, size):
    """[T, ...] -> [T / size, size, ...] (T a multiple of size, or one
    block)."""
    size = size if x.shape[0] % size == 0 else x.shape[0]
    return x.reshape((-1, size) + x.shape[1:])


def _unblocks(x):
    return x.reshape((-1,) + x.shape[2:])


def _by_rows(fn, *arrays):
    """``fn`` of each block of rows of the arrays, the results stacked
    back: for what acts on each position alone."""
    out = lax.map(lambda a: fn(*a), tuple(_blocks(x, ROWS) for x in arrays))
    return jax.tree_util.tree_map(_unblocks, out)


# -- hyper-connections -----------------------------------------------------

def sinkhorn(logits, iters, eps):
    m = jax.nn.softmax(logits, -1) + eps
    m = m / (m.sum(-2, keepdims=True) + eps)
    for _ in range(iters - 1):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return m


def _maps(X, phi, bias, alpha, m):
    """(pre [., n], post [., n], res [., n, n]) of streams X [., n, H]."""
    n = m["hc_mult"]
    flat = _rms(X.reshape(X.shape[0], -1), m["rms_norm_eps"])
    mix = jnp.matmul(flat, _f32(phi), precision=HIGHEST)
    a, b = _f32(alpha), _f32(bias)
    pre = jax.nn.sigmoid(a[0] * mix[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * mix[:, n:2 * n] + b[n:2 * n])
    res = (a[2] * mix[:, 2 * n:] + b[2 * n:]).reshape(-1, n, n)
    return pre, post, sinkhorn(res, m["hc_sinkhorn_iters"], m["hc_eps"])


def _read(X, pre, norm_w, m, precision):
    h = jnp.einsum("tn,tnh->th", pre, X, precision=HIGHEST)
    return _rms_norm(_act(h, precision), norm_w, m["rms_norm_eps"],
                     precision)


def _write(X, res, post, y, precision):
    return _act(jnp.einsum("tnm,tmh->tnh", res, X, precision=HIGHEST)
                + post[..., None] * y[:, None, :], precision)


# -- linear attention ------------------------------------------------------

def _kda(h, lw, m, precision):
    """The layer's output [T, H] for its input h [T, H]."""
    nh, D, R = (m["linear_num_heads"], m["linear_head_dim"],
                m["linear_gate_rank"])
    K, eps = m["short_conv_kernel_size"], m["rms_norm_eps"]
    conv_w = _f32(lw["kda.conv"])                       # [K, 3C]
    decay = jnp.exp(_f32(lw["kda.a_log"]))[:, None]     # [nh, 1]

    def token(S, x):
        q, k, v, g, beta = x
        S = S * jnp.exp(g)[..., None]                   # Diag(alpha) S
        u = v - jnp.einsum("hkv,hk->hv", S, k, precision=HIGHEST)
        S = S + (beta[:, None] * k)[..., None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q, precision=HIGHEST)

    def block(carry, hb):
        S, tail = carry
        raw = _dot(hb, lw["kda.qkv"], precision)        # [B, 3C]
        full = jnp.concatenate([tail, raw], 0)
        n = hb.shape[0]
        conv = sum(full[j:j + n] * conv_w[j] for j in range(K))
        q, k, v = jnp.split(_act(jax.nn.silu(conv), precision), 3, -1)
        q, k, v = (a.reshape(n, nh, D) for a in (q, k, v))
        q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
            * D ** -0.5
        k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
        ga = _dot(hb, lw["kda.gate_a"], precision)
        f = _dot(ga[:, :R], lw["kda.f_b"], precision)
        g = m["gate_lower_bound"] * jax.nn.sigmoid(
            decay * (f + _f32(lw["kda.dt_bias"])).reshape(n, nh, D))
        beta = jax.nn.sigmoid(ga[:, 2 * R:])
        gate = jax.nn.sigmoid(_dot(ga[:, R:2 * R], lw["kda.g_b"], precision))
        S, o = lax.scan(token, S, (q, k, v, _act(g, precision),
                                   _act(beta, precision)))
        o = _rms(o, eps) * _f32(lw["kda.o_norm"])
        o = _act(o.reshape(n, nh * D) * gate, precision)
        return (S, full[n:]), _dot(o, lw["attn.o"], precision)

    S0 = jnp.zeros((nh, D, D), jnp.float32)
    tail0 = jnp.zeros((K - 1, 3 * nh * D), jnp.float32)
    _, y = lax.scan(block, (S0, tail0), _blocks(h, ROWS))
    return _unblocks(y)


# -- sparse latent attention -----------------------------------------------

def _rope_interleaved(x, dim, theta):
    """x [T, ..., D] at positions 0..T-1, pairs (2i, 2i+1) of the first
    ``dim`` rotated."""
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    while angle.ndim < x.ndim:
        angle = angle[:, None]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x1, x2 = x[..., 0:dim:2], x[..., 1:dim:2]
    rot = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot.reshape(x.shape[:-1] + (dim,)),
                            x[..., dim:]], -1)


def _picks(qi, wi, ki, m):
    """Each query's kept blocks [T, index_topk / index_kpool]: its own
    and the best-scored earlier ones (-inf fills where fewer exist)."""
    kp, T = m["index_kpool"], qi.shape[0]
    nb = T // kp
    pooled = ki.reshape(nb, kp, -1).mean(1)
    keep = min(m["index_topk"] // kp, nb)
    blocks = jnp.arange(nb)

    def block(args):
        q, w, t = args                                  # [Q, nI, dI] ..
        s = jnp.einsum("qjd,bd->qjb", q, pooled, precision=HIGHEST)
        s = jnp.einsum("qjb,qj->qb", jax.nn.relu(s), w, precision=HIGHEST)
        own = (t // kp)[:, None]
        s = jnp.where(blocks == own, jnp.inf,
                      jnp.where(blocks < own, s, -jnp.inf))
        return lax.top_k(s, keep)[1]
    t = jnp.arange(T)
    return _unblocks(lax.map(block, (_blocks(qi, QUERIES),
                                     _blocks(wi, QUERIES),
                                     _blocks(t, QUERIES))))


def _dsa(h, lw, m, precision, dense=False):
    """The layer's output [T, H]; ``dense`` (a test's fault) attends every
    earlier key, the pick left out."""
    T = h.shape[0]
    nH, dq, dv = m["num_attention_heads"], m["qk_head_dim"], m["v_head_dim"]
    nI, dI, C = m["index_n_heads"], m["index_head_dim"], m["kv_lora_rank"]
    kp, eps = m["index_kpool"], m["rms_norm_eps"]
    qa = _rms_norm(_dot(h, lw["dsa.q_a"], precision), lw["dsa.q_a_norm"],
                   eps, precision)
    c = _rms_norm(_dot(h, lw["dsa.kv_a"], precision), lw["dsa.kv_norm"],
                  eps, precision)
    qi = _rope_interleaved(_dot(qa, lw["idx.q"], precision).reshape(
        T, nI, dI), m["index_rope_dim"], m["index_rope_theta"])
    ki = _dot(h, lw["idx.k"], precision)
    mu = jnp.mean(ki, -1, keepdims=True)
    ki = (ki - mu) * lax.rsqrt(jnp.mean(jnp.square(ki - mu), -1,
                                        keepdims=True) + eps)
    ki = _rope_interleaved(ki * _f32(lw["idx.k_norm"])
                           + _f32(lw["idx.k_bias"]),
                           m["index_rope_dim"], m["index_rope_theta"])
    wi = _dot(h, lw["idx.w"], precision) * (nI * dI) ** -0.5
    picks = _picks(_act(qi, precision), wi, _act(ki, precision), m)
    t = jnp.arange(T)
    w_kv = lw["dsa.kv_b"].reshape(C, nH, dq + dv)
    w_q = lw["dsa.q_b"].reshape(-1, nH, dq)
    w_o = lw["attn.o"].reshape(nH, dv, -1)
    hb = HEADS if nH % HEADS == 0 else nH

    def heads(y, i):                    # a block of heads at a time
        def project(x, w):
            w = lax.dynamic_slice_in_dim(w, i * hb, hb, 1)
            return _dot(x, w.reshape(w.shape[0], -1), precision).reshape(
                T, hb, -1)
        kv = project(c, w_kv)
        q, k, v = project(qa, w_q), kv[..., :dq], kv[..., dq:]

        def queries(args):
            qb, pb, tb = args                           # [Q, hb, dq] ..
            kept = jnp.zeros((qb.shape[0], T // kp), bool).at[
                jnp.arange(qb.shape[0])[:, None], pb].set(True)
            seen = t[None, :] <= tb[:, None]
            if not dense:
                seen = seen & jnp.repeat(kept, kp, axis=1)
            s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HIGHEST) \
                * dq ** -0.5
            p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1)
            return jnp.einsum("hqk,khd->qhd", _act(p, precision), v,
                              precision=HIGHEST)
        ctx = _unblocks(lax.map(queries, (_blocks(q, QUERIES),
                                          _blocks(picks, QUERIES),
                                          _blocks(t, QUERIES))))
        wo = lax.dynamic_slice_in_dim(w_o, i * hb, hb, 0)
        # W_o a block of its rows at a time (the int8 grid's rows and
        # columns are then the block's)
        return y + _dot(ctx.reshape(T, -1), wo.reshape(hb * dv, -1),
                        precision), None

    y, _ = lax.scan(heads, jnp.zeros((T, h.shape[1]), jnp.float32),
                    jnp.arange(nH // hb))
    return _act(y, precision)


# -- FFN ---------------------------------------------------------------------

def _swiglu(h, gate_up, down, limit, precision):
    gu = _dot(h, gate_up, precision)
    half = gu.shape[-1] // 2
    gate, up = gu[..., :half], gu[..., half:]
    if limit is not None:
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
    return _dot(_act(jax.nn.silu(gate) * up, precision), down, precision)


def _experts(h, lw, m, precision):
    first, held = m.get("first_expert_held", 0), m["n_routed_experts"]
    k, limit = m["num_experts_per_tok"], m["swiglu_limit"]
    s = jax.nn.sigmoid(jnp.matmul(h, _f32(lw["moe.router"]),
                                  precision=HIGHEST))
    _, picks = lax.top_k(s + _f32(lw["moe.bias"]), k)
    chosen = jnp.take_along_axis(s, picks, -1)
    gates = m["routed_scaling_factor"] * chosen \
        / chosen.sum(-1, keepdims=True)

    def one(total, expert):
        e, gate_up, down = expert
        weight = jnp.sum(jnp.where(picks == first + e, gates, 0.0), -1)
        y = _swiglu(h, gate_up, down, limit, precision)      # every token
        return total + weight[:, None] * y, None
    routed, _ = lax.scan(one, jnp.zeros_like(h), (
        jnp.arange(held), lw["moe.experts.gate_up"],
        lw["moe.experts.down"]))
    shared = _swiglu(h, lw["moe.shared.gate_up"], lw["moe.shared.down"],
                     limit, precision)
    return _act(routed + shared, precision)


# -- the layers --------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5), donate_argnums=0)
def _attention_sublayer(X, lw, static, kind, precision, fault):
    m = _unfreeze(static)

    def before(Xb):
        pre, post, res = _maps(Xb, lw["hc1.phi"], lw["hc1.bias"],
                               lw["hc1.alpha"], m)
        return _read(Xb, pre, lw["ln1.weight"], m, precision), post, res
    h, post, res = _by_rows(before, X)
    y = _kda(h, lw, m, precision) if kind == LINEAR \
        else _dsa(h, lw, m, precision, dense=fault == "dense")
    return _by_rows(lambda Xb, rb, pb, yb: _write(Xb, rb, pb, yb, precision),
                    X, res, post, y)


@functools.partial(jax.jit, static_argnums=(2, 3), donate_argnums=0)
def _ffn_sublayer(X, lw, static, precision):
    m = _unfreeze(static)

    def rows(Xb):
        pre, post, res = _maps(Xb, lw["hc2.phi"], lw["hc2.bias"],
                               lw["hc2.alpha"], m)
        h = _read(Xb, pre, lw["ln2.weight"], m, precision)
        y = _experts(h, lw, m, precision) if "moe.router" in lw \
            else _swiglu(h, lw["mlp.gate_up"], lw["mlp.down"], None,
                         precision)
        return _write(Xb, res, post, y, precision)
    return _by_rows(rows, X)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _embed(g, ids, static, precision):
    n = _unfreeze(static)["hc_mult"]
    x = _act(_f32(g["embed"])[ids], precision)
    return jnp.repeat(x[:, None, :], n, axis=1)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _head(g, X, static, precision):
    m = _unfreeze(static)
    x = _rms_norm(_act(X.sum(1), precision), g["norm.weight"],
                  m["rms_norm_eps"], precision)
    return _dot(x, g["head"], precision)


def _freeze(model):
    return tuple(sorted(
        (k, tuple(v) if isinstance(v, list) else v)
        for k, v in model.items()))


def _unfreeze(static):
    return dict(static)


def row_logits(model, w, ids, precision="highest", fault=None):
    """ids [T] -> logits [T, V], a layer at a time.  ``fault`` (tests):
    "dense" attends every earlier key in the sparse layers."""
    static = _freeze(model)
    ids = jnp.asarray(ids, jnp.int32)
    T = ids.shape[0]
    # whole blocks of index keys: the padding follows every real position
    ids = jnp.pad(ids, (0, -T % model["index_kpool"]))
    X = _embed(w["globals"], ids, static, precision)
    for i, kind in enumerate(model["layer_types"]):
        lw = w["layers"][i]
        X = _attention_sublayer(X, lw, static, kind, precision, fault)
        X = _ffn_sublayer(X, lw, static, precision)
    return _head(w["globals"], X, static, precision)[:T]


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
