"""Plain reference of the GPT family: float32 ``jax.numpy`` at matmul
precision "highest", no kernels, no cache, no batching tricks.

Pre-LN decoder blocks (fused QKV laid out as [3, heads, head_dim] along
the output axis, dense causal attention, tanh-GELU MLP), learned
positions, output head tied to the token embedding, shifted mean
cross-entropy, AdamW with decoupled decay on every leaf.  It follows
Brown et al. 2020 §2.1 / Radford et al. 2019 except that every layer is
dense (the paper alternates dense and banded layers).

It imports nothing of the program and takes the weights from
``benchmark/weights.py`` (stacked over the depth).  To fit beside
nothing else on one chip it works row by row and recomputes each layer in
the backward pass (``jax.checkpoint``): the same mathematics.

``precision`` is "highest" for the reference itself.  "bf16" and "int8"
put the reference in the program's place at a lower precision.  "bf16"
rounds the weights and every activation to bfloat16 (sums inside a
matmul, a norm or a softmax stay float32), which is what the
configurations state.  "int8" does that and also rounds the operands of
every linear layer and of the output head to an int8 grid (per row of
the activations, per output column of the weights), forward and, for the
incoming gradient, backward: the control that ``correct`` has to refuse where the
configuration states bfloat16.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PRECISIONS = ("highest", "bf16", "int8")


def _int8_grid(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@jax.custom_vjp
def _int8_dot(x, w):
    """An int8 matmul as a training step would run it: operands on the
    int8 grid forward, and the incoming gradient on it too (per row) for
    both backward matmuls."""
    return jnp.matmul(_int8_grid(x, -1), _int8_grid(w, 0), precision=HIGHEST)


def _int8_dot_fwd(x, w):
    xq, wq = _int8_grid(x, -1), _int8_grid(w, 0)
    return jnp.matmul(xq, wq, precision=HIGHEST), (xq, wq)


def _int8_dot_bwd(saved, g):
    xq, wq = saved
    gq = _int8_grid(g, -1)
    dx = jnp.matmul(gq, wq.T, precision=HIGHEST)
    dw = jnp.matmul(xq.reshape(-1, xq.shape[-1]).T,
                    gq.reshape(-1, gq.shape[-1]), precision=HIGHEST)
    return dx, dw


_int8_dot.defvjp(_int8_dot_fwd, _int8_dot_bwd)


def _act(x, precision):
    """Round an activation or a weight to what ``precision`` carries."""
    if precision == "highest":
        return x
    if precision not in PRECISIONS:
        raise ValueError(f"precision {precision!r} not in {PRECISIONS}")
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _dot(x, w, precision):
    """x [.., K] @ w [K, N], summed in float32."""
    x, w = _act(x, precision), _act(w, precision)
    if precision == "int8":
        return _act(_int8_dot(x, w), precision)
    return _act(jnp.matmul(x, w, precision=HIGHEST), precision)


def _layer_norm(x, scale, bias, eps, precision):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return _act((x - mu) * lax.rsqrt(var + eps) * _act(scale, precision)
                + _act(bias, precision), precision)


def _block(x, lw, heads, eps, precision):
    """One decoder block on one row: x [S, H]."""
    S, H = x.shape
    D = H // heads
    def linear(h, name):
        return _act(_dot(h, lw[name + ".weight"], precision)
                    + _act(lw[name + ".bias"], precision), precision)

    h = _layer_norm(x, lw["ln1.weight"], lw["ln1.bias"], eps, precision)
    q, k, v = jnp.moveaxis(linear(h, "attn.qkv").reshape(S, 3, heads, D), 1, 0)
    scores = jnp.einsum("qhd,khd->hqk", q, k, precision=HIGHEST) / (D ** 0.5)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    ctx = _act(jnp.einsum("hqk,khd->qhd", probs, v, precision=HIGHEST),
               precision)
    x = _act(x + linear(ctx.reshape(S, H), "attn.out"), precision)
    h = _layer_norm(x, lw["ln2.weight"], lw["ln2.bias"], eps, precision)
    h = _act(jax.nn.gelu(linear(h, "mlp.up"), approximate=True), precision)
    return _act(x + linear(h, "mlp.down"), precision)


_GLOBAL = ("wte", "wpe", "lnf.weight", "lnf.bias")


def _row_logits(w, ids, heads, eps, precision):
    """ids [S] -> logits [S, V]."""
    S = ids.shape[0]
    x = _act(_act(w["wte"], precision)[ids] + _act(w["wpe"], precision)[:S],
             precision)
    layers = {k: v for k, v in w.items() if k not in _GLOBAL}
    body = jax.checkpoint(
        lambda x, lw: (_block(x, lw, heads, eps, precision), None))
    x, _ = lax.scan(body, x, layers)
    x = _layer_norm(x, w["lnf.weight"], w["lnf.bias"], eps, precision)
    return _dot(x, w["wte"].T, precision)


def _row_loss(w, ids, heads, eps, precision):
    """Mean cross-entropy of positions 0..S-2 predicting 1..S-1."""
    logp = jax.nn.log_softmax(_row_logits(w, ids, heads, eps, precision)[:-1])
    return -jnp.mean(jnp.take_along_axis(logp, ids[1:, None], axis=-1))


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _row_loss_and_grad(w, ids, heads, eps, precision):
    return jax.value_and_grad(_row_loss)(w, ids, heads, eps, precision)


@functools.partial(jax.jit, donate_argnums=(0,))
def _accumulate(total, g, scale):
    return jax.tree_util.tree_map(lambda t, x: t + scale * x, total, g)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw(w, g, m, v, t, lr, wd, b1, b2, eps):
    def leaf(p, g, m, v):
        p = p * (1.0 - lr * wd)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * jnp.square(g)
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return p - lr * mhat / (jnp.sqrt(vhat) + eps), m, v
    out = {k: leaf(w[k], g[k], m[k], v[k]) for k in w}
    return ({k: o[0] for k, o in out.items()},
            {k: o[1] for k, o in out.items()},
            {k: o[2] for k, o in out.items()})


QKV_BIAS = "attn.qkv.bias"
QKV_PARTS = ("q", "k", "v")


@jax.jit
def leaf_norms(tree):
    """{leaf: norm}: one number for a global leaf, one per layer for a
    stacked leaf (every layer's leaf is a leaf of the program).  The
    fused QKV bias counts as three leaves, [L, 3]: the key's bias has no
    gradient under softmax, and inside the fused leaf it would hide."""
    out = {}
    for k, x in tree.items():
        if k == QKV_BIAS:
            x = x.reshape(x.shape[0], 3, -1)
        axes = None if k in _GLOBAL else tuple(range(1, x.ndim))
        if k == QKV_BIAS:
            axes = (2,)
        out[k] = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    return out


@jax.jit
def _delta_norms(a, b):
    return leaf_norms({k: a[k] - b[k] for k in a})


def flat_norms(norms):
    """{leaf or h.<i>.<leaf>: float} from ``leaf_norms``' arrays."""
    out = {}
    for k, v in jax.device_get(norms).items():
        if k in _GLOBAL:
            out[k] = float(v)
        elif k == QKV_BIAS:
            for i, row in enumerate(v):
                for part, x in zip(QKV_PARTS, row):
                    out[f"h.{i}.{k}.{part}"] = float(x)
        else:
            for i, x in enumerate(v):
                out[f"h.{i}.{k}"] = float(x)
    return out


def train_steps(model, w, batches, precision="highest", lr=3e-4, wd=0.01,
                b1=0.9, b2=0.999, adam_eps=1e-8, fault=None):
    """Follow ``len(batches)`` optimizer steps from weights ``w`` (a dict
    as ``weights.make_stacked`` gives; consumed).  ``batches`` is
    [steps, B, S] token ids; inputs and labels are the same rows.

    Returns the loss of each step, the per-leaf norm of the first
    gradient and the per-leaf norm of the parameters' change over all
    the steps.  ``fault`` plants a fault for the readings that set the
    limits: "half_batch" leaves out the second half of every batch and
    takes the mean over the rest.
    """
    heads, eps = model["num_attention_heads"], model["layer_norm_epsilon"]
    w0 = jax.tree_util.tree_map(jnp.copy, w)
    m = jax.tree_util.tree_map(jnp.zeros_like, w)
    v = jax.tree_util.tree_map(jnp.zeros_like, w)
    losses, grad1 = [], None
    for t, batch in enumerate(batches, start=1):
        rows = batch[:len(batch) // 2] if fault == "half_batch" else batch
        g = jax.tree_util.tree_map(jnp.zeros_like, w)
        loss = 0.0
        for row in rows:
            l, gr = _row_loss_and_grad(w, jnp.asarray(row, jnp.int32),
                                       heads, eps, precision)
            g = _accumulate(g, gr, 1.0 / len(rows))
            loss += float(l) / len(rows)
        losses.append(loss)
        if t == 1:
            grad1 = flat_norms(leaf_norms(g))
        w, m, v = _adamw(w, g, m, v, float(t), lr, wd, b1, b2, adam_eps)
    return {"loss": losses, "grad1_norm": grad1,
            "delta_norm": flat_norms(_delta_norms(w, w0))}


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gaps_jit(w, ids, targets, heads, eps):
    lg = _row_logits(w, ids, heads, eps, "highest")
    return jnp.max(lg, -1) - jnp.take_along_axis(
        lg, targets[:, None], axis=-1)[:, 0]


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _best_jit(w, ids, heads, eps, precision):
    return jnp.argmax(_row_logits(w, ids, heads, eps, precision), -1)


def next_token_gaps(model, w, ids, targets):
    """For a row ``ids`` [T] and the token ``targets`` [T] that followed
    each position: how far the reference's logit of that token lies below
    the reference's best logit there (0 where it is the best)."""
    return _gaps_jit(w, jnp.asarray(ids, jnp.int32),
                     jnp.asarray(targets, jnp.int32),
                     model["num_attention_heads"],
                     model["layer_norm_epsilon"])


def best_next_tokens(model, w, ids, precision):
    """The token that ``precision`` puts first after each position."""
    return _best_jit(w, jnp.asarray(ids, jnp.int32),
                     model["num_attention_heads"],
                     model["layer_norm_epsilon"], precision)
