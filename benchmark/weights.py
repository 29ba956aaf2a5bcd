"""Seeded weights of the GPT family, made on the device from the seed.

The benchmark owns the weights: the driver puts them into the program's
network, and the plain reference makes the same arrays again from the
same seed (same device, same program, so the same bits).  The layout is
the benchmark's own statement of the architecture: four global leaves
and twelve per-layer leaves stacked over the depth.  Nothing here imports
the program.
"""
import functools

import jax
import jax.numpy as jnp

INIT_STD = 0.02

# (leaf, kind): "matrix" ~ N(0, std), "bias" ~ N(0, std), "scale" ~ 1 + N(0, std).
# Biases and scales are random too, so that a dropped bias or scale shows.
GLOBAL_LEAVES = (("wte", "matrix"), ("wpe", "matrix"),
                 ("lnf.weight", "scale"), ("lnf.bias", "bias"))
LAYER_LEAVES = (("ln1.weight", "scale"), ("ln1.bias", "bias"),
                ("attn.qkv.weight", "matrix"), ("attn.qkv.bias", "bias"),
                ("attn.out.weight", "matrix"), ("attn.out.bias", "bias"),
                ("ln2.weight", "scale"), ("ln2.bias", "bias"),
                ("mlp.up.weight", "matrix"), ("mlp.up.bias", "bias"),
                ("mlp.down.weight", "matrix"), ("mlp.down.bias", "bias"))


def shapes(model):
    """{leaf: shape} for a configuration's ``model`` group; per-layer
    leaves carry the depth as their first axis."""
    H, V = model["hidden_size"], model["vocab_size"]
    P, I = model["max_position_embeddings"], model["intermediate_size"]
    L = model["num_hidden_layers"]
    if model["num_attention_heads"] * model["head_dim"] != H:
        raise ValueError("heads x head_dim must equal hidden_size")
    return {
        "wte": (V, H), "wpe": (P, H), "lnf.weight": (H,), "lnf.bias": (H,),
        "ln1.weight": (L, H), "ln1.bias": (L, H),
        "attn.qkv.weight": (L, H, 3 * H), "attn.qkv.bias": (L, 3 * H),
        "attn.out.weight": (L, H, H), "attn.out.bias": (L, H),
        "ln2.weight": (L, H), "ln2.bias": (L, H),
        "mlp.up.weight": (L, H, I), "mlp.up.bias": (L, I),
        "mlp.down.weight": (L, I, H), "mlp.down.bias": (L, H),
    }


def seed_words(seed):
    """A seed of any size as two uint32 words (the driver's seeds pass
    2**31)."""
    seed = int(seed)
    return jnp.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                       jnp.uint32)


def _key(words):
    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def _leaf(key, index, kind, shape):
    noise = INIT_STD * jax.random.normal(jax.random.fold_in(key, index),
                                         shape, jnp.float32)
    return 1.0 + noise if kind == "scale" else noise


def _layer(model, key, i):
    """The twelve leaves of layer ``i`` (traced): each leaf's own key,
    folded with the layer's number."""
    sh = shapes(model)
    return {leaf: _leaf(jax.random.fold_in(key, 100 + j), i, kind,
                        sh[leaf][1:])
            for j, (leaf, kind) in enumerate(LAYER_LEAVES)}


@functools.partial(jax.jit, static_argnums=0)
def _make_globals(model_items, words):
    model = dict(model_items)
    return {leaf: _leaf(_key(words), j, kind, shapes(model)[leaf])
            for j, (leaf, kind) in enumerate(GLOBAL_LEAVES)}


@functools.partial(jax.jit, static_argnums=0)
def _make_layer(model_items, words, i):
    return _layer(dict(model_items), _key(words), i)


@functools.partial(jax.jit, static_argnums=0)
def _make_layers_stacked(model_items, words):
    model = dict(model_items)
    return jax.lax.map(lambda i: _layer(model, _key(words), i),
                       jnp.arange(model["num_hidden_layers"]))


def _static(model):
    return tuple(sorted((k, v) for k, v in model.items()
                        if isinstance(v, int)))


def make_stacked(model, seed):
    """{leaf: float32 array}, per-layer leaves stacked over the depth:
    the reference's form, made on the device in two jitted calls."""
    items, words = _static(model), seed_words(seed)
    return {**_make_globals(items, words),
            **_make_layers_stacked(items, words)}


def per_layer(model, seed):
    """The same values a group at a time, for a driver to put into the
    program's network without holding a second copy of the model:
    yields {``<leaf>`` or ``h.<i>.<leaf>``: array}, the global leaves
    first and then each layer."""
    items, words = _static(model), seed_words(seed)
    yield _make_globals(items, words)
    for i in range(model["num_hidden_layers"]):
        layer = _make_layer(items, words, jnp.int32(i))
        yield {f"h.{i}.{leaf}": v for leaf, v in layer.items()}


def make_per_layer(model, seed):
    """All of ``per_layer`` in one dict."""
    out = {}
    for group in per_layer(model, seed):
        out.update(group)
    return out
