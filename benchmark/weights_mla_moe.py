"""Seeded weights of the ``mla_moe`` family, made on the device from the
seed, a layer at a time, IN BFLOAT16: the configuration states bfloat16
weights, the program holds one copy of them, and the plain reference
lifts the same bfloat16 values to float32 a layer at a time (4.5 billion
parameters are 18 GB in float32, more than the chip).

The layout is the benchmark's own statement of the architecture and
imports nothing of the program.  A routed expert's matrices are keyed by
its GLOBAL id, so the 32 experts a chip holds are the same whichever
chip's share is drawn (the share test adds the four quarters up).
"""
import jax
import jax.numpy as jnp

from benchmark.weights import seed_words

INIT_STD = 0.02
SELECT_STD = 0.01    # the selection bias: moves picks near the cut, unbalances no expert
DTYPE = jnp.bfloat16

GLOBAL_LEAVES = ("embed", "norm.weight", "head")
ATTENTION_LEAVES = ("ln1.weight", "attn.q", "attn.q_norm", "attn.kv_a",
                    "attn.kv_norm", "attn.kv_b", "attn.o", "ln2.weight")
# gate and up lie side by side in ONE leaf, ``[W_gate | W_up]`` along the
# output axis (how the program multiplies them: one matmul for both)
DENSE_LEAVES = ("mlp.gate_up", "mlp.down")
SPARSE_LEAVES = ("moe.router", "moe.bias", "moe.shared.gate_up",
                 "moe.shared.down")
EXPERT_LEAVES = ("moe.experts.gate_up", "moe.experts.down")
SCALES = ("norm.weight", "ln1.weight", "ln2.weight", "attn.q_norm",
          "attn.kv_norm")


def shapes(model):
    """{leaf: shape of one layer's (or the global) leaf}; an expert leaf
    is the shape of ONE expert."""
    H, V, nH = (model["hidden_size"], model["vocab_size"],
                model["num_attention_heads"])
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    C, I, Ie = (model["kv_lora_rank"], model["intermediate_size"],
                model["moe_intermediate_size"])
    Is = Ie * model["num_shared_experts"]
    return {
        "embed": (V, H), "norm.weight": (H,), "head": (H, V),
        "ln1.weight": (H,), "ln2.weight": (H,),
        "attn.q": (H, nH * (nope + rope)), "attn.q_norm": (nope + rope,),
        "attn.kv_a": (H, C + rope), "attn.kv_norm": (C,),
        "attn.kv_b": (C, nH * (nope + v)), "attn.o": (nH * v, H),
        "mlp.gate_up": (H, 2 * I), "mlp.down": (I, H),
        "moe.router": (H, model["router_num_experts"]),
        "moe.bias": (model["router_num_experts"],),
        "moe.shared.gate_up": (H, 2 * Is), "moe.shared.down": (Is, H),
        "moe.experts.gate_up": (H, 2 * Ie), "moe.experts.down": (Ie, H),
    }


def layer_leaves(model, i):
    """The leaves of layer ``i``: attention, then a dense or a sparse FFN."""
    sparse = i >= model["first_k_dense_replace"]
    return ATTENTION_LEAVES + (SPARSE_LEAVES + EXPERT_LEAVES if sparse
                               else DENSE_LEAVES)


def _key(words):
    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def _draw(key, leaf, shape):
    noise = jax.random.normal(key, shape, jnp.float32)
    if leaf in SCALES:
        return (1.0 + INIT_STD * noise).astype(DTYPE)
    std = SELECT_STD if leaf == "moe.bias" else INIT_STD
    return (std * noise).astype(DTYPE)


ALL_LEAVES = GLOBAL_LEAVES + ATTENTION_LEAVES + DENSE_LEAVES \
    + SPARSE_LEAVES + EXPERT_LEAVES


def _leaf_value(leaf, shape, experts, words, layer):
    """One leaf of one layer (``layer`` -1: a global leaf), traced.
    ``experts`` is None, or (first, count): the leaf is then stacked over
    those experts, each drawn from its global id."""
    key = jax.random.fold_in(jax.random.fold_in(
        _key(words), 1000 + ALL_LEAVES.index(leaf)), layer + 1)
    if experts is None:
        return _draw(key, leaf, shape)
    first, count = experts
    return jax.vmap(lambda e: _draw(jax.random.fold_in(key, e), leaf, shape))(
        first + jnp.arange(count))


_make_leaf = jax.jit(_leaf_value, static_argnums=(0, 1, 2))


def _held(model):
    return (model.get("first_expert_held", 0), model["num_experts"])


def global_leaves(model, seed):
    words, sh = seed_words(seed), shapes(model)
    return {leaf: _make_leaf(leaf, sh[leaf], None, words, -1)
            for leaf in GLOBAL_LEAVES}


def leaf(model, seed, i, name):
    """One leaf of layer ``i`` (-1: a global leaf)."""
    return _make_leaf(name, shapes(model)[name],
                      _held(model) if name in EXPERT_LEAVES else None,
                      seed_words(seed), i)


def layer(model, seed, i):
    """{leaf: bfloat16 array} of layer ``i``; the expert leaves stacked
    over the experts held here."""
    words, sh = seed_words(seed), shapes(model)
    return {leaf: _make_leaf(leaf, sh[leaf],
                             _held(model) if leaf in EXPERT_LEAVES else None,
                             words, i)
            for leaf in layer_leaves(model, i)}


def make_stacked(model, seed):
    """The reference's handle: ``{"globals": {...}, "layers": [{...}]}``
    in bfloat16, the values the program was given; the reference lifts a
    layer at a time."""
    return {"globals": global_leaves(model, seed),
            "layers": [layer(model, seed, i)
                       for i in range(model["num_hidden_layers"])]}
