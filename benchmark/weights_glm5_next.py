"""Seeded weights of the ``glm5_next`` family, made on the device from the
seed a leaf at a time IN BFLOAT16: the configuration states bfloat16
weights and the program holds one copy of them.  The plain reference
draws the same leaves again a layer at a time (``make_stacked`` hands it
a lazy handle): 4.7 billion parameters are 9.4 GB in bfloat16, and the
reference's float32 rows of 33,792 positions by 4 residual streams need
the rest of the chip.

The layout is the benchmark's own statement of the architecture and
imports nothing of the program.  A routed expert's matrices are keyed by
its GLOBAL id, so the experts a chip holds are the same whichever chip's
share is drawn.
"""
import jax
import jax.numpy as jnp

from benchmark.weights import seed_words

INIT_STD = 0.02
DTYPE = jnp.bfloat16
LINEAR, SPARSE = "linear_attention", "deepseek_sparse_attention"

GLOBAL_LEAVES = ("embed", "norm.weight", "head")
# each sublayer reads and writes the streams through its own
# hyper-connection: hc1 around attention, hc2 around the FFN
BLOCK_LEAVES = ("hc1.phi", "hc1.bias", "hc1.alpha", "ln1.weight",
                "hc2.phi", "hc2.bias", "hc2.alpha", "ln2.weight")
# [W_fa | W_ga | W_b] side by side in ``kda.gate_a``
KDA_LEAVES = ("kda.qkv", "kda.conv", "kda.gate_a", "kda.f_b", "kda.g_b",
              "kda.a_log", "kda.dt_bias", "kda.o_norm", "attn.o")
DSA_LEAVES = ("dsa.q_a", "dsa.q_a_norm", "dsa.q_b", "dsa.kv_a",
              "dsa.kv_norm", "dsa.kv_b", "attn.o", "idx.q", "idx.k",
              "idx.k_norm", "idx.k_bias", "idx.w")
DENSE_LEAVES = ("mlp.gate_up", "mlp.down")
SPARSE_LEAVES = ("moe.router", "moe.bias", "moe.shared.gate_up",
                 "moe.shared.down")
EXPERT_LEAVES = ("moe.experts.gate_up", "moe.experts.down")
ALL_LEAVES = tuple(dict.fromkeys(
    GLOBAL_LEAVES + BLOCK_LEAVES + KDA_LEAVES + DSA_LEAVES + DENSE_LEAVES
    + SPARSE_LEAVES + EXPERT_LEAVES))          # attn.o once
# (mean, std) of a leaf's draw where it is not N(0, INIT_STD): norm
# scales and the maps' alphas about 1; the short convolution as a 4-tap
# filter; dt_bias spread so that the decays run from about 0.99 a token
# to about 0.08 (log alpha = -5 sigmoid(.)); the selection bias small
DRAWS = {leaf: (1.0, INIT_STD) for leaf in (
    "norm.weight", "ln1.weight", "ln2.weight", "hc1.alpha", "hc2.alpha",
    "kda.o_norm", "dsa.q_a_norm", "dsa.kv_norm", "idx.k_norm")}
DRAWS.update({"kda.conv": (0.0, 0.5), "kda.dt_bias": (-3.0, 1.5),
              "moe.bias": (0.0, 0.01)})


def shapes(model, i):
    """{leaf: shape} of layer ``i`` (-1: the global leaves); an expert
    leaf is the shape of ONE expert."""
    H, V = model["hidden_size"], model["vocab_size"]
    if i < 0:
        return {"embed": (V, H), "norm.weight": (H,), "head": (H, V)}
    n = model["hc_mult"]
    maps = 2 * n + n * n
    out = {"hc1.phi": (n * H, maps), "hc1.bias": (maps,), "hc1.alpha": (3,),
           "ln1.weight": (H,), "hc2.phi": (n * H, maps),
           "hc2.bias": (maps,), "hc2.alpha": (3,), "ln2.weight": (H,)}
    if model["layer_types"][i] == LINEAR:
        nh, D, R = (model["linear_num_heads"], model["linear_head_dim"],
                    model["linear_gate_rank"])
        C = nh * D
        out.update({"kda.qkv": (H, 3 * C),
                    "kda.conv": (model["short_conv_kernel_size"], 3 * C),
                    "kda.gate_a": (H, 2 * R + nh), "kda.f_b": (R, C),
                    "kda.g_b": (R, C), "kda.a_log": (nh,),
                    "kda.dt_bias": (C,), "kda.o_norm": (D,),
                    "attn.o": (C, H)})
    else:
        nH, dq, dv = (model["num_attention_heads"], model["qk_head_dim"],
                      model["v_head_dim"])
        Q, C = model["q_lora_rank"], model["kv_lora_rank"]
        nI, dI = model["index_n_heads"], model["index_head_dim"]
        out.update({"dsa.q_a": (H, Q), "dsa.q_a_norm": (Q,),
                    "dsa.q_b": (Q, nH * dq), "dsa.kv_a": (H, C),
                    "dsa.kv_norm": (C,), "dsa.kv_b": (C, nH * (dq + dv)),
                    "attn.o": (nH * dv, H), "idx.q": (Q, nI * dI),
                    "idx.k": (H, dI), "idx.k_norm": (dI,),
                    "idx.k_bias": (dI,), "idx.w": (H, nI)})
    Ie = model["moe_intermediate_size"]
    if model["mlp_layer_types"][i] == "sparse":
        Is = Ie * model["n_shared_experts"]
        E = model["router_num_experts"]
        out.update({"moe.router": (H, E), "moe.bias": (E,),
                    "moe.shared.gate_up": (H, 2 * Is),
                    "moe.shared.down": (Is, H),
                    "moe.experts.gate_up": (H, 2 * Ie),
                    "moe.experts.down": (Ie, H)})
    else:
        I = model["intermediate_size"]
        out.update({"mlp.gate_up": (H, 2 * I), "mlp.down": (I, H)})
    return out


def _key(words):
    return jax.random.fold_in(jax.random.PRNGKey(words[0]), words[1])


def _draw(key, leaf, shape):
    mean, std = DRAWS.get(leaf, (0.0, INIT_STD))
    return (mean + std * jax.random.normal(key, shape, jnp.float32)
            ).astype(DTYPE)


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
    return (model.get("first_expert_held", 0), model["n_routed_experts"])


def leaf(model, seed, i, name):
    """One leaf of layer ``i`` (-1: a global leaf)."""
    return _make_leaf(name, shapes(model, i)[name],
                      _held(model) if name in EXPERT_LEAVES else None,
                      seed_words(seed), i)


def layer(model, seed, i):
    """{leaf: bfloat16 array} of layer ``i`` (-1: the global leaves); the
    expert leaves stacked over the experts held here."""
    return {name: leaf(model, seed, i, name) for name in shapes(model, i)}


class _Layers:
    """The layers' leaves, drawn when asked for: the reference holds one
    layer at a time."""

    def __init__(self, model, seed):
        self.model, self.seed = model, seed

    def __len__(self):
        return len(self.model["layer_types"])

    def __getitem__(self, i):
        if not 0 <= i < len(self):
            raise IndexError(i)
        return layer(self.model, self.seed, i)


def make_stacked(model, seed):
    """The reference's handle: ``{"globals": {...}, "layers": ...}``, the
    bfloat16 values the program was given; ``layers[i]`` draws layer i."""
    return {"globals": layer(model, seed, -1),
            "layers": _Layers(model, seed)}
