"""The ``glm5_next`` family (``"family": "glm5_next"``): linear attention
with a recurrent state a slot (the gated delta rule), sparse latent
attention over the keys an indexer picks, a hyper-connected residual of
several streams, and a dropless sigmoid-routed expert layer that is told
which experts it holds.

    program    ``paddle_tpu.models.GLM5NextForCausalLM`` at a
               configuration's sizes, built in bfloat16 (one copy of the
               weights), holding a seed's weights
    weights    ``benchmark/weights_glm5_next.py``: drawn on the device a
               leaf at a time in bfloat16
    counts     ``benchmark/flops_glm5_next.py``

The plain reference is ``benchmark/reference/glm5_next.py``.  A serving
family: no training loss and no ``train_steps``.
"""
import re

from benchmark.flops_glm5_next import (decode_step_min_bytes,  # noqa: F401
                                       expert_matmul_min_seconds,
                                       prefill_flops_per_program,
                                       serve_flops)
from benchmark.weights_glm5_next import make_stacked  # noqa: F401

_GLOBAL = {"model.embed_tokens": "embed", "model.norm.weight": "norm.weight",
           "lm_head": "head"}
_LAYER = re.compile(r"^model\.layers\.(\d+)\.(.+)$")
_RENAMED = {
    "attn_hc.phi": "hc1.phi", "attn_hc.bias": "hc1.bias",
    "attn_hc.alpha": "hc1.alpha", "input_layernorm.weight": "ln1.weight",
    "ffn_hc.phi": "hc2.phi", "ffn_hc.bias": "hc2.bias",
    "ffn_hc.alpha": "hc2.alpha",
    "post_attention_layernorm.weight": "ln2.weight",
    "self_attn.qkv_proj": "kda.qkv", "self_attn.conv": "kda.conv",
    "self_attn.gate_a": "kda.gate_a", "self_attn.f_b": "kda.f_b",
    "self_attn.g_b": "kda.g_b", "self_attn.a_log": "kda.a_log",
    "self_attn.dt_bias": "kda.dt_bias", "self_attn.o_norm": "kda.o_norm",
    "self_attn.o_proj": "attn.o",
    "self_attn.q_a_proj": "dsa.q_a", "self_attn.q_a_norm": "dsa.q_a_norm",
    "self_attn.q_b_proj": "dsa.q_b", "self_attn.kv_a_proj": "dsa.kv_a",
    "self_attn.kv_norm": "dsa.kv_norm", "self_attn.kv_b_proj": "dsa.kv_b",
    "self_attn.index_q": "idx.q", "self_attn.index_k": "idx.k",
    "self_attn.index_k_norm": "idx.k_norm",
    "self_attn.index_k_bias": "idx.k_bias", "self_attn.index_w": "idx.w",
    "mlp.gate_up": "mlp.gate_up", "mlp.down": "mlp.down",
    "mlp.router_weight": "moe.router", "mlp.router_bias": "moe.bias",
    "mlp.experts_gate_up": "moe.experts.gate_up",
    "mlp.experts_down": "moe.experts.down",
    "mlp.shared_gate_up": "moe.shared.gate_up",
    "mlp.shared_down": "moe.shared.down",
}
# the architecture's keys the program's configuration takes as they are
_KEYS = ("vocab_size", "hidden_size", "linear_num_heads", "linear_head_dim",
         "short_conv_kernel_size", "gate_lower_bound", "linear_gate_rank",
         "num_attention_heads", "q_lora_rank", "qk_head_dim", "v_head_dim",
         "kv_lora_rank", "index_n_heads", "index_head_dim", "index_topk",
         "index_kpool", "index_rope_dim", "index_rope_theta", "hc_mult",
         "hc_sinkhorn_iters", "hc_eps", "intermediate_size",
         "moe_intermediate_size", "num_experts_per_tok", "n_shared_experts",
         "routed_scaling_factor", "swiglu_limit", "max_position_embeddings",
         "rms_norm_eps", "prefill_piece")


def leaf_name(program_name):
    """The benchmark's name of a parameter of the program's network."""
    if program_name in _GLOBAL:
        return _GLOBAL[program_name]
    m = _LAYER.match(program_name)
    rest = m and _RENAMED.get(m.group(2))
    if not rest:
        raise KeyError(f"no benchmark leaf for parameter {program_name!r}")
    return f"h.{m.group(1)}.{rest}"


def split_leaves(named_arrays):
    """{benchmark leaf: array} of {program name: array}: one to one."""
    return {leaf_name(name): array for name, array in named_arrays.items()}


def build_network(model, seed):
    """The program's network at the configuration's sizes, its
    parameters created in bfloat16 and holding the seed's weights."""
    import paddle_tpu as paddle
    from paddle_tpu.models import GLM5NextConfig, GLM5NextForCausalLM
    config = GLM5NextConfig(
        **{k: model[k] for k in _KEYS}, dtype="bfloat16",
        layer_types=tuple(model["layer_types"]),
        mlp_layer_types=tuple(model["mlp_layer_types"]),
        n_routed_experts=model["router_num_experts"],
        experts_held=(model.get("first_expert_held", 0),
                      model["n_routed_experts"]))
    with paddle.LazyGuard():          # no initializer runs: zeros, then
        net = GLM5NextForCausalLM(config)     # the seed's weights
    put_weights(net, model, seed)
    return net


def build_loss():
    raise NotImplementedError("the glm5_next family is served, not "
                              "trained: it has no training loss")


def put_weights(net, model, seed):
    """Put the seed's weights into the network a parameter at a time (the
    chip never holds a second copy of anything)."""
    from benchmark import weights_glm5_next as weights
    known = {f"h.{i}.{leaf}" if i >= 0 else leaf
             for i in range(-1, len(model["layer_types"]))
             for leaf in weights.shapes(model, i)}
    unknown = []
    for name, p in net.named_parameters():
        leaf = leaf_name(name)
        if leaf not in known:
            unknown.append(name)
            continue
        known.discard(leaf)
        i, short = -1, leaf
        if leaf.startswith("h."):
            _, i, short = leaf.split(".", 2)
        p._replace(weights.leaf(model, seed, int(i), short))
    if unknown or known:
        raise ValueError(
            "the program's parameters and the configuration's leaves "
            f"differ: program only {sorted(unknown)[:6]}, configuration "
            f"only {sorted(known)[:6]}")
