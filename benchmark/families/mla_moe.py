"""The ``mla_moe`` family (``"family": "mla_moe"``): latent attention
over a paged latent cache, and a dropless sigmoid-routed expert layer
that is told which experts it holds.

    program    ``paddle_tpu.models.MLAMoEForCausalLM`` at a
               configuration's sizes, built in bfloat16 (ONE copy of the
               weights: the engine's cast copies nothing), holding a
               seed's weights; the benchmark's leaf of each parameter
    weights    ``benchmark/weights_mla_moe.py``: drawn on the device a
               layer at a time in bfloat16
    counts     ``benchmark/flops_mla_moe.py``

The plain reference is ``benchmark/reference/mla_moe.py``.  A serving
family: it has no training loss, no ``train_steps`` and no
``per_layer`` / ``make_per_layer`` (``put_weights`` draws a parameter at
a time; ``drivers/serve_open.py`` asks for nothing else).
"""
import re

from benchmark.flops_mla_moe import (decode_step_min_bytes,  # noqa: F401
                                     expert_matmul_min_seconds,
                                     prefill_attention_min_seconds,
                                     serve_flops)
from benchmark.weights_mla_moe import make_stacked  # noqa: F401

_GLOBAL = {"model.embed_tokens": "embed", "model.norm.weight": "norm.weight",
           "lm_head": "head"}
_LAYER = re.compile(r"^model\.layers\.(\d+)\.(.+)$")
_RENAMED = {
    "input_layernorm.weight": "ln1.weight",
    "post_attention_layernorm.weight": "ln2.weight",
    "self_attn.q_proj": "attn.q", "self_attn.q_norm": "attn.q_norm",
    "self_attn.kv_a_proj": "attn.kv_a", "self_attn.kv_norm": "attn.kv_norm",
    "self_attn.kv_b_proj": "attn.kv_b", "self_attn.o_proj": "attn.o",
    "mlp.router_weight": "moe.router", "mlp.router_bias": "moe.bias",
}
_FFN = {"mlp.gate_up": "mlp.gate_up", "mlp.down": "mlp.down",
        "mlp.experts_gate_up": "moe.experts.gate_up",
        "mlp.experts_down": "moe.experts.down",
        "mlp.shared_gate_up": "moe.shared.gate_up",
        "mlp.shared_down": "moe.shared.down"}


def leaf_name(program_name):
    """The benchmark's name of a parameter of the program's network."""
    if program_name in _GLOBAL:
        return _GLOBAL[program_name]
    m = _LAYER.match(program_name)
    rest = m and (_RENAMED.get(m.group(2)) or _FFN.get(m.group(2)))
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
    from paddle_tpu.models import MLAMoEConfig, MLAMoEForCausalLM
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "kv_lora_rank", "use_qk_norm",
            "intermediate_size", "moe_intermediate_size",
            "first_k_dense_replace", "num_experts_per_tok",
            "num_shared_experts", "routed_scaling_factor",
            "max_position_embeddings", "rms_norm_eps", "rope_theta",
            "rope_scaling")
    config = MLAMoEConfig(
        **{k: model[k] for k in keys}, dtype="bfloat16",
        num_experts=model["router_num_experts"],
        experts_held=(model.get("first_expert_held", 0),
                      model["num_experts"]))
    with paddle.LazyGuard():          # no initializer runs: zeros, then
        net = MLAMoEForCausalLM(config)   # the seed's weights
    put_weights(net, model, seed)
    return net


def build_loss():
    raise NotImplementedError("the mla_moe family is served, not trained: "
                              "it has no training loss")


def put_weights(net, model, seed):
    """Put the seed's weights into the network a parameter at a time (the
    chip never holds a second copy of anything)."""
    from benchmark import weights_mla_moe as weights
    known = set(weights.GLOBAL_LEAVES)
    for i in range(model["num_hidden_layers"]):
        known.update(f"h.{i}.{leaf}"
                     for leaf in weights.layer_leaves(model, i))
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
