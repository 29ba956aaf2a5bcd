"""What the drivers share about the program's GPT family: building the
network from a configuration file and putting the benchmark's seeded
weights into it.  The only place that knows the program's parameter
names."""
import re

from benchmark import weights

_GLOBAL = {"gpt.embeddings.word_embeddings.weight": "wte",
           "gpt.embeddings.position_embeddings.weight": "wpe",
           "gpt.final_norm.weight": "lnf.weight",
           "gpt.final_norm.bias": "lnf.bias"}
_LAYER = re.compile(r"^gpt\.layers\.(\d+)\.(.+)$")
_RENAMED = {"attn.qkv_proj": "attn.qkv", "attn.out_proj": "attn.out"}


def leaf_name(program_name):
    """The benchmark's name of a parameter of the program's network."""
    if program_name in _GLOBAL:
        return _GLOBAL[program_name]
    m = _LAYER.match(program_name)
    if not m:
        raise KeyError(f"no benchmark leaf for parameter {program_name!r}")
    rest = m.group(2)
    for old, new in _RENAMED.items():
        rest = rest.replace(old, new)
    return f"h.{m.group(1)}.{rest}"


def split_leaves(named_arrays):
    """{benchmark leaf: array} of {program name: array}; the fused QKV
    bias [3H] is three leaves (.q, .k, .v), as the reference counts it."""
    out = {}
    for name, array in named_arrays.items():
        leaf = leaf_name(name)
        if leaf.endswith("attn.qkv.bias"):
            for part, piece in zip("qkv", array.reshape(3, -1)):
                out[f"{leaf}.{part}"] = piece
        else:
            out[leaf] = array
    return out


def build_network(model, seed):
    """The program's ``GPTForPretraining`` at the configuration's sizes,
    holding the seed's weights."""
    from paddle_tpu.models import GPTConfig, GPTForPretraining
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "intermediate_size",
            "max_position_embeddings", "hidden_dropout_prob",
            "attention_probs_dropout_prob", "layer_norm_epsilon")
    net = GPTForPretraining(GPTConfig(**{k: model[k] for k in keys}))
    put_weights(net, model, seed)
    return net


def put_weights(net, model, seed):
    """Put ``weights.per_layer(model, seed)`` into the network, a layer
    at a time."""
    params = {leaf_name(n): p for n, p in net.named_parameters()}
    for group in weights.per_layer(model, seed):
        for leaf, value in group.items():
            params.pop(leaf).set_value(value)
    if params:
        raise ValueError("the program has parameters that are no leaves of "
                         f"the configuration: {sorted(params)[:6]}")
