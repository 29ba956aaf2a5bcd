"""The GPT family: everything the harness, the drivers and the readers
need to know about one kind of model, found through a configuration
file's ``"family": "gpt"``.

    program    the program's network at a configuration's sizes holding a
               seed's weights, its training loss, and the benchmark's
               leaf name of each of its parameters (the only place that
               knows the program's names)
    weights    ``benchmark/weights.py``: the seeded weights, stacked for
               the reference and a group at a time for the program
    counts     ``benchmark/flops.py``: operations and bytes the algorithm
               needs, from ``model`` (the configuration's sizes) and
               ``obs`` (what the driver counted in the run)

The family's plain reference is the file the configuration names under
``"reference"`` (``benchmark/reference/gpt.py``); the harness loads it
beside the family.
"""
import re

from benchmark import flops
from benchmark.weights import make_per_layer, make_stacked, per_layer  # noqa: F401

_GLOBAL = {"gpt.embeddings.word_embeddings.weight": "wte",
           "gpt.embeddings.position_embeddings.weight": "wpe",
           "gpt.final_norm.weight": "lnf.weight",
           "gpt.final_norm.bias": "lnf.bias"}
_LAYER = re.compile(r"^gpt\.layers\.(\d+)\.(.+)$")
_RENAMED = {"attn.qkv_proj": "attn.qkv", "attn.out_proj": "attn.out"}


# -- program ------------------------------------------------------------------

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


def build_loss():
    """The program's training loss of this family's network."""
    from paddle_tpu.models import GPTPretrainingCriterion
    return GPTPretrainingCriterion()


def put_weights(net, model, seed):
    """Put ``per_layer(model, seed)`` into the network, a layer at a
    time."""
    params = {leaf_name(n): p for n, p in net.named_parameters()}
    for group in per_layer(model, seed):
        for leaf, value in group.items():
            params.pop(leaf).set_value(value)
    if params:
        raise ValueError("the program has parameters that are no leaves of "
                         f"the configuration: {sorted(params)[:6]}")


# -- counts: f(model, obs), obs being the driver's counters of the run ---------

def train_flops_per_token(model, obs):
    """FLOPs forward and backward of one trained token."""
    return flops.train_flops_per_token(model, obs["seq_len"])


def attention_train_flops(model, obs):
    """Attention's FLOPs of one training step."""
    return flops.attention_train_flops(model, obs["batch"], obs["seq_len"])


def serve_flops(model, obs):
    """Forward FLOPs of the prompts prefilled and the positions decoded
    in the traced part of a serving window."""
    return flops.serve_flops(model, obs["traced_prompt_lens"],
                             obs["traced_decode_positions"])


def decode_step_min_bytes(model, obs):
    """Least bytes of one decode step over the traced steps' mean of live
    cached tokens."""
    return flops.decode_step_min_bytes(model,
                                       obs["traced_live_kv_tokens_mean"])
