"""Operations and bytes that the algorithm needs, from shapes alone, and
the table of peaks.  What the program actually executes (recomputed
scores, padded buckets, empty slots) is never credited.

``model`` is a configuration file's ``model`` group.
"""
import json
import os

_PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")


def peaks(device_kind):
    """Published peaks of ``device_kind`` from ``peaks.json``; a device
    that is not in the table is an error, never a default."""
    with open(_PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table:
        raise LookupError(
            f"no published peaks for device_kind {device_kind!r} (known: "
            f"{sorted(table)}); add a sourced row to benchmark/peaks.json")
    return table[device_kind]


def matmul_params(model):
    """(weights in the blocks' matmuls, weights in the output head)."""
    H, I = model["hidden_size"], model["intermediate_size"]
    blocks = model["num_hidden_layers"] * (4 * H * H + 2 * H * I)
    return blocks, model["vocab_size"] * H


def attention_forward_flops(model, context):
    """Causal attention of one token over ``context`` keys, all layers:
    QK^T and PV, 2 FLOPs per multiply-add."""
    return 4 * context * model["hidden_size"] * model["num_hidden_layers"]


def train_flops_per_token(model, seq_len):
    """Forward and backward of one token in a row of ``seq_len``: 6 per
    matmul weight, and attention counted causal (a token sees
    ``(seq_len + 1) / 2`` keys on average): two matmuls forward, four
    backward.  No recompute."""
    blocks, head = matmul_params(model)
    return 6 * (blocks + head) + \
        3 * attention_forward_flops(model, (seq_len + 1) / 2)


def attention_train_flops(model, batch, seq_len):
    """The attention share of one training step, as above."""
    return batch * seq_len * 3 * attention_forward_flops(
        model, (seq_len + 1) / 2)


def serve_flops(model, prompt_lens, positions_decoded):
    """Forward FLOPs of serving: every prompt token through the blocks
    with its causal context, the head once per prompt (only its last
    position is sampled), and each decoded token through blocks and head
    over its context.  ``positions_decoded`` lists the position (keys
    seen, itself included) of every decode-step token."""
    blocks, head = matmul_params(model)
    total = 0.0
    for n in prompt_lens:
        total += 2 * blocks * n + attention_forward_flops(
            model, n * (n + 1) / 2) + 2 * head
    for ctx in positions_decoded:
        total += 2 * (blocks + head) + attention_forward_flops(model, ctx)
    return total


def decode_step_min_bytes(model, live_kv_tokens, weight_bytes=2, kv_bytes=2):
    """Least bytes one decode step moves: every matmul weight once, and
    the keys and values of the live tokens of the active slots once."""
    blocks, head = matmul_params(model)
    kv = 2 * model["hidden_size"] * model["num_hidden_layers"] * kv_bytes
    return (blocks + head) * weight_bytes + live_kv_tokens * kv
