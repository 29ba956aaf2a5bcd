"""Operations and bytes the ``mla_moe`` family needs, from ``model`` (the
configuration's sizes AS RUN: the experts held here, the slice of the
vocabulary) and ``obs`` (what the driver counted; the engine's device
counters as ``engine.moe_*``).  Pure arithmetic; no JAX.

What the expert layer costs depends on what the router chose, so the
counts read it from the counters: the share of the routed pairs that
landed on held experts (about ``held / router width``) and the held
experts a decode step touched in a layer.  Where a run has no counter
(a program without them), the share falls back to ``held / width`` and
the touched experts to the expectation under uniform routing.
"""


def _sizes(model):
    H, nH = model["hidden_size"], model["num_attention_heads"]
    nope, rope, v = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    C = model["kv_lora_rank"]
    L = model["num_hidden_layers"]
    dense = min(model["first_k_dense_replace"], L)
    return H, nH, nope, rope, v, C, L, dense, L - dense


def attention_params(model):
    """Matmul weights of one layer's attention: q, kv_a, kv_b, o.  The
    absorbed decode multiplies by the two halves of kv_b once each, the
    expanded prefill by kv_b whole: the same count a token."""
    H, nH, nope, rope, v, C, *_ = _sizes(model)
    return H * nH * (nope + rope) + H * (C + rope) \
        + C * nH * (nope + v) + nH * v * H


def expert_params(model):
    """Matmul weights of ONE routed expert (gate, up, down)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def pairs_here_share(model, obs):
    routed = obs.get("engine.moe_pairs_routed")
    if routed:
        return obs["engine.moe_pairs_here"] / routed
    return model["num_experts"] / model["router_num_experts"]


def experts_touched_per_layer_step(model, obs, slots=8):
    steps = obs.get("engine.moe_decode_layer_steps")
    if steps:
        return obs["engine.moe_experts_touched"] / steps
    miss = 1 - 1 / model["router_num_experts"]
    return model["num_experts"] * (
        1 - miss ** (slots * model["num_experts_per_tok"]))


def token_matmul_params(model, obs):
    """Matmul weights one token passes through in the blocks ON THIS
    CHIP: attention, the dense FFN, and in an expert layer the router,
    the shared expert and its picks that are held here."""
    H, *_, L, dense, sparse = _sizes(model)
    shared = 3 * H * model["moe_intermediate_size"] \
        * model["num_shared_experts"]
    picks_here = model["num_experts_per_tok"] * pairs_here_share(model, obs)
    return L * attention_params(model) \
        + dense * 3 * H * model["intermediate_size"] \
        + sparse * (H * model["router_num_experts"] + shared
                    + picks_here * expert_params(model))


def attention_forward_flops(model, context):
    """Causal attention of one token over ``context`` keys, all layers,
    in the expanded form (QK^T at nope + rope, PV at v): the least the
    function needs; the absorbed form trades more of these for fewer
    bytes."""
    _, nH, nope, rope, v, _, L, *_ = _sizes(model)
    return 2 * (nope + rope + v) * nH * context * L


def serve_flops(model, obs):
    """Forward FLOPs of the prompts prefilled and the positions decoded
    in the traced part of a serving window; the head once a prompt."""
    blocks = token_matmul_params(model, obs)
    head = model["hidden_size"] * model["vocab_size"]
    total = 0.0
    for n in obs["traced_prompt_lens"]:
        total += 2 * blocks * n + attention_forward_flops(
            model, n * (n + 1) / 2) + 2 * head
    for ctx in obs["traced_decode_positions"]:
        total += 2 * (blocks + head) + attention_forward_flops(model, ctx)
    return total


def decode_step_min_bytes(model, obs, weight_bytes=2, cache_bytes=2):
    """Least bytes one decode step moves: attention, dense-FFN, router and
    shared-expert weights once, the held experts the step touched, the
    head, and the live latent rows of the active slots."""
    H, _, _, rope, _, C, L, dense, sparse = _sizes(model)
    shared = 3 * H * model["moe_intermediate_size"] \
        * model["num_shared_experts"]
    weights = L * attention_params(model) \
        + dense * 3 * H * model["intermediate_size"] \
        + sparse * (H * model["router_num_experts"] + shared
                    + experts_touched_per_layer_step(model, obs)
                    * expert_params(model)) \
        + H * model["vocab_size"]
    cache = obs["traced_live_kv_tokens_mean"] * (C + rope) * L * cache_bytes
    return weights * weight_bytes + cache


def expert_matmul_min_seconds(model, obs, peaks):
    """The least time the traced window's expert matmuls need: a
    prefill's rows routed to held experts at the bf16 peak (compute
    bound), a decode step's touched experts' weights at the HBM peak
    (bandwidth bound).  The same work whatever implements it."""
    *_, sparse = _sizes(model)
    rows = sum(obs["traced_prompt_lens"]) * model["num_experts_per_tok"] \
        * pairs_here_share(model, obs) * sparse
    prefill = 2 * rows * expert_params(model) / peaks["bf16_flops_per_s"]
    decode = obs.get("traced_decode_steps", 0) * sparse \
        * experts_touched_per_layer_step(model, obs) \
        * expert_params(model) * 2 / peaks["hbm_bytes_per_s"]
    return prefill + decode


FLASH_MIN_PROMPT = 513    # shorter prompts pad to a bucket under the flash floor


def prefill_attention_min_seconds(model, obs, peaks):
    """Causal attention FLOPs, at the widths the function needs (QK^T at
    nope + rope, PV at v), of the traced prompts that reach the flash
    kernels, over the bf16 peak: what the kernels pad or recompute is not
    credited, so padding shows as a lower share."""
    need = sum(attention_forward_flops(model, n * (n + 1) / 2)
               for n in obs["traced_prompt_lens"] if n >= FLASH_MIN_PROMPT)
    return need / peaks["bf16_flops_per_s"]
