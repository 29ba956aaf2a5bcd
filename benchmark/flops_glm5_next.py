"""Operations and bytes the ``glm5_next`` family needs, from ``model`` (the
configuration's sizes AS RUN: the experts held here, the slice of the
vocabulary) and ``obs`` (what the driver counted; the engine's device
counters as ``engine.<key>``).  Pure arithmetic; no JAX.

The expert layer's share is read from the counters as in
``flops_mla_moe.py`` (pairs routed to held experts, held experts a decode
step touched), with the same fallbacks where a run has no counter.  A
sparse attention layer scores every live pooled key with its indexer and
attends at most ``index_topk`` keys; a linear attention layer reads and
writes its float32 state once a token in a decode step.
"""
LINEAR = "linear_attention"


def _layers(model):
    kinds = model["layer_types"]
    kda = sum(1 for k in kinds if k == LINEAR)
    sparse = sum(1 for k in model["mlp_layer_types"] if k == "sparse")
    return kda, len(kinds) - kda, len(kinds) - sparse, sparse


def kda_params(model):
    """Matmul weights of one linear attention layer: q, k, v; the gates'
    two ranks and beta; the output."""
    H, nh, D = (model["hidden_size"], model["linear_num_heads"],
                model["linear_head_dim"])
    R, C = model["linear_gate_rank"], nh * D
    return 3 * H * C + H * (2 * R + nh) + 2 * R * C + C * H


def dsa_params(model):
    """Matmul weights of one sparse latent attention layer and its
    indexer.  The absorbed form multiplies by the two halves of W_kvb
    once a token each: the same count as expanding a token's latent."""
    H, nH = model["hidden_size"], model["num_attention_heads"]
    Q, C = model["q_lora_rank"], model["kv_lora_rank"]
    dq, dv = model["qk_head_dim"], model["v_head_dim"]
    nI, dI = model["index_n_heads"], model["index_head_dim"]
    return H * Q + Q * nH * dq + H * C + C * nH * (dq + dv) + nH * dv * H \
        + Q * nI * dI + H * dI + H * nI


def hc_params(model):
    """One hyper-connection's map: the flattened streams to the pre,
    post and residual mixing weights."""
    n = model["hc_mult"]
    return n * model["hidden_size"] * (2 * n + n * n)


def expert_params(model):
    """Matmul weights of ONE routed expert (gate, up, down)."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def pairs_here_share(model, obs):
    routed = obs.get("engine.moe_pairs_routed")
    if routed:
        return obs["engine.moe_pairs_here"] / routed
    return model["n_routed_experts"] / model["router_num_experts"]


def experts_touched_per_layer_step(model, obs, slots=8):
    steps = obs.get("engine.moe_decode_layer_steps")
    if steps:
        return obs["engine.moe_experts_touched"] / steps
    miss = 1 - 1 / model["router_num_experts"]
    return model["n_routed_experts"] * (
        1 - miss ** (slots * model["num_experts_per_tok"]))


def _weights_but_experts(model):
    """Matmul weights every token passes through on this chip, the
    routed experts and the head left out."""
    H = model["hidden_size"]
    kda, dsa, dense, sparse = _layers(model)
    shared = 3 * H * model["moe_intermediate_size"] \
        * model["n_shared_experts"]
    return kda * kda_params(model) + dsa * dsa_params(model) \
        + 2 * (kda + dsa) * hc_params(model) \
        + dense * 3 * H * model["intermediate_size"] \
        + sparse * (H * model["router_num_experts"] + shared)


def mixing_flops(model, context):
    """The sequence mixing of one token at position ``context - 1``, all
    layers: a linear layer's recurrence (k^T S, the rank-one update,
    S^T q); a sparse layer's indexer over the pooled keys up to it and
    its attention (q.k at qk_head_dim, p.v at v_head_dim) over at most
    ``index_topk`` keys."""
    kda, dsa, *_ = _layers(model)
    state = model["linear_num_heads"] * model["linear_head_dim"] ** 2
    blocks = -(-context // model["index_kpool"])
    index = 2 * model["index_n_heads"] * model["index_head_dim"] * blocks
    keys = min(context, model["index_topk"])
    attend = 2 * model["num_attention_heads"] \
        * (model["qk_head_dim"] + model["v_head_dim"]) * keys
    return kda * 6 * state + dsa * (index + attend)


def token_flops(model, obs, context):
    """Forward FLOPs of one token at position ``context - 1`` without
    the head."""
    *_, sparse = _layers(model)
    picks = model["num_experts_per_tok"] * pairs_here_share(model, obs)
    return 2 * (_weights_but_experts(model)
                + sparse * picks * expert_params(model)) \
        + mixing_flops(model, context)


def prompt_flops(model, obs, n):
    """A prompt of ``n`` tokens prefilled, the head once."""
    kda, dsa, *_ = _layers(model)
    per_token = token_flops(model, obs, 1) - mixing_flops(model, 1)
    # the mixing summed over positions 1..n in closed form
    state = model["linear_num_heads"] * model["linear_head_dim"] ** 2
    kp, top = model["index_kpool"], model["index_topk"]
    q, r = divmod(n, kp)
    blocks = kp * q * (q + 1) / 2 + r * (q + 1)     # sum of ceil(c / kp)
    index = 2 * model["index_n_heads"] * model["index_head_dim"] * blocks
    keys = n * (n + 1) / 2 if n <= top \
        else top * (top + 1) / 2 + (n - top) * top
    attend = 2 * model["num_attention_heads"] \
        * (model["qk_head_dim"] + model["v_head_dim"]) * keys
    head = 2 * model["hidden_size"] * model["vocab_size"]
    return n * per_token + kda * 6 * state * n + dsa * (index + attend) \
        + head


def serve_flops(model, obs):
    """Forward FLOPs of the prompts prefilled and the positions decoded
    in the traced part of a serving window; the head once a prompt."""
    head = 2 * model["hidden_size"] * model["vocab_size"]
    total = sum(prompt_flops(model, obs, n)
                for n in obs["traced_prompt_lens"])
    for ctx in obs["traced_decode_positions"]:
        total += token_flops(model, obs, ctx + 1) + head
    return total


def prefill_programs(model, n):
    """Prefill programs a prompt of ``n`` tokens takes: one, or a piece
    of ``prefill_piece`` tokens at a time."""
    return max(1, -(-n // model["prefill_piece"]))


def prefill_flops_per_program(model, obs):
    """The traced prompts' prefill FLOPs over the prefill programs they
    took: what one prefill program of the window does on average."""
    lens = obs["traced_prompt_lens"]
    if not lens:
        return None
    return sum(prompt_flops(model, obs, n) for n in lens) \
        / sum(prefill_programs(model, n) for n in lens)


def decode_step_min_bytes(model, obs, weight_bytes=2, cache_bytes=2):
    """Least bytes one decode step moves: every matmul weight but the
    routed experts once, the held experts the step touched, the head;
    each active slot's linear-attention states read and written (float32)
    with their convolution tails; in each sparse layer every live
    token's index key (the indexer scores them all) and the latent rows
    of the keys attended (at most ``index_topk`` a slot)."""
    H = model["hidden_size"]
    kda, dsa, dense, sparse = _layers(model)
    weights = _weights_but_experts(model) \
        + sparse * experts_touched_per_layer_step(model, obs) \
        * expert_params(model) + H * model["vocab_size"]
    steps = obs.get("traced_decode_steps") or 1
    positions = obs["traced_decode_positions"]
    slots = len(positions) / steps
    nh, D = model["linear_num_heads"], model["linear_head_dim"]
    tail = (model["short_conv_kernel_size"] - 1) * 3 * nh * D
    state = 2 * slots * kda * (nh * D * D * 4 + tail * cache_bytes)
    live = sum(p + 1 for p in positions) / steps
    attended = sum(min(p + 1, model["index_topk"]) for p in positions) \
        / steps
    rows = dsa * (live * model["index_head_dim"]
                  + attended * model["kv_lora_rank"]) * cache_bytes
    return weights * weight_bytes + state + rows


def expert_matmul_min_seconds(model, obs, peaks):
    """The least time the traced window's expert matmuls need: a
    prefill's rows routed to held experts at the bf16 peak (compute
    bound), a decode step's touched experts' weights at the HBM peak
    (bandwidth bound)."""
    *_, sparse = _layers(model)
    rows = sum(obs["traced_prompt_lens"]) * model["num_experts_per_tok"] \
        * pairs_here_share(model, obs) * sparse
    prefill = 2 * rows * expert_params(model) / peaks["bf16_flops_per_s"]
    decode = obs.get("traced_decode_steps", 0) * sparse \
        * experts_touched_per_layer_step(model, obs) \
        * expert_params(model) * 2 / peaks["hbm_bytes_per_s"]
    return prefill + decode
