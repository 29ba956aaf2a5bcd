"""The hybrid linear / sparse-latent model (``models/glm5_next.py``) and
the engine's recurrent-state cache kind against the benchmark's plain
reference (``benchmark/reference/glm5_next.py``) at a tiny size of the
same code: seeded weights, CPU, float32.

Tolerances: the program and the reference compute the same float32
function in a different order (the chunked delta rule against the
token-by-token one, the absorbed latent attention against the expanded
one, the engine's paged gathers): their logits agree to about 1e-6 of
logits of about 1.  The checks hold them to 2e-5; bfloat16 rounding of
any activation moves them by 1e-3 or more (asserted where it matters).
"""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import paddle_tpu as paddle  # noqa: E402
from paddle_tpu.inference import ServingEngine  # noqa: E402
from paddle_tpu.inference import kvcache  # noqa: E402
from paddle_tpu.models import glm5_next as gn  # noqa: E402
from paddle_tpu.models.generation import (StateCacheSpec,  # noqa: E402
                                          cache_kind)
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    DroplessMoELayer)
from paddle_tpu.incubate.distributed.models.moe.dropless import (  # noqa: E402
    swiglu)
from benchmark import weights_glm5_next as weights  # noqa: E402
from benchmark.families import glm5_next as family  # noqa: E402
from benchmark.reference import glm5_next as reference  # noqa: E402

L, S = gn.LINEAR, gn.SPARSE
# hidden 64; linear heads 4 x 16; sparse heads 4 x 16 over a latent of 24,
# an indexer keeping 4 blocks of 4 keys; 16 experts top-4, 8 held (4-11);
# a dense layer, then linear, sparse, linear; prefill pieces of 32
TINY = {"vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 4,
        "layer_types": [L, L, S, L],
        "mlp_layer_types": ["dense", "sparse", "sparse", "sparse"],
        "linear_num_heads": 4, "linear_head_dim": 16,
        "short_conv_kernel_size": 4, "gate_lower_bound": -5,
        "linear_gate_rank": 8, "num_attention_heads": 4, "q_lora_rank": 32,
        "qk_head_dim": 16, "v_head_dim": 16, "kv_lora_rank": 24,
        "index_n_heads": 2, "index_head_dim": 16, "index_topk": 16,
        "index_kpool": 4, "index_rope_dim": 8, "index_rope_theta": 10000,
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "n_routed_experts": 8, "router_num_experts": 16,
        "first_expert_held": 4, "num_experts_per_tok": 4,
        "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "swiglu_limit": 10, "max_position_embeddings": 1024,
        "rms_norm_eps": 1e-5, "prefill_piece": 32}
SEED = 3000000019
ATOL = 2e-5


def build(model=TINY, seed=SEED):
    """The program's network at ``model`` holding the seed's weights in
    float32 (the bfloat16 values lifted), and the reference's handle with
    the same values."""
    config = gn.GLM5NextConfig(
        **{k: model[k] for k in family._KEYS}, dtype="float32",
        layer_types=tuple(model["layer_types"]),
        mlp_layer_types=tuple(model["mlp_layer_types"]),
        n_routed_experts=model["router_num_experts"],
        experts_held=(model["first_expert_held"], model["n_routed_experts"]))
    net = gn.GLM5NextForCausalLM(config)
    family.put_weights(net, model, seed)
    for p in net.parameters():
        p._replace(p._value.astype(jnp.float32))
    net.eval()
    return net, weights.make_stacked(model, seed)


def row(n, seed=0, vocab=512):
    return np.random.RandomState(seed).randint(0, vocab, n).astype(np.int32)


@pytest.fixture(scope="module")
def tiny():
    return build()


def _call(net, ids, views, pos, **kw):
    """One forward of the program through cache views (numpy logits)."""
    def wrap(v):
        return type(v)(*(None if x is None else paddle.to_tensor(x)
                         for x in v))
    logits, new = net(paddle.to_tensor(ids), caches=[wrap(v) for v in views],
                      pos=paddle.to_tensor(pos), **kw)
    return np.asarray(logits._value), [
        type(v)(*(None if x is None else x._value for x in v)) for v in new]


def _manager(net, ids, steps, max_seq_len=128, slots=1):
    kv = kvcache.PagedKVManager(net.kv_cache_spec(), slots, max_seq_len, 8,
                                None, jnp.float32)
    kv.bind(0, kv.plan(ids, steps, steps))
    return kv


def _prefill(net, kv, ids, piece):
    """The engine's prefill of ``ids`` into slot 0 in programs of
    ``piece`` tokens (each padded to it); the last piece's logits at the
    last real token, and the pools."""
    table = jnp.asarray(kv.table)
    pools = kv.device_pools()
    for at in range(0, len(ids), piece):
        count = min(piece, len(ids) - at)
        padded = np.zeros((1, piece), np.int32)
        padded[0, :count] = ids[at:at + count]
        views = kvcache._layer_views(
            pools, table[:1], False, slot=jnp.asarray(0, jnp.int32),
            length=jnp.asarray(count, jnp.int32))
        logits, views = _call(net, padded, views, jnp.asarray(at, jnp.int32),
                              last=jnp.asarray(count - 1, jnp.int32))
        pools = kvcache._layer_pools(views, False)
    return logits[0, 0], pools


# -- (a) prefill, then decode, against the reference's full forward ----------

@pytest.mark.parametrize("prompt_len", [13, 70])     # one program; three
def test_cached_logits_match_the_reference(tiny, prompt_len):
    """Prefill (in pieces of 32 where longer) and 8 single-token steps
    through the state and the latent pages, against the reference's full
    forward; past 16 live keys the indexer's pick is at work."""
    net, w = tiny
    ids = row(prompt_len + 8, seed=prompt_len)
    kv = _manager(net, ids[:prompt_len], 8)
    want = np.asarray(reference.row_logits(TINY, w, ids))
    assert np.abs(want).max() > 0.05               # not a trivial agreement
    last, pools = _prefill(net, kv, ids[:prompt_len], 32)
    np.testing.assert_allclose(last, want[prompt_len - 1], atol=ATOL, rtol=0)
    table = jnp.asarray(kv.table)
    for t in range(prompt_len, prompt_len + 8):
        views = kvcache._layer_views(pools, table, False)
        logits, views = _call(net, ids[None, t:t + 1], views,
                              jnp.asarray([t], jnp.int32))
        pools = kvcache._layer_pools(views, False)
        np.testing.assert_allclose(logits[0, 0], want[t], atol=ATOL, rtol=0)
    # the bfloat16 witness is another function at this tolerance
    bf16 = np.asarray(reference.row_logits(TINY, w, ids, "bf16"))
    assert np.abs(bf16 - want).max() > 50 * ATOL


def test_prefill_in_pieces_equals_whole_prefill(tiny):
    """70 tokens as pieces of 32, 32 and 6 (each attending what the
    earlier cached, carrying the state) and as one program of 128: the
    same logits, states and latent rows."""
    net, _ = tiny
    ids = row(70, seed=3)
    whole_logits, whole = _prefill(net, _manager(net, ids, 1), ids, 128)
    piece_logits, pieces = _prefill(net, _manager(net, ids, 1), ids, 32)
    np.testing.assert_allclose(piece_logits, whole_logits, atol=ATOL, rtol=0)
    kinds = [cache_kind(s) for s in net.kv_cache_spec()]
    table = jnp.asarray(_manager(net, ids, 1).table[:1])
    for kind, a, b in zip(kinds, whole, pieces):
        if kind == "state":
            np.testing.assert_allclose(np.asarray(a.state[0]),
                                       np.asarray(b.state[0]), atol=1e-5)
            np.testing.assert_allclose(np.asarray(a.conv[0]),
                                       np.asarray(b.conv[0]), atol=1e-6)
            assert np.abs(np.asarray(a.state[0])).max() > 1e-2
        else:           # the 70 real rows (padding lands past them)
            np.testing.assert_allclose(
                np.asarray(kvcache.gather_latent(a[0], table))[0, :70],
                np.asarray(kvcache.gather_latent(b[0], table))[0, :70],
                atol=1e-5)


# -- (b) the delta rule's two forms, Sinkhorn ----------------------------------

def _delta_inputs(T, H=3, Dk=8, Dv=6, seed=0):
    rng = np.random.RandomState(seed)
    k = rng.randn(T, H, Dk)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    return tuple(jnp.asarray(a, jnp.float32) for a in (
        rng.randn(T, H, Dk) * 0.3, k, rng.randn(T, H, Dv),
        -5 * jax.nn.sigmoid(rng.randn(T, H, Dk) * 2 - 2),
        jax.nn.sigmoid(rng.randn(T, H)), rng.randn(H, Dk, Dv) * 0.5))


@pytest.mark.parametrize("T, chunk", [(37, 8), (37, 16), (5, 16)])
def test_chunked_delta_rule_equals_the_recurrence(T, chunk):
    """The WY-form chunks (padding past T included) against one step a
    token, from a state that is not zero, at the decays' lower bound."""
    q, k, v, g, beta, S0 = _delta_inputs(T)
    o, S = gn.kda_chunked(q, k, v, g, beta, S0, chunk)
    St, outs = S0[None], []
    for t in range(T):
        ot, St = gn.kda_step(q[None, t], k[None, t], v[None, t], g[None, t],
                             beta[None, t], St)
        outs.append(ot[0])
    np.testing.assert_allclose(np.asarray(o), np.asarray(jnp.stack(outs)),
                               atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(S), np.asarray(St[0]), atol=2e-5,
                               rtol=0)


def test_sinkhorn_is_doubly_stochastic():
    logits = jnp.asarray(np.random.RandomState(4).randn(50, 4, 4),
                         jnp.float32)
    m = np.asarray(gn.sinkhorn(logits, 20, 1e-6))
    assert (m > 0).all()
    # the last step normalises the columns; 20 rounds bring the rows to
    # within 1e-3 at logits of this spread
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(m, np.asarray(reference.sinkhorn(
        logits, 20, 1e-6)), atol=1e-7)


# -- (c) the indexer's pick ----------------------------------------------------

def test_indexer_attends_every_key_until_topk_then_exactly_the_pick():
    """Keys of 24 + 16 (latent | index key) in a cache of 64; queries at
    every position.  Up to 16 live keys every key is attended; past that
    the query's own block (the tail) and the 3 best earlier blocks by
    sum_j w_j ReLU(q_j . mean of the block's keys), and nothing else."""
    cfg = gn.glm5_next_tiny(dtype="float32")
    rng = np.random.RandomState(5)
    T, C, dI, nI, nH = 64, cfg.kv_lora_rank, cfg.index_head_dim, \
        cfg.index_n_heads, cfg.num_attention_heads
    # index keys and queries whose products are positive: no two blocks
    # tie at a score of 0, so the best three are one set
    cached = jnp.asarray(np.concatenate([rng.randn(1, T, C),
                                         rng.randn(1, T, dI) + 2], -1),
                         jnp.float32)
    q_lat = jnp.asarray(rng.randn(1, T, nH, C), jnp.float32)
    q_idx = jnp.asarray(np.abs(rng.randn(1, T, nI, dI)) + 0.5, jnp.float32)
    w_idx = jnp.asarray(rng.randn(1, T, nI), jnp.float32)
    positions = jnp.arange(T)[None]
    ctx, seen = gn.sparse_attend(q_lat, q_idx, w_idx, positions, cached, cfg)
    ctx, seen = np.asarray(ctx)[0], np.asarray(seen)[0]
    c = np.asarray(cached)[0]
    pooled = c[:, C:].reshape(T // 4, 4, dI).mean(1)
    for t in range(T):
        own = t // 4
        if t < 16:
            keys = np.arange(t + 1)
        else:
            score = np.einsum("jd,bd->jb", np.asarray(q_idx)[0, t], pooled)
            score = (np.maximum(score, 0) * np.asarray(w_idx)[0, t][:, None]
                     ).sum(0)[:own]
            best = np.sort(np.argsort(-score)[:3])
            keys = np.concatenate([np.arange(b * 4, b * 4 + 4) for b in best]
                                  + [np.arange(own * 4, t + 1)])
        assert seen[t] == len(keys), t
        s = np.einsum("hc,kc->hk", np.asarray(q_lat)[0, t], c[keys, :C]) \
            * cfg.qk_head_dim ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(ctx[t], p @ c[keys, :C], atol=1e-4,
                                   rtol=1e-4)


# -- (d) the expert shares, with the clamp -------------------------------------

def _expert_layer(held, whole=None, limit=0.1):
    paddle.seed(0)
    layer = DroplessMoELayer(64, 32, 16, 4, experts_held=held, scaling=2.5,
                             swiglu_limit=limit)
    if whole is not None:
        for name in ("router_weight", "router_bias", "shared_gate_up",
                     "shared_down"):
            getattr(layer, name)._replace(getattr(whole, name)._value)
        for name in ("experts_gate_up", "experts_down"):
            getattr(layer, name)._replace(
                getattr(whole, name)._value[held.start:held.stop])
    return layer


def test_the_eight_shares_with_the_clamp_add_up_to_the_uncut_layer():
    """Eight shares of 2 of 16 experts: their results, the shared expert
    counted once, add up to the whole layer's; each share matches the
    reference's expert layer; the clamp is at work (a limit of 0.1 on
    products of about 0.2)."""
    x = jnp.asarray(np.random.RandomState(2).randn(1, 40, 64), jnp.float32)
    whole = _expert_layer(range(16))
    want = np.asarray(whole(paddle.to_tensor(x))._value)[0]
    parts, shared = [], None
    for first in range(0, 16, 2):
        held = range(first, first + 2)
        layer = _expert_layer(held, whole)
        y = np.asarray(layer(paddle.to_tensor(x))._value)[0]
        lw = {"moe.router": layer.router_weight._value,
              "moe.bias": layer.router_bias._value,
              "moe.experts.gate_up": layer.experts_gate_up._value,
              "moe.experts.down": layer.experts_down._value,
              "moe.shared.gate_up": layer.shared_gate_up._value,
              "moe.shared.down": layer.shared_down._value}
        m = dict(TINY, n_routed_experts=2, first_expert_held=first,
                 swiglu_limit=0.1)
        np.testing.assert_allclose(
            y, np.asarray(reference._experts(x[0], lw, m, "highest")),
            atol=ATOL, rtol=0)
        shared = np.asarray(swiglu(x[0], layer.shared_gate_up._value,
                                   layer.shared_down._value, 0.1))
        parts.append(y - shared)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=3e-5, rtol=0)
    unclamped = np.asarray(_expert_layer(range(16), whole, None)(
        paddle.to_tensor(x))._value)[0]
    assert np.abs(unclamped - want).max() > 1e-3


# -- (e) the engine: slots, preemption, prefix -----------------------------------

def served(net, prompts, budget, **engine):
    eng = ServingEngine(net, kv_mode="paged", **engine)
    reqs = [eng.submit(p, max_new_tokens=budget) for p in prompts]
    eng.run()
    assert eng._kv.check()
    return [np.asarray(r.tokens, np.int32) for r in reqs], eng


KNOBS = dict(max_seq_len=128, chunk=4, page_size=8)


def test_engine_serves_the_reference_tokens(tiny):
    """Through submit / step: every served token is the reference's best
    at its position (a 70-token prompt is prefilled in 3 pieces)."""
    net, w = tiny
    prompts = [row(70, seed=7), row(9, seed=8)]
    tokens, eng = served(net, prompts, 12, num_slots=2, **KNOBS)
    assert eng.stats["prefill_pieces"] == 4 and eng.stats["prefills"] == 2
    for p, t in zip(prompts, tokens):
        full = np.concatenate([p, t])
        targets = np.zeros(len(full), np.int32)
        targets[len(p) - 1:-1] = t
        gaps = np.asarray(reference.next_token_gaps(TINY, w, full, targets))
        assert gaps[len(p) - 1:-1].max() < 1e-4


def test_a_reused_slot_starts_from_zero_state(tiny):
    """One slot serves A, then B: B's tokens are those of a fresh engine,
    though A left its state in the slot."""
    net, _ = tiny
    a, b = row(21, seed=11), row(30, seed=12)
    fresh, _ = served(net, [b], 10, num_slots=1, **KNOBS)
    eng = ServingEngine(net, kv_mode="paged", num_slots=1, **KNOBS)
    eng.submit(a, max_new_tokens=10)
    eng.run()
    left = [s for s in eng._kv.device_pools()
            if isinstance(s, kvcache.SlotState)]
    assert np.abs(np.asarray(left[0].state)).max() > 1e-2
    req = eng.submit(b, max_new_tokens=10)
    eng.run()
    assert list(req.tokens) == list(fresh[0])


def test_preemption_by_recompute_equals_never_being_evicted(tiny):
    """A pool too small for both requests: one is preempted mid-decode
    and resumes by recompute, its state rebuilt from position 0; both
    serve what an engine with room serves."""
    net, _ = tiny
    prompts = [row(6, seed=21), row(6, seed=22)]
    roomy, _ = served(net, prompts, 26, num_slots=2, **KNOBS)
    tight, eng = served(net, prompts, 26, num_slots=2, num_pages=7, **KNOBS)
    assert eng.stats["page_evictions"] >= 1
    for a, b in zip(roomy, tight):
        np.testing.assert_array_equal(a, b)


def test_a_preempted_request_past_the_largest_bucket_resumes_in_pieces(tiny):
    """The younger request, 70 tokens of prompt (past the largest bucket,
    64), is the one preempted: its resume of 79 tokens is prefilled in
    pieces, and both serve what an engine with room serves."""
    net, _ = tiny
    prompts = [row(6, seed=21), row(70, seed=23)]
    roomy, _ = served(net, prompts, 20, num_slots=2, **KNOBS)
    eng = ServingEngine(net, kv_mode="paged", num_slots=2, num_pages=15,
                        **KNOBS)
    assert eng.buckets[-1] == 64
    victims, evict = [], eng._evict

    def watched(slot):
        victims.append(eng.scheduler.active[slot].prompt.size)
        return evict(slot)
    eng._evict = watched
    reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
    eng.run()
    assert victims == [70]
    for r, want in zip(reqs, roomy):
        np.testing.assert_array_equal(np.asarray(r.tokens, np.int32), want)


def test_a_state_model_gets_no_prefix_hit(tiny):
    """Two requests sharing 16 tokens of prompt: no prefix is registered
    or hit (a state summarises the whole prefix), both serve as cold."""
    net, _ = tiny
    shared = row(16, seed=9)
    first = np.concatenate([shared, row(5, seed=10)])
    second = np.concatenate([shared, row(9, seed=11)])
    cold, _ = served(net, [second], 9, num_slots=2, **KNOBS)
    eng = ServingEngine(net, kv_mode="paged", num_slots=2, **KNOBS)
    assert not eng._kv.prefix_enabled
    eng.submit(first, max_new_tokens=3)
    eng.run()
    req = eng.submit(second, max_new_tokens=9)
    eng.run()
    stats = eng._kv.stats
    assert stats["prefix_hits"] == 0 and req.prefix_cached == 0
    assert stats["prefix_state_bypassed"] == 2
    assert list(req.tokens) == list(cold[0])


@pytest.mark.parametrize("knobs, mode", [
    ({"kv_mode": "dense"}, "kv_mode='dense'"),
    ({"kv_mode": "paged", "kv_dtype": "int8"}, "kv_dtype='int8'"),
    ({"kv_mode": "paged", "quant_mode": "int8"}, "quant_mode='int8'")])
def test_engine_modes_that_cannot_hold_a_state_layer_raise(tiny, knobs, mode):
    net, _ = tiny
    with pytest.raises(ValueError, match="latent/state cache layer") as e:
        ServingEngine(net, max_seq_len=128, **knobs)
    assert mode in str(e.value)


def test_state_pools_are_per_slot_and_cannot_be_exported(tiny):
    net, _ = tiny
    spec = net.kv_cache_spec()
    assert [cache_kind(s) for s in spec] == ["state", "state", "latent",
                                             "state"]
    assert spec[0] == StateCacheSpec(4, 16, 16, 3 * 4 * 16, 3)
    kv = kvcache.PagedKVManager(spec, 3, 64, 8, None, jnp.float32)
    state = kv.device_pools()[0]
    assert state.state.shape == (3, 4, 16, 16)
    assert state.state.dtype == jnp.float32 and state.conv.shape == (3, 3, 192)
    assert kv.state_bytes == 3 * 3 * (4 * 16 * 16 * 4 + 3 * 192 * 4)
    kv.bind(0, kv.plan(row(10), 2, 2))
    with pytest.raises(ValueError, match="state layer"):
        kv.export_pages(0)
