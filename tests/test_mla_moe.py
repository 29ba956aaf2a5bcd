"""The latent-attention / dropless-expert model (``models/mla_moe.py``,
``incubate/.../moe/dropless.py``) against the benchmark's plain reference
(``benchmark/reference/mla_moe.py``) at a tiny size of the same code:
seeded weights, CPU, float32."""
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
from paddle_tpu.models import MLAMoEConfig, MLAMoEForCausalLM  # noqa: E402
from paddle_tpu.models import mla_moe as mm  # noqa: E402
from paddle_tpu.models.generation import LatentCacheSpec  # noqa: E402
from paddle_tpu.incubate.distributed.models.moe import (  # noqa: E402
    DroplessMoELayer)
from paddle_tpu.incubate.distributed.models.moe.dropless import (  # noqa: E402
    route, swiglu)
from paddle_tpu.observability import devcounters  # noqa: E402
from benchmark import weights_mla_moe as weights  # noqa: E402
from benchmark.families import mla_moe as family  # noqa: E402
from benchmark.reference import mla_moe as reference  # noqa: E402

SCALING = {"type": "deepseek_yarn", "factor": 4, "beta_fast": 32,
           "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
           "original_max_position_embeddings": 64}
# hidden 64, 4 heads of 24 + 8 / 16, latent 32, 8 experts top-2, 3 layers
TINY = {"vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 3,
        "num_attention_heads": 4, "qk_nope_head_dim": 24,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32,
        "use_qk_norm": True, "intermediate_size": 128,
        "moe_intermediate_size": 32, "first_k_dense_replace": 1,
        "num_experts": 8, "router_num_experts": 8, "first_expert_held": 0,
        "num_experts_per_tok": 2, "num_shared_experts": 1,
        "routed_scaling_factor": 2.5, "max_position_embeddings": 512,
        "rms_norm_eps": 1e-6, "rope_theta": 10000, "rope_scaling": SCALING}
SEED = 3000000019


def lifted(w):
    """The reference's handle with its bfloat16 leaves as float32 (the
    same values): the program under test is float32 too."""
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), w)


def build(model=TINY, seed=SEED, dtype="float32"):
    """The program's network at ``model`` holding the seed's weights, in
    float32 (the bfloat16 values lifted), and the reference's handle."""
    keys = [f.name for f in MLAMoEConfig.__dataclass_fields__.values()]
    config = MLAMoEConfig(
        **{k: model[k] for k in keys if k in model and k != "num_experts"},
        num_experts=model["router_num_experts"], dtype=dtype,
        experts_held=(model["first_expert_held"], model["num_experts"]))
    net = MLAMoEForCausalLM(config)
    family.put_weights(net, model, seed)
    for p in net.parameters():
        p._replace(p._value.astype(dtype))
    net.eval()
    return net, lifted(weights.make_stacked(model, seed))


def row(n, seed=0, vocab=512):
    return np.random.RandomState(seed).randint(0, vocab, n).astype(np.int32)


@pytest.fixture(scope="module")
def tiny():
    return build()


# -- (a) the full forward, and (f) the selection bias matters ------------------

def test_full_forward_matches_the_reference(tiny):
    net, w = tiny
    ids = row(70)
    got = np.asarray(net(paddle.to_tensor(ids[None]))._value)[0]
    want = np.asarray(reference.row_logits(TINY, w, ids))
    assert np.abs(want).max() > 0.05           # not a trivial agreement
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


@pytest.mark.parametrize("fault", ["bias", "scale"])
def test_a_dropped_bias_or_scale_fails_the_comparison(tiny, fault):
    """The selection bias picks (zeroing it changes picks) and ``m^2``
    weighs: the reference without either is another function."""
    net, w = tiny
    ids = row(70)
    got = np.asarray(net(paddle.to_tensor(ids[None]))._value)[0]
    wrong = np.asarray(reference.row_logits(TINY, w, ids, fault=fault))
    assert np.abs(got - wrong).max() > 1e-3
    if fault == "bias":
        h = jnp.asarray(np.random.RandomState(1).randn(64, 64), jnp.float32)
        lw = w["layers"][1]
        with_bias, _ = route(h, lw["moe.router"], lw["moe.bias"], 2, 2.5)
        without, _ = route(h, lw["moe.router"], 0 * lw["moe.bias"], 2, 2.5)
        assert (np.sort(with_bias, -1) != np.sort(without, -1)).any()


# -- (b) prefill then decode through the paged engine --------------------------

def served(net, prompt, new_tokens, **engine):
    """Greedy tokens of ``prompt`` through the paged engine (and the
    engine, its allocator checked)."""
    eng = ServingEngine(net, kv_mode="paged", **engine)
    req = eng.submit(prompt, max_new_tokens=new_tokens)
    eng.run()
    assert eng._kv.check()
    return np.asarray(req.tokens, np.int32), eng


@pytest.mark.parametrize("prompt_len", [13, 40])     # buckets 16 and 64
def test_paged_prefill_and_decode_match_the_reference(tiny, prompt_len):
    """Two buckets; 23 new tokens cross pages of 8 and chunks of 4.  Every
    served token is the reference's best at its position, or within
    rounding of it (gap under 1e-4)."""
    net, w = tiny
    prompt = row(prompt_len, seed=prompt_len)
    tokens, eng = served(net, prompt, 23, max_seq_len=128,
                                num_slots=2, chunk=4, page_size=8)
    assert len(tokens) == 23 and eng.stats["prefills"] == 1
    full = np.concatenate([prompt, tokens])
    targets = np.zeros(len(full), np.int32)
    targets[prompt_len - 1:-1] = tokens
    gaps = np.asarray(reference.next_token_gaps(TINY, w, full, targets))
    assert gaps[prompt_len - 1:-1].max() < 1e-4


def test_cached_logits_match_the_reference_across_pages(tiny):
    """The logits themselves: a prefill of 21 tokens in a bucket of 32,
    then 6 single-token steps through the latent pages (pages of 8: the
    writes cross a page boundary), against the reference's full forward."""
    net, w = tiny
    ids = row(27, seed=5)
    spec = net.kv_cache_spec()
    kv = kvcache.PagedKVManager(spec, 1, 64, 8, None, jnp.float32)
    plan = kv.plan(ids[:21], 6, 6)
    kv.bind(0, plan)
    table = jnp.asarray(kv.table)
    views = kvcache._layer_views(kv.device_pools(), table, False)
    padded = np.zeros((1, 32), np.int32)
    padded[0, :21] = ids[:21]
    want = np.asarray(reference.row_logits(TINY, w, ids))

    def step(tokens, views, pos, **kw):
        logits, new = net(
            paddle.to_tensor(tokens),
            caches=[type(v)(*(paddle.to_tensor(x) for x in v)) for v in views],
            pos=paddle.to_tensor(pos), **kw)
        return np.asarray(logits._value), \
            [type(v)(*(x._value for x in v)) for v in new]
    logits, views = step(padded, views, jnp.zeros((), jnp.int32),
                         last=jnp.asarray(20, jnp.int32))
    assert logits.shape == (1, 1, 512)           # one row, not the bucket's
    np.testing.assert_allclose(logits[0, 0], want[20], atol=2e-5, rtol=0)
    for t in range(21, 27):
        logits, views = step(ids[None, t:t + 1], views,
                             jnp.asarray([t], jnp.int32))
        np.testing.assert_allclose(logits[0, 0], want[t], atol=2e-5, rtol=0)


def test_a_cached_prefix_prefills_the_suffix_alike(tiny):
    """A second request sharing two pages of prompt hits the prefix cache:
    its suffix prefill reads the shared latent pages (the absorbed form)
    and serves the same tokens as a cold engine."""
    net, _ = tiny
    shared = row(16, seed=9)
    first = np.concatenate([shared, row(5, seed=10)])
    second = np.concatenate([shared, row(9, seed=11)])
    knobs = dict(max_seq_len=128, num_slots=2, chunk=4, page_size=8)
    cold, _ = served(net, second, 9, **knobs)
    eng = ServingEngine(net, kv_mode="paged", **knobs)
    eng.submit(first, max_new_tokens=3)
    eng.run()
    req = eng.submit(second, max_new_tokens=9)
    eng.run()
    assert eng._kv.stats["prefix_hits"] == 1 and req.prefix_cached == 16
    assert list(req.tokens) == list(cold)


# -- (c) the two forms of the attention ----------------------------------------

def test_absorbed_form_equals_expanded_form():
    cfg = mm.mla_moe_tiny()
    rng = np.random.RandomState(0)
    B, S = 2, 24
    nH, nope, rope = 4, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    q_nope = jnp.asarray(rng.randn(B, S, nH, nope), jnp.float32)
    q_rope = jnp.asarray(rng.randn(B, S, nH, rope), jnp.float32)
    rows = jnp.asarray(rng.randn(B, S, cfg.kv_lora_rank + rope), jnp.float32)
    w_kvb = jnp.asarray(0.2 * rng.randn(
        cfg.kv_lora_rank, nH * (nope + cfg.v_head_dim)), jnp.float32)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    expanded = mm.expanded_attention(q_nope, q_rope, rows, w_kvb, cfg,
                                     jnp.float32)
    cached = jnp.pad(rows, ((0, 0), (0, 40), (0, 0)))    # MAX = 64
    absorbed = mm.absorbed_attention(q_nope, q_rope, cached, positions,
                                     w_kvb, cfg, jnp.float32)
    np.testing.assert_allclose(np.asarray(absorbed), np.asarray(expanded),
                               atol=2e-5, rtol=0)


def test_absorbed_form_in_query_blocks_equals_one_block(monkeypatch):
    cfg = mm.mla_moe_tiny()
    rng = np.random.RandomState(1)
    B, S, nH = 1, 32, 4
    args = (jnp.asarray(rng.randn(B, S, nH, 24), jnp.float32),
            jnp.asarray(rng.randn(B, S, nH, 8), jnp.float32),
            jnp.asarray(rng.randn(B, 64, 40), jnp.float32),
            jnp.broadcast_to(7 + jnp.arange(S), (B, S)),
            jnp.asarray(0.2 * rng.randn(32, nH * 40), jnp.float32))
    whole = mm.absorbed_attention(*args, cfg, jnp.float32)
    monkeypatch.setattr(mm, "_QUERY_BLOCK", 8)
    blocks = mm.absorbed_attention(*args, cfg, jnp.float32)
    np.testing.assert_allclose(np.asarray(blocks), np.asarray(whole),
                               atol=1e-6, rtol=0)


# -- (d) the share test, (e) dropless under skew -------------------------------

def expert_layer(held, seed=0, bias=None):
    """A float32 layer of 8 experts top-2 holding ``held``, every share
    drawn from the same seed: expert e's matrices are the same in each."""
    paddle.seed(seed)
    whole = DroplessMoELayer(64, 32, 8, 2, scaling=2.5)
    if held == range(8):
        layer = whole
    else:
        layer = DroplessMoELayer(64, 32, 8, 2, experts_held=held, scaling=2.5)
        for name in ("router_weight", "router_bias", "shared_gate_up",
                     "shared_down"):
            getattr(layer, name)._replace(getattr(whole, name)._value)
        for name in ("experts_gate_up", "experts_down"):
            getattr(layer, name)._replace(
                getattr(whole, name)._value[held.start:held.stop])
    if bias is not None:
        layer.router_bias._replace(jnp.asarray(bias, jnp.float32))
    return layer


def reference_layer(layer, x, held):
    """The reference's expert layer on the program layer's weights."""
    lw = {"moe.router": layer.router_weight._value,
          "moe.bias": layer.router_bias._value,
          "moe.experts.gate_up": layer.experts_gate_up._value,
          "moe.experts.down": layer.experts_down._value,
          "moe.shared.gate_up": layer.shared_gate_up._value,
          "moe.shared.down": layer.shared_down._value}
    m = dict(TINY, num_experts=len(held), first_expert_held=held.start)
    return np.asarray(reference._experts(x, lw, m, "highest", False))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's share test: the quarters' results, the shared expert
    counted once, add up to the whole layer's, in the program and against
    the reference."""
    x = jnp.asarray(np.random.RandomState(2).randn(1, 48, 64), jnp.float32)
    whole = expert_layer(range(8))
    want = np.asarray(whole(paddle.to_tensor(x))._value)[0]
    parts, shared = [], None
    for first in (0, 2, 4, 6):
        held = range(first, first + 2)
        layer = expert_layer(held)
        y = np.asarray(layer(paddle.to_tensor(x))._value)[0]
        np.testing.assert_allclose(y, reference_layer(layer, x[0], held),
                                   atol=2e-5, rtol=0)
        shared = np.asarray(swiglu(x[0], layer.shared_gate_up._value,
                                   layer.shared_down._value))
        parts.append(y - shared)
    np.testing.assert_allclose(sum(parts) + shared, want, atol=3e-5, rtol=0)
    np.testing.assert_allclose(
        want, reference_layer(whole, x[0], range(8)), atol=2e-5, rtol=0)
    assert np.abs(sum(parts)).max() > 10 * 3e-5


def test_dropless_under_skew_loses_no_token():
    """A selection bias that sends every token to expert 5 (and one
    other): all 48 rows land on one held expert, none is dropped, and the
    result matches the reference; the counters say so."""
    x = jnp.asarray(np.random.RandomState(3).randn(1, 48, 64), jnp.float32)
    bias = np.zeros(8, np.float32)
    bias[5] = 10.0
    held = range(4, 8)
    layer = expert_layer(held, bias=bias)
    with devcounters.collect("decode") as bag:
        y = np.asarray(layer(paddle.to_tensor(x))._value)[0]
    counted = jax.device_get(bag.totals())
    assert counted["max"]["moe_max_expert_rows"] == 48
    assert counted["sum"]["moe_pairs_routed"] == 96
    assert 48 <= counted["sum"]["moe_pairs_here"] <= 96
    np.testing.assert_allclose(y, reference_layer(layer, x[0], held),
                               atol=2e-5, rtol=0)


def test_counters_skip_rows_that_are_not_real():
    x = jnp.asarray(np.random.RandomState(4).randn(2, 1, 64), jnp.float32)
    layer = expert_layer(range(8))
    with devcounters.collect("decode",
                             rows=jnp.asarray([[True], [False]])) as bag:
        layer(paddle.to_tensor(x))
    counted = jax.device_get(bag.totals())
    assert counted["sum"] == {"moe_pairs_routed": 2, "moe_pairs_here": 2,
                              "moe_experts_touched": 2,
                              "moe_decode_layer_steps": 1}
    assert devcounters.current() is None


def test_experts_held_must_be_a_range_of_the_router():
    with pytest.raises(ValueError, match="experts_held"):
        DroplessMoELayer(64, 32, 8, 2, experts_held=range(6, 10))


# -- (g) the manager and the engine's refusals ---------------------------------

def test_manager_holds_a_latent_layer_as_one_plane():
    spec = [LatentCacheSpec(40), (4, 16), LatentCacheSpec(40)]
    kv = kvcache.PagedKVManager(spec, 2, 64, 8, 9, jnp.bfloat16)
    pools = kv.device_pools()
    assert [tuple(p.shape for p in planes) for planes in pools] == [
        ((9, 8, 40),), ((9, 8, 4, 16), (9, 8, 4, 16)), ((9, 8, 40),)]
    assert kv.kinds == ["latent", "heads", "latent"]
    assert kv.page_bytes == 8 * 2 * (40 + 2 * 4 * 16 + 40)
    views = kvcache._layer_views(pools, jnp.asarray(kv.table), False)
    assert [type(v).__name__ for v in views] == [
        "LatentCacheView", "PagedCacheView", "LatentCacheView"]
    assert [len(p) for p in kvcache._layer_pools(views, False)] == [1, 2, 1]


def test_latent_pages_export_and_import_with_their_crcs():
    spec = [LatentCacheSpec(40)] * 2
    a = kvcache.PagedKVManager(spec, 2, 64, 8, 9, jnp.float32)
    b = kvcache.PagedKVManager(spec, 2, 64, 8, 9, jnp.float32)
    prompt = row(20)
    a.bind(0, a.plan(prompt, 4, 4))
    rows = jnp.asarray(np.random.RandomState(6).randn(1, 20, 40), jnp.float32)
    table = jnp.asarray(a.table)[:1]
    a.set_pools([(kvcache.scatter_latent(p[0], rows + i, table,
                                         jnp.zeros((), jnp.int32)),)
                 for i, p in enumerate(a.device_pools())])
    payload = a.export_pages(0)
    assert payload["manifest"]["kinds"] == ["latent", "latent"]
    assert b.import_pages(1, payload) == len(payload["logical"])
    got = kvcache.gather_latent(b.device_pools()[1][0],
                                jnp.asarray(b.table)[1:2])
    np.testing.assert_array_equal(np.asarray(got)[0, :20],
                                  np.asarray(rows)[0] + 1)
    assert a.check() and b.check()
    assert a.resident_bytes == a.pages_in_use * a.page_bytes > 0
    flipped = np.array(payload["layers"][0][0])
    flipped[1, 0, 0] += 1.0
    payload["layers"][0] = (flipped,)
    with pytest.raises(kvcache.KVBundleError, match="checksum"):
        b.import_pages(0, payload)
    heads = kvcache.PagedKVManager([(4, 10)] * 2, 2, 64, 8, 9, jnp.float32)
    with pytest.raises(kvcache.KVBundleError, match="kinds"):
        heads.import_pages(0, a.export_pages(0))


@pytest.mark.parametrize("knobs, named", [
    (dict(kv_mode="dense"), "kv_mode='dense'"),
    (dict(kv_mode="paged", kv_dtype="int8"), "kv_dtype='int8'"),
    (dict(kv_mode="paged", quant_mode="int8"), "quant_mode='int8'"),
    (dict(kv_mode="paged", spec_decode=object()), "spec_decode"),
])
def test_engine_modes_that_cannot_hold_a_latent_layer_raise(tiny, knobs,
                                                           named):
    net, _ = tiny
    with pytest.raises(ValueError, match="latent cache layer") as err:
        ServingEngine(net, max_seq_len=128, **knobs)
    assert named in str(err.value)


def test_generate_and_int8_pool_refuse_a_latent_layer(tiny):
    net, _ = tiny
    with pytest.raises(ValueError, match="latent cache layer"):
        net.generate(paddle.to_tensor(row(8)[None]), max_new_tokens=2)
    with pytest.raises(ValueError, match="latent cache layer"):
        kvcache.PagedKVManager([LatentCacheSpec(40)], 2, 64, 8, 9,
                               jnp.float32, kv_dtype="int8")


def test_a_model_built_in_the_serving_dtype_is_not_copied():
    net, _ = build(dtype="bfloat16")
    eng = ServingEngine(net, kv_mode="paged", dtype="bfloat16",
                        max_seq_len=128, num_slots=2, chunk=4, page_size=8)
    assert all(a is p._value for a, p in zip(eng._pvals, eng._params))
    req = eng.submit(row(12), max_new_tokens=6)
    eng.run()
    assert len(req.tokens) == 6
    assert eng.stats["moe_pairs_routed"] == (12 + 5) * 2 * 2
    assert eng.stats["moe_decode_layer_steps"] == eng.stats["chunks"] * 4 * 2


def test_yarn_frequencies_blend_between_the_correction_dims():
    scaling = {"factor": 40, "beta_fast": 32, "beta_slow": 1,
               "original_max_position_embeddings": 4096,
               "mscale": 1, "mscale_all_dim": 1}
    got = np.asarray(mm.yarn_inv_freq(64, 10000, scaling))
    plain = 1.0 / 10000 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(got[:10], plain[:10], rtol=1e-6)   # fast: kept
    np.testing.assert_allclose(got[-8:], plain[-8:] / 40, rtol=1e-6)
    assert (np.diff(got) < 0).all()
    np.testing.assert_allclose(got, reference.yarn_inv_freq(64, 10000,
                                                            scaling))
    assert mm.softmax_scale(192, scaling) == pytest.approx(
        192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2)
