"""Meta-optimizer tests (reference pattern:
test/collective/fleet/test_fleet_lars_meta_optimizer.py,
test_fleet_dgc_meta_optimizer.py, test_fleet_gradient_merge_meta_optimizer
.py, test_fleet_localsgd_meta_optimizer.py — strategy flags must change
the applied update rule, with numeric parity checks)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.framework.core import Tensor
from paddle_tpu.optimizer import LarsMomentum, DGCMomentum, Momentum, SGD
from paddle_tpu.distributed.fleet.base.distributed_strategy import (
    DistributedStrategy)
from paddle_tpu.distributed.fleet.meta_optimizers import (
    apply_meta_optimizers, GradientMergeHelper, LocalSGDOptimizer)
from paddle_tpu.distributed.fleet.meta_parallel import (
    HybridParallelOptimizer)


def _param(arr):
    p = Tensor(jnp.asarray(arr), stop_gradient=False)
    p.is_parameter = True
    return p


def test_lars_update_matches_manual():
    rng = np.random.RandomState(0)
    w0 = rng.randn(4, 4).astype("f4")
    g = rng.randn(4, 4).astype("f4")
    p = _param(w0)
    p._grad = jnp.asarray(g)
    opt = LarsMomentum(learning_rate=0.1, momentum=0.9, lars_coeff=0.001,
                       lars_weight_decay=0.0005, parameters=[p])
    opt.step()

    w_norm = np.linalg.norm(w0)
    g_norm = np.linalg.norm(g)
    local_lr = 0.1 * 0.001 * w_norm / (1e-9 + g_norm + 0.0005 * w_norm)
    v = local_lr * (g + 0.0005 * w0)
    np.testing.assert_allclose(np.asarray(p._value), w0 - v, rtol=1e-5)
    # second step uses momentum-carried velocity
    p._grad = jnp.asarray(g)
    opt.step()
    w1 = w0 - v
    w_norm1 = np.linalg.norm(w1)
    local_lr1 = 0.1 * 0.001 * w_norm1 / (
        1e-9 + g_norm + 0.0005 * w_norm1)
    v1 = 0.9 * v + local_lr1 * (g + 0.0005 * w1)
    np.testing.assert_allclose(np.asarray(p._value), w1 - v1, rtol=1e-4)


def test_dgc_topk_and_error_feedback():
    n = 100
    g = np.zeros(n, dtype="f4")
    g[7] = 10.0   # dominant entry
    g[3] = 0.5    # small entry: must stay in the residual
    p = _param(np.zeros(n, dtype="f4"))
    p._grad = jnp.asarray(g)
    opt = DGCMomentum(learning_rate=1.0, momentum=0.0, parameters=[p],
                      sparsity=0.99)  # k = 1
    opt.step()
    w = np.asarray(p._value)
    # only the top-1 entry was applied
    assert w[7] == pytest.approx(-10.0)
    assert w[3] == 0.0
    # error feedback: the unsent entry accumulates and is applied once
    # it becomes the largest residual
    p._grad = jnp.zeros(n)
    for _ in range(2):
        opt.step()
    w = np.asarray(p._value)
    assert w[3] == pytest.approx(-0.5)  # residual eventually delivered


def test_dgc_rampup_is_plain_momentum():
    p = _param(np.ones(8, dtype="f4"))
    p._grad = jnp.full((8,), 2.0)
    opt = DGCMomentum(learning_rate=0.1, momentum=0.9, parameters=[p],
                      sparsity=0.99, rampup_begin_step=100)
    opt.step()
    np.testing.assert_allclose(np.asarray(p._value),
                               np.ones(8) - 0.1 * 2.0, rtol=1e-6)


def test_dgc_rampup_crossing_inside_jit():
    """The rampup→dgc phase switch is a traced step counter, so ONE
    compiled update function crosses rampup_begin_step correctly
    (advisor r2: a Python-branch phase flag froze at trace time)."""
    opt = DGCMomentum(learning_rate=1.0, momentum=0.0,
                      sparsity=0.875,  # k=1 for n=8
                      rampup_begin_step=2)
    w0 = np.zeros(8, dtype="f4")
    g = np.zeros(8, dtype="f4")
    g[5] = 4.0
    g[2] = 1.0

    @jax.jit
    def step(p, st):
        return opt._update(jnp.asarray(p), jnp.asarray(g), st, 1.0)

    st = opt._init_state_for(jnp.asarray(w0))
    p = jnp.asarray(w0)
    # steps 0,1: plain momentum (all entries applied)
    p, st = step(p, st)
    np.testing.assert_allclose(np.asarray(p), -g, rtol=1e-6)
    p, st = step(p, st)
    # step 2: same compiled fn, now top-k phase — only g[5] column moves
    p_before = np.asarray(p)
    p, st = step(p, st)
    delta = np.asarray(p) - p_before
    assert delta[5] != 0.0
    assert delta[2] == 0.0  # small entry held back in residual


def test_lars_exclude_from_weight_decay():
    """Excluded params (e.g. bias/bn) get plain momentum: no wd, no
    layer-adaptive scaling (advisor r2: exclusion list was ignored)."""
    w0 = np.full((4,), 2.0, dtype="f4")
    g = np.full((4,), 0.5, dtype="f4")
    p = _param(w0)
    p.name = "bn_scale_0"
    p._grad = jnp.asarray(g)
    opt = LarsMomentum(learning_rate=0.1, momentum=0.9, lars_coeff=0.001,
                       lars_weight_decay=0.0005, parameters=[p],
                       exclude_from_weight_decay=["bn", "bias"])
    opt.step()
    # plain momentum: w - lr*g, with NO lars_coeff scaling and NO wd
    np.testing.assert_allclose(np.asarray(p._value), w0 - 0.1 * g,
                               rtol=1e-6)
    # functional path honors the same exclusion via param_names
    from paddle_tpu.optimizer.optimizer import apply_functional_with_clip
    opt2 = LarsMomentum(learning_rate=0.1, momentum=0.9,
                        exclude_from_weight_decay=["bias"])
    st = [opt2._init_state_for(jnp.asarray(w0))]
    (new_w,), _ = apply_functional_with_clip(
        opt2, [jnp.asarray(w0)], [jnp.asarray(g)], st, 0.1,
        param_names=["fc_bias_1"])
    np.testing.assert_allclose(np.asarray(new_w), w0 - 0.1 * g, rtol=1e-6)
    # ...and a non-excluded name still gets the adaptive update
    (new_w2,), _ = apply_functional_with_clip(
        opt2, [jnp.asarray(w0)], [jnp.asarray(g)],
        [opt2._init_state_for(jnp.asarray(w0))], 0.1,
        param_names=["fc_weight_1"])
    assert not np.allclose(np.asarray(new_w2), w0 - 0.1 * g)


def test_gradient_merge_parity_with_large_batch():
    """k_steps=4 accumulation == one step on the averaged grad."""
    rng = np.random.RandomState(1)
    w0 = rng.randn(3, 3).astype("f4")
    grads = [rng.randn(3, 3).astype("f4") for _ in range(4)]

    p_gm = _param(w0)
    strategy = DistributedStrategy()
    strategy.gradient_merge = True
    strategy.gradient_merge_configs = {"k_steps": 4, "avg": True}
    opt = HybridParallelOptimizer(
        SGD(learning_rate=0.1, parameters=[p_gm]), strategy=strategy)
    for g in grads:
        p_gm._grad = jnp.asarray(g)
        opt.step()
        opt.clear_grad()

    p_ref = _param(w0)
    ref = SGD(learning_rate=0.1, parameters=[p_ref])
    p_ref._grad = jnp.asarray(np.mean(grads, axis=0))
    ref.step()
    np.testing.assert_allclose(np.asarray(p_gm._value),
                               np.asarray(p_ref._value), rtol=1e-5)
    # param must NOT move during the first 3 accumulation micro-steps
    p2 = _param(w0)
    opt2 = HybridParallelOptimizer(
        SGD(learning_rate=0.1, parameters=[p2]), strategy=strategy)
    p2._grad = jnp.asarray(grads[0])
    opt2.step()
    np.testing.assert_allclose(np.asarray(p2._value), w0)


def test_localsgd_sync_values_pmean():
    """Per-device divergent params average across the dp axis."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    per_dev = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)

    def f(v):
        out = LocalSGDOptimizer.sync_values([v], "data")
        return out[0]

    synced = shard_map(f, mesh=mesh, in_specs=P("data"),
                       out_specs=P("data"))(per_dev)
    np.testing.assert_allclose(np.asarray(synced),
                               np.full((8, 1), 3.5), rtol=1e-6)


def test_localsgd_wrapper_steps_inner():
    p = _param(np.ones(4, dtype="f4"))
    inner = SGD(learning_rate=0.5, parameters=[p])
    opt = LocalSGDOptimizer(inner, k_steps=2)
    p._grad = jnp.full((4,), 1.0)
    opt.step()  # world of 1: sync is identity
    np.testing.assert_allclose(np.asarray(p._value), 0.5 * np.ones(4))
    assert opt._local_steps == 1


def test_strategy_swaps_momentum_for_lars_and_dgc():
    p = _param(np.ones(4, dtype="f4"))
    mom = Momentum(learning_rate=0.1, momentum=0.9, parameters=[p])

    s = DistributedStrategy()
    s.lars = True
    s.lars_configs = {"lars_coeff": 0.002}
    out = apply_meta_optimizers(mom, s)
    assert isinstance(out, LarsMomentum)
    assert out._lars_coeff == 0.002
    assert out._parameter_list == [p]

    s2 = DistributedStrategy()
    s2.dgc = True
    out2 = apply_meta_optimizers(
        Momentum(learning_rate=0.1, parameters=[p]), s2)
    assert isinstance(out2, DGCMomentum)

    s3 = DistributedStrategy()
    s3.localsgd = True
    s3.localsgd_configs = {"k_steps": 4}
    out3 = apply_meta_optimizers(
        Momentum(learning_rate=0.1, parameters=[p]), s3)
    assert isinstance(out3, LocalSGDOptimizer)
    assert out3.k_steps == 4

    # non-Momentum inner optimizers pass through untouched
    sgd = SGD(learning_rate=0.1, parameters=[p])
    assert apply_meta_optimizers(sgd, s) is sgd


def test_fleet_save_persistables(tmp_path):
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.checkpoint import load_state_dict
    fleet.init(is_collective=True)
    paddle.seed(3)
    net = nn.Linear(4, 2)
    dnet = fleet.distributed_model(net)
    out = str(tmp_path / "persist")
    fleet.save_persistables(dirname=out)
    loaded = load_state_dict(out)
    ref = net.state_dict()
    for k, v in ref.items():
        np.testing.assert_allclose(np.asarray(loaded[k]),
                                   np.asarray(v._value))
    fleet.stop_worker()  # no PS registered: clean no-op


def test_hybrid_optimizer_trains_model_with_lars():
    paddle.seed(0)
    net = nn.Linear(4, 2)
    s = DistributedStrategy()
    s.lars = True
    opt = HybridParallelOptimizer(
        Momentum(learning_rate=0.05, momentum=0.9,
                 parameters=net.parameters()), strategy=s)
    x = Tensor(jnp.asarray(np.random.RandomState(0)
                           .randn(8, 4).astype("f4")))
    losses = []
    for _ in range(5):
        out = net(x)
        loss = (out * out).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss._value))
    assert losses[-1] < losses[0]
