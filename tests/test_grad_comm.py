"""Communication-efficient gradient reduction (distributed/grad_comm.py).

Mesh parity on the 8-virtual-device CPU conftest mesh (the reference's
multi-process golden-model pattern): the bucketed/overlapped — and
quantized, at its documented tolerance — DP stepper must match the
single-device stepper, and bucketing alone must not change the update
at all (bitwise).  Accuracy contract: docs/DISTRIBUTED.md.
"""
import textwrap
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu.distributed.fleet.base.distributed_strategy import \
    DistributedStrategy
from paddle_tpu.distributed.grad_comm import (GradCommConfig, plan_buckets,
                                              build_grad_reducer)

pytestmark = pytest.mark.multichip


def _strategy(**cfgs):
    st = DistributedStrategy()
    st.grad_comm = cfgs.pop("enabled", True)
    st.grad_comm_configs = cfgs
    return st


def _make_model(seed=0):
    paddle.seed(seed)
    return nn.Sequential(
        nn.Linear(16, 64), nn.ReLU(),
        nn.Linear(64, 64), nn.ReLU(),
        nn.Linear(64, 10),
    )


def _train(net, steps=4, bs=16):
    model = paddle.Model(net)
    inner = net._layers if hasattr(net, "_layers") else net
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=inner.parameters())
    model.prepare(opt, nn.CrossEntropyLoss())
    rng = np.random.RandomState(0)
    losses = []
    for _ in range(steps):
        x = rng.rand(bs, 16).astype("f4")
        y = rng.randint(0, 10, (bs, 1)).astype("i8")
        losses.append(model.train_batch([x], [y])[0])
    return losses, inner


# -- bucket planning (pure host code) ---------------------------------------

class TestBucketPlan:
    SHAPES = [(100,), (200, 4), (50,), (3000,), (10,)]
    DTYPES = [jnp.float32] * 5

    def test_reverse_order_partition_covers_all_params_once(self):
        plan = plan_buckets(self.SHAPES, self.DTYPES, 1600)
        flat = [i for b in plan.buckets for i in b]
        assert sorted(flat) == list(range(len(self.SHAPES)))
        # reverse parameter order: backward produces the LAST params'
        # grads first, so the first bucket must hold the highest indices
        assert flat == list(reversed(range(len(self.SHAPES))))

    def test_bucket_sizes_and_oversized_tensor(self):
        plan = plan_buckets(self.SHAPES, self.DTYPES, 1600)
        # per-bucket byte counts match their members
        for idxs, nb in zip(plan.buckets, plan.nbytes):
            assert nb == sum(int(np.prod(self.SHAPES[i])) * 4
                             for i in idxs)
        assert plan.total_bytes == sum(
            int(np.prod(s)) * 4 for s in self.SHAPES)
        # the 3000-element tensor (12000 B > 1600 B target) closes a
        # bucket on its own rather than splitting across reduces
        assert any(nb >= 12000 for nb in plan.nbytes)
        # every bucket except possibly the last reached the target
        assert all(nb >= 1600 for nb in plan.nbytes[:-1])

    def test_overlap_fraction_structural(self):
        one = plan_buckets([(8,)], [jnp.float32], 1 << 30)
        assert one.overlap_fraction == 0.0
        multi = plan_buckets(self.SHAPES, self.DTYPES, 1600)
        assert len(multi.buckets) > 1
        expect = 1.0 - multi.nbytes[-1] / multi.total_bytes
        assert multi.overlap_fraction == pytest.approx(expect)
        assert 0.0 < multi.overlap_fraction < 1.0


class TestGradCommConfig:
    def test_from_strategy_off_is_none(self):
        assert GradCommConfig.from_strategy(None) is None
        assert GradCommConfig.from_strategy(DistributedStrategy()) is None

    def test_bucket_mb_defaults_to_fuse_knob(self):
        st = _strategy()
        st.fuse_grad_size_in_MB = 7
        cc = GradCommConfig.from_strategy(st)
        assert cc.enabled and cc.bucket_mb == 7.0

    def test_zero1_and_reducer_mutually_exclusive(self):
        with pytest.raises(ValueError, match="mutually exclusive"):
            GradCommConfig(enabled=True, zero1=True)

    def test_unknown_quantize_mode_rejected(self):
        with pytest.raises(ValueError, match="unknown quantize mode"):
            GradCommConfig(quantize="int4")

    def test_fp8_mode_is_kept(self):
        assert GradCommConfig(quantize="fp8").quantize == "fp8"


# -- reducer on the 8-device mesh -------------------------------------------

class TestReducerOnMesh:
    def test_bucket_gauges_recorded(self):
        from paddle_tpu import observability as obs
        obs.get_registry().reset()
        shapes, dtypes = [(64, 8), (128,), (32, 32)], [jnp.float32] * 3
        _, plan = build_grad_reducer(shapes, dtypes,
                                     GradCommConfig(bucket_mb=0.001),
                                     "data", 8)
        reg = obs.get_registry()
        assert reg.get("pt_collective_grad_buckets").value() == \
            len(plan.buckets)
        assert reg.get("pt_collective_overlap_fraction").value() == \
            pytest.approx(plan.overlap_fraction)

    def test_quant_reduce_tracks_exact_mean(self):
        from jax import shard_map
        from jax.sharding import Mesh, PartitionSpec as P

        assert jax.device_count() == 8
        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        shapes = [(33, 7), (129,), (64, 3)]
        dtypes = [jnp.float32] * 3
        cfg = GradCommConfig(bucket_mb=0.0005, quantize="int8",
                             quant_chunk=50)
        reducer, plan = build_grad_reducer(shapes, dtypes, cfg, "data", 8)
        assert len(plan.buckets) >= 2

        def body():
            r = jax.lax.axis_index("data")
            grads = [jax.random.normal(
                jax.random.fold_in(jax.random.key(3), r * 16 + i), s)
                for i, s in enumerate(shapes)]
            exact = [jax.lax.pmean(g, "data") for g in grads]
            approx = reducer(list(grads))  # the reducer's DP mean
            return tuple(exact) + tuple(approx)

        out = jax.jit(shard_map(body, mesh=mesh, in_specs=(),
                                out_specs=tuple(P() for _ in range(6)),
                                check_vma=False))()
        exact, approx = out[:3], out[3:]
        for e, a in zip(exact, approx):
            amax = float(jnp.max(jnp.abs(e)))
            # two absmax-scaled int8 phases: per-element error is
            # bounded by ~2/127 of the group amax (docs/DISTRIBUTED.md)
            assert float(jnp.max(jnp.abs(e - a))) <= 0.05 * max(amax, 1e-6)


# -- DP stepper parity (the satellite contract) -----------------------------

class TestDPStepperParity:
    def test_fp32_bucketed_overlapped_matches_single_device(self):
        assert jax.device_count() == 8
        golden, _ = _train(_make_model(seed=7))
        net = _make_model(seed=7)
        dp = paddle.DataParallel(net, strategy=_strategy(bucket_mb=0.001))
        assert dp._placement_plan.grad_comm is not None
        losses, inner = _train(dp)
        # fp32 wire: same math as the GSPMD all-reduce, tight tolerance
        np.testing.assert_allclose(losses, golden, rtol=1e-5, atol=1e-5)
        assert inner.parameters()[0]._value.sharding.is_fully_replicated

    @pytest.mark.parametrize("mode", ["bf16", "int8"])
    def test_quantized_wire_tracks_fp32_at_documented_tolerance(self, mode):
        golden, _ = _train(_make_model(seed=7))
        net = _make_model(seed=7)
        dp = paddle.DataParallel(
            net, strategy=_strategy(bucket_mb=0.001, quantize=mode))
        losses, _ = _train(dp)
        # documented accuracy contract (docs/DISTRIBUTED.md): quantized
        # wire formats track the fp32 loss, they do not equal it
        np.testing.assert_allclose(losses, golden, rtol=0, atol=3e-2)

    def test_bucketing_alone_is_bitwise_invariant(self):
        """Bucket partitioning (many small buckets vs one monolithic
        reduce) must not change the update AT ALL — same psum values,
        same order, bitwise-equal parameters."""
        net_a = _make_model(seed=5)
        dp_a = paddle.DataParallel(net_a,
                                   strategy=_strategy(bucket_mb=0.001))
        _train(dp_a)
        net_b = _make_model(seed=5)
        dp_b = paddle.DataParallel(net_b,
                                   strategy=_strategy(overlap=False))
        _train(dp_b)
        pa = [np.asarray(p._value) for p in net_a.parameters()]
        pb = [np.asarray(p._value) for p in net_b.parameters()]
        for a, b in zip(pa, pb):
            np.testing.assert_array_equal(a, b)

    def test_indivisible_batch_raises_before_compile(self):
        net = _make_model(seed=1)
        dp = paddle.DataParallel(net, strategy=_strategy())
        with pytest.raises(ValueError, match="not divisible"):
            _train(dp, steps=1, bs=12)   # 12 % 8 != 0

    def test_nondp_plan_warns_and_falls_back(self):
        from jax.sharding import Mesh
        from paddle_tpu.distributed.engine import PlacementPlan
        devs = np.asarray(jax.devices()).reshape(4, 2)
        plan = PlacementPlan(Mesh(devs, ("data", "sharding")), level="os",
                             grad_comm=GradCommConfig())
        net = _make_model(seed=2)
        net._placement_plan = plan
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            losses, _ = _train(net, steps=1)
        assert any("grad_comm" in str(w.message) for w in caught)
        assert np.isfinite(losses[0])


class TestZero1Flag:
    def test_zero1_routes_to_os_plan_and_matches_golden(self):
        golden, _ = _train(_make_model(seed=3))
        st = DistributedStrategy()
        st.grad_comm_configs = {"zero1": True}  # flag alone, reducer off
        net = _make_model(seed=3)
        dp = paddle.DataParallel(net, strategy=st)
        plan = dp._placement_plan
        assert plan.level == "os"
        assert plan.grad_comm is None  # ZeRO-1 is plan-based, no reducer
        model = paddle.Model(dp)
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=net.parameters())
        model.prepare(opt, nn.CrossEntropyLoss())
        rng = np.random.RandomState(0)
        losses = []
        for _ in range(4):
            x = rng.rand(16, 16).astype("f4")
            y = rng.randint(0, 10, (16, 1)).astype("i8")
            losses.append(model.train_batch([x], [y])[0])
        np.testing.assert_allclose(losses, golden, rtol=2e-4, atol=2e-5)
        sharded_any = any(
            hasattr(v, "sharding") and v.ndim >= 1 and
            not v.sharding.is_fully_replicated
            for st_ in model._stepper.opt_state for v in st_.values())
        assert sharded_any, "zero1: optimizer state stayed replicated"


# -- static-analysis integration --------------------------------------------

class TestAnalysisIntegration:
    def test_reducer_surfaces_registered(self):
        from paddle_tpu.analysis import registered_surfaces
        quals = {q for _, q in registered_surfaces()}
        assert "build_grad_reducer.reduce" in quals
        assert "_build_quant_reduce.quant_reduce" in quals

    def test_collective_order_walks_reducer_wrappers(self, tmp_path):
        """A rank-conditional call to a grad_comm wrapper is exactly as
        deadlock-prone as one to the raw collective it wraps — the
        extended COLLECTIVE_CALLEES must make the pass flag it."""
        from paddle_tpu.analysis.runner import run_passes
        (tmp_path / "fixture.py").write_text(textwrap.dedent("""
            def step(rank, vec, reduce_vec, reducer):
                if rank == 0:
                    reduce_vec(vec)
                out = reducer([vec])
                return out
            """))
        found = run_passes(paths=[str(tmp_path)],
                           passes=["collective-order"])
        assert [f.code for f in found] == ["rank-conditional-collective"]
        assert "reduce_vec" in found[0].message
