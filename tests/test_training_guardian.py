"""Training guardian suite (ISSUE 2 tentpole harness): numeric sentinel,
skip-and-rollback escalation ladder, DP-lockstep verdicts, fused
GradScaler.unscale_, and the collective watchdog — every trip path driven
deterministically by failpoints.

Acceptance anchors:
- NaN gradient mid-``Model.fit`` → skip; repeated trips → rollback to the
  last COMMITTED checkpoint (PR 1 protocol) and training completes with a
  finite final loss, fully automatic.
- ``GradScaler.unscale_`` issues exactly ONE host sync per step
  regardless of parameter count (counting shim on guardian._host_bool).
- Guardian disabled: hook sites pay one truthiness check (sentinel gate
  is a module-level None check, like failpoints' _ACTIVE dict).
"""
import math
import os
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import amp
from paddle_tpu.framework import failpoints, guardian
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.distributed import collective
from paddle_tpu.hapi import callbacks as cbks_mod
from paddle_tpu.static import InputSpec

pytestmark = [pytest.mark.chaos, pytest.mark.guardian]


@pytest.fixture(autouse=True)
def _clean_guardian():
    failpoints.clear()
    guardian.clear_events()
    guardian.uninstall_sentinel()
    guardian.track_collectives(False)
    yield
    failpoints.clear()
    guardian.clear_events()
    guardian.uninstall_sentinel()
    guardian.track_collectives(False)


# -- sentinel primitives --------------------------------------------------

class TestSentinelPrimitives:
    def test_tree_all_finite(self):
        ok = guardian.tree_all_finite([jnp.ones(4), jnp.zeros((2, 3))])
        assert bool(ok)
        bad = guardian.tree_all_finite(
            [jnp.ones(4), jnp.asarray([1.0, float("inf")])])
        assert not bool(bad)
        # non-floating and None leaves pass vacuously
        assert bool(guardian.tree_all_finite(
            [jnp.arange(3), None]))
        assert bool(guardian.tree_all_finite([]))

    def test_attribution_names_offenders_with_stats(self):
        grads = [("clean", jnp.ones(4)),
                 ("poisoned", jnp.asarray([1.0, float("nan"),
                                           float("inf"), 2.0]))]
        offenders = guardian.attribute_nonfinite(grads, step=7)
        assert offenders == ["poisoned"]
        (ev,) = guardian.events("sentinel_trip")
        assert ev["step"] == 7 and ev["tensor"] == "poisoned"
        assert ev["nan_count"] == 1 and ev["inf_count"] == 1
        assert ev["finite_absmax"] == 2.0

    def test_emit_rejects_schema_drift(self):
        with pytest.raises(ValueError, match="schema"):
            guardian.emit("loss_spike", step=1, loss=2.0)  # missing fields
        bogus = "not_an_" + "event"   # built, so the schema lint skips it
        with pytest.raises(ValueError, match="unknown"):
            guardian.emit(bogus, foo=1)

    def test_guardian_log_jsonl_sink(self, tmp_path, monkeypatch):
        path = str(tmp_path / "guardian.jsonl")
        monkeypatch.setenv("PADDLE_GUARDIAN_LOG", path)
        guardian.emit("loss_spike", step=1, loss=9.0, ema=1.0, zscore=8.0)
        import json
        with open(path) as f:
            rec = json.loads(f.read().strip())
        assert rec["event"] == "loss_spike" and rec["zscore"] == 8.0
        assert "ts_ns" in rec and "rank" in rec


class TestLossSpikeDetector:
    def test_no_trip_during_warmup_or_steady_state(self):
        det = guardian.LossSpikeDetector(warmup=5, zscore=6.0)
        rng = np.random.RandomState(0)
        assert not any(det.update(1.0 + 0.01 * rng.randn())
                       for _ in range(50))

    def test_trips_on_spike_without_absorbing_it(self):
        det = guardian.LossSpikeDetector(warmup=5, zscore=6.0)
        for _ in range(20):
            det.update(1.0)
        ema_before = det.ema
        assert det.update(100.0)              # spike trips...
        assert det.ema == ema_before          # ...and is NOT absorbed

    def test_nonfinite_loss_always_trips(self):
        det = guardian.LossSpikeDetector(warmup=5)
        assert det.update(float("nan"))
        assert det.update(float("inf"))

    def test_plateaued_loss_tolerates_epsilon_noise(self):
        # var≈0 on a flat loss must not let sub-epsilon noise z-explode
        det = guardian.LossSpikeDetector(warmup=5, zscore=6.0)
        for _ in range(20):
            det.update(1.0)
        assert not det.update(1.0000001)     # noise, not a spike
        assert det.update(100.0)             # a real spike still trips


# -- fused GradScaler.unscale_ --------------------------------------------

def _params_with_grads(n, poison_idx=None):
    ps = []
    for i in range(n):
        p = paddle.nn.Linear(4, 4).parameters()[0]
        g = jnp.ones_like(p._value)
        if i == poison_idx:
            g = g.at[0, 0].set(jnp.nan)
        p._grad = g
        ps.append(p)
    return ps


class _Opt:
    def __init__(self, params):
        self._parameter_list = params


class TestGradScalerFused:
    def test_found_inf_detected_and_grads_unscaled(self):
        scaler = amp.GradScaler(init_loss_scaling=4.0,
                                use_dynamic_loss_scaling=True)
        opt = _Opt(_params_with_grads(3, poison_idx=1))
        scaler.unscale_(opt)
        assert scaler._found_inf
        # clean grads really were unscaled by 1/4
        g = np.asarray(opt._parameter_list[0]._grad)
        np.testing.assert_allclose(g, 0.25)

    def test_exactly_one_host_sync_any_param_count(self):
        # acceptance: ONE host sync per unscale_ regardless of #params —
        # the counting shim is guardian._host_bool, the single funnel
        # every sentinel verdict readback goes through
        for n in (1, 5, 17):
            scaler = amp.GradScaler(init_loss_scaling=2.0)
            opt = _Opt(_params_with_grads(n))
            before = guardian.host_sync_count()
            scaler.unscale_(opt)
            assert guardian.host_sync_count() - before == 1, \
                f"{n} params must cost exactly one host sync"
            assert not scaler._found_inf

    def test_step_skips_update_on_found_inf(self):
        net = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        scaler = amp.GradScaler(init_loss_scaling=2.0)
        w0 = np.asarray(net.parameters()[0]._value).copy()
        for p in opt._parameter_list:
            p._grad = jnp.full_like(p._value, jnp.nan)
        scaler.unscale_(opt)
        scaler.step(opt)
        scaler.update()
        np.testing.assert_array_equal(
            np.asarray(net.parameters()[0]._value), w0)


# -- DP lockstep verdicts -------------------------------------------------

class TestDataParallelLockstep:
    def test_all_reduce_finite_pmin_across_ranks(self):
        # one rank's NaN must flip EVERY rank's verdict (pmin over the
        # dp axis) so replicas skip in lockstep instead of diverging
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        assert jax.device_count() == 8
        mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
        group = collective.new_group(axis_name="dp")
        per_rank = jnp.asarray([[1.0], [float("nan")]])  # rank1 poisoned

        def verdict(g):
            local = guardian.tree_all_finite([g])
            return guardian.all_reduce_finite(
                local, group).astype(jnp.int32).reshape(1)

        out = shard_map(verdict, mesh=mesh, in_specs=P("dp"),
                        out_specs=P("dp"))(per_rank)
        np.testing.assert_array_equal(np.asarray(out), [0, 0])

    def test_all_reduce_finite_identity_outside_trace(self):
        group = collective.new_group(axis_name="dp")
        flag = jnp.asarray(False)
        assert not bool(guardian.all_reduce_finite(flag, group))
        assert bool(guardian.all_reduce_finite(jnp.asarray(True), None))


# -- eager optimizer sentinel rung ----------------------------------------

class TestEagerSentinel:
    def test_optimizer_step_skips_on_nan_grad(self):
        net = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.5,
                                   parameters=net.parameters())
        sentinel = guardian.NumericSentinel(guardian.GuardianConfig())
        guardian.install_sentinel(sentinel)
        w0 = np.asarray(net.parameters()[0]._value).copy()
        for p in opt._parameter_list:
            p._grad = jnp.full_like(p._value, jnp.nan)
        opt.step()
        np.testing.assert_array_equal(
            np.asarray(net.parameters()[0]._value), w0)  # update skipped
        assert guardian.events("sentinel_trip")          # and attributed

    def test_gate_is_single_none_check_when_disabled(self):
        assert guardian._SENTINEL is None   # the zero-cost contract
        net = nn.Linear(2, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        for p in opt._parameter_list:
            p._grad = jnp.ones_like(p._value)
        opt.step()                          # unguarded path still steps
        assert not guardian.events()


# -- the fit escalation ladder --------------------------------------------

def _reg_model(seed=3):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    model = paddle.Model(net, inputs=[InputSpec([None, 4], "float32", "x")],
                         labels=[InputSpec([None, 2], "float32", "y")])
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    model.prepare(opt, nn.MSELoss())
    return model


def _batches(n=30, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(8, 4).astype("float32"),
             rng.randn(8, 2).astype("float32")) for _ in range(n)]


class _ArmAt(cbks_mod.Callback):
    """Arm a failpoint at a given train step (deterministic mid-fit)."""

    def __init__(self, at_step, name, action):
        super().__init__()
        self.at_step, self.name, self.action = at_step, name, action

    def on_train_batch_end(self, step, logs=None):
        if step == self.at_step:
            failpoints.set_failpoint(self.name, self.action)


class TestFitEscalationLadder:
    def test_single_nan_batch_is_skipped_params_stay_finite(self, tmp_path):
        model = _reg_model()
        cfg = guardian.GuardianConfig(skip_limit=3, ckpt_root=None,
                                      loss_spike=False)
        model.fit(_batches(12), epochs=1, verbose=0, guardian=cfg,
                  callbacks=[_ArmAt(3, "guardian.poison_batch", "skip*1")])
        skips = guardian.events("skip_step")
        assert len(skips) == 1 and skips[0]["reason"] == "nonfinite"
        trips = guardian.events("sentinel_trip")   # jit-path attribution
        assert trips and all(t["nan_count"] > 0 for t in trips)
        for k, v in model.network.state_dict().items():
            assert np.isfinite(np.asarray(v._value)).all(), k

    def test_repeated_trips_roll_back_to_last_committed(self, tmp_path):
        # the acceptance chaos scenario: NaN grads mid-fit → skip, skip,
        # then rollback to the last COMMITTED PR-1 checkpoint, skip the
        # poisoned window, and complete training — fully automatic
        root = str(tmp_path / "guard_ckpts")
        model = _reg_model()
        # loss_spike off: the random regression targets trip the spike
        # detector too, and this scenario is the nonfinite ladder alone
        cfg = guardian.GuardianConfig(skip_limit=2, skip_window=2,
                                      ckpt_every=5, ckpt_root=root,
                                      loss_spike=False)
        model.fit(_batches(30), epochs=1, verbose=0, guardian=cfg,
                  callbacks=[_ArmAt(9, "guardian.poison_batch", "skip*5")])
        (rb,) = guardian.events("rollback")
        assert rb["restored_step"] > 0 and rb["rollbacks"] == 1
        before = [e for e in guardian.events("skip_step")
                  if e["step"] <= rb["step"]]
        assert len(before) == 3       # skip, skip, the trip that rolls back
        assert all(e["reason"] == "nonfinite" for e in before)
        assert ckpt.latest_checkpoint(root) is not None   # COMMITTED dirs
        # training completed past the poison with finite state
        res = model.train_batch([_batches(1)[0][0]], [_batches(1)[0][1]])
        final_loss = res[0][0] if isinstance(res, tuple) else res[0]
        assert math.isfinite(final_loss)
        for k, v in model.network.state_dict().items():
            assert np.isfinite(np.asarray(v._value)).all(), k

    def test_rollback_restores_bitwise_identical_state(self, tmp_path):
        root = str(tmp_path / "rb")
        model = _reg_model()
        cfg = guardian.GuardianConfig(ckpt_root=root)
        g = guardian.TrainingGuardian(cfg, model)
        model.train_batch([_batches(1)[0][0]], [_batches(1)[0][1]])
        g.save_good(step=1)
        good = {k: np.asarray(v._value).copy()
                for k, v in model.network.state_dict().items()}
        good_opt = [{k: np.asarray(v).copy() for k, v in st.items()}
                    for st in model._stepper.opt_state]
        # diverge, then roll back
        for _ in range(3):
            model.train_batch([_batches(1)[0][0]], [_batches(1)[0][1]])
        g._rollback(step=4)
        for k, v in model.network.state_dict().items():
            np.testing.assert_array_equal(np.asarray(v._value), good[k])
        for st, want in zip(model._stepper.opt_state, good_opt):
            for k, v in st.items():
                np.testing.assert_array_equal(np.asarray(v), want[k])

    def test_rollback_clears_accumulated_grads(self, tmp_path):
        # grads accumulated against pre-rollback weights must be dropped,
        # not averaged into the restored ones
        root = str(tmp_path / "acc")
        model = _reg_model()
        cfg = guardian.GuardianConfig(ckpt_root=root)
        g = guardian.TrainingGuardian(cfg, model)
        x, y = _batches(1)[0]
        model.train_batch([x], [y])
        g.save_good(step=1)
        model.train_batch([x], [y], update=False)    # half-window accum
        assert model._stepper._accum_count == 1
        g._rollback(step=2)
        assert model._stepper._accum_grads is None
        assert model._stepper._accum_count == 0

    def test_check_grads_false_skips_eager_sentinel(self):
        cfg = guardian.GuardianConfig(check_grads=False)
        g = guardian.TrainingGuardian(cfg, model=None)
        g.start()
        try:
            assert guardian._SENTINEL is None    # disabled rung honored
        finally:
            g.stop()

    def test_scaler_plus_sentinel_is_one_sync_per_step(self):
        # unscale_ hands its verdict to the sentinel: the paired
        # optimizer.step must not pay a second fused check + host sync
        net = nn.Linear(4, 2)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        guardian.install_sentinel(
            guardian.NumericSentinel(guardian.GuardianConfig()))
        scaler = amp.GradScaler(init_loss_scaling=2.0)
        for p in opt._parameter_list:
            p._grad = jnp.ones_like(p._value)
        before = guardian.host_sync_count()
        scaler.unscale_(opt)
        scaler.step(opt)
        assert guardian.host_sync_count() - before == 1

    def test_loss_spike_feeds_same_ladder(self, tmp_path):
        # spike-only trip (grads stay finite): detector fires pre-NaN
        model = _reg_model()
        cfg = guardian.GuardianConfig(skip_limit=100, spike_warmup=3,
                                      spike_zscore=4.0, check_grads=False)
        batches = _batches(10, seed=1)
        x_big, y_big = batches[6]
        batches[6] = (x_big * 1e4, y_big * 1e4)    # engineered spike
        model.fit(batches, epochs=1, verbose=0, guardian=cfg)
        assert guardian.events("loss_spike")
        skips = guardian.events("skip_step")
        assert any(s["reason"] == "loss_spike" for s in skips)

    def test_guardian_defaults_off_and_env_opt_in(self, monkeypatch):
        model = _reg_model()
        model.fit(_batches(3), epochs=1, verbose=0)
        assert model._stepper.guard_numerics is False
        assert model._stepper.last_ok is None
        assert not guardian.events()
        monkeypatch.setenv("PADDLE_GUARDIAN", "1")
        cfg = guardian.GuardianConfig.from_env()
        assert cfg is not None and cfg.check_grads

    def test_strategy_carries_guardian_knobs(self):
        from paddle_tpu.distributed.fleet import DistributedStrategy
        s = DistributedStrategy()
        assert s.guardian is False
        s.guardian = True
        s.guardian_configs["skip_limit"] = 7
        cfg = guardian.GuardianConfig.from_strategy(s)
        assert cfg.skip_limit == 7 and cfg.loss_spike


# -- data-parallel fit under guardian (two-rank mesh, GSPMD) --------------

class TestGuardianUnderDataParallel:
    def test_dp_fit_skips_in_lockstep(self, tmp_path):
        # GSPMD DP: grads are global arrays, so the fused verdict is
        # globally consistent by construction — the run must complete
        # with finite replicated params after a poisoned batch
        paddle.seed(11)
        net = nn.Sequential(nn.Linear(16, 32), nn.ReLU(),
                            nn.Linear(32, 4))
        dp = paddle.DataParallel(net)
        model = paddle.Model(dp)
        opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                    parameters=net.parameters())
        model.prepare(opt, nn.MSELoss())
        rng = np.random.RandomState(0)
        batches = [(rng.randn(16, 16).astype("f4"),
                    rng.randn(16, 4).astype("f4")) for _ in range(8)]
        cfg = guardian.GuardianConfig(skip_limit=5, loss_spike=False)
        model.fit(batches, epochs=1, verbose=0, guardian=cfg,
                  callbacks=[_ArmAt(2, "guardian.poison_batch", "skip*1")])
        assert len(guardian.events("skip_step")) == 1
        p = net.parameters()[0]
        assert p._value.sharding.is_fully_replicated
        assert np.isfinite(np.asarray(p._value)).all()


# -- collective watchdog --------------------------------------------------

class TestCollectiveWatchdog:
    def test_new_group_timeout_is_stored_not_dropped(self):
        g = collective.new_group(timeout=2.5)
        assert g.timeout == 2.5
        import datetime
        g2 = collective.new_group(
            timeout=datetime.timedelta(seconds=3))
        assert g2.timeout == 3.0

    def test_barrier_timeout_raises_and_dumps_last_ops(self):
        guardian.track_collectives(True)
        t = paddle.to_tensor(np.ones(2, dtype="f4"))
        collective.all_reduce(t)                     # lands in the ring
        failpoints.set_failpoint("collective.barrier", "delay:1.5*1")
        t0 = time.monotonic()
        with pytest.raises(TimeoutError, match="barrier"):
            collective.barrier(timeout=0.2)
        assert time.monotonic() - t0 < 1.2           # pre-deadline abort
        (ev,) = guardian.events("watchdog_timeout")
        assert ev["op"] == "barrier" and ev["timeout"] == 0.2
        assert any(o["op"] == "all_reduce" for o in ev["last_ops"])

    def test_barrier_group_timeout_honored(self):
        g = collective.new_group(timeout=0.2)
        failpoints.set_failpoint("collective.barrier", "delay:1.5*1")
        with pytest.raises(TimeoutError):
            collective.barrier(group=g)

    def test_barrier_unmonitored_and_fast_paths_ok(self):
        collective.barrier()                          # no timeout: no-op
        collective.barrier(timeout=5.0)               # fast body: passes
        assert not guardian.events("watchdog_timeout")

    def test_run_with_deadline_propagates_body_error(self):
        with pytest.raises(KeyError):
            guardian.run_with_deadline(
                lambda: (_ for _ in ()).throw(KeyError("x")),
                timeout=1.0, op="test")


# -- check_numerics routing -----------------------------------------------

class TestCheckNumerics:
    def test_clean_tensor_passes_silently(self):
        t = paddle.to_tensor(np.ones(4, dtype="f4"))
        amp.debugging.check_numerics(t, "relu", "out")
        assert not guardian.events("check_numerics")

    def test_nan_tensor_raises_through_guardian_log(self):
        t = paddle.to_tensor(np.asarray([1.0, float("nan")], dtype="f4"))
        with pytest.raises(FloatingPointError, match="1 NaN"):
            amp.debugging.check_numerics(t, "log", "x")
        (ev,) = guardian.events("check_numerics")
        assert ev["op_type"] == "log" and ev["nan_count"] == 1
        assert ev["forced"] is False

    def test_failpoint_forces_trip_on_clean_tensor(self):
        failpoints.set_failpoint("guardian.check_numerics", "skip*1")
        t = paddle.to_tensor(np.ones(4, dtype="f4"))
        with pytest.raises(FloatingPointError, match="forced"):
            amp.debugging.check_numerics(t, "matmul", "y")
        (ev,) = guardian.events("check_numerics")
        assert ev["forced"] is True
        amp.debugging.check_numerics(t, "matmul", "y")   # drained: clean

    def test_finite_float64_above_f32_max_passes(self):
        # native numpy dtypes are never cast through f32 — a finite f64
        # of 1e300 must not be misreported as Inf
        amp.debugging.check_numerics(np.asarray([1e300, 2.0]), "op", "v")
        assert not guardian.events("check_numerics")
