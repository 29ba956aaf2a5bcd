"""Elastic resharded resume (ISSUE 14): checkpoints that survive
topology changes, end to end.

The layout manifest written by ``save_checkpoint`` records the mesh,
per-array PartitionSpecs, world size, RNG stream, data cursor and
sharding plan; a manifest-aware restore re-derives target shardings for
whatever mesh the relaunched process comes up with.  The acceptance
chaos e2e kills a dp4×mp2 np=8 run mid-epoch (PR 1 preemption
contract + step-dir commit protocol) and resumes it at np=4 with a
different dp×mp split, comparing final params BITWISE against an
uninterrupted same-seed run.

Bitwise-across-topology note: the e2e uses integer-grid data/params
and a dyadic learning rate so every cross-shard reduction is *exact*
in fp32 — exact sums are association-invariant, so the bitwise
equality is meaningful across ANY dp×mp split (with generic float
data, re-associating a reduction moves the last ulp; that inherent
float caveat is asserted at ulp tolerance separately).
"""
import json
import os
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.observability as obs
from paddle_tpu.framework import failpoints, guardian, preemption
from paddle_tpu.framework import random as prandom
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.distributed.engine import PlacementPlan
from paddle_tpu.distributed.fleet.meta_parallel.parallel_layers.mp_layers \
    import ColumnParallelLinear
from paddle_tpu.hapi import callbacks as cbks_mod

pytestmark = [pytest.mark.chaos, pytest.mark.multichip]

DEVS = np.asarray(jax.devices())


def mesh8():
    return Mesh(DEVS.reshape(4, 2), ("data", "model"))


def mesh4():
    return Mesh(DEVS[:4].reshape(2, 2), ("data", "model"))


@pytest.fixture(autouse=True)
def _clean():
    failpoints.clear()
    preemption.reset()
    guardian.clear_events()
    was_on = obs.enabled()
    obs.enable(True)
    obs.get_registry().reset()
    yield
    failpoints.clear()
    preemption.reset()
    obs.enable(was_on)


def _sharded_state(mesh):
    """A small state dict with genuinely sharded + replicated arrays."""
    w = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                       NamedSharding(mesh, P("data", "model")))
    b = jax.device_put(jnp.arange(8, dtype=jnp.float32),
                       NamedSharding(mesh, P()))
    h = jax.device_put((jnp.arange(16, dtype=jnp.float32) / 7.0)
                       .astype(jnp.bfloat16),
                       NamedSharding(mesh, P("model")))
    return {"layer": {"w": w, "half": h}, "b": b}


def _counter(name, **labels):
    m = obs.get_registry().get(name)
    return 0 if m is None else m.value(**labels)


# -- manifest round trip ---------------------------------------------------

class TestManifest:
    def test_manifest_committed_with_sentinel(self, tmp_path):
        root = str(tmp_path)
        sd = _sharded_state(mesh8())
        p = ckpt.save_checkpoint(sd, root, step=5, manifest=True)
        assert os.path.exists(os.path.join(p, "COMMITTED"))
        man = ckpt.load_manifest(p)
        assert man["step"] == 5
        assert man["world_size"] == 8
        assert man["mesh"] == {"axis_names": ["data", "model"],
                               "shape": [4, 2]}
        assert man["pspecs"]["layer.w"] == ["data", "model"]
        assert man["pspecs"]["layer.half"] == ["model"]
        assert man["rng"]["key_data"]  # the global key chain is recorded

    def test_manifest_aware_restore_onto_smaller_mesh(self, tmp_path):
        # np=8 dp4×mp2 save → np=4 dp2×mp2 restore with NO template:
        # targets re-derived from the manifest's saved PartitionSpecs
        root = str(tmp_path)
        sd = _sharded_state(mesh8())
        ckpt.save_checkpoint(sd, root, step=1, manifest=True)
        m4 = mesh4()
        out, man, d = ckpt.restore_latest(root, mesh=m4)
        assert man["world_size"] == 8
        w = out["layer.w"]
        assert w.sharding.mesh.size == 4
        assert tuple(w.sharding.spec) == ("data", "model")
        np.testing.assert_array_equal(np.asarray(w),
                                      np.asarray(sd["layer"]["w"]))
        # reshard is observable: guardian event + counter + histogram
        ev = guardian.events("elastic_reshard")
        assert ev and ev[-1]["old_np"] == 8 and ev[-1]["new_np"] == 4
        assert ev[-1]["source"] == "load"
        assert _counter("pt_checkpoint_reshard_total", kind="load") == 1

    def test_bf16_bitwise_across_mesh_change(self, tmp_path):
        root = str(tmp_path)
        sd = _sharded_state(mesh8())
        ckpt.save_checkpoint(sd, root, step=1, manifest=True)
        out = ckpt.load_state_dict(ckpt.latest_checkpoint(root),
                                   mesh=mesh4())
        h = out["layer.half"]
        assert h.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(h).view(np.uint16),
            np.asarray(sd["layer"]["half"]).view(np.uint16))

    def test_np1_single_device_restore_of_distributed_checkpoint(
            self, tmp_path):
        root = str(tmp_path)
        sd = _sharded_state(mesh8())
        ckpt.save_checkpoint(sd, root, step=1, manifest=True)
        out = ckpt.load_state_dict(ckpt.latest_checkpoint(root))
        for key, ref in (("layer.w", sd["layer"]["w"]), ("b", sd["b"])):
            np.testing.assert_array_equal(np.asarray(out[key]),
                                          np.asarray(ref))

    def test_replicated_to_sharded_and_back(self, tmp_path):
        # opt-state style round trip: replicated→sharded via explicit
        # target, sharded→replicated via a replicated-template restore
        root = str(tmp_path)
        m8, m4 = mesh8(), mesh4()
        rep = jax.device_put(jnp.arange(16, dtype=jnp.float32),
                             NamedSharding(m8, P()))
        ckpt.save_checkpoint({"v": rep}, root, step=1, manifest=True)
        shard = ckpt.load_state_dict(
            ckpt.latest_checkpoint(root),
            shardings={"v": NamedSharding(m4, P(("data",)))})["v"]
        assert not shard.sharding.is_fully_replicated
        np.testing.assert_array_equal(np.asarray(shard), np.asarray(rep))
        root2 = str(tmp_path / "r2")
        ckpt.save_checkpoint({"v": shard}, root2, step=1, manifest=True)
        back = ckpt.load_state_dict(
            ckpt.latest_checkpoint(root2),
            template={"v": rep})["v"]
        assert back.sharding.is_fully_replicated
        np.testing.assert_array_equal(np.asarray(back), np.asarray(rep))

    def test_indivisible_dim_falls_back_to_replicated(self, tmp_path):
        # a saved axis the new mesh can't divide evenly is dropped, not
        # an error — elastic resume must accept any legal mesh
        root = str(tmp_path)
        m8 = mesh8()
        odd = jax.device_put(jnp.arange(12, dtype=jnp.float32).reshape(4, 3),
                             NamedSharding(m8, P("data", None)))
        ckpt.save_checkpoint({"odd": odd}, root, step=1, manifest=True)
        m3 = Mesh(DEVS[:3].reshape(3, 1), ("data", "model"))
        out = ckpt.load_state_dict(ckpt.latest_checkpoint(root),
                                   mesh=m3)["odd"]
        assert out.sharding.is_fully_replicated   # 4 % 3 != 0 → dropped
        np.testing.assert_array_equal(np.asarray(out), np.asarray(odd))

    def test_manifest_missing_falls_back_to_template_path(self, tmp_path):
        # PR 1 checkpoints carry no manifest: template restore still works
        root = str(tmp_path)
        sd = _sharded_state(mesh8())
        p = ckpt.save_checkpoint(sd, root, step=1)       # no manifest
        assert ckpt.load_manifest(p) is None
        out, man, _ = ckpt.restore_latest(root, template=sd)
        assert man is None
        np.testing.assert_array_equal(np.asarray(out["layer.w"]),
                                      np.asarray(sd["layer"]["w"]))

    def test_rng_round_trip(self, tmp_path):
        paddle.seed(1234)
        prandom.next_key()                      # advance the chain
        key_before = prandom.get_rng_state()[0]
        man = ckpt.build_manifest({"x": jnp.zeros(2)}, step=0)
        restored = ckpt.rng_state_from_manifest(man)
        assert np.array_equal(jax.random.key_data(restored),
                              jax.random.key_data(key_before))


# -- manifest chaos --------------------------------------------------------

class TestManifestChaos:
    def test_kill_between_shard_write_and_manifest_commit(self, tmp_path):
        # a crash before the manifest lands leaves NO sentinel: the dir
        # is torn and the resume path skips it cleanly — with the skip
        # booked as a checkpoint_fallback event, never silent
        root = str(tmp_path)
        sd1 = _sharded_state(mesh8())
        ckpt.save_checkpoint(sd1, root, step=1, manifest=True)
        failpoints.set_failpoint("ckpt.write_manifest", "error")
        with pytest.raises(ConnectionError):
            ckpt.save_checkpoint(_sharded_state(mesh8()), root, step=2,
                                 manifest=True)
        failpoints.clear()
        p2 = os.path.join(root, "step_00000002")
        assert not os.path.exists(os.path.join(p2, "COMMITTED"))
        out, man, d = ckpt.restore_latest(root, mesh=mesh4())
        assert man["step"] == 1 and d.endswith("step_00000001")
        ev = guardian.events("checkpoint_fallback")
        assert ev and ev[-1]["kind"] == "torn" and ev[-1]["step"] == 2
        assert _counter("pt_checkpoint_fallbacks_total", kind="torn") == 1

    def test_torn_manifest_degrades_to_template_restore(self, tmp_path):
        # checkpoint.manifest_torn truncates the manifest but the
        # sentinel still lands: the loader warns and restores via the
        # template path instead of failing the resume
        root = str(tmp_path)
        sd = _sharded_state(mesh8())
        failpoints.set_failpoint("checkpoint.manifest_torn", "skip")
        p = ckpt.save_checkpoint(sd, root, step=3, manifest=True)
        failpoints.clear()
        assert os.path.exists(os.path.join(p, "COMMITTED"))
        assert ckpt.load_manifest(p) is None     # unreadable, not fatal
        out = ckpt.load_state_dict(p, template=sd)
        np.testing.assert_array_equal(np.asarray(out["layer.w"]),
                                      np.asarray(sd["layer"]["w"]))

    def test_resave_of_committed_step_uncommits_first(self, tmp_path):
        # re-writing an already-committed step dir (same global step)
        # must drop the sentinel BEFORE touching shards: a kill mid-
        # rewrite then reads as torn, never as committed-with-torn-
        # shards — the state the sentinel-last protocol forbids
        root = str(tmp_path)
        p = ckpt.save_checkpoint({"v": jnp.arange(4.0)}, root, step=1,
                                 manifest=True)
        assert os.path.exists(os.path.join(p, "COMMITTED"))
        failpoints.set_failpoint("ckpt.commit_sentinel", "skip")
        ckpt.save_checkpoint({"v": jnp.arange(4.0) * 2}, root, step=1,
                             manifest=True)
        failpoints.clear()
        assert not os.path.exists(os.path.join(p, "COMMITTED"))
        assert ckpt.latest_checkpoint(root) is None   # honestly torn
        # a clean re-save re-commits
        ckpt.save_checkpoint({"v": jnp.arange(4.0) * 3}, root, step=1,
                             manifest=True)
        out = ckpt.load_state_dict(root)
        np.testing.assert_array_equal(np.asarray(out["v"]),
                                      np.arange(4.0) * 3)

    def test_corrupt_fallback_emits_event(self, tmp_path):
        root = str(tmp_path)
        ckpt.save_checkpoint({"v": jnp.arange(8.0)}, root, step=1,
                             manifest=True)
        p2 = ckpt.save_checkpoint({"v": jnp.arange(8.0) * 2}, root,
                                  step=2, manifest=True)
        # flip payload bytes in step 2's shard
        for dirpath, _, files in os.walk(p2):
            for fn in files:
                if fn.endswith(".npy"):
                    fp = os.path.join(dirpath, fn)
                    with open(fp, "r+b") as f:
                        f.seek(-4, os.SEEK_END)
                        raw = f.read(4)
                        f.seek(-4, os.SEEK_END)
                        f.write(bytes(b ^ 0xFF for b in raw))
        out, man, d = ckpt.restore_latest(root)
        assert d.endswith("step_00000001")
        ev = guardian.events("checkpoint_fallback")
        assert any(e["kind"] == "corrupt" and e["step"] == 2 for e in ev)
        assert _counter("pt_checkpoint_fallbacks_total",
                        kind="corrupt") == 1


# -- retention sweep vs concurrent reader ----------------------------------

class TestRetentionReadRace:
    def test_sweep_never_deletes_dir_under_live_restore(self, tmp_path):
        # regression (ISSUE 14 satellite): the sweep used to rmtree a
        # committed step another restore was mid-read from.  Park a
        # reader on step 1 via the read failpoint, commit new steps
        # with keep_last=1 while it reads, and require the read to
        # finish intact.
        root = str(tmp_path)
        sd = {"v": jnp.arange(32, dtype=jnp.float32)}
        p1 = ckpt.save_checkpoint(sd, root, step=1, manifest=True)
        failpoints.set_failpoint("ckpt.read_shard", "delay:0.4*1")
        result, errs = [], []

        def reader():
            try:
                result.append(ckpt.load_state_dict(p1))
            except Exception as e:      # surfaced to the main thread
                errs.append(e)

        t = threading.Thread(target=reader)
        t.start()
        time.sleep(0.15)               # reader is parked in the delay
        ckpt.save_checkpoint({"v": jnp.arange(32.0) * 2}, root, step=2,
                             keep_last=1, manifest=True)
        ckpt.save_checkpoint({"v": jnp.arange(32.0) * 3}, root, step=3,
                             keep_last=1, manifest=True)
        t.join(timeout=20)
        assert not t.is_alive()
        assert not errs, f"reader died: {errs}"
        np.testing.assert_array_equal(np.asarray(result[0]["v"]),
                                      np.asarray(sd["v"]))
        # once the read finishes, the next sweep may collect step 1
        ckpt.save_checkpoint({"v": jnp.arange(32.0)}, root, step=4,
                             keep_last=1)
        assert not os.path.exists(p1)

    def test_foreign_read_sentinel_pins_until_grace(self, tmp_path,
                                                    monkeypatch):
        # cross-process form: a fresh .READING.* file (another process's
        # restore) pins the dir; a stale one (dead reader) does not
        root = str(tmp_path)
        p1 = ckpt.save_checkpoint({"v": jnp.arange(4.0)}, root, step=1)
        sentinel = os.path.join(p1, ".READING.99999.deadbeef")
        with open(sentinel, "w") as f:
            f.write("x")
        ckpt.save_checkpoint({"v": jnp.arange(4.0)}, root, step=2,
                             keep_last=1)
        assert os.path.exists(p1)              # pinned by the sentinel
        monkeypatch.setenv("PADDLE_CKPT_READ_GRACE", "0")
        ckpt.save_checkpoint({"v": jnp.arange(4.0)}, root, step=3,
                             keep_last=1)
        assert not os.path.exists(p1)          # stale sentinel expired


# -- Model.fit(resume=) round trip -----------------------------------------

def _reg_model(seed):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    model.prepare(opt, nn.MSELoss())
    return model, net


def _float_batches(n, bs=8, din=4, dout=2, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(bs, din).astype("f4"),
             rng.randn(bs, dout).astype("f4")) for _ in range(n)]


class _KillAt(cbks_mod.Callback):
    def __init__(self, at_step):
        super().__init__()
        self.at_step = at_step

    def on_train_batch_end(self, step, logs=None):
        if step == self.at_step:
            preemption.request()


class TestModelResume:
    def test_emergency_save_is_manifest_format(self, tmp_path):
        # the preemption path and Model.fit(resume=) round-trip through
        # ONE format: the step-dir manifest protocol (the legacy
        # preempted.pdparams/.pdopt swap is gone)
        sd = str(tmp_path)
        model, _ = _reg_model(3)
        batches = _float_batches(8)
        with pytest.raises(SystemExit) as exc_info:
            model.fit(batches, epochs=2, save_dir=sd, verbose=0,
                      callbacks=[_KillAt(2)])
        assert exc_info.value.code == preemption.PREEMPTED_EXIT_CODE
        steps = [d for d in os.listdir(sd) if d.startswith("step_")]
        assert len(steps) == 1
        p = os.path.join(sd, steps[0])
        assert os.path.exists(os.path.join(p, "COMMITTED"))
        man = ckpt.load_manifest(p)
        assert man["data_cursor"] == {"epoch": 0, "step": 2}
        assert man["opt"]["global_step"] == 3
        assert not os.path.exists(os.path.join(sd, "preempted.COMMITTED"))

    def test_resume_bitwise_equals_uninterrupted(self, tmp_path):
        # single-device: kill at epoch 0 step 2, resume a FRESH model
        # (different init seed — the checkpoint must fully win) and
        # finish; final params bitwise == an uninterrupted run.  Step
        # counter, opt state, RNG stream and data cursor all restored.
        batches = _float_batches(6)
        ref, refnet = _reg_model(3)
        ref.fit(batches, epochs=2, verbose=0)
        refp = {k: np.asarray(v._value)
                for k, v in refnet.state_dict().items()}

        sd = str(tmp_path)
        m1, _ = _reg_model(3)
        preemption.reset()
        with pytest.raises(SystemExit):
            m1.fit(batches, epochs=2, save_dir=sd, verbose=0,
                   callbacks=[_KillAt(2)])
        preemption.reset()
        m2, net2 = _reg_model(99)              # different init on purpose
        m2.fit(batches, epochs=2, verbose=0, resume=sd)
        for k, v in net2.state_dict().items():
            np.testing.assert_array_equal(np.asarray(v._value), refp[k])
        assert m2._optimizer._global_step == ref._optimizer._global_step

    def test_resume_restores_rng_stream(self, tmp_path):
        sd = str(tmp_path)
        m1, _ = _reg_model(3)
        with pytest.raises(SystemExit):
            m1.fit(_float_batches(8), epochs=1, save_dir=sd, verbose=0,
                   callbacks=[_KillAt(1)])
        preemption.reset()
        man = ckpt.load_manifest(ckpt.latest_checkpoint(sd))
        paddle.seed(424242)                    # perturb the global chain
        m2, _ = _reg_model(77)
        m2.fit(_float_batches(8), epochs=1, num_iters=0, verbose=0,
               resume=sd)
        assert jax.random.key_data(
            prandom.get_rng_state()[0]).tolist() == man["rng"]["key_data"]
        # the originating seed rides along: manifests written after the
        # resume must not record this process's default seed
        assert prandom.get_seed() == man["rng"]["seed"] == 3

    def test_resume_empty_root_starts_fresh(self, tmp_path):
        m, net = _reg_model(5)
        before = {k: np.asarray(v._value)
                  for k, v in net.state_dict().items()}
        m.fit(_float_batches(2), epochs=1, verbose=0,
              resume=str(tmp_path))            # nothing there: no error
        after = {k: np.asarray(v._value)
                 for k, v in net.state_dict().items()}
        assert any(not np.array_equal(before[k], after[k])
                   for k in before)            # it actually trained

    def test_periodic_epoch_end_manifest_checkpoint(self, tmp_path):
        # crash WITHOUT the SIGTERM grace: fit(save_dir=) commits a
        # manifest step at every epoch boundary, and a relaunch resumes
        # from the last one through the same fit(resume=) path
        sd = str(tmp_path)
        batches = _float_batches(4)
        ref, refnet = _reg_model(3)
        ref.fit(batches, epochs=3, verbose=0)
        refp = {k: np.asarray(v._value)
                for k, v in refnet.state_dict().items()}

        m1, _ = _reg_model(3)
        m1.fit(batches, epochs=2, save_dir=sd, verbose=0)   # "crashes" here
        steps = sorted(d for d in os.listdir(sd) if d.startswith("step_"))
        assert len(steps) == 2                              # one per epoch
        man = ckpt.load_manifest(os.path.join(sd, steps[-1]))
        assert man["data_cursor"] == {"epoch": 1, "step": "epoch-end"}
        m2, net2 = _reg_model(99)
        m2.fit(batches, epochs=3, verbose=0, resume=sd)     # epoch 2 only
        for k, v in net2.state_dict().items():
            np.testing.assert_array_equal(np.asarray(v._value), refp[k])

    def test_preempt_at_epoch_boundary_skips_duplicate_save(
            self, tmp_path):
        # SIGTERM lands during the epoch-end window: the periodic save
        # already committed this global step, so the emergency save
        # must not burn the kill grace re-writing identical state
        sd = str(tmp_path)

        class KillAtEpochEnd(cbks_mod.Callback):
            def on_epoch_end(self, epoch, logs=None):
                preemption.request()

        m1, _ = _reg_model(3)
        with pytest.raises(SystemExit):
            m1.fit(_float_batches(4), epochs=2, save_dir=sd, verbose=0,
                   callbacks=[KillAtEpochEnd()])
        preemption.reset()
        steps = [d for d in os.listdir(sd) if d.startswith("step_")]
        assert len(steps) == 1                 # periodic save, no dupe
        man = ckpt.load_manifest(os.path.join(sd, steps[0]))
        assert man["data_cursor"]["step"] == "epoch-end"
        m2, _ = _reg_model(99)
        m2.fit(_float_batches(4), epochs=2, verbose=0, resume=sd)

    def test_eager_resume_keeps_optimizer_moments(self, tmp_path):
        # prepare(jit=False): the emergency save must carry the eager
        # accumulators — the old .pdopt path did, the manifest path
        # must not regress it
        def mk_eager(seed):
            paddle.seed(seed)
            net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(),
                                nn.Linear(8, 2))
            m = paddle.Model(net)
            opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=net.parameters())
            m.prepare(opt, nn.MSELoss(), jit=False)
            return m, net

        batches = _float_batches(6)
        ref, refnet = mk_eager(3)
        ref.fit(batches, epochs=1, verbose=0)
        refp = {k: np.asarray(v._value)
                for k, v in refnet.state_dict().items()}

        sd = str(tmp_path)
        m1, _ = mk_eager(3)
        with pytest.raises(SystemExit):
            m1.fit(batches, epochs=1, save_dir=sd, verbose=0,
                   callbacks=[_KillAt(2)])
        preemption.reset()
        flat = ckpt.load_state_dict(ckpt.latest_checkpoint(sd))
        assert any(k.startswith("opt.") for k in flat)   # moments saved
        m2, net2 = mk_eager(99)
        m2.fit(batches, epochs=1, verbose=0, resume=sd)
        for k, v in net2.state_dict().items():
            np.testing.assert_allclose(np.asarray(v._value), refp[k],
                                       rtol=1e-6, atol=1e-7)

    def test_manifest_saves_replace_legacy_epoch_pickles(self, tmp_path):
        # fit's step-dir manifest checkpoints own the periodic cadence:
        # the auto-added ModelCheckpoint no longer doubles every epoch
        # save as a <epoch>.pdparams pickle (its `final` save stays)
        sd = str(tmp_path)
        m, _ = _reg_model(3)
        m.fit(_float_batches(3), epochs=2, save_dir=sd, verbose=0)
        names = os.listdir(sd)
        assert sum(1 for n in names if n.startswith("step_")) == 2
        assert "final.pdparams" in names          # compat surface kept
        assert not any(n in ("0.pdparams", "1.pdparams") for n in names)

    def test_preemption_during_skip_replay_exits_promptly(self, tmp_path):
        # SIGTERM while fast-forwarding the data cursor must honor the
        # exit-71 contract without waiting for the first real batch
        sd = str(tmp_path)
        m1, _ = _reg_model(3)
        with pytest.raises(SystemExit):
            m1.fit(_float_batches(8), epochs=1, save_dir=sd, verbose=0,
                   callbacks=[_KillAt(4)])
        preemption.reset()
        m2, _ = _reg_model(99)
        preemption.request()                   # preempted before replay
        with pytest.raises(SystemExit) as exc_info:
            m2.fit(_float_batches(8), epochs=1, verbose=0, resume=sd)
        assert exc_info.value.code == preemption.PREEMPTED_EXIT_CODE

    def test_torn_manifest_resume_keeps_step_counter_monotonic(
            self, tmp_path):
        # manifest unreadable (documented degrade): params restore via
        # the template path, and the global step is recovered from the
        # step-dir number — later periodic saves must write NEWER
        # steps, never regress behind the committed dir
        sd = str(tmp_path)
        m1, _ = _reg_model(3)
        failpoints.set_failpoint("checkpoint.manifest_torn", "skip")
        with pytest.raises(SystemExit):
            m1.fit(_float_batches(8), epochs=1, save_dir=sd, verbose=0,
                   callbacks=[_KillAt(3)])
        failpoints.clear()
        preemption.reset()
        step_dir = ckpt.latest_checkpoint(sd)
        assert ckpt.load_manifest(step_dir) is None
        m2, _ = _reg_model(99)
        m2.fit(_float_batches(8), epochs=1, verbose=0, resume=sd,
               save_dir=sd)
        assert m2._optimizer._global_step > 4   # counted FORWARD from 4
        assert os.path.basename(ckpt.latest_checkpoint(sd)) > \
            os.path.basename(step_dir)          # newer step committed

    def test_foreign_checkpoint_fails_loudly(self, tmp_path):
        # a root whose state shares no keys with the model (e.g. a
        # guardian ckpt_root) must raise, not report an empty "resume"
        root = str(tmp_path)
        ckpt.save_checkpoint({"param.whatever": jnp.arange(4.0)}, root,
                             step=1, manifest=True)
        m, _ = _reg_model(5)
        with pytest.raises(ValueError, match="shares no keys"):
            m.fit(_float_batches(2), epochs=1, verbose=0, resume=root)

    def test_old_torn_debris_not_rebooked(self, tmp_path):
        # only torn dirs NEWER than the restored step are booked as
        # fallbacks: old debris re-reported on every resume would make
        # the event unusable for alerting
        root = str(tmp_path)
        failpoints.set_failpoint("ckpt.commit_sentinel", "skip*1")
        ckpt.save_checkpoint({"v": jnp.arange(4.0)}, root, step=1)  # torn
        ckpt.save_checkpoint({"v": jnp.arange(4.0)}, root, step=2,
                             manifest=True)
        guardian.clear_events()
        ckpt.restore_latest(root)
        assert guardian.events("checkpoint_fallback") == []

    def test_torn_emergency_save_resumes_fresh(self, tmp_path):
        # writer killed before the sentinel: the resume path must skip
        # the torn dir and (with no older step) start fresh, loudly
        sd = str(tmp_path)
        m1, _ = _reg_model(3)
        failpoints.set_failpoint("ckpt.commit_sentinel", "skip")
        with pytest.raises(SystemExit):
            m1.fit(_float_batches(8), epochs=1, save_dir=sd, verbose=0,
                   callbacks=[_KillAt(1)])
        failpoints.clear()
        preemption.reset()
        steps = [d for d in os.listdir(sd) if d.startswith("step_")]
        assert steps and not os.path.exists(
            os.path.join(sd, steps[0], "COMMITTED"))
        m2, _ = _reg_model(99)
        m2.fit(_float_batches(8), epochs=1, verbose=0, resume=sd)
        assert guardian.events("checkpoint_fallback")   # skip was booked


# -- the acceptance chaos e2e: np=8 → np=4 across a dp×mp change -----------

D_IN, D_OUT, BS = 8, 2, 16


def _int_model(mesh, seed):
    """Integer-grid column-parallel regression model (see module
    docstring): every cross-shard sum stays exact in fp32, so the
    final-params comparison is bitwise across ANY dp×mp split."""
    paddle.seed(seed)
    net = nn.Sequential(ColumnParallelLinear(D_IN, D_OUT,
                                             gather_output=True))
    r = np.random.RandomState(11)
    for p in net.parameters():
        p._value = jnp.asarray(
            r.randint(-1, 2, tuple(p.shape)).astype("f4"))
    if mesh is not None:
        net._placement_plan = PlacementPlan(mesh, batch_axes=("data",))
    model = paddle.Model(net)
    opt = paddle.optimizer.Momentum(learning_rate=0.25, momentum=0.5,
                                    parameters=net.parameters())
    model.prepare(opt, nn.MSELoss())
    return model, net


def _int_batches(n=3, seed=1):
    r = np.random.RandomState(seed)
    return [(r.randint(-1, 2, (BS, D_IN)).astype("f4"),
             r.randint(-1, 2, (BS, D_OUT)).astype("f4"))
            for _ in range(n)]


class TestElasticReshardE2E:
    def test_kill_np8_resume_np4_bitwise(self, tmp_path):
        # THE acceptance run: train on the np=8 dp4×mp2 CPU-proxy mesh,
        # kill mid-run through the PR 1 preemption contract (emergency
        # manifest save + exit 71), resume on np=4 dp2×mp2, and compare
        # final params BITWISE against uninterrupted same-seed runs at
        # np=1 AND np=8.
        batches = _int_batches()
        ref1, refnet1 = _int_model(None, seed=7)
        ref1.fit(batches, epochs=1, verbose=0)
        p_np1 = {k: np.asarray(v._value)
                 for k, v in refnet1.state_dict().items()}
        ref8, refnet8 = _int_model(mesh8(), seed=7)
        ref8.fit(batches, epochs=1, verbose=0)
        for k, v in refnet8.state_dict().items():
            np.testing.assert_array_equal(np.asarray(v._value), p_np1[k])

        sd = str(tmp_path)
        m8, _ = _int_model(mesh8(), seed=7)
        with pytest.raises(SystemExit) as exc_info:
            m8.fit(batches, epochs=1, save_dir=sd, verbose=0,
                   callbacks=[_KillAt(1)])
        assert exc_info.value.code == preemption.PREEMPTED_EXIT_CODE
        preemption.reset()
        man = ckpt.load_manifest(ckpt.latest_checkpoint(sd))
        assert man["mesh"]["shape"] == [4, 2]        # written at np=8
        assert man["pspecs"]["model.0.weight"] == [None, "model"]
        assert man["pspecs"]["opt.0.weight.velocity"] == [None, "model"]

        m4, net4 = _int_model(mesh4(), seed=123)     # np=4, fresh init
        m4.fit(batches, epochs=1, verbose=0, resume=sd)
        for k, v in net4.state_dict().items():
            np.testing.assert_array_equal(np.asarray(v._value), p_np1[k])
        # params and opt state really live on the np=4 mesh
        w = net4.state_dict()["0.weight"]._value
        assert w.sharding.mesh.size == 4
        assert tuple(w.sharding.spec) == (None, "model")
        vel = m4._stepper.opt_state[0]["velocity"]
        assert vel.sharding.mesh.size == 4
        ev = guardian.events("elastic_reshard")
        assert ev and (ev[-1]["old_np"], ev[-1]["new_np"]) == (8, 4)

    def test_float_reshard_resume_at_ulp_tolerance(self, tmp_path):
        # generic float data across the same topology change: the state
        # RESTORE is bitwise (asserted on the first post-restore
        # params), and the continued run tracks the uninterrupted one
        # at ulp-level tolerance — re-associating cross-shard sums
        # moves the last bit, same reason PR 6's DP-vs-single-device
        # parity is rtol-bounded.
        def mk(mesh, seed=7):
            paddle.seed(seed)
            net = nn.Sequential(
                ColumnParallelLinear(8, 16, gather_output=True),
                nn.ReLU(),
                ColumnParallelLinear(16, 6, gather_output=True))
            r = np.random.RandomState(11)
            for p in net.parameters():
                p._value = jnp.asarray(
                    r.randn(*tuple(p.shape)).astype("f4") * 0.5)
            if mesh is not None:
                net._placement_plan = PlacementPlan(
                    mesh, batch_axes=("data",))
            model = paddle.Model(net)
            opt = paddle.optimizer.Momentum(
                learning_rate=0.05, momentum=0.9,
                parameters=net.parameters())
            model.prepare(opt, nn.MSELoss())
            return model, net

        r = np.random.RandomState(1)
        batches = [(r.randn(32, 8).astype("f4"),
                    r.randn(32, 6).astype("f4")) for _ in range(6)]
        ref, refnet = mk(mesh8())
        ref.fit(batches, epochs=1, verbose=0)
        refp = {k: np.asarray(v._value)
                for k, v in refnet.state_dict().items()}

        sd = str(tmp_path)
        m8, net8 = mk(mesh8())
        with pytest.raises(SystemExit):
            m8.fit(batches, epochs=1, save_dir=sd, verbose=0,
                   callbacks=[_KillAt(2)])
        at_kill = {k: np.asarray(v._value)
                   for k, v in net8.state_dict().items()}
        preemption.reset()
        m4, net4 = mk(mesh4(), seed=123)
        cursor = m4._resume_from(sd)
        assert cursor == (0, 3)
        for k, v in net4.state_dict().items():      # restore IS bitwise
            np.testing.assert_array_equal(np.asarray(v._value),
                                          at_kill[k])
        m4b, net4b = mk(mesh4(), seed=321)
        m4b.fit(batches, epochs=1, verbose=0, resume=sd)
        for k, v in net4b.state_dict().items():
            np.testing.assert_allclose(np.asarray(v._value), refp[k],
                                       rtol=1e-5, atol=1e-6)

    def test_zero1_opt_state_resharded_parity(self, tmp_path):
        # ZeRO-1: optimizer moments sharded on the fsdp axis are
        # re-partitioned 2-way → 4-way across the resume (plan-based,
        # PR 6's sharding plans); training parity vs the single-device
        # golden holds at the documented mesh tolerance.
        # hidden width 48: chosen so no opt-state leaf's LOCAL shard
        # shape collides with a network output's shape on either mesh —
        # XLA's donation aliasing mispairs them and aborts (pre-existing
        # stepper quirk, reproducible without any resume involved)
        def mk(mesh, level, seed=3):
            paddle.seed(seed)
            net = nn.Sequential(nn.Linear(16, 48), nn.ReLU(),
                                nn.Linear(48, 10))
            if mesh is not None:
                net._placement_plan = PlacementPlan(mesh, level=level)
            model = paddle.Model(net)
            opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=net.parameters())
            model.prepare(opt, nn.CrossEntropyLoss())
            return model, net

        rng = np.random.RandomState(0)
        batches = [(rng.rand(16, 16).astype("f4"),
                    rng.randint(0, 10, (16, 1)).astype("i8"))
                   for _ in range(6)]
        golden, gnet = mk(None, None)
        golden.fit(batches, epochs=1, verbose=0)
        gp = {k: np.asarray(v._value)
              for k, v in gnet.state_dict().items()}

        sd = str(tmp_path)
        mz8 = Mesh(DEVS.reshape(4, 2), ("data", "sharding"))
        m8, _ = mk(mz8, "os")
        with pytest.raises(SystemExit):
            m8.fit(batches, epochs=1, save_dir=sd, verbose=0,
                   callbacks=[_KillAt(2)])
        preemption.reset()
        # moments were sharded 2-way on the fsdp axis at save time
        man = ckpt.load_manifest(ckpt.latest_checkpoint(sd))
        assert any("sharding" in str(v) for k, v in man["pspecs"].items()
                   if k.startswith("opt."))

        mz4 = Mesh(DEVS[:4].reshape(1, 4), ("data", "sharding"))
        m4, net4 = mk(mz4, "os", seed=55)
        m4.fit(batches, epochs=1, verbose=0, resume=sd)
        sharded = [v for st in m4._stepper.opt_state for v in st.values()
                   if hasattr(v, "sharding") and v.ndim >= 1 and
                   not v.sharding.is_fully_replicated]
        assert sharded, "resumed ZeRO-1 moments stayed replicated"
        assert all(v.sharding.mesh.size == 4 for v in sharded)
        for k, v in net4.state_dict().items():
            np.testing.assert_allclose(np.asarray(v._value), gp[k],
                                       rtol=2e-4, atol=2e-5)


# -- launcher / elastic wiring ---------------------------------------------

def _launch_main():
    import importlib
    return importlib.import_module("paddle_tpu.distributed.launch.main")


class TestLauncherReshard:
    def test_note_reshard_emits_event_and_metric(self):
        launch_main = _launch_main()
        launch_main._note_reshard(8, 4, "/ckpts/job")
        ev = guardian.events("elastic_reshard")
        assert ev[-1] == {**ev[-1], "old_np": 8, "new_np": 4,
                          "root": "/ckpts/job", "source": "relaunch"}
        assert _counter("pt_checkpoint_reshard_total",
                        kind="relaunch") == 1

    def test_note_reshard_honors_failpoint(self):
        launch_main = _launch_main()
        failpoints.set_failpoint("elastic.reshard", "error*1")
        with pytest.raises(ConnectionError):
            launch_main._note_reshard(8, 4, "/ckpts/job")

    def test_worker_env_resume_root(self):
        # resume is a property of the on-disk state: EVERY start with a
        # ckpt_root exports both env vars (fit treats an empty root as
        # a fresh start) — a freshly rebooted launcher rejoining an
        # elastic job must restore the same checkpoint its peers do
        import argparse
        _worker_env = _launch_main()._worker_env
        args = argparse.Namespace(nproc_per_node=1, master="",
                                  ckpt_root="/ckpts/job")
        membership = {"node_index": 0, "n_nodes": 2, "endpoints": []}
        env = _worker_env(args, 0, membership)
        assert env["PADDLE_CKPT_ROOT"] == "/ckpts/job"
        assert env["PADDLE_RESUME_ROOT"] == "/ckpts/job"
        args_no = argparse.Namespace(nproc_per_node=1, master="",
                                     ckpt_root="")
        env = _worker_env(args_no, 0, membership)
        assert "PADDLE_CKPT_ROOT" not in env or \
            env.get("PADDLE_CKPT_ROOT") == os.environ.get(
                "PADDLE_CKPT_ROOT")

    def test_new_failpoints_registered(self):
        reg = failpoints.registered()
        for name in ("elastic.reshard", "ckpt.write_manifest",
                     "checkpoint.manifest_torn", "ckpt.read_shard"):
            assert name in reg, name
        # manifest_torn is the one skippable newcomer
        failpoints.set_failpoint("checkpoint.manifest_torn", "skip")
        failpoints.clear()
        with pytest.raises(ValueError):
            failpoints.set_failpoint("ckpt.write_manifest", "skip")


# -- registry discipline ---------------------------------------------------

class TestRegistryDiscipline:
    def test_reshard_metrics_in_catalog(self):
        from paddle_tpu.observability import catalog
        for name in ("pt_checkpoint_reshard_total",
                     "pt_checkpoint_reshard_ms"):
            assert name in catalog.METRICS, name
        assert catalog.METRICS["pt_checkpoint_reshard_total"]["labels"] \
            == ("kind",)

    def test_events_in_schema(self):
        assert guardian.EVENT_SCHEMA["checkpoint_fallback"] == \
            {"root", "step", "kind", "detail"}
        assert guardian.EVENT_SCHEMA["elastic_reshard"] == \
            {"old_np", "new_np", "root", "source"}

    def test_reshard_load_books_histogram(self, tmp_path):
        root = str(tmp_path)
        ckpt.save_checkpoint(_sharded_state(mesh8()), root, step=1,
                             manifest=True)
        ckpt.load_state_dict(ckpt.latest_checkpoint(root), mesh=mesh4())
        h = obs.get_registry().get("pt_checkpoint_reshard_ms")
        assert h is not None and h.count() == 1
