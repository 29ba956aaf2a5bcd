"""The train stepper's host path (ISSUE 28): from ``_split_batch`` to
the launch of the jitted step the host does work proportional to the
number of buffers only when something can have changed.

- in steady state a fused step launches ONE device program (the key
  split rides inside the step, the learning rate's device scalar is
  kept, ``network.train()`` and the frozen/trainable split are not
  redone);
- none of it changes what the step computes: the key stream, the
  dropout masks, the losses and the parameters are the parent commit's,
  bit for bit (fused, accumulate 2, ``guard_numerics``, AMP O2);
- what CAN change between steps still reaches the next one: the mode
  (``network.eval()``), the learning rate, ``stop_gradient``.
"""
import collections
import glob
import hashlib

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import observability as obs
from paddle_tpu.framework import random as _random
from paddle_tpu.observability import compilestats


@pytest.fixture(autouse=True)
def _clean_telemetry():
    obs.enable(True)
    obs.get_registry().reset()
    compilestats.reset()
    yield
    obs.get_registry().reset()
    compilestats.reset()


class Net(nn.Layer):
    """Dropout (the key matters), a frozen weight (the split matters),
    a buffer-free MLP small enough to step in milliseconds."""

    def __init__(self):
        super().__init__()
        self.a = nn.Linear(4, 8)
        self.frozen = nn.Linear(8, 8)
        self.drop = nn.Dropout(0.5)
        self.b = nn.Linear(8, 2)
        self.frozen.weight.stop_gradient = True

    def forward(self, x):
        h = nn.functional.relu(self.a(x))
        return self.b(self.drop(self.frozen(h)))


class MaskNet(nn.Layer):
    """Its output IS the dropout mask (times a weight that stays 1)."""

    def __init__(self):
        super().__init__()
        self.w = self.create_parameter(
            [1], default_initializer=nn.initializer.Constant(1.0))
        self.drop = nn.Dropout(0.5)

    def forward(self, x):
        return self.drop(x) * self.w


def _model(seed=11, lr=None, amp=None):
    paddle.seed(seed)
    net = Net()
    if lr is None:
        lr = paddle.optimizer.lr.StepDecay(learning_rate=0.05, step_size=1,
                                           gamma=0.5)
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.AdamW(learning_rate=lr,
                                         parameters=net.parameters()),
                  nn.MSELoss(), amp_configs=amp)
    return model


def _batches(n, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(8, 4).astype("float32"),
             rng.randn(8, 2).astype("float32")) for _ in range(n)]


def _digest(model):
    h = hashlib.sha256()
    for _, p in model.network.named_parameters():
        h.update(np.asarray(p._value).tobytes())
    return h.hexdigest()[:16]


def _run(mode):
    """(losses as float hex, digest of the parameters) of one fixed
    protocol; what ``GOLDEN`` holds was read on the parent commit
    (97101744) by this same function."""
    model = _model(amp={"level": "O2", "dtype": "bfloat16"}
                   if mode.startswith("amp") else None)
    if mode in ("fused", "amp_o2"):
        losses = [model.train_batch([x], [y])[0] for x, y in _batches(3)]
    elif mode in ("accumulate2", "amp_o2_accumulate2"):
        losses = [model.train_batch([x], [y], update=bool(i % 2))[0]
                  for i, (x, y) in enumerate(_batches(4))]
    elif mode == "guard":
        model._stepper.guard_numerics = True
        losses = [model.train_batch([x], [y])[0] for x, y in _batches(3)]
        assert bool(model._stepper.last_ok)
    return [float(l).hex() for l in losses], _digest(model)


GOLDEN = {
    "fused": (["0x1.88ba720000000p+1", "0x1.a857140000000p+0",
               "0x1.13eee40000000p+0"], "2856433976d8c866"),
    "accumulate2": (["0x1.88ba720000000p+1", "0x1.8c777a0000000p+0",
                     "0x1.cac9980000000p-1", "0x1.f957960000000p+0"],
                    "c28c71e4d601e139"),
    "guard": (["0x1.88ba720000000p+1", "0x1.a857140000000p+0",
               "0x1.13eee40000000p+0"], "6d0093067e5c30a0"),
    "amp_o2": (["0x1.87c8140000000p+1", "0x1.a938500000000p+0",
                "0x1.137f8c0000000p+0"], "be251e889ba94865"),
    "amp_o2_accumulate2": (
        ["0x1.88cc340000000p+1", "0x1.8ca4340000000p+0",
         "0x1.cb5eea0000000p-1", "0x1.f95e280000000p+0"],
        "8a20f3ab6e7e2605"),
}


class TestSameResults:
    @pytest.mark.parametrize("mode", sorted(GOLDEN))
    def test_three_steps_bit_identical_to_the_parent(self, mode):
        assert _run(mode) == GOLDEN[mode]

    def test_key_stream_and_masks_are_the_eager_ones(self):
        """The compiled step splits the chain as ``next_key`` does
        eagerly: after each step the global key is one eager split on,
        the step's dropout mask is the one its drawn key gives, and the
        guardian's replay (``debug_grads``) draws the same key without
        moving the chain."""
        paddle.seed(5)
        net = MaskNet()
        model = paddle.Model(net)
        model.prepare(paddle.optimizer.SGD(learning_rate=0.0,
                                           parameters=net.parameters()),
                      nn.MSELoss())
        x, y = _batches(1)[0]
        y = y[:, :1].repeat(4, axis=1)
        chain = jax.random.key(5)
        for _ in range(3):
            chain, sub = jax.random.split(chain)
            with _random.rng_scope(sub):      # draws nothing global
                eager = np.asarray(net.drop(paddle.to_tensor(x))._value)
            _, out_vals = model._stepper.train_step([x], [y])
            assert np.array_equal(np.asarray(out_vals[0]), eager)
            state = jax.random.key_data(_random.get_rng_state()[0])
            assert np.array_equal(state, jax.random.key_data(chain))
            (g,) = model._stepper.debug_grads([x], [y])
            np.testing.assert_allclose(
                np.asarray(g), [np.mean(2 * (eager - y) * eager)],
                rtol=1e-5)
            assert np.array_equal(
                state, jax.random.key_data(_random.get_rng_state()[0]))

    def test_a_mesh_step_leaves_the_global_chain_on_one_device(self):
        """Under a plan the key is drawn eagerly as before: a chain that
        came out of a mesh program would commit every later eager draw
        (the next network's initializers) to that mesh."""
        net = nn.Linear(4, 2)
        dp = paddle.DataParallel(net)
        assert dp._placement_plan is not None and jax.device_count() == 8
        model = paddle.Model(dp)
        model.prepare(paddle.optimizer.SGD(learning_rate=0.1,
                                           parameters=net.parameters()),
                      nn.MSELoss())
        paddle.seed(3)
        chain = jax.random.key(3)
        for x, y in _batches(2):
            model.train_batch([x], [y])
            chain, _ = jax.random.split(chain)
        state = _random.get_rng_state()[0]
        assert len(state.sharding.device_set) == 1
        assert np.array_equal(jax.random.key_data(state),
                              jax.random.key_data(chain))
        fresh = nn.Linear(4, 2)         # an eager draw after the mesh steps
        assert len(fresh.weight._value.sharding.device_set) == 1


def _programs(fn):
    """Names of the jitted programs ``fn()`` launched, from a host trace
    (``PjitFunction(<name>)`` is JAX's own annotation of each jitted
    call), and how many executions the CPU client saw."""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(d + "/plugins/profile/*/*.xplane.pb")
        data = jax.profiler.ProfileData.from_file(path)
    names, executes = collections.Counter(), 0
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("PjitFunction("):
                    names[ev.name] += 1
                elif ev.name == "PjRtCpuExecutable::Execute":
                    executes += 1
    return names, executes


class TestSteadyState:
    def test_a_fused_step_launches_one_device_program(self):
        model = _model(lr=0.05)
        batches = _batches(6)
        for x, y in batches[:2]:        # compile; the LR's one upload
            model.train_batch([x], [y])
        n = len(batches) - 2
        names, executes = _programs(
            lambda: [model.train_batch([x], [y]) for x, y in batches[2:]])
        # no split, no unstack, no convert_element_type beside the step
        assert set(names) == {"PjitFunction(step)"}, names
        assert executes == n
        c = obs.get_registry().get("pt_compile_dispatch_total")
        assert c.value(surface="hapi.train_step", path="fast") == n + 1
        assert c.value(surface="hapi.train_step", path="signature") == 1

    def test_nothing_is_walked_while_nothing_changes(self, monkeypatch):
        model = _model(lr=0.05)
        st, net = model._stepper, model.network
        walks = []
        real = type(net).train
        monkeypatch.setattr(
            type(net), "train",
            lambda self: (walks.append(1), real(self))[1])
        uploads = set()
        for x, y in _batches(4):
            model.train_batch([x], [y])
            uploads.add(id(st._lr[1]))
        assert len(walks) == 1 and len(uploads) == 1
        assert st._lr[0] == 0.05 and st._lr[1] == np.float32(0.05)
        assert [st.param_names[i] for i in st.f_idx] == ["frozen.weight"]
        assert sorted(st.t_idx + st.f_idx) == list(range(len(st.params)))


class TestWhatCanChangeStillDoes:
    def test_eval_between_two_steps_is_seen_by_the_next(self):
        model = _model(lr=0.05)
        net = model.network
        (x, y), = _batches(1)
        model.train_batch([x], [y])
        net.eval()
        assert not net.drop.training
        model.train_batch([x], [y])
        assert all(l.training for l in net.sublayers(include_self=True))
        net.drop.training = False       # one flag, set directly
        model.train_batch([x], [y])
        assert net.drop.training
        model.eval_batch([x], [y])      # the model's own eval pass
        assert not net.training
        model.train_batch([x], [y])
        assert all(l.training for l in net.sublayers(include_self=True))

    def test_a_changed_learning_rate_reaches_the_next_step(self):
        model = _model(lr=0.05)
        opt = model._optimizer
        (x, y), = _batches(1)
        model.train_batch([x], [y])
        opt.set_lr(0.0)                 # AdamW: decay scales with lr too
        before = _digest(model)
        model.train_batch([x], [y])
        assert _digest(model) == before
        opt.set_lr(0.05)
        model.train_batch([x], [y])
        assert _digest(model) != before

    def test_the_frozen_split_follows_refresh_state_refs(self):
        model = _model(lr=0.05)
        st, net = model._stepper, model.network
        (x, y), = _batches(1)
        model.train_batch([x], [y])
        net.a.bias.stop_gradient = True
        st._refresh_state_refs()
        st._train_cache.clear()
        st.opt_state = None
        assert [st.param_names[i] for i in st.f_idx] == \
            ["a.bias", "frozen.weight"]
        held = np.asarray(net.a.bias._value).copy()
        model.train_batch([x], [y])
        assert np.array_equal(np.asarray(net.a.bias._value), held)


if __name__ == "__main__":
    for m in ("fused", "accumulate2", "guard", "amp_o2",
              "amp_o2_accumulate2"):
        print(repr(m) + ":", _run(m), ",")
