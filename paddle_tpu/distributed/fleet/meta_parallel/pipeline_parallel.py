"""PipelineParallel wrapper (reference: fleet/meta_parallel/
pipeline_parallel.py — train_batch with FThenB/1F1B/interleaved schedules,
micro-batch splitting, P2P meta negotiation).

TPU-native: ``train_batch`` drives ONE jitted SPMD program per batch.  Two
regimes:

- ``PipelineLayer`` with a homogeneous block run: the step compiles
  head → spmd_pipeline (shard_map + ppermute stage rotation, interleaved
  virtual stages honored) → tail → loss → grad → optimizer update.  The
  whole micro-batch schedule lives inside XLA; the only host sync is the
  final scalar loss readback.  This replaces the reference's per-rank
  1F1B send/recv runtime (SURVEY §3.4) with a compiled wavefront.
- arbitrary model: micro-batches become eager gradient accumulation
  (same math as FThenB; a wavefront adds nothing without stage-sharded
  weights).

Head/tail buffers (e.g. BN stats in a conv stem) update through the
compiled step like hapi's stepper; buffers INSIDE the homogeneous blocks
cannot ride the stacked-params rotation, so a model with block-level
buffers falls back to the eager path (checked in ``_compiled_ok``).
"""
import numpy as np
import jax
import jax.numpy as jnp

from ....analysis import register_jit_surface
from ....nn.layer.layers import Layer
from ....framework.core import Tensor
from ....framework import autograd as _ag
from ....framework.random import rng_scope, next_key
from ...engine import plan_from_hcg
from .pp_layers import PipelineLayer

__all__ = ["PipelineParallel"]

# the compiled pipeline stepper body is a nested def — registered for
# the tracer-safety/donation passes (mirrored by EXTRA_JIT_SURFACES in
# paddle_tpu/analysis/allowlist.py).  Donation audit (ISSUE 11): the
# jit donates (0, 2, 3, 4) — trainable/stacked/buffer/opt-state trees
# are consumed and re-emitted; frozen params (1) stay live.
register_jit_surface(__name__, "_PipelineStepper._build.step")


def _apply_items(items, x):
    """Sequentially apply run_function entries (layer, tag) to a Tensor,
    honoring SharedLayerDesc forward_funcs and bare callables — the same
    dispatch as PipelineLayer.forward."""
    for layer, tag in items:
        if tag is not None and tag != "func" and callable(tag):
            x = tag(layer, x)
        else:
            x = layer(x)
    return x


class _PipelineStepper:
    """Compiles the full dp×tp×pp train step for a PipelineLayer.

    Parameters split into the stacked homogeneous blocks (leading layer
    dim, sharded on "pipe") and the rest (head/tail/shared — placed by
    the plan: TP pspecs, ZeRO level, replication).  The optimizer runs
    functionally inside the same executable (fused update)."""

    def __init__(self, pipe_layer, hcg, strategy, optimizer, loss_fn,
                 n_micro):
        level = None
        if strategy is not None and \
                hcg.get_sharding_parallel_world_size() > 1:
            stage = (strategy.sharding_configs or {}).get("stage", 1)
            level = {1: "os", 2: "os_g", 3: "p_g_os"}.get(stage, "os")
        self.plan = plan_from_hcg(hcg, level=level)
        self.mesh = self.plan.mesh
        self.pipe_layer = pipe_layer
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.n_micro = n_micro

        start, end = pipe_layer._homogeneous_span()
        self.head = pipe_layer.run_function[:start]
        self.tail = pipe_layer.run_function[end:]
        self.staged = pipe_layer.staged_module(self.mesh, axis="pipe")
        self.blocks = self.staged.blocks
        self.t_names = [n for n, _ in
                        self.staged.template.named_parameters()]

        block_ids = {id(p) for b in self.blocks
                     for _, p in b.named_parameters()}
        named, seen = [], set()
        for n, p in pipe_layer.named_parameters():
            # shared (tied) layers appear under several prefixes — keep
            # one entry per param object so its grad contributions sum
            # into a single update
            if id(p) in block_ids or id(p) in seen:
                continue
            seen.add(id(p))
            named.append((n, p))
        self.other_params = [p for _, p in named]
        self.other_names = [n for n, _ in named]
        self.ot_idx = [i for i, p in enumerate(self.other_params)
                       if not p.stop_gradient]
        self.buffers = [b for _, b in pipe_layer.named_buffers()]

        plan = self.plan
        self._other_specs = [plan.param_pspec(p) for p in self.other_params]
        self._other_sh = [plan.sharding(s) for s in self._other_specs]
        t_params = [p for _, p in self.staged.template.named_parameters()]
        from jax.sharding import PartitionSpec as P
        self._stacked_specs = [P("pipe", *plan.param_pspec(p))
                               for p in t_params]
        self._stacked_sh = [plan.sharding(s) for s in self._stacked_specs]

        # place state
        for p, s in zip(self.other_params, self._other_sh):
            p._value = jax.device_put(p._value, s)
        self.stacked = [jax.device_put(v, s) for v, s in
                        zip(self.staged.stacked, self._stacked_sh)]
        self._buf_sh = [plan.replicated() for _ in self.buffers]
        for b, s in zip(self.buffers, self._buf_sh):
            b._value = jax.device_put(b._value, s)

        self.opt_state = None
        self._step_cache = {}
        self._dirty = False

    # -- state sync -------------------------------------------------------
    def sync_to_layers(self):
        """Write the stacked block values back into the per-block params
        (state_dict/checkpoint view).  Lazy: only after training steps."""
        if not self._dirty:
            return
        for j, arr in enumerate(self.stacked):
            for i, b in enumerate(self.blocks):
                params = [p for _, p in b.named_parameters()]
                params[j]._value = arr[i]
        self._dirty = False

    # -- step building ----------------------------------------------------
    def _opt_shardings(self, opt_state, specs, shapes):
        return self.plan.opt_state_shardings(opt_state, specs, shapes)

    def _build(self, x_sd, y_sd):
        opt = self.optimizer
        n_micro = self.n_micro
        ot_idx = self.ot_idx
        ot_set = set(ot_idx)
        staged, head, tail = self.staged, self.head, self.tail
        other_params, buffers = self.other_params, self.buffers
        loss_fn = self.loss_fn
        from ....optimizer.optimizer import apply_functional_with_clip
        pnames = [self.other_names[i] for i in ot_idx] + \
            [f"stacked.{n}" for n in self.t_names]

        def step(other_t, other_f, stacked_vals, buf_vals, opt_state, lr,
                 key, x, y):
            def loss_f(train_args):
                ot_vals, st_vals = train_args
                tv_map = dict(zip(ot_idx, ot_vals))
                fi = iter(other_f)
                full = [tv_map[i] if i in ot_set else next(fi)
                        for i in range(len(other_params))]
                olds = [t._value for t in other_params + buffers]
                for t, v in zip(other_params, full):
                    t._value = v
                for t, v in zip(buffers, buf_vals):
                    t._value = v
                try:
                    with _ag.suspend_tape(), rng_scope(key):
                        h = _apply_items(head, Tensor(x))
                        hv = h._value
                        B = hv.shape[0]
                        mb = B // n_micro
                        x_mb = hv.reshape(n_micro, mb, *hv.shape[1:])
                        y_mid = staged.apply(st_vals, x_mb)
                        y_mid = y_mid.reshape(B, *y_mid.shape[2:])
                        out = _apply_items(tail, Tensor(y_mid))
                        loss = loss_fn(out, Tensor(y))
                    new_buf = [t._value for t in buffers]
                    return loss._value, new_buf
                finally:
                    for t, v in zip(other_params + buffers, olds):
                        t._value = v

            (loss, new_buf), (g_ot, g_st) = jax.value_and_grad(
                loss_f, has_aux=True)((other_t, stacked_vals))
            train_vals = list(other_t) + list(stacked_vals)
            grads = list(g_ot) + list(g_st)
            new_vals, new_opt = apply_functional_with_clip(
                opt, train_vals, grads, opt_state, lr, param_names=pnames)
            k = len(other_t)  # lint: allow(len-on-traced) — python list of leaves, host-static
            return loss, new_vals[:k], new_vals[k:], new_buf, new_opt

        rep = self.plan.replicated()
        ot_sh = [self._other_sh[i] for i in ot_idx]
        of_sh = [self._other_sh[i] for i in range(len(self.other_params))
                 if i not in ot_set]
        specs = [self._other_specs[i] for i in ot_idx] + self._stacked_specs
        shapes = [tuple(self.other_params[i].shape) for i in ot_idx] + \
            [tuple(v.shape) for v in self.stacked]
        o_sh = self._opt_shardings(self.opt_state, specs, shapes)

        def traced(*args):
            # Pallas kernels run per shard of the mesh (ops/registry.py
            # "multi-device traces")
            with self.plan.kernel_partition():
                return step(*args)
        return jax.jit(
            traced, donate_argnums=(0, 2, 3, 4),
            in_shardings=(ot_sh, of_sh, list(self._stacked_sh),
                          list(self._buf_sh), o_sh, rep, rep, x_sd, y_sd),
            out_shardings=(rep, ot_sh, list(self._stacked_sh),
                           list(self._buf_sh), o_sh))

    def train_step(self, x, y):
        xv = x._value if isinstance(x, Tensor) else jnp.asarray(x)
        yv = y._value if isinstance(y, Tensor) else jnp.asarray(y)
        x_sd = self.plan.input_sharding(xv.ndim)
        y_sd = self.plan.input_sharding(yv.ndim)
        xv = jax.device_put(xv, x_sd)
        yv = jax.device_put(yv, y_sd)

        ot_set = set(self.ot_idx)
        ot_vals = [self.other_params[i]._value for i in self.ot_idx]
        of_vals = [p._value for i, p in enumerate(self.other_params)
                   if i not in ot_set]
        buf_vals = [b._value for b in self.buffers]
        if self.opt_state is None:
            self.opt_state = self.optimizer.init_functional_state(
                ot_vals + self.stacked)
            specs = [self._other_specs[i] for i in self.ot_idx] + \
                self._stacked_specs
            shapes = [tuple(np.shape(v)) for v in ot_vals + self.stacked]
            o_sh = self._opt_shardings(self.opt_state, specs, shapes)
            self.opt_state = [
                {k: jax.device_put(v, s[k]) for k, v in st.items()}
                for st, s in zip(self.opt_state, o_sh)]
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)

        key = (tuple(xv.shape), str(xv.dtype), tuple(yv.shape),
               str(yv.dtype))
        if key not in self._step_cache:
            self._step_cache[key] = self._build(x_sd, y_sd)
        loss, new_ot, new_stacked, new_buf, new_opt = self._step_cache[key](
            ot_vals, of_vals, self.stacked, buf_vals, self.opt_state, lr,
            next_key(), xv, yv)
        for i, v in zip(self.ot_idx, new_ot):
            self.other_params[i]._value = v
        for b, v in zip(self.buffers, new_buf):
            b._value = v
        self.stacked = list(new_stacked)
        self.opt_state = new_opt
        self.optimizer._global_step += 1
        self._dirty = True
        return loss


class PipelineParallel(Layer):
    def __init__(self, layers, hcg, strategy=None):
        super().__init__()
        self._layers = layers
        self._hcg = hcg
        self._strategy = strategy
        cfg = (strategy.pipeline_configs if strategy is not None else {}) \
            or {}
        self.micro_batch_size = cfg.get("micro_batch_size", 1)
        self.accumulate_steps = cfg.get("accumulate_steps", 1)
        self._placement_plan = plan_from_hcg(hcg)
        self._stepper = None
        self.total_loss = None

    def forward(self, *args, **kwargs):
        self._sync()
        return self._layers(*args, **kwargs)

    def _sync(self):
        if self._stepper is not None:
            self._stepper.sync_to_layers()

    def state_dict(self, *a, **k):
        self._sync()
        return self._layers.state_dict(*a, **k)

    def set_state_dict(self, sd, *a, **k):
        out = self._layers.set_state_dict(sd, *a, **k)
        if self._stepper is not None:
            from ...pipeline import stack_block_params
            st = self._stepper
            fresh = stack_block_params(
                [[p._value for _, p in b.named_parameters()]
                 for b in st.blocks])
            st.stacked = [jax.device_put(v, s)
                          for v, s in zip(fresh, st._stacked_sh)]
            st._dirty = False
        return out

    def _compiled_ok(self, scaler):
        if not isinstance(self._layers, PipelineLayer):
            return False
        s, e = self._layers._homogeneous_span()
        if e - s < 2:
            return False
        # block-level buffers can't ride the stacked-params rotation
        mid = [l for l, _ in self._layers.run_function[s:e]]
        if any(True for b in mid for _ in b.named_buffers()):
            return False
        if scaler is not None:
            scale = getattr(scaler, "_scale", None)
            if scale is not None and float(scale) != 1.0:
                return False
        return True

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None,
                    loss_fn=None):
        """Micro-batched train step (reference signature).  data: [x, y]."""
        x, y = data
        x = x if isinstance(x, Tensor) else Tensor(np.asarray(x))
        y = y if isinstance(y, Tensor) else Tensor(np.asarray(y))
        n_micro = self.accumulate_steps
        B = x.shape[0]
        assert B % n_micro == 0, f"batch {B} % micro {n_micro}"
        loss_f = loss_fn if loss_fn is not None else \
            getattr(self._layers, "_loss_fn", None)
        assert loss_f is not None, "PipelineParallel needs a loss_fn"

        if self._compiled_ok(scaler):
            if self._stepper is None or \
                    self._stepper.optimizer is not optimizer or \
                    self._stepper.loss_fn is not loss_f:
                self._stepper = _PipelineStepper(
                    self._layers, self._hcg, self._strategy, optimizer,
                    loss_f, n_micro)
            loss = self._stepper.train_step(x, y)
            if lr_scheduler is not None:
                lr_scheduler.step()
            self.total_loss = float(loss)
            return Tensor(np.asarray(self.total_loss, dtype="float32"))

        return self._train_batch_eager(x, y, optimizer, lr_scheduler,
                                       scaler, loss_f, n_micro)

    def _train_batch_eager(self, x, y, optimizer, lr_scheduler, scaler,
                           loss_f, n_micro):
        """Fallback: eager per-micro-batch gradient accumulation (FThenB
        math) for models without a pipelineable homogeneous run."""
        if self._stepper is not None:
            # never train two divergent copies: flush the compiled
            # stepper's state into the layer params and retire it (a
            # later compiled batch rebuilds from the layers; its
            # functional optimizer state restarts — mixing paths
            # mid-run is a correctness escape hatch, not a fast path)
            self._sync()
            self._stepper = None
        B = x.shape[0]
        mb = B // n_micro
        total = None
        for i in range(n_micro):
            xs = x[i * mb:(i + 1) * mb]
            ys = y[i * mb:(i + 1) * mb]
            out = self._layers(xs)
            loss = loss_f(out, ys)
            scaled = loss / n_micro
            if scaler is not None:
                scaled = scaler.scale(scaled)
            scaled.backward()
            total = float(loss) if total is None else total + float(loss)
        if scaler is not None:
            scaler.step(optimizer)
            scaler.update()
        else:
            optimizer.step()
        optimizer.clear_grad()
        if lr_scheduler is not None:
            lr_scheduler.step()
        self.total_loss = total / n_micro
        return Tensor(np.asarray(self.total_loss, dtype="float32"))

    def eval_batch(self, data, compute_loss=True):
        self._sync()
        # predict-style batches carry no labels
        x, y = data if len(data) == 2 else (data[0], None)
        out = self._layers(x if isinstance(x, Tensor) else Tensor(x))
        if not compute_loss:
            return out
        if y is None:
            raise ValueError("eval_batch(compute_loss=True) needs [x, y]")
        loss_f = getattr(self._layers, "_loss_fn", None)
        return loss_f(out, y if isinstance(y, Tensor) else Tensor(y))
