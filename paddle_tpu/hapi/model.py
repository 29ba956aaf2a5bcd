"""High-level Model API (reference: python/paddle/hapi/model.py —
Keras-like fit/evaluate/predict with Dynamic/StaticGraphAdapter).

TPU-native: ONE adapter.  ``prepare`` builds a compiled train step — a pure
function (params, buffers, opt_state, lr, rng, batch) → (loss, preds,
params', buffers', opt_state') jitted with donated buffers, so the whole
step (fwd+bwd+optimizer) is a single XLA executable; the reference needed
the static-graph adapter + fused optimizer kernels to get this.  Eager
(per-op) execution is kept as a debug mode (``Model.prepare(jit=False)``).
"""
import contextlib
import json
import os
import re
import time
import zlib

import numpy as np
import jax
import jax.numpy as jnp

from ..analysis import jit_surface
from .. import observability as _obs
from ..observability import tracing as _tracing
from ..observability.tracing import scope as _scope
from ..framework.core import Tensor
from ..framework import autograd as _ag
from ..framework import guardian as _guardian
from ..framework import preemption as _preemption
from ..framework.random import (rng_scope, next_key, get_rng_state,
                                set_rng_state)
from ..framework.io import save as _save, load as _load
from ..metric import Metric
from ..nn.layer.layers import mode_stamp
from ..optimizer.lr import LRScheduler
from ..optimizer.optimizer import apply_functional_with_clip
from ..io import DataLoader, Dataset, DistributedBatchSampler
from . import callbacks as cbks_mod

__all__ = ["Model"]


def _file_stamp(path):
    """Content identity [size, crc32] for the emergency-checkpoint
    COMMITTED sentinel — survives copy/rsync, unlike mtimes."""
    crc = 0
    size = 0
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                break
            size += len(chunk)
            crc = zlib.crc32(chunk, crc)
    return [size, crc]


def _to_jnp(x):
    if isinstance(x, Tensor):
        return x._value
    return jnp.asarray(np.asarray(x))


def _as_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _total(loss):
    """The sum of a loss function's results where it returns several."""
    return sum(loss[1:], loss[0]) if isinstance(loss, (list, tuple)) \
        else loss


def _fp8_apply(pv, idx, amax):
    """fp8 train pilot: fake-quantize the Linear weights in the merged
    param list with delayed scaling — the scale each weight uses THIS
    step is the amax observed on a PREVIOUS step (the state vector
    threaded through the compiled step), and the fresh amax goes back
    out with the updated state trees.  The first step (state still
    zero) seeds each scale just-in-time from the current amax; after
    that the scale lags one step and the saturating cast absorbs the
    per-step drift.  All scale math in fp32 (dtype-flow contract)."""
    from ..ops.quant_dispatch import fp8_fake_quant
    pv = list(pv)
    cur = []
    for j, i in enumerate(idx):
        wf = pv[i].astype(jnp.float32)
        cur_amax = jnp.max(jnp.abs(wf))
        scale = jnp.maximum(
            jnp.where(amax[j] > 0, amax[j], cur_amax), 1e-12)
        pv[i] = fp8_fake_quant(pv[i], scale)
        cur.append(cur_amax)
    return pv, jnp.stack(cur).astype(jnp.float32)


class _CompiledStepper:
    """Builds & caches the jitted train/eval/predict steps.

    With a PlacementPlan (fleet/DataParallel/GroupSharded wrappers attach
    one), state is device_put to its NamedSharding and the step is jitted
    with in/out shardings — DP/ZeRO/TP become GSPMD placements of the same
    executable (see distributed/engine.py).
    """

    def __init__(self, network, loss_fn, optimizer, amp_level=None,
                 plan=None):
        self.network = network
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.amp_level = amp_level
        self.plan = plan if plan is not None else getattr(
            network, "_placement_plan", None)
        self._refresh_state_refs()
        self._train_cache = {}
        self._grad_cache = {}
        self._apply_fn = None
        self._eval_cache = {}
        self.opt_state = None
        self._accum_grads = None
        self._accum_count = 0
        # guardian sentinel: when True the compiled step carries a fused
        # finite-check and skips the update on device (params/opt state
        # kept) — toggled by Model.fit, which clears the step caches
        self.guard_numerics = False
        self.last_ok = None
        self._last_rng = None    # the key chain the last step drew from
        self._lr = (None, None)  # get_lr()'s last float, its device scalar
        # fp8 train pilot (enable_fp8): trace-time constant like
        # guard_numerics; fp8_state is the delayed-scaling amax vector,
        # one fp32 entry per Linear weight, donated through the step
        self.fp8_matmul = False
        self.fp8_state = None
        self._fp8_idx = ()
        if self.plan is not None:
            self._apply_plan()

    def _apply_plan(self):
        """device_put every param/buffer onto its planned sharding and
        precompute the sharding trees the jit calls use."""
        plan = self.plan
        self._param_specs = [plan.param_pspec(p) for p in self.params]
        self._param_shardings = [plan.sharding(s) for s in self._param_specs]
        for p, s in zip(self.params, self._param_shardings):
            p._value = jax.device_put(p._value, s)
        self._buffer_shardings = [plan.replicated() for _ in self.buffers]
        for b, s in zip(self.buffers, self._buffer_shardings):
            b._value = jax.device_put(b._value, s)

    def _opt_shardings_for(self, opt_state):
        t_specs = [self._param_specs[i] for i in self.t_idx]
        t_shapes = [tuple(self.params[i].shape) for i in self.t_idx]
        return self.plan.opt_state_shardings(opt_state, t_specs, t_shapes)

    def _refresh_state_refs(self):
        self.params = [p for _, p in self.network.named_parameters()]
        self.param_names = [n for n, _ in self.network.named_parameters()]
        self.buffers = [b for _, b in self.network.named_buffers()]
        self.t_idx = [i for i, p in enumerate(self.params)
                      if not p.stop_gradient]
        trainable = set(self.t_idx)
        self.f_idx = [i for i in range(len(self.params))
                      if i not in trainable]

    def enable_fp8(self):
        """Turn on the fp8 train pilot: every Linear weight matmul in
        the compiled step runs through an fp8 e4m3 fake-quant round-trip
        with delayed scaling (see ``_fp8_apply``).  Single-device jit
        path only — placements/grad_comm keep their own numerics; and
        the amax state is checkpointed via ``Model.train_state_dict``'s
        ``fp8`` group, NOT by guardian rollback snapshots (running
        statistics re-warm in one step after a rollback)."""
        if self.plan is not None:
            raise ValueError(
                "fp8 train pilot supports the single-device jit path "
                "only (no PlacementPlan / grad_comm)")
        from ..ops import quant_dispatch as _qd
        if _qd._FP8_DTYPE is None:
            # books once, outside the trace: fake-quant degrades to
            # int8 (the grad_comm wire-mode fallback contract)
            from ..ops import registry as _kreg
            _kreg.record_fallback("quant_matmul", "fp8-unavailable")
        from ..models.generation import _linear_weight_indices
        self.fp8_matmul = True
        self._fp8_idx = tuple(_linear_weight_indices(self.network))
        self._train_cache.clear()

    def ensure_fp8_state(self):
        """Lazily init the delayed-scaling amax vector (zeros = first
        step runs at scale 1.0, then real amaxes take over)."""
        if self.fp8_state is None:
            self.fp8_state = jnp.zeros((len(self._fp8_idx),),
                                       jnp.float32)
        return self.fp8_state

    def _under_plan(self, fn):
        """``fn`` traced inside the plan's kernel-partition context
        (Pallas kernels then run per shard of the mesh — XLA cannot
        partition a Mosaic kernel); ``fn`` itself without a plan."""
        plan = self.plan
        if plan is None:
            return fn

        def scoped(*args):
            with plan.kernel_partition():
                return fn(*args)
        return scoped

    def _merged_params(self, train_vals, frozen_vals):
        """The traced step's full parameter list from its trainable and
        frozen halves; under AMP the trainable floats go in as bf16."""
        pv = [None] * len(self.params)
        cast = self._amp_cast(train_vals, jnp.floating, jnp.bfloat16)
        for i, v in zip(self.t_idx + self.f_idx,
                        list(cast) + list(frozen_vals)):
            pv[i] = v
        return pv

    def _amp_cast(self, vals, kind, to):
        """Under AMP, ``vals`` with every ``kind`` leaf cast to ``to``."""
        if self.amp_level not in ("O1", "O2"):
            return vals
        with _scope("amp_cast"):
            return [v.astype(to) if jnp.issubdtype(v.dtype, kind) else v
                    for v in vals]

    def _drawing(self, step, key_at):
        """``step`` fed the global key chain where it takes a key: the
        compiled step splits the chain itself — what ``next_key`` does
        eagerly, so the stream is the same, without a launch of its own
        — runs on the drawn half and returns the advanced chain last.
        Not under a plan: a chain that came out of a mesh program would
        commit every later eager draw of the process to that mesh."""
        if self.plan is not None:
            return step

        def drawing(*args):
            chain, key = jax.random.split(args[key_at])
            return step(*args[:key_at], key, *args[key_at + 1:]) + (chain,)
        drawing.__name__ = step.__name__   # the program's name stays
        return drawing

    def _take_key(self):
        """The next step's key argument: the global chain, which the
        step advances itself (``_drawing``); a drawn key under a plan."""
        return get_rng_state()[0] if self.plan is None else next_key()

    def _give_key(self, out):
        """A step's outputs; the advanced chain goes to the global state."""
        out = list(out)
        if self.plan is None:
            set_rng_state([out.pop()])
        return out

    def _forward_pure(self, param_vals, buffer_vals, key, inputs, training):
        """Run network on traced values; returns (outs, new_buffer_vals)."""
        olds = [t._value for t in self.params + self.buffers]
        for t, v in zip(self.params, param_vals):
            t._value = v
        for t, v in zip(self.buffers, buffer_vals):
            t._value = v
        mode_layers = []
        if not training:
            for l in self.network.sublayers(include_self=True):
                if l.training:
                    mode_layers.append(l)
                    l.training = False
        try:
            with _ag.suspend_tape(), rng_scope(key):
                outs = self.network(*[Tensor(v) for v in inputs])
            outs_l = _as_list(outs)
            out_vals = [o._value for o in outs_l]
            new_buf = [b._value for b in self.buffers]
            return out_vals, new_buf
        finally:
            for t, v in zip(self.params + self.buffers, olds):
                t._value = v
            for l in mode_layers:
                l.training = True

    def _loss_pure(self, out_vals, label_vals):
        with _ag.suspend_tape():
            outs = [Tensor(v) for v in out_vals]
            labels = [Tensor(v) for v in label_vals]
            if not callable(self.loss_fn):
                raise TypeError("loss must be callable")
            loss = self.loss_fn(*(outs + labels))
        return _total(loss)._value

    def _use_grad_comm(self):
        """True when the step should use the explicit bucketed/quantized
        gradient reducer (shard_map) instead of GSPMD's implicit
        all-reduce: a grad_comm plan on a >1 'data' axis with fully
        replicated parameters (pure DP).  TP/ZeRO placements keep the
        GSPMD path — their reduction is part of the placement."""
        plan = self.plan
        cc = getattr(plan, "grad_comm", None) if plan is not None else None
        if cc is None or not cc.enabled:
            return False
        if "data" not in plan.mesh.axis_names or \
                plan.mesh.shape["data"] <= 1:
            return False
        if plan.level is not None or any(
                any(a is not None for a in spec)
                for spec in self._param_specs):
            if not getattr(self, "_warned_grad_comm", False):
                self._warned_grad_comm = True
                import warnings
                warnings.warn(
                    "grad_comm: parameters are not replicated under this "
                    "plan (TP/ZeRO placement) — the explicit bucketed "
                    "reducer applies to pure data parallelism; falling "
                    "back to the GSPMD path")
            return False
        return True

    @jit_surface
    def _build_train_comm(self, n_in, n_lab):
        """Explicit-collective twin of ``_build_train`` for pure DP:
        shard_map over the plan's mesh, with the grad tree reduced by
        ``distributed.grad_comm`` buckets.  Each bucket's all-reduce
        depends only on its members' gradients — produced early in
        backward for the reverse-order buckets — so XLA's latency-hiding
        scheduler can overlap the collectives with the remaining
        backward compute (the T3 shape, by graph structure).  Quantized
        wire formats ride the same buckets.

        Output contract: every network output must carry the batch on
        its leading axis (out_specs shards them on 'data') — nets with
        scalar/non-batch auxiliary outputs need the GSPMD path."""
        from jax.sharding import PartitionSpec as P
        from ..distributed.grad_comm import build_grad_reducer
        opt = self.optimizer
        t_idx = self.t_idx
        guard = self.guard_numerics
        pnames = [self.param_names[i] for i in t_idx]
        plan = self.plan
        mesh = plan.mesh
        axis = "data"
        world = int(mesh.shape[axis])
        shapes = [tuple(self.params[i].shape) for i in t_idx]
        dtypes = [self.params[i]._value.dtype for i in t_idx]
        reducer, _ = build_grad_reducer(shapes, dtypes, plan.grad_comm,
                                        axis, world)

        def shard_step(train_vals, frozen_vals, buffer_vals, opt_state,
                       lr, key, inputs, labels):
            # decorrelate per-shard stochastic layers (dropout): same
            # stream as single-device only for mask-free nets, which is
            # what the parity contract covers
            key = jax.random.fold_in(key, jax.lax.axis_index(axis))

            def loss_f(tv):
                out_vals, new_buf = self._forward_pure(
                    self._merged_params(tv, frozen_vals), buffer_vals, key,
                    self._amp_cast(inputs, jnp.floating, jnp.bfloat16),
                    training=True)
                out_vals = self._amp_cast(out_vals, jnp.bfloat16,
                                          jnp.float32)
                loss = self._loss_pure(out_vals, labels)
                return loss, (out_vals, new_buf)

            (loss, (out_vals, new_buf)), grads = jax.value_and_grad(
                loss_f, has_aux=True)(train_vals)
            grads = reducer(list(grads))
            # equal shard sizes: mean of local batch-means == global mean
            loss = jax.lax.pmean(loss, axis)
            # running statistics (BN & co) are computed from the local
            # shard — average them so every replica carries the global
            # update; integer buffers (step counters) advance in
            # lockstep, pmax just re-asserts replication for the checker
            new_buf = [jax.lax.pmean(b, axis)
                       if jnp.issubdtype(b.dtype, jnp.inexact)
                       else jax.lax.pmax(b, axis) for b in new_buf]
            with _scope("optimizer"):
                new_train, new_opt = apply_functional_with_clip(
                    opt, train_vals, grads, opt_state, lr,
                    param_names=pnames)
            if guard:
                # reduced grads are replicated, so the verdict (and the
                # skip) is identical on every replica — no extra pmin
                with _scope("guard"):
                    ok = _guardian.tree_all_finite(list(grads) + [loss])
                    sel = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
                    new_train = [sel(n, o) for n, o in zip(new_train,
                                                           train_vals)]
                    new_opt = jax.tree_util.tree_map(sel, new_opt,
                                                     opt_state)
                    new_buf = [sel(n, o) for n, o in zip(new_buf,
                                                         buffer_vals)]
                return loss, new_train, new_buf, new_opt, out_vals, ok
            return loss, new_train, new_buf, new_opt, out_vals

        rep = P()
        dat = P(axis)
        sharded = jax.shard_map(
            shard_step, mesh=mesh,
            in_specs=(rep, rep, rep, rep, rep, rep, dat, dat),
            out_specs=(rep, rep, rep, rep, dat) +
                      ((rep,) if guard else ()),
            check_vma=False)
        # batch-divisibility is validated host-side in train_step (the
        # error must fire before this executable is compiled/cached)
        return jax.jit(sharded, donate_argnums=(0, 2, 3))

    @jit_surface
    def _build_train(self, n_in, n_lab):
        # OUTPUT ORDER CONTRACT: the updated state trees (new_train /
        # new_buf / new_opt) come BEFORE out_vals.  XLA pairs donated
        # inputs to outputs greedily in output order by GLOBAL
        # shape+dtype; with activations first, a batch-sharded logits
        # output whose global shape happens to equal a replicated
        # param's stole that param's donated buffer and the executable
        # aborted at launch on the local-shard size mismatch (the PR 14
        # "donation aliasing" quirk).  State-first ordering pairs every
        # donated leaf with its own updated output — same sharding,
        # always aliasable.
        if self._use_grad_comm():
            return self._build_train_comm(n_in, n_lab)
        opt = self.optimizer
        t_idx = self.t_idx
        guard = self.guard_numerics   # trace-time constant: zero cost off
        fp8 = self.fp8_matmul         # same: off costs nothing
        fp8_idx = self._fp8_idx
        pnames = [self.param_names[i] for i in t_idx]

        def step(train_vals, frozen_vals, buffer_vals, opt_state, lr, key,
                 inputs, labels, fp8_amax=None):
            def loss_f(tv):
                pv = self._merged_params(tv, frozen_vals)
                new_amax = None
                if fp8:
                    # fp8 pilot: STE fake-quant over the MERGED list
                    # (after any amp cast) so gradients flow straight
                    # through to the trainable values
                    pv, new_amax = _fp8_apply(pv, fp8_idx, fp8_amax)
                out_vals, new_buf = self._forward_pure(
                    pv, buffer_vals, key,
                    self._amp_cast(inputs, jnp.floating, jnp.bfloat16),
                    training=True)
                out_vals = self._amp_cast(out_vals, jnp.bfloat16,
                                          jnp.float32)
                loss = self._loss_pure(out_vals, labels)
                return loss, (out_vals, new_buf, new_amax)

            (loss, (out_vals, new_buf, new_amax)), grads = \
                jax.value_and_grad(loss_f, has_aux=True)(train_vals)
            with _scope("optimizer"):
                new_train, new_opt = apply_functional_with_clip(
                    opt, train_vals, grads, opt_state, lr,
                    param_names=pnames)
            if guard:
                # guardian sentinel: ONE fused finite reduction over the
                # whole grad tree + loss, then a device-side select that
                # keeps the old params/buffers/opt state on trip — the
                # skip costs no recompile and no host round-trip here.
                # An fp8 saturation (NaN loss/grads) trips this exact
                # ladder; the amax state also holds on trip so a
                # poisoned batch cannot poison the scales.
                with _scope("guard"):
                    ok = _guardian.tree_all_finite(list(grads) + [loss])
                    sel = lambda n, o: jnp.where(ok, n, o)  # noqa: E731
                    new_train = [sel(n, o) for n, o in zip(new_train,
                                                           train_vals)]
                    new_opt = jax.tree_util.tree_map(sel, new_opt,
                                                     opt_state)
                    new_buf = [sel(n, o)
                               for n, o in zip(new_buf, buffer_vals)]
                    if fp8:
                        new_amax = sel(new_amax, fp8_amax)
                if fp8:
                    return (loss, new_train, new_buf, new_opt, new_amax,
                            out_vals, ok)
                return loss, new_train, new_buf, new_opt, out_vals, ok
            if fp8:
                # OUTPUT ORDER CONTRACT: the amax state is a state tree
                # — it comes BEFORE out_vals like the others so its
                # donated input pairs with its own updated output
                return loss, new_train, new_buf, new_opt, new_amax, \
                    out_vals
            return loss, new_train, new_buf, new_opt, out_vals

        step = self._drawing(step, 5)
        if self.plan is None:
            return jax.jit(step,
                           donate_argnums=(0, 2, 3) + ((8,) if fp8
                                                       else ()))
        plan = self.plan
        t_sh = [self._param_shardings[i] for i in self.t_idx]
        f_sh = [self._param_shardings[i] for i in self.f_idx]
        b_sh = list(self._buffer_shardings)
        o_sh = self._opt_shardings_for(self.opt_state)
        rep = plan.replicated()
        out_sh = (rep, t_sh, b_sh, o_sh, None) + ((rep,) if guard else ())
        return jax.jit(
            self._under_plan(step), donate_argnums=(0, 2, 3),
            in_shardings=(t_sh, f_sh, b_sh, o_sh, rep, rep,
                          self._input_shardings, self._label_shardings),
            out_shardings=out_sh)

    @jit_surface
    def _build_grad(self):
        """Gradient-only step (no optimizer apply) for accumulation."""
        def gstep(train_vals, frozen_vals, buffer_vals, key, inputs,
                  labels):
            def loss_f(tv):
                out_vals, new_buf = self._forward_pure(
                    self._merged_params(tv, frozen_vals), buffer_vals, key,
                    inputs, training=True)
                loss = self._loss_pure(out_vals, labels)
                return loss, (out_vals, new_buf)
            (loss, (out_vals, new_buf)), grads = jax.value_and_grad(
                loss_f, has_aux=True)(train_vals)
            return loss, out_vals, new_buf, grads
        # donation-unsafe by design: train/frozen vals must stay live
        # for the later apply step, and the trip path keeps pre-batch
        # buffers when a poisoned microbatch is dropped
        return jax.jit(  # lint: allow(missing-donation)
            self._under_plan(self._drawing(gstep, 3)))

    @jit_surface
    def _build_apply(self):
        opt = self.optimizer
        pnames = [self.param_names[i] for i in self.t_idx]

        def astep(train_vals, grads, opt_state, lr):
            return apply_functional_with_clip(
                opt, train_vals, grads, opt_state, lr, param_names=pnames)
        return jax.jit(astep, donate_argnums=(0, 2))

    @jit_surface
    def _build_eval(self, n_in):
        def step(param_vals, buffer_vals, key, inputs):
            out_vals, _ = self._forward_pure(param_vals, buffer_vals, key,
                                             inputs, training=False)
            return out_vals
        # donation-unsafe by design: eval reads the LIVE weights and
        # buffers (the model keeps them across steps); outputs are
        # activations, no state tree is consumed
        if self.plan is None:
            return jax.jit(step)  # lint: allow(missing-donation)
        rep = self.plan.replicated()
        return jax.jit(  # lint: allow(missing-donation)
            self._under_plan(step), in_shardings=(
                list(self._param_shardings), list(self._buffer_shardings),
                rep, self._input_shardings))

    def _shape_key(self, arrays):
        return tuple((tuple(a.shape), str(a.dtype)) for a in arrays)

    @staticmethod
    def _tracked(fn, surface):
        """Compile-telemetry wrap (observability/compilestats.py): each
        built executable is keyed by input shapes already, so its
        declared compile budget is ONE — a second compile inside one
        cache entry is a genuine retrace (dtype drift through the merge
        paths) and raises the guardian ``compile_retrace`` sentinel."""
        return _obs.compilestats.wrap(fn, surface, budget=1)

    def train_step(self, inputs, labels, update=True):
        inputs = [_to_jnp(x) for x in _as_list(inputs)]
        labels = [_to_jnp(x) for x in _as_list(labels)]
        if self.plan is not None:
            self._input_shardings = [self.plan.input_sharding(a.ndim)
                                     for a in inputs]
            self._label_shardings = [self.plan.input_sharding(a.ndim)
                                     for a in labels]
        # shape-keyed stepper cache is the contract: one executable per
        # batch signature, and the runtime compile_retrace sentinel
        # (budget=1 per entry, _tracked below) catches real drift
        key = (self._shape_key(inputs), self._shape_key(labels))  # lint: allow(unbucketed-shape-key)
        if self._use_grad_comm():
            # host-side, BEFORE the executable is compiled/cached: the
            # shard_map stepper splits the batch into equal per-replica
            # shards (equal shards are also what make mean-of-shard-
            # means the exact global mean — the parity contract)
            world = int(self.plan.mesh.shape["data"])
            for a in inputs + labels:
                if a.ndim == 0 or a.shape[0] % world:
                    raise ValueError(
                        "grad_comm: global batch "
                        f"{a.shape[0] if a.ndim else '<scalar>'} is not "
                        f"divisible by the data-parallel world size "
                        f"{world}; pad or resize the batch")
        train_vals = [self.params[i]._value for i in self.t_idx]
        frozen_vals = [self.params[i]._value for i in self.f_idx]
        buffer_vals = [b._value for b in self.buffers]
        self.ensure_opt_state()
        lr = self.optimizer.get_lr()
        if lr != self._lr[0]:
            self._lr = (lr, jnp.asarray(lr, jnp.float32))
        lr = self._lr[1]
        rng = self._last_rng = self._take_key()   # the guardian replays it

        accumulating = (not update) or self._accum_count > 0
        if accumulating and self.fp8_matmul:
            raise ValueError(
                "fp8 train pilot does not support gradient accumulation "
                "(the amax state threads through the fused step only); "
                "use accumulate_grad_batches=1")
        if not accumulating:
            # fused fast path: fwd+bwd+update in one executable
            if key not in self._train_cache:
                self._train_cache[key] = self._tracked(
                    self._build_train(len(inputs), len(labels)),
                    "hapi.train_step_comm" if self._use_grad_comm()
                    else "hapi.train_step")
            fp8 = self.fp8_matmul
            args = (train_vals, frozen_vals, buffer_vals, self.opt_state,
                    lr, rng, inputs, labels)
            if fp8:
                args = args + (self.ensure_fp8_state(),)
            out = self._give_key(self._train_cache[key](*args))
            self.last_ok = out.pop() if self.guard_numerics else None
            if fp8:
                self.fp8_state = out.pop(4)
            loss, new_train, new_buf, new_opt, out_vals = out
            for i, v in zip(self.t_idx, new_train):
                self.params[i]._value = v
            for b, v in zip(self.buffers, new_buf):
                b._value = v
            self.opt_state = new_opt
            self.optimizer._global_step += 1
            return loss, out_vals

        # accumulation path: grads only, apply on the update step
        if key not in self._grad_cache:
            self._grad_cache[key] = self._tracked(self._build_grad(),
                                                  "hapi.grad_step")
        loss, out_vals, new_buf, grads = self._give_key(self._grad_cache[key](
            train_vals, frozen_vals, buffer_vals, rng, inputs, labels))
        if self.guard_numerics:
            # accumulation: a poisoned microbatch must not contaminate
            # the running grad sum — drop it here (host check; this path
            # already syncs per microbatch) and report the trip
            ok = _guardian.tree_all_finite(list(grads) + [loss])
            self.last_ok = ok
            if not _guardian._host_bool(ok):
                return loss, out_vals   # buffers kept pre-batch too
        else:
            self.last_ok = None
        for b, v in zip(self.buffers, new_buf):
            b._value = v
        if self._accum_grads is None:
            self._accum_grads = list(grads)
        else:
            self._accum_grads = [a + g for a, g in
                                 zip(self._accum_grads, grads)]
        self._accum_count += 1
        if update:
            k = self._accum_count
            mean_grads = [g / k for g in self._accum_grads]
            if self._apply_fn is None:
                self._apply_fn = self._tracked(self._build_apply(),
                                               "hapi.apply_step")
            new_train, new_opt = self._apply_fn(train_vals, mean_grads,
                                                self.opt_state, lr)
            for i, v in zip(self.t_idx, new_train):
                self.params[i]._value = v
            self.opt_state = new_opt
            self.optimizer._global_step += 1
            self._accum_grads = None
            self._accum_count = 0
        return loss, out_vals

    def eval_forward(self, inputs):
        inputs = [_to_jnp(x) for x in _as_list(inputs)]
        if self.plan is not None:
            self._input_shardings = [self.plan.input_sharding(a.ndim)
                                     for a in inputs]
        key = self._shape_key(inputs)  # lint: allow(unbucketed-shape-key)
        if key not in self._eval_cache:
            self._eval_cache[key] = self._tracked(
                self._build_eval(len(inputs)), "hapi.eval_step")
        fn = self._eval_cache[key]
        param_vals = [p._value for p in self.params]
        buffer_vals = [b._value for b in self.buffers]
        return fn(param_vals, buffer_vals, next_key(), inputs)

    def debug_grads(self, inputs, labels):
        """Recompute this batch's gradients without applying them —
        guardian attribution re-runs the bwd pass on the (rare) trip
        path to name the offending tensors.  Replays the tripped step's
        rng key (stochastic layers must see the same mask, and the
        global key stream must not be perturbed by a replay)."""
        inputs = [_to_jnp(x) for x in _as_list(inputs)]
        labels = [_to_jnp(x) for x in _as_list(labels)]
        key = (self._shape_key(inputs), self._shape_key(labels))  # lint: allow(unbucketed-shape-key)
        if key not in self._grad_cache:
            self._grad_cache[key] = self._tracked(self._build_grad(),
                                                  "hapi.grad_step")
        train_vals = [self.params[i]._value for i in self.t_idx]
        frozen_vals = [self.params[i]._value for i in self.f_idx]
        buffer_vals = [b._value for b in self.buffers]
        replay = self._last_rng is not None
        out = self._grad_cache[key](
            train_vals, frozen_vals, buffer_vals,
            self._last_rng if replay else self._take_key(), inputs, labels)
        if not replay:       # no step to replay: a draw of its own
            out = self._give_key(out)
        return list(out[3])

    def ensure_opt_state(self):
        """Lazily build (and plan-place) the functional optimizer state
        — the same init train_step used to do inline, factored out so
        the resume path can materialize a correctly-sharded template
        before the first step runs."""
        if self.opt_state is None:
            train_vals = [self.params[i]._value for i in self.t_idx]
            self.opt_state = self.optimizer.init_functional_state(
                train_vals)
            if self.plan is not None:
                o_sh = self._opt_shardings_for(self.opt_state)
                self.opt_state = [
                    {k: jax.device_put(v, s[k]) for k, v in st.items()}
                    for st, s in zip(self.opt_state, o_sh)]
        return self.opt_state

    def sync_opt_state_to_optimizer(self):
        if self.opt_state is not None:
            trainable = [self.params[i] for i in self.t_idx]
            self.optimizer.restore_functional_state(trainable,
                                                    self.opt_state)


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._stepper = None
        self._jit = True
        self._guardian = None
        self._train_stamp = None
        self.stop_training = False

    # -- prepare ------------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, jit=True):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _as_list(metrics)
        for m in self._metrics:
            assert isinstance(m, Metric), f"{m} is not a Metric"
        self._jit = jit
        amp_level = None
        fp8 = False
        if amp_configs:
            # fp8 train pilot: amp_configs="fp8" (pure fp8 fake-quant
            # matmuls at model dtype) or {"level": "O1", "fp8": True}
            # (fp8 on top of the bf16 autocast) — jit path only
            if isinstance(amp_configs, str):
                if amp_configs == "fp8":
                    fp8 = True
                else:
                    amp_level = amp_configs
            elif isinstance(amp_configs, dict):
                fp8 = bool(amp_configs.get("fp8", False))
                amp_level = amp_configs.get("level",
                                            None if fp8 else "O1")
        if fp8 and not jit:
            raise ValueError("fp8 train pilot requires the compiled "
                             "stepper (prepare(jit=True))")
        if jit:
            self._stepper = _CompiledStepper(self.network, loss, optimizer,
                                             amp_level)
            if fp8:
                self._stepper.enable_fp8()
        if optimizer is not None and optimizer._parameter_list is None:
            optimizer._parameter_list = self.network.parameters()

    # -- single-batch ops ---------------------------------------------------
    def _train_mode(self):
        """``network.train()``, unless no layer's mode was assigned since
        this model last called it: the walk is over every sublayer."""
        if self._train_stamp != mode_stamp():
            self.network.train()
            self._train_stamp = mode_stamp()

    def train_batch(self, inputs, labels=None, update=True):
        self._train_mode()
        if self._jit and self._stepper is not None:
            loss, out_vals = self._stepper.train_step(inputs, labels,
                                                      update=update)
            return self._train_readback(loss, out_vals, labels, update)
        # eager path
        ins = [x if isinstance(x, Tensor) else Tensor(_to_jnp(x))
               for x in _as_list(inputs)]
        labs = [x if isinstance(x, Tensor) else Tensor(_to_jnp(x))
                for x in _as_list(labels)]
        outs = _as_list(self.network(*ins))
        loss = _total(self._loss(*(outs + labs)))
        loss.backward()
        if update:
            self._optimizer.step()
            self._optimizer.clear_grad()
            if isinstance(self._optimizer._learning_rate, LRScheduler):
                self._optimizer._learning_rate.step()
        metrics = self._update_metrics(outs, labs)
        return self._pack_loss_metrics(float(loss.item()), metrics)

    def _train_readback(self, loss, out_vals, labels, update):
        """The host half of a compiled ``train_batch``, after the
        stepper's dispatch returned: metric updates, the LR schedule and
        the step's one readback, ``float(loss)`` (``fit`` books it as
        ``fit.readback``)."""
        metrics = self._update_metrics(
            [Tensor(v) for v in out_vals], _as_list(labels))
        if isinstance(self._optimizer._learning_rate, LRScheduler) and \
                update:
            self._optimizer._learning_rate.step()
        return self._pack_loss_metrics(float(loss), metrics)

    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        with _ag.no_grad():
            if self._jit and self._stepper is not None:
                out_vals = self._stepper.eval_forward(inputs)
                outs = [Tensor(v) for v in out_vals]
            else:
                ins = [x if isinstance(x, Tensor) else Tensor(_to_jnp(x))
                       for x in _as_list(inputs)]
                outs = _as_list(self.network(*ins))
            labs = [x if isinstance(x, Tensor) else Tensor(_to_jnp(x))
                    for x in _as_list(labels)]
            loss = None
            if self._loss is not None and labs:
                loss = float(_total(self._loss(*(outs + labs))).item())
            metrics = self._update_metrics(outs, labs)
        return self._pack_loss_metrics(loss, metrics)

    def predict_batch(self, inputs):
        self.network.eval()
        with _ag.no_grad():
            if self._jit and self._stepper is not None:
                out_vals = self._stepper.eval_forward(inputs)
                return [np.asarray(v) for v in out_vals]
            ins = [x if isinstance(x, Tensor) else Tensor(_to_jnp(x))
                   for x in _as_list(inputs)]
            outs = _as_list(self.network(*ins))
            return [o.numpy() for o in outs]

    def _update_metrics(self, outs, labs):
        res = {}
        for m in self._metrics:
            computed = m.compute(*(outs + labs))
            r = m.update(*_as_list(computed))
            names = m.name()
            if isinstance(names, list):
                for n, v in zip(names, _as_list(r)):
                    res[n] = v
            else:
                res[names] = r
        return res

    @staticmethod
    def _pack_loss_metrics(loss, metrics):
        if metrics:
            return [loss], list(metrics.values())
        return [loss]

    # -- elastic resume train state ----------------------------------------
    def train_state_dict(self):
        """The full train state as one nested dict for
        ``distributed/checkpoint``: ``model.<name>`` params + buffers
        and ``opt.<param_name>.<accumulator>`` functional optimizer
        state.  Keys are stable param *names*, not layout positions, so
        the same checkpoint restores onto any topology (the elastic
        resharded-resume contract).  Eager (``prepare(jit=False)``)
        models capture the optimizer's materialized accumulators under
        the ``optimizer.state_dict`` naming (``p.name`` or
        ``param_<i>``), so a preempted eager run keeps its moments."""
        state = {"model": dict(self.network.state_dict())}
        st = self._stepper
        if st is not None and st.fp8_matmul:
            # fp8 pilot: the delayed-scaling amax vector rides the
            # manifest checkpoint (guardian rollback snapshots do NOT
            # carry it — running statistics re-warm in one step)
            state["fp8"] = {"amax": st.ensure_fp8_state()}
        if st is not None and self._optimizer is not None:
            st.ensure_opt_state()
            opt = {}
            for i, idx in enumerate(st.t_idx):
                opt[st.param_names[idx]] = dict(st.opt_state[i])
            state["opt"] = opt
        elif self._optimizer is not None and \
                self._optimizer._parameter_list:
            opt = {}
            for i, p in enumerate(self._optimizer._parameter_list):
                acc = self._optimizer._accumulators.get(id(p))
                if acc:
                    opt[p.name or f"param_{i}"] = dict(acc)
            if opt:
                state["opt"] = opt
        return state

    def _restore_train_state(self, flat, manifest=None):
        """Install a flat checkpoint state (from ``restore_latest``)
        into the live model: params/buffers by name, functional opt
        state by param name, then step counter, LR-scheduler state and
        the global RNG stream from the manifest.  Values are assigned
        directly — they already carry the target shardings the restore
        derived; a host round-trip here would undo the reshard."""
        own = self.network.state_dict()
        matched = 0
        for name, t in own.items():
            v = flat.get("model." + name)
            if v is None:
                continue
            if tuple(v.shape) != tuple(t._value.shape):
                raise ValueError(
                    f"resume shape mismatch for {name}: checkpoint has "
                    f"{tuple(v.shape)}, model has {tuple(t._value.shape)}")
            if v.dtype != t._value.dtype:
                v = v.astype(t._value.dtype)
            t._value = v
            matched += 1
        if own and flat and not matched:
            # a checkpoint that shares NO keys with this model (e.g. a
            # guardian ckpt_root, or a foreign state layout) must fail
            # loudly — "resumed" with nothing restored would silently
            # train from random init
            raise ValueError(
                "resume checkpoint shares no keys with this model: "
                f"checkpoint has {sorted(flat)[:3]}..., expected "
                "'model.<param_name>' entries as written by "
                "Model.train_state_dict / the fit emergency save")
        st = self._stepper
        if st is not None and st.fp8_matmul:
            v = flat.get("fp8.amax")
            if v is not None:
                st.fp8_state = jnp.asarray(v, jnp.float32)
        if st is not None and self._optimizer is not None:
            st.ensure_opt_state()
            new_opt = []
            for i, idx in enumerate(st.t_idx):
                pname = st.param_names[idx]
                d = dict(st.opt_state[i])
                for acc in list(d):
                    v = flat.get(f"opt.{pname}.{acc}")
                    if v is not None:
                        d[acc] = v
                new_opt.append(d)
            st.opt_state = new_opt
        elif self._optimizer is not None and \
                self._optimizer._parameter_list:
            # eager path: reinstate materialized accumulators in place
            for i, p in enumerate(self._optimizer._parameter_list):
                name = p.name or f"param_{i}"
                acc = {}
                for a in self._optimizer._state_names:
                    v = flat.get(f"opt.{name}.{a}")
                    if v is not None:
                        acc[a] = v
                if acc:
                    cur = dict(self._optimizer._accumulators.get(id(p))
                               or {})
                    cur.update(acc)
                    self._optimizer._accumulators[id(p)] = cur
        if manifest:
            opt_meta = manifest.get("opt") or {}
            if self._optimizer is not None:
                self._optimizer._global_step = int(
                    opt_meta.get("global_step",
                                 self._optimizer._global_step))
                lrs = opt_meta.get("lr_scheduler")
                if lrs and self._optimizer._lr_scheduler is not None:
                    self._optimizer._lr_scheduler.set_state_dict(lrs)
            from ..distributed import checkpoint as ckpt
            key = ckpt.rng_state_from_manifest(manifest)
            if key is not None:
                set_rng_state([key],
                              seed=(manifest.get("rng") or {}).get("seed"))
        if st is not None:
            st._refresh_state_refs()
            st._train_cache.clear()
            st._grad_cache.clear()
            st._eval_cache.clear()

    def _resume_from(self, root):
        """Restore from the newest valid manifest checkpoint under
        ``root`` onto whatever mesh THIS process came up with (the
        stepper's plan, or single device), and return the data cursor
        as ``(start_epoch, skip_steps)``.  An empty root is a fresh
        start, not an error — the launcher points every (re)launch at
        the same resume root."""
        from ..distributed import checkpoint as ckpt
        st = self._stepper
        template = self.train_state_dict()
        mesh = st.plan.mesh if (st is not None and
                                st.plan is not None) else None
        try:
            state, manifest, d = ckpt.restore_latest(
                root, template=template, mesh=mesh)
        except FileNotFoundError:
            print(f"[hapi] resume: no committed checkpoint under "
                  f"{root}; starting fresh", flush=True)
            return None
        self._restore_train_state(state, manifest)
        if manifest is None and self._optimizer is not None:
            # torn/missing manifest (the documented degrade): the RNG
            # stream and data cursor are unrecoverable, but the step
            # counter must still move FORWARD — the step-dir number IS
            # the global step for fit checkpoints, and leaving it at 0
            # would make later periodic saves write step numbers older
            # than the committed dirs, regressing every future resume
            # to this stale step
            m = re.search(r"step_(\d+)$", d)
            if m:
                self._optimizer._global_step = int(m.group(1))
        cursor = (manifest or {}).get("data_cursor") or {}
        epoch = int(cursor.get("epoch", 0))
        step = cursor.get("step")
        gstep = (manifest or {}).get("opt", {}).get("global_step")
        print(f"[hapi] resumed from {d} (global step {gstep}, epoch "
              f"{epoch}, step {step})", flush=True)
        if step == "epoch-end" or step is None:
            return (epoch + 1, 0) if step == "epoch-end" else (epoch, 0)
        return epoch, int(step) + 1

    # -- fit / evaluate / predict -------------------------------------------
    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1,
            verbose=2, drop_last=False, shuffle=True, num_workers=0,
            callbacks=None, accumulate_grad_batches=1, num_iters=None,
            guardian=None, resume=None):
        train_loader = self._to_loader(train_data, batch_size, shuffle,
                                       drop_last, num_workers)
        eval_loader = self._to_loader(eval_data, batch_size, False, False,
                                      num_workers) if eval_data is not None \
            else None
        try:
            steps = len(train_loader)
        except TypeError:
            steps = None
        cbks = cbks_mod.config_callbacks(
            callbacks, model=self, epochs=epochs, steps=steps,
            log_freq=log_freq, save_freq=save_freq, save_dir=save_dir,
            verbose=verbose,
            metrics=["loss"] + self._metric_names(),
            manifest_saves=bool(save_dir))
        cbks.on_begin("train")
        self.stop_training = False
        # preemption-aware: SIGTERM sets a flag we poll between steps so a
        # preempted worker exits through one final checkpoint, and the
        # launcher relaunches it with resume (framework/preemption.py).
        # The previous disposition is restored on exit — a process that
        # has left fit() must die normally on SIGTERM, not swallow it
        # into a flag nobody polls.
        _preempt_installed = _preemption.install()
        # training guardian (framework/guardian.py): numeric sentinel +
        # skip-and-rollback ladder.  guardian= (config/dict/True) wins,
        # else fleet.DistributedStrategy.guardian, else PADDLE_GUARDIAN
        # env.  Default-off: the per-step cost is this one None-check.
        gcfg = _guardian.GuardianConfig.normalize(guardian)
        self._guardian = (_guardian.TrainingGuardian(gcfg, self)
                          if gcfg is not None else None)
        guard_jit = (self._guardian is not None and gcfg.check_grads
                     and self._jit and self._stepper is not None)
        if self._guardian is not None:
            self._guardian.start()
            if guard_jit:
                self._stepper.guard_numerics = True
                self._stepper._train_cache.clear()
        try:
            # elastic resume (resume=<checkpoint root>): restore step
            # counter, params, opt state, RNG and data cursor onto the
            # mesh THIS process came up with — the checkpoint may have
            # been written at a different np / dp×mp split.  Runs after
            # guardian setup so the restored state lands in the cleared
            # step caches.
            start_epoch = skip_steps = 0
            if resume:
                cursor = self._resume_from(resume)
                if cursor is not None:
                    start_epoch, skip_steps = cursor
            trace = _tracing.mint("fit")
            with _tracing.region(trace, None, "fit") as root:
                self._fit_epochs(epochs, eval_freq, save_dir, cbks,
                                 train_loader, eval_loader, num_iters,
                                 accumulate_grad_batches, batch_size,
                                 trace, root.id, start_epoch=start_epoch,
                                 skip_steps=skip_steps,
                                 save_freq=save_freq)
        finally:
            if self._guardian is not None:
                self._guardian.stop()
                self._guardian = None
                if guard_jit:
                    # un-instrumented steppers must not keep paying the
                    # guarded executable's select ops
                    self._stepper.guard_numerics = False
                    self._stepper._train_cache.clear()
            if _preempt_installed:
                _preemption.uninstall()

    @staticmethod
    def _timed_batches(loader, trace, parent, count):
        """``loader``'s batches, each ``__next__`` under a ``fit.data``
        span numbered like the step it feeds (``count[0]``)."""
        it = iter(loader)
        while True:
            with _tracing.region(trace, count[0], "fit.data",
                                 parent=parent) as r:
                try:
                    batch = next(it)
                except StopIteration:
                    r.args["exhausted"] = True
                    return
            yield batch

    def _fit_epochs(self, epochs, eval_freq, save_dir, cbks, train_loader,
                    eval_loader, num_iters, accumulate_grad_batches,
                    batch_size, trace, root, start_epoch=0, skip_steps=0,
                    save_freq=1):
        # the fit call's trace (observability/tracing.py): under the
        # ``fit`` span ``root``, ``fit.data`` and ``fit.step`` per step,
        # and on the compiled path ``fit.dispatch`` / ``fit.readback`` /
        # ``fit.post`` tiling the step from ``_split_batch`` on.  Every
        # stamp is a host clock read; docs/observability.md has the table
        logs = {}            # bound even when epochs == 0
        jit = self._jit and self._stepper is not None
        count = [0]          # steps of this fit call: the spans' req_id
        readback_end = None  # end of the last fit.readback, for the gap

        def child(name, n, parent, start_ns=None):
            # the eager path has no dispatch/readback boundary to stamp:
            # it books fit.step alone
            if not jit:
                return contextlib.nullcontext(_tracing.Region({}))
            return _tracing.region(trace, n, name, parent=parent,
                                   start_ns=start_ns)
        for epoch in range(start_epoch, epochs):
            cbks.on_epoch_begin(epoch)
            self._reset_metrics()
            self._train_mode()
            logs = {}
            for step, batch in enumerate(self._timed_batches(
                    train_loader, trace, root, count)):
                if num_iters is not None and step >= num_iters:
                    break
                if epoch == start_epoch and step < skip_steps:
                    # data cursor: batches the pre-kill run already
                    # trained on (exact for deterministic loaders; a
                    # reshuffling loader resumes at the right COUNT).
                    # SIGTERM during a long replay still honors the
                    # exit-71 contract promptly — the state equals the
                    # committed checkpoint we resumed from, so exiting
                    # without a new save loses nothing.
                    if _preemption.preempted():
                        cbks.on_end("train", logs)
                        raise _preemption.PreemptedExit()
                    if self.stop_training:
                        break
                    continue
                n = count[0]
                count[0] += 1
                with _tracing.region(trace, n, "fit.step",
                                     parent=root) as sp:
                    cbks.on_batch_begin("train", step, logs)
                    guard = self._guardian
                    do_update = (step + 1) % max(accumulate_grad_batches,
                                                 1) == 0
                    with child("fit.dispatch", n, sp.id) as disp:
                        ins, labs = self._split_batch(batch)
                        skip = guard is not None and guard.skip_batch()
                        if not skip:
                            if guard is not None:
                                ins = guard.filter_batch(ins)
                            # telemetry: wall time of the whole step,
                            # including the per-step loss readback
                            # below — recording adds NO device transfer
                            # (every value is a host float/shape the
                            # loop already owns)
                            t_step = time.perf_counter()
                            if jit:
                                self._train_mode()
                                stepped = self._stepper.train_step(
                                    ins, labs, update=do_update)
                    if skip:     # post-rollback poisoned window
                        cbks.on_batch_end("train", step, logs)
                        continue
                    if readback_end is not None and disp.end_ns is not None:
                        _obs.observe("pt_train_host_gap_ms",
                                     (disp.end_ns - readback_end) / 1e6)
                    with child("fit.readback", n, sp.id, disp.end_ns) as rb:
                        res = self._train_readback(
                            *stepped, labs, do_update) if jit else \
                            self.train_batch(ins, labs, update=do_update)
                    # the step's outputs (fp32 logits among them) must
                    # not outlive the step in this frame: held, the next
                    # step cannot reuse their memory
                    stepped = None
                    readback_end = rb.end_ns
                    with child("fit.post", n, sp.id, rb.end_ns) as post:
                        logs = self._after_train_batch(
                            res, guard, ins, labs, t_step, step, batch_size)
                        cbks.on_batch_end("train", step, logs)
                    sp.end_ns = post.end_ns
                if _preemption.preempted():
                    self._emergency_save(save_dir, epoch, step)
                    cbks.on_end("train", logs)
                    raise _preemption.PreemptedExit()
                if self.stop_training:
                    break
            if eval_loader is not None and \
                    ((epoch + 1) % eval_freq == 0 or epoch == epochs - 1):
                eval_logs = self._run_eval(eval_loader, cbks)
                logs.update({"eval_" + k: v for k, v in eval_logs.items()})
            cbks.on_epoch_end(epoch, logs)
            # periodic manifest checkpoint at the epoch boundary: a
            # crash that never gets the SIGTERM grace (OOM kill,
            # segfault) resumes from here through the same
            # fit(resume=root) path as the emergency save.  Best
            # effort: a failed periodic save must not kill training.
            if save_dir and (epoch + 1) % max(save_freq, 1) == 0:
                try:
                    self._save_train_checkpoint(save_dir, epoch,
                                                "epoch-end")
                except Exception as e:
                    print(f"[hapi] periodic checkpoint at epoch "
                          f"{epoch} failed: {e!r}", flush=True)
            # SIGTERM during the eval pass or at the epoch boundary must
            # not wait for the next train batch to be honored — the
            # platform's kill grace may lapse first
            if _preemption.preempted():
                self._emergency_save(save_dir, epoch, step="epoch-end")
                cbks.on_end("train", logs)
                raise _preemption.PreemptedExit()
            if self.stop_training:
                break
        cbks.on_end("train", logs)

    def _after_train_batch(self, res, guard, ins, labs, t_step, step,
                           batch_size):
        """What ``fit`` does with one step's result before
        ``on_batch_end``: the guardian's verdict, the step's metrics and
        flight sample, the logs (returned)."""
        verdict = None
        if guard is not None:
            loss_v = res[0][0] if isinstance(res, tuple) else res[0]
            ok = (self._stepper.last_ok
                  if self._jit and self._stepper is not None
                  else None)
            verdict = guard.after_step(loss_v, ok_flag=ok,
                                       batch=(ins, labs))
        step_s = time.perf_counter() - t_step
        # one token count feeds both the metrics below and the
        # flight sample — counted once so they can never drift
        tokens = None
        if ins and hasattr(ins[0], "shape"):
            tokens = 1
            for d in ins[0].shape:
                tokens *= int(d)
        if _obs.enabled():
            _obs.observe("pt_train_step_latency_ms", step_s * 1e3)
            _obs.inc("pt_train_steps_total",
                     outcome=verdict or "ok")
            if tokens is not None:
                _obs.inc("pt_train_tokens_total", tokens)
                _obs.set_gauge("pt_train_tokens_per_sec",
                               tokens / max(step_s, 1e-9))
        logs = self._make_logs(res)
        if _obs.enabled() and logs.get("loss") is not None:
            _obs.set_gauge("pt_train_loss", float(logs["loss"]))
        # flight recorder (observability/flight.py): one sample
        # per step at THIS existing sync point — every value is
        # a host number the loop already owns (wall delta,
        # static shapes, the loss readback train_batch already
        # paid), so the zero-new-host-sync A/B contract holds
        if _obs.flight.active():
            tok_s = None if tokens is None \
                else tokens / max(step_s, 1e-9)
            _obs.flight.record(
                "fit_step", step_latency_ms=step_s * 1e3,
                tokens_per_sec=tok_s,
                loss=(float(logs["loss"])
                      if logs.get("loss") is not None else None),
                verdict=verdict or "ok",
                # live-buffer census (HBM ledger): host
                # metadata only, at the post-step sync
                **_obs.memory.census_fields("fit_step"))
        logs["step"] = step
        logs["batch_size"] = (
            ins[0].shape[0] if ins and hasattr(ins[0], "shape")
            else batch_size)
        return logs

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_iters=None):
        loader = self._to_loader(eval_data, batch_size, False, False,
                                 num_workers)
        cbks = cbks_mod.config_callbacks(
            callbacks, model=self, log_freq=log_freq, verbose=verbose,
            metrics=["loss"] + self._metric_names())
        cbks.on_begin("eval")
        logs = self._run_eval(loader, cbks, num_iters=num_iters)
        cbks.on_end("eval", logs)
        return logs

    def _run_eval(self, loader, cbks, num_iters=None):
        self._reset_metrics()
        self.network.eval()
        logs = {}
        losses = []
        for step, batch in enumerate(loader):
            if num_iters is not None and step >= num_iters:
                break
            if _preemption.preempted():
                break    # cut eval short; fit's epoch loop handles exit
            cbks.on_batch_begin("eval", step, logs)
            ins, labs = self._split_batch(batch)
            res = self.eval_batch(ins, labs)
            logs = self._make_logs(res)
            if isinstance(res, tuple) and res[0][0] is not None:
                losses.append(res[0][0])
            elif isinstance(res, list) and res[0] is not None:
                losses.append(res[0])
            cbks.on_batch_end("eval", step, logs)
        if losses:
            logs["loss"] = float(np.mean(losses))
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        loader = self._to_loader(test_data, batch_size, False, False,
                                 num_workers)
        outputs = []
        for batch in loader:
            ins, _ = self._split_batch(batch, has_labels=False)
            outs = self.predict_batch(ins)
            outputs.append(outs)
        if not outputs:
            return []
        n_out = len(outputs[0])
        grouped = [[o[i] for o in outputs] for i in range(n_out)]
        if stack_outputs:
            return [np.concatenate(g, axis=0) for g in grouped]
        return grouped

    # -- helpers ------------------------------------------------------------
    def _metric_names(self):
        names = []
        for m in self._metrics:
            n = m.name()
            names.extend(n if isinstance(n, list) else [n])
        return names

    def _reset_metrics(self):
        for m in self._metrics:
            m.reset()

    def _make_logs(self, res):
        logs = {}
        if isinstance(res, tuple):
            losses, metrics = res
            if losses and losses[0] is not None:
                logs["loss"] = losses[0]
            for n, v in zip(self._metric_names(), metrics):
                logs[n] = v
        else:
            if res and res[0] is not None:
                logs["loss"] = res[0]
        return logs

    def _split_batch(self, batch, has_labels=True):
        n_in = len(self._inputs) if self._inputs else 1
        if isinstance(batch, (list, tuple)):
            batch = list(batch)
            if not has_labels:
                return batch[:n_in], []
            if self._loss is None:
                return batch, []
            if len(batch) > n_in:
                return batch[:n_in], batch[n_in:]
            return batch, []
        return [batch], []

    def _to_loader(self, data, batch_size, shuffle, drop_last, num_workers):
        if data is None:
            return None
        if isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              drop_last=drop_last, num_workers=num_workers)
        return data  # assume iterable of batches

    def _emergency_save(self, save_dir, epoch, step):
        """Final checkpoint on preemption, written through
        ``distributed/checkpoint``'s step-dir manifest protocol — ONE
        format for the emergency save, periodic saves and the elastic
        resharded resume, so the relaunched worker restores it via
        ``Model.fit(resume=save_dir)`` on WHATEVER mesh it comes up
        with.  (The pre-ISSUE-14 ``preempted.pdparams/.pdopt`` sentinel
        swap is gone: that format carried no layout manifest, so the
        resharded path could not read it; ``Model.load`` still accepts
        old checkpoints.)  The step dir is ``step_<global_step>`` under
        ``save_dir``, COMMITTED-sentinel-committed with the manifest,
        so a kill mid-save leaves a torn dir the resume path skips.
        Failures are logged, not raised — exiting with the preemption
        code matters more than a perfect save."""
        if not save_dir:
            return
        try:
            # _save_train_checkpoint dedups per global step, so SIGTERM
            # landing right after an epoch-end periodic save does not
            # burn the kill grace re-serializing identical state
            path = self._save_train_checkpoint(save_dir, epoch, step)
            print(f"[hapi] preempted at epoch {epoch} step {step}: "
                  f"emergency checkpoint saved to {path}", flush=True)
        except Exception as e:
            print(f"[hapi] preempted but emergency save failed: {e!r}",
                  flush=True)

    def _save_train_checkpoint(self, save_dir, epoch, step):
        """One train-state checkpoint through the step-dir manifest
        protocol — shared by the periodic epoch-end saves and the
        preemption emergency save, so a crash WITHOUT the SIGTERM
        grace (OOM kill, segfault) still resumes from the last epoch
        boundary via the same ``Model.fit(resume=root)`` path.

        Idempotent per global step: when the newest committed step dir
        already carries the current step number (the state it holds is
        this state — the step counter only moves on optimizer updates),
        the save is skipped rather than re-writing a committed dir."""
        from ..distributed import checkpoint as ckpt
        gstep = (self._optimizer._global_step
                 if self._optimizer is not None else 0)
        latest = ckpt.latest_checkpoint(save_dir)
        if latest is not None and os.path.basename(latest) == \
                f"step_{int(gstep):08d}":
            return latest
        state = self.train_state_dict()
        opt_meta = {"global_step": int(gstep)}
        if self._optimizer is not None and \
                self._optimizer._lr_scheduler is not None:
            opt_meta["lr_scheduler"] = \
                self._optimizer._lr_scheduler.state_dict()
        plan = self._stepper.plan if self._stepper is not None else None
        # rank 0 commits the manifest for the job; other ranks skip the
        # state walk + key readback for a dict the commit would discard
        manifest = None
        if jax.process_index() == 0:
            manifest = ckpt.build_manifest(
                state, step=gstep, plan=plan,
                data_cursor={"epoch": int(epoch), "step": step},
                opt_meta=opt_meta)
        return ckpt.save_checkpoint(state, save_dir, step=gstep,
                                    manifest=manifest)

    # -- persistence --------------------------------------------------------
    def save(self, path, training=True):
        if training:
            self._sync_opt()
            _save(self.network.state_dict(), path + ".pdparams")
            if self._optimizer is not None:
                _save(self._optimizer.state_dict(), path + ".pdopt")
        else:
            from .. import jit as _jit
            specs = self._inputs
            _jit.save(self.network, path, input_spec=specs)

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        # an emergency save commits via a COMMITTED sentinel naming a
        # generation-suffixed pair and recording its content identity;
        # loading ``<save_dir>/preempted`` follows the sentinel.  A pair
        # that contradicts it (corrupted or half-staged copy) fails
        # loudly rather than resuming mismatched params/optimizer state.
        sentinel = path + ".COMMITTED"
        if os.path.exists(sentinel):
            with open(sentinel) as f:
                stamp = json.load(f)
            real = f"{path}.g{stamp['gen']}" if "gen" in stamp else path
            for ext, want in stamp.get("files", {}).items():
                p = real + ext
                if not os.path.exists(p) or _file_stamp(p) != want:
                    raise RuntimeError(
                        f"torn emergency checkpoint at {path}: {p} does "
                        "not match its COMMITTED sentinel — the files "
                        "were corrupted or half-staged; fall back to an "
                        "older checkpoint")
            path = real
        sd = _load(path + ".pdparams")
        self.network.set_state_dict(sd)
        opt_path = path + ".pdopt"
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(opt_path):
            self._optimizer.set_state_dict(_load(opt_path))
            if self._stepper is not None:
                self._stepper.opt_state = None  # rebuilt from optimizer
        if self._stepper is not None:
            self._stepper._refresh_state_refs()
            self._stepper._train_cache.clear()
            self._stepper._eval_cache.clear()

    def _sync_opt(self):
        if self._stepper is not None:
            self._stepper.sync_opt_state_to_optimizer()

    def parameters(self, *args, **kwargs):
        return self.network.parameters(*args, **kwargs)

    def summary(self, input_size=None, dtype=None):
        from .model_summary import summary
        if input_size is None and self._inputs:
            input_size = [tuple(s.shape) for s in self._inputs]
        return summary(self.network, input_size, dtypes=dtype)
