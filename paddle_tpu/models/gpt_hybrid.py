"""Hybrid-parallel GPT train step: dp × tp × pp in ONE jitted SPMD program
(reference: the fleet GPT-3 path, SURVEY §3.4 — per-rank processes, NCCL
groups, 1F1B over send/recv; here the whole schedule is compiled).

Composition:
- data axis   : batch sharding (GSPMD inserts the grad psum)
- model axis  : Megatron TP via weight pspecs (mp_layers annotations)
- pipe axis   : stacked decoder blocks via shard_map+ppermute rotation
  (distributed/pipeline.py), manual ONLY over "pipe" so dp/tp stay under
  GSPMD inside each stage
"""
import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..framework.core import Tensor
from ..framework import autograd as _ag
from ..framework.random import rng_scope
from .gpt import GPTConfig, GPTForPretraining
from ..analysis import register_jit_surface
from ..distributed.pipeline import spmd_pipeline, stack_block_params
from ..ops import registry as kreg

__all__ = ["build_hybrid_gpt", "hybrid_train_step"]

# the hybrid stepper's compiled body is a nested def — registered for
# the tracer-safety/donation passes (mirrored by EXTRA_JIT_SURFACES in
# paddle_tpu/analysis/allowlist.py).  Donation audit (ISSUE 11): the
# jit donates (0, 1) — other params + stacked block params are consumed
# by the update and returned as new state.
register_jit_surface(__name__, "build_hybrid_gpt.step")


def _capture(layer):
    named = list(layer.named_parameters())
    return [n for n, _ in named], [p for _, p in named]


def build_hybrid_gpt(config, mesh, n_micro=2, lr=1e-3):
    """Returns (step_fn, state, data_shardings).

    step_fn(other_vals, stacked_vals, ids, labels) → (loss, new_other,
    new_stacked); jitted with full dp/tp/pp shardings.
    state = (other_vals, stacked_vals) device_put to their shardings.
    """
    model = GPTForPretraining(config)
    model.eval()  # dropout off for the deterministic compile check
    blocks = list(model.gpt.layers)

    # --- split params: stacked block params vs the rest ------------------
    template = blocks[0]
    t_names, t_params = _capture(template)
    block_vals = [[p._value for _, p in b.named_parameters()]
                  for b in blocks]
    stacked = stack_block_params(block_vals)

    block_ids = set()
    for b in blocks:
        for _, p in b.named_parameters():
            block_ids.add(id(p))
    other_params = [p for _, p in model.named_parameters()
                    if id(p) not in block_ids]
    other_vals = [p._value for p in other_params]

    # --- shardings -------------------------------------------------------
    has = set(mesh.axis_names)

    def pspec_of(p):
        explicit = getattr(p, "pspec", None)
        if explicit is not None:
            return P(*[a if a in has else None for a in explicit])
        return P()

    other_specs = [pspec_of(p) for p in other_params]
    stacked_specs = [P("pipe", *pspec_of(p)) for p in t_params]
    other_sh = [NamedSharding(mesh, s) for s in other_specs]
    stacked_sh = [NamedSharding(mesh, s) for s in stacked_specs]
    data_sh = NamedSharding(
        mesh, P("data" if "data" in has else None, None))
    rep = NamedSharding(mesh, P())

    other_vals = [jax.device_put(v, s) for v, s in zip(other_vals, other_sh)]
    stacked = [jax.device_put(v, s) for v, s in zip(stacked, stacked_sh)]

    # --- pure pieces ------------------------------------------------------
    def block_apply(blk_vals, h):
        olds = [p._value for p in t_params]
        for p, v in zip(t_params, blk_vals):
            p._value = v
        try:
            with _ag.suspend_tape():
                return template(Tensor(h))._value
        finally:
            for p, v in zip(t_params, olds):
                p._value = v

    def outer_forward(other, ids_val, h_mid_fn):
        """Embed → pipeline(h) → final norm → tied-logits."""
        olds = [p._value for p in other_params]
        for p, v in zip(other_params, other):
            p._value = v
        try:
            with _ag.suspend_tape(), rng_scope(jax.random.key(0)):
                emb = model.gpt.embeddings(Tensor(ids_val))._value
                mid = h_mid_fn(emb)
                normed = model.gpt.final_norm(Tensor(mid))._value
                wte = model.gpt.embeddings.word_embeddings.weight._value
                return normed @ wte.T
        finally:
            for p, v in zip(other_params, olds):
                p._value = v

    def loss_fn(other, stacked_vals, ids_val, labels_val):
        B, S = ids_val.shape

        def mid(emb):
            H = emb.shape[-1]
            mb = B // n_micro
            x_mb = emb.reshape(n_micro, mb, S, H)
            if "pipe" in has and mesh.shape["pipe"] > 1:
                y = spmd_pipeline(block_apply, stacked_vals, x_mb, mesh,
                                  axis="pipe", remat=True)
            else:
                def seq(x):
                    h = x
                    per = stacked_vals[0].shape[0]
                    for i in range(per):
                        h = block_apply([v[i] for v in stacked_vals], h)
                    return h
                y = seq(x_mb)
            return y.reshape(B, S, H)

        logits = outer_forward(other, ids_val, mid)
        V = logits.shape[-1]
        lg = logits[:, :-1, :].reshape(-1, V).astype(jnp.float32)
        lb = labels_val[:, 1:].reshape(-1)
        logp = jax.nn.log_softmax(lg, axis=-1)
        nll = -jnp.take_along_axis(logp, lb[:, None], axis=-1)
        return jnp.mean(nll)

    def step(other, stacked_vals, ids_val, labels_val):
        # Pallas kernels run per shard of the mesh: XLA cannot partition
        # a Mosaic kernel (ops/registry.py "multi-device traces")
        with kreg.partitioned(mesh, ("data",), "model"):
            loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
                other, stacked_vals, ids_val, labels_val)
        g_other, g_stacked = grads
        new_other = [p - lr * g for p, g in zip(other, g_other)]
        new_stacked = [p - lr * g for p, g in zip(stacked_vals, g_stacked)]
        return loss, new_other, new_stacked

    step_jit = jax.jit(
        step,
        in_shardings=(other_sh, stacked_sh, data_sh, data_sh),
        out_shardings=(rep, other_sh, stacked_sh),
        donate_argnums=(0, 1))
    return step_jit, (other_vals, stacked), data_sh
