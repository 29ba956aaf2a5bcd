"""A dropless expert layer that is told which experts it holds.

Beside :class:`MoELayer` (capacity-bucketed GShard dispatch of two-matrix
experts, which drops what overflows a bucket) this is the layer of
today's sparse models:

- **router**: ``s = sigmoid(W_r h)`` over ALL ``num_experts`` in float32;
  the ``top_k`` chosen are the largest of ``s + b`` (``b`` the selection
  bias of auxiliary-loss-free balancing: it picks and never weighs); the
  weights are ``g_i = scaling * s_i / sum_{j chosen} s_j``.
- **gated experts**: ``E(h) = W_down (silu(W_gate h) * W_up h)``; gate and
  up are stored side by side as one ``(H, 2I)`` matrix an expert.  With
  ``swiglu_limit`` L the gate is clamped to at most L and the up
  projection to [-L, L] before they meet (routed and shared experts).
- **a shared expert** every token passes through, counted once.
- **dropless**: the token-expert pairs are sorted by expert and the held
  experts' stacked matrices are applied by ONE grouped matmul
  (``ops/grouped_matmul.py``) over the rows routed to them: shapes are
  static (``tokens x top_k`` rows, the worst case), the rows a group gets
  are data, and no token is ever dropped.
- **experts_held**: a contiguous range of expert ids, this chip's share
  under expert parallelism.  The router keeps its full width, its top-k
  and its normalisation over all the chosen; the layer computes
  ``sum_{i chosen and held} g_i E_i(h) + E_shared(h)``.  What the absent
  experts would add is left out: there is no exchange here and nothing
  stands in for the other chips (the sum over the shares of all chips,
  the shared expert counted once, is the whole layer's result;
  ``tests/test_mla_moe.py`` holds that).

Device counters (``observability.devcounters``), where a program asks:
``moe_pairs_routed``, ``moe_pairs_here``, ``moe_max_expert_rows`` and, in
a decode step, ``moe_experts_touched`` and ``moe_decode_layer_steps``.
"""
import jax
import jax.numpy as jnp

from .....framework.autograd import call_op
from ..... import nn
from .....nn.initializer import Normal
from .....observability import devcounters as _devc
from .....observability.tracing import scope as _scope
from .....ops.grouped_matmul import grouped_matmul

__all__ = ["DroplessMoELayer", "route", "swiglu"]

COUNTERS = ("moe_pairs_routed", "moe_pairs_here", "moe_experts_touched",
            "moe_decode_layer_steps", "moe_max_expert_rows")


def route(h, router_w, router_b, top_k, scaling):
    """(picks (T, k) int32, gates (T, k) float32) of tokens ``h`` (T, H):
    scores, picks and weights in float32 whatever ``h`` is."""
    s = jax.nn.sigmoid(jnp.dot(h, router_w,
                               preferred_element_type=jnp.float32))
    _, picks = jax.lax.top_k(s + router_b.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, picks, axis=-1)
    gates = scaling * chosen / chosen.sum(-1, keepdims=True)
    return picks.astype(jnp.int32), gates


def gated(gu, limit=None):
    """``silu(gate) * up`` of gate and up side by side along the last
    axis; with ``limit`` the gate clamped to at most it and up to
    [-limit, limit]."""
    half = gu.shape[-1] // 2
    gate, up = gu[..., :half], gu[..., half:]
    if limit is not None:
        gate = jnp.minimum(gate, limit)
        up = jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def swiglu(h, gate_up, down, limit=None):
    """``(silu(h W_gate) * h W_up) W_down`` with gate and up side by side."""
    return jnp.dot(gated(jnp.dot(h, gate_up), limit), down)


def _count(bag, here, local, real, top_k, held):
    pair_real = jnp.repeat(real, top_k)
    mine = here & pair_real
    sizes = jnp.bincount(jnp.where(mine, local, held), length=held + 1)[:held]
    bag.add("moe_pairs_routed", real.sum() * top_k)
    bag.add("moe_pairs_here", mine.sum())
    bag.max("moe_max_expert_rows", sizes.max())
    if bag.phase == "decode":
        bag.add("moe_experts_touched", (sizes > 0).sum())
        bag.add("moe_decode_layer_steps", 1)


def _forward(x, router_w, router_b, gate_up, down, shared_gate_up,
             shared_down, *, top_k, scaling, first_held, limit=None):
    B, S, H = x.shape
    T, held = B * S, gate_up.shape[0]
    h = x.reshape(T, H)
    with _scope("moe.router"):
        picks, gates = route(h, router_w, router_b, top_k, scaling)
    with _scope("moe.dispatch"):
        # token-expert pairs sorted by held expert; a pair whose expert
        # lives on another chip sorts past the last group and is computed
        # by no one here
        local = picks.reshape(T * top_k) - first_held
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held)
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.bincount(key, length=held + 1)[:held]
        rows = h[order // top_k]                          # (T*k, H)
        bag = _devc.current()
        if bag is not None:
            real = jnp.ones((T,), bool) if bag.rows is None else \
                jnp.broadcast_to(bag.rows, (B, S)).reshape(T)
            _count(bag, here, local, real, top_k, held)
    with _scope("moe.experts"):
        gu = grouped_matmul(rows, gate_up, group_sizes)
        y = grouped_matmul(gated(gu, limit), down,
                           group_sizes)                   # zeros past the groups
    with _scope("moe.shared"):
        shared = swiglu(h, shared_gate_up, shared_down, limit)
    with _scope("moe.combine"):
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(order.shape[0], dtype=order.dtype))
        pairs = y[back].reshape(T, top_k, H)
        weights = jnp.where(here.reshape(T, top_k), gates, 0.0)
        routed = jnp.einsum("tkh,tk->th", pairs, weights,
                            preferred_element_type=jnp.float32)
        out = (routed + shared.astype(jnp.float32)).astype(x.dtype)
    return out.reshape(B, S, H)


class DroplessMoELayer(nn.Layer):
    """See the module docstring.  ``experts_held`` is a ``range`` of expert
    ids (default: all of them); ``dtype`` the parameters' dtype;
    ``swiglu_limit`` the clamp of the experts' gate and up (None: none)."""

    device_counters = COUNTERS

    def __init__(self, d_model, d_expert, num_experts, top_k,
                 experts_held=None, d_shared=None, scaling=1.0,
                 dtype=None, init_std=0.02, swiglu_limit=None):
        super().__init__()
        held = range(num_experts) if experts_held is None else experts_held
        if held.step != 1 or held.start < 0 or held.stop > num_experts \
                or not len(held):
            raise ValueError(
                f"experts_held {held!r} must be a contiguous non-empty "
                f"range within the router's {num_experts} experts")
        if top_k > num_experts:
            raise ValueError("top_k exceeds num_experts")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.experts_held, self.scaling = held, float(scaling)
        self.swiglu_limit = None if swiglu_limit is None \
            else float(swiglu_limit)
        d_shared = d_expert if d_shared is None else d_shared
        G, init = len(held), Normal(0.0, init_std)

        def param(*shape):
            return self.create_parameter(shape, dtype=dtype,
                                         default_initializer=init)
        self.router_weight = param(d_model, num_experts)
        self.router_bias = param(num_experts)
        self.experts_gate_up = param(G, d_model, 2 * d_expert)
        self.experts_down = param(G, d_expert, d_model)
        self.shared_gate_up = param(d_model, 2 * d_shared)
        self.shared_down = param(d_shared, d_model)

    def forward(self, x):
        return call_op(
            _forward, x, self.router_weight, self.router_bias,
            self.experts_gate_up, self.experts_down, self.shared_gate_up,
            self.shared_down, top_k=self.top_k, scaling=self.scaling,
            first_held=self.experts_held.start, limit=self.swiglu_limit)
