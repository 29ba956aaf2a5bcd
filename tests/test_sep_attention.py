"""Sequence-parallel (sep axis) attention parity: ring + Ulysses over a
4-device mesh == dense single-device attention (reference pattern:
hybrid-parallel runs vs single-process golden, SURVEY.md §4)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from paddle_tpu.ops.ring_attention import (ring_flash_attention,
                                           ulysses_attention)
from paddle_tpu.nn.functional.attention import _xla_attention


def _shard_map(fn, mesh, in_specs, out_specs):
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def _mesh(n=4):
    return Mesh(np.asarray(jax.devices()[:n]), ("sep",))


def test_ring_attention_2d_mesh_dp_sep():
    """dp×sep mesh: the production layout — carry vma must track both
    axes (regression for the shard_map varying-manual-axes check)."""
    import math
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.ops.ring_attention import ring_flash_attention
    mesh2 = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                 ("data", "sep"))
    rng = np.random.RandomState(0)
    B, S, H, D = 8, 16, 2, 8
    q = jnp.asarray(rng.randn(B, S, H, D).astype("float32"))
    k = jnp.asarray(rng.randn(B, S, H, D).astype("float32"))
    v = jnp.asarray(rng.randn(B, S, H, D).astype("float32"))
    sh = NamedSharding(mesh2, P("data", "sep"))
    qd, kd, vd = (jax.device_put(t, sh) for t in (q, k, v))

    @jax.jit
    def run(q, k, v):
        return _shard_map(
            lambda a, b, c: ring_flash_attention(a, b, c, "sep",
                                                 causal=True),
            mesh2, (P("data", "sep"),) * 3, P("data", "sep"))(q, k, v)

    out = np.asarray(run(qd, kd, vd))
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(D)
    mask = np.tril(np.ones((S, S), bool))
    s = np.where(mask, s, -1e30)
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", p, np.asarray(v))
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def _qkv(B=2, S=32, H=4, D=8, Hk=None, seed=0):
    rng = np.random.RandomState(seed)
    Hk = Hk or H
    q = rng.randn(B, S, H, D).astype("f4")
    k = rng.randn(B, S, Hk, D).astype("f4")
    v = rng.randn(B, S, Hk, D).astype("f4")
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense(causal):
    q, k, v = _qkv()
    mesh = _mesh(4)
    spec = P(None, "sep", None, None)
    fn = _shard_map(
        lambda a, b, c: ring_flash_attention(a, b, c, "sep", causal=causal),
        mesh, (spec, spec, spec), spec)
    out = fn(q, k, v)
    ref = _xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(causal):
    q, k, v = _qkv()
    mesh = _mesh(4)
    spec = P(None, "sep", None, None)
    fn = _shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, "sep", causal=causal),
        mesh, (spec, spec, spec), spec)
    out = fn(q, k, v)
    ref = _xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_gqa():
    q, k, v = _qkv(H=4, Hk=2)
    mesh = _mesh(4)
    qs = P(None, "sep", None, None)
    fn = _shard_map(
        lambda a, b, c: ring_flash_attention(a, b, c, "sep", causal=True),
        mesh, (qs, qs, qs), qs)
    out = fn(q, k, v)
    ref = _xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_grads_match_dense():
    q, k, v = _qkv(B=1, S=16, H=2, D=4)
    mesh = _mesh(4)
    spec = P(None, "sep", None, None)
    ring = _shard_map(
        lambda a, b, c: ring_flash_attention(a, b, c, "sep", causal=True),
        mesh, (spec, spec, spec), spec)

    def loss_ring(a, b, c):
        return jnp.sum(ring(a, b, c) ** 2)

    def loss_ref(a, b, c):
        return jnp.sum(_xla_attention(a, b, c, causal=True) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=2e-4, atol=2e-5)


def test_sep_attention_tensor_api():
    """Tensor-level sep_utils wrapper inside a jitted shard_map region."""
    from paddle_tpu.distributed.fleet.utils.sep_utils import sep_attention
    from paddle_tpu.framework.core import Tensor
    q, k, v = _qkv(S=16)
    mesh = _mesh(4)
    spec = P(None, "sep", None, None)

    def body(a, b, c):
        out = sep_attention(Tensor(a), Tensor(b), Tensor(c), is_causal=True)
        return out._value

    fn = _shard_map(body, mesh, (spec, spec, spec), spec)
    out = jax.jit(fn)(q, k, v)
    ref = _xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
