"""Pallas flash attention fwd+bwd kernels — interpret-mode parity on CPU.

Reference analogue: test/legacy_test/test_flash_attention.py (numerics vs
dense attention).  The same kernels are validated on the real v5e chip
(chip_smoke.py); interpret=True runs them here so CI exercises every code
path: the in-place lane tiles (two heads a tile at D=64, one at D=128),
the transposing path for shapes the tiles cannot address, and the three
rungs of the kernel ladder.
"""
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nn.functional import attention as A
from paddle_tpu.ops import registry as kreg
from paddle_tpu.ops.pallas import flash_attention as fa

# (H, H_kv, D): pair tiles (one and two tiles), one head a tile, GQA,
# and two shapes the lane tiles cannot address (they transpose)
TILED = [(2, 2, 64), (4, 4, 64), (2, 2, 128), (4, 2, 64)]
TRANSPOSED = [(1, 1, 64), (3, 3, 32)]


def _qkvg(H, Hk, D, S=256, B=1, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda h: jnp.asarray(rng.randn(B, S, h, D).astype("float32"))
    return mk(H), mk(Hk), mk(Hk), mk(H)


def _key_bias(B, S, seed=5):
    """Additive per-key bias: a fifth of the keys dropped, key 0 kept."""
    rng = np.random.RandomState(seed)
    drop = rng.rand(B, S) < 0.2
    drop[:, 0] = False
    return jnp.asarray(np.where(drop, -1e30, 0.0).astype("float32"))


def _dense(q, k, v, causal, bias=None):
    mask = None if bias is None else bias[:, None, None, :]
    return A._xla_attention(q, k, v, mask=mask, causal=causal)


def _dense_lse(q, k, causal, bias=None):
    H, S = q.shape[2], q.shape[1]
    k = jnp.repeat(k, H // k.shape[2], axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -1e30)
    if bias is not None:
        s = s + bias[:, None, None, :]
    return jax.scipy.special.logsumexp(s, axis=-1)


def _assert_grads(got, want):
    for name, g, w in zip(("dq", "dk", "dv"), got, want):
        assert g.shape == w.shape, name
        rel = float(jnp.abs(g - w).max()) / (float(jnp.abs(w).max()) + 1e-9)
        assert rel < 5e-3, (name, rel)


def _dense_grads(q, k, v, g, causal, bias=None):
    return jax.vjp(lambda a, b, c: _dense(a, b, c, causal, bias),
                   q, k, v)[1](g)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("H,Hk,D", TILED + TRANSPOSED)
def test_flash_fwd_bwd_parity(H, Hk, D, causal):
    """Forward, lse and all three gradients of the (B, S, H, D) entries
    (head-folded rung at this size) against the XLA attention."""
    q, k, v, g = _qkvg(H, Hk, D)
    o, lse = fa.flash_attention_fwd_lse(q, k, v, causal=causal,
                                        interpret=True)
    np.testing.assert_allclose(o, _dense(q, k, v, causal), atol=2e-3,
                               rtol=2e-3)
    np.testing.assert_allclose(lse, _dense_lse(q, k, causal), atol=1e-3,
                               rtol=1e-3)
    o_nolse = fa.flash_attention_fwd(q, k, v, causal=causal, interpret=True)
    np.testing.assert_array_equal(o_nolse, o)
    got = fa.flash_attention_bwd(q, k, v, o, lse, g, causal=causal,
                                 interpret=True)
    _assert_grads(got, _dense_grads(q, k, v, g, causal))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("H,Hk,D", [(2, 2, 64), (4, 4, 64), (2, 2, 128),
                                    (1, 1, 64)])
def test_key_bias_parity(H, Hk, D, causal):
    """The additive per-key bias (one (1, S) row a batch row, shared by
    the heads of a tile) through the head-folded forward and backward."""
    B = 2
    q, k, v, g = _qkvg(H, Hk, D, B=B)
    bias = _key_bias(B, q.shape[1])
    o, lse = fa.flash_attention_fwd_lse(q, k, v, bias, causal=causal,
                                        interpret=True)
    np.testing.assert_allclose(o, _dense(q, k, v, causal, bias), atol=2e-3,
                               rtol=2e-3)
    np.testing.assert_allclose(lse, _dense_lse(q, k, causal, bias),
                               atol=1e-3, rtol=1e-3)
    got = fa.flash_attention_bwd(q, k, v, o, lse, g, bias, causal=causal,
                                 interpret=True)
    _assert_grads(got, _dense_grads(q, k, v, g, causal, bias))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("H,D", [(2, 64), (4, 64), (2, 128), (1, 64)])
def test_q_grid_forward_parity(H, D, causal):
    """The q-grid forward (the rung past the head-folded cap), with small
    blocks forcing nq, nk > 1: o and lse, with and without the lse."""
    q, k, v, _ = _qkvg(H, H, D, seed=3)
    B, S = q.shape[:2]
    pack, unpack = fa._packing(B, H, D)
    kw = dict(head_dim=D, causal=causal, block_q=128, block_k=128,
              interpret=True)
    o, lse = fa._flash_bhsd_fwd(pack(q), pack(k), pack(v), **kw)
    np.testing.assert_allclose(unpack(o), _dense(q, k, v, causal),
                               atol=2e-3, rtol=2e-3)
    np.testing.assert_allclose(lse.reshape(B, H, S),
                               _dense_lse(q, k, causal), atol=1e-3,
                               rtol=1e-3)
    o2, none = fa._flash_bhsd_fwd(pack(q), pack(k), pack(v), with_lse=False,
                                  **kw)
    assert none is None
    np.testing.assert_array_equal(o2, o)


def _bwd_of(impl, q, k, v, g, causal, bias=None, **blocks):
    B, _, H, D = q.shape
    pack, unpack = fa._packing(B, H, D)
    o, lse = fa.flash_attention_fwd_lse(q, k, v, bias, causal=causal,
                                        interpret=True)
    extra = {} if bias is None else {"bias": bias}
    got = impl(pack(q), pack(k), pack(v), pack(o), lse, pack(g), head_dim=D,
               causal=causal, interpret=True, **extra, **blocks)
    return [unpack(x) for x in got]


_RUNGS = pytest.mark.parametrize(
    "impl", [fa._flash_bhsd_bwd_mh, fa._flash_bhsd_bwd_fused,
             fa._flash_bhsd_bwd], ids=["head_folded", "fused", "two_pass"])


@_RUNGS
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("H,D", [(2, 64), (4, 64), (2, 128), (1, 64)])
def test_bwd_impls_multiblock_parity(impl, causal, H, D):
    """Every backward rung, with small blocks forcing nq,nk>1 (exercises
    the causal block-skip and diagonal masking, and the fused and
    two-pass kernels that the S*D routing otherwise hides from CI), in
    place and transposed, must match the dense vjp."""
    q, k, v, g = _qkvg(H, H, D, seed=2)
    got = _bwd_of(impl, q, k, v, g, causal, block_q=128, block_k=128)
    _assert_grads(got, _dense_grads(q, k, v, g, causal))


# -- the backward's block visit ---------------------------------------------

@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_q,block_k,S", [
    (256, 256, 512), (256, 256, 1024), (256, 128, 512), (256, 128, 1024),
    (128, 256, 512), (128, 256, 1024), (512, 512, 2048)])
def test_visit_schedule(block_q, block_k, S, causal):
    """The schedule alone, no kernel: over every (q block, k block) pair
    the visited sub-blocks cover what may be attended exactly once, visit
    nothing wholly above the diagonal, and mask only what it crosses."""
    live = np.tril(np.ones((S, S), bool)) if causal else np.ones((S, S), bool)
    seen = np.zeros((S, S), int)
    for q_lo in range(0, S, block_q):
        for k_lo in range(0, S, block_k):
            for qo, rows, ko, cols, masked in fa._pair_visits(
                    k_lo - q_lo, block_q, block_k, causal):
                assert rows % 128 == 0 and cols % 128 == 0
                assert qo + rows <= block_q and ko + cols <= block_k
                at = (slice(q_lo + qo, q_lo + qo + rows),
                      slice(k_lo + ko, k_lo + ko + cols))
                seen[at] += 1
                assert masked == (not live[at].all())
                sq, sk = fa._sub(block_q), fa._sub(block_k)
                for r in range(at[0].start, at[0].stop, sq):
                    for c in range(at[1].start, at[1].stop, sk):
                        assert live[r:r + sq, c:c + sk].any(), \
                            "visits a sub-block of exact zeros"
    assert (seen[live] == 1).all() and seen.max() == 1
    if (block_q, block_k, S, causal) == (512, 512, 2048, True):
        # the fit cell: 6 whole pairs and 4 diagonal ones at 3 quarters
        assert seen.sum() == 9 * 512 * 512


@_RUNGS
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("H,D", [(2, 64), (2, 128), (1, 64)])
def test_bwd_sub_block_parity(impl, causal, H, D):
    """Every backward rung where the diagonal blocks are visited by
    halves (S=512, blocks of 256, sub-blocks of 128): pair tiles, one
    head a tile, and the transposed single head."""
    q, k, v, g = _qkvg(H, H, D, S=512, seed=7)
    got = _bwd_of(impl, q, k, v, g, causal, block_q=256, block_k=256)
    _assert_grads(got, _dense_grads(q, k, v, g, causal))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("H,D", [(2, 64), (2, 128), (1, 64)])
def test_bwd_sub_block_key_bias_parity(H, D, causal):
    """The key bias (a column of the transposed scores) under the same
    sub-blocking, head-folded rung."""
    B = 2
    q, k, v, g = _qkvg(H, H, D, S=512, B=B, seed=8)
    bias = _key_bias(B, 512)
    got = _bwd_of(fa._flash_bhsd_bwd_mh, q, k, v, g, causal, bias,
                  block_q=256, block_k=256)
    _assert_grads(got, _dense_grads(q, k, v, g, causal, bias))


@_RUNGS
@pytest.mark.parametrize("block_q,block_k", [(256, 128), (128, 256)])
def test_bwd_uneven_blocks_parity(impl, block_q, block_k):
    """block_q != block_k, causal: the q-grid kernels keep the static
    schedule where the diagonal's place in a block is fixed and mask the
    one block whole where it moves with the grid."""
    q, k, v, g = _qkvg(2, 2, 64, S=512, seed=9)
    got = _bwd_of(impl, q, k, v, g, True, block_q=block_q, block_k=block_k)
    _assert_grads(got, _dense_grads(q, k, v, g, True))


@pytest.mark.parametrize("S,block", [(256, 256), (384, 384), (512, 512),
                                     (768, 256), (1024, 512), (1280, 256),
                                     (640, 128), (2048, 512), (200, 200)])
def test_bwd_block_divides_S(S, block):
    assert fa._bwd_blocks(S) == block and S % block == 0


def test_bwd_at_a_length_512_does_not_divide():
    """S=768 (any length that pads to an odd multiple of 256): the
    (B, S, H, D) entry's backward with a block of 512 left dq rows
    unwritten (NaN) and dropped a third of the keys."""
    q, k, v, g = _qkvg(2, 2, 64, S=768, seed=10)
    o, lse = fa.flash_attention_fwd_lse(q, k, v, causal=True, interpret=True)
    got = fa.flash_attention_bwd(q, k, v, o, lse, g, causal=True,
                                 interpret=True)
    _assert_grads(got, _dense_grads(q, k, v, g, True))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("H,D", [(2, 64), (1, 64)])
def test_padded_sequence_parity(H, D, causal):
    """S=300 pads to the 256 granule (non-causal: the pad keys ride the
    key bias) through the custom-vjp core, in place and transposed."""
    q, k, v, g = _qkvg(H, H, D, S=300, seed=4)
    flash = A._Flash(True, True)

    def loss(fn):
        return lambda a, b, c: jnp.sum(fn(a, b, c) * g)
    got = jax.grad(loss(lambda a, b, c: A._attention_core(
        a, b, c, causal, None, flash)), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda a, b, c: _dense(a, b, c, causal)),
                    argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(
        A._attention_core(q, k, v, causal, None, flash),
        _dense(q, k, v, causal), atol=2e-3, rtol=2e-3)
    _assert_grads(got, want)


def test_transposed_path_matches_in_place():
    """The same head through both layouts: H=1, D=64 must transpose, and
    gives the numbers head 0 of an in-place pair gives, to the rounding
    of a contraction over 128 lanes (half of them zeros) against one
    over 64: the pair's second head cannot leak into the first."""
    same = lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-4,
                                                   atol=1e-5)
    q, k, v, g = _qkvg(2, 2, 64, seed=6)
    one = lambda x: x[:, :, :1]
    o2, lse2 = fa.flash_attention_fwd_lse(q, k, v, causal=True,
                                          interpret=True)
    o1, lse1 = fa.flash_attention_fwd_lse(one(q), one(k), one(v),
                                          causal=True, interpret=True)
    same(o1, one(o2))
    same(lse1, lse2[:, :1])
    got2 = fa.flash_attention_bwd(q, k, v, o2, lse2, g, causal=True,
                                  interpret=True)
    got1 = fa.flash_attention_bwd(one(q), one(k), one(v), o1, lse1, one(g),
                                  causal=True, interpret=True)
    for a, b in zip(got1, got2):
        same(a, one(b))


# -- the mechanism: no head transpose around the in-place kernels ------------

def _outer_primitives(fn, *args):
    """Primitive names of ``fn``'s jaxpr outside any pallas_call (jitted
    wrappers opened up)."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            names.append(eqn.primitive.name)
            if eqn.primitive.name == "pallas_call":
                continue
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


@pytest.mark.parametrize("H,transposes", [(4, False), (1, True)],
                         ids=["in_place", "transposed"])
def test_no_transpose_outside_the_kernels(H, transposes):
    B, S, D = 1, 1024, 64
    x = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16)
    lse = jax.ShapeDtypeStruct((B, H, S), jnp.float32)
    fwd = _outer_primitives(
        lambda q, k, v: fa.flash_attention_fwd_lse(q, k, v, causal=True),
        x, x, x)
    bwd = _outer_primitives(
        lambda q, k, v, o, l, g: fa.flash_attention_bwd(
            q, k, v, o, l, g, causal=True), x, x, x, x, lse, x)
    for names in (fwd, bwd):
        assert "pallas_call" in names
        assert ("transpose" in names) == transposes, names


def test_selects_counter_tells_the_layouts_apart(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
    reg = paddle.observability.get_registry()

    def count(impl):
        m = reg.get("pt_kernel_selects_total")
        return m.value(kernel="attention", impl=impl) if m else 0

    def select(heads):
        before = count("pallas"), count("pallas_transposed")
        sel = A._select_flash(1024, 1024, 64, True, has_mask=False,
                              mask_is_keybias=False, scale=None,
                              heads=heads)
        assert sel.use
        return (count("pallas") - before[0],
                count("pallas_transposed") - before[1])

    assert select((4, 4)) == (1, 0)
    assert select((1, 1)) == (0, 1)
    assert select((3, 3)) == (0, 1)          # odd head count at D=64
    # per shard: 4 heads over a tensor-parallel axis of 2 are pairs, 2
    # heads over it are single heads and transpose
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    with kreg.partitioned(mesh, ("data",), "model"):
        assert select((4, 4)) == (1, 0)
        assert select((2, 2)) == (0, 1)


@pytest.mark.parametrize("H,D,tiled", [
    (16, 64, True), (2, 64, True), (16, 128, True), (3, 128, True),
    (4, 32, True), (1, 256, True), (1, 64, False), (3, 64, False),
    (12, 96, False), (8, 80, False), (4, 8, False), (2, 32, False)])
def test_lane_tile_rule(H, D, tiled):
    assert fa.lane_tiled(H, D) == tiled
