"""Flash attention Pallas kernel (TPU).

Reference analogue: paddle/phi/kernels/gpu/flash_attn_kernel.cu (cutlass
flash-attn submodule).  TPU-native: blockwise online-softmax attention with
q blocks resident in VMEM, k/v streamed; grid over (batch*heads, q_blocks).
Layout is paddle's (B, S, H, D).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_LANES = 128   # lse/delta carry a broadcast lane dim (TPU tiling rule)


def _fwd_blocks(S, D=64, heads=None):
    """(block_q, block_k) from the kernel registry's autotune table
    (ops/registry.py): env override > cached micro-sweep winner >
    measured static heuristic.  Blocks must DIVIDE S — the kernels size
    their loops as S // block (S=4608 with bk=1024 would silently skip
    the last 512 keys) — and the registry guarantees that."""
    from ..registry import flash_blocks
    return flash_blocks(S, D, heads)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, block_k,
                  seq_len):
    # q_ref: (block_q, d); k_ref/v_ref: (seq_len, d); o_ref: (block_q, d)
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    q = q_ref[:] * scale
    q_idx = pl.program_id(1) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)

    num_kb = seq_len // block_k

    def body(i, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.ds(i * block_k, block_k), :]
        v = v_ref[pl.ds(i * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            k_idx = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(q_idx >= k_idx, s, -1e30)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    if causal:
        # only iterate k blocks up to (and including) this q block
        last = (pl.program_id(1) * block_q + block_q + block_k - 1) // block_k
        nkb = jnp.minimum(last, num_kb)
        acc, m, l = jax.lax.fori_loop(0, nkb, body, (acc0, m0, l0))
    else:
        acc, m, l = jax.lax.fori_loop(0, num_kb, body, (acc0, m0, l0))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                              "interpret"))
def _flash_bhsd(q, k, v, causal=False, block_q=DEFAULT_BLOCK_Q,
                block_k=DEFAULT_BLOCK_K, interpret=False):
    """q,k,v: (BH, S, D) — flattened batch*heads."""
    BH, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    scale = 1.0 / math.sqrt(D)
    grid = (BH, S // block_q)
    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_k=block_k, seq_len=S)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        interpret=interpret,
    )(q, k, v)


def _flash_kernel_lse(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                      block_k, seq_len):
    """Forward that also writes log-sum-exp rows (needed by the backward)."""
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    q = q_ref[:] * scale
    q_idx = pl.program_id(1) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    num_kb = seq_len // block_k

    def body(i, carry):
        acc, m_prev, l_prev = carry
        k = k_ref[pl.ds(i * block_k, block_k), :]
        v = v_ref[pl.ds(i * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            k_idx = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(q_idx >= k_idx, s, -1e30)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                    preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    if causal:
        last = (pl.program_id(1) * block_q + block_q + block_k - 1) // block_k
        nkb = jnp.minimum(last, num_kb)
        acc, m, l = jax.lax.fori_loop(0, nkb, body, (acc0, m0, l0))
    else:
        acc, m, l = jax.lax.fori_loop(0, num_kb, body, (acc0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    o_ref[:] = (acc / l).astype(o_ref.dtype)
    # lse broadcast across a 128-lane dim (TPU block layout requirement)
    lse_ref[:] = jnp.broadcast_to(m + jnp.log(l), (block_q, _LANES))


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, *, scale, causal, block_k, seq_len):
    """dQ for one q block: dS = P ∘ (dO·Vᵀ − Δ);  dQ = scale · dS·K.

    Matmul operands stay in the input dtype (bf16 on the fast path) with
    fp32 MXU accumulation — casting them to fp32 would fall off the
    native MXU path (measured ~2x slower)."""
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    q = q_ref[:] * scale
    do = do_ref[:]
    # (block_q, LANES) lane-broadcast rows → tile across k columns
    lse = jnp.tile(lse_ref[:], (1, block_k // _LANES))
    delta = jnp.tile(delta_ref[:], (1, block_k // _LANES))
    q_idx = pl.program_id(1) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)
    num_kb = seq_len // block_k

    def body(i, dq_acc):
        k = k_ref[pl.ds(i * block_k, block_k), :]
        v = v_ref[pl.ds(i * block_k, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            k_idx = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(q_idx >= k_idx, s, -1e30)
        p = jnp.exp(s - lse)                        # softmax via saved lse
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        return dq_acc + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((block_q, d), jnp.float32)
    if causal:
        last = (pl.program_id(1) * block_q + block_q + block_k - 1) // block_k
        nkb = jnp.minimum(last, num_kb)
        dq = jax.lax.fori_loop(0, nkb, body, dq0)
    else:
        dq = jax.lax.fori_loop(0, num_kb, body, dq0)
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)


def _flash_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, *, scale, causal, block_q, seq_len):
    """dK/dV for one kv block: dV = Pᵀ·dO;  dK = scale · dSᵀ·Q."""
    block_k = k_ref.shape[0]
    d = k_ref.shape[1]
    k = k_ref[:]
    v = v_ref[:]
    k_idx = pl.program_id(1) * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_k), 1)
    num_qb = seq_len // block_q

    def body(i, carry):
        dk_acc, dv_acc = carry
        q = q_ref[pl.ds(i * block_q, block_q), :] * scale
        do = do_ref[pl.ds(i * block_q, block_q), :]
        lse = jnp.tile(lse_ref[pl.ds(i * block_q, block_q), :],
                       (1, block_k // _LANES))
        delta = jnp.tile(delta_ref[pl.ds(i * block_q, block_q), :],
                         (1, block_k // _LANES))
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            q_idx = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            s = jnp.where(q_idx >= k_idx, s, -1e30)
        p = jnp.exp(s - lse)                        # (block_q, block_k)
        pb = p.astype(do.dtype)
        dv_acc = dv_acc + jnp.dot(pb.T, do,
                                  preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        # q is pre-scaled by `scale`, so dsᵀ·q == scale · dsᵀ·Q == dK
        dk_acc = dk_acc + jnp.dot(ds.T, q,
                                  preferred_element_type=jnp.float32)
        return dk_acc, dv_acc

    dk0 = jnp.zeros((block_k, d), jnp.float32)
    dv0 = jnp.zeros((block_k, d), jnp.float32)
    if causal:
        # only q blocks at or after this kv block contribute
        first = (pl.program_id(1) * block_k) // block_q
        dk, dv = jax.lax.fori_loop(first, num_qb, body, (dk0, dv0))
    else:
        dk, dv = jax.lax.fori_loop(0, num_qb, body, (dk0, dv0))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                              "interpret"))
def _flash_bhsd_fwd_lse(q, k, v, causal=False, block_q=DEFAULT_BLOCK_Q,
                        block_k=DEFAULT_BLOCK_K, interpret=False):
    BH, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    scale = 1.0 / math.sqrt(D)
    grid = (BH, S // block_q)
    kernel = functools.partial(_flash_kernel_lse, scale=scale, causal=causal,
                               block_k=block_k, seq_len=S)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                            dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, *,
                            scale, causal, block_k, seq_len):
    """One-pass backward: every (q,k) block pair is visited ONCE,
    producing dQ and accumulating dK/dV in fp32 VMEM scratch — vs the
    two-pass kernels that recompute S/P/dP twice.

    The grid's second axis walks q blocks SEQUENTIALLY (dimension
    semantics "arbitrary"), so only one (block_q, D) q/do tile is VMEM-
    resident at a time while the dk/dv accumulators persist across grid
    steps; that keeps the VMEM footprint ~16·S·D bytes and lets the
    one-pass kernel run to S=8192 at D=64 (the old all-in-one-program
    variant held every q block at once and topped out at S=2048).
    delta = rowsum(do*o) is computed in-kernel and lse rides the slim
    (1, S) layout (no (S, LANES) HBM broadcast)."""
    qi = pl.program_id(1)
    nq = pl.num_programs(1)
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    nk = seq_len // block_k

    @pl.when(qi == 0)
    def _zero():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q = q_ref[:] * scale
    do = do_ref[:]
    o = o_ref[:]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1,
                    keepdims=True)
    lse = lse_ref[0, pl.ds(qi * block_q, block_q)][:, None]
    q_idx = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, 1), 0)

    def body(i, dq):
        k_lo = i * block_k
        k = k_ref[pl.ds(k_lo, block_k), :]
        v = v_ref[pl.ds(k_lo, block_k), :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            k_idx = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            s = jnp.where(q_idx >= k_idx, s, -1e30)
        p = jnp.exp(s - lse)
        pb = p.astype(do.dtype)
        dv_acc[pl.ds(k_lo, block_k), :] += jnp.dot(
            pb.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        dk_acc[pl.ds(k_lo, block_k), :] += jnp.dot(
            ds.T, q, preferred_element_type=jnp.float32)
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((block_q, d), jnp.float32)
    if causal:
        # only k blocks at or below this q block's diagonal contribute
        nkb = jnp.minimum((qi * block_q + block_q + block_k - 1) // block_k,
                          nk)
        dq = jax.lax.fori_loop(0, nkb, body, dq0)
    else:
        dq = jax.lax.fori_loop(0, nk, body, dq0)
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _flush():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


# fused one-pass bwd keeps k/v (+ fp32 dk/dv scratch and bf16 dk/dv
# output tiles) VMEM-resident per (batch*head): ~16 bytes/element of
# (S, D).  Past this S·D budget it no longer fits alongside the q/do
# tiles and the two-pass kernels take over.
_FUSED_BWD_MAX_SD = 8192 * 64
# head-folded kernels fully unroll the q/k block loops, and Mosaic does
# NOT reuse stack slots across unrolled bodies — past these S*D caps the
# s/p temporaries overflow the 16MB scoped VMEM (fwd S=4096 measured
# 41MB).  Measured crossover: mh bwd beats grid-fused only at S<=1024
# (6.1 vs 5.5ms at S=2048).
_MH_FWD_MAX_SD = 2048 * 64
_MH_BWD_MAX_SD = 1024 * 64


def _bwd_prep(o, do, lse):
    """delta = rowsum(dO ∘ O); lse/delta lane-broadcast for TPU tiling —
    shared by the fused and two-pass backward entries."""
    BH, S, _ = o.shape
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)
    lse_l = jnp.broadcast_to(lse[..., None], (BH, S, _LANES))
    delta_l = jnp.broadcast_to(delta[..., None], (BH, S, _LANES))
    return lse_l, delta_l


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                              "interpret"))
def _flash_bhsd_bwd_fused(q, k, v, o, lse, do, causal=False,
                          block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                          interpret=False):
    BH, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    scale = 1.0 / math.sqrt(D)
    qblk = lambda b, i: (b, i, 0)
    full = lambda b, i: (b, 0, 0)
    spec_qd = pl.BlockSpec((None, block_q, D), qblk)
    spec_sd = pl.BlockSpec((None, S, D), full)
    spec_lse = pl.BlockSpec((None, 1, S), full)
    return pl.pallas_call(
        functools.partial(_flash_bwd_fused_kernel, scale=scale,
                          causal=causal, block_k=block_k, seq_len=S),
        grid=(BH, S // block_q),
        in_specs=[spec_qd, spec_sd, spec_sd, spec_qd, spec_qd, spec_lse],
        out_specs=[spec_qd, spec_sd, spec_sd],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((S, D), jnp.float32),
                        pltpu.VMEM((S, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, o, lse[:, None, :].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                              "interpret"))
def _flash_bhsd_bwd(q, k, v, o, lse, do, causal=False,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False):
    BH, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    scale = 1.0 / math.sqrt(D)
    lse_l, delta_l = _bwd_prep(o, do, lse)
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, seq_len=S),
        grid=(BH, S // block_q),
        in_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        interpret=interpret,
    )(q, k, v, do, lse_l, delta_l)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, seq_len=S),
        grid=(BH, S // block_k),
        in_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, S, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, S, _LANES), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((None, S, _LANES), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((None, block_k, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        interpret=interpret,
    )(k, v, q, do, lse_l, delta_l)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# head-folded kernels: several (batch, head) slices per pallas program.
# At D=64/S~1k each q-block program does only ~0.1ms-equivalent of MXU
# work while per-program overhead (prologue, DMA issue, semaphores) is
# ~3-4us, so the per-(b,h)-per-q-block grid ran at <10% MXU (measured
# r3).  Folding HB heads into one program with fully static q/k loops
# amortizes that overhead ~HB*nq-fold.
# ---------------------------------------------------------------------------

def _flash_fwd_mh_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *,
                         scale, causal, block_q, block_k, seq_len,
                         with_lse):
    """lse is stored UNBROADCAST as (hb, 1, S) — the (S, LANES) lane-
    broadcast layout cost a 128x-inflated HBM write (151MB per layer at
    BH=288/S=1024, measured ~24% of bwd time); the (block_q,) lane
    vector <-> (block_q, 1) column relayout inside the kernel is far
    cheaper.  ``bias_ref`` (optional, same slim (hb, 1, S) layout) is an
    additive per-KEY bias broadcast over queries — the key-padding /
    attention-mask path (0 keep, -1e30 drop, or any additive values
    constant over heads and queries); every row must keep >=1 live key
    (the registry's mask contract, docs/kernels.md)."""
    hb = q_ref.shape[0]
    d = q_ref.shape[2]
    nq = seq_len // block_q
    nk = seq_len // block_k
    for h in range(hb):
        for qi in range(nq):
            q_lo = qi * block_q
            q = q_ref[h, pl.ds(q_lo, block_q), :] * scale
            acc = jnp.zeros((block_q, d), jnp.float32)
            m = jnp.full((block_q, 1), -1e30, jnp.float32)
            l = jnp.zeros((block_q, 1), jnp.float32)
            for ki in range(nk):
                k_lo = ki * block_k
                if causal and k_lo > q_lo + block_q - 1:
                    continue                  # fully above the diagonal
                k = k_ref[h, pl.ds(k_lo, block_k), :]
                v = v_ref[h, pl.ds(k_lo, block_k), :]
                s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
                if bias_ref is not None:
                    s = s + bias_ref[h, 0, pl.ds(k_lo, block_k)][None, :]
                if causal and k_lo + block_k - 1 > q_lo:   # straddles diag
                    q_idx = q_lo + jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, 1), 0)
                    k_idx = k_lo + jax.lax.broadcasted_iota(
                        jnp.int32, (1, block_k), 1)
                    s = jnp.where(q_idx >= k_idx, s, -1e30)
                m_cur = jnp.max(s, axis=-1, keepdims=True)
                m_new = jnp.maximum(m, m_cur)
                p = jnp.exp(s - m_new)
                alpha = jnp.exp(m - m_new)
                l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
                acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                            preferred_element_type=jnp.float32)
                m = m_new
            l = jnp.maximum(l, 1e-30)
            o_ref[h, pl.ds(q_lo, block_q), :] = \
                (acc / l).astype(o_ref.dtype)
            if with_lse:
                lse_ref[h, 0, pl.ds(q_lo, block_q)] = \
                    (m + jnp.log(l))[:, 0]


def _flash_bwd_mh_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                         bias_ref, dq_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                         *, scale, causal, block_q, block_k, seq_len):
    """One-pass backward, HB heads per program, static loops; dk/dv
    accumulate in fp32 VMEM scratch within the program (no cross-program
    state — each program owns its heads outright).  delta = rowsum(do*o)
    is computed in-kernel from the o block and lse rides the slim
    (hb, 1, S) layout — the old precomputed (S, LANES) broadcasts were
    ~300MB/layer of pure HBM overhead (measured 24% of bwd time).
    ``bias_ref`` (optional, slim layout) replays the forward's additive
    per-key bias so the recomputed P matches bitwise."""
    hb = q_ref.shape[0]
    d = q_ref.shape[2]
    nq = seq_len // block_q
    nk = seq_len // block_k
    for h in range(hb):
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        for qi in range(nq):
            q_lo = qi * block_q
            q = q_ref[h, pl.ds(q_lo, block_q), :] * scale
            do = do_ref[h, pl.ds(q_lo, block_q), :]
            o = o_ref[h, pl.ds(q_lo, block_q), :]
            delta = jnp.sum(do.astype(jnp.float32)
                            * o.astype(jnp.float32), -1, keepdims=True)
            lse = lse_ref[h, 0, pl.ds(q_lo, block_q)][:, None]
            dq = jnp.zeros((block_q, d), jnp.float32)
            for ki in range(nk):
                k_lo = ki * block_k
                if causal and k_lo > q_lo + block_q - 1:
                    continue
                k = k_ref[h, pl.ds(k_lo, block_k), :]
                v = v_ref[h, pl.ds(k_lo, block_k), :]
                s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
                if bias_ref is not None:
                    s = s + bias_ref[h, 0, pl.ds(k_lo, block_k)][None, :]
                if causal and k_lo + block_k - 1 > q_lo:
                    q_idx = q_lo + jax.lax.broadcasted_iota(
                        jnp.int32, (block_q, 1), 0)
                    k_idx = k_lo + jax.lax.broadcasted_iota(
                        jnp.int32, (1, block_k), 1)
                    s = jnp.where(q_idx >= k_idx, s, -1e30)
                p = jnp.exp(s - lse)
                pb = p.astype(do.dtype)
                dv_acc[pl.ds(k_lo, block_k), :] += jnp.dot(
                    pb.T, do, preferred_element_type=jnp.float32)
                dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
                ds = (p * (dp - delta)).astype(q.dtype)
                dk_acc[pl.ds(k_lo, block_k), :] += jnp.dot(
                    ds.T, q, preferred_element_type=jnp.float32)
                dq = dq + jnp.dot(ds, k,
                                  preferred_element_type=jnp.float32)
            dq_ref[h, pl.ds(q_lo, block_q), :] = \
                (dq * scale).astype(dq_ref.dtype)
        dk_ref[h, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[h, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _pick_hb(BH, S, D, n_bufs, budget=2 * 1024 * 1024):
    """Heads per program: largest divisor of BH whose n_bufs (S, D)
    buffers fit the VMEM budget (the 16MB scoped budget must also hold
    double-buffered block DMA + the unrolled loop's s/p stack
    temporaries, which Mosaic does NOT slot-share across unrolled
    bodies).  lse rides the slim (1, S) f32 layout."""
    per_head = n_bufs * S * D * 2 + S * 4            # bf16 bufs + slim lse
    hb = max(1, budget // max(per_head, 1))
    while hb > 1 and BH % hb:
        hb -= 1
    return min(hb, BH)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                              "with_lse", "interpret", "hb"))
def _flash_bhsd_fwd_mh(q, k, v, bias=None, causal=False,
                       block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                       with_lse=True, interpret=False, hb=None):
    """``bias``: optional (BH, 1, S) f32 additive per-key bias (the
    attention-mask path), rides the same slim layout as lse."""
    BH, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    scale = 1.0 / math.sqrt(D)
    if hb is None:  # hb is a REAL static arg so autotune sweeps retrace
        # NOTE r4: an isolated-kernel autotune said (256,512,hb=8) wins
        # at the BERT shape, but the FULL model collapsed to 11% MFU
        # with it (VMEM pressure alongside the live model buffers) —
        # kernel tables must be validated at model level
        hb = _pick_hb(BH, S, D, n_bufs=4, budget=1280 * 1024)  # hb=2 best at S=1024 (measured)
    spec = pl.BlockSpec((hb, S, D), lambda b: (b, 0, 0))
    spec_l = pl.BlockSpec((hb, 1, S), lambda b: (b, 0, 0))
    out_specs = [spec]
    out_shape = [jax.ShapeDtypeStruct((BH, S, D), q.dtype)]
    if with_lse:
        out_specs.append(spec_l)
        out_shape.append(jax.ShapeDtypeStruct((BH, 1, S), jnp.float32))
    kernel = functools.partial(_flash_fwd_mh_kernel, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, seq_len=S, with_lse=with_lse)
    kern = kernel
    with_bias = bias is not None
    if with_bias:
        in_specs = [spec, spec, spec, spec_l]
        ins = (q, k, v, bias.astype(jnp.float32))
        if not with_lse:
            kern = lambda qr, kr, vr, br, orf: kernel(qr, kr, vr, br, orf,
                                                      None)
    else:
        in_specs = [spec, spec, spec]
        ins = (q, k, v)
        if with_lse:
            kern = lambda qr, kr, vr, orf, lr: kernel(qr, kr, vr, None,
                                                      orf, lr)
        else:
            kern = lambda qr, kr, vr, orf: kernel(qr, kr, vr, None, orf,
                                                  None)
    out = pl.pallas_call(
        kern,
        grid=(BH // hb,),
        in_specs=in_specs,
        out_specs=out_specs if with_lse else out_specs[0],
        out_shape=out_shape if with_lse else out_shape[0],
        interpret=interpret,
    )(*ins)
    if with_lse:
        return out[0], out[1][:, 0, :]     # lse -> (BH, S)
    return out, None


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                              "interpret", "hb"))
def _flash_bhsd_bwd_mh(q, k, v, o, lse, do, bias=None, causal=False,
                       block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                       interpret=False, hb=None):
    BH, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    scale = 1.0 / math.sqrt(D)
    if hb is None:  # static arg: see fwd
        hb = _pick_hb(BH, S, D, n_bufs=7, budget=1024 * 1024)  # bwd: hb=1 measured flat-optimal
    spec = pl.BlockSpec((hb, S, D), lambda b: (b, 0, 0))
    spec_l = pl.BlockSpec((hb, 1, S), lambda b: (b, 0, 0))
    kernel = functools.partial(_flash_bwd_mh_kernel, scale=scale,
                               causal=causal, block_q=block_q,
                               block_k=block_k, seq_len=S)
    if bias is not None:
        in_specs = [spec, spec, spec, spec, spec, spec_l, spec_l]
        ins = (q, k, v, do, o, lse[:, None, :].astype(jnp.float32),
               bias.astype(jnp.float32))
        kern = kernel
    else:
        in_specs = [spec, spec, spec, spec, spec, spec_l]
        ins = (q, k, v, do, o, lse[:, None, :].astype(jnp.float32))
        kern = lambda qr, kr, vr, dor, orf, lr, dqr, dkr, dvr, dka, dva: \
            kernel(qr, kr, vr, dor, orf, lr, None, dqr, dkr, dvr, dka, dva)
    return pl.pallas_call(
        kern,
        grid=(BH // hb,),
        in_specs=in_specs,
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, S, D), k.dtype),
            jax.ShapeDtypeStruct((BH, S, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((S, D), jnp.float32),
                        pltpu.VMEM((S, D), jnp.float32)],
        interpret=interpret,
    )(*ins)


def _to_bhsd(x):
    B, S, H, D = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(B * H, S, D)


def _from_bhsd(x, B, H):
    BH, S, D = x.shape
    return jnp.swapaxes(x.reshape(B, H, S, D), 1, 2)


def _bias_bh(bias, B, H, S):
    """(B, S) additive key bias -> the kernels' slim (BH, 1, S) layout."""
    if bias is None:
        return None
    bb = jnp.broadcast_to(bias.astype(jnp.float32)[:, None, :], (B, H, S))
    return bb.reshape(B * H, 1, S)


def flash_attention_fwd(q, k, v, bias=None, causal=False, interpret=False):
    """(B, S, H, D) in/out — paddle layout; supports MQA/GQA (H_kv divides
    H) by repeating kv heads.  No-grad path: uses the LSE-less kernel so
    inference pays nothing for backward residuals.  ``bias``: optional
    (B, S) additive per-key mask — head-folded kernels only (the
    registry routes masked shapes past the VMEM cap to the XLA path)."""
    B, S, H, D = q.shape
    Hk = k.shape[2]
    if Hk != H:
        rep = H // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    bq, bk = _fwd_blocks(S, D, B * H)
    if bias is not None and S * D > _MH_FWD_MAX_SD:
        raise ValueError(
            f"flash key-bias path needs S*D <= {_MH_FWD_MAX_SD} "
            f"(got S={S}, D={D}); the dispatch layer routes larger "
            "masked shapes to the XLA attention")
    if S * D <= _MH_FWD_MAX_SD:
        of, _ = _flash_bhsd_fwd_mh(_to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
                                   bias=_bias_bh(bias, B, H, S),
                                   causal=causal, block_q=bq, block_k=bk,
                                   with_lse=False, interpret=interpret)
    else:
        of = _flash_bhsd(_to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
                         causal=causal, block_q=bq, block_k=bk,
                         interpret=interpret)
    return _from_bhsd(of, B, H)


def flash_attention_fwd_lse(q, k, v, bias=None, causal=False,
                            interpret=False):
    """Forward returning (o [B,S,H,D], lse [B*H,S]) for the flash bwd."""
    B, S, H, D = q.shape
    Hk = k.shape[2]
    if Hk != H:
        rep = H // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    bq, bk = _fwd_blocks(S, D, B * H)
    if bias is not None and S * D > _MH_FWD_MAX_SD:
        raise ValueError(
            f"flash key-bias path needs S*D <= {_MH_FWD_MAX_SD} "
            f"(got S={S}, D={D}); the dispatch layer routes larger "
            "masked shapes to the XLA attention")
    if S * D <= _MH_FWD_MAX_SD:
        of, lse = _flash_bhsd_fwd_mh(_to_bhsd(q), _to_bhsd(k), _to_bhsd(v),
                                     bias=_bias_bh(bias, B, H, S),
                                     causal=causal, block_q=bq, block_k=bk,
                                     with_lse=True, interpret=interpret)
        # mh path already returns lse as (BH, S)
        return _from_bhsd(of, B, H), lse
    of, lse = _flash_bhsd_fwd_lse(_to_bhsd(q), _to_bhsd(k),
                                  _to_bhsd(v), causal=causal,
                                  block_q=bq, block_k=bk,
                                  interpret=interpret)
    return _from_bhsd(of, B, H), lse[..., 0]


def flash_attention_bwd(q, k, v, o, lse, do, bias=None, causal=False,
                        interpret=False):
    """Pallas flash backward — returns (dq, dk, dv) in (B, S, H, D);
    GQA kv grads are summed back over the repeated query-head groups.
    ``bias`` must replay the forward's additive per-key mask (head-
    folded kernel only, same cap contract as the forward)."""
    B, S, H, D = q.shape
    Hk = k.shape[2]
    if Hk != H:
        rep = H // Hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if bias is not None and S * D > _MH_BWD_MAX_SD:
        raise ValueError(
            f"flash key-bias backward needs S*D <= {_MH_BWD_MAX_SD} "
            f"(got S={S}, D={D}); the dispatch layer routes larger "
            "masked shapes to the XLA attention")
    # ladder: head-folded one-pass (smallest grids, whole (b,h) resident)
    # -> q-grid one-pass (cross-step dk/dv scratch) -> two-pass
    if S * D <= _MH_BWD_MAX_SD:
        dqf, dkf, dvf = _flash_bhsd_bwd_mh(
            _to_bhsd(q), _to_bhsd(k), _to_bhsd(v), _to_bhsd(o), lse,
            _to_bhsd(do), bias=_bias_bh(bias, B, H, S), causal=causal,
            interpret=interpret)
    else:
        bwd = _flash_bhsd_bwd_fused if S * D <= _FUSED_BWD_MAX_SD \
            else _flash_bhsd_bwd
        dqf, dkf, dvf = bwd(
            _to_bhsd(q), _to_bhsd(k), _to_bhsd(v), _to_bhsd(o), lse,
            _to_bhsd(do), causal=causal, interpret=interpret)
    dq = _from_bhsd(dqf, B, H)
    dk = _from_bhsd(dkf, B, H)
    dv = _from_bhsd(dvf, B, H)
    if Hk != H:
        rep = H // Hk
        dk = dk.reshape(B, S, Hk, rep, D).sum(3)
        dv = dv.reshape(B, S, Hk, rep, D).sum(3)
    return dq, dk, dv
