"""Varlen ("unpadded") flash attention Pallas kernels (TPU).

Reference analogue: paddle.nn.functional.flash_attention.flash_attn_unpadded
(cutlass flash_attn varlen_fwd/varlen_bwd kernels; SURVEY §5.7).  The
reference packs B variable-length sequences into one (total, H, D) tensor
with ``cu_seqlens`` prefix sums and launches per-sequence tiles.

TPU-native design: packed tokens stay one contiguous (H, total, D) array
and sequence isolation is a SEGMENT-ID mask inside the standard online-
softmax flash kernel — each token carries its sequence index (computed
from cu_seqlens with searchsorted), and a (q, k) pair contributes only
when segments match (AND the causal predicate, which — because segments
are contiguous runs — is just the global position compare).  This is the
shard_map-friendly TPU formulation (same trick as jax splash-attention's
segment ids): no ragged shapes, no per-sequence kernel launches, MXU-
sized blocks straddling sequence boundaries are handled by masking.

VMEM envelope: packs up to total*head_dim ~8192*64 run the one-pass
backward (k/v + fp32 dk/dv scratch resident per head — fastest, causal
early-exit in the loop).  Larger packs take the STREAMING tier: 3-axis
grids where k/v (and seg/lse/delta) arrive as per-block pipelined DMAs
(Pallas double-buffers grid-sliced inputs from HBM) and the online-
softmax / dk/dv accumulators live in VMEM scratch across the innermost
grid axis.  Nothing is full-T resident, so there is no hard total cap
(32k+ token packs validated on-chip).  The total is padded to the q
block size with segment id -1 (never matches a real segment).

Cross-attention packs with total_q != total_k are padded to a common
total by the wrapper (padding rides segment -1, contributing nothing).
A q token whose segment has zero live keys gets an exact 0 output (and
0 grads) instead of the exp(0)=1 softmax degeneracy.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



_VARLEN_ONEPASS_MAX_TD = 8192 * 64    # resident tier: k/v (+f32 scratch)
_BLOCK = 512


def _varlen_fwd_kernel(segq_ref, segk_ref, q_ref, k_ref, v_ref, o_ref,
                       lse_ref, *, scale, causal, block_k, total):
    """grid = (H, total // block_q); segq/segk: (8, total) int32 (row 0
    is the data; 8 rows for int32 tile alignment).  Separate q/k segment
    arrays support cross-attention packs where cu_seqlens_q and
    cu_seqlens_k slice the same total differently."""
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    q_lo = pl.program_id(1) * block_q
    q = q_ref[:] * scale
    seg_q = segq_ref[0, pl.ds(q_lo, block_q)][:, None]       # (bq, 1)
    q_idx = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)
    nk = total // block_k

    def body(i, carry):
        acc, m_prev, l_prev = carry
        k_lo = i * block_k
        k = k_ref[pl.ds(k_lo, block_k), :]
        v = v_ref[pl.ds(k_lo, block_k), :]
        seg_k = segk_ref[0, pl.ds(k_lo, block_k)][None, :]    # (1, bk)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        live = seg_q == seg_k
        if causal:
            k_idx = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            live = live & (q_idx >= k_idx)
        s = jnp.where(live, s, -1e30)
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
        # segments are contiguous: keys past this q block's last row are
        # either future positions (causal-masked) or later segments
        last = (q_lo + block_q + block_k - 1) // block_k
        nkb = jnp.minimum(last, nk)
        acc, m, l = jax.lax.fori_loop(0, nkb, body, (acc0, m0, l0))
    else:
        acc, m, l = jax.lax.fori_loop(0, nk, body, (acc0, m0, l0))
    l = jnp.maximum(l, 1e-30)
    # a q row with ZERO live keys (empty/padding segment, or a non-self
    # pack mismatch) never raises m above -1e30; exp(s - m) = 1 there
    # would emit the mean of masked v rows — emit exact zeros instead
    dead = m <= -1e29
    o_ref[:] = jnp.where(dead, 0.0, acc / l).astype(o_ref.dtype)
    lse_ref[0, pl.ds(q_lo, block_q)] = (m + jnp.log(l))[:, 0]


def _varlen_bwd_kernel(segq_ref, segk_ref, q_ref, k_ref, v_ref, do_ref,
                       o_ref, lse_ref, dq_ref, dk_ref, dv_ref, dk_acc,
                       dv_acc, *, scale, causal, block_k, total):
    """One-pass backward, sequential q-block grid axis with persistent
    dk/dv scratch (same scheme as _flash_bwd_fused_kernel) + seg mask.
    delta computed in-kernel; lse rides the slim (1, T) layout."""
    qi = pl.program_id(1)
    nq = pl.num_programs(1)
    block_q = q_ref.shape[0]
    d = q_ref.shape[1]
    nk = total // block_k
    q_lo = qi * block_q

    @pl.when(qi == 0)
    def _zero():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    q = q_ref[:] * scale
    do = do_ref[:]
    o = o_ref[:]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1,
                    keepdims=True)
    lse = lse_ref[0, pl.ds(q_lo, block_q)][:, None]
    seg_q = segq_ref[0, pl.ds(q_lo, block_q)][:, None]
    q_idx = q_lo + jax.lax.broadcasted_iota(jnp.int32, (block_q, 1), 0)

    def body(i, dq):
        k_lo = i * block_k
        k = k_ref[pl.ds(k_lo, block_k), :]
        v = v_ref[pl.ds(k_lo, block_k), :]
        seg_k = segk_ref[0, pl.ds(k_lo, block_k)][None, :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        live = seg_q == seg_k
        if causal:
            k_idx = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            live = live & (q_idx >= k_idx)
        # explicit live mask (not just exp of -1e30): a dead q row's lse
        # is ~-1e30 too, making exp(s - lse) = 1/T per masked lane
        p = jnp.where(live, jnp.exp(s - lse), 0.0)
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
        nkb = jnp.minimum((q_lo + block_q + block_k - 1) // block_k, nk)
        dq = jax.lax.fori_loop(0, nkb, body, dq0)
    else:
        dq = jax.lax.fori_loop(0, nk, body, dq0)
    dq_ref[:] = (dq * scale).astype(dq_ref.dtype)

    @pl.when(qi == nq - 1)
    def _flush():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _seg2d(seg):
    """(T,) int32 -> (8, T) for int32 tile alignment."""
    return jnp.broadcast_to(seg[None, :], (8, seg.shape[0]))


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                              "interpret"))
def _varlen_fwd(q, k, v, seg_q, seg_k, causal, block_q=_BLOCK,
                block_k=_BLOCK, interpret=False):
    """q/k/v: (H, T, D) packed+padded; seg_q/seg_k: (T,) int32, -1 =
    padding."""
    H, T, D = q.shape
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    scale = 1.0 / math.sqrt(D)
    spec_q = pl.BlockSpec((None, block_q, D), lambda h, i: (h, i, 0))
    spec_full = pl.BlockSpec((None, T, D), lambda h, i: (h, 0, 0))
    spec_seg = pl.BlockSpec((8, T), lambda h, i: (0, 0))
    out, lse = pl.pallas_call(
        functools.partial(_varlen_fwd_kernel, scale=scale, causal=causal,
                          block_k=block_k, total=T),
        grid=(H, T // block_q),
        in_specs=[
            spec_seg, spec_seg,
            spec_q, spec_full, spec_full,
        ],
        out_specs=[
            spec_q,
            pl.BlockSpec((None, 1, T), lambda h, i: (h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((H, T, D), q.dtype),
            jax.ShapeDtypeStruct((H, 1, T), jnp.float32),
        ],
        interpret=interpret,
    )(_seg2d(seg_q), _seg2d(seg_k), q, k, v)
    return out, lse[:, 0, :]


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                              "interpret"))
def _varlen_bwd(q, k, v, o, lse, do, seg_q, seg_k, causal, block_q=_BLOCK,
                block_k=_BLOCK, interpret=False):
    H, T, D = q.shape
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    scale = 1.0 / math.sqrt(D)
    spec_q = pl.BlockSpec((None, block_q, D), lambda h, i: (h, i, 0))
    spec_full = pl.BlockSpec((None, T, D), lambda h, i: (h, 0, 0))
    spec_lse = pl.BlockSpec((None, 1, T), lambda h, i: (h, 0, 0))
    return pl.pallas_call(
        functools.partial(_varlen_bwd_kernel, scale=scale, causal=causal,
                          block_k=block_k, total=T),
        grid=(H, T // block_q),
        in_specs=[
            pl.BlockSpec((8, T), lambda h, i: (0, 0)),
            pl.BlockSpec((8, T), lambda h, i: (0, 0)),
            spec_q, spec_full, spec_full, spec_q, spec_q, spec_lse,
        ],
        out_specs=[spec_q, spec_full, spec_full],
        out_shape=[
            jax.ShapeDtypeStruct((H, T, D), q.dtype),
            jax.ShapeDtypeStruct((H, T, D), k.dtype),
            jax.ShapeDtypeStruct((H, T, D), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((T, D), jnp.float32),
                        pltpu.VMEM((T, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(_seg2d(seg_q), _seg2d(seg_k), q, k, v, do, o,
      lse[:, None, :].astype(jnp.float32))


def _varlen_fwd_stream_kernel(segq_ref, segk_ref, q_ref, k_ref, v_ref,
                              o_ref, lse_ref, acc, m_scr, l_scr, *,
                              scale, causal, block_q, block_k):
    """Streaming forward: grid (H, nq, nk) — every input arrives as a
    pipelined block; acc/m/l persist in VMEM scratch across the nk axis
    (the m/l scratch carries a broadcast 128-lane dim, TPU tile rule)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    q_lo = qi * block_q
    k_lo = ki * block_k

    @pl.when(ki == 0)
    def _init():
        acc[:] = jnp.zeros_like(acc)
        m_scr[:] = jnp.full_like(m_scr, -1e30)
        l_scr[:] = jnp.zeros_like(l_scr)

    # blocks fully above the causal diagonal: skip compute (DMA already
    # paid — the streaming tier trades that for unbounded pack size)
    run = (k_lo <= q_lo + block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0] * scale
        k = k_ref[0]
        v = v_ref[0]
        seg_q = segq_ref[0, :][:, None]
        seg_k = segk_ref[0, :][None, :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        live = seg_q == seg_k
        if causal:
            q_idx = q_lo + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            k_idx = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            live = live & (q_idx >= k_idx)
        s = jnp.where(live, s, -1e30)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc[:] = acc[:] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _epilogue():
        m = m_scr[:, :1]
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        dead = m <= -1e29          # zero live keys: exact 0 output
        o_ref[0] = jnp.where(dead, 0.0, acc[:] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m + jnp.log(l))[:, 0]


def _varlen_dq_stream_kernel(segq_ref, segk_ref, q_ref, k_ref, v_ref,
                             do_ref, lse_ref, delta_ref, dq_ref, dq_acc,
                             *, scale, causal, block_q, block_k):
    """Streaming dQ: grid (H, nq, nk), dq accumulates in scratch."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    q_lo = qi * block_q
    k_lo = ki * block_k

    @pl.when(ki == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run = (k_lo <= q_lo + block_q - 1) if causal else True

    @pl.when(run)
    def _step():
        q = q_ref[0] * scale
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        seg_q = segq_ref[0, :][:, None]
        seg_k = segk_ref[0, :][None, :]
        k = k_ref[0]
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        live = seg_q == seg_k
        if causal:
            q_idx = q_lo + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            k_idx = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            live = live & (q_idx >= k_idx)
        p = jnp.where(live, jnp.exp(s - lse), 0.0)   # dead-row safe
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(k.dtype)
        dq_acc[:] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _flush():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _varlen_dkv_stream_kernel(segq_ref, segk_ref, k_ref, v_ref, q_ref,
                              do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                              dk_acc, dv_acc, *, scale, causal, block_q,
                              block_k):
    """Streaming dK/dV: grid (H, nk, nq) — each (h, k-block) program
    pair accumulates over streamed q blocks in scratch."""
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)
    k_lo = ki * block_k
    q_lo = qi * block_q

    @pl.when(qi == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    run = (q_lo + block_q - 1 >= k_lo) if causal else True

    @pl.when(run)
    def _step():
        k = k_ref[0]
        v = v_ref[0]
        q = q_ref[0] * scale
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        seg_q = segq_ref[0, :][:, None]
        seg_k = segk_ref[0, :][None, :]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        live = seg_q == seg_k
        if causal:
            q_idx = q_lo + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, 1), 0)
            k_idx = k_lo + jax.lax.broadcasted_iota(
                jnp.int32, (1, block_k), 1)
            live = live & (q_idx >= k_idx)
        p = jnp.where(live, jnp.exp(s - lse), 0.0)   # dead-row safe
        pb = p.astype(do.dtype)
        dv_acc[:] += jnp.dot(pb.T, do,
                             preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = (p * (dp - delta)).astype(q.dtype)
        # q is pre-scaled, so dsᵀ·q == scale · dsᵀ·Q == dK
        dk_acc[:] += jnp.dot(ds.T, q,
                             preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _flush():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _stream_specs(block_q, block_k, D):
    """Block specs shared by the streaming kernels, grid (H, nq, nk)."""
    return dict(
        segq=pl.BlockSpec((8, block_q), lambda h, i, j: (0, i)),
        segk=pl.BlockSpec((8, block_k), lambda h, i, j: (0, j)),
        qb=pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
        kb=pl.BlockSpec((1, block_k, D), lambda h, i, j: (h, j, 0)),
        slim=pl.BlockSpec((1, 1, block_q), lambda h, i, j: (h, 0, i)),
    )


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                              "interpret"))
def _varlen_fwd_stream(q, k, v, seg_q, seg_k, causal, block_q=_BLOCK,
                       block_k=_BLOCK, interpret=False):
    H, T, D = q.shape
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    scale = 1.0 / math.sqrt(D)
    sp = _stream_specs(block_q, block_k, D)
    out, lse = pl.pallas_call(
        functools.partial(_varlen_fwd_stream_kernel, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k),
        grid=(H, T // block_q, T // block_k),
        in_specs=[sp["segq"], sp["segk"], sp["qb"], sp["kb"], sp["kb"]],
        out_specs=[sp["qb"], sp["slim"]],
        out_shape=[jax.ShapeDtypeStruct((H, T, D), q.dtype),
                   jax.ShapeDtypeStruct((H, 1, T), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32),
                        pltpu.VMEM((block_q, 128), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(_seg2d(seg_q), _seg2d(seg_k), q, k, v)
    return out, lse[:, 0, :]


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                              "interpret"))
def _varlen_bwd_stream(q, k, v, o, lse, do, seg_q, seg_k, causal,
                       block_q=_BLOCK, block_k=_BLOCK, interpret=False):
    """Streaming backward for packs past the one-pass scratch envelope:
    nothing full-T resident; delta precomputed (slim (H, 1, T) f32)."""
    H, T, D = q.shape
    block_q = min(block_q, T)
    block_k = min(block_k, T)
    scale = 1.0 / math.sqrt(D)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                                   # (H, T)
    sp = _stream_specs(block_q, block_k, D)
    lse3 = lse[:, None, :].astype(jnp.float32)
    delta3 = delta[:, None, :]
    dq = pl.pallas_call(
        functools.partial(_varlen_dq_stream_kernel, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k),
        grid=(H, T // block_q, T // block_k),
        in_specs=[sp["segq"], sp["segk"], sp["qb"], sp["kb"], sp["kb"],
                  sp["qb"], sp["slim"], sp["slim"]],
        out_specs=sp["qb"],
        out_shape=jax.ShapeDtypeStruct((H, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(_seg2d(seg_q), _seg2d(seg_k), q, k, v, do, lse3, delta3)
    # dk/dv: grid (H, nk, nq) — swap the roles of the last two axes
    spq = pl.BlockSpec((8, block_q), lambda h, j, i: (0, i))
    spk = pl.BlockSpec((8, block_k), lambda h, j, i: (0, j))
    qb = pl.BlockSpec((1, block_q, D), lambda h, j, i: (h, i, 0))
    kb = pl.BlockSpec((1, block_k, D), lambda h, j, i: (h, j, 0))
    slim = pl.BlockSpec((1, 1, block_q), lambda h, j, i: (h, 0, i))
    dk, dv = pl.pallas_call(
        functools.partial(_varlen_dkv_stream_kernel, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k),
        grid=(H, T // block_k, T // block_q),
        in_specs=[spq, spk, kb, kb, qb, qb, slim, slim],
        out_specs=[kb, kb],
        out_shape=[jax.ShapeDtypeStruct((H, T, D), k.dtype),
                   jax.ShapeDtypeStruct((H, T, D), v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(_seg2d(seg_q), _seg2d(seg_k), k, v, q, do, lse3, delta3)
    return dq, dk, dv


def _segments_from_cu(cu_seqlens, total_pad):
    """cu_seqlens (B+1,) -> per-token segment ids (total_pad,), -1 pad.

    searchsorted over the prefix sums; tokens at/after cu[-1] get -1."""
    cu = jnp.asarray(cu_seqlens, jnp.int32)
    pos = jnp.arange(total_pad, dtype=jnp.int32)
    seg = jnp.searchsorted(cu[1:], pos, side="right").astype(jnp.int32)
    return jnp.where(pos < cu[-1], seg, -1)


def _resident_tier(T, D):
    """Small packs keep k/v (+ f32 scratch) VMEM-resident with causal
    loop early-exit; big packs take the streaming grid kernels."""
    return T * D <= _VARLEN_ONEPASS_MAX_TD


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _varlen_core(q, k, v, seg_q, seg_k, causal, interpret):
    fwd = _varlen_fwd if _resident_tier(*q.shape[1:]) else _varlen_fwd_stream
    out, _ = fwd(q, k, v, seg_q, seg_k, causal, interpret=interpret)
    return out


def _varlen_core_fwd(q, k, v, seg_q, seg_k, causal, interpret):
    fwd = _varlen_fwd if _resident_tier(*q.shape[1:]) else _varlen_fwd_stream
    out, lse = fwd(q, k, v, seg_q, seg_k, causal, interpret=interpret)
    return out, (q, k, v, out, lse, seg_q, seg_k)


def _varlen_core_bwd(causal, interpret, res, g):
    q, k, v, out, lse, seg_q, seg_k = res
    H, T, D = q.shape
    bwd = _varlen_bwd if _resident_tier(T, D) else _varlen_bwd_stream
    dq, dk, dv = bwd(q, k, v, out, lse, g, seg_q, seg_k, causal,
                     interpret=interpret)
    return dq, dk, dv, None, None


_varlen_core.defvjp(_varlen_core_fwd, _varlen_core_bwd)


def flash_attn_unpadded(q, k, v, cu_seqlens_q, cu_seqlens_k, max_seqlen_q,
                        max_seqlen_k, scale=None, dropout=0.0, causal=False,
                        return_softmax=False, interpret=False,
                        dropout_key=None):
    """Packed varlen flash attention on raw arrays.

    q/k/v: (total_q/total_k, H, D) packed across sequences;
    cu_seqlens_q/k: (B+1,) int32 prefix sums (mismatched totals are
    padded to a common total internally).  ``causal=True`` additionally
    requires cu_seqlens_q == cu_seqlens_k, since causality across
    differently-packed q/k has no well-defined position mapping — this
    is VALIDATED ONLY when both prefix sums are concrete; traced
    cu_seqlens inside jit skip it (no checkify-style traced assert is
    wired here), so a traced mismatch silently produces
    global-position causal masking.  Returns
    (out (total_q, H, D), probs-or-None); the (H, T, T) probabilities
    are materialized only under ``return_softmax=True`` (debug mode,
    dense path — reference parity).

    ``scale`` other than 1/sqrt(D) and dropout>0 fall back to a dense
    segment-masked XLA path (same math + real dropout via
    ``dropout_key``, (T, T) memory).  Raw-array function — the
    Tensor/tape wiring lives in nn.functional.attention.
    """
    q_, k_, v_ = q, k, v
    total_q, H, D = q_.shape
    total_k = k_.shape[0]
    # cross-attention packs may have different totals: pad all packs to
    # a common total — padding carries segment -1 and contributes nothing
    total = max(total_q, total_k)
    cu_q = jnp.asarray(cu_seqlens_q, jnp.int32)
    cu_k = jnp.asarray(cu_seqlens_k, jnp.int32)
    if causal:
        both_concrete = not isinstance(cu_q, jax.core.Tracer) \
            and not isinstance(cu_k, jax.core.Tracer)
        if both_concrete and (cu_q.shape != cu_k.shape
                              or not bool(jnp.all(cu_q == cu_k))):
            raise ValueError(
                "flash_attn_unpadded(causal=True) requires cu_seqlens_q "
                "== cu_seqlens_k (self-attention packing)")
    block = min(_BLOCK, total)
    pad = (-total) % block
    Tp = total + pad
    seg_q = _segments_from_cu(cu_q, Tp)
    seg_k = _segments_from_cu(cu_k, Tp)

    default_scale = scale is None or abs(scale - 1.0 / math.sqrt(D)) < 1e-9
    fits = default_scale and dropout == 0.0 and D % 128 in (0, 64)
    on_tpu = jax.default_backend() == "tpu"
    use_kernel = fits and not return_softmax and (interpret or on_tpu)
    if on_tpu and not fits:
        # a TPU call the kernel cannot express runs the dense path:
        # counted, never silent (return_softmax is the asked-for dense
        # debug mode, not a fallback)
        from .. import registry as kreg
        kreg.record_fallback("varlen_attention",
                             "scale" if not default_scale else
                             "dropout" if dropout else "head-dim")

    def packed_hTd(x):
        x = jnp.moveaxis(x, 1, 0)                     # (H, T_own, D)
        grow = Tp - x.shape[1]
        if grow:
            x = jnp.pad(x, ((0, 0), (0, grow), (0, 0)))
        return x

    if use_kernel:
        out = _varlen_core(packed_hTd(q_), packed_hTd(k_), packed_hTd(v_),
                           seg_q, seg_k, bool(causal), interpret)
        out = jnp.moveaxis(out[:, :total_q, :], 0, 1)  # (total_q, H, D)
        return out, None
    # dense fallback (and the return_softmax debug mode, which needs the
    # materialized (H, T, T) probabilities — reference parity)
    def padded_thd(x):
        grow = total - x.shape[0]
        return jnp.pad(x, ((0, grow), (0, 0), (0, 0))) if grow else x
    out, p = _varlen_dense(padded_thd(q_), padded_thd(k_), padded_thd(v_),
                           seg_q[:total], seg_k[:total],
                           scale, dropout, causal, dropout_key)
    out = out[:total_q]
    return (out, p) if return_softmax else (out, None)


def _varlen_dense(q, k, v, seg_q, seg_k, scale, dropout, causal,
                  dropout_key=None):
    """Dense segment-masked fallback (exact math, (T, T) memory).
    Returns (out, probs).  dropout>0 needs ``dropout_key``; it is
    applied to the attention probabilities with inverted-probability
    rescaling (the reference semantics)."""
    T, H, D = q.shape
    sc = scale if scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sc
    live = seg_q[:, None] == seg_k[None, :]
    if causal:
        pos = jnp.arange(T)
        live = live & (pos[:, None] >= pos[None, :])
    s = jnp.where(live[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    # rows with zero live keys: exact 0, not the uniform-softmax mean
    p = jnp.where(jnp.any(live, axis=-1)[None, :, None], p, 0.0)
    if dropout and dropout > 0.0:
        if dropout_key is None:
            raise ValueError(
                "flash_attn_unpadded: dropout>0 needs a dropout_key "
                "(the nn.functional wrapper threads the framework RNG)")
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout), 0.0)
    out = jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32)
                     ).astype(q.dtype)
    return out, p
