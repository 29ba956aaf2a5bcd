"""Flash attention Pallas kernels (TPU).

Reference analogue: paddle/phi/kernels/gpu/flash_attn_kernel.cu (cutlass
flash-attn submodule).  TPU-native: blockwise online-softmax attention,
q blocks against VMEM-resident k/v, with a one-pass recompute backward.

Layout.  The kernels read and write the projection's own layout: paddle's
(B, S, H, D) is (B, S, H*D) for free, and a BlockSpec of ``(rows, W)`` at
lane block ``t`` of that array is a *tile* of ``G = W // D`` whole heads
(``W = 128``: the pair ``2t, 2t+1`` at D=64; ``W = D``: head ``t`` when D
is a multiple of 128).  The grid is ``(B, H*D/W[, blocks])`` and every
operand and result stays where the surrounding matmuls produce and
consume it — no head transpose on either side of a kernel.  Inside a
tile a head is picked by zeroing the other heads' lanes of q (and dO):
on the 128x128 MXU a contraction of 128 with half zeros costs what a
contraction of 64 costs, and the products land lane-dense.  Shapes the
lane tiles cannot address (:func:`lane_tiled` false: D=96, an odd head
count at D=64, ...) run the same kernels over a transposed
(B*H, S, D) copy, one head a tile.

The backward's block visit.  Every rung (head-folded, q-grid one-pass,
two-pass) visits a (q block, k block) pair through one function,
:func:`_bwd_step`, which computes the scores TRANSPOSED, sᵀ = k·qᵀ as
(keys, queries): lse and Δ = rowsum(dO ∘ O) broadcast from the (1,
queries) lane rows lse is stored as, dV = pᵀ·dO and dK = dsᵀ·q are plain
matmuls, and only dQ = (dsᵀ)ᵀ·k contracts a transposed operand.  Which
pairs are visited, and how, is a static schedule, :func:`_pair_visits`:
a pair wholly under the causal diagonal is one unmasked visit (the
q-grid kernels walk those with a dynamic trip count), a pair the
diagonal crosses is visited by halves of its keys — each against the
queries from its own half of the q block on, so the quarter wholly
above the diagonal (probabilities that are exact zeros) is never
computed, and masked only where the diagonal crosses the visit — and a
pair wholly above it is never visited.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
_LANES = 128

# one-pass backward keeps k/v (+ fp32 dk/dv scratch and the dk/dv output
# tiles) VMEM-resident per head: ~16 bytes/element of (S, D).  Past this
# S·D budget the two-pass kernels take over.
_FUSED_BWD_MAX_SD = 8192 * 64
# head-folded kernels fully unroll the q/k block loops, and Mosaic does
# NOT reuse stack slots across unrolled bodies — past these S*D caps the
# s/p temporaries overflow the scoped VMEM (fwd S=4096 measured 41MB).
# The backward's cap predates the pair tiles (it was set where the head-
# folded backward lost to the q-grid one at S=2048, 6.1 vs 5.5 ms, one
# head a program).  Re-measured in PR 33 at B=4, S=2048, H=16, D=64
# (PERF.md §6): head-folded 1.115 ms a call against q-grid fused 1.308
# (the parent's kernels: 1.237 against 1.584), and both fit their VMEM.
# The cap stands until a PR moves it with the masked routing that reads
# it (nn/functional/attention.py) and the D=128 tile, which has 16 MB.
_MH_FWD_MAX_SD = 2048 * 64
_MH_BWD_MAX_SD = 1024 * 64


def _fwd_blocks(S, D=64, heads=None):
    """(block_q, block_k) from the kernel registry's static rule
    (ops/registry.py ``flash_blocks``).  Blocks must DIVIDE S — the
    kernels size their loops as S // block (S=4608 with bk=1024 would
    silently skip the last 512 keys) — and the rule guarantees that."""
    from ..registry import flash_blocks
    return flash_blocks(S, D, heads)


def _bwd_blocks(S):
    """The backward's block (q and k alike): 512, or S whole under that,
    where it divides S; else the largest of 256 / 128 that does.  S
    arrives padded to the 256 granule, and the kernels size their loops
    as S // block: at S=768 a block of 512 dropped the last 256 keys and
    left their dq rows unwritten."""
    for block in (min(DEFAULT_BLOCK_Q, S), 256, 128):
        if S % block == 0:
            return block
    return S


def lane_tiled(H, D):
    """The lane-tile rule: can the kernels address the heads of a
    (B, S, H, D) array in place, as 128-lane tiles of (B, S, H*D)?
    ``H`` is the head count the kernel sees (per shard under a mesh)."""
    return D % _LANES == 0 or (_LANES % D == 0 and (H * D) % _LANES == 0)


# -- in-kernel helpers ------------------------------------------------------

def _keep(lanes, x, other=0):
    """``x`` on one head's lanes of a tile, ``other`` elsewhere (a tile
    of one head has no ``lanes`` and keeps everything)."""
    if lanes is None:
        return x
    return jnp.where(lanes, x, jnp.asarray(other, x.dtype))


def _store_lanes(ref, rows, lanes, x):
    """``x`` into one head's lanes of ``ref[rows]``; the other heads'
    lanes keep what an earlier head of the tile stored there."""
    ref[rows, :] = x if lanes is None else jnp.where(lanes, x, ref[rows, :])


def _over_heads(rows, W, D, body, init=None):
    """``carry = body(g, lanes, carry)`` for each head of a (rows, W)
    tile, ``lanes`` the head's lanes (None where the tile is one head).
    A real loop: Mosaic does not share stack slots across unrolled
    bodies, so unrolling multiplies the s/p temporaries by the heads in
    the tile (the fused backward with both heads unrolled measured 4%
    faster at S=2048 and overran the 16 MB scoped VMEM at S=4096), and
    the head-folded forward with both heads unrolled took five times as
    long to compile."""
    if W == D:
        return body(0, None, init)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, W), 1)

    def one(g, carry):
        return body(g, (lane >= g * D) & (lane < (g + 1) * D), carry)
    return jax.lax.fori_loop(0, W // D, one, init)


def _causal_mask(s, q_lo, k_lo):
    q_idx = q_lo + jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0)
    k_idx = k_lo + jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
    return jnp.where(q_idx >= k_idx, s, -1e30)


def _softmax_step(s, v, acc, m, l):
    """One online-softmax update of (acc, m, l) with a score block."""
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)
    l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc = acc * alpha + jnp.dot(p.astype(v.dtype), v,
                                preferred_element_type=jnp.float32)
    return acc, m_new, l


def _causal_mask_t(st, rel):
    """The causal mask over transposed scores (keys down the sublanes,
    queries along the lanes); ``rel`` = first key - first query."""
    k_idx = rel + jax.lax.broadcasted_iota(jnp.int32, (st.shape[0], 1), 0)
    q_idx = jax.lax.broadcasted_iota(jnp.int32, (1, st.shape[1]), 1)
    return jnp.where(q_idx >= k_idx, st, -1e30)


_NT = (((1,), (1,)), ((), ()))      # a · bᵀ
_TN = (((0,), (0,)), ((), ()))      # aᵀ · b


def _bwd_step(q, k, v, do, lse, delta, rel=None, bias=None):
    """The backward of one (q, k) block pair: (dV, dK, dQ) contributions.

    The scores are computed TRANSPOSED, sᵀ = k·qᵀ as (keys, queries), so
    that dV = pᵀ·dO and dK = dsᵀ·q are plain matmuls and only dQ =
    (dsᵀ)ᵀ·k takes a transposed operand (the splash-attention dkv
    kernel's orientation).  ``lse`` and ``delta`` are the (1, queries)
    lane rows they are stored as, broadcast down the sublanes.  ``q`` is
    pre-scaled, so dsᵀ·q is dK.  ``rel`` (first key - first query) asks
    for the causal mask: only a pair the diagonal crosses passes it.
    ``bias`` is a (keys, 1) column.  Matmul operands stay in the input
    dtype (bf16 on the fast path) with fp32 MXU accumulation — casting
    them to fp32 would fall off the native MXU path (measured ~2x
    slower); exp runs in fp32."""
    st = jax.lax.dot_general(k, q, _NT, preferred_element_type=jnp.float32)
    if bias is not None:
        st = st + bias
    if rel is not None:
        st = _causal_mask_t(st, rel)
    pt = jnp.exp(st - lse)                            # softmax via saved lse
    dv = jnp.dot(pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
    dpt = jax.lax.dot_general(v, do, _NT, preferred_element_type=jnp.float32)
    dst = (pt * (dpt - delta)).astype(q.dtype)
    dk = jnp.dot(dst, q, preferred_element_type=jnp.float32)
    dq = jax.lax.dot_general(dst, k, _TN, preferred_element_type=jnp.float32)
    return dv, dk, dq


# -- the backward's visit schedule (pure: no kernel needed to test it) ------

def _sub(block):
    """Side of the sub-blocks a block on the diagonal is visited by:
    half the block where the halves are whole lane tiles, else the block."""
    return block // 2 if block % (2 * _LANES) == 0 else block


def _whole_visit(block_q, block_k, masked=False):
    """The schedule of a pair visited as it is, in one piece."""
    return [(0, block_q, 0, block_k, masked)]


def _pair_visits(rel, block_q, block_k, causal):
    """How the backward visits one (q block, k block) pair whose first
    key lies ``rel`` positions after its first query: a list of
    ``(q_off, rows, k_off, cols, masked)`` rectangles.  A pair wholly
    under the diagonal (or any pair when not causal) is one unmasked
    visit; a pair wholly above it is not visited; a pair the diagonal
    crosses is visited by halves of its keys, each against the queries
    from the half of the q block its first key falls in to the block's
    end: the sub-blocks wholly above the diagonal are skipped (their
    probabilities are exact zeros) and a visit is masked only where the
    diagonal crosses it.  One visit a key half and not one a sub-block:
    the longer run of queries measured 3% faster at the fit cell's
    shape (PERF.md §6, PR 33), the same work."""
    if not causal or rel + block_k - 1 <= 0:
        return _whole_visit(block_q, block_k)
    sq, sk = _sub(block_q), _sub(block_k)
    visits = []
    for ko in range(0, block_k, sk):
        qo = max(0, (rel + ko) // sq * sq)
        if qo < block_q:
            visits.append((qo, block_q - qo, ko, sk, rel + ko + sk - 1 > qo))
    return visits


def _add_rows(parts, off, x, sub):
    """``x``, the rows from ``off`` on, into the ``sub``-row pieces."""
    for j in range(0, x.shape[0], sub):
        piece = x if x.shape[0] == sub else x[j:j + sub]
        parts[off + j] = piece if off + j not in parts \
            else parts[off + j] + piece


def _join_rows(parts, n):
    """The n-row block whose equal pieces are ``parts`` ({row offset:
    piece}); rows no visit touched are zeros."""
    piece = next(iter(parts.values()))
    if piece.shape[0] == n:
        return piece
    return jnp.concatenate([parts.get(off, jnp.zeros_like(piece))
                            for off in range(0, n, piece.shape[0])], 0)


def _q_rows(do, o, lse_at, *visit_lists):
    """What a q block's visits read per query, as the (1, rows) lane rows
    they broadcast from: ``{(q_off, rows): (lse, Δ)}`` with Δ = rowsum(dO
    ∘ O) (``do`` already holds one head's lanes only).  lse is stored as
    such rows and ``lse_at(q_off, rows)`` loads one; Δ's column is turned
    once per range (a lane slice of a row is no operand Mosaic takes)."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1,
                    keepdims=True)
    out = {}
    for visits in visit_lists:
        for qo, rows, *_ in visits:
            if (qo, rows) not in out:
                out[qo, rows] = (lse_at(qo, rows),
                                 delta[qo:qo + rows, 0][None, :])
    return out


def _bwd_pair(q, k, v, do, q_rows, rel, visits, bias=None):
    """(dV, dK, dQ) of one (q block, k block) pair, summed over its
    ``visits`` (:func:`_pair_visits`).  ``rel`` = the pair's first key -
    its first query (traced where the pair's place moves with the grid)."""
    sq = min(rows for _, rows, *_ in visits)
    sk = min(cols for *_, cols, _ in visits)
    dvs, dks, dqs = {}, {}, {}
    for qo, rows, ko, cols, masked in visits:
        qs, ks = slice(qo, qo + rows), slice(ko, ko + cols)
        dv, dk, dq = _bwd_step(q[qs], k[ks], v[ks], do[qs], *q_rows[qo, rows],
                               rel + ko - qo if masked else None,
                               None if bias is None else bias[ks])
        _add_rows(dvs, ko, dv, sk)
        _add_rows(dks, ko, dk, sk)
        _add_rows(dqs, qo, dq, sq)
    return (_join_rows(dvs, k.shape[0]), _join_rows(dks, k.shape[0]),
            _join_rows(dqs, q.shape[0]))


# -- q-grid kernels: one q (or kv) block a program --------------------------

def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                      block_k, head_dim):
    """q_ref/o_ref: (block_q, W); k_ref/v_ref: (S, W); lse_ref (optional,
    the backward's residual): (G, 1, block_q) log-sum-exp rows."""
    block_q, W = q_ref.shape
    qi = pl.program_id(2)
    q2 = q_ref[:] * scale
    nkb = k_ref.shape[0] // block_k
    if causal:
        # only iterate k blocks up to (and including) this q block
        nkb = jnp.minimum(
            (qi * block_q + block_q + block_k - 1) // block_k, nkb)

    def head(g, lanes, o2):
        q = _keep(lanes, q2)

        def body(i, carry):
            k = k_ref[pl.ds(i * block_k, block_k), :]
            v = v_ref[pl.ds(i * block_k, block_k), :]
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
            if causal:
                s = _causal_mask(s, qi * block_q, i * block_k)
            return _softmax_step(s, v, *carry)

        acc, m, l = jax.lax.fori_loop(0, nkb, body, (
            jnp.zeros((block_q, W), jnp.float32),
            jnp.full((block_q, 1), -1e30, jnp.float32),
            jnp.zeros((block_q, 1), jnp.float32)))
        l = jnp.maximum(l, 1e-30)
        if lse_ref is not None:
            lse_ref[g, 0, :] = (m + jnp.log(l))[:, 0]
        return _keep(lanes, acc / l, o2)

    o_ref[:] = _over_heads(block_q, W, head_dim, head, jnp.zeros(
        (block_q, W), jnp.float32)).astype(o_ref.dtype)


def _dq_block(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dk_acc, dv_acc, *,
              scale, causal, block_k, head_dim):
    """dQ of one q block against every k block: dSᵀ = Pᵀ ∘ (V·dOᵀ − Δ),
    dQ = scale · (dSᵀ)ᵀ·K, with Δ = rowsum(dO ∘ O) computed here.  With
    ``dk_acc``/``dv_acc`` ((S, W) fp32 scratch) the same visit of each
    (q, k) block pair also accumulates dV = Pᵀ·dO and dK = dSᵀ·Q."""
    block_q, W = q_ref.shape
    q_lo = pl.program_id(2) * block_q
    q2 = q_ref[:] * scale
    do2 = do_ref[:]
    o2 = o_ref[:]
    whole = _whole_visit(block_q, block_k)
    if not causal:
        n_under, crossed = k_ref.shape[0] // block_k, []
    else:
        # the k blocks wholly under this q block's diagonal: a dynamic
        # count, no mask; the blocks above it are never visited
        n_under = q_lo // block_k
        if block_q % block_k == 0:
            # the diagonal crosses block_q // block_k blocks, each at a
            # fixed place against the q block: the static schedule
            crossed = [(rel, _pair_visits(rel, block_q, block_k, True))
                       for rel in range(0, block_q, block_k)]
        else:
            # a k block wider than the q block: where the diagonal
            # crosses it moves with the grid, so it is masked whole
            crossed = [(n_under * block_k - q_lo,
                        _whole_visit(block_q, block_k, masked=True))]

    def head(g, lanes, dq2):
        # the other heads' lanes of q and dO are zero, so their lanes of
        # dK and dV get exact zeros and the scratch sums over heads
        q = _keep(lanes, q2)
        do = _keep(lanes, do2)
        q_rows = _q_rows(do, o2, lambda qo, rows: lse_ref[g, :, pl.ds(qo, rows)],
                         whole, *(visits for _, visits in crossed))

        def pair(k_lo, rel, visits):
            kb = pl.ds(k_lo, block_k)
            dv, dk, dq = _bwd_pair(q, k_ref[kb, :], v_ref[kb, :], do, q_rows,
                                   rel, visits)
            if dk_acc is not None:
                dv_acc[kb, :] += dv
                dk_acc[kb, :] += dk
            return dq

        dq = jax.lax.fori_loop(
            0, n_under, lambda i, dq: dq + pair(i * block_k, None, whole),
            jnp.zeros((block_q, W), jnp.float32))
        for rel, visits in crossed:
            dq = dq + pair(q_lo + rel, rel, visits)
        return _keep(lanes, dq, dq2)

    dq = _over_heads(block_q, W, head_dim, head,
                     jnp.zeros((block_q, W), jnp.float32))
    return (dq * scale).astype(q_ref.dtype)


def _flash_dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, dq_ref,
                     **kw):
    dq_ref[:] = _dq_block(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                          None, None, **kw)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                            dq_ref, dk_ref, dv_ref, dk_acc, dv_acc, **kw):
    """One-pass backward: every (q,k) block pair is visited ONCE,
    producing dQ and accumulating dK/dV in fp32 VMEM scratch — vs the
    two-pass kernels that recompute S/P/dP twice.

    The grid's last axis walks q blocks SEQUENTIALLY (dimension
    semantics "arbitrary"), so only one (block_q, W) q/do tile is VMEM-
    resident at a time while the dk/dv accumulators persist across grid
    steps; that keeps the VMEM footprint ~16·S·D bytes a head and lets
    the one-pass kernel run to S=8192 at D=64."""
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _zero():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    dq_ref[:] = _dq_block(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                          dk_acc, dv_acc, **kw)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _flush():
        dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _flash_dkv_kernel(k_ref, v_ref, q_ref, do_ref, o_ref, lse_ref, dk_ref,
                      dv_ref, *, scale, causal, block_q, head_dim):
    """dK/dV for one kv block: dV = Pᵀ·dO;  dK = scale · dSᵀ·Q.
    k_ref/v_ref: (block_k, W); q_ref/do_ref/o_ref: (S, W); lse_ref:
    (G, 1, S).  The mirror of :func:`_dq_block`: the q blocks the
    diagonal crosses first (by the static schedule where their place
    against the kv block is fixed), then those wholly under it."""
    block_k, W = k_ref.shape
    k_lo = pl.program_id(2) * block_k
    k = k_ref[:]
    v = v_ref[:]
    nqb = q_ref.shape[0] // block_q
    whole = _whole_visit(block_q, block_k)
    if not causal:
        first_under, crossed = 0, []
    elif block_k % block_q == 0:
        crossed = [(-r, _pair_visits(-r, block_q, block_k, True))
                   for r in range(0, block_k, block_q)]
        first_under = (k_lo + block_k) // block_q
    else:       # a q block taller than the kv block: masked whole
        first = k_lo // block_q
        crossed = [(k_lo - first * block_q,
                    _whole_visit(block_q, block_k, masked=True))]
        first_under = first + 1

    def head(g, lanes, carry):
        def pair(q_lo, rel, visits, carry):
            qb = pl.ds(q_lo, block_q)
            q = _keep(lanes, q_ref[qb, :] * scale)
            do = _keep(lanes, do_ref[qb, :])
            q_rows = _q_rows(
                do, o_ref[qb, :],
                lambda qo, rows: lse_ref[g, :, pl.ds(q_lo + qo, rows)], visits)
            dv, dk, _ = _bwd_pair(q, k, v, do, q_rows, rel, visits)
            return carry[0] + dk, carry[1] + dv

        for rel, visits in crossed:
            carry = pair(k_lo - rel, rel, visits, carry)
        return jax.lax.fori_loop(
            first_under, nqb,
            lambda i, carry: pair(i * block_q, None, whole, carry), carry)

    zero = jnp.zeros((block_k, W), jnp.float32)
    dk, dv = _over_heads(block_q, W, head_dim, head, (zero, zero))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# head-folded kernels: the whole (S, W) tile per pallas program.
# At D=64/S~1k each q-block program does only ~0.1ms-equivalent of MXU
# work while per-program overhead (prologue, DMA issue, semaphores) is
# ~3-4us, so the per-q-block grid ran at <10% MXU (measured r3).  One
# program a tile with fully static q/k loops amortizes that overhead
# nq-fold and lets blocks off the diagonal skip the causal mask.
# ---------------------------------------------------------------------------

def _flash_fwd_mh_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref, *,
                         scale, causal, block_q, block_k, head_dim):
    """lse is stored UNBROADCAST as (G, 1, S) — an (S, LANES) lane-
    broadcast layout cost a 128x-inflated HBM write (151MB per layer at
    BH=288/S=1024, measured ~24% of bwd time); the (block_q,) lane
    vector <-> (block_q, 1) column relayout inside the kernel is far
    cheaper.  ``bias_ref`` (optional, (1, S)) is an additive per-KEY
    bias broadcast over heads and queries — the key-padding /
    attention-mask path (0 keep, -1e30 drop, or any additive values
    constant over heads and queries); every row must keep >=1 live key
    (the registry's mask contract, docs/kernels.md).

    The loop over the tile's heads is the OUTER one: a loop iteration is
    a scheduling region, and one head's whole unrolled q/k walk in it
    lets the scheduler overlap a block's exp with the next block's
    matmuls as it does with one head a program (with the head loop
    inside each q block the forward measured 33% slower)."""
    S, W = q_ref.shape

    def head(g, lanes, _):
        for qi in range(S // block_q):
            q_lo = qi * block_q
            qb = pl.ds(q_lo, block_q)
            q = _keep(lanes, q_ref[qb, :] * scale)
            acc = jnp.zeros((block_q, W), jnp.float32)
            m = jnp.full((block_q, 1), -1e30, jnp.float32)
            l = jnp.zeros((block_q, 1), jnp.float32)
            for ki in range(S // block_k):
                k_lo = ki * block_k
                if causal and k_lo > q_lo + block_q - 1:
                    continue                  # fully above the diagonal
                kb = pl.ds(k_lo, block_k)
                s = jnp.dot(q, k_ref[kb, :].T,
                            preferred_element_type=jnp.float32)
                if bias_ref is not None:
                    s = s + bias_ref[0, kb][None, :]
                if causal and k_lo + block_k - 1 > q_lo:   # straddles diag
                    s = _causal_mask(s, q_lo, k_lo)
                acc, m, l = _softmax_step(s, v_ref[kb, :], acc, m, l)
            l = jnp.maximum(l, 1e-30)
            if lse_ref is not None:
                lse_ref[g, 0, qb] = (m + jnp.log(l))[:, 0]
            _store_lanes(o_ref, qb, lanes, (acc / l).astype(o_ref.dtype))

    _over_heads(block_q, W, head_dim, head)


def _flash_bwd_mh_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref,
                         bias_ref, dq_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                         *, scale, causal, block_q, block_k, head_dim):
    """One-pass backward, one tile a program, static loops under the
    loop over the tile's heads; dk/dv accumulate in fp32 VMEM scratch
    within the program (no cross-program state — each program owns its
    heads outright).  delta = rowsum(do*o) is computed in-kernel from
    the o block and lse rides the slim (G, 1, S) layout — the old
    precomputed (S, LANES) broadcasts were ~300MB/layer of pure HBM
    overhead (measured 24% of bwd time).  ``bias_ref`` (optional,
    (1, S)) replays the forward's additive per-key bias so the
    recomputed P matches bitwise."""
    S, W = q_ref.shape
    dk_acc[:] = jnp.zeros_like(dk_acc)
    dv_acc[:] = jnp.zeros_like(dv_acc)

    def head(g, lanes, _):
        for q_lo in range(0, S, block_q):
            qb = pl.ds(q_lo, block_q)
            # the other heads' lanes of q and dO are zero, so their
            # lanes of dK and dV get exact zeros: the scratch sums heads
            q = _keep(lanes, q_ref[qb, :] * scale)
            do = _keep(lanes, do_ref[qb, :])
            # a pair wholly above the diagonal has no visits
            pairs = [(k_lo, _pair_visits(k_lo - q_lo, block_q, block_k,
                                         causal))
                     for k_lo in range(0, S, block_k)]
            q_rows = _q_rows(
                do, o_ref[qb, :],
                lambda qo, rows: lse_ref[g, :, pl.ds(q_lo + qo, rows)],
                *(visits for _, visits in pairs))
            dq = jnp.zeros((block_q, W), jnp.float32)
            for k_lo, visits in pairs:
                if not visits:
                    continue
                kb = pl.ds(k_lo, block_k)
                dv, dk, dq_i = _bwd_pair(
                    q, k_ref[kb, :], v_ref[kb, :], do, q_rows,
                    k_lo - q_lo, visits,
                    None if bias_ref is None else bias_ref[0, kb][:, None])
                dv_acc[kb, :] += dv
                dk_acc[kb, :] += dk
                dq = dq + dq_i
            _store_lanes(dq_ref, qb, lanes, (dq * scale).astype(dq_ref.dtype))

    _over_heads(block_q, W, head_dim, head)
    dk_ref[:] = dk_acc[:].astype(dk_ref.dtype)
    dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


# -- the jitted entries: (N, S, C) arrays, tiled W lanes at a time ----------
#
# "bhsd" in the names is the logical order of the dimensions; the device
# trace finds the kernels by these names (benchmark/metrics/
# kernel.fit_attention_roofline.json), so they stay.

def _tiles(x, head_dim):
    """(N, S, tiles, W) of an (N, S, C) kernel operand: 128-lane tiles of
    128 // D heads, or one head a tile where the array is a single head
    wide (the transposed layout) or D fills whole lane tiles."""
    N, S, C = x.shape
    W = head_dim if (C == head_dim or head_dim % _LANES == 0) else _LANES
    return N, S, C // W, W


def _params(W, head_dim, semantics=None):
    """A tile of G heads holds G heads' k/v and dk/dv blocks (and fp32
    dk/dv scratch) where one head a program held one, so the scoped VMEM
    budget (16 MB by default) grows with G: the fused backward at S=4096,
    D=64 asks for 16.9 MB with two heads a tile."""
    kw = {}
    if W > head_dim:
        kw["vmem_limit_bytes"] = min(W // head_dim, 6) * 16 * 1024 * 1024
    if semantics:
        kw["dimension_semantics"] = semantics
    return pltpu.CompilerParams(**kw)


def _optional(kernel, *present):
    """``kernel`` with None passed for each ref the call leaves out."""
    def kern(*refs):
        refs = iter(refs)
        return kernel(*[next(refs) if p else None for p in present])
    return kern


def _slim(lse, N):
    """(B, H, S) log-sum-exp rows as the kernels' (N, heads, 1, S)."""
    return lse.astype(jnp.float32).reshape(N, -1, 1, lse.shape[-1])


@functools.partial(jax.jit, static_argnames=(
    "head_dim", "causal", "block_q", "block_k", "with_lse", "interpret"))
def _flash_bhsd_fwd(q, k, v, *, head_dim, causal=False,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    with_lse=True, interpret=False):
    """The q-grid forward.  Returns (o, lse (N, heads, 1, S) or None)."""
    N, S, T, W = _tiles(q, head_dim)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    spec_q = pl.BlockSpec((None, block_q, W), lambda n, t, i: (n, i, t))
    spec_kv = pl.BlockSpec((None, S, W), lambda n, t, i: (n, 0, t))
    out_specs = [spec_q]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((None, W // head_dim, 1, block_q),
                                      lambda n, t, i: (n, t, 0, i)))
        out_shape.append(jax.ShapeDtypeStruct(
            (N, q.shape[2] // head_dim, 1, S), jnp.float32))
    out = pl.pallas_call(
        _optional(functools.partial(
            _flash_fwd_kernel, scale=1.0 / math.sqrt(head_dim),
            causal=causal, block_k=block_k, head_dim=head_dim),
            1, 1, 1, 1, with_lse),
        grid=(N, T, S // block_q),
        in_specs=[spec_q, spec_kv, spec_kv],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_params(W, head_dim),
        interpret=interpret,
    )(q, k, v)
    return (out[0], out[1]) if with_lse else (out[0], None)


@functools.partial(jax.jit, static_argnames=(
    "head_dim", "causal", "block_q", "block_k", "interpret"))
def _flash_bhsd_bwd_fused(q, k, v, o, lse, do, *, head_dim, causal=False,
                          block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                          interpret=False):
    N, S, T, W = _tiles(q, head_dim)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    spec_q = pl.BlockSpec((None, block_q, W), lambda n, t, i: (n, i, t))
    spec_kv = pl.BlockSpec((None, S, W), lambda n, t, i: (n, 0, t))
    spec_lse = pl.BlockSpec((None, W // head_dim, 1, block_q),
                            lambda n, t, i: (n, t, 0, i))
    return pl.pallas_call(
        functools.partial(_flash_bwd_fused_kernel,
                          scale=1.0 / math.sqrt(head_dim), causal=causal,
                          block_k=block_k, head_dim=head_dim),
        grid=(N, T, S // block_q),
        in_specs=[spec_q, spec_kv, spec_kv, spec_q, spec_q, spec_lse],
        out_specs=[spec_q, spec_kv, spec_kv],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((S, W), jnp.float32),
                        pltpu.VMEM((S, W), jnp.float32)],
        compiler_params=_params(W, head_dim,
                                ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, o, _slim(lse, N))


@functools.partial(jax.jit, static_argnames=(
    "head_dim", "causal", "block_q", "block_k", "interpret"))
def _flash_bhsd_bwd(q, k, v, o, lse, do, *, head_dim, causal=False,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False):
    """The two-pass backward: a dQ pass over q blocks, then a dK/dV pass
    over kv blocks that recomputes S/P/dP."""
    N, S, T, W = _tiles(q, head_dim)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    G = W // head_dim
    scale = 1.0 / math.sqrt(head_dim)
    blk = lambda n, t, i: (n, i, t)
    full = lambda n, t, i: (n, 0, t)
    spec_q = pl.BlockSpec((None, block_q, W), blk)
    spec_k = pl.BlockSpec((None, block_k, W), blk)
    spec_full = pl.BlockSpec((None, S, W), full)
    lse4 = _slim(lse, N)
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, scale=scale, causal=causal,
                          block_k=block_k, head_dim=head_dim),
        grid=(N, T, S // block_q),
        in_specs=[spec_q, spec_full, spec_full, spec_q, spec_q,
                  pl.BlockSpec((None, G, 1, block_q),
                               lambda n, t, i: (n, t, 0, i))],
        out_specs=spec_q,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=_params(W, head_dim),
        interpret=interpret,
    )(q, k, v, do, o, lse4)
    dk, dv = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, head_dim=head_dim),
        grid=(N, T, S // block_k),
        in_specs=[spec_k, spec_k, spec_full, spec_full, spec_full,
                  pl.BlockSpec((None, G, 1, S),
                               lambda n, t, i: (n, t, 0, 0))],
        out_specs=[spec_k, spec_k],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=_params(W, head_dim),
        interpret=interpret,
    )(k, v, q, do, o, lse4)
    return dq, dk, dv


def _mh_specs(q, bias, head_dim):
    """Grid and block specs of the head-folded kernels: the (S, W) tile,
    the slim (G, 1, S) lse rows, and — as (specs, operands), empty
    without a ``bias`` — the (1, S) key-bias row of the tile's batch row
    (the transposed layout folds heads into N)."""
    N, S, T, W = _tiles(q, head_dim)
    spec = pl.BlockSpec((None, S, W), lambda n, t: (n, 0, t))
    spec_lse = pl.BlockSpec((None, W // head_dim, 1, S),
                            lambda n, t: (n, t, 0, 0))
    if bias is None:
        return (N, T), spec, spec_lse, [], ()
    rep = N // bias.shape[0]
    return (N, T), spec, spec_lse, \
        [pl.BlockSpec((None, 1, S), lambda n, t: (n // rep, 0, 0))], \
        (bias.astype(jnp.float32)[:, None, :],)


@functools.partial(jax.jit, static_argnames=(
    "head_dim", "causal", "block_q", "block_k", "with_lse", "interpret"))
def _flash_bhsd_fwd_mh(q, k, v, bias=None, *, head_dim, causal=False,
                       block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                       with_lse=True, interpret=False):
    """``bias``: optional (B, S) additive per-key bias (the attention-
    mask path).  Returns (o, lse (N, heads, 1, S) or None)."""
    S = q.shape[1]
    grid, spec, spec_lse, bias_specs, bias_in = _mh_specs(q, bias, head_dim)
    out_specs = [spec]
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype)]
    if with_lse:
        out_specs.append(spec_lse)
        out_shape.append(jax.ShapeDtypeStruct(
            (q.shape[0], q.shape[2] // head_dim, 1, S), jnp.float32))
    out = pl.pallas_call(
        _optional(functools.partial(
            _flash_fwd_mh_kernel, scale=1.0 / math.sqrt(head_dim),
            causal=causal, block_q=min(block_q, S), block_k=min(block_k, S),
            head_dim=head_dim), 1, 1, 1, len(bias_in), 1, with_lse),
        grid=grid,
        in_specs=[spec, spec, spec] + bias_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_params(spec.block_shape[-1], head_dim),
        interpret=interpret,
    )(q, k, v, *bias_in)
    return (out[0], out[1]) if with_lse else (out[0], None)


@functools.partial(jax.jit, static_argnames=(
    "head_dim", "causal", "block_q", "block_k", "interpret"))
def _flash_bhsd_bwd_mh(q, k, v, o, lse, do, bias=None, *, head_dim,
                       causal=False, block_q=DEFAULT_BLOCK_Q,
                       block_k=DEFAULT_BLOCK_K, interpret=False):
    N, S, _ = q.shape
    grid, spec, spec_lse, bias_specs, bias_in = _mh_specs(q, bias, head_dim)
    W = spec.block_shape[-1]
    return pl.pallas_call(
        _optional(functools.partial(
            _flash_bwd_mh_kernel, scale=1.0 / math.sqrt(head_dim),
            causal=causal, block_q=min(block_q, S), block_k=min(block_k, S),
            head_dim=head_dim), 1, 1, 1, 1, 1, 1, len(bias_in), 1, 1, 1, 1, 1),
        grid=grid,
        in_specs=[spec] * 5 + [spec_lse] + bias_specs,
        out_specs=[spec, spec, spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((S, W), jnp.float32),
                        pltpu.VMEM((S, W), jnp.float32)],
        compiler_params=_params(W, head_dim),
        interpret=interpret,
    )(q, k, v, do, o, _slim(lse, N), *bias_in)


# -- (B, S, H, D) entries ---------------------------------------------------

def _to_bhsd(x):
    B, S, H, D = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(B * H, S, D)


def _from_bhsd(x, B, H):
    BH, S, D = x.shape
    return jnp.swapaxes(x.reshape(B, H, S, D), 1, 2)


def _packing(B, H, D):
    """(pack, unpack) between (B, S, H, D) and the kernels' (N, S, C):
    the free (B, S, H*D) view where the lane tiles address the heads,
    else the transposed (B*H, S, D) copy."""
    if lane_tiled(H, D):
        return (lambda x: x.reshape(B, -1, H * D),
                lambda x: x.reshape(B, -1, H, D))
    return _to_bhsd, functools.partial(_from_bhsd, B=B, H=H)


def _repeat_kv(k, v, H):
    """MQA/GQA (H_kv divides H): kv heads repeated up to the q heads."""
    rep = H // k.shape[2]
    if rep == 1:
        return k, v
    return jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)


def _check_bias_cap(bias, S, D, cap, what):
    if bias is not None and S * D > cap:
        raise ValueError(
            f"flash key-bias {what} needs S*D <= {cap} (got S={S}, "
            f"D={D}); the dispatch layer routes larger masked shapes to "
            "the XLA attention")


def _forward(q, k, v, bias, causal, interpret, with_lse):
    B, S, H, D = q.shape
    k, v = _repeat_kv(k, v, H)
    pack, unpack = _packing(B, H, D)
    bq, bk = _fwd_blocks(S, D, B * H)
    _check_bias_cap(bias, S, D, _MH_FWD_MAX_SD, "path")
    kw = dict(head_dim=D, causal=causal, block_q=bq, block_k=bk,
              with_lse=with_lse, interpret=interpret)
    if S * D <= _MH_FWD_MAX_SD:
        o, lse = _flash_bhsd_fwd_mh(pack(q), pack(k), pack(v), bias=bias,
                                    **kw)
    else:
        o, lse = _flash_bhsd_fwd(pack(q), pack(k), pack(v), **kw)
    return unpack(o), None if lse is None else lse.reshape(B, H, S)


def flash_attention_fwd(q, k, v, bias=None, causal=False, interpret=False):
    """(B, S, H, D) in/out — paddle layout; supports MQA/GQA (H_kv divides
    H) by repeating kv heads.  No-grad path: no LSE is written, so
    inference pays nothing for backward residuals.  ``bias``: optional
    (B, S) additive per-key mask — head-folded kernels only (the
    registry routes masked shapes past the VMEM cap to the XLA path)."""
    return _forward(q, k, v, bias, causal, interpret, with_lse=False)[0]


def flash_attention_fwd_lse(q, k, v, bias=None, causal=False,
                            interpret=False):
    """Forward returning (o [B,S,H,D], lse [B,H,S]) for the flash bwd."""
    return _forward(q, k, v, bias, causal, interpret, with_lse=True)


def flash_attention_bwd(q, k, v, o, lse, do, bias=None, causal=False,
                        interpret=False):
    """Pallas flash backward — returns (dq, dk, dv) in (B, S, H, D);
    GQA kv grads are summed back over the repeated query-head groups.
    ``bias`` must replay the forward's additive per-key mask (head-
    folded kernel only, same cap contract as the forward)."""
    B, S, H, D = q.shape
    Hk = k.shape[2]
    k, v = _repeat_kv(k, v, H)
    pack, unpack = _packing(B, H, D)
    _check_bias_cap(bias, S, D, _MH_BWD_MAX_SD, "backward")
    ops = (pack(q), pack(k), pack(v), pack(o), lse, pack(do))
    block = _bwd_blocks(S)
    kw = dict(head_dim=D, causal=causal, block_q=block, block_k=block,
              interpret=interpret)
    # ladder: head-folded one-pass (smallest grids, whole tile resident)
    # -> q-grid one-pass (cross-step dk/dv scratch) -> two-pass
    if S * D <= _MH_BWD_MAX_SD:
        grads = _flash_bhsd_bwd_mh(*ops, bias=bias, **kw)
    elif S * D <= _FUSED_BWD_MAX_SD:
        grads = _flash_bhsd_bwd_fused(*ops, **kw)
    else:
        grads = _flash_bhsd_bwd(*ops, **kw)
    dq, dk, dv = (unpack(g) for g in grads)
    if Hk != H:
        dk = dk.reshape(B, S, Hk, H // Hk, D).sum(3)
        dv = dv.reshape(B, S, Hk, H // Hk, D).sum(3)
    return dq, dk, dv
