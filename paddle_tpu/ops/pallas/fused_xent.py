"""Fused softmax cross-entropy Pallas kernel (TPU).

Reference analogue: paddle/phi/kernels/gpu/cross_entropy_kernel.cu
(softmax_with_cross_entropy fused kernel).  For an LM head the logits
tensor is huge (B*S x V ~ GBs in bf16); the XLA composition (max pass,
exp-sum pass, gather, then a recompute in backward) streams it from HBM
several times and materializes fp32 intermediates.  This kernel makes
ONE pass for the forward — streaming V in lane-aligned chunks with an
online max/sum (flash-style) while picking the label logit — and ONE
pass for the backward, writing dlogits = scale * (softmax - onehot)
directly from the saved row lse.

``fused_softmax_xent(logits2, labels)`` takes flattened (T, V) bf16/f32
logits and int32 labels (negative = ignore) and returns per-row
(lse - picked) with zeros at ignored rows; mean/sum reduction lives in
the caller.  Off-TPU, an identical-math jnp fallback keeps it testable.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import registry as kreg

__all__ = ["fused_softmax_xent"]

_LANES = 128
_BT = 256          # rows per program (T pads up to this granule)
_MAX_BV = 2048     # V streamed in chunks of <= this many lanes
_FORCE_INTERPRET = False   # tests: run the kernels in interpret mode on CPU

# registry policy: Pallas on TPU (or interpret mode), jnp reference math
# everywhere else; V must stay lane-aligned (the one hard constraint —
# rows pad to the _BT granule since ISSUE 15, so T is unconstrained)
kreg.register("xent", "pallas", None, platforms=("tpu",))
kreg.register("xent", "xla", None, platforms=("*",))


def _select():
    """(use_pallas, interpret) for this call — module _FORCE_INTERPRET
    (the test hook) short-circuits the registry."""
    if _FORCE_INTERPRET:
        return True, True
    sel = kreg.choose("xent")
    if sel.impl != "pallas":
        return False, False
    return True, sel.interpret


def _pick_bv(V):
    """Fixed wide chunk (good HBM streaming + few grid trips); the tail
    chunk is masked by global column index, so V only needs LANE
    alignment, not divisibility (50304 = 393·128 would otherwise force
    384-wide chunks — 131 grid trips/row-block, measured 3x slower than
    the masked 2048-wide stream)."""
    if V % _LANES:
        return None
    return min(_MAX_BV, V)


def _xent_fwd_kernel(lg_ref, lb_ref, out_ref, lse_ref, m_ref, s_ref, p_ref,
                     *, n_v, bv, V):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, -1e30)
        s_ref[:] = jnp.zeros_like(s_ref)
        p_ref[:] = jnp.zeros_like(p_ref)

    chunk = lg_ref[:].astype(jnp.float32)            # (bt, bv)
    col = vi * bv + jax.lax.broadcasted_iota(jnp.int32, chunk.shape, 1)
    if V % bv:
        # tail chunk: out-of-range lanes read padding — exclude them
        chunk = jnp.where(col < V, chunk, -1e30)
    lb = lb_ref[:, 0]                                 # (bt,)
    m_prev = m_ref[:, 0]
    m_cur = jnp.max(chunk, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    s_new = s_ref[:, 0] * alpha + jnp.sum(
        jnp.exp(chunk - m_new[:, None]), axis=-1)
    # label logit if it falls inside this chunk
    hit = col == lb[:, None]
    p_new = p_ref[:, 0] + jnp.sum(jnp.where(hit, chunk, 0.0), axis=-1)
    m_ref[:, 0] = m_new
    s_ref[:, 0] = s_new
    p_ref[:, 0] = p_new

    @pl.when(vi == n_v - 1)
    def _fin():
        lse = m_new + jnp.log(jnp.maximum(s_new, 1e-30))
        valid = lb >= 0
        out_ref[:, 0] = jnp.where(valid, lse - p_new, 0.0)
        lse_ref[:, 0] = lse


def _xent_bwd_kernel(lg_ref, lb_ref, lse_ref, g_ref, dlg_ref, *, bv, V):
    vi = pl.program_id(1)
    chunk = lg_ref[:].astype(jnp.float32)
    col = vi * bv + jax.lax.broadcasted_iota(jnp.int32, chunk.shape, 1)
    if V % bv:
        chunk = jnp.where(col < V, chunk, -1e30)  # exp -> 0 in the pad
    lb = lb_ref[:, 0]
    lse = lse_ref[:, 0]
    scale = g_ref[:, 0]                               # per-row upstream g
    p = jnp.exp(chunk - lse[:, None])
    onehot = (col == lb[:, None]).astype(jnp.float32)
    valid = (lb >= 0).astype(jnp.float32)
    dlg_ref[:] = ((p - onehot) * (scale * valid)[:, None]
                  ).astype(dlg_ref.dtype)


def _lane_col(x, bt_rows):
    """(T,) -> (T, LANES) with the value in column 0 (TPU block rule)."""
    return jnp.pad(x[:, None], ((0, 0), (0, _LANES - 1)))


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def fused_softmax_xent(logits2, labels):
    out, _ = _fwd_impl(logits2, labels)
    return out


def _ref_rowloss(logits2, labels):
    lg = logits2.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    safe = jnp.maximum(labels, 0)
    picked = jnp.take_along_axis(lg, safe[:, None], 1)[:, 0]
    return jnp.where(labels >= 0, lse - picked, 0.0)


def _pad_rows(logits2, labels):
    """Pad T up to the _BT granule with ignore rows (label -1) so the
    kernel's row-block grid divides; callers slice back to T."""
    T = logits2.shape[0]
    pad = (-T) % _BT
    if not pad:
        return logits2, labels, T
    return (jnp.pad(logits2, ((0, pad), (0, 0))),
            jnp.pad(labels, (0, pad), constant_values=-1), T)


def _fwd_pallas(logits2, lbl, *, n_v, bv, V, interpret):
    T = logits2.shape[0]
    return pl.pallas_call(
        functools.partial(_xent_fwd_kernel, n_v=n_v, bv=bv, V=V),
        grid=(T // _BT, n_v),
        in_specs=[
            pl.BlockSpec((_BT, bv), lambda t, v: (t, v)),
            pl.BlockSpec((_BT, _LANES), lambda t, v: (t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((_BT, _LANES), lambda t, v: (t, 0)),
            pl.BlockSpec((_BT, _LANES), lambda t, v: (t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, _LANES), jnp.float32),
            jax.ShapeDtypeStruct((T, _LANES), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((_BT, _LANES), jnp.float32),
                        pltpu.VMEM((_BT, _LANES), jnp.float32),
                        pltpu.VMEM((_BT, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(logits2, lbl)


# standalone dispatches are compilestats-tracked (roofline attribution
# under kernel.xent_*); traced calls inline into the caller's surface
_fwd_tracked = kreg.TrackedKernel(_fwd_pallas, kreg.XENT_FWD_SURFACE)


def _per_row_shard(local, out_specs, logits2, *rows):
    """Run ``local(logits2, *rows)`` directly, or — under a multi-device
    trace (``kreg.partitioned``), where XLA cannot partition the Mosaic
    kernel — per shard of rows: T = B*S is batch-major, so the batch
    axes split it like the batch; every row keeps its full vocab (a
    vocab-parallel logits tensor is gathered by the shard_map boundary).
    ``out_specs(t)`` maps the row-dim spec entry to the output specs."""
    part = kreg.current_partition()
    if part is None:
        return local(logits2, *rows)
    P = jax.sharding.PartitionSpec
    t = part.batch(logits2.shape[0])
    return part.shard_map(local, (P(t, None),) + (P(t),) * len(rows),
                          out_specs(P, t))(logits2, *rows)


def _fwd_rows(logits2, labels, *, bv, V, interp):
    lg_p, lb_p, T0 = _pad_rows(logits2, labels.astype(jnp.int32))
    lbl = _lane_col(lb_p, lg_p.shape[0])
    n_v = -(-V // bv)      # ceil: tail chunk masked in-kernel
    out, lse = _fwd_tracked(lg_p, lbl, n_v=n_v, bv=bv, V=V,
                            interpret=interp)
    return out[:T0, 0], lse[:T0, 0]


def _fwd_impl(logits2, labels):
    T, V = logits2.shape
    bv = _pick_bv(V)
    use, interp = _select()
    if use and bv is None:
        kreg.record_fallback("xent", "unaligned-vocab")
        use = False
    if not use:
        lg = logits2.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lg, axis=-1)
        return _ref_rowloss(logits2, labels), lse
    return _per_row_shard(
        functools.partial(_fwd_rows, bv=bv, V=V, interp=interp),
        lambda P, t: (P(t), P(t)), logits2, labels)


def _xent_fwd(logits2, labels):
    out, lse = _fwd_impl(logits2, labels)
    return out, (logits2, labels, lse)


def _bwd_pallas(logits2, lbl, lse_l, g_l, *, bv, V, interpret):
    T = logits2.shape[0]
    return pl.pallas_call(
        functools.partial(_xent_bwd_kernel, bv=bv, V=V),
        grid=(T // _BT, -(-V // bv)),
        in_specs=[
            pl.BlockSpec((_BT, bv), lambda t, v: (t, v)),
            pl.BlockSpec((_BT, _LANES), lambda t, v: (t, 0)),
            pl.BlockSpec((_BT, _LANES), lambda t, v: (t, 0)),
            pl.BlockSpec((_BT, _LANES), lambda t, v: (t, 0)),
        ],
        out_specs=pl.BlockSpec((_BT, bv), lambda t, v: (t, v)),
        out_shape=jax.ShapeDtypeStruct((T, V), logits2.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(logits2, lbl, lse_l, g_l)


_bwd_tracked = kreg.TrackedKernel(_bwd_pallas, kreg.XENT_BWD_SURFACE)


def _xent_bwd(res, g):
    logits2, labels, lse = res
    T, V = logits2.shape
    bv = _pick_bv(V)
    use, interp = _select()
    if not use or bv is None:
        p = jnp.exp(logits2.astype(jnp.float32) - lse[:, None])
        safe = jnp.maximum(labels, 0)
        onehot = jax.nn.one_hot(safe, V, dtype=jnp.float32)
        valid = (labels >= 0).astype(jnp.float32)
        dlg = (p - onehot) * (g * valid)[:, None]
        return dlg.astype(logits2.dtype), None
    return _per_row_shard(
        functools.partial(_bwd_rows, bv=bv, V=V, interp=interp),
        lambda P, t: P(t, None), logits2, labels, lse, g), None


def _bwd_rows(logits2, labels, lse, g, *, bv, V, interp):
    lg_p, lb_p, T0 = _pad_rows(logits2, labels.astype(jnp.int32))
    Tp = lg_p.shape[0]
    lbl = _lane_col(lb_p, Tp)
    lse_l = _lane_col(jnp.pad(lse, (0, Tp - T0)), Tp)
    g_l = _lane_col(jnp.pad(g.astype(jnp.float32), (0, Tp - T0)), Tp)
    dlg = _bwd_tracked(lg_p, lbl, lse_l, g_l, bv=bv, V=V, interpret=interp)
    return dlg[:T0]


fused_softmax_xent.defvjp(_xent_fwd, _xent_bwd)
