"""Ring flash attention + Ulysses all-to-all attention over a sequence-
parallel mesh axis.

Reference analogue: the "sep" segment-parallel axis in
python/paddle/distributed/fleet/base/topology.py (Ulysses-style alltoall
head<->seq reshard); ring attention with KV rotation is PaddleNLP-level in
the reference era and is made first-class here (SURVEY.md §5.7).

TPU-native design: both run INSIDE shard_map over the "sep" axis.
- Ring: each device holds a sequence chunk of q/k/v; KV chunks rotate
  around the ICI ring via ``lax.ppermute`` while each step folds one KV
  block into a blockwise online-softmax accumulator (the flash combine:
  running max ``m``, normalizer ``l``, unnormalized accumulator ``acc``).
  XLA's latency-hiding scheduler overlaps the permute with the block
  matmuls, so the ring rides ICI concurrently with MXU work.
- Ulysses: one ``lax.all_to_all`` reshards (seq-sharded, full heads) ->
  (full seq, head-sharded), full attention runs locally (flash kernel on
  TPU), and a second all_to_all reshards back.  Communication is O(S*H*D /
  sep) per device vs ring's O(S*2*H*D) but requires sep | num_heads.

Both are pure functions on raw jnp arrays in paddle's (B, S, H, D) layout;
the framework-level wrappers live in
paddle_tpu.distributed.fleet.utils.sep_utils.
"""
import math

import jax
import jax.numpy as jnp
from jax import lax

__all__ = ["ring_flash_attention", "ulysses_attention"]

_NEG_INF = -1e30


def _repeat_kv(q, k, v):
    H, Hk = q.shape[2], k.shape[2]
    if Hk != H:  # MQA/GQA: repeat kv heads
        k = jnp.repeat(k, H // Hk, axis=2)
        v = jnp.repeat(v, H // Hk, axis=2)
    return k, v


def ring_flash_attention(q, k, v, axis_name, causal=False, scale=None):
    """Blockwise ring attention; call inside shard_map with q/k/v sharded
    on the sequence dim (dim 1) over ``axis_name``.

    q: (B, S_local, H, D); k/v: (B, S_local, H_kv, D).  Returns
    (B, S_local, H, D) — the exact softmax attention over the full
    sequence, computed without ever materializing full K/V on one device.
    """
    size = lax.axis_size(axis_name)
    rank = lax.axis_index(axis_name)
    B, Sl, H, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    qf = (q.astype(jnp.float32) * scale)
    perm = [(j, (j + 1) % size) for j in range(size)]
    qi = jnp.arange(Sl)[:, None]  # local q positions
    ki = jnp.arange(Sl)[None, :]

    def step(i, carry):
        kc, vc, acc, m, l = carry
        src = (rank - i) % size  # origin rank of the KV chunk held now
        # GQA/MQA heads repeat LOCALLY per step: the ring carries the
        # narrow (H_kv) chunks so each ICI hop moves H_kv/H of the bytes
        kr, vr = _repeat_kv(q, kc, vc)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kr.astype(jnp.float32))
        if causal:
            # global positions: q at rank*Sl + qi, k at src*Sl + ki
            keep = (rank * Sl + qi) >= (src * Sl + ki)
            s = jnp.where(keep, s, _NEG_INF)
        m_s = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_s)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vr.astype(jnp.float32))
        # rotate KV one hop around the ring for the next step
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return kc, vc, acc, m_new, l

    # carry inits derive from qf so they inherit ALL of q's device-varying
    # mesh axes (not just the sep axis) — on a 2-D dp×sep mesh a bare
    # jnp.zeros carry fails shard_map's varying-manual-axes check
    q_bhsd = jnp.swapaxes(qf, 1, 2)                 # (B,H,Sl,D)
    acc0 = q_bhsd * 0.0
    m0 = q_bhsd[..., :1] * 0.0 + _NEG_INF
    l0 = q_bhsd[..., :1] * 0.0
    _, _, acc, _, l = lax.fori_loop(
        0, size, step, (k, v, acc0, m0, l0), unroll=True)
    o = acc / jnp.maximum(l, 1e-30)
    return jnp.swapaxes(o, 1, 2).astype(q.dtype)  # (B,H,Sl,D)->(B,Sl,H,D)


def ulysses_attention(q, k, v, axis_name, causal=False, scale=None,
                      attention_fn=None):
    """DeepSpeed-Ulysses style sep attention; call inside shard_map with
    q/k/v sharded on the sequence dim (dim 1) over ``axis_name``.

    all_to_all reshards to head-sharded/full-sequence, runs dense (flash)
    attention locally, reshards back.  Requires sep | H and sep | H_kv.
    """
    size = lax.axis_size(axis_name)
    if q.shape[2] % size or k.shape[2] % size:
        raise ValueError(
            f"ulysses requires sep axis size {size} to divide num heads "
            f"{q.shape[2]} (kv {k.shape[2]})")

    def seq_to_head(x):  # (B, S/sep, H, D) -> (B, S, H/sep, D)
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    q, k, v = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    if attention_fn is None:
        # flash-capable core: Pallas blockwise kernel on TPU for long S
        # (which is exactly the regime sep parallelism serves), XLA path
        # elsewhere, with the recompute-based backward; the registry
        # decides per-shard (the local S/D after the reshard)
        from ..nn.functional.attention import (_attention_core,
                                               _select_flash)

        def attention_fn(a, b, c):
            sel = _select_flash(a.shape[1], b.shape[1], a.shape[3],
                                bool(causal), has_mask=False,
                                mask_is_keybias=False, scale=scale,
                                heads=(a.shape[2], b.shape[2]))
            return _attention_core(a, b, c, bool(causal), scale, sel)
    o = attention_fn(q, k, v)
    # (B, S, H/sep, D) -> (B, S/sep, H, D)
    return lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)
