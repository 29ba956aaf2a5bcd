"""Paged decode attention: one new token a slot attends over the slot's
LIVE pages, read in the pool through the block table.

The serving engine keeps each layer's keys and values in a page pool
``(num_pages, page_size, nKV, D)`` and a per-slot block table
(``inference/kvcache.py``).  The plain form of attention over that cache
gathers every slot's table into a dense ``(B, MAX, nKV, D)`` buffer and
attends over all ``MAX`` keys under a mask; at the chat cell's size that
is 3.2 GB written and read again a decode step for about 220 live keys a
slot of 2,048.  This kernel builds nothing of width ``MAX``:

- the pools stay in HBM (``memory_space=ANY``) in the pool's own layout.
  A page is one contiguous ``(page_size, nKV, D)`` slab holding every kv
  head, so one async copy brings a page's keys for all heads into VMEM
  and one its values;
- **work follows ``lengths``**: slot ``b`` has ``ceil(lengths[b] /
  page_size)`` live pages; a page at or past that is neither copied nor
  computed, and the tail of the last page is masked by position.  A slot
  of length 0 costs nothing and returns zeros;
- the live pages of ALL slots form one stream.  A ring of ``RING`` page
  buffers keeps ``RING - 1`` copies in flight ahead of the page being
  computed, across slot boundaries (the grid is one program a slot; the
  ring's cursor lives in SMEM scratch and survives from one program to
  the next), so a slot's first page was asked for while the slot before
  it was still computing;
- online softmax in float32 over the stream of pages, every kv head at
  once: a token's ``(nKV, D)`` tile is multiplied by the query tile and
  reduced along lanes (one query row a kv head has no use for the MXU).
  Grouped heads (``nH = G * nKV``) reuse the page in VMEM ``G`` times.

``table`` and ``lengths`` are scalar-prefetch arguments (SMEM).  The
kernel tiles heads of ``D % 128 == 0`` in pools of ``nKV % 8 == 0`` kv
heads, bfloat16 or float32, at any page size; :func:`unsupported` is the
whole rule and ``models/gpt.py`` ``_cached_attention`` the one
dispatch site (docs/kernels.md "Paged decode attention").
"""
import functools
import math
from collections import namedtuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import registry as kreg

__all__ = ["paged_attention", "select", "unsupported", "KERNEL"]

KERNEL = "paged_attention"

# page buffers a pool: the copies in flight ahead of the compute, plus the
# page being read (4, 8 and 16 time the same on a v5e: PERF.md, PR 36)
RING = 8
_NEG = -1e30


def _kernel(table_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
            kbuf, vbuf, ksem, vsem, cur, *, page_size, scale):
    b = pl.program_id(0)
    B = len_ref.shape[0]
    ring = kbuf.shape[0]
    G, nKV, D = q_ref.shape

    def pages_of(s):
        return (len_ref[s] + (page_size - 1)) // page_size

    def next_live(s):
        """The first slot >= s with a live page, or B."""
        def skip(_, x):
            empty = len_ref[jnp.minimum(x, B - 1)] == 0
            return jnp.where((x < B) & empty, x + 1, x)
        return lax.fori_loop(0, B, skip, s)

    def fetch(slot):
        """Start the copies of the cursor's page into ring slot ``slot``
        and move the cursor to the next live page of the stream."""
        fb, fj = cur[0], cur[1]

        @pl.when(fb < B)
        def _():
            pid = table_ref[fb, fj]
            pltpu.make_async_copy(k_hbm.at[pid], kbuf.at[slot],
                                  ksem.at[slot]).start()
            pltpu.make_async_copy(v_hbm.at[pid], vbuf.at[slot],
                                  vsem.at[slot]).start()
            last = fj + 1 >= pages_of(fb)
            cur[0] = lax.cond(last, lambda: next_live(fb + 1), lambda: fb)
            cur[1] = jnp.where(last, 0, fj + 1)

    @pl.when(b == 0)
    def _():
        cur[0] = next_live(jnp.int32(0))
        cur[1] = jnp.int32(0)
        cur[2] = jnp.int32(0)
        for i in range(ring - 1):
            fetch(i)

    length = len_ref[b]
    q = q_ref[...].astype(jnp.float32) * scale              # (G, nKV, D)

    def body(j, carry):
        c = cur[2]
        # the slot refilled is the one the page before this was read from
        fetch(lax.rem(c + (ring - 1), ring))
        slot = lax.rem(c, ring)
        cur[2] = c + 1
        live = (j * page_size + lax.broadcasted_iota(
            jnp.int32, (page_size, nKV, 1), 0)) < length
        pltpu.make_async_copy(k_hbm.at[0], kbuf.at[slot],
                              ksem.at[slot]).wait()
        k = kbuf[slot].astype(jnp.float32)                  # (P, nKV, D)
        pltpu.make_async_copy(v_hbm.at[0], vbuf.at[slot],
                              vsem.at[slot]).wait()
        v = vbuf[slot].astype(jnp.float32)
        out = []
        for g in range(G):
            m, l, acc = carry[g]                            # (nKV, D) each
            s = jnp.sum(k * q[g][None], axis=-1, keepdims=True)
            s = jnp.where(live, s, _NEG)                    # (P, nKV, 1)
            m_new = jnp.maximum(m, jnp.max(s, axis=0))      # (nKV, D)
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[None])                    # (P, nKV, D)
            l = alpha * l + jnp.sum(p, axis=0)
            acc = alpha * acc + jnp.sum(p * v, axis=0)
            out.append((m_new, l, acc))
        return tuple(out)

    zero = jnp.zeros((nKV, D), jnp.float32)
    init = tuple((jnp.full((nKV, D), _NEG, jnp.float32), zero, zero)
                 for _ in range(G))
    done = lax.fori_loop(0, pages_of(b), body, init)
    for g in range(G):
        _, l, acc = done[g]
        # a slot of length 0 has l == 0: zeros, not 0 / 0
        o_ref[g] = (acc / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, table, lengths, *,
                    interpret=False):
    """Attention of one query row a slot over its live pages.

    ``q`` ``(B, nH, D)``; ``k_pages`` / ``v_pages`` ``(num_pages,
    page_size, nKV, D)`` with ``nH % nKV == 0`` (query head ``h`` reads kv
    head ``h // (nH // nKV)``, as ``jnp.repeat`` of the kv heads would
    give); ``table`` ``(B, n_pages)`` int32 physical page ids; ``lengths``
    ``(B,)`` int32, the live keys of each slot, at most ``n_pages *
    page_size``.  Scale ``1 / sqrt(D)``, softmax in float32.  Returns
    ``(B, nH, D)`` in ``q``'s dtype; a slot of length 0 returns zeros."""
    B, nH, D = q.shape
    _, page_size, nKV, _ = k_pages.shape
    G = nH // nKV
    # (B, G, nKV, D): a group's query rows line up with the page's heads
    qg = q.reshape(B, nKV, G, D).swapaxes(1, 2)
    block = pl.BlockSpec((None, G, nKV, D), lambda b, *_: (b, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=page_size,
                          scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[block,
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=block,
            scratch_shapes=[
                pltpu.VMEM((RING, page_size, nKV, D), k_pages.dtype),
                pltpu.VMEM((RING, page_size, nKV, D), v_pages.dtype),
                pltpu.SemaphoreType.DMA((RING,)),
                pltpu.SemaphoreType.DMA((RING,)),
                pltpu.SMEM((3,), jnp.int32),
            ]),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        # the ring's cursor runs from one program to the next
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_attention",
    )(table.astype(jnp.int32), lengths.astype(jnp.int32), qg,
      k_pages, v_pages)
    return out.swapaxes(1, 2).reshape(B, nH, D)


def unsupported(q_shape, pool_shape, pool_dtype):
    """Why the kernel does not tile this call (a fallback reason), or
    None.  ``q_shape`` is ``(B, S, nH, D)``, ``pool_shape`` the pool's
    ``(num_pages, page_size, nKV, D)``."""
    _, _, nH, D = q_shape
    nKV = pool_shape[2]
    if D % 128:
        return "head-dim"
    if jnp.dtype(pool_dtype) not in (jnp.dtype(jnp.bfloat16),
                                     jnp.dtype(jnp.float32)):
        return "cache-dtype"
    if nH % nKV or nKV % 8:
        return "kv-heads"
    return None


Kernel = namedtuple("Kernel", ["use", "interpret"])


def select(q_shape, cache, has_mask):
    """The dispatch decision for one cached-attention call over a paged
    cache, made at trace time on what the call can see: ``q_shape``
    ``(B, S, nH, D)``, the ``PagedCacheView`` and whether an extra key
    mask came with it.  The registry gives the platform's pick; the
    ladder below says what the kernel can express.
    ``pt_kernel_selects_total`` books the form that RUNS (``pallas`` is
    the kernel engaged, ``xla`` the gather path), and a call the
    platform would have given the kernel books why it did not get it in
    ``pt_kernel_fallbacks_total``."""
    sel = kreg.choose(KERNEL, book=False)
    reason = None
    if sel.impl == "pallas":
        if q_shape[1] != 1:
            reason = "multi-token"      # prefill, the speculative verify
        elif cache.k_scales is not None:
            reason = "int8-kv"
        elif has_mask:
            reason = "mask"
        elif kreg.current_partition() is not None:
            reason = "partitioned"
        else:
            reason = unsupported(q_shape, tuple(cache.k_pages.shape),
                                 cache.k_pages.dtype)
        if reason is not None:
            kreg.record_fallback(KERNEL, reason)
    use = sel.impl == "pallas" and reason is None
    kreg.record_select(KERNEL, "pallas" if use else "xla")
    return Kernel(use, bool(use and sel.interpret))


kreg.register(KERNEL, "pallas", paged_attention, platforms=("tpu",))
kreg.register(KERNEL, "xla", platforms=("*",))
