"""Block-paged KV-cache subsystem for the serving engine (reference:
``block_multihead_attention`` paged KV decode over fixed-size
``cache_kvs`` blocks, plus the inference Predictor's block tables).

The PR 4 engine preallocates a dense ``(S, MAX, nH, D)`` KV buffer per
layer — HBM scales with the *worst-case* sequence length whether or not
any slot ever reaches it, and two requests sharing a system prompt each
re-prefill it from scratch.  This module replaces that with the paged
formulation:

- **Page pool** — per layer, one fixed ``(num_pages, page_size, nH, D)``
  buffer for K and one for V (plus fp32 per-token scale planes in int8
  mode).  Physical page 0 is the *trash page*: never allocated, the
  scatter target for inactive slots and out-of-range pad writes, and the
  gather source for unmapped page-table entries (its contents are always
  model outputs, so reads stay finite and are masked out of attention
  anyway).
- **Host-side allocator** (:class:`PagedKVManager`) — a free-list over
  pages 1..num_pages-1 with per-page refcounts; per-slot page tables map
  logical pages (position // page_size) to physical pages and travel to
  device as one small int32 array per dispatch.
- **Paged attention inside the cached-attention path** — when
  ``gpt._cached_attention`` receives a :class:`PagedCacheView` instead
  of a dense ``(k_buf, v_buf)`` pair, attention over it is the kernel
  registry's ``"paged_attention"`` in one of two forms.  The plain form
  (``"xla"``) gathers the slot's pages into the same ``(B, MAX, nH, D)``
  working buffer the dense path uses, runs the *identical*
  write/mask/attention math, and scatters the newly written positions
  back to the pool.  Identical math over identical values is what keeps
  that form's greedy decode **bitwise-identical** to the dense engine
  and to ``generate()`` (tests/test_kvcache.py asserts the full chain);
  it is what runs off the chip and in every prefill.  A decode step on a
  TPU over a full-precision pool takes the Pallas form instead
  (``ops/pallas/paged_attention.py``): the step's row is scattered into
  the pool, then the query attends over the slot's live pages in place
  through the block table (:func:`live_lengths` says how many keys are
  live), within the tolerance docs/kernels.md states and not bit for
  bit.
- **Prefix cache** — page-aligned prompt prefixes are keyed by CHAINED
  per-page digests (``digest_j = sha256(digest_{j-1} || page_j)``), so
  building every prefix key of an n-token prompt is one O(n) pass
  instead of the old O(n²/page_size) whole-prefix byte keys; a hit
  still runs a full-content equality check against the stored prefix
  tokens, so a digest collision degrades to a miss and there are no
  hash-collision correctness holes.  Pages are refcount-shared
  copy-on-write: shared pages
  are only ever *read* (decode writes always land at positions past the
  shared prefix, in slot-private pages), so the "copy" never actually
  happens.  A hit skips recomputing the shared prefix: the suffix
  prefill runs the model over ``prompt[k:]`` only, at position offset
  ``k``, attending the cached pages through the same gather.  Causal
  attention is position-wise, so chunked prefill is bitwise-identical
  to cold prefill (same masked ``MAX``-wide reduction).
- **Layer kinds** — ``kv_cache_spec()`` describes a layer by kind.  A
  ``(nH, D)`` pair is the K pool and V pool above.  A
  ``generation.LatentCacheSpec(width)`` is a latent-attention layer:
  ONE pool ``(num_pages, page_size, width)`` holding, a token, the normed
  latent and the rotated key positions side by side, with no head axis
  and no V plane (:class:`LatentCacheView`, :func:`gather_latent`,
  :func:`scatter_latent`).  A page is a page: the allocator, the tables,
  the prefix cache, ``plan`` / ``bind`` / ``release`` and the handoff's
  export/import with its CRCs do not know the kind; the pools, the page
  bytes and the views follow it.  int8 KV is refused with a latent
  layer (its scale group is one token's ``nH x D`` block).
- **int8 KV** (opt-in, the serving sibling of
  ``quantization.weight_only_quantize``) — pool pages store int8 with
  one fp32 absmax scale per token row (chunkwise absmax over that
  token's ``nH x D`` values, the grad_comm/EQuARX scale discipline:
  ``scale = max(absmax, 1e-30)/127``, round-to-nearest, clip to
  ±127).  Element error is bounded by ``scale/2``; the end-to-end
  logit tolerance is documented in docs/serving.md and pinned by
  tests/test_kvcache.py.  Writes quantize, gathers dequantize; the
  in-flight working buffer stays in the compute dtype, so the *prefill*
  logits of a request are still bitwise-exact (quantization error only
  enters when later steps re-read the pool).

Engine integration (``ServingEngine(kv_mode="paged", ...)``): admission
reserves pages instead of a dense slot row, decode carries the page
tables as device state through the compiled ``lax.scan``, and page
pressure preempts the youngest in-flight request back to the queue
(its pages are freed; on re-admission it resumes by *recompute* — the
prompt plus the already-streamed tokens re-prefill as one prompt, which
is bitwise-equivalent to having never been evicted, so the parity
contract survives preemption).  See docs/serving.md.
"""
import collections
import functools
import hashlib
import threading
import zlib
from typing import Any, NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from ..analysis import register_jit_surface
from .. import observability as _obs
from ..models.generation import cache_kind
from ..observability import devcounters as _devc
from ..observability.tracing import scope as _scope

__all__ = ["KVBundleError", "PagedCacheView", "LatentCacheView",
           "PagedKVManager",
           "quantize_kv", "dequantize_kv",
           "chained_page_digests", "prefix_affinity_key"]

# the compiled bodies are nested defs a decorator can't reach —
# registered for the tracer-safety pass (mirrored by EXTRA_JIT_SURFACES
# in paddle_tpu/analysis/allowlist.py)
for _qual in ("_build_paged_prefill.paged_prefill",
              "_build_paged_decode_chunk.paged_decode_chunk"):
    register_jit_surface(__name__, _qual)

# compile-telemetry surface names (observability/compilestats.py) —
# declared HERE, beside the builders, so the cost/retrace vocabulary
# stays in sync with the registration above.  The engine wraps one
# prefill per bucket (budget 1 each: the suffix offset is a traced
# scalar, so one bucket legitimately owns exactly one compile) and one
# decode chunk (budget 1: its state shapes are fixed at construction).
PREFILL_SURFACE = "serving.paged_prefill"
DECODE_SURFACE = "serving.paged_decode_chunk"


class KVBundleError(ValueError):
    """An exported KV bundle failed integrity verification on import —
    torn shape, missing manifest, or a per-page CRC32 mismatch.  Raised
    BEFORE any page touches the importing pool, so the handoff protocol
    can reject the bundle whole and fall back to recompute."""


def _page_crcs(layers):
    """Per-page CRC32 over an export payload's host arrays: page ``i``'s
    checksum chains every layer's every buffer (K, V and — in int8
    mode — the scale planes) for that page, in layer/buffer order.  The
    checkpoint-shard integrity discipline (PR 1) applied to the KV
    wire: a torn or bit-flipped page cannot silently enter a pool."""
    if not layers:
        return []
    n = int(layers[0][0].shape[0])
    crcs = []
    for i in range(n):
        c = 0
        for pools in layers:
            for buf in pools:
                c = zlib.crc32(np.ascontiguousarray(buf[i]).tobytes(), c)
        crcs.append(c & 0xFFFFFFFF)
    return crcs


def _allocator_locked(fn):
    """Serialize a :class:`PagedKVManager` host-side mutator under the
    manager's RLock.  The allocator was engine-thread-private until the
    handoff protocol (inference/handoff.py): now the router thread
    reserves/cancels reservation pages while a decode worker plans,
    binds and releases — free list, refcounts, prefix-cache OrderedDict
    and the reservation table are all shared mutable state, and the
    prefix cache's LRU iteration in particular must never interleave
    with a reclaim.  RLock (not Lock) because locked methods call each
    other (``plan`` -> ``_alloc`` via ``import_pages``-style nesting is
    fine either way, but ``clear_prefix`` under a locked caller must
    not deadlock)."""
    @functools.wraps(fn)
    def wrapper(self, *args, **kwargs):
        with self._lock:
            return fn(self, *args, **kwargs)
    return wrapper


class PagedCacheView(NamedTuple):
    """One layer's paged KV cache as it travels through the model's
    cached-attention path: the page pools, optional int8 scale planes
    (``None`` in full-precision mode), and the per-slot page table
    ``(B, MAX // page_size)``.  A NamedTuple so jax treats it as a
    pytree and the tracer-safety pass can tell it from the dense
    ``(k_buf, v_buf)`` pair via ``hasattr(cache, "_fields")`` (a
    static, taint-stopping check)."""
    k_pages: Any
    v_pages: Any
    k_scales: Any
    v_scales: Any
    table: Any


class LatentCacheView(NamedTuple):
    """One latent layer's paged cache as it travels through the model:
    the single pool ``(num_pages, page_size, width)`` and the per-slot
    page table.  What the model does with it is
    ``models/mla_moe.py``'s; the pool's bookkeeping is the manager's."""
    pages: Any
    table: Any


# -- prefix keys (host-side, shared with the fleet router) -----------------

def chained_page_digests(prompt, page_size):
    """Chained per-page sha256 digests of every page-aligned prefix of
    ``prompt`` (``digest_j = sha256(digest_{j-1} || page_j bytes)``):
    ``keys[j-1]`` keys the first ``j`` pages.  One O(len(prompt)) pass —
    THE prefix-key primitive, shared by the prefix cache
    (:meth:`PagedKVManager._page_keys`) and the router's
    :func:`prefix_affinity_key` so the two can never disagree about
    what "the same prefix" means."""
    P = int(page_size)
    h, keys = hashlib.sha256(), []
    for j in range(len(prompt) // P):
        h.update(prompt[j * P:(j + 1) * P].tobytes())
        keys.append(h.digest())
    return keys


def prefix_affinity_key(prompt, page_size, max_pages=4):
    """O(1)-sized routing key for prefix-affinity (inference/router.py):
    the chained digest of the request's first ``min(max_pages, full
    pages)`` prompt pages.  Requests sharing a system prompt of at
    least ``max_pages * page_size`` tokens map to the same key, so the
    router can land them on the replica whose prefix cache already
    holds those pages.  Returns ``None`` when the prompt has no full
    page (nothing page-aligned to share — route by load instead).

    Capping at ``max_pages`` is deliberate: affinity only needs to
    agree on the SHARED head (the system prompt), and hashing the whole
    prompt would split requests whose suffixes differ."""
    prompt = np.asarray(prompt, np.int32).reshape(-1)
    P = int(page_size)
    j = min(int(prompt.size) // P, int(max_pages))
    if j < 1:
        return None
    h = hashlib.sha256()
    h.update(prompt[:j * P].tobytes())
    return h.hexdigest()


# -- pure-jnp kernels (called inside the compiled prefill/decode) ----------

def quantize_kv(x):
    """Per-token chunkwise absmax int8 quantization: ``x`` is
    ``(..., nH, D)``; the scale group is one token's ``nH x D`` block
    (the grad_comm/EQuARX discipline).  Returns ``(q int8, scale f32)``
    with ``scale`` shaped like ``x`` minus the last two axes."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=(-2, -1))
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None, None]),
                 -127.0, 127.0).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32)
            * scale[..., None, None]).astype(dtype)


def _positions(p, S):
    """Absolute write positions for this step as a (B, S) grid (scalar
    ``pos`` broadcasts across the batch; vector ``pos`` is per-slot)."""
    p = p.astype(jnp.int32)
    if p.ndim:
        return p[:, None] + jnp.arange(S)
    return jnp.broadcast_to(p + jnp.arange(S), (1, S))


def gather_pages(kp, vp, table):
    """Materialize the dense ``(B, MAX, nH, D)`` working buffers from
    the pool: ``table`` is ``(B, n_pages)``; ``MAX = n_pages *
    page_size``.  Unmapped entries point at the trash page — their
    values are finite model outputs and the attention mask zeroes their
    weight, so they contribute exactly 0 (same as the dense path's
    never-written zeros)."""
    B = table.shape[0]
    with _scope("kv.gather"):
        k = kp[table].reshape(B, -1, kp.shape[2], kp.shape[3])
        v = vp[table].reshape(B, -1, vp.shape[2], vp.shape[3])
    return k, v


def gather_pages_q(kp, vp, ks, vs, table, dtype):
    """int8 variant: dequantize with the per-token scale planes."""
    B = table.shape[0]
    with _scope("kv.gather"):
        k = dequantize_kv(kp[table], ks[table], dtype)
        v = dequantize_kv(vp[table], vs[table], dtype)
        return (k.reshape(B, -1, kp.shape[2], kp.shape[3]),
                v.reshape(B, -1, vp.shape[2], vp.shape[3]))


def _scatter_coords(table, pos, S, page_size):
    idx = _positions(pos, S)                        # (B, S) absolute
    B = table.shape[0]
    rows = jnp.arange(B)[:, None]
    MAX = table.shape[1] * page_size
    # positions past MAX (a speculative verify step's overhang near the
    # end of a slot's extent) must land in the trash page — the default
    # gather CLAMP would silently alias them onto the last mapped page
    lp = jnp.minimum(idx // page_size, table.shape[1] - 1)
    phys = jnp.where(idx < MAX, table[rows, lp], 0)  # (B, S) physical
    return phys, idx % page_size


def scatter_pages(kp, vp, k_new, v_new, table, pos):
    """Persist this step's freshly written K/V rows into the pool:
    positions ``pos..pos+S-1`` of each batch row land at
    ``(table[b, p // page_size], p % page_size)``.  Writes through an
    unmapped (trash) entry are discarded garbage by construction —
    inactive slots and pad positions beyond the allocated range."""
    S = k_new.shape[1]
    with _scope("kv.scatter"):
        phys, off = _scatter_coords(table, pos, S, kp.shape[1])
        kp = kp.at[phys, off].set(k_new.astype(kp.dtype))
        vp = vp.at[phys, off].set(v_new.astype(vp.dtype))
    return kp, vp


def live_lengths(table, pos, page_size):
    """The live keys of each slot at a decode step that has just written
    position ``pos``: ``pos + 1``, and 0 for a slot whose table row
    starts on the trash page.  Page 0 is never allocated, so a first
    entry of 0 is a slot with no page: an inactive slot of the decode
    chunk, whose stale ``pos`` counts nothing that is there."""
    B = table.shape[0]
    p = jnp.broadcast_to(pos.astype(jnp.int32), (B,))
    n = jnp.minimum(p + 1, table.shape[1] * page_size)
    return jnp.where(table[:, 0] == 0, 0, n)


def scatter_pages_q(kp, vp, ks, vs, k_new, v_new, table, pos):
    """int8 variant: quantize each token row and store value + scale."""
    S = k_new.shape[1]
    with _scope("kv.scatter"):
        phys, off = _scatter_coords(table, pos, S, kp.shape[1])
        qk, sk = quantize_kv(k_new)
        qv, sv = quantize_kv(v_new)
        kp = kp.at[phys, off].set(qk)
        vp = vp.at[phys, off].set(qv)
        ks = ks.at[phys, off].set(sk)
        vs = vs.at[phys, off].set(sv)
    return kp, vp, ks, vs


def gather_latent(pages, table):
    """A latent layer's ``(B, MAX, width)`` working buffer from its pool
    (``table`` is ``(B, n_pages)``); unmapped entries read the trash
    page, finite values that the attention mask weighs 0."""
    with _scope("kv.gather"):
        return pages[table].reshape(table.shape[0], -1, pages.shape[2])


def scatter_latent(pages, new, table, pos):
    """Persist this step's latent rows ``new`` (B, S, width) at
    positions ``pos..pos+S-1``, as :func:`scatter_pages` does."""
    with _scope("kv.scatter"):
        phys, off = _scatter_coords(table, pos, new.shape[1],
                                    pages.shape[1])
        return pages.at[phys, off].set(new.astype(pages.dtype))


def _layer_views(pools, table, quant):
    """One view a layer from the pools, by the layer's kind: a latent
    layer's pool is one plane, a K/V layer's two (four with int8
    scales)."""
    views = []
    for planes in pools:
        if len(planes) == 1:
            views.append(LatentCacheView(planes[0], table))
        elif quant:
            views.append(PagedCacheView(*planes, table))
        else:
            views.append(PagedCacheView(*planes, None, None, table))
    return views


def _layer_pools(views, quant):
    return [(c.pages,) if isinstance(c, LatentCacheView)
            else (c.k_pages, c.v_pages, c.k_scales, c.v_scales) if quant
            else (c.k_pages, c.v_pages) for c in views]


# -- compiled bodies -------------------------------------------------------

def _build_paged_prefill(apply, pick, eos, quant):
    """Compiled paged prefill for one suffix-length bucket: run the
    model over the right-padded ``(1, bucket)`` suffix at position
    offset ``start`` (0 cold; the cached-prefix length on a prefix-cache
    hit), attending any shared prefix pages through the paged gather,
    pick the first generated token at the last *real* suffix position,
    and arm the slot's decode state.  KV lands in the slot's pages via
    the in-attention scatter — nothing here touches a dense slot row.
    The last output is what the model's layers counted on the device
    (``observability.devcounters``; empty for a model that counts
    nothing, and then no operation of the program)."""
    def paged_prefill(pv, ids, start, length, slot, budget,
                      tokens, pos, active, remaining, pools, table):
        row = jax.lax.dynamic_slice_in_dim(table, slot, 1, axis=0)
        caches = _layer_views(pools, row, quant)
        real = (jnp.arange(ids.shape[1]) < length)[None, :]
        with _devc.collect("prefill", rows=real) as bag:
            if apply.takes_last:
                # the model applies its head to the one position picked
                logits, new = apply(pv, ids, caches, start,
                                    last=length - 1)
                last = logits[:, 0]                         # (1, V)
            else:
                logits, new = apply(pv, ids, caches, start)
                last = jax.lax.dynamic_slice_in_dim(
                    logits, length - 1, 1, axis=1)[:, 0]    # (1, V)
        pools = _layer_pools(new, quant)
        with _scope("sample"):
            t0, _ = pick(last, jax.random.key(0))           # (1,)
        t0 = t0[0]
        hit_eos = (t0 == eos) if eos is not None else jnp.asarray(False)
        fin0 = hit_eos | (budget <= 1)
        tokens = tokens.at[slot].set(t0)
        pos = pos.at[slot].set(start + length)
        active = active.at[slot].set(~fin0)
        remaining = remaining.at[slot].set(budget - 1)
        return (t0, fin0, tokens, pos, active, remaining, pools,
                bag.totals())
    return paged_prefill


def _build_paged_decode_chunk(apply, pick, chunk, eos, pad, quant):
    """Compiled paged decode over ``chunk`` tokens for all S slots: the
    dense engine's masked-finish scan body verbatim, except each step's
    KV travels through the page pool (gather -> identical attention ->
    scatter, or on a TPU scatter -> attention over the live pages in
    place).  Inactive slots have their page-table row redirected to
    the trash page so a freed-and-reassigned page can never be
    corrupted by a stale slot's ride-along writes (and the paged kernel
    reads nothing for them)."""
    def paged_decode_chunk(pv, tokens, pos, active, remaining, pools,
                           table):
        def body(carry, _):
            tokens, pos, active, remaining, pools = carry
            safe = jnp.where(active[:, None], table, 0)
            caches = _layer_views(pools, safe, quant)
            with _devc.collect("decode", rows=active[:, None]) as bag:
                logits, new = apply(pv, tokens[:, None], caches, pos)
            pools = _layer_pools(new, quant)
            with _scope("sample"):
                nxt, _ = pick(logits[:, 0, :], jax.random.key(0))
                nxt = jnp.where(active, nxt, jnp.int32(pad))
            emitted = active
            live = active.astype(jnp.int32)
            pos = pos + live
            remaining = remaining - live
            hit_eos = (nxt == eos) if eos is not None \
                else jnp.zeros_like(active)
            done = active & (hit_eos | (remaining <= 0))
            tokens = jnp.where(active, nxt, tokens)
            active = active & ~done
            return (tokens, pos, active, remaining, pools), \
                (nxt, emitted, bag.totals())
        carry = (tokens, pos, active, remaining, pools)
        (tokens, pos, active, remaining, pools), (toks, valid, counts) = \
            jax.lax.scan(body, carry, None, length=chunk)
        # the steps' device counters, folded over the chunk
        counts = {"sum": {k: v.sum() for k, v in counts["sum"].items()},
                  "max": {k: v.max() for k, v in counts["max"].items()}}
        return tokens, pos, active, remaining, pools, toks, valid, counts
    return paged_decode_chunk


# -- host-side page management ---------------------------------------------

class PagedKVManager:
    """Host-side page allocator + prefix cache + device pool owner.

    All methods run on host between compiled dispatches; none reads the
    device (the module sits in ``analysis.allowlist.MONITORED_MODULES``
    so any sync primitive appearing here must be budgeted).  The
    ``admission-time`` np ingest below is the one budgeted site.

    Page lifecycle: every physical page (1..num_pages-1) is either on
    the free list (refcount 0) or referenced by slot page-table
    mappings and/or prefix-cache entries (refcount = number of such
    holders).  ``check()`` asserts the invariant and is exercised by
    tests/test_kvcache.py.
    """

    def __init__(self, spec, num_slots, max_seq_len, page_size,
                 num_pages, cache_dtype, kv_dtype=None,
                 prefix_cache=True, max_prefix_entries=1024):
        if page_size < 1:
            raise ValueError("page_size must be >= 1")
        if max_seq_len % page_size:
            raise ValueError(
                f"page_size {page_size} must divide max_seq_len "
                f"{max_seq_len} (the paged gather must reproduce the "
                "dense MAX-wide attention for bitwise parity)")
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r} "
                             "(int8 or None)")
        self.spec = list(spec)
        self.kinds = [cache_kind(layer) for layer in self.spec]
        if kv_dtype == "int8" and "latent" in self.kinds:
            raise ValueError(
                "kv_dtype='int8' is not supported with a latent cache "
                "layer (the int8 scale group is one token's nH x D "
                "block of a K/V layer)")
        self.num_slots = int(num_slots)
        self.MAX = int(max_seq_len)
        self.page_size = int(page_size)
        self.pages_per_slot = self.MAX // self.page_size
        if num_pages is None:
            # roomy default: every slot can reach MAX (no pressure) —
            # the memory win then comes from sizing num_pages DOWN
            num_pages = self.num_slots * self.pages_per_slot + 1
        self.num_pages = int(num_pages)
        if self.num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is the "
                             "reserved trash page)")
        self.cache_dtype = cache_dtype
        self.quant = kv_dtype == "int8"
        self.prefix_enabled = bool(prefix_cache)
        self.max_prefix_entries = int(max_prefix_entries)
        # per-token bytes across all layers (K+V [+ scales], or the one
        # latent plane) for the resident-bytes gauge
        elt = jnp.dtype("int8" if self.quant else cache_dtype).itemsize
        per_tok = sum(layer.width * elt if kind == "latent"
                      else 2 * layer[0] * layer[1] * elt
                      for layer, kind in zip(self.spec, self.kinds))
        if self.quant:
            per_tok += 2 * 4 * len(self.spec)        # fp32 scale per row
        self.page_bytes = per_tok * self.page_size
        self.stats = None
        # cross-thread boundary (ISSUE 16): the router thread calls
        # reserve_pages/cancel_reservation while the engine worker
        # plans/binds/releases — every public host-side mutator runs
        # under this RLock (@_allocator_locked)
        self._lock = threading.RLock()
        self.reset()

    # -- device state ------------------------------------------------------
    @_allocator_locked
    def reset(self):
        """(Re)build zeroed pools and empty allocator/prefix state; the
        engine's compiled programs are keyed on shapes, so a reset never
        retraces."""
        N, P = self.num_pages, self.page_size
        if self.quant:
            self._pools = [
                (jnp.zeros((N, P, nh, d), jnp.int8),
                 jnp.zeros((N, P, nh, d), jnp.int8),
                 jnp.zeros((N, P), jnp.float32),
                 jnp.zeros((N, P), jnp.float32))
                for nh, d in self.spec]
        else:
            self._pools = [
                (jnp.zeros((N, P, layer.width), self.cache_dtype),)
                if kind == "latent" else
                (jnp.zeros((N, P) + tuple(layer), self.cache_dtype),
                 jnp.zeros((N, P) + tuple(layer), self.cache_dtype))
                for layer, kind in zip(self.spec, self.kinds)]
        self.table = np.zeros((self.num_slots, self.pages_per_slot),
                              np.int32)
        self._free = list(range(self.num_pages - 1, 0, -1))
        self._ref = np.zeros(self.num_pages, np.int64)
        self._slot_pages = [dict() for _ in range(self.num_slots)]
        # chained per-page digest -> (pages tuple, prefix tokens).
        # digest_j = sha256(digest_{j-1} || page_j bytes), so building
        # every prefix key of an n-token prompt is ONE O(n) pass (the
        # old whole-prefix raw-byte keys were O(n^2/page_size)); the
        # stored token array backs a full-content equality check on hit,
        # keeping the no-collision-holes contract
        self._prefix = collections.OrderedDict()
        # handoff reservations (ISSUE 16): ticket -> page list, pages
        # held at refcount 1 between the protocol's reserve and import
        # phases.  Tracked by the allocator itself so check() stays the
        # one authority on where every page is — a leaked reservation
        # is a counted invariant violation, not invisible drift.
        self._reservations = {}
        self._next_ticket = 0
        self.stats = {"prefix_hits": 0, "prefix_misses": 0,
                      "prefix_saved_tokens": 0, "pages_evicted": 0,
                      "resident_high_water_bytes": 0,
                      "prefix_key_bytes_hashed": 0}
        self._gauges()
        # HBM ledger: the live-buffer census joins this pool's own
        # bookkeeping (weakref — a dropped engine unregisters itself)
        _obs.memory.register_kv_pool(self)

    def _page_keys(self, prompt):
        """Chained per-page digests for every page-aligned prefix of
        ``prompt``: ``keys[j-1]`` keys the first ``j`` pages.  One pass,
        O(len(prompt)) total — the stats counter machine-checks that
        admission-time key construction stays linear."""
        P = self.page_size
        keys = chained_page_digests(prompt, P)
        self.stats["prefix_key_bytes_hashed"] += \
            (len(prompt) // P) * P * prompt.itemsize
        return keys

    def device_pools(self):
        return self._pools

    def set_pools(self, pools):
        self._pools = pools

    # -- accounting --------------------------------------------------------
    @property
    def pages_in_use(self):
        return self.num_pages - 1 - len(self._free)

    @property
    def resident_bytes(self):
        return self.pages_in_use * self.page_bytes

    @property
    def pool_bytes(self):
        """Allocated pool footprint (all pages, resident or not)."""
        return self.num_pages * self.page_bytes

    def _gauges(self):
        rb = self.resident_bytes
        if rb > self.stats["resident_high_water_bytes"]:
            self.stats["resident_high_water_bytes"] = rb
        if _obs.enabled():
            _obs.set_gauge("pt_kvcache_pages_in_use", self.pages_in_use)
            _obs.set_gauge("pt_kvcache_resident_kv_bytes", rb)

    # -- allocator core ----------------------------------------------------
    def _incref(self, page):
        self._ref[page] += 1

    def _decref(self, page):
        self._ref[page] -= 1
        if self._ref[page] == 0:
            self._free.append(page)

    def _reclaim_one(self):
        """Drop the least-recently-used prefix-cache entry; its pages
        free as soon as no slot still maps them."""
        if not self._prefix:
            return False
        _, (pages, _) = self._prefix.popitem(last=False)
        for p in pages:
            self._decref(p)
        return True

    def _alloc(self, count):
        """Allocate ``count`` pages (refcount 1 each), reclaiming LRU
        prefix-cache entries under pressure; all-or-nothing — and
        nothing is reclaimed when reclaiming everything still could not
        satisfy the request (an oversized, FCFS-blocked admission must
        not wipe the prefix cache as a side effect of failing)."""
        if len(self._free) < count:
            prefix_refs = collections.Counter(
                p for pages, _ in self._prefix.values() for p in pages)
            reclaimable = sum(1 for p, c in prefix_refs.items()
                              if self._ref[p] == c)
            if len(self._free) + reclaimable < count:
                return None
        while len(self._free) < count:
            if not self._reclaim_one():
                return None
        pages = [self._free.pop() for _ in range(count)]
        for p in pages:
            self._incref(p)
        return pages

    # -- admission ---------------------------------------------------------
    def coverage_page(self, pos, budget, chunk):
        """Highest logical page a chunk of up to ``chunk`` tokens can
        write for a sequence whose next write lands at ``pos`` with
        ``budget`` tokens left — THE page-coverage arithmetic, shared
        by admission planning and the engine's between-chunk top-up so
        the two can never disagree."""
        hi = min(int(pos) + min(int(chunk), int(budget)), self.MAX) - 1
        return hi // self.page_size

    @_allocator_locked
    def plan(self, prompt, budget, chunk, fit=None):
        """Reserve pages for one admission WITHOUT binding a slot:
        longest page-aligned cached prefix (that ``fit`` accepts and
        leaves >= 1 suffix token), plus freshly allocated private pages
        covering the suffix and the first decode chunk.  Returns a plan
        dict, or None when the pool cannot serve it (the scheduler then
        keeps the request queued — FCFS head-of-line, no skip-ahead).

        The plan already holds page references; ``bind`` or ``abandon``
        must follow.
        """
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n, P = int(prompt.size), self.page_size
        k_pages, shared = 0, []
        keys = self._page_keys(prompt) if self.prefix_enabled else []
        if self.prefix_enabled:
            for j in range((n - 1) // P, 0, -1):
                ent = self._prefix.get(keys[j - 1])
                if ent is None:
                    continue
                if fit is not None and not fit(j * P):
                    continue
                pages, toks = ent
                # full-content check on hit: a digest collision must
                # degrade to a miss, never to sharing wrong KV
                if toks.size != j * P or \
                        not np.array_equal(toks, prompt[:j * P]):
                    continue
                k_pages, shared = j, list(pages)
                self._prefix.move_to_end(keys[j - 1])
                break
        # hold the hit pages BEFORE allocating: _alloc's LRU reclaim may
        # drop the hit entry itself, and without the plan's references
        # its pages would land on the free list and come back as
        # "fresh" — one physical page mapped at two logical positions
        for p in shared:
            self._incref(p)
        hi = self.coverage_page(n, budget, chunk)
        fresh = self._alloc(max(0, hi - k_pages + 1))
        if fresh is None:
            for p in shared:
                self._decref(p)
            return None
        return {"prompt": prompt, "k": k_pages * P,
                "pages": shared + fresh, "keys": keys}

    @_allocator_locked
    def abandon(self, plan):
        """Release a plan that never got bound (admission raced away)."""
        for p in plan["pages"]:
            self._decref(p)
        self._gauges()

    @_allocator_locked
    def bind(self, slot, plan, register_limit=None):
        """Map a plan's pages into ``slot``'s page table and register
        this prompt's page-aligned prefixes (up to ``register_limit``
        tokens — the original prompt length on resume, so generated
        tokens never pollute the cache) for future sharing."""
        prompt, k = plan["prompt"], plan["k"]
        n, P = int(prompt.size), self.page_size
        row = self.table[slot]
        row[:] = 0
        mapping = self._slot_pages[slot]
        assert not mapping, f"slot {slot} bound while still mapped"
        for j, page in enumerate(plan["pages"]):
            row[j] = page
            mapping[j] = page
        if self.prefix_enabled:
            limit = n if register_limit is None else min(int(register_limit), n)
            keys = plan["keys"]
            for j in range(1, limit // P + 1):
                key = keys[j - 1]
                if key in self._prefix:
                    continue
                pages = tuple(int(row[i]) for i in range(j))
                for p in pages:
                    self._incref(p)
                # a VIEW, deliberately: every entry of this prompt
                # shares one base array, so registration keeps O(n)
                # bytes per prompt — per-entry copies would re-create
                # the quadratic admission cost this PR removed, just in
                # memcpy instead of hashing
                self._prefix[key] = (pages, prompt[: j * P])
            while len(self._prefix) > self.max_prefix_entries:
                self._reclaim_one()
        self.stats["prefix_hits" if k else "prefix_misses"] += 1
        self.stats["prefix_saved_tokens"] += k
        if _obs.enabled():
            _obs.inc("pt_kvcache_prefix_hits_total" if k
                     else "pt_kvcache_prefix_misses_total")
            if k:
                _obs.inc("pt_kvcache_prefix_saved_tokens_total", k)
        self._gauges()
        return k

    # -- steady state ------------------------------------------------------
    @_allocator_locked
    def ensure(self, slot, through_page):
        """Grow ``slot``'s mapping to cover logical pages
        ``<= through_page``; False when the pool is exhausted (the
        engine then evicts and retries)."""
        mapping = self._slot_pages[slot]
        through = min(int(through_page), self.pages_per_slot - 1)
        missing = [j for j in range(through + 1) if j not in mapping]
        if not missing:
            return True
        fresh = self._alloc(len(missing))
        if fresh is None:
            return False
        row = self.table[slot]
        for j, page in zip(missing, fresh):
            row[j] = page
            mapping[j] = page
        self._gauges()
        return True

    @_allocator_locked
    def clear_prefix(self):
        """Drop every prefix-cache entry (their pages free once no slot
        still maps them).  Called on ``refresh_weights``: cached-prefix
        KV was computed with the OLD parameters, and serving it after a
        weight swap would silently break the parity contract."""
        while self._reclaim_one():
            pass
        self._gauges()

    @_allocator_locked
    def release(self, slot, evicted=False):
        """Unmap a finished (or preempted) slot: private pages return to
        the free list; prefix-shared pages survive under their cache
        references.  Returns the number of pages this slot dropped."""
        mapping = self._slot_pages[slot]
        count = len(mapping)
        for page in mapping.values():
            self._decref(page)
        mapping.clear()
        self.table[slot][:] = 0
        if evicted:
            self.stats["pages_evicted"] += count
            if _obs.enabled():
                _obs.inc("pt_kvcache_page_evictions_total", count)
        self._gauges()
        return count

    # -- disaggregation seam (prefill/decode split) ------------------------
    def export_pages(self, slot):
        """KV-page handoff seam for prefill/decode disaggregation
        (ROADMAP "Internet-scale serving tier"; PAPERS.md portable
        collective redistribution): snapshot a slot's mapped pages as
        host arrays so a prefill-specialized replica can stream
        finished KV into a decode replica's pool.  Deliberately OFF the
        chunk hot path — the single bundled ``device_get`` here is the
        budgeted sync (HOST_SYNC_ALLOWLIST); ``inference/handoff.py``
        wraps the payload in the fleet's checksummed :class:`KVBundle`
        envelope, shaped so the transport (host copy today, ICI/DMA
        later) is the only thing left to swap.

        Returns ``{"logical": [logical pages, ascending], "layers":
        [per-layer tuples of (k, page_size, nH, D) page stacks, a
        latent layer's tuple holding its one (k, page_size, width)
        stack], "quant": bool, "manifest": {...}}``.  The manifest carries
        the page count/size, dtype, layer count and kinds and a per-page
        CRC32 chain
        over every buffer (scales included in int8 mode) —
        :meth:`import_pages` refuses the payload whole on any mismatch.
        """
        mapping = self._slot_pages[slot]
        order = sorted(mapping)
        phys = np.asarray([mapping[j] for j in order], np.int32)
        layers = jax.device_get(
            [tuple(buf[phys] for buf in pools) for pools in self._pools])
        manifest = {
            "pages": len(order),
            "page_size": self.page_size,
            "dtype": "int8" if self.quant else str(self.cache_dtype),
            "layers": len(self.spec),
            "kinds": list(self.kinds),
            "positions": [int(j) for j in order],
            "crc32": _page_crcs(layers),
        }
        return {"logical": order, "layers": layers, "quant": self.quant,
                "manifest": manifest}

    def _verify_payload(self, payload):
        """Integrity gate for :meth:`import_pages`: every structural
        field and every per-page CRC32 must verify BEFORE any page
        touches the pool — a torn or corrupt bundle is rejected whole
        (:class:`KVBundleError`), leaving allocator and pools
        untouched."""
        man = payload.get("manifest")
        if not man:
            raise KVBundleError(
                "KV bundle has no integrity manifest — refusing the "
                "unverifiable import (re-export with this release's "
                "export_pages)")
        order = list(payload["logical"])
        layers = payload["layers"]
        want_dtype = "int8" if self.quant else str(self.cache_dtype)
        if (man.get("pages") != len(order)
                or man.get("positions") != [int(j) for j in order]
                or len(man.get("crc32", ())) != len(order)):
            raise KVBundleError(
                f"torn KV bundle: manifest covers {man.get('pages')} "
                f"page(s) at positions {man.get('positions')} but the "
                f"payload carries {len(order)} ({order})")
        if man.get("page_size") != self.page_size \
                or man.get("layers") != len(self.spec) \
                or len(layers) != len(self.spec):
            raise KVBundleError(
                f"KV bundle layout mismatch: bundle page_size="
                f"{man.get('page_size')}/{man.get('layers')} layer(s) "
                f"vs pool page_size={self.page_size}/"
                f"{len(self.spec)} layer(s)")
        if man.get("kinds", ["heads"] * len(self.spec)) != self.kinds \
                or any(len(got) != len(pool)
                       for got, pool in zip(layers, self._pools)):
            raise KVBundleError(
                f"KV bundle layer kinds {man.get('kinds')} != pool "
                f"layer kinds {self.kinds}")
        if man.get("dtype") != want_dtype:
            raise KVBundleError(
                f"KV bundle dtype {man.get('dtype')!r} != pool dtype "
                f"{want_dtype!r}")
        got = _page_crcs(layers)
        if got != list(man["crc32"]):
            bad = [order[i] for i, (a, b)
                   in enumerate(zip(got, man["crc32"])) if a != b]
            raise KVBundleError(
                f"KV bundle checksum mismatch on logical page(s) {bad} "
                "— rejecting the bundle whole (no page touched the "
                "pool)")

    @_allocator_locked
    def import_pages(self, slot, payload, ticket=None):
        """Inverse seam: verify an :meth:`export_pages` payload, then
        write it into pages of this pool mapped to ``slot`` (same layer
        spec, same page size, same quant mode).  Verification is
        all-before-anything: a torn/corrupt bundle raises
        :class:`KVBundleError` with the pool untouched.  ``ticket``
        consumes pages held by :meth:`reserve_pages` (the handoff
        protocol's reserve phase) instead of allocating fresh ones.
        Returns the number of pages imported; raises when the pool
        cannot hold them (the decode replica's admission gate decides
        before calling)."""
        if bool(payload["quant"]) != self.quant:
            raise KVBundleError("exporter/importer kv quant modes differ")
        self._verify_payload(payload)
        order = list(payload["logical"])
        mapping = self._slot_pages[slot]
        assert not mapping, f"slot {slot} imported while still mapped"
        if ticket is not None:
            held = self._reservations.get(ticket)
            if held is None:
                raise KeyError(f"unknown/expired reservation {ticket}")
            if len(held) != len(order):
                raise ValueError(
                    f"reservation {ticket} holds {len(held)} page(s) "
                    f"but the bundle carries {len(order)}")
            fresh = self._reservations.pop(ticket)
        else:
            fresh = self._alloc(len(order))
            if fresh is None:
                raise RuntimeError(
                    f"pool cannot hold {len(order)} imported pages "
                    f"({len(self._free)} free)")
        row = self.table[slot]
        for j, page in zip(order, fresh):
            row[j] = page
            mapping[j] = page
        idx = np.asarray(fresh, np.int32)
        self._pools = [
            tuple(buf.at[idx].set(jnp.asarray(vals).astype(buf.dtype))
                  for buf, vals in zip(pools, layer))
            for pools, layer in zip(self._pools, payload["layers"])]
        self._gauges()
        return len(fresh)

    # -- handoff reservations (ISSUE 16) -----------------------------------
    @_allocator_locked
    def reserve_pages(self, count):
        """Atomically hold ``count`` pages under a reservation ticket
        (the handoff protocol's *reserve* phase): all-or-nothing like
        :meth:`_alloc`, returns the ticket or None under pool pressure.
        Reserved pages count as in-use (no slot may take them) until
        :meth:`import_pages` consumes the ticket or
        :meth:`cancel_reservation` returns them — the TTL that bounds a
        reservation's life belongs to the protocol layer
        (``inference/handoff.py``), which owns the clock."""
        pages = self._alloc(count)
        if pages is None:
            return None
        ticket = self._next_ticket
        self._next_ticket += 1
        self._reservations[ticket] = pages
        self._gauges()
        return ticket

    @_allocator_locked
    def cancel_reservation(self, ticket):
        """Release a reservation's pages back to the pool (expiry or
        protocol abort); returns the page count freed (0 for an
        unknown/already-consumed ticket — cancel is idempotent so an
        expiry sweep racing a successful import never double-frees)."""
        pages = self._reservations.pop(ticket, None)
        if pages is None:
            return 0
        for p in pages:
            self._decref(p)
        self._gauges()
        return len(pages)

    # -- invariants (test hook) --------------------------------------------
    @_allocator_locked
    def check(self):
        """Assert the allocator invariants; returns True for test
        convenience."""
        refs = np.zeros(self.num_pages, np.int64)
        for mapping in self._slot_pages:
            for page in mapping.values():
                refs[page] += 1
        for pages, _ in self._prefix.values():
            for page in pages:
                refs[page] += 1
        for pages in self._reservations.values():
            for page in pages:
                refs[page] += 1
        assert np.array_equal(refs, self._ref), \
            f"refcount drift: counted {refs} vs tracked {self._ref}"
        free = set(self._free)
        assert len(free) == len(self._free), "duplicate pages on free list"
        assert 0 not in free, "trash page leaked onto the free list"
        for page in range(1, self.num_pages):
            held = self._ref[page] > 0
            assert held != (page in free), \
                f"page {page} is {'held' if held else 'unheld'} but " \
                f"{'on' if page in free else 'off'} the free list"
        for slot, mapping in enumerate(self._slot_pages):
            row = self.table[slot]
            for j in range(self.pages_per_slot):
                want = mapping.get(j, 0)
                assert row[j] == want, \
                    f"table[{slot},{j}]={row[j]} != mapping {want}"
        return True
