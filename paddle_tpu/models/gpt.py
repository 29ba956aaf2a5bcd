"""GPT family (reference workload: GPT-3 1.3B TP+PP hybrid —
BASELINE.json config #4; model structure mirrors PaddleNLP's GPTModel,
parallelised with our mp_layers instead of per-rank weight slices).

TPU-first choices:
- fused QKV projection (one (H, 3H) matmul for the MXU);
- pre-LN blocks; bf16-friendly (params fp32, compute cast by AMP);
- attention via F.scaled_dot_product_attention (Pallas flash for long
  seqs);
- TP: QKV/MLP-up are column-parallel, attn-out/MLP-down row-parallel,
  embeddings vocab-parallel — the Megatron placement expressed as weight
  pspecs that GSPMD partitions;
- ``remat`` toggles jax.checkpoint per block (the reference's
  recompute_interval).
"""
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..framework.autograd import call_op
from .. import nn
from ..nn import functional as F
from ..distributed.fleet.meta_parallel.parallel_layers.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding)
from ..observability.tracing import scope as _scope
from .generation import GenerationMixin

__all__ = ["GPTConfig", "GPTModel", "GPTForPretraining",
           "GPTPretrainingCriterion", "gpt3_tiny", "gpt3_125m", "gpt3_1p3b"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 0        # 0 → 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.0
    attention_probs_dropout_prob: float = 0.0
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    tensor_parallel: bool = False     # use TP layers (mp mesh axis)
    remat: bool = False               # jax.checkpoint per block
    # selective remat: a jax.checkpoint_policies name (e.g.
    # "dots_saveable" keeps matmul outputs, recomputes the cheap
    # elementwise/norm ops — the reference's recompute_granularity=
    # "core_attn"/"full" ladder as a policy).  Setting it implies
    # remat; None with remat=True is full recompute (the old knob).
    remat_policy: str = None

    def __post_init__(self):
        if not self.intermediate_size:
            self.intermediate_size = 4 * self.hidden_size


def gpt3_tiny(**kw):
    return GPTConfig(vocab_size=1024, hidden_size=64, num_hidden_layers=2,
                     num_attention_heads=4, max_position_embeddings=128,
                     **kw)


def gpt3_125m(**kw):
    return GPTConfig(hidden_size=768, num_hidden_layers=12,
                     num_attention_heads=12, **kw)


def gpt3_1p3b(**kw):
    return GPTConfig(hidden_size=2048, num_hidden_layers=24,
                     num_attention_heads=16,
                     max_position_embeddings=2048, **kw)


class GPTAttention(nn.Layer):
    def __init__(self, config):
        super().__init__()
        H = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.head_dim = H // self.num_heads
        self.dropout = config.attention_probs_dropout_prob
        if config.tensor_parallel:
            self.qkv_proj = ColumnParallelLinear(H, 3 * H,
                                                 gather_output=False)
            self.out_proj = RowParallelLinear(H, H, input_is_parallel=True)
        else:
            self.qkv_proj = nn.Linear(H, 3 * H)
            self.out_proj = nn.Linear(H, H)

    def forward(self, x, cache=None, pos=None, attn_mask=None):
        from ..tensor.manipulation import reshape, concat, split
        B, S, H = x.shape
        with _scope("attention.qkv"):
            qkv = self.qkv_proj(x)
            if pos is None:
                # split the flat last axis, THEN view heads: XLA gives a
                # 5-D (B, S, 3, nH, D) view of the projection a sequence-
                # minor layout at D=64 (so as not to pad 64 lanes to
                # 128), and every q/k/v then pays a relayout copy on its
                # way into the flash kernels, which read the projection's
                # row-major layout (PERF.md PR 31: 9 copies a layer)
                q, k, v = (reshape(t, [B, S, self.num_heads, self.head_dim])
                           for t in split(qkv, 3, axis=-1))
            else:
                # the fixed-buffer decode keeps the 5-D view: with the
                # flat split its time per token measured 2% longer
                qkv = reshape(qkv, [B, S, 3, self.num_heads, self.head_dim])
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        if pos is not None:
            # static-shape decode: write this chunk's k/v at offset `pos`
            # into the preallocated (B, MAX, nH, D) buffers and attend
            # over the masked prefix — the jit/scan-friendly KV cache
            # (reference: cache_kv in fused multi_transformer inference)
            return _cached_attention(self.out_proj, q, k, v, cache, pos,
                                     B, S, H, attn_mask=attn_mask)
        if cache is not None:
            k = concat([cache[0], k], axis=1)
            v = concat([cache[1], v], axis=1)
            cache = (k, v)
        with _scope("attention.core"):
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=self.dropout,
                training=self.training)
            out = reshape(out, [B, S, H])
        with _scope("attention.out"):
            out = self.out_proj(out)
        if cache is not None:
            return out, cache
        return out


def _decode_position_ids(p, S):
    """Absolute positions for this decode chunk: scalar ``pos`` yields
    (S,) shared across the batch; a per-row (B,) ``pos`` (the serving
    engine's per-slot offsets) yields (B, S)."""
    p = p.astype(jnp.int32)
    if p.ndim:
        return p[:, None] + jnp.arange(S)
    return p + jnp.arange(S)


def _cached_attention(out_proj, q, k, v, cache, pos, B, S, H,
                      attn_mask=None):
    """Shared fixed-buffer KV attention for compiled decode: k/v land at
    offset ``pos`` (traced scalar, or per-row (B,) vector — the serving
    engine's per-slot offsets) via dynamic_update_slice / batched
    scatter; queries at absolute positions pos..pos+S-1 attend to prefix
    positions <= theirs through an additive mask.  ``attn_mask`` is an
    optional extra additive (B, MAX) key mask (0 keep / -1e30 drop) for
    left-padded ragged prompts. Returns (out, (k_buf, v_buf)).

    ``cache`` may instead be an ``inference.kvcache.PagedCacheView``
    (block-paged serving).  Attention over a paged cache is kernel
    ``"paged_attention"`` of ``ops/registry.py``, in two forms:

    - ``"xla"``, the plain form and the one every backend has: the
      slot's pages are gathered into the same (B, MAX, nH, D) working
      buffers, the write/mask/attention math below runs unchanged
      (bitwise-identical to the dense path: that contract is this
      form's), and the newly written positions scatter back to the page
      pool (quantizing in int8 mode);
    - ``"pallas"`` (:func:`_paged_decode_attention`), taken on a TPU for
      a decode step (``S == 1``) over a full-precision pool with no
      extra mask: the step's row is written into the pool first and the
      query attends over the slot's live pages in place, through the
      block table.  It agrees with the plain form within the tolerance
      docs/kernels.md states, not bit for bit.

    Every other paged call keeps the plain form and says why in
    ``pt_kernel_fallbacks_total{kernel="paged_attention"}``.  Returns
    (out, updated view) for a paged cache."""
    from ..tensor.manipulation import reshape
    paged = hasattr(cache, "_fields")
    if paged:
        from ..inference import kvcache as _kvc
        from ..ops.pallas import paged_attention as _pa
        kernel = _pa.select(tuple(q.shape), cache, attn_mask is not None)
        if kernel.use:
            return _paged_decode_attention(out_proj, q, k, v, cache, pos,
                                           B, H, kernel.interpret)
        if cache.k_scales is None:
            k_buf, v_buf = call_op(_kvc.gather_pages, cache.k_pages,
                                   cache.v_pages, cache.table)
        else:
            k_buf, v_buf = call_op(
                _kvc.gather_pages_q, cache.k_pages, cache.v_pages,
                cache.k_scales, cache.v_scales, cache.table,
                dtype=q.dtype)
    else:
        k_buf, v_buf = cache
    MAX = k_buf.shape[1]

    def write(buf, new, p):
        new = new.astype(buf.dtype)
        if p.ndim:
            idx = _decode_position_ids(p, S)                # (B, S)
            return buf.at[jnp.arange(B)[:, None], idx].set(new)
        return jax.lax.dynamic_update_slice(
            buf, new, (0, p.astype(jnp.int32), 0, 0))
    with _scope("kv.scatter"):
        k_buf = call_op(write, k_buf, k, pos)
        v_buf = call_op(write, v_buf, v, pos)

    def mask_fn(p, *extra):
        qpos = _decode_position_ids(p, S)            # (S,) or (B, S)
        valid = jnp.arange(MAX) <= qpos[..., None]   # (S,MAX) / (B,S,MAX)
        m = jnp.where(valid, 0.0, -1e30)
        # (1,1,S,MAX) for shared pos; (B,1,S,MAX) for per-row pos
        m = m[None, None] if m.ndim == 2 else m[:, None]
        if extra:
            m = m + extra[0].astype(m.dtype)[:, None, None, :]
        return m
    with _scope("attention.core"):
        mask = call_op(mask_fn, pos) if attn_mask is None else \
            call_op(mask_fn, pos, attn_mask)
        out = F.scaled_dot_product_attention(
            q, k_buf, v_buf, attn_mask=mask, is_causal=False,
            training=False)
        out = reshape(out, [B, S, H])
    with _scope("attention.out"):
        out = out_proj(out)
    if paged:
        if cache.k_scales is None:
            kp, vp = call_op(_kvc.scatter_pages, cache.k_pages,
                             cache.v_pages, k, v, cache.table, pos)
            new_cache = cache._replace(k_pages=kp, v_pages=vp)
        else:
            kp, vp, ks, vs = call_op(
                _kvc.scatter_pages_q, cache.k_pages, cache.v_pages,
                cache.k_scales, cache.v_scales, k, v, cache.table, pos)
            new_cache = cache._replace(k_pages=kp, v_pages=vp,
                                       k_scales=ks, v_scales=vs)
        return out, new_cache
    return out, (k_buf, v_buf)


def _paged_decode_attention(out_proj, q, k, v, cache, pos, B, H, interpret):
    """One new token a slot over a paged cache with nothing of width
    ``MAX`` built: the step's k/v row goes into the pool, then the query
    attends over ``pos + 1`` keys of the slot's pages where they lie
    (``ops/pallas/paged_attention.py``).  An inactive slot's table row
    points at the trash page; it attends over nothing and gets zeros,
    which the engine discards."""
    from ..inference import kvcache as _kvc
    from ..ops.pallas import paged_attention as _pa
    from ..tensor.manipulation import reshape
    kp, vp = call_op(_kvc.scatter_pages, cache.k_pages, cache.v_pages,
                     k, v, cache.table, pos)

    def attend(q_, kp_, vp_, table, p):
        lengths = _kvc.live_lengths(table, p, kp_.shape[1])
        return _pa.paged_attention(q_[:, 0], kp_, vp_, table, lengths,
                                   interpret=interpret)
    with _scope("attention.core"):
        out = reshape(call_op(attend, q, kp, vp, cache.table, pos),
                      [B, 1, H])
    with _scope("attention.out"):
        out = out_proj(out)
    return out, cache._replace(k_pages=kp, v_pages=vp)


def _cached_block(ln1, attn, ln2, ffn, x, cache, pos, attn_mask=None):
    """One decode step of a pre-LN block: cached attention + FFN with
    residuals — shared by the GPT/GPT-MoE/LLaMA decoder layers."""
    with _scope("norm"):
        h = ln1(x)
    a, cache = attn(h, cache=cache, pos=pos, attn_mask=attn_mask)
    with _scope("attention.out"):
        x = x + a
    with _scope("norm"):
        h = ln2(x)
    with _scope("mlp"):
        x = x + ffn(h)
    return x, cache


def _cached_layers(layers, caches, pos, x, final_norm, attn_mask=None):
    """Thread per-layer KV caches through the block stack and apply the
    final norm — the model-level cached forward shared by the families."""
    new_caches = []
    for blk, cache in zip(layers, caches):
        x, cache = blk(x, cache=cache, pos=pos, attn_mask=attn_mask)
        new_caches.append(cache)
    with _scope("norm"):
        return final_norm(x), new_caches


class GPTMLP(nn.Layer):
    def __init__(self, config):
        super().__init__()
        H, I = config.hidden_size, config.intermediate_size
        if config.tensor_parallel:
            self.up = ColumnParallelLinear(H, I, gather_output=False)
            self.down = RowParallelLinear(I, H, input_is_parallel=True)
        else:
            self.up = nn.Linear(H, I)
            self.down = nn.Linear(I, H)

    def forward(self, x):
        with _scope("mlp"):
            return self.down(F.gelu(self.up(x), approximate=True))


class GPTDecoderLayer(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.ln1 = nn.LayerNorm(config.hidden_size,
                                epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln2 = nn.LayerNorm(config.hidden_size,
                                epsilon=config.layer_norm_epsilon)
        self.mlp = GPTMLP(config)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)
        self._remat = config.remat

    def forward(self, x, cache=None, pos=None, attn_mask=None):
        if pos is not None:
            return _cached_block(self.ln1, self.attn, self.ln2, self.mlp,
                                 x, cache, pos, attn_mask=attn_mask)
        # the residual adds ride the scope of the branch they close, so
        # that a matmul+add fusion has one name whichever is its root
        with _scope("norm"):
            h = self.ln1(x)
        a = self.attn(h)
        with _scope("attention.out"):
            x = x + self.dropout(a)
        with _scope("norm"):
            h = self.ln2(x)
        with _scope("mlp"):
            x = x + self.dropout(self.mlp(h))
        return x


class GPTEmbeddings(nn.Layer):
    def __init__(self, config):
        super().__init__()
        if config.tensor_parallel:
            self.word_embeddings = VocabParallelEmbedding(
                config.vocab_size, config.hidden_size)
        else:
            self.word_embeddings = nn.Embedding(config.vocab_size,
                                                config.hidden_size)
        self.position_embeddings = nn.Embedding(
            config.max_position_embeddings, config.hidden_size)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, position_ids=None):
        from ..tensor.creation import arange
        if position_ids is None:
            S = input_ids.shape[1]
            position_ids = arange(S, dtype="int64")
        with _scope("embed"):
            return self.dropout(self.word_embeddings(input_ids) +
                                self.position_embeddings(position_ids))


class GPTModel(nn.Layer):
    def __init__(self, config):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.layers = nn.LayerList(
            [GPTDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.final_norm = nn.LayerNorm(config.hidden_size,
                                       epsilon=config.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None, caches=None, pos=None,
                attn_mask=None):
        if pos is not None:
            S = input_ids.shape[1]
            position_ids = call_op(
                lambda p: _decode_position_ids(p, S), pos)
            x = self.embeddings(input_ids, position_ids)
            return _cached_layers(self.layers, caches, pos, x,
                                  self.final_norm, attn_mask=attn_mask)
        x = self.embeddings(input_ids, position_ids)
        for blk in self.layers:
            if self.config.remat or self.config.remat_policy:
                x = _remat_block(blk, x, self.config.remat_policy)
            else:
                x = blk(x)
        with _scope("norm"):
            return self.final_norm(x)


def _remat_policy(name):
    """Resolve a ``jax.checkpoint_policies`` name (``None`` = recompute
    everything, the classic full-remat knob)."""
    if name is None:
        return None
    pol = getattr(jax.checkpoint_policies, name, None)
    if pol is None or name.startswith("_") or not callable(pol):
        known = sorted(n for n in dir(jax.checkpoint_policies)
                       if not n.startswith("_"))
        raise ValueError(f"unknown remat_policy {name!r}; available "
                         f"jax.checkpoint_policies: {known}")
    return pol


def _remat_block(blk, x, policy=None):
    """jax.checkpoint the block (reference: fleet recompute per layer);
    ``policy`` selects which intermediates are saved vs recomputed
    (e.g. ``"dots_saveable"`` keeps the expensive matmul outputs)."""
    params = [p for _, p in blk.named_parameters()]

    def run(xv, *pv):
        olds = [p._value for p in params]
        for p, v in zip(params, pv):
            p._value = v
        try:
            from ..framework import autograd as _ag
            with _ag.suspend_tape():
                return blk(Tensor(xv))._value
        finally:
            for p, v in zip(params, olds):
                p._value = v
    return call_op(jax.checkpoint(run, policy=_remat_policy(policy)),
                   x, *params)


def _init_gpt_weights(root, std):
    """normal(0, initializer_range) for matmul/embedding weights, zero
    biases, ones for norm scales — the GPT init scheme."""
    import numpy as np
    rng = np.random.RandomState(0)
    for name, p in root.named_parameters():
        shape = tuple(p.shape)
        if name.endswith("bias") or len(shape) == 0:
            p._value = jnp.zeros(shape, p.dtype)
        elif len(shape) == 1:
            # norm weight
            if "norm" in name or name.endswith(".weight") and \
                    "embedding" not in name:
                p._value = jnp.ones(shape, p.dtype)
        else:
            p._value = jnp.asarray(
                rng.normal(0.0, std, shape).astype("float32"))


class GPTForPretraining(nn.Layer, GenerationMixin):
    """LM head tied to the input embedding (reference: shared weights via
    SharedLayerDesc in PP; here the tie is literal reuse)."""

    def __init__(self, config):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config
        _init_gpt_weights(self, config.initializer_range)

    @property
    def tied_lm_head(self):
        """The vocab embedding doubling as the LM head (the literal
        weight tie above).  ``generation.quantize_weights`` reads this
        to narrow the table TRANSPOSED — per-vocab channels serve both
        the decode head matmul (``quant_matmul``) and the input gather
        (``dequant_rows``)."""
        return self.gpt.embeddings.word_embeddings.weight

    def _head(self, x, w):
        # serving quantization may have swapped the tied table for a
        # transposed QuantizedWeight: the head then dispatches through
        # the kernel registry (closure capture, like F.linear's branch)
        wv = getattr(w, "_value", None)
        with _scope("lm_head"):
            if type(wv).__name__ == "QuantizedWeight":
                from ..ops.quant_dispatch import quant_matmul
                return call_op(lambda h: quant_matmul(h, wv,
                                                      out_dtype=h.dtype), x)
            return call_op(lambda h, t: h @ t.T, x, w)

    def forward(self, input_ids, position_ids=None, caches=None, pos=None,
                attn_mask=None):
        w = self.gpt.embeddings.word_embeddings.weight
        if pos is not None:
            x, caches = self.gpt(input_ids, caches=caches, pos=pos,
                                 attn_mask=attn_mask)
            return self._head(x, w), caches
        x = self.gpt(input_ids, position_ids)
        return self._head(x, w)


class GPTPretrainingCriterion(nn.Layer):
    """Shifted LM cross-entropy; with TP the logits arrive vocab-sharded
    and the CE reductions lower to the c_softmax_with_cross_entropy wire
    pattern.

    The shift rides an IGNORE label at the last position instead of
    slicing ``logits[:, :-1]``: the flattened row count stays B*S (so
    the fused-xent kernel needs no row padding) and the (B, S, V)
    logits tensor is never re-materialized by a slice copy — same math,
    mean over the same B*(S-1) valid rows."""

    def __init__(self, config=None):
        super().__init__()

    def forward(self, logits, labels):
        V = logits.shape[-1]
        from ..tensor.creation import full
        from ..tensor.manipulation import concat, reshape
        B = labels.shape[0]
        with _scope("xent"):
            tail = full([B, 1], -100, dtype=str(labels.dtype))
            lb = concat([labels[:, 1:], tail], axis=1)
            return F.cross_entropy(reshape(logits, [-1, V]),
                                   reshape(lb, [-1]))
