"""Autoregressive generation (reference: PaddleNLP GenerationMixin
``model.generate`` with decode_strategy greedy_search/sampling, and the
inference fused_multi_transformer cache_kv decode path).

TPU-native design: ONE jitted function runs prefill plus a ``lax.scan``
over single-token steps against preallocated static-shape KV caches
(``jax.lax.dynamic_update_slice`` writes, additive prefix masks) — no
per-token dispatch, no growing shapes, so the whole decode is a single
compiled program. Sampling uses counter-based keys split per step;
finished rows emit ``pad_token_id`` (scan has no early exit — the
standard masked-finish formulation).
"""
import threading
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from ..analysis import register_jit_surface
from ..framework.core import Tensor
from ..framework import autograd as _ag
from ..framework.random import rng_scope

# generate()'s compiled bodies are nested defs a decorator can't reach —
# registered here for the tracer-safety pass (mirrored by
# EXTRA_JIT_SURFACES in paddle_tpu/analysis/allowlist.py).  The apply/
# pick builders are shared with the serving engine
# (paddle_tpu/inference/serving.py), which registers its own surfaces.
for _qual in ("generate.run", "generate.beam_run", "generate.prefill",
              "build_apply.apply", "build_pick.pick"):
    register_jit_surface(__name__, _qual)


class LatentCacheSpec(NamedTuple):
    """A layer of ``kv_cache_spec()`` whose cache is ONE plane of
    ``width`` values a token (latent attention: the normed latent and
    the rotated key positions side by side) with no head axis and no V
    plane.  A layer that keeps keys and values by head stays the plain
    ``(num_kv_heads, head_dim)`` pair it always was."""
    width: int


class StateCacheSpec(NamedTuple):
    """A layer of ``kv_cache_spec()`` that keeps a recurrent state a slot
    and no cache a token (a linear-attention layer): ``heads`` matrices of
    ``key_dim x value_dim`` in float32, and the last ``conv_tail`` inputs
    of its short convolutions, ``conv_channels`` wide.  The state is not
    paged and cannot be shared by prefix."""
    heads: int
    key_dim: int
    value_dim: int
    conv_channels: int
    conv_tail: int


def cache_kind(layer_spec):
    """"latent", "state" or "heads": the kind of one entry of
    ``kv_cache_spec()``."""
    if isinstance(layer_spec, LatentCacheSpec):
        return "latent"
    return "state" if isinstance(layer_spec, StateCacheSpec) else "heads"


def require_head_caches(spec, what):
    """Raise where ``what`` (a mode that preallocates dense
    ``(B, MAX, nH, D)`` K/V buffers) meets a layer kind it cannot hold."""
    kinds = sorted({cache_kind(s) for s in spec} - {"heads"})
    if kinds:
        raise ValueError(
            f"{what} holds keys and values by head and cannot hold a "
            f"{kinds[0]} cache layer: serve this model through "
            "ServingEngine(kv_mode='paged')")


class _GenCaches(dict):
    """Cache holder that refuses to travel: deepcopy (e.g.
    quantization.fp8_quantize) gets None instead of a copy — a copied
    entry's jit closures would capture the ORIGINAL model's parameter
    list (shape crashes) and pin that model plus its cast weight sets in
    memory; pickling degrades to an empty plain dict (jit functions
    aren't picklable)."""

    def __deepcopy__(self, memo):
        return None

    def __reduce__(self):
        return (dict, ())


def _caches_for(model):
    """Per-model generation caches (compiled programs + cast weights),
    stored on the instance so the model→cache→closure→model cycle stays
    collectible by the GC (a module-global registry would pin every
    model forever through the jit closures). The ``owner_id`` token is a
    second line of defense against entries that arrive by shallow copy.
    id() collision with a dead original is impossible while a stale
    entry exists — its closures keep the original alive.
    """
    entry = model.__dict__.get("_generation_caches")
    if entry is None or entry.get("owner_id") != id(model):
        entry = _GenCaches(owner_id=id(model), jit={}, cast=None,
                           quant=None)
        # plain attr set: Layer.__setattr__ would try to register it
        object.__setattr__(model, "_generation_caches", entry)
    return entry

__all__ = ["generate", "GenerationMixin"]

_STRATEGIES = ("greedy_search", "sampling", "beam_search")


def dominant_float_dtype(pvals):
    """The model's dominant floating dtype by element count — a bf16
    model gets bf16 caches; a stray fp32 norm or embedding doesn't flip
    the choice."""
    sizes = {}
    for v in pvals:
        if jnp.issubdtype(v.dtype, jnp.floating):
            sizes[v.dtype] = sizes.get(v.dtype, 0) + int(v.size)
    return max(sizes, key=sizes.get) if sizes else jnp.float32


def cast_weights(model, pvals, cache_dtype):
    """Cast the parameter values to ``cache_dtype`` once per (dtype,
    weight identity): repeated serving calls must not re-materialize a
    full low-precision weight copy.  Identity is checked by ``is``
    against strongly-held originals, so a train step (new ``_value``
    arrays) recasts automatically."""
    caches = _caches_for(model)
    cast = caches["cast"]
    if (cast is not None and cast[0] == str(cache_dtype)
            and len(cast[1]) == len(pvals)
            and all(a is b for a, b in zip(cast[1], pvals))):
        return cast[2]
    originals = pvals
    # a value already in ``cache_dtype`` is kept, never copied: a model
    # built in the serving dtype holds ONE copy of its weights
    out = [v.astype(cache_dtype)
           if jnp.issubdtype(v.dtype, jnp.floating)
           and v.dtype != cache_dtype else v
           for v in pvals]
    caches["cast"] = (str(cache_dtype), originals, out)
    return out


def _linear_weight_indices(model):
    """Positions (in ``named_parameters()`` order) of 2-D floating
    Linear weights — the matmuls the quantization pass narrows.  Biases,
    norms and (untied) embeddings stay in the original dtype; a tied LM
    head is handled separately (see :func:`quantize_weights`)."""
    from ..nn.layer.common import Linear
    params = [p for _, p in model.named_parameters()]
    index = {id(p): i for i, p in enumerate(params)}
    out = set()
    for _, sub in model.named_sublayers():
        if not isinstance(sub, Linear):
            continue
        i = index.get(id(getattr(sub, "weight", None)))
        if i is None:
            continue
        v = params[i]._value
        if v.ndim == 2 and jnp.issubdtype(v.dtype, jnp.floating):
            out.add(i)
    return sorted(out)


def quantize_weights(model, pvals, mode):
    """Pre-quantize the model's Linear weights once per (mode, weight
    identity): each selected ``pvals`` entry is replaced by an
    ``ops.quant_dispatch.QuantizedWeight`` (a registered pytree, so the
    list threads through the existing serving jit signatures unchanged,
    and ``build_apply`` swaps the container into the parameter where
    ``F.linear`` dispatches it through ``quant_matmul``).  Identity
    caching mirrors :func:`cast_weights`: a train step (new ``_value``
    arrays) re-quantizes automatically; repeated serving calls never
    re-materialize the narrow copies."""
    from ..ops.quant_dispatch import quantize_weight
    caches = _caches_for(model)
    # seed-era cache entries predate the "quant" slot
    ent = caches.get("quant")
    if (ent is not None and ent[0] == str(mode)
            and len(ent[1]) == len(pvals)
            and all(a is b for a, b in zip(ent[1], pvals))):
        return ent[2]
    originals = pvals
    out = list(pvals)
    for i in _linear_weight_indices(model):
        out[i] = quantize_weight(pvals[i], mode)
    # A tied LM head (``model.tied_lm_head`` → the vocab table reused as
    # the logits matmul, e.g. GPT) is the single largest weight stream
    # in decode.  Quantize it TRANSPOSED — (H, V) with per-vocab-channel
    # scales — so one narrow copy serves both consumers: the head
    # matmul (``quant_matmul``) and the input-embedding gather
    # (``dequant_rows`` via ``F.embedding``).
    tied = getattr(model, "tied_lm_head", None)
    if tied is not None:
        params = [p for _, p in model.named_parameters()]
        for i, p in enumerate(params):
            if p is tied:
                v = pvals[i]
                if v.ndim == 2 and jnp.issubdtype(v.dtype, jnp.floating):
                    out[i] = quantize_weight(v.T, mode)
                break
    caches["quant"] = (str(mode), originals, out)
    return out


# build_apply swaps values INTO the (shared) model's parameters for the
# duration of one traced forward.  Two serving-fleet replicas tracing
# over the same model concurrently would leak one thread's tracers into
# the other's trace as hoisted constants ("Computation compiled for N
# inputs but called with M" / "Detected argument of Tracer type"), so
# the swap->forward->restore window is one atomic critical section.
# Held only while TRACING (apply bodies run under jit); compiled
# dispatch never takes it.
_APPLY_LOCK = threading.RLock()


def build_apply(model, params):
    """Functional forward over the model's cached decode path, shared by
    ``generate()`` and the serving engine: swap ``pv`` into the
    parameters, run one cached step, restore.  ``pos`` may be a scalar
    (uniform batch) or a per-row (B,) vector (the engine's per-slot
    offsets); ``attn_mask`` is an optional additive (B, MAX) key mask.
    Thread-safe across models sharing parameters (the fleet's replicas):
    the swap-restore window is serialized by ``_APPLY_LOCK``.

    A model whose class sets ``forward_takes_last`` takes ``last=`` (a
    traced position): its cached forward then applies the output head to
    that one position and returns ``(B, 1, V)`` logits, so a prefill
    over a long bucket never forms the bucket's logits
    (``apply.takes_last`` tells the caller)."""
    def _wrap(c):
        # dense (k, v) pair or a paged cache view (a NamedTuple whose
        # optional scale fields may be None) — wrap leaves, keep shape
        if hasattr(c, "_fields"):
            return type(c)(*(None if x is None else Tensor(x)
                             for x in c))
        return tuple(Tensor(x) for x in c)

    def _unwrap(c):
        if hasattr(c, "_fields"):
            return type(c)(*(None if x is None else x._value
                             for x in c))
        return tuple(x._value for x in c)

    def apply(pv, ids, caches, pos, attn_mask=None, last=None):
        with _APPLY_LOCK:
            olds = [p._value for p in params]
            for p, v in zip(params, pv):
                p._value = v
            try:
                kw = {}
                if attn_mask is not None:
                    kw["attn_mask"] = Tensor(attn_mask)
                if last is not None:
                    kw["last"] = Tensor(last)
                with _ag.suspend_tape(), rng_scope(jax.random.key(0)):
                    logits, new_caches = model(
                        Tensor(ids),
                        caches=[_wrap(c) for c in caches],
                        pos=Tensor(pos), **kw)
                return logits._value, [_unwrap(c) for c in new_caches]
            finally:
                for p, v in zip(params, olds):
                    p._value = v
    apply.takes_last = bool(getattr(model, "forward_takes_last", False))
    return apply


def build_pick(greedy, temperature, top_k, top_p):
    """Token-selection builder shared by ``generate()`` and the serving
    engine: fp32 log-softmax, then argmax (greedy) or filtered
    categorical sampling.  Returns ``(next_token int32, logprob)``."""
    def pick(logits, key):
        lg = logits.astype(jnp.float32)
        if not greedy and temperature != 1.0:
            lg = lg / max(float(temperature), 1e-6)
        logp = jax.nn.log_softmax(lg, axis=-1)
        if greedy:
            nxt = jnp.argmax(lg, axis=-1)
        else:
            nxt = jax.random.categorical(
                key, _top_k_top_p_filter(lg, top_k, top_p), axis=-1)
        score = jnp.take_along_axis(logp, nxt[:, None], -1)[:, 0]
        return nxt.astype(jnp.int32), score
    return pick


class GenerationMixin:
    """Shared generation protocol for the causal-LM families: a
    ``generate()`` entry and the default per-layer KV-cache spec derived
    from the model config (GQA-aware via ``num_key_value_heads``)."""

    def _gen_config(self):
        cfg = getattr(self, "config", None)
        if cfg is None:
            cfg = self.model.config
        return cfg

    def kv_cache_spec(self):
        """One description a layer of what its cache holds: a
        ``(num_kv_heads, head_dim)`` pair where keys and values are kept
        by head (generation's preallocated buffers, the engine's dense
        rows and K/V page pools), a :class:`LatentCacheSpec` where the
        layer keeps one latent plane, a :class:`StateCacheSpec` where it
        keeps a recurrent state a slot (:func:`cache_kind` tells them
        apart)."""
        c = self._gen_config()
        kv = getattr(c, "num_key_value_heads", 0) or c.num_attention_heads
        return [(kv, c.hidden_size // c.num_attention_heads)] * \
            c.num_hidden_layers

    def generate(self, input_ids, **kw):
        return generate(self, input_ids, **kw)

    def speculative_generate(self, input_ids, **kw):
        """Greedy draft–verify generation, bitwise identical to
        ``generate(decode_strategy="greedy_search")`` — see
        ``paddle_tpu.inference.speculative`` (lazy import: the
        speculative module pulls in the serving stack)."""
        from ..inference.speculative import speculative_generate
        return speculative_generate(self, input_ids, **kw)


def _top_k_top_p_filter(logits, top_k, top_p):
    """Mask logits outside the top-k set / top-p nucleus to -inf.
    (B, V) fp32; always keeps at least the argmax."""
    if top_k and top_k > 0:
        # clamp to the vocab: the habitual top_k=50 on a small-vocab
        # model must degrade to "keep everything", not crash the trace
        # with an out-of-bounds static index (reference TopKProcess
        # clamps the same way)
        k = min(int(top_k), logits.shape[-1])
        kth = jnp.sort(logits, axis=-1)[:, -k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        desc = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p       # first column is always kept
        kept_min = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                           keepdims=True)
        logits = jnp.where(logits < kept_min, -jnp.inf, logits)
    return logits


def generate(model, input_ids, max_new_tokens=32,
             decode_strategy="greedy_search", temperature=1.0, top_k=0,
             top_p=1.0, num_beams=1, length_penalty=0.0,
             eos_token_id=None, pad_token_id=0, seed=0, dtype=None,
             attention_mask=None):
    """Generate ``max_new_tokens`` continuations of ``input_ids``.

    Returns ``(ids, scores)``: the generated tokens (B, max_new_tokens)
    and their selected-token log-probabilities (generated portion only,
    prompt excluded).

    Scores contract — a DELIBERATE deviation from the reference: the
    reference's greedy/sampling path returns a (B, 1) running-mean
    log-prob (``update_scores_for_generation``) computed from
    pre-temperature origin log-probs, and its beam scorer normalizes by
    ``len**length_penalty``.  Here scores are per-token ``(B, N)``
    POST-temperature log-probs of the selected tokens, and beam search
    uses the GNMT penalty ``((5+len)/6)**length_penalty`` — richer for
    streaming/serving consumers, but not numerically comparable to
    reference scores.

    The model must expose ``kv_cache_spec()`` and a
    ``forward(input_ids, caches=..., pos=...)`` cached mode (the GPT,
    LLaMA and GPT-MoE families do). ``dtype="bfloat16"`` runs the whole
    decode in bf16 weights/caches (serving mode; token picks stay fp32).

    ``attention_mask`` (B, P) of 1/0 (or bool) marks real prompt tokens:
    pad positions are excluded from attention for the WHOLE decode via
    an additive key mask, so left-padded ragged prompts stop silently
    attending pad tokens.  Position embeddings still run over absolute
    buffer positions (a left-padded row sees shifted positions relative
    to an unpadded run of the same prompt — same as the reference's
    fused decode without position-id correction); ``None`` (the default)
    compiles the exact program this function always compiled.

    ``decode_strategy="beam_search"`` carries ``num_beams`` hypotheses
    per row through the same single compiled scan: KV caches live at
    (B*K, ...) and are re-gathered by parent beam each step; a beam that
    emits eos is frozen (only an eos continuation at +0 score); the
    winner is picked by GNMT length-penalised score
    ``sum_logp / ((5+len)/6)**length_penalty`` (``length_penalty=0`` =
    pure sum). Returned scores are the winning beam's per-token
    log-probs.

    MoE note: expert routing runs per decode step, so capacity is
    competed among that step's tokens only (B of them; B*num_beams
    under beam search, where sibling hypotheses of a row route
    together) — the well-defined causal semantics. A capacity-dropping
    full re-forward (teacher forcing) routes batch-globally and may
    drop differently; exact parity holds when capacity never binds.

    Strategy knobs are per-strategy: temperature/top_k/top_p/seed apply
    to sampling only, num_beams/length_penalty to beam search only;
    knobs of the other strategy are ignored (and canonicalized out of
    the compiled-program cache key, so they never force a retrace).

    The compiled prefill+scan program is cached on the model per
    (shapes, strategy, knobs) signature, so repeated serving calls pay
    tracing once.
    """
    if decode_strategy not in _STRATEGIES:
        raise ValueError(
            f"decode_strategy {decode_strategy!r} not in {_STRATEGIES}")
    if max_new_tokens < 1:
        raise ValueError("max_new_tokens must be >= 1")
    if num_beams < 1:
        raise ValueError("num_beams must be >= 1")
    beam = decode_strategy == "beam_search"
    ids_np = np.asarray(input_ids._value if isinstance(input_ids, Tensor)
                        else input_ids).astype("int32")
    if ids_np.ndim != 2:
        raise ValueError("input_ids must be (batch, prompt_len)")
    B, P = ids_np.shape
    MAX = P + max_new_tokens
    cfg = getattr(model, "config", None) \
        or getattr(getattr(model, "model", None), "config", None)
    limit = getattr(cfg, "max_position_embeddings", None)
    if limit is not None and MAX > limit:
        # past the table, position lookups would clamp and silently
        # produce degenerate logits — refuse instead
        raise ValueError(
            f"prompt_len + max_new_tokens = {MAX} exceeds the model's "
            f"max_position_embeddings = {limit}")
    spec = model.kv_cache_spec()
    require_head_caches(spec, "generate()")
    params = [p for _, p in model.named_parameters()]
    pvals = [p._value for p in params]
    # KV caches follow the model's dominant floating dtype unless
    # `dtype` overrides (see dominant_float_dtype / cast_weights)
    cache_dtype = dominant_float_dtype(pvals)
    if dtype is not None:
        cache_dtype = jnp.dtype(dtype)
        pvals = cast_weights(model, pvals, cache_dtype)
    greedy = decode_strategy == "greedy_search"
    eos = None if eos_token_id is None else int(eos_token_id)
    pad = int(pad_token_id)
    # pad positions become an additive (B, MAX) key mask: -1e30 columns
    # are excluded from attention for the whole decode (pad KV is never
    # overwritten — decode appends at positions >= P)
    mask_np = None
    if attention_mask is not None:
        am = np.asarray(attention_mask._value
                        if isinstance(attention_mask, Tensor)
                        else attention_mask)
        if am.shape != (B, P):
            raise ValueError(
                f"attention_mask shape {am.shape} must match input_ids "
                f"{(B, P)}")
        mask_np = np.zeros((B, MAX), np.float32)
        mask_np[:, :P][am.astype(bool) == False] = -1e30  # noqa: E712

    was_training = model.training
    model.eval()

    apply = build_apply(model, params)
    pick = build_pick(greedy, temperature, top_k, top_p)

    def prefill(pv, prompt, extra_mask=None):
        caches = [(jnp.zeros((B, MAX, nh, d), cache_dtype),
                   jnp.zeros((B, MAX, nh, d), cache_dtype))
                  for nh, d in spec]
        return apply(pv, prompt, caches, jnp.zeros((), jnp.int32),
                     attn_mask=extra_mask)

    def run(pv, prompt, key, extra_mask=None):
        logits, caches = prefill(pv, prompt, extra_mask)
        k0, key = jax.random.split(key)
        tok0, sc0 = pick(logits[:, -1, :], k0)
        finished = jnp.zeros((B,), bool) if eos is None else (tok0 == eos)

        def body(carry, step_key):
            tok, caches, pos, finished = carry
            logits, caches = apply(pv, tok[:, None], caches, pos,
                                   attn_mask=extra_mask)
            nxt, score = pick(logits[:, 0, :], step_key)
            nxt = jnp.where(finished, pad, nxt)
            score = jnp.where(finished, 0.0, score)
            if eos is not None:
                new_fin = finished | (nxt == eos)
            else:
                new_fin = finished
            return (nxt, caches, pos + 1, new_fin), (nxt, score)

        if max_new_tokens > 1:
            keys = jax.random.split(key, max_new_tokens - 1)
            _, (toks, scores) = jax.lax.scan(
                body, (tok0, caches, jnp.full((), P, jnp.int32), finished),
                keys)
            out_ids = jnp.concatenate([tok0[:, None], toks.T], axis=1)
            out_sc = jnp.concatenate([sc0[:, None], scores.T], axis=1)
        else:
            out_ids, out_sc = tok0[:, None], sc0[:, None]
        return out_ids, out_sc

    def beam_run(pv, prompt, key, extra_mask=None):
        K, N = num_beams, max_new_tokens
        logits, caches = prefill(pv, prompt, extra_mask)
        logp0 = jax.nn.log_softmax(
            logits[:, -1, :].astype(jnp.float32), axis=-1)      # (B, V)
        V = logp0.shape[-1]
        beam_scores, tok0 = jax.lax.top_k(logp0, K)             # (B, K)
        tok0 = tok0.astype(jnp.int32)
        # every beam shares the prompt prefix: replicate the prefill
        # caches (and the pad key mask) to the (B*K) beam batch
        caches = [(jnp.repeat(k, K, axis=0), jnp.repeat(v, K, axis=0))
                  for k, v in caches]
        beam_mask = None if extra_mask is None \
            else jnp.repeat(extra_mask, K, axis=0)
        seqs = jnp.zeros((B, K, N), jnp.int32).at[:, :, 0].set(tok0)
        steplp = jnp.zeros((B, K, N), jnp.float32) \
            .at[:, :, 0].set(beam_scores)
        finished = (tok0 == eos) if eos is not None \
            else jnp.zeros((B, K), bool)
        bidx = jnp.arange(B)[:, None]

        def body(carry, _):
            tok, caches, pos, t, beam_scores, seqs, steplp, fin = carry
            logits, caches = apply(pv, tok.reshape(B * K, 1), caches, pos,
                                   attn_mask=beam_mask)
            logp = jax.nn.log_softmax(
                logits[:, 0, :].astype(jnp.float32), -1).reshape(B, K, V)
            if eos is not None:
                # frozen beams may only continue with eos at +0, so they
                # compete with live beams at their final score
                frozen = jnp.full((V,), -jnp.inf,
                                  jnp.float32).at[eos].set(0.0)
                logp = jnp.where(fin[:, :, None], frozen[None, None, :],
                                 logp)
            total = beam_scores[:, :, None] + logp              # (B,K,V)
            new_scores, flat = jax.lax.top_k(total.reshape(B, K * V), K)
            parent = flat // V                                   # (B, K)
            token = (flat % V).astype(jnp.int32)
            tok_lp = new_scores - beam_scores[bidx, parent]
            seqs = seqs[bidx, parent].at[:, :, t].set(token)
            steplp = steplp[bidx, parent].at[:, :, t].set(tok_lp)
            fin = fin[bidx, parent]
            flat_parent = (bidx * K + parent).reshape(-1)        # (B*K,)
            caches = [(kc[flat_parent], vc[flat_parent])
                      for kc, vc in caches]
            if eos is not None:
                fin = fin | (token == eos)
            return (token, caches, pos + 1, t + 1, new_scores, seqs,
                    steplp, fin), None

        if N > 1:
            init = (tok0, caches, jnp.full((), P, jnp.int32),
                    jnp.ones((), jnp.int32), beam_scores, seqs, steplp,
                    finished)
            (_, caches, _, _, beam_scores, seqs, steplp,
             finished), _ = jax.lax.scan(body, init, None, length=N - 1)
        # GNMT length penalty over the generated length (up to and
        # including the first eos); length_penalty=0 -> pure logp sum
        if eos is not None:
            iseos = seqs == eos
            length = jnp.where(iseos.any(-1),
                               jnp.argmax(iseos, -1) + 1, N)
        else:
            length = jnp.full((B, K), N, jnp.int32)
        lp = ((5.0 + length.astype(jnp.float32)) / 6.0) \
            ** float(length_penalty)
        best = jnp.argmax(beam_scores / lp, axis=1)              # (B,)
        bid = jnp.arange(B)
        out_ids = seqs[bid, best]
        out_sc = steplp[bid, best]
        if eos is not None:
            # positions strictly after the first eos become pad
            cum = jnp.cumsum((out_ids == eos).astype(jnp.int32), axis=1)
            after = jnp.concatenate(
                [jnp.zeros((B, 1), jnp.int32), cum[:, :-1]], axis=1) >= 1
            out_ids = jnp.where(after, pad, out_ids)
            out_sc = jnp.where(after, 0.0, out_sc)
        return out_ids, out_sc

    # the param structure is part of the key: in-place structural
    # mutation (e.g. fp8_quantize(model, inplace=True) turning Linear
    # weights into buffers) must retrace — the cached closure's
    # parameter list would otherwise misalign with the new pvals
    struct = tuple((tuple(v.shape), str(v.dtype)) for v in pvals)
    # knobs that don't apply to the chosen strategy are canonicalized so
    # they can't force a spurious retrace (they're ignored by the math)
    sampling = decode_strategy == "sampling"
    # generate() is the one-shot API and compiles per (B, P) by
    # documented contract — the serving engine is the bucketed path
    sig = (B, P, max_new_tokens, decode_strategy,  # lint: allow(unbucketed-shape-key)
           float(temperature) if sampling else 1.0,
           int(top_k or 0) if sampling else 0,
           float(top_p if top_p is not None else 1.0) if sampling else 1.0,
           int(num_beams) if beam else 1,
           float(length_penalty) if beam else 0.0,
           eos, pad, str(cache_dtype), struct, mask_np is not None)
    jit_cache = _caches_for(model)["jit"]
    fn = jit_cache.get(sig)
    if fn is None:
        # prompt ids, PRNG key and pad mask are fresh per call and
        # consumed by the decode — donate them so XLA reuses the
        # buffers (the weights in position 0 stay live: the model owns
        # them).  compilestats.wrap puts the decode on the same
        # pt_compile_* surface vocabulary as the serving jits (no
        # retrace budget: the sig-keyed cache legitimately owns one
        # compile per entry, so each wrapper compiles exactly once).
        from ..observability import compilestats as _cstats
        fn = jit_cache[sig] = _cstats.wrap(
            jax.jit(beam_run if beam else run, donate_argnums=(1, 2, 3)),
            "generation.decode", budget=1)
    # MoE gates record their aux loss as a side-effect attribute during
    # forward; inside the jitted scan that value is a tracer, and leaving
    # it behind would crash the next aux_loss()/get_loss() read — restore
    # the pre-generate values after the compiled call
    from ..incubate.distributed.models.moe.gate import BaseGate
    gates = [m for _, m in model.named_sublayers()
             if isinstance(m, BaseGate)]
    saved_losses = [g.loss for g in gates]
    try:
        import warnings
        with warnings.catch_warnings():
            # donation usability is backend-dependent: on TPU the
            # prompt/key/mask buffers alias scan temporaries; the CPU
            # proxy can decline some (it still frees them early) and
            # warns once per compile — deliberate, not actionable here
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable")
            out_ids, out_sc = fn(pvals, jnp.asarray(ids_np),
                                 jax.random.key(int(seed)),
                                 None if mask_np is None
                                 else jnp.asarray(mask_np))
    finally:
        for g, l in zip(gates, saved_losses):
            object.__setattr__(g, "loss", l)
        if was_training:
            model.train()
    return Tensor(out_ids), Tensor(out_sc)
