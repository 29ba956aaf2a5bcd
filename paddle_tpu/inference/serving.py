"""Continuous-batching serving engine: slot-based compiled decode with
in-flight admission (reference: the inference Predictor driving
``fused_multi_transformer`` cache_kv decode / ``block_multihead_attention``
paged KV).

``models.generation.generate()`` decodes one *static* batch: finished
rows burn FLOPs emitting pad until the slowest row drains, and a new
request cannot start until the whole batch finishes.  This engine keeps
**S fixed slots** alive instead:

- per-slot device state (``tokens``/``pos``/``active``/``remaining``)
  and per-slot preallocated KV ``(S, MAX, nH, D)`` per layer — the same
  fixed-buffer cache ``generate()`` uses, indexed per-row via the
  vector-``pos`` cached-attention path;
- decode runs as ONE compiled ``lax.scan`` over a tunable ``chunk`` of
  tokens (the per-dispatch cost this amortizes is not measured on the
  local chip — ``chunk`` is to be re-derived from it, ROADMAP queue 1
  item 3; same shape as ``generate()``'s single scan);
- between chunks the FCFS scheduler admits queued requests into freed
  slots: prefill compiles at a small set of power-of-two length
  buckets, right-pads the prompt to the bucket (pad positions sit
  *after* the real tokens, so the causal prefix mask already excludes
  them, and decode overwrites them before they are ever attended), and
  writes the prompt's KV directly into the assigned slot;
- the chunk boundary costs exactly ONE host sync (a single
  ``jax.device_get`` of the token/state bundle — budgeted in
  ``analysis.allowlist.HOST_SYNC_ALLOWLIST``), which streams per-token
  callbacks and frees finished slots.

Greedy decode only (token picks shared bitwise with ``generate()`` via
``build_pick``); TTFT/throughput/queue-depth counters go to the
guardian structured log (``serving_admit``/``serving_finish``/
``serving_stats``) and the program spans of ``observability.tracing``
(``serving.step`` and its children, mirrored into the profiler).  See
``docs/serving.md``.
"""
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp

from .. import observability as _obs
from ..observability import tracing as _tracing
from ..analysis import register_jit_surface
from ..framework import guardian
from ..models.generation import (build_apply, build_pick, cache_kind,
                                 cast_weights, dominant_float_dtype,
                                 quantize_weights)
from .scheduler import FCFSScheduler, Request

__all__ = ["ServingEngine", "Request", "FCFSScheduler"]

# the compiled bodies are nested defs a decorator can't reach —
# registered for the tracer-safety pass (mirrored by EXTRA_JIT_SURFACES
# in paddle_tpu/analysis/allowlist.py)
for _qual in ("_build_prefill.prefill", "_build_decode_chunk.decode_chunk"):
    register_jit_surface(__name__, _qual)


# the Prometheus twin of each device counter a model may keep
# (``Layer.device_counters``; observability/catalog.py declares them)
_DEVICE_COUNTER_METRICS = {
    "moe_pairs_routed": "pt_serving_moe_pairs_routed_total",
    "moe_pairs_here": "pt_serving_moe_pairs_here_total",
    "moe_experts_touched": "pt_serving_moe_experts_touched_total",
    "moe_decode_layer_steps": "pt_serving_moe_decode_layer_steps_total",
    "moe_max_expert_rows": "pt_serving_moe_max_expert_rows",
    "dsa_keys_attended": "pt_serving_dsa_keys_attended_total",
    "dsa_keys_live": "pt_serving_dsa_keys_live_total",
    "prefill_pieces": "pt_serving_prefill_pieces_total",
}


def _build_prefill(apply, pick, spec, cache_dtype, MAX, eos):
    """Compiled prefill for one length bucket: run the model over the
    right-padded (1, bucket) prompt with fresh single-row caches, pick
    the first generated token from the last *real* position, scatter the
    prompt KV into the assigned slot, and arm the slot's decode state."""
    def prefill(pv, ids, length, slot, budget, tokens, pos, active,
                remaining, caches):
        fresh = [(jnp.zeros((1, MAX, nh, d), cache_dtype),
                  jnp.zeros((1, MAX, nh, d), cache_dtype))
                 for nh, d in spec]
        logits, new = apply(pv, ids, fresh, jnp.zeros((), jnp.int32))
        last = jax.lax.dynamic_slice_in_dim(
            logits, length - 1, 1, axis=1)[:, 0]            # (1, V)
        with _tracing.scope("sample"):
            t0, _ = pick(last, jax.random.key(0))           # (1,)
        t0 = t0[0]
        caches = [(jax.lax.dynamic_update_slice(
                       ck, nk.astype(ck.dtype), (slot, 0, 0, 0)),
                   jax.lax.dynamic_update_slice(
                       vc, nv.astype(vc.dtype), (slot, 0, 0, 0)))
                  for (ck, vc), (nk, nv) in zip(caches, new)]
        hit_eos = (t0 == eos) if eos is not None else jnp.asarray(False)
        fin0 = hit_eos | (budget <= 1)
        tokens = tokens.at[slot].set(t0)
        pos = pos.at[slot].set(length)
        active = active.at[slot].set(~fin0)
        remaining = remaining.at[slot].set(budget - 1)
        return t0, fin0, tokens, pos, active, remaining, caches
    return prefill


def _build_decode_chunk(apply, pick, chunk, eos, pad):
    """Compiled decode over ``chunk`` tokens for all S slots: one
    ``lax.scan`` whose body advances only active slots (inactive slots
    ride along emitting pad with ``valid=False``), exactly the masked-
    finish formulation ``generate()`` uses — so dispatch amortizes the
    same way and greedy picks stay bitwise-identical."""
    def decode_chunk(pv, tokens, pos, active, remaining, caches):
        def body(carry, _):
            tokens, pos, active, remaining, caches = carry
            logits, caches = apply(pv, tokens[:, None], caches, pos)
            with _tracing.scope("sample"):
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
            return (tokens, pos, active, remaining, caches), (nxt, emitted)
        carry = (tokens, pos, active, remaining, caches)
        (tokens, pos, active, remaining, caches), (toks, valid) = \
            jax.lax.scan(body, carry, None, length=chunk)
        return tokens, pos, active, remaining, caches, toks, valid
    return decode_chunk


class ServingEngine:
    """Continuous-batching greedy decode over ``num_slots`` fixed slots.

    Usage::

        eng = ServingEngine(model, num_slots=8, chunk=32)
        req = eng.submit(prompt_ids, max_new_tokens=64,
                         callback=lambda r, tok, last: ...)
        eng.run()              # drain queue + in-flight work
        req.tokens             # generated ids (list of host ints)

    Knobs:

    - ``num_slots``: concurrent sequences (the compiled batch width);
    - ``chunk``: decode tokens per dispatch (16-64; one dispatch and one
      host sync per chunk vs. admission latency at chunk boundaries);
    - ``prefill_buckets``: compile-once prompt length buckets (prompts
      right-pad to the smallest fitting bucket);
    - ``max_prefills_per_gap``: the prefill-vs-decode interleave knob
      (see :class:`FCFSScheduler`);
    - ``dtype``: e.g. ``"bfloat16"`` casts weights + KV once
      (``cast_weights``) like ``generate(dtype=...)``;
    - ``kv_mode="paged"`` swaps the dense per-slot KV rows for the
      block-paged subsystem (``inference/kvcache.py``): a fixed page
      pool sized by ``num_pages`` x ``page_size``, per-slot page tables,
      a prompt-prefix cache (``prefix_cache``) so shared system prompts
      prefill once, opt-in ``kv_dtype="int8"`` quantized KV, and
      page-pressure preemption back to the queue.  Greedy output stays
      bitwise-identical to the dense engine and ``generate()`` (int8
      aside); resident KV HBM scales with live tokens instead of
      S x MAX.  See docs/serving.md.
    - ``quant_mode="int8"`` (or ``"fp8"``) pre-quantizes the model's
      Linear weights once (per-output-channel absmax scales, via
      ``generation.quantize_weights``) and routes every decode-chunk
      linear through the ``quant_matmul`` kernel dispatch — the
      weight-stream-bound decode reads 1 byte/weight instead of 2-4.
      Greedy picks over quantized logits track bf16 at a measured
      token-agreement rate (docs/serving.md documents the contract);
      the default ``quant_mode=None`` path is untouched and stays
      bitwise-identical to ``generate()``.  Composes with both KV
      modes (int8 KV included) and speculative decoding (the draft
      model stays unquantized — it is small by construction, and
      greedy verification re-anchors output on the quantized target
      either way).
    - ``spec_decode=SpecConfig(...)`` turns on speculative decoding
      (``inference/speculative.py``): each compiled chunk runs
      draft–verify steps that emit 1..gamma+1 tokens per batched target
      forward — greedy verification keeps the output bitwise identical
      to the non-speculative engine and ``generate()``, whatever the
      drafter proposes.  Composes with both KV modes (paged: per-slot
      lengths rewind on rejection, pages stay reserved).

    The engine snapshots parameter values at construction; rebuild it
    (or call :meth:`refresh_weights`) after a training step.  Greedy
    only — sampling state per slot is future work (docs/serving.md).
    """

    def __init__(self, model, num_slots=8, chunk=32, max_seq_len=None,
                 prefill_buckets=None, dtype=None, eos_token_id=None,
                 pad_token_id=0, max_prefills_per_gap=None,
                 kv_mode="dense", page_size=16, num_pages=None,
                 kv_dtype=None, prefix_cache=True, spec_decode=None,
                 quant_mode=None):
        if chunk < 1:
            raise ValueError("chunk must be >= 1")
        if quant_mode is not None and quant_mode not in ("int8", "fp8"):
            raise ValueError(f"quant_mode {quant_mode!r} not in "
                             "(None, 'int8', 'fp8')")
        if kv_mode not in ("dense", "paged"):
            raise ValueError(f"kv_mode {kv_mode!r} not in "
                             "('dense', 'paged')")
        if kv_mode == "dense" and (kv_dtype is not None
                                   or num_pages is not None):
            raise ValueError("kv_dtype/num_pages require kv_mode='paged'")
        self._paged = kv_mode == "paged"
        # submit() is the engine's only cross-thread entry (router
        # threads, ahead of the multi-replica tier); the lock covers
        # the state submit shares with the owner loop (stats, the
        # scheduler rebind in reset) — see CONCURRENT_CLASSES.
        # RLock: reset() holds it across the whole scheduler+stats
        # transition while _init_state re-enters for the stats rebind.
        self._lock = threading.RLock()
        self.model = model
        cfg = getattr(model, "config", None) \
            or getattr(getattr(model, "model", None), "config", None)
        limit = getattr(cfg, "max_position_embeddings", None)
        self.MAX = int(max_seq_len or limit or 2048)
        if limit is not None and self.MAX > limit:
            raise ValueError(
                f"max_seq_len {self.MAX} exceeds the model's "
                f"max_position_embeddings {limit}")
        self.num_slots = int(num_slots)
        self.chunk = int(chunk)
        # fleet identity (inference/router.py sets this to the replica
        # index): rides the flight recorder's serving_sync samples so
        # the watchdog can keep per-replica throughput/queue windows
        # instead of interleaving concurrent engines into one stream
        self.replica_label = None
        self.eos = None if eos_token_id is None else int(eos_token_id)
        self.pad = int(pad_token_id)
        if prefill_buckets is None:
            b, buckets = 16, []
            while b < self.MAX:
                buckets.append(b)
                b *= 2
            prefill_buckets = buckets or [self.MAX - 1]
        self.buckets = sorted(int(b) for b in prefill_buckets)
        if self.buckets[-1] >= self.MAX:
            raise ValueError(
                "largest prefill bucket must leave room for at least one "
                f"generated token (bucket {self.buckets[-1]} >= "
                f"max_seq_len {self.MAX})")
        # a model that names the longest prefill it runs
        # (``prefill_piece``) has a longer prompt prefilled in pieces of
        # that many tokens, each padded to it (one program), carrying its
        # state and attending what the earlier pieces cached
        self._piece = getattr(model, "prefill_piece", None)
        if self._piece is not None and self._piece not in self.buckets:
            raise ValueError(
                f"the model's prefill piece {self._piece} is not one of "
                f"the prefill buckets {self.buckets}")
        self._params = [p for _, p in model.named_parameters()]
        self._kvspec = model.kv_cache_spec()
        self._refuse_unserved_kinds(kv_mode, kv_dtype, quant_mode,
                                    spec_decode)
        self._pvals = [p._value for p in self._params]
        self.cache_dtype = dominant_float_dtype(self._pvals)
        self._cast_override = dtype is not None
        if self._cast_override:
            self.cache_dtype = jnp.dtype(dtype)
            self._pvals = cast_weights(model, self._pvals,
                                       self.cache_dtype)
        self.quant_mode = quant_mode
        if quant_mode is not None:
            # weight-quantization pass AFTER the cast (mirrors
            # refresh_weights): Linear weights become QuantizedWeight
            # pytrees that ride self._pvals through every jit family
            # unchanged; F.linear dispatches them via quant_matmul
            self._pvals = quantize_weights(model, self._pvals,
                                           quant_mode)
            self._book_quant_bytes()
        apply = build_apply(model, self._params)
        pick = build_pick(True, 1.0, 0, 1.0)       # greedy, fp32 picks
        self._spec = spec_decode
        self._spec_steps = 0
        self._draft_params = []
        self._draft_pvals = []
        if spec_decode is not None:
            from .speculative import validate_spec
            validate_spec(spec_decode, model, self.MAX)
            self._spec_steps = self.chunk if spec_decode.steps is None \
                else int(spec_decode.steps)
            if self._spec_steps < 1:
                raise ValueError("SpecConfig.steps must be >= 1")
        if self._paged:
            from .kvcache import PagedKVManager
            self._kv = PagedKVManager(
                self._kvspec, self.num_slots, self.MAX, page_size,
                num_pages, self.cache_dtype, kv_dtype=kv_dtype,
                prefix_cache=prefix_cache)
            quant = self._kv.quant
        else:
            self._kv = None
            quant = False
        if self._spec is not None:
            from .speculative import (_build_spec_decode_chunk,
                                      _build_spec_prefill,
                                      build_model_drafter,
                                      build_ngram_drafter)
            sc = self._spec
            self._model_draft = sc.draft_model is not None
            if self._model_draft:
                dm = sc.draft_model
                self._draft_kvspec = dm.kv_cache_spec()
                self._draft_params = [p for _, p in dm.named_parameters()]
                self._draft_pvals = [p._value for p in self._draft_params]
                if self._cast_override:
                    self._draft_pvals = cast_weights(
                        dm, self._draft_pvals, self.cache_dtype)
                draft_apply = build_apply(dm, self._draft_params)
                drafter = build_model_drafter(draft_apply, pick, sc.gamma)
            else:
                self._draft_kvspec = []
                draft_apply = None
                drafter = build_ngram_drafter(sc.gamma, sc.ngram, self.MAX)
            # ONE jit each: jax specializes per (suffix, full) bucket
            # shape pair, so the per-bucket dict the non-spec paths keep
            # would be redundant here.  Compile telemetry
            # (observability/compilestats.py): the prefill legitimately
            # owns one compile per (suffix, full) pair; the decode
            # chunk's state shapes are fixed, so its budget is ONE —
            # a second compile is the retrace sentinel's bug class
            # (e.g. a dtype drift through refresh_weights)
            _wrap = _obs.compilestats.wrap
            self._prefill_jit = _wrap(jax.jit(
                _build_spec_prefill(apply, draft_apply, pick,
                                    self._kvspec, self._draft_kvspec,
                                    self.cache_dtype, self.MAX, self.eos,
                                    self._paged, quant),
                donate_argnums=(8, 9, 10, 11, 12, 13, 14)),
                "serving.spec_prefill",
                budget=len(self.buckets) ** 2)
            self._decode_jit = _wrap(jax.jit(
                _build_spec_decode_chunk(apply, pick, drafter,
                                         self._spec_steps, sc.gamma,
                                         self.eos, self.pad, self._paged,
                                         quant, self._model_draft),
                donate_argnums=(2, 3, 4, 5, 6, 7, 8)),
                "serving.spec_decode_chunk", budget=1)
        elif self._paged:
            from .kvcache import (_build_paged_prefill,
                                  _build_paged_decode_chunk,
                                  PREFILL_SURFACE, DECODE_SURFACE)
            _wrap = _obs.compilestats.wrap
            self._prefill_jit = {
                b: _wrap(jax.jit(_build_paged_prefill(apply, pick,
                                                      self.eos, quant),
                                 donate_argnums=(6, 7, 8, 9, 10)),
                         PREFILL_SURFACE, budget=1)
                for b in self.buckets}
            self._decode_jit = _wrap(jax.jit(
                _build_paged_decode_chunk(apply, pick, self.chunk,
                                          self.eos, self.pad, quant),
                donate_argnums=(1, 2, 3, 4, 5)),
                DECODE_SURFACE, budget=1)
        else:
            _wrap = _obs.compilestats.wrap
            self._prefill_jit = {
                b: _wrap(jax.jit(_build_prefill(apply, pick, self._kvspec,
                                                self.cache_dtype, self.MAX,
                                                self.eos),
                                 donate_argnums=(5, 6, 7, 8, 9)),
                         "serving.prefill", budget=1)
                for b in self.buckets}
            self._decode_jit = _wrap(jax.jit(
                _build_decode_chunk(apply, pick, self.chunk, self.eos,
                                    self.pad),
                donate_argnums=(1, 2, 3, 4, 5)),
                "serving.decode_chunk", budget=1)
        self.scheduler = FCFSScheduler(self.num_slots,
                                       max_prefills_per_gap)
        # MoE gates record aux loss as a side-effect attribute during
        # forward; tracing would leave a tracer behind (see generate())
        from ..incubate.distributed.models.moe.gate import BaseGate
        self._gates = [m for _, m in model.named_sublayers()
                       if isinstance(m, BaseGate)]
        self.stats = None
        self._init_state()

    def _refuse_unserved_kinds(self, kv_mode, kv_dtype, quant_mode,
                               spec_decode):
        """A cache layer that is not keys and values by head (a latent
        layer, a state layer) is served by the paged engine in full
        precision only: every other mode raises here, naming the mode
        and the kinds — no fallback that hides what ran."""
        kinds = sorted({cache_kind(s) for s in self._kvspec} - {"heads"})
        if not kinds:
            return
        for mode, on in ((f"kv_mode={kv_mode!r}", kv_mode != "paged"),
                         (f"kv_dtype={kv_dtype!r}", kv_dtype is not None),
                         (f"quant_mode={quant_mode!r}",
                          quant_mode is not None),
                         ("spec_decode", spec_decode is not None)):
            if on:
                raise ValueError(
                    f"ServingEngine: {mode} is not supported with a "
                    f"{'/'.join(kinds)} cache layer; this model is "
                    "served with "
                    "kv_mode='paged' and none of kv_dtype, quant_mode, "
                    "spec_decode")

    # -- state -------------------------------------------------------------
    def _init_state(self):
        self._init_device_state()
        with self._lock:
            self.stats = {"requests": 0, "finished": 0,
                          "decoded_tokens": 0, "chunks": 0,
                          "prefills": 0, "ttft_ms": [],
                          "max_concurrent": 0, "page_evictions": 0,
                          "spec_proposed": 0, "spec_accepted": 0,
                          "spec_verify_steps": 0, "spec_chunks": 0}
            # what the model's layers count on the device
            # (observability.devcounters), e.g. the expert layer's
            # moe_* keys: a model that names none adds none
            for key in getattr(self.model, "device_counters", ()):
                self.stats[key] = 0

    def _init_device_state(self):
        S = self.num_slots
        # the engine's trace (observability/tracing.py): one
        # ``serving.step`` span per cycle, numbered by ``_cycle``; the
        # end of the last ``serving.sync`` feeds pt_serving_host_gap_ms
        # while requests stay in flight across the boundary
        self._trace = _tracing.mint("engine")
        self._cycle = 0
        self._sync_end_ns = None
        # device counters of the cycle's programs, read at its sync
        self._counts = []
        self._tokens = jnp.full((S,), self.pad, jnp.int32)
        self._pos = jnp.zeros((S,), jnp.int32)
        self._active = jnp.zeros((S,), bool)
        self._remaining = jnp.zeros((S,), jnp.int32)
        if self._paged:
            self._kv.reset()
            self._pools = self._kv.device_pools()
            self._caches = None
        else:
            self._caches = [
                (jnp.zeros((S, self.MAX, nh, d), self.cache_dtype),
                 jnp.zeros((S, self.MAX, nh, d), self.cache_dtype))
                for nh, d in self._kvspec]
        if self._spec is not None:
            # slot token history (the n-gram drafter's haystack; also
            # what resume-by-recompute re-prefills) + the draft model's
            # compact per-slot KV (always dense, even beside paged
            # target KV — it is small by construction)
            self._history = jnp.full((S, self.MAX), self.pad, jnp.int32)
            self._draft_caches = [
                (jnp.zeros((S, self.MAX, nh, d), self.cache_dtype),
                 jnp.zeros((S, self.MAX, nh, d), self.cache_dtype))
                for nh, d in self._draft_kvspec] \
                if self._model_draft else None
        else:
            self._history = self._draft_caches = None

    def reset(self):
        """Drop all queued/in-flight work and zero the device state (the
        compiled programs are kept — bench reruns pay tracing once)."""
        # one critical section for the whole transition: a racing
        # submit() lands entirely before (its request dropped with the
        # old queue, counted in the old stats) or entirely after (new
        # scheduler, new stats) — never split across the two
        with self._lock:
            self.scheduler = FCFSScheduler(
                self.num_slots, self.scheduler.max_prefills_per_gap)
            self._init_state()

    def refresh_weights(self):
        """Re-snapshot parameter values (after a train step swapped the
        underlying arrays).  Mirrors construction exactly: a ``dtype``
        override always routes through ``cast_weights`` (identity-cached,
        so a no-op refresh is cheap) — deciding by the *current* dominant
        dtype instead would let minority-dtype params (an fp32 norm in a
        bf16 model) slip through uncast and silently retrace the decode
        program with mixed dtypes."""
        pvals = [p._value for p in self._params]
        if self._cast_override:
            pvals = cast_weights(self.model, pvals, self.cache_dtype)
        if self.quant_mode is not None:
            # re-quantize AFTER the cast, mirroring construction; the
            # pass is identity-cached on the (cast) value list, so a
            # no-op refresh re-quantizes nothing
            pvals = quantize_weights(self.model, pvals, self.quant_mode)
        self._pvals = pvals
        if self.quant_mode is not None:
            self._book_quant_bytes()
        if self._spec is not None and self._model_draft:
            dpvals = [p._value for p in self._draft_params]
            if self._cast_override:
                dpvals = cast_weights(self._spec.draft_model, dpvals,
                                      self.cache_dtype)
            self._draft_pvals = dpvals
        if self._paged:
            # cached-prefix KV belongs to the old weights; in-flight
            # slots are the user's race (same as dense), but serving a
            # stale prefix to a FUTURE admission never is
            self._kv.clear_prefix()

    def _book_quant_bytes(self):
        """Book the resident-weight bytes the quantization pass saved
        (host arithmetic over shapes/dtypes — no device sync)."""
        from ..ops.quant_dispatch import QuantizedWeight
        saved = sum(v.bytes_saved() for v in self._pvals
                    if isinstance(v, QuantizedWeight))
        _obs.set_gauge("pt_serving_quant_bytes_saved", saved)

    # -- API ---------------------------------------------------------------
    def _check_extent(self, prompt_len, total_extent):
        """Shared admission validation for :meth:`submit` and
        :meth:`submit_request`: the (resume-)prompt must fit a prefill
        bucket, the request's full extent must fit the sequence budget,
        and (paged) the pool must be able to finish it even running
        alone — discovering that mid-decode (after page pressure has
        already evicted everything else) would throw away every
        in-flight request's streamed tokens."""
        if prompt_len == 0:
            raise ValueError("empty prompt")
        if prompt_len > self._longest_prompt:
            raise ValueError(
                f"prompt length {prompt_len} exceeds the largest prompt "
                f"a prefill takes ({self._longest_prompt})")
        if total_extent > self.MAX:
            raise ValueError(
                f"prompt_len + max_new_tokens = {total_extent} exceeds "
                f"max_seq_len = {self.MAX}")
        if self._paged:
            P = self._kv.page_size
            extent = int(total_extent)
            if self._spec is not None:
                # verify steps write a gamma-token overhang past the
                # last emitted position (clamped to MAX; beyond-MAX
                # writes are trash-paged)
                extent = min(extent + self._spec.gamma, self.MAX)
            full = -(-extent // P)
            if full > self._kv.num_pages - 1:
                raise ValueError(
                    f"request needs {full} KV pages at full decode but "
                    f"the pool has {self._kv.num_pages - 1} allocatable "
                    f"pages — raise num_pages (or page_size) or lower "
                    "max_new_tokens")

    def submit(self, prompt, max_new_tokens=32, callback=None):
        """Queue one request; returns its :class:`Request`.  ``prompt``
        is a 1-D int sequence (list/np array/Tensor)."""
        prompt = np.asarray(getattr(prompt, "_value", prompt),
                            dtype=np.int32).reshape(-1)
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self._check_extent(int(prompt.size),
                           int(prompt.size) + int(max_new_tokens))
        # the lock spans the scheduler handoff: a submit racing reset()
        # must land entirely on the old scheduler (whose queued work
        # reset drops) or entirely on the new one — never return a
        # Request parked on an abandoned queue after the new stats
        # dict already counted it.  Lock order is engine -> scheduler
        # (nothing takes them in reverse).
        with self._lock:
            self.stats["requests"] += 1
            return self.scheduler.submit(prompt, max_new_tokens,
                                         callback)

    def submit_request(self, req):
        """Enqueue an *existing* :class:`Request` — the fleet router's
        dispatch seam (``inference/router.py``).  Same validation as
        :meth:`submit`, but the Request object (id, callback, trace id,
        already-streamed tokens) is preserved, so a request drained off
        a dead replica re-enters here and resumes by recompute exactly
        like a page-pressure re-admission (bitwise-equivalent output).
        Like :meth:`submit`, this is a declared cross-thread entry (the
        router dispatches while the replica loop steps)."""
        budget = req.max_new_tokens - len(req.tokens)
        if budget < 1:
            raise ValueError(
                f"request {req.req_id} has no generation budget left")
        rp = self._resume_prompt(req)
        self._check_extent(int(rp.size),
                           int(req.prompt.size) + int(req.max_new_tokens))
        with self._lock:
            self.stats["requests"] += 1
            self.scheduler.enqueue(req)
        return req

    def drain(self):
        """Remove and return every queued + in-flight request (oldest
        first) and rebuild the engine's device state — the replica
        lifecycle seam: the router drains a dead or scaled-down replica
        and re-routes the requests to survivors, where they resume by
        recompute (prompt + streamed tokens re-prefill, bitwise-
        equivalent to uninterrupted decode).

        Contract: call only with the engine loop quiesced (the replica
        worker dead or joined) — drain rebuilds the slot/KV device
        state from scratch, so it must never race a ``step()``.  That
        also makes it safe after a mid-step crash left donated buffers
        invalidated: nothing here reads the old device arrays."""
        with self._lock:
            for slot in sorted(self.scheduler.active):
                self.scheduler.requeue(slot)
                if self._paged:
                    self._kv.release(slot, evicted=True)
            out = self.scheduler.drain_queue()
            self._init_device_state()
        return out

    def step(self):
        """One engine cycle: admit queued requests into free slots
        (compiled bucket prefills), run one compiled decode chunk over
        all slots, then ONE host sync that streams tokens and frees
        finished slots.  Returns the requests finished this cycle."""
        toks = valid = None
        saved_losses = [g.loss for g in self._gates]
        trace, cyc = self._trace, self._cycle
        self._cycle += 1
        with _tracing.region(trace, cyc, "serving.step") as sp:
            # ``last`` is the child that ended last: the next one starts
            # at its end, so the children tile the step
            try:
                with _tracing.region(trace, cyc, "serving.admit",
                                     parent=sp.id,
                                     start_ns=sp.start_ns) as last:
                    if self._paged:
                        self._page_pressure()
                    pending = self._admit(cyc, last.id)
                if self._paged and self.scheduler.queue_depth and \
                        not pending and not self.scheduler.active:
                    head = self.scheduler._queue[0]
                    raise RuntimeError(
                        f"kv page pool too small: request {head.req_id} "
                        f"(resume length {self._resume_prompt(head).size}, "
                        f"budget {head.max_new_tokens - len(head.tokens)}) "
                        f"cannot be admitted even with all "
                        f"{self._kv.num_pages - 1} pages free — raise "
                        "num_pages or lower max_new_tokens")
                if self.scheduler.active:
                    with _tracing.region(trace, cyc, "serving.decode_chunk",
                                         parent=sp.id,
                                         start_ns=last.end_ns) as last:
                        toks, valid = self._dispatch_chunk()
                    if self._sync_end_ns is not None and \
                            last.end_ns is not None:
                        _obs.observe(
                            "pt_serving_host_gap_ms",
                            (last.end_ns - self._sync_end_ns) / 1e6)
                    self.stats["chunks"] += 1
                    _obs.inc("pt_serving_chunks_total")
            finally:
                for g, l in zip(self._gates, saved_losses):
                    object.__setattr__(g, "loss", l)
            self.stats["max_concurrent"] = max(
                self.stats["max_concurrent"], len(self.scheduler.active))
            finished, sp.end_ns = self._sync(pending, toks, valid, cyc,
                                             sp.id, last.end_ns)
            sp.args["in_flight"] = len(self.scheduler.active)
        return finished

    def _dispatch_chunk(self):
        """Upload the page table and dispatch one compiled decode chunk
        over all slots; returns the chunk's (tokens, valid) device
        handles, read back at the sync."""
        if self._spec is not None:
            kv = self._pools if self._paged else self._caches
            table = jnp.asarray(self._kv.table) \
                if self._paged else None
            (self._tokens, self._pos, self._active,
             self._remaining, kv, self._draft_caches,
             self._history, toks, valid) = \
                self._decode_jit(
                    self._pvals, self._draft_pvals,
                    self._tokens, self._pos, self._active,
                    self._remaining, kv, self._draft_caches,
                    self._history, table)
            if self._paged:
                self._pools = kv
                self._kv.set_pools(kv)
            else:
                self._caches = kv
            self.stats["spec_chunks"] += 1
            _obs.inc("pt_serving_spec_draft_chunks_total")
        elif self._paged:
            (self._tokens, self._pos, self._active,
             self._remaining, self._pools, toks, valid, counts) = \
                self._decode_jit(
                    self._pvals, self._tokens, self._pos,
                    self._active, self._remaining,
                    self._pools, jnp.asarray(self._kv.table))
            self._kv.set_pools(self._pools)
            self._counts.append(counts)
        else:
            (self._tokens, self._pos, self._active,
             self._remaining, self._caches, toks, valid) = \
                self._decode_jit(
                    self._pvals, self._tokens, self._pos,
                    self._active, self._remaining,
                    self._caches)
        return toks, valid

    def run(self, timeout=None):
        """Drain the queue and all in-flight slots; returns finished
        requests in submission order.  Emits a ``serving_stats``
        guardian event with the run's counters."""
        was_training = self.model.training
        self.model.eval()
        finished = []
        t0 = time.perf_counter()
        try:
            while self.scheduler.has_work:
                finished.extend(self.step())
                if timeout is not None and \
                        time.perf_counter() - t0 > timeout:
                    raise TimeoutError(
                        f"serving run exceeded {timeout}s with "
                        f"{self.scheduler.queue_depth} queued / "
                        f"{len(self.scheduler.active)} in-flight")
        finally:
            if was_training:
                self.model.train()
        wall = time.perf_counter() - t0
        ttfts = self.stats["ttft_ms"]
        guardian.emit(
            "serving_stats",
            requests=self.stats["requests"],
            decoded_tokens=self.stats["decoded_tokens"],
            chunks=self.stats["chunks"],
            prefills=self.stats["prefills"],
            mean_ttft_ms=round(sum(ttfts) / len(ttfts), 3) if ttfts
            else None,
            tokens_per_sec=round(self.stats["decoded_tokens"]
                                 / max(wall, 1e-9), 1),
            queue_depth=self.scheduler.queue_depth)
        _obs.set_gauge("pt_serving_useful_tokens_per_sec",
                       self.stats["decoded_tokens"] / max(wall, 1e-9))
        if self._spec is not None:
            prop = self.stats["spec_proposed"]
            acc = self.stats["spec_accepted"]
            # per SLOT-step (0..gamma, the accept_len histogram's
            # domain), not per batched verify step — dividing by
            # verify_steps would scale with slot occupancy
            part = prop // max(self._spec.gamma, 1)
            guardian.emit(
                "serving_spec_accept", gamma=self._spec.gamma,
                proposed=prop, accepted=acc,
                accept_rate=round(acc / prop, 4) if prop else None,
                mean_accept_len=round(acc / part, 3) if part else None,
                verify_steps=self.stats["spec_verify_steps"])
        return sorted(finished, key=lambda r: r.req_id)

    # -- paged-KV internals ------------------------------------------------
    def _coverage_page(self, req):
        """Highest logical page the NEXT decode chunk can write for this
        request's slot (host arithmetic from sync-time counters, the
        manager's shared coverage formula)."""
        pos = req.resume_len + max(0, req.emitted_since_admit - 1)
        left = req.max_new_tokens - len(req.tokens)
        if self._spec is not None:
            # each verify step writes gamma+1 positions from a pos that
            # advances only by what it commits, so a chunk's write
            # extent is min(steps*(gamma+1), left + gamma) tokens:
            # emissions are capped by the budget (then the slot goes
            # inactive and trash-pages its writes), and the final
            # step's overhang adds at most gamma
            g = self._spec.gamma
            return self._kv.coverage_page(pos, left + g,
                                          self._spec_steps * (g + 1))
        return self._kv.coverage_page(pos, left, self.chunk)

    @property
    def _longest_prompt(self):
        """The largest prefill bucket, or any prompt short of
        ``max_seq_len`` where the model prefills in pieces."""
        return self.buckets[-1] if self._piece is None else self.MAX - 1

    def _resume_fits(self, req):
        n = req.prompt.size + len(req.tokens)
        return n <= self._longest_prompt

    def _pick_victim(self, keep):
        """Youngest-admitted active request whose resume prompt still
        fits a prefill bucket — protect older work, and never strand a
        request that could not be re-prefilled."""
        cands = sorted(
            ((s, r) for s, r in self.scheduler.active.items()
             if s != keep and self._resume_fits(r)),
            key=lambda sr: sr[1].admit_ns, reverse=True)
        return cands[0][0] if cands else None

    def _evict(self, slot):
        """Preempt one in-flight request: free its pages, flag the slot
        inactive on device, and requeue it at the front (it resumes by
        recompute — prompt + streamed tokens re-prefill as one prompt,
        bitwise-equivalent to uninterrupted decode)."""
        req = self.scheduler.requeue(slot)
        pages = self._kv.release(slot, evicted=True)
        self._active = self._active.at[slot].set(False)
        self.stats["page_evictions"] += 1
        guardian.emit("serving_page_evict", req_id=req.req_id, slot=slot,
                      pages_freed=pages,
                      resume_len=req.prompt.size + len(req.tokens),
                      queue_depth=self.scheduler.queue_depth)
        # trace marker from the requeue stamp the scheduler just took —
        # a host clock read between chunks, not a device sync
        _tracing.instant(req.trace_id, req.req_id, "page_evict",
                         req.requeue_ns, pages_freed=pages,
                         **({} if req.replica is None
                            else {"replica": req.replica}))
        return req

    def _page_pressure(self):
        """Before each chunk, grow every active slot's page table to
        cover the chunk's writes, oldest request first; when the pool
        runs dry, evict the youngest in-flight request back to the
        queue and retry (so the oldest always makes progress — the
        no-livelock guarantee page-pressure tests rely on)."""
        order = sorted(self.scheduler.active.items(),
                       key=lambda sr: sr[1].admit_ns)
        for slot, req in order:
            if self.scheduler.active.get(slot) is not req:
                continue                      # evicted earlier this gap
            while not self._kv.ensure(slot, self._coverage_page(req)):
                victim = self._pick_victim(keep=slot)
                if victim is None:
                    if not self._resume_fits(req):
                        raise RuntimeError(
                            f"kv page pool exhausted and request "
                            f"{req.req_id} can neither grow nor be "
                            f"evicted (resume length "
                            f"{req.prompt.size + len(req.tokens)} "
                            f"exceeds the largest prompt a prefill takes, "
                            f"{self._longest_prompt})")
                    victim = slot
                self._evict(victim)
                if victim == slot:
                    break

    # -- internals ---------------------------------------------------------
    def _bucket_for(self, n):
        for b in self.buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds largest bucket")

    def _resume_prompt(self, req):
        """The token sequence a (re-)admission prefills: the original
        prompt plus any tokens already streamed before a page-pressure
        eviction — resume-by-recompute, which is bitwise-equivalent to
        never having been evicted (chunked causal prefill is exact)."""
        if req.tokens:
            return np.concatenate(
                [req.prompt, np.asarray(req.tokens, np.int32)])
        return req.prompt

    def _import_bundle(self, req, slot, h):
        """Import phase of the prefill/decode handoff (the decode-side
        half of ``inference/handoff.py``): verify + scatter the
        checksummed bundle into this engine's pool under the
        reservation ticket, then extend the slot's page table to cover
        the first decode chunk.  Any failure leaves the pool untouched
        (checksum verification precedes every write; a coverage
        shortfall releases exactly the just-imported mapping) and
        books the fallback on the record — the caller then falls
        through to a local re-prefill."""
        from .kvcache import KVBundleError
        try:
            self._kv.import_pages(slot, h.bundle.payload,
                                  ticket=h.ticket)
        except (KVBundleError, KeyError, ValueError, RuntimeError) as e:
            h.import_failed("import_rejected", detail=e)
            return False
        n = int(h.bundle.prompt_len)
        budget = req.max_new_tokens - len(req.tokens)
        # the bundle maps pages only through position n, but the first
        # decode chunk runs in THIS step (after _page_pressure already
        # passed): grow coverage now or the chunk's scatter would land
        # in the trash page and silently corrupt the slot
        unresumable = n + budget > self.buckets[-1]
        horizon = budget if unresumable else self.chunk
        if not self._kv.ensure(slot,
                               self._kv.coverage_page(n, budget,
                                                      horizon)):
            self._kv.release(slot)
            h.import_failed("decode_pool_pressure")
            return False
        # the import rebuilt the manager's pool arrays: refresh the
        # engine's handles NOW so a normal admission later in this
        # same gap prefills against (and set_pools preserves) the
        # imported data instead of clobbering it with stale pools
        self._pools = self._kv.device_pools()
        return True

    def _admit(self, cycle, parent):
        """Admit queued requests into free slots (bounded by the
        interleave knob): one compiled bucket prefill each, KV written
        straight into the assigned slot (dense) or into reserved pages
        (paged; a prefix-cache hit prefills only the uncached suffix).
        Returns the pending (request, first-token, finished-flag) device
        handles — read back at the chunk-boundary sync, never here.
        Each prefill dispatch books a ``serving.prefill`` span of this
        ``cycle`` under ``parent`` (the cycle's ``serving.admit``)."""
        pending = []
        bound, armed, can_admit = {}, {}, None
        if self._paged:
            def can_admit(req, slot):
                h = req.handoff
                if h is not None:
                    # disaggregated prefill/decode (inference/
                    # handoff.py): single-shot — whatever happens in
                    # the import, a later (re-)admission of this
                    # request must take the normal resume path below
                    req.handoff = None
                    if h.consume() and self._import_bundle(req, slot, h):
                        armed[req.req_id] = h
                        return True
                    # fall through: local re-prefill on THIS replica —
                    # the protocol's fallback leg runs inside the same
                    # admission, so FCFS head-of-line order holds
                # reserve AND bind here (atomically per admission) so a
                # later admission in the same gap can already hit this
                # prompt's freshly registered prefix pages
                rp = self._resume_prompt(req)
                budget = req.max_new_tokens - len(req.tokens)

                def fit(k):
                    m = rp.size - k
                    return m <= self.buckets[-1] and \
                        k + self._bucket_for(m) <= self.MAX
                # a request that could outgrow the largest prefill
                # bucket would become UN-resumable mid-decode (evicting
                # it then would strand it); reserve its full extent up
                # front so it never needs to grow — every growth-time
                # allocation below then belongs to a resumable request,
                # which can always self-evict, so page pressure can
                # never hard-fail the run
                unresumable = rp.size + budget > self.buckets[-1]
                if self._spec is not None:
                    # plan in WRITE tokens: the worst-case extent is
                    # budget + gamma (pos advances only by committed
                    # tokens; the final step overhangs by at most
                    # gamma), additionally capped per chunk by
                    # steps*(gamma+1) — NOT budget*(gamma+1), which
                    # would over-demand pages and let a small-budget
                    # request submit() accepted hard-fail admission
                    g = self._spec.gamma
                    horizon = budget + g if unresumable \
                        else self._spec_steps * (g + 1)
                    plan_budget = budget + g
                else:
                    horizon = budget if unresumable else self.chunk
                    plan_budget = budget
                plan = self._kv.plan(rp, plan_budget, horizon, fit=fit)
                if plan is None:
                    return False
                k = self._kv.bind(slot, plan,
                                  register_limit=req.prompt.size)
                bound[req.req_id] = (rp, k)
                return True
        for req, slot in self.scheduler.admissions(can_admit):
            if req.req_id in armed:
                # arm phase of the prefill/decode handoff: the slot's
                # KV pages were imported (checksum-verified) in the
                # gate above — rebuild host/device state exactly as
                # the compiled prefill would have left it (position n,
                # first token seeded, budget-1 remaining) and skip the
                # prefill dispatch entirely: no suffix re-prefill
                h = armed.pop(req.req_id)
                n = int(h.bundle.prompt_len)
                budget = req.max_new_tokens - len(req.tokens)
                t0 = int(h.bundle.first_token)
                fin0 = (self.eos is not None and t0 == self.eos) \
                    or budget <= 1
                self._tokens = self._tokens.at[slot].set(t0)
                self._pos = self._pos.at[slot].set(n)
                self._active = self._active.at[slot].set(not fin0)
                self._remaining = self._remaining.at[slot].set(budget - 1)
                req.prefix_cached = 0
                req.resume_len = n
                req.emitted_since_admit = 0
                req.bucket = h.bundle.bucket
                pending.append((req, slot, t0, fin0))
                h.armed(slot)
                guardian.emit("serving_admit", req_id=req.req_id,
                              slot=slot,
                              queue_depth=self.scheduler.queue_depth,
                              prompt_len=n, bucket=h.bundle.bucket)
                if _obs.enabled():
                    _obs.inc("pt_serving_admissions_total")
                    if req.evictions == 0:
                        _obs.observe("pt_serving_queue_wait_ms",
                                     req.queue_wait_ms)
                continue
            if self._paged:
                rp, k = bound.pop(req.req_id)
                n, m = int(rp.size), int(rp.size) - k
                budget = req.max_new_tokens - len(req.tokens)
                # a suffix longer than the model's prefill piece goes in
                # pieces of it; any other in one program of its bucket
                bucket = self._piece if self._piece is not None and \
                    m > self._piece else self._bucket_for(m)

                def padded(at, count):
                    ids = np.full((1, bucket), self.pad, np.int32)
                    ids[0, :count] = rp[at:at + count]
                    return jnp.asarray(ids)
                req.prefix_cached = k
                req.resume_len = n
                req.emitted_since_admit = 0
                with _tracing.region(self._trace, cycle, "serving.prefill",
                                     parent=parent, request=req.req_id,
                                     bucket=bucket):
                    if self._spec is not None:
                        # the draft (and the token history) prefill the
                        # FULL resume prompt — the draft has no prefix
                        # cache to cover a suffix-only start
                        bucket_f = self._bucket_for(n)
                        ids_f = np.full((1, bucket_f), self.pad, np.int32)
                        ids_f[0, :n] = rp
                        (t0, fin0, self._tokens, self._pos, self._active,
                         self._remaining, self._pools,
                         self._draft_caches, self._history) = \
                            self._prefill_jit(
                                self._pvals, self._draft_pvals,
                                jnp.asarray(ids_f), padded(k, m),
                                jnp.asarray(k, jnp.int32),
                                jnp.asarray(m, jnp.int32),
                                jnp.asarray(slot, jnp.int32),
                                jnp.asarray(int(budget), jnp.int32),
                                self._tokens, self._pos, self._active,
                                self._remaining, self._pools,
                                self._draft_caches, self._history,
                                jnp.asarray(self._kv.table))
                    else:
                        table = jnp.asarray(self._kv.table)
                        # the last piece's first token and state are what
                        # the decode goes on from
                        for at in range(k, n, bucket):
                            count = min(bucket, n - at)
                            (t0, fin0, self._tokens, self._pos,
                             self._active, self._remaining, self._pools,
                             counts) = self._prefill_jit[bucket](
                                self._pvals, padded(at, count),
                                jnp.asarray(at, jnp.int32),
                                jnp.asarray(count, jnp.int32),
                                jnp.asarray(slot, jnp.int32),
                                jnp.asarray(int(budget), jnp.int32),
                                self._tokens, self._pos, self._active,
                                self._remaining, self._pools, table)
                            self._counts.append(counts)
                self._kv.set_pools(self._pools)
                if k:
                    guardian.emit("serving_prefix_hit", req_id=req.req_id,
                                  slot=slot, cached_tokens=k,
                                  pages_shared=k // self._kv.page_size,
                                  prompt_len=n)
            else:
                # resume-by-recompute works on the dense path too (the
                # fleet router requeues a dead replica's in-flight work
                # here): the resume prompt re-prefills prompt + already-
                # streamed tokens with the REMAINING budget — for a
                # fresh request this is exactly the original formulation
                rp = self._resume_prompt(req)
                n = int(rp.size)
                budget = req.max_new_tokens - len(req.tokens)
                bucket = self._bucket_for(n)
                ids = np.full((1, bucket), self.pad, np.int32)
                ids[0, :n] = rp
                req.resume_len = n
                req.emitted_since_admit = 0
                with _tracing.region(self._trace, cycle, "serving.prefill",
                                     parent=parent, request=req.req_id,
                                     bucket=bucket):
                    if self._spec is not None:
                        ids_j = jnp.asarray(ids)   # full == suffix: no
                        (t0, fin0, self._tokens,   # dense prefix cache
                         self._pos, self._active, self._remaining,
                         self._caches, self._draft_caches,
                         self._history) = self._prefill_jit(
                            self._pvals, self._draft_pvals, ids_j, ids_j,
                            jnp.zeros((), jnp.int32),
                            jnp.asarray(n, jnp.int32),
                            jnp.asarray(slot, jnp.int32),
                            jnp.asarray(int(budget), jnp.int32),
                            self._tokens, self._pos, self._active,
                            self._remaining, self._caches,
                            self._draft_caches, self._history)
                    else:
                        (t0, fin0, self._tokens, self._pos, self._active,
                         self._remaining, self._caches) = \
                            self._prefill_jit[bucket](
                                self._pvals, jnp.asarray(ids),
                                jnp.asarray(n, jnp.int32),
                                jnp.asarray(slot, jnp.int32),
                                jnp.asarray(int(budget), jnp.int32),
                                self._tokens, self._pos, self._active,
                                self._remaining, self._caches)
            self.stats["prefills"] += 1
            req.bucket = bucket
            pending.append((req, slot, t0, fin0))
            guardian.emit("serving_admit", req_id=req.req_id, slot=slot,
                          queue_depth=self.scheduler.queue_depth,
                          prompt_len=n, bucket=bucket)
            # telemetry: all host values (scheduler stamps + static
            # bucket metadata) — nothing here reads the device
            if _obs.enabled():
                _obs.inc("pt_serving_admissions_total")
                _obs.inc("pt_serving_prefills_total", bucket=str(bucket))
                if req.evictions == 0:
                    # a page-pressure re-admission re-stamps admit_ns;
                    # submit->admit would then count the earlier decode
                    # span as "queue wait" and inflate the histogram
                    # exactly in the overload regime it diagnoses
                    _obs.observe("pt_serving_queue_wait_ms",
                                 req.queue_wait_ms)
        if pending and _obs.enabled():
            _obs.set_gauge("pt_serving_slot_occupancy",
                           len(self.scheduler.active))
            _obs.set_gauge("pt_serving_queue_depth",
                           self.scheduler.queue_depth)
        return pending

    def _sync(self, pending, toks, valid, cycle, parent, start_ns):
        """THE chunk-boundary host sync: one ``jax.device_get`` of the
        prefill first-tokens + decode-chunk tokens + slot liveness
        (``serving.sync``), then the delivery (``serving.deliver``).
        Returns (finished requests, end of the delivery)."""
        with _tracing.region(self._trace, cycle, "serving.sync",
                             parent=parent, start_ns=start_ns) as sy:
            counts, self._counts = self._counts, []
            bundle = jax.device_get(
                ([(t0, fin0) for _, _, t0, fin0 in pending],
                 toks, valid, self._active, counts))
        with _tracing.region(self._trace, cycle, "serving.deliver",
                             parent=parent, start_ns=sy.end_ns) as dl:
            self._book_device_counters(bundle[4])
            finished = self._deliver(pending, bundle[:4], parent)
        # the host gap to the next chunk's dispatch counts only while
        # requests stay in flight: an idle engine's wait is no host work
        self._sync_end_ns = sy.end_ns if self.scheduler.active else None
        return finished, dl.end_ns

    def _book_device_counters(self, counts):
        """Fold what the cycle's programs counted on the device (host
        values of the sync's one readback) into ``stats`` and the
        ``pt_serving_<key>`` metrics of the same names."""
        for part in counts:
            for key, value in part["sum"].items():
                with self._lock:
                    self.stats[key] += int(value)
                _obs.inc(_DEVICE_COUNTER_METRICS[key], int(value))
            for key, value in part["max"].items():
                with self._lock:
                    most = self.stats[key] = max(self.stats[key],
                                                 int(value))
                _obs.set_gauge(_DEVICE_COUNTER_METRICS[key], most)

    def _deliver(self, pending, bundle, parent):
        """Everything after the readback, all on host values it brought:
        stream callbacks, stamp TTFT, book the request spans (children of
        ``parent``, the cycle's ``serving.step``) and free finished
        slots."""
        first, toks_h, valid_h, active_h = bundle
        now = time.perf_counter_ns()
        new_ttfts = []       # stamped THIS sync (flight-recorder sample)
        # per-slot emissions this cycle, in chronological order:
        # the prefill's first token, then the chunk's tokens
        emitted = {}
        for (req, slot, _, _), (t0, fin0) in zip(pending, first):
            if req.first_token_ns is None:
                # guard for paged re-admission after eviction: TTFT is
                # the FIRST first-token, not the resume's
                req.first_token_ns = now
                self.stats["ttft_ms"].append(req.ttft_ms)
                _obs.observe("pt_serving_ttft_ms", req.ttft_ms)
                new_ttfts.append(round(req.ttft_ms, 3))
            emitted[slot] = [int(t0)]
            if fin0:
                req.finish_reason = "eos" if (
                    self.eos is not None and int(t0) == self.eos) \
                    else "budget"
        if toks_h is not None and toks_h.ndim == 3:
            # speculative chunk: (steps, S, gamma+1) — stream each verify
            # step's accepted prefix in order, and book acceptance from
            # the SAME readback (no extra sync): a slot that emitted at
            # all was offered gamma drafts and accepted e-1 of them
            gamma = self._spec.gamma
            for s in range(toks_h.shape[0]):
                vstep = valid_h[s]                       # (S, gamma+1)
                part = np.nonzero(vstep[:, 0])[0]
                if part.size:
                    self.stats["spec_verify_steps"] += 1
                    _obs.inc("pt_serving_spec_verify_steps_total")
                for slot in part:
                    e = int(vstep[slot].sum())
                    acc = e - 1
                    emitted.setdefault(int(slot), []).extend(
                        int(t) for t in toks_h[s, slot, :e])
                    self.stats["spec_proposed"] += gamma
                    self.stats["spec_accepted"] += acc
                    req = self.scheduler.active.get(int(slot))
                    if req is not None:
                        req.spec_proposed += gamma
                        req.spec_accepted += acc
                    if _obs.enabled():
                        _obs.inc("pt_serving_spec_proposed_total", gamma)
                        if acc:
                            _obs.inc("pt_serving_spec_accepted_total",
                                     acc)
                        _obs.observe("pt_serving_spec_accept_len", acc)
        elif toks_h is not None:
            for s in range(toks_h.shape[0]):
                for slot in np.nonzero(valid_h[s])[0]:
                    emitted.setdefault(int(slot), []).append(
                        int(toks_h[s, slot]))
        finished = []
        admitted_slots = {slot for _, slot, _, _ in pending}
        for slot, toks_slot in sorted(emitted.items()):
            req = self.scheduler.active[slot]
            req.tokens.extend(toks_slot)
            req.emitted_since_admit += len(toks_slot)
            if req.finish_reason is None and not bool(active_h[slot]):
                last = toks_slot[-1] if toks_slot else None
                req.finish_reason = "eos" if (
                    self.eos is not None and last == self.eos) \
                    else "budget"
            self.stats["decoded_tokens"] += len(toks_slot)
            _obs.inc("pt_serving_decoded_tokens_total", len(toks_slot))
            done = req.finish_reason is not None
            # request-scoped trace spans, booked from host stamps the
            # engine already owns (scheduler clocks + THIS sync's
            # ``now``): queue_wait + prefill for this cycle's
            # admissions, one decode span per chunk participation —
            # per request they tile submit -> finish exactly
            if _obs.enabled():
                # spans carry the replica label when the request came
                # through the fleet router (report --requests
                # --per-replica groups on it); single-engine traces are
                # unchanged
                rep = {} if req.replica is None \
                    else {"replica": req.replica}
                if slot in admitted_slots:
                    # queue wait restarts at the LATEST of submit, the
                    # page-pressure requeue, and the router's dispatch
                    # stamp — the route span (router-side) ends where
                    # this one starts, so per-request spans still tile
                    qstart = max(s for s in (req.submit_ns,
                                             req.requeue_ns,
                                             req.route_ns) if s)
                    _tracing.span(req.trace_id, req.req_id, "queue_wait",
                                  qstart, req.admit_ns, parent=parent,
                                  resume=req.evictions > 0, **rep)
                    _tracing.span(req.trace_id, req.req_id, "prefill",
                                  req.admit_ns, now, parent=parent,
                                  bucket=req.bucket,
                                  cached_tokens=req.prefix_cached,
                                  resume=req.evictions > 0,
                                  tokens=len(toks_slot),
                                  reason=req.finish_reason, **rep)
                else:
                    start = req.span_ns or req.admit_ns
                    _tracing.span(req.trace_id, req.req_id,
                                  "spec_decode" if self._spec is not None
                                  else "decode",
                                  start, now, parent=parent,
                                  tokens=len(toks_slot),
                                  reason=req.finish_reason, **rep)
            # decode_ms (the TPOT numerator) and the span cursor are
            # host stamps the flight recorder reads too, so they
            # accumulate whether or not the metrics gate is on — a
            # flight sample must never report tpot=0 just because
            # telemetry was disabled
            if slot not in admitted_slots:
                req.decode_ms += \
                    (now - (req.span_ns or req.admit_ns)) / 1e6
            req.span_ns = now
            if req.callback is not None:
                for i, tok in enumerate(toks_slot):
                    req.callback(req, tok,
                                 done and i == len(toks_slot) - 1)
            if done:
                req.finish_ns = now
                # TPOT = decode-phase span time per token after the
                # first (the catalog contract; same numerator as
                # `report --requests`) — NOT wall since first token,
                # which would fold an evicted request's requeue wait
                # and re-prefill into its per-token time
                _tracing.finish(
                    req.decode_ms / (len(req.tokens) - 1)
                    if len(req.tokens) > 1 else None)
                self.scheduler.release(slot)
                if self._paged:
                    self._kv.release(slot)
                self.stats["finished"] += 1
                finished.append(req)
                guardian.emit("serving_finish", req_id=req.req_id,
                              slot=slot, tokens=len(req.tokens),
                              ttft_ms=round(req.ttft_ms, 3),
                              reason=req.finish_reason)
                _obs.inc("pt_serving_evictions_total",
                         reason=req.finish_reason)
        if finished and _obs.enabled():
            _obs.set_gauge("pt_serving_slot_occupancy",
                           len(self.scheduler.active))
        # flight recorder: one sample per chunk-boundary sync plus one
        # per finish — all values are host numbers this sync already
        # produced (the bundled device_get above is the ONLY transfer)
        if _obs.flight.active():
            _obs.flight.record(
                "serving_sync",
                decoded_tokens=sum(len(t) for t in emitted.values()),
                queue_depth=self.scheduler.queue_depth,
                active=len(self.scheduler.active),
                finished=len(finished), ttft_ms=new_ttfts,
                replica=self.replica_label,
                # live-buffer census (HBM ledger): host metadata only,
                # taken at this pre-existing sync — feeds hbm_pressure
                **_obs.memory.census_fields("serving_sync"))
            for req in finished:
                _obs.flight.record(
                    "request",
                    ttft_ms=(round(req.ttft_ms, 3)
                             if req.first_token_ns else None),
                    tpot_ms=(round(req.decode_ms /
                                   (len(req.tokens) - 1), 3)
                             if len(req.tokens) > 1 else None),
                    replica=req.replica, reason=req.finish_reason,
                    tokens=len(req.tokens))
        return finished
