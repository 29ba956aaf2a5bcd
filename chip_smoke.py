"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of GPT-3 125M (``models/gpt.py`` ``gpt3_125m``: 12 layers,
hidden 768, 12 heads of 64, vocab 50,304, S=1024, bf16 compute over fp32
master weights; random seeded weights):

  train    ``paddle.Model(...).prepare(AdamW, criterion, O2 bf16)`` +
           ``Model.fit`` over a seeded synthetic ``Dataset`` through the
           real ``DataLoader``, B=22 S=1024, 8 steps (B=24 does not fit
           under the hapi stepper, which returns the fp32 logits: the
           step then needs 10.0 GiB of program memory beside a 4.6 GiB
           logits output and 1.5 GiB of state on a 15.75 GiB chip; B=22
           ran, B=23 was not tried — CHANGES.md PR 21);
  serve    the same network in ``eval()`` behind
           ``ServingEngine(kv_mode="paged")``: every request equals
           ``net.generate()``; then ``ServingEngine(quant_mode="int8")``;
  kernels  every Pallas kernel in ``ops/pallas/`` once, compiled, at a
           shape a supported model uses, against its reference;
  facts    dispatch round trip, whether ``block_until_ready`` is a
           barrier, a host callback, peak memory, compile seconds;
  four     (>= 4 chips only) dp=2 x mp=2 ``Model.fit`` at 125M against
           the one-chip losses, one pp=2 x mp=2 pipeline step, GPT-3 1.3B.

One process (a chip belongs to one process).  It exits non-zero before
doing any work unless JAX reports platform ``tpu`` and a ``device_kind``
in the peaks table (``paddle_tpu/device/chip.py``).  A failed check ends
the run non-zero and is named; an exception is never downgraded to a
message.  The last line of stdout is ``{"ok": true, "device": {...}}``.

    python chip_smoke.py          # on the chip machine, ~7 min cold
"""
import dataclasses
import gc
import json
import math
import os
import statistics
import sys
import time

import numpy as np

TRAIN_B, TRAIN_S, TRAIN_STEPS = 22, 1024, 8
SERVE_PROMPT_LENS = (7, 16, 17, 33, 90)    # 16|17 and 33 cross a bucket
SERVE_NEW_TOKENS = 64
SEED = 0


def say(phase, **facts):
    """One JSON line per phase, flushed (the run may be cut short)."""
    print(json.dumps({"phase": phase, **facts}), flush=True)


class Checks:
    """Named pass/fail checks of one phase; ``finish`` ends the run
    non-zero naming every failed check."""

    def __init__(self, phase):
        self.phase = phase
        self.failed = []

    def check(self, name, ok, detail=""):
        if not ok:
            self.failed.append(f"{name}: {detail}" if detail else name)
        return bool(ok)

    def finish(self):
        if self.failed:
            say(self.phase, failed_checks=self.failed)
            sys.exit(f"chip_smoke: phase {self.phase!r} failed checks: "
                     + "; ".join(self.failed))


class CompileMeter:
    """Process-wide compile accounting from JAX's own monitoring events:
    backend compile seconds, compile count, persistent-cache hits and
    misses.  ``delta`` reads what a phase added."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, name, **kw):
        if name.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("compilation_cache/cache_misses"):
            self.misses += 1

    def snap(self):
        return (self.compile_s, self.compiles, self.hits, self.misses)

    def delta(self, since):
        now = self.snap()
        return {"compile_s": round(now[0] - since[0], 2),
                "compiles": now[1] - since[1],
                "cache_hits": now[2] - since[2],
                "cache_misses": now[3] - since[3]}


def peak_bytes(device):
    return int(device.memory_stats()["peak_bytes_in_use"])


def bytes_in_use(device):
    return int(device.memory_stats()["bytes_in_use"])


def metric_series(name):
    """{label-tuple: value} of one registry metric ({} when never set)."""
    from paddle_tpu.observability import metrics
    m = metrics.get_registry().get(name)
    if m is None:
        return {}
    return {tuple(sorted(lbl.items())): v for lbl, v in m.series()}


def rel_max(a, b):
    """max|a-b| / max|b| in fp32 (the docs/kernels.md error measure)."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def synthetic_tokens(n, S, V, seed):
    """Seeded learnable token stream: a skewed unigram distribution (so
    the loss has somewhere to fall within a few steps) with a
    deterministic successor on every other position."""
    rng = np.random.RandomState(seed)
    ranks = np.arange(1, V + 1, dtype=np.float64)
    p = 1.0 / ranks ** 1.1
    ids = rng.choice(V, size=(n, S), p=p / p.sum()).astype(np.int32)
    ids[:, 1::2] = (ids[:, 0::2] * 31 + 7) % V
    return ids


def make_dataset(n, S, V, seed):
    from paddle_tpu.io import Dataset

    class SyntheticLM(Dataset):
        def __init__(self):
            self.ids = synthetic_tokens(n, S, V, seed)

        def __len__(self):
            return len(self.ids)

        def __getitem__(self, i):
            return self.ids[i], self.ids[i]

    return SyntheticLM()


def fit_gpt(cfg, B, S, steps, meter, wrap=None, lr=3e-4):
    """``Model.fit`` for ``steps`` steps; returns (net, model, record).
    ``wrap`` (the four-chip phase) maps the network through
    ``fleet.distributed_model`` before ``paddle.Model`` sees it."""
    import paddle_tpu as paddle
    from paddle_tpu.io import DataLoader
    from paddle_tpu.models import GPTForPretraining, GPTPretrainingCriterion

    class Record(paddle.callbacks.Callback):
        def __init__(self):
            super().__init__()
            self.losses, self.ends, self.compiles_at = [], [], []

        def on_train_batch_end(self, step, logs=None):
            self.losses.append(float(logs["loss"]))
            self.ends.append(time.perf_counter())
            self.compiles_at.append(meter.compiles)

    paddle.seed(SEED)
    net = GPTForPretraining(cfg)
    opt = paddle.optimizer.AdamW(learning_rate=lr, weight_decay=0.01,
                                 parameters=net.parameters())
    model = paddle.Model(wrap(net) if wrap else net)
    model.prepare(opt, GPTPretrainingCriterion(),
                  amp_configs={"level": "O2", "dtype": "bfloat16"})
    loader = DataLoader(make_dataset(B * steps, S, cfg.vocab_size, SEED),
                        batch_size=B, shuffle=False, drop_last=True)
    rec = Record()
    rec.t0 = time.perf_counter()
    model.fit(loader, epochs=1, verbose=0, callbacks=[rec])
    return net, model, rec


def phase_train(cfg, B, S, steps, device, meter):
    import jax

    from paddle_tpu.observability import compilestats
    from paddle_tpu.ops import registry as kreg

    ck = Checks("train")
    before = meter.snap()
    net, model, rec = fit_gpt(cfg, B, S, steps, meter)
    losses = rec.losses
    ck.check("steps_run", len(losses) == steps, f"{len(losses)} != {steps}")
    ck.check("losses_finite", all(math.isfinite(x) for x in losses),
             str(losses))
    ck.check("loss_fell", losses[-1] < losses[0],
             f"first {losses[0]:.4f} last {losses[-1]:.4f}")
    params = [p._value for p in net.parameters()]
    ck.check("params_on_tpu",
             all(d.platform == "tpu" for v in params for d in v.devices()),
             str({d.platform for v in params for d in v.devices()}))
    # kernel selection, from the registry's counters and policy
    selects = metric_series("pt_kernel_selects_total")
    for kernel in ("attention", "xent"):
        sel = kreg.choose(kernel)
        ck.check(f"{kernel}_policy_pallas_compiled",
                 sel.impl == "pallas" and not sel.interpret, str(sel))
        n_pallas = selects.get((("impl", "pallas"), ("kernel", kernel)), 0)
        n_xla = selects.get((("impl", "xla"), ("kernel", kernel)), 0)
        ck.check(f"{kernel}_selected_pallas", n_pallas > 0 and n_xla == 0,
                 f"pallas={n_pallas} xla={n_xla}")
    fallbacks = metric_series("pt_kernel_fallbacks_total")
    ck.check("no_kernel_fallbacks", not fallbacks, str(fallbacks))
    # the Mosaic kernels are IN the compiled train step
    surface = next(iter(model._stepper._train_cache.values()))
    hlo = next(iter(surface._cache.values())).as_text()
    ck.check("train_step_has_mosaic_calls", "tpu_custom_call" in hlo,
             "no tpu_custom_call in the train step's HLO")
    stats = compilestats.snapshot().get("hapi.train_step", {})
    ck.check("one_train_compile",
             stats.get("compiles") == 1 and stats.get("retraces") == 0,
             str(stats))
    late = rec.compiles_at[-1] - rec.compiles_at[1]
    ck.check("no_compile_after_step_2", late == 0,
             f"{late} backend compile(s) after step 2")
    step_s = [b - a for a, b in zip(rec.ends[1:], rec.ends[2:])]
    say("train", batch=B, seq=S, steps=steps,
        losses=[round(x, 4) for x in losses],
        first_step_s=round(rec.ends[0] - rec.t0, 2),
        steady_step_ms=round(statistics.median(step_s) * 1e3, 1),
        tokens_per_step=B * S,
        train_step_memory_bytes=stats.get("memory_bytes"),
        peak_bytes_in_use=peak_bytes(device),
        **meter.delta(before))
    ck.finish()
    return net, model, losses


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def teacher_logits(net, pvals, ids, dtype):
    """fp32 logits (len(ids), V) of ``ids`` through the model's cached
    decode path (``generation.build_apply`` — the forward ``generate()``
    and the engine share), with ``pvals`` as the weights."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.generation import build_apply

    n = len(ids)
    padded = -(-n // 128) * 128        # causal: the tail never matters
    buf = np.zeros((1, padded), np.int32)
    buf[0, :n] = ids
    apply = build_apply(net, [p for _, p in net.named_parameters()])
    caches = [(jnp.zeros((1, padded, nh, d), dtype),
               jnp.zeros((1, padded, nh, d), dtype))
              for nh, d in net.kv_cache_spec()]
    logits, _ = jax.jit(apply)(pvals, jnp.asarray(buf), caches,
                               jnp.zeros((), jnp.int32))
    return np.asarray(logits[0, :n].astype(jnp.float32))


def phase_serve(net, prompt_lens, new_tokens, max_seq_len, device, meter):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import ServingEngine
    from paddle_tpu.ops import registry as kreg

    ck = Checks("serve")
    before = meter.snap()
    net.eval()
    V = net.config.vocab_size
    rng = np.random.RandomState(SEED + 1)
    prompts = [rng.randint(0, V, (n,)).astype(np.int32)
               for n in prompt_lens]

    # -- bf16, paged: every request equals generate() ---------------------
    eng = ServingEngine(net, kv_mode="paged", max_seq_len=max_seq_len,
                        dtype="bfloat16")
    reqs = [eng.submit(p, new_tokens) for p in prompts]
    t0 = time.perf_counter()
    done = eng.run(timeout=600)
    serve_s = time.perf_counter() - t0
    ck.check("all_requests_finished",
             len(done) == len(prompts)
             and all(len(r.tokens) == new_tokens for r in reqs),
             str([len(r.tokens) for r in reqs]))
    diverged = []
    for i, (p, r) in enumerate(zip(prompts, reqs)):
        ids, _ = net.generate(paddle.to_tensor(p[None, :]),
                              max_new_tokens=new_tokens, dtype="bfloat16")
        ref = [int(t) for t in np.asarray(ids._value)[0]]
        got = [int(t) for t in r.tokens]
        if got == ref:
            continue
        # bf16 near-tie: different program shapes (bucket-padded prefill,
        # 8-slot decode) round differently.  The check is narrowed, not
        # dropped: at the first diverging position both tokens must be
        # within bf16 tolerance of each other in the reference logits.
        at = next(j for j, (a, b) in enumerate(zip(got, ref)) if a != b)
        prefix = np.concatenate([p, np.asarray(ref[:at], np.int32)])
        row = teacher_logits(net, eng._pvals, prefix, jnp.bfloat16)[-1]
        gap = float(abs(row[ref[at]] - row[got[at]]))
        tol = float(np.max(np.abs(row))) * 2.0 ** -6     # 2 bf16 ulps
        diverged.append({"request": i, "position": at, "engine": got[at],
                         "generate": ref[at], "logit_gap": round(gap, 5),
                         "bf16_tol": round(tol, 5)})
        ck.check(f"request_{i}_matches_generate", gap <= tol,
                 f"first divergence at {at}: logit gap {gap:.5f} > bf16 "
                 f"tolerance {tol:.5f}")
    stats = dict(eng.stats)
    say("serve_bf16", requests=len(prompts), prompt_lens=list(prompt_lens),
        new_tokens=new_tokens, num_slots=eng.num_slots, chunk=eng.chunk,
        buckets_used=sorted({eng._bucket_for(n) for n in prompt_lens}),
        bitwise_equal_generate=len(prompts) - len(diverged),
        near_tie_divergences=diverged, run_s=round(serve_s, 2),
        prefills=stats["prefills"], chunks=stats["chunks"],
        decoded_tokens=stats["decoded_tokens"],
        peak_bytes_in_use=peak_bytes(device),
        **meter.delta(before))
    ref_logits = teacher_logits(net, eng._pvals, prompts[-1], jnp.bfloat16)
    del eng
    gc.collect()

    # -- int8 weights: completes, finite, the Pallas kernel compiled ------
    before = meter.snap()
    sel = kreg.choose("quant_matmul")
    ck.check("quant_matmul_policy_pallas_compiled",
             sel.impl == "pallas" and not sel.interpret, str(sel))
    selects0 = metric_series("pt_kernel_selects_total")
    eng8 = ServingEngine(net, kv_mode="paged", max_seq_len=max_seq_len,
                         dtype="bfloat16", quant_mode="int8")
    reqs8 = [eng8.submit(p, new_tokens) for p in prompts]
    t0 = time.perf_counter()
    eng8.run(timeout=600)
    serve8_s = time.perf_counter() - t0
    ck.check("int8_all_requests_finished",
             all(len(r.tokens) == new_tokens for r in reqs8),
             str([len(r.tokens) for r in reqs8]))
    ck.check("int8_tokens_in_vocab",
             all(0 <= int(t) < V for r in reqs8 for t in r.tokens))
    selects = metric_series("pt_kernel_selects_total")
    key_p = (("impl", "pallas"), ("kernel", "quant_matmul"))
    key_x = (("impl", "xla"), ("kernel", "quant_matmul"))
    n_pallas = selects.get(key_p, 0) - selects0.get(key_p, 0)
    n_xla = selects.get(key_x, 0) - selects0.get(key_x, 0)
    ck.check("int8_selected_pallas", n_pallas > 0 and n_xla == 0,
             f"pallas={n_pallas} xla={n_xla}")
    hlo = next(iter(eng8._decode_jit._cache.values())).as_text()
    ck.check("int8_decode_has_mosaic_calls", "tpu_custom_call" in hlo,
             "no tpu_custom_call in the int8 decode chunk's HLO")
    q_logits = teacher_logits(net, eng8._pvals, prompts[-1], jnp.bfloat16)
    ck.check("int8_logits_finite", bool(np.isfinite(q_logits).all()))
    agree = float(np.mean([a == b for r, r8 in zip(reqs, reqs8)
                           for a, b in zip(r.tokens, r8.tokens)]))
    say("serve_int8", requests=len(prompts), run_s=round(serve8_s, 2),
        logits_rel_max_vs_bf16=round(rel_max(q_logits, ref_logits), 4),
        free_running_token_agreement_vs_bf16=round(agree, 4),
        peak_bytes_in_use=peak_bytes(device),
        **meter.delta(before))
    del eng8
    gc.collect()
    ck.finish()


# ---------------------------------------------------------------------------
# kernels: each Pallas kernel once, compiled, against its reference.
# bf16 inputs (what the models feed) against an fp32 "highest"-precision
# reference of the same values; tolerances are docs/kernels.md's on-chip
# contract (relative-max error).
# ---------------------------------------------------------------------------

FWD_TOL, GRAD_TOL = 2e-2, 4e-2


def _randn(rng, shape, dtype, scale=1.0):
    import jax.numpy as jnp
    return jnp.asarray((rng.randn(*shape) * scale).astype("float32"), dtype)


def _f32(x):
    import jax.numpy as jnp
    return x.astype(jnp.float32)


def _sdpa_case(ck, name, B, S, H, D, causal, masked=False):
    """Public ``F.scaled_dot_product_attention`` forced onto the Pallas
    flash kernels (fwd + bwd) vs the registered XLA attention."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    from paddle_tpu.nn.functional.attention import _xla_attention

    rng = np.random.RandomState(len(name))
    q, k, v, g = (_randn(rng, (B, S, H, D), jnp.bfloat16) for _ in range(4))
    mask = None
    if masked:       # key-padding mask (B, 1, 1, S): last quarter dropped
        keep = np.ones((B, 1, 1, S), np.float32)
        keep[:, :, :, S - S // 4:] = 0.0
        mask = jnp.asarray((1.0 - keep) * -1e30)
    fb0 = metric_series("pt_kernel_fallbacks_total")
    qt, kt, vt = (paddle.to_tensor(x, stop_gradient=False)
                  for x in (q, k, v))
    with F.sdp_kernel(enable_math=False):
        out = F.scaled_dot_product_attention(
            qt, kt, vt, attn_mask=None if mask is None
            else paddle.to_tensor(mask), is_causal=causal, training=False)
        (out * paddle.to_tensor(g)).sum().backward()
    ck.check(f"{name}_no_fallback",
             metric_series("pt_kernel_fallbacks_total") == fb0,
             "the forced flash call booked a fallback")
    with jax.default_matmul_precision("highest"):
        ref, vjp = jax.vjp(
            lambda a, b, c: _xla_attention(a, b, c, mask=mask,
                                           causal=causal),
            _f32(q), _f32(k), _f32(v))
        dq, dk, dv = vjp(_f32(g))
    errs = {"fwd": rel_max(out._value, ref),
            "dq": rel_max(qt.grad._value, dq),
            "dk": rel_max(kt.grad._value, dk),
            "dv": rel_max(vt.grad._value, dv)}
    ck.check(f"{name}_fwd", errs["fwd"] <= FWD_TOL, str(errs))
    ck.check(f"{name}_grads",
             max(errs["dq"], errs["dk"], errs["dv"]) <= GRAD_TOL, str(errs))
    return {k_: round(e, 5) for k_, e in errs.items()}


def kernel_flash_gpt125m(ck):
    """head-folded causal kernels at the GPT-125M shape (the train step's
    own attention), now against a reference."""
    return _sdpa_case(ck, "flash_gpt125m", 2, 1024, 12, 64, causal=True)


def kernel_flash_bert_mask(ck):
    """head-folded kernels with the key-bias path: BERT-base, S=512,
    key-padding mask."""
    return _sdpa_case(ck, "flash_bert_mask", 2, 512, 12, 64, causal=False,
                      masked=True)


def kernel_flash_padded(ck):
    """S=300: the 256-granule padding, non-causal (pad keys dropped by
    the additive bias) and causal."""
    return {"noncausal": _sdpa_case(ck, "flash_pad_noncausal", 2, 300, 12,
                                    64, causal=False),
            "causal": _sdpa_case(ck, "flash_pad_causal", 2, 300, 12, 64,
                                 causal=True)}


def kernel_flash_s4096(ck):
    """grid kernels past the head-folded cap: gpt125m_s4096 (q-grid
    forward, one-pass fused backward)."""
    return _sdpa_case(ck, "flash_s4096", 1, 4096, 12, 64, causal=True)


def kernel_flash_gpt1p3b(ck):
    """GPT-3 1.3B attention: S=2048, heads of 128."""
    return _sdpa_case(ck, "flash_gpt1p3b", 1, 2048, 16, 128, causal=True)


def kernel_flash_two_pass(ck):
    """two-pass dq / dkv backward: S*D past the fused-backward cap; a
    single head of 64 (the transposing path) and a pair tile (in place)."""
    return {"transposed": _sdpa_case(ck, "flash_two_pass", 1, 16384, 1, 64,
                                     causal=True),
            "in_place": _sdpa_case(ck, "flash_two_pass_pair", 1, 16384, 2,
                                   64, causal=True)}


def kernel_fused_xent(ck):
    """fused softmax cross-entropy at V=50304 (2048-lane chunks with a
    masked tail), rows a multiple of 256 and not (row padding), ignore
    labels; fp32 rows to docs tolerance, bf16 grads keep their dtype."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import fused_xent as fx

    out = {}
    V = 50304
    for T, dtype in ((2048, jnp.float32), (300, jnp.float32),
                     (2048, jnp.bfloat16)):
        rng = np.random.RandomState(T)
        lg = _randn(rng, (T, V), dtype, scale=2.0)
        lb = rng.randint(0, V, (T,)).astype(np.int32)
        lb[::7] = -100
        lb = jnp.asarray(lb)
        rows, vjp = jax.vjp(lambda x: fx.fused_softmax_xent(x, lb), lg)
        (dlg,) = vjp(jnp.ones((T,), jnp.float32))
        ref_rows, ref_vjp = jax.vjp(
            lambda x: fx._ref_rowloss(x, lb), _f32(lg))
        (ref_d,) = ref_vjp(jnp.ones((T,), jnp.float32))
        tag = f"xent_T{T}_{jnp.dtype(dtype).name}"
        row_err = float(np.max(np.abs(np.asarray(rows) -
                                      np.asarray(ref_rows))))
        grad_err = rel_max(dlg, ref_d)
        f32 = dtype == jnp.float32
        ck.check(f"{tag}_rows", row_err <= 2e-4,
                 f"max abs row error {row_err:.2e}")
        ck.check(f"{tag}_grads", grad_err <= (1e-4 if f32 else 1e-2),
                 f"relative-max grad error {grad_err:.2e}")
        ck.check(f"{tag}_ignored_rows_zero",
                 bool(np.all(np.asarray(rows)[::7] == 0.0)))
        ck.check(f"{tag}_grad_dtype", dlg.dtype == lg.dtype, str(dlg.dtype))
        out[tag] = {"row_abs": float(f"{row_err:.2e}"),
                    "grad_rel": float(f"{grad_err:.2e}")}
    return out


def kernel_int8_matmul(ck):
    """int8 matmul kernel at the engine's shapes: decode (M = 8 slots)
    and a prefill bucket, GPT-125M's qkv / mlp-down / tied head."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.quant_matmul import int8_matmul

    out = {}
    for M, K, N in ((8, 768, 2304), (8, 3072, 768), (8, 768, 50304),
                    (128, 768, 2304)):
        rng = np.random.RandomState(M + N)
        x = rng.randn(M, K).astype("float32")
        w_int = rng.randint(-127, 128, (K, N)).astype(np.int8)
        w_scale = (0.5 + rng.rand(N)).astype("float32")
        a_s = float(np.abs(x).max())
        got = int8_matmul(jnp.asarray(x, jnp.bfloat16), jnp.asarray(w_int),
                          jnp.asarray(w_scale), a_s,
                          out_dtype=jnp.float32)
        xb = np.asarray(jnp.asarray(x, jnp.bfloat16).astype(jnp.float32))
        xq = np.clip(np.round(xb / a_s * 127.0), -128, 127)
        gold = (xq.astype(np.int64) @ w_int.astype(np.int64)) \
            .astype(np.float64) * (a_s / 127.0) * (w_scale / 127.0)
        err = rel_max(got, gold)
        ck.check(f"int8_matmul_{M}x{K}x{N}", err <= 1e-4,
                 f"relative-max error {err:.2e} vs the integer golden")
        out[f"{M}x{K}x{N}"] = float(f"{err:.2e}")
    return out


def kernel_fused_adamw(ck):
    """multi-tensor AdamW over GPT-125M-shaped tensors vs the jnp
    update."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.fused_adamw import fused_adamw

    rng = np.random.RandomState(3)
    shapes = [(768, 2304), (2304,), (3072, 768), (768,)]
    ps = [_randn(rng, s, jnp.float32, 0.02) for s in shapes]
    gs = [_randn(rng, s, jnp.float32, 0.01) for s in shapes]
    ms = [_randn(rng, s, jnp.float32, 0.001) for s in shapes]
    vs = [jnp.abs(_randn(rng, s, jnp.float32, 1e-4)) for s in shapes]
    lr, b1, b2, eps, wd, t = 1e-3, 0.9, 0.999, 1e-8, 0.01, 3
    mask = [1.0, 0.0, 1.0, 0.0]
    new_p, new_m, new_v = fused_adamw(ps, gs, ms, vs, lr, b1, b2, eps, wd,
                                      step=t, decay_mask=mask)
    worst = 0.0
    for p, g, m, v, dm, pn, mn, vn in zip(ps, gs, ms, vs, mask, new_p,
                                          new_m, new_v):
        p, g, m, v = (np.asarray(a, np.float64) for a in (p, g, m, v))
        em = b1 * m + (1 - b1) * g
        ev = b2 * v + (1 - b2) * g * g
        upd = (em / (1 - b1 ** t)) / (np.sqrt(ev / (1 - b2 ** t)) + eps) \
            + wd * dm * p
        worst = max(worst, rel_max(pn, p - lr * upd), rel_max(mn, em),
                    rel_max(vn, ev))
    ck.check("fused_adamw", worst <= 1e-4, f"relative-max error {worst:.2e}")
    return float(f"{worst:.2e}")


def kernel_fused_norm(ck):
    """fused LayerNorm / RMSNorm rows at hidden 768 (GPT) and 4096
    (llama-class), bf16, forward + the custom VJP."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import fused_norm as fn

    out = {}
    for H in (768, 4096):
        rng = np.random.RandomState(H)
        x = _randn(rng, (2048, H), jnp.bfloat16)
        g = _randn(rng, (H,), jnp.bfloat16, 0.1) + 1
        b = _randn(rng, (H,), jnp.bfloat16, 0.1)
        dy = _randn(rng, (2048, H), jnp.bfloat16)
        for tag, fused, ref, args in (
                ("layer_norm", fn.fused_layer_norm,
                 lambda *a: fn._ln_ref(*a, 1e-5), (x, g, b)),
                ("rms_norm", fn.fused_rms_norm,
                 lambda *a: fn._rms_ref(*a, 1e-6), (x, g))):
            y, vjp = jax.vjp(fused, *args)
            grads = vjp(dy)
            ry, rvjp = jax.vjp(ref, *(_f32(a) for a in args))
            rgrads = rvjp(_f32(dy))
            errs = [rel_max(y, ry)] + [rel_max(a, r)
                                       for a, r in zip(grads, rgrads)]
            ck.check(f"{tag}_H{H}", errs[0] <= FWD_TOL
                     and max(errs[1:]) <= GRAD_TOL, str(errs))
            out[f"{tag}_H{H}"] = round(max(errs), 5)
    return out


def kernel_conv1x1(ck):
    """fused 1x1-conv + BN + ReLU (+ residual) at a ResNet-50
    bottleneck shape (56x56, 64 -> 256), bf16."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.conv1x1 import conv1x1_bn_act_nhwc

    rng = np.random.RandomState(5)
    x = _randn(rng, (8, 56, 56, 64), jnp.bfloat16)
    w = _randn(rng, (64, 256), jnp.bfloat16, 0.05)
    sc = jnp.asarray(rng.rand(256).astype("float32") + 0.5)
    sh = jnp.asarray(rng.randn(256).astype("float32"))
    res = _randn(rng, (8, 56, 56, 256), jnp.bfloat16)
    got = conv1x1_bn_act_nhwc(x, w, sc, sh, residual=res, relu=True)
    with jax.default_matmul_precision("highest"):
        ref = jnp.maximum(
            (_f32(x).reshape(-1, 64) @ _f32(w)) * sc + sh
            + _f32(res).reshape(-1, 256), 0.0)
    err = rel_max(got.reshape(-1, 256), ref)
    ck.check("conv1x1_bn_act", err <= FWD_TOL, f"relative-max {err:.2e}")
    return round(err, 5)


def kernel_varlen(ck):
    """packed varlen flash attention, resident tier (2048 tokens) and
    streaming tier (8704 tokens), 12 / 2 heads of 64, causal, fwd+bwd
    vs the dense segment-masked path."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import flash_attention_varlen as fv

    out = {}
    for tag, lens, H in (("resident", (700, 300, 1048), 12),
                         ("streaming", (4000, 1000, 3704), 2)):
        rng = np.random.RandomState(len(tag))
        total, D = sum(lens), 64
        q, k, v, g = (_randn(rng, (total, H, D), jnp.bfloat16)
                      for _ in range(4))
        cu = jnp.asarray(np.concatenate([[0], np.cumsum(lens)]), jnp.int32)

        def fused(a, b, c):
            return fv.flash_attn_unpadded(a, b, c, cu, cu, max(lens),
                                          max(lens), causal=True)[0]
        seg = fv._segments_from_cu(cu, total)

        def dense(a, b, c):
            return fv._varlen_dense(a, b, c, seg, seg, None, 0.0, True)[0]
        y, vjp = jax.vjp(fused, q, k, v)
        grads = vjp(g)
        with jax.default_matmul_precision("highest"):
            ry, rvjp = jax.vjp(dense, _f32(q), _f32(k), _f32(v))
            rgrads = rvjp(_f32(g))
        errs = [rel_max(y, ry)] + [rel_max(a, r)
                                   for a, r in zip(grads, rgrads)]
        ck.check(f"varlen_{tag}", errs[0] <= FWD_TOL
                 and max(errs[1:]) <= GRAD_TOL, str(errs))
        out[tag] = round(max(errs), 5)
    return out


def kernel_paged_attention(ck):
    """paged decode attention at the chat cell's shape (8 slots, 16 heads
    of 128, pages of 16, 128 table entries) and LLaMA's grouped shape,
    bf16 and fp32 pools: lengths 0 .. MAX in one batch over a permuted
    table vs the gathered, masked fp32 attention."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference import kvcache as kvc
    from paddle_tpu.nn.functional.attention import _xla_attention
    from paddle_tpu.ops.pallas.paged_attention import paged_attention

    out = {}
    P, n_pages, pool, D = 16, 128, 1025, 128
    lens = np.asarray([1, 15, 16, 17, 220, n_pages * P, 0, 700], np.int32)
    for tag, nH, nKV, dtype in (("chat_cell", 16, 16, jnp.bfloat16),
                                ("grouped", 32, 8, jnp.bfloat16),
                                ("fp32", 16, 16, jnp.float32)):
        rng = np.random.RandomState(len(tag))
        q = _randn(rng, (8, nH, D), dtype)
        kp, vp = (_randn(rng, (pool, P, nKV, D), dtype) for _ in range(2))
        table = np.zeros((8, n_pages), np.int32)
        free = list(rng.permutation(np.arange(1, pool)))
        for b, n in enumerate(lens):
            for j in range(-(-int(n) // P)):
                table[b, j] = free.pop()
        table = jnp.asarray(table)
        got = paged_attention(q, kp, vp, table, jnp.asarray(lens))
        k, v = kvc.gather_pages(_f32(kp), _f32(vp), table)
        dead = jnp.arange(n_pages * P)[None, :] >= lens[:, None]
        with jax.default_matmul_precision("highest"):
            ref = _xla_attention(_f32(q)[:, None], k, v, mask=jnp.where(
                dead, -1e30, 0.0)[:, None, None, :])[:, 0]
        ref = jnp.where((lens > 0)[:, None, None], ref, 0.0)
        err = rel_max(got, ref)
        ck.check(f"paged_attention_{tag}", err <= FWD_TOL
                 and bool(jnp.all(got[6] == 0)), f"relative-max {err:.2e}")
        out[tag] = float(f"{err:.2e}")
    return out


KERNEL_CASES = (kernel_flash_gpt125m, kernel_flash_bert_mask,
                kernel_flash_padded, kernel_flash_s4096,
                kernel_flash_gpt1p3b, kernel_flash_two_pass,
                kernel_fused_xent, kernel_int8_matmul, kernel_fused_adamw,
                kernel_fused_norm, kernel_conv1x1, kernel_varlen,
                kernel_paged_attention)


def phase_kernels(device, meter):
    import jax

    ck = Checks("kernels")
    before = meter.snap()
    errors = {}
    for case in KERNEL_CASES:
        errors[case.__name__[len("kernel_"):]] = case(ck)
    say("kernels", errors_vs_reference=errors,
        tolerance={"fwd_rel_max": FWD_TOL, "grad_rel_max": GRAD_TOL},
        peak_bytes_in_use=peak_bytes(device),
        **meter.delta(before))
    ck.finish()


# ---------------------------------------------------------------------------
# facts for the next PR (printed; only the host callback is a check)
# ---------------------------------------------------------------------------

def phase_facts(model, cfg, B, S, device, meter):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.framework import native

    ck = Checks("facts")
    tiny = jax.jit(lambda a: jnp.sum(a))
    x = jnp.zeros((8, 8), jnp.float32)
    float(tiny(x))
    trips = []
    for _ in range(50):
        t0 = time.perf_counter()
        float(tiny(x))
        trips.append(time.perf_counter() - t0)

    # is block_until_ready a barrier?  One (asynchronous) train step:
    # time to return from dispatch, to block_until_ready, and to the
    # float() readback that follows.  A barrier leaves the readback
    # nothing to wait for; a no-op leaves it the whole step.
    ids = synthetic_tokens(B, S, cfg.vocab_size, SEED + 2)
    model.network.train()
    float(model._stepper.train_step([ids], [ids])[0])      # warm
    t0 = time.perf_counter()
    loss, _ = model._stepper.train_step([ids], [ids])
    t_dispatch = time.perf_counter() - t0
    loss.block_until_ready()
    t_ready = time.perf_counter() - t0
    float(loss)
    t_read = time.perf_counter() - t0

    seen = []
    jax.jit(lambda a: jax.debug.callback(
        lambda v: seen.append(float(v)), jnp.sum(a)))(x + 3.0)
    jax.effects_barrier()
    ck.check("host_callback_ran", seen == [192.0], str(seen))

    say("facts",
        dispatch_round_trip_ms={
            "median": round(statistics.median(trips) * 1e3, 3),
            "min": round(min(trips) * 1e3, 3),
            "max": round(max(trips) * 1e3, 3)},
        train_step_ms={"dispatch_returns": round(t_dispatch * 1e3, 2),
                       "block_until_ready": round(t_ready * 1e3, 2),
                       "float_readback": round(t_read * 1e3, 2)},
        block_until_ready_is_barrier=bool(
            t_read - t_ready <= 0.1 * t_read),
        host_callback_ran=seen == [192.0],
        native_so_loaded=bool(native.available()),
        peak_bytes_in_use=peak_bytes(device),
        bytes_limit=int(device.memory_stats().get("bytes_limit", 0)))
    ck.finish()


# ---------------------------------------------------------------------------
# four chips, one process driving all four
# ---------------------------------------------------------------------------

def fleet_init(**degrees):
    from paddle_tpu.distributed import fleet
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sharding_degree": 1,
                               **degrees}
    fleet.init(is_collective=True, strategy=strategy)
    return fleet


def check_spread(ck, tag, net, n_devices):
    """Every parameter's sharding spans the mesh's distinct devices, and
    those its pspec shards really are split."""
    import jax

    devs = set()
    bad = []
    for name, p in net.named_parameters():
        sh = p._value.sharding
        devs |= set(sh.device_set)
        spec = tuple(getattr(p, "pspec", None) or ())
        wants_split = any(a is not None for a in spec)
        if len(sh.device_set) != n_devices or \
                wants_split == sh.is_fully_replicated:
            bad.append(f"{name}: pspec={spec} devices={len(sh.device_set)}"
                       f" replicated={sh.is_fully_replicated}")
    ck.check(f"{tag}_params_span_{n_devices}_devices",
             len(devs) == n_devices and not bad, "; ".join(bad[:4]))
    in_use = [bytes_in_use(d) for d in jax.devices()[:n_devices]]
    ck.check(f"{tag}_memory_on_every_chip", min(in_use) > 64 << 20,
             str(in_use))
    return in_use


def four_chips_125m(B, S, steps, one_chip_losses, meter):
    """(a) GPT-3 125M, dp=2 x mp=2, same seed and global batch as the
    one-chip phase: losses agree step for step."""
    from paddle_tpu.models import gpt3_125m

    ck = Checks("four_chips_125m")
    before = meter.snap()
    fleet = fleet_init(dp_degree=2, mp_degree=2)
    net, model, rec = fit_gpt(
        gpt3_125m(tensor_parallel=True), B, S, steps, meter,
        wrap=fleet.distributed_model)
    losses = rec.losses
    ck.check("125m_losses_finite", all(math.isfinite(x) for x in losses),
             str(losses))
    worst = max(abs(a - b) / abs(b)
                for a, b in zip(losses, one_chip_losses))
    ck.check("125m_losses_match_one_chip",
             len(losses) == len(one_chip_losses) and worst <= 1e-2,
             f"worst relative difference {worst:.4f}")
    in_use = check_spread(ck, "125m", net, 4)
    say("four_chips_125m", mesh="dp=2 x mp=2", batch=B, seq=S,
        losses=[round(x, 4) for x in losses],
        one_chip_losses=[round(x, 4) for x in one_chip_losses],
        worst_rel_diff=round(worst, 5), bytes_in_use_per_chip=in_use,
        first_step_s=round(rec.ends[0] - rec.t0, 2),
        steady_step_ms=round(statistics.median(
            b - a for a, b in zip(rec.ends[1:], rec.ends[2:])) * 1e3, 1),
        **meter.delta(before))
    ck.finish()


def four_chips_pipeline(S, meter):
    """One spmd_pipeline step, pp=2 x mp=2 (shard_map + ppermute), at the
    125M width with depth cut to 4 layers; the reference is the plain
    network's loss on the same seeded weights and batch, on one chip."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import paddle_tpu as paddle
    from paddle_tpu.models import (GPTForPretraining,
                                   GPTPretrainingCriterion, gpt3_125m)
    from paddle_tpu.models.gpt_hybrid import build_hybrid_gpt

    ck = Checks("four_chips_pipeline")
    before = meter.snap()
    ids = synthetic_tokens(4, S, gpt3_125m().vocab_size, SEED + 3)
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(1, 2, 2),
                ("data", "pipe", "model"))
    step, (other, stacked), data_sh = build_hybrid_gpt(
        dataclasses.replace(gpt3_125m(tensor_parallel=True),
                            num_hidden_layers=4), mesh, n_micro=2)
    batch = jax.device_put(jnp.asarray(ids), data_sh)
    compiled = step.lower(other, stacked, batch, batch).compile()
    ck.check("pipeline_step_has_ppermute",
             "collective-permute" in compiled.as_text(),
             "no collective-permute in the compiled step")
    ck.check("pipeline_params_on_4_devices",
             all(len(v.sharding.device_set) == 4 for v in stacked))
    loss, other, stacked = compiled(other, stacked, batch, batch)
    pipe_loss = float(loss)
    del step, compiled, other, stacked
    plain = GPTForPretraining(
        dataclasses.replace(gpt3_125m(), num_hidden_layers=4))
    plain.eval()
    with paddle.no_grad():
        ref_loss = float(GPTPretrainingCriterion()(
            plain(paddle.to_tensor(ids)), paddle.to_tensor(ids)))
    del plain
    gc.collect()
    diff = abs(pipe_loss - ref_loss) / abs(ref_loss)
    ck.check("pipeline_loss_matches_one_chip",
             math.isfinite(pipe_loss) and diff <= 1e-2,
             f"pipeline {pipe_loss} one chip {ref_loss}")
    say("four_chips_pipeline", mesh="pp=2 x mp=2", layers=4, batch=4, seq=S,
        loss=round(pipe_loss, 5), one_chip_loss=round(ref_loss, 5),
        rel_diff=round(diff, 6), **meter.delta(before))
    ck.finish()


def four_chips_1p3b(meter):
    """(b) GPT-3 1.3B (the north-star model) at its full width and depth,
    S=2048, remat on, dp=2 x mp=2.  Resident state is 12 B/param (fp32
    master + two moments) x 0.66 G params a chip = 7.9 GB; measured 8.4
    GB in use and 8.8 GB peak of 16 GB (PR 21 chip run), so dp=2 fits
    and ZeRO sharding is not needed.  The learning rate is a tenth of
    the published 2e-4 peak, standing in for the warm-up this three-step
    run does not have (at 3e-4 the loss climbed 11.1 -> 14.4, same
    run)."""
    import jax

    from paddle_tpu.models import gpt3_1p3b

    ck = Checks("four_chips_1p3b")
    before = meter.snap()
    fleet = fleet_init(dp_degree=2, mp_degree=2)
    big_B, big_S, big_steps = 4, 2048, 3
    net, model, rec = fit_gpt(
        gpt3_1p3b(tensor_parallel=True, remat=True), big_B, big_S,
        big_steps, meter, wrap=fleet.distributed_model, lr=2e-5)
    ck.check("1p3b_steps_run", len(rec.losses) == big_steps,
             str(rec.losses))
    ck.check("1p3b_losses_finite",
             all(math.isfinite(x) for x in rec.losses), str(rec.losses))
    in_use = check_spread(ck, "1p3b", net, 4)
    say("four_chips_1p3b", mesh="dp=2 x mp=2", batch=big_B,
        seq=big_S, params=int(sum(np.prod(p.shape)
                                  for p in net.parameters())),
        losses=[round(x, 4) for x in rec.losses],
        bytes_in_use_per_chip=in_use,
        peak_bytes_per_chip=[peak_bytes(d) for d in jax.devices()[:4]],
        first_step_s=round(rec.ends[0] - rec.t0, 2),
        step_ms=[round((b - a) * 1e3, 1)
                 for a, b in zip(rec.ends, rec.ends[1:])],
        **meter.delta(before))
    ck.finish()


def phase_four_chips(B, S, steps, one_chip_losses, meter):
    """One process driving all four chips; the 1.3B model last, so that
    a failure there leaves the other results printed."""
    four_chips_125m(B, S, steps, one_chip_losses, meter)
    gc.collect()
    four_chips_pipeline(S, meter)
    gc.collect()
    four_chips_1p3b(meter)


# ---------------------------------------------------------------------------

def main():
    import importlib.metadata as md

    import jax

    from paddle_tpu.device import chip
    from paddle_tpu.models import gpt3_125m

    cache_dir = chip.enable_compile_cache()
    device = chip.describe()
    print(json.dumps({
        "phase": "start", **device,
        "jax": jax.__version__, "jaxlib": md.version("jaxlib"),
        "libtpu": md.version("libtpu"), "python": sys.version.split()[0],
        "compile_cache_dir": cache_dir,
        "JAX_COMPILATION_CACHE_DIR_set":
            bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))}), flush=True)
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{device['platform']!r}); nothing was run")
    chip.peaks(device["kind"])         # unknown device_kind raises here
    dev0 = jax.devices()[0]
    meter = CompileMeter()
    t_start = time.perf_counter()

    cfg = gpt3_125m()
    net, model, losses = phase_train(cfg, TRAIN_B, TRAIN_S, TRAIN_STEPS,
                                     dev0, meter)
    phase_facts(model, cfg, TRAIN_B, TRAIN_S, dev0, meter)
    del model                          # frees the optimizer state
    gc.collect()
    phase_serve(net, SERVE_PROMPT_LENS, SERVE_NEW_TOKENS,
                cfg.max_position_embeddings, dev0, meter)
    del net
    gc.collect()
    phase_kernels(dev0, meter)
    if device["count"] >= 4:
        phase_four_chips(TRAIN_B, TRAIN_S, TRAIN_STEPS, losses, meter)
    else:
        say("four_chips", ran=False,
            reason=f"{device['count']} chip(s) visible; needs 4")
    total = meter.delta((0.0, 0, 0, 0))
    say("done", wall_s=round(time.perf_counter() - t_start, 1), **total)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
