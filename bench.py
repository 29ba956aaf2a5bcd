"""Benchmark runner — prints ONE JSON line for the driver.

Primary metric: GPT (125M-class) training throughput in tokens/sec/chip —
fused fwd+bwd+AdamW in one jitted executable, bf16 compute with fp32
master params (the BASELINE GPT workload scaled to one chip).  The
``extra.configs`` map carries the other BASELINE workloads measured on the
same chip: GPT-350M (larger single-chip config so the headline MFU is not
a 125M proxy), ResNet-50 images/sec, and BERT-base AMP tokens/sec.

MFU accounting: model FLOPs per token = 6·N_params (fwd 2N + bwd 4N; the
tied LM head matmul is covered by counting the embedding table once, the
input lookup is gather-only) + 6·L·S·H for causal attention scores/values
(QKᵀ and AV are real executed matmuls; the causal flash kernel computes
half the S² square, hence 6 not 12 per layer-token).  Dividing by the
chip's peak bf16 FLOPs gives MFU.

Timing: every loop ends with a host readback (float of a value
data-dependent on the whole step chain), which is a completion barrier on
every backend.  Rounds 1-5 found that on their device path
block_until_ready() returns BEFORE execution finishes (~70x inflation);
whether it is a barrier on the local chip is printed by chip_smoke.py.
tests/test_bench_timing.py guards the readback contract.

Devices: a chip belongs to one process.  Importing this file touches no
backend; ``main()`` fails when JAX finds no TPU unless ``JAX_PLATFORMS=cpu``
is set explicitly (the labeled CPU-proxy branch); every config result
carries the platform / device_kind / count of the process that produced it;
configs that measure in child processes are refused by name on a TPU; a
config that raised makes the run exit non-zero after its line is printed.

Dropout note: all benched models run with dropout probability 0.0 (the
perf-relevant configs train without dropout); nets are put in eval() mode
purely so no dropout mask ops enter the graph — the math equals train()
at p=0.
"""
import json
import os
import time

import numpy as np


def _readback_sync(x):
    """True device-completion barrier: D2H of a dependent value."""
    return float(x)


def _dispatch_latency_ms():
    """Median round-trip of a tiny jitted reduction — the per-dispatch
    latency the validity gates subtract/compare against.  NOT
    ``chip_calibration``: its 300-matmul compute chain is for peak-frac,
    overkill here and pathological on the CPU proxy.  Returns None when
    the probe itself fails (callers then report validity as unknown)."""
    try:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _tiny(a):
            return jnp.sum(a)
        x = jnp.zeros((8, 8), jnp.float32)
        _readback_sync(_tiny(x))
        lats = []
        for _ in range(3):
            t0 = time.perf_counter()
            _readback_sync(_tiny(x))
            lats.append(time.perf_counter() - t0)
        return sorted(lats)[1] * 1e3
    except Exception:
        return None


def _telemetry_snapshot(tag, reset=True):
    """Dump the observability registry as sink-format fixtures next to
    the bench JSON: ``<dir>/<tag>.prom`` (Prometheus text exposition) +
    ``<tag>.jsonl`` (the PADDLE_METRICS_LOG line format), dir from
    ``BENCH_TELEMETRY_DIR`` (default ``telemetry/``).  ``reset`` zeroes
    the registry afterwards so the next config's snapshot is its own
    (counters are process-cumulative otherwise).

    Idempotent per tag: the ``.prom`` write truncates (atomic replace)
    and the ``.jsonl`` write is run-id-keyed (``replace_run``), so
    re-running bench updates the snapshot in place instead of appending
    one copy per invocation.  A run that produced request-trace spans
    (serving configs) also drops ``<tag>_requests.trace.json`` — the
    per-request-lane chrome trace ``report --requests`` summarizes."""
    try:
        from paddle_tpu import observability as obs
        from paddle_tpu.observability import export as obs_export
        from paddle_tpu.observability import timeline as obs_timeline
        from paddle_tpu.observability import tracing as obs_tracing
        d = os.environ.get("BENCH_TELEMETRY_DIR", "telemetry")
        os.makedirs(d, exist_ok=True)
        prom = obs_export.write_prometheus(os.path.join(d, f"{tag}.prom"))
        jsl = obs_export.write_jsonl(os.path.join(d, f"{tag}.jsonl"),
                                     run=tag, replace_run=True)
        out = {"prometheus": prom, "jsonl": jsl}
        if obs_tracing.spans():
            out["requests_trace"] = obs_timeline.export_chrome_trace(
                os.path.join(d, f"{tag}_requests.trace.json"),
                include_profiler=False, include_guardian=False,
                include_samples=False)
            obs_tracing.reset()
        if reset:
            obs.get_registry().reset()
        return out
    except Exception as e:  # telemetry must never sink the bench line
        return {"error": repr(e)[:160]}


def _roofline_snapshot(measured_ms, peak_flops, hbm_bw):
    """Join the process's compile telemetry (every surface any config
    compiled) with measured step latency into the per-surface
    roofline/MFU-attribution table the MFU-plateau roadmap item asks
    for, committed as ``<dir>/roofline.json`` (the same table
    ``report --roofline`` renders from a ``.prom`` snapshot)."""
    try:
        import json as _json
        from paddle_tpu.observability import compilestats, report
        stats = compilestats.snapshot()
        if not stats:
            return {"skipped": "no compile telemetry recorded"}
        table = report.roofline_from_stats(stats, measured_ms,
                                           peak_flops, hbm_bw)
        d = os.environ.get("BENCH_TELEMETRY_DIR", "telemetry")
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, "roofline.json")
        tmp = path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            _json.dump(table, f, indent=1, sort_keys=True)
            f.write("\n")
        os.replace(tmp, path)
        return {"roofline": path, "surfaces": len(table["rows"])}
    except Exception as e:
        return {"error": repr(e)[:160]}


def _memory_snapshot():
    """Write the HBM ledger's two-sided snapshot next to roofline.json
    (``<dir>/memory.json``): one static memory_analysis row per
    registry surface plus the run's census/forecast summary.  The
    bench gate requires this artifact to accompany committed BENCH_*
    files — a quant/serving change must never land without its memory
    story."""
    try:
        from paddle_tpu.observability import memory
        path = memory.write_memory_json()
        snap = memory.snapshot()
        compiled = sum(1 for r in snap["surfaces"].values()
                       if r.get("compiled"))
        return {"memory": path, "surfaces": len(snap["surfaces"]),
                "compiled": compiled}
    except Exception as e:
        return {"error": repr(e)[:160]}


def _timeit(step, iters, *state):
    """Run ``state = step(*state)`` iters times; the caller's step returns
    (loss_like_scalar, *new_state).  Returns (seconds, final_loss)."""
    t0 = time.perf_counter()
    loss = None
    for _ in range(iters):
        out = step(*state)
        loss, state = out[0], out[1:]
    final = _readback_sync(loss)
    dt = time.perf_counter() - t0
    return dt, final, state


def chip_calibration():
    """Chip health probe: (dispatch_latency_ms, matmul_peak_frac).

    Per-call dispatch latency (not measured on the local chip before
    PR 21; ~5-100 ms on the remote device path of rounds 1-5) is
    measured on a trivial op and SUBTRACTED from the matmul chain so
    peak_frac reflects compute health against the device_kind's
    published bf16 peak (device/chip.py; an unknown kind raises).
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu.device import chip

    peak = chip.peaks().bf16_flops
    rng = np.random.RandomState(0)
    a = jnp.asarray(rng.randn(4096, 4096).astype("f4"), dtype=jnp.bfloat16)
    b = jnp.asarray(rng.randn(4096, 4096).astype("f4"), dtype=jnp.bfloat16)

    @jax.jit
    def tiny(a):
        return jnp.sum(a[:8, :8].astype(jnp.float32))

    # chain length must make COMPUTE dominate the dispatch latency, or
    # the subtraction bottoms out and the frac reads nonsense (a 20-matmul
    # chain is ~14ms — under one slow ~90ms dispatch round trip).
    # 300 matmuls ~ 0.2s at peak: latency-robust within ~5%.
    N_CHAIN = 300

    @jax.jit
    def chain(a, b):
        def body(_, o):
            return (o @ b).astype(jnp.bfloat16)
        o = jax.lax.fori_loop(0, N_CHAIN, body, a)
        return jnp.sum(o.astype(jnp.float32))

    import statistics

    # MEDIAN of N for BOTH sides of the subtraction (BENCH_r05 fix):
    # min(tiny) - min(chain) paired the luckiest dispatch against the
    # luckiest chain run, so whenever dispatch jitter exceeded the ~5%
    # margin the subtraction overcorrected and the raw frac read >1.0
    # (1.198 in r05, tripping jitter_suspect on every run).  Medians of
    # the same sample counts are robust to one congested round trip in
    # either direction; min latency is still reported separately (it IS
    # the best-case dispatch floor the serving engine amortizes).
    _readback_sync(tiny(a))
    tiny_times = []
    for _ in range(7):
        t0 = time.perf_counter()
        _readback_sync(tiny(a))
        tiny_times.append(time.perf_counter() - t0)
    lat = statistics.median(tiny_times)
    _readback_sync(chain(a, b))
    chain_times = []
    for _ in range(5):
        t0 = time.perf_counter()
        _readback_sync(chain(a, b))
        chain_times.append(time.perf_counter() - t0)
    med = statistics.median(chain_times)
    per = max(med - lat, 1e-6) / N_CHAIN
    frac = 2 * 4096 ** 3 / per / peak
    # frac above 1.0 is physically impossible — it means the dispatch
    # latency measured on the tiny probe overshot the latency actually
    # paid by the chain run (jitter between the two measurements), and
    # the subtraction overcorrected.  With the median-of-N subtraction
    # above that now genuinely signals something pathological (clock
    # skew, a wrong peak constant), not routine dispatch noise.  Clamp
    # the headline number so downstream health checks can treat it as a
    # fraction, keep the raw value for trend analysis, and flag the
    # jitter machine-readably instead of in a free-text note.
    out = {"dispatch_latency_ms": round(min(tiny_times) * 1e3, 1),
           "dispatch_latency_median_ms": round(lat * 1e3, 1),
           "matmul_peak_frac": round(min(frac, 1.0), 4),
           "matmul_peak_frac_raw": round(frac, 4),
           "jitter_suspect": frac > 1.0}
    return out


# ---------------------------------------------------------------------------
# GPT (125M / 350M): fused fwd+bwd+AdamW, bf16 compute fp32 master
# ---------------------------------------------------------------------------

def bench_gpt(cfg, B, S, iters, peak):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.framework import autograd as _ag
    from paddle_tpu.framework.random import rng_scope
    from paddle_tpu.models import GPTForPretraining

    paddle.seed(0)
    net = GPTForPretraining(cfg)
    net.eval()  # dropout-mask-free graph; p=0.0 so math == train()
    params = [p for _, p in net.named_parameters()]
    pvals = [p._value for p in params]

    def forward_pure(pv, ids):
        olds = [p._value for p in params]
        for p, v in zip(params, pv):
            p._value = v
        try:
            with _ag.suspend_tape(), rng_scope(jax.random.key(0)):
                return net(paddle.Tensor(ids))._value
        finally:
            for p, v in zip(params, olds):
                p._value = v

    def loss_fn(pv, ids, labels):
        # Pallas fused softmax-xent: ONE streamed pass fwd (online
        # max/sum + label pick, no slicing copy — the shift rides an
        # ignore label), ONE pass bwd writing dlogits directly.  42.3%
        # MFU with the jnp LSE loss -> 46.4% with this kernel (B=24).
        from paddle_tpu.ops.pallas.fused_xent import fused_softmax_xent
        compute = [v.astype(jnp.bfloat16)
                   if jnp.issubdtype(v.dtype, jnp.floating) else v
                   for v in pv]
        logits = forward_pure(compute, ids)              # bf16 [B,S,V]
        Bv, Sv, V = logits.shape
        lb = jnp.concatenate([labels[:, 1:],
                              jnp.full((Bv, 1), -1, labels.dtype)], 1)
        row = fused_softmax_xent(logits.reshape(Bv * Sv, V),
                                 lb.reshape(-1).astype(jnp.int32))
        return jnp.sum(row) / (Bv * (Sv - 1))

    b1, b2, eps, lr, wd = 0.9, 0.95, 1e-8, 1e-4, 0.01

    def step(pv, m, v, t, ids, labels):
        loss, g = jax.value_and_grad(loss_fn)(pv, ids, labels)
        t = t + 1
        new_p, new_m, new_v = [], [], []
        for p, gi, mi, vi in zip(pv, g, m, v):
            nmi = b1 * mi + (1 - b1) * gi
            nvi = b2 * vi + (1 - b2) * gi * gi
            mhat = nmi / (1 - b1 ** t)
            vhat = nvi / (1 - b2 ** t)
            np_ = p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
            new_p.append(np_)
            new_m.append(nmi)
            new_v.append(nvi)
        return loss, new_p, new_m, new_v, t

    # K train steps ride ONE dispatch via lax.scan so per-call dispatch
    # latency cannot contaminate short steps (its size is not measured
    # on the local chip; K is to be re-derived, ROADMAP queue 1 item 3)
    K = int(os.environ.get("BENCH_STEPS_PER_CALL", "5"))

    def scan_steps(pv, m, v, t, ids, labels):
        def body(carry, _):
            pv, m, v, t = carry
            loss, pv, m, v, t = step(pv, m, v, t, ids, labels)
            return (pv, m, v, t), loss
        (pv, m, v, t), losses = jax.lax.scan(
            body, (pv, m, v, t), None, length=K)
        return losses[-1], pv, m, v, t

    # compile telemetry (observability/compilestats.py): the scan
    # stepper is ONE executable covering K inner steps — its analytical
    # FLOPs/bytes and the per-DISPATCH latency recorded below are what
    # `report --roofline` / telemetry/roofline.json join
    from paddle_tpu.observability import compilestats as _cstats
    step_jit = _cstats.wrap(jax.jit(scan_steps, donate_argnums=(0, 1, 2)),
                            "bench.train_step", budget=1)
    m0 = [jnp.zeros_like(v) for v in pvals]
    v0 = [jnp.zeros_like(v) for v in pvals]
    t0 = jnp.zeros((), jnp.int32)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                  (B, S)).astype("int32"))

    def run(pv, m, v, t):
        loss, pv, m, v, t = step_jit(pv, m, v, t, ids, ids)
        return loss, pv, m, v, t

    loss, pvals, m0, v0, t0 = run(pvals, m0, v0, t0)
    _readback_sync(loss)  # compile + warmup
    dt, final_loss, _ = _timeit(run, iters, pvals, m0, v0, t0)
    tokens_per_sec = iters * K * B * S / dt

    # aggregate telemetry for the train-config snapshot: the scan-
    # chained loop deliberately has no per-step sync, so one latency
    # observation = the measured mean step (latency-robust, same number
    # the JSON reports)
    from paddle_tpu import observability as obs
    obs.observe("pt_train_step_latency_ms", dt / (iters * K) * 1e3)
    # per-DISPATCH measured latency for the roofline join (the scan
    # covers K steps, so this is K x the per-step number above)
    obs.observe("pt_compile_dispatch_ms", dt / iters * 1e3,
                surface="bench.train_step")
    obs.inc("pt_train_tokens_total", iters * K * B * S)
    obs.set_gauge("pt_train_tokens_per_sec", tokens_per_sec)
    obs.set_gauge("pt_train_loss", final_loss)

    n_params = sum(int(np.prod(p.shape)) for p in params)
    flops_per_tok = 6 * n_params \
        + 6 * cfg.num_hidden_layers * S * cfg.hidden_size  # causal attn
    mfu = tokens_per_sec * flops_per_tok / peak
    return {"tokens_per_sec": round(tokens_per_sec, 1),
            "mfu": round(mfu, 4), "loss": round(final_loss, 4),
            "params": n_params, "batch": B, "seq": S,
            "step_ms": round(dt / (iters * K) * 1e3, 3),
            "dispatch_ms": round(dt / iters * 1e3, 3)}


def bench_longctx_sweep(peak, on_tpu=True):
    """remat-policy x attention-impl grid at the long-context shape
    (ISSUE 15): selective remat frees activation HBM so the batch can
    grow past the B=2 operating point the no-remat sweep topped out at,
    and the attention-impl axis isolates how much of each cell is the
    flash kernel vs the dense XLA path.  Opt-in
    (``BENCH_CONFIGS=longctx_sweep``): the grid costs one compile per
    cell.  Off-TPU a tiny proxy runs the same grid through interpret
    mode — plumbing and reporting, not physics."""
    from paddle_tpu.models import GPTConfig
    if on_tpu:
        shape = dict(vocab_size=50304, hidden_size=768,
                     num_hidden_layers=12, num_attention_heads=12,
                     max_position_embeddings=4096)
        S, iters = 4096, 6
        # (remat_policy, attn_impl, B): the no-remat B sweep topped out
        # at B=2 (46.7%); dots_saveable cells probe past it
        combos = [(None, "flash", 2), (None, "dense", 2),
                  ("dots_saveable", "flash", 4),
                  ("dots_saveable", "flash", 8),
                  ("dots_saveable", "dense", 8)]
    else:
        shape = dict(vocab_size=1024, hidden_size=64,
                     num_hidden_layers=2, num_attention_heads=2,
                     max_position_embeddings=512)
        S, iters = 512, 2
        combos = [(None, "dense", 2), (None, "flash", 2),
                  ("dots_saveable", "flash", 4)]
    saved = {k: os.environ.get(k) for k in
             ("PADDLE_TPU_ATTN_IMPL", "PADDLE_TPU_KERNEL_INTERPRET")}
    rows = []
    try:
        for policy, impl, B in combos:
            os.environ["PADDLE_TPU_ATTN_IMPL"] = \
                "flash" if impl == "flash" else "dense"
            if not on_tpu and impl == "flash":
                os.environ["PADDLE_TPU_KERNEL_INTERPRET"] = "1"
            elif not on_tpu:
                os.environ.pop("PADDLE_TPU_KERNEL_INTERPRET", None)
            row = {"remat_policy": policy, "attn_impl": impl, "batch": B}
            try:
                cfg = GPTConfig(**shape, remat_policy=policy)
                r = bench_gpt(cfg, B=B, S=S, iters=iters, peak=peak)
                row.update(tokens_per_sec=r["tokens_per_sec"],
                           mfu=r["mfu"], step_ms=r["step_ms"])
            except Exception as e:
                row["error"] = repr(e)[:160]
            rows.append(row)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    ok = [r for r in rows if "error" not in r]
    # best stays NESTED (no top-level rate keys): the sweep is opt-in,
    # and a sometimes-present top-level metric would trip the bench
    # gate's disappearance check on runs that skip it
    return {"rows": rows,
            "best": max(ok, key=lambda r: r["mfu"]) if ok else None,
            "seq": S}


def bench_kernel_probe(on_tpu=True):
    """Standalone kernel-surface probe (opt-in ``kernels`` config):
    dispatch the registry-tracked flash + fused-xent kernels outside any
    stepper so compilestats owns ``kernel.*`` rows (analytical
    FLOPs/bytes from the AOT lowering), run the block-size autotune
    micro-sweep, and time each kernel latency-clean — the measured ms
    feed the roofline join, which is how ``telemetry/roofline.json``
    attributes the per-kernel share of the step.  Off-TPU the same
    probe runs tiny shapes through interpret mode (plumbing, labeled
    cpu-proxy by the peak constant — not physics)."""
    import statistics

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import registry as kreg
    from paddle_tpu.nn.functional import attention as fattn
    from paddle_tpu.ops.pallas import fused_xent as fx

    prev_interp = os.environ.get("PADDLE_TPU_KERNEL_INTERPRET")
    if not on_tpu:
        os.environ["PADDLE_TPU_KERNEL_INTERPRET"] = "1"
    try:
        if on_tpu:
            S, D, H, B, V, reps = 4096, 64, 12, 2, 50304, 5
        else:
            S, D, H, B, V, reps = 256, 32, 2, 1, 384, 2
        interp = not on_tpu
        sweep = kreg.autotune_flash(S, D, heads=H, batch=B,
                                    interpret=interp, persist=on_tpu)
        rng = np.random.RandomState(0)
        q, k, v = (jnp.asarray(rng.randn(B, S, H, D).astype("f4"))
                   for _ in range(3))
        g = jnp.asarray(rng.randn(B, S, H, D).astype("f4"))

        from paddle_tpu import observability as obs

        def sync(out):
            # honest-readback barrier (bench methodology contract): D2H
            # of a dependent scalar — never the device-side ready wait
            # (commit 9ce47d5)
            leaf = jax.tree_util.tree_leaves(out)[0]
            _readback_sync(leaf.ravel()[0])

        def timed(surface, fn):
            sync(fn())                      # compile + warm
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                sync(fn())
                times.append((time.perf_counter() - t0) * 1e3)
            med = statistics.median(times)
            obs.observe("pt_compile_dispatch_ms", med, surface=surface)
            return med

        measured = {}
        measured[kreg.FLASH_FWD_LSE_SURFACE] = timed(
            kreg.FLASH_FWD_LSE_SURFACE,
            lambda: fattn._flash_fwd_lse(q, k, v, None, causal=True,
                                         interpret=interp))
        o, lse = fattn._flash_fwd_lse(q, k, v, None, causal=True,
                                      interpret=interp)
        measured[kreg.FLASH_BWD_SURFACE] = timed(
            kreg.FLASH_BWD_SURFACE,
            lambda: fattn._flash_bwd(q, k, v, o, lse, g, None,
                                     causal=True, interpret=interp))
        T = B * S
        lg = jnp.asarray(rng.randn(T, V).astype("f4"))
        lb = jnp.asarray(rng.randint(0, V, (T,)).astype("i4"))
        force = fx._FORCE_INTERPRET
        fx._FORCE_INTERPRET = interp
        try:
            measured[kreg.XENT_FWD_SURFACE] = timed(
                kreg.XENT_FWD_SURFACE,
                lambda: fx.fused_softmax_xent(lg, lb))
            gfn = jax.grad(lambda x: jnp.sum(fx.fused_softmax_xent(x, lb)))
            measured[kreg.XENT_BWD_SURFACE] = timed(
                kreg.XENT_BWD_SURFACE, lambda: gfn(lg))
        finally:
            fx._FORCE_INTERPRET = force
        return {"autotune": sweep,
                "measured_ms": {s: round(m, 3)
                                for s, m in measured.items()},
                "shape": {"S": S, "D": D, "heads": H, "batch": B, "V": V},
                "interpret": interp, "measured": measured,
                "note": "kernel.xent_bwd times the grad dispatch "
                        "(fwd recompute + bwd kernel in one executable)"}
    finally:
        if prev_interp is None:
            os.environ.pop("PADDLE_TPU_KERNEL_INTERPRET", None)
        else:
            os.environ["PADDLE_TPU_KERNEL_INTERPRET"] = prev_interp


# ---------------------------------------------------------------------------
# ResNet-50: fwd+bwd+SGD-momentum, bf16 compute (BASELINE "ResNet-50 DP")
# ---------------------------------------------------------------------------

def bench_resnet50(B, iters):
    """r3 analysis vs BASELINE's 2.5-3.7k img/s/chip public anchor:
    measured v5e-1 ceiling here is ~2.4k at B=256 (2.1k in r2; the gain
    came from folding BN into one fused E[x]/E[x^2] pass + bf16 apply).
    r5 B-sweep re-check: 256 -> 2447, 320 -> 2174, 384 -> 2271,
    512 -> 2280 img/s — larger batches LOSE (activation HBM pressure),
    so B=256 stays the operating point.
    Why it tops out: ResNet-50's 1x1 bottleneck convs are HBM-bound
    (arith intensity ~Cout flops/byte -> roofline ~26% of bf16 peak),
    and the 3x3 convs reach only 16-25% of peak under the XLA conv
    emitter regardless of logical layout (NHWC == NCHW within noise).
    B=320/384/512 all measure lower than B=256.

    r4 closes the VERDICT #6 experiment with a measured three-way
    comparison at every bottleneck shape (B=256, latency-free 20-rep
    scan chains; ops/pallas/conv1x1.py is the fused kernel):
      - the Pallas fused conv+BN+ReLU kernel ties-or-beats BOTH XLA
        forms at 6/8 shapes (e.g. 5.46ms vs conv 8.55ms at 28x28
        128->512) and the plain dot form beats the conv emitter up to
        2.8x in isolation (3.26 vs 9.13ms at 56x56 64->256);
      - but wiring the dot form INTO the model measured 1858 img/s vs
        2344 with lax.conv (the NCHW transpose the isolated chain does
        not pay dominates), so the emitter stays;
      - all three forms sit far below even the HBM roofline in
        isolation (3-8% of peak) — the op is bandwidth/latency bound,
        and the remaining gap to the 2.5k+ anchors is the input-layout
        conversion economics of a single chip, not the lowering.
    The anchor numbers come from multi-chip runs whose per-chip batch
    and input pipeline differ; on this exact chip the bound is memory
    bandwidth, not our lowering."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.framework import autograd as _ag
    from paddle_tpu.framework.random import rng_scope
    from paddle_tpu.vision.models import resnet50

    paddle.seed(0)
    net = resnet50(num_classes=1000)
    net.train()
    params = [p for _, p in net.named_parameters()]
    buffers = [b for _, b in net.named_buffers()]
    pvals = [p._value for p in params]
    bvals = [b._value for b in buffers]

    def loss_fn(pv, bv, x, y):
        olds = [t._value for t in params + buffers]
        compute = [v.astype(jnp.bfloat16)
                   if jnp.issubdtype(v.dtype, jnp.floating) else v
                   for v in pv]
        for t, v in zip(params, compute):
            t._value = v
        for t, v in zip(buffers, bv):
            t._value = v
        try:
            with _ag.suspend_tape(), rng_scope(jax.random.key(0)):
                # input must match the bf16 params (lax.conv requires
                # uniform dtypes)
                logits = net(paddle.Tensor(x.astype(jnp.bfloat16))
                             )._value.astype(jnp.float32)
            new_bv = [t._value for t in buffers]
            logp = jax.nn.log_softmax(logits, -1)
            nll = -jnp.take_along_axis(logp, y[:, None], 1).mean()
            return nll, new_bv
        finally:
            for t, v in zip(params + buffers, olds):
                t._value = v

    lr, mom = 0.1, 0.9

    def step(pv, bv, vel, x, y):
        (loss, new_bv), g = jax.value_and_grad(loss_fn, has_aux=True)(
            pv, bv, x, y)
        new_p, new_vel = [], []
        for p, gi, vi in zip(pv, g, vel):
            nv = mom * vi + gi
            new_p.append(p - lr * nv)
            new_vel.append(nv)
        return loss, new_p, new_bv, new_vel

    step_jit = jax.jit(step, donate_argnums=(0, 1, 2))
    vel0 = [jnp.zeros_like(v) for v in pvals]
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(B, 3, 224, 224).astype("float32"))
    y = jnp.asarray(rng.randint(0, 1000, (B,)).astype("int32"))

    def run(pv, bv, vel):
        loss, pv, bv, vel = step_jit(pv, bv, vel, x, y)
        return loss, pv, bv, vel

    loss, pvals, bvals, vel0 = run(pvals, bvals, vel0)
    _readback_sync(loss)
    dt, final_loss, _ = _timeit(run, iters, pvals, bvals, vel0)
    return {"images_per_sec": round(iters * B / dt, 1),
            "loss": round(final_loss, 4), "batch": B}


# ---------------------------------------------------------------------------
# BERT-base: MLM-style train step with AMP O2 semantics (bf16 compute,
# fp32 master) — BASELINE "BERT-base DP+AMP"
# ---------------------------------------------------------------------------

def bench_bert(B, S, iters, peak):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.framework import autograd as _ag
    from paddle_tpu.framework.random import rng_scope
    from paddle_tpu.models import BertConfig, BertForPretraining

    paddle.seed(0)
    cfg = BertConfig()
    net = BertForPretraining(cfg)
    net.eval()  # p=0.0 dropout
    params = [p for _, p in net.named_parameters()]
    pvals = [p._value for p in params]

    def loss_fn(pv, ids, labels):
        olds = [p._value for p in params]
        compute = [v.astype(jnp.bfloat16)
                   if jnp.issubdtype(v.dtype, jnp.floating) else v
                   for v in pv]
        for p, v in zip(params, compute):
            p._value = v
        try:
            with _ag.suspend_tape(), rng_scope(jax.random.key(0)):
                out = net(paddle.Tensor(ids))
            logits = (out[0] if isinstance(out, (tuple, list))
                      else out)._value                    # bf16
            from paddle_tpu.ops.pallas.fused_xent import fused_softmax_xent
            V = logits.shape[-1]
            row = fused_softmax_xent(
                logits.reshape(-1, V),
                labels.reshape(-1).astype(jnp.int32))
            return row.mean()
        finally:
            for p, v in zip(params, olds):
                p._value = v

    lr = 1e-4
    K = int(os.environ.get("BENCH_STEPS_PER_CALL", "5"))

    def step(pv, ids, labels):
        loss, g = jax.value_and_grad(loss_fn)(pv, ids, labels)
        return loss, [p - lr * gi for p, gi in zip(pv, g)]

    def scan_steps(pv, ids, labels):
        def body(pv, _):
            loss, pv = step(pv, ids, labels)
            return pv, loss
        pv, losses = jax.lax.scan(body, pv, None, length=K)
        return losses[-1], pv

    step_jit = jax.jit(scan_steps, donate_argnums=(0,))
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                  (B, S)).astype("int32"))

    def run(pv):
        loss, pv = step_jit(pv, ids, ids)
        return loss, pv

    loss, pvals = run(pvals)
    _readback_sync(loss)
    dt, final_loss, _ = _timeit(run, iters, pvals)
    tokens_per_sec = iters * K * B * S / dt
    n_params = sum(int(np.prod(p.shape)) for p in params)
    flops_per_tok = 6 * n_params \
        + 12 * cfg.num_hidden_layers * S * cfg.hidden_size  # bidirectional
    return {"tokens_per_sec": round(tokens_per_sec, 1),
            "mfu": round(tokens_per_sec * flops_per_tok / peak, 4),
            "loss": round(final_loss, 4), "params": n_params,
            "batch": B, "seq": S}


# ---------------------------------------------------------------------------
# Eager-tape overhead: per-op vjp train step vs the jitted stepper on the
# same tiny model (VERDICT r1 weak #7 — make the eager path's cost known)
# ---------------------------------------------------------------------------

def bench_fp8_linear(M=32, K=4096, N=4096, layers=32, reps=1200):
    """Quantized-weight linear vs bf16 in the regime quantization
    targets: small-M (decode-style serving) where the matmul is
    WEIGHT-bandwidth-bound.

    r5 measurement fix (VERDICT r4 #1): every variant chains
    ``layers * reps`` linears inside ONE dispatch via nested lax.scan.
    r4 timed 20 *separate* async dispatches under a ~95 ms
    dispatch latency, which is why the artifact said fp8_speedup 0.72
    at 85 GB/s while the README said 1.63x — both were latency noise.
    Scan-chained, latency-subtracted, repeat-stable truth (r5, v5e,
    this config at reps=1200): bf16 1.46 ms/pass (733 GB/s), weight-
    only fp8 0.88 ms (**1.66x**, 609 GB/s), int8-MXU Pallas 1.11 ms
    (1.32x).  v5e has no MXU fp8 arithmetic: the fp8 win is
    purely the 2x weight-HBM-traffic cut (XLA fuses the upconvert into
    its weight streaming); at large M (training) fp8 ~ties bf16 — that
    is why fp8_quantize targets deploy, not the train step.
    """
    import time
    import jax
    from jax import lax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.quant_matmul import (fp8_matmul,
                                                    fp8_quantize_weight,
                                                    int8_matmul)

    rng = np.random.RandomState(0)
    Wf = rng.randn(layers, K, N).astype("f4") * 0.02
    Wb = jnp.asarray(Wf, jnp.bfloat16)
    w8s = [fp8_quantize_weight(Wf[i]) for i in range(layers)]
    W8 = jnp.stack([w for w, _ in w8s])
    S8 = jnp.stack([s for _, s in w8s])
    sci = np.maximum(np.abs(Wf).max(axis=1) / 127.0, 1e-12)
    Wi = jnp.asarray(np.clip(np.round(Wf / sci[:, None, :]), -127, 127),
                     jnp.int8)
    Si = jnp.asarray(sci * 127.0, jnp.float32)  # int8_matmul scale convention
    x = jnp.asarray(rng.randn(M, K).astype("f4"), dtype=jnp.bfloat16)

    def chained(layer_fn):
        @jax.jit
        def run(x, *stacked):
            def rep(o, _):
                def one(o, ws):
                    return layer_fn(o, ws), None
                o, _ = lax.scan(one, o, stacked if len(stacked) > 1
                                else stacked[0])
                return o, None
            o, _ = lax.scan(rep, x, None, length=reps)
            return jnp.sum(o.astype(jnp.float32))
        return run

    run_bf16 = chained(lambda o, w: ((o @ w).astype(jnp.bfloat16) * 0.01))
    run_fp8 = chained(lambda o, ws: (fp8_matmul(
        o, ws[0], ws[1], out_dtype=jnp.bfloat16) * 0.01))
    run_i8 = chained(lambda o, ws: (int8_matmul(
        o, ws[0], ws[1], act_scale=8.0,
        out_dtype=jnp.bfloat16) * 0.01).astype(jnp.bfloat16))

    # dispatch-latency calibration for the validity flag
    dispatch_ms = _dispatch_latency_ms() or 0.0

    def timed(f, *stacked):
        _readback_sync(f(x, *stacked))
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            _readback_sync(f(x, *stacked))
            ts.append((time.perf_counter() - t0) / reps)
        # subtract the (separately calibrated) per-dispatch latency share
        med = sorted(ts)[1] - dispatch_ms / 1e3 / reps
        return med, max(ts) / min(ts)

    t_bf16, j_bf16 = timed(run_bf16, Wb)
    t_fp8, j_fp8 = timed(run_fp8, W8, S8)
    t_i8, j_i8 = timed(run_i8, Wi, Si)
    latency_share = dispatch_ms / (reps * t_bf16 * 1e3 + dispatch_ms)
    return {"bf16_ms": round(t_bf16 * 1e3, 3),
            "fp8_ms": round(t_fp8 * 1e3, 3),
            "int8_ms": round(t_i8 * 1e3, 3),
            "fp8_speedup": round(t_bf16 / t_fp8, 3),
            "int8_speedup": round(t_bf16 / t_i8, 3),
            "fp8_weight_gbps": round(layers * K * N / t_fp8 / 1e9, 1),
            "bf16_weight_gbps": round(layers * K * N * 2 / t_bf16 / 1e9, 1),
            "repeat_jitter": {"bf16": round(j_bf16, 3),
                              "fp8": round(j_fp8, 3),
                              "int8": round(j_i8, 3)},
            "dispatch_latency_ms": round(dispatch_ms, 1),
            "latency_share_of_timing": round(latency_share, 4),
            # timings subtract the calibrated dispatch latency, so the
            # residual error is the latency JITTER (~2%) times the share;
            # <10% share keeps that under ~0.5% per-pass
            "valid": latency_share < 0.10,
            "shape": f"M{M} K{K} N{N} x{layers} reps{reps}"}


def bench_eager_overhead(iters=5):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn

    paddle.seed(0)
    net = paddle.vision.models.LeNet()
    x = np.random.RandomState(0).rand(32, 1, 28, 28).astype("f4")
    y = np.random.RandomState(1).randint(0, 10, (32, 1)).astype("i8")
    loss_fn = nn.CrossEntropyLoss()

    def eager_step():
        opt = getattr(eager_step, "_opt", None)
        if opt is None:
            opt = paddle.optimizer.SGD(0.01, parameters=net.parameters())
            eager_step._opt = opt
        out = net(paddle.to_tensor(x))
        loss = loss_fn(out, paddle.to_tensor(y))
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    # warm + time eager (per-op tape, no jit)
    _readback_sync(eager_step()._value)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = eager_step()
    _readback_sync(loss._value)
    eager_dt = (time.perf_counter() - t0) / iters

    # jitted stepper via hapi Model on the same net/loss
    paddle.seed(0)
    net2 = paddle.vision.models.LeNet()
    model = paddle.Model(net2)
    model.prepare(paddle.optimizer.SGD(0.01,
                                       parameters=net2.parameters()),
                  nn.CrossEntropyLoss())
    model.train_batch([x], [y])  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        res = model.train_batch([x], [y])
    jit_dt = (time.perf_counter() - t0) / iters
    # EVERY eager op call pays dispatch latency, so when that is high
    # this ratio measures the dispatch path, not the tape.
    # r5: the ratio is GATED on a low dispatch latency —
    # eager steps cannot be scan-chained (op-by-op dispatch is what
    # "eager" means), so when dispatch latency is high the only honest
    # output is the raw timings plus valid=False, never a ratio that
    # would be read as tape overhead (r4's latency-masked "1.1x").
    try:
        lat_ms = chip_calibration()["dispatch_latency_ms"]
    except Exception:
        lat_ms = None
    healthy = lat_ms is not None and lat_ms < 10.0 \
        and jit_dt * 1e3 >= 3 * lat_ms
    out = {"eager_ms": round(eager_dt * 1e3, 2),
           "jit_ms": round(jit_dt * 1e3, 2),
           "eager_over_jit": (round(eager_dt / max(jit_dt, 1e-9), 1)
                              if healthy else None),
           "dispatch_latency_ms": lat_ms,
           "valid": healthy}
    if not healthy:
        out["invalid_reason"] = (
            "latency-bound: dispatch latency too high to attribute the "
            "eager/jit delta to the tape (need <10ms and jit step >= 3x "
            "latency); last trustworthy reading: 1.7x (r3)")
    return out


# ---------------------------------------------------------------------------
# GPT-3 1.3B hybrid (the BASELINE north-star config): dp x mp sharded via
# GSPMD.  Runs whenever >1 chip is visible; on 1 chip the same config is
# re-exec'd as a subprocess onto an 8-virtual-device CPU mesh
# (--xla_force_host_platform_device_count, the conftest trick) at proxy
# scale — explicitly labeled cpu_proxy — instead of returning skipped.
# ---------------------------------------------------------------------------

def bench_gpt1p3b_hybrid(peak, iters=5, hidden=2048, layers=24,
                         heads=16, seq=1024, vocab=50304, per_dp_batch=4):
    import jax

    from paddle_tpu.models import GPTConfig

    n = jax.device_count()
    if n < 2:
        return _hybrid_cpu_proxy()
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import paddle_tpu as paddle
    from paddle_tpu.framework import autograd as _ag
    from paddle_tpu.framework.random import rng_scope
    from paddle_tpu.models import GPTForPretraining

    cfg = GPTConfig(vocab_size=vocab, hidden_size=hidden,
                    num_hidden_layers=layers, num_attention_heads=heads,
                    max_position_embeddings=seq)
    mp = 2 if n % 2 == 0 else 1
    dp = n // mp
    B, S = dp * per_dp_batch, seq
    mesh = Mesh(np.asarray(jax.devices()[:dp * mp]).reshape(dp, mp),
                ("data", "model"))
    paddle.seed(0)
    net = GPTForPretraining(cfg)
    net.eval()
    params = [p for _, p in net.named_parameters()]

    def shard(p):
        spec = [None] * len(p.shape)
        if len(p.shape) == 2 and int(np.prod(p.shape)) >= hidden * hidden:
            spec[-1] = "model"  # column-shard the big matmuls
        return NamedSharding(mesh, P(*spec))
    pvals = [jax.device_put(p._value, shard(p)) for p in params]

    def forward_pure(pv, ids):
        olds = [p._value for p in params]
        for p, v in zip(params, pv):
            p._value = v
        try:
            with _ag.suspend_tape(), rng_scope(jax.random.key(0)):
                return net(paddle.Tensor(ids))._value
        finally:
            for p, v in zip(params, olds):
                p._value = v

    def loss_fn(pv, ids):
        compute = [v.astype(jnp.bfloat16)
                   if jnp.issubdtype(v.dtype, jnp.floating) else v
                   for v in pv]
        logits = forward_pure(compute, ids)
        V = logits.shape[-1]
        lg = logits[:, :-1, :].reshape(-1, V)
        lb = ids[:, 1:].reshape(-1)
        m = jnp.max(lg, axis=-1)
        ex = jnp.exp((lg - m[:, None]).astype(jnp.float32))
        lse = m.astype(jnp.float32) + jnp.log(jnp.sum(ex, axis=-1))
        picked = jnp.take_along_axis(lg, lb[:, None], 1)[:, 0]
        return (lse - picked.astype(jnp.float32)).mean()

    lr = 1e-4

    def step(pv, ids):
        # Pallas kernels run per shard: XLA cannot partition a Mosaic
        # kernel (paddle_tpu/ops/registry.py "multi-device traces")
        from paddle_tpu.ops import registry as kreg
        with kreg.partitioned(mesh, ("data",), "model"):
            loss, g = jax.value_and_grad(loss_fn)(pv, ids)
        return loss, [p - lr * gi for p, gi in zip(pv, g)]

    step_jit = jax.jit(step, donate_argnums=(0,))
    rng = np.random.RandomState(0)
    ids = jax.device_put(
        jnp.asarray(rng.randint(0, cfg.vocab_size, (B, S), dtype=np.int32)),
        NamedSharding(mesh, P("data", None)))
    loss, pvals = step_jit(pvals, ids)
    _readback_sync(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss, pvals = step_jit(pvals, ids)
    final = _readback_sync(loss)
    dt = time.perf_counter() - t0
    tps = iters * B * S / dt
    n_params = sum(int(np.prod(p.shape)) for p in params)
    fpt = 6 * n_params + 6 * cfg.num_hidden_layers * S * cfg.hidden_size
    return {"tokens_per_sec": round(tps, 1),
            "tokens_per_sec_per_chip": round(tps / (dp * mp), 1),
            "mfu": round(tps * fpt / (peak * dp * mp), 4),
            "loss": round(final, 4), "params": n_params,
            "dp": dp, "mp": mp, "batch": B, "seq": S}


def _run_child(argv, env, timeout_s, cwd=None):
    """Start a child bench process.  A chip belongs to one process: a
    parent that holds a TPU backend cannot give it to a child, and a
    child must never quietly measure on the CPU under a TPU parent — so
    a child is only ever started from a parent whose backend is the
    (shareable) CPU one, and inherits the parent's JAX_PLATFORMS."""
    import subprocess

    import jax
    if jax.default_backend() != "cpu":
        raise RuntimeError(
            "bench.py refuses to start a child process from a parent on "
            f"the {jax.default_backend()!r} backend (one process per "
            "chip)")
    return subprocess.run(argv, env=env, cwd=cwd, capture_output=True,
                          text=True, timeout=timeout_s)


def _hybrid_cpu_proxy(timeout_s=900):
    """One visible chip: re-exec this file onto a simulated 8-device CPU
    mesh (``--xla_force_host_platform_device_count=8``) and measure the
    hybrid config at proxy scale there.  The result is explicitly
    labeled ``cpu_proxy`` — it proves the dp x mp wire pattern and the
    grad_comm bucketed/quantized reducer end to end and gives honest
    *relative* numbers (per-collective bytes, wire-format ratios), not
    TPU throughput."""
    import sys

    if os.environ.get("BENCH_HYBRID_CHILD"):
        # recursion guard: we ARE the re-exec'd child yet still see <2
        # devices (e.g. the caller's XLA_FLAGS pins its own
        # host_platform_device_count) — report, never fork again
        return {"error": "cpu-proxy child still sees <2 devices; check "
                         "XLA_FLAGS for a conflicting "
                         "host_platform_device_count"}
    env = dict(os.environ)
    env["BENCH_HYBRID_CHILD"] = "1"
    flags = env.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    here = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = here + os.pathsep + env.get("PYTHONPATH", "")
    try:
        proc = _run_child(
            [sys.executable, os.path.abspath(__file__),
             "--hybrid-cpu-proxy"], env, timeout_s, cwd=here)
        if proc.returncode != 0:
            return {"error": "cpu-proxy subprocess failed: "
                             + (proc.stderr or "")[-300:]}
        child = json.loads(proc.stdout.strip().splitlines()[-1])
    except Exception as e:
        return {"error": f"cpu-proxy subprocess: {repr(e)[:200]}"}
    return {"cpu_proxy": True,
            "note": "1 chip visible: measured on a simulated 8-device "
                    "CPU mesh at proxy model scale — wire pattern and "
                    "byte ratios are real, absolute tokens/sec is CPU",
            **child}


def _bench_grad_comm_wire_modes(iters=3, B=8, S=128):
    """Pure-DP proxy GPT through the hapi grad_comm stepper, once per
    wire format (fp32 psum / bf16 / int8 quantized), on the current
    (8-virtual-device) mesh.  Per-collective bytes come from the
    ``pt_collective_bytes_total`` counters — ticked per *tracing*, so
    each mode's number is its per-replica wire bytes for one step.  The
    registry is NOT reset between modes: each mode's ops have distinct
    names, so one final telemetry snapshot carries the whole fp32-vs-
    quantized comparison."""
    import paddle_tpu as paddle
    from paddle_tpu import observability as obs
    from paddle_tpu.distributed.fleet.base.distributed_strategy import \
        DistributedStrategy
    from paddle_tpu.models import (GPTConfig, GPTForPretraining,
                                   GPTPretrainingCriterion)

    cfg = GPTConfig(vocab_size=4096, hidden_size=128,
                    num_hidden_layers=2, num_attention_heads=4,
                    max_position_embeddings=S)
    obs.get_registry().reset()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (B, S)).astype("i4")
    out = {}
    for mode in (None, "bf16", "int8"):
        st = DistributedStrategy()
        st.grad_comm = True
        st.grad_comm_configs = {"bucket_mb": 0.25, "overlap": True,
                                "quantize": mode}
        paddle.seed(0)
        net = GPTForPretraining(cfg)
        net.eval()  # p=0 dropout: mask-free graph, math == train()
        dp = paddle.DataParallel(net, strategy=st)
        model = paddle.Model(dp)
        model.prepare(paddle.optimizer.AdamW(
            1e-4, parameters=net.parameters()), GPTPretrainingCriterion())
        model.train_batch([ids], [ids])  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            res = model.train_batch([ids], [ids])
        _readback_sync(res[0] if isinstance(res, (list, tuple)) else res)
        dt = time.perf_counter() - t0
        bytes_m = obs.get_registry().get("pt_collective_bytes_total")
        per_op = {lbl["op"]: int(v) for lbl, v in bytes_m.series()
                  if lbl["op"].startswith("grad_")} if bytes_m else {}
        ops = {"bf16": ("grad_bucket_psum_bf16",),
               "int8": ("grad_quant_all_to_all", "grad_quant_all_gather"),
               }.get(mode, ("grad_bucket_psum",))
        out[mode or "fp32"] = {
            "tokens_per_sec": round(iters * B * S / dt, 1),
            "wire_bytes_per_step": sum(per_op.get(o, 0) for o in ops),
            "ops": {o: per_op.get(o, 0) for o in ops},
        }
    fp32_b = out["fp32"]["wire_bytes_per_step"]
    for mode in ("bf16", "int8"):
        if fp32_b:
            out[mode]["wire_bytes_vs_fp32"] = round(
                out[mode]["wire_bytes_per_step"] / fp32_b, 4)
    return out


def _hybrid_cpu_proxy_child():
    """Child entry (``bench.py --hybrid-cpu-proxy``): runs on the forced
    8-device CPU mesh, prints ONE JSON line for the parent."""
    import jax

    from paddle_tpu.device import chip

    device = chip.describe()
    if device["platform"] != "cpu":
        raise SystemExit("--hybrid-cpu-proxy is the CPU-mesh child; it "
                         "must inherit JAX_PLATFORMS=cpu from its parent")
    out = {"devices": jax.device_count(), "device": device,
           "mesh": "xla_force_host_platform_device_count=8"}
    hybrid = bench_gpt1p3b_hybrid(peak=_CPU_PROXY_PEAK_FLOPS, iters=3,
                                  hidden=256,
                                  layers=4, heads=8, seq=256, vocab=8192,
                                  per_dp_batch=2)
    hybrid["proxy_model"] = "hidden=256 L=4 heads=8 S=256 V=8192"
    out["hybrid_gspmd"] = hybrid
    try:
        out["grad_comm"] = _bench_grad_comm_wire_modes()
    except Exception as e:
        out["grad_comm"] = {"error": repr(e)[:200]}
    else:
        # _telemetry_snapshot reports its own failure inline; never let
        # a sink problem overwrite the computed wire-mode comparison
        out["telemetry"] = _telemetry_snapshot("hybrid_proxy")
    print(json.dumps(out))


# ---------------------------------------------------------------------------
# Autoregressive decode (serving): GPT-125M bf16 greedy generation with the
# static KV cache — prefill + the whole token-by-token scan is ONE compiled
# dispatch, so the number is latency-robust by construction.
# ---------------------------------------------------------------------------

def bench_decode(B=8, P=128, N=128, iters=3):
    """Measured r5: bf16 1.22-1.44 ms/step.  fp8-quantizing the model
    (quantization.fp8_quantize + generate, measured directly) TIES bf16
    here (1.25 vs 1.22 ms/step): at 768-wide layers the decode step is
    not weight-bandwidth-dominated, so halving matmul weight bytes
    doesn't move it — the fp8 serving win needs the K=N=4096-class
    layers the fp8_linear config measures (1.66x there).  A 1.3B-scale
    decode (where the weight stream WOULD dominate) could not be
    measured on the remote device path of round 5 (the 24-layer x
    128-step scan program failed to compile there); not measured on
    the local chip."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    cfg = GPTConfig(vocab_size=50304, hidden_size=768,
                    num_hidden_layers=12, num_attention_heads=12,
                    max_position_embeddings=P + N)
    paddle.seed(0)
    net = GPTForPretraining(cfg)
    net.eval()
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(
        rng.randint(0, cfg.vocab_size, (B, P)).astype("int32"))
    out, _ = net.generate(ids, max_new_tokens=N, dtype="bfloat16")
    _readback_sync(out._value[:, -1].astype("float32").sum())  # warmup
    t0 = time.perf_counter()
    for i in range(iters):
        out, _ = net.generate(ids, max_new_tokens=N, dtype="bfloat16",
                              seed=i)
        _readback_sync(out._value[:, -1].astype("float32").sum())
    dt = time.perf_counter() - t0
    decode_tps = iters * B * N / dt
    return {"decode_tokens_per_sec": round(decode_tps, 1),
            "ms_per_step": round(dt / (iters * N) * 1e3, 3),
            "batch": B, "prompt": P, "new_tokens": N,
            "model": "gpt125m", "dtype": "bfloat16"}


# ---------------------------------------------------------------------------
# Serving: continuous-batching engine vs static-batch generate() on a
# mixed-length request trace — the workload where static batching burns
# slots on drained rows (ISSUE 4 tentpole).
# ---------------------------------------------------------------------------

def bench_serving(n_requests=64, seed=0, hidden=768, layers=12, heads=12,
                  p_range=(32, 512), n_range=(16, 256), slots=8, chunk=32,
                  p_lams=(48, 96, 192, 384), n_lams=(24, 64, 160)):
    """Mixed-length trace (prompts 32-512, new-tokens 16-256, both
    log-uniform-ish via Poisson-mixed geometric draws) through:

      1. the static-batch baseline: FCFS groups of 8 through
         ``generate()``, prompts left-padded (attention_mask) to the
         group's power-of-two bucket and every row decoding the group's
         max budget rounded up to a bucket — the padding/drain waste is
         the point, but bucketing keeps the compile count bounded;
      2. the continuous-batching ``ServingEngine`` (8 slots, chunk=32)
         over the identical requests.

    Both run the full trace once to compile (programs cache), then the
    timed pass.  tokens/sec counts USEFUL tokens only (each request's
    own budget).  Validity mirrors eager_overhead: the engine pays one
    dispatch per chunk + one per prefill, so when the calibrated
    dispatch latency accounts for >30% of the engine's wall the ratio
    measures the dispatch path, not the scheduler — reported with
    ``valid=False`` + ``invalid_reason`` instead of a hollow speedup.
    """
    import jax  # noqa: F401  (device selection side effects)

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    def bucket(n, lo):
        b = lo
        while b < n:
            b *= 2
        return b

    GROUP = slots
    p_lo, p_hi = p_range
    n_lo, n_hi = n_range
    max_seq = bucket(p_hi, p_lo) + bucket(n_hi, n_lo)
    cfg = GPTConfig(vocab_size=50304, hidden_size=hidden,
                    num_hidden_layers=layers, num_attention_heads=heads,
                    max_position_embeddings=max_seq)
    paddle.seed(0)
    net = GPTForPretraining(cfg)
    net.eval()

    rng = np.random.RandomState(seed)
    # Poisson-mixed lengths, clipped into the brief's ranges
    plens = np.clip(rng.poisson(lam=rng.choice(p_lams, size=n_requests)),
                    p_lo, p_hi).astype(int)
    budgets = np.clip(rng.poisson(lam=rng.choice(n_lams, size=n_requests)),
                      n_lo, n_hi).astype(int)
    prompts = [rng.randint(0, cfg.vocab_size, (int(n),)).astype("int32")
               for n in plens]
    useful = int(budgets.sum())

    def run_static():
        done_tokens = 0
        ttfts = []
        t_start = time.perf_counter()
        for g in range(0, n_requests, GROUP):
            gp = prompts[g:g + GROUP]
            gb = budgets[g:g + GROUP]
            P = bucket(max(p.size for p in gp), p_lo)
            N = bucket(int(gb.max()), n_lo)
            ids = np.zeros((len(gp), P), np.int32)
            mask = np.zeros((len(gp), P), np.int32)
            for i, p in enumerate(gp):          # left-pad to the bucket
                ids[i, P - p.size:] = p
                mask[i, P - p.size:] = 1
            out, _ = net.generate(paddle.to_tensor(ids),
                                  max_new_tokens=N, dtype="bfloat16",
                                  attention_mask=mask)
            # completion barrier: data-dependent readback
            _readback_sync(out._value[:, -1].astype("float32").sum())
            now = time.perf_counter()
            # a static group's tokens all materialize when the group
            # returns; only each row's own budget counts as useful
            done_tokens += int(gb.sum())
            ttfts.extend([(now - t_start) * 1e3] * len(gp))
        wall = time.perf_counter() - t_start
        return done_tokens / wall, sum(ttfts) / len(ttfts), wall

    def run_engine(eng):
        eng.reset()
        t_start = time.perf_counter()
        for p, b in zip(prompts, budgets):
            eng.submit(p, int(b))
        eng.run()
        wall = time.perf_counter() - t_start
        tt = eng.stats["ttft_ms"]
        return (eng.stats["decoded_tokens"] / wall,
                sum(tt) / len(tt), wall)

    # the engine's default power-of-two buckets (16..<max_seq) cover the
    # trace; buckets no prompt lands in never trace (jax.jit is lazy)
    eng = ServingEngine(net, num_slots=GROUP, chunk=chunk,
                        max_seq_len=max_seq, dtype="bfloat16")
    # compile passes (programs cache on the model / in the engine)
    run_engine(eng)
    run_static()
    static_tps, static_ttft, _ = run_static()
    # timed pass runs with the flight recorder watching (ISSUE 13):
    # sampling is host-only at the existing chunk sync, so it is free
    # at bench fidelity — and a healthy bench run must raise ZERO watch
    # alerts, which the committed bench line records
    from paddle_tpu.framework import guardian as _guardian
    from paddle_tpu.observability import flight as _flight
    _alerts0 = len(_guardian.events("watch_alert"))
    # dump_dir=False: alerts-only, so a rule trip can never start disk
    # I/O inside the timed region even when PADDLE_FLIGHT_DIR is set;
    # and never stomp a recorder the user installed via PADDLE_FLIGHT=1
    _owned = not _flight.active()
    _rec = _flight.enable(dump_dir=False) if _owned \
        else _flight.recorder()
    try:
        engine_tps, engine_ttft, engine_wall = run_engine(eng)
        watch_alerts = len(_guardian.events("watch_alert")) - _alerts0
        flight_samples = len(_rec.samples())
    finally:
        if _owned:
            _flight.disable()

    lat_ms = _dispatch_latency_ms()
    n_dispatch = eng.stats["chunks"] + eng.stats["prefills"]
    lat_share = None if lat_ms is None else \
        min(n_dispatch * lat_ms / 1e3 / max(engine_wall, 1e-9), 1.0)
    healthy = lat_share is not None and lat_share < 0.30
    out = {"engine_tokens_per_sec": round(engine_tps, 1),
           "static_tokens_per_sec": round(static_tps, 1),
           "speedup": round(engine_tps / max(static_tps, 1e-9), 3),
           "engine_mean_ttft_ms": round(engine_ttft, 1),
           "static_mean_ttft_ms": round(static_ttft, 1),
           "useful_tokens": useful,
           "requests": n_requests, "slots": GROUP, "chunk": chunk,
           "chunks": eng.stats["chunks"],
           "prefills": eng.stats["prefills"],
           "flight_samples": flight_samples,
           "watch_alerts": watch_alerts,
           "dispatch_latency_ms": lat_ms,
           "latency_share_of_engine_wall": (round(lat_share, 4)
                                            if lat_share is not None
                                            else None),
           "valid": healthy,
           "model": f"gpt_h{hidden}_l{layers}", "dtype": "bfloat16"}
    if not healthy:
        out["invalid_reason"] = (
            "latency-bound: per-chunk/prefill dispatch latency accounts "
            "for >=30% of the engine's wall clock, so the ratio measures "
            "dispatch latency, not continuous batching")
    return out


# ---------------------------------------------------------------------------
# Serving, prefix-heavy: 64 requests sharing one system prompt — the
# workload the paged KV subsystem (ISSUE 7) exists for.  Dense re-prefills
# the shared prompt per request and holds S x MAX KV regardless of
# occupancy; paged prefills it once (prefix cache) and keeps only live
# pages resident.
# ---------------------------------------------------------------------------

def bench_serving_prefix(n_requests=64, seed=0, hidden=768, layers=12,
                         heads=12, sys_len=256, sfx_range=(8, 48),
                         n_range=(16, 64), slots=8, chunk=32,
                         page_size=16):
    """The same engine/trace/validity discipline as ``bench_serving``,
    but every request is ``system_prompt + unique_suffix`` and the trace
    runs through three engines — dense, paged, paged+int8 — reporting:

    - prefix hit-rate and prefill tokens actually computed (the FLOPs
      saved is proportional: prefill FLOPs ~ 2 * params * tokens);
    - KV HBM high-water: dense's static ``S x MAX`` allocation vs the
      paged pool's resident high-water (``pt_kvcache_*`` gauges);
    - useful tokens/sec per mode (same dispatch-latency validity gate).

    Token parity between dense and paged is asserted, not reported —
    a perf number for a wrong answer is worthless.
    """
    import jax  # noqa: F401

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    def bucket(n, lo=16):
        b = lo
        while b < n:
            b *= 2
        return b

    max_seq = bucket(sys_len + sfx_range[1]) + bucket(n_range[1])
    cfg = GPTConfig(vocab_size=50304, hidden_size=hidden,
                    num_hidden_layers=layers, num_attention_heads=heads,
                    max_position_embeddings=max_seq)
    paddle.seed(0)
    net = GPTForPretraining(cfg)
    net.eval()

    rng = np.random.RandomState(seed)
    sysp = rng.randint(0, cfg.vocab_size, (sys_len,)).astype("int32")
    prompts = [np.concatenate([sysp, rng.randint(
        0, cfg.vocab_size,
        (int(rng.randint(*sfx_range)),)).astype("int32")])
        for _ in range(n_requests)]
    budgets = rng.randint(*n_range, size=n_requests)
    useful = int(budgets.sum())
    prompt_tokens = int(sum(p.size for p in prompts))

    def run(eng):
        eng.reset()
        t0 = time.perf_counter()
        reqs = [eng.submit(p, int(b)) for p, b in zip(prompts, budgets)]
        eng.run()
        wall = time.perf_counter() - t0
        return reqs, eng.stats["decoded_tokens"] / wall, wall

    def dense_kv_bytes(eng):
        # the dense engine's static per-layer (S, MAX, nH, D) K+V rows
        return sum(2 * k.nbytes for k, _ in eng._caches)

    results, walls, dispatches, baseline = {}, [], [], None
    modes = (("dense", {}),
             ("paged", {"kv_mode": "paged", "page_size": page_size}),
             ("paged_int8", {"kv_mode": "paged", "page_size": page_size,
                             "kv_dtype": "int8"}))
    for name, kw in modes:
        eng = ServingEngine(net, num_slots=slots, chunk=chunk,
                            max_seq_len=max_seq, dtype="bfloat16", **kw)
        run(eng)                                    # compile pass
        reqs, tps, wall = run(eng)
        walls.append(wall)
        dispatches.append(eng.stats["chunks"] + eng.stats["prefills"])
        toks = [list(r.tokens) for r in sorted(reqs,
                                               key=lambda r: r.req_id)]
        if name == "dense":
            baseline = toks
            results[name] = {
                "tokens_per_sec": round(tps, 1),
                "kv_hbm_high_water_bytes": dense_kv_bytes(eng),
                "prefill_tokens_computed": prompt_tokens}
        else:
            if name == "paged":
                # full precision must be BITWISE; int8 is tolerance-
                # bounded (docs/serving.md) and reported, not asserted
                assert toks == baseline, \
                    "paged engine output diverged from dense"
            kv = eng._kv
            hits = kv.stats["prefix_hits"]
            saved = kv.stats["prefix_saved_tokens"]
            results[name] = {
                "tokens_per_sec": round(tps, 1),
                "kv_hbm_high_water_bytes":
                    kv.stats["resident_high_water_bytes"],
                "prefix_hit_rate": round(hits / n_requests, 3),
                "prefill_tokens_computed": prompt_tokens - saved,
                "prefill_tokens_saved": saved,
                "prefill_flops_saved_frac":
                    round(saved / prompt_tokens, 3),
                "page_evictions": eng.stats["page_evictions"]}
            if name == "paged_int8":
                agree = [int(a == b) for ta, tb in zip(toks, baseline)
                         for a, b in zip(ta, tb)]
                results[name]["token_agreement_vs_dense"] = round(
                    sum(agree) / max(len(agree), 1), 4)
        del eng

    # dispatch-latency validity gate (same probe as bench_serving)
    lat_ms = _dispatch_latency_ms()
    lat_share = None if lat_ms is None else \
        min(max(d * lat_ms / 1e3 / max(w, 1e-9)
                for d, w in zip(dispatches, walls)), 1.0)
    healthy = lat_share is not None and lat_share < 0.30
    dense_hw = results["dense"]["kv_hbm_high_water_bytes"]
    out = {"modes": results,
           "kv_hbm_paged_over_dense": round(
               results["paged"]["kv_hbm_high_water_bytes"] / dense_hw, 4),
           "kv_hbm_paged_int8_over_dense": round(
               results["paged_int8"]["kv_hbm_high_water_bytes"]
               / dense_hw, 4),
           "requests": n_requests, "shared_prefix_len": sys_len,
           "useful_tokens": useful, "slots": slots, "chunk": chunk,
           "page_size": page_size,
           "dispatch_latency_ms": lat_ms,
           "latency_share_of_engine_wall": (round(lat_share, 4)
                                            if lat_share is not None
                                            else None),
           "valid": healthy,
           "model": f"gpt_h{hidden}_l{layers}", "dtype": "bfloat16"}
    if not healthy:
        out["invalid_reason"] = (
            "latency-bound: per-chunk/prefill dispatch latency accounts "
            "for >=30% of an engine's wall clock, so mode ratios "
            "measure dispatch latency, not the KV subsystem")
    return out


# ---------------------------------------------------------------------------
# Serving, speculative: the SAME Poisson trace as `serving`, replayed with
# and without draft-verify speculation on both KV modes (ISSUE 8).  How
# dispatch-bound decode is on the local chip is not measured; speculation
# multiplies tokens-per-dispatch by the accepted draft length, so the win
# shows up as useful tokens/sec on an identical-output run.
# ---------------------------------------------------------------------------

def bench_serving_spec(n_requests=64, seed=0, hidden=768, layers=12,
                       heads=12, p_range=(32, 512), n_range=(16, 256),
                       slots=8, chunk=32, gamma=4, ngram=3, page_size=16,
                       p_lams=(48, 96, 192, 384), n_lams=(24, 64, 160)):
    """Four engines over one trace — dense, dense+spec, paged,
    paged+spec — using the model-free n-gram prompt-lookup drafter (no
    second network to keep honest; the draft-model path is covered by
    tests).  Greedy speculative output is asserted BITWISE equal to the
    non-speculative engine per KV mode (a speedup for a different
    answer is worthless), acceptance telemetry is reported from
    ``engine.stats``, and the same dispatch-latency validity gate as
    ``serving`` guards the ratios."""
    import jax  # noqa: F401

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.inference.speculative import SpecConfig
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    def bucket(n, lo):
        b = lo
        while b < n:
            b *= 2
        return b

    p_lo, p_hi = p_range
    n_lo, n_hi = n_range
    max_seq = bucket(p_hi, p_lo) + bucket(n_hi, n_lo)
    cfg = GPTConfig(vocab_size=50304, hidden_size=hidden,
                    num_hidden_layers=layers, num_attention_heads=heads,
                    max_position_embeddings=max_seq)
    paddle.seed(0)
    net = GPTForPretraining(cfg)
    net.eval()
    rng = np.random.RandomState(seed)
    plens = np.clip(rng.poisson(lam=rng.choice(p_lams, size=n_requests)),
                    p_lo, p_hi).astype(int)
    budgets = np.clip(rng.poisson(lam=rng.choice(n_lams, size=n_requests)),
                      n_lo, n_hi).astype(int)
    prompts = [rng.randint(0, cfg.vocab_size, (int(n),)).astype("int32")
               for n in plens]
    useful = int(budgets.sum())

    def run(eng):
        eng.reset()
        t0 = time.perf_counter()
        reqs = [eng.submit(p, int(b)) for p, b in zip(prompts, budgets)]
        eng.run()
        wall = time.perf_counter() - t0
        toks = [list(r.tokens) for r in sorted(reqs,
                                               key=lambda r: r.req_id)]
        return toks, eng.stats["decoded_tokens"] / wall, wall

    spec = SpecConfig(gamma=gamma, ngram=ngram)
    modes = (("dense", {}),
             ("dense_spec", {"spec_decode": spec}),
             ("paged", {"kv_mode": "paged", "page_size": page_size}),
             ("paged_spec", {"kv_mode": "paged", "page_size": page_size,
                             "spec_decode": spec}))
    results, walls, dispatches, baseline = {}, {}, {}, {}
    for name, kw in modes:
        eng = ServingEngine(net, num_slots=slots, chunk=chunk,
                            max_seq_len=max_seq, dtype="bfloat16", **kw)
        run(eng)                                    # compile pass
        toks, tps, wall = run(eng)
        walls[name] = wall
        dispatches[name] = eng.stats["chunks"] + eng.stats["prefills"]
        res = {"tokens_per_sec": round(tps, 1),
               "chunks": eng.stats["chunks"],
               "prefills": eng.stats["prefills"]}
        if kw.get("spec_decode") is not None:
            base = name.split("_")[0]
            # the parity contract IS the product: bitwise or bust
            assert toks == baseline[base], \
                f"speculative {base} output diverged from {base}"
            prop = eng.stats["spec_proposed"]
            acc = eng.stats["spec_accepted"]
            part = prop // gamma                # slot-steps, not steps
            res.update({
                "speedup_vs_base": round(
                    tps / max(results[base]["tokens_per_sec"], 1e-9), 3),
                "gamma": gamma, "ngram": ngram,
                "proposed": prop, "accepted": acc,
                "accept_rate": round(acc / prop, 4) if prop else None,
                "mean_accept_len": round(acc / part, 3) if part
                else None,
                "tokens_per_dispatch": round(
                    useful / max(dispatches[name], 1), 2)})
        else:
            baseline[name] = toks
            res["tokens_per_dispatch"] = round(
                useful / max(dispatches[name], 1), 2)
        results[name] = res
        del eng

    lat_ms = _dispatch_latency_ms()
    lat_share = None if lat_ms is None else \
        min(max(d * lat_ms / 1e3 / max(walls[n], 1e-9)
                for n, d in dispatches.items()), 1.0)
    healthy = lat_share is not None and lat_share < 0.30
    out = {"modes": results,
           "speedup_dense": results["dense_spec"]["speedup_vs_base"],
           "speedup_paged": results["paged_spec"]["speedup_vs_base"],
           "requests": n_requests, "useful_tokens": useful,
           "slots": slots, "chunk": chunk, "gamma": gamma,
           "dispatch_latency_ms": lat_ms,
           "latency_share_of_engine_wall": (round(lat_share, 4)
                                            if lat_share is not None
                                            else None),
           "valid": healthy,
           "model": f"gpt_h{hidden}_l{layers}", "dtype": "bfloat16"}
    if not healthy:
        out["invalid_reason"] = (
            "latency-bound: per-chunk/prefill dispatch latency accounts "
            "for >=30% of an engine's wall clock, so spec ratios measure "
            "dispatch latency, not draft-verify speculation")
    return out


# ---------------------------------------------------------------------------
# Serving, quantized weights: the SAME Poisson trace through base,
# int8-weight and fp8-weight engines (ISSUE 19).  Decode is weight-
# stream-bound, so shrinking resident weight bytes is the lever; the
# measured token-agreement rate vs the base stream is reported next to
# every ratio (docs/serving.md "Quantized decode": floor >= 99%).
# ---------------------------------------------------------------------------

def bench_serving_quant(n_requests=64, seed=0, hidden=768, layers=12,
                        heads=12, p_range=(32, 512), n_range=(16, 256),
                        slots=8, chunk=32, dtype="bfloat16",
                        p_lams=(48, 96, 192, 384), n_lams=(24, 64, 160)):
    """Three engines over ONE trace — base (``dtype``), int8 weights,
    fp8 weights — same trace/validity discipline as ``bench_serving``.
    Reports useful tokens/sec per mode, speedup vs base, the MEASURED
    token-agreement rate against the base greedy stream (quantization
    changes the model, so agreement is a reported number, not an
    assert), and the ``pt_serving_quant_bytes_saved`` gauge per mode.
    The dispatch-latency validity gate guards the ratios exactly as in
    ``serving``."""
    import jax  # noqa: F401

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    def bucket(n, lo):
        b = lo
        while b < n:
            b *= 2
        return b

    p_lo, p_hi = p_range
    n_lo, n_hi = n_range
    max_seq = bucket(p_hi, p_lo) + bucket(n_hi, n_lo)
    cfg = GPTConfig(vocab_size=50304, hidden_size=hidden,
                    num_hidden_layers=layers, num_attention_heads=heads,
                    max_position_embeddings=max_seq)
    paddle.seed(0)
    net = GPTForPretraining(cfg)
    net.eval()
    rng = np.random.RandomState(seed)
    plens = np.clip(rng.poisson(lam=rng.choice(p_lams, size=n_requests)),
                    p_lo, p_hi).astype(int)
    budgets = np.clip(rng.poisson(lam=rng.choice(n_lams, size=n_requests)),
                      n_lo, n_hi).astype(int)
    prompts = [rng.randint(0, cfg.vocab_size, (int(n),)).astype("int32")
               for n in plens]
    useful = int(budgets.sum())

    def run(eng):
        eng.reset()
        t0 = time.perf_counter()
        reqs = [eng.submit(p, int(b)) for p, b in zip(prompts, budgets)]
        eng.run()
        wall = time.perf_counter() - t0
        toks = [list(r.tokens) for r in sorted(reqs,
                                               key=lambda r: r.req_id)]
        return toks, eng.stats["decoded_tokens"] / wall, wall

    def agreement(a, b):
        """(free-running agreement, mean prefix-agreement).  Greedy
        decode on a random-init model is chaotic — near-flat logit
        margins mean ONE quant-flipped argmax diverges the whole tail,
        so the free-running rate is a lower bound that collapses with
        sequence length; the prefix rate (tokens before the first
        divergence) is the per-decision number.  Per-step decision
        fidelity at trained-margin scales is machine-checked at >=99%
        in tests/test_quant_paths.py."""
        n = d = 0
        prefixes = []
        for x, y in zip(a, b):
            first = None
            for i, (u, v) in enumerate(zip(x, y)):
                d += 1
                if u == v:
                    n += 1
                elif first is None:
                    first = i
            prefixes.append((len(x) if first is None else first)
                            / max(len(x), 1))
        return n / max(d, 1), sum(prefixes) / max(len(prefixes), 1)

    from paddle_tpu.observability import get_registry
    modes = (("base", None), ("int8", "int8"), ("fp8", "fp8"))
    results, walls, dispatches, base_toks = {}, {}, {}, None
    for name, qmode in modes:
        eng = ServingEngine(net, num_slots=slots, chunk=chunk,
                            max_seq_len=max_seq, dtype=dtype,
                            quant_mode=qmode)
        saved = None
        if qmode is not None:
            g = get_registry().get("pt_serving_quant_bytes_saved")
            saved = int(g.value()) if g is not None else None
        run(eng)                                    # compile pass
        toks, tps, wall = run(eng)
        walls[name] = wall
        dispatches[name] = eng.stats["chunks"] + eng.stats["prefills"]
        res = {"useful_tokens_per_sec": round(tps, 1),
               "chunks": eng.stats["chunks"],
               "prefills": eng.stats["prefills"]}
        if qmode is None:
            base_toks = toks
        else:
            agree, prefix = agreement(base_toks, toks)
            res.update({
                "speedup_vs_base": round(
                    tps / max(results["base"]["useful_tokens_per_sec"],
                              1e-9), 3),
                "token_agreement_vs_base": round(agree, 4),
                "prefix_agreement_vs_base": round(prefix, 4),
                "quant_bytes_saved": saved})
        results[name] = res
        del eng

    # One eager dispatch per mode at the decode-head shape (M=slots,
    # K=hidden, N=vocab): engine-traced quant_matmul calls inline into
    # the serving.decode_chunk surface, so the roofline's standalone
    # `kernel.quant_matmul` row comes from this measured dispatch.
    import jax.numpy as jnp
    from paddle_tpu.ops import quant_dispatch as _qd
    table = jnp.asarray(net.tied_lm_head._value).T      # (H, V)
    x_dec = jnp.asarray(rng.randn(slots, hidden).astype("float32"))
    for m in ("int8", "fp8"):
        np.asarray(_qd.quant_matmul(x_dec, _qd.quantize_weight(table, m)))

    lat_ms = _dispatch_latency_ms()
    lat_share = None if lat_ms is None else \
        min(max(d * lat_ms / 1e3 / max(walls[n], 1e-9)
                for n, d in dispatches.items()), 1.0)
    healthy = lat_share is not None and lat_share < 0.30
    out = {"modes": results,
           "speedup_int8": results["int8"]["speedup_vs_base"],
           "speedup_fp8": results["fp8"]["speedup_vs_base"],
           "agreement_int8": results["int8"]["token_agreement_vs_base"],
           "agreement_fp8": results["fp8"]["token_agreement_vs_base"],
           # the kernel-level uplift on real accelerator silicon, from
           # the scan-chained latency-subtracted fp8_linear row (r5,
           # v5e, M=32 K=N=4096): the CPU proxy reproduces the int8
           # weight-stream win via the tiled off-TPU lowering, but the
           # fp8 upconvert is software-emulated there, so the fp8
           # column's deploy-path truth lives in these numbers
           "kernel_uplift_v5e": {"fp8": 1.66, "int8": 1.32,
                                 "source": "fp8_linear r5"},
           "requests": n_requests, "useful_tokens": useful,
           "slots": slots, "chunk": chunk,
           "dispatch_latency_ms": lat_ms,
           "latency_share_of_engine_wall": (round(lat_share, 4)
                                            if lat_share is not None
                                            else None),
           "valid": healthy,
           "model": f"gpt_h{hidden}_l{layers}", "dtype": dtype}
    if not healthy:
        out["invalid_reason"] = (
            "latency-bound: per-chunk/prefill dispatch latency accounts "
            "for >=30% of an engine's wall clock, so quant ratios "
            "measure dispatch latency, not the weight-stream win")
    return out


# ---------------------------------------------------------------------------
# fp8 train pilot: the hapi stepper's delayed-scaling fake-quant A/B
# (ISSUE 19).  Parity is the product — the loss envelope is the gate;
# the step-time ratio reports what the fake-quant costs where there is
# no fp8 hardware to pay it back.
# ---------------------------------------------------------------------------

def bench_fp8_train(peak, B=16, steps=30, in_dim=64, width=256,
                    depth=3, out_dim=32, warmup=5):
    """The same regression fit with and without
    ``amp_configs="fp8"`` (identical seeds/batches): reports steps/sec
    per mode, the loss-parity envelope (max relative deviation over
    the run; docs/kernels.md documents <= 5%), a flops-proxy MFU, and
    the delayed-scaling amax state's health."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu.static import InputSpec

    rng = np.random.RandomState(0)
    batches = [(rng.randn(B, in_dim).astype("float32"),
                rng.randn(B, out_dim).astype("float32"))
               for _ in range(steps)]

    def build(amp_configs=None):
        paddle.seed(3)
        layers = [nn.Linear(in_dim, width), nn.ReLU()]
        for _ in range(depth - 2):
            layers += [nn.Linear(width, width), nn.ReLU()]
        layers += [nn.Linear(width, out_dim)]
        net = nn.Sequential(*layers)
        m = paddle.Model(net,
                         inputs=[InputSpec([None, in_dim], "float32",
                                           "x")],
                         labels=[InputSpec([None, out_dim], "float32",
                                           "y")])
        opt = paddle.optimizer.Adam(learning_rate=1e-3,
                                    parameters=net.parameters())
        m.prepare(opt, nn.MSELoss(), amp_configs=amp_configs)
        return m

    def fit(m):
        losses, t_timed = [], None
        for i, (x, y) in enumerate(batches):
            if i == warmup:
                t_timed = time.perf_counter()
            res = m.train_batch([x], [y])
            loss = res[0] if isinstance(res, (tuple, list)) else res
            while isinstance(loss, (tuple, list, np.ndarray)):
                loss = loss[0]
            losses.append(float(loss))
        wall = time.perf_counter() - t_timed
        return losses, (steps - warmup) / wall

    base_losses, base_sps = fit(build())
    m8 = build(amp_configs="fp8")
    fp8_losses, fp8_sps = fit(m8)
    rel = [abs(a - b) / max(abs(a), 1e-6)
           for a, b in zip(base_losses, fp8_losses)]
    amax = np.asarray(m8._stepper.fp8_state)
    # flops proxy: fwd 2*B*W + bwd 4*B*W per step over the matmul params
    wparams = in_dim * width + (depth - 2) * width * width \
        + width * out_dim
    flops = 6.0 * B * wparams
    return {"steps_per_sec_base": round(base_sps, 2),
            "steps_per_sec_fp8": round(fp8_sps, 2),
            "fp8_step_overhead": round(base_sps / max(fp8_sps, 1e-9), 3),
            "mfu": round(flops * fp8_sps / peak, 6),
            "max_rel_loss_dev": round(max(rel), 4),
            "final_rel_loss_dev": round(rel[-1], 4),
            "loss_parity_ok": max(rel) < 0.05,
            "final_loss_base": round(base_losses[-1], 4),
            "final_loss_fp8": round(fp8_losses[-1], 4),
            "amax_entries": int(amax.size),
            "amax_finite": bool(np.isfinite(amax).all()),
            "steps": steps, "batch": B,
            "model": f"mlp_{in_dim}x{width}x{depth}"}


# ---------------------------------------------------------------------------
# Serving fleet: the SAME Poisson trace replayed through ONE engine and
# through N-replica ServingFleet routers (ISSUE 12).  Each replica is its
# own engine (slots + KV + compiled programs) stepped by its own thread.
# Every config runs in a FRESH SUBPROCESS whose CPU affinity is set to
# one core per replica-chip BEFORE jax initializes -- the chip-proxy
# discipline (PR 6's --xla_force_host_platform_device_count sibling):
# without it, XLA:CPU's machine-wide intra-op pool lets the single
# "one-chip" baseline borrow every core during prefill matmuls, which
# understates fleet scaling by exactly the borrowed factor.  Output is
# asserted BITWISE equal to the single engine per request (same seeds ->
# same weights in every child); the N=max child snapshots telemetry
# under the `router` tag (telemetry/router.{prom,jsonl} +
# router_requests.trace.json -- traces span router->replica).
# ---------------------------------------------------------------------------

_FLEET_CHILD_ENV = "BENCH_FLEET_CHILD"


def _fleet_run_config(P, n_replicas, snapshot=False):
    """One serving_fleet sub-config (runs inside the pinned child):
    ``n_replicas == 1`` is the plain single-engine baseline, else a
    ``ServingFleet`` with worker threads.  Returns plain-JSON results
    including every request's token ids (the parent's bitwise check)."""
    import jax  # noqa: F401  (device selection side effects)

    import paddle_tpu as paddle
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.inference.router import ServingFleet
    from paddle_tpu.models import GPTConfig, GPTForPretraining

    def bucket(n, lo):
        b = lo
        while b < n:
            b *= 2
        return b

    p_lo, p_hi = P["p_range"]
    n_lo, n_hi = P["n_range"]
    chunk = int(P["chunk"])
    max_seq = bucket(p_hi, p_lo) + bucket(n_hi, n_lo)
    # modest vocab ON PURPOSE: one replica's decode matmuls should fit
    # one proxy core the way one real replica fits one chip
    cfg = GPTConfig(vocab_size=P["vocab"], hidden_size=P["hidden"],
                    num_hidden_layers=P["layers"],
                    num_attention_heads=P["heads"],
                    max_position_embeddings=max_seq)
    paddle.seed(0)
    net = GPTForPretraining(cfg)
    net.eval()
    rng = np.random.RandomState(P["seed"])
    n_requests = int(P["n_requests"])
    plens = np.clip(
        rng.poisson(lam=rng.choice(P["p_lams"], size=n_requests)),
        p_lo, p_hi).astype(int)
    budgets = np.clip(
        rng.poisson(lam=rng.choice(P["n_lams"], size=n_requests)),
        n_lo, n_hi).astype(int)
    spl = int(P["sys_prompt_len"])
    sys_prompt = rng.randint(0, cfg.vocab_size, (spl,)).astype("int32")
    prompts = []
    for i, n in enumerate(plens):
        body = rng.randint(0, cfg.vocab_size, (int(n),)).astype("int32")
        if i % 2 == 0 and n > spl:
            body[:spl] = sys_prompt            # shared-prefix half
        prompts.append(body)

    def warm(eng):
        # compile every prefill bucket + the decode chunk once (the
        # timed pass then measures scheduling, not tracing)
        for b in eng.buckets:
            budget = min(chunk + 2, eng.MAX - b)
            if b <= p_hi * 2 and budget >= 1:
                eng.submit(np.ones((b,), np.int32), budget)
        eng.run()
        eng.reset()

    dtype = P.get("dtype", "float32")
    ekw = {"dtype": dtype}
    paged = P.get("paged") or {}
    if paged:
        ekw.update(kv_mode="paged", page_size=int(paged["page_size"]),
                   prefill_buckets=tuple(int(b)
                                         for b in paged["prefill_buckets"]))
        if paged.get("num_pages"):
            ekw["num_pages"] = int(paged["num_pages"])
    roles = P.get("roles")
    if n_replicas == 1:
        fe = ServingEngine(net, num_slots=P["slots"], chunk=chunk,
                           max_seq_len=max_seq, **ekw)
        warm(fe)
        reset = fe.reset
        run_trace = fe.run
        submit = fe.submit
    else:
        fl = ServingFleet(net, num_replicas=n_replicas,
                          num_slots=P["slots"], chunk=chunk,
                          max_seq_len=max_seq,
                          roles=tuple(roles) if roles else None,
                          handoff_ttl_s=float(P.get("handoff_ttl_s", 60.0)),
                          **ekw)
        for rep in fl.replicas:
            warm(rep.engine)
        if roles:
            # the per-engine warm bypassed the router: run a few real
            # requests through the fleet so the handoff path (budget-1
            # stub prefill + arm-at-k) is compiled before the clock
            for b in fl.replicas[0].engine.buckets:
                if b <= p_hi * 2:
                    fl.submit(np.ones((min(int(b), max_seq - n_lo),),
                                      np.int32), 2)
            fl.run(threads=True)
            fl.reset()
        reset = fl.reset
        run_trace = lambda: fl.run(threads=True)   # noqa: E731
        submit = fl.submit
    # best of `trials` timed passes (compiles amortized after warm):
    # the fleet walls are thread-scheduling-sensitive on the shared
    # cpu proxy, and the min is the capability estimate (the
    # chip_calibration discipline); outputs are asserted identical
    # across trials — noise may move the clock, never the tokens
    best = None
    for _ in range(int(P.get("trials", 2))):
        reset()
        try:
            # per-trial telemetry reset so the committed snapshot is
            # one-run-shaped (the last trial's), not a 2x aggregate
            from paddle_tpu import observability as _obs
            from paddle_tpu.framework import guardian as _guardian
            from paddle_tpu.observability import tracing as _tracing
            _obs.get_registry().reset()
            _tracing.reset()
            _guardian.clear_events()
        except Exception:
            pass
        t0 = time.perf_counter()
        reqs = [submit(p, int(b)) for p, b in zip(prompts, budgets)]
        run_trace()
        wall = time.perf_counter() - t0
        toks = [list(map(int, r.tokens)) for r in reqs]
        if best is not None:
            assert toks == best["toks"], "trial outputs diverged"
        if best is None or wall < best["wall"]:
            ttfts = sorted(r.ttft_ms for r in reqs)
            best = {"toks": toks, "wall": wall,
                    "ttfts": [round(r.ttft_ms, 2) for r in reqs],
                    "p99": ttfts[min(int(0.99 * (len(ttfts) - 1)),
                                     len(ttfts) - 1)]}
    if n_replicas == 1:
        extra = {"chunks": fe.stats["chunks"],
                 "prefills": fe.stats["prefills"]}
    else:
        extra = {"affinity_routes": fl.stats["affinity_routes"],
                 "least_loaded_routes":
                     fl.stats["least_loaded_routes"],
                 "rebalanced": fl.stats["rebalanced"],
                 "chunks": sum(r.engine.stats["chunks"]
                               for r in fl.replicas),
                 "prefills": sum(r.engine.stats["prefills"]
                                 for r in fl.replicas)}
        if roles:
            from paddle_tpu.framework import guardian
            hs = fl._handoff.snapshot()
            transfer_ms = sorted(
                e["transfer_ms"]
                for e in guardian.events("handoff_transfer"))
            extra.update(
                prefills_by_role={
                    r.role: r.engine.stats["prefills"]
                    for r in fl.replicas},
                handoff_transfers=hs["transfers"],
                handoff_fallbacks=hs["fallbacks"],
                mean_transfer_ms=round(
                    sum(transfer_ms) / len(transfer_ms), 2)
                if transfer_ms else None,
                p99_transfer_ms=round(
                    transfer_ms[min(int(0.99 * (len(transfer_ms) - 1)),
                                    len(transfer_ms) - 1)], 2)
                if transfer_ms else None)
            # the recompute-saved side of the TTFT attribution: what a
            # fallback would pay — one median prompt re-prefilled on the
            # (already-compiled) decode replica, timed directly
            dec = next(r.engine for r in fl.replicas
                       if r.role == "decode")
            probe = prompts[int(np.argsort(plens)[len(plens) // 2])]
            t0 = time.perf_counter()
            dec.submit(probe, 1)
            dec.run()
            extra["reprefill_probe_ms"] = round(
                (time.perf_counter() - t0) * 1e3, 2)
            dec.reset()
    useful = int(budgets.sum())
    out = {"tokens": best["toks"],
           "useful_tokens": useful,
           "useful_tokens_per_sec": round(useful / best["wall"], 1),
           "p99_ttft_ms": round(best["p99"], 1),
           "ttfts_ms": best["ttfts"], **extra}
    if snapshot:
        out["telemetry"] = _telemetry_snapshot(
            P.get("snapshot_tag", "router"))
    return out


def _fleet_child_main():
    """Child-process entry (``BENCH_FLEET_CHILD`` env): run one config
    in a fresh process (its own XLA pool + metrics registry — the
    telemetry snapshot a fleet child writes is that run's alone) and
    print one tagged JSON line.

    CPU affinity is set PROPORTIONALLY before jax initializes:
    ``cores_per_replica * n_replicas`` cores — every replica is backed
    by the same slice of hardware whatever the config, exactly like a
    real replica owning a chip.  Without it, XLA:CPU's machine-wide
    intra-op pool lets the "one-chip" baseline borrow every core
    during prefill matmuls (measured: 202-291 tok/s run-to-run on one
    machine), which both understates fleet scaling and makes the
    ratio noisy.  The trace runs fp32 ON PURPOSE: different affinity
    masks change XLA:CPU reduction partitioning, and at bf16 that
    flipped a near-tie greedy pick (one token in 5.5k) between masks —
    at fp32 the cross-config output is bitwise (asserted by the
    parent)."""
    spec = json.loads(os.environ[_FLEET_CHILD_ENV])
    n = int(spec["n_replicas"])
    cpr = int(spec.get("cores_per_replica") or 0)
    pinned = False
    if cpr > 0 and hasattr(os, "sched_setaffinity"):
        try:
            os.sched_setaffinity(
                0, set(range(min(cpr * n, os.cpu_count() or 1))))
            pinned = True
        except OSError:
            pass
    out = _fleet_run_config(spec["params"], n,
                            snapshot=spec.get("snapshot", False))
    out["pinned"] = pinned
    from paddle_tpu.device import chip
    out["device"] = chip.describe()
    print("FLEET_CHILD_RESULT:" + json.dumps(out))


def bench_serving_fleet(n_requests=64, seed=0, hidden=256, layers=6,
                        heads=8, vocab=8192, p_range=(32, 224),
                        n_range=(32, 160), slots=4, chunk=64,
                        p_lams=(48, 96, 192), n_lams=(48, 96, 128),
                        replica_counts=(2, 4), sys_prompt_len=64):
    """Single engine (the baseline fleet-of-one) vs ``ServingFleet`` at
    each ``replica_counts`` entry, all over one Poisson-mixed trace
    submitted as a burst (every request queued at t=0 -- the regime
    where a deeper fleet drains the queue Nx faster, which is exactly
    what p99 TTFT measures).  Half the requests share a
    ``sys_prompt_len``-token system prompt so prefix-affinity routing
    has something to route on (dense engines here -- warmth effects are
    covered by the paged fleet tests; this config measures *scaling*).
    Each config runs in its own pinned subprocess (see the banner
    comment); useful-tok/s counts each request's own budget."""
    import sys

    P = {"n_requests": n_requests, "seed": seed, "hidden": hidden,
         "layers": layers, "heads": heads, "vocab": vocab,
         "p_range": list(p_range), "n_range": list(n_range),
         "slots": slots, "chunk": chunk, "p_lams": list(p_lams),
         "n_lams": list(n_lams), "sys_prompt_len": sys_prompt_len}
    counts = [1] + [int(n) for n in replica_counts]
    # the even-division anchor: one replica-chip = ncpu / max-replicas
    # cores, for EVERY config (hardware scales with replica count the
    # way chips do in a real fleet)
    cores_per_replica = max(1, (os.cpu_count() or 1) // max(counts))
    results, base, telemetry, pinned = {}, None, None, True
    for n in counts:
        spec = {"n_replicas": n, "params": P,
                "cores_per_replica": cores_per_replica,
                "snapshot": n == max(counts)}
        env = dict(os.environ)
        env[_FLEET_CHILD_ENV] = json.dumps(spec)
        proc = _run_child([sys.executable, os.path.abspath(__file__)],
                          env, 1800)
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("FLEET_CHILD_RESULT:")]
        if proc.returncode != 0 or not line:
            raise RuntimeError(
                f"fleet child N={n} failed (rc={proc.returncode}): "
                f"{(proc.stderr or proc.stdout)[-400:]}")
        r = json.loads(line[-1][len("FLEET_CHILD_RESULT:"):])
        toks = r.pop("tokens")
        pinned &= bool(r.pop("pinned"))
        telemetry = r.pop("telemetry", telemetry)
        if n == 1:
            base = {"toks": toks,
                    "tps": r["useful_tokens_per_sec"],
                    "p99": r["p99_ttft_ms"],
                    "useful": r["useful_tokens"]}
        else:
            # the parity contract IS the product: bitwise or bust,
            # whatever replica/slot a request landed on
            assert toks == base["toks"], f"fleet N={n} output diverged"
            r["speedup_vs_one"] = round(
                r["useful_tokens_per_sec"] / max(base["tps"], 1e-9), 3)
            r["p99_ttft_vs_one"] = round(
                r["p99_ttft_ms"] / max(base["p99"], 1e-9), 3)
        r.pop("useful_tokens", None)
        r.pop("ttfts_ms", None)       # per-request detail: pd_split's
        results[str(n)] = r
    scaling_ok = all(results[str(n)]["speedup_vs_one"] >= 0.75 * n
                     for n in counts[1:])
    p99_ok = all(results[str(n)]["p99_ttft_ms"] < base["p99"]
                 for n in counts[1:])
    lat_ms = _dispatch_latency_ms()
    out = {"replicas": results,
           "speedup_n2": results.get("2", {}).get("speedup_vs_one"),
           "speedup_n4": results.get("4", {}).get("speedup_vs_one"),
           "bitwise": True,                 # asserted above, per fleet
           "scaling_near_linear": bool(scaling_ok),
           "p99_ttft_strictly_lower": bool(p99_ok),
           "requests": n_requests, "useful_tokens": base["useful"],
           "slots_per_replica": slots, "chunk": chunk,
           "dispatch_latency_ms": lat_ms,
           "cores_per_replica": cores_per_replica,
           "cpu_proxy_affinity": bool(pinned),
           "valid": bool(scaling_ok and p99_ok),
           "model": f"gpt_h{hidden}_l{layers}", "dtype": "float32",
           "note": ("burst-submitted Poisson trace, one subprocess "
                    "per config with PROPORTIONAL affinity (one "
                    "replica-chip = ncpu/max-replicas cores, set "
                    "before jax init — hardware scales with replica "
                    "count the way chips do; fp32 keeps cross-mask "
                    "greedy picks bitwise): replicas multiply the "
                    "slot pool and overlap dispatches; idle replicas "
                    "steal queued work from deep ones (router "
                    "rebalance), flattening the variable-budget "
                    "straggler tail.  Shared-host caveat: a replica "
                    "can transiently borrow sibling replicas' idle "
                    "cores through the child's one XLA pool, which "
                    "can push measured scaling slightly SUPER-linear "
                    "-- real chips cannot; read >=N as ~N")}
    if telemetry is not None:
        out["telemetry"] = telemetry
    if not out["valid"]:
        out["invalid_reason"] = (
            "fleet scaling below 0.75x-per-replica or p99 TTFT not "
            "strictly lower than the single engine -- the ratio is "
            "reported but should not be read as the fleet win")
    return out


def bench_prefill_decode_split(n_requests=32, seed=0, hidden=256,
                               layers=6, heads=8, vocab=8192,
                               p_range=(16, 96), n_range=(16, 64),
                               slots=4, chunk=16, page_size=16,
                               p_lams=(24, 48, 80), n_lams=(24, 48),
                               sys_prompt_len=16):
    """Disaggregated prefill/decode fleet (``roles=("prefill",
    "decode")``) vs the SAME 2-replica paged fleet unified, over one
    Poisson burst — both in pinned subprocesses like serving_fleet.
    The contract under measurement: every prompt prefills on the
    prefill replica only (``prefills_by_role["decode"] == 0``), its KV
    crosses as a checksummed bundle, and the output is BITWISE equal
    to the unified fleet.  TTFT attribution splits what the handoff
    costs (measured per-transfer wall, the `handoff_transfer` guardian
    events) from what it saves the decode replica (one median prompt
    re-prefilled there directly, the fallback price)."""
    import sys

    def bucket(n, lo):
        b = lo
        while b < n:
            b *= 2
        return b

    buckets = []
    b = p_range[0]
    while b < bucket(p_range[1], p_range[0]) * 2:
        buckets.append(b)
        b *= 2
    # decode pool sized for the WHOLE admitted burst: every launched
    # handoff holds its page reservation until its decode slot frees,
    # and decode drains far slower than prefill — an undersized pool
    # turns the burst into reserve_timeout fallbacks (that ladder is
    # chaos-tested; this config measures the happy path)
    num_pages = n_requests * ((p_range[1] + n_range[1]) // page_size
                              + 2) + 1
    P = {"n_requests": n_requests, "seed": seed, "hidden": hidden,
         "layers": layers, "heads": heads, "vocab": vocab,
         "p_range": list(p_range), "n_range": list(n_range),
         "slots": slots, "chunk": chunk, "p_lams": list(p_lams),
         "n_lams": list(n_lams), "sys_prompt_len": sys_prompt_len,
         "paged": {"page_size": page_size, "prefill_buckets": buckets,
                   "num_pages": num_pages},
         "snapshot_tag": "pd_split"}
    cores_per_replica = max(1, (os.cpu_count() or 1) // 2)
    results, telemetry = {}, None
    for name, roles in (("unified", None),
                        ("split", ["prefill", "decode"])):
        spec = {"n_replicas": 2,
                "params": {**P, "roles": roles},
                "cores_per_replica": cores_per_replica,
                "snapshot": roles is not None}
        env = dict(os.environ)
        env[_FLEET_CHILD_ENV] = json.dumps(spec)
        proc = _run_child([sys.executable, os.path.abspath(__file__)],
                          env, 1800)
        line = [l for l in proc.stdout.splitlines()
                if l.startswith("FLEET_CHILD_RESULT:")]
        if proc.returncode != 0 or not line:
            raise RuntimeError(
                f"pd_split child {name} failed (rc={proc.returncode}): "
                f"{(proc.stderr or proc.stdout)[-400:]}")
        r = json.loads(line[-1][len("FLEET_CHILD_RESULT:"):])
        r.pop("pinned", None)
        telemetry = r.pop("telemetry", telemetry)
        results[name] = r
    uni, spl = results["unified"], results["split"]
    # the parity contract IS the product: same tokens whether the KV
    # was computed in place or crossed replicas as a bundle
    bitwise = uni.pop("tokens") == spl.pop("tokens")
    uni_ttfts = uni.pop("ttfts_ms")
    spl_ttfts = spl.pop("ttfts_ms")
    mean = lambda xs: round(sum(xs) / len(xs), 2)   # noqa: E731
    attribution = {
        "mean_ttft_unified_ms": mean(uni_ttfts),
        "mean_ttft_split_ms": mean(spl_ttfts),
        "mean_transfer_ms": spl.get("mean_transfer_ms"),
        "p99_transfer_ms": spl.get("p99_transfer_ms"),
        "transfer_share_of_ttft": round(
            spl["mean_transfer_ms"] / max(mean(spl_ttfts), 1e-9), 3)
        if spl.get("mean_transfer_ms") else None,
        "reprefill_saved_ms": spl.get("reprefill_probe_ms"),
    }
    decode_prefills = spl.get("prefills_by_role", {}).get("decode")
    valid = bool(bitwise and decode_prefills == 0
                 and spl.get("handoff_fallbacks") == 0
                 and spl.get("handoff_transfers") == n_requests)
    out = {"unified": uni, "split": spl, "bitwise": bitwise,
           "decode_prompt_prefills": decode_prefills,
           "ttft_attribution": attribution,
           "requests": n_requests, "slots_per_replica": slots,
           "chunk": chunk, "page_size": page_size,
           "cores_per_replica": cores_per_replica,
           "valid": valid,
           "model": f"gpt_h{hidden}_l{layers}", "dtype": "float32",
           "note": ("same Poisson burst through a unified 2-replica "
                    "paged fleet and the same fleet split "
                    "prefill/decode; both pinned like serving_fleet.  "
                    "The split config serializes all prompt prefills "
                    "on ONE replica, so burst p99 TTFT is expected to "
                    "trail the unified fleet on this proxy -- the win "
                    "disaggregation buys (decode batches never stall "
                    "behind a prompt prefill) shows as the decode "
                    "replica's zero prompt prefills and in the "
                    "attribution: a bundle import costs "
                    "mean_transfer_ms where the fallback "
                    "(re-prefill on the decode replica) costs "
                    "reprefill_saved_ms")}
    if telemetry is not None:
        out["telemetry"] = telemetry
    if not valid:
        out["invalid_reason"] = (
            "expected bitwise output, zero decode prompt prefills, "
            "zero fallbacks and one transfer per request")
    return out


# ---------------------------------------------------------------------------
# ---------------------------------------------------------------------------
# GPT-MoE: GShard-pattern sparse FFNs (every other layer 8-expert top-2),
# single chip.  MFU is computed over ACTIVE FLOPs (top_k of E experts per
# token), the standard sparse-model accounting.
# ---------------------------------------------------------------------------

def bench_gpt_moe(peak, B=12, S=1024, iters=6):
    # B sweep (r5, scanned): 8 -> 76.2k tok/s (37.6%), 12 -> 77.8k
    # (38.5%), 16 -> 76.0k (37.5%); capacity-bucket padding waste peaks
    # at small B, HBM pressure at large
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.framework import autograd as _ag
    from paddle_tpu.framework.random import rng_scope
    from paddle_tpu.models import GPTMoEForPretraining, gpt_moe_small

    cfg = gpt_moe_small(vocab_size=50304)
    paddle.seed(0)
    net = GPTMoEForPretraining(cfg)
    net.eval()
    params = [p for _, p in net.named_parameters()]
    pvals = [p._value for p in params]
    moes = net.gpt.moe_layers()

    def loss_fn(pv, ids, labels):
        from paddle_tpu.ops.pallas.fused_xent import fused_softmax_xent
        olds = [p._value for p in params]
        for p, v in zip(params, pv):
            p._value = v.astype(jnp.bfloat16) \
                if jnp.issubdtype(v.dtype, jnp.floating) else v
        try:
            with _ag.suspend_tape(), rng_scope(jax.random.key(0)):
                logits = net(paddle.Tensor(ids))._value
                aux = net.aux_loss()._value
        finally:
            for p, v in zip(params, olds):
                p._value = v
        Bv, Sv, V = logits.shape
        lb = jnp.concatenate([labels[:, 1:],
                              jnp.full((Bv, 1), -1, labels.dtype)], 1)
        row = fused_softmax_xent(logits.reshape(Bv * Sv, V),
                                 lb.reshape(-1).astype(jnp.int32))
        ce = jnp.sum(row) / (Bv * (Sv - 1))
        return ce + cfg.aux_loss_weight * aux.astype(jnp.float32)

    b1, b2, eps, lr, wd = 0.9, 0.95, 1e-8, 1e-4, 0.01

    def step(pv, m, v, t, ids, labels):
        loss, g = jax.value_and_grad(loss_fn)(pv, ids, labels)
        t = t + 1
        new_p, new_m, new_v = [], [], []
        for p, gi, mi, vi in zip(pv, g, m, v):
            nmi = b1 * mi + (1 - b1) * gi
            nvi = b2 * vi + (1 - b2) * gi * gi
            np_ = p - lr * ((nmi / (1 - b1 ** t)) /
                            (jnp.sqrt(nvi / (1 - b2 ** t)) + eps) + wd * p)
            new_p.append(np_)
            new_m.append(nmi)
            new_v.append(nvi)
        return loss, new_p, new_m, new_v, t

    K = int(os.environ.get("BENCH_STEPS_PER_CALL", "5"))

    def scan_steps(pv, m, v, t, ids, labels):
        def body(carry, _):
            pv, m, v, t = carry
            loss, pv, m, v, t = step(pv, m, v, t, ids, labels)
            return (pv, m, v, t), loss
        (pv, m, v, t), losses = jax.lax.scan(
            body, (pv, m, v, t), None, length=K)
        return losses[-1], pv, m, v, t

    step_jit = jax.jit(scan_steps, donate_argnums=(0, 1, 2))
    m0 = [jnp.zeros_like(v) for v in pvals]
    v0 = [jnp.zeros_like(v) for v in pvals]
    t0 = jnp.zeros((), jnp.int32)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size,
                                  (B, S)).astype("int32"))

    def run(pv, m, v, t):
        loss, pv, m, v, t = step_jit(pv, m, v, t, ids, ids)
        return loss, pv, m, v, t

    loss, pvals, m0, v0, t0 = run(pvals, m0, v0, t0)
    _readback_sync(loss)
    dt, final_loss, _ = _timeit(run, iters, pvals, m0, v0, t0)
    tokens_per_sec = iters * K * B * S / dt

    n_params = sum(int(np.prod(p.shape)) for p in params)
    expert_params = sum(
        int(np.prod(getattr(m, nm).shape))
        for m in moes for nm in ("expert_w1", "expert_b1",
                                 "expert_w2", "expert_b2"))
    active = n_params - expert_params * (1 - cfg.top_k / cfg.num_experts)
    fpt = 6 * active + 6 * cfg.num_hidden_layers * S * cfg.hidden_size
    return {"tokens_per_sec": round(tokens_per_sec, 1),
            "active_mfu": round(tokens_per_sec * fpt / peak, 4),
            "loss": round(final_loss, 4), "params": n_params,
            "active_params": int(active),
            "num_experts": cfg.num_experts, "top_k": cfg.top_k,
            "moe_layers": len(moes), "batch": B, "seq": S}


# nominal roofs for the labeled CPU-proxy branch ONLY (explicit
# JAX_PLATFORMS=cpu): a CPU has no row in the peaks table, its "mfu" is a
# proxy-scale figure and every result it prints carries platform "cpu".
# On a TPU the roofs come from device/chip.py by device_kind.
_CPU_PROXY_PEAK_FLOPS = 1e12
_CPU_PROXY_HBM_BW = 50e9

# configs that measure in child processes (one per replica count / role
# split): a chip belongs to one process, so on a TPU they are refused
_CHILD_CONFIGS = ("serving_fleet", "prefill_decode_split")


def main():
    import sys

    from paddle_tpu.device import chip
    from paddle_tpu.models import GPTConfig

    cache_dir = chip.enable_compile_cache()
    device = chip.describe()
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and os.environ.get("JAX_PLATFORMS") != "cpu":
        raise SystemExit(
            f"bench.py: JAX found no TPU (devices: {device}).  The "
            "CPU-proxy branch runs only when JAX_PLATFORMS=cpu is set "
            "explicitly; a measurement run never falls back to it.")
    if on_tpu:
        roofs = chip.peaks(device["kind"])     # unknown kind raises
        peak, hbm_bw = roofs.bf16_flops, roofs.hbm_bytes_per_s
    else:
        peak, hbm_bw = _CPU_PROXY_PEAK_FLOPS, _CPU_PROXY_HBM_BW
    which = os.environ.get("BENCH_CONFIGS", "").split(",") \
        if os.environ.get("BENCH_CONFIGS") else None
    # extras stop launching once the budget is spent so the primary JSON
    # line always lands inside the driver's window
    budget_s = float(os.environ.get("BENCH_TIME_BUDGET_S", "1500"))
    start = time.perf_counter()

    def want(name, result_key=None):
        named = (which is None or name in which
                 or (result_key is not None and result_key in which))
        if not named:
            return False
        if name != "gpt125m" and time.perf_counter() - start > budget_s:
            configs[result_key or name] = {
                "skipped": "BENCH_TIME_BUDGET_S exhausted"}
            return False
        return True

    configs = {}
    telemetry = {}
    primary = None
    kernel_measured = {}
    metric = "gpt125m_train_tokens_per_sec_per_chip"
    if on_tpu:
        for name in _CHILD_CONFIGS:
            configs[name] = {
                "refused": "measures in child processes; a chip belongs "
                           "to one process, so this config does not run "
                           "on a TPU"}
        try:
            # chip-health reference: bare-matmul fraction of peak (see
            # chip_calibration docstring; every MFU below scales with
            # this number)
            configs["chip_calibration"] = chip_calibration()
        except Exception as e:
            configs["chip_calibration"] = {"error": repr(e)[:200]}
        gpt125 = GPTConfig(vocab_size=50304, hidden_size=768,
                           num_hidden_layers=12, num_attention_heads=12,
                           max_position_embeddings=1024)
        # B=24: best measured single-chip throughput with the fused-CE
        # loss (B=16: 39%, B=24: 42.3%, B=28: 40.2%, B=32: 38.7% —
        # larger batches start spilling on the bf16 logits + bwd)
        if want("gpt125m"):
            primary = bench_gpt(gpt125, B=24, S=1024, iters=20, peak=peak)
            telemetry["train"] = _telemetry_snapshot("train")
        if want("gpt350m"):
            try:
                gpt350 = GPTConfig(
                    vocab_size=50304, hidden_size=1024,
                    num_hidden_layers=24, num_attention_heads=16,
                    max_position_embeddings=1024)
                configs["gpt350m"] = bench_gpt(gpt350, B=8, S=1024,
                                               iters=10, peak=peak)
            except Exception as e:
                configs["gpt350m"] = {"error": repr(e)[:200]}
        if want("resnet50"):
            try:
                configs["resnet50"] = bench_resnet50(B=256, iters=10)
            except Exception as e:
                configs["resnet50"] = {"error": repr(e)[:200]}
        if want("bert", "bert_base_amp"):
            try:
                # B sweep (r3): 16→36.0%, 32→37.9%, 48→41.2%, 64→38.2%
                # (the MLM logits block tops out VMEM-friendly at 48);
                # r4 scanned re-check: 48→43.4%, 64→40.6%, 96→38.0%.
                #
                # Why BERT sits at ~43% (latency-free r4 analysis, the
                # VERDICT #2 "residual is physics" note): the bidir
                # flash kernels are VPU-transcendental-bound, not
                # schedule-bound — EVERY (hb, bq, bk) config measures
                # fwd 2.5-2.8ms / bwd 2.9-3.4ms per layer on 50-call
                # latency-free chains (7-17% of MXU peak; attention is
                # 8% of credited FLOPs but ~30% of wall). The XLA
                # dense path is 1.27-1.37x SLOWER at this shape, so
                # flash is the right call. Ablations: stubbing
                # attention or the MLM head moves the step <5% each;
                # the non-attention remainder runs at ~85% matmul
                # efficiency. A microbench-winning config (256,512,
                # hb=8) collapsed the FULL model to 11% MFU (VMEM
                # pressure beside live model buffers) — kernel tables
                # must be validated at model level.
                configs["bert_base_amp"] = bench_bert(B=48, S=512,
                                                      iters=10, peak=peak)
            except Exception as e:
                configs["bert_base_amp"] = {"error": repr(e)[:200]}
        if want("longctx", "gpt125m_s4096"):
            try:
                gptlc = GPTConfig(
                    vocab_size=50304, hidden_size=768,
                    num_hidden_layers=12, num_attention_heads=12,
                    max_position_embeddings=4096)
                # r4 scanned-bench B sweep: B=6 45.4%, 4 46.0%, 3 46.1%,
                # 2 46.7%, 1 43.4% — smaller per-step HBM live set wins
                # until B=1 under-fills the MXU.
                #
                # Why ~47% is the ceiling at S=4096 (r5 physics note,
                # VERDICT r4 #3; latency-subtracted tensor-carry chains,
                # tools/s4096_analysis.py — beware: scalar-carry chains
                # get their matmul hoisted by XLA's c*(A@B) rewrite and
                # read >100% of peak):
                #   step = 87.6 ms (B=2, 8192 tok, 46.9% MFU).  Budget:
                #   - flash attention f+b: 3.12 ms/layer x 12 = 37.4 ms
                #     = 43% of wall at 29% of MXU peak, carrying only
                #     23% of credited FLOPs.  fwd alone 1.05 ms (25%).
                #     Same class as the BERT note: VPU/exp-bound, not
                #     schedule-bound — the (bq, bk) landscape re-swept
                #     at S=4096 is flat (512/1024/2048 combos: 46.3,
                #     46.9, 46.9, 46.9%), dense attention is 11x slower
                #     (11.6 ms fwd), and remat is off so fwd is paid
                #     once.
                #   - lm head + fused xent f+b: 11.4 ms at 84% of peak
                #     (50304-wide streaming, near its HBM roofline).
                #   - proj+MLP matmuls reach 95% of peak in isolation;
                #     the remaining 38.8 ms of layer-remainder (norms,
                #     residual/cast traffic, AdamW's ~4 ms HBM sweep of
                #     124M fp32 m/v/p) averages 55%.
                #   With attention pinned at its measured floor and
                #   every other component at its best measured
                #   efficiency, the step bottoms at ~75 ms = ~53% MFU;
                #   the 47->53 gap is the remainder's backward (55% vs
                #   95% isolated), the same VPU-bound fused-norm + cast
                #   overheads quantified in the BERT note below.  48%+
                #   needs a faster flash-bwd class (e.g. fusing the
                #   exp recompute differently), not block tuning.
                configs["gpt125m_s4096"] = bench_gpt(gptlc, B=2, S=4096,
                                                     iters=10, peak=peak)
            except Exception as e:
                configs["gpt125m_s4096"] = {"error": repr(e)[:200]}
        if want("longctx_remat", "gpt125m_s4096_remat"):
            try:
                # selective remat (dots_saveable keeps matmul outputs,
                # recomputes norms/elementwise) frees activation HBM so
                # the batch can grow past the B=2 operating point the
                # no-remat sweep topped out at (0.468 MFU) — report the
                # MFU delta against the plain config alongside
                gptlcr = GPTConfig(
                    vocab_size=50304, hidden_size=768,
                    num_hidden_layers=12, num_attention_heads=12,
                    max_position_embeddings=4096,
                    remat_policy="dots_saveable")
                r = bench_gpt(gptlcr, B=8, S=4096, iters=10, peak=peak)
                base = configs.get("gpt125m_s4096") or {}
                if isinstance(base, dict) and base.get("mfu"):
                    r["mfu_delta_vs_no_remat"] = round(
                        r["mfu"] - base["mfu"], 4)
                r["remat_policy"] = "dots_saveable"
                configs["gpt125m_s4096_remat"] = r
            except Exception as e:
                configs["gpt125m_s4096_remat"] = {"error": repr(e)[:200]}
        if want("longctx_sweep", "gpt125m_s4096_sweep"):
            try:
                configs["gpt125m_s4096_sweep"] = bench_longctx_sweep(
                    peak, on_tpu=True)
            except Exception as e:
                configs["gpt125m_s4096_sweep"] = {"error": repr(e)[:200]}
        if want("kernels", "kernel_probe"):
            try:
                kp = bench_kernel_probe(on_tpu=True)
                kernel_measured.update(kp.pop("measured", {}))
                configs["kernel_probe"] = kp
            except Exception as e:
                configs["kernel_probe"] = {"error": repr(e)[:200]}
        if want("gpt1p3b", "gpt1p3b_hybrid"):
            try:
                configs["gpt1p3b_hybrid"] = bench_gpt1p3b_hybrid(peak=peak)
            except Exception as e:
                configs["gpt1p3b_hybrid"] = {"error": repr(e)[:200]}
        if want("eager", "eager_overhead"):
            try:
                configs["eager_overhead"] = bench_eager_overhead()
            except Exception as e:
                configs["eager_overhead"] = {"error": repr(e)[:200]}
        if want("fp8", "fp8_linear"):
            try:
                configs["fp8_linear"] = bench_fp8_linear()
            except Exception as e:
                configs["fp8_linear"] = {"error": repr(e)[:200]}
        # decode before gpt_moe: in a full run gpt_moe ends near the time
        # budget and whatever follows it risks a budget skip
        if want("decode"):
            try:
                configs["decode"] = bench_decode()
            except Exception as e:
                configs["decode"] = {"error": repr(e)[:200]}
        if want("serving"):
            try:
                configs["serving"] = bench_serving()
            except Exception as e:
                configs["serving"] = {"error": repr(e)[:200]}
            telemetry["serving"] = _telemetry_snapshot("serving")
        if want("serving_prefix"):
            try:
                configs["serving_prefix"] = bench_serving_prefix()
            except Exception as e:
                configs["serving_prefix"] = {"error": repr(e)[:200]}
            telemetry["serving_prefix"] = _telemetry_snapshot("serving_prefix")
        if want("serving_spec"):
            try:
                configs["serving_spec"] = bench_serving_spec()
            except Exception as e:
                configs["serving_spec"] = {"error": repr(e)[:200]}
            telemetry["serving_spec"] = _telemetry_snapshot("serving_spec")
        if want("serving_quant"):
            try:
                configs["serving_quant"] = bench_serving_quant()
            except Exception as e:
                configs["serving_quant"] = {"error": repr(e)[:200]}
            telemetry["serving_quant"] = _telemetry_snapshot("serving_quant")
        if want("fp8_train"):
            try:
                configs["fp8_train"] = bench_fp8_train(peak=peak)
            except Exception as e:
                configs["fp8_train"] = {"error": repr(e)[:200]}
            telemetry["fp8_train"] = _telemetry_snapshot("fp8_train")
        if want("moe", "gpt_moe"):
            try:
                configs["gpt_moe"] = bench_gpt_moe(peak=peak)
            except Exception as e:
                configs["gpt_moe"] = {"error": repr(e)[:200]}
    else:
        tiny = GPTConfig(vocab_size=1024, hidden_size=128,
                         num_hidden_layers=2, num_attention_heads=4,
                         max_position_embeddings=256)
        primary = bench_gpt(tiny, B=2, S=128, iters=5, peak=peak)
        telemetry["train"] = _telemetry_snapshot("train")
        metric = "gpt_tiny_cpu_proxy_tokens_per_sec"
        if which is not None and "serving" in which:
            try:
                configs["serving"] = bench_serving(
                    n_requests=8, hidden=64, layers=2, heads=2,
                    p_range=(8, 32), n_range=(4, 16), slots=4, chunk=8,
                    p_lams=(12, 24), n_lams=(6, 12))
            except Exception as e:
                configs["serving"] = {"error": repr(e)[:200]}
            telemetry["serving"] = _telemetry_snapshot("serving")
        if which is not None and "serving_prefix" in which:
            try:
                configs["serving_prefix"] = bench_serving_prefix(
                    n_requests=8, hidden=64, layers=2, heads=2,
                    sys_len=32, sfx_range=(4, 12), n_range=(4, 12),
                    slots=4, chunk=8, page_size=8)
            except Exception as e:
                configs["serving_prefix"] = {"error": repr(e)[:200]}
            telemetry["serving_prefix"] = _telemetry_snapshot("serving_prefix")
        if which is not None and "serving_spec" in which:
            try:
                # decode-heavy trace on a weight-stream-bound proxy
                # (h=128 with the 50304-wide head): a gamma+1-wide
                # verify costs near one narrow step, the same fixed-
                # cost-amortization physics as the TPU dispatch story
                # (measured 2.0x dense / 2.0x paged at 0.59 acceptance)
                configs["serving_spec"] = bench_serving_spec(
                    n_requests=12, hidden=128, layers=2, heads=2,
                    p_range=(8, 16), n_range=(48, 96), slots=4, chunk=8,
                    gamma=6, ngram=2, page_size=8,
                    p_lams=(8, 12), n_lams=(64, 80))
            except Exception as e:
                configs["serving_spec"] = {"error": repr(e)[:200]}
            telemetry["serving_spec"] = _telemetry_snapshot("serving_spec")
        if which is not None and "serving_quant" in which:
            try:
                # decode-heavy, weight-stream-bound proxy: h=512 puts
                # the 50304-wide fp32 head at 103MB — DRAM-resident, so
                # the tiled int8 lowering's 4x byte cut is a measured
                # win on the CPU backend too (1.4-1.6x at decode
                # M=slots); fp8's e4m3 upconvert is software-emulated
                # off-TPU, so its column reads ~1.0x here and the
                # deploy truth is the kernel_uplift_v5e cross-ref
                configs["serving_quant"] = bench_serving_quant(
                    n_requests=12, hidden=512, layers=2, heads=4,
                    p_range=(8, 16), n_range=(24, 48), slots=8, chunk=8,
                    dtype="float32", p_lams=(8, 12), n_lams=(28, 40))
            except Exception as e:
                configs["serving_quant"] = {"error": repr(e)[:200]}
            telemetry["serving_quant"] = _telemetry_snapshot("serving_quant")
        if which is not None and "fp8_train" in which:
            try:
                configs["fp8_train"] = bench_fp8_train(peak=peak)
            except Exception as e:
                configs["fp8_train"] = {"error": repr(e)[:200]}
            telemetry["fp8_train"] = _telemetry_snapshot("fp8_train")
        if which is not None and "serving_fleet" in which:
            try:
                configs["serving_fleet"] = bench_serving_fleet()
            except Exception as e:
                configs["serving_fleet"] = {"error": repr(e)[:200]}
            # the pinned N=max CHILD wrote the router telemetry
            # snapshot (its registry holds the fleet run, ours is
            # empty) — surface its paths instead of overwriting
            telemetry["router"] = configs["serving_fleet"].pop(
                "telemetry", {"skipped": "fleet child did not report"})
        if which is not None and "prefill_decode_split" in which:
            try:
                configs["prefill_decode_split"] = \
                    bench_prefill_decode_split()
            except Exception as e:
                configs["prefill_decode_split"] = {"error": repr(e)[:200]}
            telemetry["pd_split"] = configs["prefill_decode_split"].pop(
                "telemetry", {"skipped": "pd_split child did not report"})
        if which is not None and \
                {"longctx_sweep", "gpt125m_s4096_sweep"} & set(which):
            try:
                configs["gpt125m_s4096_sweep"] = bench_longctx_sweep(
                    peak, on_tpu=False)
            except Exception as e:
                configs["gpt125m_s4096_sweep"] = {"error": repr(e)[:200]}
        if which is not None and \
                {"kernels", "kernel_probe"} & set(which):
            try:
                kp = bench_kernel_probe(on_tpu=False)
                kernel_measured.update(kp.pop("measured", {}))
                configs["kernel_probe"] = kp
            except Exception as e:
                configs["kernel_probe"] = {"error": repr(e)[:200]}
        if which is not None and \
                {"gpt1p3b", "gpt1p3b_hybrid"} & set(which):
            # 1 visible device -> bench_gpt1p3b_hybrid re-execs itself
            # onto the simulated 8-device mesh (cpu_proxy result)
            try:
                configs["gpt1p3b_hybrid"] = bench_gpt1p3b_hybrid(peak=peak)
            except Exception as e:
                configs["gpt1p3b_hybrid"] = {"error": repr(e)[:200]}

    # roofline/MFU-attribution artifact: join every surface the run
    # compiled (train stepper + any serving engines) with the measured
    # per-dispatch latency.  Roofs: the device_kind's row of the peaks
    # table on a TPU; the CPU proxy gets nominal figures (the table
    # still shows analytical intensity + compute/memory split —
    # attribution fractions are proxy-scale there and labeled by the
    # peak used).
    measured = dict(kernel_measured)   # kernel_probe latency-clean rows
    if primary is not None and isinstance(primary, dict) and \
            primary.get("dispatch_ms"):
        measured["bench.train_step"] = primary["dispatch_ms"]
    telemetry["roofline"] = _roofline_snapshot(measured, peak, hbm_bw)
    telemetry["memory"] = _memory_snapshot()

    if primary is not None:
        rate = primary["tokens_per_sec"]
    else:
        # BENCH_CONFIGS excluded gpt125m: promote the first config that
        # produced a throughput number, labeled by its own name
        for name, cfg in configs.items():
            if not isinstance(cfg, dict):
                continue
            for key in ("tokens_per_sec", "images_per_sec",
                        "decode_tokens_per_sec"):
                if cfg.get(key):
                    metric = f"{name}_{key}"
                    rate = cfg[key]
                    primary = cfg
                    break
            if primary is not None:
                break
        else:
            raise SystemExit("no benchmark config produced a number: "
                             + json.dumps(configs))
    # every result names the device of the process that produced it
    # (child-measured configs were stamped by their child)
    for cfg in [primary] + list(configs.values()):
        cfg.setdefault("device", device)
    # a config whose result is an error — raised here or returned by a
    # helper — or that was asked for by name and refused fails the run
    failed = sorted(
        name for name, cfg in configs.items()
        if "error" in cfg or ("refused" in cfg and which is not None
                              and name in which))
    print(json.dumps({
        "metric": metric,
        "value": rate,
        "unit": "tokens/sec" if "tokens" in metric else "images/sec",
        "vs_baseline": 1.0,
        "device": device,
        "compile_cache_dir": cache_dir,
        "failed_configs": failed,
        "extra": {**primary, "configs": configs,
                  "telemetry": telemetry},
    }))
    if failed:
        sys.exit(f"bench.py: config(s) failed or were refused: {failed}")


if __name__ == "__main__":
    import sys

    if "--hybrid-cpu-proxy" in sys.argv[1:]:
        _hybrid_cpu_proxy_child()
    elif _FLEET_CHILD_ENV in os.environ:
        _fleet_child_main()
    else:
        main()
