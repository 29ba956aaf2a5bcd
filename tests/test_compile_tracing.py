"""Compile-layer + per-request observability (ISSUE 10 tentpole):
compile telemetry with the retrace sentinel, request-scoped serving
traces, the roofline join, and their satellite (idempotent telemetry
snapshots).

Acceptance anchors:
- the retrace sentinel fires (with an old-vs-new signature diff) on a
  deliberately shape-unstable surface and stays SILENT across a
  3-chunk serving run and a 3-step fit;
- cost_analysis FLOPs for a known matmul land within 2x of the
  hand-computed number;
- request-trace spans tile submit -> finish (sum == measured wall);
- the PR 5 zero-sync A/B extends to the new layers: device-transfer
  counts are identical with compile telemetry + tracing on vs off.
"""
import json

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import observability as obs
from paddle_tpu.observability import (compilestats, export, report,
                                      timeline, tracing)
from paddle_tpu.framework import guardian
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models import GPTForPretraining, gpt3_tiny

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _clean_telemetry():
    obs.enable(True)
    obs.get_registry().reset()
    compilestats.reset()
    tracing.reset()
    guardian.clear_events()
    yield
    obs.enable(True)
    obs.get_registry().reset()
    compilestats.reset()
    tracing.reset()
    guardian.clear_events()


@pytest.fixture(scope="module")
def gpt():
    paddle.seed(0)
    return GPTForPretraining(gpt3_tiny())


def _reg_model(seed=3):
    paddle.seed(seed)
    net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.Adam(learning_rate=1e-2,
                                        parameters=net.parameters()),
                  nn.MSELoss())
    return model


def _batches(n=3, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(8, 4).astype("float32"),
             rng.randn(8, 2).astype("float32")) for _ in range(n)]


def _run_engine(gpt, budgets=(3, 12, 4), chunk=4):
    rng = np.random.RandomState(5)
    eng = ServingEngine(gpt, num_slots=2, chunk=chunk,
                        prefill_buckets=(8,))
    reqs = [eng.submit(rng.randint(0, 1024, (6,)).astype("int32"), b)
            for b in budgets]
    return eng, reqs, eng.run()


# -- compile telemetry -----------------------------------------------------

class TestCompileStats:
    def test_cost_analysis_within_2x_of_hand_computed_matmul(self):
        M, K, N = 128, 256, 64
        f = compilestats.wrap(jax.jit(lambda a, b: a @ b), "t.mm")
        f(jnp.ones((M, K), jnp.float32), jnp.ones((K, N), jnp.float32))
        st = compilestats.snapshot()["t.mm"]
        hand = 2 * M * K * N
        assert hand / 2 <= st["flops"] <= hand * 2
        assert st["bytes_accessed"] > 0 and st["memory_bytes"] > 0
        assert st["compiles"] == 1 and st["retraces"] == 0
        reg = obs.get_registry()
        assert reg.get("pt_compile_compiles_total").value(
            surface="t.mm") == 1
        assert reg.get("pt_compile_wall_ms").count(surface="t.mm") == 1
        assert reg.get("pt_compile_flops").value(
            surface="t.mm") == st["flops"]

    def test_aot_path_bitwise_matches_plain_jit(self):
        fn = lambda a: jnp.sin(a) @ a.T * 3 + jnp.cos(a)  # noqa: E731
        x = jnp.asarray(np.random.RandomState(0).randn(16, 16),
                        jnp.float32)
        plain = jax.jit(fn)(x)
        wrapped = compilestats.wrap(jax.jit(fn), "t.bitwise")(x)
        assert np.asarray(plain).tobytes() == \
            np.asarray(wrapped).tobytes()

    def test_retrace_sentinel_fires_with_signature_diff(self):
        f = compilestats.wrap(jax.jit(lambda a: a + 1), "t.unstable",
                              budget=1)
        f(jnp.ones((4,), jnp.float32))
        assert guardian.events("compile_retrace") == []
        f(jnp.ones((8,), jnp.float32))     # shape-unstable: retrace!
        (ev,) = guardian.events("compile_retrace")
        assert ev["surface"] == "t.unstable"
        assert ev["compiles"] == 2 and ev["budget"] == 1
        assert "float32[4]" in ev["diff"] and "float32[8]" in ev["diff"]
        assert obs.get_registry().get("pt_compile_retraces_total").value(
            surface="t.unstable") == 1
        # dtype drift trips it too, with the dtype in the diff
        f(jnp.ones((8,), jnp.bfloat16))
        assert "bfloat16[8]" in \
            guardian.events("compile_retrace")[-1]["diff"]

    def test_sentinel_silent_across_serving_run_and_fit(self, gpt):
        _, _, finished = _run_engine(gpt)      # >= 3 decode chunks
        model = _reg_model()
        model.fit(_batches(3), epochs=1, verbose=0)
        assert len(finished) == 3
        assert guardian.events("compile_retrace") == []
        snap = compilestats.snapshot()
        assert snap["serving.decode_chunk"]["compiles"] == 1
        assert snap["serving.prefill"]["compiles"] == 1
        assert snap["hapi.train_step"]["compiles"] == 1
        assert all(s["retraces"] == 0 for s in snap.values())

    def test_serving_outputs_unchanged_by_wrapping(self, gpt):
        # the AOT executable cache must not perturb the engine's
        # bitwise-parity contract: same trace with telemetry disabled
        # (wrapper still active) == enabled
        _, reqs_a, _ = _run_engine(gpt)
        with obs.disabled():
            _, reqs_b, _ = _run_engine(gpt)
        assert [r.tokens for r in reqs_a] == [r.tokens for r in reqs_b]


# -- the wrapper's call path (ISSUE 28) ------------------------------------

def _dispatches(surface):
    c = obs.get_registry().get("pt_compile_dispatch_total")
    return (c.value(surface=surface, path="fast"),
            c.value(surface=surface, path="signature"))


class TestCallPath:
    @pytest.fixture
    def walks(self, monkeypatch):
        """Counts the Python ``signature`` walks (counted, not timed)."""
        seen = []
        real = compilestats.signature

        def counting(args):
            seen.append(1)
            return real(args)
        monkeypatch.setattr(compilestats, "signature", counting)
        return seen

    @pytest.fixture
    def backend_compiles(self):
        """Backend compiles by JAX's own monitoring event (what
        ``chip_smoke.CompileMeter`` counts)."""
        import jax.monitoring as mon
        seen = []

        def on(name, secs, **kw):
            if name.endswith("backend_compile_duration"):
                seen.append(secs)
        mon.register_event_duration_secs_listener(on)
        yield seen
        mon.unregister_event_duration_listener(on)

    def test_known_call_walks_nothing_and_compiles_once(
            self, walks, backend_compiles):
        f = compilestats.wrap(
            jax.jit(lambda a, b: jnp.tanh(a @ b) * 3), "t.path", budget=1)
        a = jnp.ones((8, 8), jnp.float32)
        n = 6
        before = len(backend_compiles)
        outs = [f(a, a) for _ in range(n)]
        assert len(walks) == 1
        assert _dispatches("t.path") == (n - 1, 1)
        # lowered again for its analysis, dispatched by jax.jit: still
        # ONE backend compile of the executable
        assert len(backend_compiles) - before == 1
        st = compilestats.snapshot()["t.path"]
        assert st["compiles"] == 1 and st["signatures"] == 1
        assert st["flops"] > 0 and st["memory_bytes"] > 0
        assert st["compile_wall_ms"] > 0
        assert f.compiles == 1
        assert "jit" in next(iter(compilestats.op_names()))
        assert all(np.array_equal(outs[0], o) for o in outs)
        assert guardian.events("compile_retrace") == []

    @pytest.mark.parametrize("drift,diff", [
        (lambda: jnp.ones((4,), jnp.bfloat16),
         "arg[0]: float32[4] -> bfloat16[4]"),
        (lambda: jnp.ones((6,), jnp.float32),
         "arg[0]: float32[4] -> float32[6]"),
    ], ids=["dtype", "shape"])
    def test_drift_compiles_once_more_and_says_what_changed(
            self, walks, backend_compiles, drift, diff):
        f = compilestats.wrap(jax.jit(lambda a: a * 2), "t.drift", budget=1)
        x, y = jnp.ones((4,), jnp.float32), drift()
        f(x), f(x)
        before = len(backend_compiles)
        f(y), f(y), f(x)
        assert len(backend_compiles) - before == 1
        assert len(walks) == 2
        assert _dispatches("t.drift") == (3, 2)
        st = compilestats.snapshot()["t.drift"]
        assert st["compiles"] == 2 and st["signatures"] == 2
        assert st["retraces"] == 1
        (ev,) = guardian.events("compile_retrace")
        assert ev["diff"] == diff
        assert ev["compiles"] == 2 and ev["budget"] == 1

    def test_none_against_array_is_another_executable(self):
        f = compilestats.wrap(
            jax.jit(lambda a, m: a if m is None else a * m), "t.none")
        x = jnp.ones((4,), jnp.float32)
        assert np.array_equal(f(x, None), x)
        assert np.array_equal(f(x, x * 3), x * 3)
        assert np.array_equal(f(x, None), x)
        st = compilestats.snapshot()["t.none"]
        assert st["compiles"] == 2 and st["signatures"] == 2
        assert _dispatches("t.none") == (1, 2)

    def test_donated_arguments_stay_donated(self):
        f = compilestats.wrap(
            jax.jit(lambda s, g: s + g, donate_argnums=(0,)), "t.donate")
        state, g = jnp.zeros((64,), jnp.float32), jnp.ones((64,))
        for _ in range(3):
            old, state = state, f(state, g)
            assert old.is_deleted() and not g.is_deleted()
        assert float(state[0]) == 3.0
        # the first call's signature was walked over a deleted buffer
        assert compilestats.snapshot()["t.donate"]["compiles"] == 1

    def test_an_error_inside_the_executable_propagates(self):
        def boom(x):
            raise RuntimeError("host callback failed")

        def fn(a):
            return jax.pure_callback(
                boom, jax.ShapeDtypeStruct(a.shape, a.dtype), a)
        f = compilestats.wrap(jax.jit(fn), "t.boom")
        with pytest.raises(Exception, match="host callback failed"):
            jax.block_until_ready(f(jnp.ones((4,), jnp.float32)))
        # and one that fails while tracing, before anything compiled
        g = compilestats.wrap(jax.jit(lambda a: a @ a), "t.trace_error")
        with pytest.raises(TypeError):
            g(jnp.ones((3, 4), jnp.float32))
        assert "t.trace_error" not in compilestats.snapshot()


# -- request-scoped traces -------------------------------------------------

class TestRequestTracing:
    def test_spans_tile_submit_to_finish(self, gpt):
        _, reqs, finished = _run_engine(gpt)
        assert len(finished) == len(reqs)
        summaries = {r["trace"]: r for r in tracing.request_summaries()}
        for req in reqs:
            s = summaries[req.trace_id]
            wall_ms = (req.finish_ns - req.submit_ns) / 1e6
            # spans are booked from the same stamps, so the sum matches
            # the measured wall to rounding (ms-scale tolerance)
            assert s["span_sum_ms"] == pytest.approx(wall_ms, abs=1.0)
            assert s["total_ms"] == pytest.approx(wall_ms, abs=1.0)
            assert s["tokens"] == len(req.tokens)
            assert s["ttft_ms"] == pytest.approx(req.ttft_ms, abs=1.0)
        phases = {sp["phase"] for sp in tracing.spans()}
        assert {"queue_wait", "prefill", "decode"} <= phases
        reg = obs.get_registry()
        assert reg.get("pt_trace_requests_total").value() == len(reqs)
        assert reg.get("pt_trace_spans_total").value(
            phase="prefill") == len(reqs)

    def test_prefill_span_carries_admission_metadata(self, gpt):
        _run_engine(gpt)
        pre = [s for s in tracing.spans() if s["phase"] == "prefill"]
        assert pre and all(s["args"]["bucket"] == 8 for s in pre)
        assert all(s["args"]["cached_tokens"] == 0 for s in pre)

    def test_request_lanes_round_trip_through_chrome_trace(
            self, gpt, tmp_path):
        _, reqs, _ = _run_engine(gpt)
        path = str(tmp_path / "t.trace.json")
        timeline.export_chrome_trace(path, include_profiler=False,
                                     include_guardian=False,
                                     include_samples=False)
        with open(path) as f:
            events = json.load(f)["traceEvents"]
        lanes = {e["args"]["name"] for e in events
                 if e.get("name") == "thread_name"
                 and e["tid"] >= timeline.TID_REQUESTS}
        assert lanes == {f"req {r.trace_id}" for r in reqs}
        rows = report.request_rows_from_trace(path)
        direct = tracing.request_summaries()
        assert {r["trace"] for r in rows} == {r["trace"] for r in direct}
        by_trace = {r["trace"]: r for r in rows}
        for d in direct:
            # µs-quantized by the chrome ts/dur round trip
            assert by_trace[d["trace"]]["ttft_ms"] == pytest.approx(
                d["ttft_ms"], abs=0.1)
        summary = report.requests_view(rows)
        assert summary["requests"] == len(reqs)
        assert summary["ttft_ms"]["p50"] is not None
        assert summary["tail_phase_ms_mean"]

    def test_tracing_off_books_nothing(self, gpt):
        with obs.disabled():
            _run_engine(gpt)
        assert tracing.spans() == []

    def test_ring_overflow_is_visible(self):
        assert tracing.dropped_spans() == 0
        for i in range(tracing._SPANS.maxlen + 5):
            tracing.span(f"t{i}", i, "decode", 0, 1)
        assert tracing.dropped_spans() == 5
        tracing.reset()
        assert tracing.dropped_spans() == 0


# -- THE overhead contract, extended ---------------------------------------

class TestZeroSyncContract:
    def test_serving_same_device_get_count_with_new_layers_on_vs_off(
            self, gpt, monkeypatch):
        """PR 5 A/B extended: compile telemetry (AOT dispatch) +
        request tracing add ZERO device transfers — counts match with
        the whole observability stack on vs off."""
        counts = {"n": 0}
        real = jax.device_get

        def counting(x):
            counts["n"] += 1
            return real(x)

        def run_once(enabled):
            rng = np.random.RandomState(5)
            eng = ServingEngine(gpt, num_slots=2, chunk=4,
                                prefill_buckets=(8,))
            for b in (3, 9, 4):
                eng.submit(rng.randint(0, 1024, (6,)).astype("int32"), b)
            counts["n"] = 0
            monkeypatch.setattr(jax, "device_get", counting)
            try:
                if enabled:
                    eng.run()
                else:
                    with obs.disabled():
                        eng.run()
            finally:
                monkeypatch.setattr(jax, "device_get", real)
            return counts["n"], eng.stats["chunks"]

        n_on, chunks_on = run_once(True)
        n_off, chunks_off = run_once(False)
        assert chunks_on == chunks_off
        assert n_on == n_off
        assert n_on > 0
        assert len(tracing.spans()) > 0     # tracing DID run in the on leg

    def test_fit_same_host_sync_count_with_compile_telemetry(self):
        """The guarded fit's one-sync-per-step contract survives the
        compile-telemetry wrap of the stepper executables."""
        cfg = dict(skip_limit=10, ckpt_root=None, loss_spike=False)

        def syncs_of(enabled):
            model = _reg_model(seed=7)
            before = guardian.host_sync_count()
            if enabled:
                model.fit(_batches(4), epochs=1, verbose=0,
                          guardian=guardian.GuardianConfig(**cfg))
            else:
                with obs.disabled():
                    model.fit(_batches(4), epochs=1, verbose=0,
                              guardian=guardian.GuardianConfig(**cfg))
            return guardian.host_sync_count() - before

        on, off = syncs_of(True), syncs_of(False)
        assert on == off == 4
        assert "hapi.train_step" in compilestats.snapshot()


# -- roofline --------------------------------------------------------------

class TestRoofline:
    def test_roofline_math_and_attribution(self):
        stats = {"s.compute": {"flops": 2e9, "bytes_accessed": 1e6,
                               "memory_bytes": 1e6, "compiles": 1,
                               "retraces": 0},
                 "s.memory": {"flops": 1e6, "bytes_accessed": 1e9,
                              "memory_bytes": 1e9, "compiles": 2,
                              "retraces": 1}}
        table = report.roofline_from_stats(
            stats, measured_ms={"s.compute": 4.0},
            peak_flops=1e12, hbm_bw=1e9)
        rows = {r["surface"]: r for r in table["rows"]}
        c = rows["s.compute"]
        assert c["bound"] == "compute"
        assert c["compute_ms"] == pytest.approx(2.0)
        assert c["memory_ms"] == pytest.approx(1e6 / 1e9 * 1e3)
        att = c["attribution"]
        assert att["compute_frac"] == pytest.approx(0.5)
        assert att["memory_frac"] == 0.0        # hidden under compute
        assert att["dispatch_other_frac"] == pytest.approx(0.5)
        assert sum(att.values()) == pytest.approx(1.0)  # a partition
        assert c["mfu"] == pytest.approx(2e9 / 4e-3 / 1e12, rel=1e-3)
        m = rows["s.memory"]
        assert m["bound"] == "memory" and m["attribution"] is None

    def test_roofline_rows_from_a_prom_exposition(self, gpt, tmp_path):
        """The readers the doctor feeds ``roofline_from_stats`` with:
        compile telemetry and measured dispatch latency out of one
        written exposition."""
        _run_engine(gpt)
        obs.observe("pt_compile_dispatch_ms", 5.0,
                    surface="serving.decode_chunk")
        prom = str(tmp_path / "t.prom")
        export.write_prometheus(prom)
        metrics = report.parse_prometheus(prom)
        table = report.roofline_from_stats(
            report.compile_stats_from_prom(metrics),
            report.measured_from_prom(metrics),
            peak_flops=1e12, hbm_bw=5e10)
        rows = {r["surface"]: r for r in table["rows"]}
        assert "serving.prefill" in rows
        dec = rows["serving.decode_chunk"]
        assert dec["measured_ms"] == pytest.approx(5.0)
        att = dec["attribution"]
        assert 0 <= att["compute_frac"] <= 1
        assert att["dispatch_other_frac"] > 0   # tiny model: dispatch


# -- satellites ------------------------------------------------------------

class TestSnapshotIdempotency:
    def test_write_jsonl_replace_run_is_idempotent(self, tmp_path):
        path = str(tmp_path / "t.jsonl")
        obs.inc("pt_serving_chunks_total", 3)
        export.write_jsonl(path, run="other")          # foreign run
        export.write_jsonl(path, run="train", replace_run=True)
        n1 = len(open(path).read().splitlines())
        export.write_jsonl(path, run="train", replace_run=True)
        export.write_jsonl(path, run="train", replace_run=True)
        lines = open(path).read().splitlines()
        assert len(lines) == n1                        # no growth
        runs = {json.loads(l)["run"] for l in lines}
        assert runs == {"other", "train"}              # foreign kept
        # plain append still appends (the guardian-log sink behavior)
        export.write_jsonl(path, run="train")
        assert len(open(path).read().splitlines()) > n1
