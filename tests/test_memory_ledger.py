"""HBM memory ledger (ISSUE 20 tentpole) + satellites.

Acceptance anchors:

- static side: every registry jit surface gets a row in the
  ``memory.json`` snapshot (never-compiled ones as explicit
  placeholders); the compile hook feeds the ledger; an over-envelope
  surface raises the guardian ``memory_budget`` event;
- dynamic side: the live-buffer census reconciles against the real
  ``PagedKVManager``'s analytic bookkeeping within 1% on the CPU
  proxy, forecasts OOM from a linear growth trend, and books the
  ``pt_memory_*`` gauges (``-1`` forecast sentinel included);
- chaos e2e: shrinking the page pool mid-run trips ``hbm_pressure``,
  the forensic bundle carries ``memory.jsonl``, and ``doctor`` ranks
  ``memory_pressure`` as the top cause;
- satellites: dropped-span ring-overflow accounting surfaces in the
  counter, the trace metadata and ``report --requests``; the timeline
  guardian clock offset is minted once with no capture; two
  near-simultaneous watchdog trips coalesce into ONE bundle and
  retention never deletes a mid-write dot-tmp dir.
"""
import collections
import json
import os
import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.framework import failpoints, guardian
from paddle_tpu.inference.kvcache import PagedKVManager
from paddle_tpu.observability import (compilestats, doctor, export,
                                      flight, memory, metrics, report,
                                      timeline, tracing, watch)

pytestmark = pytest.mark.obs


@pytest.fixture(autouse=True)
def _clean():
    flight.disable()
    obs.enable(True)
    obs.get_registry().reset()
    tracing.reset()
    compilestats.reset()
    memory.reset()
    failpoints.clear()
    guardian.clear_events()
    yield
    flight.disable()
    obs.enable(True)
    obs.get_registry().reset()
    tracing.reset()
    compilestats.reset()
    memory.reset()
    failpoints.clear()
    guardian.clear_events()


def _gauge(name, **labels):
    """Latest value of one gauge/counter series from the registry."""
    key = tuple(sorted(labels.items()))
    for rec in export.snapshot():
        if rec["metric"] == name and \
                tuple(sorted(rec["labels"].items())) == key:
            return rec["value"]
    return None


class FakePool:
    """Minimal PagedKVManager accounting surface for census tests."""

    def __init__(self, num_pages=11, page_bytes=1024, in_use=0):
        self.num_pages = num_pages
        self.page_bytes = page_bytes
        self._in_use = in_use
        self._buf = np.zeros(num_pages * page_bytes, np.int8)

    @property
    def pages_in_use(self):
        return self._in_use

    @property
    def resident_bytes(self):
        return self._in_use * self.page_bytes

    @property
    def pool_bytes(self):
        return self.num_pages * self.page_bytes

    def device_pools(self):
        return [(self._buf,)]


def _mgr(num_pages=9):
    return PagedKVManager(spec=[(2, 8)], num_slots=2, max_seq_len=16,
                         page_size=4, num_pages=num_pages,
                         cache_dtype="float32")


# -- static side -----------------------------------------------------------

class TestStaticLedger:
    def test_record_books_total_and_gauges(self, monkeypatch):
        monkeypatch.setenv(memory.HBM_ENVELOPE_ENV, "1000000")
        row = memory.record_static(
            "kernel.flash_fwd",
            {"argument": 100, "output": 60, "temp": 30,
             "generated_code": 10},
            cost={"flops": 7.0, "bytes accessed": 9.0})
        assert row["total_bytes"] == 200
        assert row["flops"] == 7.0 and row["bytes_accessed"] == 9.0
        assert _gauge("pt_memory_static_bytes",
                      surface="kernel.flash_fwd", kind="total") == 200
        assert _gauge("pt_memory_static_bytes",
                      surface="kernel.flash_fwd", kind="argument") == 100
        frac = _gauge("pt_memory_budget_frac",
                      surface="kernel.flash_fwd")
        assert frac == pytest.approx(200 / 1000000)

    def test_partial_kinds_degrade_not_crash(self):
        # XLA:CPU under-reports: absent kinds stay None, the total sums
        # only what the backend exposed
        row = memory.record_static("hapi.train_step",
                                   {"argument": 50, "output": 14})
        assert row["kinds"]["temp"] is None
        assert row["kinds"]["generated_code"] is None
        assert row["total_bytes"] == 64
        assert _gauge("pt_memory_static_bytes",
                      surface="hapi.train_step", kind="temp") is None

    def test_over_envelope_emits_memory_budget(self, monkeypatch):
        monkeypatch.setenv(memory.HBM_ENVELOPE_ENV, "1000")
        memory.record_static("generation.decode", {"argument": 4000})
        (e,) = [e for e in guardian.events()
                if e["event"] == "memory_budget"]
        assert e["surface"] == "generation.decode"
        assert e["bytes"] == 4000 and e["envelope"] == 1000
        assert e["frac"] == pytest.approx(4.0)

    def test_compile_hook_feeds_ledger(self):
        f = compilestats.wrap(jax.jit(lambda x: x * 2.0 + 1.0),
                              "kernel.flash_fwd", budget=4)
        x = jnp.ones((16, 8), jnp.float32)
        np.testing.assert_allclose(np.asarray(f(x)),
                                   np.asarray(x) * 2.0 + 1.0)
        snap = memory.static_snapshot()
        assert "kernel.flash_fwd" in snap
        row = snap["kernel.flash_fwd"]
        assert row["compiled"] is True
        # at least argument/output bytes exist even on XLA:CPU
        assert row["total_bytes"] is not None and row["total_bytes"] > 0

    def test_snapshot_covers_every_registry_surface(self):
        from paddle_tpu.analysis.allowlist import COMPILE_SURFACES
        memory.record_static("serving.decode_chunk", {"argument": 8})
        doc = memory.snapshot()
        for s in COMPILE_SURFACES:
            assert s in doc["surfaces"], s
        assert doc["surfaces"]["serving.decode_chunk"]["compiled"]
        placeholders = [s for s, r in doc["surfaces"].items()
                        if not r["compiled"]]
        assert placeholders          # never-compiled rows are explicit
        for s in placeholders:
            assert doc["surfaces"][s]["total_bytes"] is None

    def test_write_memory_json_atomic(self, tmp_path):
        memory.record_static("hapi.eval_step", {"argument": 32})
        path = memory.write_memory_json(str(tmp_path / "memory.json"))
        assert not os.path.exists(path + ".tmp")
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        assert doc["hbm_envelope_bytes"] == memory.hbm_envelope()
        assert doc["surfaces"]["hapi.eval_step"]["total_bytes"] == 32
        assert "dynamic" in doc and "platform" in doc


# -- dynamic side ----------------------------------------------------------

class TestCensus:
    def test_counts_live_arrays_host_side(self):
        x = jnp.zeros((128, 64), jnp.float32)
        rec = memory.census("fit_step")
        assert rec["live_bytes"] >= x.nbytes
        assert rec["live_buffers"] >= 1
        assert rec["point"] == "fit_step"
        assert rec["kv_occupancy"] is None    # no pool registered
        del x

    def test_reconciles_with_real_paged_pool(self):
        # PagedKVManager registers itself at construction; the measured
        # device-buffer bytes must reconcile with the pool's analytic
        # bookkeeping within 1% (the ISSUE acceptance bound)
        mgr = _mgr()
        mgr._free = mgr._free[:-4]            # 4 of 8 pages in use
        rec = memory.census("serving_sync")
        assert rec["kv_pool_bytes"] == mgr.pool_bytes
        assert abs(rec["kv_device_bytes"] - rec["kv_pool_bytes"]) \
            <= 0.01 * rec["kv_pool_bytes"]
        assert rec["kv_pages_in_use"] == 4
        assert rec["kv_pages_total"] == mgr.num_pages - 1
        assert rec["kv_occupancy"] == pytest.approx(0.5)
        assert rec["kv_headroom_bytes"] == 4 * mgr.page_bytes
        assert rec["kv_resident_bytes"] == 4 * mgr.page_bytes

    def test_reset_never_double_registers(self):
        mgr = _mgr()
        mgr.reset()
        mgr.reset()                           # re-registers by identity
        rec = memory.census()
        assert rec["kv_pool_bytes"] == mgr.pool_bytes
        assert rec["kv_pages_total"] == mgr.num_pages - 1

    def test_dropped_pool_unregisters_via_weakref(self):
        pool = FakePool(in_use=5)
        memory.register_kv_pool(pool)
        assert memory.census()["kv_occupancy"] is not None
        del pool
        assert memory.census()["kv_occupancy"] is None

    def test_forecast_linear_growth_and_flat(self):
        pool = FakePool(num_pages=101, page_bytes=100, in_use=10)
        memory.register_kv_pool(pool)
        for _ in range(6):                    # +5 pages per census
            pool._in_use += 5
            rec = memory.census("serving_sync")
        # headroom / slope: (101-1-40)*100 B left, growing 500 B/census
        assert rec["steps_to_exhaustion"] == pytest.approx(
            (101 - 1 - 40) * 100 / 500, rel=0.01)
        memory.reset()
        memory.register_kv_pool(pool)
        for _ in range(6):                    # flat: no trend
            rec = memory.census("serving_sync")
        assert rec["steps_to_exhaustion"] is None
        assert memory.forecast() is None

    def test_census_fields_gauges_and_sentinel(self):
        pool = FakePool(in_use=8)
        memory.register_kv_pool(pool)
        fields = memory.census_fields("router_gap")
        assert fields["kv_occupancy"] == pytest.approx(0.8)
        assert "steps_to_exhaustion" not in fields   # no trend yet
        assert _gauge("pt_memory_live_bytes", pool="total") is not None
        assert _gauge("pt_memory_live_bytes", pool="kv_pages") == \
            pool.pool_bytes
        assert _gauge("pt_memory_kv_occupancy") == pytest.approx(0.8)
        assert _gauge("pt_memory_kv_headroom_bytes") == \
            2 * pool.page_bytes
        # the gauge's no-trend sentinel is -1, never an absent series
        assert _gauge("pt_memory_steps_to_exhaustion") == -1

    def test_ledger_records_static_then_census(self):
        memory.record_static("hapi.grad_step", {"argument": 4})
        memory.census("fit_step")
        recs = memory.ledger_records()
        kinds = [r["kind"] for r in recs]
        assert kinds == ["static", "census"]
        assert recs[0]["surface"] == "hapi.grad_step"
        assert recs[1]["point"] == "fit_step"


# -- hbm_pressure watch rule -----------------------------------------------

class TestHbmPressureRule:
    def _eng(self, **kw):
        kw.setdefault("rules", ("hbm_pressure",))
        kw.setdefault("hbm_min_samples", 2)
        kw.setdefault("cooldown_s", 0.0)
        return watch.WatchEngine(watch.WatchConfig(**kw))

    def test_occupancy_trip(self):
        eng = self._eng()
        s = {"point": "serving_sync", "kv_occupancy": 0.95,
             "kv_headroom_bytes": 100}
        assert eng.evaluate(dict(s)) == []    # below min samples
        (a,) = eng.evaluate(dict(s))
        assert a["rule"] == "hbm_pressure"
        assert a["value"] == pytest.approx(0.95)
        assert "occupancy" in a["detail"]

    def test_forecast_trip(self):
        eng = self._eng()
        s = {"point": "fit_step", "kv_occupancy": 0.5,
             "steps_to_exhaustion": 12.0}
        eng.evaluate(dict(s))
        (a,) = eng.evaluate(dict(s))
        assert a["rule"] == "hbm_pressure"
        assert "OOM forecast" in a["detail"]

    def test_needs_census_bearing_samples(self):
        eng = self._eng()
        # census-free samples never advance the rule
        for _ in range(8):
            assert eng.evaluate({"point": "serving_sync",
                                 "queue_depth": 0}) == []
        assert eng.state_summary()["hbm_samples"] == 0

    def test_only_census_sync_points(self):
        eng = self._eng()
        for _ in range(4):
            alerts = eng.evaluate({"point": "request",
                                   "kv_occupancy": 0.99,
                                   "ttft_ms": 1.0, "tpot_ms": 1.0,
                                   "replica": None})
        assert alerts == []


# -- chaos e2e -------------------------------------------------------------

class TestChaosPoolShrink:
    def test_shrink_trips_bundle_and_doctor(self, tmp_path):
        """Shrink the page pool mid-run: hbm_pressure trips, ONE bundle
        is written carrying memory.jsonl, and doctor ranks
        memory_pressure as the top cause."""
        d = str(tmp_path / "flight")
        flight.enable(dump_dir=d, dump_async=False,
                      config=watch.WatchConfig(
                          rules=("hbm_pressure",), hbm_min_samples=2,
                          cooldown_s=0.0))
        mgr = _mgr(num_pages=9)
        memory.record_static("serving.paged_decode_chunk",
                             {"argument": 64, "output": 32})
        tripped = []
        for i in range(6):
            if i == 3:
                mgr._free = []                # pool shrink: 8/8 in use
            fields = memory.census_fields("serving_sync")
            tripped += flight.record("serving_sync", decoded=i,
                                     **fields)
        assert any(a["rule"] == "hbm_pressure" for a in tripped)
        bundles = [n for n in os.listdir(d) if n.startswith("bundle_")]
        assert len(bundles) == 1              # cooldown coalesces
        bdir = os.path.join(d, bundles[0])
        mem_lines = [json.loads(l) for l in
                     open(os.path.join(bdir, "memory.jsonl"),
                          encoding="utf-8")]
        assert any(r["kind"] == "static" and
                   r["surface"] == "serving.paged_decode_chunk"
                   for r in mem_lines)
        census = [r for r in mem_lines if r["kind"] == "census"]
        assert census and census[-1]["kv_occupancy"] >= 0.87
        result = doctor.diagnose(doctor.load_bundle(bdir))
        top = result["diagnoses"][0]
        assert top["cause"] == "memory_pressure"
        assert any("occupancy" in e for e in top["evidence"])

    def test_doctor_cli_names_memory_pressure(self, tmp_path, capsys):
        d = str(tmp_path / "flight")
        flight.enable(dump_dir=d, dump_async=False,
                      config=watch.WatchConfig(
                          rules=("hbm_pressure",), hbm_min_samples=2,
                          cooldown_s=0.0))
        pool = FakePool(in_use=10)            # 100% occupancy
        memory.register_kv_pool(pool)
        for _ in range(3):
            flight.record("router_gap",
                          **memory.census_fields("router_gap"))
        (bundle,) = flight.recorder().dumps()
        assert report.main(["doctor", bundle]) == 0
        assert "memory_pressure" in capsys.readouterr().out


# -- satellite 3: bundle retention under concurrent trips -------------------

class TestBundleRetention:
    def test_concurrent_trips_coalesce_to_one_bundle(self, tmp_path):
        d = str(tmp_path / "flight")
        rec = flight.FlightRecorder(
            dump_dir=d, dump_async=False, dump_cooldown_s=120.0,
            config=watch.WatchConfig(
                rules=("guardian_escalation", "straggler_replica"),
                cooldown_s=0.0))
        barrier = threading.Barrier(2)

        def trip_rollback():
            barrier.wait()
            rec.record("fit_step", verdict="rollback", step=1)

        def trip_straggler():
            barrier.wait()
            rec.record("router_gap", stale_replicas=1, queue_depth=0)

        ts = [threading.Thread(target=trip_rollback),
              threading.Thread(target=trip_straggler)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        # two different rules tripped near-simultaneously; the global
        # dump cooldown coalesces the incident into exactly one bundle
        assert len([n for n in os.listdir(d)
                    if n.startswith("bundle_")]) == 1
        assert len(rec.dumps()) == 1

    def test_retention_spares_midwrite_tmp_dirs(self, tmp_path):
        d = str(tmp_path / "flight")
        os.makedirs(d)
        # a concurrent dump mid-write: dot-tmp dirs are invisible to
        # the keep-last-K sweep (only published bundle_* names count)
        midwrite = os.path.join(d, ".bundle_1_hbm_pressure.tmp")
        os.makedirs(midwrite)
        with open(os.path.join(midwrite, "meta.json"), "w") as f:
            f.write("{}")
        rec = flight.FlightRecorder(dump_dir=d, dump_async=False,
                                    keep=1, dump_cooldown_s=0.0)
        first = rec.dump(trigger="manual")
        time.sleep(0.002)                     # distinct ns timestamps
        second = rec.dump(trigger="manual")
        assert os.path.isdir(midwrite)        # never swept mid-write
        bundles = [n for n in os.listdir(d) if n.startswith("bundle_")]
        assert bundles == [os.path.basename(second)]
        assert not os.path.exists(first)


# -- satellite 1: dropped-span accounting ----------------------------------

class TestDroppedSpans:
    def test_ring_overflow_ticks_counter(self, monkeypatch):
        monkeypatch.setattr(tracing, "_SPANS",
                            collections.deque(maxlen=2))
        t0 = time.perf_counter_ns()
        for i in range(5):
            tracing.span(f"t{i}", i, "decode", t0, t0 + 1000, tokens=2)
        assert tracing.dropped_spans() == 3
        assert _gauge("pt_trace_dropped_spans_total") == 3

    def test_report_requests_flags_tiling_violation(self, tmp_path,
                                                    monkeypatch,
                                                    capsys):
        monkeypatch.setattr(tracing, "_SPANS",
                            collections.deque(maxlen=2))
        t0 = time.perf_counter_ns()
        ms = 1_000_000
        tracing.span("t1-r0", 0, "prefill", t0, t0 + 5 * ms, tokens=1)
        tracing.span("t1-r0", 0, "decode", t0 + 5 * ms, t0 + 9 * ms,
                     tokens=4)
        tracing.span("t2-r1", 1, "prefill", t0, t0 + 3 * ms, tokens=1)
        assert tracing.dropped_spans() == 1
        path = str(tmp_path / "trace.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"traceEvents": timeline.merged_trace_events(
                include_profiler=False, include_guardian=False,
                include_samples=False)}, f)
        assert report.dropped_spans_from_trace(path) == 1
        assert report.main(["report", "--trace", path,
                            "--requests"]) == 0
        out = capsys.readouterr().out
        assert "WARNING" in out and "span-tiling invariant" in out
        assert "pt_trace_dropped_spans_total" in out

    def test_clean_run_no_flag(self, tmp_path, capsys):
        t0 = time.perf_counter_ns()
        ms = 1_000_000
        tracing.span("t3-r0", 0, "prefill", t0, t0 + 2 * ms, tokens=1)
        path = str(tmp_path / "trace.json")
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"traceEvents": timeline.merged_trace_events(
                include_profiler=False, include_guardian=False,
                include_samples=False)}, f)
        assert report.dropped_spans_from_trace(path) == 0
        assert report.main(["report", "--trace", path,
                            "--requests"]) == 0
        assert "WARNING" not in capsys.readouterr().out


# -- satellite 2: guardian clock offset with no capture ---------------------

class TestGuardianClockOffset:
    def test_offset_minted_once_and_reused(self):
        old_pair = metrics._CLOCK_PAIR[0]
        old_fallback = timeline._FALLBACK_PAIR[0]
        metrics._CLOCK_PAIR[0] = None         # no capture ran
        timeline._FALLBACK_PAIR[0] = None
        try:
            guardian.emit("flight_dump", trigger="manual", path="/x",
                          alerts=0, kept=1)

            def guardian_ts():
                evs = timeline.merged_trace_events(
                    include_profiler=False, include_samples=False,
                    include_requests=False)
                return [e["ts"] for e in evs
                        if e.get("cat") == "guardian"]
            first = guardian_ts()
            assert first
            time.sleep(0.01)
            # a second export must reuse the SAME minted (wall, perf)
            # pair — re-minting would shift every guardian instant by
            # the time between exports
            assert guardian_ts() == first
        finally:
            metrics._CLOCK_PAIR[0] = old_pair
            timeline._FALLBACK_PAIR[0] = old_fallback

    def test_timeline_memory_counter_tracks(self):
        pool = FakePool(in_use=6)
        memory.register_kv_pool(pool)
        memory.census_fields("fit_step")
        evs = timeline.merged_trace_events(include_profiler=False,
                                           include_guardian=False,
                                           include_requests=False)
        names = {e["name"] for e in evs if e.get("cat") == "memory"}
        assert "pt_memory_live_bytes{pool=kv_pages}" in names
        assert "pt_memory_kv_occupancy" in names


# -- report --memory --------------------------------------------------------

class TestReportMemory:
    def test_memory_view_from_artifact(self, tmp_path):
        memory.record_static("hapi.train_step",
                             {"argument": 100, "output": 28})
        pool = FakePool(in_use=4)
        memory.register_kv_pool(pool)
        memory.census("serving_sync")
        path = memory.write_memory_json(str(tmp_path / "memory.json"))
        view = report.memory_view(memory_json=path)
        assert view["static"]["hapi.train_step"]["total_bytes"] == 128
        assert view["live"]["kv_occupancy"] == pytest.approx(0.4)
        text = report.render_memory(view)
        assert "hapi.train_step" in text
        assert "(not compiled this run)" in text

    def test_memory_view_from_prom(self, tmp_path):
        memory.record_static("serving.prefill", {"argument": 64})
        pool = FakePool(in_use=2)
        memory.register_kv_pool(pool)
        memory.census_fields("serving_sync")
        prom = str(tmp_path / "m.prom")
        export.write_prometheus(prom)
        view = report.memory_view(prom=prom)
        assert view["static"]["serving.prefill"]["total_bytes"] == 64
        assert view["live"]["kv_occupancy"] == pytest.approx(0.2)
        # -1 forecast sentinel is filtered, not rendered as a forecast
        assert "steps_to_exhaustion" not in view["live"]

    def test_no_data_discipline(self, tmp_path, capsys):
        missing = str(tmp_path / "nope.json")
        assert report.main(["report", "--memory",
                            "--memory-json", missing]) == 0
        assert "no data: memory" in capsys.readouterr().out
        assert report.main(["report", "--memory"]) == 2
