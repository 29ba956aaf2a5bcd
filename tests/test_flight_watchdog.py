"""Flight recorder + SLO watchdog + doctor (ISSUE 13 tentpole), and
the report/export satellites.

Acceptance anchors:

- zero-new-host-sync A/B extended to the recorder+watchdog: device
  transfers and ``guardian._host_bool`` syncs are identical with the
  flight recorder on vs off, for a 3-step ``fit`` AND a threaded fleet
  run (where scheduling is nondeterministic, the invariant is one
  bundled ``device_get`` per engine sync — recorder on or off);
- chaos e2e: a ``serving.replica_crash`` death mid-decode and a
  guardian rollback each produce exactly ONE forensic bundle whose
  ``doctor`` top-ranked diagnosis names the injected cause; bundle
  writes are atomic (tmp+rename) with keep-last-K retention;
- ``report --requests`` no-data discipline, the NaN/zero
  measured-latency roofline guard, concurrent ``write_jsonl`` writers,
  histogram quantile edge cases, and the watch-rule docs-table lint.
"""
import json
import math
import os
import threading

import numpy as np
import pytest
import jax

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import observability as obs
from paddle_tpu.framework import failpoints, guardian
from paddle_tpu.inference.router import ServingFleet
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.observability import (compilestats, doctor, export,
                                      flight, report, tracing, watch)
from paddle_tpu.models import GPTForPretraining, gpt3_tiny

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean():
    flight.disable()
    obs.enable(True)
    obs.get_registry().reset()
    tracing.reset()
    compilestats.reset()
    obs.memory.reset()
    failpoints.clear()
    guardian.clear_events()
    yield
    flight.disable()
    obs.enable(True)
    obs.get_registry().reset()
    tracing.reset()
    compilestats.reset()
    obs.memory.reset()
    failpoints.clear()
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


def _batches(n=6, seed=0):
    rng = np.random.RandomState(seed)
    return [(rng.randn(8, 4).astype("float32"),
             rng.randn(8, 2).astype("float32")) for _ in range(n)]


def _prompts(seed, lens):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, 1024, (n,)).astype("int32") for n in lens]


def _bundles(d):
    return sorted(n for n in os.listdir(d) if n.startswith("bundle_"))


def _eng(**kw):
    kw.setdefault("cooldown_s", 0.0)
    return watch.WatchEngine(watch.WatchConfig(**kw))


# -- watch rules -----------------------------------------------------------

class TestWatchRules:
    def test_slo_burn_p99_over_target(self):
        eng = _eng(rules=("slo_burn",), slo_ttft_ms=100.0,
                   min_ttft_samples=4)
        alerts = []
        for _ in range(5):
            alerts = eng.evaluate({"point": "request", "ttft_ms": 250.0,
                                   "tpot_ms": 1.0, "replica": None})
        (a,) = alerts
        assert a["rule"] == "slo_burn" and a["value"] > 100.0
        assert "p99" in a["detail"]

    def test_slo_burn_shed_rate(self):
        eng = _eng(rules=("slo_burn",), shed_rate=0.5, min_requests=8)
        assert eng.evaluate({"point": "router_gap", "requests": 4,
                             "shed": 4, "queue_depth": 0}) == []  # < min
        (a,) = eng.evaluate({"point": "router_gap", "requests": 10,
                             "shed": 6, "queue_depth": 0})
        assert a["rule"] == "slo_burn" and "shed" in a["detail"]

    def test_throughput_collapse_after_warmup_only(self):
        eng = _eng(rules=("throughput_collapse",), tput_warmup=5,
                   tput_drop=0.5, fast_alpha=1.0)
        for _ in range(6):
            assert eng.evaluate({"point": "fit_step",
                                 "tokens_per_sec": 1000.0}) == []
        (a,) = eng.evaluate({"point": "fit_step",
                             "tokens_per_sec": 10.0})
        assert a["rule"] == "throughput_collapse"
        assert a["value"] < a["threshold"]

    def test_queue_runaway_monotonic_growth_only(self):
        eng = _eng(rules=("queue_runaway",), queue_limit=4,
                   queue_window=3)
        for d in (1, 9, 2, 8, 3):   # oscillating: never trips
            assert eng.evaluate({"point": "serving_sync",
                                 "queue_depth": d,
                                 "decoded_tokens": 0,
                                 "ttft_ms": []}) == []
        eng2 = _eng(rules=("queue_runaway",), queue_limit=4,
                    queue_window=3)
        out = []
        for d in (4, 5, 6):
            out = eng2.evaluate({"point": "serving_sync",
                                 "queue_depth": d,
                                 "decoded_tokens": 0, "ttft_ms": []})
        (a,) = out
        assert a["rule"] == "queue_runaway" and a["value"] == 6

    def test_queue_runaway_per_point_windows(self):
        """Review regression: interleaved small per-replica serving
        depths must not defeat the fleet queue's monotonic-growth
        check — each sync point keeps its own window."""
        eng = _eng(rules=("queue_runaway",), queue_limit=4,
                   queue_window=3)
        out = []
        for fleet_d in (4, 5, 6):
            # a replica's tiny engine depth lands between fleet samples
            eng.evaluate({"point": "serving_sync", "queue_depth": 0,
                          "decoded_tokens": 0, "ttft_ms": []})
            out = eng.evaluate({"point": "router_gap",
                                "queue_depth": fleet_d, "requests": 0,
                                "shed": 0})
        (a,) = out
        assert a["rule"] == "queue_runaway"
        assert "router_gap" in a["detail"]

    def test_serving_streams_keyed_per_replica(self):
        """Review regression: two replica engines syncing concurrently
        must not interleave into one rate/depth stream — replica B
        syncing 50us after replica A is not a 1000x throughput spike,
        and B's flat queue must not break A's monotonic growth."""
        eng = _eng(rules=("queue_runaway",), queue_limit=4,
                   queue_window=3)
        out = []
        for d in (4, 5, 6):
            eng.evaluate({"point": "serving_sync", "queue_depth": 0,
                          "decoded_tokens": 1, "ttft_ms": [],
                          "replica": 1})
            out = eng.evaluate({"point": "serving_sync",
                                "queue_depth": d, "decoded_tokens": 1,
                                "ttft_ms": [], "replica": 0})
        (a,) = out
        assert "serving_sync[0]" in a["detail"]
        # per-stream rate: replica B's first sync right after A's must
        # not divide A's tokens by a microsecond cross-replica delta
        eng2 = _eng(rules=("throughput_collapse",), tput_warmup=1,
                    fast_alpha=1.0, slow_alpha=1.0)
        eng2.evaluate({"point": "serving_sync", "ts_ns": 1_000_000_000,
                       "decoded_tokens": 100, "queue_depth": 0,
                       "ttft_ms": [], "replica": 0})
        eng2.evaluate({"point": "serving_sync", "ts_ns": 1_000_050_000,
                       "decoded_tokens": 100, "queue_depth": 0,
                       "ttft_ms": [], "replica": 1})
        assert eng2._fast is None        # no cross-replica rate booked

    def test_straggler_skew_and_stale(self):
        eng = _eng(rules=("straggler_replica",), straggler_skew=2.0,
                   straggler_min_requests=3)
        alerts = []
        for rep, tpot in ((0, 1.0), (1, 10.0)) * 3:
            alerts = eng.evaluate({"point": "request", "ttft_ms": 5.0,
                                   "tpot_ms": tpot, "replica": rep})
        (a,) = alerts
        assert a["rule"] == "straggler_replica" and "replica 1" in \
            a["detail"]
        eng2 = _eng(rules=("straggler_replica",))
        (a2,) = eng2.evaluate({"point": "router_gap", "requests": 0,
                               "shed": 0, "queue_depth": 0,
                               "stale_replicas": 1})
        assert "stale" in a2["detail"]

    def test_guardian_escalation_rollback_and_death(self):
        eng = _eng(rules=("guardian_escalation",))
        (a,) = eng.evaluate({"point": "fit_step", "verdict": "rollback",
                             "tokens_per_sec": 1.0})
        assert "rollback" in a["detail"]
        assert eng.evaluate({"point": "router_gap", "replica_deaths": 0,
                             "requests": 0, "shed": 0,
                             "queue_depth": 0}) == []
        (a2,) = eng.evaluate({"point": "router_gap",
                              "replica_deaths": 1, "requests": 0,
                              "shed": 0, "queue_depth": 0})
        assert "death" in a2["detail"]

    def test_retrace_storm_from_compile_registry(self):
        sig = ("td", ())
        compilestats._record("t.surface", sig, 1.0, None, None)
        eng = _eng(rules=("retrace_storm",), retrace_limit=2)
        assert eng.evaluate({"point": "fit_step",
                             "tokens_per_sec": 1.0}) == []  # baseline
        for _ in range(2):
            compilestats._count_retrace("t.surface")
        (a,) = eng.evaluate({"point": "fit_step",
                             "tokens_per_sec": 1.0})
        assert a["rule"] == "retrace_storm" and a["value"] == 2

    def test_cooldown_suppresses_repeat_trips(self):
        eng = watch.WatchEngine(watch.WatchConfig(
            rules=("guardian_escalation",), cooldown_s=300.0))
        s = {"point": "fit_step", "verdict": "rollback",
             "tokens_per_sec": 1.0}
        assert len(eng.evaluate(s)) == 1
        assert eng.evaluate(s) == []          # within cooldown

    def test_unknown_rule_rejected(self):
        with pytest.raises(ValueError, match="unknown watch rules"):
            watch.WatchConfig(rules=("not_a_rule",))


# -- flight recorder -------------------------------------------------------

class TestFlightRecorder:
    def test_window_bounded_and_gauge(self):
        rec = flight.enable(dump_dir=None, window=4)
        for i in range(9):
            rec.record("fit_step", tokens_per_sec=float(i),
                       step_latency_ms=1.0, loss=0.1, verdict="ok")
        assert len(rec.samples()) == 4
        assert rec.samples()[-1]["tokens_per_sec"] == 8.0
        reg = obs.get_registry()
        assert reg.get("pt_flight_samples").value() == 4
        assert reg.get("pt_watch_evals_total").value() == 9

    def test_trip_emits_event_metric_and_atomic_bundle(self, tmp_path):
        d = str(tmp_path / "flight")
        rec = flight.enable(
            dump_dir=d, dump_async=False,
            config=watch.WatchConfig(rules=("guardian_escalation",),
                                     cooldown_s=0.0))
        rec.record("fit_step", verdict="rollback", tokens_per_sec=1.0,
                   step_latency_ms=1.0, loss=None)
        (ev,) = guardian.events("watch_alert")
        assert ev["rule"] == "guardian_escalation"
        assert ev["point"] == "fit_step"
        assert obs.get_registry().get("pt_watch_alerts_total").value(
            rule="guardian_escalation") == 1
        (name,) = _bundles(d)
        assert not [n for n in os.listdir(d) if n.endswith(".tmp")]
        bdir = os.path.join(d, name)
        assert sorted(os.listdir(bdir)) == sorted(flight.BUNDLE_FILES)
        meta = json.load(open(os.path.join(bdir, "meta.json")))
        assert meta["trigger"] == "guardian_escalation"
        assert meta["alerts"][0]["rule"] == "guardian_escalation"
        assert meta["config"]["rules"] == ["guardian_escalation"]
        assert any(k.startswith("JAX_") or k.startswith("PADDLE_")
                   for k in meta["env"])
        # every bundle file parses with the self-contained parsers
        for line in open(os.path.join(bdir, "guardian.jsonl")):
            json.loads(line)
        for line in open(os.path.join(bdir, "metrics.jsonl")):
            assert json.loads(line)["run"] == "flight"
        assert "traceEvents" in json.load(
            open(os.path.join(bdir, "trace.json")))
        (dump_ev,) = guardian.events("flight_dump")
        assert dump_ev["path"] == bdir and dump_ev["kept"] == 1
        assert obs.get_registry().get("pt_flight_dumps_total").value() \
            == 1

    def test_keep_last_k_retention(self, tmp_path):
        d = str(tmp_path / "flight")
        rec = flight.enable(dump_dir=d, keep=2, dump_async=False)
        paths = [rec.dump(trigger=f"manual{i}") for i in range(4)]
        names = _bundles(d)
        assert len(names) == 2
        assert os.path.basename(paths[-1]) in names
        assert os.path.basename(paths[0]) not in names

    def test_async_dump_thread_lands_bundle(self, tmp_path):
        d = str(tmp_path / "flight")
        rec = flight.enable(
            dump_dir=d, dump_async=True,
            config=watch.WatchConfig(rules=("guardian_escalation",),
                                     cooldown_s=0.0))
        rec.record("fit_step", verdict="rollback", tokens_per_sec=1.0,
                   step_latency_ms=1.0, loss=None)
        assert rec.flush(timeout=10.0)
        assert len(_bundles(d)) == 1

    def test_fit_and_serving_hooks_record_samples(self, gpt):
        rec = flight.enable(dump_dir=None)
        model = _reg_model()
        model.fit(_batches(3), epochs=1, verbose=0)
        points = [s["point"] for s in rec.samples()]
        assert points.count("fit_step") == 3
        fit = [s for s in rec.samples() if s["point"] == "fit_step"]
        assert all(s["verdict"] == "ok" and s["tokens_per_sec"] > 0
                   for s in fit)
        eng = ServingEngine(gpt, num_slots=2, chunk=4,
                            prefill_buckets=(8,))
        for p in _prompts(3, (5, 6)):
            eng.submit(p, 4)
        eng.run()
        pts = [s["point"] for s in rec.samples()]
        assert "serving_sync" in pts
        reqs = [s for s in rec.samples() if s["point"] == "request"]
        assert len(reqs) == 2
        assert all(s["reason"] == "budget" and s["ttft_ms"] > 0
                   for s in reqs)

    def test_disabled_recorder_costs_one_flag_check(self):
        assert not flight.active()
        assert flight.record("fit_step") == []    # no-op, no recorder

    def test_manual_dump_without_dir_raises_cleanly(self):
        rec = flight.enable(dump_dir=False)
        with pytest.raises(ValueError, match="alerts-only"):
            rec.dump(trigger="manual")


# -- THE zero-sync A/B contract --------------------------------------------

class TestZeroSyncFlight:
    def test_fit_same_host_bool_count_with_flight_on_vs_off(self):
        """3-step guarded fit: one verdict readback per step, flight
        recorder on or off."""
        cfg = dict(skip_limit=10, ckpt_root=None, loss_spike=False)

        def syncs_of(enabled):
            if enabled:
                flight.enable(dump_dir=None)
            else:
                flight.disable()
            model = _reg_model(seed=7)
            before = guardian.host_sync_count()
            model.fit(_batches(3), epochs=1, verbose=0,
                      guardian=guardian.GuardianConfig(**cfg))
            return guardian.host_sync_count() - before

        on, off = syncs_of(True), syncs_of(False)
        assert on == off == 3

    def test_threaded_fleet_device_get_equals_sync_count(self, gpt,
                                                         monkeypatch):
        """Threaded fleet: scheduling is nondeterministic, so the
        invariant is structural — exactly one bundled device_get per
        engine sync, recorder on or off."""
        # list.append is GIL-atomic — safe to count from two replica
        # worker threads (an int += would be a racy read-modify-write)
        counts = {"get": [], "sync": []}
        real_get = jax.device_get
        orig_sync = ServingEngine._sync

        def counting_get(x):
            counts["get"].append(1)
            return real_get(x)

        def counting_sync(self, *a, **kw):
            counts["sync"].append(1)
            return orig_sync(self, *a, **kw)

        def run_once(enabled):
            if enabled:
                flight.enable(dump_dir=None)
            else:
                flight.disable()
            fleet = ServingFleet(gpt, num_replicas=2, num_slots=2,
                                 chunk=4, prefill_buckets=(8, 16))
            reqs = [fleet.submit(p, 6)
                    for p in _prompts(4, (5, 7, 6, 4))]
            counts["get"].clear()
            counts["sync"].clear()
            monkeypatch.setattr(jax, "device_get", counting_get)
            monkeypatch.setattr(ServingEngine, "_sync", counting_sync)
            try:
                fleet.run(threads=True, timeout=120)
            finally:
                monkeypatch.setattr(jax, "device_get", real_get)
                monkeypatch.setattr(ServingEngine, "_sync", orig_sync)
            assert all(r.finish_reason == "budget" for r in reqs)
            return len(counts["get"]), len(counts["sync"])

        g_on, s_on = run_once(True)
        g_off, s_off = run_once(False)
        assert g_on == s_on > 0      # one transfer per sync, flight on
        assert g_off == s_off > 0    # ... and flight off


# -- chaos e2e: anomaly -> bundle -> doctor --------------------------------

@pytest.mark.chaos
class TestChaosBundles:
    def test_replica_crash_yields_one_bundle_doctor_names_it(
            self, gpt, tmp_path, capsys):
        d = str(tmp_path / "flight")
        flight.enable(
            dump_dir=d, dump_async=False,
            config=watch.WatchConfig(rules=("guardian_escalation",),
                                     cooldown_s=300.0))
        failpoints.set_failpoint("serving.replica_crash", "error*1")
        fleet = ServingFleet(gpt, num_replicas=2, num_slots=2, chunk=4,
                             prefill_buckets=(8, 16, 32))
        reqs = [fleet.submit(p, 8) for p in _prompts(8, (5, 7, 6, 4))]
        fleet.run(threads=False, timeout=120)
        assert fleet.stats["replica_deaths"] == 1
        assert all(r.finish_reason is not None for r in reqs)
        names = _bundles(d)
        assert len(names) == 1                      # exactly ONE bundle
        assert not [n for n in os.listdir(d) if n.endswith(".tmp")]
        bdir = os.path.join(d, names[0])
        result = doctor.diagnose(doctor.load_bundle(bdir))
        assert result["verdict"] == "replica_death"
        top = result["diagnoses"][0]
        assert top["cause"] == "replica_death"
        assert any("died" in e for e in top["evidence"])
        # serving_sync samples in the bundle window carry the replica
        # identity the watchdog streams are keyed on
        window = [json.loads(line) for line in
                  open(os.path.join(bdir, "window.jsonl"))]
        reps = {s.get("replica") for s in window
                if s["point"] == "serving_sync"}
        assert reps and reps <= {0, 1}
        # the CLI agrees and exits 0
        assert report.main(["doctor", bdir]) == 0
        out = capsys.readouterr().out
        assert "verdict: replica_death" in out

    def test_guardian_rollback_yields_one_bundle_doctor_names_it(
            self, tmp_path, capsys):
        from paddle_tpu.hapi import callbacks as cbks_mod

        class _ArmAt(cbks_mod.Callback):
            def on_train_batch_end(self, step, logs=None):
                if step == 9:
                    failpoints.set_failpoint("guardian.poison_batch",
                                             "skip*5")

        d = str(tmp_path / "flight")
        flight.enable(
            dump_dir=d, dump_async=False,
            config=watch.WatchConfig(rules=("guardian_escalation",),
                                     cooldown_s=300.0))
        root = str(tmp_path / "guard_ckpts")
        model = _reg_model()
        # loss_spike off: the random regression targets trip the spike
        # detector too, and this scenario is the nonfinite ladder alone
        cfg = guardian.GuardianConfig(skip_limit=2, skip_window=2,
                                      ckpt_every=5, ckpt_root=root,
                                      loss_spike=False)
        model.fit(_batches(30), epochs=1, verbose=0, guardian=cfg,
                  callbacks=[_ArmAt()])
        (rb,) = guardian.events("rollback")
        assert rb["rollbacks"] == 1
        before = [e for e in guardian.events("skip_step")
                  if e["step"] <= rb["step"]]
        assert before and all(e["reason"] == "nonfinite" for e in before)
        names = _bundles(d)
        assert len(names) == 1                      # exactly ONE bundle
        bdir = os.path.join(d, names[0])
        result = doctor.diagnose(doctor.load_bundle(bdir))
        assert result["verdict"] == "numeric_instability"
        top = result["diagnoses"][0]
        assert any("rollback" in e for e in top["evidence"])
        # the bundle's guardian.jsonl holds the rollback AND the alert
        evs = [json.loads(line) for line in
               open(os.path.join(bdir, "guardian.jsonl"))]
        kinds = {e["event"] for e in evs}
        assert {"rollback", "watch_alert"} <= kinds
        assert report.main(["doctor", bdir]) == 0
        assert "numeric_instability" in capsys.readouterr().out


# -- doctor ----------------------------------------------------------------

def _healthy_fit_prom(tmp_path):
    """Today's exporter over a healthy few-step fit: the sink the
    doctor reads from another process."""
    _reg_model().fit(_batches(4), epochs=1, verbose=0)
    return export.write_prometheus(str(tmp_path / "train.prom"))


class TestDoctor:
    def test_healthy_fit_telemetry_is_no_alerts(self, tmp_path, capsys):
        prom = _healthy_fit_prom(tmp_path)
        assert report.main(["doctor", "--prom", prom]) == 0
        out = capsys.readouterr().out
        assert "verdict: no alerts" in out

    def test_overload_diagnosis_from_shed_events(self):
        ev = doctor._empty_evidence()
        for i in range(3):
            ev["guardian_events"].append(
                {"event": "router_shed", "req_id": i,
                 "priority": "batch", "projected_wait_ms": 900.0,
                 "slo_ttft_ms": 200.0})
        ev["alerts"] = [{"rule": "slo_burn", "value": 0.6,
                         "threshold": 0.5, "detail": "6/10 shed",
                         "point": "router_gap"}]
        result = doctor.diagnose(ev)
        assert result["verdict"] == "overload_shed"
        assert result["incident"]

    def test_retrace_diagnosis_from_compile_stats(self):
        ev = doctor._empty_evidence()
        ev["compile"] = {"serving.decode_chunk":
                         {"compiles": 9, "retraces": 8, "flops": None,
                          "bytes_accessed": None, "memory_bytes": None}}
        ev["alerts"] = [{"rule": "retrace_storm", "value": 8,
                         "threshold": 3, "detail": "8 recompiles",
                         "point": "serving_sync"}]
        result = doctor.diagnose(ev)
        assert result["verdict"] == "retrace_storm"

    def test_throughput_collapse_alert_is_the_verdict(self):
        """Review regression: a bundle triggered by throughput_collapse
        alone (no roofline latency to attribute) must not fall through
        to 'no alerts'."""
        ev = doctor._empty_evidence()
        ev["alerts"] = [{"rule": "throughput_collapse", "value": 10.0,
                         "threshold": 100.0,
                         "detail": "fast EWMA fell under the trailing "
                                   "baseline", "point": "fit_step"}]
        result = doctor.diagnose(ev)
        assert result["verdict"] == "throughput_collapse"
        assert result["incident"]

    def test_missing_bundle_dir_errors_cleanly(self, capsys):
        assert report.main(["doctor", "/nonexistent/bundle"]) == 1
        assert "error" in capsys.readouterr().err

    def test_report_doctor_flag(self, tmp_path, capsys):
        prom = _healthy_fit_prom(tmp_path)
        assert report.main(["report", "--prom", prom, "--doctor"]) == 0
        out = capsys.readouterr().out
        assert "paddle_tpu doctor" in out

    def test_doctor_cli_needs_input(self, capsys):
        assert report.main(["doctor"]) == 2


# -- report no-data satellites ---------------------------------------------

class TestReportNoData:
    def test_requests_missing_file_one_line_exit_0(self, tmp_path,
                                                   capsys):
        missing = str(tmp_path / "nope.trace.json")
        assert report.main(["report", "--requests",
                            "--trace", missing]) == 0
        out = capsys.readouterr().out
        assert out.count("\n") == 1 and "no data" in out
        assert report.main(["report", "--requests", "--per-replica",
                            "--trace", missing, "--json"]) == 0
        assert json.loads(capsys.readouterr().out) == {}

    def test_requests_empty_and_torn_files(self, tmp_path, capsys):
        empty = tmp_path / "empty.trace.json"
        empty.write_text("")
        assert report.main(["report", "--requests",
                            "--trace", str(empty)]) == 0
        assert "no data" in capsys.readouterr().out
        torn = tmp_path / "torn.trace.json"
        torn.write_text('{"traceEvents": [{"cat": "request", "ts"')
        assert report.main(["report", "--requests",
                            "--trace", str(torn)]) == 0
        assert "no data" in capsys.readouterr().out

    def test_prom_torn_last_line_is_skipped(self, tmp_path):
        p = tmp_path / "torn.prom"
        p.write_text("# TYPE pt_train_loss gauge\n"
                     "pt_train_loss 1.5\n"
                     'pt_serving_ttft_ms_bucket{le="1')     # torn tail
        metrics = report.parse_prometheus(str(p))
        assert metrics["pt_train_loss"]["series"][()] == 1.5


# -- roofline measured-latency guard ---------------------------------------

class TestRooflineGuard:
    STATS = {"s.a": {"compiles": 1, "retraces": 0, "flops": 1e12,
                     "bytes_accessed": 1e9, "memory_bytes": None}}

    def test_nan_zero_and_absent_measured_render_na(self):
        for meas, reason in ((float("nan"),
                              "nonfinite-measured-latency"),
                             (float("inf"),
                              "nonfinite-measured-latency"),
                             (0.0, "zero-measured-latency")):
            table = report.roofline_from_stats(self.STATS,
                                               {"s.a": meas})
            (row,) = table["rows"]
            assert row["attribution"] is None and row["mfu"] is None
            assert row["attribution_reason"] == reason
        table = report.roofline_from_stats(self.STATS, {})
        (row,) = table["rows"]
        assert row["attribution_reason"] == "no-measured-latency"
        # a clean row keeps attribution and a finite mfu
        table = report.roofline_from_stats(self.STATS, {"s.a": 50.0})
        (row,) = table["rows"]
        assert row["attribution_reason"] is None
        assert math.isfinite(row["mfu"])

    def test_nan_dispatch_sum_in_a_prom_gives_no_mfu(self, tmp_path):
        p = tmp_path / "nan.prom"
        p.write_text(
            "# TYPE pt_compile_flops gauge\n"
            'pt_compile_flops{surface="s.a"} 1e12\n'
            "# TYPE pt_compile_bytes_accessed gauge\n"
            'pt_compile_bytes_accessed{surface="s.a"} 1e9\n'
            "# TYPE pt_compile_dispatch_ms histogram\n"
            'pt_compile_dispatch_ms_sum{surface="s.a"} NaN\n'
            'pt_compile_dispatch_ms_count{surface="s.a"} 3\n')
        metrics = report.parse_prometheus(str(p))
        table = report.roofline_from_stats(
            report.compile_stats_from_prom(metrics),
            report.measured_from_prom(metrics))
        (row,) = table["rows"]
        assert row["mfu"] is None
        assert row["attribution_reason"] == "nonfinite-measured-latency"
        json.loads(json.dumps(table, allow_nan=False))  # no NaN leaks


# -- export.write_jsonl under concurrency ----------------------------------

class TestWriteJsonlConcurrent:
    def test_replace_run_concurrent_writers_and_torn_line(self,
                                                          tmp_path):
        path = str(tmp_path / "m.jsonl")
        foreign = {"ts_ns": 1, "metric": "pt_train_loss",
                   "type": "gauge", "labels": {}, "run": "foreign",
                   "value": 1.0}
        with open(path, "w") as f:
            f.write(json.dumps(foreign) + "\n")
            f.write('{"torn": tru')                 # pre-existing tear
        obs.set_gauge("pt_train_loss", 2.0)         # one live series
        errs = []

        def writer(i):
            try:
                for _ in range(5):
                    export.write_jsonl(path, run=f"r{i}",
                                       replace_run=True)
            except Exception as e:          # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=writer, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        lines = open(path).read().splitlines()
        assert any(line.startswith('{"torn"') for line in lines)
        recs = []
        for line in lines:
            try:
                recs.append(json.loads(line))
            except ValueError:
                pass
        runs = {r.get("run") for r in recs}
        assert {"foreign", "r0", "r1", "r2", "r3"} <= runs
        # idempotent per run: each writer's final snapshot, exactly once
        from collections import Counter
        per = Counter((r["run"], r["metric"]) for r in recs
                      if str(r.get("run", "")).startswith("r"))
        assert per and all(v == 1 for v in per.values())


# -- histogram quantile edge cases -----------------------------------------

class TestQuantileEdges:
    def test_empty_histogram(self):
        assert report._quantile([], 0.5) == (None, False)
        assert report._quantile([("+Inf", 0)], 0.9) == (None, False)

    def test_single_bucket_interpolates(self):
        buckets = [("1.0", 4), ("+Inf", 4)]
        v, exact = report._quantile(buckets, 0.5)
        assert exact and 0 < v <= 1.0

    def test_all_in_overflow_bucket_inexact(self):
        buckets = [("1.0", 0), ("+Inf", 7)]
        v, exact = report._quantile(buckets, 0.99)
        assert not exact and v == 1.0

    def test_requests_view_empty_rows_no_crash(self):
        out = report.requests_view([])
        assert out["requests"] == 0 and out["tail_requests"] == 0
        assert out["ttft_ms"]["p99"] is None


# -- lint wiring -----------------------------------------------------------

@pytest.mark.lint
class TestLintWiring:
    def test_flight_modules_lint_clean_baseline_empty(self):
        from paddle_tpu.analysis import runner
        findings = runner.run_passes(
            paths=["paddle_tpu/observability/flight.py",
                   "paddle_tpu/observability/watch.py",
                   "paddle_tpu/observability/doctor.py",
                   "paddle_tpu/inference/serving.py",
                   "paddle_tpu/inference/router.py",
                   "paddle_tpu/hapi/model.py"],
            passes=["concurrency", "host-sync", "tracer-safety"])
        assert findings == []
        base = os.path.join(REPO, "tools", "lint_baseline.json")
        with open(base, encoding="utf-8") as f:
            assert not json.load(f)["findings"]

    def test_registry_lints_clean_tree(self):
        from paddle_tpu.analysis import runner
        findings = runner.run_passes(
            passes=["metrics-registry", "guardian-log"])
        assert findings == []

    def test_watch_table_lint_catches_drift(self, tmp_path):
        from paddle_tpu.analysis.registry_lints import MetricNamesPass
        doc = tmp_path / "obs.md"
        doc.write_text(
            "## Watch rules\n\n"
            "| rule | signal | trips when |\n| --- | --- | --- |\n"
            "| `slo_burn` | `wrong signal` | `wrong condition` |\n")
        p = MetricNamesPass()
        findings = p._check_watch_table(str(doc))
        codes = {f.code for f in findings}
        assert codes == {"watch-rule-drift"}
        drift = [f for f in findings if "slo_burn" in f.message]
        assert drift                 # row drifted from WATCH_RULES
        # the 6 other rules are reported undocumented
        assert sum("undocumented" in f.message for f in findings) == 6
        # a doc with no section at all is itself a finding
        nosec = tmp_path / "nosec.md"
        nosec.write_text("# nothing here\n")
        assert any(f.detail == "missing-table"
                   for f in p._check_watch_table(str(nosec)))
        # the real doc is clean
        real = os.path.join(REPO, "docs", "observability.md")
        assert p._check_watch_table(real) == []
