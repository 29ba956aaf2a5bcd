"""The reader of the program's spans (``benchmark/readers/program_spans.py``)
on hand-made spans: window clipping, the nearest-rank p90, the gap
between spans of consecutive steps, and nothing where spans were dropped
or the program books none.  Nothing in this file loads libtpu.
"""
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402
from benchmark.readers import program_spans  # noqa: E402
from paddle_tpu.observability import tracing  # noqa: E402

MS = 1_000_000


def span(trace, req_id, phase, start_ms, end_ms):
    return {"trace": trace, "req_id": req_id, "phase": phase,
            "start_ns": start_ms * MS, "end_ns": end_ms * MS, "args": {}}


def steps(trace, first, count, at_ms, step_ms=100, gap_ms=10):
    """``count`` fit steps from ``at_ms``: dispatch 4 ms, readback to the
    end of the step, then ``gap_ms`` of host time to the next dispatch's
    start."""
    out = []
    for i in range(count):
        t = at_ms + i * (step_ms + gap_ms)
        out.append(span(trace, first + i, "fit.dispatch", t, t + 4))
        out.append(span(trace, first + i, "fit.readback", t + 4, t + step_ms))
    return out


def test_durations_keep_spans_that_start_inside_the_window():
    spans = [span("a", 0, "fit.dispatch", 90, 104),     # starts before
             span("a", 1, "fit.dispatch", 100, 103),
             span("a", 2, "fit.dispatch", 150, 159),
             span("a", 3, "fit.dispatch", 199, 230),    # ends after: kept
             span("a", 4, "fit.dispatch", 200, 201),    # starts at the end
             span("a", 2, "fit.readback", 160, 190)]    # another phase
    values = program_spans.durations(spans, 100 * MS, 200 * MS, "fit.dispatch")
    assert values == [3, 9, 31]
    assert program_spans.reduce(
        spans, 100 * MS, 200 * MS,
        {"phase": "fit.dispatch", "stat": "median"}) == 9
    assert program_spans.reduce(
        spans, 300 * MS, 400 * MS,
        {"phase": "fit.dispatch", "stat": "median"}) is None


def test_p90_is_nearest_rank_over_requests_submitted_in_the_window():
    spans = []
    for i in range(20):                   # queue waits of 1..20 ms
        spans.append(span(f"r{i}", i, "queue_wait", 100 + i, 101 + 2 * i))
        spans.append(span(f"r{i}", i, "prefill", 101 + 2 * i, 600 + i))
    # a request evicted and admitted again: only its FIRST spans count
    spans.append(span("r3", 3, "queue_wait", 700, 900))
    spans.append(span("r3", 3, "prefill", 900, 2000))
    # submitted before the window opened
    spans.insert(0, span("early", 99, "queue_wait", 50, 450))
    spans.insert(1, span("early", 99, "prefill", 450, 460))
    # submitted in the window, not yet admitted when it was read
    spans.append(span("late", 98, "queue_wait", 110, 112))
    t0, t1 = 100 * MS, 1000 * MS
    waits = {"phase": "queue_wait", "of_requests_with": "queue_wait",
             "stat": "p90"}
    assert sorted(program_spans.durations(
        spans, t0, t1, "queue_wait", "queue_wait"))[:3] == [1, 2, 2]
    # 21 values (1..20 from r0..r19, and 2 from "late"): the nearest
    # rank of 90% is the 19th, which is 18
    assert program_spans.reduce(spans, t0, t1, waits) == 18
    first_token = dict(waits, phase="prefill")
    values = program_spans.durations(spans, t0, t1, "prefill", "queue_wait")
    assert len(values) == 20 and max(values) == 499      # no "late", no 1100
    assert program_spans.reduce(spans, t0, t1, first_token) == \
        sorted(values)[17]


def test_gap_runs_from_one_steps_readback_to_the_next_steps_dispatch():
    spans = steps("fit-a", 0, 5, at_ms=1000, gap_ms=10) \
        + steps("fit-b", 0, 3, at_ms=5000, gap_ms=30)
    params = {"gap": {"from_end_of": "fit.readback",
                      "to_end_of": "fit.dispatch"}, "stat": "median"}
    # gap = host time between the steps + the next dispatch (4 ms)
    assert program_spans.gaps(spans, 0, 10_000 * MS, "fit.readback",
                              "fit.dispatch") == [14] * 4 + [34] * 2
    # only trace b's first readback starts inside this window
    assert program_spans.reduce(spans, 5000 * MS, 5100 * MS, params) == 34
    # the last step of a trace has no next one; another trace's step 0 is
    # not "the next step"
    assert program_spans.reduce(spans, 1400 * MS, 1600 * MS, params) is None


def _run(setup_s=2.0, window_s=1.0, started=10.0):
    run = types.SimpleNamespace(started=started, setup_s=setup_s,
                                obs={"window_s": window_s})
    return run


def test_read_takes_the_window_from_the_run_and_none_on_dropped_spans(
        monkeypatch):
    t0_ms = 12_000                         # (started + setup_s) in ms
    ring = steps("fit-w", 0, 3, at_ms=t0_ms - 300) \
        + steps("fit-w", 3, 6, at_ms=t0_ms + 30, gap_ms=20)
    monkeypatch.setattr(tracing, "spans", lambda: ring)
    monkeypatch.setattr(tracing, "dropped_spans", lambda: 0)
    gap = {"gap": {"from_end_of": "fit.readback",
                   "to_end_of": "fit.dispatch"}, "stat": "median"}
    assert program_spans.read(_run(), gap) == 24
    assert program_spans.read(
        _run(), {"phase": "fit.dispatch", "stat": "median"}) == 4
    # before the window is measured there is nothing to read
    assert program_spans.read(_run(setup_s=None), gap) is None
    run = _run()
    run.obs = {}
    assert program_spans.read(run, gap) is None
    # a ring that overflowed has lost the oldest spans: no number
    monkeypatch.setattr(tracing, "dropped_spans", lambda: 3)
    assert program_spans.read(_run(), gap) is None


def test_a_program_without_these_spans_reads_nothing(monkeypatch):
    """What the parent commit gives: request spans only, no ``fit.*``
    or ``serving.*`` in the ring."""
    ring = [span("r0", 0, "queue_wait", 12_100, 12_150),
            span("r0", 0, "prefill", 12_150, 12_700)]
    monkeypatch.setattr(tracing, "spans", lambda: ring)
    monkeypatch.setattr(tracing, "dropped_spans", lambda: 0)
    for name in ("fit.host_gap_ms", "fit.dispatch_ms", "serve.host_gap_ms"):
        spec = harness.load_json(ROOT, "benchmark", "metrics", name + ".json")
        assert spec["reader"] == "program_spans"
        assert program_spans.read(_run(), spec["params"]) is None
    spec = harness.load_json(ROOT, "benchmark", "metrics",
                             "serve.queue_wait_p90_ms.json")
    assert program_spans.read(_run(), spec["params"]) == 50


@pytest.mark.parametrize("cell,names", [
    ("fit-gpt3-medium", {"fit.host_gap_ms", "fit.dispatch_ms"}),
    ("chat-gpt3-xl", {"serve.queue_wait_p90_ms",
                      "serve.first_token_wait_p90_ms", "serve.host_gap_ms"})])
def test_the_manifest_lists_the_new_metrics_in_their_cells(cell, names):
    manifest = harness.load_manifest(ROOT)
    listed = {m["name"]: m for m in
              harness.cell_metrics(manifest, cell, "per_layer")}
    assert names <= set(listed)
    for name in names:
        assert listed[name]["source"] == "program_counter"
        assert listed[name]["unit"] == "ms"
        assert listed[name]["workloads"] == [cell]
