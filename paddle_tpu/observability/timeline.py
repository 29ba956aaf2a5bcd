"""One run, one timeline: merge profiler host spans, guardian events
and captured metric samples into a single chrome://tracing JSON.

Three telemetry streams exist with two clock bases:

- profiler host spans (``RecordEvent``) and metric capture samples are
  stamped with ``time.perf_counter_ns`` (CLOCK_MONOTONIC on Linux —
  the same base the native C++ tracer's steady_clock uses, see
  ``profiler.Profiler.export``);
- guardian events are stamped with wall ``time.time_ns`` (they must be
  mergeable across processes).

The merge converts guardian timestamps onto the perf_counter axis via
the (wall_ns, perf_ns) pair captured at
:func:`metrics.start_capture` (minted on the fly if no capture ran —
both clocks tick at the same rate, so the offset is all that matters).

Event mapping:

- host spans  -> ``"ph": "X"`` duration events (tid 0, the span track)
- guardian    -> ``"ph": "i"`` instants (tid 1, full args attached)
- samples     -> ``"ph": "C"`` counters (one track per metric+labels)
- request traces (``tracing.py``) -> one LANE per request (tid 100+,
  named by trace id): ``"X"`` spans for queue_wait/prefill/decode,
  ``"i"`` instants for page evictions — already on the perf clock;
- program spans of the same ring (``fit.*``, ``serving.*``) -> ``"X"``
  events with ``"cat": "program"`` on tid 2, ``id``/``parent`` in args.
"""
import json
import os
import time

from . import metrics as _metrics
from . import tracing as _tracing

__all__ = ["merged_trace_events", "export_chrome_trace"]

PID = 0
TID_SPANS = 0
TID_GUARDIAN = 1
TID_PROGRAM = 2         # the loops' own spans (fit.*, serving.*)
TID_REQUESTS = 100      # first per-request lane

# fallback (wall_ns, perf_ns) pair when no metric capture ran: minted
# ONCE and reused for every subsequent export — a fresh pair per call
# would give each export a slightly different offset and skew guardian
# instants across merged traces of the same run
_FALLBACK_PAIR = [None]


def _clock_pair():
    pair = _metrics.clock_pair()
    if pair is not None:
        return pair
    if _FALLBACK_PAIR[0] is None:
        _FALLBACK_PAIR[0] = (time.time_ns(), time.perf_counter_ns())
    return _FALLBACK_PAIR[0]


def _guardian_to_perf_ns(ts_ns, pair):
    wall0, perf0 = pair
    return ts_ns - wall0 + perf0


def merged_trace_events(include_profiler=True, include_guardian=True,
                        include_samples=True, include_requests=True):
    """Build the merged chrome traceEvents list (timestamps in µs on
    the perf_counter axis)."""
    events = [
        {"name": "process_name", "ph": "M", "pid": PID,
         "args": {"name": "paddle_tpu run"}},
        {"name": "thread_name", "ph": "M", "pid": PID, "tid": TID_SPANS,
         "args": {"name": "host spans"}},
        {"name": "thread_name", "ph": "M", "pid": PID,
         "tid": TID_GUARDIAN, "args": {"name": "guardian events"}},
        {"name": "thread_name", "ph": "M", "pid": PID,
         "tid": TID_PROGRAM, "args": {"name": "program spans"}},
    ]
    if include_profiler:
        from ..profiler import _collect_events
        for e in _collect_events():
            events.append({
                "name": e.name, "cat": str(e.event_type), "ph": "X",
                "ts": e.start / 1e3, "dur": (e.end - e.start) / 1e3,
                "pid": PID, "tid": TID_SPANS})
    if include_guardian:
        from ..framework.guardian import events as guardian_events
        pair = _clock_pair()
        for rec in guardian_events():
            events.append({
                "name": rec["event"], "cat": "guardian", "ph": "i",
                "s": "g",
                "ts": _guardian_to_perf_ns(rec["ts_ns"], pair) / 1e3,
                "pid": PID, "tid": TID_GUARDIAN, "args": dict(rec)})
    if include_requests:
        if _tracing.dropped_spans():
            # ring overflow: the oldest lanes below are incomplete —
            # stamp it into the trace so a reader can tell
            events.append({
                "name": "tracing_dropped_spans", "ph": "M", "pid": PID,
                "args": {"count": _tracing.dropped_spans()}})
        lanes = {}
        for s in _tracing.spans():
            if _tracing.is_program_span(s["phase"]):
                events.append({
                    "name": s["phase"], "cat": "program", "ph": "X",
                    "ts": s["start_ns"] / 1e3,
                    "dur": (s["end_ns"] - s["start_ns"]) / 1e3,
                    "pid": PID, "tid": TID_PROGRAM,
                    "args": {"trace": s["trace"], "req_id": s["req_id"],
                             "id": s["id"], "parent": s["parent"],
                             **s["args"]}})
                continue
            tid = lanes.get(s["trace"])
            if tid is None:
                tid = lanes[s["trace"]] = TID_REQUESTS + len(lanes)
                events.append({
                    "name": "thread_name", "ph": "M", "pid": PID,
                    "tid": tid, "args": {"name": f"req {s['trace']}"}})
            args = {"trace": s["trace"], "req_id": s["req_id"],
                    "phase": s["phase"], "parent": s["parent"],
                    **s["args"]}
            if s["end_ns"] > s["start_ns"]:
                events.append({
                    "name": s["phase"], "cat": "request", "ph": "X",
                    "ts": s["start_ns"] / 1e3,
                    "dur": (s["end_ns"] - s["start_ns"]) / 1e3,
                    "pid": PID, "tid": tid, "args": args})
            else:
                events.append({
                    "name": s["phase"], "cat": "request", "ph": "i",
                    "s": "t", "ts": s["start_ns"] / 1e3,
                    "pid": PID, "tid": tid, "args": args})
    if include_samples:
        for s in _metrics.samples():
            labels = s["labels"]
            name = s["metric"]
            if labels:
                name += "{" + ",".join(
                    f"{k}={v}" for k, v in sorted(labels.items())) + "}"
            events.append({
                "name": name, "cat": "metric", "ph": "C",
                "ts": s["ts_perf_ns"] / 1e3, "pid": PID,
                "args": {"value": s["value"]}})
        # memory counter tracks from the census history — already on
        # the perf clock, one track per pool plus the occupancy /
        # headroom / forecast gauges (covers censuses taken outside a
        # metric capture window)
        from . import memory as _memory
        for rec in _memory.history():
            ts = rec["perf_ns"] / 1e3
            for pool, v in rec["pools"].items():
                events.append({
                    "name": f"pt_memory_live_bytes{{pool={pool}}}",
                    "cat": "memory", "ph": "C", "ts": ts, "pid": PID,
                    "args": {"value": v}})
            for key, metric in (
                    ("kv_occupancy", "pt_memory_kv_occupancy"),
                    ("kv_headroom_bytes", "pt_memory_kv_headroom_bytes"),
                    ("steps_to_exhaustion",
                     "pt_memory_steps_to_exhaustion")):
                v = rec.get(key)
                if v is not None:
                    events.append({
                        "name": metric, "cat": "memory", "ph": "C",
                        "ts": ts, "pid": PID, "args": {"value": v}})
    events.sort(key=lambda e: (e.get("ts", -1), e["ph"]))
    return events


def export_chrome_trace(path, include_profiler=True,
                        include_guardian=True, include_samples=True,
                        include_requests=True):
    """Write the merged timeline as chrome://tracing / Perfetto JSON."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    data = {"traceEvents": merged_trace_events(
        include_profiler, include_guardian, include_samples,
        include_requests),
        "displayTimeUnit": "ms"}
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(data, f)
    os.replace(tmp, path)
    return path
