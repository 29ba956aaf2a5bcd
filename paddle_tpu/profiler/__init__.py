"""Profiler (reference: python/paddle/profiler/ over C++ CUPTI tracers).

TPU-native: ``jax.profiler`` emits XLA-aware traces (TensorBoard/perfetto);
``RecordEvent`` maps to TraceAnnotation so host spans appear alongside
device ops.  Summary statistics come from the trace-event collection we
keep host-side.
"""
import time
from contextlib import contextmanager
from enum import Enum

import jax

__all__ = ["Profiler", "RecordEvent", "ProfilerTarget", "ProfilerState",
           "make_scheduler", "export_chrome_tracing", "load_profiler_result",
           "ProfilerResult", "SummaryView"]


class SummaryView(Enum):
    """reference: paddle.profiler.SummaryView — which stats table
    summary() renders."""
    DeviceView = 0
    OverView = 1
    ModelView = 2
    DistributedView = 3
    KernelView = 4
    OperatorView = 5
    MemoryView = 6
    MemoryManipulationView = 7
    UDFView = 8


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    def schedule(step):
        s = step - skip_first
        if s < 0:
            return ProfilerState.CLOSED
        total = closed + ready + record
        if repeat and s >= repeat * total:
            return ProfilerState.CLOSED
        pos = s % total
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == total - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return schedule


class _HostEvent:
    __slots__ = ("name", "start", "end", "event_type")

    def __init__(self, name, start, end, event_type):
        self.name = name
        self.start = start
        self.end = end
        self.event_type = event_type


_HOST_EVENTS = []
_COLLECTING = [False]


def _native_tracer():
    from ..framework import native
    return native.get_lib()


def _collect_events():
    """Merged host spans: native C++ tracer dump + Python fallback list."""
    events = list(_HOST_EVENTS)
    lib = _native_tracer()
    if lib is not None:
        import ctypes
        import struct
        from ..framework import native
        out = ctypes.POINTER(ctypes.c_uint8)()
        n = lib.pt_tracer_dump(ctypes.byref(out))
        blob = native.take_buffer(lib, out, n)
        off = 0
        while off < len(blob):
            (nl,) = struct.unpack_from("<I", blob, off); off += 4
            name = blob[off:off + nl].decode(); off += nl
            (cl,) = struct.unpack_from("<I", blob, off); off += 4
            cat = blob[off:off + cl].decode(); off += cl
            t0, t1, _tid = struct.unpack_from("<qqq", blob, off); off += 24
            events.append(_HostEvent(name, t0, t1, cat))
    return events


def _view_of(event_type):
    """Map a host event's category to the SummaryView it renders under:
    user ``RecordEvent`` annotations (the default ``UserDefined`` type)
    belong to ``UDFView``; every other category is framework-internal
    and renders under ``OperatorView``."""
    return (SummaryView.UDFView if "UserDefined" in str(event_type)
            else SummaryView.OperatorView)


class RecordEvent:
    """Host-span annotation (reference: platform/profiler RecordEvent).
    Collected by the native C++ tracer (csrc/host_tracer.cc) when built,
    and mirrored into jax profiler traces via TraceAnnotation."""

    def __init__(self, name, event_type="UserDefined"):
        self.name = name
        self.event_type = event_type
        self._ann = None
        self._t0 = None
        self._native_h = 0

    def begin(self):
        lib = _native_tracer()
        if lib is not None:
            self._native_h = lib.pt_tracer_span_begin(
                self.name.encode(), str(self.event_type).encode())
        self._t0 = time.perf_counter_ns()
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()

    def end(self):
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._native_h:
            _native_tracer().pt_tracer_span_end(self._native_h)
            self._native_h = 0
        elif _COLLECTING[0] and self._t0 is not None:
            _HOST_EVENTS.append(_HostEvent(
                self.name, self._t0, time.perf_counter_ns(),
                self.event_type))

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 timer_only=False, record_shapes=False, profile_memory=False,
                 with_flops=False, log_dir=None):
        self._scheduler = scheduler if callable(scheduler) else (
            make_scheduler(record=scheduler[1] - scheduler[0],
                           closed=scheduler[0])
            if isinstance(scheduler, (tuple, list)) else None)
        self._on_trace_ready = on_trace_ready
        self._timer_only = timer_only
        self._log_dir = log_dir or "./profiler_log"
        self._step = 0
        self._running = False
        self._step_times = []
        self._last_step_t = None

    def start(self):
        _COLLECTING[0] = True
        _HOST_EVENTS.clear()
        lib = _native_tracer()
        if lib is not None:
            lib.pt_tracer_clear()
            lib.pt_tracer_enable(1)
        if not self._timer_only:
            try:
                jax.profiler.start_trace(self._log_dir)
                self._running = True
            except Exception:
                self._running = False
        self._last_step_t = time.perf_counter()

    def stop(self):
        _COLLECTING[0] = False
        lib = _native_tracer()
        if lib is not None:
            lib.pt_tracer_enable(0)
        if self._running:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._running = False
            # what `report --device <log_dir>` joins device events to
            from ..observability import compilestats
            compilestats.write_op_names(self._log_dir)
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._last_step_t is not None:
            self._step_times.append(now - self._last_step_t)
        self._last_step_t = now
        self._step += 1

    def step_info(self, unit=None):
        if not self._step_times:
            return ""
        import numpy as np
        arr = np.asarray(self._step_times[-10:])
        return (f"avg_step_time: {arr.mean()*1000:.2f} ms "
                f"(min {arr.min()*1000:.2f}, max {arr.max()*1000:.2f})")

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms", views=None):
        """Render the merged host-event table.

        ``views`` (a :class:`SummaryView` or list of them) filters the
        rows by the view each event maps to (see :func:`_view_of`):
        ``UDFView`` selects ``RecordEvent`` user spans (the default
        ``UserDefined`` event_type), every other category renders under
        ``OperatorView``.  Parity gaps vs the reference: this is a
        host-span profiler, so Device/Kernel/Memory*/Distributed views
        have no rows of their own — requesting only those views yields
        a header-only table (device timing lives in the jax profiler
        trace under ``log_dir``); ``OverView``/``ModelView`` are not
        separately aggregated and fold into ``OperatorView``.
        """
        if views is not None and not isinstance(views, (list, tuple)):
            views = [views]
        lines = ["------------------- Profiler Summary -------------------"]
        if views is not None:
            names = ", ".join(v.name for v in views)
            lines.append(f"views: {names}")
        by_name = {}
        for e in _collect_events():
            if views is not None and _view_of(e.event_type) not in views:
                continue
            d = by_name.setdefault(e.name, [0, 0.0])
            d[0] += 1
            d[1] += (e.end - e.start) / 1e6
        for name, (cnt, total) in sorted(by_name.items(),
                                         key=lambda kv: -kv[1][1]):
            lines.append(f"{name:<40} calls={cnt:<6} total={total:.3f}ms "
                         f"avg={total / cnt:.3f}ms")
        out = "\n".join(lines)
        print(out)
        return out

    def export(self, path=None, format="json"):
        """Write host spans as a chrome://tracing JSON (reference:
        chrometracinglogger.cc; device-side traces live in the jax
        profiler log_dir)."""
        import json as _json
        import os as _os
        path = path or _os.path.join(self._log_dir, "host_trace.json")
        _os.makedirs(_os.path.dirname(path) or ".", exist_ok=True)
        # Always merge via _collect_events: on Linux both clock bases
        # (perf_counter_ns and C++ steady_clock) are CLOCK_MONOTONIC, so
        # native and fallback spans align on one timeline.
        events = [{"name": e.name, "cat": str(e.event_type), "ph": "X",
                   "ts": e.start / 1e3, "dur": (e.end - e.start) / 1e3,
                   "pid": 0, "tid": 0} for e in _collect_events()]
        with open(path, "w") as f:
            _json.dump({"traceEvents": events}, f)
        return path

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False


def export_chrome_tracing(dir_name, worker_name=None):
    def handler(prof):
        import os as _os
        name = worker_name or f"worker_{_os.getpid()}"
        prof.export(_os.path.join(dir_name, f"{name}.json"))
    return handler


class ProfilerResult:
    """Queryable host-event collection parsed back from an exported
    chrome trace (the ``Profiler.export`` format).

    ``events`` holds :class:`_HostEvent`-shaped records — ``name``,
    ``start``/``end`` (ns, on the exporting process's
    ``perf_counter_ns`` clock), ``event_type`` (the trace ``cat``
    field).  Iteration and ``len()`` delegate to it."""

    def __init__(self, events):
        self.events = list(events)

    def __len__(self):
        return len(self.events)

    def __iter__(self):
        return iter(self.events)

    def query(self, name=None, event_type=None, view=None):
        """Events filtered by exact ``name``, exact ``event_type``
        (category string), and/or :class:`SummaryView` membership."""
        out = self.events
        if name is not None:
            out = [e for e in out if e.name == name]
        if event_type is not None:
            out = [e for e in out if str(e.event_type) == str(event_type)]
        if view is not None:
            out = [e for e in out if _view_of(e.event_type) == view]
        return list(out)


def load_profiler_result(path):
    """Parse a chrome-trace JSON written by :meth:`Profiler.export`
    back into queryable host events.

    Return contract: a :class:`ProfilerResult` whose ``.events`` hold
    one ``_HostEvent`` per complete-span (``"ph": "X"``) trace event,
    with ``start``/``end`` reconstructed in nanoseconds from the file's
    microsecond ``ts``/``dur`` (so ``export`` → ``load_profiler_result``
    round-trips names, categories and durations to µs precision on the
    same clock base).  Non-span phases — the instants and counter
    samples a merged ``observability.timeline`` trace adds — are
    skipped, as are the span ring's lanes (``"cat": "request"`` and
    ``"program"``: the ring's copies, not host profiler spans), so a
    merged trace loads as its host-span subset.  Returns
    ``None`` when ``path`` does not exist (probe-friendly, the old stub
    behavior); raises ``ValueError`` on a file that is not a chrome
    trace (no ``traceEvents``)."""
    import json as _json
    import os as _os
    if not _os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        data = _json.load(f)
    if not isinstance(data, dict) or "traceEvents" not in data:
        raise ValueError(
            f"{path} is not an exported profiler trace "
            "(missing traceEvents)")
    events = []
    for rec in data["traceEvents"]:
        if rec.get("ph") != "X" or \
                rec.get("cat") in ("request", "program"):
            continue
        start = int(round(rec.get("ts", 0) * 1e3))
        dur = int(round(rec.get("dur", 0) * 1e3))
        events.append(_HostEvent(rec.get("name", ""), start, start + dur,
                                 rec.get("cat", "UserDefined")))
    return ProfilerResult(events)
