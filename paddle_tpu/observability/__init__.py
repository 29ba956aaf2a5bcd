"""Unified telemetry layer: framework-wide metrics registry, run
timeline, and zero-sync hot-path instrumentation.

The repo grew three disjoint telemetry streams — profiler host spans
(``paddle_tpu.profiler``), the guardian structured log
(``framework.guardian``), and one-shot bench scripts.  This package is
the fourth piece that makes them ONE picture:

- :mod:`.metrics` — process-wide Counter/Gauge/Histogram registry with
  labels, recorded from every hot layer (hapi fit stepper, serving
  engine/scheduler, collectives, TCPStore client, dataloader,
  checkpoint I/O);
- :mod:`.catalog` — the declared metric names (``pt_<subsystem>_...``),
  lint-checked against docs/tests by the ``metrics-registry`` pass the
  same way guardian events are;
- :mod:`.export` — Prometheus text exposition + JSONL sink
  (``PADDLE_METRICS_LOG``, the guardian-log pattern);
- :mod:`.timeline` — the merged chrome trace overlaying metric samples
  and guardian events onto the profiler's host spans on one clock;
- :mod:`.report` — ``python -m paddle_tpu.observability report``
  renders a run summary from the sinks (``--device`` gives device
  time by named scope from a profiler trace; ``--requests`` summarizes
  the per-request lanes);
- :mod:`.compilestats` — compile telemetry per jit surface (analytical
  FLOPs/bytes/footprint from the lowering, compile counts + wall, the
  ``compile_retrace`` guardian sentinel on budget overrun);
- :mod:`.tracing` — the span ring: request-scoped serving traces
  booked at the engine's existing chunk-boundary sync, the program
  spans of ``Model.fit`` and ``ServingEngine.step`` (``region``), and
  the vocabulary of named scopes in the device programs (``SCOPES``).

THE design constraint (machine-checked: this package sits in
``analysis.allowlist.MONITORED_MODULES``, and the instrumented call
sites live in modules the host-sync pass already monitors): recording
adds **zero host syncs on jit surfaces**.  In-jit quantities accumulate
device-side and are drained only at pre-existing sync points — the
stepper's per-step loss readback, the serving engine's one bundled
``device_get`` per chunk; every recorded value is a host number the
call site already owned.  ``tests/test_observability.py`` additionally
A/B-counts device transfers with telemetry on vs off (the guardian
``_host_bool``-shim pattern) to pin the contract at runtime.

Import-light: ``from paddle_tpu import observability`` pulls stdlib
only; exporters/timeline import numpy/profiler lazily on use.
"""

from .metrics import (    # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, get_registry,
    inc, observe, set_gauge, enabled, enable, disabled,
    start_capture, stop_capture, capture_active, samples, clock_pair,
    DEFAULT_BUCKETS,
)
from .catalog import METRICS    # noqa: F401
# compile telemetry + request tracing (ISSUE 10): both import-light
# (stdlib + the metrics registry; jax is touched lazily on use)
from . import compilestats     # noqa: F401
from . import tracing          # noqa: F401
# flight recorder + SLO watchdog (ISSUE 13): rolling windows recorded
# at existing sync points, anomaly-triggered forensic bundles; the
# `doctor` CLI (doctor.py) loads lazily like report.py
from . import flight           # noqa: F401
from . import watch            # noqa: F401
# HBM memory ledger (ISSUE 20): static per-surface memory_analysis +
# live-buffer census/OOM forecast; import-light (jax loads lazily
# inside the census)
from . import memory           # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "inc", "observe", "set_gauge", "enabled", "enable", "disabled",
    "start_capture", "stop_capture", "capture_active", "samples",
    "clock_pair", "DEFAULT_BUCKETS", "METRICS", "main",
    "compilestats", "tracing", "flight", "watch", "memory",
]


def main(argv=None):
    """CLI entry (``python -m paddle_tpu.observability``)."""
    from .report import main as _main
    return _main(argv)
