"""Flight recorder: always-on rolling telemetry windows with
anomaly-triggered forensic bundle dumps.

The continuous-profiling model (Google-Wide Profiling): keep a bounded,
cheap record of the recent past *in process*, and when the watchdog
(``watch.py``) trips, persist everything an engineer (or ``doctor``)
needs to diagnose the anomaly — after the fact, from one directory.

One :class:`FlightRecorder` (module singleton, ``enable()`` /
``PADDLE_FLIGHT=1``) keeps a rolling window of **samples**: small host
dicts recorded ONLY at pre-existing sync points —

- the hapi fit stepper's post-step (``point="fit_step"``),
- the serving engine's one-``device_get``-per-chunk sync
  (``"serving_sync"`` plus one ``"request"`` sample per finish), and
- the fleet router's dispatch gap (``"router_gap"``).

Every value recorded is a host number the call site already owned, so
the zero-new-host-sync A/B contract extends to the recorder verbatim
(asserted by ``tests/test_flight_watchdog.py``); when no recorder is
installed each hook site pays one truthiness check
(:func:`active`, the failpoints/guardian discipline).

Each sample runs through the :class:`~.watch.WatchEngine`; a rule trip
emits a guardian ``watch_alert`` event, ticks ``pt_watch_alerts_total``,
and — when ``PADDLE_FLIGHT_DIR`` (or ``dump_dir=``) names a directory —
writes a **forensic bundle**: the windowed samples, a registry metrics
snapshot, the guardian event ring, the merged chrome trace (request
lanes included), the compile-telemetry snapshot, the rule verdicts and
the config/env, all under one ``bundle_<ts>_<rule>/`` directory.
Bundles are written atomically (dot-tmp dir + ``os.rename``) with
keep-last-K retention, on a daemon dump thread so the hot loop never
blocks on file I/O (``dump_async=False`` forces inline dumps for
deterministic tests).  ``python -m paddle_tpu.observability doctor
<bundle>`` turns a bundle into a ranked probable-cause diagnosis.
"""
import collections
import json
import logging
import os
import shutil
import threading
import time

from . import metrics as _metrics

__all__ = ["FlightRecorder", "active", "recorder", "record", "enable",
           "disable", "FLIGHT_ENV", "FLIGHT_DIR_ENV", "BUNDLE_FILES"]

_logger = logging.getLogger("paddle_tpu.flight")

FLIGHT_ENV = "PADDLE_FLIGHT"
FLIGHT_DIR_ENV = "PADDLE_FLIGHT_DIR"

# one bundle = these files, exactly (doctor.load_bundle and the docs
# list them; tests assert the set)
BUNDLE_FILES = ("meta.json", "window.jsonl", "metrics.jsonl",
                "guardian.jsonl", "trace.json", "compilestats.json",
                "memory.jsonl")

# env prefixes worth snapshotting into a bundle's meta (knobs that
# change framework behavior; values are configuration, never secrets)
_ENV_PREFIXES = ("PADDLE_", "JAX_", "XLA_")


class FlightRecorder:
    """Bounded rolling sample window + watchdog + forensic dumps.

    Thread model: ``record()`` is called from any hot thread (fit loop,
    replica workers, the router loop) and serializes window/watch state
    under ``self._lock``; bundle dumps run on a lazily-started daemon
    worker so file I/O never blocks a sync point (the declared
    cross-thread surface — see ``CONCURRENT_CLASSES``)."""

    def __init__(self, dump_dir=None, window=512, keep=4, watch=None,
                 config=None, dump_async=True, dump_cooldown_s=30.0):
        """``dump_dir=None`` reads ``PADDLE_FLIGHT_DIR``; pass
        ``dump_dir=False`` to force alerts-only (no bundle dumps even
        when the env names a directory — bench's timed passes use this
        so file I/O can never perturb a measurement)."""
        if keep < 1:
            raise ValueError("keep must be >= 1")
        self.dump_dir = dump_dir if dump_dir is not None \
            else os.environ.get(FLIGHT_DIR_ENV)
        if dump_dir is False:
            self.dump_dir = None
        self.keep = int(keep)
        self.dump_cooldown_s = float(dump_cooldown_s)
        if watch is None:
            from .watch import WatchEngine
            watch = WatchEngine(config)
        elif config is not None:
            raise ValueError("pass watch= or config=, not both")
        self._watch = watch
        self._window = collections.deque(maxlen=int(window))
        self._lock = threading.Lock()
        self._jobs = collections.deque()
        self._job_ready = threading.Event()
        self._thread = None
        self._closed = False
        self._last_dump = None
        self._dump_async = bool(dump_async)
        self._dumps = []

    # -- recording ---------------------------------------------------------
    def record(self, point, **values):
        """Append one sample (host values only — the caller already
        owned every number here) and run the watchdog over it."""
        sample = {"ts_ns": time.time_ns(), "point": str(point)}
        sample.update(values)
        with self._lock:
            self._window.append(sample)
            n = len(self._window)
            alerts = self._watch.evaluate(sample) if self._watch else []
        if _metrics.enabled():
            _metrics.set_gauge("pt_flight_samples", n)
            _metrics.inc("pt_watch_evals_total")
        if alerts:
            self._trip(alerts)
        return alerts

    def samples(self):
        """Snapshot of the rolling window, oldest first."""
        with self._lock:
            return list(self._window)

    def dumps(self):
        """Paths of bundles written by this recorder, oldest first."""
        with self._lock:
            return list(self._dumps)

    @property
    def watch(self):
        return self._watch

    # -- tripping ----------------------------------------------------------
    def _trip(self, alerts):
        from ..framework import guardian
        for a in alerts:
            guardian.emit("watch_alert", rule=a["rule"],
                          value=a["value"], threshold=a["threshold"],
                          detail=a["detail"], point=a["point"])
            if _metrics.enabled():
                _metrics.inc("pt_watch_alerts_total", rule=a["rule"])
        if not self.dump_dir:
            return
        now = time.perf_counter()
        with self._lock:
            if self._last_dump is not None and \
                    now - self._last_dump < self.dump_cooldown_s:
                return                      # one bundle per incident
            self._last_dump = now
            if self._dump_async:
                self._jobs.append(list(alerts))
        if self._dump_async:
            self._ensure_thread()
            self._job_ready.set()
        else:
            self._dump_safe(list(alerts))

    # -- the dump ----------------------------------------------------------
    def dump(self, alerts=(), trigger=None):
        """Write one forensic bundle NOW (atomic tmp+rename, keep-last-K
        retention); returns the bundle path.  Callable directly for a
        manual snapshot (``trigger="manual"``)."""
        t0 = time.perf_counter()
        if not self.dump_dir:
            raise ValueError(
                "no dump directory configured — construct the recorder "
                "with dump_dir=... or set PADDLE_FLIGHT_DIR (this "
                "recorder is alerts-only)")
        alerts = list(alerts)
        trigger = trigger or (alerts[0]["rule"] if alerts else "manual")
        with self._lock:
            window = list(self._window)
            verdicts = self._watch.state_summary() if self._watch \
                else None
            cfg = self._watch.config.summary() if self._watch else None
        from ..framework import guardian
        from . import compilestats, export, timeline
        d = self.dump_dir
        os.makedirs(d, exist_ok=True)
        name = f"bundle_{time.time_ns()}_{trigger}"
        tmp = os.path.join(d, "." + name + ".tmp")
        os.makedirs(tmp)
        meta = {
            "trigger": trigger, "ts_ns": time.time_ns(),
            "alerts": alerts, "verdicts": verdicts, "config": cfg,
            "window_samples": len(window),
            "env": {k: v for k, v in sorted(os.environ.items())
                    if k.startswith(_ENV_PREFIXES)},
        }
        with open(os.path.join(tmp, "meta.json"), "w",
                  encoding="utf-8") as f:
            json.dump(meta, f, indent=1, sort_keys=True)
        with open(os.path.join(tmp, "window.jsonl"), "w",
                  encoding="utf-8") as f:
            for s in window:
                f.write(json.dumps(s) + "\n")
        with open(os.path.join(tmp, "metrics.jsonl"), "w",
                  encoding="utf-8") as f:
            for rec in export.snapshot(run="flight"):
                f.write(json.dumps(rec) + "\n")
        with open(os.path.join(tmp, "guardian.jsonl"), "w",
                  encoding="utf-8") as f:
            for rec in guardian.events():
                f.write(json.dumps(rec) + "\n")
        with open(os.path.join(tmp, "trace.json"), "w",
                  encoding="utf-8") as f:
            json.dump({"traceEvents": timeline.merged_trace_events(),
                       "displayTimeUnit": "ms"}, f)
        with open(os.path.join(tmp, "compilestats.json"), "w",
                  encoding="utf-8") as f:
            json.dump(compilestats.snapshot(), f, indent=1,
                      sort_keys=True)
        from . import memory as _memory
        with open(os.path.join(tmp, "memory.jsonl"), "w",
                  encoding="utf-8") as f:
            for rec in _memory.ledger_records():
                f.write(json.dumps(rec) + "\n")
        final = os.path.join(d, name)
        os.rename(tmp, final)               # atomic publish
        kept = self._retain(d)
        with self._lock:
            self._dumps.append(final)
        guardian.emit("flight_dump", trigger=trigger, path=final,
                      alerts=len(alerts), kept=kept)
        if _metrics.enabled():
            _metrics.inc("pt_flight_dumps_total")
            _metrics.observe("pt_flight_dump_ms",
                             (time.perf_counter() - t0) * 1e3)
        return final

    def _retain(self, d):
        """Keep-last-K sweep; returns the surviving bundle count."""
        bundles = sorted(n for n in os.listdir(d)
                         if n.startswith("bundle_")
                         and os.path.isdir(os.path.join(d, n)))
        for stale in bundles[:-self.keep]:
            shutil.rmtree(os.path.join(d, stale), ignore_errors=True)
        return min(len(bundles), self.keep)

    def _dump_safe(self, alerts):
        try:
            self.dump(alerts)
        except Exception as e:      # a failed dump must never take the
            _logger.warning("flight bundle dump failed: %r", e)  # run down

    # -- dump worker -------------------------------------------------------
    def _ensure_thread(self):
        with self._lock:
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._dump_loop, name="flight-dump",
                    daemon=True)
                self._thread.start()

    def _dump_loop(self):
        while True:
            self._job_ready.wait(0.1)
            self._job_ready.clear()
            while True:
                with self._lock:
                    job = self._jobs.popleft() if self._jobs else None
                if job is None:
                    break
                self._dump_safe(job)
            with self._lock:
                if self._closed and not self._jobs:
                    return

    def flush(self, timeout=10.0):
        """Block until queued bundle dumps have landed (tests)."""
        deadline = time.perf_counter() + timeout
        while time.perf_counter() < deadline:
            with self._lock:
                if not self._jobs:
                    return True
            self._job_ready.set()
            time.sleep(0.01)
        return False

    def close(self):
        """Drain pending dumps and stop the worker."""
        self.flush()
        with self._lock:
            self._closed = True
            t = self._thread
        self._job_ready.set()
        if t is not None and t.is_alive():
            t.join(timeout=5.0)


# -- module singleton -------------------------------------------------------

_RECORDER = [None]


def active():
    """One truthiness check — the whole hot-path cost when no recorder
    is installed (the hook sites gate on this)."""
    return _RECORDER[0] is not None


def recorder():
    """The installed recorder, or None."""
    return _RECORDER[0]


def record(point, **values):
    """Record one sample into the installed recorder (no-op when none
    is installed — but prefer gating call sites on :func:`active`)."""
    r = _RECORDER[0]
    if r is not None:
        return r.record(point, **values)
    return []


def enable(dump_dir=None, **kwargs):
    """Install a fresh :class:`FlightRecorder` as THE process recorder
    (replacing and closing any previous one); returns it."""
    r = FlightRecorder(dump_dir=dump_dir, **kwargs)
    prev, _RECORDER[0] = _RECORDER[0], r
    if prev is not None:
        prev.close()
    return r


def disable():
    """Uninstall (and close) the process recorder."""
    prev, _RECORDER[0] = _RECORDER[0], None
    if prev is not None:
        prev.close()


if os.environ.get(FLIGHT_ENV, "").lower() in ("1", "true", "yes", "on"):
    enable()        # always-on via env, dump dir from PADDLE_FLIGHT_DIR
