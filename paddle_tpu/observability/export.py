"""Metric exporters: Prometheus text exposition + JSONL snapshot sink.

Two sink shapes, same registry snapshot:

- :func:`write_prometheus` — the text exposition format scrapers and
  dashboards already speak (``# HELP`` / ``# TYPE`` headers, cumulative
  ``_bucket{le=...}`` histogram series);
- :func:`write_jsonl` — one JSON object per series appended to a file,
  the guardian-log pattern: ``PADDLE_METRICS_LOG`` names a default sink
  the way ``PADDLE_GUARDIAN_LOG`` does, lines are self-describing and
  greppable, and ``python -m paddle_tpu.observability report``
  summarizes them.

Exporters run OFF the hot path (end of a bench config, end of a run,
test teardown).  :func:`_materialize` is the one budgeted place a
device scalar handed to a gauge may legally sync (mirroring
``guardian._host_bool``: a single named funnel the host-sync lint
budgets, instead of ad-hoc readbacks).
"""
import json
import os
import threading
import time

import numpy as np

from . import metrics as _metrics

__all__ = ["prometheus_text", "write_prometheus", "snapshot",
           "write_jsonl", "JSONL_ENV"]

JSONL_ENV = "PADDLE_METRICS_LOG"

# serializes same-process writers: two threads replace_run-rewriting
# one file would otherwise race read-rewrite-replace and silently drop
# each other's freshly appended run (cross-process writers remain the
# caller's problem — see the write_jsonl docstring)
_WRITE_LOCK = threading.Lock()


def _materialize(v):
    """THE exporter-side sync funnel: collapse a (possibly device)
    scalar to a host float exactly once, at export time — never on the
    recording path.  Budgeted in ``analysis.allowlist``."""
    if isinstance(v, (int, float)):
        return float(v)
    return float(np.asarray(v))


def _esc(s):
    return str(s).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _labelstr(labels, extra=None):
    items = list(labels.items()) + (list(extra.items()) if extra else [])
    if not items:
        return ""
    body = ",".join(f'{k}="{_esc(v)}"' for k, v in items)
    return "{" + body + "}"


def _fmt(v):
    v = _materialize(v)
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


def prometheus_text(registry=None):
    """Render the registry in Prometheus text exposition format."""
    reg = registry if registry is not None else _metrics.get_registry()
    lines = []
    for m in reg.collect():
        if not m["series"]:
            continue
        lines.append(f"# HELP {m['name']} {_esc(m['help'])}")
        lines.append(f"# TYPE {m['name']} {m['type']}")
        for s in m["series"]:
            if m["type"] == "histogram":
                cum = 0
                for le, c in zip(list(m["buckets"]) + ["+Inf"],
                                 s["counts"]):
                    cum += c
                    le_s = le if le == "+Inf" else _fmt(le)
                    lines.append(
                        f"{m['name']}_bucket"
                        f"{_labelstr(s['labels'], {'le': le_s})} {cum}")
                lines.append(f"{m['name']}_sum{_labelstr(s['labels'])} "
                             f"{_fmt(s['sum'])}")
                lines.append(f"{m['name']}_count{_labelstr(s['labels'])} "
                             f"{s['count']}")
            else:
                lines.append(f"{m['name']}{_labelstr(s['labels'])} "
                             f"{_fmt(s['value'])}")
    return "\n".join(lines) + ("\n" if lines else "")


def write_prometheus(path, registry=None):
    """Atomically write the exposition file (scrape-safe: a reader
    never sees a torn snapshot)."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        f.write(prometheus_text(registry))
    os.replace(tmp, path)
    return path


def snapshot(registry=None, run=None):
    """Flat JSON-ready sample list: one dict per live series, stamped
    with wall-clock ``ts_ns`` (cross-process mergeable, like guardian
    events)."""
    reg = registry if registry is not None else _metrics.get_registry()
    now = time.time_ns()
    out = []
    for m in reg.collect():
        for s in m["series"]:
            rec = {"ts_ns": now, "metric": m["name"], "type": m["type"],
                   "labels": s["labels"]}
            if run is not None:
                rec["run"] = str(run)
            if m["type"] == "histogram":
                rec["count"] = s["count"]
                rec["sum"] = _materialize(s["sum"])
                rec["buckets"] = [
                    [b, c] for b, c in zip(m["buckets"], s["counts"])]
                rec["buckets"].append(["+Inf", s["counts"][-1]])
            else:
                rec["value"] = _materialize(s["value"])
            out.append(rec)
    return out


def write_jsonl(path=None, registry=None, run=None, replace_run=False):
    """Append one snapshot (one JSON line per series) to ``path``, or
    to ``$PADDLE_METRICS_LOG`` when ``path`` is None — the guardian-log
    sink pattern.  Returns the path written, or None when no sink is
    configured.

    ``replace_run=True`` (needs ``run``) makes the write idempotent per
    run id: existing records carrying the same ``run`` are dropped
    before the new snapshot lands (atomic rewrite), while records of
    *other* runs — and unparseable lines — survive untouched, so a
    per-tag snapshot file does not grow by one snapshot per invocation.

    Use ``replace_run`` only on files this process owns: same-process writers are serialized by a module
    lock (concurrent threads each land their own run intact), but the
    read-rewrite-replace cycle still races a *foreign-process* appender
    — and after the replace a live writer's open fd points at the
    unlinked old inode — so a long-lived ``PADDLE_METRICS_LOG`` sink
    shared across processes must stick to the append path.
    """
    path = path or os.environ.get(JSONL_ENV)
    if not path:
        return None
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    recs = snapshot(registry, run=run)
    with _WRITE_LOCK:
        if replace_run and run is not None and os.path.exists(path):
            kept = []
            with open(path, encoding="utf-8") as f:
                for line in f:
                    if not line.strip():
                        continue
                    try:
                        rec = json.loads(line)
                        if isinstance(rec, dict) and \
                                rec.get("run") == str(run):
                            continue
                    except ValueError:
                        pass    # torn tail: keep, never destroy data
                    kept.append(line.rstrip("\n"))
            tmp = path + ".tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                for line in kept:
                    f.write(line + "\n")
                for rec in recs:
                    f.write(json.dumps(rec) + "\n")
            os.replace(tmp, path)
            return path
        with open(path, "a", encoding="utf-8") as f:
            for rec in recs:
                f.write(json.dumps(rec) + "\n")
    return path
