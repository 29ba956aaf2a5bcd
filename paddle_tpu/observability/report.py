"""Run-summary renderer: ``python -m paddle_tpu.observability report``.

Reads the sinks the framework writes — a Prometheus text exposition
file, a JSONL metrics log, a merged chrome trace — and renders one
human-readable run summary: counters and gauges grouped by subsystem,
histograms with count / mean / estimated p50/p90/p99 (linear
interpolation inside the winning bucket), trace-event totals.

Focused subviews:

- ``report --device <trace dir>`` — device time from a jax profiler
  trace (``.xplane.pb``), by the named scope of the program
  (``tracing.SCOPES``), by compiled program, and the device's idle gaps
  by the program span (``fit.*``, ``serving.*``) the host was in; see
  :func:`device_view`;
- ``report --requests --trace <file>`` — fold the per-request lanes of
  a merged chrome trace back into request summaries: TTFT/TPOT
  percentiles plus the mean per-phase breakdown of the slowest-TTFT
  decile (where the tail's time went).

Both support ``--json``.  The parsers are deliberately self-contained
(stdlib only, ``--device`` aside, which reads the trace through
``jax.profiler.ProfileData``): the report must run against files
produced by an earlier process or a different machine — never against
live registry state.
"""
import argparse
import bisect
import glob
import json
import math
import os
import re
import sys

from ..device import chip as _chip

__all__ = ["parse_prometheus", "parse_jsonl", "render_report",
           "roofline_from_stats", "compile_stats_from_prom",
           "load_device_trace", "device_view", "render_device",
           "scope_of", "requests_view", "request_rows_from_trace",
           "dropped_spans_from_trace", "memory_view", "main"]

# default roofs where a caller of roofline_from_stats passes none: the
# v5e row of the one peaks table; a live caller passes the roofs of the
# device it measured on (device.chip.peaks())
DEFAULT_PEAK_FLOPS = _chip.peaks(_chip.V5E).bf16_flops
DEFAULT_HBM_BW = _chip.peaks(_chip.V5E).hbm_bytes_per_s

# fallback join for surfaces whose measured latency the sinks already
# carry: the hapi steppers map onto the step-latency histogram (one
# fit step == one dispatch of that surface).  The primary join is the
# per-surface pt_compile_dispatch_ms histogram — the bench scan-chained
# stepper runs K inner steps per dispatch, so the step histogram would
# be K-off for it.
_MEASURED_LATENCY = {
    "hapi.train_step": "pt_train_step_latency_ms",
    "hapi.train_step_comm": "pt_train_step_latency_ms",
}


# -- parsers ---------------------------------------------------------------

def _parse_labels(body):
    labels = {}
    for part in filter(None, body.split(",")):
        k, _, v = part.partition("=")
        labels[k.strip()] = v.strip().strip('"')
    return labels


def _split_sample(line):
    """``name{a="b"} 1.5`` -> (name, labels dict, float)."""
    if "{" in line:
        name, rest = line.split("{", 1)
        body, _, val = rest.rpartition("}")
        return name.strip(), _parse_labels(body), float(val)
    name, _, val = line.rpartition(" ")
    return name.strip(), {}, float(val)


def parse_prometheus(path):
    """{metric: {"type", "help", "series": {labelkey: value},
    "buckets": {labelkey: [(le, cumcount)...]}}} from an exposition
    file.  Histogram ``_bucket``/``_sum``/``_count`` samples fold back
    under the base metric name."""
    metrics = {}

    def base(name):
        for suf in ("_bucket", "_sum", "_count"):
            if name.endswith(suf) and name[:-len(suf)] in metrics:
                return name[:-len(suf)], suf
        return name, ""

    with open(path, encoding="utf-8") as f:
        for raw in f:
            line = raw.strip()
            if not line:
                continue
            if line.startswith("# TYPE "):
                _, _, rest = line.partition("# TYPE ")
                name, _, kind = rest.partition(" ")
                metrics.setdefault(name, {
                    "type": kind.strip(), "help": "",
                    "series": {}, "buckets": {}})
                continue
            if line.startswith("# HELP "):
                _, _, rest = line.partition("# HELP ")
                name, _, help_ = rest.partition(" ")
                metrics.setdefault(name, {
                    "type": "", "help": "", "series": {}, "buckets": {}})
                metrics[name]["help"] = help_
                continue
            if line.startswith("#"):
                continue
            try:
                name, labels, value = _split_sample(line)
            except ValueError:
                continue     # torn tail / foreign line: never let one
                #              bad sample hide the rest of the file
            name, suffix = base(name)
            m = metrics.setdefault(name, {"type": "", "help": "",
                                          "series": {}, "buckets": {}})
            if suffix == "_bucket":
                le = labels.pop("le", "+Inf")
                key = tuple(sorted(labels.items()))
                m["buckets"].setdefault(key, []).append((le, value))
            else:
                key = tuple(sorted(labels.items())) + \
                    ((("__sample__", suffix),) if suffix else ())
                m["series"][key] = value
    return metrics


def parse_jsonl(path):
    """List of snapshot records (newest last); bad lines are skipped
    with a count so a torn tail never hides the rest of the run."""
    recs, bad = [], 0
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                recs.append(json.loads(line))
            except ValueError:
                bad += 1
    return recs, bad


# -- rendering -------------------------------------------------------------

def _quantile(buckets, q):
    """Estimate a quantile from cumulative (le, count) pairs; returns
    (value, exact) where exact=False marks an +Inf-bucket hit."""
    if not buckets:
        return None, False
    finite = [(float(le), c) for le, c in buckets if le != "+Inf"]
    total = max(c for _, c in buckets)
    if total <= 0:
        return None, False
    target = q * total
    prev_le, prev_c = 0.0, 0.0
    for le, c in sorted(finite):
        if c >= target:
            span = c - prev_c
            frac = (target - prev_c) / span if span > 0 else 1.0
            return prev_le + (le - prev_le) * frac, True
        prev_le, prev_c = le, c
    return (max(le for le, _ in finite) if finite else None), False


def _labelkey_str(key):
    parts = [f"{k}={v}" for k, v in key if k != "__sample__"]
    return "{" + ",".join(parts) + "}" if parts else ""


def _subsystem(name):
    bits = name.split("_", 2)
    return bits[1] if len(bits) > 2 and bits[0] == "pt" else "other"


def _render_prom(metrics, lines):
    by_sub = {}
    for name, m in sorted(metrics.items()):
        by_sub.setdefault(_subsystem(name), []).append((name, m))
    for sub in sorted(by_sub):
        lines.append(f"\n[{sub}]")
        for name, m in by_sub[sub]:
            if m["type"] == "histogram" or m["buckets"]:
                for key, buckets in sorted(m["buckets"].items()):
                    skey = dict(key)
                    count = m["series"].get(
                        tuple(sorted(skey.items())) +
                        (("__sample__", "_count"),), 0)
                    total = m["series"].get(
                        tuple(sorted(skey.items())) +
                        (("__sample__", "_sum"),), 0.0)
                    mean = total / count if count else 0.0
                    qs = []
                    for q in (0.5, 0.9, 0.99):
                        v, exact = _quantile(buckets, q)
                        qs.append(f"p{int(q * 100)}"
                                  f"{'~' if exact else '>'}"
                                  f"{v:.3g}" if v is not None else
                                  f"p{int(q * 100)}=?")
                    lines.append(
                        f"  {name}{_labelkey_str(key)}  count={count:g} "
                        f"mean={mean:.3g} " + " ".join(qs))
            else:
                for key, value in sorted(m["series"].items()):
                    lines.append(
                        f"  {name}{_labelkey_str(key)}  {value:g}")


def render_report(prom=None, jsonl=None, trace=None):
    """Render the text report from whichever sinks were given."""
    lines = ["== paddle_tpu telemetry report =="]
    if prom:
        metrics = parse_prometheus(prom)
        n_series = sum(len(m["series"]) + len(m["buckets"])
                       for m in metrics.values())
        lines.append(f"prometheus: {prom} "
                     f"({len(metrics)} metrics, {n_series} series)")
        _render_prom(metrics, lines)
    if jsonl:
        recs, bad = parse_jsonl(jsonl)
        runs = sorted({r["run"] for r in recs if "run" in r})
        span_ns = (max(r["ts_ns"] for r in recs) -
                   min(r["ts_ns"] for r in recs)) if recs else 0
        lines.append(f"\njsonl: {jsonl} ({len(recs)} samples"
                     + (f", {bad} unparseable" if bad else "")
                     + (f", runs: {', '.join(runs)}" if runs else "")
                     + f", span {span_ns / 1e9:.3f}s)")
        latest = {}
        for r in recs:
            key = (r.get("metric"),
                   tuple(sorted((r.get("labels") or {}).items())))
            latest[key] = r
        for (name, key), r in sorted(latest.items()):
            if name is None:
                continue
            if r["type"] == "histogram":
                lines.append(f"  {name}{_labelkey_str(key)}  "
                             f"count={r['count']:g} sum={r['sum']:.4g}")
            else:
                lines.append(f"  {name}{_labelkey_str(key)}  "
                             f"{r['value']:g}")
    if trace:
        with open(trace, encoding="utf-8") as f:
            events = json.load(f).get("traceEvents", [])
        by_ph = {}
        for e in events:
            by_ph[e.get("ph", "?")] = by_ph.get(e.get("ph", "?"), 0) + 1
        lines.append(
            f"\ntrace: {trace} ({len(events)} events — "
            f"{by_ph.get('X', 0)} spans, {by_ph.get('i', 0)} instants, "
            f"{by_ph.get('C', 0)} counter samples)")
    if len(lines) == 1:
        lines.append("(no sinks given — pass --prom/--jsonl/--trace)")
    return "\n".join(lines)


# -- roofline arithmetic -----------------------------------------------------
#
# Still called by ``observability/doctor.py`` (its compute/memory/dispatch
# attribution rows, and through ``evidence_from_sinks`` the two prom
# readers below); the ``report --roofline`` view that rendered it is
# gone: it divided XLA's ``cost_analysis`` by a host-timed dispatch.
# ROADMAP queue 3 item 3 takes the caller, and these functions with it.

def roofline_from_stats(stats, measured_ms=None, peak_flops=None,
                        hbm_bw=None, wire_bytes=None):
    """Per-surface roofline/attribution rows from compile-telemetry
    stats (``compilestats.snapshot()`` shape, or the same rebuilt from
    a prom file).  ``measured_ms`` maps surface -> measured wall ms per
    dispatch; rows with a measured number get the step-time attribution
    across compute / memory / dispatch+other and an analytical MFU.

    The attribution is a PARTITION of the measured step (fractions sum
    to 1): the binding roof takes its analytical share, the non-binding
    roof is reported as 0 — in the roofline model its traffic hides
    under the binding resource (its analytical ms stays in its own
    ``compute_ms``/``memory_ms`` column) — and ``dispatch_other_frac``
    is the residual above the roof."""
    peak_flops = peak_flops or DEFAULT_PEAK_FLOPS
    hbm_bw = hbm_bw or DEFAULT_HBM_BW
    measured_ms = measured_ms or {}
    rows = []
    for surface, st in sorted(stats.items()):
        flops = st.get("flops")
        bytes_ = st.get("bytes_accessed")
        row = {"surface": surface,
               "compiles": st.get("compiles"),
               "retraces": st.get("retraces"),
               "flops": flops, "bytes_accessed": bytes_,
               "memory_bytes": st.get("memory_bytes"),
               "intensity_flop_per_byte":
                   round(flops / bytes_, 3) if flops and bytes_ else None}
        t_c = flops / peak_flops * 1e3 if flops else None
        t_m = bytes_ / hbm_bw * 1e3 if bytes_ else None
        row["compute_ms"] = round(t_c, 6) if t_c is not None else None
        row["memory_ms"] = round(t_m, 6) if t_m is not None else None
        roof = max(t_c or 0.0, t_m or 0.0) or None
        row["roofline_ms"] = round(roof, 6) if roof else None
        row["bound"] = None if roof is None else (
            "compute" if (t_c or 0.0) >= (t_m or 0.0) else "memory")
        # measured-latency guard (ISSUE 13 satellite): a zero or
        # non-finite measured pt_compile_dispatch_ms (torn sink, NaN
        # exposition sample, count-without-sum) must never surface as
        # a NaN/inf MFU row — such surfaces render n/a with a reason
        meas = measured_ms.get(surface)
        reason = None
        if meas is None:
            reason = "no-measured-latency"
        elif not math.isfinite(meas):
            reason = "nonfinite-measured-latency"
            meas = None
        elif meas <= 0:
            reason = "zero-measured-latency"
            meas = None
        row["measured_ms"] = round(meas, 3) if meas else None
        if meas and roof:
            bound_c = row["bound"] == "compute"
            # measured below the analytical roof (timing noise, or a
            # wrong peak) clamps to an all-roof split rather than >100%
            roof_frac = min(roof / meas, 1.0)
            row["attribution"] = {
                "compute_frac": round(roof_frac if bound_c else 0.0, 4),
                "memory_frac": round(0.0 if bound_c else roof_frac, 4),
                "dispatch_other_frac": round(1.0 - roof_frac, 4)}
            row["mfu"] = round(flops / (meas * 1e-3) / peak_flops, 4) \
                if flops else None
            row["attribution_reason"] = None
        else:
            if meas and not roof:
                reason = "no-analytical-cost"
            row["attribution"] = None
            row["mfu"] = None
            row["attribution_reason"] = reason
        rows.append(row)
    return {"peak_flops": peak_flops, "hbm_bw_bytes_per_s": hbm_bw,
            "wire_bytes_per_step": wire_bytes, "rows": rows}


def _series_value(metrics, name, **want):
    m = metrics.get(name)
    if not m:
        return None
    key = tuple(sorted(want.items()))
    return m["series"].get(key)


def compile_stats_from_prom(metrics):
    """Rebuild the ``compilestats.snapshot()`` shape from a parsed
    prom exposition (the ``pt_compile_*`` series)."""
    stats = {}

    def fold(metric, field):
        m = metrics.get(metric)
        if not m:
            return
        for key, value in m["series"].items():
            labels = dict(k for k in key if k[0] != "__sample__")
            surface = labels.get("surface")
            if surface is None or "__sample__" in dict(key):
                continue
            stats.setdefault(surface, {})[field] = value

    fold("pt_compile_flops", "flops")
    fold("pt_compile_bytes_accessed", "bytes_accessed")
    fold("pt_compile_memory_bytes", "memory_bytes")
    fold("pt_compile_compiles_total", "compiles")
    fold("pt_compile_retraces_total", "retraces")
    return stats


def measured_from_prom(metrics):
    """surface -> measured ms per dispatch: the per-surface
    ``pt_compile_dispatch_ms`` histogram mean first, then the hapi
    step-latency fallback for surfaces it does not cover."""
    out = {}
    m = metrics.get("pt_compile_dispatch_ms")
    if m:
        sums, counts = {}, {}
        for key, value in m["series"].items():
            kd = dict(key)
            suf = kd.pop("__sample__", None)
            surface = kd.get("surface")
            if surface is None:
                continue
            if suf == "_sum":
                sums[surface] = value
            elif suf == "_count":
                counts[surface] = value
        for s, total in sums.items():
            if counts.get(s):
                out[s] = total / counts[s]
    for surface, hist in _MEASURED_LATENCY.items():
        if surface in out:
            continue
        m = metrics.get(hist)
        if not m:
            continue
        count = m["series"].get((("__sample__", "_count"),))
        total = m["series"].get((("__sample__", "_sum"),))
        if count:
            out[surface] = total / count
    return out


def _fmt_num(v):
    if v is None:
        return "-"
    if v == 0:
        return "0"
    for unit in ("", "K", "M", "G", "T", "P"):
        if abs(v) < 1000:
            return f"{v:.3g}{unit}"
        v /= 1000.0
    return f"{v:.3g}E"


# -- device view -------------------------------------------------------------
#
# Device time by the program's named scopes, from a jax profiler trace.

_DEVICE_PLANE = re.compile(r"^/device:(?:TPU|GPU):(\d+)$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"
_OP_NAME_STATS = ("tf_op", "op_name")
_NUMBER = re.compile(r"(\.\d+)+$")
_MODULE_ID = re.compile(r"\(\d+\)$")
_SCOPE_SEGMENT = re.compile(r"^(?:\w+\()*([\w.]+?)\)*$")
# a gap this short between two operations of one program is the device's
# own launch overhead, not the host's doing
SHORT_GAP_NS = 10_000
SHORT_GAPS = "between_ops_under_10us"
NO_SPAN = "no_span"


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under ``trace_dir`` (as
    ``jax.profiler.start_trace`` lays it out), or None."""
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def load_device_trace(path):
    """Read an ``.xplane.pb`` with ``jax.profiler.ProfileData`` into
    plain lists: ``{"devices": {index: {"ops": [...], "modules": [...]}},
    "spans": [...]}``.  A device operation is ``(name, start_ns, end_ns,
    op_name or None)``, a program run (the ``XLA Modules`` line) and a
    program span (a host event named ``fit``, ``fit.*`` or ``serving.*``:
    ``tracing.region`` mirrors them into the trace, on the device's
    clock) are ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    from . import tracing as _tracing
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     {"ops": [], "modules": []})
            for line in plane.lines:
                if line.name == _MODULES_LINE:
                    dev["modules"].extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
                elif line.name == _OPS_LINE:
                    events = list(line.events)
                    # reading every event's stats is the slow part: look
                    # at the first to learn whether this runtime puts
                    # the op_name there at all
                    stat = next((k for k, _ in events[0].stats
                                 if k in _OP_NAME_STATS), None) \
                        if events else None
                    for e in events:
                        dev["ops"].append((
                            e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats).get(stat) if stat else None))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events
                    if _tracing.is_program_span(e.name))
    return {"devices": devices, "spans": spans}


def scope_of(op_name):
    """(scope, "fwd" | "bwd") of an HLO ``op_name``: the innermost
    segment of the name stack that is a name of ``tracing.SCOPES``,
    backward where it or a segment above it sits under ``transpose(``
    (what ``value_and_grad`` makes of ``jvp(<scope>)``; a scope nested in
    another keeps its bare name below the outer's
    ``transpose(jvp(<outer>))``); None outside every scope."""
    from .tracing import SCOPES
    segments = (op_name or "").split("/")
    for i in range(len(segments) - 1, -1, -1):
        m = _SCOPE_SEGMENT.match(segments[i])
        if m and m.group(1) in SCOPES:
            back = any(seg.startswith("transpose(")
                       for seg in segments[:i + 1])
            return m.group(1), "bwd" if back else "fwd"
    return None


def _instruction(event_name):
    """``fusion.12`` of ``%fusion.12 = f32[..] fusion(..)``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def _self_times(events):
    """(event, self ns) per event of one line: its duration less that of
    the events nested inside it (a ``while`` holds its body's)."""
    out, stack = [], []          # stack of [event, self_ns]
    for ev in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][0][2] <= ev[1]:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= min(ev[2], stack[-1][0][2]) - ev[1]
        stack.append([ev, ev[2] - ev[1]])
    out.extend(tuple(x) for x in stack)
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def device_view(loaded, op_names=None):
    """The reduction, on what :func:`load_device_trace` gives (or a
    hand-made list of the same shape).  Device 0 is the lowest device
    index; the window runs from its first operation to its last.

    (a) ``by_scope``: device SELF time by named scope, forward and
    backward apart.  The ``op_name`` comes from the event where the
    runtime puts it there (a ``tf_op`` / ``op_name`` stat); libtpu 0.0.34
    does not, so the event's instruction name (number kept) and its
    program (the ``XLA Modules`` run that covers it) are joined to
    ``op_names`` — ``compilestats.op_names()``, which the profiler
    writes beside the trace as ``op_names.json``.  A fusion has one
    ``op_name``, the one XLA leaves on the fusion instruction: its
    root's, or, where the root is a compiler-made convert or tuple with
    none, that of the operation the fusion was built around (the matmul
    of a ``convolution_*_fusion``); a fusion that spans two scopes is
    booked whole to that one.  An operation outside every scope is
    booked under its HLO name (number dropped), so the rows always sum
    to the busy time.  ``cross`` splits every row by HLO name and by
    program (the decode chunk's rows apart from the prefill's).
    (b) ``by_program``: seconds and runs per compiled program.
    (c) ``idle_gaps``: device 0's idle time by the innermost program
    span covering each gap's middle."""
    if not loaded["devices"]:
        raise ValueError("the trace holds no device plane")
    dev0 = loaded["devices"][min(loaded["devices"])]
    ops = dev0["ops"]
    if not ops:
        raise ValueError("device 0 ran no operation in the trace")
    op_names = op_names or {}
    t0, t1 = min(e[1] for e in ops), max(e[2] for e in ops)
    runs = sorted((s, e, _MODULE_ID.sub("", name))
                  for name, s, e in dev0["modules"])
    starts = [r[0] for r in runs]

    def program_at(t):
        i = bisect.bisect_right(starts, t) - 1
        return runs[i][2] if i >= 0 and t < runs[i][1] else None

    rows, cross, programs = {}, {}, {}
    for (name, s, e, stat), self_ns in _self_times(ops):
        instr, program = _instruction(name), program_at(s)
        op = stat or op_names.get(program, {}).get(instr)
        hlo = _NUMBER.sub("", instr)
        key = scope_of(op) or (hlo, None)
        rows[key] = rows.get(key, 0) + self_ns
        at = key + (hlo, program)
        cross[at] = cross.get(at, 0) + self_ns
    for s, e, name in runs:
        sec, n = programs.get(name, (0, 0))
        programs[name] = (sec + e - s, n + 1)
    merged = _union((e[1], e[2]) for e in ops)
    busy_ns = sum(e - s for s, e in merged)
    spans = sorted((e - s, name, s, e) for name, s, e in loaded["spans"])
    gaps = {}
    for (_, gs), (ge, _) in zip(merged, merged[1:]):
        mid = (gs + ge) / 2
        owner = SHORT_GAPS if ge - gs < SHORT_GAP_NS else next(
            (name for _, name, s, e in spans if s <= mid <= e), NO_SPAN)
        gaps[owner] = gaps.get(owner, 0) + ge - gs
    outside = sum(ns for (_, way), ns in rows.items() if way is None)

    def table(d, make):
        return [make(k, v / 1e9) for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])]
    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_s": (t1 - t0 - busy_ns) / 1e9,
        "outside_scope_s": outside / 1e9,
        "by_scope": table(rows, lambda k, sec: {
            "scope": k[0], "direction": k[1], "seconds": sec}),
        "cross": table(cross, lambda k, sec: {
            "scope": k[0], "direction": k[1], "hlo": k[2],
            "program": k[3], "seconds": sec}),
        "by_program": table(
            {k: v[0] for k, v in programs.items()}, lambda k, sec: {
                "program": k, "seconds": sec, "runs": programs[k][1]}),
        "idle_gaps": table(gaps, lambda k, sec: {
            "span": k, "seconds": sec}),
    }


def render_device(view, top=3):
    busy = view["busy_s"]
    lines = ["== device time by scope ==",
             f"window={view['window_s']:.4f}s  busy={busy:.4f}s  "
             f"idle={view['idle_s']:.4f}s  outside every scope="
             f"{view['outside_scope_s']:.4f}s "
             f"({100 * view['outside_scope_s'] / busy:.1f}% of busy)",
             f"{'scope':<34} {'seconds':>10} {'share':>7}  "
             "largest HLO operations"]
    for r in view["by_scope"]:
        inside = {}
        for c in view["cross"]:
            if (c["scope"], c["direction"]) == (r["scope"], r["direction"]):
                inside[c["hlo"]] = inside.get(c["hlo"], 0) + c["seconds"]
        inside = sorted(inside.items(), key=lambda kv: -kv[1])[:top]
        name = r["scope"] if r["direction"] != "bwd" \
            else r["scope"] + " (bwd)"
        if r["direction"] is None:
            name = "hlo:" + name
        lines.append(
            f"{name:<34} {r['seconds']:>10.4f} "
            f"{100 * r['seconds'] / busy:>6.1f}%  "
            + ", ".join(f"{hlo} {sec:.4f}" for hlo, sec in inside
                        if r["direction"] is not None))
    lines.append("== by program ==")
    for r in view["by_program"]:
        lines.append(f"{r['program']:<34} {r['seconds']:>10.4f} "
                     f"runs={r['runs']}")
    lines.append("== idle gaps by program span ==")
    for r in view["idle_gaps"]:
        lines.append(f"{r['span']:<34} {r['seconds']:>10.4f}")
    return "\n".join(lines)


def run_device(trace_dir, as_json):
    """``report --device``: print the view and return the exit code;
    non-zero where there is no trace, or the trace has no device plane
    (a CPU trace: its program spans still load, and the line says how
    many)."""
    path = find_xplane(trace_dir)
    if path is None:
        print(f"no data: device — no .xplane.pb under {trace_dir}",
              file=sys.stderr)
        return 1
    from .compilestats import OP_NAMES_FILE
    loaded = load_device_trace(path)
    names = {}
    sidecar = os.path.join(trace_dir, OP_NAMES_FILE)
    if os.path.exists(sidecar):
        with open(sidecar, encoding="utf-8") as f:
            names = json.load(f)
    try:
        view = device_view(loaded, names)
    except ValueError as e:
        print(f"no data: device — {e} ({path}; "
              f"{len(loaded['spans'])} program spans loaded)",
              file=sys.stderr)
        return 1
    view["trace"] = path
    view["op_names"] = sidecar if names else None
    print(json.dumps(view, indent=1) if as_json else render_device(view))
    return 0


# -- requests view ---------------------------------------------------------

def request_rows_from_trace(path):
    """Fold a merged chrome trace's per-request lanes (``cat:
    "request"``) back into one summary per trace id (the
    ``tracing.request_summaries`` shape)."""
    with open(path, encoding="utf-8") as f:
        events = json.load(f).get("traceEvents", [])
    span_list = []
    for e in events:
        if e.get("cat") != "request":
            continue
        args = e.get("args", {})
        start_ns = int(e["ts"] * 1e3)
        end_ns = start_ns + int(e.get("dur", 0) * 1e3)
        span_list.append({
            "trace": args.get("trace", f"tid{e.get('tid')}"),
            "req_id": args.get("req_id"),
            "phase": args.get("phase", e.get("name")),
            "start_ns": start_ns, "end_ns": end_ns,
            "args": args})
    from . import tracing as _tracing
    return _tracing.request_summaries(span_list)


def dropped_spans_from_trace(path):
    """Span-ring overflow count stamped into a merged trace by the
    timeline export (``tracing_dropped_spans`` metadata event), or 0.
    Nonzero means the oldest request lanes are incomplete and their
    summaries violate the span-tiling invariant — ``report --requests``
    must flag it, never silently under-report."""
    with open(path, encoding="utf-8") as f:
        events = json.load(f).get("traceEvents", [])
    for e in events:
        if e.get("name") == "tracing_dropped_spans" and \
                e.get("ph") == "M":
            return int((e.get("args") or {}).get("count", 0))
    return 0


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return None
    pos = q * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return round(sorted_vals[lo] +
                 (sorted_vals[hi] - sorted_vals[lo]) * (pos - lo), 3)


def requests_view(rows):
    """TTFT/TPOT percentiles + the tail's per-phase attribution (mean
    phase breakdown of the slowest-TTFT decile)."""
    ttfts = sorted(r["ttft_ms"] for r in rows if r["ttft_ms"] is not None)
    tpots = sorted(r["tpot_ms"] for r in rows if r["tpot_ms"] is not None)
    out = {"requests": len(rows),
           "evictions": sum(r["evictions"] for r in rows),
           "tokens": sum(r["tokens"] for r in rows),
           "ttft_ms": {f"p{int(q * 100)}": _percentile(ttfts, q)
                       for q in (0.5, 0.9, 0.99)},
           "tpot_ms": {f"p{int(q * 100)}": _percentile(tpots, q)
                       for q in (0.5, 0.9, 0.99)}}
    p90 = _percentile(ttfts, 0.9)
    tail = [r for r in rows
            if r["ttft_ms"] is not None and p90 is not None
            and r["ttft_ms"] >= p90] or rows
    phases = {}
    for r in tail:
        for ph, ms in r["phase_ms"].items():
            phases[ph] = phases.get(ph, 0.0) + ms
    out["tail_requests"] = len(tail)
    out["tail_phase_ms_mean"] = {
        ph: round(ms / len(tail), 3) for ph, ms in sorted(phases.items())}
    return out


def per_replica_views(rows):
    """Group request summaries by the replica that served them (the
    fleet router's ``replica`` span label; the LAST replica for a
    request that migrated after a replica death) and fold each group
    through :func:`requests_view`.  Requests with no replica label
    (single-engine serving, or shed before dispatch) group under
    ``"-"``."""
    groups = {}
    for r in rows:
        key = r.get("replica")
        groups.setdefault("-" if key is None else str(key), []).append(r)
    return {k: requests_view(v) for k, v in sorted(groups.items())}


def render_per_replica(views):
    lines = ["== per-replica request summary =="]
    for rep, v in views.items():
        t, p = v["ttft_ms"], v["tpot_ms"]
        lines.append(
            f"  replica {rep}: requests={v['requests']} "
            f"tokens={v['tokens']} "
            f"ttft p50={t['p50']} p99={t['p99']} "
            f"tpot p50={p['p50']} p99={p['p99']} "
            f"evictions={v['evictions']}")
    return "\n".join(lines)


def render_requests(summary, rows):
    lines = ["== per-request serving traces ==",
             f"requests={summary['requests']} "
             f"tokens={summary['tokens']} "
             f"evictions={summary['evictions']}"]
    if summary.get("dropped_spans"):
        lines.append(
            f"  WARNING: {summary['dropped_spans']} span(s) dropped by "
            "ring overflow (pt_trace_dropped_spans_total) — the oldest "
            "lanes are incomplete and their span-tiling invariant does "
            "not hold")
    for name in ("ttft_ms", "tpot_ms"):
        qs = summary[name]
        lines.append("  " + name + "  " + "  ".join(
            f"{k}={v if v is not None else '-'}"
            for k, v in qs.items()))
    lines.append(f"  tail (slowest-TTFT decile, "
                 f"{summary['tail_requests']} req) mean phase ms: "
                 + ", ".join(f"{k}={v}" for k, v in
                             summary["tail_phase_ms_mean"].items()))
    for r in rows[:32]:
        lines.append(
            f"  {r['trace']:<12} req={r['req_id']} "
            f"total={r['total_ms']:.1f}ms ttft={r['ttft_ms']}ms "
            f"tpot={r['tpot_ms'] if r['tpot_ms'] is not None else '-'}"
            f"ms tokens={r['tokens']} "
            + " ".join(f"{k}={v}" for k, v in r["phase_ms"].items())
            + (f" evictions={r['evictions']}" if r["evictions"] else ""))
    if len(rows) > 32:
        lines.append(f"  ... {len(rows) - 32} more")
    return "\n".join(lines)


# -- memory view ------------------------------------------------------------

def memory_view(prom=None, memory_json=None):
    """Per-surface static + per-pool live memory tables from the HBM
    ledger's sinks: a ``memory.json`` artifact and/or the
    ``pt_memory_*`` series of a prom exposition.  Either input alone
    works (the artifact carries the full static ledger; prom carries
    the last census's gauges); returns None when neither yields data."""
    static = {}
    live = {}
    envelope = None
    platform = None
    if memory_json:
        with open(memory_json, encoding="utf-8") as f:
            doc = json.load(f)
        envelope = doc.get("hbm_envelope_bytes")
        platform = doc.get("platform")
        for surface, row in sorted((doc.get("surfaces") or {}).items()):
            if isinstance(row, dict):
                static[surface] = row
        dyn = doc.get("dynamic") or {}
        last = dyn.get("last")
        if last:
            for pool, v in (last.get("pools") or {}).items():
                live[f"pool.{pool}"] = v
            for key in ("live_buffers", "kv_occupancy",
                        "kv_headroom_bytes", "steps_to_exhaustion"):
                if last.get(key) is not None:
                    live[key] = last[key]
            live["censuses"] = dyn.get("censuses")
    if prom:
        metrics = parse_prometheus(prom)
        m = metrics.get("pt_memory_static_bytes")
        if m:
            for key, value in m["series"].items():
                kd = dict(key)
                surface, kind = kd.get("surface"), kd.get("kind")
                if surface is None or kind is None:
                    continue
                row = static.setdefault(
                    surface, {"compiled": True, "kinds": {}})
                if kind == "total":
                    row["total_bytes"] = value
                else:
                    row.setdefault("kinds", {})[kind] = value
        m = metrics.get("pt_memory_budget_frac")
        if m:
            for key, value in m["series"].items():
                surface = dict(key).get("surface")
                if surface in static:
                    static[surface].setdefault("budget_frac", value)
        m = metrics.get("pt_memory_live_bytes")
        if m:
            for key, value in m["series"].items():
                pool = dict(key).get("pool")
                if pool is not None:
                    live.setdefault(f"pool.{pool}", value)
        for name, key in (("pt_memory_live_buffers", "live_buffers"),
                          ("pt_memory_kv_occupancy", "kv_occupancy"),
                          ("pt_memory_kv_headroom_bytes",
                           "kv_headroom_bytes"),
                          ("pt_memory_steps_to_exhaustion",
                           "steps_to_exhaustion")):
            v = _series_value(metrics, name)
            if v is not None and key not in live:
                # the gauge's -1 sentinel means "no computable trend"
                if not (key == "steps_to_exhaustion" and v < 0):
                    live[key] = v
    if not static and not live:
        return None
    return {"platform": platform, "hbm_envelope_bytes": envelope,
            "static": static, "live": live}


def render_memory(view):
    lines = ["== HBM memory ledger =="]
    head = []
    if view.get("platform"):
        head.append(f"platform={view['platform']}")
    if view.get("hbm_envelope_bytes"):
        head.append(f"envelope={_fmt_num(view['hbm_envelope_bytes'])}B")
    if head:
        lines.append("  ".join(head))
    if view["static"]:
        lines.append(f"{'surface':<30} {'arg':>8} {'out':>8} "
                     f"{'temp':>8} {'code':>8} {'total':>8} "
                     f"{'budget':>7}")
        for surface, row in sorted(view["static"].items()):
            if not row.get("compiled", True):
                lines.append(f"{surface:<30} (not compiled this run)")
                continue
            kinds = row.get("kinds") or {}
            frac = row.get("budget_frac")
            lines.append(
                f"{surface:<30} "
                f"{_fmt_num(kinds.get('argument')):>8} "
                f"{_fmt_num(kinds.get('output')):>8} "
                f"{_fmt_num(kinds.get('temp')):>8} "
                f"{_fmt_num(kinds.get('generated_code')):>8} "
                f"{_fmt_num(row.get('total_bytes')):>8} "
                f"{(f'{frac:.1%}' if frac is not None else '-'):>7}")
    if view["live"]:
        lines.append("live census:")
        for key, v in sorted(view["live"].items()):
            if key == "kv_occupancy" and v is not None:
                lines.append(f"  {key} = {v:.1%}")
            else:
                lines.append(f"  {key} = "
                             f"{_fmt_num(v) if v is not None else '-'}")
    return "\n".join(lines)


def _sink_note(path, what):
    """One-line no-data reason for a subview's sink, or None when the
    file at least exists and is non-empty (ISSUE 13 satellite: a
    missing or torn telemetry file must never traceback a report)."""
    if path is None:
        return f"no {what} file given"
    if not os.path.exists(path):
        return f"missing file {path}"
    try:
        if os.path.getsize(path) == 0:
            return f"empty file {path}"
    except OSError as e:
        return f"unreadable file {path} ({e})"
    return None


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.observability",
        description="Telemetry tooling for the unified metrics "
                    "registry (see docs/observability.md).")
    sub = ap.add_subparsers(dest="cmd")
    dp = sub.add_parser("doctor",
                        help="ranked probable-cause diagnosis from a "
                             "flight-recorder bundle or loose sinks")
    dp.add_argument("bundle", nargs="?", default=None,
                    help="forensic bundle directory written by the "
                         "flight recorder (PADDLE_FLIGHT_DIR)")
    dp.add_argument("--prom", default=None,
                    help="Prometheus text exposition file")
    dp.add_argument("--jsonl", default=None,
                    help="JSONL metrics log")
    dp.add_argument("--trace", default=None,
                    help="merged chrome-trace JSON")
    dp.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the diagnosis as JSON")
    rp = sub.add_parser("report",
                        help="summarize telemetry sinks into one "
                             "run report")
    rp.add_argument("--prom", default=None,
                    help="Prometheus text exposition file")
    rp.add_argument("--jsonl", default=None,
                    help="JSONL metrics log (PADDLE_METRICS_LOG format)")
    rp.add_argument("--trace", default=None,
                    help="merged chrome-trace JSON (timeline.py)")
    rp.add_argument("--device", default=None, metavar="TRACE_DIR",
                    help="device time by named scope, by program, and "
                         "idle gaps by program span, from the newest "
                         ".xplane.pb under a jax profiler trace dir "
                         "(joined to its op_names.json)")
    rp.add_argument("--requests", action="store_true",
                    help="per-request TTFT/TPOT summary from the "
                         "--trace file's request lanes")
    rp.add_argument("--per-replica", action="store_true",
                    dest="per_replica",
                    help="with --requests: additionally group the "
                         "summary by the fleet router's replica label")
    rp.add_argument("--memory", action="store_true",
                    help="per-surface static + per-pool live memory "
                         "tables from the HBM ledger (pt_memory_* "
                         "series of --prom and/or --memory-json)")
    rp.add_argument("--memory-json", default=None, dest="memory_json",
                    help="memory.json artifact "
                         "(memory.write_memory_json)")
    rp.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the subview as JSON (with --device / "
                         "--requests / --memory)")
    rp.add_argument("--doctor", action="store_true", dest="doctor",
                    help="append the doctor's ranked probable-cause "
                         "diagnosis built from the same sinks")
    args = ap.parse_args(argv)
    if args.cmd == "doctor":
        from . import doctor as _doctor
        return _doctor.run_cli(args)
    if args.cmd != "report":
        ap.print_help()
        return 2
    if args.device:
        return run_device(args.device, args.as_json)
    if args.requests and not args.trace:
        print("error: --requests needs --trace", file=sys.stderr)
        return 2
    if args.per_replica and not args.requests:
        print("error: --per-replica needs --requests", file=sys.stderr)
        return 2
    if args.memory and not (args.prom or args.memory_json):
        print("error: --memory needs --prom or --memory-json",
              file=sys.stderr)
        return 2
    if not (args.prom or args.jsonl or args.trace or args.memory_json):
        print("error: pass at least one of --prom/--jsonl/--trace/"
              "--memory-json", file=sys.stderr)
        return 2
    try:
        if args.requests or args.memory:
            # no-data discipline (ISSUE 13 satellite): a missing,
            # empty, or torn telemetry file prints ONE line and exits
            # 0 (`--json` emits {}) — a cron job or CI smoke over a
            # quiet run must not die on a traceback
            out = {}
            no_data = []
            if args.requests:
                note = _sink_note(args.trace, "trace")
                rows = None
                if note is None:
                    try:
                        rows = request_rows_from_trace(args.trace)
                    except ValueError as e:
                        note = f"unparseable trace {args.trace} " \
                               f"(torn write? {e})"
                    else:
                        if not rows:
                            note = f"no request lanes in {args.trace}"
                            rows = None
                if rows is None:
                    no_data.append(f"no data: requests — {note}")
                else:
                    summary = requests_view(rows)
                    summary["dropped_spans"] = \
                        dropped_spans_from_trace(args.trace)
                    if args.as_json:
                        out["requests"] = {"summary": summary,
                                           "per_request": rows}
                    else:
                        print(render_requests(summary, rows))
                    if args.per_replica:
                        views = per_replica_views(rows)
                        if args.as_json:
                            out["per_replica"] = views
                        else:
                            print(render_per_replica(views))
            if args.memory:
                view = None
                notes = []
                mj = args.memory_json
                if mj is not None:
                    note = _sink_note(mj, "memory.json")
                    if note is not None:
                        notes.append(note)
                        mj = None
                pr = args.prom
                if pr is not None:
                    note = _sink_note(pr, "prom")
                    if note is not None:
                        notes.append(note)
                        pr = None
                if mj or pr:
                    try:
                        view = memory_view(prom=pr, memory_json=mj)
                    except ValueError as e:
                        notes.append(f"unparseable memory sink "
                                     f"(torn write? {e})")
                if view is None:
                    notes = notes or ["no pt_memory_* series / "
                                      "memory.json rows in the sinks"]
                    no_data.append("no data: memory — "
                                   + "; ".join(notes))
                elif args.as_json:
                    out["memory"] = view
                else:
                    print(render_memory(view))
            if args.doctor:
                from . import doctor as _doctor
                result = _doctor.diagnose(_doctor.evidence_from_sinks(
                    prom=args.prom, jsonl=args.jsonl,
                    trace=args.trace))
                if args.as_json:
                    out["doctor"] = result
                else:
                    print(_doctor.render(result))
            if args.as_json:
                print(json.dumps(out, indent=1, sort_keys=True)
                      if out else "{}")
            else:
                for line in no_data:
                    print(line)
            return 0
        print(render_report(prom=args.prom, jsonl=args.jsonl,
                            trace=args.trace))
        if args.doctor:
            from . import doctor as _doctor
            result = _doctor.diagnose(_doctor.evidence_from_sinks(
                prom=args.prom, jsonl=args.jsonl, trace=args.trace))
            print(_doctor.render(result))
    except (OSError, ValueError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 1
    return 0
