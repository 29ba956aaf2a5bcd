"""Run-summary renderer: ``python -m paddle_tpu.observability report``.

Reads the sinks the framework writes — a Prometheus text exposition
file, a JSONL metrics log, a merged chrome trace — and renders one
human-readable run summary: counters and gauges grouped by subsystem,
histograms with count / mean / estimated p50/p90/p99 (linear
interpolation inside the winning bucket), trace-event totals.

Two focused subviews (ISSUE 10):

- ``report --roofline --prom <file>`` — join the compile-telemetry
  analytical costs (``pt_compile_flops`` / ``pt_compile_bytes_accessed``
  per surface) with measured step latency and the grad_comm wire-bytes
  gauge into a per-surface roofline table: arithmetic intensity, the
  compute/memory roofline time at the given ``--peak-flops`` /
  ``--hbm-bw``, which roof binds, and — where a measured latency
  exists — the step-time attribution across compute / memory /
  dispatch+other (the artifact the MFU-plateau roadmap item asks for;
  bench runs commit it as ``telemetry/roofline.json``);
- ``report --requests --trace <file>`` — fold the per-request lanes of
  a merged chrome trace back into request summaries: TTFT/TPOT
  percentiles plus the mean per-phase breakdown of the slowest-TTFT
  decile (where the tail's time went).

Both support ``--json``.  The parsers are deliberately self-contained
(stdlib only): the report must run against files produced by an earlier
process, a different machine, or a BENCH_* artifact — never against
live registry state.
"""
import argparse
import json
import math
import os
import sys

from ..device import chip as _chip

__all__ = ["parse_prometheus", "parse_jsonl", "render_report",
           "roofline_from_stats", "compile_stats_from_prom",
           "roofline_view", "requests_view", "request_rows_from_trace",
           "dropped_spans_from_trace", "memory_view", "main"]

# default roofs for the OFFLINE report (sink files carry no device_kind):
# the v5e row of the one peaks table; a live caller passes the roofs of
# the device it measured on (device.chip.peaks())
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


# -- roofline view ---------------------------------------------------------

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


def roofline_view(prom, peak_flops=None, hbm_bw=None):
    """Build the roofline table from one prom exposition file."""
    metrics = parse_prometheus(prom)
    stats = compile_stats_from_prom(metrics)
    wire = _series_value(metrics, "pt_collective_wire_bytes_per_step")
    return roofline_from_stats(stats, measured_from_prom(metrics),
                               peak_flops, hbm_bw, wire_bytes=wire)


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


def render_roofline(table):
    lines = ["== roofline / MFU attribution ==",
             f"peak_flops={_fmt_num(table['peak_flops'])}  "
             f"hbm_bw={_fmt_num(table['hbm_bw_bytes_per_s'])}B/s"
             + (f"  wire_bytes/step="
                f"{_fmt_num(table['wire_bytes_per_step'])}"
                if table.get("wire_bytes_per_step") else "")]
    hdr = (f"{'surface':<28} {'flops':>8} {'bytes':>8} {'int.':>7} "
           f"{'bound':>7} {'roof_ms':>9} {'meas_ms':>9} {'mfu':>6}  "
           "attribution c/m/d")
    lines.append(hdr)
    for r in table["rows"]:
        att = r["attribution"]
        if att:
            att_s = (f"{att['compute_frac']:.0%}/"
                     f"{att['memory_frac']:.0%}/"
                     f"{att['dispatch_other_frac']:.0%}")
        else:
            reason = r.get("attribution_reason")
            att_s = f"n/a ({reason})" if reason else "-"
        mfu_s = f"{r['mfu']:.3f}" if r["mfu"] is not None else "-"
        lines.append(
            f"{r['surface']:<28} {_fmt_num(r['flops']):>8} "
            f"{_fmt_num(r['bytes_accessed']):>8} "
            f"{_fmt_num(r['intensity_flop_per_byte']):>7} "
            f"{(r['bound'] or '-'):>7} "
            f"{_fmt_num(r['roofline_ms']):>9} "
            f"{_fmt_num(r['measured_ms']):>9} "
            f"{mfu_s:>6}  {att_s}")
    if not table["rows"]:
        lines.append("(no pt_compile_* series in this exposition — run "
                     "with compile telemetry wired, e.g. bench.py)")
    return "\n".join(lines)


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
    ledger's sinks: a ``telemetry/memory.json`` artifact and/or the
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
    rp.add_argument("--roofline", action="store_true",
                    help="per-surface roofline/MFU-attribution table "
                         "from the --prom file's pt_compile_* series")
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
                    help="memory.json artifact written next to "
                         "roofline.json (bench runs / "
                         "memory.write_memory_json)")
    rp.add_argument("--json", action="store_true", dest="as_json",
                    help="emit the subview as JSON (with --roofline / "
                         "--requests)")
    rp.add_argument("--doctor", action="store_true", dest="doctor",
                    help="append the doctor's ranked probable-cause "
                         "diagnosis built from the same sinks")
    rp.add_argument("--peak-flops", type=float,
                    default=DEFAULT_PEAK_FLOPS,
                    help="compute roof (FLOP/s) for --roofline "
                         "(default: TPU v5e bf16 peak)")
    rp.add_argument("--hbm-bw", type=float, default=DEFAULT_HBM_BW,
                    help="memory roof (bytes/s) for --roofline "
                         "(default: TPU v5e HBM)")
    args = ap.parse_args(argv)
    if args.cmd == "doctor":
        from . import doctor as _doctor
        return _doctor.run_cli(args)
    if args.cmd != "report":
        ap.print_help()
        return 2
    if args.roofline and not args.prom:
        print("error: --roofline needs --prom", file=sys.stderr)
        return 2
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
        if args.roofline or args.requests or args.memory:
            # no-data discipline (ISSUE 13 satellite): a missing,
            # empty, or torn telemetry file prints ONE line and exits
            # 0 (`--json` emits {}) — a cron job or CI smoke over a
            # quiet run must not die on a traceback
            out = {}
            no_data = []
            if args.roofline:
                note = _sink_note(args.prom, "prom")
                table = None
                if note is None:
                    table = roofline_view(args.prom, args.peak_flops,
                                          args.hbm_bw)
                    if not table["rows"]:
                        note = f"no pt_compile_* series in {args.prom}"
                        table = None
                if table is None:
                    no_data.append(f"no data: roofline — {note}")
                elif args.as_json:
                    out["roofline"] = table
                else:
                    print(render_roofline(table))
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
