"""``doctor``: ranked probable-cause diagnosis from a forensic bundle
or loose telemetry sinks.

The flight recorder (``flight.py``) answers *"what happened around the
anomaly"* by persisting a bundle; this module answers *"so what was
it"*: it joins the bundle's guardian events, watch alerts, metrics,
request lanes and compile-telemetry roofline into a **ranked** list of
probable causes with the evidence lines that support each verdict —

- ``replica_death``      — a fleet replica crashed and was drained;
- ``straggler_replica``  — one replica served markedly slower than its
  peers (or hung with a stale heartbeat);
- ``handoff_failure``    — the disaggregated prefill/decode protocol
  degraded requests to local re-prefill (dropped/corrupt bundles,
  reservation expiries, prefill deaths mid-transfer);
- ``numeric_instability``— the guardian ladder fired (sentinel trips,
  loss spikes, a rollback);
- ``retrace_storm``      — hot jit surfaces recompiled past budget;
- ``overload_shed``      — SLO admission control shed traffic / the
  queue ran away;
- ``throughput_collapse``— the watchdog's EWMA rule tripped with no
  roofline latency to attribute it (plus a catch-all so any future
  alert rule always surfaces as a diagnosis);
- ``memory_pressure``    — the HBM ledger's evidence: an
  ``hbm_pressure`` trip, a pressured census in the bundle's
  ``memory.jsonl``, or a guardian ``memory_budget`` envelope breach;
- ``dispatch_bound`` / ``memory_bound`` / ``compute_bound`` —
  the roofline attribution of the hottest measured surface
  (informational unless an alert points at performance).

Inputs: a bundle directory (``flight.BUNDLE_FILES``) or any subset of
``--prom`` / ``--jsonl`` / ``--trace`` sinks — the same self-contained
stdlib parsers ``report`` uses, so ``doctor`` runs against artifacts
from another process or machine (healthy artifacts parse clean and
yield the ``no alerts`` verdict).
Missing / empty / torn inputs degrade to notes, never tracebacks.

CLI::

    python -m paddle_tpu.observability doctor <bundle-dir> [--json]
    python -m paddle_tpu.observability doctor --prom F [--trace F] ...
    python -m paddle_tpu.observability report --prom F --doctor
"""
import json
import math
import os

__all__ = ["load_bundle", "evidence_from_sinks", "diagnose", "render",
           "run_cli", "INCIDENT_CAUSES"]

INCIDENT_CAUSES = ("replica_death", "straggler_replica",
                   "handoff_failure", "numeric_instability",
                   "retrace_storm", "overload_shed",
                   "throughput_collapse", "memory_pressure")
# the roofline-attribution causes: informational unless an alert exists
PERF_CAUSES = ("dispatch_bound", "memory_bound", "compute_bound")

# verdict threshold: an incident cause below this stays in the ranked
# list but does not flip the verdict away from "no alerts" on its own
_MIN_INCIDENT_SCORE = 3.0


# -- evidence assembly ------------------------------------------------------

def _empty_evidence():
    return {"sources": [], "notes": [], "guardian_events": [],
            "alerts": [], "meta": None, "window": [], "prom": None,
            "jsonl_latest": {}, "requests": [], "compile": None,
            "measured": {}, "memory": []}


def _read_jsonl(path):
    """Thin alias over report.parse_jsonl — ONE torn-line policy for
    every sink parser (doctor and report must never disagree on the
    same file)."""
    from . import report as _report
    return _report.parse_jsonl(path)


def _fold_jsonl(ev, recs):
    """Latest record per (metric, labels) — the render_report fold."""
    for r in recs:
        key = (r.get("metric"),
               tuple(sorted((r.get("labels") or {}).items())))
        if key[0] is not None:
            ev["jsonl_latest"][key] = r


def _measured_from_jsonl(ev):
    out = {}
    for (name, key), r in ev["jsonl_latest"].items():
        if name != "pt_compile_dispatch_ms":
            continue
        surface = dict(key).get("surface")
        count = r.get("count")
        if surface and count:
            out[surface] = r["sum"] / count
    return out


def _ingest_trace(ev, path):
    from . import report as _report
    try:
        if os.path.getsize(path) == 0:
            ev["notes"].append(f"trace {path}: empty file")
            return
        rows = _report.request_rows_from_trace(path)
    except (OSError, ValueError) as e:
        ev["notes"].append(f"trace {path}: unreadable ({e})")
        return
    if path not in ev["sources"]:
        ev["sources"].append(path)
    ev["requests"] = rows


def evidence_from_sinks(prom=None, jsonl=None, trace=None):
    """Build the evidence dict from loose sink files; any missing /
    empty / unparseable input becomes a note."""
    from . import report as _report
    ev = _empty_evidence()
    if prom:
        if not os.path.exists(prom):
            ev["notes"].append(f"prom {prom}: missing file")
        else:
            ev["prom"] = _report.parse_prometheus(prom)
            ev["sources"].append(prom)
            if not ev["prom"]:
                ev["notes"].append(f"prom {prom}: no series")
    if jsonl:
        if not os.path.exists(jsonl):
            ev["notes"].append(f"jsonl {jsonl}: missing file")
        else:
            recs, bad = _read_jsonl(jsonl)
            _fold_jsonl(ev, recs)
            ev["sources"].append(jsonl)
            if bad:
                ev["notes"].append(f"jsonl {jsonl}: {bad} unparseable "
                                   "line(s) skipped")
    if trace:
        if not os.path.exists(trace):
            ev["notes"].append(f"trace {trace}: missing file")
        else:
            _ingest_trace(ev, trace)
    _finish_evidence(ev)
    return ev


def load_bundle(path):
    """Build the evidence dict from one flight-recorder bundle
    directory.  Raises ``OSError`` when the directory itself is
    unreadable; individual missing files degrade to notes."""
    if not os.path.isdir(path):
        raise OSError(f"not a bundle directory: {path!r}")
    ev = _empty_evidence()

    def have(name):
        p = os.path.join(path, name)
        if os.path.exists(p):
            ev["sources"].append(p)
            return p
        ev["notes"].append(f"bundle file {name}: missing")
        return None

    p = have("meta.json")
    if p:
        try:
            with open(p, encoding="utf-8") as f:
                ev["meta"] = json.load(f)
        except ValueError as e:
            ev["notes"].append(f"meta.json: unreadable ({e})")
    p = have("guardian.jsonl")
    if p:
        ev["guardian_events"], _ = _read_jsonl(p)
    p = have("window.jsonl")
    if p:
        ev["window"], _ = _read_jsonl(p)
    p = have("metrics.jsonl")
    if p:
        recs, _ = _read_jsonl(p)
        _fold_jsonl(ev, recs)
    p = have("trace.json")
    if p:
        _ingest_trace(ev, p)
    p = have("compilestats.json")
    if p:
        try:
            with open(p, encoding="utf-8") as f:
                ev["compile"] = json.load(f)
        except ValueError as e:
            ev["notes"].append(f"compilestats.json: unreadable ({e})")
    p = have("memory.jsonl")
    if p:
        ev["memory"], _ = _read_jsonl(p)
    _finish_evidence(ev)
    return ev


def _finish_evidence(ev):
    """Derive the cross-source fields: alerts, compile stats, measured
    latency."""
    alerts = [e for e in ev["guardian_events"]
              if e.get("event") == "watch_alert"]
    if ev["meta"] and ev["meta"].get("alerts"):
        known = {(a.get("rule"), a.get("detail")) for a in alerts}
        for a in ev["meta"]["alerts"]:
            if (a.get("rule"), a.get("detail")) not in known:
                alerts.append(a)
    ev["alerts"] = alerts
    if ev["compile"] is None and ev["prom"]:
        from . import report as _report
        stats = _report.compile_stats_from_prom(ev["prom"])
        ev["compile"] = stats or None
    if ev["prom"]:
        from . import report as _report
        ev["measured"].update(_report.measured_from_prom(ev["prom"]))
    for k, v in _measured_from_jsonl(ev).items():
        ev["measured"].setdefault(k, v)


# -- diagnosis --------------------------------------------------------------

def _metric_total(ev, name):
    """Sum of a metric's series values across labels (prom first, then
    the jsonl fold); None when the metric is absent everywhere."""
    prom = ev.get("prom")
    if prom and name in prom:
        tot, found = 0.0, False
        for key, v in prom[name]["series"].items():
            if any(k == "__sample__" for k, _ in key):
                continue
            tot, found = tot + v, True
        if found:
            return tot
    tot, found = 0.0, False
    for (n, _), r in ev["jsonl_latest"].items():
        if n == name and "value" in r:
            tot, found = tot + r["value"], True
    return tot if found else None


def _events(ev, name):
    return [e for e in ev["guardian_events"] if e.get("event") == name]


def _alerts(ev, rule):
    return [a for a in ev["alerts"] if a.get("rule") == rule]


def _replica_skew(rows, min_requests=3, skew=2.0):
    """(worst_replica, worst_mean, peer_median) from request rows, or
    None — the doctor-side twin of the straggler watch rule."""
    groups = {}
    for r in rows:
        rep = r.get("replica")
        if rep is not None and r.get("tpot_ms") is not None:
            groups.setdefault(rep, []).append(r["tpot_ms"])
    means = {r: sum(v) / len(v) for r, v in groups.items()
             if len(v) >= min_requests}
    if len(means) < 2:
        return None
    worst = max(means, key=means.get)
    others = sorted(v for r, v in means.items() if r != worst)
    median = others[len(others) // 2]
    if median > 0 and means[worst] > skew * median:
        return worst, means[worst], median
    return None


def diagnose(ev):
    """Rank probable causes over one evidence dict.  Returns
    ``{"verdict", "incident", "alerts", "diagnoses", "notes",
    "sources"}`` — ``verdict`` is the top-ranked cause when incident
    evidence exists, else ``"no alerts"`` (the healthy-artifact
    contract the CI smoke asserts)."""
    diags = []

    def add(cause, score, lines):
        if score > 0 and lines:
            diags.append({"cause": cause, "score": round(score, 2),
                          "class": "performance"
                          if cause in PERF_CAUSES else "incident",
                          "evidence": lines[:6]})

    # replica death
    deaths = _events(ev, "router_replica_death")
    score, lines = 0.0, []
    for e in deaths:
        score += 10
        lines.append(f"guardian: replica {e.get('replica')} died "
                     f"({e.get('error')}), {e.get('requeued')} "
                     "request(s) requeued")
    if not deaths:
        n = _metric_total(ev, "pt_router_replica_deaths_total") or 0
        if n:
            score += 6 * n
            lines.append(f"pt_router_replica_deaths_total = {n:g}")
    for a in _alerts(ev, "guardian_escalation"):
        if "death" in str(a.get("detail", "")):
            score += 2
            lines.append(f"watch_alert guardian_escalation: "
                         f"{a.get('detail')}")
    add("replica_death", score, lines)

    # straggler / hung replica
    score, lines = 0.0, []
    for a in _alerts(ev, "straggler_replica"):
        score += 8
        lines.append(f"watch_alert straggler_replica: "
                     f"{a.get('detail')}")
    skew = _replica_skew(ev["requests"])
    if skew:
        worst, mean, median = skew
        score += 6
        lines.append(f"request lanes: replica {worst} mean tpot "
                     f"{mean:.2f}ms vs peer median {median:.2f}ms")
    add("straggler_replica", score, lines)

    # prefill/decode handoff degradation: every fallback event is one
    # request that paid a local re-prefill (output stayed bitwise —
    # this diagnoses the TTFT/availability regression, not corruption)
    falls = _events(ev, "handoff_fallback")
    score, lines = 0.0, []
    for e in falls[:6]:
        lines.append(f"guardian: request {e.get('req_id')} fell back "
                     f"to local re-prefill on replica {e.get('dst')} "
                     f"({e.get('reason')})")
    if falls:
        score += 10 * len(falls)
        if len(falls) > 6:
            lines.append(f"... and {len(falls) - 6} more fallback(s)")
    else:
        n = _metric_total(ev, "pt_handoff_fallbacks_total") or 0
        if n:
            score += 6 * n
            lines.append(f"pt_handoff_fallbacks_total = {n:g}")
    n = _metric_total(ev, "pt_handoff_reserve_expired_total") or 0
    if n:
        score += 2
        lines.append(f"pt_handoff_reserve_expired_total = {n:g} "
                     "(bundles never arrived; reservations TTL-freed)")
    add("handoff_failure", score, lines)

    # numeric instability
    score, lines = 0.0, []
    for e in _events(ev, "rollback"):
        score += 10
        lines.append(f"guardian: rollback at step {e.get('step')} to "
                     f"step {e.get('restored_step')} "
                     f"(rollback #{e.get('rollbacks')})")
    trips = _events(ev, "sentinel_trip")
    if trips:
        score += 3 * len(trips)
        worst = max(trips, key=lambda e: e.get("nan_count", 0))
        lines.append(f"guardian: {len(trips)} sentinel trip(s), e.g. "
                     f"tensor {worst.get('tensor')!r} with "
                     f"{worst.get('nan_count')} NaN / "
                     f"{worst.get('inf_count')} Inf")
    spikes = _events(ev, "loss_spike")
    if spikes:
        score += 2 * len(spikes)
        lines.append(f"guardian: {len(spikes)} loss spike(s), last "
                     f"z-score {spikes[-1].get('zscore')}")
    skips = [e for e in _events(ev, "skip_step")
             if e.get("reason") == "nonfinite"]
    if skips:
        score += len(skips)
        lines.append(f"guardian: {len(skips)} step(s) skipped "
                     "nonfinite")
    for a in _alerts(ev, "guardian_escalation"):
        if "rollback" in str(a.get("detail", "")):
            score += 2
            lines.append(f"watch_alert guardian_escalation: "
                         f"{a.get('detail')}")
    add("numeric_instability", score, lines)

    # retrace storm
    score, lines = 0.0, []
    retr_ev = _events(ev, "compile_retrace")
    for e in retr_ev[:3]:
        lines.append(f"guardian: {e.get('surface')} compiled "
                     f"{e.get('compiles')} > budget "
                     f"{e.get('budget')} ({e.get('diff')})")
    score += 4 * len(retr_ev)
    retr = _metric_total(ev, "pt_compile_retraces_total")
    if retr is None and ev["compile"]:
        retr = sum(st.get("retraces") or 0
                   for st in ev["compile"].values())
    if retr:
        score += 2 * retr
        lines.append(f"compile telemetry: {retr:g} over-budget "
                     "recompile(s) across surfaces")
    for a in _alerts(ev, "retrace_storm"):
        score += 4
        lines.append(f"watch_alert retrace_storm: {a.get('detail')}")
    add("retrace_storm", score, lines)

    # overload / shed
    score, lines = 0.0, []
    sheds = _events(ev, "router_shed")
    if sheds:
        score += 3 * len(sheds)
        lines.append(f"guardian: {len(sheds)} request(s) shed, e.g. "
                     f"projected {sheds[-1].get('projected_wait_ms')}ms"
                     f" vs slo {sheds[-1].get('slo_ttft_ms')}ms")
    shed_total = _metric_total(ev, "pt_router_shed_total")
    if not sheds and shed_total:
        score += 2 * shed_total
        lines.append(f"pt_router_shed_total = {shed_total:g}")
    for a in _alerts(ev, "slo_burn"):
        score += 4
        lines.append(f"watch_alert slo_burn: {a.get('detail')}")
    for a in _alerts(ev, "queue_runaway"):
        score += 3
        lines.append(f"watch_alert queue_runaway: {a.get('detail')}")
    add("overload_shed", score, lines)

    # throughput collapse: alert-backed even when no roofline latency
    # exists to attribute it (input stall, straggler) — without this a
    # bundle triggered by the rule would fall through to "no alerts"
    score, lines = 0.0, []
    for a in _alerts(ev, "throughput_collapse"):
        score += 4
        lines.append(f"watch_alert throughput_collapse: "
                     f"{a.get('detail')}")
    add("throughput_collapse", score, lines)

    # memory pressure: the hbm_pressure alert plus the memory ledger's
    # own census trail (bundle memory.jsonl) and the guardian
    # memory_budget static-envelope breaches.  The prom fallback fires
    # only on a genuinely pressured occupancy gauge — committed healthy
    # snapshots must keep scoring 0 (the CI doctor smoke's contract).
    score, lines = 0.0, []
    for a in _alerts(ev, "hbm_pressure"):
        score += 8
        lines.append(f"watch_alert hbm_pressure: {a.get('detail')}")
    censuses = [r for r in ev.get("memory") or []
                if r.get("kind") == "census"]
    if censuses:
        last = censuses[-1]
        occ = last.get("kv_occupancy")
        steps = last.get("steps_to_exhaustion")
        if occ is not None and occ >= 0.9:
            score += 4
            lines.append(f"memory ledger: KV page occupancy {occ:.0%} "
                         f"at the last census "
                         f"({last.get('kv_pages_in_use')}/"
                         f"{last.get('kv_pages_total')} pages, "
                         f"{last.get('kv_headroom_bytes')} B headroom)")
        if steps is not None and 0 < steps <= 64:
            score += 2
            lines.append(f"memory ledger: OOM forecast ~{steps} "
                         "censuses to headroom exhaustion at the "
                         "current growth trend")
    for e in _events(ev, "memory_budget"):
        score += 3
        lines.append(f"guardian: surface {e.get('surface')} static "
                     f"footprint {e.get('bytes')} B is "
                     f"{e.get('frac'):.2f}x the {e.get('envelope')} B "
                     "HBM envelope")
    if not censuses:
        prom = ev.get("prom")
        if prom and "pt_memory_kv_occupancy" in prom:
            for _, v in prom["pt_memory_kv_occupancy"]["series"].items():
                if v >= 0.9:
                    score += 2
                    lines.append("pt_memory_kv_occupancy = "
                                 f"{v:.2f} (pressured)")
                    break
    add("memory_pressure", score, lines)

    # catch-all: an alert rule none of the causes above folded in must
    # still surface as a diagnosis (future rules, custom engines)
    folded = {"slo_burn", "queue_runaway", "retrace_storm",
              "straggler_replica", "guardian_escalation",
              "throughput_collapse", "hbm_pressure"}
    for rule in sorted({str(a.get("rule")) for a in ev["alerts"]}
                       - folded):
        add(rule, 4.0,
            [f"watch_alert {rule}: {a.get('detail')}"
             for a in _alerts(ev, rule)])

    # roofline attribution of the hottest measured surface
    if ev["compile"]:
        from . import report as _report
        table = _report.roofline_from_stats(ev["compile"],
                                            ev["measured"])
        best = None
        for r in table["rows"]:
            if r["attribution"] and (best is None or
                                     r["measured_ms"] >
                                     best["measured_ms"]):
                best = r
        if best is not None:
            att = best["attribution"]
            frac, kind = max(
                (att["dispatch_other_frac"], "dispatch_bound"),
                (att["memory_frac"], "memory_bound"),
                (att["compute_frac"], "compute_bound"))
            if math.isfinite(frac) and frac > 0:
                tput_hint = 4 * len(_alerts(ev, "throughput_collapse"))
                add(kind, 2 + 4 * frac + tput_hint,
                    [f"roofline: surface {best['surface']} spends "
                     f"{frac:.0%} of its measured "
                     f"{best['measured_ms']}ms at the "
                     f"{kind.split('_')[0]} side (roof "
                     f"{best['roofline_ms']}ms, mfu {best['mfu']})"])

    diags.sort(key=lambda d: (-d["score"], d["cause"]))
    incident = bool(ev["alerts"]) or any(
        d["class"] == "incident" and d["score"] >= _MIN_INCIDENT_SCORE
        for d in diags)
    verdict = diags[0]["cause"] if incident and diags else "no alerts"
    return {"verdict": verdict, "incident": incident,
            "alerts": ev["alerts"], "diagnoses": diags,
            "notes": ev["notes"], "sources": ev["sources"]}


# -- rendering / CLI --------------------------------------------------------

def render(result):
    lines = ["== paddle_tpu doctor =="]
    if result["sources"]:
        lines.append("sources: " + ", ".join(result["sources"]))
    for n in result["notes"]:
        lines.append(f"note: {n}")
    if result["verdict"] == "no alerts":
        extra = f" ({len(result['diagnoses'])} informational " \
                "signal(s) below)" if result["diagnoses"] else ""
        lines.append("verdict: no alerts — telemetry parses clean, no "
                     "incident evidence" + extra)
    else:
        lines.append(f"verdict: {result['verdict']} "
                     f"(score {result['diagnoses'][0]['score']}, "
                     f"{len(result['alerts'])} watch alert(s))")
    for i, d in enumerate(result["diagnoses"], 1):
        lines.append(f"  {i}. {d['cause']}  [{d['class']}]  "
                     f"score={d['score']}")
        for e in d["evidence"]:
            lines.append(f"     - {e}")
    return "\n".join(lines)


def run_cli(args):
    """Entry for the ``doctor`` subcommand (argparse namespace from
    ``report.main``): bundle dir XOR loose sinks; exit 0 whatever the
    verdict — the diagnosis is the output, not the exit code."""
    import sys
    if args.bundle:
        try:
            ev = load_bundle(args.bundle)
        except OSError as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
    elif args.prom or args.jsonl or args.trace:
        ev = evidence_from_sinks(prom=args.prom, jsonl=args.jsonl,
                                 trace=args.trace)
    else:
        print("error: pass a bundle directory or at least one of "
              "--prom/--jsonl/--trace", file=sys.stderr)
        return 2
    result = diagnose(ev)
    if getattr(args, "as_json", False):
        print(json.dumps(result, indent=1, sort_keys=True))
    else:
        print(render(result))
    return 0
