"""The program's spans: where one request's latency went, and where one
step of each loop (``Model.fit``, ``ServingEngine.step``) spent the host.

The PR 5 timeline shows the *engine's* spans (prefill/chunk/sync); a
single slow request is invisible in them — its TTFT might be queue
wait, a cold prefill, a page eviction, or plain decode cadence.  This
module gives every request a trace id (minted at ``submit()``) and
books one span per lifecycle phase:

- ``route``      — submit (or drain-requeue) -> replica dispatch by the
  fleet router (args: pick reason ``affinity | least_loaded | shed``
  and the replica index; absent for single-engine serving);
- ``queue_wait`` — replica dispatch (or submit / page-pressure
  requeue, whichever is latest) -> slot admission;
- ``prefill``    — admission -> the chunk-boundary sync that streamed
  its first token (args: bucket, prefix-hit/cached tokens, resume flag);
- ``decode`` / ``spec_decode`` — one span per decode chunk the request
  participated in, tiling sync-to-sync (args: tokens emitted);
- ``page_evict`` — instant: preempted back to the queue;
- finish is the end of the last span (reason in its args).

THE contract (the PR 5 discipline, A/B-verified by
``tests/test_compile_tracing.py``): spans are booked **only from host
timestamps the engine already owns** — ``submit_ns``/``admit_ns`` are
host-side scheduler stamps, and every span end is the engine's ONE
bundled ``device_get`` per chunk.  Tracing adds zero host syncs; by
construction a request's spans tile submit -> finish, so their sum
equals its measured wall time (the machine-checked invariant).

Program spans (``fit.*``, ``serving.*``) are booked through ONE
mechanism, :func:`region`: a context manager that enters a
``profiler.RecordEvent`` (so the span is in the jax profiler's trace, on
the clock the device trace shares, and in ``Profiler.summary``) and
books the same interval into this ring on exit.  Every span carries an
``id`` and the ``parent`` that caused it; the request spans of a cycle
name that cycle's ``serving.step``.  The table of spans, parents and
booking sites is in ``docs/observability.md`` ("Program spans").

:data:`SCOPES` is the fixed vocabulary of ``jax.named_scope`` names the
device programs carry (:func:`scope`); ``report --device`` keys device
time by it.

Sinks: per-request lanes in the merged chrome trace
(``timeline.export_chrome_trace``) and the ``report --requests`` view
(TTFT/TPOT percentiles with per-phase tail attribution).  Import-light:
stdlib only, gated by the same :func:`metrics.enabled` switch as every
other recorder.
"""
import collections
import contextlib
import itertools
import threading
import time

from . import metrics as _metrics

__all__ = ["mint", "span", "instant", "region", "finish", "spans", "reset",
           "dropped_spans", "request_summaries", "scope", "SCOPES",
           "is_program_span", "Region"]

# the named scopes of the device programs (models/gpt.py,
# inference/kvcache.py, hapi/model.py), innermost wins; under
# value_and_grad the name stack splits each into forward
# ``jvp(<scope>)`` and backward ``transpose(jvp(<scope>))`` for free
SCOPES = ("embed", "norm", "attention.qkv", "attention.core",
          "attention.out", "kv.gather", "kv.scatter", "mlp", "lm_head",
          "xent", "sample", "amp_cast", "optimizer", "guard",
          # latent attention (models/mla_moe.py): the latent projection,
          # its norm, rotation and expansion; the absorbed form's two
          # products with W_kvb
          "attention.latent_kv", "attention.absorb",
          # the dropless expert layer (incubate/.../moe/dropless.py)
          "moe.router", "moe.dispatch", "moe.experts", "moe.shared",
          "moe.combine")

_SPANS = collections.deque(maxlen=65536)
_LOCK = threading.Lock()
_IDS = itertools.count()
_SPAN_IDS = itertools.count(1)
# ring overflow tally: once the deque wraps, the oldest requests lose
# their queue_wait/prefill spans and the tiling invariant no longer
# holds for them — consumers must be able to SEE that it happened
# (timeline export stamps it into the trace; drain with reset())
_DROPPED = [0]


def is_program_span(phase):
    """True for the spans of the two loops (``fit``, ``fit.*``,
    ``serving.*``); everything else in the ring is a phase of one
    request (``queue_wait``, ``prefill``, ``decode``, ...)."""
    return phase == "fit" or phase.startswith(("fit.", "serving."))


def mint(req_id):
    """Mint a trace id for one submitted request — unique per process
    even when engines (and their req_id counters) are rebuilt."""
    return f"t{next(_IDS)}-r{req_id}"


def span(trace_id, req_id, phase, start_ns, end_ns, parent=None,
         span_id=None, **args):
    """Book one [start_ns, end_ns] perf_counter_ns span.  Both stamps
    must be host values the caller already owned (never taken around a
    new device readback).  ``parent`` is the ``id`` of the span that
    caused this one; returns this span's ``id`` (None with the gate
    off)."""
    if not _metrics.enabled():
        return None
    if span_id is None:
        span_id = next(_SPAN_IDS)
    with _LOCK:
        dropped = len(_SPANS) == _SPANS.maxlen
        if dropped:
            _DROPPED[0] += 1
        _SPANS.append({"trace": trace_id, "req_id": req_id,
                       "phase": phase, "start_ns": int(start_ns),
                       "end_ns": int(end_ns), "id": span_id,
                       "parent": parent, "args": args})
    _metrics.inc("pt_trace_spans_total", phase=phase)
    if dropped:
        # overflow is a real counter, not just a module tally: the
        # prom sink must show the trace view under-reporting even
        # when nobody exports a timeline
        _metrics.inc("pt_trace_dropped_spans_total")
    return span_id


class Region:
    """What :func:`region` yields: the span's ``id`` (known from entry,
    so children can name it before it is booked), its stamps, and the
    ``args`` booked with it (a body may add to them).  With the gate off
    every field stays None and nothing is booked."""
    __slots__ = ("id", "start_ns", "end_ns", "args")

    def __init__(self, args):
        self.id = self.start_ns = self.end_ns = None
        self.args = args


@contextlib.contextmanager
def region(trace_id, req_id, name, parent=None, start_ns=None, **args):
    """THE span mechanism of the program's loops: enter a
    ``profiler.RecordEvent(name)`` (the jax profiler's trace and
    ``Profiler.summary`` see the span, on the device trace's clock) and
    book the same interval into the ring on exit, exception or not.
    Both stamps are host ``perf_counter_ns`` reads; nothing here touches
    the device.  ``start_ns`` lets a span begin at its elder sibling's
    ``end_ns``, and a body may set ``end_ns`` to its last child's, so
    that children tile their parent exactly.  Gated by
    :func:`metrics.enabled` like every recorder: off, the body runs
    bare."""
    r = Region(args)
    if not _metrics.enabled():
        yield r
        return
    from ..profiler import RecordEvent
    r.id = next(_SPAN_IDS)
    event = RecordEvent(name)
    r.start_ns = time.perf_counter_ns() if start_ns is None else start_ns
    event.begin()
    try:
        yield r
    finally:
        event.end()
        if r.end_ns is None:     # a body may pin it to its last child's
            r.end_ns = time.perf_counter_ns()
        span(trace_id, req_id, name, r.start_ns, r.end_ns, parent=parent,
             span_id=r.id, **r.args)


def scope(name):
    """``jax.named_scope(name)`` for a name of :data:`SCOPES`: metadata
    on the operations traced inside it (``op_name`` in the HLO and in
    the profiler's device events), no operation of its own."""
    if name not in SCOPES:
        raise ValueError(f"scope {name!r} is not in tracing.SCOPES")
    import jax
    return jax.named_scope(name)


def instant(trace_id, req_id, phase, ts_ns, **args):
    """Book a zero-duration marker (eviction, resume)."""
    span(trace_id, req_id, phase, ts_ns, ts_ns, **args)


def spans():
    """Snapshot, oldest first."""
    with _LOCK:
        return list(_SPANS)


def dropped_spans():
    """Spans evicted by ring overflow since the last :func:`reset` —
    nonzero means the oldest traces in :func:`spans` are incomplete
    (their summaries under-report early phases)."""
    return _DROPPED[0]


def reset():
    with _LOCK:
        _SPANS.clear()
        _DROPPED[0] = 0


def finish(tpot_ms=None):
    """Book the request-level summary counters at finish (all host
    numbers computed from existing stamps)."""
    if not _metrics.enabled():
        return
    _metrics.inc("pt_trace_requests_total")
    if tpot_ms is not None:
        _metrics.observe("pt_trace_tpot_ms", tpot_ms)


def request_summaries(span_list=None):
    """Fold spans into one record per trace id: total/queue/prefill/
    decode milliseconds, ttft (queue+prefill), tokens and tpot.  Used
    by ``report --requests`` and the span-sum test."""
    per = {}
    for s in (span_list if span_list is not None else spans()):
        if is_program_span(s["phase"]):
            continue           # the loops' own spans are no request's
        r = per.setdefault(s["trace"], {
            "trace": s["trace"], "req_id": s["req_id"],
            "start_ns": s["start_ns"], "end_ns": s["end_ns"],
            "tokens": 0, "evictions": 0, "phase_ms": {}})
        r["start_ns"] = min(r["start_ns"], s["start_ns"])
        r["end_ns"] = max(r["end_ns"], s["end_ns"])
        dur = (s["end_ns"] - s["start_ns"]) / 1e6
        ph = s["phase"]
        if "replica" in s["args"]:
            # LAST replica that touched the request (a drained request
            # finishes on a survivor — that's the one tail attribution
            # should blame); report --per-replica groups on this
            r["replica"] = s["args"]["replica"]
        if ph == "page_evict":
            r["evictions"] += 1
            continue
        if ph == "drain":
            continue           # instant marker (replica death), no wall
        r["phase_ms"][ph] = r["phase_ms"].get(ph, 0.0) + dur
        r["tokens"] += int(s["args"].get("tokens", 0))
        if ph == "prefill" and "first_token_end_ns" not in r:
            r["first_token_end_ns"] = s["end_ns"]
        if s["args"].get("reason"):
            r["reason"] = s["args"]["reason"]
    out = []
    for r in per.values():
        r["total_ms"] = (r["end_ns"] - r["start_ns"]) / 1e6
        r["span_sum_ms"] = round(sum(r["phase_ms"].values()), 3)
        decode = r["phase_ms"].get("decode", 0.0) + \
            r["phase_ms"].get("spec_decode", 0.0)
        # TTFT from the FIRST prefill span's end (an evicted request's
        # re-prefill must not restart its clock)
        first = r.pop("first_token_end_ns", r["end_ns"])
        r["ttft_ms"] = round((first - r["start_ns"]) / 1e6, 3)
        r["tpot_ms"] = round(decode / (r["tokens"] - 1), 3) \
            if r["tokens"] > 1 else None
        r["phase_ms"] = {k: round(v, 3)
                         for k, v in sorted(r["phase_ms"].items())}
        out.append(r)
    return sorted(out, key=lambda r: r["start_ns"])
