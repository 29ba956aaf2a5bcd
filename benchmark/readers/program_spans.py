"""A statistic of the program's own spans over the measured window.

The program books its spans into ``paddle_tpu.observability.tracing``
(a ring in memory, stamped with ``perf_counter_ns``, the clock of
``run.started``): per request ``queue_wait`` and ``prefill``; per fit
step and per engine cycle ``fit.*`` and ``serving.*``.  ``params``:

    {"phase": p, "stat": "median" | "p90"}
        durations of the spans named p that START inside the window
    {"phase": p, "of_requests_with": q, "stat": ...}
        of each request (trace) whose first span named q starts inside
        the window, the duration of its first span named p
    {"gap": {"from_end_of": a, "to_end_of": b}, "stat": ...}
        of each span named a that starts inside the window, from its end
        to the end of the span named b with the NEXT number (``req_id``:
        the step or cycle) of the same trace

Milliseconds.  ``p90`` is nearest-rank, as the end-to-end tails are.
Nothing to read (a program without these spans, an untraced window, a
ring that overflowed and dropped spans) gives None.
"""
import statistics

from benchmark import arrivals

STATS = {"median": statistics.median,
         "p90": lambda values: arrivals.percentile(values, 90)}


def durations(spans, t0, t1, phase, of_requests_with=None):
    """Milliseconds; ``spans`` oldest first, as the ring gives them."""
    if of_requests_with is None:
        return [(s["end_ns"] - s["start_ns"]) / 1e6 for s in spans
                if s["phase"] == phase and t0 <= s["start_ns"] < t1]
    first = {}               # trace -> {phase: its first span}
    for s in spans:
        if s["phase"] in (phase, of_requests_with):
            first.setdefault(s["trace"], {}).setdefault(s["phase"], s)
    return [(f[phase]["end_ns"] - f[phase]["start_ns"]) / 1e6
            for f in first.values()
            if of_requests_with in f and phase in f
            and t0 <= f[of_requests_with]["start_ns"] < t1]


def gaps(spans, t0, t1, from_end_of, to_end_of):
    """Milliseconds from the end of each ``from_end_of`` span that starts
    inside the window to the end of the next number's ``to_end_of``."""
    ends = {(s["trace"], s["req_id"]): s["end_ns"] for s in spans
            if s["phase"] == to_end_of}
    out = []
    for s in spans:
        if s["phase"] == from_end_of and t0 <= s["start_ns"] < t1 \
                and isinstance(s["req_id"], int):
            nxt = ends.get((s["trace"], s["req_id"] + 1))
            if nxt is not None:
                out.append((nxt - s["end_ns"]) / 1e6)
    return out


def reduce(spans, t0, t1, params):
    values = gaps(spans, t0, t1, **params["gap"]) if "gap" in params \
        else durations(spans, t0, t1, params["phase"],
                       params.get("of_requests_with"))
    return STATS[params["stat"]](values) if values else None


def read(run, params):
    from paddle_tpu.observability import tracing
    window_s = run.obs.get("window_s")
    if run.setup_s is None or window_s is None or tracing.dropped_spans():
        return None
    t0 = (run.started + run.setup_s) * 1e9
    return reduce(tracing.spans(), t0, t0 + window_s * 1e9, params)
