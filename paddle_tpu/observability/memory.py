"""HBM memory ledger: per-surface static footprints + live-buffer
census + OOM forecasting (ISSUE 20).

Every roadmap item left (3D/4D parallelism, ring-attention context,
AOT-warmed autoscale, multi-LoRA serving) is gated by HBM, yet the
observability stack attributes *time* (roofline) and *compiles*
(``pt_compile_*``) while memory shows up only as a crash.  This module
is the two-sided ledger that closes the gap:

**Static side** — ``compilestats`` already lowers+compiles every
tracked jit surface once per signature; its hook now hands the FULL
``memory_analysis()`` breakdown here (argument / output / temp /
generated-code bytes, each getattr-guarded because XLA:CPU
under-reports temp and generated code — rows degrade to partial data
off-TPU, never to a traceback).  Booked as
``pt_memory_static_bytes{surface,kind}`` gauges, checked against a
configurable device HBM envelope (``PADDLE_HBM_BYTES``, default the
TPU v5e's 16 GiB — an over-envelope surface raises the guardian
``memory_budget`` event), and written by :func:`write_memory_json`
with one row for EVERY surface in the analysis registry
(never-compiled surfaces get explicit placeholder rows, so a vanished
surface is visible drift, not silence).

**Dynamic side** — a live-buffer census sampled ONLY at the flight
recorder's pre-existing sync points (hapi post-step, serving chunk
sync, router dispatch gap — the PR 13 discipline: zero added host
syncs).  :func:`census` walks ``jax.live_arrays()`` reading host
metadata only (``.nbytes`` — never a value), joins the registered
serving page pools' own bookkeeping (``PagedKVManager`` registers
itself by weakref), and produces ``pt_memory_live_bytes{pool}``,
KV-page occupancy/headroom, and a linear-trend OOM forecast
(``steps_to_exhaustion`` = headroom / least-squares growth slope over
the recent census history).  The census reconciles against the page
pool's analytic bookkeeping within 1% (machine-checked by
``tests/test_memory_ledger.py``), and the ``hbm_pressure`` watch rule
trips on the fields :func:`census_fields` merges into flight samples.

Import-light (stdlib + metrics + the peaks table; jax is used lazily
inside the census) and monitored by the host-sync lint with ZERO budgeted entries: a
device readback anywhere in this module is always a bug.
"""
import collections
import os
import threading
import time
import weakref

from . import metrics as _metrics
from ..device import chip as _chip

__all__ = [
    "KINDS", "HBM_ENVELOPE_ENV", "DEFAULT_HBM_BYTES", "hbm_envelope",
    "record_static", "static_snapshot", "register_kv_pool", "census",
    "census_fields", "history", "forecast", "snapshot",
    "write_memory_json", "ledger_records", "reset",
]

# memory_analysis() breakdown kinds, in ledger order ("total" rides
# along as the derived gauge row)
KINDS = ("argument", "output", "temp", "generated_code")

HBM_ENVELOPE_ENV = "PADDLE_HBM_BYTES"
# off the chip the ledger budgets against the chip the repo targets
# (the v5e row of the one peaks table, device/chip.py)
DEFAULT_HBM_BYTES = _chip.peaks(_chip.V5E).hbm_bytes

# forecast shape: least-squares slope over the last _TREND_WINDOW
# censuses, reported only after _TREND_MIN samples exist (a 2-point
# "trend" at startup would forecast exhaustion from warmup noise)
_TREND_WINDOW = 32
_TREND_MIN = 4

_LOCK = threading.Lock()
_STATIC = {}            # surface -> static row (see record_static)
_HISTORY = collections.deque(maxlen=512)     # census records
_POOLS = {}             # name -> weakref to a PagedKVManager-like pool
_POOL_IDS = iter(range(1 << 30))


def hbm_envelope():
    """Device HBM envelope in bytes (the per-surface budget
    denominator): ``PADDLE_HBM_BYTES`` when set; on a TPU backend the
    local ``device_kind``'s row of the peaks table (an unknown kind
    raises); elsewhere the target chip's."""
    raw = os.environ.get(HBM_ENVELOPE_ENV)
    if raw:
        try:
            v = int(float(raw))
            if v > 0:
                return v
        except ValueError:
            pass
    if _platform() == "tpu":
        return _chip.peaks().hbm_bytes
    return DEFAULT_HBM_BYTES


def _platform():
    """Backend name for the graceful-degradation note (XLA:CPU
    under-reports temp/generated-code bytes); never forces a backend
    init failure into the ledger."""
    try:
        import jax
        return str(jax.default_backend())
    except Exception:
        return "unknown"


# -- static side ------------------------------------------------------------

def record_static(surface, kinds, cost=None):
    """Book one surface's ``memory_analysis()`` breakdown (called from
    the compilestats hook at each compile; last signature wins, the
    same convention as the roofline's analytical columns).  ``kinds``
    maps each :data:`KINDS` name to bytes or None (off-TPU backends
    omit fields); ``cost`` is the cost_analysis dict when available."""
    kinds = {k: (int(kinds[k]) if kinds.get(k) is not None else None)
             for k in KINDS}
    known = [v for v in kinds.values() if v is not None]
    total = sum(known) if known else None
    envelope = hbm_envelope()
    frac = round(total / envelope, 6) if total is not None else None
    row = {"compiled": True, "kinds": kinds, "total_bytes": total,
           "budget_frac": frac,
           "flops": cost.get("flops") if cost else None,
           "bytes_accessed":
               cost.get("bytes accessed") if cost else None}
    with _LOCK:
        _STATIC[surface] = row
    if _metrics.enabled():
        for k, v in kinds.items():
            if v is not None:
                _metrics.set_gauge("pt_memory_static_bytes", v,
                                   surface=surface, kind=k)
        if total is not None:
            _metrics.set_gauge("pt_memory_static_bytes", total,
                               surface=surface, kind="total")
        if frac is not None:
            _metrics.set_gauge("pt_memory_budget_frac", frac,
                               surface=surface)
    if total is not None and total > envelope:
        from ..framework import guardian
        guardian.emit("memory_budget", surface=surface, bytes=total,
                      envelope=envelope, frac=frac)
    return row


def static_snapshot():
    """{surface: row} for every surface that compiled at least once."""
    with _LOCK:
        return {s: dict(r, kinds=dict(r["kinds"]))
                for s, r in sorted(_STATIC.items())}


# -- dynamic side -----------------------------------------------------------

def register_kv_pool(pool, name=None):
    """Register a page pool for the census (weakref — a dropped engine
    unregisters itself).  ``pool`` must expose the ``PagedKVManager``
    accounting surface: ``pages_in_use`` / ``resident_bytes`` /
    ``pool_bytes`` / ``num_pages`` / ``page_bytes`` and
    ``device_pools()``.  Returns the registered name.  Re-registering
    the same object (``PagedKVManager.reset()`` runs at construction
    AND on every reuse) keeps its existing name — one pool, one census
    row, never double-counted."""
    with _LOCK:
        for existing, ref in _POOLS.items():
            if ref() is pool:
                return existing
        if name is None:
            name = f"kv{next(_POOL_IDS)}"
        _POOLS[name] = weakref.ref(pool)
    return name


def _live_pools():
    """[(name, pool)] for registered pools still alive; prunes dead
    weakrefs in place."""
    out, dead = [], []
    with _LOCK:
        items = list(_POOLS.items())
    for name, ref in items:
        pool = ref()
        if pool is None:
            dead.append(name)
        else:
            out.append((name, pool))
    if dead:
        with _LOCK:
            for name in dead:
                _POOLS.pop(name, None)
    return out


def _trend_slope(values):
    """Least-squares slope of ``values`` over sample index, or None
    when no trend is computable."""
    n = len(values)
    if n < 2:
        return None
    mx = (n - 1) / 2.0
    my = sum(values) / n
    denom = sum((i - mx) ** 2 for i in range(n))
    if denom <= 0:
        return None
    num = sum((i - mx) * (v - my) for i, v in enumerate(values))
    return num / denom


def census(point=None):
    """One live-buffer census record (host metadata only — reading an
    array's ``.nbytes`` never touches the device).  Walks
    ``jax.live_arrays()`` for the process total, joins the registered
    page pools (both their analytic bookkeeping and the measured
    ``.nbytes`` of their device buffers — the two must reconcile within
    1%), and appends the record to the forecast history."""
    try:
        import jax
        arrays = jax.live_arrays()
    except Exception:
        arrays = []
    live_bytes = 0
    for x in arrays:
        nb = getattr(x, "nbytes", None)
        if nb:
            live_bytes += int(nb)
    kv_pool = kv_device = kv_resident = 0
    pages_in_use = pages_total = 0
    have_kv = False
    for _, pool in _live_pools():
        have_kv = True
        kv_pool += int(pool.pool_bytes)
        kv_resident += int(pool.resident_bytes)
        pages_in_use += int(pool.pages_in_use)
        # allocatable pages exclude the trash page (page 0)
        pages_total += max(int(pool.num_pages) - 1, 0)
        try:
            for layer in pool.device_pools():
                for buf in layer:
                    nb = getattr(buf, "nbytes", None)
                    if nb:
                        kv_device += int(nb)
        except Exception:
            kv_device += int(pool.pool_bytes)
    occupancy = (pages_in_use / pages_total
                 if have_kv and pages_total else None)
    # headroom exact per pool: free pages x that pool's page size
    headroom = None
    if have_kv:
        headroom = 0
        for _, pool in _live_pools():
            free = max(int(pool.num_pages) - 1 - int(pool.pages_in_use),
                       0)
            headroom += free * int(pool.page_bytes)
    rec = {
        "ts_ns": time.time_ns(),
        "perf_ns": time.perf_counter_ns(),
        "point": point,
        "live_bytes": live_bytes,
        "live_buffers": len(arrays),
        "pools": {"total": live_bytes,
                  "kv_pages": kv_device if have_kv else 0,
                  "other": max(live_bytes -
                               (kv_device if have_kv else 0), 0)},
        "kv_pool_bytes": kv_pool if have_kv else None,
        "kv_device_bytes": kv_device if have_kv else None,
        "kv_resident_bytes": kv_resident if have_kv else None,
        "kv_pages_in_use": pages_in_use if have_kv else None,
        "kv_pages_total": pages_total if have_kv else None,
        "kv_occupancy": (round(occupancy, 6)
                         if occupancy is not None else None),
        "kv_headroom_bytes": headroom,
    }
    with _LOCK:
        _HISTORY.append(rec)
    rec["steps_to_exhaustion"] = _forecast_locked()
    return rec


def _forecast_locked():
    """Linear-trend OOM forecast over the recent census history:
    censuses left until headroom hits zero at the current growth
    slope.  None when there is no computable upward trend (shrinking,
    flat, or fewer than ``_TREND_MIN`` samples)."""
    with _LOCK:
        recent = list(_HISTORY)[-_TREND_WINDOW:]
    if len(recent) < _TREND_MIN:
        return None
    last = recent[-1]
    if last.get("kv_resident_bytes") is not None:
        series = [r.get("kv_resident_bytes") or 0 for r in recent]
        headroom = last.get("kv_headroom_bytes") or 0
    else:
        series = [r.get("live_bytes") or 0 for r in recent]
        headroom = max(hbm_envelope() - series[-1], 0)
    slope = _trend_slope(series)
    if slope is None or slope <= 0:
        return None
    return round(headroom / slope, 2)


def census_fields(point=None):
    """Run one census and return the host fields the flight hook sites
    merge into their existing samples (the ``hbm_pressure`` watch rule
    reads exactly these keys); books the ``pt_memory_*`` gauges.
    Everything here is metadata the process already owns — the A/B
    device-transfer contract extends to this call verbatim."""
    rec = census(point)
    if _metrics.enabled():
        for pool, v in rec["pools"].items():
            _metrics.set_gauge("pt_memory_live_bytes", v, pool=pool)
        _metrics.set_gauge("pt_memory_live_buffers",
                           rec["live_buffers"])
        if rec["kv_occupancy"] is not None:
            _metrics.set_gauge("pt_memory_kv_occupancy",
                               rec["kv_occupancy"])
        if rec["kv_headroom_bytes"] is not None:
            _metrics.set_gauge("pt_memory_kv_headroom_bytes",
                               rec["kv_headroom_bytes"])
        steps = rec["steps_to_exhaustion"]
        _metrics.set_gauge("pt_memory_steps_to_exhaustion",
                           -1 if steps is None else steps)
    out = {"live_bytes": rec["live_bytes"]}
    for key in ("kv_occupancy", "kv_headroom_bytes",
                "steps_to_exhaustion"):
        if rec[key] is not None:
            out[key] = rec[key]
    return out


def history():
    """Census records, oldest first (the timeline's memory counter
    track and the bundle's ``memory.jsonl`` read this)."""
    with _LOCK:
        return list(_HISTORY)


def forecast():
    """Current ``steps_to_exhaustion`` (None = no upward trend)."""
    return _forecast_locked()


# -- artifacts --------------------------------------------------------------

def snapshot(envelope=None):
    """The full two-sided ledger document (the ``memory.json`` shape):
    one static row for EVERY surface in the analysis jit-surface
    registry — never-compiled surfaces get ``{"compiled": false}``
    placeholders so registry drift stays visible — plus the dynamic
    census/forecast summary."""
    envelope = envelope or hbm_envelope()
    from ..analysis.allowlist import COMPILE_SURFACES
    static = static_snapshot()
    surfaces = {}
    for s in sorted(set(COMPILE_SURFACES) | set(static)):
        row = static.get(s)
        if row is None:
            surfaces[s] = {"compiled": False,
                           "kinds": {k: None for k in KINDS},
                           "total_bytes": None, "budget_frac": None,
                           "flops": None, "bytes_accessed": None}
        else:
            surfaces[s] = row
    hist = history()
    return {
        "platform": _platform(),
        "hbm_envelope_bytes": envelope,
        "surfaces": surfaces,
        "dynamic": {
            "censuses": len(hist),
            "last": hist[-1] if hist else None,
            "steps_to_exhaustion": _forecast_locked(),
        },
    }


def write_memory_json(path, envelope=None):
    """Write the ledger snapshot to ``path`` atomically (tmp +
    ``os.replace``).  Returns the path."""
    import json
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(snapshot(envelope), f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def ledger_records():
    """Flat record list for the flight bundle's ``memory.jsonl``: one
    ``kind="static"`` line per compiled surface, then one
    ``kind="census"`` line per history record (oldest first)."""
    out = []
    for surface, row in static_snapshot().items():
        out.append(dict(row, kind="static", surface=surface))
    for rec in history():
        out.append(dict(rec, kind="census"))
    return out


def reset():
    """Drop static rows, census history and pool registrations (test
    isolation / bench per-run snapshots)."""
    with _LOCK:
        _STATIC.clear()
        _HISTORY.clear()
        _POOLS.clear()
