"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers use: device busy and idle time, time per device operation and per
program, and the idle gaps by what the host was doing.

``load`` reads the file with ``jax.profiler.ProfileData`` into plain
lists; ``reduce`` is arithmetic on those lists and is tested without a
trace.  The traced window is the host span ``bench.window`` that the
harness records around the traced part of the run; everything is clipped
to it.
"""
import glob
import os
import re

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# gaps between consecutive operations of one program are the device's own
# launch overhead, not the host's doing: they share one name
SHORT_GAP_NS = 10_000
SHORT_GAPS = "between_ops_under_10us"
_SUFFIX = re.compile(r"(\.\d+)+$")
_MODULE_ID = re.compile(r"\(\d+\)$")


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def op_name(name):
    """One name per kind of operation.  The device line names an event by
    its whole HLO instruction, ``%fusion.123 = f32[..] fusion(..)``: keep
    the instruction's name and drop its number, so that the layers' copies
    of one operation add up."""
    name = name.split(" = ", 1)[0].lstrip("%")
    return _SUFFIX.sub("", name)


def device_index(plane_name):
    """The device's index where the plane is a device's, else None."""
    m = DEVICE_PLANE.match(plane_name)
    return int(m.group(1)) if m else None


def line_kind(line_name):
    """"ops" / "modules" for the two lines of a device plane that the
    reduction reads, else None."""
    return {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line_name)


def load(path):
    """{"devices": {index: {"ops": [...], "modules": [...]}},
    "spans": [...]}, every event as (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        index = device_index(plane.name)
        if index is not None:
            dev = devices.setdefault(index, {"ops": [], "modules": []})
            for line in plane.lines:
                kind = line_kind(line.name)
                if kind is not None:
                    dev[kind].extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def _union(intervals):
    """Merge (start, end) pairs into disjoint sorted intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def _clip(events, t0, t1):
    out = []
    for name, s, e in events:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((name, s, e))
    return out


def self_times(events):
    """(name, self seconds) per event: its duration less that of the
    events nested inside it on the same line (a ``while`` holds the
    operations of its body)."""
    out, stack = [], []          # stack of [name, end, self_ns]
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            done = stack.pop()
            out.append((done[0], done[2] / 1e9))
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    out.extend((name, ns / 1e9) for name, _, ns in stack)
    return out


def reduce(loaded, top=10):
    """The reduction.  Times in seconds.  Device 0 is the lowest device
    index; ``busy_s`` is the mean over all devices of the union of their
    operations' intervals inside the window."""
    windows = [(s, e) for name, s, e in loaded["spans"]
               if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host span")
    t0, t1 = min(s for s, _ in windows), max(e for _, e in windows)
    if not loaded["devices"]:
        raise ValueError("the trace holds no device plane")
    busy = []
    for idx in sorted(loaded["devices"]):
        ops = _clip(loaded["devices"][idx]["ops"], t0, t1)
        busy.append(sum(e - s for s, e in
                        _union((s, e) for _, s, e in ops)) / 1e9)
    dev0 = loaded["devices"][min(loaded["devices"])]
    ops0 = _clip(dev0["ops"], t0, t1)
    per_op, per_module = {}, {}
    for name, sec in self_times(ops0):
        k = op_name(name)
        per_op[k] = per_op.get(k, 0.0) + sec
    for name, s, e in _clip(dev0["modules"], t0, t1):
        k = _MODULE_ID.sub("", name)
        sec, n = per_module.get(k, (0.0, 0))
        per_module[k] = (sec + (e - s) / 1e9, n + 1)
    # idle gaps of device 0, each given to the innermost benchmark span
    # that covers its middle
    spans = sorted((e - s, name, s, e) for name, s, e in loaded["spans"]
                   if name != WINDOW_SPAN)
    gaps = {}
    merged = _union((s, e) for _, s, e in ops0)
    edges = [t0] + [t for iv in merged for t in iv] + [t1]
    for gs, ge in zip(edges[0::2], edges[1::2]):
        if ge <= gs:
            continue
        if ge - gs < SHORT_GAP_NS:
            owner = SHORT_GAPS
        else:
            mid = (gs + ge) / 2
            owner = next((name for _, name, s, e in spans
                          if s <= mid <= e), "no_span")
        gaps[owner] = gaps.get(owner, 0.0) + (ge - gs) / 1e9
    window_s = (t1 - t0) / 1e9
    busy0 = busy[0]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        "busy0_s": busy0,
        "idle0_share": 1.0 - busy0 / window_s,
        "op_seconds": per_op,
        "module_seconds": per_module,
        "gap_seconds": gaps,
        "breakdown": {
            "device_ops": [[k, v] for k, v in sorted(
                per_op.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[k, v] for k, v in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:top]],
        },
    }


def reduce_dir(trace_dir):
    return reduce(load(find_xplane(trace_dir)))
