"""The benchmark's harness: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file found by the name ``BENCHMARK.json`` gives:

    configs/<config>.json   traffic/<mix>.json   limits/<cell>.json
    families/<family>.py    (the configuration's "family": its program,
                            weights and counts) and its plain reference
                            (the configuration's "reference", a path)
    drivers/<kind>.py       (the mix's "kind")
    metrics/<metric>.json   readers/<reader>.py  (the metric's "reader")

The last line of standard output is the result object; everything else
(the split of set-up, compile counts, generator lateness, the numbers
compared beside their limits) goes on earlier lines and to standard
error.
"""
import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NO_CHIP_EXIT = 3


def say(event, **facts):
    """One JSON line on standard output, flushed (a run may be cut)."""
    print(json.dumps({"event": event, **facts}), flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_manifest(root=ROOT):
    return load_json(root, "BENCHMARK.json")


def cell_metrics(manifest, cell, group):
    """The entries of ``group`` ("end_to_end" or "per_layer") that the
    cell reports: those that list it, and those with no list whose
    ``moves`` (or, end to end, whose own name) the cell reports."""
    e2e = [m for m in manifest["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if group == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in manifest["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in names)]


def require_chip(chips):
    """The devices of this process, or exit: no accelerator, or fewer
    chips than the cell asks for, prints no result."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmark: needs {chips} TPU chip(s); JAX reports "
              f"{len(devices)} x {devices[0].platform!r}", file=sys.stderr)
        sys.exit(NO_CHIP_EXIT)
    return devices[:chips]


def enable_compile_cache():
    """JAX's persistent cache at the program's fixed directory
    (``JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``), with no
    threshold: the sub-second programs are most of a run's compiles."""
    import jax
    from paddle_tpu.device import chip
    directory = chip.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory


class CompileMeter:
    """Compile accounting from JAX's monitoring events (copied from
    ``chip_smoke.py``): backend compiles, their seconds, and persistent
    cache hits and misses."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.compiles = self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            self.compile_s += secs
            self.compiles += 1

    def _on_event(self, name, **kw):
        if name.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("compilation_cache/cache_misses"):
            self.misses += 1

    def snap(self):
        return {"compiles": self.compiles, "cache_hits": self.hits,
                "cache_misses": self.misses,
                "compile_s": round(self.compile_s, 3)}


class Tracer:
    """Profiles the part [start_s, end_s) of the window; drivers call
    ``tick(elapsed)`` at step boundaries.  The host span ``bench.window``
    marks the traced part for the reducer.

    The profiler's stop writes the trace and holds its caller for as long
    as that takes (tens of seconds at a serving cell's size).  That time
    is the benchmark's own: ``stop`` books it in ``held_s`` and prints it,
    and a driver reads the window's time from ``clock()``, which stands
    still across it."""

    def __init__(self, enabled, directory, span):
        self.directory = directory
        self.start_s, self.end_s = span
        self.state = "armed" if enabled else "off"
        self.held_s = 0.0
        self._window = None

    def clock(self):
        """The window's clock: the host's, less what ``stop`` held."""
        return time.perf_counter() - self.held_s

    @property
    def active(self):
        return self.state == "on"

    def tick(self, elapsed):
        """Returns "started" / "stopped" when this tick changed the state."""
        import jax
        if self.state == "armed" and elapsed >= self.start_s:
            shutil.rmtree(self.directory, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # host spans only: ours
            jax.profiler.start_trace(self.directory,
                                     profiler_options=options)
            self._window = jax.profiler.TraceAnnotation("bench.window")
            self._window.__enter__()
            self.state = "on"
            return "started"
        if self.state == "on" and elapsed >= self.end_s:
            self.stop()
            return "stopped"
        return None

    def stop(self):
        import jax
        if self.state == "on":
            t0 = time.perf_counter()
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = "done"
            self.held_s += time.perf_counter() - t0
            say("trace_written", seconds=round(self.held_s, 3))


def span(name):
    """A host span of the benchmark's own in the profiler's trace."""
    import jax
    return jax.profiler.TraceAnnotation(name)


class Check:
    """The numbers ``correct`` compares, each beside its limit."""

    def __init__(self):
        self.rows = []

    def at_most(self, name, value, limit):
        ok = value is not None and value == value and value <= limit
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": bool(ok)})
        return ok

    @property
    def correct(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)


class Run:
    """What a driver and the readers see of one run."""

    def __init__(self, args, manifest, root, started):
        self.root, self.manifest, self.started = root, manifest, started
        self.cell_name = args.workload
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace_on = bool(args.trace)
        cells = {c["name"]: c for c in manifest["workloads"]}
        if args.workload not in cells:
            raise SystemExit(f"benchmark: no workload {args.workload!r} in "
                             f"BENCHMARK.json (has {sorted(cells)})")
        self.cell = cells[args.workload]
        configs = {c["name"]: c for c in manifest["configs"]}
        config_file = configs[self.cell["config"]]["file"]
        self.config = load_json(root, config_file)
        for key in ("family", "reference"):
            if key not in self.config:
                raise KeyError(f"benchmark: {config_file} names no {key!r}")
        self.model = self.config["model"]
        self.family = load_module(root, "families", self.config["family"])
        self.reference = load_module_file(root, self.config["reference"])
        self.traffic = load_json(root, "benchmark", "traffic",
                                 self.cell["traffic"] + ".json")
        self.limits = load_json(root, "benchmark", "limits",
                                self.cell_name + ".json")
        self.chips = self.cell["chips"]
        self.check = Check()
        self.obs = {}            # counters and spans of the drivers
        self.trace = None        # trace_reduce.reduce(...) in a traced run
        self.setup_s = None
        self.memory_peak_bytes = None
        self.devices = self.meter = self.tracer = self.peaks = None

    def window_starts(self, **split):
        """Set-up ends here: stamps ``setup_s`` and prints its split."""
        self.setup_s = time.perf_counter() - self.started
        self.compiles_before_window = self.meter.compiles
        say("setup", setup_s=round(self.setup_s, 3), **split,
            **self.meter.snap())

    def window_closed(self):
        """Reads what must be read before the reference touches the
        chip: compiles inside the window and the peak memory."""
        self.obs["compiles_in_window"] = \
            self.meter.compiles - self.compiles_before_window
        self.memory_peak_bytes = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in self.devices)
        self.obs["memory_peak_bytes"] = self.memory_peak_bytes
        say("window_closed", compiles_in_window=self.obs["compiles_in_window"],
            memory_peak_bytes=self.memory_peak_bytes)


def load_module_file(root, relative):
    """The module in ``<root>/<relative>``; a file that is not there is
    an error that names it."""
    path = os.path.join(root, relative)
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark: no file {relative} in {root}")
    spec = importlib.util.spec_from_file_location(
        os.path.splitext(relative)[0].replace("/", "."), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_module(root, kind, name):
    """``<root>/benchmark/<kind>/<name>.py``, found by the name a data
    file gives (a family by a configuration's "family", a driver by a
    mix's "kind", a reader by a metric's "reader")."""
    return load_module_file(root, f"benchmark/{kind}/{name}.py")


def read_metric(run, name):
    """One per-layer metric through its reader, or None where the reader
    finds nothing to read."""
    spec = load_json(run.root, "benchmark", "metrics", name + ".json")
    reader = load_module(run.root, "readers", spec["reader"])
    return reader.read(run, spec.get("params", {}))


def result_line(run, end_to_end, attempted, failed):
    manifest, cell = run.manifest, run.cell_name
    metrics = {}
    if run.trace_on:
        for m in cell_metrics(manifest, cell, "per_layer"):
            value = read_metric(run, m["name"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(end_to_end, setup_s=run.setup_s)
        for m in cell_metrics(manifest, cell, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    d0 = run.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    line = {"correct": run.check.correct, "attempted": attempted,
            "failed": failed, "metrics": metrics, "device": device}
    if run.trace_on:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = run.trace["breakdown"]
    line["compared"] = {r["name"]: [r["value"], r["limit"]]
                        for r in run.check.rows}
    return line


def main(argv=None, started=None, root=ROOT):
    started = time.perf_counter() if started is None else started
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    run = Run(args, load_manifest(root), root, started)
    run.devices = require_chip(run.chips)
    from benchmark import flops
    run.peaks = flops.peaks(run.devices[0].device_kind)
    cache_dir = enable_compile_cache()
    run.meter = CompileMeter()
    trace_dir = os.path.join(root, ".bench_trace", run.cell_name)
    run.tracer = Tracer(run.trace_on, trace_dir,
                        run.traffic.get("trace_window_s", [2.0, 8.0]))
    say("start", workload=run.cell_name, seed=run.seed,
        seconds=run.seconds, trace=run.trace_on, compile_cache=cache_dir,
        platform=run.devices[0].platform, kind=run.devices[0].device_kind,
        count=len(run.devices))
    driver = load_module(root, "drivers", run.traffic["kind"])
    end_to_end, attempted, failed = driver.run(run)
    if run.trace_on:
        from benchmark import trace_reduce
        t0 = time.perf_counter()
        run.trace = trace_reduce.reduce_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        say("trace_reduced", seconds=round(time.perf_counter() - t0, 2),
            window_s=run.trace["window_s"], busy_s=run.trace["busy_s"],
            modules={k: list(v) for k, v in
                     run.trace["module_seconds"].items()})
    line = result_line(run, end_to_end, attempted, failed)
    for r in run.check.rows:
        print(f"compared {r['name']}: {r['value']!r} limit {r['limit']!r} "
              f"{'ok' if r['ok'] else 'FAILED'}", file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0
