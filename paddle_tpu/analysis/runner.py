"""Unified runner for the static-analysis passes.

``python -m paddle_tpu.analysis`` (or ``python tools/lint.py``) runs all
passes over the repo; ``--json`` emits machine-readable findings; the
committed baseline (``tools/lint_baseline.json``) suppresses
pre-existing findings so only NEW ones fail the run (exit 1).  Update
the baseline deliberately with ``--update-baseline`` — a growing
baseline is a growing debt, and the diff shows it.
"""
import argparse
import json
import os
import sys

from .base import Finding, ProjectIndex, collect_py_files, \
    collect_text_files

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_BASELINE = os.path.join("tools", "lint_baseline.json")


def _passes():
    # imported lazily so `from paddle_tpu.analysis import jit_surface`
    # stays free of the pass machinery
    from .tracer_safety import TracerSafetyPass
    from .host_sync import HostSyncPass
    from .collective_order import CollectiveOrderPass
    from .donation import DonationPass
    from .retrace_hazard import RetraceHazardPass
    from .concurrency import ConcurrencyPass
    from .mesh_axes import MeshAxesPass
    from .dtype_flow import DtypeFlowPass
    from .spec_drift import SpecDriftPass
    from .registry_lints import (FailpointRefsPass, GuardianLogSchemaPass,
                                 MetricNamesPass)
    return {p.name: p for p in (TracerSafetyPass, HostSyncPass,
                                CollectiveOrderPass, DonationPass,
                                RetraceHazardPass, ConcurrencyPass,
                                MeshAxesPass, DtypeFlowPass,
                                SpecDriftPass,
                                FailpointRefsPass, GuardianLogSchemaPass,
                                MetricNamesPass)}


class Context:
    """What a pass sees: the parsed code index plus the reference files
    (tests/docs) the registry lints scan."""

    def __init__(self, root, py_files, ref_files, default_tree):
        self.root = root
        self.py_files = py_files
        self.ref_files = ref_files
        self.default_tree = default_tree
        self._index = None

    @property
    def index(self):
        if self._index is None:
            self._index = ProjectIndex(self.root, self.py_files)
        return self._index


def make_context(paths=None, root=None):
    if paths:
        missing = [p for p in paths if not os.path.exists(p)]
        if missing:
            raise ValueError(f"path(s) do not exist: {missing}")
        py = collect_py_files(paths)
        ref = collect_text_files(paths)
        if not py and not ref:
            raise ValueError(
                f"no .py/.md files found under {list(paths)} — a typo'd "
                "path must not report a green lint")
        # in-repo scoped runs keep the registry lints' reference scope
        # identical to the default run (tests/ + docs/): package source
        # is analyzed code, not a reference corpus — a docstring example
        # must not fail a scoped run that the full run passes
        def _is_ref(f):
            rel = os.path.relpath(os.path.abspath(f), REPO_ROOT)
            return rel.replace(os.sep, "/").startswith(("tests/", "docs/"))
        if all(os.path.commonpath([REPO_ROOT, os.path.abspath(p)])
               == REPO_ROOT for p in paths):
            ref = [f for f in ref if _is_ref(f)]
        if root is None:
            # paths inside the repo keep repo-rooted relpaths so the
            # relpath-keyed policy (monitored modules, EXTRA surfaces,
            # baseline keys) applies identically to partial runs;
            # out-of-tree fixtures root at their common parent
            absolute = [os.path.abspath(p) for p in paths]
            if all(os.path.commonpath([REPO_ROOT, a]) == REPO_ROOT
                   for a in absolute):
                root = REPO_ROOT
            else:
                dirs = [a if os.path.isdir(a) else os.path.dirname(a) or "."
                        for a in absolute]
                root = os.path.commonpath(dirs)
        return Context(os.path.abspath(root), py, ref, default_tree=False)
    root = os.path.abspath(root or REPO_ROOT)
    py = collect_py_files([os.path.join(root, "paddle_tpu")])
    ref = collect_text_files([os.path.join(root, "tests"),
                              os.path.join(root, "docs")])
    return Context(root, py, ref, default_tree=True)


def run_passes(paths=None, passes=None, root=None, ctx=None,
               timings=None):
    """Run the selected passes; returns a deterministically-ordered
    Finding list (parse failures included as `parse` findings).  Pass
    a dict as ``timings`` to collect per-pass wall seconds plus the
    ``"total"`` (the sweep shares one parsed-module cache across
    passes, and ``--json`` reports the resulting wall time)."""
    import time
    ctx = ctx or make_context(paths, root)
    registry = _passes()
    names = list(registry) if not passes else list(passes)
    unknown = [n for n in names if n not in registry]
    if unknown:
        raise ValueError(
            f"unknown pass(es) {unknown}; known: {sorted(registry)}")
    findings = []
    ast_passes = {"tracer-safety", "host-sync", "collective-order",
                  "donation", "retrace-hazard", "concurrency",
                  "mesh-axes", "dtype-flow", "spec-drift"}
    t_total = time.perf_counter()
    if any(n in ast_passes for n in names):
        for rel, msg in ctx.index.errors:
            findings.append(Finding("parse", rel, 1, "<module>",
                                    "syntax-error", msg, "syntax"))
    for name in names:
        t0 = time.perf_counter()
        findings.extend(registry[name]().run(ctx))
        if timings is not None:
            timings[name] = round(time.perf_counter() - t0, 4)
    if timings is not None:
        timings["total"] = round(time.perf_counter() - t_total, 4)
    return sorted(findings, key=Finding.sort_key)


# -- baseline --------------------------------------------------------------

def load_baseline(path):
    if not path or not os.path.exists(path):
        return {}
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    return dict(data.get("findings", {}))


def write_baseline(path, findings):
    counts = {}
    for f in findings:
        counts[f.key()] = counts.get(f.key(), 0) + 1
    data = {"version": 1,
            "comment": "pre-existing lint findings suppressed by "
                       "paddle_tpu.analysis; shrink me, don't grow me "
                       "(--update-baseline)",
            "findings": {k: counts[k] for k in sorted(counts)}}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=1, sort_keys=True)
        f.write("\n")


def split_new(findings, baseline_counts):
    """Partition findings into (new, baselined) against baseline key
    counts — the first N occurrences of a key are baselined, the rest
    are new."""
    seen = {}
    new, old = [], []
    for f in findings:
        k = f.key()
        seen[k] = seen.get(k, 0) + 1
        if seen[k] <= baseline_counts.get(k, 0):
            old.append(f)
        else:
            new.append(f)
    return new, old


# -- changed-only scoping --------------------------------------------------

def git_changed_files(root):
    """Repo files changed vs HEAD (staged + unstaged) plus untracked,
    filtered to the extensions the passes read and to files that still
    exist.  Used by ``--changed-only`` so the inner loop lints the diff
    while CI stays exhaustive."""
    import subprocess
    out = []
    for cmd in (["git", "diff", "--name-only", "HEAD"],
                ["git", "ls-files", "--others", "--exclude-standard"]):
        try:
            res = subprocess.run(cmd, cwd=root, capture_output=True,
                                 text=True, timeout=30)
        except (OSError, subprocess.TimeoutExpired) as e:
            raise RuntimeError(f"--changed-only needs git: {e}")
        if res.returncode != 0:
            raise RuntimeError(
                f"--changed-only: `{' '.join(cmd)}` failed: "
                f"{res.stderr.strip()}")
        out.extend(res.stdout.splitlines())
    files = []
    for rel in sorted(set(out)):
        if not rel.endswith((".py", ".md")):
            continue
        path = os.path.join(root, rel)
        if os.path.exists(path):          # deleted files have no AST
            files.append(path)
    return files


# -- CLI -------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis",
        description="Static-analysis suite: tracer-safety, host-sync "
                    "budget, collective-order and registry lints.")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to analyze (default: the repo's "
                         "paddle_tpu/ + tests/ + docs/)")
    ap.add_argument("--passes", default=None,
                    help="comma-separated pass subset (see --list-passes)")
    ap.add_argument("--list-passes", action="store_true")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable findings on stdout")
    ap.add_argument("--baseline", default=None,
                    help="baseline file (default: tools/lint_baseline.json "
                         "for full-tree runs)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore any baseline: all findings are new")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline to the current findings "
                         "and exit 0")
    ap.add_argument("--changed-only", action="store_true",
                    help="lint only files changed vs git HEAD (plus "
                         "untracked) — the inner-loop mode; CI runs "
                         "the full sweep")
    args = ap.parse_args(argv)

    if args.list_passes:
        for name in _passes():
            print(name)
        return 0

    passes = [p.strip() for p in args.passes.split(",")] \
        if args.passes else None
    paths = args.paths or None
    if args.changed_only:
        if paths:
            print("error: --changed-only and explicit paths are "
                  "mutually exclusive", file=sys.stderr)
            return 2
        if args.update_baseline:
            print("error: --update-baseline needs the full default "
                  "tree, not a --changed-only subset", file=sys.stderr)
            return 2
        try:
            paths = git_changed_files(REPO_ROOT)
        except RuntimeError as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        if not paths:
            print("OK: no changed .py/.md files vs HEAD "
                  "(--changed-only)")
            return 0
    timings = {}
    try:
        ctx = make_context(paths)
        findings = run_passes(passes=passes, ctx=ctx, timings=timings)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2

    baseline_path = args.baseline
    if baseline_path is None and ctx.root == REPO_ROOT:
        # in-repo runs (full tree OR explicit repo paths) share the
        # committed baseline — relpaths are repo-rooted either way, so
        # a partial run must not re-fail already-baselined findings
        baseline_path = os.path.join(ctx.root, DEFAULT_BASELINE)
    if args.update_baseline:
        if not baseline_path or \
                (not ctx.default_tree and args.baseline is None) or \
                (passes is not None and args.baseline is None):
            # a partial run (path subset OR pass subset) must never
            # overwrite the shared baseline — it would erase every
            # finding outside its scope
            print("error: --update-baseline needs the full default tree "
                  "with all passes, or an explicit --baseline",
                  file=sys.stderr)
            return 2
        write_baseline(baseline_path, findings)
        print(f"baseline updated: {len(findings)} finding(s) -> "
              f"{os.path.relpath(baseline_path, ctx.root)}")
        return 0

    baseline = {} if args.no_baseline else load_baseline(baseline_path)
    new, old = split_new(findings, baseline)

    if args.as_json:
        new_ids = {id(f) for f in new}
        out = {"total": len(findings), "new": len(new),
               "baselined": len(old),
               "wall_time_s": timings,
               "findings": [dict(f.to_dict(), new=(id(f) in new_ids))
                            for f in findings]}
        print(json.dumps(out, indent=1, sort_keys=True))
        return 1 if new else 0

    for f in new:
        print(f"NEW {f!r}")
    if old:
        print(f"({len(old)} baselined finding(s) suppressed; "
              "see tools/lint_baseline.json)")
    ran = ",".join(passes) if passes else "all passes"
    if new:
        print(f"FAIL: {len(new)} new finding(s) ({ran}); fix them, "
              "`# lint: allow(<code>)` a justified one, or "
              "--update-baseline deliberately")
        return 1
    print(f"OK: no new findings ({ran}, {len(findings)} total, "
          f"{len(old)} baselined, {timings.get('total', 0.0):.2f}s)")
    return 0
