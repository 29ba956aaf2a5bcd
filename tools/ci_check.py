#!/usr/bin/env python
"""Single CI entry point: lint sweep -> opt-in suites -> tier-1 tests,
in that order, stopping at the first failing stage.

`tools/lint.py` and the tier-1 pytest invocation from ROADMAP.md as one
pipeline, so "is this tree green" is one command:

    python tools/ci_check.py                  # lint + tests
    python tools/ci_check.py --changed-only   # git-diff-scoped lint,
                                              # then tests
    python tools/ci_check.py --chaos          # + the chaos-marked
                                              # elastic-resume + PD-
                                              # handoff suites (opt-in:
                                              # kill/resume e2e is
                                              # slower than tier-1
                                              # unit tests)
    python tools/ci_check.py --kernels        # + the Pallas kernel /
                                              # registry suites with
                                              # interpret mode forced
                                              # (selected TPU kernels
                                              # run on the CPU backend)
    python tools/ci_check.py --obs            # + the observability
                                              # suites (HBM memory
                                              # ledger, tracing, flight
                                              # recorder / watchdog)
    python tools/ci_check.py --skip-tests     # lint (+ opt-in
                                              # suites) only
    python tools/ci_check.py --lint-only      # lint sweep alone: the
                                              # pre-commit fast path
                                              # (<10s, no pytest, no
                                              # opt-in suites)

Stages:

1. **lint** — the full static-analysis suite (`python -m
   paddle_tpu.analysis`, baseline-suppressed).  `--changed-only`
   passes through to the runner's git-diff scoping.
2. **opt-in suites** (``--obs``, ``--chaos``, ``--kernels``) — each a
   pytest subset described above.
3. **tests** — tier-1: ``pytest tests/ -m 'not slow'`` on the CPU
   backend (the ROADMAP.md verify command without the log plumbing).
   ``--pytest-args "..."`` appends extra flags (e.g. ``-x -k serving``).

Exit code: the first failing stage's (lint: 1; tests: pytest's).
"""
import argparse
import os
import shlex
import subprocess
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _stage(name):
    print(f"\n=== ci_check: {name} ===", flush=True)
    return time.perf_counter()


def run_lint(changed_only):
    from paddle_tpu.analysis import main as lint_main
    t0 = _stage("lint sweep" + (" (--changed-only)" if changed_only
                                else ""))
    argv = ["--changed-only"] if changed_only else []
    rc = lint_main(argv)
    print(f"lint: {'OK' if rc == 0 else 'FAIL'} "
          f"({time.perf_counter() - t0:.1f}s)")
    return rc


def run_tests(extra):
    t0 = _stage("tier-1 tests (pytest -m 'not slow')")
    cmd = [sys.executable, "-m", "pytest", "tests/", "-q",
           "-m", "not slow", "--continue-on-collection-errors",
           "-p", "no:cacheprovider"] + extra
    print("$", " ".join(shlex.quote(c) for c in cmd), flush=True)
    rc = subprocess.call(cmd, cwd=REPO)
    print(f"tests: {'OK' if rc == 0 else f'FAIL (rc={rc})'} "
          f"({time.perf_counter() - t0:.1f}s)")
    return rc


def run_chaos():
    """Chaos stage (the ISSUE 14 CI satellite, opt-in): run the
    `chaos`-marked suites — elastic-resume (manifest save/restore
    across topology changes, the np=8 → np=4 kill/resume e2e,
    retention/read races) on the 8-virtual-device CPU-proxy mesh the
    tests/conftest forces, plus the prefill/decode handoff chaos suite
    (dropped/corrupt bundles, reservation expiry, mid-transfer prefill
    death — bitwise fallback, zero leaked pages)."""
    t0 = _stage("chaos suites (opt-in: elastic resume + handoff)")
    cmd = [sys.executable, "-m", "pytest",
           "tests/test_elastic_resume.py", "tests/test_fault_tolerance.py",
           "tests/test_handoff.py",
           "-q", "-m", "chaos", "--continue-on-collection-errors",
           "-p", "no:cacheprovider"]
    print("$", " ".join(shlex.quote(c) for c in cmd), flush=True)
    rc = subprocess.call(cmd, cwd=REPO)
    print(f"chaos: {'OK' if rc == 0 else f'FAIL (rc={rc})'} "
          f"({time.perf_counter() - t0:.1f}s)")
    return rc


def run_kernels():
    """Kernel stage (the ISSUE 15 CI satellite, opt-in): run the Pallas
    kernel + registry suites with interpret mode forced, so the
    *selected* TPU kernels — dispatch, padding, masks, custom VJPs —
    execute end to end on the CPU backend (the same parity contract
    the train-step tests machine-check)."""
    t0 = _stage("interpret-mode kernel suite (opt-in)")
    cmd = [sys.executable, "-m", "pytest",
           "tests/test_flash_attention.py", "tests/test_fused_xent.py",
           "tests/test_pallas_fused.py", "tests/test_quant_matmul.py",
           "tests/test_varlen_attention.py",
           "tests/test_kernel_registry.py", "tests/test_quant_paths.py",
           "tests/test_paged_attention.py",
           "-q", "--continue-on-collection-errors",
           "-p", "no:cacheprovider"]
    env = {**os.environ, "PADDLE_TPU_KERNEL_INTERPRET": "1"}
    print("$ PADDLE_TPU_KERNEL_INTERPRET=1",
          " ".join(shlex.quote(c) for c in cmd), flush=True)
    rc = subprocess.call(cmd, cwd=REPO, env=env)
    print(f"kernels: {'OK' if rc == 0 else f'FAIL (rc={rc})'} "
          f"({time.perf_counter() - t0:.1f}s)")
    return rc


def run_obs():
    """Observability stage (the ISSUE 20 CI satellite, opt-in): run
    the memory-ledger + tracing/compile-telemetry + flight/watchdog
    suites — the HBM ledger, the hbm_pressure watchdog path, the
    dropped-spans accounting and the bundle retention discipline."""
    t0 = _stage("observability suites (opt-in: memory + tracing)")
    cmd = [sys.executable, "-m", "pytest",
           "tests/test_memory_ledger.py", "tests/test_compile_tracing.py",
           "tests/test_flight_watchdog.py", "tests/test_observability.py",
           "-q", "--continue-on-collection-errors",
           "-p", "no:cacheprovider"]
    print("$", " ".join(shlex.quote(c) for c in cmd), flush=True)
    rc = subprocess.call(cmd, cwd=REPO)
    print(f"obs: {'OK' if rc == 0 else f'FAIL (rc={rc})'} "
          f"({time.perf_counter() - t0:.1f}s)")
    return rc


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="lint sweep -> opt-in suites -> tier-1 pytest")
    ap.add_argument("--changed-only", action="store_true",
                    help="scope the lint sweep to the git diff "
                         "(tests still run in full)")
    ap.add_argument("--chaos", action="store_true",
                    help="also run the chaos-marked elastic-resume "
                         "tests (8-device CPU-proxy mesh) and the "
                         "prefill/decode handoff chaos suite")
    ap.add_argument("--kernels", action="store_true",
                    help="also run the Pallas kernel + registry suites "
                         "with interpret mode forced (the selected TPU "
                         "kernels execute on the CPU backend)")
    ap.add_argument("--obs", action="store_true",
                    help="also run the observability suites (HBM "
                         "memory ledger, tracing, flight recorder / "
                         "watchdog)")
    ap.add_argument("--skip-tests", action="store_true",
                    help="lint (and opt-in suites) only")
    ap.add_argument("--lint-only", action="store_true",
                    help="run the lint sweep alone and stop — the "
                         "pre-commit fast path (no pytest, no opt-in "
                         "suites; combine with --changed-only for the "
                         "inner loop)")
    ap.add_argument("--pytest-args", default="",
                    help="extra pytest flags, quoted (e.g. '-x -k "
                         "serving')")
    args = ap.parse_args(argv)

    rc = run_lint(args.changed_only)
    if rc != 0:
        return rc
    if args.lint_only:
        print("\nci_check: LINT GREEN (--lint-only: tests and suites "
              "skipped)")
        return 0
    if args.obs:
        rc = run_obs()
        if rc != 0:
            return rc
    if args.chaos:
        rc = run_chaos()
        if rc != 0:
            return rc
    if args.kernels:
        rc = run_kernels()
        if rc != 0:
            return rc
    if not args.skip_tests:
        rc = run_tests(shlex.split(args.pytest_args))
        if rc != 0:
            return rc
    print("\nci_check: ALL GREEN")
    return 0


if __name__ == "__main__":
    sys.exit(main())
