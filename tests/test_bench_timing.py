"""bench.py methodology guards.

Round-1 lesson: on the device path of that time block_until_ready()
returned before execution finished — timings synced that way were ~70x
inflated (commit 9ce47d5).  A host readback of a dependent value is a
completion barrier on every backend; chip_smoke.py prints whether
block_until_ready is one on the local chip (CHANGES.md PR 21).  These
tests pin the readback contract so a refactor can't silently
reintroduce fantasy numbers, and smoke-run the CPU-proxy bench
end-to-end."""
import inspect
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import bench  # noqa: E402


def test_timing_loop_syncs_via_host_readback():
    src = inspect.getsource(bench._timeit)
    assert "_readback_sync" in src, \
        "_timeit must end with a host readback of the final loss"
    sync_src = inspect.getsource(bench._readback_sync)
    assert "float" in sync_src
    # the whole bench must never rely on block_until_ready for timing
    full = inspect.getsource(bench)
    assert "block_until_ready" not in full.replace(
        "block_until_ready() returns", ""), \
        "bench.py must sync via host readback, not block_until_ready"


def test_every_bench_config_warms_up_before_timing():
    # each bench_* fn must force a readback (compile+warmup) before _timeit
    for name in ("bench_gpt", "bench_resnet50", "bench_bert"):
        src = inspect.getsource(getattr(bench, name))
        warm = src.index("_readback_sync")
        timed = src.index("_timeit")
        assert warm < timed, f"{name}: warmup readback must precede timing"


def test_cpu_proxy_bench_emits_schema():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""
    r = subprocess.run([sys.executable, os.path.join(REPO, "bench.py")],
                       env=env, cwd=REPO, capture_output=True, text=True,
                       timeout=580)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.strip().splitlines()
            if ln.startswith("{")][-1]
    out = json.loads(line)
    assert set(out) >= {"metric", "value", "unit", "vs_baseline", "extra"}
    assert out["value"] > 0
    assert "mfu" in out["extra"] and "configs" in out["extra"]
