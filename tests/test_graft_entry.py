"""The driver's dryrun_multichip contract must hold WITHOUT the test
harness: __graft_entry__ has to obtain its own virtual CPU mesh even when
the calling process already initialized a different jax backend (round-1
failure mode: the process had claimed the TPU before the dryrun ran and
it crashed — MULTICHIP_r01.json RED)."""
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_dryrun_multichip_in_process():
    # conftest already forced an 8-device CPU mesh in this process; the
    # entry must detect that and run inline without spawning anything.
    import __graft_entry__ as g

    assert g._ensure_cpu_devices(8)
    g.dryrun_multichip(8)


def test_dryrun_multichip_reexecs_when_backend_claimed():
    # Fresh interpreter that pre-initializes a 1-device backend before
    # calling the entry: dryrun must notice the mesh is unusable and
    # re-exec itself in a clean subprocess rather than crash.
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # 1 CPU device only
    env.pop("_GRAFT_DRYRUN_SUBPROCESS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    code = (
        "import jax, jax.numpy as jnp\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "jnp.zeros(()).block_until_ready()  # initialize 1-device backend\n"
        "assert len(jax.devices()) < 8\n"
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(8)\n"
        "print('REEXEC-PATH-OK')\n"
    )
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    assert "REEXEC-PATH-OK" in r.stdout
    assert "fleet dp=" in r.stdout  # the dryrun body itself really ran
