"""Test config: force CPU with 8 virtual devices (the reference's
"Gloo-for-CPU-tests" trick, SURVEY.md §4) so all multi-device sharding
logic runs in CI without TPU hardware."""
import os

# FORCE cpu: a chip belongs to one process, so a test run that claimed
# the TPU would block (or be blocked by) whatever else holds it.  Tests
# always run on virtual CPU devices, whatever JAX_PLATFORMS was set to.
#
# The config is updated again AFTER importing jax, in case jax was
# imported with another platform list before this file ran (backends
# are still uninitialized at conftest time, so no chip is ever claimed).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# CPU matmuls default to a bf16-ish fast path; tests compare against numpy
jax.config.update("jax_default_matmul_precision", "highest")


def pytest_configure(config):
    # registered here (no pytest.ini): `chaos` = failpoint-driven
    # fault-injection tests — fast ones run in tier-1 (`-m 'not slow'`);
    # anything over ~5s must ALSO carry `slow` to stay out of tier-1
    config.addinivalue_line(
        "markers", "chaos: fault-injection test driven by failpoints")
    config.addinivalue_line(
        "markers", "slow: long-running test, excluded from tier-1")
    config.addinivalue_line(
        "markers", "guardian: training-guardian (sentinel/ladder/"
        "watchdog) test — select with -m guardian")
    config.addinivalue_line(
        "markers", "lint: static-analysis suite (paddle_tpu.analysis) "
        "test — select with -m lint")
    config.addinivalue_line(
        "markers", "serving: continuous-batching serving engine "
        "(inference/serving.py) test — select with -m serving")
    config.addinivalue_line(
        "markers", "obs: unified telemetry layer "
        "(paddle_tpu/observability/) test — select with -m obs")
    config.addinivalue_line(
        "markers", "multichip: multi-device mesh parity test (runs on "
        "the forced-8-virtual-device CPU mesh above; exercises "
        "grad_comm / hybrid DP wire patterns) — select with -m multichip")


@pytest.fixture(autouse=True)
def _observability_gate_restored():
    """Put the one recording gate (``observability.enabled()``) back
    after every test: a test that leaves it off would silence whatever
    file the same xdist worker runs next."""
    from paddle_tpu import observability as obs
    was_on = obs.enabled()
    yield
    obs.enable(was_on)
