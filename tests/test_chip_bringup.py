"""PR 21 bring-up contracts: nothing that can hide the device.

Importing the package (or the launcher) claims no chip; a
device, kernel impl, peak or mesh that is not there raises instead of
falling back; the compile cache lives at one fixed place."""
import os
import subprocess
import sys

import pytest

import paddle_tpu as paddle
from paddle_tpu.device import chip
from paddle_tpu.ops import registry as kreg

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_argv, **env):
    argv = [sys.executable, "-c", code_or_argv] \
        if isinstance(code_or_argv, str) else code_or_argv
    e = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    e.update(env)
    return subprocess.run(argv, cwd=REPO, env=e, capture_output=True,
                          text=True, timeout=300)


def test_imports_leave_backend_uninitialised():
    r = _run("import paddle_tpu\n"
             "import paddle_tpu.distributed.launch.main\n"
             "from jax._src import xla_bridge\n"
             "assert not xla_bridge.backends_are_initialized()\n"
             "paddle_tpu.seed(3)\n"            # first key use builds it
             "assert xla_bridge.backends_are_initialized()\n")
    assert r.returncode == 0, r.stderr[-2000:]


def test_chip_smoke_refuses_to_run_without_a_tpu():
    r = _run([sys.executable, os.path.join(REPO, "chip_smoke.py")])
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert '"phase": "train"' not in r.stdout     # no work was done
    assert "no TPU" in r.stderr


class TestDeviceSelection:
    def test_set_device_tpu_raises_on_the_cpu_mesh(self):
        for spec in ("tpu", "tpu:0", "gpu"):
            with pytest.raises(RuntimeError, match="no 'tpu' device"):
                paddle.set_device(spec)

    def test_out_of_range_index_raises(self):
        assert paddle.set_device("cpu:7") is not None   # 8 virtual devices
        with pytest.raises(ValueError, match="out of range"):
            paddle.set_device("cpu:8")
        paddle.set_device("cpu")

    def test_is_compiled_with_tpu_means_platform_tpu(self):
        assert not paddle.is_compiled_with_tpu()

    def test_predictor_on_an_absent_device_raises(self):
        from paddle_tpu.inference import Config, create_predictor
        cfg = Config("no/such/model")
        cfg.enable_use_gpu(100, 0)
        with pytest.raises(RuntimeError, match="no 'tpu' device"):
            create_predictor(cfg)


class TestRegistryErrors:
    def test_unknown_forced_impl_raises(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_KERNEL_ATTENTION", "no_such_impl")
        with pytest.raises(ValueError, match="no impl 'no_such_impl'"):
            kreg.choose("attention")
        monkeypatch.delenv("PADDLE_TPU_KERNEL_ATTENTION")
        with kreg.force("xent", "palas"), pytest.raises(ValueError):
            kreg.choose("xent")

    def test_interpret_env_is_an_error_on_a_tpu_backend(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_KERNEL_INTERPRET", "1")
        assert kreg.interpret_enabled()                 # CPU: the CI knob
        monkeypatch.setattr(kreg.jax, "default_backend", lambda: "tpu")
        with pytest.raises(RuntimeError, match="on a TPU backend"):
            kreg.choose("attention")

    def test_no_impl_for_the_platform_raises(self):
        kreg.register("_only_tpu_kernel", "pallas", None, platforms=("tpu",))
        with pytest.raises(RuntimeError, match="no impl for platform"):
            kreg.choose("_only_tpu_kernel")


class TestChipModule:
    def test_peaks_table_knows_v5e_and_raises_on_unknown(self):
        p = chip.peaks(chip.V5E)
        assert (p.bf16_flops, p.int8_ops, p.hbm_bytes_per_s) == \
            (197e12, 393e12, 819e9)
        assert p.hbm_bytes == 16 * 1024 ** 3 and "Google Cloud" in p.source
        with pytest.raises(LookupError, match="no published peaks"):
            chip.peaks("TPU v99")
        with pytest.raises(LookupError):
            chip.peaks()                 # the local device_kind is "cpu"

    def test_describe_names_the_process_devices(self):
        assert chip.describe() == {"platform": "cpu", "kind": "cpu",
                                   "count": 8}

    def test_compile_cache_dir_is_env_or_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert chip.compile_cache_dir() == os.path.join(REPO, ".jax_cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert chip.compile_cache_dir() == "/some/dir"
        import jax
        before = jax.config.jax_compilation_cache_dir
        assert chip.enable_compile_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before  # untouched

    def test_child_would_claim_tpu(self, monkeypatch):
        monkeypatch.setattr(chip, "local_tpu_chips", lambda: 4)
        assert chip.child_would_claim_tpu({})
        assert chip.child_would_claim_tpu({"JAX_PLATFORMS": "tpu,cpu"})
        assert not chip.child_would_claim_tpu({"JAX_PLATFORMS": "cpu"})
        monkeypatch.setattr(chip, "local_tpu_chips", lambda: 0)
        assert not chip.child_would_claim_tpu({})


def test_process_mesh_oversubscription_raises():
    from paddle_tpu.distributed.mesh import ProcessMesh
    assert ProcessMesh(shape=(2, 4), dim_names=["dp", "mp"]).shape == [2, 4]
    with pytest.raises(ValueError, match="only 8 cpu device"):
        ProcessMesh(shape=(4, 4), dim_names=["dp", "mp"])


def test_launcher_refuses_several_workers_on_a_tpu_host(monkeypatch):
    from paddle_tpu.distributed.launch import main as launch
    monkeypatch.setattr(chip, "local_tpu_chips", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(sys, "argv", ["launch", "--nproc_per_node", "2",
                                      "train.py"])
    with pytest.raises(SystemExit, match="one process"):
        launch()


def test_spawn_refuses_several_workers_on_a_tpu_host(monkeypatch):
    from paddle_tpu.distributed import spawn
    monkeypatch.setattr(chip, "local_tpu_chips", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="One process drives"):
        spawn(print, nprocs=2)
