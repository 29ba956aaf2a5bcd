"""What the repo knows about the accelerator, in one place: the
published peaks keyed by ``device_kind``, the identity every printed
result carries, where the persistent compile cache lives, and a probe
that counts the host's chips without initialising a JAX backend.

Nothing here runs at ``import paddle_tpu``: entry points (chip_smoke.py,
the benchmark harness, the launcher) call it explicitly.
"""
import glob
import os
from collections import namedtuple

__all__ = ["Peaks", "V5E", "peaks", "describe", "compile_cache_dir",
           "enable_compile_cache", "local_tpu_chips", "child_would_claim_tpu"]

Peaks = namedtuple("Peaks", ["bf16_flops", "int8_ops", "hbm_bytes_per_s",
                             "hbm_bytes", "source"])

# ``jax.Device.device_kind`` of one TPU v5e chip
V5E = "TPU v5 lite"

# One table, keyed by what JAX reports.  A device that is not here is an
# error, never a default: add its row with the source of the numbers.
_PEAKS = {
    V5E: Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes_per_s=819e9,
               hbm_bytes=16 * 1024 ** 3,
               source="Google Cloud documentation, 'TPU v5e' system "
                      "architecture: 197 TFLOP/s bf16, 393 TOP/s int8, "
                      "16 GB HBM2e at 819 GB/s per chip"),
}


def peaks(device_kind=None):
    """Published peaks for ``device_kind`` (default: the kind of local
    device 0, which initialises the backend).  Unknown kinds raise."""
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    try:
        return _PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device_kind {device_kind!r} "
            f"(known: {sorted(_PEAKS)}); add a sourced row to "
            "paddle_tpu/device/chip.py rather than assuming one") from None


def describe():
    """``{"platform", "kind", "count"}`` of the process's JAX devices —
    the identity every benchmark result and smoke line carries."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def compile_cache_dir():
    """Directory of JAX's persistent compilation cache:
    ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``
    (derived from this file's location — a fixed path, because a cache
    that moves between runs never hits)."""
    env = os.environ.get(_CACHE_ENV)
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def enable_compile_cache():
    """Turn the persistent compilation cache on for this process and
    return its directory.  With ``JAX_COMPILATION_CACHE_DIR`` set JAX
    reads the variable itself and this sets nothing."""
    if not os.environ.get(_CACHE_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
    return compile_cache_dir()


_GOOGLE_PCI_VENDOR = "0x1ae0"
# PCI device ids of TPU chips (v3, v4, v5p, v5e, v6e, 7x)
_TPU_PCI_DEVICES = {"0x0027", "0x005e", "0x0062", "0x0063", "0x006f",
                    "0x0076"}


def local_tpu_chips():
    """Number of TPU chips on this host's PCI bus — possibly more than
    the process may open (the one-chip machine of the chip tool shows
    the host's four).  Reads sysfs only, so a process that must stay off
    the backend (the launcher parent) can still tell a TPU host from a
    CPU one."""
    n = 0
    for vendor_path in glob.glob("/sys/bus/pci/devices/*/vendor"):
        try:
            with open(vendor_path) as f:
                if f.read().strip() != _GOOGLE_PCI_VENDOR:
                    continue
            with open(os.path.join(os.path.dirname(vendor_path),
                                   "device")) as f:
                if f.read().strip() in _TPU_PCI_DEVICES:
                    n += 1
        except OSError:
            continue
    return n


def child_would_claim_tpu(env=None):
    """Would a process started with ``env`` (default: this process's)
    take the host's TPU?  Chips on the PCI bus and no ``JAX_PLATFORMS``
    that keeps JAX off them.  A chip belongs to one process, so parents
    that start workers (launcher, ``distributed.spawn``) ask this first."""
    platforms = (os.environ if env is None else env).get(
        "JAX_PLATFORMS", "")
    return local_tpu_chips() > 0 and \
        (not platforms or "tpu" in platforms.split(","))
