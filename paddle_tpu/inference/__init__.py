"""paddle.inference — deployment predictor API (reference:
paddle/fluid/inference/api/analysis_predictor.cc + python wrapper
python/paddle/inference/__init__.py).

TPU-native: the ``.pdmodel`` artifact is serialized StableHLO (produced by
``paddle_tpu.jit.save``); "analysis passes" are XLA's own optimization
pipeline at compile time, so there is no IR pass stack to run here.  The
predictor AOT-compiles once with donated input buffers and runs zero-copy:
``copy_from_cpu`` stages host arrays, ``run`` executes the compiled
program on device, ``copy_to_cpu`` fetches results.
"""
import numpy as np
import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from .. import jit as _jit

__all__ = ["Config", "create_predictor", "Predictor", "PrecisionType",
           "PlaceType"]


class PrecisionType:
    Float32 = "float32"
    Half = "float16"
    Bfloat16 = "bfloat16"
    Int8 = "int8"


class PlaceType:
    CPU = "cpu"
    GPU = "tpu"   # no GPUs here; accelerator = TPU
    TPU = "tpu"
    XPU = "tpu"


class Config:
    """Mirrors paddle.inference.Config's commonly used knobs; GPU/TensorRT
    options map onto the TPU/XLA equivalents or record as no-ops."""

    def __init__(self, prog_file=None, params_file=None):
        if prog_file is not None and prog_file.endswith(".pdmodel"):
            prog_file = prog_file[:-len(".pdmodel")]
        self._prefix = prog_file
        self._params_file = params_file
        self._device = None        # None = JAX's default backend
        self._device_id = 0
        self._precision = PrecisionType.Float32
        # convert_to_mixed_precision leaves a sidecar naming the dtype;
        # honor it so converted models load at the converted precision
        if prog_file is not None:
            import json
            import os
            side = prog_file + ".precision.json"
            if os.path.exists(side):
                try:
                    with open(side) as f:
                        self._precision = json.load(f)["mixed_precision"]
                except (OSError, KeyError, ValueError):
                    pass
        self._memory_optim = True
        self._ir_optim = True
        self._cpu_threads = 1
        self._enable_profile = False

    # -- device selection ---------------------------------------------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0,
                       precision=PrecisionType.Float32):
        self._device = "tpu"
        self._device_id = device_id
        self._precision = precision

    def disable_gpu(self):
        self._device = "cpu"

    def use_gpu(self):
        return self._device == "tpu"

    def gpu_device_id(self):
        return self._device_id

    def set_cpu_math_library_num_threads(self, n):
        self._cpu_threads = n

    # -- optimization knobs (XLA handles these; recorded for summary) -------
    def enable_memory_optim(self, flag=True):
        self._memory_optim = flag

    def switch_ir_optim(self, flag=True):
        self._ir_optim = flag

    def ir_optim(self):
        return self._ir_optim

    def enable_tensorrt_engine(self, *a, **k):
        pass  # XLA is the compiler; no TRT subgraphs on TPU

    def tensorrt_engine_enabled(self):
        return False

    def enable_profile(self):
        self._enable_profile = True

    def switch_use_feed_fetch_ops(self, flag):
        pass

    def switch_specify_input_names(self, flag=True):
        pass

    def prog_file(self):
        return (self._prefix or "") + ".pdmodel"

    def params_file(self):
        if self._params_file is not None:
            return self._params_file
        return (self._prefix or "") + ".pdiparams"

    def summary(self):
        return (f"device: {self._device or 'default'}:{self._device_id}\n"
                f"precision: {self._precision}\n"
                f"model: {self.prog_file()}\n"
                f"ir_optim: {self._ir_optim}  "
                f"memory_optim: {self._memory_optim}")


class _IOHandle:
    """Zero-copy-style tensor handle (reference: paddle_infer::Tensor)."""

    def __init__(self, name, predictor, is_input):
        self._name = name
        self._pred = predictor
        self._is_input = is_input
        self._shape = None

    def name(self):
        return self._name

    def reshape(self, shape):
        self._shape = tuple(shape)

    def copy_from_cpu(self, arr):
        if not self._is_input:
            raise RuntimeError("copy_from_cpu on an output handle")
        arr = np.ascontiguousarray(arr)
        if self._shape is not None and tuple(arr.shape) != self._shape:
            arr = arr.reshape(self._shape)
        self._pred._inputs[self._name] = jax.device_put(
            arr, self._pred._device)

    def share_external_data(self, arr):
        self.copy_from_cpu(np.asarray(arr))

    def copy_to_cpu(self):
        if self._is_input:
            raise RuntimeError("copy_to_cpu on an input handle")
        out = self._pred._outputs.get(self._name)
        if out is None:
            raise RuntimeError("run() has not produced outputs yet")
        return np.asarray(out)

    def shape(self):
        src = (self._pred._inputs if self._is_input
               else self._pred._outputs)
        arr = src.get(self._name)
        return list(arr.shape) if arr is not None else list(self._shape or [])


class Predictor:
    """Loads a jit.save artifact and runs it AOT-compiled (reference:
    AnalysisPredictor::Run / ZeroCopyRun)."""

    def __init__(self, config):
        self._config = config
        # an explicit device that is absent or out of range raises
        # (framework.core._parse_device) — never a quiet CPU predictor
        from ..framework.core import _parse_device
        platform = config._device or jax.default_backend()
        self._device = _parse_device(f"{platform}:{config._device_id}")
        self._layer = _jit.load(config._prefix,
                                params_path=config.params_file())
        specs = self._layer._meta.get("input_specs", [])
        self._input_names = [
            (s[2] or f"input_{i}") for i, s in enumerate(specs)]
        self._inputs = {}
        self._outputs = {}
        self._output_names = []

    def get_input_names(self):
        return list(self._input_names)

    def get_input_handle(self, name):
        return _IOHandle(name, self, is_input=True)

    def get_output_names(self):
        return list(self._output_names)

    def get_output_handle(self, name):
        return _IOHandle(name, self, is_input=False)

    def run(self, inputs=None):
        """Zero-copy run over staged inputs; with ``inputs`` (list of numpy
        arrays) behaves like the old feed-list API and returns outputs."""
        if inputs is not None:
            for n, a in zip(self._input_names, inputs):
                self._inputs[n] = jax.device_put(np.asarray(a), self._device)
        missing = [n for n in self._input_names if n not in self._inputs]
        if missing:
            raise RuntimeError(f"inputs not set: {missing}")
        args = [Tensor(self._inputs[n]) for n in self._input_names]
        out = self._layer(*args)
        flat = jax.tree.leaves(
            jax.tree.map(lambda o: o._value if isinstance(o, Tensor) else o,
                         out, is_leaf=lambda o: isinstance(o, Tensor)))
        self._output_names = [f"output_{i}" for i in range(len(flat))]
        self._outputs = dict(zip(self._output_names, flat))
        if inputs is not None:
            return [np.asarray(v) for v in flat]
        return None

    def clear_intermediate_tensor(self):
        self._inputs.clear()
        self._outputs.clear()

    def try_shrink_memory(self):
        pass


def create_predictor(config):
    return Predictor(config)


def get_version():
    """reference: paddle.inference.get_version."""
    from ..version import full_version
    return f"paddle_tpu inference {full_version}"


def convert_to_mixed_precision(src_model, src_params, dst_model,
                               dst_params, mixed_precision=None,
                               backend=None, keep_io_types=True,
                               black_list=None, **kwargs):
    """reference: paddle.inference.convert_to_mixed_precision — rewrite
    a saved model's params to the mixed dtype.

    Envelope note (differs from the reference): a jax.export artifact's
    EXECUTION dtypes are fixed at export time, so this converts the
    stored params payload (disk / transfer size halves for bf16) and
    jit.load casts back to the exported program's dtypes at load.  For
    actual bf16 execution, export the model under ``amp.decorate`` —
    on TPU that is the native precision path.
    """
    import json
    import os
    import pickle as _pkl
    import shutil
    import numpy as _np
    for src, dst in ((src_model, dst_model), (src_params, dst_params)):
        if src and dst and os.path.exists(src) and src != dst:
            os.makedirs(os.path.dirname(dst) or ".", exist_ok=True)
            shutil.copy(src, dst)
    target = str(mixed_precision or "bfloat16")
    if dst_params and os.path.exists(dst_params):
        import jax.numpy as _jnp
        with open(dst_params, "rb") as f:
            meta = _pkl.load(f)
        black = set(black_list or [])
        for group in ("params", "buffers"):
            for name, arr in list(meta.get(group, {}).items()):
                a = _np.asarray(arr)
                if a.dtype == _np.float32 and name not in black:
                    meta[group][name] = _np.asarray(
                        _jnp.asarray(a).astype(target))
        with open(dst_params, "wb") as f:
            _pkl.dump(meta, f)
    if not dst_model:
        raise ValueError("convert_to_mixed_precision needs dst_model to "
                         "record the converted precision")
    prefix = dst_model[:-len(".pdmodel")] \
        if dst_model.endswith(".pdmodel") else dst_model
    with open(prefix + ".precision.json", "w") as f:
        json.dump({"mixed_precision": str(mixed_precision or "bfloat16"),
                   "keep_io_types": bool(keep_io_types),
                   "black_list": sorted(black_list or [])}, f)


__all__ += ["get_version", "convert_to_mixed_precision"]

# continuous-batching serving engine (lazy: serving pulls in the model
# stack; Predictor users shouldn't pay for it)
def __getattr__(name):
    if name in ("ServingEngine", "FCFSScheduler", "Request"):
        from . import serving as _serving
        return getattr(_serving, name)
    if name in ("SpecConfig", "speculative_generate"):
        from . import speculative as _speculative
        return getattr(_speculative, name)
    if name in ("ServingFleet", "PRIORITY_CLASSES"):
        from . import router as _router
        return getattr(_router, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ += ["ServingEngine", "FCFSScheduler", "Request", "SpecConfig",
            "speculative_generate", "ServingFleet", "PRIORITY_CLASSES"]
