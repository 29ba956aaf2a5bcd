"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capability surface, built on JAX/XLA/Pallas.

Layer map vs the reference (see SURVEY.md §1/§7): PJRT+XLA replace the
device runtime/allocators/executors; jax tracing+vjp replace the eager
autograd engine; GSPMD/pjit replaces Fleet's hand-built hybrid parallelism;
Pallas kernels replace the CUDA kernel library.
"""
from .framework import dtypes as _dtypes
from .framework.dtypes import (  # noqa: F401
    float16, bfloat16, float32, float64, int8, int16, int32, int64,
    uint8, bool, complex64, complex128,
    set_default_dtype, get_default_dtype)
from .framework.core import (  # noqa: F401
    Tensor, to_tensor, set_device, get_device, is_tensor,
    set_printoptions)
from .framework.autograd import no_grad, enable_grad, set_grad_enabled, \
    is_grad_enabled, grad  # noqa: F401
from .framework.random import seed, get_rng_state, set_rng_state  # noqa: F401
from .framework.io import save, load  # noqa: F401
from .framework import random as _random

from .tensor import *  # noqa: F401,F403
from .tensor import linalg  # noqa: F401  (paddle.linalg namespace)
from .tensor import creation as _creation

from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from . import amp  # noqa: F401
from . import distributed  # noqa: F401
from .distributed.parallel import DataParallel  # noqa: F401
from . import vision  # noqa: F401
from . import jit  # noqa: F401
from . import static  # noqa: F401
from . import device  # noqa: F401
from . import profiler  # noqa: F401
from . import distribution  # noqa: F401
from . import autograd  # noqa: F401
from . import sparse  # noqa: F401
from . import quantization  # noqa: F401
from . import inference  # noqa: F401
from . import text  # noqa: F401
from . import onnx  # noqa: F401
from . import regularizer  # noqa: F401
from . import sysconfig  # noqa: F401
from .autograd import PyLayer  # noqa: F401
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import audio  # noqa: F401
from . import geometric  # noqa: F401
from . import incubate  # noqa: F401
from . import hub  # noqa: F401
from . import utils  # noqa: F401
from .hapi.model import Model  # noqa: F401
from .hapi.model_summary import summary  # noqa: F401
from .hapi import callbacks  # noqa: F401
from .framework.flags import set_flags, get_flags  # noqa: F401

# paddle API aliases
create_parameter = _creation.create_parameter
from .static import enable_static, disable_static  # noqa: F401,E402

CPUPlace = lambda: "cpu"
CUDAPlace = lambda idx=0: f"tpu:{idx}"  # no GPUs; map onto TPU
TPUPlace = lambda idx=0: f"tpu:{idx}"

__version__ = "0.3.0"


def in_dynamic_mode():
    from . import static as _static
    return not _static._static_mode[0]


def is_compiled_with_cuda():
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_tpu():
    import jax
    # (the builtin any() is shadowed here by the star-exported tensor op)
    return jax.devices()[0].platform == "tpu"


def is_compiled_with_distribute():
    return True


def is_grad_enabled_():
    return is_grad_enabled()


def get_cudnn_version():
    return None


from . import version  # noqa: F401,E402


def iinfo(dtype):
    """reference: paddle.iinfo."""
    import numpy as _np
    return _np.iinfo(_np.dtype(str(_dtypes.convert_dtype(dtype))))


def finfo(dtype):
    """reference: paddle.finfo."""
    import jax.numpy as _jnp
    return _jnp.finfo(_dtypes.convert_dtype(dtype))


# CUDA-named RNG state entry points map to the device-agnostic RNG
# (reference: get/set_cuda_rng_state; one RNG stream here)
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state


def flops(net, input_size, custom_ops=None, print_detail=False):
    """reference: paddle.flops — model FLOPs for one forward pass.

    TPU-native: instead of the reference's per-layer-type FLOPs table,
    trace the ACTUAL forward with jax and read XLA's compiled cost
    analysis — counts every op the compiler will run, including fusions
    the table-based counter cannot see."""
    import numpy as _np
    import jax
    import jax.numpy as _jnp
    from .framework import autograd as _ag
    from .framework.random import rng_scope

    x = _jnp.zeros(tuple(input_size), _jnp.float32)
    params = [p for _, p in net.named_parameters()]
    vals = [p._value for p in params]

    def fwd(pv, xv):
        olds = [p._value for p in params]
        for p, v in zip(params, pv):
            p._value = v
        try:
            with _ag.suspend_tape(), rng_scope(jax.random.key(0)):
                out = net(Tensor(xv))
            return out._value if hasattr(out, "_value") else out
        finally:
            for p, v in zip(params, olds):
                p._value = v

    compiled = jax.jit(fwd).lower(vals, x).compile()
    try:
        # only the analysis readout is best-effort — trace/compile
        # errors above are REAL user errors and must propagate
        cost = compiled.cost_analysis()
        total = int(cost.get("flops", 0)) if cost else 0
    except Exception:
        total = 0
    if print_detail:
        import builtins
        # NB: plain `sum` here would resolve to paddle.sum (the tensor
        # reduce op star-exported into this module)
        n_params = builtins.sum(int(_np.prod(p.shape)) for p in params)
        print(f"Total Flops: {total}     Total Params: {n_params}")
    return total


def in_static_mode():
    return not in_dynamic_mode()


def is_compiled_with_rocm():
    return False


def is_compiled_with_custom_device(device_name=None):
    # the TPU is the one non-CPU device this build targets
    return is_compiled_with_tpu()


def disable_signal_handler():
    """reference: paddle.disable_signal_handler — the reference installs
    C++ fault handlers it sometimes must drop; PJRT installs none, so
    this is a true no-op kept for API parity."""


def batch(reader, batch_size, drop_last=False):
    """reference: paddle.batch — wrap an item reader into a batch
    reader (legacy reader-decorator API)."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched


class LazyGuard:
    """reference: paddle.LazyGuard — delay parameter initialization.

    Inside the context, ``create_parameter`` skips running the
    initializer (parameters hold zeros of the right shape/dtype and
    remember their initializer); call ``param.initialize()`` — or
    iterate ``layer.parameters()`` calling it — to materialize.  On TPU
    the main win is skipping redundant init compute for params that a
    checkpoint load or a sharded init will overwrite anyway.
    """

    def __enter__(self):
        from .nn.layer import layers as _l
        _l._LAZY_INIT[0] = True
        return self

    def __exit__(self, *exc):
        from .nn.layer import layers as _l
        _l._LAZY_INIT[0] = False
        return False
