"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's
capability surface (reference: python/paddle/__init__.py, 387 exports),
built on JAX/XLA/Pallas/pjit rather than ported from the CUDA design.
"""

from __future__ import annotations

# start-up's clock (profiler.startup_stats): the process's account of itself
# begins here, and jax, the largest single import, is timed apart
import time as _time

_IMPORT_BEGAN = _time.perf_counter()
import jax as _jax  # noqa: F401,E402

_IMPORT_JAX_DONE = _time.perf_counter()

# dtypes
from ._core.dtype import (  # noqa: F401
    DType,
    bfloat16,
    bool_ as bool8,
    complex64,
    complex128,
    dtype,
    float16,
    float32,
    float64,
    float8_e4m3fn,
    float8_e5m2,
    int8,
    int16,
    int32,
    int64,
    uint8,
)
from ._core.place import (  # noqa: F401
    CPUPlace,
    CustomPlace,
    Place,
    TPUPlace,
    device_count,
    get_device,
    is_compiled_with_tpu,
    set_device,
)
from ._core.flags import get_flags, set_flags  # noqa: F401
from ._core.random import get_rng_state, seed, set_rng_state  # noqa: F401
from ._core.tensor import Parameter, Tensor  # noqa: F401
from ._core.autograd import enable_grad, is_grad_enabled, no_grad, set_grad_enabled  # noqa: F401
from ._core.autograd import grad  # noqa: F401

# Full tensor-op surface (also patches Tensor methods).
from .tensor import *  # noqa: F401,F403
from .tensor import creation as _creation  # noqa: F401

# Common bool dtype name
from ._core import dtype as _dtype_mod

bool = _dtype_mod.bool_  # noqa: A001

# Subpackages land incrementally; import what exists.
import importlib as _importlib

for _sub in (
    "autograd",
    "nn",
    "optimizer",
    "amp",
    "io",
    "device",
    "framework",
    "jit",
    "static",
    "distributed",
    "incubate",
    "metric",
    "vision",
    "inference",
    "hapi",
    "profiler",
    "distribution",
    "sparse",
    "fft",
    "signal",
    "text",
    "audio",
    "geometric",
    "quantization",
    "onnx",
    "cost_model",
    "linalg",
    "utils",
    "decomposition",
):
    try:
        globals()[_sub] = _importlib.import_module(f".{_sub}", __name__)
    except ModuleNotFoundError:
        pass

try:
    from .framework.io_utils import load, save, wait_async_save  # noqa: F401,E402
except ImportError:
    pass
try:
    from .nn.layer.layers import Layer  # noqa: F401,E402
except ImportError:
    pass

__version__ = "0.6.0"


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    return False


try:
    from .hapi import Model, summary, flops  # noqa: F401,E402
    from .hapi import callbacks  # noqa: F401,E402
except ImportError:
    pass
from . import regularizer  # noqa: F401,E402
from . import reader  # noqa: F401,E402
from . import sysconfig  # noqa: F401,E402
from . import hub  # noqa: F401,E402
from . import pir  # noqa: F401,E402
from . import dataset  # noqa: F401,E402
from .static.program import enable_static, disable_static, in_dynamic_mode  # noqa: F401,E402

# Framework defaults / dtype info / compat surface (reference top-level names)
from .framework.defaults import (  # noqa: F401,E402
    LazyGuard,
    batch,
    check_shape,
    create_parameter,
    disable_signal_handler,
    finfo,
    get_default_dtype,
    iinfo,
    set_default_dtype,
    set_printoptions,
)
from ._core.place import CUDAPinnedPlace, CUDAPlace  # noqa: F401,E402
from .nn.layer.layers import ParamAttr  # noqa: F401,E402
from .distributed import DataParallel  # noqa: F401,E402

# CUDA-named RNG state APIs are the generic device generator state here.
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state


def tolist(x):
    """paddle.tolist parity: nested Python list of the tensor's values."""
    from ._core.tensor import Tensor

    return x.tolist() if isinstance(x, Tensor) else Tensor(x).tolist()


profiler.startup.imported(_IMPORT_BEGAN, _IMPORT_JAX_DONE, _time.perf_counter())
