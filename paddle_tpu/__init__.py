"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's capabilities.

Built new on JAX/XLA/Pallas/pjit (NOT a port): eager tensors with define-by-run autograd
over jax.vjp tapes, a static Program/Executor path compiled by XLA, mesh-based
distributed training (DP/TP/PP/SP/EP + ZeRO sharding + semi-auto SPMD), AMP, DataLoader,
and the paddle.* API surface users of the reference expect.  See SURVEY.md for the
component-by-component mapping to the reference (PaddlePaddle @ /root/reference)."""
from __future__ import annotations

import sys as _sys
import time as _time

# the start-up record's ``import`` phase (observability/compilecache.py) is
# stamped here and on the last line; it holds JAX's import only when this
# package is the first to ask for it
_IMPORT_T0, _JAX_WAS_IMPORTED = _time.perf_counter(), "jax" in _sys.modules

import jax as _jax  # noqa: E402

# float64/int64 parity with Paddle (reference default int dtype is int64; fp64 kernels
# exist on every backend).  Creation ops still default to float32.
_jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"

from paddle_tpu.core import dtype as _dtype_mod  # noqa: E402
from paddle_tpu.core.dtype import (  # noqa: F401,E402
    bfloat16, bool_, complex64, complex128, finfo, float8_e4m3fn, float8_e5m2,
    float16, float32, float64, get_default_dtype, iinfo, int8, int16, int32, int64,
    set_default_dtype, uint8,
)

bool = bool_  # paddle.bool
dtype = _dtype_mod.convert_dtype

from paddle_tpu.core.device import (  # noqa: F401,E402
    CPUPlace, CUDAPinnedPlace, CUDAPlace, CustomPlace, Place, TPUPlace, XPUPlace,
    get_device, set_device, is_compiled_with_cuda, is_compiled_with_xpu,
    is_compiled_with_tpu, is_compiled_with_custom_device,
)

from paddle_tpu.tensor import Tensor, Parameter, is_tensor  # noqa: F401,E402
from paddle_tpu.tensor.creation import *  # noqa: F401,F403,E402
from paddle_tpu.tensor.math import *  # noqa: F401,F403,E402
from paddle_tpu.tensor.manipulation import *  # noqa: F401,F403,E402
from paddle_tpu.tensor.logic import *  # noqa: F401,F403,E402
from paddle_tpu.tensor.linalg import (  # noqa: F401,E402
    norm, dist, einsum, tensordot, cdist, cholesky, cholesky_solve,
    cholesky_inverse, eigvalsh, histogram_bin_edges, histogramdd,
)
from paddle_tpu import linalg  # noqa: F401,E402
from paddle_tpu import distribution  # noqa: F401,E402
from paddle_tpu import sparse  # noqa: F401,E402
from paddle_tpu import geometric  # noqa: F401,E402
from paddle_tpu import incubate  # noqa: F401,E402
from paddle_tpu import profiler  # noqa: F401,E402
from paddle_tpu import quantization  # noqa: F401,E402
from paddle_tpu import regularizer  # noqa: F401,E402
from paddle_tpu import decomposition  # noqa: F401,E402
from paddle_tpu import audio  # noqa: F401,E402
from paddle_tpu import text  # noqa: F401,E402
from paddle_tpu import inference  # noqa: F401,E402
from paddle_tpu.tensor.random import (  # noqa: F401,E402
    bernoulli, binomial, gaussian, get_rng_state, multinomial, normal, poisson,
    rand, randint, randint_like, randn, randperm, seed, set_rng_state,
    standard_gamma, standard_normal, uniform, default_generator,
)
from paddle_tpu.tensor.math import matmul  # noqa: F401,E402  (canonical)

from paddle_tpu.autograd.engine import (  # noqa: F401,E402
    enable_grad, grad, is_grad_enabled, no_grad, set_grad_enabled,
)
from paddle_tpu import autograd  # noqa: F401,E402

# subpackages loaded lazily to keep import light and avoid cycles
import importlib as _importlib

_LAZY = {
    "nn", "optimizer", "io", "amp", "distributed", "vision", "metric", "jit",
    "static", "device", "framework", "hapi", "profiler", "incubate", "sparse",
    "fft", "signal", "text", "audio", "quantization", "distribution", "geometric",
    "utils", "inference", "callbacks", "hub", "onnx", "version", "sysconfig",
    "base", "observability", "serving", "analysis",
}


def __getattr__(name):
    if name in _LAZY:
        mod = _importlib.import_module(f"paddle_tpu.{name}")
        globals()[name] = mod
        return mod
    if name == "Model":
        from paddle_tpu.hapi.model import Model as _M

        return _M
    if name == "metric":
        mod = _importlib.import_module("paddle_tpu.metric")
        globals()[name] = mod
        return mod
    if name == "models":
        mod = _importlib.import_module("paddle_tpu.models")
        globals()[name] = mod
        return mod
    if name == "save":
        from paddle_tpu.framework.io import save as _s

        return _s
    if name == "load":
        from paddle_tpu.framework.io import load as _l

        return _l
    if name == "summary":
        from paddle_tpu.hapi.model_summary import summary as _sm

        return _sm
    if name == "flops":
        from paddle_tpu.hapi.dynamic_flops import flops as _fl

        return _fl
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def enable_static():
    from paddle_tpu import static as _st

    _st._enable_static()


def disable_static():
    from paddle_tpu import static as _st

    _st._disable_static()


def in_dynamic_mode():
    try:
        from paddle_tpu import static as _st

        return not _st._static_mode_enabled()
    except Exception:
        return True


def in_static_mode():
    return not in_dynamic_mode()


in_dygraph_mode = in_dynamic_mode


def disable_signal_handler():
    pass


def device_count():
    from paddle_tpu.core.device import device_count as _dc

    return _dc()


def get_flags(flags=None):
    from paddle_tpu.framework import flags as _flags

    return _flags.get_flags(flags)


def set_flags(flags):
    from paddle_tpu.framework import flags as _flags

    return _flags.set_flags(flags)


def set_printoptions(**kwargs):
    import numpy as _np

    _np.set_printoptions(**{k: v for k, v in kwargs.items() if k in (
        "precision", "threshold", "edgeitems", "linewidth", "suppress")})

from paddle_tpu.tensor.extra_ops import *  # noqa: F401,F403,E402

# top-level re-exports the reference keeps in paddle.* (python/paddle/__init__.py)
from paddle_tpu.nn.layer.layers import ParamAttr  # noqa: F401,E402
from paddle_tpu.distributed.parallel import DataParallel  # noqa: F401,E402
from paddle_tpu.tensor.random import (  # noqa: F401,E402
    get_rng_state as get_cuda_rng_state, set_rng_state as set_cuda_rng_state,
)


class LazyGuard:
    """Deferred-init guard (reference python/paddle/base/dygraph/base.py
    LazyGuard): parameters created inside materialize lazily.  Eager jax arrays
    are cheap to build, so this is a bookkeeping context for API parity."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def tolist(x):
    return x.tolist()


def is_complex(x):
    from paddle_tpu.core import dtype as _dt
    return _dt.is_complex(x.dtype)


def is_integer(x):
    from paddle_tpu.core import dtype as _dt
    return _dt.is_integer(x.dtype)


def is_floating_point(x):
    from paddle_tpu.core import dtype as _dt
    return _dt.is_floating_point(x.dtype)


def check_shape(x):  # static-graph debugging helper (reference static/nn/control_flow)
    return list(x.shape)


def batch(reader, batch_size, drop_last=False):
    """Deprecated reader combinator (reference python/paddle/reader): groups a
    sample generator into batches."""

    def gen():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return gen


def _register_inplace_variants():
    """The reference exposes ``op_``-suffixed inplace twins for elementwise ops
    (generated from ops.yaml inplace specs); here they wrap the out-of-place op
    via Tensor._in_place, preserving autograd."""
    import sys

    mod = sys.modules[__name__]
    names = [
        "abs", "acos", "asin", "atan", "cos", "sin", "tan", "sinh", "cosh",
        "tanh", "exp", "expm1", "log", "log2", "log10", "log1p", "sqrt",
        "rsqrt", "square", "floor", "ceil", "round", "trunc", "frac", "neg",
        "erf", "erfinv", "lgamma", "digamma", "gammaln", "sigmoid", "logit",
        "i0", "sinc", "nan_to_num", "add", "subtract", "multiply", "divide",
        "floor_divide", "remainder", "mod", "floor_mod", "pow", "gcd", "lcm",
        "hypot", "ldexp", "copysign", "cumsum", "cumprod", "clip", "scale",
        "equal", "less_than", "less_equal", "greater_than", "greater_equal",
        "not_equal", "logical_and", "logical_or", "logical_not", "logical_xor",
        "bitwise_and", "bitwise_or", "bitwise_xor", "bitwise_not",
        "bitwise_left_shift", "bitwise_right_shift", "tril", "triu", "t",
        "transpose", "addmm", "multigammaln", "gammainc", "gammaincc",
        "masked_scatter",
    ]  # fill-style randoms (normal_/bernoulli_/cauchy_/geometric_/log_normal_)
       # have their own signatures and live in tensor/extra_ops.py
    from paddle_tpu.tensor.tensor import Tensor as _T

    def make(base_fn):
        def inplace(x, *args, **kwargs):
            return x._in_place(base_fn(x, *args, **kwargs))

        inplace.__name__ = base_fn.__name__ + "_"
        return inplace

    for n in names:
        base = getattr(mod, n, None)
        if base is None or hasattr(mod, n + "_"):
            continue
        fn = make(base)
        setattr(mod, n + "_", fn)
        if hasattr(_T, n) and not hasattr(_T, n + "_"):
            setattr(_T, n + "_", fn)


_register_inplace_variants()

from paddle_tpu.observability import compilecache as _compilecache  # noqa: E402

_compilecache.note_phase("import", _IMPORT_T0, _time.perf_counter(),
                         jax_imported_before=_JAX_WAS_IMPORTED)
