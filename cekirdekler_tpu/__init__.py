"""cekirdekler_tpu — a TPU-native multi-chip compute framework.

A from-scratch, TPU-first framework with the capabilities of the reference
C#/OpenCL Cekirdekler API: treat all chips of a TPU slice as one device for
user-supplied kernels.  Kernels (an OpenCL-C-like subset, Python functions,
or raw Pallas) are JIT-compiled via XLA and dispatched across chips with an
iterative, per-compute-id load balancer; host arrays stage through pinned
aligned buffers; transfer/compute overlap rides XLA async dispatch; pipeline
stages exchange data over ICI collectives; pools, a cluster tier, and
sequence/tensor parallel utilities sit on top.
"""

import os as _os

import jax as _jax

# jax's persistent compilation cache, configured ONCE, here, where the
# package first touches jax.  Every knob yields to its own JAX_* environment
# variable (jax has read those already; none that is set is overridden).
#
# - Place.  JAX_COMPILATION_CACHE_DIR, else one fixed, git-ignored
#   directory in the checkout — never tempfile-, pid- or time-derived (a
#   directory that moves is never found by the next process).
#   PLACED_CACHE_DIR names it when the package placed it: that directory is
#   the package's own to size (core/compilecache.trim_placed_jax_cache);
#   one placed from outside belongs to whoever placed it.
# - Floor.  Every executable persists: the launch ladder is made of MANY
#   sub-second executables (a Mosaic rung compiles in 0.15-0.75 s on a
#   v5e), so under jax's 1 s default nearly none of them reach the disk
#   and a new process pays them all again.
PLACED_CACHE_DIR = None
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    PLACED_CACHE_DIR = _os.path.join(
        _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
        ".jax_cache")
    _jax.config.update("jax_compilation_cache_dir", PLACED_CACHE_DIR)
if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in _os.environ:
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

from .arrays import ClArray, FastArr, FloatArr, IntArr, ParameterGroup, TransferFlags, wrap
from .errors import (
    CekirdeklerError,
    ComputeValidationError,
    DeviceSelectionError,
    KernelCompileError,
    KernelLanguageError,
)
from .hardware import (
    AcceleratorType, Device, Devices, Platform, Platforms, all_devices,
    chip_devices, platforms,
)
from . import metrics  # always-on health registry (docs/OBSERVABILITY.md)
from . import obs  # live introspection plane (docs/OBSERVABILITY.md)
from . import trace  # span-based attribution (docs/OBSERVABILITY.md)

__version__ = "0.1.0"

__all__ = [
    "AcceleratorType",
    "CekirdeklerError",
    "ClArray",
    "ComputeValidationError",
    "Device",
    "DeviceSelectionError",
    "Devices",
    "FastArr",
    "FloatArr",
    "IntArr",
    "KernelCompileError",
    "KernelLanguageError",
    "ParameterGroup",
    "Platform",
    "Platforms",
    "TransferFlags",
    "all_devices",
    "chip_devices",
    "platforms",
    "metrics",
    "trace",
    "wrap",
]
