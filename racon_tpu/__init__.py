"""racon-tpu: a TPU-native long-read consensus / assembly-polishing framework.

Feature-parity re-design of lbcb-sci/racon (v1.5.0): reads + overlaps
(MHAP/PAF/SAM) + draft targets in, polished contigs (or error-corrected
fragments) out. The host runtime (parsing, data model, filtering, windowing,
POA oracle, stitching) is native C++ (racon_tpu/native); the accelerated path
runs batched banded alignment and batched partial-order alignment as JAX/
Pallas kernels sharded over TPU meshes (racon_tpu/ops, racon_tpu/parallel).
"""

__version__ = "0.1.0"

import os as _os
import sys as _sys

# One persistent XLA/Mosaic compilation cache (kernel geometries are
# stable, so every later process skips the compiles).  Where
# JAX_COMPILATION_CACHE_DIR is set — "" included, the explicit opt-out —
# JAX uses it and nothing here names a directory.  Where it is not, the
# cache is a fixed git-ignored directory in the checkout: the path is
# part of JAX's cache key, so it is never built from a uid, pid, temp
# name or time.  The threshold is set here and nowhere else: 0 caches
# every compile, so a warm run loads all of its kernels (the sub-second
# Hirschberg ones included) instead of rebuilding them.
JAX_CACHE_DIR = _os.path.join(
    _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))),
    ".jax_cache")
for _var, _opt, _val in (
        ("JAX_COMPILATION_CACHE_DIR", "jax_compilation_cache_dir",
         JAX_CACHE_DIR),
        ("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
         "jax_persistent_cache_min_compile_time_secs", 0.0)):
    if _var not in _os.environ:
        _os.environ[_var] = str(_val)
        if "jax" in _sys.modules:
            # imported before us: it has already read the environment
            _sys.modules["jax"].config.update(_opt, _val)

from .polisher import CpuPolisher, TpuPolisher, create_polisher  # noqa: F401
from .pipeline import Pipeline  # noqa: F401
