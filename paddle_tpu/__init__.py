"""paddle_tpu — a TPU-native deep-learning framework with the capabilities of
transition-era PaddlePaddle (v2 + Fluid).

Structure:
  paddle_tpu.fluid     program IR + layers + lowering executor (the core)
  paddle_tpu.v2        legacy v2 user API (init/layer/trainer/events) on fluid
  paddle_tpu.parallel  device meshes, SPMD sharding, distributed init
  paddle_tpu.resilience  fault tolerance: retries, chaos injection,
                       crash-safe training driver
  paddle_tpu.models    the "book" model zoo (fit_a_line ... transformer)
  paddle_tpu.native    ctypes bridge to the C++ IR library (csrc/)
  paddle_tpu.ops       Pallas TPU kernels for ops XLA fusion can't cover
  paddle_tpu.utils     profiler, flags, misc runtime utilities
"""

import os as _os


def _place_compile_cache() -> None:
    """The program's ONE compile cache is JAX's persistent compilation
    cache, placed here because every entry point (and every child:
    supervised gateways, fleet replicas, launch workers) imports this
    package before it compiles anything.  Where the environment names a
    directory (``JAX_COMPILATION_CACHE_DIR``) JAX reads it itself and
    nothing is set in code; otherwise the cache lives at a FIXED path
    next to the package — the path is part of the cache key's lookup, so
    a directory built from a tempdir, a pid or the time never hits."""
    if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    checkout = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    jax.config.update("jax_compilation_cache_dir",
                      _os.path.join(checkout, ".jax_cache"))


_place_compile_cache()

from . import fluid  # noqa: F401,E402
from . import parallel  # noqa: F401,E402
from . import resilience  # noqa: F401,E402
from . import utils  # noqa: F401,E402
from . import native  # noqa: F401,E402

__version__ = "0.1.0"
