"""Which device this process runs on, and where its compiled
programs are kept.

One installation, one way to pick the device: JAX's own default. A
chip run sets nothing; a CPU run sets ``JAX_PLATFORMS=cpu``. Every
"kernel or interpreter", "bf16 or f32 default" and "measure or refuse"
decision in the tree asks :func:`on_tpu` and nothing else.
"""

from __future__ import annotations

import os

import jax

# <checkout>/.zoo_tpu_cache/xla — git-ignored. The path is part of
# every cache key, so it must never carry a temp name, pid or time.
DEFAULT_COMPILE_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".zoo_tpu_cache", "xla")


def on_tpu() -> bool:
    """True only when JAX's default backend is a real TPU."""
    return jax.default_backend() == "tpu"


def setup_compile_cache() -> str:
    """Place JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this
    sets nothing in code. Unset: the fixed in-checkout
    :data:`DEFAULT_COMPILE_CACHE`. Called by ``init_nncontext`` and by
    the bench/smoke scripts before their first compile — JAX decides
    once, at the first compile, whether a cache is in use.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir",
                      DEFAULT_COMPILE_CACHE)
    return DEFAULT_COMPILE_CACHE
