"""Accelerator platform names and the persistent XLA compile cache (L2).

Reference analog: ``gst/nnstreamer/hw_accel.c`` — the one place that says
what counts as the acceleration target. Platform *selection* is jax's own
(``JAX_PLATFORMS`` or its default order); a missing chip is jax's error,
never a fallback decided here.
"""
from __future__ import annotations

import os

TPU_PLATFORMS = ("tpu",)

#: the checkout root (the directory holding the ``nnstreamer_tpu`` package)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: where the XLA binary cache lives when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset. The path is part of jax's cache key, so it is one fixed
#: directory: a cache that moves never hits.
COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_compile_cache")


def is_tpu_platform(platform: str) -> bool:
    return platform in TPU_PLATFORMS


def pallas_interpret(platform: str) -> bool:
    """Whether a Pallas TPU kernel runs through the interpreter on
    ``platform``: Mosaic-lowered on a TPU, interpreted on the CPU (how the
    tests cover the kernels), an error anywhere else — a TPU kernel must
    not be Triton-lowered for a GPU, and no other backend may quietly
    interpret."""
    if is_tpu_platform(platform):
        return False
    if platform == "cpu":
        return True
    raise ValueError(
        f"pallas TPU kernels run on tpu (or interpreted on cpu for tests), "
        f"not on {platform!r}")


def enable_compilation_cache() -> str:
    """Turn on jax's persistent compilation cache for this process and
    return the directory in use. Process entry points call this before
    their first compile (the CLI, ``chip_smoke.py``, ``benchmark/run.py``);
    importing the library never does.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already honours it, the cache
    is the outside's to configure, and nothing is assigned here. Unset:
    :data:`COMPILE_CACHE_DIR`, keeping every program and not only the
    slow ones — a pipeline is many small jitted stages and a restart pays
    for each (on the v5e a warm ``chip_smoke.py`` still spent a quarter of
    the cold run's compile seconds under jax's 1 s threshold, PR 21).

    The program's compile account (``obs.context.compile_account``) begins
    to listen here, so that it holds every compile of a process whose entry
    point enabled the cache, those before its first program span too.
    """
    from ..obs import context as obs_context

    obs_context.compile_account()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return COMPILE_CACHE_DIR
