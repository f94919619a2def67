"""Backend detection and compile-cache placement.

One rule decides whether a Pallas kernel compiles or interprets, and
every kernel module asks it here: the default backend is the TPU. A
backend that fails to initialise raises — it is never read as "no TPU".
"""

import os

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def is_tpu_backend() -> bool:
    # evaluated per call (no cache): a process may switch backends, e.g.
    # jax.config.update("jax_platforms", "cpu") + clear_backends
    return jax.default_backend() == "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already and
    nothing is set in code. Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache`` (git-ignored): the path is part of the cache
    key, so it never carries a pid, a time or a temp name. The minimum
    compile time is 0 because Pallas kernels compile in 1-2 s and would
    otherwise never be cached."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
