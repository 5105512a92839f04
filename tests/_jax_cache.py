"""Shared persistent-compile-cache prelude for subprocess test scripts.

The compile-bound subprocess tests (engine parity fp64/spmd, the launch
small-mesh compile) prepend this to their ``python -c`` scripts so lowered
XLA artifacts persist and reruns skip compilation.  The directory follows
``repro.launch.cache.enable_compile_cache``: ``$JAX_COMPILATION_CACHE_DIR``
when set (JAX reads it itself), the checkout's ``.jax_cache/`` otherwise.
One copy here keeps the recipe in sync across modules.
"""
import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CACHE_PRELUDE = (
    "import os, jax\n"
    "from repro.launch.cache import enable_compile_cache\n"
    "enable_compile_cache()\n"
    "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.5)\n"
)


def subprocess_env(**extra: str) -> dict:
    """Minimal environment for the subprocess scripts: the repo's sources,
    plus the parent's platform choice and compile-cache directory."""
    env = {"PYTHONPATH": os.path.join(REPO_ROOT, "src"),
           "PATH": "/usr/bin:/bin", "HOME": os.path.expanduser("~")}
    for k in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR"):
        if k in os.environ:
            env[k] = os.environ[k]
    env.update(extra)
    return env
