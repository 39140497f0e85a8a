"""One place that decides where JAX's persistent compilation cache lives.

Every ``main`` of this repo (the CLIs, ``chip_smoke.py``, the tools
that jit, ``tests/conftest.py``) calls
:func:`configure_compile_cache` first thing, so the whole program shares
one cache and a cold full-width compile is paid once per machine:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself;
    nothing is touched, and a config that disagrees with it is an error
    (somebody called ``jax.config.update`` behind the variable's back).
  * unset — one fixed directory inside the checkout,
    :data:`DEFAULT_CACHE_DIR` (git-ignored).  Never a temporary name, a
    pid or a time: a directory that moves never hits.
"""

from __future__ import annotations

import os
from typing import Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — next to the ``diff3d_tpu`` package.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache(cache_dir: Optional[str] = None) -> str:
    """Settle the persistent compile cache's directory; returns it.

    ``cache_dir`` is an explicit caller choice (``worker_cli
    --compile_cache``) used in place of :data:`DEFAULT_CACHE_DIR`; like
    the default it yields to ``JAX_COMPILATION_CACHE_DIR``.
    """
    import jax

    from_env = os.environ.get(ENV_VAR)
    if from_env:
        configured = jax.config.jax_compilation_cache_dir
        if configured != from_env:
            raise RuntimeError(
                f"{ENV_VAR}={from_env!r} but jax_compilation_cache_dir is "
                f"{configured!r}: something set the cache directory in "
                "code; only the variable may place it when it is set")
        return from_env
    path = cache_dir or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    return path
