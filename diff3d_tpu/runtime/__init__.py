"""Cross-cutting runtime services shared by training, serving and the
entry points.

  * :mod:`diff3d_tpu.runtime.retry` — one policy object for "how do we
    classify and survive a transient backend/IO fault", so the trainer
    and the serving engine stop hand-rolling divergent copies of the
    same failure handling.
  * :mod:`diff3d_tpu.runtime.compile_cache` — where JAX's persistent
    compilation cache lives; every ``main`` calls it first.
"""

from diff3d_tpu.runtime.compile_cache import configure_compile_cache
from diff3d_tpu.runtime.retry import (RetryPolicy, RetryableError,
                                      is_transient_backend_error,
                                      is_transient_io_error)

__all__ = [
    "RetryPolicy", "RetryableError", "configure_compile_cache",
    "is_transient_backend_error", "is_transient_io_error",
]
