"""Time the autoregressive sampler: seconds per synthesised view at the
reference's config (256 steps, 8-weight guidance sweep, 64x64).

The reference's sampler does 2 model forwards per step with host round
trips per step (``/root/reference/sampling.py:97-103``); here one view is
one compiled ``lax.scan``.
"""

from __future__ import annotations

import os
import sys
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def main() -> None:
    import dataclasses

    import jax

    from diff3d_tpu.runtime import configure_compile_cache

    configure_compile_cache()
    import numpy as np

    from diff3d_tpu.config import srn64_config
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling.runtime import Sampler
    from diff3d_tpu.train.trainer import init_params

    cfg = srn64_config()
    if len(sys.argv) > 1:
        cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, attn_impl=sys.argv[1]))
        print(f"attn_impl={sys.argv[1]}")
    model = build_model(cfg)
    rng = jax.random.PRNGKey(0)
    params = init_params(model, cfg, rng)
    sampler = Sampler(model, params, cfg)

    rs = np.random.RandomState(0)
    n_views = 4
    views = {
        "imgs": rs.randn(n_views, cfg.model.H, cfg.model.W,
                         3).astype(np.float32),
        "R": np.broadcast_to(np.eye(3, dtype=np.float32),
                             (n_views, 3, 3)).copy(),
        "T": rs.randn(n_views, 3).astype(np.float32),
        "K": np.array([[64 * 1.2, 0, 32], [0, 64 * 1.2, 32], [0, 0, 1]],
                      np.float32),
    }

    # Warmup (compile) at the SAME record-buffer capacity as the timed run.
    sampler.synthesize(views, rng, max_views=n_views)

    t0 = time.perf_counter()
    sampler.synthesize(views, rng, max_views=n_views)
    # graftlint: disable-next-line=GL106(synthesize fetches the record to host before returning - value-synced)
    dt = time.perf_counter() - t0
    per_view = dt / (n_views - 1)
    print(f"sampler: {per_view:.2f}s/view "
          f"({per_view / cfg.diffusion.timesteps * 1e3:.1f}ms per "
          f"diffusion step, {len(cfg.diffusion.guidance_weights)}-weight "
          "sweep)")


if __name__ == "__main__":
    main()
