"""Image quantization at the host->device boundary.

PNG sources are 8-bit, but the reference ships float32 images to the
device (4 bytes/px/channel).  On TPU the host->HBM link is the scarce
resource, so batches cross it as uint8 — 4x less traffic and host RAM — and the
normalization to [-1, 1] runs on-device inside the jitted step, where
XLA fuses it into the first conv for free.

The [-1, 1] float pipeline quantizes to the same 1/127.5 grid the 8-bit
sources came from, so the roundtrip costs at most half a quantization
step (resized pixels land off-grid by < 1/255 — invisible to training).
"""

from __future__ import annotations

import logging
import os

import numpy as np

log = logging.getLogger(__name__)
# warn_state for direct quantize_uint8(imgs) calls (public API default):
# one first-call range check process-wide.
_default_warn_state: dict = {}


def _check_always() -> bool:
    """DIFF3D_CHECK_RANGE=always: range-check EVERY batch (full min/max
    scan) instead of only each loader's first — for debugging data that
    may go out of range mid-run (e.g. a warmup-scheduled augmentation).
    Read per call (os.environ lookup is ~100ns against a min/max scan of
    a multi-MB batch) so flipping the env var mid-process takes effect."""
    return os.environ.get("DIFF3D_CHECK_RANGE", "").lower() == "always"


def quantize_uint8(imgs: np.ndarray, warn_state: dict = None) -> np.ndarray:
    """Host-side ``[-1, 1] float`` -> ``[0, 255] uint8`` (round-to-nearest).

    Inputs are expected in [-1, 1]; anything outside (a future dataset or
    augmentation with wider range / >8-bit precision) would be silently
    clipped and quantized.  ``warn_state`` is a per-caller mutable dict
    (e.g. one per :class:`InfiniteLoader`): the FIRST array it sees is
    range-checked and an out-of-range source logged, then the flag flips
    so steady state pays no min/max scan and one loader's bad data never
    silences another's warning.  Default: a process-wide first-call
    check.  Data that only goes out of range later in a run is NOT
    caught by the first-batch check — set ``DIFF3D_CHECK_RANGE=always``
    to scan every batch, or opt out of uint8 transport per loader with
    ``InfiniteLoader(images_uint8=False)`` for wide-range data.
    """
    imgs = np.asarray(imgs)
    if warn_state is None:
        warn_state = _default_warn_state
    if _check_always() or not warn_state.get("checked"):
        # Benign race under the loader's thread pool: concurrent first
        # calls may each scan (and at worst double-log) — per-loader
        # state just bounds it to that loader's first batch.
        warn_state["checked"] = True
        lo, hi = float(imgs.min()), float(imgs.max())
        if lo < -1.0001 or hi > 1.0001:
            # Warn on the first offence, then only when the violation
            # WORSENS past the previously warned extremes: a steady
            # out-of-range stream logs once, but data drifting further
            # out mid-run (always-mode's stated use case) keeps
            # signalling instead of being latched silent (ADVICE r4).
            worst_lo = warn_state.get("warned_lo", -1.0)
            worst_hi = warn_state.get("warned_hi", 1.0)
            if lo < worst_lo - 1e-6 or hi > worst_hi + 1e-6:
                warn_state["warned_lo"] = min(lo, worst_lo)
                warn_state["warned_hi"] = max(hi, worst_hi)
                log.warning(
                    "quantize_uint8: input range [%.3f, %.3f] exceeds "
                    "[-1, 1]; values will be clipped (pass "
                    "images_uint8=False to the loader to keep full "
                    "precision)", lo, hi)
    return np.clip((imgs + 1.0) * 127.5 + 0.5, 0, 255).astype(np.uint8)


def dequantize(imgs):
    """``uint8 [0, 255]`` -> ``float32 [-1, 1]``; float inputs pass through.

    jnp- and np-compatible (dtype dispatch is static under jit), so it is
    safe inside compiled train/eval steps.
    """
    if imgs.dtype == np.uint8:
        return imgs.astype(np.float32) / 127.5 - 1.0
    return imgs
