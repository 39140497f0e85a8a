"""Frame-sequence writer: the ``save_image`` sibling for trajectories.

A trajectory result is an ordered stack of frames; qualitative review
wants two artefacts per sequence: the ordered ``frame_%03d.png``
directory (drop into ffmpeg or a viewer) and a single contact-sheet
strip for eyeballing the whole turntable at a glance.  Used by
``eval_cli --orbit`` and handy from notebooks.
"""

from __future__ import annotations

import os
from typing import Optional

import numpy as np

__all__ = ["save_frame_sequence"]


def save_frame_sequence(out_dir: str, frames: np.ndarray,
                        prefix: str = "frame",
                        contact_sheet: bool = True,
                        columns: Optional[int] = None) -> dict:
    """Write ``frames`` as ``<out_dir>/<prefix>_%03d.png`` plus a
    ``contact_sheet.png`` strip.

    ``frames`` is ``[n, H, W, 3]`` in [-1, 1] (a guidance axis
    ``[n, B, H, W, 3]`` is accepted — lane 0 is written, matching how
    single-view results are reviewed).  The contact sheet tiles frames
    row-major, ``columns`` per row (default: all frames in one strip).
    Returns ``{"dir", "frames", "contact_sheet"}`` with the paths
    written, so CLI callers can report artefact locations.
    """
    # imported here: sampling imports the model, whose layers import
    # this package's profiling module
    from diff3d_tpu.sampling.runtime import save_image, to_uint8

    frames = np.asarray(frames, np.float32)
    if frames.ndim == 5:
        frames = frames[:, 0]
    if frames.ndim != 4 or frames.shape[-1] != 3:
        raise ValueError(
            f"frames must be [n, H, W, 3] (or [n, B, H, W, 3]), got "
            f"{frames.shape}")
    n = frames.shape[0]
    if n == 0:
        raise ValueError("no frames to write")
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    for k in range(n):
        path = os.path.join(out_dir, f"{prefix}_{k:03d}.png")
        save_image(path, frames[k])
        paths.append(path)
    out = {"dir": out_dir, "frames": paths, "contact_sheet": None}
    if contact_sheet:
        from PIL import Image

        cols = n if columns is None else max(1, min(int(columns), n))
        rows = -(-n // cols)
        H, W = frames.shape[1:3]
        sheet = np.zeros((rows * H, cols * W, 3), np.uint8)
        for k in range(n):
            r, c = divmod(k, cols)
            sheet[r * H:(r + 1) * H, c * W:(c + 1) * W] = to_uint8(
                frames[k])
        sheet_path = os.path.join(out_dir, "contact_sheet.png")
        Image.fromarray(sheet).save(sheet_path)
        out["contact_sheet"] = sheet_path
    return out
