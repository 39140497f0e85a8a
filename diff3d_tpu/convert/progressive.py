"""Progressive resolution transfer: reuse trained weights across H/W.

The X-UNet is resolution-independent everywhere except the
ConditioningProcessor's learned per-pixel embedding ``pos_emb [H, W, 144]``
(reference ``xunet.py:280-282``): convs slide, GroupNorm/FiLM act per
channel, attention runs over whatever H*W tokens arrive, and the ray/NeRF
pose embeddings are computed from the camera at the current resolution.
So a model trained at 64^2 transfers to 128^2 by copying every parameter
and bilinearly upsampling ``pos_emb`` — the coarse spatial prior it
learned stays aligned (pixel i of H covers the same image fraction as
pixel 2i of 2H).

Why this exists: the paper's 128^2 config costs ~4x the compute per
example of 64^2, and training it from scratch inside a fixed chip-hour
budget underfits (round-3: held-out PSNR 3.6 dB below the copy baseline
at 640K examples).  Seeding from a trained 64^2 model hands
the 128^2 run everything resolution-independent — geometry conditioning,
cross-view attention, the denoising prior — so its budget is spent on the
only new thing, fine spatial detail.  (The reference has no counterpart:
it cannot even run 128^2, ``/root/reference/README.md:39``.)
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def adapt_params_resolution(params, dst_hw: Tuple[int, int]):
    """Return ``params`` adapted to a model of resolution ``dst_hw``.

    Every leaf is copied unchanged except
    ``conditioningprocessor/pos_emb [H, W, C]``, which is resized with
    bilinear interpolation.  Raises KeyError if the tree has no
    conditioningprocessor (not an X-UNet param tree) — passing e.g. an
    opt-state pytree here would otherwise silently no-op.

    Works on concrete arrays and (for shape checks) ShapeDtypeStructs.
    """
    cp = dict(params["conditioningprocessor"])
    if "pos_emb" in cp:
        pe = cp["pos_emb"]
        H2, W2 = dst_hw
        if pe.shape[:2] != (H2, W2):
            cp["pos_emb"] = jax.image.resize(
                pe, (H2, W2, pe.shape[2]), method="bilinear")
    out = dict(params)
    out["conditioningprocessor"] = cp
    return out


def init_student_from_teacher(params, dst_hw: Tuple[int, int] | None = None):
    """Fresh student params for one progressive-distillation round
    (``diff3d_tpu.train.distill``): the teacher's weights, deep-copied so
    the student's donated train step can never alias the teacher buffers
    it must keep reading, optionally resolution-adapted first (a 64^2
    teacher can seed a 128^2 student the same way full training transfers
    across resolutions)."""
    if dst_hw is not None:
        params = adapt_params_resolution(params, dst_hw)
    return jax.tree.map(jnp.copy, params)


def check_resolution_compatible(src_params, dst_params) -> None:
    """Assert ``src_params`` (adapted) matches ``dst_params``'s tree —
    same widths everywhere; only pos_emb may have differed.  Raises
    ValueError naming the first mismatch (e.g. seeding a --ch 128 run
    from a --ch 64 checkpoint)."""
    src_flat = dict(jax.tree_util.tree_flatten_with_path(src_params)[0])
    dst_flat = dict(jax.tree_util.tree_flatten_with_path(dst_params)[0])
    if src_flat.keys() != dst_flat.keys():
        missing = sorted(map(jax.tree_util.keystr,
                             dst_flat.keys() - src_flat.keys()))
        extra = sorted(map(jax.tree_util.keystr,
                           src_flat.keys() - dst_flat.keys()))
        raise ValueError(
            f"init_from checkpoint tree mismatch: missing={missing[:4]} "
            f"extra={extra[:4]} — the source model's width/depth "
            "(--ch/--emb_ch/--num_res_blocks) must equal the target's")
    for k in dst_flat:
        if jnp.shape(src_flat[k]) != jnp.shape(dst_flat[k]):
            raise ValueError(
                f"init_from shape mismatch at {jax.tree_util.keystr(k)}: "
                f"source {jnp.shape(src_flat[k])} vs target "
                f"{jnp.shape(dst_flat[k])} — source width must equal "
                "target width (only H/W may differ)")
