"""Plain ``jax.numpy`` X-UNet (Watson et al. 2022, "Novel view synthesis
with diffusion models", section 3 and appendix B), written from the
paper's description and the PyTorch re-implementation's layer list
(SURVEY.md).  Nothing of ``diff3d_tpu`` is imported: this file is the
yardstick the timed path is compared with.

Layout: feature maps are ``[B, F, H, W, C]`` (F = 2 frames: conditioning
view and noisy target).  Parameters are one flat ``dict`` keyed by the
``/``-joined path of the layer, so that the same seeded values can be
handed to the program (which nests them by the same names) and to this
file.  ``param_shapes(cfg)`` enumerates them by walking the forward pass.

Precision (``prec``; a traced int32 instead of a name keeps that many
mantissa bits, see ``round_mantissa``):
  * ``"float32"``  -- every contraction at ``Precision.HIGHEST``, all
    arithmetic float32.  The reference.
  * ``"bfloat16"`` -- operands of every contraction rounded to bfloat16,
    float32 accumulation.  What the configurations state.
  * ``"fp8"``      -- operands rounded to 3 mantissa bits (e4m3's).  The
    control: the nearest precision below the stated one.

Departures from the paper, all shared with the program and noted here:
GroupNorm epsilon 1e-5 (torch's), residual sums divided by sqrt(2),
dropout after FiLM inside each residual block, the guidance mask zeroing
the pose encoding of both frames.
"""

from __future__ import annotations

import hashlib
import math
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

POS_DEG = 15
DIR_DEG = 8
POSE_CH = (3 + 2 * 3 * POS_DEG) + (3 + 2 * 3 * DIR_DEG)   # 93 + 51 = 144
GN_EPS = 1e-5

_HI = jax.lax.Precision.HIGHEST


MANTISSA_BITS = {"bfloat16": 7, "fp8": 3}


def _round(x, prec: str):
    """``x`` rounded to the mantissa of ``prec``'s operand type, by
    ``lax.reduce_precision`` with float32's exponent: fp8 (e4m3) as a
    well-scaled tensor would see it, 3 mantissa bits and no overflow or
    flush.  Not by a cast there and back: XLA on the TPU removes such a
    pair (it allows excess precision), and the control then computed in
    float32 (chip readings, PERF.md PR 23).  The gradient passes straight
    through: the backward contractions read the rounded forward values,
    cotangents are not themselves rounded."""
    if isinstance(prec, str):
        if prec == "float32":
            return x
        if prec not in MANTISSA_BITS:
            raise ValueError(f"unknown precision {prec!r}")
        low = jax.lax.reduce_precision(x, exponent_bits=8,
                                       mantissa_bits=MANTISSA_BITS[prec])
    else:
        low = round_mantissa(x, prec)
    return x + jax.lax.stop_gradient(low - x)


def round_mantissa(x, bits):
    """``x`` (float32) rounded to nearest-even at ``bits`` mantissa bits,
    ``bits`` a traced int32 in [0, 23]; 23 gives ``x`` back bit for bit.
    One compiled reference then serves as itself and as its control
    (calibration only: the benchmark's own runs compile no rounding)."""
    shift = (23 - jnp.asarray(bits, jnp.int32)).astype(jnp.uint32)
    word = jax.lax.bitcast_convert_type(x, jnp.uint32)
    one = jnp.uint32(1)
    half = (one << shift) >> one
    odd = (word >> shift) & one
    bias = jnp.where(shift > 0, half - one + odd, jnp.uint32(0))
    kept = ~((one << shift) - one)
    return jax.lax.bitcast_convert_type((word + bias) & kept, jnp.float32)


# ----------------------------------------------------------------- layers

def dense(x, w, b, prec):
    y = jnp.einsum("...i,io->...o", _round(x, prec), _round(w, prec),
                   precision=_HI, preferred_element_type=jnp.float32)
    return y + b


def conv(x, w, b, prec, stride: int = 1):
    """``x [N, H, W, Cin]``, ``w [kh, kw, Cin, Cout]``; zero padding of
    ``k // 2`` on each side (torch's ``padding=1`` for 3x3)."""
    k = w.shape[0]
    y = jax.lax.conv_general_dilated(
        _round(x, prec), _round(w, prec), (stride, stride),
        [(k // 2, k // 2)] * 2, dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=_HI, preferred_element_type=jnp.float32)
    return y + b


def num_groups(C: int) -> int:
    g = min(32, C)
    while C % g:
        g -= 1
    return g


def group_norm(x, scale, bias):
    """Per frame, per example: ``x [N, H, W, C]`` normalised over
    (H, W, C/G) for each of G groups."""
    N, H, W, C = x.shape
    G = num_groups(C)
    xg = x.reshape(N, H * W, G, C // G)
    mean = xg.mean(axis=(1, 3), keepdims=True)
    var = jnp.square(xg - mean).mean(axis=(1, 3), keepdims=True)
    y = ((xg - mean) * jax.lax.rsqrt(var + GN_EPS)).reshape(N, H, W, C)
    return y * scale + bias


def silu(x):
    return x * jax.nn.sigmoid(x)


def attention(q, k, v, heads: int, prec):
    """``[N, L, C]`` each; softmax(q k^T / sqrt(d)) v per head."""
    N, L, C = q.shape
    d = C // heads
    qh = _round(q, prec).reshape(N, L, heads, d)
    kh = _round(k, prec).reshape(N, k.shape[1], heads, d)
    vh = _round(v, prec).reshape(N, v.shape[1], heads, d)
    logits = jnp.einsum("nqhd,nkhd->nhqk", qh, kh, precision=_HI,
                        preferred_element_type=jnp.float32) / math.sqrt(d)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("nhqk,nkhd->nqhd", _round(p, prec), vh, precision=_HI,
                     preferred_element_type=jnp.float32)
    return out.reshape(N, L, C)


def posenc_ddpm(t, emb_ch: int):
    """DDPM sinusoidal embedding of logsnr (scaled by 1000)."""
    half = emb_ch // 2
    freq = np.exp(np.arange(half) * -(np.log(10000.0) / (half - 1)))
    e = (t * 1000.0)[..., None] * jnp.asarray(freq, jnp.float32)
    return jnp.concatenate([jnp.sin(e), jnp.cos(e)], axis=-1)


def posenc_nerf(x, deg: int):
    scales = jnp.asarray([2.0 ** i for i in range(deg)], jnp.float32)
    xb = (x[..., None, :] * scales[:, None]).reshape(*x.shape[:-1], -1)
    return jnp.concatenate(
        [x, jnp.sin(jnp.concatenate([xb, xb + jnp.pi / 2.0], axis=-1))],
        axis=-1)


def camera_rays(R, t, K, H: int, W: int):
    """Pinhole rays at pixel centres.  ``R [B,F,3,3]`` world-from-camera,
    ``t [B,F,3]``, ``K [B,3,3]`` -> origins and unit directions
    ``[B,F,H,W,3]``."""
    u = jnp.arange(W, dtype=jnp.float32) + 0.5
    v = jnp.arange(H, dtype=jnp.float32) + 0.5
    uu, vv = jnp.meshgrid(u, v)
    px = jnp.stack([uu, vv, jnp.ones_like(uu)], axis=-1)
    cam = jnp.einsum("bij,hwj->bhwi", jnp.linalg.inv(K), px, precision=_HI)
    d = jnp.einsum("bfij,bhwj->bfhwi", R, cam, precision=_HI)
    d = d / jnp.linalg.norm(d, axis=-1, keepdims=True)
    return jnp.broadcast_to(t[:, :, None, None, :], d.shape), d


# ---------------------------------------------- dropout keys (flax's rule)

def dropout_key(key, path: Tuple[str, ...]):
    """The key flax hands the ``Dropout_0`` child of the module at
    ``path``: ``fold_in(key, first 4 bytes of sha1(path names + counter
    1))``.  The program's masks follow from the step's key by this rule,
    so the reference can draw the same masks from the same key."""
    m = hashlib.sha1()
    for name in path + ("Dropout_0",):
        m.update(name.encode("utf-8"))
    m.update((1).to_bytes(1, "big"))
    return jax.random.fold_in(
        key, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


# ---------------------------------------------------------------- forward

class _Params:
    """Looks parameters up by path; in spec mode records their shapes."""

    def __init__(self, values: Optional[Dict[str, jnp.ndarray]]):
        self.values = values
        self.shapes: Dict[str, Tuple[Tuple[int, ...], str]] = {}

    def __call__(self, path: str, shape, kind: str):
        shape = tuple(int(s) for s in shape)
        self.shapes[path] = (shape, kind)
        if self.values is None:
            return jnp.zeros(shape, jnp.float32)
        v = self.values[path]
        if tuple(v.shape) != shape:
            raise ValueError(f"{path}: have {v.shape}, need {shape}")
        return v.astype(jnp.float32)


def forward(params: Optional[Dict[str, jnp.ndarray]], batch: dict,
            cond_mask, cfg: dict, *, prec: str = "float32",
            drop_key=None, rows: Optional[Tuple] = None,
            full_rows: Optional[int] = None,
            _p: Optional[_Params] = None):
    """Predicted noise of the target frame, ``[B, H, W, 3]``.

    ``batch``: ``x, z [B,H,W,3]``, ``logsnr [B,2]``, ``R [B,2,3,3]``,
    ``t [B,2,3]``, ``K [B,3,3]``.  ``drop_key`` switches dropout on
    (training); ``rows = (start, size)`` then says which rows of the
    step's microbatch of ``full_rows`` these are (``start`` may be traced), so that a block of rows draws its part of
    the whole microbatch's mask.
    """
    P = _p or _Params(params)
    H, W = cfg["H"], cfg["W"]
    ch, emb_ch = cfg["ch"], cfg["emb_ch"]
    mult = list(cfg["ch_mult"])
    nres = len(mult)
    nblk = cfg["num_res_blocks"]
    attn_levels = set(cfg["attn_levels"])
    heads = cfg["attn_heads"]
    rate = float(cfg["dropout"]) if drop_key is not None else 0.0
    dims = [ch * m for m in mult]
    B = batch["x"].shape[0]
    full_rows = B if full_rows is None else int(full_rows)

    def cv(path, x5, cout, k=3, stride=1, kind="conv"):
        b, f, h, w, cin = x5.shape
        y = conv(x5.reshape(b * f, h, w, cin),
                 P(f"{path}/kernel", (k, k, cin, cout), kind),
                 P(f"{path}/bias", (cout,), "bias"), prec, stride)
        return y.reshape(b, f, *y.shape[1:])

    def dn(path, x, cout, kind="dense"):
        return dense(x, P(f"{path}/kernel", (x.shape[-1], cout), kind),
                     P(f"{path}/bias", (cout,), "bias"), prec)

    def gn(path, x5):
        b, f, h, w, c = x5.shape
        y = group_norm(x5.reshape(b * f, h, w, c),
                       P(f"{path}/GroupNorm_0/scale", (c,), "scale"),
                       P(f"{path}/GroupNorm_0/bias", (c,), "bias"))
        return y.reshape(x5.shape)

    def dropout(path, h):
        if rate == 0.0:
            return h
        keep = 1.0 - rate
        shape = (full_rows,) + h.shape[1:]
        mask = jax.random.bernoulli(
            dropout_key(drop_key, tuple(path.split("/"))), keep, shape)
        if rows is not None:
            mask = jax.lax.dynamic_slice_in_dim(mask, rows[0], rows[1])
        return jnp.where(mask, h / keep, 0.0)

    def resnet(path, h_in, emb, features, resample=None):
        cin = h_in.shape[-1]
        h = silu(gn(f"{path}/FrameGroupNorm_0", h_in))
        h = cv(f"{path}/conv1", h, features)
        h = gn(f"{path}/FrameGroupNorm_1", h)
        film = dn(f"{path}/FiLM_0/Dense_0", silu(emb), 2 * features)
        scale, shift = jnp.split(film, 2, axis=-1)
        h = h * (1.0 + scale) + shift
        h = dropout(path, h)
        h = cv(f"{path}/conv2", h, features, kind="conv_zero")
        if cin != features:
            h_in = cv(f"{path}/skip_proj", h_in, features, k=1)
        out = (h + h_in) / math.sqrt(2.0)
        if resample == "down":
            b, f, hh, ww, c = out.shape
            out = out.reshape(b, f, hh // 2, 2, ww // 2, 2, c).mean((3, 5))
        elif resample == "up":
            out = jnp.repeat(jnp.repeat(out, 2, axis=2), 2, axis=3)
        return out

    def attn_block(path, h_in, kind):
        b, f, hh, ww, c = h_in.shape
        tok = gn(f"{path}/FrameGroupNorm_0", h_in).reshape(b, f, hh * ww, c)
        q = tok.reshape(b * f, hh * ww, c)
        kv = q if kind == "self" else jnp.roll(tok, -1, axis=1).reshape(
            b * f, hh * ww, c)
        a = attention(dn(f"{path}/attn/q_proj", q, c),
                      dn(f"{path}/attn/k_proj", kv, c),
                      dn(f"{path}/attn/v_proj", kv, c), heads, prec)
        a = dn(f"{path}/attn/out_proj", a, c).reshape(b, f, hh, ww, c)
        a = cv(f"{path}/out_conv", a, c, k=1, kind="conv_zero")
        return (a + h_in) / math.sqrt(2.0)

    def block(path, h, emb, features, use_attn):
        h = resnet(f"{path}/resnetblock", h, emb, features)
        if use_attn:
            h = attn_block(f"{path}/attnblock_self", h, "self")
            h = attn_block(f"{path}/attnblock_cross", h, "cross")
        return h

    # ---- conditioning: noise level and pose -> one embedding per level
    cp = "conditioningprocessor"
    clip = cfg.get("logsnr_clip", 20.0)
    le = posenc_ddpm(jnp.clip(batch["logsnr"], -clip, clip), emb_ch)
    le = dn(f"{cp}/Dense_0", le, emb_ch)
    le = dn(f"{cp}/Dense_1", silu(le), emb_ch)                # [B, 2, emb]
    pos, dirs = camera_rays(batch["R"].astype(jnp.float32),
                            batch["t"].astype(jnp.float32),
                            batch["K"].astype(jnp.float32), H, W)
    pose = jnp.concatenate([posenc_nerf(pos, POS_DEG),
                            posenc_nerf(dirs, DIR_DEG)], axis=-1)
    pose = jnp.where(cond_mask[:, None, None, None, None], pose, 0.0)
    pose = pose + P(f"{cp}/pos_emb", (H, W, POSE_CH), "emb")[None, None]
    first = P(f"{cp}/first_emb", (1, 1, 1, 1, POSE_CH), "emb")
    other = P(f"{cp}/other_emb", (1, 1, 1, 1, POSE_CH), "emb")
    pose = pose + jnp.concatenate([first, other], axis=1)
    embs = []
    for lvl in range(nres):
        e = cv(f"{cp}/level_conv_{lvl}", pose, emb_ch, stride=2 ** lvl)
        embs.append(le[:, :, None, None, :] + e)

    # ---- U-Net over both frames
    h = jnp.stack([batch["x"], batch["z"]], axis=1).astype(jnp.float32)
    h = cv("stem_conv", h, ch)
    skips = [h]
    for lvl in range(nres):
        for i in range(nblk):
            h = block(f"down_{lvl}_{i}", h, embs[lvl], dims[lvl],
                      lvl in attn_levels)
            skips.append(h)
        if lvl != nres - 1:
            h = resnet(f"down_{lvl}_downsample", h, embs[lvl], dims[lvl],
                       "down")
            skips.append(h)
    h = block("middle", h, embs[-1], dims[-1], nres in attn_levels)
    for lvl in reversed(range(nres)):
        for i in range(nblk + 1):
            h = jnp.concatenate([h, skips.pop()], axis=-1)
            h = block(f"up_{lvl}_{i}", h, embs[lvl], dims[lvl],
                      lvl in attn_levels)
        if lvl != 0:
            h = resnet(f"up_{lvl}_upsample", h, embs[lvl], dims[lvl], "up")
    assert not skips
    h = silu(gn("last_gn", h))
    h = cv("last_conv", h, 3, kind="conv_zero")
    return h[:, 1]


def param_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """``{path: (shape, kind)}`` of every parameter, in forward order."""
    H, W = cfg["H"], cfg["W"]
    rec = _Params(None)
    batch = {"x": jnp.zeros((1, H, W, 3)), "z": jnp.zeros((1, H, W, 3)),
             "logsnr": jnp.zeros((1, 2)),
             "R": jnp.broadcast_to(jnp.eye(3), (1, 2, 3, 3)),
             "t": jnp.zeros((1, 2, 3)),
             "K": jnp.broadcast_to(jnp.eye(3), (1, 3, 3))}
    jax.eval_shape(lambda: forward(None, batch, jnp.ones((1,), bool), cfg,
                                   _p=rec))
    return dict(rec.shapes)


def make_params(cfg: dict, key, *, zero_gain: float = 0.3
                ) -> Callable[[], Dict[str, jnp.ndarray]]:
    """Seeded float32 parameters for every leaf, made in one jitted call
    on the default device.  A trained model's values are not public, so:
    kernels are N(0, 1/fan_in) (variance preserving), the layers the
    architecture initialises to zero (second conv of a residual block,
    attention output conv, last conv) are N(0, zero_gain^2/fan_in) so that
    every layer's output and every leaf's gradient matter, norm scales
    are 1 + N(0, 0.1^2), biases N(0, 0.1^2), embeddings N(0, 1/144)."""
    shapes = param_shapes(cfg)
    sizes = {name: int(np.prod(shape)) for name, (shape, _) in shapes.items()}

    def place(n, shape, kind):
        if kind in ("conv", "dense", "conv_zero"):
            fan_in = int(np.prod(shape[:-1]))
            gain = zero_gain if kind == "conv_zero" else 1.0
            return n * (gain / math.sqrt(fan_in))
        if kind == "scale":
            return 1.0 + 0.1 * n
        if kind == "bias":
            return 0.1 * n
        if kind == "emb":
            return n / math.sqrt(POSE_CH)
        raise ValueError(kind)

    @jax.jit
    def build(k):
        # one draw for all leaves: a few hundred separate generators
        # take minutes to compile for the chip
        flat = jax.random.normal(k, (sum(sizes.values()),), jnp.float32)
        out, at = {}, 0
        for name, (shape, kind) in shapes.items():
            out[name] = place(flat[at:at + sizes[name]].reshape(shape),
                              shape, kind)
            at += sizes[name]
        return out

    return lambda: build(key)


def nest(flat: Dict[str, jnp.ndarray]) -> dict:
    """``{"a/b": x}`` -> ``{"a": {"b": x}}``: the tree the program reads."""
    out: dict = {}
    for path, v in flat.items():
        node = out
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = v
    return out


def flatten(tree: dict, prefix: str = "") -> Dict[str, jnp.ndarray]:
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, p))
        else:
            out[p] = v
    return out
