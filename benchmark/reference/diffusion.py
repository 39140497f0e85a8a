"""Plain reference of what the timed paths compute around the X-UNet: the
epsilon-prediction loss with guidance dropout, its gradient through
accumulation, Adam with linear warm-up, and the ancestral / DDIM reverse
process with classifier-free guidance and stochastic conditioning (Watson
et al. 2022, sections 2-3; Ho & Salimans 2021 for the guidance form).

Imports nothing of ``diff3d_tpu``.  The random draws follow the program's
documented key stream (which key is split into what), because the same
seed has to give the same noise, masks and conditioning indices on both
sides; the arithmetic is written from the equations.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import xunet

ADAM_EPS = 1e-8


def logsnr_cosine(t, lo: float, hi: float):
    b = math.atan(math.exp(-0.5 * hi))
    a = math.atan(math.exp(-0.5 * lo)) - b
    return -2.0 * jnp.log(jnp.tan(a * t + b))


def alpha_sigma(logsnr):
    return jnp.sqrt(jax.nn.sigmoid(logsnr)), jnp.sqrt(jax.nn.sigmoid(-logsnr))


# --------------------------------------------------------------- training

def microbatch_key(base_key, step: int, micro: int, accum: int):
    """Key of microbatch ``micro`` of optimizer step ``step``."""
    k = jax.random.fold_in(base_key, step)
    return jax.random.fold_in(k, micro) if accum > 1 else k


def block_loss(params, mb: dict, key, start, size: int, mcfg: dict,
               dcfg: dict, prec: str, half_batch=False):
    """This block's share of the microbatch loss: squared error summed over
    rows ``[start, start + size)`` over the microbatch's element count.

    ``mb``: ``imgs [B,2,H,W,3]`` uint8 (or float in [-1, 1]), ``R, T, K``.
    ``half_batch`` (may be traced) plants a fault: the second half of the
    microbatch is left out and the mean taken over the rest.
    """
    imgs = mb["imgs"]
    if imgs.dtype == jnp.uint8:
        imgs = imgs.astype(jnp.float32) / 127.5 - 1.0
    B = imgs.shape[0]
    x, z = imgs[:, 0], imgs[:, 1]
    key, k_drop = jax.random.split(key)
    k_t, k_noise, k_mask, k_xn = jax.random.split(key, 4)
    lo, hi = dcfg["logsnr_min"], dcfg["logsnr_max"]
    logsnr = logsnr_cosine(jax.random.uniform(k_t, (B,)), lo, hi)
    noise = jax.random.normal(k_noise, z.shape, jnp.float32)
    alpha, sigma = alpha_sigma(logsnr)
    z_noisy = (alpha[:, None, None, None] * z
               + sigma[:, None, None, None] * noise)
    cond_mask = jax.random.uniform(k_mask, (B,)) > dcfg["cond_prob"]
    x_cond = jnp.where(cond_mask[:, None, None, None], x,
                       jax.random.normal(k_xn, x.shape, jnp.float32))

    def rows(a):
        return jax.lax.dynamic_slice_in_dim(a, start, size)

    batch = {"x": rows(x_cond), "z": rows(z_noisy),
             "logsnr": jnp.stack([jnp.full((size,), hi, jnp.float32),
                                  rows(logsnr)], axis=1),
             "R": rows(mb["R"]), "t": rows(mb["T"]), "K": rows(mb["K"])}
    eps = xunet.forward(params, batch, rows(cond_mask), mcfg, prec=prec,
                        drop_key=k_drop, rows=(start, size), full_rows=B)
    err = jnp.square(rows(noise) - eps)
    keep = jnp.logical_or(jnp.logical_not(half_batch),
                          (start + jnp.arange(size)) < (B // 2))
    count = jnp.where(half_batch, B // 2, B)
    err = err * keep[:, None, None, None]
    return err.sum() / (count * math.prod(err.shape[1:]))


def lr_at(step: int, tcfg: dict) -> float:
    warm = max(1, tcfg["warmup_examples"] // tcfg["global_batch"])
    return tcfg["lr"] * min(max((step + 1.0) / warm, 0.0), 1.0)


def adam_update(params, mu, nu, grads, step: int, tcfg: dict) -> None:
    """One Adam step (Kingma & Ba), bias-corrected, lr from the warm-up,
    in float32 NumPy on the host and in place: the moments of a 482 M
    parameter model do not fit on the chip beside the gradient pass."""
    b1, b2 = (np.float32(b) for b in tcfg["betas"])
    t = step + 1
    c1 = np.float32(1.0 - float(b1) ** t)
    c2 = np.float32(1.0 - float(b2) ** t)
    lr = np.float32(lr_at(step, tcfg))
    one = np.float32(1.0)
    for k, p in params.items():
        g, m, v = grads[k], mu[k], nu[k]
        m *= b1
        m += (one - b1) * g
        v *= b2
        v += (one - b2) * (g * g)
        p -= lr * (m / c1) / (np.sqrt(v / c2) + np.float32(ADAM_EPS))


class TrainReference:
    """Follows the first optimizer steps of a training cell in float32
    (or, as the control, a lower precision: by name, or with
    ``prec="bits"`` the mantissa bits given to ``run``, so that one
    compiled pass serves both), block of rows by block of
    rows so that it fits beside nothing else on the chip."""

    def __init__(self, mcfg: dict, dcfg: dict, tcfg: dict, *,
                 block: int, prec: str = "float32"):
        self.mcfg, self.dcfg, self.tcfg = mcfg, dcfg, tcfg
        self.accum = max(1, tcfg["accum_steps"])
        self.micro = tcfg["global_batch"] // self.accum
        if self.micro % block:
            raise ValueError(f"block {block} must divide {self.micro}")
        self.block = block

        def grad_block(params, mb, key, start, half, loss_acc, grad_acc,
                       bits):
            loss, g = jax.value_and_grad(block_loss)(
                params, mb, key, start, block, mcfg, dcfg,
                bits if prec == "bits" else prec, half)
            return loss_acc + loss, jax.tree.map(jnp.add, grad_acc, g)

        self._grad_block = jax.jit(grad_block, donate_argnums=(5, 6))

    def run(self, make_params, batches, base_key,
            half_batch: bool = False, bits: int = 23) -> dict:
        """``make_params()`` gives the starting parameters (flat, float32;
        called once, so that no second copy outlives the first update);
        ``batches``: the global batches of the first steps, as fed.
        Returns the losses, the first step's gradient and the parameters
        after the last step (all float32, flat by path).  On the device
        are only the parameters, the gradient being summed and one
        block's pass; Adam's moments stay on the host."""
        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        params = make_params()
        host = {k: np.array(v, np.float32) for k, v in params.items()}
        mu = {k: np.zeros_like(v) for k, v in host.items()}
        nu = {k: np.zeros_like(v) for k, v in host.items()}
        losses, first_grad = [], None
        scale = np.float32(1.0 / self.accum)
        for step, batch in enumerate(batches):
            loss = jnp.zeros((), jnp.float32)
            grads = zeros(params)
            for i in range(self.accum):
                mb = {k: jnp.asarray(v[i * self.micro:(i + 1) * self.micro])
                      for k, v in batch.items()}
                key = microbatch_key(base_key, step, i, self.accum)
                for start in range(0, self.micro, self.block):
                    loss, grads = self._grad_block(
                        params, mb, key, jnp.int32(start),
                        jnp.bool_(half_batch), loss, grads,
                        jnp.int32(bits))
            losses.append(float(loss) * float(scale))
            g = {k: np.asarray(v) * scale for k, v in grads.items()}
            del grads, params
            if first_grad is None:
                first_grad = {k: v.copy() for k, v in g.items()}
            adam_update(host, mu, nu, g, step, self.tcfg)
            params = {k: jnp.asarray(v) for k, v in host.items()}
        return {"losses": losses, "first_grad": first_grad, "params": host}


# --------------------------------------------------------------- sampling

def guided_eps(eps_cond, eps_uncond, w):
    w = w[:, None, None, None]
    return (1.0 + w) * eps_cond - w * eps_uncond


def reverse_step(eps, z, logsnr, logsnr_next, noise, kind: str,
                 clip_x0: bool):
    """One reverse step in logSNR form (Kingma et al. 2021, eq. 33-34 for
    the ancestral posterior; Song et al. 2021 for DDIM, eta = 0)."""
    alpha, sigma = alpha_sigma(logsnr)
    alpha_n, sigma_n = alpha_sigma(logsnr_next)
    x0 = (z - sigma * eps) / alpha
    if clip_x0:
        x0 = jnp.clip(x0, -1.0, 1.0)
    if kind == "ddim":
        if clip_x0:
            eps = (z - alpha * x0) / sigma
        return alpha_n * x0 + sigma_n * eps
    c = -jnp.expm1(logsnr - logsnr_next)
    mean = alpha_n * (z * (1.0 - c) / alpha + c * x0)
    var = jax.nn.sigmoid(-logsnr_next) * c
    return jnp.where(logsnr_next == 0.0, mean, mean + jnp.sqrt(var) * noise)


def synthesize_view(params, record_imgs, record_R, record_T, record_len,
                    K, key, mcfg: dict, dcfg: dict, *, steps: int,
                    kind: str = "ancestral", prec: str = "float32"):
    """One novel view of one object for every guidance weight.

    ``record_imgs [N, B, H, W, 3]`` holds the views so far (entry b made
    with weight b), ``record_R/T`` the poses of all views, entry
    ``record_len`` being the target's.  At every step the conditioning
    view is drawn uniformly from the first ``record_len`` entries.
    ``key`` is the object's key before this view.  Returns the
    ``[B, H, W, 3]`` view and the object's next key.
    """
    w = jnp.asarray(dcfg["guidance_weights"], jnp.float32)
    B = w.shape[0]
    H, W = mcfg["H"], mcfg["W"]
    lo, hi = dcfg["logsnr_min"], dcfg["logsnr_max"]
    T = dcfg["timesteps"]
    ts = jnp.linspace(1.0, 0.0, T + 1)[::T // steps]
    logsnrs = logsnr_cosine(ts[:-1], lo, hi)
    logsnr_nexts = logsnr_cosine(ts[1:], lo, hi)

    next_key, k = jax.random.split(key)
    carry_key, k_init, k_idx = jax.random.split(k, 3)
    z0 = jax.random.normal(k_init, (B, H, W, 3))
    idx = jax.random.randint(k_idx, (steps,), 0, record_len)
    tgt_R, tgt_T = record_R[record_len], record_T[record_len]
    mask = jnp.concatenate([jnp.ones((B,), bool), jnp.zeros((B,), bool)])

    def step(carry, xs):
        z, ck = carry
        logsnr, logsnr_next, i = xs
        ck, k_x, k_noise = jax.random.split(ck, 3)
        cond = record_imgs[i]
        x_un = jax.random.normal(k_x, cond.shape, jnp.float32)
        R = jnp.broadcast_to(jnp.stack([record_R[i], tgt_R])[None],
                             (2 * B, 2, 3, 3))
        t = jnp.broadcast_to(jnp.stack([record_T[i], tgt_T])[None],
                             (2 * B, 2, 3))
        batch = {"x": jnp.concatenate([cond, x_un]),
                 "z": jnp.concatenate([z, z]),
                 "logsnr": jnp.stack([jnp.full((2 * B,), hi, jnp.float32),
                                      jnp.full((2 * B,), logsnr)], axis=1),
                 "R": R, "t": t,
                 "K": jnp.broadcast_to(K[None], (2 * B, 3, 3))}
        eps = xunet.forward(params, batch, mask, mcfg, prec=prec)
        eps = guided_eps(eps[:B], eps[B:], w)
        noise = jax.random.normal(k_noise, z.shape, jnp.float32)
        z = reverse_step(eps, z, logsnr, logsnr_next, noise, kind,
                         dcfg["clip_x0"])
        return (z, ck), None

    (z, _), _ = jax.lax.scan(step, (z0, carry_key),
                             (logsnrs, logsnr_nexts, idx))
    return z, next_key


# ------------------------------------------------------------ comparisons

def worst_leaf_gap(program: Dict[str, np.ndarray],
                   reference: Dict[str, np.ndarray],
                   skip=()) -> Tuple[float, str]:
    """Largest, over leaves, of |norm(program) - norm(reference)| over the
    larger of the reference's norm of that leaf and of the median leaf."""
    names = [k for k in reference if k not in skip]
    ref_n = {k: float(np.linalg.norm(reference[k].astype(np.float64)))
             for k in names}
    med = float(np.median(list(ref_n.values())))
    worst, at = 0.0, ""
    for k in names:
        pn = float(np.linalg.norm(np.asarray(program[k], np.float64)))
        gap = abs(pn - ref_n[k]) / max(ref_n[k], med, 1e-30)
        if not np.isfinite(gap):
            gap = float("inf")
        if gap >= worst:
            worst, at = gap, k
    return worst, at


def relative_difference(program: Dict[str, np.ndarray],
                        reference: Dict[str, np.ndarray], skip=()) -> float:
    """Norm of the difference over all leaves, over the reference's
    norm."""
    num = den = 0.0
    for k, r in reference.items():
        if k in skip:
            continue
        r = r.astype(np.float64)
        num += float(np.sum(np.square(np.asarray(program[k], np.float64) - r)))
        den += float(np.sum(np.square(r)))
    return math.sqrt(num / max(den, 1e-300))


def nought_gradient_leaves(first_grad: Dict[str, np.ndarray],
                           share: float = 1e-3):
    """Leaves whose reference gradient norm is under ``share`` of the
    median leaf's: under Adam they move by round-off alone."""
    n = {k: float(np.linalg.norm(g.astype(np.float64)))
         for k, g in first_grad.items()}
    med = float(np.median(list(n.values())))
    return {k for k, v in n.items() if v < share * med}
