"""The single compiled train step.

Replaces the reference hot loop (``/root/reference/train.py:264-293``):
loss -> backward -> Adam -> (checkpoint cadence) with per-step
``dist.barrier()``s and host-side RNG.  Here the entire step — logsnr draw,
q_sample, CFG dropout, forward, grad, all-reduce, Adam update, EMA — is ONE
jitted function over global arrays sharded by the mesh layer.  XLA inserts
the gradient collectives (the DDP all-reduce equivalent) from the sharding
specs; donation reuses the old state's buffers.
"""

from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import optax

from diff3d_tpu.config import Config
from diff3d_tpu.data.images import dequantize
from diff3d_tpu.diffusion import p_losses
from diff3d_tpu.parallel import MeshEnv
from diff3d_tpu.train.state import (TrainState, ema_decay_per_step,
                                    make_optimizer, warmup_schedule)
from diff3d_tpu.utils.profiling import scope, span

TrainStepFn = Callable[[TrainState, Dict[str, jnp.ndarray], jax.Array],
                       Tuple[TrainState, Dict[str, jnp.ndarray]]]


def make_train_step(model, cfg: Config, env: MeshEnv | None = None,
                    donate: bool = True) -> TrainStepFn:
    """Build ``(state, batch, rng) -> (state, metrics)``, jit-compiled with
    explicit shardings when a mesh is given.

    ``batch``: ``imgs [B,2,H,W,3]``, ``R [B,2,3,3]``, ``T [B,2,3]``,
    ``K [B,3,3]`` — global shapes, batch axis sharded over the data axis.
    ``rng`` is folded with the step counter so every step draws fresh
    noise/logsnr/CFG masks deterministically from one seed (the reference
    uses unseeded host RNG, ``train.py:272``).
    """
    tx = make_optimizer(cfg.train)
    sched = warmup_schedule(cfg.train)
    ema_decay = ema_decay_per_step(cfg.train)
    dcfg = cfg.diffusion

    accum = max(1, cfg.train.accum_steps)
    # GSPMD context parallelism: constrain activations' spatial axis onto
    # the model axis so XLA compiles conv halo exchanges / GN reductions /
    # attention KV gathers (MeshConfig.context_parallel).
    constrain = (env.activation_constraint()
                 if env is not None and cfg.mesh.context_parallel else None)

    def loss_and_grad(params, batch, rng):
        with scope("loss"):
            rng, k_drop = jax.random.split(rng)

        def loss_fn(params):
            def denoise(model_batch, cond_mask):
                return model.apply({"params": params}, model_batch,
                                   cond_mask=cond_mask, deterministic=False,
                                   rngs={"dropout": k_drop},
                                   constrain=constrain)
            # Loader batches arrive as uint8 (data/images.py); the cast
            # to [-1, 1] f32 happens here on device, fused by XLA.
            with scope("loss"):
                imgs = dequantize(batch["imgs"])
            return p_losses(
                denoise, imgs, batch["R"], batch["T"],
                batch["K"], rng, cond_prob=dcfg.cond_prob,
                loss_type=dcfg.loss_type, logsnr_min=dcfg.logsnr_min,
                logsnr_max=dcfg.logsnr_max)

        return jax.value_and_grad(loss_fn)(params)

    def step_fn(state: TrainState, batch: Dict[str, jnp.ndarray],
                rng: jax.Array) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        with scope("loss"):
            rng = jax.random.fold_in(rng, state.step)

        if accum == 1:
            loss, grads = loss_and_grad(state.params, batch, rng)
        else:
            # Scan over `accum` microbatches; only one microbatch's
            # activations are live at a time, grads averaged.  The scope
            # is the loop's and the accumulation's; every op of the loss
            # and the model inside it carries its own, inner tag.
            with scope("grad_accum"):
                micro = jax.tree.map(
                    lambda x: x.reshape(accum, x.shape[0] // accum,
                                        *x.shape[1:]), batch)

                def body(carry, inp):
                    i, mb = inp
                    l, g = loss_and_grad(state.params, mb,
                                         jax.random.fold_in(rng, i))
                    loss_acc, grads_acc = carry
                    return (loss_acc + l,
                            jax.tree.map(jnp.add, grads_acc, g)), None

                init = (jnp.zeros(()),
                        jax.tree.map(jnp.zeros_like, state.params))
                (loss, grads), _ = jax.lax.scan(
                    body, init, (jnp.arange(accum), micro))
                loss = loss / accum
                grads = jax.tree.map(lambda g: g / accum, grads)
        with scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
        with scope("ema"):
            ema_params = jax.tree.map(
                lambda e, p: ema_decay * e + (1.0 - ema_decay) * p,
                state.ema_params, params)
        with scope("optimizer"):
            new_state = TrainState(step=state.step + 1, params=params,
                                   opt_state=opt_state,
                                   ema_params=ema_params)
        with scope("metrics"):
            metrics = {
                "loss": loss,
                "lr": sched(state.step),
                "grad_norm": optax.global_norm(grads),
            }
        return new_state, metrics

    if env is None:
        return jax.jit(step_fn, donate_argnums=(0,) if donate else ())

    batch_sh = env.batch()
    rep = env.replicated()

    jitted = None  # built on first call (shardings come from the pytrees)

    def _jitted(state, batch):
        nonlocal jitted
        if jitted is None:
            st_sh = env.state_shardings(state)
            batch_shardings = jax.tree.map(lambda _: batch_sh, batch)
            jitted = jax.jit(
                step_fn,
                in_shardings=(st_sh, batch_shardings, rep),
                out_shardings=(st_sh, rep),
                donate_argnums=(0,) if donate else ())
        return jitted

    calls = 0

    def sharded_step(state, batch, rng):
        # the host's cost of handing the state's leaves to the compiled
        # step: an enqueue, timed as one on purpose
        nonlocal calls
        calls += 1
        with span("train.dispatch", id=calls):
            return _jitted(state, batch)(state, batch, rng)

    # The sharded path jits lazily inside this closure; expose the same
    # ``.lower`` the env=None jit has so analysis tooling (shardcheck)
    # can lower the REAL sharded program on abstract args
    # (ShapeDtypeStructs work — the sharding pytrees only map leaves).
    sharded_step.lower = (
        lambda state, batch, rng: _jitted(state, batch).lower(
            state, batch, rng))
    return sharded_step
