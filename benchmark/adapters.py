"""The one place where the benchmark touches the program: it builds the
system under test (``diff3d_tpu``'s own config, train step, loader and
sampler, as ``train_cli`` / ``eval_cli`` wire them) from the benchmark's
data files, and reads back what the comparison needs.  Traffic, the
reference, FLOP counts and trace reduction are elsewhere and import none
of this.
"""

from __future__ import annotations

from typing import Dict, Iterator, List

import numpy as np

from benchmark.reference import xunet as rx

MODEL_KEYS = ("H", "W", "ch", "ch_mult", "emb_ch", "num_res_blocks",
              "attn_levels", "attn_heads", "dropout", "logsnr_clip")


def build_config(config: dict, train_over: dict | None = None):
    """``diff3d_tpu.config.Config`` from a ``benchmark/configs`` file."""
    from diff3d_tpu.config import (Config, DataConfig, DiffusionConfig,
                                   ModelConfig, TrainConfig)

    m = {k: config[k] for k in MODEL_KEYS}
    m.update(dtype=config["dtype"], remat=config["remat"])
    for k in ("ch_mult", "attn_levels"):
        m[k] = tuple(m[k])
    d = dict(config["diffusion"])
    d["guidance_weights"] = tuple(d["guidance_weights"])
    t = dict(config["train"], **(train_over or {}))
    t["betas"] = tuple(t["betas"])
    cfg = Config(model=ModelConfig(**m), diffusion=DiffusionConfig(**d),
                 train=TrainConfig(**t),
                 data=DataConfig(imgsize=config["H"]))
    cfg.validate()
    return cfg


def model_dict(cfg) -> dict:
    return {k: getattr(cfg.model, k) for k in MODEL_KEYS}


def diffusion_dict(cfg) -> dict:
    d = cfg.diffusion
    return {"logsnr_min": d.logsnr_min, "logsnr_max": d.logsnr_max,
            "cond_prob": d.cond_prob, "timesteps": d.timesteps,
            "guidance_weights": list(d.guidance_weights),
            "clip_x0": d.clip_x0}


def train_dict(cfg) -> dict:
    t = cfg.train
    return {"lr": t.lr, "betas": list(t.betas),
            "warmup_examples": t.warmup_examples,
            "global_batch": t.global_batch, "accum_steps": t.accum_steps}


def check_tree(cfg, flat: Dict[str, object]) -> None:
    """The benchmark's parameter names and shapes against the program's
    own ``init`` (shapes only): a renamed layer is an error here, not a
    silent mismatch."""
    import jax

    from diff3d_tpu.models import XUNet
    from diff3d_tpu.train.trainer import init_params

    theirs = rx.flatten(jax.eval_shape(
        lambda: init_params(XUNet(cfg.model), cfg, jax.random.PRNGKey(0))))
    ours = {k: tuple(v.shape) for k, v in flat.items()}
    if ours != {k: tuple(v.shape) for k, v in theirs.items()}:
        diff = set(ours) ^ set(theirs)
        raise RuntimeError(f"parameter trees differ: {sorted(diff)[:8]}")


class TrainProgram:
    """The compiled train step with its state, as ``train_cli`` builds it
    (``make_mesh`` -> ``make_train_step`` -> ``InfiniteLoader`` ->
    ``prefetch_to_device``), without checkpoints."""

    def __init__(self, cfg, chips: int = 1):
        import jax

        from diff3d_tpu.models import XUNet
        from diff3d_tpu.parallel import make_mesh
        from diff3d_tpu.train.step import make_train_step

        self.cfg = cfg
        self.env = make_mesh(cfg.mesh, devices=jax.devices()[:chips])
        self.model = XUNet(cfg.model)
        self.step_fn = make_train_step(self.model, cfg, self.env)
        self.base_key = jax.random.PRNGKey(cfg.train.seed)
        self.state = None

    def load(self, flat: Dict[str, object]) -> None:
        import jax

        from diff3d_tpu.train.state import create_train_state

        state = create_train_state(rx.nest(flat), self.cfg.train)
        self.state = jax.device_put(state, self.env.state_shardings(state))

    def loader(self, dataset, workers: int) -> Iterator:
        from diff3d_tpu.data import InfiniteLoader, prefetch_to_device

        inner = InfiniteLoader(dataset, self.cfg.train.global_batch,
                               seed=self.cfg.train.seed,
                               num_workers=workers)
        return prefetch_to_device(inner, self.env.batch(),
                                  depth=self.cfg.data.prefetch)

    def step(self, batch) -> dict:
        batch = {k: batch[k] for k in ("imgs", "R", "T", "K")}
        self.state, metrics = self.step_fn(self.state, batch, self.base_key)
        return metrics

    def first_moment(self) -> Dict[str, np.ndarray]:
        """Adam's first moment, flat by path (host copies)."""
        import optax

        for s in self.state.opt_state:
            if isinstance(s, optax.ScaleByAdamState):
                return {k: np.asarray(v) for k, v in rx.flatten(
                    _plain(s.mu)).items()}
        raise RuntimeError("no Adam state in the optimizer state")

    def params(self) -> Dict[str, np.ndarray]:
        return {k: np.asarray(v)
                for k, v in rx.flatten(_plain(self.state.params)).items()}

    def free(self) -> None:
        import jax

        for leaf in jax.tree.leaves(self.state):
            leaf.delete()
        self.state = None


def _plain(tree):
    """FrozenDict or dict -> nested plain dict."""
    return {k: _plain(v) if hasattr(v, "items") else v
            for k, v in tree.items()}


def drive_first_steps(prog: TrainProgram, feed: Iterator, n: int,
                      fault: str | None = None) -> dict:
    """Drive the program through its first ``n`` steps by the window's own
    call and feed, keeping what the comparison reads: the batches as fed
    (host copies), each step's loss, the first gradient as Adam got it
    (first moment after one step over ``1 - b1``) and the parameters
    after step ``n``.  ``fault`` plants one of the faults the tests must
    see: ``"state_unchanged"`` (every step returns the state it was
    given) or ``"loss_altered"`` (a loss altered where it is produced)."""
    import jax

    b1 = prog.cfg.train.betas[0]
    batches: List[dict] = []
    losses: List[float] = []
    first_grad = None
    for i in range(n):
        batch = next(feed)
        batches.append({k: np.asarray(batch[k])
                        for k in ("imgs", "R", "T", "K")})
        if fault == "state_unchanged":
            kept = jax.tree.map(lambda x: x.copy(), prog.state)
            metrics = prog.step(batch)
            prog.state = kept
        else:
            metrics = prog.step(batch)
        loss = float(metrics["loss"])
        losses.append(loss * 1.05 if fault == "loss_altered" else loss)
        if i == 0:
            first_grad = {k: v / (1.0 - b1)
                          for k, v in prog.first_moment().items()}
    return {"batches": batches, "losses": losses, "first_grad": first_grad,
            "params": prog.params()}


class SampleProgram:
    """``Sampler`` as ``eval_cli`` builds it; the timed call is
    ``synthesize_many``."""

    def __init__(self, cfg, flat: Dict[str, object], *, kind: str,
                 steps: int | None):
        from diff3d_tpu.models import XUNet
        from diff3d_tpu.sampling import Sampler

        self.cfg = cfg
        self.sampler = Sampler(XUNet(cfg.model), rx.nest(flat), cfg,
                               sampler_kind=kind, steps=steps)

    def warm(self, views_list, keys, max_views: int) -> None:
        """Run one view step at the call's own shapes (objects, record
        capacity), staged as ``synthesize_many`` stages it, so that the
        call compiles nothing: not the view program, and not the small
        copy and slice programs around it."""
        import jax

        s = self.sampler
        recs = [s._record_init(np.asarray(v["imgs"][0], np.float32),
                               np.asarray(v["R"], np.float32),
                               np.asarray(v["T"], np.float32), max_views)
                for v in views_list]
        staged = [s._put(x, s._obj) for x in (
            np.stack([r[0] for r in recs]), np.stack([r[1] for r in recs]),
            np.stack([r[2] for r in recs]),
            np.full((len(recs),), 1, np.int32),
            np.stack([np.asarray(v["K"], np.float32) for v in views_list]),
            np.stack([np.asarray(k) for k in keys]))]
        _, rec, _, _ = s._run_view_many(s.params, *staged)
        np.asarray(jax.block_until_ready(rec[:len(recs), 1:max_views]))

    def call(self, views_list, keys, max_views: int) -> np.ndarray:
        """``[objects, max_views - 1, weights, H, W, 3]`` synthesised views
        (the call fetches them, so it has ended when it returns)."""
        return np.asarray(self.sampler.synthesize_many(
            views_list, keys, max_views=max_views))

    def free(self) -> None:
        import jax

        for leaf in jax.tree.leaves(self.sampler.params):
            leaf.delete()
