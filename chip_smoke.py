#!/usr/bin/env python3
"""Does the program still start on the chip?  Train -> sample -> serve at
srn64's published width, through the entry points a user calls.

    python chip_smoke.py            # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4  # only the cross-chip paths, four chips

One process, the only one that touches JAX (a chip belongs to one process).
It exits non-zero, and prints no result line, unless
``jax.devices()[0].platform == "tpu"``: it never sets ``JAX_PLATFORMS`` and
never falls back to the CPU.  Weights are random (made from a seed), depth
and width are srn64's own (``ch=128``, 136.67 M parameters).

Each phase prints one JSON line with its wall seconds split into compile
(trace + lower + backend compile or cache read, from JAX's own monitoring
events) and run (the rest); any failed check ends the run non-zero.  The
last line of stdout is the device as JAX reports it::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

The numbers are smoke timings, not benchmark results.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
#: Scratch for what the phases write (the train workdir holds two 2.2 GB
#: checkpoints): inside the checkout, git-ignored, removed when done.
OUT_DIR = os.path.join(HERE, "_smoke_out")
#: The phase lines again, small enough to come back from a chip run.
LOG_PATH = os.path.join(HERE, "chiprun_out", "chip_smoke.jsonl")

#: srn64 train batch, no accumulation, no remat (``train_cli --config
#: srn64``'s own defaults otherwise).  Fixed from the chip compiler's
#: ``memory_analysis()`` of the whole train step for one v5e (16 GB):
#: see CHANGES.md, PR 21 — no fallback ladder.
TRAIN_BATCH = 24
PARAMS_M = 136.67        # srn64_config(): ch=128, emb_ch=1024, 3 blocks
TRAIN_STEPS = 4
CKPT_EVERY = 2          # fires once (step 2) before the final save (step 4)
SEED = 0

#: Kernel-vs-XLA tolerances in the kernels phase, bf16 in and out.  The
#: fused GroupNorm kernel keeps the whole chain in f32 and rounds once;
#: the XLA composition rounds to bf16 after the affine and again after
#: FiLM and SiLU.  Outputs reach |y| < 8, where one bf16 ulp is 2^-5
#: (0.031): four ulps bound three roundings against one.  (The same
#: inputs through the interpreter on CPU differ by two ulps, 2^-4.)
#: Flash attention's outputs are convex combinations of unit-scale
#: values (|o| < 4, ulp 2^-6), one bf16 rounding on each side plus the
#: reference's bf16 P*V matmul: two ulps.
GN_ATOL = 2.0 ** -3
ATTN_ATOL = 2.0 ** -5


def emit(obj: dict) -> None:
    line = json.dumps(obj)
    print(line, flush=True)
    with open(LOG_PATH, "a") as f:
        f.write(line + "\n")


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@contextlib.contextmanager
def phase(name: str, clock):
    """Time one phase, print its JSON line, and end the run on failure."""
    details: dict = {}
    before, t0 = clock.snapshot(), time.perf_counter()
    try:
        yield details
    except BaseException as e:
        emit({"phase": name, "ok": False,
              "error": f"{type(e).__name__}: {e}"[:2000]})
        raise SystemExit(1)
    wall = time.perf_counter() - t0
    after = clock.snapshot()
    d = {k: after[k] - before[k] for k in after}
    compile_s = d["trace_s"] + d["lower_s"] + d["backend_compile_s"]
    emit({"phase": name, "ok": True, "wall_s": round(wall, 2),
          "compile_s": round(compile_s, 2),
          "run_s": round(max(wall - compile_s, 0.0), 2),
          "backend_compile_s": round(d["backend_compile_s"], 2),
          "backend_compiles": d["backend_compiles"],
          "cache_hits": d["cache_hits"], **details})


def fresh_dir(name: str) -> str:
    path = os.path.join(OUT_DIR, name)
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path)
    return path


def synthetic_views(obj: int, n_views: int, seed: int = SEED) -> dict:
    from diff3d_tpu.data import SyntheticDataset

    ds = SyntheticDataset(num_objects=obj + 1, num_views=n_views,
                          imgsize=64, seed=seed)
    return ds.all_views(obj)


def train_argv(batch: int, *extra: str) -> list:
    """``train_cli`` arguments of the smoke's srn64 run."""
    return ["--config", "srn64", "--synthetic", "--batch", str(batch),
            "--steps", str(TRAIN_STEPS), "--ckpt_every", str(CKPT_EVERY),
            *extra]


def train_cfg(argv: list):
    """The Config ``train_cli`` builds from ``argv`` — needed again to
    resume from its workdir."""
    from diff3d_tpu.cli import train_cli

    return train_cli.build_config(train_cli.build_parser().parse_args(argv))


# --------------------------------------------------------------------------
# one chip: train -> sample -> serve -> kernels -> decoder
# --------------------------------------------------------------------------


def phase_train(clock) -> object:
    """``train_cli.main`` for a few srn64 steps, then resume from what it
    saved.  Returns the restored EMA params for the sample phase."""
    import jax
    import numpy as np

    from diff3d_tpu.cli import train_cli
    from diff3d_tpu.parallel import make_mesh
    from diff3d_tpu.train import Trainer

    workdir = fresh_dir("train")
    with phase("train", clock) as out:
        argv = train_argv(TRAIN_BATCH, "--workdir", workdir)
        train_cli.main(argv)
        cfg = train_cfg(argv)
        with open(os.path.join(workdir, "metrics.jsonl")) as f:
            recs = [json.loads(line) for line in f if line.strip()]
        # train_cli has no flag for the log cadence (srn64: every 50
        # steps and the last), so "every step it logs" is what a user
        # running this command gets.
        every = cfg.train.log_every
        want = [s for s in range(1, TRAIN_STEPS + 1)
                if s % every == 0 or s == TRAIN_STEPS]
        check([r["step"] for r in recs] == want,
              f"metrics.jsonl steps {[r['step'] for r in recs]} != {want}")
        check(all(np.isfinite(r["loss"]) and np.isfinite(r["grad_norm"])
                  for r in recs), f"non-finite loss/grad_norm: {recs}")
        ckpt_root = os.path.join(workdir, cfg.train.checkpoint_dir)
        check(os.path.isdir(os.path.join(ckpt_root, str(TRAIN_STEPS))),
              f"no checkpoint for step {TRAIN_STEPS} under {ckpt_root}: "
              f"{os.listdir(ckpt_root) if os.path.isdir(ckpt_root) else None}")
        resumed = Trainer(cfg, env=make_mesh(cfg.mesh), workdir=workdir,
                          transfer=True)
        check(int(resumed.state.step) == TRAIN_STEPS,
              f"resumed at step {int(resumed.state.step)}, "
              f"saved {TRAIN_STEPS}")
        params = resumed.state.ema_params
        n_params = sum(int(np.prod(p.shape))
                       for p in jax.tree.leaves(params))
        check(abs(n_params / 1e6 - PARAMS_M) < 0.01,
              f"{n_params / 1e6:.2f}M params, srn64 has {PARAMS_M}M")
        out.update(config="srn64", ch=cfg.model.ch,
                   params_m=round(n_params / 1e6, 2), batch=TRAIN_BATCH,
                   steps=TRAIN_STEPS, losses=[r["loss"] for r in recs],
                   checkpoints=sorted(os.listdir(ckpt_root)),
                   resumed_step=int(resumed.state.step))
        del resumed
    shutil.rmtree(workdir, ignore_errors=True)
    return params


def phase_sample(clock, params) -> None:
    """One novel view of a synthetic object through ``Sampler.synthesize``
    (what ``sample_cli`` calls): 256 ancestral steps, 8 guidance weights."""
    import jax
    import numpy as np

    from diff3d_tpu.config import srn64_config
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling import Sampler

    with phase("sample", clock) as out:
        cfg = srn64_config()
        sampler = Sampler(build_model(cfg), params, cfg)
        views = synthetic_views(obj=0, n_views=2)
        imgs = sampler.synthesize(views, jax.random.PRNGKey(SEED),
                                  max_views=2)
        n_w = len(cfg.diffusion.guidance_weights)
        check(imgs.shape == (1, n_w, 64, 64, 3), f"shape {imgs.shape}")
        check(np.isfinite(imgs).all(), "non-finite pixels")
        stds = imgs[0].reshape(n_w, -1).std(axis=1)
        check((stds > 1e-3).all(), f"constant image(s): std {stds}")
        out.update(steps=sampler.steps, sampler=sampler.sampler_kind,
                   guidance_weights=n_w, shape=list(imgs.shape),
                   min_std=float(stds.min()))


def _http(method: str, url: str, payload: dict | None = None):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as resp:
        return resp.status, json.loads(resp.read())


def phase_serve(clock) -> None:
    """``serve_cli.build_service`` + the HTTP front door, three requests
    from a thread of this process, then /healthz and /metrics."""
    import numpy as np

    from diff3d_tpu.analysis import RecompilationSentinel
    from diff3d_tpu.cli import serve_cli

    with phase("serve", clock) as out:
        args = serve_cli.build_parser().parse_args(
            ["--config", "srn64", "--init", "random", "--warmup",
             "--port", "0"])
        service = serve_cli.build_service(args)     # compiles in --warmup
        warm = clock.snapshot()
        sentinel = RecompilationSentinel()
        for key, s in service.engine.samplers.items():
            sentinel.track(f"view_step{key}", s._run_view_many)
        service.start(serve_http=True)
        try:
            base = f"http://127.0.0.1:{service.port}"
            # n_views per request.  A request's program is keyed by its
            # record capacity (record_capacity(n_views): 2, 4, 8, 16) and
            # --warmup compiles only the max_views one (16), so requests
            # that must not compile ask for 9..16 views; each grows its
            # autoregressive record eight times inside the engine.
            asked = [9, 9, 9]
            replies: list = []

            def client():
                for i, n_views in enumerate(asked):
                    v = synthetic_views(obj=i, n_views=n_views)
                    replies.append(_http("POST", base + "/synthesize", {
                        "views": {k: np.asarray(a).tolist()
                                  for k, a in v.items()},
                        "seed": i, "n_views": n_views}))

            t = threading.Thread(target=client, name="smoke-client")
            t.start()
            t.join()
            check(len(replies) == len(asked), f"{len(replies)} replies")
            for n_views, (status, body) in zip(asked, replies):
                check(status == 200, f"HTTP {status}: {body}")
                got = np.asarray(body["views"], np.float32)
                check(got.shape[0] == n_views - 1
                      and got.shape[-3:] == (64, 64, 3),
                      f"n_views={n_views}: shape {got.shape}")
                check(np.isfinite(got).all(), "non-finite pixels served")
            h_status, health = _http("GET", base + "/healthz")
            m_status, metrics = _http("GET", base + "/metrics?format=json")
            check(h_status == 200 and health["status"] == "ok",
                  f"health {h_status} {health}")
            views_done = metrics["counters"]["serving_views_completed_total"]
            novel = sum(n - 1 for n in asked)
            check(m_status == 200 and views_done == novel,
                  f"views completed {views_done} != asked {novel}")
            after = clock.snapshot()
            compiles_after_warmup = (after["backend_compiles"]
                                     - warm["backend_compiles"])
            check(sentinel.total() == 0,
                  f"view-step programs compiled after warm-up: "
                  f"{sentinel.counts()}")
            check(compiles_after_warmup == 0,
                  f"{compiles_after_warmup} backend compiles after "
                  "warm-up")
        finally:
            service.stop()
        out.update(
            requests=len(asked), views_completed=views_done,
            health=health["status"],
            compiles_after_warmup=compiles_after_warmup,
            view_step_p50_s=metrics["histograms"][
                "serving_view_step_seconds"].get("p50"))


def phase_kernels(clock) -> None:
    """Each Pallas family once, compiled (never interpreted), at one real
    srn64 site, against the XLA composition of the same chain."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from diff3d_tpu.ops import dispatch
    from diff3d_tpu.ops.pallas_attention import flash_attention
    from diff3d_tpu.ops.pallas_film import fused_groupnorm, xla_groupnorm

    with phase("kernels", clock) as out:
        check(dispatch.interpret_default() is False,
              "Pallas would run in interpret mode on this process")
        rs = np.random.RandomState(SEED)

        def rnd(*shape):
            return jnp.asarray(rs.randn(*shape), jnp.bfloat16)

        def compiled_call(fn, *a):
            jitted = jax.jit(fn)
            check("tpu_custom_call" in jitted.lower(*a).as_text(),
                  "no tpu_custom_call in the lowered kernel program")
            return np.asarray(jax.block_until_ready(jitted(*a)),
                              np.float32)

        # srn64 level-0 ResnetBlock under the sampler's 16-image forward:
        # [N = 16 x 2 frames, L = 64*64, C = 128], bf16, 32 groups.
        N, L, C, G = 32, 4096, 128, 32
        x, scale, shift = rnd(N, L, C), 0.1 * rnd(N, L, C), rnd(N, L, C)
        gamma = jnp.asarray(1 + 0.1 * rs.randn(C), jnp.float32)
        beta = jnp.asarray(0.1 * rs.randn(C), jnp.float32)
        errs = {}
        for name, kw in (("gn_silu", dict(silu=True)),
                         ("gn_film", dict(scale=scale, shift=shift))):
            got = compiled_call(
                lambda x, g, b, kw=kw: fused_groupnorm(
                    x, g, b, num_groups=G, **kw), x, gamma, beta)
            ref = np.asarray(xla_groupnorm(x, gamma, beta, num_groups=G,
                                           **kw), np.float32)
            errs[name] = float(np.abs(got - ref).max())
            check(np.isfinite(got).all() and errs[name] <= GN_ATOL,
                  f"{name}: max |pallas - xla| = {errs[name]} > {GN_ATOL}")
        # srn64 level-2 attention: [B = 32, L = 16*16, heads 4, D = 64].
        q, k, v = rnd(32, 256, 4, 64), rnd(32, 256, 4, 64), rnd(32, 256, 4, 64)
        got = compiled_call(flash_attention, q, k, v)
        ref = np.asarray(jax.nn.dot_product_attention(q, k, v), np.float32)
        errs["flash_attention"] = float(np.abs(got - ref).max())
        check(np.isfinite(got).all()
              and errs["flash_attention"] <= ATTN_ATOL,
              f"flash_attention: max err {errs['flash_attention']} > "
              f"{ATTN_ATOL}")
        # What each op resolves to on this process at those sites.
        resolved = {
            "groupnorm[auto]": dispatch.resolve(
                "groupnorm", "auto", x, num_groups=G).name,
            "groupnorm[config default 'xla']": dispatch.resolve(
                "groupnorm", "xla", x, num_groups=G).name,
            "sdpa[config default 'auto']": dispatch.resolve(
                "sdpa", "auto", q, k, v).name,
        }
        out.update(interpret=False, max_abs_err=errs,
                   tolerances={"groupnorm": GN_ATOL,
                               "attention": ATTN_ATOL},
                   resolved=resolved)


def phase_decoder(clock) -> None:
    from diff3d_tpu import native

    with phase("decoder", clock) as out:
        ok = native.available()
        out.update(native_available=ok,
                   png_path="native libd3dnative.so" if ok else "PIL")


# --------------------------------------------------------------------------
# --chips 4: only what exists across chips, and what it is compared with
# --------------------------------------------------------------------------

#: Sharded vs one-device losses: same seed, same global batch, bf16
#: compute.  The only difference is the order of the cross-device
#: gradient/loss reductions and XLA's per-shard tiling of the bf16 convs.
#: Measured on four v5e chips: 1.2e-7 relative (PR 21); the bound leaves
#: room for another compiler's tiling, not for a wrong gradient.
FSDP_LOSS_RTOL = 1e-3
#: Sharded vs one-at-a-time sampler: 256 ancestral steps in bf16 would
#: amplify any tiling difference between a 4-object and a 1-object batch;
#: images are in [-1, 1].  Measured on four v5e chips: bit-identical
#: (PR 21).  The bound is on the mean absolute difference.
SAMPLER_MEAN_ATOL = 1e-2
FOUR_BATCH = TRAIN_BATCH  # global batch of the fsdp comparison (6 / chip)
FOUR_STEPS = 3


def _bytes_in_use(devices) -> list:
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def phase_fsdp_train(clock, devices) -> None:
    import dataclasses

    import jax
    import numpy as np

    from diff3d_tpu.data import InfiniteLoader, SyntheticDataset
    from diff3d_tpu.models import build_model
    from diff3d_tpu.parallel import make_mesh
    from diff3d_tpu.train import create_train_state, make_train_step
    from diff3d_tpu.train.trainer import init_params

    with phase("fsdp_train_x4", clock) as out:
        cfg = train_cfg(train_argv(FOUR_BATCH, "--param_sharding", "fsdp"))
        model = build_model(cfg)
        rng = jax.random.PRNGKey(cfg.train.seed)
        ds = SyntheticDataset(num_objects=64, num_views=32, imgsize=64)
        loader = InfiniteLoader(ds, FOUR_BATCH, seed=SEED, num_workers=0)
        batches = [next(loader) for _ in range(FOUR_STEPS)]

        def run(env):
            state = create_train_state(init_params(model, cfg, rng),
                                       cfg.train)
            state = jax.device_put(state, env.state_shardings(state))
            step = make_train_step(model, cfg, env)
            losses, spans = [], None
            for raw in batches:
                batch = jax.device_put(
                    {k: raw[k] for k in ("imgs", "R", "T", "K")},
                    env.batch())
                if spans is None:
                    big = max(jax.tree.leaves(state.params),
                              key=lambda p: p.size)
                    spans = (len(big.sharding.device_set),
                             len(batch["imgs"].sharding.device_set),
                             _bytes_in_use(env.mesh.devices.flat))
                state, metrics = step(state, batch, rng)
                losses.append(float(jax.block_until_ready(
                    metrics["loss"])))
            del state
            return losses, spans

        before = _bytes_in_use(devices)
        four, (p_span, b_span, in_use) = run(make_mesh(cfg.mesh))
        check(p_span == 4 and b_span == 4,
              f"state spans {p_span} devices, batch {b_span}; want 4")
        check(all(a > b for a, b in zip(in_use, before)),
              f"bytes_in_use did not grow on every device: "
              f"{before} -> {in_use}")
        one_cfg = dataclasses.replace(cfg.mesh, data_parallel=1)
        one, _ = run(make_mesh(one_cfg, devices=devices[:1]))
        check(np.isfinite(four).all() and np.isfinite(one).all(),
              f"non-finite losses {four} {one}")
        np.testing.assert_allclose(four, one, rtol=FSDP_LOSS_RTOL)
        out.update(mesh={"data": 4}, param_sharding="fsdp",
                   global_batch=FOUR_BATCH, losses_x4=four, losses_x1=one,
                   rtol=FSDP_LOSS_RTOL, state_devices=p_span,
                   batch_devices=b_span,
                   bytes_in_use_grew=[a - b for a, b in
                                      zip(in_use, before)])


def phase_sharded_sampler(clock, devices) -> None:
    """``Sampler(mesh=...).step_many`` with four objects on the data axis
    against ``Sampler.step`` on the same objects one at a time."""
    import jax
    import numpy as np

    from diff3d_tpu.config import srn64_config
    from diff3d_tpu.models import build_model
    from diff3d_tpu.parallel import make_mesh
    from diff3d_tpu.sampling import Sampler, record_capacity
    from diff3d_tpu.train.trainer import init_params

    with phase("sharded_sampler_x4", clock) as out:
        cfg = srn64_config()
        model = build_model(cfg)
        params = init_params(model, cfg, jax.random.PRNGKey(SEED))
        n_w = len(cfg.diffusion.guidance_weights)
        cap = record_capacity(2)
        rec = np.zeros((4, cap, n_w, 64, 64, 3), np.float32)
        rec_R = np.zeros((4, cap, 3, 3), np.float32)
        rec_T = np.zeros((4, cap, 3), np.float32)
        Ks = np.zeros((4, 3, 3), np.float32)
        for i in range(4):
            v = synthetic_views(obj=i, n_views=2)
            rec[i, 0] = v["imgs"][0][None]
            rec_R[i, :2], rec_T[i, :2], Ks[i] = v["R"], v["T"], v["K"]
        keys = np.asarray(jax.random.split(jax.random.PRNGKey(SEED), 4))
        ones = np.ones((4,), np.int32)

        before = _bytes_in_use(devices)
        sharded = Sampler(model, params, cfg, mesh=make_mesh(cfg.mesh))
        check(sharded.lane_multiple == 4,
              f"lane multiple {sharded.lane_multiple}")
        got, carry, _, _ = sharded.step_many(rec, rec_R, rec_T, ones, Ks,
                                             keys)
        jax.block_until_ready(got)
        in_use = _bytes_in_use(devices)
        big = max(jax.tree.leaves(sharded.params), key=lambda p: p.size)
        spans = (len(big.sharding.device_set),
                 len(carry.sharding.device_set),
                 len(got.sharding.device_set))
        check(spans == (4, 4, 4),
              f"params/record/output span {spans} devices; want 4 each")
        check(all(a > b for a, b in zip(in_use, before)),
              f"bytes_in_use did not grow on every device: "
              f"{before} -> {in_use}")
        got = np.asarray(got)
        check(got.shape == (4, n_w, 64, 64, 3), f"shape {got.shape}")
        del sharded, carry

        single = Sampler(model, jax.device_put(params, devices[0]), cfg)
        ref = np.stack([
            np.asarray(single.step(rec[i], rec_R[i], rec_T[i], 1, Ks[i],
                                   keys[i])[0]) for i in range(4)])
        check(np.isfinite(got).all() and np.isfinite(ref).all(),
              "non-finite pixels")
        mean_err = float(np.abs(got - ref).mean())
        check(mean_err <= SAMPLER_MEAN_ATOL,
              f"sharded vs one-at-a-time mean |diff| {mean_err} > "
              f"{SAMPLER_MEAN_ATOL}")
        out.update(objects=4, mesh={"data": 4}, span_devices=list(spans),
                   mean_abs_diff=mean_err,
                   max_abs_diff=float(np.abs(got - ref).max()),
                   atol_mean=SAMPLER_MEAN_ATOL)


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=[1, 4], default=1,
                   help="4 runs ONLY the cross-chip phases (fsdp train "
                        "steps and the sharded sampler against their "
                        "one-device comparisons) on four chips")
    args = p.parse_args(argv)

    import logging

    logging.basicConfig(level=logging.INFO, stream=sys.stderr)
    logging.getLogger("absl").setLevel(logging.WARNING)

    from diff3d_tpu.runtime import configure_compile_cache

    cache_dir = configure_compile_cache()

    import jax

    devices = jax.devices()
    dev = {"platform": devices[0].platform,
           "kind": devices[0].device_kind, "count": len(devices)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev}", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    os.makedirs(os.path.dirname(LOG_PATH), exist_ok=True)
    # the program's one compile clock (registered when the package's
    # profiling module is imported, which is before anything compiles)
    from diff3d_tpu.utils.profiling import COMPILE_CLOCK as clock
    emit({"phase": "start", "device": dev, "compile_cache": cache_dir,
          "jax": jax.__version__})

    if args.chips == 4:
        phase_fsdp_train(clock, devices)
        phase_sharded_sampler(clock, devices)
    else:
        params = phase_train(clock)
        phase_sample(clock, params)
        del params
        phase_serve(clock)
        phase_kernels(clock)
        phase_decoder(clock)
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    emit({"ok": True, "device": dev})
    return 0


if __name__ == "__main__":
    sys.exit(main())
