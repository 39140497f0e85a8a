"""Training traffic: optimizer steps of the program's own compiled step
(``make_train_step``), fed by its own loader and device prefetch.

Set-up builds one object, the compiled step with its state, loads the
seeded weights, drives it through its first steps (which compiles, and is
what the reference follows) and hands the same object to the window.  The
window keeps one step queued behind the one that runs, as the trainer's
own loop does between its log lines, and ends at a step boundary.
"""

from __future__ import annotations

import time

import jax
import numpy as np

from benchmark import adapters, flops, traffic
from benchmark.reference import diffusion as rd
from benchmark.reference import xunet as rx


class Driver:
    def __init__(self, *, config, mix, seed, chips, spans):
        self.config, self.mix, self.seed = config, mix, int(seed)
        self.chips, self.spans = chips, spans
        self.fault = None           # tests plant one here
        self._refs = {}
        self._start = None
        self.notes = {}
        self.key_seed = self.seed % (2 ** 31 - 1)
        over = {"accum_steps": mix["accum_steps"], "seed": self.key_seed,
                "max_steps": 10 ** 9, "ckpt_every": 0, "log_every": 0}
        if mix.get("warmup_examples") == "one_batch":
            # lr is at its published 1e-4 from the first step: under the
            # 10M-example warm-up the first updates (1e-9) are below the
            # float32 rounding of the weights and compare nothing
            over["warmup_examples"] = config["train"]["global_batch"]
        self.cfg = adapters.build_config(config, over)
        self.mcfg = {k: config[k] for k in adapters.MODEL_KEYS}
        self.dcfg = adapters.diffusion_dict(self.cfg)
        self.tcfg = adapters.train_dict(self.cfg)
        # () -> the seeded parameters, made anew on the device at each call
        self.weights = rx.make_params(self.mcfg,
                                      jax.random.PRNGKey(self.key_seed))

    # ------------------------------------------------------------- set-up
    def setup(self, reuse=None) -> None:
        """``reuse``: a driver of the same cell whose compiled step and
        references this one takes over (calibration reads many seeds in
        one process)."""
        span = self.spans.span
        with span("setup.build"):
            if reuse is not None:
                self.prog, self._refs = reuse.prog, reuse._refs
                self.prog.base_key = jax.random.PRNGKey(self.key_seed)
                self.prog.cfg = self.cfg
            else:
                self.prog = adapters.TrainProgram(self.cfg, self.chips)
        with span("setup.weights"):
            flat = self.weights()
        with span("setup.load_state"):
            self.prog.load(flat)
            del flat
        with span("setup.loader"):
            ds = traffic.ViewDataset(self.seed, imgsize=self.config["H"],
                                     **self.mix["dataset"])
            self.feed = self.prog.loader(ds, self.mix["loader_workers"])
        with span("setup.first_steps"):
            self.seen = adapters.drive_first_steps(
                self.prog, self.feed, self.mix["check"]["steps"],
                self.fault)
        if self.fault == "half_batch":       # the reference in its place
            self.seen = dict(self.reference(half_batch=True),
                             batches=self.seen["batches"])

    # ------------------------------------------------------------- window
    def measure(self, seconds: float, on_start=None) -> dict:
        prog, feed, spans = self.prog, self.feed, self.spans
        batch_size = self.cfg.train.global_batch
        if on_start:
            on_start()
        t0 = time.perf_counter()
        done, pending, last_done = 0, None, t0
        while True:
            with spans.span("input_wait"):
                batch = next(feed)
            with spans.span("dispatch"):
                metrics = prog.step(batch)
            if pending is not None:
                jax.block_until_ready(pending)
                now = time.perf_counter()
                spans.add("step", last_done, now)
                last_done, done = now, done + 1
                if now - t0 >= seconds:
                    break
            pending = metrics["loss"]
        jax.block_until_ready(metrics["loss"])
        now = time.perf_counter()
        spans.add("step", last_done, now)
        done += 1
        window_s = now - t0
        self.last_loss = float(metrics["loss"])
        examples = done * batch_size
        return {"attempted": done, "failed": 0 if np.isfinite(
                    self.last_loss) else done,
                "window_s": window_s, "steps": done,
                "flops": done * flops.train_step_flops(self.mcfg, batch_size),
                "end_to_end": {"train_examples_per_s": examples / window_s}}

    def release(self) -> None:
        self.feed.close()
        self.prog.free()

    # ------------------------------------------------------------- verify
    def reference(self, prec="float32", half_batch=False, bits=23) -> dict:
        if prec not in self._refs:
            self._refs[prec] = rd.TrainReference(
                self.mcfg, self.dcfg, self.tcfg,
                block=self.mix["check"]["block"], prec=prec)
        return self._refs[prec].run(self.weights, self.seen["batches"],
                                    self.prog.base_key, half_batch, bits)

    def compare(self, seen: dict, ref: dict) -> list:
        """The numbers compared, each with its limit: the widest relative
        gap of a step's loss; by the worst leaf the gap of the first
        gradient's norm and of the norm of the parameters' change; and the
        norm of the first gradient's difference over the reference's norm
        (a gap of norms is second order in unbiased rounding, so it alone
        cannot tell bfloat16 from fp8: PERF.md, section 4)."""
        lim = self.mix["limits"]
        if self._start is None:
            self._start = {k: np.asarray(v)
                           for k, v in self.weights().items()}
        start = self._start
        skip = rd.nought_gradient_leaves(ref["first_grad"])
        loss_gap = max(abs(a - b) / abs(b)
                       for a, b in zip(seen["losses"], ref["losses"]))
        grad_gap, grad_at = rd.worst_leaf_gap(seen["first_grad"],
                                              ref["first_grad"], skip)
        grad_diff = rd.relative_difference(seen["first_grad"],
                                           ref["first_grad"], skip)

        def delta(p):
            return {k: np.asarray(p[k], np.float32) - start[k]
                    for k in start}
        change_gap, change_at = rd.worst_leaf_gap(
            delta(seen["params"]), delta(ref["params"]), skip)
        self.notes = {"losses": seen["losses"], "ref_losses": ref["losses"],
                      "grad_gap_at": grad_at, "change_gap_at": change_at,
                      "leaves_left_out": len(skip)}
        return [("loss_gap", float(loss_gap), lim["loss_gap"]),
                ("grad_gap", float(grad_gap), lim["grad_gap"]),
                ("grad_diff", float(grad_diff), lim["grad_diff"]),
                ("change_gap", float(change_gap), lim["change_gap"])]

    def verify(self) -> list:
        return self.compare(self.seen, self.reference())

    def readings(self, seconds: float, control: bool) -> dict:
        """Calibration: this seed's numbers for the program, and where
        asked for the control (the reference at fp8's 3 mantissa bits in
        the program's place) and the half-batch fault, each against the
        reference.  One compiled pass serves all three."""
        ref = self.reference("bits")

        def numbers(seen):
            got = {n: v for n, v, _ in self.compare(seen, ref)}
            return dict(got, **self.notes)

        out = {"program": numbers(self.seen)}
        if control:
            out["control_fp8"] = numbers(self.reference("bits", bits=3))
            out["fault_half_batch"] = numbers(
                self.reference("bits", half_batch=True))
        return out
