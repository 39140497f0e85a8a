"""Offline sampling traffic: repeated ``Sampler.synthesize_many`` calls,
each on fresh objects and keys drawn from the seed.  A new call starts
only while the last call's duration still fits before the window's end;
one call always runs; the window is the time of the completed calls.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import adapters, flops, traffic
from benchmark.reference import diffusion as rd
from benchmark.reference import xunet as rx


class Driver:
    def __init__(self, *, config, mix, seed, chips, spans):
        self.config, self.mix, self.seed = config, mix, int(seed)
        self.chips, self.spans = chips, spans
        self.fault = None
        self._ref_fns = {}
        self.notes = {}
        self.key_seed = self.seed % (2 ** 31 - 1)
        self.cfg = adapters.build_config(config)
        self.mcfg = {k: config[k] for k in adapters.MODEL_KEYS}
        self.dcfg = adapters.diffusion_dict(self.cfg)
        self.steps = mix["steps"] or self.dcfg["timesteps"]
        # () -> the seeded parameters, made anew on the device at each call
        self.weights = rx.make_params(self.mcfg,
                                      jax.random.PRNGKey(self.key_seed))

    def inputs(self, call: int):
        """Objects and keys of call ``call``: always the same for a seed."""
        n = self.mix["objects"]
        objs = [(call * n + i) % len(self.ds) for i in range(n)]
        keys = [np.asarray(jax.random.fold_in(
            jax.random.PRNGKey(self.key_seed), 1000 + call * n + i))
            for i in range(n)]
        return [self.ds.all_views(o) for o in objs], keys

    def setup(self, reuse=None) -> None:
        """``reuse``: a driver of the same cell whose compiled programs
        this one takes over (calibration reads many seeds in a process)."""
        span = self.spans.span
        with span("setup.weights"):
            flat = self.weights()
        self.ds = traffic.ViewDataset(self.seed, imgsize=self.config["H"],
                                      **self.mix["dataset"])
        with span("setup.build"):
            if reuse is not None:
                self.prog, self._ref_fns = reuse.prog, reuse._ref_fns
                self.prog.sampler.params = rx.nest(flat)
            else:
                self.prog = adapters.SampleProgram(
                    self.cfg, flat, kind=self.mix["sampler"],
                    steps=self.mix["steps"])
        with span("setup.warm"):
            views, keys = self.inputs(0)
            self.prog.warm(views, keys, self.mix["max_views"])

    def measure(self, seconds: float, on_start=None) -> dict:
        n, mv = self.mix["objects"], self.mix["max_views"]
        if on_start:
            on_start()
        self.outs = []
        t0 = time.perf_counter()
        last = 0.0
        while True:
            views, keys = self.inputs(len(self.outs))
            c0 = time.perf_counter()
            with self.spans.span("call"):
                out = self.prog.call(views, keys, mv)
            last = time.perf_counter() - c0
            if self.fault == "answer_altered":
                out = out + 0.5
            self.outs.append(out)
            if time.perf_counter() - t0 + last > seconds:
                break
        window_s = time.perf_counter() - t0
        calls = len(self.outs)
        views_done = calls * n * (mv - 1)
        bad = sum(int(not np.isfinite(o).all()) for o in self.outs)
        weights = len(self.dcfg["guidance_weights"])
        return {"attempted": calls, "failed": bad, "window_s": window_s,
                "calls": calls, "views": views_done,
                "model_steps": calls * (mv - 1) * self.steps,
                "flops": views_done * flops.sample_view_flops(
                    self.mcfg, self.steps, weights),
                "end_to_end": {"sample_s_per_view": window_s / views_done}}

    def release(self) -> None:
        self.prog.free()

    def reference_view(self, call: int, obj: int, view: int,
                       prec="float32", bits=23) -> np.ndarray:
        """View ``view`` of object ``obj`` of call ``call`` by the plain
        reference.  The record before it holds the views the program
        returned (as a reference decodes a served answer over its served
        prefix); the object's key is advanced as the earlier views did."""
        views, keys = self.inputs(call)
        v, mv = views[obj], self.mix["max_views"]
        B = len(self.dcfg["guidance_weights"])
        H = self.config["H"]
        cap = 1 << (mv - 1).bit_length()
        rec = np.zeros((cap, B, H, H, 3), np.float32)
        rec[0] = v["imgs"][0][None]
        for j in range(1, view):
            rec[j] = self.outs[call][obj, j - 1]
        R = np.zeros((cap, 3, 3), np.float32)
        T = np.zeros((cap, 3), np.float32)
        R[:mv], T[:mv] = v["R"][:mv], v["T"][:mv]
        key = jnp.asarray(keys[obj])
        for _ in range(1, view):
            key, _ = jax.random.split(key)
        if prec not in self._ref_fns:
            self._ref_fns[prec] = jax.jit(
                lambda p, ri, rR, rT, K, k, n, b: rd.synthesize_view(
                    p, ri, rR, rT, n, K, k, self.mcfg, self.dcfg,
                    steps=self.steps, kind=self.mix["sampler"],
                    prec=b if prec == "bits" else prec)[0])
        fn = self._ref_fns[prec]
        return np.asarray(fn(self.weights(), rec, R, T,
                             np.asarray(v["K"], np.float32), key,
                             jnp.int32(view), jnp.int32(bits)))

    def picks(self):
        """The answers compared: drawn from the seed among those the
        window finished."""
        rng = np.random.default_rng([self.seed, 0x7069636B])
        n, mv = self.mix["objects"], self.mix["max_views"]
        return [(int(rng.integers(len(self.outs))), int(rng.integers(n)),
                 int(rng.integers(1, mv)))
                for _ in range(self.mix["check"]["answers"])]

    def image_gap(self, prog_img: np.ndarray, ref_img: np.ndarray) -> float:
        """Largest, over the guidance weights, mean absolute difference of
        an image (values in [-1, 1]) over ``1 + w``: guidance multiplies
        the model's rounding by about that, so unscaled the largest weight
        alone would decide (chip readings, PERF.md PR 23)."""
        w = np.asarray(self.dcfg["guidance_weights"], np.float64)
        gaps = np.abs(prog_img - ref_img).mean(axis=(1, 2, 3))
        return float((gaps / (1.0 + w)).max())

    def verify(self) -> list:
        picks = self.picks()
        gap = 0.0
        for call, obj, view in picks:
            ref = self.reference_view(call, obj, view)
            gap = max(gap, self.image_gap(self.outs[call][obj, view - 1], ref))
        self.notes = {"picks": picks}
        return [("image_gap", gap, self.mix["limits"]["image_gap"])]

    def readings(self, seconds: float, control: bool) -> dict:
        """Calibration: one window, then for the answers a run of this
        seed would compare the program's gap to the reference and, where
        asked for, the control's (the reference in fp8)."""
        self.measure(seconds)
        out = {"picks": self.picks(), "program": [], "control_fp8": [],
               "program_by_weight": [], "control_fp8_by_weight": []}

        def by_weight(a, b):
            return [float(g) for g in np.abs(a - b).mean(axis=(1, 2, 3))]

        for call, obj, view in self.picks():
            ref = self.reference_view(call, obj, view, "bits")
            got = self.outs[call][obj, view - 1]
            out["program"].append(self.image_gap(got, ref))
            out["program_by_weight"].append(by_weight(got, ref))
            if control:
                low = self.reference_view(call, obj, view, "bits", bits=3)
                out["control_fp8"].append(self.image_gap(low, ref))
                out["control_fp8_by_weight"].append(by_weight(low, ref))
        return out
