"""Offline sampling traffic for the token denoiser: repeated
``Sampler.synthesize_many`` calls, each on fresh objects and keys drawn
from the seed, closed loop, as ``drivers/sample.py`` drives the X-UNet.  A
new call starts only while the last call's duration still fits before the
window's end; one call always runs; the window is the time of the
completed calls.

The comparison that decides ``correct``: one answer the window finished is
synthesised again by the plain reference (``reference/token_denoiser.py``,
float32, example by example) over the record and key stream the program
used (``image_gap``, as the X-UNet cell reads it).  The run's ``notes``
carry that gap for each guidance weight and the reference's largest
max-over-mean expert load.

On rehearsal ``run.py`` hands every driver X-UNet's ``configs/tiny.json``:
the traffic's ``rehearsal`` entry names this model's tiny configuration
file (``config_file``), and it is read here.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import adapters_tokens as adapters
from benchmark import flops_tokens, traffic
from benchmark.reference import token_denoiser as rt

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Driver:
    def __init__(self, *, config, mix, seed, chips, spans):
        if "config_file" in mix:                      # rehearsal
            with open(os.path.join(HERE, mix["config_file"])) as f:
                config = json.load(f)
        self.config, self.mix, self.seed = config, mix, int(seed)
        self.chips, self.spans = chips, spans
        self.fault = None
        self._ref_fns = {}
        self.notes = {}
        self.key_seed = self.seed % (2 ** 31 - 1)
        self.cfg = adapters.build_config(config)
        self.mcfg = rt.model_dict(config)
        self.dcfg = adapters.diffusion_dict(self.cfg)
        self.steps = mix["steps"] or self.dcfg["timesteps"]
        # () -> the seeded parameters, made anew on the device at each call
        self.weights = rt.make_params(self.mcfg,
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
        if reuse is not None:
            reuse.release()         # 10 GB: never two sets at once
        with span("setup.weights"):
            flat = self.weights()
        self.ds = traffic.ViewDataset(self.seed, imgsize=self.config["H"],
                                      **self.mix["dataset"])
        with span("setup.build"):
            if reuse is not None:
                self.prog, self._ref_fns = reuse.prog, reuse._ref_fns
                self.prog.sampler.params = adapters.nest(flat)
            else:
                adapters.check_tree(self.cfg, flat)
                self.prog = adapters.SampleProgram(
                    self.cfg, flat, kind=self.mix["sampler"],
                    steps=self.mix["steps"])
        del flat
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
                "flops": views_done * flops_tokens.sample_view_flops(
                    self.mcfg, self.steps, weights),
                "end_to_end": {"sample_s_per_view": window_s / views_done}}

    def release(self) -> None:
        self.prog.free()

    def _record(self, call: int, obj: int, view: int):
        """Record, poses, intrinsics and key before ``view`` of object
        ``obj`` of call ``call``, as ``drivers/sample.py`` builds them."""
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
        return rec, R, T, np.asarray(v["K"], np.float32), key

    def reference_view(self, flat, call: int, obj: int, view: int,
                       bits=23, over=None):
        """View ``view`` of object ``obj`` of call ``call`` by the plain
        reference from the parameters ``flat``, and the largest
        max-over-mean expert load it saw.  ``bits`` < 23 is the control
        (that many mantissa bits in every contraction); ``over`` changes
        keys of the model's configuration (a planted fault)."""
        rec, R, T, K, key = self._record(call, obj, view)
        name = json.dumps(over or {}, sort_keys=True)
        if name not in self._ref_fns:
            mcfg = dict(self.mcfg, **(over or {}))
            self._ref_fns[name] = jax.jit(
                lambda p, ri, rR, rT, K, k, n, b: rt.synthesize_view(
                    p, ri, rR, rT, n, K, k, mcfg, self.dcfg,
                    steps=self.steps, kind=self.mix["sampler"],
                    prec=b)[::2])
        img, load = self._ref_fns[name](flat, rec, R, T, K, key,
                                        jnp.int32(view), jnp.int32(bits))
        return np.asarray(img), float(load)

    def _half(self) -> dict:
        return {"indexer_topk": self.mcfg["indexer_topk"] // 2}

    def picks(self):
        """The answers compared: drawn from the seed among those the
        window finished."""
        rng = np.random.default_rng([self.seed, 0x7069636B])
        n, mv = self.mix["objects"], self.mix["max_views"]
        return [(int(rng.integers(len(self.outs))), int(rng.integers(n)),
                 int(rng.integers(1, mv)))
                for _ in range(self.mix["check"]["answers"])]

    def image_gaps(self, prog_img: np.ndarray, ref_img: np.ndarray
                   ) -> np.ndarray:
        """For each guidance weight ``w``: mean absolute difference of an
        image (values in [-1, 1]) over ``1 + w``; the largest of them is
        ``image_gap``, as the X-UNet cell reads it."""
        w = np.asarray(self.dcfg["guidance_weights"], np.float64)
        return np.abs(prog_img - ref_img).mean(axis=(1, 2, 3)) / (1.0 + w)

    def verify(self) -> list:
        picks = self.picks()
        flat = self.weights()
        gaps, load = np.zeros(len(self.dcfg["guidance_weights"])), 0.0
        for call, obj, view in picks:
            ref, seen = self.reference_view(flat, call, obj, view)
            got = self.outs[call][obj, view - 1]
            if self.fault == "selection_halved":
                got = self.reference_view(flat, call, obj, view,
                                          over=self._half())[0]
            gaps = np.maximum(gaps, self.image_gaps(got, ref))
            load = max(load, seen)
        self.notes = {"picks": picks, "expert_load_max_over_mean": load,
                      "image_gap_by_weight": [float(g) for g in gaps]}
        return [("image_gap", float(gaps.max()),
                 self.mix["limits"]["image_gap"])]

    def readings(self, seconds: float, control: bool) -> dict:
        """Calibration: one window, then for the answers a run of this
        seed would compare: the program's gap and, where asked for, the
        control's (the reference at 3 mantissa bits in the program's
        place) and one planted fault's (the reference selecting half the
        keys in the program's place), each for every guidance weight."""
        self.measure(seconds)
        self.release()
        flat = self.weights()
        out = {"picks": self.picks(), "program": [], "control": [],
               "fault_topk_halved": [], "expert_load_max_over_mean": []}
        for call, obj, view in self.picks():
            ref, load = self.reference_view(flat, call, obj, view)
            got = self.outs[call][obj, view - 1]
            out["expert_load_max_over_mean"].append(load)
            out["program"].append(self.image_gaps(got, ref).tolist())
            if control:
                low = self.reference_view(flat, call, obj, view, bits=3)[0]
                out["control"].append(self.image_gaps(low, ref).tolist())
                bad = self.reference_view(flat, call, obj, view,
                                          over=self._half())[0]
                out["fault_topk_halved"].append(
                    self.image_gaps(bad, ref).tolist())
        return out
