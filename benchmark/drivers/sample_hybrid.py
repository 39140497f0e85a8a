"""Offline sampling traffic for the hybrid token denoiser
(``configs/granite4_h_micro_tok128.json``): ``drivers/sample_tokens.py``'s
closed loop of ``Sampler.synthesize_many`` calls, inputs, warm-up, record
and picks, with this model's reference (``reference/hybrid_denoiser.py``:
float32, the state-space layers as a sequential recurrence), adapter and
FLOP count in place of Keye's.

The comparison that decides ``correct`` is that driver's: one answer the
window finished, drawn from the seed, synthesised again by the reference
over the record and key stream the program used; ``image_gap`` alone.  The
planted fault is the reference with the state-space layers' state zeroed
every ``mamba_chunk_size`` tokens in the program's place: what a chunked
scan that loses its carry computes.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import adapters_hybrid as adapters
from benchmark import flops_hybrid
from benchmark.drivers import sample_tokens
from benchmark.reference import hybrid_denoiser as rh

HERE = sample_tokens.HERE


class Driver(sample_tokens.Driver):
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
        self.mcfg = rh.model_dict(config)
        self.dcfg = adapters.diffusion_dict(self.cfg)
        self.steps = mix["steps"] or self.dcfg["timesteps"]
        # () -> the seeded parameters, made anew on the device at each call
        self.weights = rh.make_params(self.mcfg,
                                      jax.random.PRNGKey(self.key_seed))

    def measure(self, seconds: float, on_start=None) -> dict:
        n, mv = self.mix["objects"], self.mix["max_views"]
        if on_start:
            on_start()
        self.outs = []
        t0 = time.perf_counter()
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
                "flops": views_done * flops_hybrid.sample_view_flops(
                    self.mcfg, self.steps, weights),
                "end_to_end": {"sample_s_per_view": window_s / views_done}}

    def reference_view(self, flat, call: int, obj: int, view: int,
                       bits=23, over=None) -> np.ndarray:
        """View ``view`` of object ``obj`` of call ``call`` by the plain
        reference from the parameters ``flat``.  ``bits`` < 23 is the
        control (that many mantissa bits in every contraction); ``over``
        changes keys of the model's configuration (the planted fault)."""
        rec, R, T, K, key = self._record(call, obj, view)
        name = json.dumps(over or {}, sort_keys=True)
        if name not in self._ref_fns:
            mcfg = dict(self.mcfg, **(over or {}))
            self._ref_fns[name] = jax.jit(
                lambda p, ri, rR, rT, K, k, n, b: rh.synthesize_view(
                    p, ri, rR, rT, n, K, k, mcfg, self.dcfg,
                    steps=self.steps, kind=self.mix["sampler"], prec=b)[0])
        return np.asarray(self._ref_fns[name](
            flat, rec, R, T, K, key, jnp.int32(view), jnp.int32(bits)))

    def _dropped(self) -> dict:
        return {"state_reset_every": self.mcfg["mamba_chunk_size"]}

    def verify(self) -> list:
        picks = self.picks()
        flat = self.weights()
        gaps = np.zeros(len(self.dcfg["guidance_weights"]))
        for call, obj, view in picks:
            ref = self.reference_view(flat, call, obj, view)
            got = self.outs[call][obj, view - 1]
            if self.fault == "state_dropped":
                got = self.reference_view(flat, call, obj, view,
                                          over=self._dropped())
            gaps = np.maximum(gaps, self.image_gaps(got, ref))
        self.notes = {"picks": picks,
                      "image_gap_by_weight": [float(g) for g in gaps]}
        return [("image_gap", float(gaps.max()),
                 self.mix["limits"]["image_gap"])]

    def readings(self, seconds: float, control: bool) -> dict:
        """Calibration: one window, then for the answers a run of this
        seed would compare: the program's gap and, where asked for, the
        control's (the reference at 3 mantissa bits in the program's
        place) and the planted fault's (the reference dropping the state
        at every chunk boundary in the program's place), each for every
        guidance weight."""
        self.measure(seconds)
        self.release()
        flat = self.weights()
        out = {"picks": self.picks(), "program": [], "control": [],
               "fault_state_dropped": []}
        for call, obj, view in self.picks():
            ref = self.reference_view(flat, call, obj, view)
            got = self.outs[call][obj, view - 1]
            out["program"].append(self.image_gaps(got, ref).tolist())
            if control:
                low = self.reference_view(flat, call, obj, view, bits=3)
                out["control"].append(self.image_gaps(low, ref).tolist())
                bad = self.reference_view(flat, call, obj, view,
                                          over=self._dropped())
                out["fault_state_dropped"].append(
                    self.image_gaps(bad, ref).tolist())
        return out
