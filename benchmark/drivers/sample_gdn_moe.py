"""Offline sampling traffic for the token denoiser on a ``qwen3_next``
block (``configs/qwen3_next_tok128.json``): Gated DeltaNet linear
attention three to one with gated full attention, softmax-routed experts
beside a gated shared expert.  ``drivers/sample_hybrid.py``'s closed loop
of ``Sampler.synthesize_many`` calls, inputs, warm-up, record and picks,
with this model's reference (``reference/gdn_moe_denoiser.py``: float32,
the delta rule token by token, per-head gated attention, the published
router, the held share of the experts beside the gated shared expert),
adapter and FLOP count in place of h-micro's, as
``drivers/sample_mla_moe.py`` puts Pangu's.  The parameters are made and
held in bfloat16, as the configuration's ``param_dtype`` says; the
reference reads the same ones.

The comparison that decides ``correct`` is that driver's: one answer the
window finished, drawn from the seed, synthesised again by the reference
over the record and key stream the program used; ``image_gap`` (the
largest over the guidance weights) and ``image_gap_w0`` (at weight 0,
where the program's rounding is smallest and the control's gap stands
farthest above it).  Two planted faults, each the reference in the
program's place:
``delta_dropped`` (the mixer without the delta correction: gated linear
attention, ``S_t = exp(g_t) S_{t-1} + beta_t k_t v_t^T``) and
``experts_dropped`` (the held experts' sum zeroed).  The run's ``notes``
carry the gap of each guidance weight and the reference's load of the
held experts.

The weights are made from the traffic file's ``weights_seed``, the same
in every run: on seeded routers the held experts' load is skewed by the
weights, and with it the experts' time, a third of a call.  The run's
seed draws the inputs, the key stream and the answers compared.

The configuration is built and the parameter tree checked against the
program's own ``init`` before any weight is made, so that a program
without the Gated DeltaNet mixer fails at once.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import adapters_gdn_moe as adapters
from benchmark import flops_gdn_moe
from benchmark.drivers import sample_hybrid
from benchmark.reference import gdn_moe_denoiser as rm
from benchmark.reference.xunet import MANTISSA_BITS

HERE = sample_hybrid.HERE
FAULTS = {"delta_dropped": {"delta_dropped": True},
          "experts_dropped": {"experts_dropped": True}}


class Driver(sample_hybrid.Driver):
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
        self.mcfg = rm.model_dict(config)
        self.dcfg = adapters.diffusion_dict(self.cfg)
        self.steps = mix["steps"] or self.dcfg["timesteps"]
        dtype = jnp.dtype(self.cfg.model.param_dtype)
        adapters.check_tree(self.cfg, {
            k: jax.ShapeDtypeStruct(s, dtype)
            for k, (s, _) in rm.param_shapes(self.mcfg).items()})
        # () -> the cell's parameters, made anew on the device at each call
        self.weights = rm.make_params(
            self.mcfg, jax.random.PRNGKey(mix["weights_seed"] % (2 ** 31 - 1)),
            dtype=dtype)

    def measure(self, seconds: float, on_start=None) -> dict:
        """``drivers/sample_hybrid.py``'s window, its FLOPs counted by
        ``flops_gdn_moe.py``."""
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
                "flops": views_done * flops_gdn_moe.sample_view_flops(
                    dict(self.mcfg, **self.config["tiles"]), self.steps,
                    weights),
                "end_to_end": {"sample_s_per_view": window_s / views_done}}

    def reference_view(self, flat, call: int, obj: int, view: int,
                       bits=23, over=None):
        """View ``view`` of object ``obj`` of call ``call`` by the plain
        reference from the parameters ``flat``, and the held experts' load
        it saw (``reference/gdn_moe_denoiser.py make_view_fn``).  ``bits``
        < 23 is the control; ``over`` changes keys of the model's
        configuration (a planted fault)."""
        rec, R, T, K, key = self._record(call, obj, view)
        name = json.dumps(over or {}, sort_keys=True)
        if name not in self._ref_fns:
            self._ref_fns[name] = rm.make_view_fn(
                dict(self.mcfg, **(over or {})), self.dcfg,
                steps=self.steps, kind=self.mix["sampler"])
        img, _, load = self._ref_fns[name](
            flat, rec, R, T, view, K, key,
            {23: "float32", MANTISSA_BITS["fp8"]: "fp8"}[bits])
        return np.asarray(img), [float(x) for x in load]

    def verify(self) -> list:
        picks = self.picks()
        flat = self.weights()
        gaps = np.zeros(len(self.dcfg["guidance_weights"]))
        load = [0.0, 0.0, 0.0]
        for call, obj, view in picks:
            ref, load = self.reference_view(flat, call, obj, view)
            got = self.outs[call][obj, view - 1]
            if self.fault in FAULTS:
                got = self.reference_view(flat, call, obj, view,
                                          over=FAULTS[self.fault])[0]
            gaps = np.maximum(gaps, self.image_gaps(got, ref))
        self.notes = {"picks": picks,
                      "image_gap_by_weight": [float(g) for g in gaps],
                      "held_expert_rows_max": load[0],
                      "held_expert_rows_mean": load[1],
                      "held_share_of_assignments": load[2]}
        limits = self.mix["limits"]
        return [("image_gap", float(gaps.max()), limits["image_gap"]),
                ("image_gap_w0", float(gaps[list(
                    self.dcfg["guidance_weights"]).index(0)]),
                 limits["image_gap_w0"])]

    def readings(self, seconds: float, control: bool) -> dict:
        """Calibration: one window, then for the answers a run of this
        seed would compare: the program's gap and, where asked for, the
        control's (the reference at 3 mantissa bits in the program's
        place) and each planted fault's, each for every guidance
        weight."""
        self.measure(seconds)
        self.release()
        flat = self.weights()
        out = {"picks": self.picks(), "program": [], "control": [],
               "held_expert_load": [],
               **{"fault_" + name: [] for name in FAULTS}}
        for call, obj, view in self.picks():
            ref, load = self.reference_view(flat, call, obj, view)
            got = self.outs[call][obj, view - 1]
            out["held_expert_load"].append(load)
            out["program"].append(self.image_gaps(got, ref).tolist())
            if control:
                low = self.reference_view(flat, call, obj, view, bits=3)[0]
                out["control"].append(self.image_gaps(low, ref).tolist())
                for name, over in FAULTS.items():
                    bad = self.reference_view(flat, call, obj, view,
                                              over=over)[0]
                    out["fault_" + name].append(
                        self.image_gaps(bad, ref).tolist())
        return out
