"""Offline sampling traffic for the hybrid token denoiser with routed
experts beside a shared expert (``configs/granite4_h_small_tok128.json``):
``drivers/sample_hybrid.py``'s closed loop of ``Sampler.synthesize_many``
calls, inputs, warm-up, record and picks, with this model's reference
(``reference/hybrid_moe_denoiser.py``: float32, the router the published
way, the held share of the experts, the shared expert, one norm, one add),
adapter and FLOP count in place of h-micro's.

The comparison that decides ``correct`` is that driver's: one answer the
window finished, drawn from the seed, synthesised again by the reference
over the record and key stream the program used; ``image_gap`` alone.  Two
planted faults, each the reference in the program's place with one branch
of the feed-forward left out: ``shared_dropped`` (no shared expert: what a
layer builds that takes ``num_experts > 0`` for "no dense MLP") and
``experts_dropped`` (the held experts' sum zeroed).  The run's ``notes``
carry the gap of each guidance weight and the reference's load of the held
experts.

The parameter tree is checked against the program's own ``init`` before
any weight is made, so that a program without the two-branch feed-forward
fails at once.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import adapters_hybrid_moe as adapters
from benchmark import flops_hybrid_moe
from benchmark.drivers import sample_hybrid
from benchmark.reference import hybrid_moe_denoiser as rm

HERE = sample_hybrid.HERE
FAULTS = {"shared_dropped": {"shared_dropped": True},
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
        adapters.check_tree(self.cfg, {
            k: jax.ShapeDtypeStruct(s, jnp.float32)
            for k, (s, _) in rm.param_shapes(self.mcfg).items()})
        # () -> the seeded parameters, made anew on the device at each call
        self.weights = rm.make_params(self.mcfg,
                                      jax.random.PRNGKey(self.key_seed))

    def measure(self, seconds: float, on_start=None) -> dict:
        window = super().measure(seconds, on_start)
        window["flops"] = window["views"] * \
            flops_hybrid_moe.sample_view_flops(
                self.mcfg, self.steps, len(self.dcfg["guidance_weights"]))
        return window

    def reference_view(self, flat, call: int, obj: int, view: int,
                       bits=23, over=None):
        """View ``view`` of object ``obj`` of call ``call`` by the plain
        reference from the parameters ``flat``, and the held experts' load
        it saw (``reference/hybrid_moe_denoiser.py synthesize_view``).
        ``bits`` < 23 is the control; ``over`` changes keys of the model's
        configuration (a planted fault)."""
        rec, R, T, K, key = self._record(call, obj, view)
        name = json.dumps(over or {}, sort_keys=True)
        if name not in self._ref_fns:
            self._ref_fns[name] = rm.make_view_fn(
                dict(self.mcfg, **(over or {})), self.dcfg,
                steps=self.steps, kind=self.mix["sampler"])
        img, _, load = self._ref_fns[name](flat, rec, R, T, view, K, key,
                                           jnp.int32(bits))
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
        return [("image_gap", float(gaps.max()),
                 self.mix["limits"]["image_gap"])]

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
