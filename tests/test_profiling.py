"""utils/profiling.py: the recorder (spans, counters, the compile clock),
the scope contract on the two timed programs, and the reducer from a
profiler trace to seconds by class."""

import contextlib
import dataclasses
import json
import os
import re
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from diff3d_tpu.config import test_config as make_tiny_config
from diff3d_tpu.data import (InfiniteLoader, SyntheticDataset,
                             prefetch_to_device)
from diff3d_tpu.models import XUNet, build_model
from diff3d_tpu.parallel import make_mesh
from diff3d_tpu.sampling import Sampler
from diff3d_tpu.train.state import create_train_state
from diff3d_tpu.train.step import make_train_step
from diff3d_tpu.train.trainer import init_params
from diff3d_tpu.utils import profiling
from diff3d_tpu.utils.profiling import (RECORDER, SCOPES, Recorder, op_class,
                                        reduce_scopes, scope, span)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_cfg(**train_kw):
    cfg = make_tiny_config(imgsize=8, ch=8, shallow=True)
    # dropout on, or the tiny step has no op of that class
    cfg = dataclasses.replace(
        cfg, model=dataclasses.replace(cfg.model, dropout=0.1))
    if train_kw:
        cfg = dataclasses.replace(
            cfg, train=dataclasses.replace(cfg.train, **train_kw))
    return cfg


def spans_since(mark: float, name: str):
    return [s for s in RECORDER.spans(name) if s.start >= mark]


@contextlib.contextmanager
def fresh_compile_cache(path, monkeypatch):
    """An empty persistent cache of the test's own that keeps every
    program however small, placed by the program's rule; the process's
    own cache again after."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from diff3d_tpu.runtime import compile_cache

    was = jax.config.jax_compilation_cache_dir
    min_s = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    compile_cache.configure_compile_cache(path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cc.reset_cache()
    try:
        yield
    finally:
        compile_cache.configure_compile_cache(was)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_s)
        cc.reset_cache()


@contextlib.contextmanager
def no_compile_cache():
    """The persistent cache's key leaves metadata out, so a cached
    executable carries the ``op_name``s of whichever tree compiled it
    first: compile afresh where the test reads them."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


# ------------------------------------------------------------- recorder


def test_nested_spans_have_parents_ids_and_threads():
    rec = Recorder()
    with rec.span("outer", id=7):
        with rec.span("inner", id=7):
            pass
        with rec.span("inner"):
            pass
    names = [(s.name, s.parent, s.id) for s in rec.spans()]
    assert names == [("inner", "outer", 7), ("inner", "outer", None),
                     ("outer", None, 7)]
    outer = rec.spans("outer")[0]
    for s in rec.spans("inner"):
        assert outer.start <= s.start <= s.end <= outer.end
        assert s.thread == threading.get_ident()
    assert rec.totals()["inner"][0] == 2
    # a span that raises is still recorded, and the stack unwinds
    with pytest.raises(KeyError):
        with rec.span("boom"):
            raise KeyError("x")
    with rec.span("after"):
        pass
    assert rec.spans("boom") and rec.spans("after")[0].parent is None


def test_ring_is_bounded_and_totals_are_not():
    rec = Recorder(capacity=8)
    for i in range(50):
        rec.add("x", float(i), float(i) + 0.5, id=i)
    kept = rec.spans()
    assert len(kept) == 8 and [s.id for s in kept] == list(range(42, 50))
    assert rec.totals()["x"] == (50, 25.0)
    rec.count("n")
    rec.count("n", 4)
    assert rec.counters() == {"n": 5}


def test_recorder_loses_no_update_under_contention():
    rec = Recorder(capacity=1 << 12)
    workers, per = 16, 400
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work(k):
            for i in range(per):
                with rec.span("w", id=k):
                    rec.count("c")
        ts = [threading.Thread(target=work, args=(k,))
              for k in range(workers)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    assert rec.totals()["w"][0] == workers * per
    assert rec.counters()["c"] == workers * per
    assert len(rec.spans()) == 1 << 12
    # every span's parent is from its own thread's stack: none here
    assert all(s.parent is None for s in rec.spans())
    assert {s.name for s in rec.spans()} == {"w"}
    assert rec.counters() == {"c": workers * per}


def test_a_batch_is_followed_across_threads_by_its_id():
    """loader.batch (producer) -> prefetch.put (producer) -> prefetch.wait
    (consumer): the k-th batch has id k in all three."""
    ds = SyntheticDataset(num_objects=2, num_views=3, imgsize=8)
    env = make_mesh(make_tiny_config().mesh)
    mark = time.perf_counter()
    starved = RECORDER.counters().get("prefetch.starved", 0)
    it = prefetch_to_device(InfiniteLoader(ds, 8, seed=0, num_workers=0),
                            env.batch(), depth=2)
    for _ in range(4):
        next(it)
    it.close()
    waits = spans_since(mark, "prefetch.wait")
    assert [s.id for s in waits] == [0, 1, 2, 3]
    me = threading.get_ident()
    assert all(s.thread == me for s in waits)
    for k in range(4):
        made = [s for s in spans_since(mark, "loader.batch") if s.id == k]
        put = [s for s in spans_since(mark, "prefetch.put") if s.id == k]
        assert len(made) == 1 and len(put) == 1
        assert made[0].thread == put[0].thread != me
        assert made[0].end <= put[0].start <= put[0].end <= waits[k].end
    # the first get always finds the queue empty
    assert RECORDER.counters()["prefetch.starved"] >= starved + 1


# ------------------------------------------------- the compile clock


def test_compile_clock_lives_in_profiling_and_chip_smoke_imports_it():
    sys.path.insert(0, ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(ROOT)
    assert not hasattr(chip_smoke, "CompileClock")
    src = open(os.path.join(ROOT, "chip_smoke.py")).read()
    assert "import COMPILE_CLOCK" in src
    assert "register_event" not in src

    def never_seen_before(x):
        return jnp.cos(x) * 3.25 + 0.125

    x = jnp.ones(7)
    before = profiling.COMPILE_CLOCK.snapshot()
    mark = time.perf_counter()
    with no_compile_cache():
        jax.jit(never_seen_before)(x).block_until_ready()
    after = profiling.COMPILE_CLOCK.snapshot()
    assert after["backend_compiles"] == before["backend_compiles"] + 1
    for k in ("trace_s", "lower_s", "backend_compile_s"):
        assert after[k] > before[k]
    for name in ("compile.trace", "compile.lower", "compile.backend",
                 "compile.fresh"):
        mine = [s for s in spans_since(mark - 1.0, name)
                if "never_seen_before" in str(s.id)]
        assert mine and all(mark <= s.end for s in mine), name


def test_a_backend_compile_is_a_cache_read_or_a_fresh_compile(
        tmp_path, monkeypatch):
    """Under an empty cache the program is compiled (``compile.fresh``);
    with the in-memory caches cleared the same call reads it back
    (``compile.cache_read``).  Either is part of its ``compile.backend``
    span, on its thread, under its function's name."""
    def never_cached(x):
        return jnp.sin(x) * 1.75 - 0.5

    def call():
        mark = time.perf_counter()
        jax.jit(never_cached)(jnp.ones(5)).block_until_ready()
        return {n: [s for s in spans_since(mark, n)
                    if "never_cached" in str(s.id)]
                for n in ("compile.backend", "compile.fresh",
                          "compile.cache_read")}, mark

    with fresh_compile_cache(str(tmp_path), monkeypatch):
        first, _ = call()
        jax.clear_caches()
        hits = profiling.COMPILE_CLOCK.snapshot()["cache_hits"]
        second, mark = call()
        hits = profiling.COMPILE_CLOCK.snapshot()["cache_hits"] - hits
    assert len(first["compile.fresh"]) == 1
    assert not first["compile.cache_read"]
    assert len(second["compile.cache_read"]) == 1
    assert not second["compile.fresh"]
    for got in (first, second):
        (backend,) = got["compile.backend"]
        (part,) = got["compile.fresh"] + got["compile.cache_read"]
        assert part.id == backend.id and part.thread == backend.thread
        assert backend.start <= part.start <= part.end <= backend.end
    # the snapshot's hits are the reads, one for each program read back
    assert hits == len(spans_since(mark, "compile.cache_read")) >= 1


#: each build step of the program, called as its callers call it
BUILD_STEPS = {
    "init.model": lambda p: build_model(p["cfg"]),
    # the benchmark's check_tree: timed at trace time, once
    "init.params": lambda p: jax.eval_shape(
        lambda: init_params(p["model"], p["cfg"], jax.random.PRNGKey(0))),
    "init.state": lambda p: create_train_state(p["params"],
                                               p["cfg"].train),
    "init.train_step": lambda p: make_train_step(p["model"], p["cfg"]),
    "init.sampler": lambda p: Sampler(p["model"], p["params"], p["cfg"],
                                      steps=4),
}


@pytest.mark.parametrize("name", sorted(BUILD_STEPS))
def test_each_build_step_is_one_span_a_call(programs, name):
    mark = time.perf_counter()
    for _ in range(2):
        BUILD_STEPS[name](programs)
    mine = spans_since(mark, name)
    assert len(mine) == 2
    me = threading.get_ident()
    assert all(s.thread == me and s.parent is None for s in mine)
    # no build step opens another's span
    assert {s.name for s in RECORDER.spans() if s.start >= mark
            and s.name.startswith("init.")} == {name}


def test_the_set_up_vocabulary_names_every_build_step():
    assert {n for n in profiling.SETUP_SPANS
            if n.startswith("init.")} == set(BUILD_STEPS)


def _read_elsewhere(clock):
    t = threading.Thread(target=clock._dur, args=(clock.CACHE_READ, 0.001))
    t.start()
    t.join()


#: JAX's events as one thread of a clock sees them, oldest first: a read
#: of the persistent cache, or the end of a backend compile that began
#: ``secs`` before it; and what each backend span is split into
CLOCK_CASES = {
    "a read inside its backend span": (
        [("read", 0.001), ("backend", 10.0)], ["compile.cache_read"]),
    "a stray read that ended before the span began": (
        [("read", 0.001), ("backend", 0.0)], ["compile.fresh"]),
    "one read is one span's": (
        [("read", 0.001), ("backend", 10.0), ("backend", 10.0)],
        ["compile.cache_read", "compile.fresh"]),
    "a read on another thread": (
        [("elsewhere", None), ("backend", 10.0)], ["compile.fresh"]),
}


@pytest.mark.parametrize("case", sorted(CLOCK_CASES))
def test_a_backend_span_takes_only_a_read_inside_it_on_its_thread(
        case, monkeypatch):
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener",
                        lambda listener: None)
    rec = profiling.Recorder()
    clock = profiling.CompileClock(rec)
    events, want = CLOCK_CASES[case]
    for kind, secs in events:
        time.sleep(0.002)
        if kind == "elsewhere":
            _read_elsewhere(clock)
        elif kind == "read":
            clock._dur(clock.CACHE_READ, secs)
        else:
            clock._dur("/jax/core/compile/backend_compile_duration", secs,
                       fun_name="f")
    backends = rec.spans("compile.backend")
    parts = [s for s in rec.spans() if s.name != "compile.backend"]
    assert [s.name for s in parts] == want
    for backend, part in zip(backends, parts):
        assert part.id == backend.id == "f"
        assert backend.start <= part.start <= part.end <= backend.end


def test_union_seconds_counts_an_overlap_once():
    assert profiling.union_seconds([(0, 4), (1, 2), (3, 6), (10, 11)]) == 7
    assert profiling.union_seconds([]) == 0


# ------------------------------------------------------- scope contract


def test_scope_refuses_a_tag_outside_the_vocabulary():
    with pytest.raises(ValueError, match="vocabulary"):
        scope("convolution")
    assert op_class("jit(f)/transpose(jvp(d3d.conv))/dot_general") == (
        "conv", True)
    assert op_class("jit(f)/d3d.film/FiLM_0/d3d.groupnorm/mul") == (
        "groupnorm", False)
    assert op_class("jit(f)/while/body/add") == (None, False)


def op_names(compiled_text: str):
    """``[(opcode, op_name)]`` of a compiled module's instructions, those
    that are no work of their own left out."""
    out = []
    for line in compiled_text.split("\n"):
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = \S+ ([\w\-]+)\(", line)
        if m and m.group(1) not in ("parameter", "constant", "tuple",
                                    "get-tuple-element", "bitcast"):
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), name.group(1) if name else ""))
    return out


@pytest.fixture(scope="module")
def programs():
    """The tiny train step (2 microbatches, dropout on) and view program,
    lowered; with the model and its parameters."""
    cfg = tiny_cfg(accum_steps=2)
    model = XUNet(cfg.model)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    env = make_mesh(cfg.mesh, devices=jax.devices()[:1])
    B, H = cfg.train.global_batch, cfg.model.H
    batch = {"imgs": jnp.zeros((B, 2, H, H, 3), jnp.uint8),
             "R": jnp.zeros((B, 2, 3, 3)), "T": jnp.zeros((B, 2, 3)),
             "K": jnp.zeros((B, 3, 3))}

    def lower_train():
        step = make_train_step(model, cfg, env)
        state = create_train_state(params, cfg.train)
        return step.lower(state, batch, jax.random.PRNGKey(0))

    def lower_view():
        return Sampler(model, params, cfg).lower_step_many(2, 2)

    return {"cfg": cfg, "model": model, "params": params,
            "lower_train": lower_train, "lower_view": lower_view,
            "lower": lambda: (lower_train(), lower_view())}


#: the token denoiser's own classes (models/token_denoiser.py)
TOKEN_SCOPES = {"patch_embed", "moe_router", "experts", "indexer",
                "sparse_attention", "rope"}
#: ... and those of its hybrid layers (models/mamba.py, token_layers.py)
HYBRID_SCOPES = {"ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate", "mlp"}
#: ... and those of latent attention (models/latent_attention.py)
MLA_SCOPES = {"mla_q", "mla_kv"}
#: ... and those of a Gated DeltaNet mixer (models/gated_deltanet.py)
DELTA_SCOPES = {"delta_proj", "delta_conv", "delta_scan", "delta_gate"}


def test_the_token_denoisers_classes_are_in_its_view_program():
    """Every device op of the token denoiser's view program falls in a
    class: its own six and the shared ``attention`` (projections),
    ``residual``, ``conditioning``, ``sampler``, ``record``."""
    from diff3d_tpu.config import token_test_config
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling import Sampler

    cfg = token_test_config()
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
    low = Sampler(model, params, cfg, sampler_kind="ddim",
                  steps=2).lower_step_many(1, 2)
    with no_compile_cache():
        text = low.compile().as_text()
    ops = op_names(text)
    tags = [op_class(n)[0] for _, n in ops]
    assert set(tags) - {None} == TOKEN_SCOPES | {
        "attention", "residual", "conditioning", "sampler", "record"}
    assert tags.count(None) / len(tags) < 0.12
    # a bare primitive name (``lt_to``, ``reduce_sum``) is the body of a
    # sort's comparator or a reduction's adder, no op of its own
    named_untagged = [n for (_, n), t in zip(ops, tags)
                      if t is None and "/" in n]
    assert len(named_untagged) / len(tags) < 0.002, named_untagged[:5]


def test_the_hybrid_layers_classes_are_in_the_view_program():
    """Every device op of the hybrid token denoiser's view program falls
    in a class: the state-space mixer's four, ``mlp``, and the shared
    ``attention`` (the plain attention layer whole), ``residual``,
    ``patch_embed``, ``conditioning``, ``sampler``, ``record``."""
    from diff3d_tpu.config import hybrid_test_config
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling import Sampler

    cfg = hybrid_test_config()
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
    low = Sampler(model, params, cfg, sampler_kind="ddim",
                  steps=2).lower_step_many(1, 2)
    with no_compile_cache():
        text = low.compile().as_text()
    ops = op_names(text)
    tags = [op_class(n)[0] for _, n in ops]
    assert set(tags) - {None} == HYBRID_SCOPES | {
        "attention", "residual", "patch_embed", "conditioning", "sampler",
        "record"}
    # by count; what has none is unnamed: copies, broadcasts and fusions
    # the compiler made (0.122 at this size)
    assert tags.count(None) / len(tags) < 0.15
    named_untagged = [n for (_, n), t in zip(ops, tags)
                      if t is None and "/" in n]
    assert len(named_untagged) / len(tags) < 0.002, named_untagged[:5]


def test_a_hybrid_layer_with_both_feed_forwards_splits_into_eight_classes():
    """The view program of the hybrid preset with routed experts beside a
    shared expert: the mixers' classes, and the one feed-forward half as
    ``moe_router | experts | mlp`` (the shared expert is class ``mlp``
    though it runs inside the experts' chunk map)."""
    from diff3d_tpu.config import hybrid_moe_test_config
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling import Sampler

    cfg = hybrid_moe_test_config()
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
    low = Sampler(model, params, cfg, sampler_kind="ddim",
                  steps=2).lower_step_many(1, 2)
    with no_compile_cache():
        text = low.compile().as_text()
    ops = op_names(text)
    tags = [op_class(n)[0] for _, n in ops]
    assert set(tags) - {None} == HYBRID_SCOPES | {
        "moe_router", "experts", "attention", "residual", "patch_embed",
        "conditioning", "sampler", "record"}
    # the shared expert's two matmuls are ``mlp`` under ``experts``
    inside = [n for _, n in ops if "d3d.experts" in n and "d3d.mlp" in n]
    assert inside and all(op_class(n)[0] == "mlp" for n in inside)
    assert tags.count(None) / len(tags) < 0.15
    named_untagged = [n for (_, n), t in zip(ops, tags)
                      if t is None and "/" in n]
    assert len(named_untagged) / len(tags) < 0.002, named_untagged[:5]


def test_a_latent_attention_stack_splits_into_its_classes():
    """The view program of the latent-attention preset: the two latent
    projection paths, ``rope``, the core and ``W_o`` as ``attention``,
    the dense layer's and the shared expert's ``mlp``, the router and the
    experts, the four norms of a layer under ``residual``."""
    from diff3d_tpu.config import mla_moe_test_config
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling import Sampler

    cfg = mla_moe_test_config()
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
    low = Sampler(model, params, cfg, sampler_kind="ddim",
                  steps=2).lower_step_many(1, 2)
    with no_compile_cache():
        text = low.compile().as_text()
    ops = op_names(text)
    tags = [op_class(n)[0] for _, n in ops]
    assert set(tags) - {None} == MLA_SCOPES | {
        "rope", "attention", "mlp", "moe_router", "experts", "residual",
        "patch_embed", "conditioning", "sampler", "record"}
    assert tags.count(None) / len(tags) < 0.15
    named_untagged = [n for (_, n), t in zip(ops, tags)
                      if t is None and "/" in n]
    assert len(named_untagged) / len(tags) < 0.002, named_untagged[:5]


def test_a_gated_deltanet_stack_splits_into_its_classes():
    """The view program of the ``qwen3_next`` preset: the Gated DeltaNet
    mixer's four classes, the gated attention layer whole as
    ``attention`` with its rotary turn as ``rope``, the router, the
    experts and the gated shared expert (``mlp``), the norms under
    ``residual``."""
    from diff3d_tpu.config import gdn_moe_test_config
    from diff3d_tpu.models import build_model
    from diff3d_tpu.sampling import Sampler

    cfg = gdn_moe_test_config()
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
    low = Sampler(model, params, cfg, sampler_kind="ddim",
                  steps=2).lower_step_many(1, 2)
    with no_compile_cache():
        text = low.compile().as_text()
    ops = op_names(text)
    tags = [op_class(n)[0] for _, n in ops]
    assert set(tags) - {None} == DELTA_SCOPES | {
        "rope", "attention", "mlp", "moe_router", "experts", "residual",
        "patch_embed", "conditioning", "sampler", "record"}
    assert tags.count(None) / len(tags) < 0.15
    named_untagged = [n for (_, n), t in zip(ops, tags)
                      if t is None and "/" in n]
    assert len(named_untagged) / len(tags) < 0.002, named_untagged[:5]


def test_every_class_is_in_the_compiled_programs_and_few_ops_have_none(
        programs):
    train, view = programs["lower"]()
    with no_compile_cache():
        texts = {"train": train.compile().as_text(),
                 "view": view.compile().as_text()}
    seen = set()
    # by count; what is left has no op_name at all: copies, broadcasts and
    # fusions the compiler made (readings at this size: 0.10 and 0.035)
    for name, limit in (("train", 0.15), ("view", 0.08)):
        ops = op_names(texts[name])
        tags = [op_class(n)[0] for _, n in ops]
        seen |= set(tags)
        assert tags.count(None) / len(tags) < limit, name
        named_untagged = [n for (_, n), t in zip(ops, tags)
                          if t is None and n]
        assert len(named_untagged) / len(tags) < 0.002, named_untagged[:5]
    assert seen - {None} == (set(SCOPES) - TOKEN_SCOPES - HYBRID_SCOPES
                             - MLA_SCOPES - DELTA_SCOPES)
    # forward and backward share a tag; the backward is told by transpose(
    bwd = {op_class(n) for _, n in op_names(texts["train"])}
    for tag in ("conv", "film", "groupnorm", "attention", "conditioning",
                "dropout", "residual"):
        assert (tag, False) in bwd and (tag, True) in bwd, tag


SCOPED_MODULES = ("diff3d_tpu.models.layers", "diff3d_tpu.models.xunet",
                  "diff3d_tpu.models.conditioning",
                  "diff3d_tpu.diffusion.core", "diff3d_tpu.train.step",
                  "diff3d_tpu.sampling.runtime")


@contextlib.contextmanager
def scopes_off(monkeypatch):
    with monkeypatch.context() as m:
        for mod in SCOPED_MODULES:
            m.setattr(sys.modules[mod], "scope",
                      lambda tag: contextlib.nullcontext())
        yield


def test_scopes_are_metadata_only(programs, monkeypatch):
    """The lowered programs are the same text without their locations,
    and the model computes the same bits, with the scopes taken out."""
    with_scopes = [low.as_text() for low in programs["lower"]()]
    assert "d3d." not in with_scopes[0]          # locations are not printed
    cfg, model, params = (programs[k] for k in ("cfg", "model", "params"))
    B, H = 2, cfg.model.H
    key = jax.random.PRNGKey(1)
    batch = {"x": jax.random.normal(key, (B, H, H, 3)),
             "z": jax.random.normal(jax.random.fold_in(key, 1),
                                    (B, H, H, 3)),
             "logsnr": jnp.zeros((B, 2)), "R": jnp.tile(jnp.eye(3),
                                                        (B, 2, 1, 1)),
             "t": jnp.ones((B, 2, 3)), "K": jnp.tile(jnp.eye(3), (B, 1, 1))}

    def forward():
        return np.asarray(jax.jit(lambda p: model.apply(
            {"params": p}, batch, cond_mask=jnp.ones((B,), bool)))(params))

    out = forward()
    with scopes_off(monkeypatch):
        without = [low.as_text() for low in programs["lower"]()]
        out_off = forward()
    assert with_scopes == without
    assert out.tobytes() == out_off.tobytes()


def test_conditioning_counters_read_the_sharing_of_each_program(programs):
    """`conditioning.groups` / `.examples` are added once per trace of the
    model: 8 guidance weights share a row in the sampler's view program
    (G = 2 rows for 16 examples per object), nothing is shared in the
    train step (a pose, a logSNR and a mask draw per example)."""
    def traced(lower):
        before = RECORDER.counters()
        lower()
        after = RECORDER.counters()
        return tuple(after.get(k, 0) - before.get(k, 0)
                     for k in ("conditioning.groups",
                               "conditioning.examples"))

    groups, examples = traced(programs["lower_train"])
    assert groups > 0 and examples == groups            # 1 example a row
    n_w = len(programs["cfg"].diffusion.guidance_weights)
    groups, examples = traced(programs["lower_view"])
    assert (groups, examples) == (2, 2 * n_w) and examples // groups == 8


# ------------------------------------------------- spans of the two loops


def test_span_counts_per_train_step_and_log_record(tmp_path):
    from diff3d_tpu.cli import train_cli

    mark = time.perf_counter()
    steps = 4
    train_cli.main(["--config", "test", "--synthetic", "--steps",
                    str(steps), "--workdir", str(tmp_path),
                    "--profile_steps", "2:4"])
    dispatch = spans_since(mark, "train.dispatch")
    assert len(dispatch) == steps
    assert [s.id for s in dispatch] == [1, 2, 3, 4]
    waits = spans_since(mark, "prefetch.wait")
    assert [s.id for s in waits] == [0, 1, 2, 3]
    # the producer runs ahead by the queue's depth, and no further
    depth = make_tiny_config().data.prefetch
    for name in ("loader.batch", "prefetch.put"):
        n = len(spans_since(mark, name))
        assert steps <= n <= steps + depth + 1, (name, n)
    recs = [json.loads(x) for x in
            (tmp_path / "metrics.jsonl").read_text().splitlines()]
    recs = [r for r in recs if "loss" in r]
    assert recs and all(r["input_wait_s"] >= 0 and r["starved"] >= 0
                        for r in recs)
    total = sum(s.end - s.start for s in waits)
    assert sum(r["input_wait_s"] for r in recs) == pytest.approx(
        total, rel=1e-6)
    # --profile_steps reached profile_window: the trace and its reduction
    by_scope = json.loads(
        (tmp_path / "profile" / "by_scope.json").read_text())
    assert by_scope["busy_s"] > 0 and by_scope["window_s"] > 0
    assert profiling.find_xplane(str(tmp_path / "profile"))
    with pytest.raises(SystemExit):
        train_cli.build_parser().parse_args(["--profile_steps", "0:3"])
    with pytest.raises(SystemExit):
        train_cli.build_parser().parse_args(["--profile_steps", "3"])
    # no start_trace of the trainer's own: one capture path
    src = open(os.path.join(ROOT, "diff3d_tpu", "train",
                            "trainer.py")).read()
    assert "start_trace" not in src and "stop_trace" not in src


def test_span_counts_per_synthesize_many_call(programs):
    cfg = programs["cfg"]
    sampler = Sampler(programs["model"], programs["params"], cfg, steps=4)
    ds = SyntheticDataset(num_objects=2, num_views=3, imgsize=cfg.model.H)
    views = [ds.all_views(0), ds.all_views(1)]
    keys = [jax.random.PRNGKey(i) for i in range(2)]
    for call in (1, 2):
        mark = time.perf_counter()
        out = sampler.synthesize_many(views, keys, max_views=3)
        assert out.shape[:2] == (2, 2)
        got = {n: spans_since(mark, "sampler." + n)
               for n in ("stage", "dispatch", "wait", "fetch")}
        assert {n: len(v) for n, v in got.items()} == {
            "stage": 1, "dispatch": 2, "wait": 1, "fetch": 1}
        assert {s.id for v in got.values() for s in v} == {call}
        order = [got["stage"][0], *got["dispatch"], got["wait"][0],
                 got["fetch"][0]]
        assert all(a.end <= b.start for a, b in zip(order, order[1:]))
    mark = time.perf_counter()
    sampler.synthesize(views[0], keys[0], max_views=2)
    assert len(spans_since(mark, "sampler.dispatch")) == 1
    assert spans_since(mark, "sampler.fetch")[0].id == 3


# ------------------------------------------------------------ the reducer


def test_reduce_scopes_arithmetic_on_a_recorded_trace():
    """Plain form, times in ns.  A while op (1000..9000) encloses a
    forward conv (1000..4000), a backward conv (4000..6000) and an op with
    no tag (6000..7000); then a gap of 3000 under a host span, an
    optimizer op, a gap of 500 under nothing, an EMA op."""
    conv = "jit(step_fn)/d3d.grad_accum/while/body/jvp(d3d.conv)/conv"
    conv_t = ("jit(step_fn)/d3d.grad_accum/while/body/"
              "transpose(jvp(d3d.conv))/conv")
    trace = {"device": {"/device:TPU:0": [
        ["%while.1 = (s32[]) while(...)", 1000.0, 8000.0,
         "jit(step_fn)/d3d.grad_accum/while", 0, 0],
        ["%fusion.1 = bf16[8,8]{1,0} fusion(...)", 1000.0, 3000.0, conv,
         100, 10],
        ["%fusion.2 = bf16[8,8]{1,0} fusion(...)", 4000.0, 2000.0, conv_t,
         200, 20],
        ["%copy.3 = f32[4]{0} copy(...)", 6000.0, 1000.0, "", 0, 5],
        ["%fusion.4 = f32[4]{0} fusion(...)", 12000.0, 1000.0,
         "jit(step_fn)/d3d.optimizer/mul", 7, 8],
        ["%fusion.5 = f32[4]{0} fusion(...)", 13500.0, 500.0,
         "jit(step_fn)/d3d.ema/add", 1, 2],
    ], "/device:TPU:1": []},
        "host": [["train.dispatch", 9500.0, 2000.0],
                 ["outer", 0.0, 20000.0]]}
    r = reduce_scopes(trace)
    ns = 1e-9
    assert r["by_class"]["conv"] == {
        "fwd_s": pytest.approx(3000 * ns), "bwd_s": pytest.approx(2000 * ns),
        "flops": 300, "bytes": 30}
    # the loop's own time: 8000 less the 6000 it encloses
    assert r["by_class"]["grad_accum"]["fwd_s"] == pytest.approx(2000 * ns)
    assert r["by_class"]["optimizer"]["fwd_s"] == pytest.approx(1000 * ns)
    assert r["by_class"]["ema"]["fwd_s"] == pytest.approx(500 * ns)
    assert r["unscoped_s"] == pytest.approx(1000 * ns)
    assert r["unscoped_top"] == [["copy.3 f32[4]", pytest.approx(1000 * ns)]]
    assert r["busy_s"] == pytest.approx(9500 * ns)
    assert r["window_s"] == pytest.approx(13000 * ns)
    # innermost covering span by the gap's middle
    assert r["idle_by_span"] == {
        "train.dispatch": pytest.approx(3000 * ns),
        "outer": pytest.approx(500 * ns)}
    by_class_s = sum(c["fwd_s"] + c["bwd_s"]
                     for c in r["by_class"].values())
    assert by_class_s + r["unscoped_s"] == pytest.approx(r["busy_s"])
    empty = reduce_scopes({"device": {}, "host": []})
    assert empty["busy_s"] == 0 and empty["by_class"] == {}


def test_profile_window_reduces_a_real_capture(tmp_path):
    """A CPU capture: ops come with hlo_op / hlo_module only and are named
    through the HLO protos the trace itself carries."""
    from diff3d_tpu.utils import profile_window

    def loss(x):
        with scope("conv"):
            y = x @ x
        with scope("film"):
            y = jnp.tanh(y) * 2.0
        return y.sum()

    f = jax.jit(jax.grad(loss))
    x = jnp.ones((256, 256))
    with no_compile_cache():
        f(x).block_until_ready()
    logdir = str(tmp_path / "prof")
    with profile_window(logdir):
        with span("outer", id=1):
            for _ in range(3):
                f(x).block_until_ready()
                time.sleep(0.002)
    r = json.loads(open(os.path.join(logdir, "by_scope.json")).read())
    assert r == json.loads(json.dumps(
        profiling.scope_seconds(profiling.find_xplane(logdir))))
    assert r["by_class"]["conv"]["bwd_s"] > 0
    assert set(r["by_class"]) <= {"conv", "film"}
    scoped = sum(c["fwd_s"] + c["bwd_s"] for c in r["by_class"].values())
    assert scoped > 0.5 * r["busy_s"]
    assert 0 < r["busy_s"] <= r["window_s"]
    # the d3d: span is in the same trace, and the sleeps are idle under it
    trace = profiling.load_xplane(profiling.find_xplane(logdir))
    assert [ev[0] for ev in trace["host"]] == ["outer"]
    assert r["idle_by_span"].get("outer", 0) > 0.002
