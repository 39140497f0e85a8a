"""The analysis subsystem, tested from both sides.

For every lint rule (GL101–GL107) there is a known-BAD fixture that must
fire and a known-GOOD fixture that must stay silent — the silent side
matters as much as the loud one, because each rule's whitelist encodes a
JAX idiom this repo actually uses (re-stored rng carries, static
shape args, ``is None`` checks on traced params).  Then the suppression
grammar, the baseline round-trip, and the runtime harness: sentinel
accuracy under a forced retrace, the compile-budget marker, the transfer
guard, and the donation guards against a real donating jit.

The last test is the tier-1 gate itself: the repo's own lint run must be
clean (zero unsuppressed findings over ``diff3d_tpu/`` and ``tools/``).
"""

import os
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from diff3d_tpu.analysis import lint_source, lint_paths
from diff3d_tpu.analysis.lint import (DEFAULT_TARGETS, apply_baseline,
                                      load_baseline, write_baseline)
from diff3d_tpu.analysis.runtime import (CompileBudgetExceeded,
                                         RecompilationSentinel,
                                         assert_consumed, assert_live,
                                         compile_budget,
                                         no_host_transfers, owned)

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _findings(src, rule=None):
    out = lint_source("<fixture>.py", textwrap.dedent(src))
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


def _live(src, rule=None):
    return [f for f in _findings(src, rule) if not f.suppressed]


# ---------------------------------------------------------------------------
# GL001 / GL002: parse failures and reasonless suppressions
# ---------------------------------------------------------------------------


def test_gl001_syntax_error_is_a_finding():
    (f,) = _live("def f(:\n", "GL001")
    assert f.severity == "error" and "parse" in f.message


def test_gl002_suppression_without_reason():
    src = """
        import jax

        def f(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.uniform(key, (2,))  # graftlint: disable=GL101
            return a + b
    """
    assert not _live(src, "GL101")          # the suppression still works
    (f,) = _live(src, "GL002")
    assert "no (reason)" in f.message


# ---------------------------------------------------------------------------
# GL101: rng key reuse
# ---------------------------------------------------------------------------


def test_gl101_fires_on_key_reuse():
    src = """
        import jax

        def f(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.uniform(key, (2,))
            return a + b
    """
    (f,) = _live(src, "GL101")
    assert "key" in f.message


def test_gl101_silent_on_split_discipline():
    src = """
        import jax

        def f(key):
            k1, k2 = jax.random.split(key)
            a = jax.random.normal(k1, (2,))
            b = jax.random.uniform(k2, (2,))
            return a + b
    """
    assert not _live(src, "GL101")


def test_gl101_silent_on_restored_carry():
    # The repo's sampling-loop idiom: `rng, k = split(rng)` re-arms rng.
    src = """
        import jax

        def g(rng):
            for _ in range(3):
                rng, k = jax.random.split(rng)
                x = jax.random.normal(k, (2,))
            return x
    """
    assert not _live(src, "GL101")


def test_gl101_sees_module_alias():
    src = """
        import jax.random as jr

        def f(key):
            a = jr.normal(key, (2,))
            b = jr.normal(key, (2,))
            return a + b
    """
    assert len(_live(src, "GL101")) == 1


# ---------------------------------------------------------------------------
# GL102: Python branch on a traced value
# ---------------------------------------------------------------------------


def test_gl102_fires_on_traced_if():
    src = """
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """
    (f,) = _live(src, "GL102")
    assert f.severity == "error"


def test_gl102_silent_on_static_argnums():
    src = """
        from functools import partial
        import jax

        @partial(jax.jit, static_argnums=(1,))
        def f(x, n):
            if n > 2:
                return x * n
            return x
    """
    assert not _live(src, "GL102")


def test_gl102_silent_on_none_and_shape_checks():
    src = """
        import jax

        @jax.jit
        def f(x, y=None):
            if y is None:
                return x
            if x.shape[0] > 2:
                return x + y
            return x - y
    """
    assert not _live(src, "GL102")


def test_gl102_fires_inside_scan_body():
    src = """
        import jax

        def outer(xs):
            def body(c, x):
                if x > 0:
                    c = c + x
                return c, x
            return jax.lax.scan(body, 0.0, xs)
    """
    assert len(_live(src, "GL102")) == 1


# ---------------------------------------------------------------------------
# GL103: host sync inside a traced context
# ---------------------------------------------------------------------------


def test_gl103_fires_on_float_of_traced():
    src = """
        import jax

        @jax.jit
        def f(x):
            return float(x) * 2
    """
    assert len(_live(src, "GL103")) == 1


def test_gl103_fires_on_item_and_asarray_in_jit():
    src = """
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            v = x.item()
            return np.asarray(x) + v
    """
    assert len(_live(src, "GL103")) == 2


def test_gl103_silent_outside_traced_context():
    src = """
        import numpy as np

        def report(x):
            return float(np.asarray(x).mean())
    """
    assert not _live(src, "GL103")


# ---------------------------------------------------------------------------
# GL104: read of a donated buffer
# ---------------------------------------------------------------------------

_DONATING_PRELUDE = """
    import jax

    def g(a, b):
        return a + b, b

    step = jax.jit(g, donate_argnums=(0,))
"""


def test_gl104_fires_on_read_after_donation():
    src = _DONATING_PRELUDE + """
    def run(x, y):
        out, new = step(x, y)
        return out + x
    """
    (f,) = _live(src, "GL104")
    assert "donat" in f.message


def test_gl104_silent_when_reading_returned_buffer():
    src = _DONATING_PRELUDE + """
    def run(x, y):
        out, new = step(x, y)
        return out + new
    """
    assert not _live(src, "GL104")


def test_gl104_loop_carry_rebind_is_clean_but_leak_fires():
    clean = _DONATING_PRELUDE + """
    def loop(x, y):
        for _ in range(3):
            out, x = step(x, y)
        return x
    """
    assert not _live(clean, "GL104")
    leak = _DONATING_PRELUDE + """
    def loop(x, y):
        for _ in range(3):
            out, new = step(x, y)
        return out
    """
    # x is donated on iteration 1 and re-donated (a read) on iteration 2.
    assert _live(leak, "GL104")


# ---------------------------------------------------------------------------
# GL105: shape-like param traced
# ---------------------------------------------------------------------------


def test_gl105_fires_on_traced_shape_param():
    src = """
        import jax
        import jax.numpy as jnp

        def f(x, shape):
            return jnp.zeros(shape) + x

        g = jax.jit(f)
    """
    (f,) = _live(src, "GL105")
    assert f.severity == "warning"


def test_gl105_silent_when_static():
    src = """
        import jax
        import jax.numpy as jnp

        def f(x, shape):
            return jnp.zeros(shape) + x

        g = jax.jit(f, static_argnames=("shape",))
    """
    assert not _live(src, "GL105")


# ---------------------------------------------------------------------------
# GL106: timing device work without a sync
# ---------------------------------------------------------------------------

_TIMING_PRELUDE = """
    import time
    import jax

    f = jax.jit(lambda x: x * 2)
"""


def test_gl106_fires_on_unsynced_timing():
    src = _TIMING_PRELUDE + """
    def bench(x):
        t0 = time.perf_counter()
        y = f(x)
        dt = time.perf_counter() - t0
        return dt, y
    """
    (f,) = _live(src, "GL106")
    assert "dispatch" in f.message


def test_gl106_silent_with_block_until_ready():
    src = _TIMING_PRELUDE + """
    def bench(x):
        t0 = time.perf_counter()
        y = jax.block_until_ready(f(x))
        dt = time.perf_counter() - t0
        return dt, y
    """
    assert not _live(src, "GL106")


def test_gl106_silent_on_host_only_timing():
    src = """
        import time

        def bench(n):
            t0 = time.perf_counter()
            total = sum(range(n))
            dt = time.perf_counter() - t0
            return dt, total
    """
    assert not _live(src, "GL106")


# ---------------------------------------------------------------------------
# GL107: mutable state under trace
# ---------------------------------------------------------------------------


def test_gl107_fires_on_mutable_default_and_traced_global():
    src = """
        import jax

        COUNT = 0

        def h(x, cache={}):
            return cache.setdefault("k", x)

        @jax.jit
        def f(x):
            global COUNT
            COUNT += 1
            return x
    """
    found = _live(src, "GL107")
    assert len(found) == 2
    severities = sorted(f.severity for f in found)
    assert severities == ["error", "warning"]


def test_gl107_silent_on_none_default_and_untraced_global():
    src = """
        CONFIG = None

        def setup(x, cache=None):
            global CONFIG
            CONFIG = x
            return cache
    """
    assert not _live(src, "GL107")


# ---------------------------------------------------------------------------
# Suppression grammar
# ---------------------------------------------------------------------------

_BAD_RNG = """
    import jax

    def f(key):
        a = jax.random.normal(key, (2,))
        b = jax.random.uniform(key, (2,)){supp}
        return a + b
"""


def test_suppression_same_line_with_reason():
    src = _BAD_RNG.format(
        supp="  # graftlint: disable=GL101(fixture: reuse is the point)")
    fs = _findings(src, "GL101")
    assert len(fs) == 1 and fs[0].suppressed
    assert fs[0].suppress_reason == "fixture: reuse is the point"
    assert not _live(src, "GL002")


def test_suppression_next_line():
    src = """
        import jax

        def f(key):
            a = jax.random.normal(key, (2,))
            # graftlint: disable-next-line=GL101(fixture)
            b = jax.random.uniform(key, (2,))
            return a + b
    """
    fs = _findings(src, "GL101")
    assert len(fs) == 1 and fs[0].suppressed


def test_suppression_file_scope_and_all():
    src = """
        # graftlint: disable-file=all(fixture file, every rule off)
        import jax

        @jax.jit
        def f(x, key):
            if x > 0:
                a = jax.random.normal(key, (2,))
                b = jax.random.uniform(key, (2,))
                return float(a + b)
            return 0.0
    """
    fs = _findings(src)
    assert fs and all(f.suppressed for f in fs)


def test_suppression_reason_with_nested_parens():
    src = _BAD_RNG.format(
        supp="  # graftlint: disable=GL101(sync via float(jnp.sum(x)) ok)")
    fs = _findings(src, "GL101")
    assert fs[0].suppress_reason == "sync via float(jnp.sum(x)) ok"
    assert not _live(src, "GL002")


def test_suppression_does_not_cover_other_rules():
    src = """
        import jax

        @jax.jit
        def f(x):  # graftlint: disable=GL101(wrong rule on purpose)
            if x > 0:
                return x
            return -x
    """
    assert len(_live(src, "GL102")) == 1


# ---------------------------------------------------------------------------
# Baseline round-trip
# ---------------------------------------------------------------------------


def test_baseline_round_trip(tmp_path):
    bad = textwrap.dedent("""
        import jax

        def f(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.uniform(key, (2,))
            return a + b
    """)
    mod = tmp_path / "legacy.py"
    mod.write_text(bad)
    baseline_path = str(tmp_path / "baseline.json")

    findings = lint_paths([str(mod)])
    assert [f.rule for f in findings] == ["GL101"]
    n = write_baseline(baseline_path, findings, str(tmp_path))
    assert n == 1

    baseline = load_baseline(baseline_path)
    masked = apply_baseline(lint_paths([str(mod)]), baseline,
                            str(tmp_path))
    assert masked[0].suppressed and masked[0].suppress_reason == "baseline"

    # Editing the violating line invalidates its fingerprint: the
    # finding comes back live instead of hiding behind a stale entry.
    mod.write_text(bad.replace("jax.random.uniform(key, (2,))",
                               "jax.random.uniform(key, (4,))"))
    fresh = apply_baseline(lint_paths([str(mod)]), baseline,
                           str(tmp_path))
    assert [f.rule for f in fresh] == ["GL101"] and not fresh[0].suppressed


def test_missing_baseline_is_empty(tmp_path):
    assert load_baseline(str(tmp_path / "nope.json")) == set()


# ---------------------------------------------------------------------------
# Recompilation sentinel
# ---------------------------------------------------------------------------


def test_sentinel_counts_retraces_exactly():
    f = jax.jit(lambda x: x * 2.0)
    s = RecompilationSentinel()
    s.track("f", f)
    jax.block_until_ready(f(jnp.ones((4,))))
    assert s.counts() == {"f": 1}
    jax.block_until_ready(f(jnp.ones((4,))))     # same shape: cached
    assert s.counts() == {"f": 1}
    jax.block_until_ready(f(jnp.ones((5,))))     # forced retrace
    assert s.counts() == {"f": 2} and s.total() == 2
    with pytest.raises(CompileBudgetExceeded, match="2 > 1"):
        s.assert_budget(1)
    s.assert_budget(2)
    s.reset()
    assert s.total() == 0


def test_sentinel_zero_point_ignores_warm_cache():
    f = jax.jit(lambda x: x - 1.0)
    jax.block_until_ready(f(jnp.ones((3,))))     # warm before tracking
    s = RecompilationSentinel()
    s.track("f", f)
    jax.block_until_ready(f(jnp.ones((3,))))
    assert s.counts() == {"f": 0}


def test_sentinel_rejects_plain_functions():
    with pytest.raises(TypeError, match="_cache_size"):
        RecompilationSentinel().track("plain", lambda x: x)


def test_compile_budget_context_manager():
    f = jax.jit(lambda x: x + 3.0)
    with compile_budget(1, f=f):
        jax.block_until_ready(f(jnp.ones((4,))))
    with pytest.raises(CompileBudgetExceeded):
        with compile_budget(0, f=f):
            jax.block_until_ready(f(jnp.ones((6,))))


@pytest.mark.compile_budget(1)
def test_compile_budget_marker_enforces(compile_sentinel):
    f = jax.jit(lambda x: x * 0.5)
    compile_sentinel.track("f", f)
    jax.block_until_ready(f(jnp.ones((4,))))
    jax.block_until_ready(f(jnp.ones((4,))))
    assert compile_sentinel.counts() == {"f": 1}


# ---------------------------------------------------------------------------
# Transfer and donation guards
# ---------------------------------------------------------------------------


def test_no_host_transfers_clean_on_device_resident_work():
    f = jax.jit(lambda x: x * 2.0)
    x = jnp.ones((4,))
    jax.block_until_ready(f(x))
    with no_host_transfers():
        jax.block_until_ready(f(x))


def test_no_host_transfers_faults_on_host_staging():
    f = jax.jit(lambda x: x * 2.0)
    jax.block_until_ready(f(jnp.ones((4,))))
    with pytest.raises(Exception, match="[Dd]isallow"):
        with no_host_transfers():
            f(np.ones((4,), np.float32))         # numpy arg: host upload


def test_donation_guards_on_a_donating_jit():
    g = jax.jit(lambda a: a + 1.0, donate_argnums=(0,))
    a = owned(np.ones((8,), np.float32))
    b = g(a)
    jax.block_until_ready(b)
    assert_consumed(a)
    assert_live(b)
    with pytest.raises(AssertionError, match="still live"):
        assert_consumed(b)
    with pytest.raises(AssertionError, match="deleted"):
        assert_live(a)


def test_owned_copies_host_passes_device_through():
    host = np.arange(6, dtype=np.float32)
    dev = owned(host)
    assert isinstance(dev, jax.Array)
    np.testing.assert_array_equal(np.asarray(dev), host)
    # Donating the owned copy must leave the caller's numpy memory alone.
    g = jax.jit(lambda a: a * 2.0, donate_argnums=(0,))
    jax.block_until_ready(g(dev))
    np.testing.assert_array_equal(host, np.arange(6, dtype=np.float32))
    already = jnp.ones((3,))
    assert owned(already) is already


# ---------------------------------------------------------------------------
# The tier-1 gate: the repo's own tree lints clean
# ---------------------------------------------------------------------------


def test_tools_import_safely():
    """Every ``tools/*.py`` must import without side effects (no work at
    module scope, no cwd-dependent sys.path mutation) — importing from a
    foreign cwd is exactly what the lint CLI and pytest collection do."""
    import glob
    import importlib.util
    paths = sorted(glob.glob(os.path.join(_REPO_ROOT, "tools", "*.py")))
    assert paths, "no tools found"
    for path in paths:
        name = "_toolcheck_" + os.path.basename(path)[:-3]
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert callable(getattr(mod, "main", None)), (
            f"{path}: tools expose their work as main(), "
            "run only under __main__")


def test_repo_lints_clean():
    """Every finding in the shipped tree is fixed or carries an inline
    reason — the same invariant `python -m diff3d_tpu.analysis` gates in
    CI, pinned here so plain `pytest` enforces it too."""
    targets = [os.path.join(_REPO_ROOT, t) for t in DEFAULT_TARGETS]
    targets = [t for t in targets if os.path.exists(t)]
    assert targets, "lint targets missing from the checkout"
    live = [f for f in lint_paths(targets) if not f.suppressed]
    assert not live, "unsuppressed graftlint findings:\n" + "\n".join(
        f.render() for f in live)
