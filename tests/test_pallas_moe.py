"""The routed experts' Pallas cores (ops/pallas_moe.py: op ``'expert_ffn'``,
the grouped kernel; ops ``'expert_rows'`` and ``'expert_combine'``, how
rows move into the padded layout and back out as the gated sum) against
the XLA expressions they stand for, in interpret mode on the CPU: through
``models/moe.expert_outputs`` (``impl='pallas'`` asks all three) and each
alone, on routings chosen by hand, under the sampler's ``vmap``, under
``grad``; what ``supports`` refuses and what the registry does with an
explicit ``'pallas'`` there; what ``'auto'`` resolves to by backend and
by the share of the experts a layer holds.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from diff3d_tpu.models import moe
from diff3d_tpu.ops import dispatch, pallas_moe
from diff3d_tpu.utils.profiling import RECORDER

E, K, D, F, M = 8, 2, 128, 128, 16          # one lane tile, bf16's sublanes
T = 128
HIGHEST = jax.lax.Precision.HIGHEST


def _ids(counts, seed=0):
    """``[T, K]`` expert ids with ``counts[e]`` assignments to expert
    ``e``, in a seeded order (``expert_outputs`` asks nothing more of a
    routing than ids and gates)."""
    flat = np.repeat(np.arange(len(counts)), counts)
    assert flat.size % K == 0
    flat = np.random.default_rng(seed).permutation(flat)
    return jnp.asarray(flat.reshape(-1, K), jnp.int32)


def _most(A, share, e, n=E):
    """Expert ``e`` takes ``share`` of ``A`` assignments, the rest even."""
    big = int(A * share)
    rest = np.full(n, (A - big) // (n - 1))
    rest[e] = big
    rest[(e + 1) % n] += A - rest.sum()
    return rest


# name -> (counts over all experts, held = (first, count))
ROUTINGS = {
    "even": (np.full(E, T * K // E), (0, E)),
    "one_expert_takes_nine_tenths": (_most(T * K, 0.9, 3), (0, E)),
    "empty_experts": (np.array([0, 100, 0, 0, 90, 0, 66, 0]), (0, E)),
    # 16 experts routed over, 8 held here: 5/8 of the assignments belong
    # to experts held elsewhere and sort into the bucket past the last
    "held_elsewhere": (np.array([20] * 4 + [12] * 8 + [20] * 4), (4, 8)),
    "first_expert_held_alone": (_most(T * K, 0.5, 0), (0, 1)),
    # every run one row over a whole number of blocks: 8 runs of 33 rows
    # fill 24 blocks, all but one of the bound ceil(264 / 16) + 8 = 25
    # (no routing fills the bound itself: the padding is under a block a
    # run, under E blocks in all)
    "runs_fill_the_static_bound": (np.full(E, 33), (0, E)),
    "no_assignment_held_here": (np.array([64] * 4 + [0] * 4), (4, 4)),
}


def _operands(name, dtype=jnp.float32, seed=0):
    counts, (first, held) = ROUTINGS[name]
    ids = _ids(counts, seed)
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    tokens = ids.shape[0]
    x = jax.random.normal(k[0], (tokens, D))
    gates = jax.nn.softmax(jax.random.normal(k[1], (tokens, K)), axis=-1)
    w = [jax.random.normal(kk, s) / np.sqrt(s[1]) for kk, s in
         zip(k[2:], [(held, D, F), (held, D, F), (held, F, D)])]
    return (x.astype(dtype), ids, gates, *[a.astype(dtype) for a in w]), first


def _run(args, first, impl):
    return moe.expert_outputs(*args, first=first, block=M, impl=impl)


def _written_out(args, first):
    """The layer's sum with no layout at all, float32 at ``HIGHEST``."""
    x, ids, gates, wg, wu, wd = (a.astype(jnp.float32)
                                 if a.dtype != jnp.int32 else a for a in args)
    dot = lambda a, b: jnp.dot(a, b, precision=HIGHEST)  # noqa: E731
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(wg.shape[0]):
        gate = jnp.where(ids == first + e, gates, 0.0).sum(axis=-1)
        out += gate[:, None] * dot(jax.nn.silu(dot(x, wg[e]))
                                   * dot(x, wu[e]), wd[e])
    return out


@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_kernel_is_the_scan_in_float32(routing):
    args, first = _operands(routing)
    got = _run(args, first, "pallas")
    want = _run(args, first, "xla")
    assert got.dtype == want.dtype and got.shape == want.shape
    assert bool(jnp.isfinite(got).all())
    # float32 on both sides: only the order of the sums may differ
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    np.testing.assert_allclose(got, _written_out(args, first), atol=2e-5,
                               rtol=0)
    if routing != "no_assignment_held_here":
        assert float(jnp.abs(want).mean()) > 0.01
    else:
        assert not bool(jnp.abs(got).any())


@pytest.mark.parametrize("routing", list(ROUTINGS))
def test_kernel_in_bf16_is_no_farther_from_float32_than_the_scan(routing):
    """Same operands, same rounding points (float32 accumulation, ``h``
    cast before the down matmul, the result cast once): against the
    layer written out in float32 at ``HIGHEST`` the kernel's error is the
    scan's."""
    args, first = _operands(routing, jnp.bfloat16)
    exact = _written_out(args, first)
    gap = lambda impl: float(jnp.abs(  # noqa: E731
        _run(args, first, impl).astype(jnp.float32) - exact).mean())
    kernel, scan = gap("pallas"), gap("xla")
    assert kernel <= scan * 1.02 + 1e-6, (kernel, scan)
    assert scan < 0.02 * float(jnp.abs(exact).mean()) + 1e-6


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_two_objects_under_the_samplers_vmap(dtype):
    """``Sampler`` maps the view program over objects with the parameters
    unbatched: tokens, ids and gates carry the object axis, so the
    kernel's prefetched tables do (jax then loops over the objects)."""
    a, first = _operands("one_expert_takes_nine_tenths", dtype, seed=1)
    b, _ = _operands("empty_experts", dtype, seed=2)
    xs, ids, gates = (jnp.stack([a[i], b[i]]) for i in range(3))

    def both(impl):
        return jax.jit(jax.vmap(lambda x, i, g: moe.expert_outputs(
            x, i, g, *a[3:], first=first, block=M, impl=impl)))(xs, ids,
                                                                gates)
    got, want = both("pallas"), both("xla")
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got.astype(jnp.float32),
                               want.astype(jnp.float32), atol=tol, rtol=0)
    np.testing.assert_allclose(
        got[0].astype(jnp.float32),
        _run((xs[0], ids[0], gates[0], *a[3:]), first,
             "xla").astype(jnp.float32), atol=tol, rtol=0)
    assert float(jnp.abs(got[0] - got[1]).mean()) > 0.01


def test_chunks_mapped_inside_the_objects_vmap():
    """The layer's own nesting: ``lax.map`` over chunks of tokens inside
    the sampler's ``vmap`` over objects."""
    a, first = _operands("held_elsewhere", seed=3)
    x, ids, gates = (jnp.stack([jnp.stack([v, v[::-1]]),
                                jnp.stack([v[::-1], v])]) for v in a[:3])

    def nested(impl):
        chunk = lambda s: moe.expert_outputs(  # noqa: E731
            *s, *a[3:], first=first, block=M, impl=impl)
        return jax.vmap(lambda *o: jax.lax.map(chunk, o))(x, ids, gates)
    np.testing.assert_allclose(nested("pallas"), nested("xla"), atol=2e-5,
                               rtol=0)


@pytest.mark.parametrize("routing", ["even", "held_elsewhere",
                                     "runs_fill_the_static_bound"])
def test_gradient_through_the_custom_vjp_is_the_scans(routing):
    """Forward the kernel, backward the scan's own VJP on the saved
    operands: the gradients of the two cores agree as their forwards do
    (the loss is squared, so the backward needs the kernel's output)."""
    args, first = _operands(routing, seed=4)
    x, ids, gates, *w = args

    def loss(impl):
        return lambda x, gates, w: jnp.sum(moe.expert_outputs(
            x, ids, gates, *w, first=first, block=M, impl=impl) ** 2)
    got = jax.grad(loss("pallas"), argnums=(0, 1, 2))(x, gates, w)
    want = jax.grad(loss("xla"), argnums=(0, 1, 2))(x, gates, w)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.abs(r).mean()) > 1e-4
        np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)


def test_rows_past_the_last_run_reach_no_result():
    """The blocks past the last run are skipped, not multiplied: whatever
    stands in their rows (here NaN) leaves the blocks in use as the scan
    gives them."""
    args, first = _operands("empty_experts", seed=5)
    x, ids, _, wg, wu, wd = args
    n_blocks = -(-ids.size // M) + E
    counts = ROUTINGS["empty_experts"][0]
    ends = jnp.cumsum(jnp.asarray(-(-counts // M) * M, jnp.int32))
    used = int(ends[-1]) // M
    assert 0 < used < n_blocks
    e_blk = jnp.minimum((jnp.arange(n_blocks)[:, None] * M
                         >= ends[None, :]).sum(axis=1), E - 1)
    rows = jax.random.normal(jax.random.PRNGKey(6), (n_blocks, M, D))
    got = pallas_moe.expert_ffn(rows.at[used:].set(jnp.nan), e_blk, ends,
                                wg, wu, wd)
    want = pallas_moe.expert_ffn_reference(rows, e_blk, ends, wg, wu, wd)
    np.testing.assert_allclose(got[:used], want[:used], atol=2e-5, rtol=0)


def _shapes(m=M, d=D, f=F, e=E, dtype=jnp.float32, wdtype=None, n=24):
    sds = jax.ShapeDtypeStruct
    wdtype = wdtype or dtype
    return (sds((n, m, d), dtype), sds((n,), jnp.int32),
            sds((e,), jnp.int32), sds((e, d, f), wdtype),
            sds((e, d, f), wdtype), sds((e, f, d), wdtype))


def test_supports_takes_the_cells_shapes_and_these():
    assert pallas_moe.expert_ffn_supports(*_shapes())
    assert pallas_moe.expert_ffn_supports(*_shapes(dtype=jnp.bfloat16))
    # keye_vl2_tok128: 384 blocks of 256 rows, 128 experts of 2048 x 768
    assert pallas_moe.expert_ffn_supports(*_shapes(
        m=256, d=2048, f=768, e=128, dtype=jnp.bfloat16, n=384))
    assert pallas_moe.expert_ffn_supports(*_shapes(
        m=256, d=2048, f=768, e=128, n=384))


REFUSED = {
    # the token_test preset and benchmark/configs/keye_vl2_tok_tiny.json
    "token_test_widths": dict(d=64, f=32),
    "hidden_not_whole_lane_tiles": dict(d=192),
    "expert_width_not_whole_lane_tiles": dict(f=96),
    "block_not_a_whole_sublane_tile": dict(m=12),
    "bf16_block_of_8_rows": dict(m=8, dtype=jnp.bfloat16),
    "float16": dict(dtype=jnp.float16),
    "rows_and_matrices_of_two_dtypes": dict(dtype=jnp.bfloat16,
                                            wdtype=jnp.float32),
    "one_experts_matrices_over_the_vmem_budget": dict(d=4096, f=2048),
}


@pytest.mark.parametrize("why", list(REFUSED))
def test_supports_refuses_and_an_explicit_pallas_raises(why):
    shapes = _shapes(**REFUSED[why])
    assert not pallas_moe.expert_ffn_supports(*shapes)
    with pytest.raises(ValueError, match="expert_ffn.*requested explicitly"):
        dispatch.resolve("expert_ffn", "pallas", *shapes)
    # 'auto' may choose, and chooses the scan, whatever the backend
    assert dispatch.resolve("expert_ffn", "auto", *shapes).name == "xla"


def test_an_explicit_pallas_raises_through_expert_outputs():
    x = jnp.zeros((32, 64))
    ids = jnp.zeros((32, K), jnp.int32)
    w = [jnp.zeros(s) for s in [(E, 64, 32), (E, 64, 32), (E, 32, 64)]]
    with pytest.raises(ValueError, match="expert_rows.*float32\\[32, 64\\]"):
        moe.expert_outputs(x, ids, jnp.ones((32, K)), *w, first=0, block=M,
                           impl="pallas")
    assert moe.expert_outputs(x, ids, jnp.ones((32, K)), *w, first=0,
                              block=M).shape == (32, 64)


@pytest.mark.parametrize("backend,core", [("tpu", "pallas"), ("cpu", "xla")])
def test_auto_takes_the_kernel_on_a_tpu_process_alone(monkeypatch, backend,
                                                      core):
    monkeypatch.setattr(dispatch, "default_backend", lambda: backend)
    assert dispatch.resolve("expert_ffn", "auto", *_shapes()).name == core
    assert dispatch.resolve("expert_ffn", "xla", *_shapes()).name == "xla"
    # and the counter says which core a traced site took
    args, first = _operands("even")
    before = RECORDER.counters()
    monkeypatch.setattr(dispatch, "interpret_default", lambda: True)
    jax.eval_shape(lambda *a: moe.expert_outputs(*a, first=first, block=M),
                   *args)
    after = RECORDER.counters()
    other = {"xla": "pallas", "pallas": "xla"}[core]
    assert after[f"experts.{core}"] - before.get(f"experts.{core}", 0) == 1
    assert after.get(f"experts.{other}", 0) == before.get(
        f"experts.{other}", 0)


def test_cpu_lowering_of_a_chunk_is_the_scans_and_holds_no_kernel():
    args, first = _operands("even")

    def text(impl):
        return jax.jit(lambda *a: moe.expert_outputs(
            *a, first=first, block=M, impl=impl)).lower(*args).as_text()
    auto = text("auto")
    assert auto == text("xla")
    assert "while" in auto and "custom_call" not in auto


# ------------------------------------------------- how rows move, alone

# name -> (ids [T, k] or counts over all experts, held = (first, count),
# experts routed over)
MOVES = {
    "a_share_with_an_expert_without_a_row":
        (np.array([30, 0, 20, 14] + [16] * 12), (0, 4), 16),
    "no_assignment_held_here": (np.array([64] * 4 + [0] * 4), (4, 4), 8),
    # token 0 has both slots held, token 1 none, the rest one of two
    "a_token_with_every_slot_held_and_one_with_none":
        (np.array([[4, 5], [0, 1]] + [[2, 6], [7, 3]] * 63), (4, 4), 16),
    # runs of 32, 16 and 48 rows: each ends on a block boundary
    "runs_that_end_on_a_block_boundary":
        (np.array([32, 16, 48, 0] + [40] * 4), (0, 4), 8),
    "every_assignment_to_one_held_expert":
        (np.array([0, 0, T * K, 0]), (1, 3), 4),
    "all_experts_held": (np.full(E, T * K // E), (0, E), E),
}


def _moving(name, dtype, monkeypatch, seed=0):
    """The operands ``expert_outputs`` hands the two ops on routing
    ``name``: ``(x, token, ends)`` and ``(ys, at, gates, ends)``, read
    off its own XLA cores."""
    ids, (first, held), of = MOVES[name]
    ids = _ids(ids, seed) if ids.ndim == 1 else jnp.asarray(ids, jnp.int32)
    k = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(k[0], (ids.shape[0], D)).astype(dtype)
    gates = jax.nn.softmax(jax.random.normal(k[1], ids.shape), axis=-1)
    w = [(jax.random.normal(kk, s) / np.sqrt(s[1])).astype(dtype) for kk, s
         in zip(k[2:], [(held, D, F), (held, D, F), (held, F, D)])]
    seen = {}
    for op in ("expert_rows", "expert_combine"):
        impls = dispatch._REGISTRY[op]

        def spy(*a, _op=op, _fn=impls["xla"].fn):
            seen[_op] = a
            return _fn(*a)
        monkeypatch.setitem(impls, "xla", dispatch.KernelImpl(op, "xla", spy))
    moe.expert_outputs(x, ids, gates, *w, first=first, block=M, of=of,
                       impl="xla")
    return seen["expert_rows"], seen["expert_combine"]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("routing", list(MOVES))
def test_rows_in_are_the_gathers_on_every_block_in_use(routing, dtype,
                                                       monkeypatch):
    """Copies: equal to the last bit, the padding rows of a run's last
    block zero; the blocks past the last run are the kernel's to leave."""
    (x, token, ends, m), _ = _moving(routing, dtype, monkeypatch)
    want = pallas_moe.expert_rows_reference(x, token, ends, m)
    got = pallas_moe.expert_rows(x, token, ends, m)
    assert got.dtype == want.dtype and got.shape == want.shape
    used = int(ends[-1]) // m
    assert (used == 0) == (routing == "no_assignment_held_here")
    np.testing.assert_array_equal(np.asarray(got[:used], np.float32),
                                  np.asarray(want[:used], np.float32))
    if used:
        assert float(jnp.abs(want[:used].astype(jnp.float32)).mean()) > 0.1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("routing", list(MOVES))
def test_the_gated_combine_is_the_gather_and_sum(routing, dtype,
                                                 monkeypatch):
    """The same float32 products summed in slot order and cast once:
    float32 within an ulp of the XLA expression (whose compiler may fuse
    a product into the sum), bf16 no farther from the sum written out in
    float64 than the XLA expression is.  The blocks ``expert_ffn`` leaves
    unwritten (here NaN) are never read."""
    _, (ys, at, gates, ends) = _moving(routing, dtype, monkeypatch)
    used = int(ends[-1]) // M
    want = pallas_moe.expert_combine_reference(ys, at, gates, ends)
    got = pallas_moe.expert_combine(ys.at[used:].set(jnp.nan), at, gates,
                                    ends)
    assert got.dtype == want.dtype and got.shape == want.shape
    y0 = np.concatenate([np.asarray(ys, np.float64).reshape(-1, D),
                         np.zeros((1, D))])
    exact = (y0[np.asarray(at)].reshape(*gates.shape, D)
             * np.asarray(gates, np.float64)[..., None]).sum(axis=1)

    def gap(a):
        return np.abs(np.asarray(a, np.float64) - exact).mean()
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=2.4e-7, rtol=1.2e-7)
    assert gap(got) <= gap(want) * 1.02 + 1e-9, (gap(got), gap(want))
    held = (at < ys.shape[0] * M).reshape(gates.shape)
    none = ~held.any(axis=1)
    assert not bool(jnp.abs(got[none]).any())
    if routing == "a_token_with_every_slot_held_and_one_with_none":
        assert bool(held[0].all()) and bool(none[1])
    if routing != "no_assignment_held_here":
        assert float(jnp.abs(want.astype(jnp.float32)).mean()) > 0.01


def test_ten_slots_sum_in_slot_order():
    """The cell's top-10: every slot's product added in turn to a float32
    sum that starts at the first."""
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    Kk, rows = 10, 6 * M
    ys = jax.random.normal(k[0], (6, M, D))
    at = jax.random.randint(k[1], (T * Kk,), 0, 8 * M)   # a quarter absent
    at = jnp.where(at < rows, at, rows)
    gates = jax.nn.softmax(jax.random.normal(k[2], (T, Kk)), axis=-1)
    ends = jnp.array([rows], jnp.int32)
    got = pallas_moe.expert_combine(ys, at, gates, ends)
    picked = jnp.concatenate([ys.reshape(rows, D), jnp.zeros((1, D))])[
        at].reshape(T, Kk, D) * gates[..., None]
    want = picked[:, 0]
    for s in range(1, Kk):
        want = want + picked[:, s]
    np.testing.assert_allclose(got, want, atol=2.4e-7, rtol=1.2e-7)
    np.testing.assert_allclose(
        got, pallas_moe.expert_combine_reference(ys, at, gates, ends),
        atol=1e-6, rtol=0)


@pytest.mark.parametrize("op", ["expert_rows", "expert_combine"])
def test_two_objects_are_one_call_with_an_object_axis(op, monkeypatch):
    """Under the sampler's ``vmap`` each op is its kernel once more with
    the objects as a grid axis: no loop over objects that slices every
    operand out, as jax's own rule for prefetched scalars is."""
    a = _moving("a_share_with_an_expert_without_a_row", jnp.float32,
                monkeypatch, seed=1)
    b = _moving("runs_that_end_on_a_block_boundary", jnp.float32,
                monkeypatch, seed=2)
    i = ["expert_rows", "expert_combine"].index(op)
    static = a[i][3:] if op == "expert_rows" else ()
    stacked = [jnp.stack([p, q]) for p, q in zip(a[i][:3 + i], b[i][:3 + i])]
    fn = {"expert_rows": lambda *o: pallas_moe.expert_rows(*o, *static),
          "expert_combine": pallas_moe.expert_combine}[op]
    ref = {"expert_rows": lambda *o: pallas_moe.expert_rows_reference(
        *o, *static), "expert_combine":
        pallas_moe.expert_combine_reference}[op]
    got, want = jax.vmap(fn)(*stacked), jax.vmap(ref)(*stacked)
    for o, ends in enumerate(stacked[-1]):
        used = int(ends[-1]) // M if op == "expert_rows" else None
        np.testing.assert_allclose(got[o][:used], want[o][:used],
                                   atol=2.4e-7, rtol=1.2e-7)
    text = str(jax.make_jaxpr(jax.vmap(fn))(*stacked))
    assert "pallas_call" in text and "grid=(2, " in text
    assert "grid=(1, " not in text and "dynamic_update_slice" not in text
    assert float(jnp.abs(got[0][:1] - got[1][:1]).mean()) > 0.01


def test_gradient_through_each_custom_vjp_is_the_xla_expressions(
        monkeypatch):
    (x, token, ends, m), (ys, at, gates, _) = _moving(
        "a_share_with_an_expert_without_a_row", jnp.float32, monkeypatch,
        seed=4)
    used = int(ends[-1]) // m
    w = jax.random.normal(jax.random.PRNGKey(5), (used, m, D))

    def rows(fn):
        return jax.grad(lambda x: jnp.sum(
            fn(x, token, ends, m)[:used] ** 2 * w))(x)
    got, want = rows(pallas_moe.expert_rows), rows(
        pallas_moe.expert_rows_reference)
    assert float(jnp.abs(want).mean()) > 1e-3
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)

    def combine(fn):
        return jax.grad(lambda y, g: jnp.sum(fn(y, at, g, ends) ** 2),
                        argnums=(0, 1))(ys, gates)
    for g, r in zip(combine(pallas_moe.expert_combine),
                    combine(pallas_moe.expert_combine_reference)):
        assert float(jnp.abs(r).mean()) > 1e-4
        np.testing.assert_allclose(g, r, atol=1e-5, rtol=1e-5)


MOVE_SUPPORTS = {"expert_rows": pallas_moe.expert_rows_supports,
                 "expert_combine": pallas_moe.expert_combine_supports}


def _move_shapes(op, d=D, m=M, dtype=jnp.float32, t=T, k=K, n=24,
                 gdtype=jnp.float32):
    sds = jax.ShapeDtypeStruct
    ends = sds((E,), jnp.int32)
    if op == "expert_rows":
        return (sds((t, d), dtype), sds((n * m,), jnp.int32), ends, m)
    return (sds((n, m, d), dtype), sds((t * k,), jnp.int32),
            sds((t, k), gdtype), ends)


def test_the_moves_support_both_cells_shapes():
    for op, supports in MOVE_SUPPORTS.items():
        assert supports(*_move_shapes(op))
        # granite4_h_small_tok128: 169 blocks a chunk of 4096 at top-10
        assert supports(*_move_shapes(op, d=4096, m=256, t=4096, k=10,
                                      n=169, dtype=jnp.bfloat16))
        # keye_vl2_tok128: 384 blocks a chunk of 8192 at top-8
        assert supports(*_move_shapes(op, d=2048, m=256, t=8192, k=8,
                                      n=384, dtype=jnp.bfloat16))


MOVES_REFUSED = {
    "token_test_widths": dict(d=64),
    "hidden_not_whole_lane_tiles": dict(d=192),
    "block_not_a_whole_sublane_tile": dict(m=12),
    "bf16_block_of_8_rows": dict(m=8, dtype=jnp.bfloat16),
    "float16": dict(dtype=jnp.float16),
}


@pytest.mark.parametrize("op", ["expert_rows", "expert_combine"])
@pytest.mark.parametrize("why", list(MOVES_REFUSED))
def test_the_moves_refuse_and_an_explicit_pallas_raises(why, op,
                                                        monkeypatch):
    shapes = _move_shapes(op, **MOVES_REFUSED[why])
    assert not MOVE_SUPPORTS[op](*shapes)
    with pytest.raises(ValueError, match=f"{op}.*requested explicitly"):
        dispatch.resolve(op, "pallas", *shapes, held=9, of=72)
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    assert dispatch.resolve(op, "auto", *shapes, held=9,
                            of=72).name == "xla"


def test_the_combine_refuses_gates_that_are_not_float32():
    shapes = _move_shapes("expert_combine", gdtype=jnp.bfloat16)
    assert not pallas_moe.expert_combine_supports(*shapes)


@pytest.mark.parametrize("backend,held,of,core", [
    ("tpu", 9, 72, "pallas"), ("tpu", 64, 128, "pallas"),
    ("tpu", 65, 128, "xla"), ("tpu", 128, 128, "xla"),
    ("tpu", 9, None, "xla"), ("cpu", 9, 72, "xla"), ("cpu", 8, 8, "xla")])
def test_auto_moves_rows_by_kernel_on_a_tpu_process_at_a_share_alone(
        monkeypatch, backend, held, of, core):
    """The rule reads the static held share: at most half of the experts
    routed over.  ``expert_ffn`` does not ask (it wins wherever it runs)."""
    monkeypatch.setattr(dispatch, "default_backend", lambda: backend)
    monkeypatch.setattr(dispatch, "interpret_default", lambda: True)
    for op in ("expert_rows", "expert_combine"):
        assert dispatch.resolve(op, "auto", *_move_shapes(op), held=held,
                                of=of).name == core
        assert dispatch.resolve(op, "xla", *_move_shapes(op), held=held,
                                of=of).name == "xla"
    # and the counters say which core each traced site took
    x = jax.ShapeDtypeStruct((T, D), jnp.float32)
    ids = jax.ShapeDtypeStruct((T, K), jnp.int32)
    w = [jax.ShapeDtypeStruct(s, jnp.float32) for s in
         [(held, D, F), (held, D, F), (held, F, D)]]
    before = RECORDER.counters()
    out = jax.eval_shape(lambda *a: moe.expert_outputs(
        *a, first=0, block=M, of=of), x, ids, x.update(shape=(T, K)), *w)
    assert out.shape == (T, D)
    after = RECORDER.counters()
    d = {k: after[k] - before.get(k, 0) for k in after
         if k.startswith("experts.") and after[k] != before.get(k, 0)}
    ffn = "pallas" if backend == "tpu" else "xla"
    assert d == {f"experts.rows.{core}": 1, f"experts.combine.{core}": 1,
                 f"experts.{ffn}": 1}


def test_cpu_lowering_of_a_share_holds_no_kernel_and_is_all_helds_text():
    """On a CPU process ``of`` decides nothing: the text of a chunk at a
    share is the text with ``of`` left out, the parent's expression."""
    (x, ids, gates, *w), first = _operands("held_elsewhere")

    def text(**kw):
        return jax.jit(lambda *a: moe.expert_outputs(
            *a, first=first, block=M, **kw)).lower(
                x, ids, gates, *w).as_text()
    auto = text(of=16)
    assert auto == text() == text(of=16, impl="xla")
    assert "custom_call" not in auto
