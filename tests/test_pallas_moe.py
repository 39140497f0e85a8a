"""The routed experts' grouped Pallas kernel (ops/pallas_moe.py, op
``'expert_ffn'``) against the XLA scan it stands for, in interpret mode on
the CPU: through ``models/moe.expert_outputs`` (the layout, the kernel,
the gated sum) on routings chosen by hand, under the sampler's ``vmap``,
under ``grad``; what ``supports`` refuses and what the registry does with
an explicit ``'pallas'`` there; what ``'auto'`` resolves to by backend.
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
    with pytest.raises(ValueError, match="expert_ffn.*float32\\[12, 16, 64\\]"):
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
