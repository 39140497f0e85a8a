"""The chip's compiler, asked here (no chip attached) for every Pallas
kernel site of srn64 and srn128, forward and backward, ``interpret=False``.

Interpret-mode tests prove the tile programs compute the right numbers;
they cannot see what Mosaic refuses (unaligned slices, too much VMEM, a
dtype it will not load).  These compiles can, at about two seconds a case
and no chip time.  A compile that passes is not a chip run.

Sites are the distinct ``(L, C, dtype, film, silu)`` GroupNorm operands
and ``(L, heads, D, dtype)`` attention operands that one forward of
``XUNet(srn64_config())`` / ``XUNet(srn128_config())`` hands the
dispatcher with ``kernels='pallas'`` / ``attn_impl='pallas'`` (enumerated
by tracing the model with recording stand-ins, PR 21).  The residual
stream is float32 (``/ np.sqrt(2.0)`` promotes it), so most GroupNorm
inputs are float32 while FiLM sites see the bf16 conv output.  N (frames
x batch) only scales the kernels' outer grid; the tests use N = 2.

The token denoiser has one kernel site, ``sdpa(keep=)`` of
``keye_vl2_tok128``: one tile of 512 queries (32 heads) against the 8192
keys of an example (4 heads) at head dim 128, vmapped over the sampler's
objects (PR 27).

Its second is the routed experts' block kernel, ``expert_ffn`` of one
chunk of 8192 tokens: 384 blocks of 256 rows x 2048 through 128 experts
of 2048 x 768, alone, under the sampler's ``vmap`` over one object (the
cell's: the prefetched tables lose the axis) and over two (jax loops),
and with the scan's VJP behind it (PR 29).

The hybrid cell (``granite4_h_micro_tok128``, PR 30) has one kernel
site, plain ``sdpa`` of its one attention layer: one tile of 512 queries
(32 heads) against the 8192 keys of an example (8 heads) at head dim 64,
``plain_attention`` (PR 31), compiled as the selected kernel's site is.
Its whole view program, as ``Sampler`` builds it at full width (ten
layers, 752 M parameters, sixteen 8192-token examples a call), is also
compiled once, 16 s, and its memory held to the chip's: a chunked scan
whose decay tiles stood in HBM for every example at once, or attention
that wrote its scores, would not fit or would show in the text, and
passes every CPU test.

The hybrid mixture-of-experts cell (``granite4_h_small_tok128``, PR 32)
adds no kernel but runs two at new shapes: ``expert_ffn`` at hidden 4096
on a **share** of the experts (9 held of 72: 169 blocks of 256 rows a
chunk of 4096 tokens at top-10, the configuration's tile; 329 a chunk of
8192) and ``plain_attention`` at head dim 128; its whole view program (ten
layers, 2.02 B parameters) is compiled once and its memory held to the
chip's, 29 s.

Since PR 33 the rows of that cell's experts move by three more kernels
a layer (``expert_rows``, the re-tiling of the blocks in use, and
``expert_combine``: per-row DMAs addressed through ``[rows, D / 128,
128]``, SMEM operands by their own block specs, the object axis a grid
axis): compiled at both token cells' shapes in bf16 and float32, under
the sampler's ``vmap`` over one and two objects, and with the XLA
expressions' VJPs behind them; the view program holds 41 custom calls and
neither the gather over all ``T k`` assignments nor the float32 tile of
picked rows.

Fixture rules (on-chip-measurement guide, section 2): the topology is
described inside a module-scoped, non-autouse fixture of THIS file, which
skips where it cannot be described — never at import, in ``skipif``, in
``parametrize`` arguments or in ``conftest.py``; compiles run in the
test's own process (the worker that loads libtpu keeps its lock); the
persistent cache is off around them (a TPU executable written from a CPU
process cannot be read back).
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from diff3d_tpu.ops.pallas_attention import (flash_attention,
                                             plain_attention,
                                             selected_attention)
from diff3d_tpu.ops.pallas_film import fused_groupnorm
from diff3d_tpu.ops.pallas_moe import (expert_combine, expert_ffn,
                                       expert_rows)

F32, BF16 = "float32", "bfloat16"

# (L, C, dtype, film, silu); 32 groups everywhere.
GN_SITES = {
    "srn64": [
        (4096, 128, BF16, False, True), (4096, 128, BF16, True, False),
        (4096, 128, F32, False, True), (4096, 256, F32, False, True),
        (4096, 384, F32, False, True),
        (1024, 128, F32, False, True), (1024, 256, BF16, True, False),
        (1024, 256, F32, False, True), (1024, 384, F32, False, True),
        (1024, 512, F32, False, True),
        (256, 256, BF16, True, False), (256, 256, F32, False, False),
        (256, 256, F32, False, True), (256, 512, F32, False, True),
        (256, 768, F32, False, True),
        (64, 256, F32, False, True), (64, 512, BF16, True, False),
        (64, 512, F32, False, False), (64, 512, F32, False, True),
        (64, 768, F32, False, True), (64, 1024, F32, False, True),
    ],
    "srn128": [
        (16384, 256, BF16, False, True), (16384, 256, BF16, True, False),
        (16384, 256, F32, False, True), (16384, 512, F32, False, True),
        (16384, 768, F32, False, True),
        (4096, 256, F32, False, True), (4096, 512, BF16, True, False),
        (4096, 512, F32, False, True), (4096, 768, F32, False, True),
        (4096, 1024, F32, False, True),
        (1024, 512, BF16, True, False), (1024, 512, F32, False, False),
        (1024, 512, F32, False, True), (1024, 1024, F32, False, True),
        (1024, 1536, F32, False, True),
        (256, 512, F32, False, True), (256, 1024, BF16, True, False),
        (256, 1024, F32, False, False), (256, 1024, F32, False, True),
        (256, 1536, F32, False, True), (256, 2048, F32, False, True),
    ],
}
# (L, heads, D, dtype) — self- and cross-attention share Lq == Lk.
ATTN_SITES = {
    "srn64": [(256, 4, 64, BF16), (64, 4, 128, BF16)],
    "srn128": [(1024, 4, 128, BF16), (256, 4, 256, BF16)],
}
N = 2
GROUPS = 32


def _cases(table):
    return [pytest.param(*site, id=f"{cfg}-" + "-".join(map(str, site)))
            for cfg, sites in table.items() for site in sites]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A TPU executable compiled from this CPU process is written to the
    persistent cache but cannot be read back without a chip: the next
    compile would warn and compile again.  Off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_for_chip(fn, *args):
    """Lower + compile ``fn`` for the described chip; the kernel must be
    in the program as a Mosaic custom call, not as interpreter ops."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _gn_operands(one_chip, L, C, dtype, film):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)

    x = sds((N, L, C), dtype)
    affine = (sds((C,), F32), sds((C,), F32))
    mod = (sds((N, L, C), dtype), sds((N, L, C), dtype)) if film else ()
    return (x, *affine, *mod)


def _gn(silu):
    def fn(x, gamma, beta, *mod):
        kw = dict(scale=mod[0], shift=mod[1]) if mod else {}
        return fused_groupnorm(x, gamma, beta, num_groups=GROUPS,
                               silu=silu, interpret=False, **kw)
    return fn


@pytest.mark.parametrize("L,C,dtype,film,silu", _cases(GN_SITES))
def test_fused_groupnorm_forward_compiles_for_v5e(
        one_chip, no_persistent_cache, L, C, dtype, film, silu):
    _compile_for_chip(_gn(silu), *_gn_operands(one_chip, L, C, dtype, film))


@pytest.mark.parametrize("L,C,dtype,film,silu", _cases(GN_SITES))
def test_fused_groupnorm_backward_compiles_for_v5e(
        one_chip, no_persistent_cache, L, C, dtype, film, silu):
    """The ``custom_vjp`` pair the train step runs: the stats-saving
    forward and the fused dx/dgamma/dbeta(/dscale/dshift) backward."""
    args = _gn_operands(one_chip, L, C, dtype, film)
    fn = _gn(silu)

    def loss(*a):
        return jnp.sum(fn(*a).astype(jnp.float32))

    _compile_for_chip(jax.grad(loss, argnums=tuple(range(len(args)))),
                      *args)


def _qkv(one_chip, L, heads, D, dtype):
    s = jax.ShapeDtypeStruct((N, L, heads, D), jnp.dtype(dtype),
                             sharding=one_chip)
    return s, s, s


def _flash(q, k, v):
    return flash_attention(q, k, v, interpret=False)


@pytest.mark.parametrize("L,heads,D,dtype", _cases(ATTN_SITES))
def test_flash_attention_forward_compiles_for_v5e(
        one_chip, no_persistent_cache, L, heads, D, dtype):
    _compile_for_chip(_flash, *_qkv(one_chip, L, heads, D, dtype))


@pytest.mark.parametrize("L,heads,D,dtype", _cases(ATTN_SITES))
def test_flash_attention_backward_compiles_for_v5e(
        one_chip, no_persistent_cache, L, heads, D, dtype):
    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v).astype(jnp.float32))

    _compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)),
                      *_qkv(one_chip, L, heads, D, dtype))


# keye_vl2_tok128's sdpa(keep=) site: (Lq, Lk, Hq, Hkv, D)
SELECTED_SITE = (512, 8192, 32, 4, 128)
# a tile's scores, [.., Lq, Lk], the array neither attention kernel writes
SCORES = r"(f32|bf16)\[[\d,]*512,8192\]"


def _selected_operands(one_chip, dtype, lead=()):
    Lq, Lk, Hq, Hkv, D = SELECTED_SITE

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(lead + shape, jnp.dtype(dt),
                                    sharding=one_chip)
    return (sds((1, Lq, Hq, D), dtype), sds((1, Lk, Hkv, D), dtype),
            sds((1, Lk, Hkv, D), dtype), sds((1, Lq, Lk), "bool"))


def _selected(q, k, v, keep):
    return selected_attention(q, k, v, keep, interpret=False)


@pytest.mark.parametrize("dtype", [BF16, F32])
def test_selected_attention_forward_compiles_for_v5e(
        one_chip, no_persistent_cache, dtype):
    compiled = _compile_for_chip(_selected,
                                 *_selected_operands(one_chip, dtype))
    # the score tile stays on chip: nothing of [Hq, Lq, Lk] in the program
    assert not re.search(SCORES, compiled.as_text())


def test_selected_attention_under_the_samplers_vmap_compiles_for_v5e(
        one_chip, no_persistent_cache):
    """``Sampler`` vmaps the view program over objects: Pallas adds a grid
    axis in front of the kernel's own, which the chip's compiler must
    take with the kernel's ``dimension_semantics``."""
    _compile_for_chip(jax.vmap(_selected),
                      *_selected_operands(one_chip, BF16, lead=(2,)))


def test_selected_attention_gradient_compiles_for_v5e(
        one_chip, no_persistent_cache):
    """The token train step's pair: the kernel forward, the XLA
    expression's VJP backward."""
    q, k, v, keep = _selected_operands(one_chip, BF16)

    def loss(q, k, v, keep):
        # squared, so that the backward needs the kernel's output
        return jnp.sum(_selected(q, k, v, keep).astype(jnp.float32) ** 2)

    _compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)), q, k, v, keep)


# granite4_h_micro_tok128's plain sdpa site: (Lq, Lk, Hq, Hkv, D)
PLAIN_SITE = (512, 8192, 32, 8, 64)


def _plain_operands(one_chip, dtype, lead=()):
    Lq, Lk, Hq, Hkv, D = PLAIN_SITE

    def sds(shape):
        return jax.ShapeDtypeStruct(lead + shape, jnp.dtype(dtype),
                                    sharding=one_chip)
    return (sds((1, Lq, Hq, D)), sds((1, Lk, Hkv, D)), sds((1, Lk, Hkv, D)))


def _plain(q, k, v):
    return plain_attention(q, k, v, interpret=False)


@pytest.mark.parametrize("dtype", [BF16, F32])
def test_plain_attention_forward_compiles_for_v5e(
        one_chip, no_persistent_cache, dtype):
    compiled = _compile_for_chip(_plain, *_plain_operands(one_chip, dtype))
    # the score tile stays on chip: nothing of [Hq, Lq, Lk] in the program
    assert not re.search(SCORES, compiled.as_text())


def test_plain_attention_under_the_samplers_vmap_compiles_for_v5e(
        one_chip, no_persistent_cache):
    _compile_for_chip(jax.vmap(_plain),
                      *_plain_operands(one_chip, BF16, lead=(2,)))


def test_plain_attention_gradient_compiles_for_v5e(
        one_chip, no_persistent_cache):
    """The hybrid train step's pair: the kernel forward, the XLA
    expression's VJP backward (which does write the scores)."""
    def loss(q, k, v):
        # squared, so that the backward needs the kernel's output
        return jnp.sum(_plain(q, k, v).astype(jnp.float32) ** 2)

    compiled = _compile_for_chip(jax.grad(loss, argnums=(0, 1, 2)),
                                 *_plain_operands(one_chip, BF16))
    assert re.search(SCORES, compiled.as_text())


# keye_vl2_tok128's expert_outputs site: (blocks, rows, D, experts, F)
EXPERT_SITE = (384, 256, 2048, 128, 768)


def _expert_operands(one_chip, dtype, lead=(), site=EXPERT_SITE):
    n, m, D, E, F = site

    def sds(shape, dt, lead=lead):
        return jax.ShapeDtypeStruct(lead + shape, jnp.dtype(dt),
                                    sharding=one_chip)
    return (sds((n, m, D), dtype), sds((n,), "int32"), sds((E,), "int32"),
            *[sds(s, dtype, ()) for s in [(E, D, F), (E, D, F), (E, F, D)]])


def _experts(rows, e_blk, ends, w_gate, w_up, w_down):
    return expert_ffn(rows, e_blk, ends, w_gate, w_up, w_down,
                      interpret=False)


@pytest.mark.parametrize("dtype", [BF16, F32])
def test_expert_ffn_forward_compiles_for_v5e(one_chip, no_persistent_cache,
                                             dtype):
    compiled = _compile_for_chip(_experts, *_expert_operands(one_chip, dtype))
    # gate and up stay on chip: nothing of [.., 256, 768] in the program
    assert not re.search(r"(f32|bf16)\[[\d,]*256,768\]", compiled.as_text())


@pytest.mark.parametrize("objects", [1, 2])
def test_expert_ffn_under_the_samplers_vmap_compiles_for_v5e(
        one_chip, no_persistent_cache, objects):
    """Rows and the prefetched tables carry the object axis, the experts'
    matrices do not."""
    _compile_for_chip(
        jax.vmap(_experts, in_axes=(0, 0, 0, None, None, None)),
        *_expert_operands(one_chip, BF16, lead=(objects,)))


def test_expert_ffn_gradient_compiles_for_v5e(one_chip, no_persistent_cache):
    """The token train step's pair: the kernel forward, the scan's VJP
    backward."""
    def grads(rows, e_blk, ends, *w):
        def loss(rows, w):
            # squared, so that the backward needs the kernel's output
            return jnp.sum(_experts(rows, e_blk, ends, *w).astype(
                jnp.float32) ** 2)
        return jax.grad(loss, argnums=(0, 1))(rows, w)

    _compile_for_chip(grads, *_expert_operands(one_chip, BF16))


def test_the_hybrid_cells_view_program_compiles_and_fits_a_v5e(
        one_chip, no_persistent_cache, monkeypatch):
    """``Sampler._run_view_many`` of ``benchmark/configs/
    granite4_h_micro_tok128.json`` on one object, as the cell calls it,
    resolved as a TPU process resolves it."""
    import json
    import os

    from benchmark import adapters_hybrid
    from diff3d_tpu.models import build_model
    from diff3d_tpu.ops import dispatch
    from diff3d_tpu.sampling import Sampler
    from diff3d_tpu.train.trainer import init_params

    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    monkeypatch.setattr(dispatch, "interpret_default", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite4_h_micro_tok128.json")) as f:
        cfg = adapters_hybrid.build_config(json.load(f))
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
    sampler = Sampler(model, params, cfg, sampler_kind="ddim", steps=8)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)
    H, B = cfg.model.H, len(cfg.diffusion.guidance_weights)
    compiled = sampler._run_view_many.lower(
        jax.tree.map(lambda x: sds(x.shape, x.dtype), params),
        sds((1, 2, B, H, H, 3), F32), sds((1, 2, 3, 3), F32),
        sds((1, 2, 3), F32), sds((1,), "int32"), sds((1, 3, 3), F32),
        sds((1, 2), "uint32")).compile()
    mem = compiled.memory_analysis()
    assert 4 * 752_425_932 < mem.argument_size_in_bytes < 3.02e9
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.generated_code_size_in_bytes)
    # 6.80 GB since the kernel (temporaries 3.70; 6.95 and 3.86 with
    # XLA's score tile, held to 8.5): under half the chip's 16.9
    assert total < 8.35e9, mem
    # the attention layer's tile is the kernel's: one custom call, and no
    # score array of a tile, let alone [heads, L, L]; no decay tile for
    # all 16 examples
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert not re.search(r"f32\[[\d,]*512,8192\]", text)
    assert not re.search(r"f32\[[\d,]*32,8192,8192\]", text)
    assert not re.search(r"f32\[16,[\d,]*256,256\]", text)


# granite4_h_small_tok128's expert_outputs site at a chunk of 4096 tokens
# (the configuration's tile) and of 8192: (blocks, rows, D, held, F)
SHARE_SITES = [(169, 256, 4096, 9, 768), (329, 256, 4096, 9, 768)]


@pytest.mark.parametrize("site", SHARE_SITES, ids=lambda s: f"blocks{s[0]}")
def test_expert_ffn_on_a_share_at_hidden_4096_compiles_for_v5e(
        one_chip, no_persistent_cache, site):
    """One expert's three matrices of 4096 x 768 twice in VMEM beside the
    row and result blocks: 49.5 MiB by ``_vmem_need``, inside the
    kernel's budget and the limit it hands Mosaic."""
    from diff3d_tpu.ops.pallas_moe import (VMEM_BUDGET, _vmem_need,
                                           expert_ffn_supports)

    operands = _expert_operands(one_chip, BF16, site=site)
    assert expert_ffn_supports(*operands)
    assert 49 << 20 < _vmem_need(256, 4096, 768, 2) < VMEM_BUDGET
    compiled = _compile_for_chip(_experts, *operands)
    assert not re.search(r"(f32|bf16)\[[\d,]*256,768\]", compiled.as_text())


# the chunk whose rows move: (tokens, top-k, D, blocks, rows a block) at
# granite4_h_small_tok128 (9 of 72 held: where the kernels run) and at
# keye_vl2_tok128 (all held: where ``auto`` leaves them, by hand here)
MOVE_SITES = {"h_small": (4096, 10, 4096, 169, 256),
              "keye": (8192, 8, 2048, 384, 256)}


def _move_operands(one_chip, site, dtype, lead=()):
    T, K, D, n, m = MOVE_SITES[site]

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(lead + shape, jnp.dtype(dt),
                                    sharding=one_chip)
    ends = sds((9,), "int32")
    return ((sds((T, D), dtype), sds((n * m,), "int32"), ends),
            (sds((n, m, D), dtype), sds((T * K,), "int32"),
             sds((T, K), F32), ends))


def _rows_in(x, token, ends):
    return expert_rows(x, token, ends, 256, interpret=False)


def _sum_back(ys, at, gates, ends):
    return expert_combine(ys, at, gates, ends, interpret=False)


@pytest.mark.parametrize("dtype", [BF16, F32])
@pytest.mark.parametrize("site", list(MOVE_SITES))
def test_the_rows_way_in_and_back_compile_for_v5e(one_chip,
                                                  no_persistent_cache,
                                                  site, dtype):
    rows, back = _move_operands(one_chip, site, dtype)
    T, K, D, n, m = MOVE_SITES[site]
    text = _compile_for_chip(_rows_in, *rows).as_text()
    # no gather over the static bound beside the kernel
    assert not re.search(rf"(f32|bf16)\[{n * m},{D}\]", text)
    text = _compile_for_chip(_sum_back, *back).as_text()
    assert text.count("tpu_custom_call") == 2
    assert not re.search(rf"(f32|bf16)\[{T * K},{D}\]", text)
    assert not re.search(rf"f32\[{T},{K},{D}\]", text)


@pytest.mark.parametrize("objects", [1, 2])
def test_the_rows_way_in_and_back_under_the_samplers_vmap_compile_for_v5e(
        one_chip, no_persistent_cache, objects):
    """The object axis is a grid axis of each kernel: no loop that slices
    an object's operands out (at two objects ``expert_ffn``'s prefetched
    tables make jax loop; these do not)."""
    rows, back = _move_operands(one_chip, "h_small", BF16, lead=(objects,))
    for fn, operands in [(_rows_in, rows), (_sum_back, back)]:
        text = _compile_for_chip(jax.vmap(fn), *operands).as_text()
        assert "while" not in text


def test_the_rows_way_in_and_back_gradients_compile_for_v5e(
        one_chip, no_persistent_cache):
    """The token train step's pairs: each kernel forward, its XLA
    expression's VJP backward."""
    rows, back = _move_operands(one_chip, "h_small", BF16)

    def square(y):
        return jnp.sum(y.astype(jnp.float32) ** 2)
    _compile_for_chip(jax.grad(lambda x, t, e: square(_rows_in(x, t, e))),
                      *rows)
    _compile_for_chip(jax.grad(lambda y, a, g, e: square(
        _sum_back(y, a, g, e)), argnums=(0, 2)), *back)


def test_the_hybrid_moe_cells_view_program_compiles_and_fits_a_v5e(
        one_chip, no_persistent_cache, monkeypatch):
    """``Sampler._run_view_many`` of ``benchmark/configs/
    granite4_h_small_tok128.json`` on one object, as the cell calls it,
    resolved as a TPU process resolves it."""
    import json
    import os

    from benchmark import adapters_hybrid_moe
    from diff3d_tpu.models import build_model
    from diff3d_tpu.ops import dispatch
    from diff3d_tpu.sampling import Sampler
    from diff3d_tpu.train.trainer import init_params

    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    monkeypatch.setattr(dispatch, "interpret_default", lambda: False)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "granite4_h_small_tok128.json")) as f:
        cfg = adapters_hybrid_moe.build_config(json.load(f))
    model = build_model(cfg)
    params = jax.eval_shape(
        lambda: init_params(model, cfg, jax.random.PRNGKey(0)))
    sampler = Sampler(model, params, cfg, sampler_kind="ddim", steps=4)

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dt), sharding=one_chip)
    H, B = cfg.model.H, len(cfg.diffusion.guidance_weights)
    compiled = sampler._run_view_many.lower(
        jax.tree.map(lambda x: sds(x.shape, x.dtype), params),
        sds((1, 2, B, H, H, 3), F32), sds((1, 2, 3, 3), F32),
        sds((1, 2, 3), F32), sds((1,), "int32"), sds((1, 3, 3), F32),
        sds((1, 2), "uint32")).compile()
    mem = compiled.memory_analysis()
    assert 4 * 2_023_950_988 < mem.argument_size_in_bytes < 8.11e9
    total = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
             + mem.generated_code_size_in_bytes)
    # 15.39 GB (temporaries 7.26: the state-space layers' peak; 9.67 and
    # over the chip at an expert chunk of 8192 tokens) of the chip's 16.91:
    # the compiler does not always refuse a program that is over
    assert total < 15.6e9, mem
    # ten expert sites (rows in, blocks, re-tiling, gated sum back) and
    # the attention site on their kernels; no gather over all ``T k``
    # assignments or over the static bound of rows, no float32 tile of
    # picked rows; no score array of a tile, no decay tile for all 16
    # examples
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 41
    assert not re.search(r"bf16\[(40960|43264|43265),4096\]", text)
    assert not re.search(r"f32\[4096,10,4096\]", text)
    assert not re.search(r"f32\[[\d,]*512,8192\]", text)
    assert not re.search(r"f32\[[\d,]*32,8192,8192\]", text)
    assert not re.search(r"f32\[16,[\d,]*256,256\]", text)
