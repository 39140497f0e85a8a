"""Op ``ssm_scan``'s Pallas core (``ops/ssd.py ssd_pallas``) in interpret
mode on the CPU: against the recurrence token by token and against the
XLA core ``ssd_chunked``, at shapes the kernel tiles (head dim 64, state
128, chunks of whole lane tiles, lane blocks of 128 and 256); its
gradient, its ``vmap``, and what ``pallas_supports`` refuses.

The chip's compiler is asked for the kernel at the cells' shapes in
``tests/test_chip_compile.py``.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from _scan_helpers import scan_inputs, sequential  # noqa: E402
from diff3d_tpu.ops import dispatch  # noqa: E402
from diff3d_tpu.ops import ssd as ops_ssd  # noqa: E402
from diff3d_tpu.ops.ssd import (pallas_supports, ssd, ssd_chunked,  # noqa: E402
                                ssd_pallas)

P, N = 64, 128


def kernel(*args, chunk):
    return jax.jit(lambda *a: ssd_pallas(*a, chunk, interpret=True))(*args)


@pytest.fixture(params=[128, 256], ids=lambda w: f"lanes{w}")
def lanes(request, monkeypatch):
    """Four heads of 64 as two lane blocks of 128 (two head blocks, each
    with its own state) or one of 256."""
    monkeypatch.setattr(ops_ssd, "LANES", request.param)
    return request.param


@pytest.mark.parametrize("decay", ["near_one", "near_zero", "mixed"])
@pytest.mark.parametrize("L,chunk", [(512, 256), (400, 128)],
                         ids=["whole_chunks", "padded"])
def test_the_kernel_is_the_recurrence_and_the_xla_core(lanes, L, chunk,
                                                       decay):
    """Two chunks of 256; or three of 128 and a part (400 tokens: the
    last chunk is padded with tokens of ``dt = 0``)."""
    args = scan_inputs(L=L, H=4, P=P, N=N, decay=decay)
    assert pallas_supports(*args, chunk)
    assert ops_ssd._head_block(4, P) == lanes
    got = kernel(*args, chunk=chunk)
    assert got.shape == (L, 4, P) and got.dtype == jnp.float32
    want = sequential(*args)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=3e-5 * scale, rtol=0)
    np.testing.assert_allclose(got, ssd_chunked(*args, chunk),
                               atol=2e-6 * scale, rtol=0)


def test_bfloat16_operands_round_where_the_xla_core_rounds(lanes):
    """bf16 ``x``, ``B``, ``C``: the kernel casts ``M``, ``x`` times its
    weight to the chunk's end and the carried state to bf16 where the XLA
    core does, so the two stay within a bf16 rounding of each other,
    far inside what bf16 operands cost against float32."""
    x, dt, A, B, C, D = scan_inputs(L=512, H=4, P=P, N=N, decay="mixed",
                                    seed=3)
    args = (x.astype(jnp.bfloat16), dt, A, B.astype(jnp.bfloat16),
            C.astype(jnp.bfloat16), D)
    got = kernel(*args, chunk=256)
    assert got.dtype == jnp.bfloat16
    xla = ssd_chunked(*args, 256).astype(jnp.float32)
    f32 = sequential(*(a.astype(jnp.float32) for a in args))
    size = float(jnp.abs(f32).mean())
    gap = float(jnp.abs(got.astype(jnp.float32) - xla).mean())
    assert gap < 1e-3 * size, (gap, size)
    assert float(jnp.abs(xla - f32).mean()) > 2 * gap


def test_the_kernels_state_crosses_chunk_and_frame_boundaries(lanes):
    """Zeroing the state at a chunk boundary (every 128 tokens) or
    between the two frames (every 256) reads as far from the kernel as
    from the XLA core, and the kernel follows the recurrence that keeps
    the state."""
    args = scan_inputs(L=512, H=4, P=P, N=N, decay="slow")
    want = sequential(*args)
    got = kernel(*args, chunk=128)
    xla = ssd_chunked(*args, 128)
    size = float(jnp.abs(want).mean())
    for every in (128, 256):
        cut = sequential(*args, reset=every)
        off = float(jnp.abs(cut - got).mean())
        assert off > 0.05 * size
        assert abs(off - float(jnp.abs(cut - xla).mean())) < 1e-4 * off
    assert float(jnp.abs(got - want).mean()) < 1e-5 * size


def test_the_gradient_is_the_xla_cores():
    """The ``custom_vjp``'s backward is ``ssd_chunked``'s on the saved
    operands: every operand's gradient equal to the XLA core's."""
    args = scan_inputs(L=384, H=4, P=P, N=N, decay="mixed", seed=1)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) ** 2)
    want = jax.grad(loss(lambda *a: ssd_chunked(*a, 128)),
                    argnums=range(6))(*args)
    got = jax.jit(jax.grad(loss(lambda *a: ssd_pallas(*a, 128,
                                                      interpret=True)),
                           argnums=range(6)))(*args)
    for g, w in zip(got, want):
        scale = float(jnp.abs(w).max())
        assert scale > 0
        np.testing.assert_allclose(g, w, atol=1e-5 * scale, rtol=0)


def test_two_objects_under_vmap_are_two_calls():
    """The sampler maps the view program over objects: the kernel's grid
    takes the object axis in front, each object's state from zero."""
    x, dt, A, B, C, D = scan_inputs(L=256, H=4, P=P, N=N, decay="slow",
                                    seed=2)
    xs, dts = jnp.stack([x, x[::-1]]), jnp.stack([dt, dt[::-1]])
    both = jax.jit(jax.vmap(lambda x, dt: ssd_pallas(
        x, dt, A, B, C, D, 128, interpret=True)))(xs, dts)
    for i in range(2):
        np.testing.assert_allclose(
            both[i], kernel(xs[i], dts[i], A, B, C, D, chunk=128),
            atol=1e-6, rtol=0)


def _shapes(L=512, H=4, P=P, N=N, dtype=jnp.float32, bc=None):
    def s(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt)
    bc = bc or dtype
    return (s((L, H, P), dtype), s((L, H)), s((H,)), s((L, N), bc),
            s((L, N), bc), s((H,)))


@pytest.mark.parametrize("shapes,chunk", [
    (_shapes(P=48), 256),                 # a head across lane tiles
    (_shapes(H=1), 256),                  # 64 lanes: no whole tile
    (_shapes(N=16), 256),                 # a state of 16
    (_shapes(), 48),                      # chunk not whole lane tiles
    (_shapes(L=200), 256),                # a chunk of 200 (the sequence)
    (_shapes(dtype=jnp.float16), 256),
    (_shapes(dtype=jnp.bfloat16, bc=jnp.float32), 256),
    (_shapes(L=8192, H=2048), 256),       # every state: 64 MiB of VMEM
], ids=["P48", "H1", "N16", "chunk48", "L200", "float16", "mixed_dtypes",
        "vmem"])
def test_supports_refuses_what_it_cannot_tile_and_pallas_then_raises(
        shapes, chunk):
    assert not pallas_supports(*shapes, chunk)
    with pytest.raises(ValueError, match="ssm_scan"):
        dispatch.resolve("ssm_scan", "pallas", *shapes, chunk)
    assert dispatch.resolve("ssm_scan", "auto", *shapes, chunk).name == "xla"


@pytest.mark.parametrize("H", [64, 128])
def test_auto_takes_the_kernel_on_a_tpu_process_from_4096_tokens(
        monkeypatch, H):
    """h-micro's and h-small's scans (64 and 128 heads of 64, state 128,
    chunk 256): ``xla`` on this CPU process at any length; as a TPU
    process resolves them, ``pallas`` at the cells' 8192 tokens and from
    ``MIN_TOKENS`` (4096) on, ``xla`` below, where the XLA core is as fast
    or faster; the tiny preset's (chunk 24, head dim 16) stays on
    ``xla``."""
    def core(L, shapes=None):
        shapes = shapes or _shapes(L=L, H=H, dtype=jnp.bfloat16)
        return dispatch.resolve("ssm_scan", "auto", *shapes, 256).name
    assert ops_ssd.MIN_TOKENS == 4096
    for L in (2048, 4096, 8192):
        assert pallas_supports(*_shapes(L=L, H=H, dtype=jnp.bfloat16), 256)
        assert core(L) == "xla"
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    assert [core(L) for L in (1024, 2048, 4096, 8192)] == [
        "xla", "xla", "pallas", "pallas"]
    assert core(0, _shapes(L=128, H=8, P=16, N=16)) == "xla"


def test_ssd_counts_the_core_it_ran(monkeypatch):
    """``ssd`` on a TPU process, the kernel's operands (the rule's length
    lowered to them): the Pallas core runs (interpret mode, this being a
    CPU process) and ``ssm_scan.pallas`` counts it."""
    from diff3d_tpu.utils.profiling import RECORDER

    args = scan_inputs(L=256, H=2, P=P, N=N, decay="mixed", seed=4)
    monkeypatch.setattr(dispatch, "default_backend", lambda: "tpu")
    monkeypatch.setattr(dispatch, "interpret_default", lambda: True)
    monkeypatch.setattr(ops_ssd, "MIN_TOKENS", 256)
    before = RECORDER.counters().get("ssm_scan.pallas", 0)
    got = ssd(*args, 128)
    assert RECORDER.counters()["ssm_scan.pallas"] == before + 1
    np.testing.assert_allclose(got, ssd_chunked(*args, 128),
                               atol=2e-6 * float(jnp.abs(got).max()), rtol=0)
