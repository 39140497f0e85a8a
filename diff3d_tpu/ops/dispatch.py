"""Shared kernel-dispatch registry for the hot-path ops.

One registry answers "which implementation of op X runs here?" for every
backend-dispatched op in the model — attention's sdpa core and the fused
GroupNorm epilogues (:mod:`diff3d_tpu.ops.pallas_film`).  Before this
module each op hand-rolled its own resolution (``attention._resolve_auto``);
the rules are now stated once:

  * ``'xla'``    — the plain XLA composition, always available.  The
    default everywhere: CPU-mesh tests, the analysis pillars' lowering
    passes and converted-checkpoint parity all run it.
  * ``'pallas'`` — the hand-tiled TPU kernel.  An explicit request is
    honoured or it raises: operands the registered ``supports``
    predicate rejects are a ``ValueError`` naming op and shapes, never a
    silent swap to xla.  On a CPU process the kernels run in Pallas
    interpret mode (:func:`interpret_default`) — that is how the CPU
    tests exercise the exact TPU tile program; on a TPU process they are
    compiled, always.
  * ``'auto'``   — the only request that may choose: pallas on a
    TPU-default-backend process when the impl's ``auto`` policy (a
    heuristic, e.g. attention's D>64/L>=4096 rule) and ``supports`` both
    accept the operands; else xla.

Resolution happens at TRACE time from static shapes/dtypes and the
process-default backend, so dispatch can never introduce a retrace
(pinned by ``tests/test_pallas_film.py``'s compile_budget test).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional


def _always(*args, **kwargs) -> bool:
    return True


@dataclasses.dataclass(frozen=True)
class KernelImpl:
    """One registered implementation of a dispatched op.

    ``supports`` gates correctness (shapes/dtypes the kernel handles at
    all); ``auto`` gates the 'auto' policy (where the kernel *wins*).
    Both see the same operands the caller passes to :func:`resolve`.
    """

    op: str
    name: str
    fn: Callable
    supports: Callable[..., bool] = _always
    auto: Callable[..., bool] = _always


_REGISTRY: Dict[str, Dict[str, KernelImpl]] = {}


def register(op: str, name: str, fn: Callable, *,
             supports: Optional[Callable[..., bool]] = None,
             auto: Optional[Callable[..., bool]] = None) -> KernelImpl:
    """Register ``fn`` as implementation ``name`` of ``op``.
    Re-registering the same (op, name) replaces the entry (module
    reload friendliness); every op must register an 'xla' fallback."""
    impl = KernelImpl(op=op, name=name, fn=fn,
                      supports=supports or _always, auto=auto or _always)
    _REGISTRY.setdefault(op, {})[name] = impl
    return impl


def implementations(op: str) -> Dict[str, KernelImpl]:
    """The registered implementations of ``op`` (empty dict if none)."""
    return dict(_REGISTRY.get(op, {}))


def default_backend() -> str:
    """Process-default jax backend.  A backend that fails to come up
    raises here — it is never read as 'cpu'."""
    import jax

    return jax.default_backend()


def interpret_default() -> bool:
    """Whether a Pallas kernel called without an explicit ``interpret``
    runs in interpret mode: on a CPU process only.  A TPU process
    compiles its kernels or the call raises."""
    return default_backend() != "tpu"


def _describe(args, kwargs) -> str:
    parts = [f"{getattr(a, 'dtype', type(a).__name__)}"
             f"{list(getattr(a, 'shape', ()))}" for a in args]
    parts += [f"{k}={v!r}" for k, v in kwargs.items()]
    return ", ".join(parts)


def resolve(op: str, requested: str, *args, **kwargs) -> KernelImpl:
    """Resolve ``requested`` ('auto' | 'pallas' | 'xla') to a registered
    implementation of ``op`` given the operands.

    The operands are passed to the candidate's ``supports`` / ``auto``
    predicates; they are trace-time values, so only static properties
    (shape, dtype) may be inspected.
    """
    impls = _REGISTRY.get(op)
    if not impls:
        raise KeyError(f"no implementations registered for op {op!r}")
    if requested not in ("auto", "pallas", "xla"):
        raise ValueError(
            f"op {op!r}: requested impl {requested!r} not in "
            "('auto', 'pallas', 'xla')")
    pallas = impls.get("pallas")
    if requested == "pallas":
        if pallas is None:
            raise KeyError(f"op {op!r} has no 'pallas' implementation")
        if not pallas.supports(*args, **kwargs):
            raise ValueError(
                f"op {op!r}: 'pallas' was requested explicitly but the "
                f"kernel does not support ({_describe(args, kwargs)}); "
                "request 'auto' to let dispatch choose, or 'xla'")
        return pallas
    if requested == "auto" and pallas is not None \
            and default_backend() == "tpu" \
            and pallas.auto(*args, **kwargs) \
            and pallas.supports(*args, **kwargs):
        return pallas
    xla = impls.get("xla")
    if xla is None:
        raise KeyError(f"op {op!r} has no 'xla' fallback registered")
    return xla


def dispatch(op: str, requested: str, *args, **kwargs):
    """Resolve and call in one step."""
    return resolve(op, requested, *args, **kwargs).fn(*args, **kwargs)
