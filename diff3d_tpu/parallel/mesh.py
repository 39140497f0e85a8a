"""Device mesh + sharding-spec layer.

The reference's distributed surface is ``torch.distributed`` DDP over gloo
(``/root/reference/train.py:187,224-233`` — broken as shipped, SURVEY.md
§2.7) plus per-step ``dist.barrier()`` calls.  The TPU-native equivalent:
one ``jax.sharding.Mesh`` over ``(data, model)`` axes; ``jit`` with
``NamedSharding`` in/out specs compiles the gradient all-reduce into XLA
collectives that ride ICI within a slice and DCN across slices.  No
user-level barriers exist because every compiled step is globally
synchronous by construction.

Param placement is a config switch (``MeshConfig.param_sharding``):

  * ``'replicated'`` — DDP-like; params/opt-state replicated, gradients
    all-reduced (what the reference intends).
  * ``'fsdp'``       — ZeRO-style; each param's largest divisible axis is
    sharded over the data axis, all-gathered on use.

The ``model`` axis is reserved for tensor parallelism — not needed for
reference parity (SURVEY.md §2.8: the reference has DP only) but a config
change, not a rewrite, when models outgrow a chip.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from diff3d_tpu.config import MeshConfig


@dataclasses.dataclass(frozen=True)
class MeshEnv:
    """A mesh plus the sharding rules derived from config."""

    mesh: Mesh
    cfg: MeshConfig

    @property
    def data_axis(self) -> str:
        return self.cfg.data_axis

    @property
    def data_size(self) -> int:
        """Number of devices on the data axis — the divisibility quantum
        for any leading dim sharded with :meth:`batch` (the sampler's
        object axis, the serving engine's lane counts)."""
        return int(self.mesh.shape[self.cfg.data_axis])

    def batch(self) -> NamedSharding:
        return batch_sharding(self.mesh, self.cfg.data_axis)

    def replicated(self) -> NamedSharding:
        return replicated_sharding(self.mesh)

    def state_shardings(self, state):
        """Sharding pytree for a :class:`TrainState`-shaped object: step
        replicated, params / opt-state / EMA per the param policy.  The one
        placement rule every trainer, bench, and dry run shares."""
        return type(state)(
            step=self.replicated(),
            params=self.params(state.params),
            opt_state=self.params(state.opt_state),
            ema_params=self.params(state.ema_params),
        )

    def activation_constraint(self):
        """``h -> h`` hook sharding ``[B, F, H, W, C]`` activations: batch
        over the data axis, image rows (the token axis once flattened to
        ``H*W`` sequences — H is the outer dim of the merge, so GSPMD
        propagates the sharding through the reshape) over the model axis.
        Threaded through :meth:`XUNet.__call__ <diff3d_tpu.models.xunet.
        XUNet.__call__>`'s ``constrain`` kwarg when
        ``MeshConfig.context_parallel`` is on."""
        sh = NamedSharding(
            self.mesh, P(self.cfg.data_axis, None, self.cfg.model_axis))

        def constrain(h):
            if h.ndim != 5:
                return h
            return jax.lax.with_sharding_constraint(h, sh)

        return constrain

    def param_spec_table(self, pytree) -> dict:
        """Flat ``{leaf path: str(PartitionSpec)}`` of the policy's
        intended placement — works on abstract (``ShapeDtypeStruct``)
        templates since only shapes are read.  The human-readable side
        of :meth:`params`, used by shardcheck's reports to say which
        placement each param *should* have gotten."""
        flat = jax.tree_util.tree_flatten_with_path(
            self.params(pytree),
            is_leaf=lambda x: isinstance(x, NamedSharding))[0]
        return {jax.tree_util.keystr(path): str(tuple(sh.spec))
                for path, sh in flat}

    def topology_summary(self) -> dict:
        """JSON-able description of the mesh topology this env shards
        over.  Stamped into checkpoint manifests so a restore into a
        *different* topology is recognised as a first-class reshard (and
        logged as such) rather than silently assumed identical — the
        elasticity loop's re-mesh contract (docs/DESIGN.md §16)."""
        return {
            "axes": {k: int(v) for k, v in self.mesh.shape.items()},
            "n_devices": int(self.mesh.size),
            "n_processes": int(jax.process_count()),
            "param_sharding": self.cfg.param_sharding,
        }

    def params(self, pytree) -> object:
        """Sharding pytree for params/opt-state per the config policy."""
        mode = self.cfg.param_sharding
        if mode == "replicated":
            return jax.tree.map(lambda _: self.replicated(), pytree)
        if mode == "fsdp":
            return jax.tree.map(
                lambda x: param_sharding(self.mesh, np.shape(x),
                                         self.cfg.data_axis), pytree)
        if mode in ("tp", "fsdp+tp"):
            fsdp_axis = self.cfg.data_axis if mode == "fsdp+tp" else None
            return jax.tree_util.tree_map_with_path(
                lambda path, x: tp_param_sharding(
                    self.mesh, path, np.shape(x), self.cfg.model_axis,
                    fsdp_axis=fsdp_axis), pytree)
        raise ValueError(mode)


def make_mesh(cfg: MeshConfig = MeshConfig(),
              devices: Optional[Sequence[jax.Device]] = None) -> MeshEnv:
    """Build a ``(data, model)`` mesh over all (or given) devices.

    ``data_parallel == -1`` takes every device not claimed by
    ``model_parallel``.  Placement is ICI-topology-aware: on a full
    device set ``mesh_utils.create_device_mesh`` orders the grid so the
    (inner) model axis rides the fastest ICI links, and on multi-slice
    TPU (slices joined by DCN) ``create_hybrid_device_mesh`` keeps the
    model axis inside a slice and splits only the data axis across the
    DCN boundary — the scaling-playbook layout.  Explicit device subsets
    (tests, dry runs) fall back to a plain reshape of the given order.
    """
    explicit = devices is not None
    devices = list(devices if devices is not None else jax.devices())
    mp = max(1, cfg.model_parallel)
    dp = cfg.data_parallel
    if dp == -1:
        dp = len(devices) // mp
    if dp * mp > len(devices):
        raise ValueError(
            f"mesh {dp}x{mp} needs {dp * mp} devices, have {len(devices)}")
    grid = _device_grid(devices[: dp * mp], dp, mp,
                        topology_aware=not explicit)
    mesh = Mesh(grid, (cfg.data_axis, cfg.model_axis))
    return MeshEnv(mesh=mesh, cfg=cfg)


def _device_grid(devices: list, dp: int, mp: int,
                 topology_aware: bool) -> np.ndarray:
    """[dp, mp] device grid, ICI/DCN-aware when possible."""
    fallback = np.asarray(devices).reshape(dp, mp)
    if not topology_aware or len(devices) <= 1:
        return fallback
    try:
        from jax.experimental import mesh_utils

        slices = {getattr(d, "slice_index", 0) for d in devices}
        if len(slices) > 1:
            # Multi-slice: model axis must stay inside a slice (ICI); the
            # data axis absorbs the across-slice (DCN) factor.
            n_slices = len(slices)
            if dp % n_slices:
                return fallback
            return mesh_utils.create_hybrid_device_mesh(
                (dp // n_slices, mp), (n_slices, 1), devices=devices)
        return mesh_utils.create_device_mesh((dp, mp), devices=devices)
    except Exception:
        # Any topology helper failure (odd shapes, virtual devices) must
        # never block mesh construction.
        return fallback


def batch_sharding(mesh: Mesh, data_axis: str = "data") -> NamedSharding:
    """Leading (batch) dim over the data axis, rest replicated."""
    return NamedSharding(mesh, P(data_axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def tp_param_sharding(mesh: Mesh, path, shape: Sequence[int],
                      model_axis: str = "model",
                      fsdp_axis: Optional[str] = None) -> NamedSharding:
    """Megatron-style tensor-parallel spec for one X-UNet param leaf.

    GSPMD turns these seed shardings into the classic TP comm pattern at
    compile time (column-parallel q/k/v needs no collective; the row-
    parallel out-proj matmul reduces partial sums over the model axis):

      * attention ``q/k/v_proj`` kernels ``[C, C]`` — output dim over
        ``model`` (column parallel); their biases likewise.
      * attention ``out_proj`` kernel — input dim over ``model`` (row
        parallel); bias replicated.
      * conv kernels ``[kh, kw, cin, cout]`` and Dense kernels (FiLM,
        logsnr MLP) — output channels over ``model``; biases likewise.
      * the token denoiser's expert stacks ``moe/w_gate``, ``w_up``,
        ``w_down`` ``[experts, in, out]`` — the expert dim over ``model``
        (expert parallelism: a device holds whole experts); the router,
        which scores all experts, replicated.  The layer itself is one
        device's program today (models/moe.py): on a mesh GSPMD gathers
        what its dynamic expert index needs, with no token exchange.
      * the token denoiser's dense MLP ``mlp/w1`` ``[D, 2 F]`` column-
        and ``mlp/w2`` ``[F, D]`` row-parallel; its plain attention's
        ``q/k/v_proj`` and ``o_proj`` likewise.  The same two leaves
        where the MLP is a shared expert beside an expert stack (one
        layer then has ``moe/...`` by whole experts, ``mlp/...`` split
        inside, and a replicated mixer).
      * latent attention: the latent projections ``q_a_proj``,
        ``kv_a_proj`` replicated, explicitly (their outputs are normed
        whole, and ``kv_a_proj``'s carries the rotary key all heads
        share); the expansions ``q_b_proj``, ``kv_b_proj`` column-
        parallel (whole heads where the head count divides), ``o_proj``
        row-parallel as above.
      * every leaf of a state-space mixer (``mamba/...``) — replicated,
        explicitly: the fused ``[z | xBC | dt]`` projection does not
        split at one column boundary, and the heads' state, conv and
        gated norm would have to follow it (ROADMAP reach B3).  The same
        for a Gated DeltaNet mixer (``linear_attn/...``), whose
        ``[q | k | v | z]`` projection is four runs of columns that a
        split by heads would have to regroup by key head first.  A gated
        attention layer's ``q_proj`` (``[q_h | gate_h]`` by head) is
        column-parallel as above, by whole heads where they divide; its
        per-head norms and the shared expert's one-column ``gate`` are
        replicated.
      * everything else (norm scales, learned pose embeddings, tiny
        leaves) — replicated.

    Dims not divisible by the axis size fall back to replication.  With
    ``fsdp_axis`` set, the largest still-unsharded divisible dim is
    additionally sharded over it (ZeRO-style weight sharding on top of TP).
    """
    names = [getattr(p, "key", str(p)) for p in path]
    tp = mesh.shape[model_axis]
    spec: list = [None] * len(shape)

    def shardable(dim: int) -> bool:
        return len(shape) > dim and shape[dim] % tp == 0 and shape[dim] >= tp

    is_kernel = names and names[-1] == "kernel"
    if "mamba" in names or "linear_attn" in names or any(
            n in ("q_a_proj", "kv_a_proj") for n in names):
        pass                               # whole on every device
    elif tp > 1 and names and names[-1] in ("w_gate", "w_up", "w_down"):
        if len(shape) == 3 and shardable(0):
            spec[0] = model_axis           # expert stacks: whole experts
    elif tp > 1 and is_kernel:
        if any(n in ("q_proj", "k_proj", "v_proj", "q_b_proj",
                     "kv_b_proj") for n in names):
            if shardable(len(shape) - 1):
                spec[-1] = model_axis
        elif any(n in ("out_proj", "o_proj", "w2") for n in names):
            if shardable(0):
                spec[0] = model_axis
        elif shardable(len(shape) - 1) and shape[-1] > 4:
            spec[-1] = model_axis          # conv/Dense output channels
    elif tp > 1 and names and names[-1] == "bias":
        # Only biases of column-parallel layers (q/k/v, convs, Dense):
        # norm biases stay replicated with their (replicated) scales, and
        # the row-parallel out_proj bias is added after the reduce.
        parent = names[-2] if len(names) >= 2 else ""
        col_parallel = (parent in ("q_proj", "k_proj", "v_proj")
                        or "conv" in parent or parent.startswith("Dense")
                        or parent == "skip_proj")
        if col_parallel and shardable(0) and shape[0] > 4:
            spec[0] = model_axis

    if fsdp_axis is not None:
        n = mesh.shape[fsdp_axis]
        free = [i for i, s in enumerate(shape)
                if spec[i] is None and s % n == 0 and s >= n]
        if free and int(np.prod(shape)) >= n * 128:
            axis = max(free, key=lambda i: shape[i])
            spec[axis] = fsdp_axis
    return NamedSharding(mesh, P(*spec))


def param_sharding(mesh: Mesh, shape: Sequence[int],
                   data_axis: str = "data") -> NamedSharding:
    """FSDP-style spec: shard the largest axis divisible by the data-axis
    size; replicate params too small to bother (< one tile per device)."""
    n = mesh.shape[data_axis]
    if n == 1 or not shape or int(np.prod(shape)) < n * 128:
        return NamedSharding(mesh, P())
    candidates = [i for i, s in enumerate(shape) if s % n == 0]
    if not candidates:
        return NamedSharding(mesh, P())
    axis = max(candidates, key=lambda i: shape[i])
    spec = [None] * len(shape)
    spec[axis] = data_axis
    return NamedSharding(mesh, P(*spec))
