"""Typed configuration for the whole framework.

The reference scatters its configuration across hardcoded constants
(``/root/reference/train.py:210-217``), argparse flags
(``/root/reference/lightning/train.py:19-28``) and class-attribute defaults
overridden via ``self.__dict__.update(kwargs)``
(``/root/reference/xunet.py:356-369``).  Here everything lives in one place as
frozen dataclasses, including the paper config documented in the reference
docstring (``/root/reference/lightning/diff3d.py:11-20``): peak lr 1e-4 with
linear warmup over the first 10M examples, global batch 128, cond_prob 0.1,
Adam betas (0.9, 0.99), EMA half-life 500K examples.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple, Union


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """X-UNet hyperparameters (reference ``xunet.py:355-366``).

    ``attn_levels`` are *depth levels* (0..num_resolutions), not pixel
    resolutions — same semantics as the reference's ``attn_resolutions``.
    """

    H: int = 128
    W: int = 128
    ch: int = 256
    ch_mult: Sequence[int] = (1, 2, 2, 4)
    emb_ch: int = 1024
    num_res_blocks: int = 3
    attn_levels: Sequence[int] = (2, 3, 4)
    attn_heads: int = 4
    dropout: float = 0.1
    use_pos_emb: bool = True
    use_ref_pose_emb: bool = True
    # Noise-level embedding clip bound; keep equal to
    # DiffusionConfig.logsnr_max (reference hardcodes 20, xunet.py:305).
    logsnr_clip: float = 20.0
    # TPU-first additions (no reference counterpart):
    dtype: str = "bfloat16"        # compute dtype; params stay float32
    remat: bool = False            # jax.checkpoint each UNet block
    # What each rematted block keeps: 'nothing' recomputes everything in
    # the backward (min memory); 'dots' saves matmul/conv outputs and
    # recomputes only cheap elementwise ops (less recompute, more HBM).
    remat_policy: str = "nothing"  # 'nothing' | 'dots'
    # 'auto' | 'pallas' | 'xla', or a sequence-parallel core
    # 'ring:<axis>' / 'ulysses:<axis>' for token-sharded attention inside
    # shard_map (long-context scaling; see ops/attention.py).
    attn_impl: str = "auto"
    # Kernel backend for the fused GroupNorm->FiLM/SiLU epilogues
    # (ops/pallas_film.py via ops/dispatch.py): 'xla' (default) keeps the
    # plain composition — bit-identical graphs to pre-kernel-layer
    # checkpoints; 'pallas' forces the fused kernels or raises (compiled
    # on a TPU process; interpret mode on a CPU process, so CPU tests
    # exercise the TPU tile program); 'auto' uses pallas only on a
    # TPU-default-backend process.  CLI: --pallas.
    kernels: str = "xla"

    @property
    def num_resolutions(self) -> int:
        return len(self.ch_mult)

    def validate(self) -> None:
        down = 2 ** (len(self.ch_mult) - 1)
        if self.H % down or self.W % down:
            raise ValueError(
                f"H={self.H}, W={self.W} must be divisible by {down} "
                f"(len(ch_mult)-1 downsamplings)"
            )
        if self.remat_policy not in ("nothing", "dots"):
            raise ValueError(
                f"remat_policy={self.remat_policy!r} not in "
                "('nothing', 'dots')")
        if self.kernels not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"kernels={self.kernels!r} not in ('auto', 'pallas', "
                "'xla')")
        core, _, axis = self.attn_impl.partition(":")
        if not (self.attn_impl in ("auto", "pallas", "xla")
                or (core in ("ring", "ulysses") and axis)):
            raise ValueError(
                f"attn_impl={self.attn_impl!r}: expected 'auto', 'pallas', "
                "'xla', 'ring:<axis>' or 'ulysses:<axis>'")


@dataclasses.dataclass(frozen=True)
class TokenModelConfig:
    """The token denoiser (``models/token_denoiser.py``): both frames as
    one sequence of ``patch x patch`` patches through pre-norm decoder
    layers, each a sequence mixer and a feed-forward.  Field names follow
    the public language model configs such blocks come from
    (``hidden_size``, ``num_experts``, ``layer_types``, ...), so a
    benchmark configuration file maps onto this one key for key; the
    defaults are the widths of ``benchmark/configs/keye_vl2_tok128.json``.

    Which mixer a layer gets is its entry of ``layer_types``:
    ``"sparse_attention"`` (every layer when the pattern is empty),
    grouped-query attention with per-head RMSNorm and mRoPE over the
    ``indexer_topk`` keys a lightning indexer selects; ``"attention"``,
    grouped-query attention over all keys with no position of any kind
    and scores times ``attention_multiplier``; ``"mamba"``, a Mamba-2
    state-space mixer (``mamba_*``; one group, conv bias, no projection
    bias).  Which feed-forward: routed experts where ``num_experts > 0``
    (each ``moe_intermediate_size`` wide: the ``intermediate_size`` of a
    published config that has no key of its own for it), else a dense
    gated MLP of ``shared_intermediate_size``; with both, the MLP is a
    shared expert beside the routed ones, ``h += r (routed(u) +
    mlp(u))`` on one normed ``u``.

    Multi-head latent attention (``"latent_attention"``, every layer
    when the pattern is empty and ``kv_lora_rank > 0``): queries from a
    ``q_lora_rank`` latent and keys and values from a ``kv_lora_rank``
    latent, each with its own RMSNorm, a ``qk_rope_head_dim`` rotary key
    shared by all heads, values ``v_head_dim`` wide.  The first
    ``first_k_dense_replace`` layers have a dense gated MLP of
    ``dense_intermediate_size`` in place of the experts; ``scoring_func``
    ``"sigmoid"`` gates the top-k by sigmoid scores normalised over the
    k and times ``routed_scaling_factor``; ``sandwich_norm`` adds a norm
    on each half's output before its residual add.  ``param_dtype`` is
    the dtype the parameters are made and held in.

    A ``qwen3_next`` block (``full_attention_interval > 0`` and an empty
    pattern): layer ``i`` is ``"full_attention"`` where ``(i + 1) %
    full_attention_interval == 0``, else ``"linear_attention"``, a Gated
    DeltaNet mixer (``linear_*``: key and value heads, their widths, the
    conv's taps; ``delta_chunk`` tokens a chunk of its scan).
    ``"full_attention"`` is the ``"attention"`` layer with an output gate
    from a second half of ``W_q``, per-head RMSNorms on ``q`` and ``k`` and
    the rotary turn on ``partial_rotary_factor`` of each head's dims.
    Every RMSNorm of such a block but the mixer's gated one scales by
    ``1 + w`` (``w`` made 0), and its shared expert's output is times
    ``sigmoid(w_g^T u)`` (:attr:`qwen3_next`).

    ``experts_held`` is ``(first, count)``: the router scores all
    ``num_experts``, the layer holds the weights of experts ``first ..
    first + count - 1`` and computes their part of the result (all of
    them on one chip; a share of them where experts are spread over
    chips)."""

    H: int = 128
    W: int = 128
    patch: int = 2
    hidden_size: int = 2048
    num_hidden_layers: int = 4
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    # frequency pairs of a head rotated by (frame, patch row, patch column)
    mrope_section: Sequence[int] = (16, 24, 24)
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    experts_held: Tuple[int, int] = (0, 128)
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_topk: int = 2048
    # One mixer kind per layer; () is "sparse_attention" throughout.
    layer_types: Sequence[str] = ()
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2          # n_heads * d_head = expand * hidden_size
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256    # tokens of one chunk of the scan
    shared_intermediate_size: int = 0
    # The four scalars of a block with a rescaled residual path: on the
    # embedding's sum, on each half-layer's output before its residual
    # add, on the "attention" layers' scores (None: head_dim^-1/2), under
    # the head's output.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    # Multi-head latent attention (0: none).
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Leading layers whose feed-forward is a dense MLP of this width.
    first_k_dense_replace: int = 0
    dense_intermediate_size: int = 0
    scoring_func: str = "softmax"  # the router's: 'softmax' | 'sigmoid'
    routed_scaling_factor: float = 1.0
    sandwich_norm: bool = False
    # Gated DeltaNet beside gated full attention (0: none).
    full_attention_interval: int = 0
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    partial_rotary_factor: float = 1.0
    # Tile sizes, not semantics: queries of one example attended at a
    # time; tokens routed at a time; rows of one expert's matmul; tokens
    # of one chunk of the delta rule's scan.
    q_chunk: int = 512
    expert_token_chunk: int = 8192
    expert_block: int = 256
    delta_chunk: int = 64
    emb_ch: int = 256              # width of the logSNR sinusoid
    logsnr_clip: float = 20.0      # as ModelConfig.logsnr_clip
    dtype: str = "bfloat16"        # compute dtype
    param_dtype: str = "float32"   # float32, or the compute dtype

    @property
    def tokens(self) -> int:
        return 2 * (self.H // self.patch) * (self.W // self.patch)

    @property
    def mixers(self) -> Tuple[str, ...]:
        """The mixer kind of every layer, the empty pattern filled in."""
        if self.layer_types:
            return tuple(self.layer_types)
        if self.full_attention_interval:
            return tuple(
                "full_attention" if (i + 1) % self.full_attention_interval
                == 0 else "linear_attention"
                for i in range(self.num_hidden_layers))
        default = ("latent_attention" if self.kv_lora_rank
                   else "sparse_attention")
        return (default,) * self.num_hidden_layers

    @property
    def qwen3_next(self) -> bool:
        """Whether the stack is a ``qwen3_next`` block (it has a
        ``linear_attention`` or ``full_attention`` layer): its norms are
        zero-centred and its shared expert gated."""
        return bool({"linear_attention", "full_attention"}
                    & set(self.mixers))

    @property
    def rotary_dim(self) -> int:
        """Dims of a ``full_attention`` head the rotary turn takes."""
        return int(self.head_dim * self.partial_rotary_factor)

    def dense_layer(self, i: int) -> bool:
        """Whether layer ``i`` is one of the leading dense layers."""
        return bool(self.num_experts) and i < self.first_k_dense_replace

    def validate(self) -> None:
        if self.H % self.patch or self.W % self.patch:
            raise ValueError(
                f"H={self.H}, W={self.W} must be divisible by "
                f"patch={self.patch}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_key_value_heads={self.num_key_value_heads} must "
                f"divide num_attention_heads={self.num_attention_heads}")
        kinds = self.mixers
        unknown = set(kinds) - {"sparse_attention", "attention", "mamba",
                                "latent_attention", "linear_attention",
                                "full_attention"}
        if unknown or len(kinds) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types={kinds} must name 'sparse_attention', "
                f"'attention', 'mamba', 'latent_attention', "
                f"'linear_attention' or 'full_attention' for each of the "
                f"{self.num_hidden_layers} layers")
        if "latent_attention" in kinds:
            self._validate_latent_attention()
        if {"linear_attention", "full_attention"} & set(kinds):
            self._validate_gated_deltanet()
        if self.param_dtype not in ("float32", self.dtype):
            raise ValueError(
                f"param_dtype={self.param_dtype!r} must be 'float32' or "
                f"the compute dtype {self.dtype!r}")
        if self.param_dtype != "float32" and set(kinds) - {
                "latent_attention", "linear_attention", "full_attention"}:
            raise ValueError(
                f"param_dtype={self.param_dtype!r}: only a stack of "
                "'latent_attention', 'linear_attention' and "
                "'full_attention' layers makes its parameters in it (the "
                "other mixers make theirs in float32)")
        if self.first_k_dense_replace:
            if not (self.num_experts and self.dense_intermediate_size > 0
                    and self.first_k_dense_replace
                    < self.num_hidden_layers):
                raise ValueError(
                    f"first_k_dense_replace={self.first_k_dense_replace} "
                    "needs routed experts, dense_intermediate_size > 0 "
                    "and expert layers after the dense ones")
        if "mamba" in kinds:
            if (self.mamba_n_heads * self.mamba_d_head
                    != self.mamba_expand * self.hidden_size):
                raise ValueError(
                    f"mamba_n_heads * mamba_d_head = "
                    f"{self.mamba_n_heads * self.mamba_d_head} must be "
                    f"mamba_expand * hidden_size = "
                    f"{self.mamba_expand * self.hidden_size}")
            if self.mamba_n_groups != 1:
                raise ValueError(
                    f"mamba_n_groups={self.mamba_n_groups}: the mixer "
                    "shares one B and C among all heads")
            if self.mamba_chunk_size < 1 or self.mamba_d_conv < 1:
                raise ValueError("mamba_chunk_size and mamba_d_conv must "
                                 "be >= 1")
        if self.num_experts == 0:
            if self.shared_intermediate_size < 1:
                raise ValueError(
                    "a model without routed experts (num_experts=0) needs "
                    "shared_intermediate_size > 0 for its dense MLP")
        else:
            self._validate_experts()
        if "sparse_attention" in kinds:
            self._validate_sparse_attention()
        if (set(kinds) - {"mamba", "linear_attention"}
                and self.tokens % min(self.q_chunk, self.tokens)):
            raise ValueError(
                f"q_chunk={self.q_chunk} must divide the {self.tokens} "
                "tokens of an example (or exceed them)")

    def _validate_sparse_attention(self) -> None:
        sec = tuple(self.mrope_section)
        if len(sec) != 3 or 2 * sum(sec) != self.head_dim:
            raise ValueError(
                f"mrope_section={sec} must be three counts of frequency "
                f"pairs summing to head_dim/2 = {self.head_dim // 2}")
        if any(s % 2 for s in sec) or 2 * self.indexer_head_dim != self.head_dim:
            raise ValueError(
                "the indexer rotates by half the section sizes: "
                f"mrope_section={sec} must be even and indexer_head_dim="
                f"{self.indexer_head_dim} half of head_dim={self.head_dim}")
        if self.indexer_topk < 1:
            raise ValueError(f"indexer_topk={self.indexer_topk} must be >= 1")

    def _validate_experts(self) -> None:
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(
                f"experts_held={self.experts_held} is not a range of the "
                f"{self.num_experts} experts")
        if not 1 <= self.num_experts_per_tok <= self.num_experts:
            raise ValueError(
                f"num_experts_per_tok={self.num_experts_per_tok} not in "
                f"1..{self.num_experts}")
        if self.scoring_func not in ("softmax", "sigmoid"):
            raise ValueError(f"scoring_func={self.scoring_func!r} not in "
                             "('softmax', 'sigmoid')")
        if self.scoring_func == "softmax" and self.routed_scaling_factor != 1:
            raise ValueError("routed_scaling_factor scales sigmoid gates "
                             "only: the softmax's top-k sum to one")

    def _validate_gated_deltanet(self) -> None:
        heads = (self.linear_num_key_heads, self.linear_num_value_heads,
                 self.linear_key_head_dim, self.linear_value_head_dim,
                 self.linear_conv_kernel_dim, self.delta_chunk)
        if min(heads) < 1 or (self.linear_num_value_heads
                              % self.linear_num_key_heads):
            raise ValueError(
                "Gated DeltaNet needs linear_* heads, widths, conv taps and "
                "delta_chunk >= 1, and key heads dividing value heads "
                f"(have {heads})")
        r = self.rotary_dim
        if r < 2 or r % 2 or r > self.head_dim:
            raise ValueError(
                f"partial_rotary_factor={self.partial_rotary_factor} turns "
                f"{r} of head_dim={self.head_dim}: must be even and >= 2")

    def _validate_latent_attention(self) -> None:
        ranks = (self.q_lora_rank, self.kv_lora_rank, self.qk_nope_head_dim,
                 self.qk_rope_head_dim, self.v_head_dim)
        if min(ranks) < 1 or self.qk_rope_head_dim % 2:
            raise ValueError(
                "latent attention needs q_lora_rank, kv_lora_rank, "
                "qk_nope_head_dim, v_head_dim >= 1 and an even "
                f"qk_rope_head_dim (have {ranks})")


@dataclasses.dataclass(frozen=True)
class DiffusionConfig:
    """Continuous-time logSNR-parameterised VP diffusion (reference
    ``train.py:30-177``)."""

    logsnr_min: float = -20.0
    logsnr_max: float = 20.0
    cond_prob: float = 0.1           # CFG dropout prob (train.py:80)
    loss_type: str = "l2"            # 'l1' | 'l2' | 'huber'
    timesteps: int = 256             # sampler steps (sampling.py:130)
    guidance_weights: Sequence[float] = (0, 1, 2, 3, 4, 5, 6, 7)
    clip_x0: bool = True             # clamp z_start to [-1,1] (train.py:160)


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Trainer settings (reference ``train.py:210-217,235,267`` +
    paper config ``lightning/diff3d.py:11-20``)."""

    lr: float = 1e-4
    betas: Sequence[float] = (0.9, 0.99)
    warmup_examples: int = 10_000_000   # linear warmup over examples
    global_batch: int = 128
    max_steps: int = 100_000
    ckpt_every: int = 50
    log_every: int = 50
    ema_halflife_examples: int = 500_000
    # Gradient accumulation: each optimizer step scans over `accum_steps`
    # microbatches of global_batch/accum_steps examples, averaging grads.
    # Lets the reference's batch-128 config train on HBM that only holds
    # batch-64 activations (no reference counterpart; their answer to OOM
    # was "use a smaller image size", README.md:39).
    accum_steps: int = 1
    # Validation-loss cadence (0 disables).  The reference's own TODO #1
    # ("Assessing the behavior of the loss along training", README.md:32)
    # — it never had a val path; here attach Trainer.val_loader and the
    # EMA params are scored on held-out batches every `eval_every` steps.
    eval_every: int = 0
    seed: int = 0
    checkpoint_dir: str = "checkpoints"
    keep_checkpoints: int = 3
    # "full" = whole TrainState (exact resume); "ema_bf16" = bf16 EMA
    # params only, ~1/16 the bytes — for checkpointing full-width models
    # over constrained device->host links (see train/checkpoint.py).
    # None follows an existing directory marker (resume keeps whatever
    # mode the run started with), defaulting to "full" on fresh dirs.
    ckpt_mode: Optional[str] = None
    # full_sliced only: snapshot device->host on the training thread,
    # commit files from a background writer (retry + backoff + atomic
    # rename), so a slow filesystem no longer stalls the step loop.  The
    # preemption path still waits on the durability barrier before
    # exiting.  False = fully synchronous saves (the parity oracle).
    ckpt_async: bool = True
    grad_clip: float = 0.0            # 0 disables (reference has none)


@dataclasses.dataclass(frozen=True)
class DataConfig:
    """SRN dataset settings (reference ``SRNdataset.py:42-95``)."""

    path: str = "./data/SRN/cars_train"
    picklefile: str = "./data/cars.pickle"
    imgsize: int = 64
    split_seed: int = 0               # random.seed(0) split (SRNdataset.py:52)
    train_fraction: float = 0.9
    num_views_per_sample: int = 2
    prefetch: int = 2                 # device prefetch depth


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device-mesh layout.  The reference's entire distributed surface is data
    parallelism over NCCL/gloo (``train.py:187,224-233``); here the mesh also
    reserves a model axis for tensor/fsdp sharding so scaling beyond DP is a
    config change, not a rewrite."""

    data_axis: str = "data"
    model_axis: str = "model"
    data_parallel: int = -1           # -1: all devices
    model_parallel: int = 1
    # 'replicated' keeps params/opt-state replicated like the reference's
    # DDP; 'fsdp' shards them over the data axis (ZeRO-ish); 'tp' applies
    # Megatron-style rules over the model axis (attention q/k/v column-,
    # out-proj row-parallel, conv output channels); 'fsdp+tp' composes
    # both (TP rule first, then the largest free axis over data).
    param_sharding: str = "replicated"
    # GSPMD context parallelism: shard the activations' spatial (image-row
    # = token) axis over the model axis via sharding constraints between
    # UNet blocks; XLA inserts conv halo exchanges, global GroupNorm
    # reductions, and attention KV gathers.  Activation memory per device
    # drops by the axis size — for resolutions past what one chip's HBM
    # holds.  (The shard_map alternative for the attention op alone is
    # ModelConfig.attn_impl='ring:<axis>'.)
    context_parallel: bool = False


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Batched novel-view inference service (``diff3d_tpu/serving``).

    The service shares the chip across concurrent requests by microbatching
    them into fixed-shape device batches (bucketed by image size and record
    capacity) and admitting new requests between view steps (continuous
    batching at view granularity).  No reference counterpart — the
    reference stops at a one-shot offline sampler (``sampling.py:169-184``).
    """

    host: str = "127.0.0.1"
    port: int = 8080
    # Backpressure: submissions beyond this many pending requests are
    # REJECTED (HTTP 429), never silently queued without bound.
    max_queue: int = 64
    # Device-batch lane ceiling per bucket; the engine pads the active set
    # up to the next power of two <= max_batch (logarithmic number of
    # compiled programs per bucket, same trick as the record capacity).
    # When the sampler rides a mesh, the engine additionally rounds lane
    # counts — and this ceiling itself — UP to a multiple of the mesh's
    # data-axis size (a sharded object axis must divide evenly; see
    # serving/engine.py lane_count).
    max_batch: int = 8
    # Microbatcher flush deadline: after the first request of a bucket
    # arrives, wait at most this long for co-batchable requests before
    # launching underfull.
    max_wait_ms: float = 50.0
    # Per-request wall-clock deadline (queue wait + compute); expired
    # requests get an explicit timeout error, not a hang.
    default_timeout_s: float = 300.0
    # LRU result cache entries keyed by request content hash (0 disables).
    result_cache_entries: int = 32
    # Per-request view-count ceiling (bounds record capacity / HBM).
    max_views: int = 16
    # ---- fault tolerance (serving/engine.py watchdog + health) ------
    # Stuck-step watchdog: a view-step dispatch older than this is
    # declared stuck — its in-flight requests are failed with a typed
    # retryable error and the engine degrades.  Generous by default
    # (srn128 runs ~107 s/view); 0 disables the watchdog.
    watchdog_timeout_s: float = 600.0
    # Attempts per view-step dispatch (1 = no retry) and the base
    # backoff between them.  Inputs are re-stacked host buffers, so a
    # re-dispatch after a transient backend fault is safe and bit-exact.
    step_retry_attempts: int = 2
    step_retry_backoff_s: float = 0.2
    # Consecutive clean steps required to leave `degraded` for `ok`.
    degraded_recovery_steps: int = 3
    # Advisory client wait carried on typed retryable rejections
    # (HTTP maps it to a Retry-After header).
    retry_after_s: float = 5.0
    # Watchdog respawns of a dead engine loop before giving up and
    # failing new submissions fast.
    engine_max_restarts: int = 3
    # ---- fleet (serving/router.py) ----------------------------------
    # In-process engine replicas behind the fleet router's front door
    # (1 = single-replica ServingService, no router).  Each replica owns
    # its own scheduler/engine/program cache; sessions pin to replicas.
    replicas: int = 1
    # ---- cross-process fleet (serving/transport.py, DESIGN.md §19) ---
    # RemoteReplica connection supervision: probe the worker every
    # `interval`; a worker silent past `timeout` is marked dead (its
    # sticky sessions get SessionLost, exactly like an in-process kill).
    heartbeat_interval_s: float = 0.25
    heartbeat_timeout_s: float = 3.0
    # Transport frame-size ceiling (a garbage length prefix must not
    # demand gigabytes of buffer).
    max_frame_bytes: int = 1 << 30

    def validate(self) -> None:
        if self.max_batch < 1:
            raise ValueError(f"max_batch={self.max_batch} must be >= 1")
        if self.max_queue < 1:
            raise ValueError(f"max_queue={self.max_queue} must be >= 1")
        if self.max_wait_ms < 0:
            raise ValueError(f"max_wait_ms={self.max_wait_ms} must be >= 0")
        if self.default_timeout_s <= 0:
            raise ValueError(
                f"default_timeout_s={self.default_timeout_s} must be > 0")
        if self.max_views < 2:
            raise ValueError(
                f"max_views={self.max_views} must be >= 2 (one "
                "conditioning view + one target)")
        if self.watchdog_timeout_s < 0:
            raise ValueError(
                f"watchdog_timeout_s={self.watchdog_timeout_s} must be "
                ">= 0 (0 disables)")
        if self.step_retry_attempts < 1:
            raise ValueError(
                f"step_retry_attempts={self.step_retry_attempts} must be "
                ">= 1 (1 = no retry)")
        if self.step_retry_backoff_s < 0:
            raise ValueError(
                f"step_retry_backoff_s={self.step_retry_backoff_s} must "
                "be >= 0")
        if self.degraded_recovery_steps < 1:
            raise ValueError(
                f"degraded_recovery_steps={self.degraded_recovery_steps} "
                "must be >= 1")
        if self.retry_after_s <= 0:
            raise ValueError(
                f"retry_after_s={self.retry_after_s} must be > 0")
        if self.engine_max_restarts < 0:
            raise ValueError(
                f"engine_max_restarts={self.engine_max_restarts} must be "
                ">= 0")
        if self.replicas < 1:
            raise ValueError(f"replicas={self.replicas} must be >= 1")
        if self.heartbeat_interval_s <= 0:
            raise ValueError(
                f"heartbeat_interval_s={self.heartbeat_interval_s} must "
                "be > 0")
        if self.heartbeat_timeout_s <= self.heartbeat_interval_s:
            raise ValueError(
                f"heartbeat_timeout_s={self.heartbeat_timeout_s} must "
                f"exceed heartbeat_interval_s={self.heartbeat_interval_s} "
                "(a single missed probe must not kill a replica)")
        if self.max_frame_bytes < (1 << 16):
            raise ValueError(
                f"max_frame_bytes={self.max_frame_bytes} must be >= 64 KiB "
                "(a single 8x8 view frame already needs ~1 KiB of JSON)")


@dataclasses.dataclass(frozen=True)
class Config:
    # The kind of denoiser is the type of this field, read in one place:
    # diff3d_tpu.models.build_model.
    model: Union[ModelConfig, TokenModelConfig] = dataclasses.field(
        default_factory=ModelConfig)
    diffusion: DiffusionConfig = dataclasses.field(default_factory=DiffusionConfig)
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)
    data: DataConfig = dataclasses.field(default_factory=DataConfig)
    mesh: MeshConfig = dataclasses.field(default_factory=MeshConfig)
    serving: ServingConfig = dataclasses.field(default_factory=ServingConfig)

    def validate(self) -> None:
        self.model.validate()
        self.serving.validate()
        if self.mesh.context_parallel and self.mesh.model_parallel <= 1:
            raise ValueError(
                "context_parallel shards the spatial axis over the model "
                f"axis, but model_parallel={self.mesh.model_parallel} makes "
                "that a no-op — set model_parallel > 1")
        if self.train.global_batch % max(1, self.train.accum_steps):
            raise ValueError(
                f"global_batch ({self.train.global_batch}) must be "
                f"divisible by accum_steps ({self.train.accum_steps})")
        if self.model.logsnr_clip != self.diffusion.logsnr_max:
            raise ValueError(
                f"model.logsnr_clip ({self.model.logsnr_clip}) must equal "
                f"diffusion.logsnr_max ({self.diffusion.logsnr_max}) — the "
                "noise-level embedding clip and the schedule bound are the "
                "same quantity")


def srn64_config() -> Config:
    """The config every reference entry point actually runs:
    ``XUNet(H=64, W=64, ch=128)`` (train.py:229, lightning/diff3d.py:38,
    sampling.py:51) at batch 128."""
    return Config(model=ModelConfig(H=64, W=64, ch=128))


def srn128_config() -> Config:
    """The paper's full-resolution config (README.md:39 notes it OOMs on the
    reference's 8x3090; on TPU we enable bf16 + remat instead)."""
    return Config(model=ModelConfig(H=128, W=128, ch=256, remat=True))


def token_test_config(imgsize: int = 16) -> Config:
    """Tiny token-denoiser config for unit tests and CPU drives: hidden
    64, 8 experts top-2, 2 layers, the indexer keeping a quarter of the
    ``2 * (imgsize / 2)^2`` keys (32 of 128 at 16x16), as the full-width
    configuration keeps 2048 of 8192."""
    tokens = 2 * (imgsize // 2) ** 2
    return Config(
        model=TokenModelConfig(
            H=imgsize, W=imgsize, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=2, head_dim=32,
            mrope_section=(4, 6, 6), num_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=32, experts_held=(0, 8),
            indexer_num_heads=4, indexer_head_dim=16,
            indexer_topk=tokens // 4, q_chunk=tokens // 2,
            expert_token_chunk=4 * tokens, expert_block=16, emb_ch=32,
            dtype="float32"),
        train=TrainConfig(global_batch=8, warmup_examples=1024,
                          max_steps=4, ckpt_every=2, log_every=1),
        data=DataConfig(imgsize=imgsize),
        diffusion=DiffusionConfig(timesteps=4),
    )


def hybrid_test_config(imgsize: int = 16) -> Config:
    """Tiny hybrid token-denoiser config for unit tests and CPU drives:
    ``mamba, mamba, attention, mamba, mamba`` at hidden 64 with a dense
    gated MLP, the 128 tokens of a 16x16 pair in chunks of 24 (five whole
    chunks and a part of a sixth), a rescaled residual path."""
    tokens = 2 * (imgsize // 2) ** 2
    return Config(
        model=TokenModelConfig(
            H=imgsize, W=imgsize, hidden_size=64, num_hidden_layers=5,
            layer_types=("mamba", "mamba", "attention", "mamba", "mamba"),
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            rms_norm_eps=1e-5, num_experts=0, num_experts_per_tok=0,
            shared_intermediate_size=96, mamba_n_heads=8, mamba_d_head=16,
            mamba_d_state=16, mamba_d_conv=4, mamba_expand=2,
            mamba_chunk_size=24, embedding_multiplier=12.0,
            residual_multiplier=0.22, attention_multiplier=1.0 / 16,
            logits_scaling=8.0, q_chunk=tokens // 2, emb_ch=32,
            dtype="float32"),
        train=TrainConfig(global_batch=8, warmup_examples=1024,
                          max_steps=4, ckpt_every=2, log_every=1),
        data=DataConfig(imgsize=imgsize),
        diffusion=DiffusionConfig(timesteps=4),
    )


def hybrid_moe_test_config(imgsize: int = 16) -> Config:
    """:func:`hybrid_test_config` with the feed-forward of two branches:
    routed experts (24, top-4, 32 wide) beside a shared expert (48 wide)
    under one norm, and of the experts one of eight chips' share, the
    first three."""
    base = hybrid_test_config(imgsize)
    return dataclasses.replace(base, model=dataclasses.replace(
        base.model, num_experts=24, num_experts_per_tok=4,
        moe_intermediate_size=32, shared_intermediate_size=48,
        experts_held=(0, 3), expert_token_chunk=2 * base.model.tokens,
        expert_block=16))


def mla_moe_test_config(imgsize: int = 16) -> Config:
    """Tiny token-denoiser config with latent attention under sandwich
    norms: one dense layer, then two layers of sigmoid-routed experts (8
    of 16 held, top-4, times 2.5) beside a shared expert, hidden 256,
    value heads narrower than query / key heads (32 against 48)."""
    tokens = 2 * (imgsize // 2) ** 2
    return Config(
        model=TokenModelConfig(
            H=imgsize, W=imgsize, hidden_size=256, num_hidden_layers=3,
            num_attention_heads=4, num_key_value_heads=4, head_dim=48,
            rms_norm_eps=1e-5, rope_theta=25_600_000.0, q_lora_rank=64,
            kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
            v_head_dim=32, num_experts=16, num_experts_per_tok=4,
            moe_intermediate_size=64, experts_held=(0, 8),
            shared_intermediate_size=64, first_k_dense_replace=1,
            dense_intermediate_size=384, scoring_func="sigmoid",
            routed_scaling_factor=2.5, sandwich_norm=True,
            q_chunk=tokens // 2, expert_token_chunk=2 * tokens,
            expert_block=16, emb_ch=32, dtype="float32"),
        train=TrainConfig(global_batch=8, warmup_examples=1024,
                          max_steps=4, ckpt_every=2, log_every=1),
        data=DataConfig(imgsize=imgsize),
        diffusion=DiffusionConfig(timesteps=4),
    )


def gdn_moe_test_config(imgsize: int = 16) -> Config:
    """Tiny token-denoiser config on a ``qwen3_next`` block: one period,
    three Gated DeltaNet layers (2 key and 4 value heads of 16, chunks of
    24: five whole and a part of a sixth of the 128 tokens) and one gated
    attention layer (4 / 2 heads of 32, the rotary turn on 8 dims), every
    layer's experts 8 of 16 held, top-4, beside a gated shared expert,
    zero-centred norms, hidden 64."""
    tokens = 2 * (imgsize // 2) ** 2
    return Config(
        model=TokenModelConfig(
            H=imgsize, W=imgsize, hidden_size=64, num_hidden_layers=4,
            full_attention_interval=4, num_attention_heads=4,
            num_key_value_heads=2, head_dim=32, partial_rotary_factor=0.25,
            rope_theta=10_000_000, rms_norm_eps=1e-6,
            linear_num_key_heads=2, linear_num_value_heads=4,
            linear_key_head_dim=16, linear_value_head_dim=16,
            linear_conv_kernel_dim=4, num_experts=16,
            num_experts_per_tok=4, moe_intermediate_size=32,
            experts_held=(0, 8), shared_intermediate_size=32,
            q_chunk=tokens // 2, expert_token_chunk=2 * tokens,
            expert_block=16, delta_chunk=24, emb_ch=32, dtype="float32"),
        train=TrainConfig(global_batch=8, warmup_examples=1024,
                          max_steps=4, ckpt_every=2, log_every=1),
        data=DataConfig(imgsize=imgsize),
        diffusion=DiffusionConfig(timesteps=4),
    )


def test_config(imgsize: int = 16, ch: int = 8,
                shallow: bool = False) -> Config:
    """Tiny config for unit tests / CPU-mesh dry runs.

    ``shallow=True`` uses a 2-level UNet (vs the reference's 4) — half
    the blocks to compile.  For tests of *properties that don't depend on
    depth* (sharded==replicated equality, NaN guards, accumulation);
    structure-sensitive tests (up-path bookkeeping, whole-model torch
    parity, the driver dryrun) keep the full 4-level shape.
    """
    model_kw = dict(H=imgsize, W=imgsize, ch=ch, emb_ch=32,
                    num_res_blocks=1, dropout=0.0, dtype="float32")
    if shallow:
        model_kw.update(ch_mult=(1, 2), attn_levels=(1, 2))
    return Config(
        model=ModelConfig(**model_kw),
        train=TrainConfig(global_batch=8, warmup_examples=1024,
                          max_steps=4, ckpt_every=2, log_every=1),
        data=DataConfig(imgsize=imgsize),
        diffusion=DiffusionConfig(timesteps=4),
    )


#: The presets the CLIs' ``--config`` names, by the name of the function
#: in this module that builds each.
NAMED_CONFIGS = {"srn64": "srn64_config", "srn128": "srn128_config",
                 "test": "test_config", "token_test": "token_test_config",
                 "hybrid_test": "hybrid_test_config",
                 "hybrid_moe_test": "hybrid_moe_test_config",
                 "mla_moe_test": "mla_moe_test_config",
                 "gdn_moe_test": "gdn_moe_test_config"}


def named_config(name: str) -> Config:
    """The preset ``name``, built by this module's function of that name
    as it is bound now (tests stand a patched builder in its place)."""
    return globals()[NAMED_CONFIGS[name]]()
