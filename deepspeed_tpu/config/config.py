"""Typed JSON configuration for deepspeed_tpu.

Mirrors the reference's config surface (DeepSpeedConfig,
reference: deepspeed/runtime/config.py:648 and the pydantic
DeepSpeedConfigModel machinery in runtime/config_utils.py:17) with the same
JSON keys — ``train_batch_size``, ``train_micro_batch_size_per_gpu``,
``gradient_accumulation_steps``, ``zero_optimization``, ``bf16``/``fp16``,
``optimizer``, ``scheduler``, ``gradient_clipping`` — so an existing DeepSpeed
JSON config parses unchanged.  Implementation is dataclass-based (no pydantic
dependency) with the same batch-size arithmetic/validation semantics
(reference: runtime/config.py `_batch_assertion`/`_do_batch_inference`).
"""
from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "DeepSpeedTPUConfig",
    "ZeroConfig",
    "OffloadConfig",
    "PrecisionConfig",
    "OptimizerConfig",
    "SchedulerConfig",
    "ParallelConfig",
    "MoEConfig",
    "ActivationCheckpointingConfig",
    "CheckpointConfig",
    "MonitorConfig",
    "ServingConfig",
    "TenancyConfig",
    "TracingConfig",
    "FleetConfig",
    "CommsLoggerConfig",
    "FlopsProfilerConfig",
    "CompressionConfig",
    "DataEfficiencyConfig",
    "ElasticityConfig",
    "AutotuningConfig",
    "ConfigError",
]


class ConfigError(ValueError):
    """Raised for invalid or inconsistent configuration."""


def _get(d: Dict[str, Any], key: str, default: Any = None) -> Any:
    v = d.get(key, default)
    return default if v is None else v


@dataclass
class OffloadConfig:
    """Offload target for optimizer states or parameters.

    Reference: runtime/zero/offload_config.py (device/pin_memory/ratio).
    On TPU, ``device="cpu"`` places tensors in host RAM via
    ``jax.device_put(..., may_alias)`` / host callbacks; ``device="nvme"``
    goes through the aio swapper (runtime/swap_tensor analog).
    """

    device: str = "none"  # none | cpu | nvme
    nvme_path: Optional[str] = None
    pin_memory: bool = False
    buffer_count: int = 4
    ratio: float = 1.0

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "OffloadConfig":
        d = d or {}
        return cls(
            device=_get(d, "device", "none"),
            nvme_path=d.get("nvme_path"),
            pin_memory=_get(d, "pin_memory", False),
            buffer_count=_get(d, "buffer_count", 4),
            ratio=float(_get(d, "ratio", 1.0)),
        )


@dataclass
class ZeroConfig:
    """ZeRO redundancy-optimizer settings.

    Reference: runtime/zero/config.py (stage, buckets, overlap_comm,
    zero++ knobs at :298/:302/:314).  On TPU the stages are realized as SPMD
    sharding rules (see runtime/zero/sharding.py) rather than eager
    hook-driven partitioning:

    - stage 0: params+grads+opt replicated over dp (DDP semantics)
    - stage 1: optimizer states sharded over dp
    - stage 2: + gradients reduce-scattered (automatic under SPMD)
    - stage 3: + parameters sharded over dp, allgathered on use by XLA
    """

    stage: int = 0
    contiguous_gradients: bool = True
    overlap_comm: bool = True
    # compute-collective overlap mode (T3, arxiv 2401.16677):
    #   "none"      — bit-exact default: one reduction per GAS window,
    #                 scheduled after the backward (today's behavior)
    #   "microstep" — double-buffered microsteps: microstep i's grad
    #                 reduction is issued before microstep i+1's
    #                 forward/backward inside the compiled step, so XLA's
    #                 async collective scheduler hides it under compute
    #                 (needs gradient_accumulation_steps > 1 to matter)
    #   "layer"     — layer-granular in-backward reduction: each scanned
    #                 layer's grad collective is issued inside the backward
    #                 scan, overlapping the previous layer's math (stage<3
    #                 needs zero_quantized_allreduce; stage-3 per-layer
    #                 gathers already reduce in-backward)
    #   "microstep+layer" — both
    overlap_mode: str = "none"
    reduce_scatter: bool = True
    reduce_bucket_size: int = int(5e8)
    allgather_bucket_size: int = int(5e8)
    allgather_partitions: bool = True
    round_robin_gradients: bool = False
    offload_optimizer: OffloadConfig = field(default_factory=OffloadConfig)
    offload_param: OffloadConfig = field(default_factory=OffloadConfig)
    sub_group_size: int = int(1e9)
    # ZeRO-3 fetch tuning (kept for config compatibility; prefetch is
    # compile-time on TPU so these are advisory only).
    stage3_max_live_parameters: int = int(1e9)
    stage3_max_reuse_distance: int = int(1e9)
    stage3_prefetch_bucket_size: int = int(5e7)
    stage3_param_persistence_threshold: int = int(1e5)
    stage3_gather_16bit_weights_on_model_save: bool = False
    # ZeRO++ (reference: zero/config.py:298-314)
    zero_hpz_partition_size: int = 1
    zero_quantized_weights: bool = False
    zero_quantized_gradients: bool = False
    # wire width of the qgZ gradient exchange: 8 (default — safest
    # trajectory parity) or 4 (the reference's all_to_all_quant_reduce
    # ships int4, quant_reduce.cu; halves the qgZ bytes again)
    zero_quantized_gradients_bits: int = 8
    # ZeRO++ 2-hop qgZ (arxiv 2306.10209 §hierarchical partitioning): the
    # grad reduction rides a factored (intra, inter) mesh-axis pair —
    # intra hop over the ICI-like axis at full precision (or
    # zero_quantized_gradients_intra_bits), inter hop quantized over the
    # DCN-like axis.  "none" (off) | "auto" ((fsdp, dp) when both > 1) |
    # explicit [intra_axis, inter_axis].
    zero_quantized_gradients_hierarchy: Any = "none"
    # intra-hop wire width under hierarchy: 0 = full precision (bf16/f32
    # — the reference's intra-node choice), or 4/8 to quantize the intra
    # hop too
    zero_quantized_gradients_intra_bits: int = 0
    # EQuARX-style quantized all-reduce (arxiv 2506.17615) for the data-
    # axis grad psum path (stage < 3 semantics: replicated-grad leaves and
    # the replica-axis reduction): quantized reduce-scatter + quantized
    # all-gather, payload and scales fused into one launch per hop
    zero_quantized_allreduce: bool = False
    # gradient bucketing for the quantized psum path: coalesce small
    # leaves into flat buckets of this many ELEMENTS before quantization,
    # so tiny params stop paying per-leaf launch + block-quant padding
    # overhead.  0 = off (per-leaf).
    zero_quantized_bucket_size: int = 0
    # MiCS (reference: runtime/zero/mics.py)
    mics_shard_size: int = -1
    mics_hierarchical_params_gather: bool = False
    # ZenFlow selective/async offloaded updates (reference:
    # runtime/zenflow/zenflow_config.py; raw dict, interpreted by
    # runtime/zenflow.py)
    zenflow: Optional[Dict[str, Any]] = None
    # Misc
    ignore_unused_parameters: bool = True
    log_trace_cache_warnings: bool = False

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ZeroConfig":
        d = d or {}
        cfg = cls(
            stage=int(_get(d, "stage", 0)),
            contiguous_gradients=_get(d, "contiguous_gradients", True),
            overlap_comm=_get(d, "overlap_comm", True),
            overlap_mode=str(_get(d, "overlap_mode", "none")),
            reduce_scatter=_get(d, "reduce_scatter", True),
            reduce_bucket_size=int(float(_get(d, "reduce_bucket_size", 5e8))),
            allgather_bucket_size=int(float(_get(d, "allgather_bucket_size", 5e8))),
            allgather_partitions=_get(d, "allgather_partitions", True),
            round_robin_gradients=_get(d, "round_robin_gradients", False),
            offload_optimizer=OffloadConfig.from_dict(d.get("offload_optimizer")),
            offload_param=OffloadConfig.from_dict(d.get("offload_param")),
            sub_group_size=int(float(_get(d, "sub_group_size", 1e9))),
            stage3_max_live_parameters=int(float(_get(d, "stage3_max_live_parameters", 1e9))),
            stage3_max_reuse_distance=int(float(_get(d, "stage3_max_reuse_distance", 1e9))),
            stage3_prefetch_bucket_size=int(float(_get(d, "stage3_prefetch_bucket_size", 5e7))),
            stage3_param_persistence_threshold=int(
                float(_get(d, "stage3_param_persistence_threshold", 1e5))),
            stage3_gather_16bit_weights_on_model_save=_get(
                d, "stage3_gather_16bit_weights_on_model_save", False),
            zero_hpz_partition_size=int(_get(d, "zero_hpz_partition_size", 1)),
            zero_quantized_weights=_get(d, "zero_quantized_weights", False),
            zero_quantized_gradients=_get(d, "zero_quantized_gradients", False),
            zero_quantized_gradients_bits=int(
                _get(d, "zero_quantized_gradients_bits", 8)),
            zero_quantized_gradients_hierarchy=_get(
                d, "zero_quantized_gradients_hierarchy", "none"),
            zero_quantized_gradients_intra_bits=int(
                _get(d, "zero_quantized_gradients_intra_bits", 0)),
            zero_quantized_allreduce=_get(
                d, "zero_quantized_allreduce", False),
            zero_quantized_bucket_size=int(
                float(_get(d, "zero_quantized_bucket_size", 0))),
            mics_shard_size=int(_get(d, "mics_shard_size", -1)),
            mics_hierarchical_params_gather=_get(d, "mics_hierarchical_params_gather", False),
            zenflow=d.get("zenflow"),
            ignore_unused_parameters=_get(d, "ignore_unused_parameters", True),
        )
        if cfg.stage not in (0, 1, 2, 3):
            raise ConfigError(f"zero_optimization.stage must be 0..3, got {cfg.stage}")
        # ZeRO++ flag/stage compatibility (reference: qwZ/qgZ are stage-3
        # features; our qgZ formulation also covers the stage-2
        # reduce-scatter) — validated at parse time like every sibling
        if cfg.zero_quantized_weights and cfg.stage < 3:
            raise ConfigError(
                "zero_quantized_weights (ZeRO++ qwZ) quantizes the stage-3 "
                f"parameter allgather; it requires stage 3 (got stage {cfg.stage})")
        if cfg.zero_quantized_gradients_bits not in (4, 8):
            raise ConfigError(
                f"zero_quantized_gradients_bits must be 4 or 8, got "
                f"{cfg.zero_quantized_gradients_bits}")
        if cfg.zero_quantized_gradients and cfg.stage < 2:
            raise ConfigError(
                "zero_quantized_gradients (ZeRO++ qgZ) quantizes the "
                "gradient reduce-scatter; it requires stage >= 2 "
                f"(got stage {cfg.stage})")
        # overlapped + hierarchical + quantized collective knobs (T3 /
        # ZeRO++ 2-hop / EQuARX) — validated here so a typo'd mode can
        # never silently fall back to the serialized path
        if cfg.overlap_mode not in ("none", "microstep", "layer",
                                    "microstep+layer"):
            raise ConfigError(
                f"zero_optimization.overlap_mode must be one of none | "
                f"microstep | layer | microstep+layer, got "
                f"{cfg.overlap_mode!r}")
        hier = cfg.zero_quantized_gradients_hierarchy
        if isinstance(hier, (list, tuple)):
            hier = tuple(str(a) for a in hier)
            if len(hier) != 2 or hier[0] == hier[1] or \
                    not set(hier) <= {"dp", "fsdp"}:
                raise ConfigError(
                    f"zero_quantized_gradients_hierarchy must be 'none', "
                    f"'auto', or a pair of distinct data axes out of "
                    f"('fsdp', 'dp') as [intra, inter], got {hier}")
            cfg.zero_quantized_gradients_hierarchy = hier
        elif hier not in ("none", "auto"):
            raise ConfigError(
                f"zero_quantized_gradients_hierarchy must be 'none', "
                f"'auto', or [intra_axis, inter_axis], got {hier!r}")
        if cfg.zero_quantized_gradients_hierarchy != "none" and not (
                cfg.zero_quantized_gradients or cfg.zero_quantized_allreduce):
            raise ConfigError(
                "zero_quantized_gradients_hierarchy (2-hop qgZ) quantizes "
                "the inter hop of the gradient reduction; enable "
                "zero_quantized_gradients (or zero_quantized_allreduce) "
                "with it")
        if cfg.zero_quantized_gradients_intra_bits not in (0, 4, 8):
            raise ConfigError(
                f"zero_quantized_gradients_intra_bits must be 0 (full "
                f"precision), 4, or 8, got "
                f"{cfg.zero_quantized_gradients_intra_bits}")
        if cfg.zero_quantized_gradients_intra_bits and \
                cfg.zero_quantized_gradients_hierarchy == "none":
            raise ConfigError(
                "zero_quantized_gradients_intra_bits quantizes the INTRA "
                "hop of the hierarchical reduction; set "
                "zero_quantized_gradients_hierarchy too")
        if cfg.zero_quantized_bucket_size < 0:
            raise ConfigError(
                f"zero_quantized_bucket_size must be >= 0 (elements), got "
                f"{cfg.zero_quantized_bucket_size}")
        if cfg.zero_quantized_bucket_size and not (
                cfg.zero_quantized_gradients or cfg.zero_quantized_allreduce):
            raise ConfigError(
                "zero_quantized_bucket_size buckets the quantized grad "
                "reduction; enable zero_quantized_gradients or "
                "zero_quantized_allreduce with it")
        if "layer" in cfg.overlap_mode and cfg.stage < 3 and \
                not cfg.zero_quantized_allreduce:
            raise ConfigError(
                "overlap_mode includes 'layer': at stage < 3 the in-"
                "backward per-layer reduction is the quantized all-reduce "
                "— enable zero_quantized_allreduce (stage 3 reduces per "
                "layer inside the backward already via the per-layer "
                "quantized gathers)")
        # ZeRO++ hpZ / MiCS shard-group knobs (reference: zero/config.py:298
        # zero_hpz_partition_size; runtime/zero/mics.py:64 mics_shard_size).
        # Both carve the data axes into a dp×fsdp mesh (engine builds it);
        # invalid values fail HERE, never silently no-op.
        if cfg.zero_hpz_partition_size < 1:
            raise ConfigError(
                f"zero_hpz_partition_size must be >= 1, got "
                f"{cfg.zero_hpz_partition_size}")
        if cfg.zero_hpz_partition_size > 1 and cfg.stage != 3:
            raise ConfigError(
                "zero_hpz_partition_size (ZeRO++ hpZ secondary partition) "
                "restricts the stage-3 parameter allgather; it requires "
                f"stage 3 (got stage {cfg.stage})")
        if cfg.mics_shard_size != -1 and cfg.mics_shard_size < 2:
            raise ConfigError(
                f"mics_shard_size must be -1 (off) or a shard-group size "
                f">= 2, got {cfg.mics_shard_size} (a group of 1 is full "
                f"replication — use zero stage 0 for DDP semantics)")
        if cfg.mics_shard_size > 0 and cfg.stage != 3:
            raise ConfigError(
                "mics_shard_size (MiCS sub-group sharding) partitions "
                f"stage-3 parameters; it requires stage 3 (got stage {cfg.stage})")
        if cfg.mics_shard_size > 0 and cfg.zero_hpz_partition_size > 1:
            raise ConfigError(
                "mics_shard_size and zero_hpz_partition_size both carve the "
                "data axes into shard sub-groups with conflicting semantics "
                "(MiCS: opt state within the group; hpZ: opt state across "
                "the world) — set at most one")
        return cfg


@dataclass
class PrecisionConfig:
    """bf16/fp16 settings.

    Reference: runtime/precision_config.py; fp16 loss scaling semantics from
    runtime/fp16/loss_scaler.py:93 (DynamicLossScaler).  On TPU bf16 is the
    native fast dtype; fp16 is supported for parity (with dynamic loss
    scaling) but bf16 is the default recommendation.
    """

    bf16_enabled: bool = False
    fp16_enabled: bool = False
    fp16_auto_cast: bool = False
    loss_scale: float = 0.0  # 0 => dynamic
    initial_scale_power: int = 16
    loss_scale_window: int = 1000
    hysteresis: int = 2
    min_loss_scale: float = 1.0
    fp32_reduce_scatter: bool = False

    @property
    def dtype(self):
        import jax.numpy as jnp
        if self.bf16_enabled:
            return jnp.bfloat16
        if self.fp16_enabled:
            return jnp.float16
        return jnp.float32

    @classmethod
    def from_dict(cls, root: Dict[str, Any]) -> "PrecisionConfig":
        bf16 = root.get("bf16", {}) or {}
        fp16 = root.get("fp16", {}) or {}
        cfg = cls(
            bf16_enabled=_get(bf16, "enabled", False),
            fp16_enabled=_get(fp16, "enabled", False),
            fp16_auto_cast=_get(fp16, "auto_cast", False),
            loss_scale=float(_get(fp16, "loss_scale", 0.0)),
            initial_scale_power=int(_get(fp16, "initial_scale_power", 16)),
            loss_scale_window=int(_get(fp16, "loss_scale_window", 1000)),
            hysteresis=int(_get(fp16, "hysteresis", 2)),
            min_loss_scale=float(_get(fp16, "min_loss_scale", 1.0)),
            fp32_reduce_scatter=_get(root, "fp32_reduce_scatter", False),
        )
        if cfg.bf16_enabled and cfg.fp16_enabled:
            raise ConfigError("bf16 and fp16 cannot both be enabled")
        return cfg


@dataclass
class OptimizerConfig:
    """Optimizer selection, mirroring the reference config block
    (reference: runtime/config.py get_optimizer_name/params).

    Supported types: adam/adamw (FusedAdam analog), lamb, lion, sgd,
    adagrad, onebitadam/zerooneadam/onebitlamb (compressed-comm variants).
    """

    type: str = "adamw"
    params: Dict[str, Any] = field(default_factory=dict)

    @property
    def lr(self) -> float:
        return float(self.params.get("lr", 1e-3))

    @property
    def betas(self) -> Tuple[float, float]:
        b = self.params.get("betas", (0.9, 0.999))
        return (float(b[0]), float(b[1]))

    @property
    def eps(self) -> float:
        return float(self.params.get("eps", 1e-8))

    @property
    def weight_decay(self) -> float:
        return float(self.params.get("weight_decay", 0.0))

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> Optional["OptimizerConfig"]:
        if not d:
            return None
        return cls(type=str(_get(d, "type", "adamw")).lower(), params=_get(d, "params", {}))


@dataclass
class SchedulerConfig:
    """LR schedule selection (reference: runtime/lr_schedules.py —
    LRRangeTest :273, OneCycle :371, WarmupLR :633, WarmupDecayLR :726,
    WarmupCosineLR :777)."""

    type: str = "WarmupLR"
    params: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> Optional["SchedulerConfig"]:
        if not d:
            return None
        return cls(type=_get(d, "type", "WarmupLR"), params=_get(d, "params", {}))


@dataclass
class ParallelConfig:
    """Mesh axis sizes for the 5-D parallel topology.

    TPU-native: one `jax.sharding.Mesh` with named axes replaces the
    reference's process-group zoo (utils/groups.py, runtime/pipe/topology.py).
    Axes: dp (data), fsdp (ZeRO-3 param shard), tp (tensor), sp (sequence/
    Ulysses/ring), pp (pipeline), ep (expert).  Unset axes default to 1; dp is
    inferred from world size.
    """

    tensor_parallel_size: int = 1
    pipeline_parallel_size: int = 1
    sequence_parallel_size: int = 1
    expert_parallel_size: int = 1
    data_parallel_size: int = -1  # inferred
    # Context parallel (ring attention) — TPU-native addition; the reference
    # covers CP with Ulysses (SURVEY §5.7).
    context_parallel_size: int = 1
    autotp_size: int = 0  # reference: tensor_parallel.autotp_size

    @classmethod
    def from_dict(cls, root: Dict[str, Any]) -> "ParallelConfig":
        tp = root.get("tensor_parallel", {}) or {}
        sp = root.get("sequence_parallel", {}) or {}
        pp = root.get("pipeline", {}) or {}
        return cls(
            tensor_parallel_size=int(_get(tp, "tp_size", _get(root, "tensor_parallel_size", 1))),
            autotp_size=int(_get(tp, "autotp_size", 0)),
            pipeline_parallel_size=int(_get(pp, "stages", _get(root, "pipeline_parallel_size", 1))),
            sequence_parallel_size=int(
                _get(sp, "size", _get(root, "sequence_parallel_size", 1))),
            context_parallel_size=int(_get(root, "context_parallel_size", 1)),
            expert_parallel_size=int(_get(root, "expert_parallel_size", 1)),
            data_parallel_size=int(_get(root, "data_parallel_size", -1)),
        )


@dataclass
class MoEConfig:
    """Mixture-of-experts settings (reference: moe/layer.py:17 MoE args)."""

    enabled: bool = False
    num_experts: int = 1
    top_k: int = 1
    capacity_factor: float = 1.0
    eval_capacity_factor: float = 1.0
    min_capacity: int = 4
    noisy_gate_policy: Optional[str] = None
    drop_tokens: bool = True
    use_residual: bool = False

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "MoEConfig":
        d = d or {}
        return cls(
            enabled=_get(d, "enabled", bool(d)),
            num_experts=int(_get(d, "num_experts", 1)),
            top_k=int(_get(d, "top_k", 1)),
            capacity_factor=float(_get(d, "capacity_factor", 1.0)),
            eval_capacity_factor=float(_get(d, "eval_capacity_factor", 1.0)),
            min_capacity=int(_get(d, "min_capacity", 4)),
            noisy_gate_policy=d.get("noisy_gate_policy"),
            drop_tokens=_get(d, "drop_tokens", True),
            use_residual=_get(d, "use_residual", False),
        )


@dataclass
class ActivationCheckpointingConfig:
    """Reference: runtime/activation_checkpointing/checkpointing.py.
    On TPU this maps to `jax.checkpoint` (remat) policies; partition_activations
    maps to sharding the saved residuals over tp/sp axes."""

    partition_activations: bool = False
    cpu_checkpointing: bool = False
    contiguous_memory_optimization: bool = False
    number_checkpoints: Optional[int] = None
    synchronize_checkpoint_boundary: bool = False
    profile: bool = False
    # TPU-native: name of the remat policy (see runtime/activation_checkpointing.py)
    policy: str = "none"

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ActivationCheckpointingConfig":
        d = d or {}
        return cls(
            partition_activations=_get(d, "partition_activations", False),
            cpu_checkpointing=_get(d, "cpu_checkpointing", False),
            contiguous_memory_optimization=_get(d, "contiguous_memory_optimization", False),
            number_checkpoints=d.get("number_checkpoints"),
            synchronize_checkpoint_boundary=_get(d, "synchronize_checkpoint_boundary", False),
            profile=_get(d, "profile", False),
            policy=_get(d, "policy", "none"),
        )


@dataclass
class CheckpointConfig:
    """Checkpoint behavior (reference: runtime/config.py checkpoint_config +
    checkpoint_engine selection in runtime/checkpoint_engine/)."""

    engine: str = "native"  # native | orbax | async
    use_node_local_storage: bool = False
    parallel_write_pipeline: bool = False
    tag_validation: str = "Warn"  # Ignore | Warn | Fail
    load_universal: bool = False
    async_save: bool = False

    @classmethod
    def from_dict(cls, root: Dict[str, Any]) -> "CheckpointConfig":
        d = root.get("checkpoint", {}) or {}
        return cls(
            engine=_get(d, "engine", "native"),
            use_node_local_storage=_get(d, "use_node_local_storage", False),
            parallel_write_pipeline=_get(
                (d.get("parallel_write") or {}), "pipeline_stage", False),
            tag_validation=_get(d, "tag_validation", "Warn"),
            load_universal=_get(d, "load_universal", False),
            async_save=_get(d, "async_save", False),
        )


@dataclass
class MonitorConfig:
    """Metrics sinks (reference: deepspeed/monitor/config.py:125)."""

    enabled: bool = False
    tensorboard: Dict[str, Any] = field(default_factory=dict)
    wandb: Dict[str, Any] = field(default_factory=dict)
    csv_monitor: Dict[str, Any] = field(default_factory=dict)

    @classmethod
    def from_dict(cls, root: Dict[str, Any]) -> "MonitorConfig":
        tb = root.get("tensorboard", {}) or {}
        wb = root.get("wandb", {}) or {}
        csv = root.get("csv_monitor", {}) or {}
        return cls(
            enabled=bool(tb.get("enabled") or wb.get("enabled") or csv.get("enabled")),
            tensorboard=tb, wandb=wb, csv_monitor=csv,
        )


@dataclass
class SupervisorConfig:
    """Automatic fleet health (`serving/fleet/supervisor.py`): per-replica
    step-progress heartbeats + deadline clocks checked each router tick
    drive the HEALTHY -> SUSPECT -> DRAINED state machine without an
    operator in the loop.  All times are on the fleet's serve clock (the
    fake clock in tests), all thresholds deterministic."""

    # a replica WITH WORK whose progress counter has not advanced for
    # this long is demoted HEALTHY -> SUSPECT (missed heartbeat)
    heartbeat_timeout_s: float = 5.0
    # this many step errors inside error_window_s demote to SUSPECT
    error_burst: int = 3
    error_window_s: float = 10.0
    # a SUSPECT replica still silent/erroring this long after demotion is
    # declared dead: automatic drain/adopt failover (queued work
    # re-routed, in-flight work re-queued or FAILED per retry budget)
    failover_after_s: float = 15.0
    # consecutive clean ticks (progress when work exists, zero errors)
    # before SUSPECT promotes back to HEALTHY...
    recovery_ticks: int = 8
    # ...scaled up by the flap count: each demotion within flap_window_s
    # of the previous promotion doubles the required streak, so a
    # flapping replica cannot thrash the router (hysteresis)
    flap_window_s: float = 60.0
    # times one request may be pulled off a dead replica and re-queued
    # before it is finalized FAILED (waiters raise, never hang)
    max_request_retries: int = 1

    def validate(self) -> None:
        if self.heartbeat_timeout_s <= 0:
            raise ConfigError(
                f"supervisor.heartbeat_timeout_s must be > 0, got "
                f"{self.heartbeat_timeout_s}")
        if self.error_burst < 1:
            raise ConfigError(
                f"supervisor.error_burst must be >= 1, got "
                f"{self.error_burst}")
        if self.error_window_s <= 0:
            raise ConfigError(
                f"supervisor.error_window_s must be > 0, got "
                f"{self.error_window_s}")
        if self.failover_after_s <= 0:
            raise ConfigError(
                f"supervisor.failover_after_s must be > 0, got "
                f"{self.failover_after_s}")
        if self.recovery_ticks < 1:
            raise ConfigError(
                f"supervisor.recovery_ticks must be >= 1, got "
                f"{self.recovery_ticks}")
        if self.flap_window_s < 0:
            raise ConfigError(
                f"supervisor.flap_window_s must be >= 0, got "
                f"{self.flap_window_s}")
        if self.max_request_retries < 0:
            raise ConfigError(
                f"supervisor.max_request_retries must be >= 0, got "
                f"{self.max_request_retries}")

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "SupervisorConfig":
        d = d or {}
        cfg = cls(
            heartbeat_timeout_s=float(_get(d, "heartbeat_timeout_s", 5.0)),
            error_burst=int(_get(d, "error_burst", 3)),
            error_window_s=float(_get(d, "error_window_s", 10.0)),
            failover_after_s=float(_get(d, "failover_after_s", 15.0)),
            recovery_ticks=int(_get(d, "recovery_ticks", 8)),
            flap_window_s=float(_get(d, "flap_window_s", 60.0)),
            max_request_retries=int(_get(d, "max_request_retries", 1)),
        )
        cfg.validate()
        return cfg


@dataclass
class AutoscaleConfig:
    """Elastic fleet sizing (`serving/fleet/autoscaler.py`): spawn or
    drain replicas from measured fleet occupancy with high-/low-watermark
    hysteresis and a cooldown, reusing the zero-loss drain/adopt handoff
    so scale-down loses nothing."""

    min_replicas: int = 1
    max_replicas: int = 8
    # mean live-replica load (queue + batch occupancy + KV reservation,
    # the routing load measure) above this for patience_ticks -> spawn
    high_watermark: float = 0.8
    # ...below this for patience_ticks (and above min_replicas) -> drain
    # the least-loaded replica and retire it once idle
    low_watermark: float = 0.2
    # consecutive out-of-band ticks before acting (debounce)
    patience_ticks: int = 4
    # serve-clock seconds after any scale event before the next one
    cooldown_s: float = 30.0
    # feed TTFT/TPOT SLA violation counters (per-replica incremental
    # counters; targets from DisaggConfig) into the watermark signal:
    # NEW violations since a group's last tick count as above-high-
    # watermark pressure for the responsible pool (TTFT -> prefill,
    # TPOT -> decode, both -> the unified fleet group), so pools size
    # to their SLA rather than to occupancy alone.  Default off =
    # bit-for-bit the occupancy-only autoscaler (locked by test).
    sla_pressure: bool = False

    def validate(self) -> None:
        if self.min_replicas < 1:
            raise ConfigError(
                f"autoscale.min_replicas must be >= 1, got "
                f"{self.min_replicas}")
        if self.max_replicas < self.min_replicas:
            raise ConfigError(
                f"autoscale.max_replicas ({self.max_replicas}) must be "
                f">= min_replicas ({self.min_replicas})")
        if not (0.0 <= self.low_watermark < self.high_watermark):
            raise ConfigError(
                f"autoscale watermarks need 0 <= low < high, got "
                f"low={self.low_watermark}, high={self.high_watermark}")
        if self.patience_ticks < 1:
            raise ConfigError(
                f"autoscale.patience_ticks must be >= 1, got "
                f"{self.patience_ticks}")
        if self.cooldown_s < 0:
            raise ConfigError(
                f"autoscale.cooldown_s must be >= 0, got "
                f"{self.cooldown_s}")

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "AutoscaleConfig":
        d = d or {}
        cfg = cls(
            min_replicas=int(_get(d, "min_replicas", 1)),
            max_replicas=int(_get(d, "max_replicas", 8)),
            high_watermark=float(_get(d, "high_watermark", 0.8)),
            low_watermark=float(_get(d, "low_watermark", 0.2)),
            patience_ticks=int(_get(d, "patience_ticks", 4)),
            cooldown_s=float(_get(d, "cooldown_s", 30.0)),
            sla_pressure=bool(_get(d, "sla_pressure", False)),
        )
        cfg.validate()
        return cfg


@dataclass
class DisaggConfig:
    """Disaggregated prefill/decode serving
    (`serving/fleet/disagg/`): the fleet splits into a PREFILL pool
    (chunked prefill to completion, prompt-only KV reservations, large
    admission batches, decode suppressed) and a DECODE pool (burst
    loop + speculative, high occupancy).  A request admitted to the
    prefill pool runs its prompt there, the finished prompt KV streams
    to a decode replica through the migration transport (batched
    multi-block transfers, optional int8 wire quant), and the SAME
    Request object is adopted by the decode replica — waiters survive,
    the handoff is invisible apart from latency.  Kills prefill/decode
    interference under heavy mixed traffic (DistServe/FastGen-style).
    None = the unified fleet, bit-for-bit (locked by test)."""

    # replicas assigned each role at fleet construction (by position:
    # the first `prefill_replicas` loops, then `decode_replicas`; any
    # remainder stays unified).  These are also each pool's MIN FLOOR:
    # supervisor failovers dropping a pool below its floor spawn a
    # replacement (loop factory required) per router tick.
    prefill_replicas: int = 1
    decode_replicas: int = 1
    # handoff wire format: "none" ships raw KV bytes, "int8" quantizes
    # per (layer, block) like migration_quant (~2x fewer bytes; decoded
    # outputs are then NOT bit-for-bit vs unified serving)
    handoff_quant: str = "none"
    # prompts spanning fewer than this many WHOLE KV blocks route
    # straight to the decode pool and serve end-to-end there — a
    # handoff that moves no block would just re-prefill the prompt
    min_handoff_blocks: int = 1
    # per-pool SLA targets (seconds; None = untracked).  TTFT is the
    # prefill pool's responsibility (queue + prefill + handoff up to
    # the first token), TPOT the decode pool's; violations are counted
    # per pool in FleetTelemetry.summary()["pools"] and published as
    # fleet/pool_* monitor events.
    prefill_ttft_target_s: Optional[float] = None
    decode_tpot_target_s: Optional[float] = None

    def validate(self) -> None:
        if self.prefill_replicas < 1:
            raise ConfigError(
                f"disagg.prefill_replicas must be >= 1, got "
                f"{self.prefill_replicas}")
        if self.decode_replicas < 1:
            raise ConfigError(
                f"disagg.decode_replicas must be >= 1, got "
                f"{self.decode_replicas}")
        if self.handoff_quant not in ("none", "int8"):
            raise ConfigError(
                f"disagg.handoff_quant must be 'none' or 'int8', got "
                f"{self.handoff_quant!r}")
        if self.min_handoff_blocks < 1:
            raise ConfigError(
                f"disagg.min_handoff_blocks must be >= 1, got "
                f"{self.min_handoff_blocks}")
        for name in ("prefill_ttft_target_s", "decode_tpot_target_s"):
            v = getattr(self, name)
            if v is not None and v <= 0:
                raise ConfigError(
                    f"disagg.{name} must be positive, got {v}")

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "DisaggConfig":
        d = d or {}
        ttft = d.get("prefill_ttft_target_s")
        tpot = d.get("decode_tpot_target_s")
        cfg = cls(
            prefill_replicas=int(_get(d, "prefill_replicas", 1)),
            decode_replicas=int(_get(d, "decode_replicas", 1)),
            handoff_quant=str(_get(d, "handoff_quant", "none")),
            min_handoff_blocks=int(_get(d, "min_handoff_blocks", 1)),
            prefill_ttft_target_s=(float(ttft) if ttft is not None
                                   else None),
            decode_tpot_target_s=(float(tpot) if tpot is not None
                                  else None),
        )
        cfg.validate()
        return cfg


@dataclass
class FleetConfig:
    """Cache-aware fleet routing knobs (`deepspeed_tpu.serving.fleet`):
    a router fronting N serve replicas steers each request to the
    replica with the longest cached prefix (SGLang-style cache-aware
    routing) using per-replica `PrefixCache.snapshot()` publications,
    with least-loaded fallback, per-replica health/failover, and
    optional replica-to-replica KV-block migration."""

    # serve replicas the fleet fronts (FleetRouter.build spawns this
    # many ServeLoops from an engine factory; a pre-built loop list
    # overrides it)
    replicas: int = 1
    # publish each replica's prefix-index snapshot to the router every N
    # fleet steps (the staleness window: a snapshot can be up to N steps
    # behind the replica's own tree — the stale-view protocol makes that
    # safe, this knob makes it small)
    snapshot_interval_steps: int = 4
    # routing score = prefix_weight * (matched prefix fraction of the
    # prompt) - load_weight * (replica load fraction); highest score
    # wins, least-loaded on a tie
    prefix_weight: float = 1.0
    load_weight: float = 0.5
    # multi-tenant adapter affinity (serving/tenancy): requests that
    # carry an adapter_id add adapter_weight * (residency claim / 2) to
    # the score — claim 2 = HBM-resident on that replica, 1 = host-
    # spilled (promotable at admission), 0 = absent.  Requests without
    # an adapter never read this (the tenancy-off parity state).
    adapter_weight: float = 1.0
    # "cache_aware" routes by the score above; "round_robin" ignores the
    # prefix index (the bench baseline cache-aware routing must beat)
    routing: str = "cache_aware"
    # stream hot prefix KV blocks from the owning replica into the
    # routed target's arena when the target's own cache covers less
    # (fleet/migration.py): the transfer, not a re-prefill, pays for
    # adoption of a hot prefix
    migration: bool = False
    # "none" ships raw KV bytes; "int8" quantizes per (layer, block) on
    # the wire (ZeRO++/EQuARX-style compressed communication — ~halves
    # DCN bytes for bf16 arenas at a bounded dequant error, so migrated-
    # prefix outputs are no longer bit-for-bit)
    migration_quant: str = "none"
    # router steps a (source, target) replica pair sits out of migration
    # after a transport failure before it is retried (retry-with-backoff;
    # the failed submit itself falls back to cold prefill immediately)
    migration_backoff_steps: int = 32
    # automatic heartbeat health + failover (serving/fleet/supervisor.py);
    # None = PR-5 operator-driven health, bit-for-bit
    supervisor: Optional[SupervisorConfig] = None
    # elastic replica count (serving/fleet/autoscaler.py); None = fixed
    # fleet, bit-for-bit
    autoscale: Optional[AutoscaleConfig] = None
    # disaggregated prefill/decode pools (serving/fleet/disagg/); None =
    # unified fleet, bit-for-bit
    disagg: Optional[DisaggConfig] = None

    def validate(self) -> None:
        if self.replicas < 1:
            raise ConfigError(
                f"serving.fleet.replicas must be >= 1, got "
                f"{self.replicas}")
        if self.snapshot_interval_steps < 1:
            raise ConfigError(
                f"serving.fleet.snapshot_interval_steps must be >= 1, "
                f"got {self.snapshot_interval_steps}")
        if self.prefix_weight < 0 or self.load_weight < 0 \
                or self.adapter_weight < 0:
            raise ConfigError(
                f"serving.fleet routing weights must be >= 0, got "
                f"prefix_weight={self.prefix_weight}, "
                f"load_weight={self.load_weight}, "
                f"adapter_weight={self.adapter_weight}")
        if self.routing not in ("cache_aware", "round_robin"):
            raise ConfigError(
                f"serving.fleet.routing must be 'cache_aware' or "
                f"'round_robin', got {self.routing!r}")
        if self.migration_quant not in ("none", "int8"):
            raise ConfigError(
                f"serving.fleet.migration_quant must be 'none' or "
                f"'int8', got {self.migration_quant!r}")
        if self.migration and self.routing != "cache_aware":
            raise ConfigError(
                "serving.fleet.migration requires routing='cache_aware': "
                "migration happens AT the routing decision (stream the "
                "prefix to the scored target), so under "
                f"routing={self.routing!r} it would silently never run")
        if self.migration_backoff_steps < 0:
            raise ConfigError(
                f"serving.fleet.migration_backoff_steps must be >= 0, "
                f"got {self.migration_backoff_steps}")
        if self.supervisor is not None:
            self.supervisor.validate()
        if self.disagg is not None:
            self.disagg.validate()
            pooled = (self.disagg.prefill_replicas
                      + self.disagg.decode_replicas)
            if pooled > self.replicas:
                raise ConfigError(
                    f"serving.fleet.disagg assigns {pooled} pooled "
                    f"replicas (prefill_replicas="
                    f"{self.disagg.prefill_replicas} + decode_replicas="
                    f"{self.disagg.decode_replicas}) but the fleet has "
                    f"only replicas={self.replicas}")
        if self.autoscale is not None:
            self.autoscale.validate()
            if self.supervisor is None:
                raise ConfigError(
                    "serving.fleet.autoscale requires a supervisor: "
                    "scale-down retires replicas through the supervised "
                    "drain lifecycle, and an unsupervised elastic fleet "
                    "would keep routing to a replica that died — set "
                    "serving.fleet.supervisor (defaults are fine)")
            if self.autoscale.min_replicas > self.replicas:
                raise ConfigError(
                    f"serving.fleet.autoscale.min_replicas "
                    f"({self.autoscale.min_replicas}) exceeds the "
                    f"initial fleet size replicas={self.replicas}")
            if self.replicas > self.autoscale.max_replicas:
                raise ConfigError(
                    f"serving.fleet.replicas ({self.replicas}) exceeds "
                    f"autoscale.max_replicas "
                    f"({self.autoscale.max_replicas}): the fleet would "
                    f"start above the ceiling the autoscaler enforces "
                    f"(scale-down only fires on low occupancy, so the "
                    f"bound would silently never hold under load)")

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "FleetConfig":
        d = d or {}
        sup = d.get("supervisor")
        aut = d.get("autoscale")
        dis = d.get("disagg")
        cfg = cls(
            replicas=int(_get(d, "replicas", 1)),
            snapshot_interval_steps=int(
                _get(d, "snapshot_interval_steps", 4)),
            prefix_weight=float(_get(d, "prefix_weight", 1.0)),
            load_weight=float(_get(d, "load_weight", 0.5)),
            adapter_weight=float(_get(d, "adapter_weight", 1.0)),
            routing=str(_get(d, "routing", "cache_aware")),
            migration=bool(_get(d, "migration", False)),
            migration_quant=str(_get(d, "migration_quant", "none")),
            migration_backoff_steps=int(
                _get(d, "migration_backoff_steps", 32)),
            supervisor=(SupervisorConfig.from_dict(sup)
                        if sup is not None else None),
            autoscale=(AutoscaleConfig.from_dict(aut)
                       if aut is not None else None),
            disagg=(DisaggConfig.from_dict(dis)
                    if dis is not None else None),
        )
        cfg.validate()
        return cfg


@dataclass
class SpeculativeConfig:
    """Speculative decoding under the serve lifecycle
    (`deepspeed_tpu.serving.speculative`): model-free prompt-lookup
    drafts verified by one batched forward over the draft span with
    on-device accept/reject.  Greedy rows stay BIT-IDENTICAL to
    spec-off serving (the verify span's logits are bitwise the
    sequential decode chain's); stochastic rows use standard rejection
    sampling, which preserves the target distribution but not the
    random stream."""

    # "off" = bit-for-bit today's burst serve loop (locked by test);
    # "prompt_lookup" = stage-1 model-free drafts (n-gram match against
    # the request's own prompt + generated context).  A stage-2 draft
    # model slots in behind the same DraftSource/verify interface.
    mode: str = "off"
    # longest n-gram the drafter tries to match (it backs off n, n-1,
    # ..., 1 and drafts the continuation of the most recent match)
    ngram: int = 3
    # max draft tokens verified per dispatch.  Each verify dispatch's
    # compiled span is bucketed to a power of two capped by
    # 1 + max_draft (speculative.span_bucket), so every draft length
    # maps into the small FIXED shape set {2, 4, ...,
    # span_bucket(1 + max_draft)} — the DST004 recompile discipline.
    # 0 = draft nothing: the serve loop's coverage gate then never
    # fires a verify dispatch and serving is bit-for-bit spec-off (the
    # parity-lock degenerate).
    max_draft: int = 7

    def validate(self) -> None:
        if self.mode not in ("off", "prompt_lookup"):
            raise ConfigError(
                f"serving.speculative.mode must be 'off' or "
                f"'prompt_lookup', got {self.mode!r}")
        if self.ngram < 1:
            raise ConfigError(
                f"serving.speculative.ngram must be >= 1, got "
                f"{self.ngram}")
        if self.max_draft < 0:
            raise ConfigError(
                f"serving.speculative.max_draft must be >= 0, got "
                f"{self.max_draft}")

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "SpeculativeConfig":
        d = d or {}
        cfg = cls(
            mode=str(_get(d, "mode", "off")),
            ngram=int(_get(d, "ngram", 3)),
            max_draft=int(_get(d, "max_draft", 7)),
        )
        cfg.validate()
        return cfg


@dataclass
class TracingConfig:
    """Serving observability (`deepspeed_tpu.serving.tracing`): per-
    request distributed span traces + the per-step timeline profiler.
    Both default off and off is bit-for-bit the untraced serve loop
    (locked by test) — tracing is observe-only by construction."""

    # attach a span tree to every Request covering its whole fleet
    # lifecycle (queued/routed/admitted/prefill chunks/handoff/decode
    # bursts/failover/terminal), exportable as Chrome-trace JSON
    # (perfetto) and JSONL
    enabled: bool = False
    # entry cap per request trace; overflow increments the trace's
    # `dropped` counter instead of growing without bound
    max_spans_per_request: int = 512
    # per-step phase-duration ring on the serve loop (finalize /
    # admission / prefill / decode wall per step + token counts),
    # surfaced via telemetry summary(), monitor sinks, and
    # `prometheus_text()`.  0 = timeline off.
    step_timeline: int = 0
    # per-tick metric time series (serving/observatory/metrics.py): a
    # bounded MetricRing row per ServeLoop.step / FleetRouter.step
    # (queue depth, active/parked, arena blocks free, prefix-cache
    # residency, per-pool load, acceptance rate, utilization),
    # exportable as JSONL + Prometheus text.  0 = sampler off =
    # bit-for-bit the unsampled loop (locked by test).
    metrics_ring: int = 0

    def validate(self) -> None:
        if self.max_spans_per_request < 16:
            raise ConfigError(
                f"serving.tracing.max_spans_per_request must be >= 16 "
                f"(a single admission already records several entries), "
                f"got {self.max_spans_per_request}")
        if self.step_timeline < 0:
            raise ConfigError(
                f"serving.tracing.step_timeline must be >= 0 (0 = "
                f"timeline off), got {self.step_timeline}")
        if self.metrics_ring < 0:
            raise ConfigError(
                f"serving.tracing.metrics_ring must be >= 0 (0 = "
                f"time-series sampler off), got {self.metrics_ring}")

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "TracingConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, "enabled", False)),
            max_spans_per_request=int(_get(d, "max_spans_per_request",
                                           512)),
            step_timeline=int(_get(d, "step_timeline", 0)),
            metrics_ring=int(_get(d, "metrics_ring", 0)),
        )
        cfg.validate()
        return cfg


@dataclass
class StreamingConfig:
    """Incremental token delivery (`deepspeed_tpu.serving.streaming`):
    every request carries a sequence-numbered token log appended at
    first-token and burst/verify-span boundaries, consumable through an
    event-driven iterator/callback seam with EXACTLY-ONCE semantics
    that survive failover — an adopted request's regeneration is
    verified against the already-delivered log and replayed tokens are
    suppressed, so every consumer sees a duplicate-free, gap-free
    sequence bit-identical to the no-fault run.  Default off =
    bit-for-bit the unstreamed serve loop (locked by test)."""

    enabled: bool = False
    # auto-assign a per-request sampling seed (`Request.seed`,
    # counter-based stream — serving/streaming.py) to stochastic
    # submits that did not bring one, so replay after failover is
    # verifiable for temperature > 0 rows too.  Greedy rows need no
    # seed (determinism is the model's).
    auto_seed: bool = True

    def validate(self) -> None:
        pass

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "StreamingConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, "enabled", False)),
            auto_seed=bool(_get(d, "auto_seed", True)),
        )
        cfg.validate()
        return cfg


@dataclass
class PreemptionConfig:
    """SLO-aware priority preemption (`deepspeed_tpu.serving.server`):
    when a request that would violate its TTFT SLO cannot admit, the
    scheduler preempts the lowest-priority DECODE-state request by
    **KV swap-or-recompute** — the victim's live mid-decode KV is
    stashed in the radix prefix cache and demoted through the host
    tier (serving/kv_tier.py) when one is attached, or recomputed via
    the prefix-cache cold path when not — and the victim stream-resumes
    seamlessly after the urgent request drains (admission re-prefills
    `prompt + generated`, which reproduces the KV bit-for-bit).
    Default off = bit-for-bit the no-preemption scheduler (locked by
    test)."""

    enabled: bool = False
    # the TTFT SLO (serve-clock seconds) preemption defends: a queued
    # request that has not produced its first token becomes URGENT once
    # its age reaches `urgency_fraction * ttft_slo_s`
    ttft_slo_s: float = 10.0
    # fraction of the SLO a request may queue before preemption fires —
    # below 1.0 leaves budget for the prefill itself
    urgency_fraction: float = 0.5
    # victims preempted per serve step (bounds per-step swap IO)
    max_victims_per_step: int = 1
    # a victim must have priority >= urgent.priority + this gap (lower
    # priority value admits first, so the gap keeps preemption strictly
    # priority-ordered — equal-priority work is never preempted)
    min_priority_gap: int = 1

    def validate(self) -> None:
        if self.ttft_slo_s <= 0:
            raise ConfigError(
                f"serving.preemption.ttft_slo_s must be positive, got "
                f"{self.ttft_slo_s}")
        if not 0.0 < self.urgency_fraction <= 1.0:
            raise ConfigError(
                f"serving.preemption.urgency_fraction must be in "
                f"(0, 1], got {self.urgency_fraction}")
        if self.max_victims_per_step < 1:
            raise ConfigError(
                f"serving.preemption.max_victims_per_step must be >= 1, "
                f"got {self.max_victims_per_step}")
        if self.min_priority_gap < 1:
            raise ConfigError(
                f"serving.preemption.min_priority_gap must be >= 1 "
                f"(equal-priority preemption would let a request evict "
                f"its own class), got {self.min_priority_gap}")

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "PreemptionConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, "enabled", False)),
            ttft_slo_s=float(_get(d, "ttft_slo_s", 10.0)),
            urgency_fraction=float(_get(d, "urgency_fraction", 0.5)),
            max_victims_per_step=int(_get(d, "max_victims_per_step", 1)),
            min_priority_gap=int(_get(d, "min_priority_gap", 1)),
        )
        cfg.validate()
        return cfg


@dataclass
class TenancyConfig:
    """Multi-tenant serving (`deepspeed_tpu.serving.tenancy`): one base
    model serves many per-tenant LoRA adapters from a single continuous
    batch.  Adapter weights live in a block-granular HBM pool with an
    optional host spill tier (the serving/kv_tier.py demote/promote
    discipline applied to weights, optional ZeRO++-style int8 spill
    quant at the per-(layer,block) scale grain — arxiv 2306.10209), and
    admission RESERVES adapter residency like KV blocks so an admitted
    request never faults on a missing adapter mid-decode.  Tenants get
    admission economics: token-bucket rate limits and deterministic
    virtual-time weighted-fair queueing on the serve clock (per-tenant
    FIFO preserved), plus tenant weight priced into preemption victim
    choice.  Default off (= `ServingConfig.tenancy = None`) is
    bit-for-bit the single-tenant scheduler, locked by test — as is a
    request with `adapter_id=None` under an enabled pool (the LoRA
    epilogue contributes exactly zero for base rows)."""

    enabled: bool = False
    # HBM adapter pool capacity in blocks (serving/tenancy/adapter_pool
    # .AdapterPool); each registered adapter occupies
    # ceil(params / adapter_block_elems) blocks.  0 with enabled=True is
    # QoS-only multi-tenancy (no adapters served).
    adapter_pool_blocks: int = 0
    # elements per pool block — the paging grain shared by the HBM pool
    # and the host spill tier (block-granular demote/promote, like KV)
    adapter_block_elems: int = 4096
    # host spill tier capacity in blocks behind the HBM pool (0 = off:
    # evicted adapters are dropped and must re-register to return)
    host_spill_blocks: int = 0
    # "int8" stores each spilled block as int8 codes + one fp32 scale
    # per (layer, block) — promoted adapters are then no longer
    # bit-for-bit; "none" spills raw pages (round trips bit-exact)
    host_spill_quant: str = "none"
    # tenant -> admitted tokens/sec: the token-bucket refill rate.  A
    # tenant absent from the table is unmetered.  Refusals are loud
    # (rejected_rate_limited counter), never silent drops.
    rate_limits: Dict[str, float] = field(default_factory=dict)
    # seconds of refill a bucket may hold (capacity = rate * burst_s):
    # bounds how far a tenant can burst past its sustained rate
    burst_s: float = 2.0
    # tenant -> WFQ weight (virtual time advances by tokens/weight, so
    # a weight-2 tenant drains twice the tokens per unit of service).
    # Tenants absent from the table get default_weight.
    weights: Dict[str, float] = field(default_factory=dict)
    default_weight: float = 1.0
    # tenant -> max KV-arena blocks the tenant's ACTIVE requests may
    # hold concurrently (the admission ledger's reservations, prefill
    # chunks + full decode allowance).  An over-quota tenant's requests
    # WAIT in queue — the fair scheduler skips that tenant's head and
    # serves others, so one tenant can never starve the arena — and
    # admit when its own requests finish and release blocks.  A tenant
    # absent from the table is unquota'd.
    kv_block_quota: Dict[str, int] = field(default_factory=dict)

    def validate(self) -> None:
        if self.adapter_pool_blocks < 0:
            raise ConfigError(
                f"serving.tenancy.adapter_pool_blocks must be >= 0, got "
                f"{self.adapter_pool_blocks}")
        if self.adapter_block_elems < 1:
            raise ConfigError(
                f"serving.tenancy.adapter_block_elems must be >= 1, got "
                f"{self.adapter_block_elems}")
        if self.host_spill_blocks < 0:
            raise ConfigError(
                f"serving.tenancy.host_spill_blocks must be >= 0, got "
                f"{self.host_spill_blocks}")
        if self.host_spill_blocks > 0 and self.adapter_pool_blocks <= 0:
            raise ConfigError(
                "serving.tenancy.host_spill_blocks is the spill tier "
                "BEHIND the HBM adapter pool (evictions demote into "
                "it), so it requires serving.tenancy.adapter_pool_blocks "
                "> 0")
        if self.host_spill_quant not in ("none", "int8"):
            raise ConfigError(
                f"serving.tenancy.host_spill_quant must be 'none' or "
                f"'int8', got {self.host_spill_quant!r}")
        if self.burst_s <= 0:
            raise ConfigError(
                f"serving.tenancy.burst_s must be positive, got "
                f"{self.burst_s}")
        for tenant, rate in self.rate_limits.items():
            if rate <= 0:
                raise ConfigError(
                    f"serving.tenancy.rate_limits[{tenant!r}] must be "
                    f"positive (omit the tenant to leave it unmetered), "
                    f"got {rate}")
        for tenant, weight in self.weights.items():
            if weight <= 0:
                raise ConfigError(
                    f"serving.tenancy.weights[{tenant!r}] must be "
                    f"positive, got {weight}")
        if self.default_weight <= 0:
            raise ConfigError(
                f"serving.tenancy.default_weight must be positive, got "
                f"{self.default_weight}")
        for tenant, quota in self.kv_block_quota.items():
            if quota < 1:
                raise ConfigError(
                    f"serving.tenancy.kv_block_quota[{tenant!r}] must be "
                    f">= 1 (omit the tenant to leave it unquota'd), got "
                    f"{quota}")

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "TenancyConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, "enabled", False)),
            adapter_pool_blocks=int(_get(d, "adapter_pool_blocks", 0)),
            adapter_block_elems=int(_get(d, "adapter_block_elems", 4096)),
            host_spill_blocks=int(_get(d, "host_spill_blocks", 0)),
            host_spill_quant=str(_get(d, "host_spill_quant", "none")),
            rate_limits={str(k): float(v)
                         for k, v in (_get(d, "rate_limits", {})
                                      or {}).items()},
            burst_s=float(_get(d, "burst_s", 2.0)),
            weights={str(k): float(v)
                     for k, v in (_get(d, "weights", {}) or {}).items()},
            default_weight=float(_get(d, "default_weight", 1.0)),
            kv_block_quota={str(k): int(v)
                            for k, v in (_get(d, "kv_block_quota", {})
                                         or {}).items()},
        )
        cfg.validate()
        return cfg


@dataclass
class StructuredConfig:
    """Grammar-constrained decoding (`deepspeed_tpu.serving.structured`):
    requests carrying a `response_format` (regex or JSON schema) decode
    under an on-device token-level automaton — the per-step mask is one
    table gather inside the compiled multi-step scan, so constrained
    decoding adds ZERO per-step host round-trips.  Attaching this config
    only builds the compiled-automaton cache; requests WITHOUT a
    response_format stay bit-for-bit the unconstrained loop (locked by
    test), and `ServingConfig.structured = None` refuses constrained
    submits loudly."""

    enabled: bool = True
    # compiled automatons held in the LRU cache (keyed by grammar
    # digest, shared across requests; see structured/cache.py) — each
    # entry is states x vocab transition + bitmask tables
    cache_size: int = 16
    # DFA state budget per grammar: compilation fails loudly past this
    # (submit-time rejection), bounding both compile time and the
    # states x vocab device tables
    max_states: int = 4096
    # token id -> text mapping the automaton is lifted onto: "bytes"
    # (token i = chr(i), the synthetic tiny-model default) or an
    # explicit list of token strings from a real tokenizer (empty
    # string = unmappable special token, never allowed by any mask)
    vocab: Any = "bytes"

    def validate(self) -> None:
        if self.cache_size < 1:
            raise ConfigError(
                f"serving.structured.cache_size must be >= 1, got "
                f"{self.cache_size}")
        if self.max_states < 2:
            raise ConfigError(
                f"serving.structured.max_states must be >= 2 (a useful "
                f"grammar has at least a start and an accept state), "
                f"got {self.max_states}")
        if isinstance(self.vocab, str):
            if self.vocab != "bytes":
                raise ConfigError(
                    f"serving.structured.vocab must be 'bytes' or a "
                    f"list of token strings, got {self.vocab!r}")
        elif not isinstance(self.vocab, (list, tuple)) or not all(
                isinstance(s, str) for s in self.vocab):
            raise ConfigError(
                "serving.structured.vocab must be 'bytes' or a list of "
                "token strings (one per token id)")

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "StructuredConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, "enabled", True)),
            cache_size=int(_get(d, "cache_size", 16)),
            max_states=int(_get(d, "max_states", 4096)),
            vocab=_get(d, "vocab", "bytes"),
        )
        cfg.validate()
        return cfg


@dataclass
class MoeServingConfig:
    """Expert-paged MoE decode (`deepspeed_tpu.serving.experts`): only
    `slots_per_layer` experts per layer stay HBM-resident in slot
    stacks; the rest live on host (optionally int8) and promote back on
    demand, while the router reroutes their tokens to resident experts
    (counted, never faulted).  Requires an MoE engine
    (`supports_moe`); refused under fused-TP collectives and
    speculative decoding (validated in ServingConfig).  Default off
    (= `ServingConfig.moe = None`) serves the unpaged model —
    bit-for-bit, locked both directions by test."""

    enabled: bool = True
    # HBM expert slots per layer; 0 = one slot per expert (full
    # residency — bit-for-bit the unpaged model under spill="none",
    # with the paging machinery live)
    slots_per_layer: int = 0
    # host-tier storage for demoted experts: "int8" quantizes the
    # canonical copies (~4x less host RAM for f32 models; LOSSY — a
    # promoted expert differs at the quant step, parity-gated by test),
    # "none" keeps exact copies (promote is bit-exact)
    spill: str = "none"
    # drain the router census and rebalance residency every N serve
    # steps (0 = never: residency only changes via explicit pool calls)
    census_interval_steps: int = 0
    # cap on promotions per rebalance pass (0 = unbounded) — bounds the
    # h2d burst a census-driven reshuffle can issue in one step
    max_promotes_per_step: int = 0

    def validate(self) -> None:
        if self.slots_per_layer < 0:
            raise ConfigError(
                f"serving.moe.slots_per_layer must be >= 0 (0 = one "
                f"slot per expert), got {self.slots_per_layer}")
        if self.spill not in ("none", "int8"):
            raise ConfigError(
                f"serving.moe.spill must be 'none' or 'int8', got "
                f"{self.spill!r}")
        if self.census_interval_steps < 0:
            raise ConfigError(
                f"serving.moe.census_interval_steps must be >= 0 (0 = "
                f"no periodic rebalance), got "
                f"{self.census_interval_steps}")
        if self.max_promotes_per_step < 0:
            raise ConfigError(
                f"serving.moe.max_promotes_per_step must be >= 0 (0 = "
                f"unbounded), got {self.max_promotes_per_step}")

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "MoeServingConfig":
        d = d or {}
        cfg = cls(
            enabled=bool(_get(d, "enabled", True)),
            slots_per_layer=int(_get(d, "slots_per_layer", 0)),
            spill=str(_get(d, "spill", "none")),
            census_interval_steps=int(_get(d, "census_interval_steps", 0)),
            max_promotes_per_step=int(_get(d, "max_promotes_per_step", 0)),
        )
        cfg.validate()
        return cfg


@dataclass
class ServingConfig:
    """Serving-layer knobs (reference: DeepSpeed-MII serving config —
    queue bounds + per-request defaults for the continuous-batching
    serve loop in `deepspeed_tpu.serving`)."""

    enabled: bool = False
    # bounded admission queue: a submit past this raises QueueFullError
    # (explicit backpressure, never a silent drop)
    max_queue_len: int = 128
    # per-request defaults, overridable per submit()
    default_max_new_tokens: int = 64
    # relative deadline applied to every request (None = no deadline)
    default_timeout_s: Optional[float] = None
    # publish serving telemetry through the monitor sinks every N serve
    # steps (0 = only on explicit ServingTelemetry.publish())
    monitor_interval_steps: int = 0
    # decode tokens per compiled burst in ServeLoop: > 1 fuses sampling
    # into the engine's on-device decode program (logits never leave the
    # device; one host observation per burst), trading cancellation /
    # deadline granularity — expiry is checked at burst boundaries — for
    # throughput.  1 = the per-step path: one token per engine step,
    # admission every step; a greedy row takes the argmax the step's
    # program returns beside its logits, every other row is sampled on
    # the host from its own logits row (serving/server.py step 5) —
    # token for token the all-host sampler it replaced.
    decode_burst: int = 1
    # decode steps per compiled step-GROUP in ServeLoop: > 1 runs K
    # decode iterations in ONE dispatch with on-device per-row sampling
    # (counter-based Philox streams for seeded requests) AND on-device
    # EOS/max-token termination (engine decode_multi_step) — the host
    # sees one packed fetch per group, so admission, streaming flush,
    # deadline/cancel checks, preemption, and ledger accounting all
    # move to group boundaries.  Differs from decode_burst (the
    # lockstep burst: every row decodes all K steps, EOS handled by
    # host truncation): a multi-step row STOPS on device, pins its KV
    # length, and emits nothing past termination.  Mutually exclusive
    # with decode_burst > 1 and with speculative decoding (validated
    # below).  1 = off = bit-for-bit today's loop, locked by test.
    multi_step: int = 1
    # KV blocks the radix prefix cache may hold (serving/prefix_cache.py):
    # completed prompts' full KV blocks are kept in a radix tree and
    # later prompts sharing a token prefix attach them read-only,
    # prefilling only the uncovered suffix.  0 = off = bit-for-bit
    # today's behavior (every prompt prefills from position 0).
    prefix_cache_blocks: int = 0
    # KV blocks the HOST spill tier behind the radix cache may hold
    # (serving/kv_tier.HostKVTier — ZeRO-Offload's HBM -> host
    # hierarchy, applied to serving): LRU eviction demotes cold prefix
    # KV to (pinned) host memory instead of dropping it, and a later
    # hit promotes the span back ahead of admission, so the effective
    # prefix cache grows to host-RAM scale.  Requires
    # prefix_cache_blocks > 0.  0 = off = bit-for-bit the HBM-only
    # cache (locked both directions by test).
    host_cache_blocks: int = 0
    # spill-byte quantization for the host tier: "int8" stores each
    # (layer, k/v, block) page as int8 codes + one fp32 scale (the
    # fleet-migration wire-quant grain; ~2x fewer spill bytes, bounded
    # dequant error — promoted KV is then no longer bit-for-bit),
    # "none" spills raw pages (demote/promote round trips are
    # bit-exact).
    host_cache_quant: str = "none"
    # debug-mode block-conservation audit: after every serve step that
    # finished a request, verify free + live + cache-held blocks account
    # for every block and refcount (DSStateManager.audit) — loud leak
    # detection for tests and canaries, off in production serving
    audit_blocks: bool = False
    # dynamic host-sync sanitizer (analysis/transfer_guard.py): run every
    # serve step under jax's device->host transfer guard.  The hot paths
    # make every INTENDED fetch explicit (jax.device_get), so "disallow"
    # turns any accidental logits/array materialization — the bug class
    # of full logits fetched on every token — into a loud error at the
    # offending call ("log" just reports it).  "off" = no guard.  NOTE:
    # CPU-backend d2h is zero-copy and invisible to the guard; this has
    # full teeth on real accelerators (tests force the h2d direction for
    # CPU-visible enforcement — see tests/test_serving.py).
    transfer_guard: str = "off"
    # cache-aware fleet routing across serve replicas
    # (deepspeed_tpu.serving.fleet); None = single-replica serving,
    # bit-for-bit today's behavior
    fleet: Optional[FleetConfig] = None
    # speculative decoding (prompt-lookup drafts + on-device verify,
    # serving/speculative.py); None (or mode="off") = bit-for-bit
    # today's serve loop, locked by test
    speculative: Optional[SpeculativeConfig] = None
    # request tracing + step timeline profiler (serving/tracing.py);
    # None (or all-off) = bit-for-bit the untraced loop, locked by test
    tracing: Optional[TracingConfig] = None
    # incremental token delivery with exactly-once failover semantics
    # (serving/streaming.py); None (or enabled=False) = bit-for-bit
    # the unstreamed serve loop, locked by test
    streaming: Optional[StreamingConfig] = None
    # SLO-aware priority preemption by KV swap-or-recompute
    # (ServeLoop._preempt_for_admission); None (or enabled=False) =
    # bit-for-bit the no-preemption scheduler, locked by test
    preemption: Optional[PreemptionConfig] = None
    # multi-tenant serving: paged multi-LoRA adapters + per-tenant QoS
    # (serving/tenancy); None (or enabled=False) = bit-for-bit the
    # single-tenant serve loop, locked by test
    tenancy: Optional[TenancyConfig] = None
    # grammar-constrained decoding: per-request response_format specs
    # (regex / JSON schema) enforced by an on-device token automaton
    # (serving/structured); None = constrained submits refused, and
    # requests without a response_format are bit-for-bit the
    # unconstrained loop either way (locked both directions by test)
    structured: Optional[StructuredConfig] = None
    # expert-paged MoE decode: slotted HBM expert pages with LRU
    # demotion to host + census-driven promotion (serving/experts.py);
    # None (or enabled=False) = bit-for-bit the unpaged serve loop,
    # locked BOTH directions by test
    moe: Optional[MoeServingConfig] = None
    # tensor-parallel serving (inference/v2): shard the engine's weights
    # column/row-wise and the KV arena on the kv-head dim over the first
    # N devices.  1 = single-device serving, bit-for-bit today's
    # behavior.  Engine factories fold this onto the engine config
    # (model_registry.apply_serving_tp); ServeLoop refuses an engine
    # whose tp degree disagrees with a non-default value here.
    tensor_parallel_size: int = 1
    # how the per-block TP collectives run (read only at tp > 1):
    # "xla" = GSPMD-inserted all-reduces (the default escape hatch),
    # "fused" = ring compute-collective matmuls (ops/tp_matmul.py) with
    # the whole serving program in one shard_map region — refuses
    # unsupported model layouts loudly at engine construction.
    tp_collectives: str = "xla"

    def validate(self) -> None:
        if self.max_queue_len < 1:
            raise ConfigError(
                f"serving.max_queue_len must be >= 1, got "
                f"{self.max_queue_len}")
        if self.default_max_new_tokens < 1:
            raise ConfigError(
                f"serving.default_max_new_tokens must be >= 1, got "
                f"{self.default_max_new_tokens}")
        if self.default_timeout_s is not None and self.default_timeout_s <= 0:
            raise ConfigError(
                f"serving.default_timeout_s must be positive, got "
                f"{self.default_timeout_s}")
        if self.monitor_interval_steps < 0:
            raise ConfigError(
                f"serving.monitor_interval_steps must be >= 0, got "
                f"{self.monitor_interval_steps}")
        if self.decode_burst < 1:
            raise ConfigError(
                f"serving.decode_burst must be >= 1 (1 = the per-step "
                f"path), got {self.decode_burst}")
        if self.multi_step < 1:
            raise ConfigError(
                f"serving.multi_step must be >= 1 (1 = multi-step "
                f"decode off), got {self.multi_step}")
        if self.multi_step > 1 and self.decode_burst > 1:
            raise ConfigError(
                "serving.multi_step > 1 and serving.decode_burst > 1 "
                "are two spellings of 'K tokens per dispatch' — pick "
                "one: multi_step adds on-device termination + seeded "
                "sampling; decode_burst is the lockstep host-truncated "
                "burst")
        if self.multi_step > 1 and self.speculative is not None \
                and self.speculative.mode != "off":
            raise ConfigError(
                "serving.multi_step cannot combine with "
                "serving.speculative: drafts are built on the host from "
                "each row's emitted prefix EVERY dispatch, which is "
                "exactly the per-step host round-trip the step-group "
                "path removes — and rejection sampling would break the "
                "one-draw-per-position seeded stream contract.  Run "
                "speculative fleets with multi_step=1 (decode_burst "
                "spans) or multi-step fleets with speculative "
                "mode='off'")
        if self.prefix_cache_blocks < 0:
            raise ConfigError(
                f"serving.prefix_cache_blocks must be >= 0 (0 = prefix "
                f"cache off), got {self.prefix_cache_blocks}")
        if self.host_cache_blocks < 0:
            raise ConfigError(
                f"serving.host_cache_blocks must be >= 0 (0 = host KV "
                f"tier off), got {self.host_cache_blocks}")
        if self.host_cache_blocks > 0 and self.prefix_cache_blocks <= 0:
            raise ConfigError(
                "serving.host_cache_blocks is the spill tier BEHIND the "
                "radix prefix cache (evictions demote into it), so it "
                "requires serving.prefix_cache_blocks > 0")
        if self.host_cache_quant not in ("none", "int8"):
            raise ConfigError(
                f"serving.host_cache_quant must be 'none' or 'int8', "
                f"got {self.host_cache_quant!r}")
        if self.transfer_guard not in ("off", "log", "disallow"):
            raise ConfigError(
                f"serving.transfer_guard must be 'off', 'log' or "
                f"'disallow', got {self.transfer_guard!r}")
        if self.tensor_parallel_size < 1:
            raise ConfigError(
                f"serving.tensor_parallel_size must be >= 1 (1 = "
                f"single-device serving), got {self.tensor_parallel_size}")
        if self.tp_collectives not in ("xla", "fused"):
            raise ConfigError(
                f"serving.tp_collectives must be 'xla' or 'fused', got "
                f"{self.tp_collectives!r}")
        if self.tp_collectives == "fused" and self.tensor_parallel_size <= 1:
            raise ConfigError(
                "serving.tp_collectives='fused' requires "
                "serving.tensor_parallel_size > 1 (there is no collective "
                "to fuse at tp=1)")
        if self.fleet is not None:
            self.fleet.validate()
            if self.fleet.migration and self.prefix_cache_blocks <= 0:
                raise ConfigError(
                    "serving.fleet.migration streams PREFIX KV blocks "
                    "between replicas, so it requires "
                    "serving.prefix_cache_blocks > 0 (the per-replica "
                    "radix cache that holds them)")
            if self.fleet.disagg is not None \
                    and self.prefix_cache_blocks <= 0:
                raise ConfigError(
                    "serving.fleet.disagg hands finished prompt KV from "
                    "the prefill pool to the decode pool through each "
                    "replica's radix prefix cache (the insert-before-"
                    "decref ownership seam), so it requires "
                    "serving.prefix_cache_blocks > 0")
        if self.tracing is not None:
            self.tracing.validate()
        if self.streaming is not None:
            self.streaming.validate()
        if self.preemption is not None:
            self.preemption.validate()
        if self.tenancy is not None:
            self.tenancy.validate()
            if (self.tenancy.enabled and self.speculative is not None
                    and self.speculative.mode != "off"):
                raise ConfigError(
                    "serving.tenancy cannot combine with "
                    "serving.speculative: the draft-verify program has "
                    "no gather-LoRA epilogue, so adapter rows would "
                    "silently verify against the BASE model's "
                    "distribution — run tenant fleets with "
                    "speculative.mode='off'")
        if self.structured is not None:
            self.structured.validate()
        if self.moe is not None:
            self.moe.validate()
            if (self.moe.enabled and self.speculative is not None
                    and self.speculative.mode != "off"):
                raise ConfigError(
                    "serving.moe cannot combine with serving.speculative: "
                    "the router census and reroute counters advance for "
                    "every drafted token, and rejected drafts cannot roll "
                    "them back — paged-MoE fleets must run "
                    "speculative.mode='off'")
            if self.moe.enabled and self.tp_collectives == "fused":
                # before the tp-size refusal: fused implies tp > 1, and
                # the fused program's closed region is the sharper reason
                raise ConfigError(
                    "serving.moe cannot combine with "
                    "tp_collectives='fused': the fused-TP program is one "
                    "closed shard_map region with no slot-indexed expert "
                    "gather — run paged MoE with tp_collectives='xla'")
            if self.moe.enabled and self.tensor_parallel_size > 1:
                raise ConfigError(
                    "serving.moe requires tensor_parallel_size=1: expert "
                    "slot pages are whole-expert HBM tiles and are not "
                    "sharded over the tp axis (expert parallelism is the "
                    "MoE scaling axis — see PARALLELISM.md)")
        if self.speculative is not None:
            self.speculative.validate()
            if self.speculative.mode != "off" and self.decode_burst <= 1:
                raise ConfigError(
                    "serving.speculative needs decode_burst > 1: draft "
                    "verification rides the burst serve path (on-device "
                    "accept/reject in the compiled program); the "
                    "decode_burst=1 per-step loop has no verify step "
                    "to extend")

    @classmethod
    def from_dict(cls, d: Optional[Dict[str, Any]]) -> "ServingConfig":
        d = d or {}
        timeout = d.get("default_timeout_s")
        fleet = d.get("fleet")
        spec = d.get("speculative")
        tracing = d.get("tracing")
        streaming = d.get("streaming")
        preemption = d.get("preemption")
        tenancy = d.get("tenancy")
        structured = d.get("structured")
        moe = d.get("moe")
        cfg = cls(
            enabled=bool(_get(d, "enabled", False)),
            max_queue_len=int(_get(d, "max_queue_len", 128)),
            default_max_new_tokens=int(_get(d, "default_max_new_tokens",
                                            64)),
            default_timeout_s=float(timeout) if timeout is not None
            else None,
            monitor_interval_steps=int(_get(d, "monitor_interval_steps",
                                            0)),
            decode_burst=int(_get(d, "decode_burst", 1)),
            multi_step=int(_get(d, "multi_step", 1)),
            prefix_cache_blocks=int(_get(d, "prefix_cache_blocks", 0)),
            host_cache_blocks=int(_get(d, "host_cache_blocks", 0)),
            host_cache_quant=str(_get(d, "host_cache_quant", "none")),
            audit_blocks=bool(_get(d, "audit_blocks", False)),
            transfer_guard=str(_get(d, "transfer_guard", "off")),
            fleet=(FleetConfig.from_dict(fleet) if fleet is not None
                   else None),
            speculative=(SpeculativeConfig.from_dict(spec)
                         if spec is not None else None),
            tracing=(TracingConfig.from_dict(tracing)
                     if tracing is not None else None),
            streaming=(StreamingConfig.from_dict(streaming)
                       if streaming is not None else None),
            preemption=(PreemptionConfig.from_dict(preemption)
                        if preemption is not None else None),
            tenancy=(TenancyConfig.from_dict(tenancy)
                     if tenancy is not None else None),
            structured=(StructuredConfig.from_dict(structured)
                        if structured is not None else None),
            moe=(MoeServingConfig.from_dict(moe)
                 if moe is not None else None),
            tensor_parallel_size=int(_get(d, "tensor_parallel_size", 1)),
            tp_collectives=str(_get(d, "tp_collectives", "xla")),
        )
        cfg.validate()
        return cfg


@dataclass
class CommsLoggerConfig:
    """Per-collective logging (reference: utils/comms_logging.py:67)."""

    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    prof_ops: List[str] = field(default_factory=list)
    debug: bool = False

    @classmethod
    def from_dict(cls, root: Dict[str, Any]) -> "CommsLoggerConfig":
        d = root.get("comms_logger", {}) or {}
        return cls(
            enabled=_get(d, "enabled", False),
            verbose=_get(d, "verbose", False),
            prof_all=_get(d, "prof_all", True),
            prof_ops=_get(d, "prof_ops", []),
            debug=_get(d, "debug", False),
        )


@dataclass
class FlopsProfilerConfig:
    """Reference: deepspeed/profiling/config.py.  TPU implementation reads
    XLA HLO cost analysis (SURVEY §7 step 13) instead of monkeypatching."""

    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None

    @classmethod
    def from_dict(cls, root: Dict[str, Any]) -> "FlopsProfilerConfig":
        d = root.get("flops_profiler", {}) or {}
        return cls(
            enabled=_get(d, "enabled", False),
            profile_step=int(_get(d, "profile_step", 1)),
            module_depth=int(_get(d, "module_depth", -1)),
            top_modules=int(_get(d, "top_modules", 1)),
            detailed=_get(d, "detailed", True),
            output_file=d.get("output_file"),
        )


@dataclass
class CompressionConfig:
    """Reference: deepspeed/compression/config.py — QAT / pruning trees are
    passed through as raw dicts and interpreted by deepspeed_tpu.compression."""

    raw: Dict[str, Any] = field(default_factory=dict)

    @property
    def enabled(self) -> bool:
        return bool(self.raw)

    @classmethod
    def from_dict(cls, root: Dict[str, Any]) -> "CompressionConfig":
        return cls(raw=root.get("compression_training", {}) or {})


@dataclass
class DataEfficiencyConfig:
    """Reference: runtime/data_pipeline/config.py (curriculum learning +
    random-LTD).  Raw dict preserved; interpreted by runtime/data_pipeline."""

    raw: Dict[str, Any] = field(default_factory=dict)

    @property
    def enabled(self) -> bool:
        return bool(self.raw.get("enabled", bool(self.raw)))

    @classmethod
    def from_dict(cls, root: Dict[str, Any]) -> "DataEfficiencyConfig":
        return cls(raw=root.get("data_efficiency", {}) or {})


@dataclass
class ElasticityConfig:
    """Reference: deepspeed/elasticity/config.py + elasticity.py:233."""

    enabled: bool = False
    max_train_batch_size: int = 0
    micro_batch_sizes: List[int] = field(default_factory=list)
    min_gpus: int = 1
    max_gpus: int = 10000
    min_time: int = 0
    prefer_larger_batch: bool = True
    ignore_non_elastic_batch_info: bool = False
    version: float = 0.2
    model_parallel_size: int = 1
    num_gpus_per_node: int = 1

    @classmethod
    def from_dict(cls, root: Dict[str, Any]) -> "ElasticityConfig":
        d = root.get("elasticity", {}) or {}
        return cls(
            enabled=_get(d, "enabled", False),
            max_train_batch_size=int(_get(d, "max_train_batch_size", 0)),
            micro_batch_sizes=list(_get(d, "micro_batch_sizes", [])),
            min_gpus=int(_get(d, "min_gpus", 1)),
            max_gpus=int(_get(d, "max_gpus", 10000)),
            min_time=int(_get(d, "min_time", 0)),
            prefer_larger_batch=_get(d, "prefer_larger_batch", True),
            ignore_non_elastic_batch_info=_get(d, "ignore_non_elastic_batch_info", False),
            version=float(_get(d, "version", 0.2)),
            model_parallel_size=int(_get(d, "model_parallel_size", 1)),
            num_gpus_per_node=int(_get(d, "num_gpus_per_node", 1)),
        )


@dataclass
class AutotuningConfig:
    """Reference: deepspeed/autotuning/config.py."""

    enabled: bool = False
    fast: bool = True
    metric: str = "throughput"
    start_profile_step: int = 3
    end_profile_step: int = 5
    num_tuning_micro_batch_sizes: int = 3
    tuner_type: str = "gridsearch"
    tuner_early_stopping: int = 5
    tuner_num_trials: int = 50
    max_train_batch_size: Optional[int] = None
    mp_size: int = 1

    @classmethod
    def from_dict(cls, root: Dict[str, Any]) -> "AutotuningConfig":
        d = root.get("autotuning", {}) or {}
        return cls(
            enabled=_get(d, "enabled", False),
            fast=_get(d, "fast", True),
            metric=_get(d, "metric", "throughput"),
            start_profile_step=int(_get(d, "start_profile_step", 3)),
            end_profile_step=int(_get(d, "end_profile_step", 5)),
            num_tuning_micro_batch_sizes=int(_get(d, "num_tuning_micro_batch_sizes", 3)),
            tuner_type=_get(d, "tuner_type", "gridsearch"),
            tuner_early_stopping=int(_get(d, "tuner_early_stopping", 5)),
            tuner_num_trials=int(_get(d, "tuner_num_trials", 50)),
            max_train_batch_size=d.get("max_train_batch_size"),
            mp_size=int(_get(d, "mp_size", 1)),
        )


@dataclass
class DeepSpeedTPUConfig:
    """Top-level config. Accepts a dict or a path to a JSON file, exactly like
    the reference's `deepspeed.initialize(config=...)`.

    Batch-size arithmetic follows the reference contract
    (runtime/config.py): train_batch_size = micro_batch * grad_accum * dp_world.
    Any two of the three determine the third.
    """

    raw: Dict[str, Any] = field(default_factory=dict)
    train_batch_size: int = 0
    train_micro_batch_size_per_gpu: int = 0
    gradient_accumulation_steps: int = 0
    steps_per_print: int = 10
    gradient_clipping: float = 0.0
    prescale_gradients: bool = False
    gradient_predivide_factor: float = 1.0
    communication_data_type: Optional[str] = None
    seed: int = 1234
    wall_clock_breakdown: bool = False
    memory_breakdown: bool = False
    dump_state: bool = False
    disable_allgather: bool = False
    sparse_gradients: bool = False
    # reference: runtime/config.py data_types.grad_accum_dtype — dtype the
    # engine accumulates/holds gradients in between backward and optimizer
    # step (fp32 default; bf16 halves the resident grad buffer)
    grad_accum_dtype: Optional[str] = None

    zero: ZeroConfig = field(default_factory=ZeroConfig)
    precision: PrecisionConfig = field(default_factory=PrecisionConfig)
    optimizer: Optional[OptimizerConfig] = None
    scheduler: Optional[SchedulerConfig] = None
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    moe: MoEConfig = field(default_factory=MoEConfig)
    activation_checkpointing: ActivationCheckpointingConfig = field(
        default_factory=ActivationCheckpointingConfig)
    checkpoint: CheckpointConfig = field(default_factory=CheckpointConfig)
    monitor: MonitorConfig = field(default_factory=MonitorConfig)
    serving: ServingConfig = field(default_factory=ServingConfig)
    comms_logger: CommsLoggerConfig = field(default_factory=CommsLoggerConfig)
    flops_profiler: FlopsProfilerConfig = field(default_factory=FlopsProfilerConfig)
    compression: CompressionConfig = field(default_factory=CompressionConfig)
    data_efficiency: DataEfficiencyConfig = field(default_factory=DataEfficiencyConfig)
    elasticity: ElasticityConfig = field(default_factory=ElasticityConfig)
    autotuning: AutotuningConfig = field(default_factory=AutotuningConfig)

    # ------------------------------------------------------------------
    @classmethod
    def from_json(cls, config, world_size: int = 1) -> "DeepSpeedTPUConfig":
        """Build from a dict, JSON string, or path to a JSON file."""
        if isinstance(config, cls):
            return config
        if isinstance(config, str):
            if os.path.exists(config):
                with open(config) as f:
                    config = json.load(f)
            else:
                try:
                    config = json.loads(config)
                except json.JSONDecodeError as e:
                    raise ConfigError(
                        f"config is neither an existing file nor valid JSON: {config!r}"
                    ) from e
        if not isinstance(config, dict):
            raise ConfigError(f"config must be dict or path, got {type(config)}")

        d = dict(config)
        cfg = cls(
            raw=d,
            train_batch_size=int(_get(d, "train_batch_size", 0)),
            train_micro_batch_size_per_gpu=int(_get(d, "train_micro_batch_size_per_gpu", 0)),
            gradient_accumulation_steps=int(_get(d, "gradient_accumulation_steps", 0)),
            steps_per_print=int(_get(d, "steps_per_print", 10)),
            gradient_clipping=float(_get(d, "gradient_clipping", 0.0)),
            prescale_gradients=_get(d, "prescale_gradients", False),
            gradient_predivide_factor=float(_get(d, "gradient_predivide_factor", 1.0)),
            communication_data_type=d.get("communication_data_type"),
            seed=int(_get(d, "seed", 1234)),
            wall_clock_breakdown=_get(d, "wall_clock_breakdown", False),
            memory_breakdown=_get(d, "memory_breakdown", False),
            dump_state=_get(d, "dump_state", False),
            sparse_gradients=_get(d, "sparse_gradients", False),
            grad_accum_dtype=(d.get("data_types") or {}).get("grad_accum_dtype"),
            zero=ZeroConfig.from_dict(d.get("zero_optimization")),
            precision=PrecisionConfig.from_dict(d),
            optimizer=OptimizerConfig.from_dict(d.get("optimizer")),
            scheduler=SchedulerConfig.from_dict(d.get("scheduler")),
            parallel=ParallelConfig.from_dict(d),
            moe=MoEConfig.from_dict(d.get("moe")),
            activation_checkpointing=ActivationCheckpointingConfig.from_dict(
                d.get("activation_checkpointing")),
            checkpoint=CheckpointConfig.from_dict(d),
            monitor=MonitorConfig.from_dict(d),
            serving=ServingConfig.from_dict(d.get("serving")),
            comms_logger=CommsLoggerConfig.from_dict(d),
            flops_profiler=FlopsProfilerConfig.from_dict(d),
            compression=CompressionConfig.from_dict(d),
            data_efficiency=DataEfficiencyConfig.from_dict(d),
            elasticity=ElasticityConfig.from_dict(d),
            autotuning=AutotuningConfig.from_dict(d),
        )
        cfg._resolve_batch_sizes(world_size)
        return cfg

    # ------------------------------------------------------------------
    def _resolve_batch_sizes(self, world_size: int) -> None:
        """train_batch_size = micro * gas * dp_world (reference:
        runtime/config.py _configure_train_batch_size)."""
        dp = max(1, world_size // (
            self.parallel.tensor_parallel_size
            * self.parallel.pipeline_parallel_size
            * max(1, self.parallel.sequence_parallel_size)
            * max(1, self.parallel.context_parallel_size)))
        tb, mb, gas = (self.train_batch_size, self.train_micro_batch_size_per_gpu,
                       self.gradient_accumulation_steps)
        if tb and mb and gas:
            if tb != mb * gas * dp:
                raise ConfigError(
                    f"train_batch_size {tb} != micro_batch {mb} * gas {gas} * dp {dp}")
        elif tb and mb:
            if tb % (mb * dp):
                raise ConfigError(
                    f"train_batch_size {tb} not divisible by micro_batch*dp {mb * dp}")
            gas = tb // (mb * dp)
        elif tb and gas:
            if tb % (gas * dp):
                raise ConfigError(
                    f"train_batch_size {tb} not divisible by gas*dp {gas * dp}")
            mb = tb // (gas * dp)
        elif mb and gas:
            tb = mb * gas * dp
        elif mb:
            gas = 1
            tb = mb * dp
        elif tb:
            gas = 1
            if tb % dp:
                raise ConfigError(f"train_batch_size {tb} not divisible by dp {dp}")
            mb = tb // dp
        else:
            mb, gas, tb = 1, 1, dp
        self.train_batch_size = tb
        self.train_micro_batch_size_per_gpu = mb
        self.gradient_accumulation_steps = gas
        self.data_parallel_size = dp

    # ------------------------------------------------------------------
    def reconcile_topology(self, dp_size: int) -> None:
        """Recompute the batch triple against the actual mesh's data-parallel
        degree (used when an explicit MeshTopology overrides the config's
        axis sizes)."""
        if dp_size == self.data_parallel_size:
            return
        mb, gas = self.train_micro_batch_size_per_gpu, self.gradient_accumulation_steps
        if mb and gas:
            self.train_batch_size = mb * gas * dp_size
        elif self.train_batch_size:
            if self.train_batch_size % (gas * dp_size):
                raise ConfigError(
                    f"train_batch_size {self.train_batch_size} not divisible by "
                    f"gas*dp {gas * dp_size}")
            self.train_micro_batch_size_per_gpu = self.train_batch_size // (gas * dp_size)
        self.data_parallel_size = dp_size

    # ------------------------------------------------------------------
    def to_dict(self) -> Dict[str, Any]:
        def conv(o):
            if dataclasses.is_dataclass(o):
                return {k: conv(v) for k, v in dataclasses.asdict(o).items()}
            return o
        return conv(self)
