"""Core enums and configuration dataclasses.

TPU-native counterpart of the reference's ``utils/dataclasses.py``
(``/root/reference/src/accelerate/utils/dataclasses.py`` — ``DistributedType:600``,
``PrecisionType:765``, ``RNGType:781``, ``DataLoaderConfiguration:814``,
``ProjectConfiguration:909``, ``GradientAccumulationPlugin:972``,
``ProfileKwargs:484``, ``LoggerType:737``). Engine-specific plugins (DeepSpeed /
Megatron / FSDP-torch) collapse into sharding configuration — see
``accelerate_tpu/parallel/`` and ``parallelism_config.py``.
"""

from __future__ import annotations

import enum
import os
from dataclasses import dataclass, field
from datetime import timedelta
from typing import Any, Callable, Optional

from .environment import (
    parse_flag_from_env,
    parse_int_from_env,
    parse_optional_int_from_env,
    parse_seconds_from_env,
)


class BaseEnum(str, enum.Enum):
    def __str__(self) -> str:  # so f-strings print the bare value
        return self.value

    @classmethod
    def list(cls) -> list[str]:
        return [v.value for v in cls]


class DistributedType(BaseEnum):
    """How this process participates in distributed execution.

    Unlike the reference (``utils/dataclasses.py:600`` — one value per engine:
    MULTI_GPU / DEEPSPEED / FSDP / MEGATRON_LM / XLA), a JAX program has exactly one
    execution model: SPMD over a device mesh. The interesting structure (dp/fsdp/tp/
    cp/sp sizes) lives in :class:`~accelerate_tpu.parallelism_config.ParallelismConfig`.
    """

    NO = "NO"  # single device
    SPMD = "SPMD"  # >1 device, single- or multi-host, via mesh + GSPMD
    MULTI_HOST = "MULTI_HOST"  # SPMD spanning multiple processes/hosts


class PrecisionType(BaseEnum):
    """Mixed-precision modes (reference ``utils/dataclasses.py:765``).

    On TPU bf16 needs no loss scaling (MXU-native); fp16 is supported for parity but
    bf16 is the recommended mode. fp8 uses XLA fp8 dot_general / Pallas kernels.
    """

    NO = "no"
    FP16 = "fp16"
    BF16 = "bf16"
    FP8 = "fp8"


class RNGType(BaseEnum):
    """RNG streams that can be synchronized/checkpointed (reference ``:781``)."""

    JAX = "jax"  # explicit jax.random key held by the Accelerator
    NUMPY = "numpy"
    PYTHON = "python"
    TORCH = "torch"  # host-side torch generators used by interop dataloaders
    GENERATOR = "generator"


class LoggerType(BaseEnum):
    ALL = "all"
    TENSORBOARD = "tensorboard"
    WANDB = "wandb"
    MLFLOW = "mlflow"
    COMETML = "comet_ml"
    AIM = "aim"
    CLEARML = "clearml"
    DVCLIVE = "dvclive"
    SWANLAB = "swanlab"
    TRACKIO = "trackio"
    JSONL = "jsonl"  # built-in dependency-free tracker


class SaveFormat(BaseEnum):
    MSGPACK = "msgpack"  # flax serialization
    SAFETENSORS = "safetensors"
    NUMPY = "npz"
    ORBAX = "orbax"


@dataclass
class KwargsHandler:
    """Base for kwargs passthrough dataclasses (reference ``:68``)."""

    def to_dict(self) -> dict[str, Any]:
        return {k: v for k, v in self.__dict__.items()}

    def to_kwargs(self) -> dict[str, Any]:
        from dataclasses import fields

        default = self.__class__()
        return {
            f.name: getattr(self, f.name)
            for f in fields(self)
            if getattr(self, f.name) != getattr(default, f.name)
        }


@dataclass
class InitProcessGroupKwargs(KwargsHandler):
    """Options for ``jax.distributed.initialize`` (reference ``:273`` wraps
    ``torch.distributed.init_process_group``)."""

    coordinator_address: Optional[str] = None
    num_processes: Optional[int] = None
    process_id: Optional[int] = None
    local_device_ids: Optional[list[int]] = None
    initialization_timeout: timedelta = field(default_factory=lambda: timedelta(seconds=300))


@dataclass
class GradientAccumulationPlugin(KwargsHandler):
    """Reference ``utils/dataclasses.py:972``. ``adjust_scheduler`` multiplies
    scheduler steps; ``sync_with_dataloader`` forces a sync step at end-of-epoch."""

    num_steps: int = 1
    adjust_scheduler: bool = True
    sync_with_dataloader: bool = True
    sync_each_batch: bool = False

    def __post_init__(self):
        if self.num_steps < 1:
            raise ValueError(f"gradient accumulation steps must be >= 1, got {self.num_steps}")


@dataclass
class DataLoaderConfiguration(KwargsHandler):
    """Reference ``utils/dataclasses.py:814``.

    ``dispatch_batches``: process 0 reads batches and broadcasts (DataLoaderDispatcher,
    reference ``data_loader.py:704``); default per-process sharded reads.
    ``even_batches``: wrap around to equalize final batches (static shapes make this
    the strongly-recommended default under XLA).
    ``prefetch_depth``: how many batches the background producer may fetch,
    host-process and transfer to device ahead of the consuming step (no
    reference counterpart — TPU-native async input pipeline, see
    ``docs/data_pipeline.md``). ``0`` disables prefetching and restores fully
    synchronous iteration.
    """

    split_batches: bool = False
    dispatch_batches: Optional[bool] = None
    even_batches: bool = True
    use_seedable_sampler: bool = True
    non_blocking: bool = True
    use_stateful_dataloader: bool = False
    data_seed: Optional[int] = None
    prefetch_depth: int = 2


@dataclass
class ProjectConfiguration(KwargsHandler):
    """Checkpoint/artifact layout (reference ``utils/dataclasses.py:909``)."""

    project_dir: Optional[str] = None
    logging_dir: Optional[str] = None
    automatic_checkpoint_naming: bool = False
    total_limit: Optional[int] = None
    iteration: int = 0
    save_on_each_node: bool = False

    def set_directories(self, project_dir: Optional[str] = None) -> None:
        self.project_dir = project_dir
        if self.logging_dir is None:
            self.logging_dir = project_dir

    def __post_init__(self):
        if self.logging_dir is None:
            self.logging_dir = self.project_dir


@dataclass
class JitConfig(KwargsHandler):
    """Compilation options — the moral twin of ``TorchDynamoPlugin`` (reference
    ``utils/dataclasses.py:1024``). Under JAX, jit is default-on; these knobs tune it.

    ``donate_params``: donate param/opt-state buffers to the train step (halves HBM
    for the update). ``persistent_cache_dir`` places JAX's persistent compilation
    cache — unless ``JAX_COMPILATION_CACHE_DIR`` is set: the cache then lives
    where the environment says and no code moves it (the path is part of the
    cache's key, and the machine that runs the program decides where a cache
    survives). ``remat_policy`` names a jax.checkpoint policy for activation
    rematerialisation.
    """

    disable_jit: bool = field(
        default_factory=lambda: parse_flag_from_env("ACCELERATE_TPU_DISABLE_JIT", False)
    )
    donate_params: bool = True
    persistent_cache_dir: Optional[str] = None
    remat_policy: Optional[str] = None  # e.g. "nothing_saveable", "dots_saveable"

    def apply(self) -> None:
        import jax

        if self.persistent_cache_dir and not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", self.persistent_cache_dir)
        if self.disable_jit:
            jax.config.update("jax_disable_jit", True)


@dataclass
class ProfileConfig(KwargsHandler):
    """``jax.profiler`` trace configuration — counterpart of ``ProfileKwargs``
    (reference ``utils/dataclasses.py:484-599`` builds ``torch.profiler.profile``).

    ``output_trace_dir`` receives a TensorBoard/Perfetto-compatible trace; the
    reference exports per-rank Chrome traces (``accelerator.py:4148-4205``).

    Two complementary mechanisms:

    - the ``accelerator.profile(...)`` *context* (whole-block, or the
      reference-style ``wait/warmup/active/repeat`` step schedule below);
    - **automatic trace windows** on the tracked train step (no context
      needed): every ``trace_every`` steps — or one-shot at step
      ``trace_at`` — a window of ``trace_steps`` steps is traced, parsed
      (top-k ops, compute/collective/idle split, comms-overlap ratio — see
      ``telemetry/xplane.py``) and emitted as a ``trace`` telemetry record.
      Env-seeded (``ACCELERATE_TRACE_EVERY`` / ``ACCELERATE_TRACE_STEPS`` /
      ``ACCELERATE_TRACE_AT`` / ``ACCELERATE_TRACE_DIR``) so a launcher can
      arm profiling with zero code changes.
    """

    output_trace_dir: Optional[str] = field(
        default_factory=lambda: os.environ.get("ACCELERATE_TRACE_DIR") or None
    )
    create_perfetto_link: bool = False
    create_perfetto_trace: bool = True
    host_tracer_level: int = 2
    python_tracer_level: int = 0
    device_tracer_level: int = 1
    # step-windowed schedule (reference ProfileKwargs wait/warmup/active/
    # repeat/skip_first, ``utils/dataclasses.py:484-599``): when ``active > 0``
    # the profile context traces only the active window of each cycle, driven
    # by ``prof.step()`` calls; ``repeat=0`` cycles until the context exits
    skip_first: int = 0
    wait: int = 0
    warmup: int = 0
    active: int = 0
    repeat: int = 0
    # automatic trace windows on the tracked step (telemetry/xplane.py):
    # every Nth step / a one-shot step index, window length in steps
    trace_every: int = field(
        default_factory=lambda: parse_int_from_env("ACCELERATE_TRACE_EVERY", 0)
    )
    trace_steps: int = field(
        default_factory=lambda: max(1, parse_int_from_env("ACCELERATE_TRACE_STEPS", 1))
    )
    trace_at: Optional[int] = field(
        default_factory=lambda: parse_optional_int_from_env("ACCELERATE_TRACE_AT")
    )

    @property
    def schedule_enabled(self) -> bool:
        return self.active > 0

    @property
    def windows_enabled(self) -> bool:
        """True when automatic trace windows should drive the tracked step."""
        return self.trace_every > 0 or self.trace_at is not None

    def build_options(self):
        import jax

        options = jax.profiler.ProfileOptions()
        for attr in ("host_tracer_level", "python_tracer_level", "device_tracer_level"):
            value = getattr(self, attr)
            try:
                setattr(options, attr, value)
            except (AttributeError, ValueError):  # older jax ProfileOptions surface
                pass
        return options


@dataclass
class AutocastConfig(KwargsHandler):
    """Scoped opt-out of the bf16 compute policy (reference ``AutocastKwargs:113``)."""

    enabled: bool = True
    cache_enabled: bool = True


@dataclass
class WatchdogConfig(KwargsHandler):
    """Hang/straggler forensics (no reference counterpart — pod-scale TPU runs
    need hang *attribution*, see ``telemetry/watchdog.py`` and
    ``docs/troubleshooting.md``).

    ``timeout`` seconds without a heartbeat (train step, prefetch producer) or
    with one blocking phase held open (a collective, backend init) before the
    watchdog dumps ``flight-rank<k>.json`` — all-thread stacks, the event ring,
    and the name of the phase the rank is blocked in. ``0`` (the default)
    disables the watchdog entirely: no thread is started and no file is
    opened. Defaults seed from ``ACCELERATE_WATCHDOG_TIMEOUT`` /
    ``ACCELERATE_WATCHDOG_INTERVAL`` / ``ACCELERATE_WATCHDOG_ABORT`` /
    ``ACCELERATE_FLIGHT_DIR`` so a launcher can arm forensics without code
    changes. ``abort_on_stall`` exits the process (code 101) after dumping so
    an orchestrator restarts the rank instead of wedging the pod. Size the
    timeout above your longest legitimate gap between steps (checkpointing,
    eval) — a stall dump is cheap but noisy.
    """

    timeout: float = field(
        default_factory=lambda: parse_seconds_from_env("ACCELERATE_WATCHDOG_TIMEOUT")
    )
    interval: Optional[float] = None
    abort_on_stall: bool = field(
        default_factory=lambda: parse_flag_from_env("ACCELERATE_WATCHDOG_ABORT")
    )
    flight_dir: Optional[str] = field(
        default_factory=lambda: os.environ.get("ACCELERATE_FLIGHT_DIR")
    )

    @property
    def enabled(self) -> bool:
        """True when a positive timeout arms the watchdog."""
        return self.timeout > 0


@dataclass
class CheckpointConfig(KwargsHandler):
    """Asynchronous zero-stall checkpointing (no reference counterpart — the
    reference's ``save_state`` blocks for the full serialize+write; see
    ``docs/checkpointing.md`` "Async saves and crash consistency").

    ``async_save``: default for ``Accelerator.save_state`` — when True, saves
    run ``blocking=False``: the train loop only pays the device→host snapshot
    (milliseconds) and a single daemon writer serializes, fsyncs and commits
    in the background. Per-call ``save_state(..., blocking=...)`` overrides.
    ``max_in_flight``: how many snapshots may be queued/writing at once;
    an additional ``save_state`` blocks (back-pressure) until a slot frees —
    the default of 1 bounds host RAM to one extra state copy.
    ``save_on_each_node``: default for the same-named ``save_state`` kwarg
    (reference ``save_state:3529``): every node writes a full copy to its
    node-local dir instead of only the main process writing one.
    Seeds from ``ACCELERATE_ASYNC_CHECKPOINT`` so a launcher can flip saves
    async without code changes.
    """

    async_save: bool = field(
        default_factory=lambda: parse_flag_from_env("ACCELERATE_ASYNC_CHECKPOINT", False)
    )
    max_in_flight: int = 1
    save_on_each_node: bool = False

    def __post_init__(self):
        if self.max_in_flight < 1:
            raise ValueError(f"max_in_flight must be >= 1, got {self.max_in_flight}")


@dataclass
class GradScalerConfig(KwargsHandler):
    """fp16 loss-scaling settings (reference ``GradScalerKwargs:241``). Only used for
    ``mixed_precision="fp16"``; bf16 on TPU needs no scaler. Implemented with a
    DynamicScale-style state threaded through the train step."""

    init_scale: float = 2.0**15
    growth_factor: float = 2.0
    backoff_factor: float = 0.5
    growth_interval: int = 2000
    enabled: bool = True


# ---------------------------------------------------------------------------
# Mixed-precision policy


@dataclass(frozen=True)
class MixedPrecisionPolicy:
    """dtype policy for params / compute / output, jmp-style.

    The reference wraps forward in ``torch.autocast`` + ``convert_outputs_to_fp32``
    (``accelerator.py:1778-1789``); under JAX we cast inputs/params at well-defined
    boundaries instead, which XLA then fuses.
    """

    param_dtype: Any = None  # jnp dtype or None = float32
    compute_dtype: Any = None
    output_dtype: Any = None

    @classmethod
    def from_precision(cls, precision: str | PrecisionType) -> "MixedPrecisionPolicy":
        import jax.numpy as jnp

        precision = PrecisionType(str(precision))
        if precision == PrecisionType.NO:
            return cls(None, None, None)  # "no" = never touch dtypes
        if precision == PrecisionType.BF16:
            return cls(jnp.float32, jnp.bfloat16, jnp.float32)
        if precision == PrecisionType.FP16:
            return cls(jnp.float32, jnp.float16, jnp.float32)
        if precision == PrecisionType.FP8:
            # fp8 applies per-matmul via Pallas/XLA recipes; activations stay bf16.
            return cls(jnp.float32, jnp.bfloat16, jnp.float32)
        raise ValueError(f"unknown precision {precision}")

    def cast_to_compute(self, tree):
        import jax
        import jax.numpy as jnp

        if self.compute_dtype is None:
            return tree

        from ..ops.quantization import QuantizedArray

        def _cast(path, x):
            # quantized leaves (int8 codes + f32 scales) and fp8 delayed-scaling
            # meta must pass through untouched — casting their f32 scales to
            # bf16 silently degrades accuracy
            if isinstance(x, QuantizedArray):
                return x
            if any(getattr(k, "key", None) == "fp8_meta" for k in path):
                return x
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(self.compute_dtype)
            return x

        return jax.tree_util.tree_map_with_path(
            _cast, tree, is_leaf=lambda x: isinstance(x, QuantizedArray)
        )

    def cast_to_param(self, tree):
        import jax
        import jax.numpy as jnp

        if self.param_dtype is None:
            return tree

        def _cast(x):
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(self.param_dtype)
            return x

        return jax.tree_util.tree_map(_cast, tree)

    def cast_to_output(self, tree):
        import jax
        import jax.numpy as jnp

        if self.output_dtype is None:
            return tree

        def _cast(x):
            if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating):
                return x.astype(self.output_dtype)
            return x

        return jax.tree_util.tree_map(_cast, tree)


# ---------------------------------------------------------------------------
# Reference-compat plugin/kwargs spellings.
#
# The reference steers torch engines (DDP buckets, torch FSDP wrappers, the
# DeepSpeed runtime) through these objects. On TPU the same intents are
# sharding assignments and dtype policies, so each shim translates its knobs
# into the native configuration (and warns about knobs with no XLA meaning)
# rather than mirroring engine internals.


class DDPCommunicationHookType(BaseEnum):
    """Gradient-compression choices (reference ``DDPCommunicationHookType``,
    ``utils/dataclasses.py:134``). The allreduce itself is GSPMD-inserted on
    TPU; the hook's wire-compression half maps to casting the gradient signal
    (see ``examples/by_feature/gradient_compression.py``)."""

    NO = "no"
    FP16 = "fp16"
    BF16 = "bf16"
    POWER_SGD = "power_sgd"
    BATCHED_POWER_SGD = "batched_power_sgd"


@dataclass
class DistributedDataParallelKwargs(KwargsHandler):
    """Reference ``DistributedDataParallelKwargs`` (``utils/dataclasses.py:155``)
    compat. Bucketing/graph knobs steer torch DDP's NCCL schedule and have no
    GSPMD counterpart (XLA schedules grad collectives itself); they are accepted
    so reference configs parse. ``comm_hook`` is honored: it selects the dtype
    returned by :meth:`gradient_compression_dtype`, which
    ``Accelerator.prepare_train_step`` applies to the gradient signal."""

    bucket_cap_mb: int = 25
    find_unused_parameters: bool = False
    gradient_as_bucket_view: bool = False
    static_graph: bool = False
    comm_hook: DDPCommunicationHookType = DDPCommunicationHookType.NO

    def __post_init__(self):
        self.comm_hook = DDPCommunicationHookType(str(self.comm_hook))

    def gradient_compression_dtype(self) -> Optional[str]:
        """dtype name the gradient signal is bounded to, or None."""
        if self.comm_hook == DDPCommunicationHookType.FP16:
            return "float16"
        if self.comm_hook == DDPCommunicationHookType.BF16:
            return "bfloat16"
        if self.comm_hook in (
            DDPCommunicationHookType.POWER_SGD,
            DDPCommunicationHookType.BATCHED_POWER_SGD,
        ):
            import warnings

            warnings.warn(
                "PowerSGD low-rank gradient compression has no XLA counterpart; "
                "falling back to a bf16 cast of the gradient signal."
            )
            return "bfloat16"
        return None


@dataclass
class FullyShardedDataParallelPlugin(KwargsHandler):
    """Migration shim for reference ``FullyShardedDataParallelPlugin``
    (``utils/dataclasses.py:1566``). FSDP on TPU is not a module wrapper — it is
    a ``NamedSharding`` assignment over the ``dp_shard`` mesh axis — so this
    object's one real job is :meth:`to_parallelism_config`. Wrapper-scheduling
    knobs (auto-wrap policy, backward prefetch, ``use_orig_params``) have no
    XLA meaning: GSPMD decides gather/reshard scheduling.

    ``sharding_strategy`` accepts the reference spellings (``FULL_SHARD``,
    ``SHARD_GRAD_OP``, ``NO_SHARD``, ``HYBRID_SHARD``, or their 1-4 codes).
    ``FULL_SHARD`` and ``SHARD_GRAD_OP`` collapse: under GSPMD, params are
    gathered on demand either way, so ZeRO-2 vs ZeRO-3 is a scheduling detail
    the compiler owns."""

    sharding_strategy: Any = "FULL_SHARD"
    cpu_offload: bool = False
    activation_checkpointing: bool = False
    state_dict_type: str = "SHARDED_STATE_DICT"
    # None = unset: the FSDP_CPU_RAM_EFFICIENT_LOADING env flag (written by
    # enable/disable_fsdp_ram_efficient_loading) supplies the default, True
    # absent that; an EXPLICIT constructor value always wins over the env
    cpu_ram_efficient_loading: Optional[bool] = None

    _STRATEGIES = {1: "FULL_SHARD", 2: "SHARD_GRAD_OP", 3: "NO_SHARD", 4: "HYBRID_SHARD"}

    def __post_init__(self):
        if self.cpu_ram_efficient_loading is None:
            env_flag = os.environ.get("FSDP_CPU_RAM_EFFICIENT_LOADING", "true")
            self.cpu_ram_efficient_loading = env_flag.strip().lower() in ("1", "true", "yes")
        s = self.sharding_strategy
        if isinstance(s, int):
            if s not in self._STRATEGIES:
                raise ValueError(
                    f"unknown sharding_strategy code {s} (valid: {sorted(self._STRATEGIES)})"
                )
            s = self._STRATEGIES[s]
        s = str(s).rsplit(".", 1)[-1].upper()  # accept "ShardingStrategy.FULL_SHARD"
        if s not in self._STRATEGIES.values():
            raise ValueError(f"unknown sharding_strategy {self.sharding_strategy!r}")
        self.sharding_strategy = s

    @property
    def remat(self) -> "bool | str":
        """The ``activation_checkpointing`` knob in native form: pass this as
        the model forward's ``remat=`` argument (e.g.
        ``llama_loss(..., remat=plugin.remat)``). Maps to the
        ``"dots_no_batch"`` policy — the transformer sweet spot — rather than
        full recompute, matching torch FSDP's per-block checkpointing cost."""
        return "dots_no_batch" if self.activation_checkpointing else False

    def to_parallelism_config(
        self, num_devices: Optional[int] = None, dp_replicate_size: int = 1
    ):
        """Translate to the native mesh config. ``HYBRID_SHARD`` needs
        ``dp_replicate_size`` (the outer replica count; reference HSDP)."""
        from ..parallelism_config import ParallelismConfig

        if self.sharding_strategy == "NO_SHARD":
            if num_devices is None:
                import jax

                num_devices = len(jax.devices())
            return ParallelismConfig(dp_replicate_size=num_devices)
        if self.sharding_strategy == "HYBRID_SHARD" and dp_replicate_size == 1:
            raise ValueError("HYBRID_SHARD requires dp_replicate_size > 1")
        return ParallelismConfig(dp_replicate_size=dp_replicate_size, dp_shard_size=-1)


@dataclass
class DeepSpeedPlugin(KwargsHandler):
    """Migration shim for reference ``DeepSpeedPlugin`` (``utils/dataclasses.py:1113``).
    ZeRO stages are shardings here: stage 0 → pure replication; stage 1 →
    params replicated with the OPTIMIZER STATE sharded across replicas
    (``parallel.sharding.zero1_state_specs``); stages 2-3 → the ``dp_shard``
    FSDP NamedSharding (grad/param sharding collapse under GSPMD's
    compiler-scheduled gathers). A reference ``hf_ds_config`` dict is accepted
    and mined for the fields that still mean something here (stage,
    accumulation, clipping, offload)."""

    zero_stage: int = 2
    gradient_accumulation_steps: int = 1
    gradient_clipping: Optional[float] = None
    offload_optimizer_device: Optional[str] = None
    offload_param_device: Optional[str] = None
    zero3_init_flag: bool = False
    zero3_save_16bit_model: bool = False
    hf_ds_config: Optional[dict] = None

    def __post_init__(self):
        cfg = self.hf_ds_config or {}
        zero = cfg.get("zero_optimization", {})

        def _fill(attr, value, cast):
            """ds_config fills fields still at their DEFAULT; an explicit
            constructor value wins (with a warning on disagreement — the
            reference errors on flag/config mismatches, ``fill_match``)."""
            if value is None or _is_auto(value):
                return
            value = cast(value)
            current = getattr(self, attr)
            default = type(self).__dataclass_fields__[attr].default
            if current == default:
                setattr(self, attr, value)
            elif current != value:
                import warnings

                warnings.warn(
                    f"DeepSpeedPlugin.{attr}={current!r} (explicit) disagrees with "
                    f"hf_ds_config value {value!r}; keeping the explicit value"
                )

        _fill("zero_stage", zero.get("stage"), int)
        _fill("gradient_accumulation_steps", cfg.get("gradient_accumulation_steps"), int)
        _fill("gradient_clipping", cfg.get("gradient_clipping"), float)
        for src, attr in (("offload_optimizer", "offload_optimizer_device"),
                          ("offload_param", "offload_param_device")):
            dev = zero.get(src, {}).get("device")
            if dev and dev != "none":
                _fill(attr, dev, str)
        if not 0 <= self.zero_stage <= 3:
            raise ValueError(f"zero_stage must be 0-3, got {self.zero_stage}")

    @classmethod
    def from_env(cls) -> "DeepSpeedPlugin":
        """Build from the launcher's env protocol (reference
        ``utils/launch.py:557-577`` writer / ``utils/dataclasses.py:1225-1232``
        reader): ``ACCELERATE_DEEPSPEED_ZERO_STAGE``, offload devices,
        ``ACCELERATE_GRADIENT_CLIPPING``, ``ACCELERATE_DEEPSPEED_CONFIG_FILE``
        (json loaded into ``hf_ds_config``)."""
        kwargs: dict[str, Any] = {}
        stage = os.environ.get("ACCELERATE_DEEPSPEED_ZERO_STAGE")
        if stage is not None and not _is_auto(stage):
            kwargs["zero_stage"] = int(stage)
        clip = os.environ.get("ACCELERATE_GRADIENT_CLIPPING")
        if clip is not None and not _is_auto(clip):
            kwargs["gradient_clipping"] = float(clip)
        for env_name, attr in (
            ("ACCELERATE_DEEPSPEED_OFFLOAD_OPTIMIZER_DEVICE", "offload_optimizer_device"),
            ("ACCELERATE_DEEPSPEED_OFFLOAD_PARAM_DEVICE", "offload_param_device"),
        ):
            dev = os.environ.get(env_name)
            if dev and dev != "none":
                kwargs[attr] = dev
        config_file = os.environ.get("ACCELERATE_DEEPSPEED_CONFIG_FILE")
        if config_file:
            import json

            with open(config_file) as f:
                kwargs["hf_ds_config"] = json.load(f)
        accum = os.environ.get("ACCELERATE_GRADIENT_ACCUMULATION_STEPS")
        if accum is not None and not _is_auto(accum):
            kwargs["gradient_accumulation_steps"] = int(accum)
        return cls(**kwargs)

    def to_parallelism_config(self, num_devices: Optional[int] = None):
        from ..parallelism_config import ParallelismConfig

        if self.zero_stage in (0, 1):
            # stage 1 keeps params replicated (the optimizer-state sharding is
            # applied separately over the dp_replicate axis)
            if num_devices is None:
                import jax

                num_devices = len(jax.devices())
            return ParallelismConfig(dp_replicate_size=num_devices)
        return ParallelismConfig(dp_shard_size=-1)

    @property
    def mixed_precision(self) -> Optional[str]:
        """Precision requested by the ds config's ``bf16``/``fp16`` sections
        (None when absent — the Accelerator's own setting then applies)."""
        cfg = self.hf_ds_config or {}
        if cfg.get("bf16", {}).get("enabled") is True:
            return "bf16"
        if cfg.get("fp16", {}).get("enabled") is True:
            return "fp16"
        return None

    def dummy_optim_kwargs(self) -> dict:
        """Hyperparameters for a :class:`DummyOptim` from the ds config's
        ``optimizer`` section (the reference's config-is-source-of-truth flow:
        ``examples/by_feature/deepspeed_with_config_support.py``). ``auto``
        values are omitted so the placeholder's own values fill them."""
        params = (self.hf_ds_config or {}).get("optimizer", {}).get("params", {})
        out: dict = {}
        for src, dst, cast in (
            ("lr", "lr", float),
            ("weight_decay", "weight_decay", float),
            ("betas", "betas", tuple),
            ("eps", "eps", float),
        ):
            v = params.get(src)
            if v is not None and not _is_auto(v):
                out[dst] = cast(v)
        return out

    def dummy_scheduler_kwargs(self) -> dict:
        """``DummyScheduler`` fields from the ds config's ``scheduler`` section
        (WarmupLR / WarmupDecayLR shapes)."""
        params = (self.hf_ds_config or {}).get("scheduler", {}).get("params", {})
        out: dict = {}
        total = params.get("total_num_steps")
        if total is not None and not _is_auto(total):
            out["total_num_steps"] = int(total)
        warm = params.get("warmup_num_steps")
        if warm is not None and not _is_auto(warm):
            out["warmup_num_steps"] = int(warm)
        return out


def _is_auto(v) -> bool:
    return isinstance(v, str) and v == "auto"


# Reference names for config objects that already exist natively (the reference
# calls every kwargs-handler "...Kwargs"; our spellings say what they configure).
AutocastKwargs = AutocastConfig
GradScalerKwargs = GradScalerConfig
ProfileKwargs = ProfileConfig


class CustomDtype(BaseEnum):
    """reference ``CustomDtype`` — sub-byte / fp8 markers for memory-size
    accounting (``dtype_byte_size``/``infer_auto_device_map``): these have no
    numpy dtype, so device-map math names them explicitly."""

    FP8 = "fp8"
    INT4 = "int4"
    INT2 = "int2"


class ComputeEnvironment(BaseEnum):
    """reference ``utils/dataclasses.py`` — config-file field; SageMaker
    clusters are not a TPU deployment target but configs naming them parse."""

    LOCAL_MACHINE = "LOCAL_MACHINE"
    AMAZON_SAGEMAKER = "AMAZON_SAGEMAKER"


class SageMakerDistributedType(BaseEnum):
    """reference config-file enum (parsed, not acted on — no SageMaker on TPU)."""

    NO = "NO"
    DATA_PARALLEL = "DATA_PARALLEL"
    MODEL_PARALLEL = "MODEL_PARALLEL"


class DynamoBackend(BaseEnum):
    """reference ``DynamoBackend:684``. On TPU there is exactly one compiler —
    XLA via jit, on by default — so these values only steer :class:`JitConfig`:
    ``EAGER`` disables jit (debugging), everything else keeps it on."""

    NO = "NO"
    EAGER = "EAGER"
    AOT_EAGER = "AOT_EAGER"
    INDUCTOR = "INDUCTOR"
    AOT_TS_NVFUSER = "AOT_TS_NVFUSER"
    NVPRIMS_NVFUSER = "NVPRIMS_NVFUSER"
    CUDAGRAPHS = "CUDAGRAPHS"
    OFI = "OFI"
    FX2TRT = "FX2TRT"
    ONNXRT = "ONNXRT"
    TENSORRT = "TENSORRT"
    IPEX = "IPEX"
    TVM = "TVM"


@dataclass
class TorchDynamoPlugin(KwargsHandler):
    """Migration shim for reference ``TorchDynamoPlugin:1024``. XLA compilation
    is default-on; the one actionable knob is ``backend=EAGER`` → run eager
    (:class:`JitConfig` ``disable_jit``). ``mode``/``fullgraph``/``dynamic``
    have no XLA meaning (jit always captures the full graph with static
    shapes) and are accepted for config compatibility."""

    backend: Any = DynamoBackend.NO
    mode: str = "default"
    fullgraph: bool = False
    dynamic: Optional[bool] = None
    options: Optional[dict] = None
    disable: bool = False

    def to_jit_config(self) -> JitConfig:
        backend = str(self.backend).rsplit(".", 1)[-1].upper()
        return JitConfig(disable_jit=(backend == "EAGER"))


@dataclass
class TorchContextParallelConfig(KwargsHandler):
    """Migration shim for reference ``TorchContextParallelConfig:2186``:
    ``cp_comm_strategy`` maps onto the native ``cp_rotate_method`` —
    ``allgather`` → allgather rotation, ``alltoall`` → the zig-zag
    load-balanced ring (the rotation-style strategy here)."""

    cp_comm_strategy: Optional[str] = None

    def __post_init__(self):
        if self.cp_comm_strategy is None:
            self.cp_comm_strategy = os.environ.get(
                "PARALLELISM_CONFIG_CP_COMM_STRATEGY", "allgather"
            )
        if self.cp_comm_strategy not in ("allgather", "alltoall"):
            raise ValueError(
                f"cp_comm_strategy must be 'allgather' or 'alltoall', got "
                f"{self.cp_comm_strategy!r}"
            )

    @property
    def cp_rotate_method(self) -> str:
        return "allgather" if self.cp_comm_strategy == "allgather" else "zigzag"


@dataclass
class TorchTensorParallelConfig(KwargsHandler):
    """Migration shim for reference ``TorchTensorParallelConfig:2264``.
    ``enable_async_tp`` is accepted and ignored with the same warning the
    reference emits — XLA already overlaps TP collectives with compute."""

    enable_async_tp: bool = False

    def __post_init__(self):
        if self.enable_async_tp:
            import warnings

            warnings.warn(
                "async tensor parallelism is not a knob under XLA (collective "
                "overlap is compiler-scheduled); ignoring enable_async_tp",
                stacklevel=2,
            )


@dataclass
class TorchTensorParallelPlugin(KwargsHandler):
    """Migration shim: reference TP plugin → ``tp`` mesh axis size."""

    tp_size: int = 1
    torch_device_mesh: Any = None  # accepted for signature parity

    def to_parallelism_config(self):
        from ..parallelism_config import ParallelismConfig

        return ParallelismConfig(tp_size=self.tp_size, dp_shard_size=-1)


@dataclass
class DeepSpeedSequenceParallelConfig(KwargsHandler):
    """Migration shim for reference ``DeepSpeedSequenceParallelConfig:2214``
    (Ulysses/ALST). Sequence-length knobs are accepted (our Ulysses works at
    any length divisible by ``sp``); ``sp_attn_implementation`` maps onto the
    native ``attention_impl``."""

    sp_seq_length: Optional[int] = None
    sp_seq_length_is_variable: Optional[bool] = None
    sp_attn_implementation: Optional[str] = None

    def __post_init__(self):
        if self.sp_seq_length_is_variable is None:
            self.sp_seq_length_is_variable = (
                os.environ.get("PARALLELISM_CONFIG_SP_SEQ_LENGTH_IS_VARIABLE", "true").lower()
                == "true"
            )
        if self.sp_attn_implementation is None:
            self.sp_attn_implementation = os.environ.get(
                "PARALLELISM_CONFIG_SP_ATTN_IMPLEMENTATION", None
            )
        if self.sp_attn_implementation is not None and self.sp_attn_implementation not in (
            "flash_attention_2", "flash_attention_3", "sdpa"
        ):
            raise ValueError(
                f"invalid sp_attn_implementation {self.sp_attn_implementation!r}"
            )

    @property
    def attention_impl(self) -> str:
        """Native ``attention_impl`` for the model forward."""
        if self.sp_attn_implementation in ("flash_attention_2", "flash_attention_3"):
            return "flash"
        return "xla"


class DummyOptim:
    """Placeholder optimizer (reference ``utils/deepspeed.py`` ``DummyOptim``):
    in the reference the real optimizer comes from the DeepSpeed config; here
    ``Accelerator.prepare`` materializes an optax AdamW from the recorded
    hyperparameters — user scripts written against the reference's
    DummyOptim/prepare flow run unchanged."""

    def __init__(self, params=None, lr: float = 1e-3, weight_decay: float = 0.0, **kwargs):
        self.params = params
        self.lr = lr
        self.weight_decay = weight_decay
        self.kwargs = kwargs

    def to_optax(self, learning_rate=None):
        """Materialize as optax AdamW. ``learning_rate`` (a schedule fn)
        overrides the constant ``lr`` — the paired-DummyScheduler case.
        Recorded betas/eps hyperparameters carry over; other kwargs warn."""
        import optax

        kwargs = dict(self.kwargs)
        b1, b2 = kwargs.pop("betas", (0.9, 0.999))
        eps = kwargs.pop("eps", 1e-8)
        kwargs.pop("params", None)
        if kwargs:
            import warnings

            warnings.warn(
                f"DummyOptim: ignoring unsupported hyperparameters {sorted(kwargs)}",
                stacklevel=2,
            )
        return optax.adamw(
            learning_rate if learning_rate is not None else self.lr,
            b1=b1, b2=b2, eps=eps, weight_decay=self.weight_decay,
        )


class DummyScheduler:
    """Placeholder scheduler (reference ``DummyScheduler``): ``prepare`` turns
    it into a linear warmup→decay optax schedule over ``total_num_steps`` with
    ``warmup_num_steps`` of warmup applied to the paired optimizer's LR."""

    def __init__(self, optimizer=None, total_num_steps: Optional[int] = None,
                 warmup_num_steps: int = 0, lr_scheduler_callable=None, **kwargs):
        self.optimizer = optimizer
        self.total_num_steps = total_num_steps
        self.warmup_num_steps = warmup_num_steps
        self.lr_scheduler_callable = lr_scheduler_callable
        self.kwargs = kwargs


def add_model_config_to_megatron_parser(*args, **kwargs):  # pragma: no cover
    raise NotImplementedError(
        "Megatron-LM is a CUDA engine; its TP/PP/EP capabilities are provided natively "
        "via ParallelismConfig mesh axes on TPU."
    )


# --------------------------------------------------- fp8 recipe kwargs shims --
@dataclass
class FP8RecipeKwargs(KwargsHandler):
    """Migration shim for reference ``FP8RecipeKwargs`` (``utils/dataclasses.py:455``,
    deprecated there in favor of backend-specific kwargs). Every backend maps to
    the ONE native fp8 path: XLA fp8 ``dot_general`` with delayed scaling
    (``ops/fp8.py``); :meth:`to_native` yields that recipe."""

    backend: Optional[str] = None
    margin: int = 0
    interval: int = 1  # accepted: native scaling re-derives per step
    fp8_format: str = "HYBRID"
    amax_history_len: int = 16
    amax_compute_algo: str = "max"
    override_linear_precision: Any = None  # TE triple; see filter_first_and_last_linear_layers
    use_autocast_during_eval: bool = False

    def __post_init__(self):
        if self.backend is not None:
            self.backend = str(self.backend).upper()
            if self.backend not in ("TE", "MSAMP", "AO"):
                raise ValueError(f"unknown fp8 backend {self.backend!r}")
        self.fp8_format = str(self.fp8_format).upper()
        if self.fp8_format not in ("HYBRID", "E4M3"):
            # same validation as the native FP8Recipe this builds — silently
            # coercing would mask exactly the misconfigurations it rejects
            raise ValueError(
                f"unknown fp8_format {self.fp8_format!r} (valid: HYBRID, E4M3)"
            )

    def to_native(self):
        from ..ops.fp8 import FP8Recipe

        return FP8Recipe(
            margin=self.margin,
            amax_history_len=self.amax_history_len,
            amax_compute_algo=self.amax_compute_algo,
            fp8_format=self.fp8_format,
        )


@dataclass
class TERecipeKwargs(FP8RecipeKwargs):
    """TransformerEngine recipe spelling (reference ``utils/dataclasses.py:359``)."""

    def __post_init__(self):
        self.backend = "TE"
        super().__post_init__()


@dataclass
class AORecipeKwargs(FP8RecipeKwargs):
    """torchao Float8 recipe spelling (reference ``utils/dataclasses.py:311``).
    ``config``/``module_filter_func`` accepted for signature parity."""

    config: Any = None
    module_filter_func: Any = None

    def __post_init__(self):
        self.backend = "AO"
        super().__post_init__()


@dataclass
class MSAMPRecipeKwargs(FP8RecipeKwargs):
    """MS-AMP recipe spelling (reference ``utils/dataclasses.py:438``).
    ``opt_level`` accepted: optimizer-state precision is governed natively by
    the optax transform chain."""

    opt_level: str = "O2"

    def __post_init__(self):
        self.backend = "MSAMP"
        super().__post_init__()


# ------------------------------------------------------- Megatron-LM shim ----
@dataclass
class MegatronLMPlugin(KwargsHandler):
    """Migration shim for reference ``MegatronLMPlugin`` (``utils/dataclasses.py:2286``).

    The Megatron ENGINE (CUDA kernels, fused softmax, its own runtime) is not
    ported — its capabilities are native here: TP/PP/EP/SP are mesh axes and
    GSPMD shardings. This shim maps the plugin's parallelism degrees onto
    :class:`~accelerate_tpu.parallelism_config.ParallelismConfig` so a script
    that passes ``megatron_lm_plugin=MegatronLMPlugin(tp_degree=2, ...)``
    configures the same mesh. Engine-tuning knobs (fused kernels, selective
    recompute spellings) are accepted and ignored; XLA owns those decisions.
    """

    tp_degree: int = 1
    pp_degree: int = 1
    num_micro_batches: int = 1
    expert_model_parallel_size: int = 1
    context_parallel_size: int = 1
    sequence_parallelism: bool = False
    gradient_clipping: Optional[float] = None
    use_distributed_optimizer: bool = False  # ZeRO-style: opt state sharded anyway
    recompute_activations: bool = False
    other_megatron_args: Optional[dict] = None

    @property
    def remat(self) -> "bool | str":
        return "dots_no_batch" if self.recompute_activations else False

    def to_parallelism_config(self):
        from ..parallelism_config import ParallelismConfig

        # NOTE: Megatron's sequence_parallelism is a FLAG on the tp group
        # (norm/dropout activations sharded along the existing tp axis, no
        # extra devices) — NOT a Ulysses sp mesh axis. Under GSPMD the
        # activation sharding it buys is compiler-inserted from the tp param
        # specs, so the flag maps to nothing; mapping it to sp_size would
        # demand tp*2 devices and build a different topology than asked for.
        return ParallelismConfig(
            tp_size=self.tp_degree,
            pp_size=self.pp_degree,
            ep_size=self.expert_model_parallel_size,
            cp_size=self.context_parallel_size,
            dp_shard_size=-1,
        )


# ------------------------------------------------ DeepSpeed-surface spellings --
class HfDeepSpeedConfig:
    """Thin holder for a ds_config dict/file (reference ``utils/deepspeed.py``
    ``HfDeepSpeedConfig``): dotted-path access + stage probes. The values feed
    :class:`DeepSpeedPlugin`'s config-file mapping; there is no engine to hand
    the dict to."""

    def __init__(self, config_file_or_dict):
        import json as _json

        if isinstance(config_file_or_dict, dict):
            self.config = dict(config_file_or_dict)
        else:
            with open(config_file_or_dict) as f:
                self.config = _json.load(f)

    def get_value(self, ds_key_long: str, default=None):
        node = self.config
        for part in ds_key_long.split("."):
            if not isinstance(node, dict) or part not in node:
                return default
            node = node[part]
        return node

    def is_true(self, ds_key_long: str) -> bool:
        return bool(self.get_value(ds_key_long))

    def is_false(self, ds_key_long: str) -> bool:
        value = self.get_value(ds_key_long)
        return value is not None and not bool(value)

    def is_zero2(self) -> bool:
        return self.get_value("zero_optimization.stage") == 2

    def is_zero3(self) -> bool:
        return self.get_value("zero_optimization.stage") == 3

    def is_offload(self) -> bool:
        for key in ("offload_optimizer", "offload_param"):
            device = self.get_value(f"zero_optimization.{key}.device")
            if device not in (None, "none"):
                return True
        return False


def get_active_deepspeed_plugin(state_or_accelerator):
    """The active :class:`DeepSpeedPlugin` (reference ``utils/deepspeed.py``
    spelling). Accepts an ``Accelerator`` or anything exposing
    ``deepspeed_plugin``; raises when no plugin is configured."""
    plugin = getattr(state_or_accelerator, "deepspeed_plugin", None)
    if isinstance(plugin, dict):  # reference multi-plugin dict: the selected one
        for p in plugin.values():
            if getattr(p, "selected", False):
                return p
        raise ValueError("no DeepSpeedPlugin in the dict is selected")
    if plugin is None:
        raise ValueError(
            "no DeepSpeedPlugin is active; pass deepspeed_plugin= to Accelerator"
        )
    return plugin


def deepspeed_required(func):
    """Decorator: the wrapped method requires an active DeepSpeedPlugin
    (reference ``utils/deepspeed.py`` spelling)."""
    import functools as _functools

    @_functools.wraps(func)
    def wrapper(self, *args, **kwargs):
        get_active_deepspeed_plugin(self)  # raises with the actionable message
        return func(self, *args, **kwargs)

    return wrapper


# --------------------------------------------- fsdp ram-efficient toggles ----
def enable_fsdp_ram_efficient_loading() -> None:
    """Set the env flag that makes :class:`FullyShardedDataParallelPlugin`
    default to cpu-ram-efficient loading (reference ``utils/fsdp_utils.py``
    spelling; the native mechanism is abstract init via ``jax.eval_shape`` +
    per-shard reads in ``sharded_checkpoint``)."""
    os.environ["FSDP_CPU_RAM_EFFICIENT_LOADING"] = "true"


def disable_fsdp_ram_efficient_loading() -> None:
    os.environ["FSDP_CPU_RAM_EFFICIENT_LOADING"] = "false"
