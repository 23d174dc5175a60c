"""Process/device runtime state singletons.

TPU-native counterpart of the reference's ``state.py``:

- :class:`PartialState` — reference ``state.py:122``: process bootstrap (here
  ``jax.distributed.initialize`` instead of ``torch.distributed.init_process_group``
  ``state.py:243``), rank/world/device info, process-control helpers
  (``wait_for_everyone :376``, ``split_between_processes :424``,
  ``main_process_first :515``, decorators ``:556-712``).
- :class:`AcceleratorState` — reference ``state.py:863``: adds mixed precision and
  parallelism routing; here it owns the device :class:`jax.sharding.Mesh`.
- :class:`GradientState` — reference ``state.py:1225``: gradient-accumulation
  bookkeeping shared between Accelerator, dataloaders, optimizer and scheduler.

All three use the shared-``__dict__`` singleton trick (reference ``state.py:90-119``)
so every instance in the process observes the same state.
"""

from __future__ import annotations

import os
import time
from contextlib import contextmanager
from functools import wraps
from typing import Any, Callable, Optional

from .parallelism_config import ParallelismConfig
from .utils.dataclasses import (
    DistributedType,
    GradientAccumulationPlugin,
    MixedPrecisionPolicy,
    PrecisionType,
)
from .utils.environment import parse_flag_from_env


def _jax():
    import jax

    return jax


def do_nothing(*args, **kwargs):
    """reference ``state.py:86`` — the default no-op callback."""
    return None


def is_initialized() -> bool:
    return PartialState._shared_state.get("_initialized", False)


def _forensic_env_int(key: str, default: int) -> int:
    # a malformed launcher env (set-but-blank template var) must not crash the
    # crash handler itself — identity degrades to the default, never raises
    try:
        return int(os.environ.get(key, "") or default)
    except (TypeError, ValueError):
        return default


def process_identity() -> "dict[str, Any]":
    """Rank/host identity for forensic artifacts (flight records, watchdog
    dumps). Safe to call from signal handlers and background threads: when
    :class:`PartialState` is not yet initialized it answers from the launcher
    env protocol instead of booting ``jax.distributed`` (which could itself
    hang — the exact failure being diagnosed)."""
    import socket

    ident: dict[str, Any] = {"pid": os.getpid()}
    try:
        ident["hostname"] = socket.gethostname()
    except OSError:
        ident["hostname"] = "?"
    if is_initialized():
        state = PartialState()
        ident.update(
            process_index=state.process_index,
            num_processes=state.num_processes,
            local_process_index=state.local_process_index,
            backend=state.backend,
            run_id=state.run_id,
        )
        return ident
    ident.update(
        process_index=_forensic_env_int("ACCELERATE_PROCESS_ID", 0),
        num_processes=_forensic_env_int("ACCELERATE_NUM_PROCESSES", 1),
        local_process_index=_forensic_env_int("ACCELERATE_LOCAL_PROCESS_INDEX", 0),
        run_id=os.environ.get("ACCELERATE_RUN_ID"),
    )
    return ident


class PartialState:
    """Singleton holding process topology: how many processes, which one am I,
    which devices are mine. First construction performs multi-host initialization
    when the launcher's env protocol requests it."""

    _shared_state: dict[str, Any] = {}

    def __init__(self, cpu: bool = False, **kwargs: Any):
        self.__dict__ = self._shared_state
        if self.initialized:
            return
        jax = _jax()

        if cpu or parse_flag_from_env("ACCELERATE_USE_CPU"):
            jax.config.update("jax_platforms", "cpu")

        # Multi-host bootstrap — the launcher writes ACCELERATE_COORDINATOR_ADDRESS /
        # ACCELERATE_NUM_PROCESSES / ACCELERATE_PROCESS_ID (moral twin of
        # MASTER_ADDR/RANK/WORLD_SIZE, reference utils/launch.py:98-196).
        coordinator = kwargs.pop("coordinator_address", None) or os.environ.get(
            "ACCELERATE_COORDINATOR_ADDRESS"
        )
        if coordinator and not jax.distributed.is_initialized():
            if "cpu" in str(getattr(jax.config, "jax_platforms", "") or ""):
                # CPU-backend multi-process (tests, dev boxes): collectives
                # need an explicit implementation or the backend refuses them
                jax.config.update("jax_cpu_collectives_implementation", "gloo")
            init_kwargs = {}
            if kwargs.get("local_device_ids") is not None:
                init_kwargs["local_device_ids"] = kwargs.pop("local_device_ids")
            if kwargs.get("initialization_timeout") is not None:
                timeout = kwargs.pop("initialization_timeout")
                init_kwargs["initialization_timeout"] = (
                    int(timeout.total_seconds()) if hasattr(timeout, "total_seconds") else int(timeout)
                )
            jax.distributed.initialize(
                coordinator_address=coordinator,
                num_processes=int(
                    kwargs.pop("num_processes", os.environ.get("ACCELERATE_NUM_PROCESSES", 1))
                ),
                process_id=int(
                    kwargs.pop("process_id", os.environ.get("ACCELERATE_PROCESS_ID", 0))
                ),
                **init_kwargs,
            )

        self.num_processes = jax.process_count()
        self.process_index = jax.process_index()
        # One process per host on TPU-VM → every process is its host's local main.
        # (A LOCAL_RANK-style env override is honored for exotic multi-proc-per-host.)
        self.local_process_index = int(os.environ.get("ACCELERATE_LOCAL_PROCESS_INDEX", 0))
        self.devices = jax.devices()
        self.local_devices = jax.local_devices()
        self.num_devices = len(self.devices)
        self.num_local_devices = len(self.local_devices)
        self.device = self.local_devices[0]
        self.backend = jax.default_backend()
        if self.num_processes > 1:
            self.distributed_type = DistributedType.MULTI_HOST
        elif self.num_devices > 1:
            self.distributed_type = DistributedType.SPMD
        else:
            self.distributed_type = DistributedType.NO
        self.debug = parse_flag_from_env("ACCELERATE_DEBUG_MODE")
        # Run identity (telemetry/tracking correlation): the launcher should
        # set ACCELERATE_RUN_ID so all processes of one run agree; without it
        # a process-local id is generated — exact for single-process runs,
        # per-process otherwise.
        self.run_id = os.environ.get("ACCELERATE_RUN_ID") or f"run-{int(time.time())}-{os.getpid()}"
        self.initialized = True

    # ------------------------------------------------------------------ info --
    def __repr__(self) -> str:
        return (
            f"PartialState(backend={self.backend!r}, distributed_type={self.distributed_type}, "
            f"num_processes={self.num_processes}, process_index={self.process_index}, "
            f"num_devices={self.num_devices})"
        )

    @property
    def initialized(self) -> bool:
        return self._shared_state.get("_initialized", False)

    @initialized.setter
    def initialized(self, value: bool) -> None:
        self._shared_state["_initialized"] = value

    @property
    def use_distributed(self) -> bool:
        return self.num_devices > 1 or self.num_processes > 1

    @property
    def is_main_process(self) -> bool:
        return self.process_index == 0

    @property
    def is_local_main_process(self) -> bool:
        return self.local_process_index == 0

    @property
    def is_last_process(self) -> bool:
        return self.process_index == self.num_processes - 1

    # -------------------------------------------------------------- control --
    def wait_for_everyone(self, tag: str = "accelerate_tpu.wait_for_everyone") -> None:
        """Cross-host barrier (reference ``state.py:376``). Under a single process
        this is a no-op; across hosts it syncs via a tiny global collective."""
        if self.num_processes > 1:
            from jax.experimental import multihost_utils

            from .telemetry import flight_recorder as _flight

            _flight.record_collective("barrier", tag)
            multihost_utils.sync_global_devices(tag)

    @contextmanager
    def main_process_first(self):
        """Main process runs the body first, others wait (reference ``state.py:515``)."""
        # sequenced-barrier idiom: every rank enters the "enter" barrier
        # exactly once (non-main before the body, main after), so the
        # schedules match even though each call site is rank-conditional
        if not self.is_main_process:
            self.wait_for_everyone("main_process_first.enter")  # jaxlint: disable=R4
        try:
            yield
        finally:
            if self.is_main_process:
                self.wait_for_everyone("main_process_first.enter")  # jaxlint: disable=R4
            self.wait_for_everyone("main_process_first.exit")

    @contextmanager
    def local_main_process_first(self):
        with self.main_process_first():
            yield

    def on_main_process(self, function: Callable) -> Callable:
        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_main_process:
                return function(*args, **kwargs)
            return None

        return wrapper

    def on_local_main_process(self, function: Callable) -> Callable:
        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_local_main_process:
                return function(*args, **kwargs)
            return None

        return wrapper

    def on_last_process(self, function: Callable) -> Callable:
        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.is_last_process:
                return function(*args, **kwargs)
            return None

        return wrapper

    def on_process(self, function: Callable = None, process_index: int = None) -> Callable:
        if function is None:
            return lambda f: self.on_process(f, process_index)

        @wraps(function)
        def wrapper(*args, **kwargs):
            if self.process_index == process_index:
                return function(*args, **kwargs)
            return None

        return wrapper

    @contextmanager
    def split_between_processes(self, inputs, apply_padding: bool = False):
        """Split a list/tuple/dict/array evenly between processes (reference
        ``state.py:424``). With ``apply_padding`` the last element is repeated so
        every process gets the same count (needed for static shapes)."""
        if self.num_processes == 1:
            yield inputs
            return
        length = len(inputs)
        num = self.num_processes
        base, extra = divmod(length, num)
        if isinstance(inputs, dict):
            results = {}
            for key, value in inputs.items():
                with self.split_between_processes(value, apply_padding) as v:
                    results[key] = v
            yield results
            return
        start = self.process_index * base + min(self.process_index, extra)
        end = start + base + (1 if self.process_index < extra else 0)
        chunk = inputs[start:end]
        if apply_padding and extra != 0:
            target = base + 1
            while len(chunk) < target:
                chunk = list(chunk) + [chunk[-1] if len(chunk) else inputs[-1]]
        yield chunk

    def destroy_process_group(self) -> None:
        jax = _jax()
        if jax.distributed.is_initialized():
            jax.distributed.shutdown()

    @classmethod
    def _reset_state(cls) -> None:
        """Testing hook (reference ``state.py`` ``_reset_state``)."""
        cls._shared_state.clear()

    def print(self, *args, **kwargs) -> None:
        if self.is_main_process:
            print(*args, **kwargs)


class AcceleratorState:
    """Adds precision + parallelism layout (the mesh) on top of PartialState
    (reference ``state.py:863``)."""

    _shared_state: dict[str, Any] = {}

    def __init__(
        self,
        mixed_precision: Optional[str] = None,
        cpu: bool = False,
        parallelism_config: Optional[ParallelismConfig] = None,
        **kwargs: Any,
    ):
        self.__dict__ = self._shared_state
        if self.initialized:
            if parallelism_config is not None and parallelism_config != self.parallelism_config:
                raise ValueError(
                    "AcceleratorState already initialized with a different ParallelismConfig; "
                    "call AcceleratorState._reset_state() first (tests) or construct once."
                )
            if (
                mixed_precision is not None
                and PrecisionType(str(mixed_precision)) != self.mixed_precision
            ):
                raise ValueError(
                    f"AcceleratorState already initialized with mixed_precision="
                    f"{self.mixed_precision}; got conflicting {mixed_precision!r}."
                )
            return
        self._partial = PartialState(cpu=cpu, **kwargs)
        if mixed_precision is None:
            mixed_precision = os.environ.get("ACCELERATE_MIXED_PRECISION", "no")
        self.mixed_precision = PrecisionType(str(mixed_precision))
        self.mixed_precision_policy = MixedPrecisionPolicy.from_precision(self.mixed_precision)
        if parallelism_config is None:
            if any(k.startswith("PARALLELISM_CONFIG_") for k in os.environ):
                parallelism_config = ParallelismConfig.from_env()
            else:
                # default: pure DP over all devices
                parallelism_config = ParallelismConfig(dp_replicate_size=self._partial.num_devices)
        self.parallelism_config = parallelism_config
        self.mesh = parallelism_config.build_mesh(self._partial.devices)
        self.initialized = True

    @property
    def initialized(self) -> bool:
        return self._shared_state.get("_initialized", False)

    @initialized.setter
    def initialized(self, value: bool) -> None:
        self._shared_state["_initialized"] = value

    def __getattr__(self, name: str):
        # delegate topology attrs to PartialState
        partial = self.__dict__.get("_partial")
        if partial is not None and hasattr(partial, name):
            return getattr(partial, name)
        raise AttributeError(f"AcceleratorState has no attribute {name!r}")

    def __repr__(self) -> str:
        return (
            f"AcceleratorState(mixed_precision={self.mixed_precision}, "
            f"mesh={self.parallelism_config.describe(self._partial.num_devices)}, "
            f"{self._partial!r})"
        )

    @classmethod
    def _reset_state(cls, reset_partial_state: bool = False) -> None:
        cls._shared_state.clear()
        if reset_partial_state:
            PartialState._reset_state()


class GradientState:
    """Gradient-accumulation bookkeeping singleton (reference ``state.py:1225``).

    ``sync_gradients`` flags whether the current micro-step is an optimizer-update
    boundary; dataloaders flip ``end_of_dataloader``/``remainder`` so the final
    partial accumulation window still updates (reference ``_set_sync_gradients
    :1318``, ``_add_dataloader :1329``). The XLA ``mark_step`` graph-cut the
    reference performs has no equivalent here: the whole step is one jitted fn.
    """

    _shared_state: dict[str, Any] = {}

    def __init__(self, gradient_accumulation_plugin: Optional[GradientAccumulationPlugin] = None):
        self.__dict__ = self._shared_state
        if not self.initialized:
            self.sync_gradients = True
            self.active_dataloader = None
            self.dataloader_references = []
            self.plugin = gradient_accumulation_plugin or GradientAccumulationPlugin()
            self.num_steps_count = 0
            self.initialized = True
        elif gradient_accumulation_plugin is not None:
            self.plugin = gradient_accumulation_plugin

    @property
    def initialized(self) -> bool:
        return self._shared_state.get("_initialized", False)

    @initialized.setter
    def initialized(self, value: bool) -> None:
        self._shared_state["_initialized"] = value

    @property
    def num_steps(self) -> int:
        return self.plugin.num_steps

    @property
    def adjust_scheduler(self) -> bool:
        return self.plugin.adjust_scheduler

    @property
    def sync_with_dataloader(self) -> bool:
        return self.plugin.sync_with_dataloader

    @property
    def end_of_dataloader(self) -> bool:
        if not self.in_dataloader:
            return False
        return self.active_dataloader.end_of_dataloader

    @property
    def remainder(self) -> int:
        if not self.in_dataloader:
            return -1
        return self.active_dataloader.remainder

    @property
    def in_dataloader(self) -> bool:
        return self.active_dataloader is not None

    def _set_sync_gradients(self, sync: bool) -> None:
        self.sync_gradients = sync

    def _add_dataloader(self, dataloader) -> None:
        self.active_dataloader = dataloader
        self.dataloader_references.append(dataloader)

    def _remove_dataloader(self, dataloader) -> None:
        if dataloader in self.dataloader_references:
            self.dataloader_references.remove(dataloader)
        self.active_dataloader = self.dataloader_references[-1] if self.dataloader_references else None

    def __repr__(self) -> str:
        return (
            f"GradientState(sync_gradients={self.sync_gradients}, num_steps={self.num_steps}, "
            f"end_of_dataloader={self.end_of_dataloader}, remainder={self.remainder})"
        )

    @classmethod
    def _reset_state(cls) -> None:
        cls._shared_state.clear()
