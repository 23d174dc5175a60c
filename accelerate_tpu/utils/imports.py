"""Capability probes: which optional libraries / hardware are available.

TPU-native counterpart of the reference's ``utils/imports.py``
(``/root/reference/src/accelerate/utils/imports.py:62-426`` — ~50 ``is_*_available``
probes). Here the compute stack is always JAX; probes cover optional integrations
(trackers, orbax, flax, torch-interop) and the accelerator platform itself.
"""

from __future__ import annotations

import functools
import importlib.util


@functools.lru_cache(maxsize=None)
def _package_available(name: str) -> bool:
    return importlib.util.find_spec(name) is not None


def is_flax_available() -> bool:
    return _package_available("flax")


def is_optax_available() -> bool:
    return _package_available("optax")


def is_orbax_available() -> bool:
    return _package_available("orbax")


def is_chex_available() -> bool:
    return _package_available("chex")


def is_torch_available() -> bool:
    return _package_available("torch")


def is_transformers_available() -> bool:
    return _package_available("transformers")


def is_datasets_available() -> bool:
    return _package_available("datasets")


def is_safetensors_available() -> bool:
    return _package_available("safetensors")


def is_tensorboard_available() -> bool:
    return _package_available("tensorboard") or _package_available("tensorboardX")


def is_comet_ml_available() -> bool:
    return _package_available("comet_ml")


def is_aim_available() -> bool:
    return _package_available("aim")


def is_clearml_available() -> bool:
    return _package_available("clearml")


def is_dvclive_available() -> bool:
    return _package_available("dvclive")


def is_swanlab_available() -> bool:
    return _package_available("swanlab")


def is_trackio_available() -> bool:
    return _package_available("trackio")


def is_wandb_available() -> bool:
    return _package_available("wandb")


def is_mlflow_available() -> bool:
    return _package_available("mlflow")


def is_rich_available() -> bool:
    return _package_available("rich")


def is_tqdm_available() -> bool:
    return _package_available("tqdm")


def is_pandas_available() -> bool:
    return _package_available("pandas")


def is_pytest_available() -> bool:
    return _package_available("pytest")


@functools.lru_cache(maxsize=None)
def is_tpu_available() -> bool:
    """True when the default JAX backend is a TPU."""
    import jax

    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


@functools.lru_cache(maxsize=None)
def is_gpu_available() -> bool:
    import jax

    try:
        return jax.default_backend() == "gpu"
    except Exception:
        return False


def is_cpu_only() -> bool:
    import jax

    return jax.default_backend() == "cpu"


def is_pallas_available() -> bool:
    """Pallas ships with jax; TPU lowering needs a TPU backend, CPU uses interpret mode."""
    return _package_available("jax")


def is_multihost() -> bool:
    import jax

    return jax.process_count() > 1


# --------------------------------------------------------------------------
# Reference-spelling probes migrated user code calls
# (reference utils/imports.py:62-426). Answers reflect THIS stack honestly:
# precision probes describe what the jitted step supports; torch-engine
# probes are plain package probes and stay False in a TPU image.


def is_bf16_available(ignore_tpu: bool = False) -> bool:
    """bf16 is the native TPU matmul dtype; supported everywhere here
    (reference checks CUDA capability; its ``ignore_tpu`` flag is accepted
    for signature parity)."""
    return True


def is_fp16_available() -> bool:
    """fp16 compute with in-graph dynamic loss scaling is always available."""
    return True


@functools.lru_cache(maxsize=None)
def is_fp8_available() -> bool:
    """True when jax exposes float8 dtypes (XLA fp8 dot support)."""
    try:
        import jax.numpy as jnp

        return hasattr(jnp, "float8_e4m3fn")
    except Exception:
        return False


def is_cuda_available() -> bool:
    return is_gpu_available()


def is_mps_available(min_version: str | None = None) -> bool:
    return False


def is_peft_available() -> bool:
    return _package_available("peft")


def is_timm_available() -> bool:
    return _package_available("timm")


def is_torchvision_available() -> bool:
    return _package_available("torchvision")


def is_matplotlib_available() -> bool:
    return _package_available("matplotlib")


def is_deepspeed_available() -> bool:
    """Plain package probe; ZeRO capabilities are provided natively via
    sharding (DeepSpeedPlugin shim), so this is False in a TPU image."""
    return _package_available("deepspeed")


def is_megatron_lm_available() -> bool:
    return _package_available("megatron")


def is_bnb_available() -> bool:
    """bitsandbytes (CUDA); int8/NF4 quantization is native here
    (``ops/quantization.py``)."""
    return _package_available("bitsandbytes")


def is_torch_xla_available(check_is_tpu: bool = False, check_is_gpu: bool = False) -> bool:
    """The reference gates its TPU path on torch_xla; this framework IS the
    TPU path, so the probe only reports whether the package exists for
    interop purposes."""
    return _package_available("torch_xla")


# -- remaining reference probe spellings (utils/imports.py:62-426): plain
# package probes so reference-written capability gates evaluate honestly on a
# TPU image (most are CUDA/torch-ecosystem packages and report False here)
def is_boto3_available() -> bool:
    return _package_available("boto3")


def is_sagemaker_available() -> bool:
    return _package_available("sagemaker")


def is_triton_available() -> bool:
    return _package_available("triton")


def is_schedulefree_available() -> bool:
    return _package_available("schedulefree")


def is_lomo_available() -> bool:
    """LOMO's fused update is native here (``Accelerator.lomo_backward``);
    the probe reports the torch package for interop parity."""
    return _package_available("lomo_optim")


def is_pynvml_available() -> bool:
    return _package_available("pynvml")


def is_import_timer_available() -> bool:
    return _package_available("import_timer")


def is_torchdata_available() -> bool:
    return _package_available("torchdata")


def is_torchdata_stateful_dataloader_available() -> bool:
    if not _package_available("torchdata"):
        return False
    try:
        from torchdata.stateful_dataloader import StatefulDataLoader  # noqa: F401

        return True
    except ImportError:
        return False


def is_pippy_available() -> bool:
    """Pipeline parallelism is native (``parallel/pipeline.py``, trainable);
    reference gates on torch.distributed.pipelining instead."""
    try:
        import torch.distributed.pipelining  # noqa: F401

        return True
    except ImportError:
        return False


def is_xccl_available() -> bool:
    try:
        import torch

        return hasattr(torch.distributed, "is_xccl_available") and torch.distributed.is_xccl_available()
    except ImportError:
        return False


def is_weights_only_available() -> bool:
    """torch.load(weights_only=...) support probe (reference gates torch>=2.4)."""
    try:
        import torch

        from .versions import compare_versions

        return compare_versions(torch.__version__, ">=", "2.4.0")
    except ImportError:
        return False


# -- device-vendor probes (reference utils/imports.py:62-426): each reports
# whether that accelerator stack is importable. On a TPU image none are, so
# reference-written gates like ``if is_xpu_available(): ...`` fall through
# honestly rather than raising ImportError at the import site.
def is_xpu_available(check_device: bool = False) -> bool:
    return _package_available("intel_extension_for_pytorch")


def is_npu_available(check_device: bool = False) -> bool:
    return _package_available("torch_npu")


def is_mlu_available(check_device: bool = False) -> bool:
    return _package_available("torch_mlu")


def is_musa_available(check_device: bool = False) -> bool:
    return _package_available("torch_musa")


def is_sdaa_available(check_device: bool = False) -> bool:
    return _package_available("torch_sdaa")


def is_hpu_available(init_hccl: bool = False) -> bool:
    return _package_available("habana_frameworks")


def is_habana_gaudi1() -> bool:
    """Gaudi1 detection requires the habana stack; absent it, not Gaudi1."""
    return False


# -- quantization/fp8 engine probes: the capabilities exist natively
# (``ops/quantization.py`` int8/NF4 kernels, ``ops/fp8.py`` delayed-scaling
# fp8 dot); these report whether the CUDA engines the reference delegates to
# are importable, for scripts that branch on the engine rather than the
# capability.
def is_4bit_bnb_available() -> bool:
    return is_bnb_available()


def is_8bit_bnb_available() -> bool:
    return is_bnb_available()


def is_bitsandbytes_multi_backend_available() -> bool:
    return is_bnb_available()


def is_torchao_available() -> bool:
    return _package_available("torchao")


def is_msamp_available() -> bool:
    return _package_available("msamp")


def is_transformer_engine_available() -> bool:
    return _package_available("transformer_engine")


def is_transformer_engine_mxfp8_available() -> bool:
    """MXFP8 needs TE + Blackwell-class hardware; without TE it is False."""
    return False


def is_peft_model(model) -> bool:
    """True iff ``model`` is a PEFT-wrapped torch model (reference
    ``utils/other.py`` spelling). Works through our torch bridge: unwraps
    ``BridgedModule`` to the underlying torch module first."""
    inner = getattr(model, "torch_module", model)
    if not is_peft_available():
        return False
    try:
        from peft import PeftModel  # type: ignore

        return isinstance(inner, PeftModel)
    except Exception:
        return False


def model_has_dtensor(model) -> bool:
    """torch DTensor probe (reference ``utils/modeling.py``). Sharding here is
    GSPMD ``jax.Array`` — a torch model routed through the bridge never holds
    DTensors, and a plain torch model is checked directly."""
    try:
        from torch.distributed.tensor import DTensor  # type: ignore
    except Exception:
        return False
    params = getattr(model, "parameters", None)
    if params is None:
        return False
    return any(isinstance(p, DTensor) for p in model.parameters())


def torchao_required(func):
    """Decorator guard (reference ``utils/ao.py``): the wrapped function needs
    the torchao CUDA engine, which has no TPU meaning — the native fp8 path is
    ``ops/fp8.py``. Raises with that pointer when called without torchao."""
    import functools as _functools

    @_functools.wraps(func)
    def wrapper(*args, **kwargs):
        if not is_torchao_available():
            raise ImportError(
                f"{func.__name__} requires torchao (CUDA fp8 engine). On TPU use "
                "the native fp8 path: ops/fp8.py (fp8_dot / make_fp8_optimizer) "
                "with FP8RecipeKwargs/AORecipeKwargs."
            )
        return func(*args, **kwargs)

    return wrapper
