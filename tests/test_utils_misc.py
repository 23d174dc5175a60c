"""Unit tests for the small utility surfaces the reference covers in
``tests/test_utils.py`` / ``test_imports.py`` / ``test_logging.py``:
environment parsing, env patching, capability probes, the rank-aware logging
adapter, the main-process-only tqdm/rich helpers, the public-API export
contracts, ``write_basic_config``, and the notebook/debug launchers."""

import logging
import os

import pytest

from accelerate_tpu.logging import MultiProcessAdapter, get_logger
from accelerate_tpu.utils import environment as env
from accelerate_tpu.utils import imports


class TestEnvironment:
    def test_str_to_bool(self):
        for s in ("1", "true", "True", "YES", "on"):
            assert env.str_to_bool(s) == 1
        for s in ("0", "false", "OFF", "no"):
            assert env.str_to_bool(s) == 0
        with pytest.raises(ValueError):
            env.str_to_bool("maybe")

    def test_parse_flag_from_env(self):
        with env.patch_environment(MY_FLAG="true"):
            assert env.parse_flag_from_env("MY_FLAG") is True
        with env.patch_environment(MY_FLAG="0"):
            assert env.parse_flag_from_env("MY_FLAG", default=True) is False
        assert env.parse_flag_from_env("MY_FLAG_UNSET", default=True) is True

    def test_parse_choice_and_int(self):
        with env.patch_environment(MP="bf16", N1="4"):
            assert env.parse_choice_from_env("MP") == "bf16"
            assert env.get_int_from_env(("N0", "N1"), 7) == 4
        assert env.get_int_from_env(("N0", "N1"), 7) == 7

    def test_patch_environment_restores_and_deletes(self):
        os.environ["KEEP_ME"] = "original"
        with env.patch_environment(KEEP_ME="patched", ADDED="x"):
            assert os.environ["KEEP_ME"] == "patched"
            assert os.environ["ADDED"] == "x"
        assert os.environ["KEEP_ME"] == "original"
        assert "ADDED" not in os.environ
        del os.environ["KEEP_ME"]

    def test_patch_environment_restores_on_exception(self):
        with pytest.raises(RuntimeError):
            with env.patch_environment(BOOM_VAR="1"):
                raise RuntimeError
        assert "BOOM_VAR" not in os.environ

    def test_are_libraries_initialized(self):
        assert "numpy" in env.are_libraries_initialized("numpy", "not_a_real_lib_xyz")


class TestImports:
    def test_probes_return_bool(self):
        for name in dir(imports):
            if name.startswith("is_") and name.endswith("_available"):
                assert isinstance(getattr(imports, name)(), bool), name

    def test_known_available(self):
        # baked into the environment (see repo instructions)
        assert imports.is_optax_available()
        assert imports.is_torch_available()
        assert imports.is_safetensors_available()

    def test_no_duplicate_probe_definitions(self):
        """A probe defined twice silently shadows the first: keep the module
        free of copy-paste duplicates."""
        import ast
        import inspect

        tree = ast.parse(inspect.getsource(imports))
        names = [n.name for n in tree.body if isinstance(n, ast.FunctionDef)]
        assert len(names) == len(set(names)), sorted(
            n for n in names if names.count(n) > 1
        )


class TestLogging:
    def test_main_process_logs(self, caplog):
        logger = get_logger("t_log_main")
        with caplog.at_level(logging.INFO, logger="t_log_main"):
            logger.info("hello %s", "world")
        assert "hello world" in caplog.text

    def test_level_from_env(self):
        with env.patch_environment(ACCELERATE_LOG_LEVEL="ERROR"):
            logger = get_logger("t_log_env")
            assert logger.logger.level == logging.ERROR

    def test_warning_once_dedupes(self, caplog):
        logger = get_logger("t_log_once")
        with caplog.at_level(logging.WARNING, logger="t_log_once"):
            logger.warning_once("repeat me")
            logger.warning_once("repeat me")
            logger.warning_once("another")
        assert caplog.text.count("repeat me") == 1
        assert caplog.text.count("another") == 1

    def test_in_order_single_process(self, caplog):
        logger = get_logger("t_log_order")
        with caplog.at_level(logging.INFO, logger="t_log_order"):
            logger.info("ordered", in_order=True, main_process_only=False)
        assert "ordered" in caplog.text

    def test_adapter_type(self):
        assert isinstance(get_logger("t_log_type"), MultiProcessAdapter)


class TestPublicAPI:
    def test_reference_top_level_names_resolve(self):
        """The reference's own top-level exports (its ``__init__.py``) must all
        exist here — incl. ``prepare_pippy``, aliased to the native
        ``prepare_pipeline`` (trainable, unlike PiPPy); the exhaustive sweep
        lives in test_api_parity.py."""
        import accelerate_tpu as at

        for name in ("Accelerator", "PartialState", "ParallelismConfig",
                     "notebook_launcher", "debug_launcher", "skip_first_batches",
                     "prepare_pippy"):
            assert getattr(at, name) is not None, name

    def test_all_exports_resolve(self):
        import accelerate_tpu as at

        for name in at.__all__:
            assert getattr(at, name) is not None, name

    def test_utils_namespace_parity(self):
        """Reference users spell `from accelerate.utils import gather,
        set_seed, send_to_device, ...` — the same names must resolve from
        accelerate_tpu.utils (lazily, to dodge the state import cycle)."""
        from accelerate_tpu import utils

        for name in sorted(utils._OPERATIONS | utils._RANDOM) + [
            "DistributedType", "ProjectConfiguration", "patch_environment", "str_to_bool",
        ]:
            assert getattr(utils, name) is not None, name
        # every __all__ entry must resolve (star-import contract) and be
        # visible to dir() (tab completion)
        for name in utils.__all__:
            assert getattr(utils, name) is not None, name
        assert set(utils.__all__) <= set(dir(utils))
        with pytest.raises(AttributeError):
            utils.not_a_real_name


class TestLaunchers:
    def test_debug_launcher_runs_on_virtual_mesh(self):
        import jax

        from accelerate_tpu import debug_launcher

        def fn(mult):
            assert os.environ.get("ACCELERATE_USE_CPU") == "yes"
            return len(jax.devices()) * mult

        # conftest already forced the 8-device CPU mesh; the launcher must run
        # the function under the accelerate env and hand back its return
        assert debug_launcher(fn, args=(2,)) == 16
        assert "ACCELERATE_USE_CPU" not in os.environ  # env restored

    def test_notebook_launcher_single_host(self):
        from accelerate_tpu import notebook_launcher

        def fn(x):
            assert os.environ.get("ACCELERATE_MIXED_PRECISION") == "bf16"
            return x + 1

        assert notebook_launcher(fn, args=(41,), mixed_precision="bf16") == 42

    def test_notebook_launcher_multinode_needs_master_addr(self):
        from accelerate_tpu import notebook_launcher

        with pytest.raises(ValueError):
            notebook_launcher(lambda: None, num_nodes=2)

    def test_notebook_launcher_multinode_sets_coordinator_env(self):
        from accelerate_tpu import notebook_launcher

        def fn():
            return (
                os.environ["ACCELERATE_COORDINATOR_ADDRESS"],
                os.environ["ACCELERATE_NUM_PROCESSES"],
                os.environ["ACCELERATE_PROCESS_ID"],
            )

        addr, n, rank = notebook_launcher(
            fn, master_addr="10.0.0.1", use_port="9999", num_nodes=2, node_rank=1
        )
        assert addr == "10.0.0.1:9999"
        assert (n, rank) == ("2", "1")
        assert "ACCELERATE_COORDINATOR_ADDRESS" not in os.environ


class TestWriteBasicConfig:
    def test_writes_default_and_refuses_clobber(self, tmp_path, capsys):
        from accelerate_tpu.commands.config import ClusterConfig
        from accelerate_tpu.utils import write_basic_config

        path = str(tmp_path / "cfg.yaml")
        out = write_basic_config("fp16", path)
        assert out == path
        cfg = ClusterConfig.load(path)
        assert cfg.mixed_precision == "fp16"
        assert write_basic_config("bf16", path) is False  # no clobber
        assert ClusterConfig.load(path).mixed_precision == "fp16"

    def test_rejects_unknown_precision(self, tmp_path):
        from accelerate_tpu.utils import write_basic_config

        with pytest.raises(ValueError):
            write_basic_config("tf32", str(tmp_path / "x.yaml"))

    def test_uppercase_precision_accepted(self, tmp_path):
        """Reference parity: accelerate lowercases before validating."""
        from accelerate_tpu.commands.config import ClusterConfig
        from accelerate_tpu.utils import write_basic_config

        path = str(tmp_path / "u.yaml")
        assert write_basic_config("BF16", path) == path
        assert ClusterConfig.load(path).mixed_precision == "bf16"


class TestRich:
    def test_console_singleton_and_print(self, capsys):
        pytest.importorskip("rich")
        from accelerate_tpu.utils.rich import get_console, rich_print

        assert get_console() is get_console()
        rich_print("hello rich")
        assert "hello rich" in capsys.readouterr().out

    def test_print_gates_on_main_process(self, capsys):
        pytest.importorskip("rich")
        from unittest.mock import PropertyMock, patch

        from accelerate_tpu.state import PartialState
        from accelerate_tpu.utils.rich import rich_print

        with patch.object(type(PartialState()), "is_main_process",
                          new_callable=PropertyMock, return_value=False):
            rich_print("suppressed")  # non-main + default main_process_only
            rich_print("forced", main_process_only=False)
        out = capsys.readouterr().out
        assert "suppressed" not in out
        assert "forced" in out


class TestTqdm:
    def test_main_process_enabled(self):
        from accelerate_tpu.utils.tqdm import tqdm

        bar = tqdm(range(3), main_process_only=True)
        # single process IS the main process: bar must not be disabled
        assert not bar.disable
        assert sum(1 for _ in bar) == 3

    def test_kwargs_passthrough(self):
        from accelerate_tpu.utils.tqdm import tqdm

        bar = tqdm(range(2), disable=True)
        assert bar.disable
        list(bar)


def test_env_compile_cache_dir_wins_over_jit_config(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set JAX's persistent cache lives
    there and no code moves it: JitConfig(persistent_cache_dir=...) only
    places the cache when the environment has not."""
    import jax

    from accelerate_tpu.utils.dataclasses import JitConfig

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "from_env"))
        JitConfig(persistent_cache_dir=str(tmp_path / "from_code")).apply()
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        JitConfig(persistent_cache_dir=str(tmp_path / "from_code")).apply()
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "from_code")
        assert JitConfig().persistent_cache_dir is None  # no env name of our own
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_measurement_scripts_share_one_fixed_cache_dir(monkeypatch, tmp_path):
    """benchmarks/_common.enable_jax_cache: the environment's directory where
    it names one, else on a chip the fixed in-checkout path — never a temp
    dir — and on the CPU no cache at all (entries of an earlier test process
    made the serving benchmark's compile counts depend on what ran before)."""
    import os
    import sys

    import jax

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from benchmarks._common import enable_jax_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_jax_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before  # untouched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_jax_cache() is None  # the tests run on the CPU
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert enable_jax_cache() == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(repo, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
