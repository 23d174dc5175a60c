"""What every run of every cell shares: finding a cell's files by name, the
device record, JAX's compile cache, the compile counter, peak memory, the
profiler slice, and the result line.

Nothing here knows a model, a traffic mix or a metric by name: those sit in
files of their own (``configs/``, ``traffic/``, ``workloads/``, ``kinds/``,
``runners/``, ``layer_metrics/``), so a later PR adds files and manifest
entries and edits nothing that is here."""

from __future__ import annotations

import contextlib
import dataclasses
import glob
import importlib
import importlib.util
import json
import os
import shutil
from typing import Any, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(REPO, "BENCHMARK.json")

# the result line has exactly these keys (plus `breakdown` in a traced run), `compared` last
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device", "compared")


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` in ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    spec: dict          # workloads/<cell>.json: runner, parallelism, depth, engine settings
    config: dict        # configs/<config>.json: the published configuration
    traffic: dict       # traffic/<traffic>.json: the mix one general generator reads
    end_to_end: list    # the manifest's end-to-end metric entries this cell reports
    per_layer: list     # the manifest's per-layer metric entries this cell reports
    root: str = HERE    # where its files were found: kinds/ and layer_metrics/ are looked up there

    @property
    def runner(self) -> str:
        return self.spec["runner"]


@dataclasses.dataclass
class Record:
    """What a runner hands back. ``clocks`` holds the runner's host clocks and
    the program's counters, ``trace`` the reduced profiler slice of a traced
    run; the per-layer readers take their numbers from these two, and the
    cell's published keys and engine settings from ``cell``, which
    ``result_line`` puts there. ``compared`` is what decided ``correct``:
    ``{name: [number, limit]}``."""

    correct: bool
    attempted: int
    failed: int
    end_to_end: dict
    clocks: dict
    facts: dict
    trace: Optional[dict] = None
    compared: dict = dataclasses.field(default_factory=dict)
    cell: Optional[Cell] = None


def _read_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _reported_by(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def load_cell(name: str, manifest_path: str = MANIFEST) -> Cell:
    """A cell with its files read. They are found from the manifest's place:
    beside another ``BENCHMARK.json`` lies another ``benchmarks/chip/`` with
    its own ``configs/``, ``traffic/``, ``workloads/``, ``kinds/`` and
    ``layer_metrics/`` (the tests build one under a temporary directory)."""
    manifest = _read_json(manifest_path)
    top = os.path.dirname(os.path.abspath(manifest_path))
    root = os.path.join(top, os.path.relpath(HERE, REPO))
    entries = {w["name"]: w for w in manifest["workloads"]}
    if name not in entries:
        raise SystemExit(f"no cell {name!r} in BENCHMARK.json; it has {sorted(entries)}")
    entry = entries[name]
    config_file = {c["name"]: c["file"] for c in manifest["configs"]}[entry["config"]]
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        spec=_read_json(root, "workloads", name + ".json"),
        config=_read_json(top, config_file),
        traffic=_read_json(root, "traffic", entry["traffic"] + ".json"),
        end_to_end=[m for m in manifest["end_to_end"] if _reported_by(m, name)],
        per_layer=[m for m in manifest["per_layer"] if _reported_by(m, name)],
        root=root,
    )


def runner_of(cell: Cell):
    return importlib.import_module(f"benchmarks.chip.runners.{cell.runner}")


def module_from_path(path: str, prefix: str):
    """A data-named file as a module: loaded by path, because the name of a
    metric or a kind may hold dots and the file may lie under another root."""
    stem = os.path.basename(path)[:-3].replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(prefix + stem, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def layer_metric_reader(name: str, root: str = HERE):
    """The reader of one per-layer metric: ``<root>/layer_metrics/<name>.py``."""
    return module_from_path(os.path.join(root, "layer_metrics", name + ".py"), "layer_metric_").read


# ---------------------------------------------------------------- the device


def device_record() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def require_device(chips: int, *, allow_cpu: bool = False) -> None:
    """Anything but ``chips`` TPU chips is refused: there is no CPU fallback.
    ``allow_cpu`` is what the tests pass to rehearse a runner at a tiny size."""
    device = device_record()
    if not allow_cpu and (device["platform"] != "tpu" or device["count"] != chips):
        raise SystemExit(f"needs {chips} TPU chip(s); JAX reports {device}. "
                         "There is no CPU fallback and no result.")


def memory_peak_bytes() -> int:
    """Peak device memory on the fullest chip: the allocator's peak of live
    buffers plus the peak the runtime reserved for the temporaries of loaded
    programs. The v5e's runtime counts the two apart (a bert-base step with
    10.2 GB of temporaries left ``peak_bytes_in_use`` at 1.5 GB and
    ``peak_bytes_reserved`` at 10.1 GB, my chip run, PR 23), and a reservation
    lasts while its program is loaded, so the two peaks coincide. 0 where the
    backend reports nothing, as the CPU does."""
    import jax

    def peak(device) -> int:
        stats = device.memory_stats() or {}
        return stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)

    return int(max(peak(d) for d in jax.local_devices()))


def enable_jax_cache() -> str:
    """JAX's persistent compilation cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else the one fixed, git-ignored directory of this checkout (the path
    is part of the cache's key, so it never moves). Every program is kept,
    however quickly it compiled, so that a second run compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = 0
_listening = False


def _on_duration(event: str, duration: float, **kwargs) -> None:
    global _compiles
    if event == _COMPILE_EVENT:
        _compiles += 1


def compile_count() -> int:
    """Backend compiles (cache hits included: each is a program the warm-up
    missed) in this process since the first call of this function."""
    global _listening
    if not _listening:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True
    return _compiles


# ------------------------------------------------------------ profiler slice


@contextlib.contextmanager
def annotate(name: str):
    """A host span in the profiler's own trace (`cb.` for "chip benchmark"),
    so that an idle gap of the device can be named by what the host did."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def profiler_slice(out: dict):
    """Record a profiler trace of the body and leave its reduction in
    ``out["trace"]``. The body marks its steady part with
    ``annotate("cb.window")``; only that part is reduced."""
    import jax

    from benchmarks.chip import trace_reduce

    trace_dir = os.path.join(os.environ.get("TMPDIR") or os.path.join(REPO, ".bench_tmp"),
                             "chip_benchmark_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)
    jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out["trace"] = trace_reduce.reduce_file(files[0]) if files else None
    keep = os.environ.get("CHIP_BENCHMARK_KEEP_TRACE")  # for cutting a test fixture
    if keep and files:
        os.makedirs(os.path.dirname(keep) or ".", exist_ok=True)
        shutil.copy(files[0], keep)
    shutil.rmtree(trace_dir, ignore_errors=True)


# ------------------------------------------------------------ the result line


def nearest_rank(values, q: float) -> float:
    """The ``q``-th percentile by nearest rank: the smallest value with at
    least ``q`` percent of the sample at or below it. The benchmark's own copy
    of the arithmetic of ``accelerate_tpu.telemetry.metrics.percentile``: the
    yardstick stays where no later PR can change it."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of nothing")
    rank = max(1, -(-len(ordered) * q // 100))  # ceil
    return float(ordered[int(rank) - 1])


def result_line(cell: Cell, record: Record, *, traced: bool) -> dict:
    """The one JSON object the driver reads: the cell's end-to-end metrics in
    a plain run, its per-layer metrics in a traced run. A reader that finds
    nothing to read returns None and its metric is left out. The readers get
    the record with the cell on it. Last in the line comes ``compared``: every
    number that decided ``correct``, beside its limit."""
    record = dataclasses.replace(record, cell=cell)
    metrics: dict[str, Any] = {}
    if traced:
        for entry in cell.per_layer:
            value = layer_metric_reader(entry["name"], cell.root)(record)
            if value is not None:
                metrics[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    else:
        for entry in cell.end_to_end:
            metrics[entry["name"]] = {"value": float(record.end_to_end[entry["name"]]),
                                      "unit": entry["unit"]}
    device = {**device_record(), "memory_peak_bytes": memory_peak_bytes()}
    line = {"correct": bool(record.correct), "attempted": int(record.attempted),
            "failed": int(record.failed), "metrics": metrics, "device": device}
    if traced and record.trace is not None:
        device["busy_s"] = record.trace["busy_s"]
        device["window_s"] = record.trace["window_s"]
        line["breakdown"] = {"device_ops": record.trace["device_ops"][:10],
                             "idle_gaps": record.trace["idle_gaps"][:10]}
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, (value, limit) in record.compared.items()}
    return line
