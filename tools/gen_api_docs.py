"""Generate docs/package_reference/*.md from the live package.

Mirrors the reference's ``docs/source/package_reference/`` file set (15 pages:
accelerator, state, big_modeling, cli, deepspeed, fp8, fsdp, inference,
kwargs, launchers, logging, megatron_lm, torch_wrappers, tracking, utilities)
but the content is INTROSPECTED from this package — signatures and first
docstring paragraphs — so the reference pages can never drift from the code.
``tests/test_docs.py`` regenerates into a temp dir and asserts zero diff.

Run:  python tools/gen_api_docs.py [outdir]
"""

from __future__ import annotations

import importlib
import inspect
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")  # introspection needs no chip


# page -> (title, intro, [(module, [names] | None=all public)])
PAGES: "dict[str, tuple[str, str, list]]" = {
    "accelerator": (
        "Accelerator",
        "The central orchestration facade (reference `accelerator.py:183`): "
        "prepare assigns shardings, the hot path is one jitted train step.",
        [("accelerate_tpu.accelerator", ["Accelerator", "StepProfiler", "RemovableHandle"])],
    ),
    "state": (
        "State singletons",
        "Process/mesh state (reference `state.py`): PartialState boots "
        "`jax.distributed`, AcceleratorState owns the mesh, GradientState "
        "tracks accumulation.",
        [("accelerate_tpu.state", ["PartialState", "AcceleratorState", "GradientState"])],
    ),
    "big_modeling": (
        "Big-model inference",
        "Zero-RAM init, device maps, dispatch and offload "
        "(reference `big_modeling.py`).",
        [("accelerate_tpu.big_modeling", None), ("accelerate_tpu.hooks", None)],
    ),
    "cli": (
        "CLI",
        "`accelerate-tpu {config,launch,env,estimate-memory,merge-weights,"
        "test,tpu-config,to-fsdp2}` (reference `commands/`). Each command "
        "module exposes `main`/`*_command` entry points.",
        [("accelerate_tpu.commands.launch", ["launch_command", "build_launch_env"]),
         ("accelerate_tpu.commands.config", ["write_basic_config", "ClusterConfig"]),
         ("accelerate_tpu.commands.estimate", None),
         ("accelerate_tpu.commands.merge", None),
         ("accelerate_tpu.commands.to_fsdp2", ["to_fsdp2_command"])],
    ),
    "deepspeed": (
        "DeepSpeed (shim)",
        "There is no DeepSpeed engine on TPU: the plugin maps ZeRO staging "
        "onto GSPMD sharding (see `docs/concept_guides/fsdp_gspmd.md`).",
        [("accelerate_tpu.utils.dataclasses",
          ["DeepSpeedPlugin", "HfDeepSpeedConfig", "DummyOptim", "DummyScheduler",
           "get_active_deepspeed_plugin", "deepspeed_required"])],
    ),
    "fp8": (
        "FP8",
        "Native delayed-scaling fp8 over XLA's fp8 `dot_general` "
        "(reference delegates to TE/torchao/MS-AMP CUDA).",
        [("accelerate_tpu.ops.fp8", None),
         ("accelerate_tpu.utils.dataclasses",
          ["FP8RecipeKwargs", "TERecipeKwargs", "AORecipeKwargs", "MSAMPRecipeKwargs"])],
    ),
    "fsdp": (
        "FSDP",
        "FSDP is a NamedSharding assignment over the `dp_shard` mesh axis; "
        "the FSDP1/FSDP2 split collapses under GSPMD. Every spec decision "
        "flows through ONE `make_sharding_plan` entry point (ISSUE 9); the "
        "fused bucketed ZeRO-1 weight update lives in "
        "`parallel.weight_update`.",
        [("accelerate_tpu.utils.dataclasses", ["FullyShardedDataParallelPlugin"]),
         ("accelerate_tpu.parallel.sharding", None),
         ("accelerate_tpu.parallel.weight_update", None),
         ("accelerate_tpu.sharded_checkpoint", None)],
    ),
    "inference": (
        "Inference",
        "KV-cache generation and pipeline-parallel inference "
        "(reference `inference.py` PiPPy route). Concurrent-request serving "
        "lives in `accelerate_tpu.serving` (see the serving page).",
        [("accelerate_tpu.generation", None),
         ("accelerate_tpu.parallel.pipeline", None)],
    ),
    "serving": (
        "Serving",
        "Continuous batching over a paged KV cache (no reference "
        "counterpart): step-granular admission into running decode batches, "
        "fixed-size KV blocks in one preallocated pool with a host-side "
        "allocator, watermark/LIFO preemption with persisted resume, and a "
        "static bucket lattice so admission churn never recompiles — "
        "with automatic prefix caching (content-addressed refcounted block "
        "sharing + copy-on-write) and Pallas paged-attention decode + "
        "chunked-prefill kernels on TPU — replicated behind a health-checked "
        "router with token-exact failover, deadlines, and graceful overload "
        "shedding. Speculative decoding (a truncated-layer self-draft with "
        "bitwise-accept verification) emits multiple tokens per step without "
        "changing a single output token. "
        "The fleet can be split into disaggregated prefill/decode tiers "
        "(content-addressed KV handoff, bitwise parity with the monolith) "
        "with SLO-burn-driven autoscaling and warm pre-shipped scale-up. "
        "See `docs/serving.md` for the guide and `benchmarks/serving/` "
        "(`make bench-serve`) for the continuous-vs-static, replicated, "
        "shared-prefix, disaggregated and speculative-decoding benchmarks.",
        [("accelerate_tpu.serving.engine", ["ServingEngine"]),
         ("accelerate_tpu.serving.kv_pager",
          ["BlockAllocator", "BlockAllocatorError", "BlockPoolExhausted",
           "PrefixPlan", "PrefixAllocation"]),
         ("accelerate_tpu.ops.flash_attention",
          ["init_block_pool", "kv_lane_pack", "paged_write_attend", "paged_attention",
           "paged_attention_gather", "paged_attention_decode",
           "paged_attention_prefill", "prefill_walk_blocks",
           "paged_kernel_mode"]),
         ("accelerate_tpu.models.transformer",
          ["llama_paged_forward", "llama_layer", "draft_config", "draft_params"]),
         ("accelerate_tpu.serving.scheduler",
          ["Request", "RequestStatus", "Scheduler", "SchedulingError"]),
         ("accelerate_tpu.serving.buckets", ["BucketLattice"]),
         ("accelerate_tpu.serving.router",
          ["ServingRouter", "RouterRequest", "RouterRequestStatus"]),
         ("accelerate_tpu.serving.replica",
          ["ReplicaSpec", "ReplicaState", "LocalReplica", "ProcessReplica"]),
         ("accelerate_tpu.serving.admission",
          ["AdmissionController", "AdmissionVerdict", "TokenBucket"]),
         ("accelerate_tpu.serving.disagg",
          ["PrefillEngine", "DecodeEngine", "DisaggRouter", "KVHandoff",
           "KVTransport", "LocalBlockCopyTransport"]),
         ("accelerate_tpu.serving.autoscaler",
          ["AutoscalerPolicy", "lattice_fns"]),
         ("accelerate_tpu.serving.canary",
          ["CanaryGolden", "CanaryProbe", "precompute_goldens"])],
    ),
    "analysis": (
        "Static analysis (jaxlint)",
        "AST-based analyzer for jit-traced code (no reference counterpart): "
        "discovers the jit/pjit/shard_map call graph and flags host syncs "
        "(R1), recompile hazards (R2), donation bugs (R3), rank-divergent "
        "collectives (R4) and trace-time nondeterminism (R5). CLI: "
        "`python -m accelerate_tpu.analysis lint` / `make lint`. See "
        "`docs/static_analysis.md` for the rule catalog.",
        [("accelerate_tpu.analysis.engine", ["run_lint", "LintResult"]),
         ("accelerate_tpu.analysis.findings", ["Finding", "Severity", "summarize"]),
         ("accelerate_tpu.analysis.callgraph",
          ["build_package_index", "discover_traced", "PackageIndex", "ModuleIndex",
           "FunctionInfo", "JitSpec", "TracedRegion"]),
         ("accelerate_tpu.analysis.rules", ["Rule", "RuleContext", "load_all_rules"]),
         ("accelerate_tpu.analysis.baseline",
          ["load_baseline", "apply_baseline", "write_baseline", "discover_baseline"]),
         ("accelerate_tpu.analysis.reporters", ["render_human", "render_json"])],
    ),
    "checkpointing": (
        "Checkpointing",
        "Crash-consistent (staging + fsync + `_COMMITTED` marker + atomic "
        "rename) save/load with an async zero-stall path: "
        "`save_state(blocking=False)` pays only the device→host snapshot; a "
        "background writer serializes and commits (see `docs/checkpointing.md`).",
        [("accelerate_tpu.checkpointing",
          ["CheckpointCorruptError", "CheckpointSnapshot", "snapshot_accelerator_state",
           "write_snapshot", "commit_snapshot", "write_and_commit",
           "save_accelerator_state", "load_accelerator_state", "find_latest_checkpoint",
           "is_committed_checkpoint", "rotate_checkpoints", "repair_interrupted_commit",
           "save_model", "load_checkpoint_in_model"]),
         ("accelerate_tpu.checkpoint_async", ["CheckpointManager"]),
         ("accelerate_tpu.utils.dataclasses", ["CheckpointConfig"])],
    ),
    "kwargs": (
        "Kwargs handlers and plugins",
        "Configuration dataclasses (reference `utils/dataclasses.py`).",
        [("accelerate_tpu.utils.dataclasses", None)],
    ),
    "launchers": (
        "Launchers",
        "Notebook/debug launchers (reference `launchers.py`).",
        [("accelerate_tpu.launchers", None)],
    ),
    "logging": (
        "Logging",
        "Rank-aware logging (reference `logging.py`).",
        [("accelerate_tpu.logging", None)],
    ),
    "megatron_lm": (
        "Megatron-LM (shim)",
        "The Megatron engine is not ported; its TP/PP/EP degrees map onto the "
        "native mesh. Engine internals are excluded with reasons in "
        "`accelerate_tpu.utils.api_boundary.EXCLUDED_REFERENCE_UTILS`.",
        [("accelerate_tpu.utils.dataclasses", ["MegatronLMPlugin"]),
         ("accelerate_tpu.parallelism_config", ["ParallelismConfig"])],
    ),
    "torch_wrappers": (
        "Training-object wrappers and the torch bridge",
        "Data loader / optimizer / scheduler wrappers (reference "
        "`data_loader.py`, `optimizer.py`, `scheduler.py`) and the "
        "torch.export→JAX bridge that runs torch models on the TPU path.",
        [("accelerate_tpu.data_loader", None),
         ("accelerate_tpu.optimizer", None),
         ("accelerate_tpu.scheduler", None),
         ("accelerate_tpu.bridge.module", ["BridgedModule", "BridgedOutput"])],
    ),
    "telemetry": (
        "Telemetry",
        "Built-in observability (no reference counterpart): structured step "
        "events, recompile/memory/comms metrics, performance attribution "
        "(MFU/roofline cost capture + profiler trace windows), hang/crash "
        "forensics (flight recorder + watchdog), and the "
        "`python -m accelerate_tpu.telemetry report` CLI. See "
        "`docs/telemetry.md`, `docs/performance.md` and "
        "`docs/troubleshooting.md` for the guides.",
        [("accelerate_tpu.telemetry.events",
          ["EventLog", "enable", "disable", "maybe_enable_from_env", "is_enabled",
           "get_event_log", "emit", "counter", "gauge", "span", "set_step",
           "hard_flush"]),
         ("accelerate_tpu.telemetry.step_profiler",
          ["StepTelemetry", "RecompileWatcher", "install_compile_listener",
           "compile_snapshot", "record_data_wait"]),
         ("accelerate_tpu.telemetry.memory", None),
         ("accelerate_tpu.telemetry.perf",
          ["HardwarePeaks", "CompiledCost", "peaks_for_device", "device_peak_flops",
           "device_hbm_bandwidth", "train_flops_per_sample", "lm_train_mfu", "mfu",
           "arithmetic_intensity", "roofline_bucket", "capture_enabled",
           "cost_from_compiled", "capture_compiled"]),
         ("accelerate_tpu.telemetry.xplane",
          ["TraceWindows", "parse_xspace", "parse_chrome_trace", "find_trace_files",
           "summarize_planes", "summarize_trace", "is_collective_op", "is_infra_event"]),
         ("accelerate_tpu.telemetry.flight_recorder",
          ["FlightRecorder", "get_recorder", "record", "phase", "set_step",
           "current_phases", "dump", "install", "uninstall", "enabled_from_env",
           "load_flight_records"]),
         ("accelerate_tpu.telemetry.watchdog",
          ["Watchdog", "start", "stop", "maybe_start_from_env", "get_watchdog",
           "beat", "register", "unregister", "env_timeout"]),
         ("accelerate_tpu.telemetry.tracing",
          ["TraceContext", "arm", "disarm", "maybe_arm_from_env", "is_armed",
           "new_trace", "span_open", "span_close", "make_span", "emit_spans",
           "finish_trace", "spans_by_trace", "validate_span_tree",
           "chrome_trace", "format_timeline"]),
         ("accelerate_tpu.telemetry.metrics",
          ["Counter", "Gauge", "Histogram", "MetricsRegistry", "percentile",
           "quantile_from_buckets", "hist_dist", "enable", "disable",
           "maybe_enable_from_env", "inc", "set_gauge", "observe",
           "snapshot_now", "maybe_snapshot", "serve", "server_port",
           "stop_server", "parse_prometheus_text", "histogram_from_scrape"]),
         ("accelerate_tpu.telemetry.slo",
          ["SLObjective", "SLOMonitor", "serving_slos",
           "step_latency_slo_from_env", "restart_downtime_slo_from_env"]),
         ("accelerate_tpu.telemetry.goodput",
          ["build_ledger", "verdict_line", "restart_stats", "note",
           "note_step", "note_serving_step", "maybe_emit", "emit_now"]),
         ("accelerate_tpu.telemetry.regress",
          ["MetricSpec", "register", "spec_for", "load_payload", "fingerprint",
           "comparable", "extract_metrics", "compare_metrics", "scan_dir",
           "run_regress"]),
         ("accelerate_tpu.telemetry.report",
          ["build_report", "build_report_from_events", "format_report",
           "format_rank_section", "format_serving_section",
           "format_router_section", "format_slo_section",
           "format_goodput_section", "format_anomaly_section",
           "format_canary_section", "render_request",
           "find_request_trace", "load_events", "run_doctor", "main"]),
         ("accelerate_tpu.telemetry.hub",
          ["FileTail", "FleetModel", "EventHub", "render_top", "run_top",
           "run_follow"]),
         ("accelerate_tpu.telemetry.anomaly",
          ["EwmaDetector", "TrendDetector", "AnomalyEngine"]),
         ("accelerate_tpu.telemetry.tracker_bridge", None)],
    ),
    "compile_cache": (
        "Compile cache",
        "Zero-cold-start recovery (no reference counterpart): a crash-safe "
        "persistent cache of serialized AOT executables, content-addressed on "
        "(StableHLO fingerprint, mesh axes, device kind, jax/jaxlib/XLA "
        "versions, compile flags), committed with the staged-fsync-CRC-"
        "manifest-rename protocol and read defensively (corrupt/mismatched "
        "entries are quarantined and fall back to a fresh compile). Probed by "
        "the Accelerator on restart generations >= 1, loaded wholesale by the "
        "serving engine's warmup, pre-touched by the elastic supervisor, and "
        "pre-shipped to autoscaler joiners for warm (zero-compile) scale-up. "
        "See `docs/compile_cache.md`.",
        [("accelerate_tpu.compile_cache.cache",
          ["CacheKey", "CompileCache", "LoadResult", "StoreResult",
           "key_from_lowered", "environment_fingerprint", "compile_flags"]),
         ("accelerate_tpu.compile_cache.runtime",
          ["cache_enabled", "configured_cache_dir", "get_cache", "aot_compile",
           "maybe_load_executable", "maybe_export", "call_with_fallback",
           "pretouch", "preship"])],
    ),
    "resilience": (
        "Resilience",
        "Elastic preemption-tolerant training (no reference counterpart): the "
        "`accelerate-tpu launch --elastic` supervisor (exit-code "
        "classification, heartbeat-file gaps, bounded-backoff auto-resume, "
        "poison-step diagnosis), cohort membership across restarts, "
        "cross-topology checkpoint re-sharding, and the deterministic chaos "
        "harness behind `make chaos`. See `docs/resilience.md`.",
        [("accelerate_tpu.resilience.supervisor",
          ["RestartPolicy", "Supervisor", "classify_exit", "supervise_command"]),
         ("accelerate_tpu.resilience.membership",
          ["CohortSpec", "MembershipError", "negotiate_membership",
           "announce_membership", "read_roster", "publish_cohort_spec",
           "load_cohort_spec", "await_roster", "current_generation"]),
         ("accelerate_tpu.resilience.reshard",
          ["check_topology", "topology_matches", "is_elastic_compatible",
           "mesh_shape_dict", "saved_topology", "describe_shapes"]),
         ("accelerate_tpu.resilience.chaos",
          ["ChaosSchedule", "Fault", "ChaosFaultError", "arm",
           "maybe_arm_from_env", "maybe_inject", "replan_data_assignment"])],
    ),
    "tracking": (
        "Experiment tracking",
        "Tracker abstraction + integrations (reference `tracking.py`).",
        [("accelerate_tpu.tracking", None)],
    ),
    "utilities": (
        "Utilities",
        "Collectives, modeling utils, memory, offload, environment "
        "(reference `utils/`). The full reference-name boundary lives in "
        "`accelerate_tpu/utils/api_boundary.py`.",
        [("accelerate_tpu.utils.operations", None),
         ("accelerate_tpu.utils.modeling", None),
         ("accelerate_tpu.utils.memory", None),
         ("accelerate_tpu.utils.offload", None),
         ("accelerate_tpu.utils.environment", None),
         ("accelerate_tpu.utils.random", None),
         ("accelerate_tpu.utils.other", None)],
    ),
}


def _first_paragraph(obj) -> str:
    doc = inspect.getdoc(obj) or ""
    para = doc.split("\n\n", 1)[0].strip()
    return " ".join(para.split())


def _signature(obj) -> str:
    import re

    try:
        sig = str(inspect.signature(obj))
    except (ValueError, TypeError):
        return "(...)"
    # function/object default reprs embed memory addresses — nondeterministic
    # across runs, which would make the freshness test flap
    return re.sub(r" at 0x[0-9a-f]+", "", sig)


def _public_names(mod) -> list:
    names = getattr(mod, "__all__", None)
    if names is None:
        names = [n for n, v in vars(mod).items()
                 if not n.startswith("_") and getattr(v, "__module__", None) == mod.__name__
                 and (inspect.isclass(v) or inspect.isfunction(v))]
    return names


def _render_entry(name: str, obj) -> list:
    lines = []
    if inspect.isclass(obj):
        lines.append(f"### `class {name}{_signature(obj)}`\n")
        para = _first_paragraph(obj)
        if para:
            lines.append(para + "\n")
        methods = [
            (mn, mv) for mn, mv in vars(obj).items()
            if not mn.startswith("_")
            and (inspect.isfunction(mv) or isinstance(mv, (property, classmethod, staticmethod)))
        ]
        for mn, mv in methods:
            if isinstance(mv, property):
                lines.append(f"- **`{mn}`** (property) — {_first_paragraph(mv.fget) or ''}")
            elif isinstance(mv, (classmethod, staticmethod)):
                kind = "classmethod" if isinstance(mv, classmethod) else "staticmethod"
                fn = mv.__func__
                lines.append(
                    f"- **`{mn}{_signature(fn)}`** ({kind}) — {_first_paragraph(fn) or ''}"
                )
            else:
                lines.append(f"- **`{mn}{_signature(mv)}`** — {_first_paragraph(mv) or ''}")
        if methods:
            lines.append("")
    elif inspect.isfunction(obj):
        lines.append(f"### `{name}{_signature(obj)}`\n")
        para = _first_paragraph(obj)
        if para:
            lines.append(para + "\n")
    else:
        lines.append(f"### `{name}`\n")
    return lines


def render_page(page: str) -> str:
    title, intro, sections = PAGES[page]
    out = [
        "<!-- GENERATED by tools/gen_api_docs.py — edit docstrings, not this file;",
        "     tests/test_docs.py fails when this page is stale. -->",
        f"# {title}\n",
        intro + "\n",
    ]
    for module_name, names in sections:
        mod = importlib.import_module(module_name)
        out.append(f"## `{module_name}`\n")
        mod_doc = _first_paragraph(mod)
        if mod_doc:
            out.append(mod_doc + "\n")
        for name in names or _public_names(mod):
            obj = getattr(mod, name, None)
            if obj is None:
                raise SystemExit(f"{module_name} has no attribute {name!r}")
            out.extend(_render_entry(name, obj))
    return "\n".join(out).rstrip() + "\n"


def main(outdir: str) -> None:
    os.makedirs(outdir, exist_ok=True)
    for page in sorted(PAGES):
        path = os.path.join(outdir, f"{page}.md")
        with open(path, "w") as f:
            f.write(render_page(page))
        print(f"wrote {path}")


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else os.path.join(REPO, "docs", "package_reference"))
