"""``accelerate-tpu launch`` — env-var protocol + process spawn.

Reference: ``commands/launch.py`` (SURVEY.md §2.4, §3.1). The reference forks one
process per accelerator (torchrun / ``xmp.spawn``) and rendezvouses over
MASTER_ADDR; under SPMD we spawn ONE process per host — single-host launch is
"set env, exec the script", and multi-host launch distributes
``ACCELERATE_COORDINATOR_ADDRESS`` / ``ACCELERATE_NUM_PROCESSES`` /
``ACCELERATE_PROCESS_ID`` (consumed by ``state.py`` →
``jax.distributed.initialize``), optionally fanning out over a TPU pod via
``gcloud compute tpus tpu-vm ssh --worker=all`` (the moral twin of the
reference's ``tpu_pod_launcher`` → ``xla_dist``, ``commands/launch.py:1117``).
"""

from __future__ import annotations

import argparse
import os
import shlex
import subprocess
import sys
from typing import Optional

from .config import ClusterConfig, resolve_config_file


def launch_command_parser(subparsers=None) -> argparse.ArgumentParser:
    if subparsers is not None:
        p = subparsers.add_parser("launch", help="Launch a training script")
    else:
        p = argparse.ArgumentParser("accelerate-tpu launch")
    p.add_argument("--config_file", default=None)
    p.add_argument("-m", "--module", action="store_true",
                   help="Interpret the script as a python module (python -m)")
    p.add_argument("--cpu", action="store_true",
                   help="Run on simulated CPU devices (sets JAX_PLATFORMS=cpu)")
    p.add_argument("--num_processes", type=int, default=None,
                   help="With --cpu: number of simulated devices "
                        "(XLA_FLAGS=--xla_force_host_platform_device_count)")
    p.add_argument("--num_machines", type=int, default=None, help="Number of hosts")
    p.add_argument("--machine_rank", type=int, default=None, help="This host's rank")
    p.add_argument("--main_process_ip", default=None, help="Coordinator (host 0) IP")
    p.add_argument("--main_process_port", type=int, default=None)
    p.add_argument("--mixed_precision", default=None,
                   choices=("no", "bf16", "fp16", "fp8"))
    p.add_argument("--gradient_accumulation_steps", type=int, default=None)
    p.add_argument("--max_restarts", type=int, default=None,
                   help="Elastic supervision: relaunch the script up to N times on "
                        "nonzero exit (reference: torchrun --max_restarts passthrough, "
                        "commands/launch.py:998-1031). Restarted runs see "
                        "ACCELERATE_RESTART_COUNT and ACCELERATE_RESUME_FROM_CHECKPOINT=latest "
                        "so they can load_state() and continue.")
    p.add_argument("--monitor_interval", type=float, default=5.0,
                   help="Seconds to wait between a failure and the relaunch")
    p.add_argument("--elastic", action="store_true",
                   help="Full elastic supervision (resilience/supervisor.py): watch "
                        "exit codes (101 = watchdog stall abort), heartbeat-file gaps "
                        "and flight dumps; auto-resume the cohort from the last "
                        "committed checkpoint with bounded exponential backoff under "
                        "the --max_restarts budget (default 3 when --elastic); "
                        "repeated crashes at the same step stop with a poison-step "
                        "diagnosis. Arms the watchdog (ACCELERATE_WATCHDOG_ABORT) and "
                        "sets ACCELERATE_ELASTIC_RESUME so a cross-topology resume "
                        "re-shards instead of erroring.")
    p.add_argument("--heartbeat_timeout", type=float, default=0.0,
                   help="With --elastic: restart the cohort when a rank's heartbeat "
                        "file (touched by its watchdog every tick) goes stale for "
                        "this many seconds. 0 disables the file watch.")
    p.add_argument("--debug", action="store_true",
                   help="ACCELERATE_DEBUG_MODE: verify collective shapes across processes")
    # DeepSpeed-style flags (reference utils/launch.py:557-577 env protocol;
    # here they configure the native ZeRO shardings via DeepSpeedPlugin.from_env)
    p.add_argument("--use_deepspeed", action="store_true",
                   help="Signal DeepSpeed-style config: the script's Accelerator() "
                        "builds a DeepSpeedPlugin from the ACCELERATE_DEEPSPEED_* env")
    p.add_argument("--zero_stage", type=int, default=None)
    p.add_argument("--offload_optimizer_device", default=None,
                   choices=("none", "cpu", "nvme"))
    p.add_argument("--offload_param_device", default=None, choices=("none", "cpu", "nvme"))
    p.add_argument("--gradient_clipping", type=float, default=None)
    p.add_argument("--deepspeed_config_file", default=None,
                   help="Reference ds_config json; mined for stage/accum/clipping/offload")
    # Mesh axes (PARALLELISM_CONFIG_* protocol, parallelism_config.py)
    for axis in ("dp_replicate", "dp_shard", "tp", "cp", "sp", "ep", "pp"):
        p.add_argument(f"--{axis}_size", type=int, default=None)
    p.add_argument("--cp_rotate_method", default=None, choices=("allgather", "ring", "zigzag"))
    # TPU pod fan-out
    p.add_argument("--tpu_pod", action="store_true",
                   help="Fan out to every TPU-VM worker via gcloud ssh")
    p.add_argument("--tpu_name", default=None)
    p.add_argument("--tpu_zone", default=None)
    p.add_argument("--no_tpu_cluster", dest="tpu_pod", action="store_false")
    p.add_argument("training_script", help="Path to the script (or module with -m)")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    if subparsers is not None:
        p.set_defaults(func=launch_command)
    return p


def _merge_config(args) -> ClusterConfig:
    """CLI flags override config-file values (reference ``_validate_launch_command``)."""
    path = resolve_config_file(args.config_file)
    cfg = ClusterConfig.load(path) if path else ClusterConfig()
    for attr, flag in [
        ("num_machines", args.num_machines),
        ("machine_rank", args.machine_rank),
        ("main_process_ip", args.main_process_ip),
        ("main_process_port", args.main_process_port),
        ("mixed_precision", args.mixed_precision),
        ("num_processes", args.num_processes),
        ("tpu_name", args.tpu_name),
        ("tpu_zone", args.tpu_zone),
    ]:
        if flag is not None:
            setattr(cfg, attr, flag)
    for axis in ("dp_replicate", "dp_shard", "tp", "cp", "sp", "ep", "pp"):
        v = getattr(args, f"{axis}_size")
        if v is not None:
            setattr(cfg, f"{axis}_size", v)
    if args.cp_rotate_method is not None:
        cfg.cp_rotate_method = args.cp_rotate_method
    if args.gradient_accumulation_steps is not None:
        cfg.gradient_accumulation_steps = args.gradient_accumulation_steps
    if args.cpu:
        cfg.use_cpu = True
    if args.debug:
        cfg.debug = True
    return cfg


def build_launch_env(cfg: ClusterConfig) -> dict[str, str]:
    """The env-var channel (reference ``utils/launch.py:197-420``)."""
    env: dict[str, str] = {}
    env["ACCELERATE_MIXED_PRECISION"] = cfg.mixed_precision
    if cfg.gradient_accumulation_steps != 1:
        env["ACCELERATE_GRADIENT_ACCUMULATION_STEPS"] = str(cfg.gradient_accumulation_steps)
    if cfg.debug:
        env["ACCELERATE_DEBUG_MODE"] = "true"
    if cfg.use_cpu:
        # PartialState selects the platform (jax.config.update) from this
        env["ACCELERATE_USE_CPU"] = "true"
        n = cfg.num_processes or 8
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (flags + f" --xla_force_host_platform_device_count={n}").strip()
    if cfg.num_machines > 1:
        if not cfg.main_process_ip:
            raise ValueError("multi-host launch requires --main_process_ip (worker 0)")
        port = cfg.main_process_port or 8476
        env["ACCELERATE_COORDINATOR_ADDRESS"] = f"{cfg.main_process_ip}:{port}"
        env["ACCELERATE_NUM_PROCESSES"] = str(cfg.num_machines)
        env["ACCELERATE_PROCESS_ID"] = str(cfg.machine_rank)
    # Mesh geometry → PARALLELISM_CONFIG_* (reference utils/launch.py:396-420)
    mesh_flags = {
        "PARALLELISM_CONFIG_DP_REPLICATE_SIZE": cfg.dp_replicate_size,
        "PARALLELISM_CONFIG_DP_SHARD_SIZE": cfg.dp_shard_size,
        "PARALLELISM_CONFIG_TP_SIZE": cfg.tp_size,
        "PARALLELISM_CONFIG_CP_SIZE": cfg.cp_size,
        "PARALLELISM_CONFIG_SP_SIZE": cfg.sp_size,
        "PARALLELISM_CONFIG_EP_SIZE": cfg.ep_size,
        "PARALLELISM_CONFIG_PP_SIZE": cfg.pp_size,
    }
    if any(v not in (1, None) for v in mesh_flags.values()):
        for k, v in mesh_flags.items():
            env[k] = str(v)
        env["PARALLELISM_CONFIG_CP_ROTATE_METHOD"] = cfg.cp_rotate_method
    return env


def _script_cmd(args) -> list[str]:
    cmd = [sys.executable]
    if args.module:
        cmd.append("-m")
    cmd.append(args.training_script)
    cmd.extend(args.training_script_args)
    return cmd


_DS_FLAG_ENV = {
    "zero_stage": "ACCELERATE_DEEPSPEED_ZERO_STAGE",
    "offload_optimizer_device": "ACCELERATE_DEEPSPEED_OFFLOAD_OPTIMIZER_DEVICE",
    "offload_param_device": "ACCELERATE_DEEPSPEED_OFFLOAD_PARAM_DEVICE",
    "gradient_clipping": "ACCELERATE_GRADIENT_CLIPPING",
    "deepspeed_config_file": "ACCELERATE_DEEPSPEED_CONFIG_FILE",
}


def deepspeed_env(args) -> dict[str, str]:
    """DeepSpeed-style flags → the reference's env protocol
    (``utils/launch.py:557-577``); consumed by ``DeepSpeedPlugin.from_env``.

    DeepSpeed mode activates only on the explicit signals — ``--use_deepspeed``,
    ``--zero_stage`` or ``--deepspeed_config_file`` — never on auxiliary knobs
    alone (``--gradient_clipping 1.0`` by itself must not silently flip the
    run to ZeRO-2 sharding)."""
    values = {env: getattr(args, flag, None) for flag, env in _DS_FLAG_ENV.items()}
    active = (
        getattr(args, "use_deepspeed", False)
        or getattr(args, "zero_stage", None) is not None
        or getattr(args, "deepspeed_config_file", None) is not None
    )
    if not active:
        dropped = sorted(k for k, v in values.items() if v is not None)
        if dropped:
            print(
                f"[accelerate-tpu launch] ignoring DeepSpeed flags without "
                f"--use_deepspeed/--zero_stage: {dropped}",
                file=sys.stderr,
            )
        return {}
    env = {"ACCELERATE_USE_DEEPSPEED": "true"}
    env.update({k: str(v) for k, v in values.items() if v is not None})
    return env


def simple_launcher(args, cfg: ClusterConfig) -> int:
    """Single-host launch: set env, run the script (reference ``simple_launcher:986``).

    With ``--max_restarts N`` this doubles as the minimal elastic supervisor
    (the reference exposes torchrun's elastic agent for this,
    ``commands/launch.py:998-1031``): on nonzero exit the script is relaunched
    with ``ACCELERATE_RESTART_COUNT`` and
    ``ACCELERATE_RESUME_FROM_CHECKPOINT=latest`` set, so a training loop that
    calls ``accelerator.load_state()`` when that env var is present resumes
    from its newest checkpoint instead of restarting cold.
    """
    import time

    env = {**os.environ, **build_launch_env(cfg), **deepspeed_env(args)}
    # make accelerate_tpu importable in the child even for uninstalled checkouts
    pkg_parent = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (pkg_parent, env.get("PYTHONPATH")) if p
    )
    max_restarts = max(0, getattr(args, "max_restarts", None) or 0)
    monitor_interval = max(0.0, getattr(args, "monitor_interval", 5.0))
    rc = 1
    for attempt in range(max_restarts + 1):
        env["ACCELERATE_RESTART_COUNT"] = str(attempt)
        if attempt > 0:
            env["ACCELERATE_RESUME_FROM_CHECKPOINT"] = "latest"
        proc = subprocess.run(_script_cmd(args), env=env)
        rc = proc.returncode
        if rc == 0:
            return 0
        if attempt < max_restarts:
            print(
                f"[accelerate-tpu launch] script exited rc={rc}; restart "
                f"{attempt + 1}/{max_restarts} in {monitor_interval}s",
                file=sys.stderr,
            )
            time.sleep(monitor_interval)
    return rc


def tpu_pod_launcher(args, cfg: ClusterConfig) -> int:
    """Fan out to every pod worker over gcloud ssh (reference ``tpu_pod_launcher:1117``).

    Each worker re-invokes ``accelerate-tpu launch`` WITHOUT --tpu_pod and with its
    own ``--machine_rank``; jax.distributed handles rendezvous at the coordinator.
    """
    if not cfg.tpu_name:
        raise ValueError("--tpu_pod requires --tpu_name (and usually --tpu_zone)")
    if not cfg.main_process_ip:
        # every worker must agree on ONE coordinator — resolving it per-worker
        # (e.g. hostname -i) would rendezvous nowhere
        raise ValueError(
            "--tpu_pod requires --main_process_ip set to worker 0's internal IP "
            "(gcloud compute tpus tpu-vm describe <name> --format='value("
            "networkEndpoints[0].ipAddress)')"
        )
    inner = [
        "accelerate-tpu", "launch",
        "--num_machines", str(cfg.num_machines),
        "--main_process_ip", cfg.main_process_ip,
        "--main_process_port", str(cfg.main_process_port or 8476),
        "--mixed_precision", cfg.mixed_precision,
        "--gradient_accumulation_steps", str(cfg.gradient_accumulation_steps),
        "--cp_rotate_method", cfg.cp_rotate_method,
    ]
    for axis in ("dp_replicate", "dp_shard", "tp", "cp", "sp", "ep", "pp"):
        inner += [f"--{axis}_size", str(getattr(cfg, f"{axis}_size"))]
    # NOTE: --max_restarts is deliberately NOT forwarded to the inner
    # launchers. One worker restarting alone cannot rejoin the running SPMD
    # collective (the other hosts are blocked inside the old incarnation's
    # collectives) — multi-host restart must re-fan-out the WHOLE pod, which
    # is handled by the pod-level supervision loop below.
    if cfg.debug:
        inner.append("--debug")
    if getattr(args, "use_deepspeed", False):
        inner.append("--use_deepspeed")
    for flag in _DS_FLAG_ENV:
        v = getattr(args, flag, None)
        if v is not None:
            inner += [f"--{flag}", str(v)]
    if args.module:
        inner.append("-m")
    script_part = [args.training_script, *args.training_script_args]
    # gcloud sets no rank env; each worker reads its index from the TPU
    # metadata server (the xla_dist-equivalent rank channel). --machine_rank
    # must precede the script positional or REMAINDER swallows it.
    rank_probe = (
        "RANK=$(curl -s -H 'Metadata-Flavor: Google' "
        "http://metadata.google.internal/computeMetadata/v1/instance/attributes/agent-worker-number); "
    )
    remote = (rank_probe + shlex.join(inner) + " --machine_rank=$RANK "
              + shlex.join(script_part))
    cmd = [
        "gcloud", "compute", "tpus", "tpu-vm", "ssh", cfg.tpu_name,
        "--worker=all", f"--command={remote}",
    ]
    if cfg.tpu_zone:
        cmd.insert(6, f"--zone={cfg.tpu_zone}")
    # pod-level elastic supervision: if ANY worker exits nonzero (gcloud
    # propagates it) the whole pod is re-fanned-out together, with resume-from-
    # latest hints injected into every worker's env — the multi-host analogue
    # of simple_launcher's restart loop (all hosts must restart as one
    # incarnation to rendezvous)
    import time

    max_restarts = max(0, getattr(args, "max_restarts", None) or 0)
    monitor_interval = max(0.0, getattr(args, "monitor_interval", 5.0))
    rc = 1
    base_remote = cmd[-1] if cmd[-1].startswith("--command=") else None
    for attempt in range(max_restarts + 1):
        run_cmd = list(cmd)
        if base_remote is not None and attempt > 0:
            hint = (
                f"export ACCELERATE_RESTART_COUNT={attempt} "
                "ACCELERATE_RESUME_FROM_CHECKPOINT=latest; "
            )
            run_cmd[-1] = "--command=" + hint + base_remote[len("--command="):]
        print("Running:", shlex.join(run_cmd))
        rc = subprocess.run(run_cmd).returncode
        if rc == 0:
            return 0
        if attempt < max_restarts:
            print(
                f"[accelerate-tpu launch] pod exited rc={rc}; re-fan-out "
                f"{attempt + 1}/{max_restarts} in {monitor_interval}s",
                file=sys.stderr,
            )
            time.sleep(monitor_interval)
    return rc


def elastic_launcher(args, cfg: ClusterConfig) -> int:
    """``accelerate-tpu launch --elastic``: the per-host spawn wrapped in the
    resilience supervisor (``resilience/supervisor.py``) — exit-code
    classification, heartbeat-file gap watch, bounded-backoff auto-resume
    from the last committed checkpoint, poison-step diagnosis, and restart
    telemetry for the report CLI's "restarts" section."""
    import time

    from ..resilience.supervisor import RestartPolicy, supervise_command

    env = {**os.environ, **build_launch_env(cfg), **deepspeed_env(args)}
    pkg_parent = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (pkg_parent, env.get("PYTHONPATH")) if p
    )
    # one run id across incarnations so telemetry streams merge into one story
    env.setdefault("ACCELERATE_RUN_ID", f"elastic-{int(time.time())}-{os.getpid()}")
    # a stalled rank must turn into a restartable exit: arm the watchdog with
    # the abort path unless the operator configured it explicitly
    env.setdefault("ACCELERATE_WATCHDOG_TIMEOUT", "300")
    env.setdefault("ACCELERATE_WATCHDOG_ABORT", "1")
    telemetry_dir = env.setdefault("ACCELERATE_TELEMETRY_DIR", "telemetry")
    axis_sizes = {
        axis: int(getattr(cfg, f"{axis}_size") or 1)
        for axis in ("dp_replicate", "dp_shard", "tp", "cp", "sp", "ep", "pp")
    }
    axis_sizes = {a: s for a, s in axis_sizes.items() if s > 1}
    policy = RestartPolicy(
        # None = unset -> elastic default 3; an EXPLICIT 0 means "supervise,
        # classify, but never auto-restart" and must be honored
        max_restarts=3 if args.max_restarts is None else max(0, args.max_restarts),
        backoff_base_s=max(0.0, args.monitor_interval),
        heartbeat_timeout_s=max(0.0, getattr(args, "heartbeat_timeout", 0.0)),
    )
    return supervise_command(
        _script_cmd(args), env=env, policy=policy,
        telemetry_dir=telemetry_dir, axis_sizes=axis_sizes or None,
    )


def launch_command(args) -> int:
    cfg = _merge_config(args)
    if args.tpu_pod:
        if getattr(args, "elastic", False):
            # pod fan-out keeps its own whole-pod restart loop; the full
            # supervisor (exit classification, heartbeat watch, poison-step
            # diagnosis) does not apply through gcloud ssh — say so instead
            # of silently downgrading
            print(
                "[accelerate-tpu launch] --elastic is not supported with "
                "--tpu_pod; using the pod-level re-fan-out loop "
                "(--max_restarts) instead. Run --elastic per-host inside the "
                "pod for full supervision.",
                file=sys.stderr,
            )
        return tpu_pod_launcher(args, cfg)
    if getattr(args, "elastic", False):
        return elastic_launcher(args, cfg)
    return simple_launcher(args, cfg)


def register_parser(subparsers) -> argparse.ArgumentParser:
    return launch_command_parser(subparsers)


def main():
    parser = launch_command_parser()
    args = parser.parse_args()
    raise SystemExit(launch_command(args))


if __name__ == "__main__":
    main()
