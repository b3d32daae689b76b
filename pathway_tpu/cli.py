"""`pathway-tpu` CLI — multi-process launcher + record/replay flags.

TPU-native counterpart of the reference CLI
(reference: python/pathway/cli.py — `pathway spawn` launches N OS
processes with PATHWAY_PROCESSES/PATHWAY_PROCESS_ID/PATHWAY_FIRST_PORT env
vars; `--record`/`--replay_mode` set PATHWAY_REPLAY_STORAGE /
PATHWAY_SNAPSHOT_ACCESS). On TPU pods the unit of scale-out is one JAX
process per host over the same mesh, so `spawn` sets the standard JAX
distributed env (coordinator address, process count/index) alongside the
pathway ones.
"""

from __future__ import annotations

import argparse
import os
import secrets
import subprocess
import sys


def _spawn(args, extra: list[str]) -> int:
    n = args.processes
    env_base = dict(os.environ)
    env_base["PATHWAY_PROCESSES"] = str(n)
    # per-job shared secret authenticating host-mesh frames (HMAC); see
    # parallel/host_exchange.py
    env_base.setdefault("PATHWAY_DCN_SECRET", secrets.token_hex(32))
    env_base["PATHWAY_THREADS"] = str(args.threads)
    env_base["PATHWAY_FIRST_PORT"] = str(args.first_port)
    # -t T workers = T engine key-shards over the device mesh (reference:
    # PATHWAY_THREADS timely workers per process, config.rs:88-121; here
    # engine/sharded.py execs). The mesh is built from the default
    # backend's own devices and T more than it has is an error
    # (parallel/mesh.py): on a TPU host that means T <= local chips; the
    # XLA flag below gives a CPU-backend run its T virtual devices and
    # does nothing on a TPU.
    if args.threads > 1:
        env_base["PATHWAY_ENGINE_SHARDS"] = str(args.threads)
        flags = env_base.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env_base["XLA_FLAGS"] = (
                flags
                + f" --xla_force_host_platform_device_count={args.threads}"
            ).strip()
    if args.record:
        env_base["PATHWAY_REPLAY_STORAGE"] = args.record_path
        env_base["PATHWAY_SNAPSHOT_ACCESS"] = "record"
    elif args.replay_mode:
        env_base["PATHWAY_REPLAY_STORAGE"] = args.record_path
        env_base["PATHWAY_SNAPSHOT_ACCESS"] = args.replay_mode
    if not extra:
        print("nothing to run", file=sys.stderr)
        return 2
    if extra and extra[0] == "--":
        extra = extra[1:]
    if getattr(args, "supervise", False):
        # Phoenix Mesh: run the group under the restart supervisor — a
        # dead rank tears the group down and the whole group respawns
        # from the latest group-committed snapshot generation, up to
        # PATHWAY_MESH_MAX_RESTARTS times (parallel/supervisor.py)
        from pathway_tpu.parallel.supervisor import GroupSupervisor

        env_base.setdefault(
            "JAX_COORDINATOR_ADDRESS", f"127.0.0.1:{args.first_port}"
        )
        env_base.setdefault("JAX_NUM_PROCESSES", str(n))

        def rank_env(pid: int) -> dict:
            return {"JAX_PROCESS_ID": str(pid)}

        sup = GroupSupervisor(
            extra,
            n,
            env=env_base,
            rank_env=rank_env,
            max_restarts=args.max_restarts,
        )
        return sup.run()
    procs = []
    for pid in range(n):
        env = dict(env_base)
        env["PATHWAY_PROCESS_ID"] = str(pid)
        # JAX multi-host convention: one process per host on a pod slice
        env.setdefault("JAX_COORDINATOR_ADDRESS", f"127.0.0.1:{args.first_port}")
        env.setdefault("JAX_NUM_PROCESSES", str(n))
        env["JAX_PROCESS_ID"] = str(pid)
        procs.append(subprocess.Popen(extra, env=env))
    code = 0
    for p in procs:
        code = p.wait() or code
    return code


def _spawn_from_env(args, extra: list[str]) -> int:
    """`spawn-from-env` — read the spawn arguments from PATHWAY_SPAWN_ARGS
    (reference: cli.py spawn-from-env, used by container entrypoints)."""
    spawn_args = os.environ.get("PATHWAY_SPAWN_ARGS", "").split()
    return main(["spawn", *spawn_args, "--", *extra] if extra else ["spawn", *spawn_args])


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(prog="pathway-tpu")
    sub = parser.add_subparsers(dest="command", required=True)
    sp = sub.add_parser("spawn", help="launch a program over N processes")
    sp.add_argument("--processes", "-n", type=int, default=1)
    sp.add_argument("--threads", "-t", type=int, default=1)
    sp.add_argument("--first-port", type=int, default=10000)
    sp.add_argument(
        "--supervise",
        action="store_true",
        help="restart the whole group on rank failure (Phoenix Mesh), "
        "up to --max-restarts times",
    )
    sp.add_argument("--max-restarts", type=int, default=None)
    sp.add_argument("--record", action="store_true")
    sp.add_argument("--record-path", default="./record")
    sp.add_argument(
        "--replay-mode", dest="replay_mode", choices=["replay", "full"], default=None
    )
    sub.add_parser("spawn-from-env", help="spawn with args from PATHWAY_SPAWN_ARGS")
    args, extra = parser.parse_known_args(argv)
    if args.command == "spawn":
        return _spawn(args, extra)
    if args.command == "spawn-from-env":
        return _spawn_from_env(args, extra)
    return 2


if __name__ == "__main__":
    raise SystemExit(main())
