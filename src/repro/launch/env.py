"""Runtime-config surface for single- and multi-process serving launches.

Wraps the knobs the HomebrewNLP-Jax / olmax `run.sh` scripts set by hand
(XLA_FLAGS with `--xla_force_host_platform_device_count`, TF logging,
coordinator address/port, process index) into one helper, so tests, CI
and benchmarks all launch N-process meshes the same way
`tests/_mesh_parity_child.py` forces 8 host devices — through an env
dict built here instead of ad-hoc string pasting per call site.

The multi-process contract is three env vars (read back by
`launch/distributed.initialize` BEFORE the first jax device query):

    REPRO_COORDINATOR    host:port of the rank-0 coordination service
    REPRO_NUM_PROCESSES  process (replica-group) count
    REPRO_PROCESS_ID     this process's rank in [0, NUM_PROCESSES)

`launch` spawns N ranks of an arbitrary command with those vars set
(concurrently by default — `jax.distributed.initialize` blocks until
every rank connects — or sequentially for solo-rank replicas that skip
group init), and the module doubles as a CLI launcher:

    PYTHONPATH=src python -m repro.launch.env --procs 2 --host-devices 2 \
        -- python -m repro.launch.serve --smoke --cim --traffic ...

Everything after `--` is the per-rank command. This module deliberately
never imports jax at module level: the parent must stay device-free so
children own their backends.

`--host-devices` forces CPU host-platform devices and is CPU-only. On an
accelerator a chip belongs to one process at a time: a parent that has
touched JAX holds it, and a child that needs it then fails or hangs, so
multi-device work on a TPU host runs in ONE process over all its chips.

`enable_compile_cache` is the one place the persistent compilation cache
is configured; every entry point (serve, train, chip_smoke.py) calls it
first.
"""
from __future__ import annotations

import argparse
import os
import socket
import subprocess
import sys
from typing import Dict, List, Optional, Sequence, Tuple

ENV_COORDINATOR = "REPRO_COORDINATOR"
ENV_NUM_PROCESSES = "REPRO_NUM_PROCESSES"
ENV_PROCESS_ID = "REPRO_PROCESS_ID"

DEFAULT_COORD_PORT = 46223

# <repo>/.jax_cache — a fixed path: the cache key includes it, so a cache
# that moves with a temporary directory never hits. Git-ignored.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. If JAX_COMPILATION_CACHE_DIR is set, JAX already reads it
    and nothing else is set; otherwise the cache lives at
    COMPILE_CACHE_DIR. Only sets a config flag — the backend is not
    started, so callers may still pick devices afterwards."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def xla_flags(host_devices: Optional[int] = None,
              base: Optional[str] = None) -> str:
    """The XLA_FLAGS value for one rank: the caller's existing flags (or
    `base`) with the host-platform device forcing appended. Any existing
    `--xla_force_host_platform_device_count` is replaced, not duplicated
    (XLA rejects repeated flags)."""
    flags = [f for f in (base if base is not None
                         else os.environ.get("XLA_FLAGS", "")).split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    if host_devices:
        flags.append(f"--xla_force_host_platform_device_count="
                     f"{int(host_devices)}")
    return " ".join(flags)


def runtime_env(*, num_processes: int = 1, process_id: int = 0,
                coordinator: Optional[str] = None,
                host_devices: Optional[int] = None,
                base: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    """One rank's full process environment (a copy — never mutates the
    parent's). Always quiets TF logging the way the run.sh files do;
    sets XLA_FLAGS when host devices are forced; sets the three
    REPRO_* coordination vars only for a real multi-process group, and
    strips them otherwise so a solo rank inheriting a launcher's
    environment cannot accidentally re-join a group."""
    env = dict(base if base is not None else os.environ)
    env.setdefault("TF_CPP_MIN_LOG_LEVEL", "4")
    fl = xla_flags(host_devices, base=env.get("XLA_FLAGS", ""))
    if fl:
        env["XLA_FLAGS"] = fl
    else:
        env.pop("XLA_FLAGS", None)
    if num_processes > 1:
        if not 0 <= process_id < num_processes:
            raise ValueError(f"process_id {process_id} outside "
                             f"[0, {num_processes})")
        env[ENV_COORDINATOR] = coordinator or \
            f"localhost:{DEFAULT_COORD_PORT}"
        env[ENV_NUM_PROCESSES] = str(num_processes)
        env[ENV_PROCESS_ID] = str(process_id)
    else:
        for k in (ENV_COORDINATOR, ENV_NUM_PROCESSES, ENV_PROCESS_ID):
            env.pop(k, None)
    return env


def from_env(environ: Optional[Dict[str, str]] = None
             ) -> Optional[Tuple[str, int, int]]:
    """(coordinator, num_processes, process_id) from the REPRO_* vars, or
    None when this process was not launched as part of a group. A
    partial var set raises — a half-configured rank would otherwise
    silently serve solo while its peers block on the coordinator."""
    env = os.environ if environ is None else environ
    vals = [env.get(k) for k in (ENV_COORDINATOR, ENV_NUM_PROCESSES,
                                 ENV_PROCESS_ID)]
    if all(v is None for v in vals):
        return None
    if any(v is None for v in vals):
        raise RuntimeError(
            f"partial multi-process environment: need all of "
            f"{ENV_COORDINATOR}/{ENV_NUM_PROCESSES}/{ENV_PROCESS_ID}, "
            f"got {vals}")
    coord, n, pid = vals
    n, pid = int(n), int(pid)
    if n < 1 or not 0 <= pid < n:
        raise RuntimeError(f"bad multi-process environment: "
                           f"num_processes={n} process_id={pid}")
    return coord, n, pid


def require_cpu_parent(what: str) -> None:
    """Raise unless this process runs JAX on the CPU. For a parent that
    has started JAX and is about to spawn children that need the device:
    on an accelerator the parent already holds the chip, and the children
    would fail or hang."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{what} spawns child processes that need the device, but this "
            f"process already holds the {backend!r} backend (a chip belongs "
            "to one process at a time); it runs on CPU host devices only "
            "(JAX_PLATFORMS=cpu)")


def free_port() -> int:
    """An OS-assigned free TCP port for a localhost coordinator (the
    fixed DEFAULT_COORD_PORT collides when smokes/tests run back-to-back
    and the previous coordinator socket lingers in TIME_WAIT)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch(cmd: Sequence[str], *, num_processes: int,
           host_devices: Optional[int] = None,
           coordinator: Optional[str] = None,
           sequential: bool = False,
           timeout: Optional[float] = None,
           extra_env: Optional[Dict[str, str]] = None
           ) -> List[subprocess.CompletedProcess]:
    """Run `cmd` as an N-rank group, one subprocess per rank, each with
    `runtime_env(...)`. Concurrent by default (group init blocks until
    all ranks connect); `sequential=True` runs rank after rank WITHOUT
    the coordination vars — N independent solo replicas, the shape the
    scaling bench uses to model per-host throughput on a one-core CI
    box. Captures each rank's stdout/stderr; returns CompletedProcess
    per rank in rank order (check .returncode yourself — a failed rank
    must not kill the parent before peers are collected)."""
    if num_processes < 1:
        raise ValueError(f"num_processes must be >= 1, got {num_processes}")
    solo = sequential or num_processes == 1
    if not solo and coordinator is None:
        coordinator = f"localhost:{free_port()}"
    envs = [runtime_env(num_processes=1 if solo else num_processes,
                        process_id=0 if solo else r,
                        coordinator=coordinator, host_devices=host_devices)
            for r in range(num_processes)]
    if extra_env:
        for e in envs:
            e.update(extra_env)
    if solo:
        return [subprocess.run(list(cmd), env=e, capture_output=True,
                               text=True, timeout=timeout) for e in envs]
    procs = [subprocess.Popen(list(cmd), env=e, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for e in envs]
    done = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        done.append(subprocess.CompletedProcess(list(cmd), p.returncode,
                                                out, err))
    return done


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="launch N ranks of a command as a jax.distributed "
                    "group (everything after -- is the rank command)")
    ap.add_argument("--procs", type=int, default=2,
                    help="rank count (the replica-group size)")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force this many host-platform devices per rank "
                         "(0 = leave XLA_FLAGS alone)")
    ap.add_argument("--port", type=int, default=0,
                    help="coordinator port on localhost (0 = a free one)")
    ap.add_argument("--sequential", action="store_true",
                    help="run ranks one after another as solo replicas "
                         "(no group init) instead of concurrently")
    ap.add_argument("--timeout", type=float, default=0.0,
                    help="per-group timeout in seconds (0 = none)")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="-- then the per-rank command")
    args = ap.parse_args(argv)
    cmd = args.cmd[1:] if args.cmd and args.cmd[0] == "--" else args.cmd
    if not cmd:
        ap.error("no rank command given (append: -- python -m ...)")
    coord = f"localhost:{args.port}" if args.port else None
    results = launch(cmd, num_processes=args.procs,
                     host_devices=args.host_devices or None,
                     coordinator=coord, sequential=args.sequential,
                     timeout=args.timeout or None)
    status = 0
    for rank, r in enumerate(results):
        for stream, text in (("stdout", r.stdout), ("stderr", r.stderr)):
            for line in (text or "").splitlines():
                print(f"[rank {rank} {stream}] {line}")
        if r.returncode != 0:
            print(f"[rank {rank}] exited {r.returncode}", file=sys.stderr)
            status = r.returncode
    return status


if __name__ == "__main__":
    raise SystemExit(main())
