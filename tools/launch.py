#!/usr/bin/env python
"""Distributed job launcher (reference ``tools/launch.py:57-116``).

The reference forks a ps-lite scheduler + servers + workers with
``DMLC_ROLE`` env vars; on TPU there is no parameter server — SPMD workers
coordinate through the jax coordination service — so the launcher only has
to (1) pick a coordinator address, (2) spawn N copies of the command with
per-process rank env, (3) propagate failures.  The training script should
call ``mx.parallel.initialize()`` before its first jax computation;
``kvstore.create('dist_sync')`` also attempts it from the same env as a
best-effort fallback (too late if jax backends already initialized).

Launchers:
  local — N processes on this host (the reference's ``--launcher local``
          test fixture, SURVEY.md §4 "distributed tests without a real
          cluster").  CPU-ONLY for N > 1: every worker gets the same
          environment, so on a host with chips each would try to take
          every chip, and a chip belongs to one process at a time.
          ``launch_local`` refuses N > 1 unless the workers' environment
          pins them to the CPU (``JAX_PLATFORMS=cpu``).  One process
          drives all the chips of a host through a device mesh
          (``python chip_smoke.py --chips 4``); N = 1 runs unrestricted,
          and this launcher itself never touches jax.
  ssh   — one process per host from --hostfile.

Env contract (set for each spawned process):
  MXNET_TPU_COORDINATOR_ADDRESS  host:port of process 0
  MXNET_TPU_NUM_PROCESSES        N
  MXNET_TPU_PROCESS_ID           rank
(DMLC_NUM_WORKER / DMLC_WORKER_ID are also set for reference scripts.)
"""
import argparse
import os
import socket
import subprocess
import sys

DEFAULT_PORT = 9462


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _worker_env(base, coordinator, n, rank):
    env = dict(base)
    env.update({
        "MXNET_TPU_COORDINATOR_ADDRESS": coordinator,
        "MXNET_TPU_NUM_PROCESSES": str(n),
        "MXNET_TPU_PROCESS_ID": str(rank),
        "DMLC_NUM_WORKER": str(n),
        "DMLC_WORKER_ID": str(rank),
        "DMLC_ROLE": "worker",
    })
    return env


def launch_local(n, command, env=None):
    """Spawn n local workers; returns the list of exit codes.  More than
    one worker on a host is a CPU fixture (see the module docstring)."""
    env = os.environ if env is None else env
    if n > 1 and env.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            "launch_local(%d, ...) is CPU-only: %d workers with one "
            "environment would each try to take every chip of this host. "
            "Set JAX_PLATFORMS=cpu for the workers, or drive the chips "
            "from one process with a device mesh." % (n, n))
    coordinator = "127.0.0.1:%d" % _free_port()
    procs = []
    for rank in range(n):
        procs.append(subprocess.Popen(
            command, shell=isinstance(command, str),
            env=_worker_env(env, coordinator, n, rank)))
    codes = [p.wait() for p in procs]
    return codes


def launch_ssh(hosts, command, env_keys=("PYTHONPATH",), port=DEFAULT_PORT):
    import shlex
    coordinator = "%s:%d" % (hosts[0], port)
    procs = []
    for rank, host in enumerate(hosts):
        env = _worker_env({}, coordinator, len(hosts), rank)
        for k in env_keys:
            if k in os.environ:
                env[k] = os.environ[k]
        exports = " ".join("%s=%s" % (k, shlex.quote(v))
                           for k, v in env.items())
        remote_cmd = command if isinstance(command, str) \
            else " ".join(shlex.quote(c) for c in command)
        cmd = ["ssh", "-o", "StrictHostKeyChecking=no", host,
               "cd %s; env %s %s" % (shlex.quote(os.getcwd()), exports,
                                     remote_cmd)]
        procs.append(subprocess.Popen(cmd))
    return [p.wait() for p in procs]


def main():
    parser = argparse.ArgumentParser(
        description="Launch a distributed training job (reference "
                    "tools/launch.py)")
    parser.add_argument("-n", "--num-workers", type=int, required=True)
    parser.add_argument("--launcher", choices=["local", "ssh"],
                        default="local")
    parser.add_argument("-H", "--hostfile", default=None,
                        help="one host per line (ssh launcher)")
    parser.add_argument("-p", "--port", type=int, default=DEFAULT_PORT,
                        help="coordination-service port on host 0 (ssh "
                             "launcher); change when two jobs share a "
                             "coordinator host")
    parser.add_argument("command", nargs=argparse.REMAINDER)
    args = parser.parse_args()
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if not args.command:
        parser.error("no command given")
    if args.launcher == "local":
        codes = launch_local(args.num_workers, args.command)
    else:
        with open(args.hostfile) as f:
            hosts = [h.strip() for h in f if h.strip()]
        assert len(hosts) >= args.num_workers, "not enough hosts"
        codes = launch_ssh(hosts[:args.num_workers], args.command,
                           port=args.port)
    bad = [c for c in codes if c != 0]
    if bad:
        sys.exit(bad[0])


if __name__ == "__main__":
    main()
