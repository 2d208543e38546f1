"""paddle.distributed.spawn — analog of python/paddle/distributed/
spawn.py: launch `func` in nprocs fresh processes with the collective
env contract set, so `init_parallel_env()` inside func just works.

Uses the multiprocessing 'spawn' start method (fresh interpreters — a
forked jax runtime is unusable), a held probe socket for the coordinator
port (same race-avoidance as the launcher CLI), and re-raises the first
failing rank's traceback in the parent (the reference's
MultiprocessContext.join error surfacing)."""
from __future__ import annotations

import multiprocessing as mp
import os
import socket
import traceback

__all__ = ["spawn", "probe_free_port"]


def probe_free_port(host="127.0.0.1"):
    """Bind an OS-assigned port with SO_REUSEADDR and HOLD the socket
    (caller closes just before the real binder starts, shrinking the
    steal window to microseconds). Returns (socket, "host:port")."""
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind((host, 0))
    return s, f"{host}:{s.getsockname()[1]}"


def rank_env_overrides(rank, nprocs, master, backend=None,
                       devices_per_proc=1, nservers=0, server_rank=None,
                       rpc_master=None):
    """The collective env contract for one rank, as an overrides dict
    (value None = unset). SHARED by dist.spawn and the launcher CLI —
    the single definition of PADDLE_*/MASTER_*/backend env.
    server_rank is not None => a PS server process (TRAINING_ROLE=
    PSERVER): servers join the rpc world but never the device
    collective, so they are pinned to the CPU backend.
    rpc_master, when given, is a job-private probed-free endpoint for
    the rpc rendezvous — without it init_rpc falls back to coordinator
    port + 1, which collides when jobs run concurrently."""
    if server_rank is not None:
        env = {
            "TRAINING_ROLE": "PSERVER",
            "PADDLE_PSERVER_ID": str(server_rank),
            "PADDLE_PSERVER_NUM": str(nservers),
            "PADDLE_TRAINERS_NUM": str(nprocs),
            "PADDLE_MASTER": master,
            # a table server must not grab a TPU chip
            "JAX_PLATFORMS": "cpu",
        }
        # None UNSETS a stale endpoint inherited from an enclosing job
        # so init_rpc falls back to the explicit-master convention
        env["PADDLE_RPC_MASTER"] = rpc_master or None
        env["MASTER_ADDR"], env["MASTER_PORT"] = master.split(":")
        return env
    env = {
        "TRAINING_ROLE": "TRAINER",
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nprocs),
        "PADDLE_MASTER": master,
        "PADDLE_RPC_MASTER": rpc_master or None,
    }
    if nservers:
        env["PADDLE_PSERVER_NUM"] = str(nservers)
    env["MASTER_ADDR"], env["MASTER_PORT"] = master.split(":")
    if backend == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        flags = " ".join(
            f for f in flags.split()
            if not f.startswith("--xla_force_host_platform_device_count"))
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count="
            + str(devices_per_proc)).strip()
    elif backend == "tpu":
        env["JAX_PLATFORMS"] = "tpu"
    return env


def _worker(func, args, err_q, rank):
    try:
        func(*args)
    except Exception:
        err_q.put((rank, traceback.format_exc()))
        raise


def spawn(func, args=(), nprocs=1, join=True, daemon=False, backend=None,
          devices_per_proc=1, **options):
    """paddle.distributed.spawn parity. func runs in each rank's process
    with PADDLE_TRAINER_ID/PADDLE_TRAINERS_NUM/MASTER_* set."""
    ctx = mp.get_context("spawn")
    err_q = ctx.Queue()

    probe, master = probe_free_port()
    # second probed-free port for the rpc rendezvous: job-private, so
    # concurrent jobs never collide on the old coordinator+1 default
    rpc_probe, rpc_master = probe_free_port()

    procs = []
    for rank in range(nprocs):
        if rank == 0:
            probe.close()  # release just before rank 0 can bind it
            rpc_probe.close()
        # the rank env must be live in the PARENT at start(): the
        # spawn child inherits it at exec, before it imports jax
        overrides = rank_env_overrides(rank, nprocs, master, backend,
                                       devices_per_proc,
                                       rpc_master=rpc_master)
        saved = {k: os.environ.get(k) for k in overrides}
        try:
            for k, v in overrides.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            p = ctx.Process(target=_worker,
                            args=(func, tuple(args), err_q, rank),
                            daemon=daemon)
            p.start()
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        procs.append(p)

    if not join:
        return procs
    # poll-based watch (launcher watch-loop semantics): first failure
    # terminates the surviving ranks instead of blocking on their join
    import time

    rc = 0
    pending = set(range(nprocs))
    while pending:
        for i in list(pending):
            code = procs[i].exitcode
            if code is not None:
                pending.discard(i)
                if code != 0 and rc == 0:
                    rc = code
                    for j in pending:
                        if procs[j].is_alive():
                            procs[j].terminate()
        if pending:
            time.sleep(0.1)
    if rc:
        detail = ""
        if not err_q.empty():
            rank, tb = err_q.get()
            detail = f"\n--- rank {rank} traceback ---\n{tb}"
        raise RuntimeError(f"spawn: a rank exited with code {rc}{detail}")
    return procs
