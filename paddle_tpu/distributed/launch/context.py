"""Launch context: CLI args + env → a job description.

Reference: python/paddle/distributed/launch/context/__init__.py (Context holds
args/envs/node) and launch/main.py:23's documented argument surface. TPU-native
simplifications: no device enumeration per GPU — one worker process per mesh
slot (on real TPU pods one process per host), backend picked explicitly.
"""
from __future__ import annotations

import argparse
import os
import socket


def free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="Launch a distributed paddle_tpu job (collective "
                    "controller). A TPU host takes ONE worker process, which "
                    "drives every chip of the host: a chip belongs to one "
                    "process at a time, so --backend tpu refuses "
                    "--nproc_per_node > 1.",
    )
    p.add_argument("--master", default=None,
                   help="host:port of the rendezvous store / jax coordinator "
                        "(default: spawn one locally)")
    p.add_argument("--nnodes", type=int, default=int(os.environ.get("PADDLE_NNODES", "1")),
                   help="number of nodes in the job")
    p.add_argument("--node_rank", type=int,
                   default=int(os.environ.get("PADDLE_NODE_RANK", "0")),
                   help="rank of this node [0, nnodes)")
    p.add_argument("--nproc_per_node", type=int,
                   default=int(os.environ.get("PADDLE_NPROC_PER_NODE", "1")),
                   help="worker processes to spawn on this node (1 with "
                        "--backend tpu: the one process owns all its chips)")
    p.add_argument("--backend", default=os.environ.get("PADDLE_DISTRI_BACKEND", "tpu"),
                   choices=["tpu", "cpu"],
                   help="device backend for workers (cpu = gloo collectives, for "
                        "tests and host-only jobs)")
    p.add_argument("--log_dir", default=os.environ.get("PADDLE_LOG_DIR", "log"),
                   help="directory for per-worker logs (workerlog.N)")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="restart the pod this many times if a worker fails")
    p.add_argument("--heartbeat_interval", type=float, default=5.0,
                   help="seconds between worker heartbeats to the store")
    p.add_argument("--stop_grace", type=float, default=30.0,
                   help="seconds to wait after SIGTERM before SIGKILL on pod "
                        "teardown (must cover a preemption autocheckpoint)")
    p.add_argument("--heartbeat_timeout", type=float, default=0.0,
                   help="declare a worker hung after this many seconds without a "
                        "heartbeat (0 = disabled)")
    p.add_argument("--run_mode", default="collective", choices=["collective"],
                   help="job mode (only collective is supported)")
    p.add_argument("-m", "--module", action="store_true",
                   help="treat training_script as a module path "
                        "(python -m style) instead of a file")
    p.add_argument("training_script", help="script file or (with -m) module to run")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


class Context:
    """Everything the controller needs: args, this node's identity, endpoints."""

    def __init__(self, args):
        self.args = args
        self.nnodes = args.nnodes
        self.node_rank = args.node_rank
        self.nproc_per_node = args.nproc_per_node
        if args.backend == "tpu" and self.nproc_per_node > 1:
            # nothing binds a worker to a subset of the host's chips, so N
            # workers would each claim all of them and all but one hang
            raise ValueError(
                f"--backend tpu takes one worker process per host (got "
                f"--nproc_per_node {self.nproc_per_node}): a chip belongs to "
                f"one process, and that process drives every chip of the "
                f"host. Use --nnodes for more hosts, or --backend cpu for a "
                f"multi-process test job.")
        self.world_size = self.nnodes * self.nproc_per_node
        if args.master:
            host, port = args.master.rsplit(":", 1)
            self.master_host, self.master_port = host, int(port)
            self.spawn_store = self.node_rank == 0
            # jax coordinator rides the port right above the store on the
            # master host (documented contract for multi-node jobs)
            self.jax_port = self.master_port + 1
        else:
            if self.nnodes > 1:
                raise ValueError("--master host:port is required when nnodes > 1")
            self.master_host, self.master_port = "127.0.0.1", free_port()
            self.jax_port = free_port()
            self.spawn_store = True
        self.log_dir = args.log_dir

    def rank_of(self, local_rank):
        return self.node_rank * self.nproc_per_node + local_rank

    def worker_env(self, local_rank):
        """Env block for one worker process (reference wires PADDLE_TRAINER_* the
        same way; jax coordinator vars replace NCCL ones)."""
        rank = self.rank_of(local_rank)
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(self.world_size),
            "PADDLE_LOCAL_RANK": str(local_rank),
            "PADDLE_NNODES": str(self.nnodes),
            "PADDLE_NODE_RANK": str(self.node_rank),
            "MASTER_ADDR": self.master_host,
            "MASTER_PORT": str(self.master_port),
            "PADDLE_MASTER": f"{self.master_host}:{self.master_port}",
            "PADDLE_JAX_COORDINATOR": f"{self.master_host}:{self.jax_port}",
            "PADDLE_DISTRI_BACKEND": self.args.backend,
            "PADDLE_HEARTBEAT_INTERVAL": str(self.args.heartbeat_interval),
            "PADDLE_CURRENT_ENDPOINT": f"{self.master_host}:{self.master_port + 2 + rank}",
            "PADDLE_TRAINER_ENDPOINTS": ",".join(
                f"{self.master_host}:{self.master_port + 2 + r}"
                for r in range(self.world_size)),
        })
        return env
