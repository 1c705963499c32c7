"""Distributed environment. Reference: python/paddle/distributed/parallel.py
(init_parallel_env:978, ParallelEnv).

TPU-native: one Python process per host, all devices visible; "rank" maps to
jax.process_index() for multi-host and to 0 on single host. The reference's
TCPStore/env-var bootstrap is replaced by jax.distributed.initialize (the coordinator).
"""
from __future__ import annotations

import os

import jax

_initialized = False


_heartbeat = None
_store = None


def init_parallel_env(coordinator_address=None, num_processes=None, process_id=None):
    """Reference: parallel.py:978. On a TPU pod-slice each host calls this; under a
    single host it is a no-op (world = local devices).

    When spawned by ``python -m paddle_tpu.distributed.launch`` the env carries
    PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM / MASTER_ADDR+PORT /
    PADDLE_DISTRI_BACKEND; this bootstraps jax.distributed off those, flips the
    backend to CPU+gloo for host-only jobs, connects the control-plane store,
    and starts the heartbeat thread the launch watchdog monitors."""
    global _initialized, _heartbeat, _store
    if _initialized:
        return ParallelEnv()
    backend = os.environ.get("PADDLE_DISTRI_BACKEND", "")
    if backend == "cpu":
        # a host-only job on a machine that may hold an accelerator: the
        # config flip wins as long as no backend has initialized yet
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    addr = coordinator_address or os.environ.get("MASTER_ADDR")
    if addr and os.environ.get("MASTER_PORT"):
        addr = f"{addr}:{os.environ['MASTER_PORT']}"
    nproc = num_processes or int(os.environ.get("PADDLE_TRAINERS_NUM", "0")) or None
    pid = process_id if process_id is not None else (
        int(os.environ["PADDLE_TRAINER_ID"]) if "PADDLE_TRAINER_ID" in os.environ else None
    )
    if addr and nproc and nproc > 1:
        jax_addr = os.environ.get("PADDLE_JAX_COORDINATOR", addr)
        store_addr = os.environ.get("PADDLE_MASTER")
        if store_addr and ":" in store_addr:
            # Launched by paddle_tpu.distributed.launch: the TCP store owns
            # PADDLE_MASTER's port; the jax coordinator rides the port above it
            # (context.py contract) unless PADDLE_JAX_COORDINATOR says otherwise.
            from .launch.watchdog import Heartbeat
            from .store import TCPStore

            host, port = store_addr.rsplit(":", 1)
            if "PADDLE_JAX_COORDINATOR" not in os.environ:
                jax_addr = f"{host}:{int(port) + 1}"
            _store = TCPStore(host=host, port=int(port), world_size=nproc)
            interval = float(os.environ.get("PADDLE_HEARTBEAT_INTERVAL", "5"))
            # the heartbeat gets its own store connection: the app store socket
            # can be held for minutes inside barrier()/wait(), and a starved
            # heartbeat would make the watchdog kill a healthy pod
            hb_store = TCPStore(host=host, port=int(port), world_size=nproc)
            _heartbeat = Heartbeat(hb_store, pid or 0, interval).start()
        jax.distributed.initialize(jax_addr, num_processes=nproc, process_id=pid)
    _initialized = True
    return ParallelEnv()


def is_initialized():
    return _initialized


def get_rank(group=None):
    if group is not None:
        return group.rank
    return jax.process_index()


def get_world_size(group=None):
    if group is not None:
        return group.nranks
    return jax.process_count()


class ParallelEnv:
    @property
    def rank(self):
        return get_rank()

    @property
    def local_rank(self):
        return get_rank()

    @property
    def world_size(self):
        return get_world_size()

    @property
    def nranks(self):
        return get_world_size()

    @property
    def device_id(self):
        return 0

    @property
    def current_endpoint(self):
        return os.environ.get("PADDLE_CURRENT_ENDPOINT", "127.0.0.1:6170")

    @property
    def trainer_endpoints(self):
        return os.environ.get("PADDLE_TRAINER_ENDPOINTS", "127.0.0.1:6170").split(",")
