"""Multi-host bootstrap — replaces NCCL + TCP rendezvous.

The reference initializes distribution with
`dist.init_process_group('nccl', init_method='tcp://127.0.0.1:1224', ...)`
(`code/distributed_training/model_parallel.py:57-58`) and forks one process
per GPU with `mp.spawn` (`model_parallel.py:160-163`). On TPU there is one
process per *host*; `jax.distributed.initialize()` discovers the pod slice
from the TPU metadata service (or from explicit coordinator args when run
under a generic launcher), and all devices execute one traced SPMD program.
"""

from __future__ import annotations

import logging
import os
from typing import Optional

import jax

log = logging.getLogger(__name__)

_initialized = False


def _multi_host_env() -> bool:
    """True when the environment describes a job of more than one host,
    i.e. when `jax.distributed.initialize()` has peers to find.

    A single TPU host commonly carries `TPU_WORKER_ID=0` too (with
    `TPU_WORKER_HOSTNAMES=localhost`); a rendezvous there has nobody to
    meet and, with no network, nothing to discover — so where the host
    list is given, its length decides, and the bare worker-id variables
    count only without one."""
    if "COORDINATOR_ADDRESS" in os.environ:
        return True
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES")
    if hosts is not None:
        return len([h for h in hosts.split(",") if h.strip()]) > 1
    return any(
        v in os.environ for v in ("CLOUD_TPU_TASK_ID", "TPU_WORKER_ID")
    )


def initialize_backend(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Idempotent multi-host init.

    Single-host (the common dev / single-chip case): a no-op — JAX already
    sees all local devices. Multi-host: wires up the cross-host runtime so
    `jax.devices()` is global and collectives ride ICI/DCN.

    Mirrors the reference's `--dist-url tcp://...` flag surface
    (`model_parallel.py:19-24`): pass `coordinator_address='host:port'` for
    an explicit rendezvous, or nothing to autodiscover (TPU pod metadata /
    cluster env vars).
    """
    global _initialized
    if _initialized:
        return
    if coordinator_address is not None and "://" in coordinator_address:
        # Accept reference-style URLs ('tcp://127.0.0.1:1224',
        # `model_parallel.py:19`); jax wants bare host:port.
        coordinator_address = coordinator_address.split("://", 1)[1]
    if coordinator_address is not None or _multi_host_env():
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        log.info(
            "distributed backend up: process %d/%d, %d global devices",
            jax.process_index(),
            jax.process_count(),
            jax.device_count(),
        )
    _initialized = True


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_primary() -> bool:
    """True on the host that owns logging/checkpoint writes (reference keeps
    these on rank 0, `data_parallel.py:143-155`)."""
    return jax.process_index() == 0
