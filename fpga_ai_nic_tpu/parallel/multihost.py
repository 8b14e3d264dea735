"""Multi-host control plane — the MPI layer of the reference, TPU-native.

The reference's distributed backend has two planes (SURVEY.md §2): MPI for
control (`MPI_Init_thread`/`Scatter`/`Bcast`/`Barrier`,
sw/mlp_mpi_example_f32.cpp:195,452-470,688, launched by mpirun with a
`hostlist` side file, sw/README:1-3) and the FPGA ring for data.  On TPU
both collapse into JAX: `jax.distributed.initialize` is the control plane
(coordinator + process ids from flags or the environment — TPU pod
environments autoconfigure), and ICI/DCN collectives are the data plane.

What this module adds over raw jax.distributed:
- `initialize()` — idempotent, env-var-driven init (the mpirun/hostlist
  ritual as one call), no-op on single process.
- `local_batch_to_global()` — each process feeds its PROCESS-LOCAL batch
  shard and gets the global sharded array (the per-rank MPI_Scatter that
  the loaders sit on top of).
- `barrier()` — MPI_Barrier.

Every trainer in `parallel/` already takes an explicit Mesh, and
`make_mesh` builds over `jax.devices()` — which is the GLOBAL device list
after initialize() — so multi-host scaling is: initialize(); make_mesh
(global sizes); feed with local_batch_to_global.  The 8-device virtual CPU
mesh exercises the same code paths single-process (num_processes=1).
"""

from __future__ import annotations

import os
from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding

_initialized = False


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               local_device_ids: Optional[list] = None) -> None:
    """Idempotent `jax.distributed.initialize` with env fallbacks.

    Resolution order per field: explicit arg -> JAX_COORDINATOR_ADDRESS /
    JAX_NUM_PROCESSES / JAX_PROCESS_ID env -> platform autodetection when
    a multi-host TPU environment is detected (no-arg
    jax.distributed.initialize; libtpu publishes worker topology via
    TPU_WORKER_HOSTNAMES / MEGASCALE_COORDINATOR_ADDRESS on pods).
    Plain single-process runs (no args, no env, no pod markers) are a
    no-op, so the same training script runs unmodified on a laptop, one
    host, or a pod — unlike the reference, which hard-requires mpirun +
    hostlist even for one node.
    """
    global _initialized
    if _initialized:
        return
    coord = coordinator_address or os.environ.get("JAX_COORDINATOR_ADDRESS")
    nproc = num_processes if num_processes is not None else int(
        os.environ.get("JAX_NUM_PROCESSES", "0") or 0) or None
    pid = process_id if process_id is not None else (
        int(os.environ["JAX_PROCESS_ID"])
        if "JAX_PROCESS_ID" in os.environ else None)
    if coord is None and nproc in (None, 1):
        if _on_multihost_tpu():
            # pod slice: let jax autodetect coordinator + process ids
            jax.distributed.initialize()
            _initialized = True
        return                       # single-process: nothing to coordinate
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=nproc, process_id=pid,
                               local_device_ids=local_device_ids)
    _initialized = True


def _on_multihost_tpu() -> bool:
    """Detect a multi-worker TPU environment from env alone (never probes
    jax — a process that has touched jax holds the chip)."""
    hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hostnames.split(",") if h.strip()]) > 1:
        return True
    return bool(os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"))


def process_info() -> dict:
    """(rank, size) readback — the reference prints these from MPI
    (sw/mlp_mpi_example_f32.cpp:300-302)."""
    return {"process_id": jax.process_index(),
            "num_processes": jax.process_count(),
            "local_devices": len(jax.local_devices()),
            "global_devices": len(jax.devices())}


def local_batch_to_global(batch: Any, mesh: Mesh, spec) -> Any:
    """Assemble global sharded arrays from PROCESS-LOCAL host data.

    Each process passes only the rows it loaded (global_batch /
    num_processes of them); the result behaves like one global array laid
    out per `spec` — the MPI_Scatter analogue
    (sw/mlp_mpi_example_f32.cpp:452-460), except no root process ever
    materializes the full batch.  Single-process this degrades to a plain
    device_put, so loaders can use it unconditionally.
    """
    ns = NamedSharding(mesh, spec)
    if jax.process_count() == 1:
        return jax.tree_util.tree_map(lambda x: jax.device_put(x, ns), batch)
    return jax.tree_util.tree_map(
        lambda x: jax.make_array_from_process_local_data(ns, np.asarray(x)),
        batch)


def barrier(name: str = "barrier") -> None:
    """Block until every process arrives (MPI_Barrier,
    sw/mlp_mpi_example_f32.cpp:688)."""
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(name)
