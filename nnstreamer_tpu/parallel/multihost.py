"""Multi-host (DCN) runtime integration (L5/TPU-native distribution).

The reference's inter-device backend is nnstreamer-edge TCP/MQTT between
pipelines (SURVEY.md §5.8); the TPU-native equivalent has two tiers:

* intra-slice: ``jax.sharding`` over a Mesh — XLA emits ICI collectives
  (parallel/mesh.py);
* inter-host: the JAX distributed runtime over DCN — every host runs the
  same program, ``jax.distributed.initialize`` wires the coordinator, and
  ``jax.devices()`` becomes the GLOBAL device set, so the same Mesh code
  scales from one chip to a pod without touching element code.

``init_multihost()`` wraps that bootstrap with env-var conventions
(NNS_COORD/NNS_NUM_PROCS/NNS_PROC_ID, falling back to JAX's own
auto-detection on TPU pods), and ``global_mesh()`` builds the
dp/tp/sp mesh over all addressable+remote devices. Single-process runs
degrade to a no-op so the same entry script works everywhere.
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Sequence

from ..utils.log import logger
from .mesh import AXES, factor_devices, make_mesh

_initialized = False


def init_multihost(coordinator: Optional[str] = None,
                   num_processes: Optional[int] = None,
                   process_id: Optional[int] = None) -> bool:
    """Bring up the JAX distributed runtime (idempotent).

    Args default from env: ``NNS_COORD`` ("host:port"),
    ``NNS_NUM_PROCS``, ``NNS_PROC_ID``. Returns True when a multi-process
    runtime was initialized, False for the single-process no-op. On TPU
    pods with no explicit configuration, ``jax.distributed.initialize()``
    auto-detects from the TPU metadata — pass nothing and it still works.
    """
    global _initialized
    if _initialized:
        return True
    coordinator = coordinator or os.environ.get("NNS_COORD")
    num_processes = num_processes or _env_int("NNS_NUM_PROCS")
    process_id = process_id if process_id is not None else _env_int("NNS_PROC_ID")

    import jax

    if coordinator is None and num_processes is None:
        # bare single-process run (CI, laptops): nothing to wire up unless
        # we're on a TPU pod where auto-detection applies. Pod-ish env vars
        # can be present on a single-chip host, so a failed auto-detect
        # degrades to the single-process no-op, not an error.
        if os.environ.get("TPU_WORKER_HOSTNAMES") or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):
            try:
                jax.distributed.initialize()
            except (ValueError, RuntimeError) as e:
                logger.info("multihost: auto-detect unavailable (%s); "
                            "running single-process", e)
                return False
            _initialized = True
            logger.info("multihost: auto-initialized (process %d of %d)",
                        jax.process_index(), jax.process_count())
            return True
        return False
    missing = [name for name, val in (
        ("NNS_COORD", coordinator), ("NNS_NUM_PROCS", num_processes),
        ("NNS_PROC_ID", process_id)) if val is None]
    if missing:
        raise ValueError(
            f"multihost: partial distributed config — set {missing} too "
            "(or none of them for a single-process run)")
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )
    _initialized = True
    logger.info("multihost: initialized process %d of %d via %s",
                jax.process_index(), jax.process_count(), coordinator)
    return True


def _env_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return int(v) if v else None


def global_mesh(axis_sizes: Optional[Dict[str, int]] = None,
                axes: Sequence[str] = AXES):
    """A dp/tp/sp Mesh over the GLOBAL device set (all hosts).

    Keeps tp/sp inside a host's addressable devices when possible so those
    collectives ride ICI while dp spans hosts over DCN — the layout rule
    of the scaling-book recipe (cheap axes inner, expensive axes outer).
    """
    import jax

    devices = jax.devices()  # global across processes after init_multihost
    sizes = axis_sizes or factor_devices(len(devices))
    local = jax.local_device_count()
    tp_sp = sizes.get("tp", 1) * sizes.get("sp", 1)
    if tp_sp > local and len(devices) > local:
        logger.warning(
            "global_mesh: tp*sp=%d exceeds local device count %d — model/"
            "sequence collectives will cross DCN; prefer dp for the "
            "cross-host axis", tp_sp, local)
    return make_mesh(devices, sizes)


def process_info() -> Dict[str, int]:
    """(process_index, process_count, local/global device counts) for
    logging and data-sharding decisions."""
    import jax

    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": jax.local_device_count(),
        "global_devices": jax.device_count(),
    }
