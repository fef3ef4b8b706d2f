"""Process bootstrap + topology discovery (TPU-native `comm_core` L0).

Reference equivalents (all in /root/reference):
  - ``g_init/g_rank/g_size/g_barriar`` — MPI_Init / MPI_Comm_rank / size /
    MPI_Barrier (common/comm_core/src/communicator.cpp:5-23). Here, process
    bootstrap is ``jax.distributed.initialize()`` (TPU slice metadata /
    coordinator discovery) and the "world" is the set of JAX devices.
  - MPI hostfiles (configs/cluster*) — replaced by device enumeration: every
    process sees the full global device list; no hostfile is needed.
  - NCCL communicator setup (ncclGetUniqueId + MPI_Bcast + ncclCommInitRank,
    communicator.cpp:43-66) — replaced by a `jax.sharding.Mesh`; XLA builds
    the ICI/DCN rings at compile time.

Rank/size semantics: the reference runs one process per GPU, so
``rank()``/``size()`` are both the process *and* accelerator world. On TPU a
process typically owns several chips, so we expose both notions:
``rank()/size()`` are process-level (use for logging, roots, file I/O) and
``device_count()`` is the accelerator world (use for sharding math). The
data-parallel degree of the default mesh equals ``device_count()``.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional, Sequence

import jax
import numpy as np

logger = logging.getLogger("dear_pytorch_tpu")

_lock = threading.Lock()
_initialized = False
_global_mesh: Optional[jax.sharding.Mesh] = None

#: Name of the data-parallel mesh axis used throughout the framework.
DP_AXIS = "dp"
#: Name of the sequence-parallel mesh axis (ring attention / Ulysses).
SP_AXIS = "sp"
#: Name of the tensor-parallel mesh axis (reserved; reference has no TP).
TP_AXIS = "tp"


def _env_flag(name: str) -> bool:
    """Boolean env parsing: '0', 'false', 'no', '' are False."""
    return os.environ.get(name, "").strip().lower() not in ("", "0", "false", "no")


def _multiprocess_env_configured() -> bool:
    """True when distributed (multi-host) bootstrap info is in the environment.

    Replaces the reference's "was I launched under mpirun" implicit contract
    (dear/horovod_mpi_cj.sh:33-41): on TPU pods, `jax.distributed.initialize`
    auto-discovers peers from slice metadata; on CPU/GPU clusters it reads the
    coordinator address from these variables.
    """
    if _env_flag("DEAR_DISABLE_DISTRIBUTED"):
        return False
    n = _env_int("JAX_NUM_PROCESSES", "DEAR_NUM_PROCESSES")
    if n is not None and n > 1:
        return True
    for k in (
        "JAX_COORDINATOR_ADDRESS",
        "DEAR_COORDINATOR_ADDRESS",
        "COORDINATOR_ADDRESS",
        "TPU_WORKER_HOSTNAMES",
        "MEGASCALE_COORDINATOR_ADDRESS",
    ):
        v = os.environ.get(k, "")
        # single-host values are not a distributed launch
        if v and v not in ("localhost", "127.0.0.1"):
            return True
    return False


def _env_int(*names: str) -> Optional[int]:
    """First set variable among ``names`` parsed as int, with an error that
    names the offending variable (a bare int() ValueError from deep inside
    bootstrap detection is undebuggable on a remote host)."""
    for k in names:
        v = os.environ.get(k, "").strip()
        if v:
            try:
                return int(v)
            except ValueError:
                raise ValueError(
                    f"{k}={v!r} is not an integer (launcher contract: "
                    "see launch/README.md)"
                ) from None
    return None


def _initialize_kwargs() -> dict:
    """Explicit bootstrap parameters from the launcher contract.

    TPU pods need none of these (`jax.distributed.initialize()`
    auto-detects peers from slice metadata); CPU/GPU clusters and the
    launch/ scripts export ``JAX_COORDINATOR_ADDRESS`` +
    ``JAX_NUM_PROCESSES`` + ``JAX_PROCESS_ID`` (or the ``DEAR_``-prefixed
    equivalents), replacing the reference's mpirun -np/-hostfile pair
    (dear/horovod_mpi_cj.sh:33-41, configs/cluster*).
    """
    kwargs: dict = {}
    np_ = _env_int("JAX_NUM_PROCESSES", "DEAR_NUM_PROCESSES")
    pid = _env_int("JAX_PROCESS_ID", "DEAR_PROCESS_ID")
    addr = os.environ.get("DEAR_COORDINATOR_ADDRESS")
    if np_ is not None and pid is not None:
        kwargs["num_processes"] = np_
        kwargs["process_id"] = pid
    if addr:
        kwargs["coordinator_address"] = addr
    return kwargs


#: Where compiled programs persist when ``JAX_COMPILATION_CACHE_DIR`` does
#: not say: one fixed, git-ignored directory inside the checkout. The path
#: is part of the cache key, so it must never carry a temp name, pid or time.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def _apply_platform_env() -> None:
    """Apply DEAR_NUM_CPU_DEVICES and place the persistent compilation
    cache, before first device contact.

    The cache lives where ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads
    that variable itself — nothing is touched here), else in
    `DEFAULT_COMPILATION_CACHE_DIR`. ``JAX_ENABLE_COMPILATION_CACHE=0``
    (JAX's own switch) turns it off; the test suite runs that way.

    The cache is keyed on the instructions' metadata too. By default JAX
    strips it from the key, so a program that differs only in its named
    scopes (or in the lines they were traced from) is served the executable
    compiled first, whose ``as_text()`` carries the old ``op_name``s: the
    join from a device trace to the step's scopes (docs/OBSERVABILITY.md)
    would then silently read another build's names.
    """
    ndev = _env_int("DEAR_NUM_CPU_DEVICES")
    if ndev:
        # raises if a backend with a different device count is already live
        jax.config.update("jax_num_cpu_devices", ndev)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          DEFAULT_COMPILATION_CACHE_DIR)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


def init(
    axis_names: Sequence[str] = (DP_AXIS,),
    mesh_shape: Optional[Sequence[int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> jax.sharding.Mesh:
    """Bootstrap the distributed runtime and build the global device mesh.

    Mirrors ``dear.init()`` (reference dear/dear_dopt.py:45-51), which runs
    MPI_Init at import time and builds NCCL communicators. Here:

      1. If launched multi-host (env-configured), join the cluster via
         ``jax.distributed.initialize()``.
      2. Build a `Mesh` over the global devices. By default this is a 1-D
         data-parallel mesh ``('dp',)`` covering every chip; pass
         ``axis_names``/``mesh_shape`` for dp×sp/tp meshes.

    Idempotent: calling again returns the existing mesh (reinit with
    different arguments requires `shutdown()` first, the analog of
    ``Communicator::reload``, communicator.cpp:75-80).
    """
    global _initialized, _global_mesh
    with _lock:
        if _initialized and _global_mesh is not None:
            return _global_mesh
        _apply_platform_env()
        # Join the cluster BEFORE any call that touches the XLA backend
        # (jax.devices/process_count would lock in a single-process world).
        if _multiprocess_env_configured():
            try:
                jax.distributed.initialize(**_initialize_kwargs())
            except Exception as exc:  # pragma: no cover - env-specific
                # A silently degraded "multi-host" run where every host
                # trains alone is worse than a crash. Allow opt-in fallback
                # for single-host debugging of multi-host launch scripts.
                if _env_flag("DEAR_ALLOW_SINGLE_PROCESS_FALLBACK"):
                    logger.error(
                        "jax.distributed.initialize() failed (%s); continuing "
                        "single-process by DEAR_ALLOW_SINGLE_PROCESS_FALLBACK",
                        exc,
                    )
                else:
                    raise RuntimeError(
                        "Distributed bootstrap env detected but "
                        "jax.distributed.initialize() failed. Call dear.init() "
                        "before any other JAX API, or set "
                        "DEAR_ALLOW_SINGLE_PROCESS_FALLBACK=1 to proceed "
                        "single-process."
                    ) from exc
        if devices is None:
            devices = jax.devices()
        ndev = len(devices)
        axis_names = tuple(axis_names)
        if mesh_shape is None:
            mesh_shape = (ndev,) + (1,) * (len(axis_names) - 1)
        mesh_shape = tuple(mesh_shape)
        if int(np.prod(mesh_shape)) != ndev:
            raise ValueError(
                f"mesh_shape {mesh_shape} does not cover {ndev} devices"
            )
        device_grid = np.asarray(devices).reshape(mesh_shape)
        _global_mesh = jax.sharding.Mesh(device_grid, axis_names)
        _initialized = True
        logger.info(
            "dear_pytorch_tpu.init: %d process(es), %d device(s), mesh %s",
            jax.process_count(), ndev, dict(zip(axis_names, mesh_shape)),
        )
        return _global_mesh


def is_initialized() -> bool:
    return _initialized


def shutdown() -> None:
    """Tear down backend state (analog of ``Communicator::destroy``,
    reference communicator.cpp:68-74). Safe to call multiple times."""
    global _initialized, _global_mesh
    with _lock:
        _initialized = False
        _global_mesh = None


def rank() -> int:
    """Process index (reference ``g_rank`` → MPI_Comm_rank,
    communicator.cpp:9-14). Use for logging roots and file I/O."""
    return jax.process_index()


def size() -> int:
    """Process count (reference ``g_size`` → MPI_Comm_size,
    communicator.cpp:15-20)."""
    return jax.process_count()


def local_rank() -> int:
    """Index of this process among the processes on the same host.

    The reference pins ``gpu = rank() % 4`` (dear/imagenet_benchmark.py:65);
    on TPU device assignment is automatic and the canonical deployment is one
    process per host, so this is 0 unless a launcher exports one of the
    standard local-rank variables."""
    for k in ("DEAR_LOCAL_RANK", "LOCAL_RANK", "OMPI_COMM_WORLD_LOCAL_RANK",
              "SLURM_LOCALID"):
        v = os.environ.get(k)
        if v is not None:
            return int(v)
    return 0


def local_size() -> int:
    """Number of processes on this host (one, unless a launcher says
    otherwise via the standard variables)."""
    for k in ("DEAR_LOCAL_SIZE", "LOCAL_WORLD_SIZE",
              "OMPI_COMM_WORLD_LOCAL_SIZE", "SLURM_NTASKS_PER_NODE"):
        v = os.environ.get(k)
        if v is not None:
            return int(v)
    return 1


def local_device_count() -> int:
    """Number of addressable (process-local) accelerator devices."""
    return jax.local_device_count()


def device_count() -> int:
    """Global accelerator world size — the data-parallel degree."""
    return jax.device_count()


def barrier() -> None:
    """Block until every process reaches this point (reference ``g_barriar``
    [sic] → MPI_Barrier, communicator.cpp:21-23)."""
    if jax.process_count() > 1:  # pragma: no cover - multi-host only
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices("dear_pytorch_tpu.barrier")


# Keep the reference's misspelling available for drop-in parity
# (comm_core.cpp:15 exports `barriar`).
barriar = barrier


def global_mesh() -> jax.sharding.Mesh:
    """The framework-wide mesh. Lazily creates the default 1-D dp mesh if
    `init()` has not been called (mirrors the reference's import-time
    ``comm_init()`` side effect, dear/dear_dopt.py:37 — but lazily, so simply
    importing the package never touches devices)."""
    if _global_mesh is None:
        return init()
    return _global_mesh


def set_global_mesh(mesh: jax.sharding.Mesh) -> None:
    """Install a custom mesh (used by tests and multi-axis configurations)."""
    global _global_mesh, _initialized
    with _lock:
        _global_mesh = mesh
        _initialized = True


def dp_size(mesh: Optional[jax.sharding.Mesh] = None) -> int:
    """Data-parallel degree of the (global) mesh."""
    mesh = mesh or global_mesh()
    return mesh.shape[DP_AXIS]
