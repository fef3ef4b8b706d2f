"""Collective operations — XLA-native equivalents of `comm_core`'s NCCL ops.

Two API layers:

1. **Per-shard functions** (``*_`` free functions taking ``axis_name``) — used
   *inside* ``jax.shard_map`` regions, i.e. inside compiled train steps. These
   are where the DeAR pipeline actually runs; XLA lowers them to async
   ReduceScatter/AllGather/AllReduce/CollectivePermute over ICI/DCN and its
   latency-hiding scheduler overlaps them with compute (replacing the
   reference's CUDA side streams, communicator.cpp:43-66).

2. **Stacked-array helpers** (`spmd_call`) — run a per-shard function eagerly
   over a mesh on a "stacked" array of shape ``(world, ...)`` whose leading
   axis is sharded one slice per device. This gives each device its own
   distinct input, mirroring the reference's per-rank tensors in
   common/comm_core/tests/test_comm.py, and powers the eager `Communicator`
   mirror and the collective microbenchmarks.

Reference mapping (common/comm_core/src/communicator.cpp):
  reduce           :130-138  -> `reduce`
  bcast            :140-155  -> `broadcast`
  reduceScatter    :157-169  -> `reduce_scatter`
  allGather        :171-183  -> `all_gather`
  allReduce        :237-242  -> `all_reduce`
  allReduceRB      :185-196  -> `all_reduce_rb`
  allReduceRSAG    :198-235  -> `all_reduce_rsag` (incl. padding semantics)
  multiBcast       :244-285  -> `multi_bcast`
  sendrecv         :287-304  -> `send_recv` / `permute`
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from dear_pytorch_tpu.comm import backend
from dear_pytorch_tpu.comm.backend import DP_AXIS

# ---------------------------------------------------------------------------
# Padding helpers (reference pads inside allReduceRSAG, communicator.cpp:204-213
# and in the optimizer's fusion buffers, dear/dear_dopt.py:186-194).
# ---------------------------------------------------------------------------


def padded_length(n: int, world: int) -> int:
    """Smallest multiple of `world` that is >= n (0 stays 0)."""
    if n == 0:
        return 0
    return ((n + world - 1) // world) * world


def pad_to_multiple(x: jax.Array, world: int) -> jax.Array:
    """Zero-pad a flat vector so reduce-scatter shards evenly.

    Mirrors `_get_pad_tensor` (reference dear/dear_dopt.py:186-194) and the
    in-collective padding of allReduceRSAG (communicator.cpp:204-213).
    """
    n = x.shape[0]
    target = padded_length(n, world)
    if target == n:
        return x
    return jnp.concatenate([x, jnp.zeros((target - n,), dtype=x.dtype)])


#: lanes of a TPU tile
LANES = 128


def lanes(x: jax.Array) -> jax.Array:
    """A flat buffer as ``[len / 128, 128]``: the layout in which XLA:TPU
    tiles a bucket, where a flat operand is tiled 1-D and its
    reduce-scatter padded into an all-reduce (`ops.fusion.bucket_length`)."""
    return x.reshape(-1, LANES)


# ---------------------------------------------------------------------------
# Per-shard collectives (use inside shard_map)
# ---------------------------------------------------------------------------


def all_reduce(x: jax.Array, axis_name: str = DP_AXIS) -> jax.Array:
    """Sum across the axis (ncclAllReduce, communicator.cpp:237-242)."""
    return lax.psum(x, axis_name)


def all_reduce_mean(x: jax.Array, axis_name: str = DP_AXIS) -> jax.Array:
    return lax.pmean(x, axis_name)


def reduce_scatter(x: jax.Array, axis_name: str = DP_AXIS) -> jax.Array:
    """Sum-reduce-scatter along dim 0 (ncclReduceScatter, :157-169).

    ``x.shape[0]`` must be divisible by the axis size — use
    `pad_to_multiple` first (the fusion engine pre-pads its buffers).
    """
    return lax.psum_scatter(x, axis_name, scatter_dimension=0, tiled=True)


def all_gather(x: jax.Array, axis_name: str = DP_AXIS) -> jax.Array:
    """Concatenate shards along dim 0 (ncclAllGather, :171-183)."""
    return lax.all_gather(x, axis_name, axis=0, tiled=True)


def reduce(x: jax.Array, root: int = 0, axis_name: str = DP_AXIS) -> jax.Array:
    """Sum on `root`; other ranks keep their input (ncclReduce, :130-138,
    whose non-root recv buffers are left untouched in-place)."""
    total = lax.psum(x, axis_name)
    idx = lax.axis_index(axis_name)
    return jnp.where(idx == root, total, x)


def broadcast(x: jax.Array, root: int = 0, axis_name: str = DP_AXIS) -> jax.Array:
    """Every rank receives root's value (ncclBroadcast, :140-155).

    Lowered as a single masked all-reduce — one collective, same cost class
    as NCCL broadcast on a ring.
    """
    idx = lax.axis_index(axis_name)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name)


def all_reduce_rsag(x: jax.Array, axis_name: str = DP_AXIS) -> jax.Array:
    """Decomposed all-reduce = reduce-scatter → all-gather (:198-235).

    Handles arbitrary flat length by internal padding, exactly like the
    reference pads to a multiple of world size and strips afterwards.
    This is the decomposition whose two halves DeAR schedules into different
    parts of the training step.
    """
    world = lax.axis_size(axis_name)
    orig_shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    padded = pad_to_multiple(flat, world)
    shard = reduce_scatter(padded, axis_name)
    full = all_gather(shard, axis_name)
    return full[:n].reshape(orig_shape)


def all_reduce_rb(
    x: jax.Array, root: int = 0, axis_name: str = DP_AXIS
) -> jax.Array:
    """Decomposed all-reduce = reduce → broadcast (:185-196)."""
    reduced = reduce(x, root, axis_name)
    return broadcast(reduced, root, axis_name)


def permute(
    x: jax.Array, perm: Sequence[tuple[int, int]], axis_name: str = DP_AXIS
) -> jax.Array:
    """Point-to-point pattern as a collective-permute.

    The reference's ``sendrecv`` (ncclGroupStart/ncclSend/ncclRecv/GroupEnd,
    communicator.cpp:287-304) expresses pairwise exchange; on TPU the native
    primitive is `lax.ppermute` over ICI neighbours. `perm` is a list of
    (source, destination) pairs; ranks not named as a destination receive
    zeros.
    """
    return lax.ppermute(x, axis_name, perm=list(perm))


def send_recv(x: jax.Array, peer_of: Sequence[int], axis_name: str = DP_AXIS) -> jax.Array:
    """Pairwise exchange: rank i sends `x` to ``peer_of[i]`` and receives from
    whichever rank names it as peer. Mirrors the gTop-k usage of sendrecv
    (reference wfbp/dopt.py:76-78)."""
    perm = [(src, dst) for src, dst in enumerate(peer_of)]
    return permute(x, perm, axis_name)


def multi_bcast(
    tensors: Sequence[jax.Array],
    fn: Callable[[jax.Array], jax.Array],
    min_elems: int = 512 * 512,
    axis_name: str = DP_AXIS,
) -> list[jax.Array]:
    """Round-robin owner computes `fn` then broadcasts (:244-285).

    Tensors with fewer than `min_elems` elements are computed locally by
    every rank (the reference's ≥512×512 size filter); large tensors are
    assigned owners round-robin, each owner computes `fn(t)` and the result
    is broadcast. In SPMD form the non-owner branch contributes zeros to a
    masked all-reduce; XLA dead-code-eliminates the unused local `fn` where
    it can. (KFAC-era utility; kept for API completeness.)
    """
    world = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    out: list[jax.Array] = []
    owner_counter = 0
    for t in tensors:
        if t.size < min_elems:
            out.append(fn(t))
            continue
        owner = owner_counter % world
        owner_counter += 1
        local = fn(t)
        masked = jnp.where(idx == owner, local, jnp.zeros_like(local))
        out.append(lax.psum(masked, axis_name))
    return out


# ---------------------------------------------------------------------------
# Eager SPMD execution over stacked arrays
# ---------------------------------------------------------------------------

_spmd_cache: dict = {}


def spmd_call(
    fn: Callable,
    *stacked: jax.Array,
    mesh: Optional[jax.sharding.Mesh] = None,
    axis_name: str = DP_AXIS,
):
    """Run a per-shard function over the mesh on stacked `(world, ...)` inputs.

    Each device receives slice ``stacked[i]`` (with the leading world axis
    squeezed away), runs `fn`, and the per-device results are restacked. This
    reproduces the reference's eager per-rank collective calls
    (test_comm.py) without mpirun: world size = mesh dp size.
    """
    mesh = mesh or backend.global_mesh()
    key = (id(mesh), fn, axis_name)
    wrapped = _spmd_cache.get(key)
    if wrapped is None:
        spec = jax.P(axis_name)

        def per_device(*args):
            squeezed = [a.reshape(a.shape[1:]) for a in args]
            res = fn(*squeezed)
            return jax.tree.map(lambda r: jnp.expand_dims(r, 0), res)

        wrapped = jax.jit(
            jax.shard_map(
                per_device,
                mesh=mesh,
                in_specs=spec,
                out_specs=spec,
            )
        )
        _spmd_cache[key] = wrapped
    mesh_spec = jax.sharding.NamedSharding(mesh, jax.P(axis_name))
    placed = [jax.device_put(jnp.asarray(a), mesh_spec) for a in stacked]
    return wrapped(*placed)


# ---------------------------------------------------------------------------
# Host-level metric averaging (reference dear_dopt.py:546-549 `allreduce`)
# ---------------------------------------------------------------------------


def allreduce(x, average: bool = True):
    """Average a host-side metric across processes.

    The reference uses a blocking NCCL allReduce + divide for metric
    averaging (dear/dear_dopt.py:546-549; examples/mnist/pytorch_mnist.py:
    112-116). In this framework, per-device metrics inside a train step are
    already reduced with `lax.pmean`; this helper covers host-level values in
    multi-process (multi-host) runs, and is the identity in single-process
    runs where the in-step reduction has already seen every shard.
    """
    if jax.process_count() == 1:
        return x
    from jax.experimental import multihost_utils  # pragma: no cover

    gathered = multihost_utils.process_allgather(jnp.asarray(x))
    total = gathered.sum(axis=0)
    return total / jax.process_count() if average else total


def host_allgather(x):
    """Host-level allgather: every process's value stacked on a new
    leading axis of length ``process_count`` (index-ordered). The
    single-process fast path never touches `jax.distributed`. This is the
    host collective the resilience cluster layer
    (`resilience.cluster.AllgatherTransport`) builds its consensus
    exchanges on."""
    import numpy as np

    if jax.process_count() == 1:
        return np.asarray(x)[None, ...]
    from jax.experimental import multihost_utils  # pragma: no cover

    return np.asarray(multihost_utils.process_allgather(jnp.asarray(x)))
