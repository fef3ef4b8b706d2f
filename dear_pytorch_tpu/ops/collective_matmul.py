"""Fused computation-collective Pallas TPU kernels for the DeAR hot path.

The bucket-granular schedule (`parallel/dear.py`) launches whole-bucket
reduce-scatter / all-gather collectives and delegates hiding to XLA's
latency-hiding scheduler. FLUX (arxiv 2406.06858) and T3 (arxiv
2401.16677) show that *tile-granularity* fusion of the collective into the
adjacent compute kernel beats scheduler-level overlap; the TPU-native
analog is a Pallas kernel driving the ring itself with async remote copies
(`pltpu.make_async_remote_copy`), so each RDMA hop overlaps the previous
tile's compute inside ONE kernel instead of across XLA-scheduled ops.
Three kernel families, wired in as ``mode="dear-fused"``:

  - `ring_all_gather` — the per-bucket parameter gather as a ring of
    remote copies: chunk t+1 streams while chunk t lands in the output
    (replaces ``lax.all_gather``; bit-identical output — pure data
    movement in ring order).
  - `fused_reduce_scatter_update` — the per-bucket gradient reduce-scatter
    fused with the optimizer-update epilogue: each ring step RDMAs the
    partial-sum tile to the right neighbor, accumulates the incoming tile
    in fp32, and the FINAL step applies the optimizer update to the owned
    shard in the same kernel — the update math is the *traced*
    `ShardOptimizer.update` (fused SGD / AdamW, ops/fused_sgd.py), so
    given the same reduced gradient the epilogue is bit-identical to the
    unfused update.
  - `allgather_matmul` — a ring collective-matmul ``y = x @ gather(w)``
    over a row-sharded weight: compute starts on the LOCAL parameter
    shard while remote shards stream in. Differentiable (custom VJP: dx
    re-streams the shards; dw is a second ring that fuses the
    ``xᵀ·dy`` tile matmul into the reduce-scatter accumulation). Wired
    into the BERT/GPT QKV and MLP projection paths via the models'
    ``projection_impl`` hook (`make_ring_projection_impl`).

Interpret-mode status (the honest part): every kernel here — including
the remote copies and their semaphores — runs under ``interpret=True`` on
the CPU-emulated multi-device mesh, so tier-1 exercises the exact ring
schedule, DMA slot protocol, and epilogue tracing that would run on chip
(tests/test_collective_matmul.py asserts agreement with the unfused
'dear' schedule). What interpret mode does NOT validate, per the
`ops/flash_attention.py` precedent: Mosaic memory-layout efficiency of
the flat rank-2 buffers, VMEM ceilings for large buckets (the epilogue
holds the whole shard resident — on chip, keep ``threshold_mb`` such
that ~5 shard-sized fp32 buffers fit in 16 MB VMEM, i.e. buckets
≲ 6 MB/world·5, or tile the epilogue), and on-chip RDMA timing. See
docs/KERNELS.md for the ring schedule diagrams and the caveat list.

Reduction-order note: the ring accumulates partial sums in a fixed ring
order with fp32 accumulation (never worse than the wire dtype), which is
a DIFFERENT floating-point association than XLA's ``psum_scatter``.
'dear-fused' therefore matches 'dear' at dtype-appropriate tolerance,
not bitwise; the all-gather leg and the update epilogue are exact.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dear_pytorch_tpu.observability import tracer as _telemetry


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


# Trace-time kernel-construction telemetry below counts one per pallas
# ring program traced into a step program, NOT per executed step —
# step-cadence counters live in parallel/dear.py's ``step()``. Counter
# names stay literal at every ``.count()`` call site so the
# docs/OBSERVABILITY.md audit (tests/test_observability.py) can scan them.


def _ring_neighbors(axis_name):
    world = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    return world, my


# ---------------------------------------------------------------------------
# the shared ring transport: double-buffered hops, DMA/compute overlap,
# receiver->sender flow control
# ---------------------------------------------------------------------------
#
# Hop h (1..W-1) moves comm[(h-1)%2] on the sender into comm[h%2] on its
# right neighbor. Two comm slots alternate parity; the hop that will
# overwrite a slot is always two hops after the one that filled it, and
# REGULAR "capacity" semaphores give the writer proof the reader is done:
# after a device finishes consuming slot s (local compute done AND its own
# forwarding send has drained the slot), it signals cap[s] on its LEFT
# neighbor — the only device that writes into it. The priming signals at
# kernel entry double as the neighbor barrier: no remote write can land
# before its target device has entered the kernel. Credits are balanced
# exactly (prime 1 + slot-0 release + rounds 1..W-3 = W-1 signals against
# W-1 waits), so the semaphores drain to zero by kernel end.
#
# Interpret mode cannot execute remote semaphore signals (NotImplementedError:
# "Remote signal not implemented"), so the capacity protocol is the one
# piece of the ring that only the CHIP path runs — the interpreter
# delivers each emulated copy atomically at its wait point, so there is
# no concurrent DMA to race. Stated in docs/KERNELS.md's caveat list.


def _hop(comm, send_sem, recv_sem, src_slot, dst_slot, right):
    return pltpu.make_async_remote_copy(
        src_ref=comm.at[src_slot], dst_ref=comm.at[dst_slot],
        send_sem=send_sem.at[src_slot], recv_sem=recv_sem.at[dst_slot],
        device_id=right, device_id_type=pltpu.DeviceIdType.LOGICAL,
    )


def _ring_rounds(axis_name, world, comm, send_sem, recv_sem, cap_sem, *,
                 fill0, consume0=None, prepare=None, combine=None,
                 consume=None):
    """Drive the W-1 rightward hops over double-buffered ``comm`` slots.

    Round r (1..W-1) handles the chunk arriving in ``comm[r%2]``:

      prepare(r)      independent local work for round r (chunk DMA, a
                      contribution matmul) — issued while hop r's RDMA is
                      still in flight
      combine(r, s)   after the receive: fold prepare's result into
                      ``comm[s]`` (reduce-scatter-shaped rings); hop r+1
                      is issued AFTER combine so the payload carries the
                      accumulation
      consume(r, s)   read ``comm[s]`` (copy-out / matmul); for
                      forwarding rings (no combine) this runs with hop
                      r+1's send already in flight — the compute/RDMA
                      overlap these kernels exist for

    ``fill0`` writes the hop-1 payload into ``comm[0]``; ``consume0`` is
    the round-0 local compute, overlapped with hop 1 (the collective
    matmul's compute-on-the-local-shard-first). ``cap_sem=None`` skips
    the flow-control protocol (the interpret path — see section comment).
    """
    my = lax.axis_index(axis_name)
    left = lax.rem(my + world - 1, world)
    right = lax.rem(my + 1, world)

    def signal_left(slot):
        pltpu.semaphore_signal(
            cap_sem.at[slot], inc=1, device_id=left,
            device_id_type=pltpu.DeviceIdType.LOGICAL)

    fill0()
    if cap_sem is not None:
        signal_left(1)                     # prime: my slot 1 is writable
        pltpu.semaphore_wait(cap_sem.at[1], 1)   # right entered + ready
    pending = _hop(comm, send_sem, recv_sem, 0, 1, right)
    pending.start()
    if consume0 is not None:
        consume0()                         # round-0 compute ∥ hop 1
    pending.wait_send()                    # slot 0 drained by my own send
    if cap_sem is not None and world >= 3:
        signal_left(0)                     # ...only now may left's hop 2 land

    for r in range(1, world):
        s = r % 2
        if prepare is not None:
            prepare(r)                     # ∥ hop r's RDMA
        _hop(comm, send_sem, recv_sem, (r - 1) % 2, s, right).wait_recv()
        if combine is not None:
            combine(r, s)
        nxt = None
        if r < world - 1:
            if cap_sem is not None:
                pltpu.semaphore_wait(cap_sem.at[(r + 1) % 2], 1)
            nxt = _hop(comm, send_sem, recv_sem, s, (r + 1) % 2, right)
            nxt.start()
        if consume is not None:
            consume(r, s)                  # ∥ hop r+1's send
        if nxt is not None:
            nxt.wait_send()
        if cap_sem is not None and 1 <= r <= world - 3:
            signal_left(s)                 # slot s free for left's hop r+2


def _ring_scratch(slots_shape, slots_dtype):
    """comm slots + the ring's semaphore set. The REGULAR capacity pair is
    allocated on every backend (uniform kernel signature) but only USED on
    chip (`_ring_rounds` with cap_sem=None skips it under interpret)."""
    return [
        pltpu.VMEM((2,) + tuple(slots_shape), slots_dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.REGULAR((2,)),
        pltpu.SemaphoreType.DMA(()),       # local-copy semaphore
    ]


def _cap(cap_sem):
    return None if _interpret() else cap_sem


# ---------------------------------------------------------------------------
# ring all-gather
# ---------------------------------------------------------------------------


def _ag_kernel(x_ref, o_ref, comm, send_sem, recv_sem, cap_sem, copy_sem,
               *, world: int, axis_name):
    my = lax.axis_index(axis_name)

    def copy(src, dst):
        cp = pltpu.make_async_copy(src, dst, copy_sem)
        cp.start()
        cp.wait()

    def fill0():
        copy(x_ref, comm.at[0])

    def consume0():
        copy(comm.at[0], o_ref.at[my])

    def consume(r, s):
        copy(comm.at[s], o_ref.at[lax.rem(my - r + world, world)])

    _ring_rounds(axis_name, world, comm, send_sem, recv_sem, _cap(cap_sem),
                 fill0=fill0, consume0=consume0, consume=consume)


def ring_all_gather(shard: jax.Array, axis_name) -> jax.Array:
    """Pallas ring all-gather of a flat shard: ``(n,) -> (world*n,)``,
    identical to ``lax.all_gather(shard, axis, tiled=True)`` (chunk order =
    axis order; data movement only, so bitwise). Call inside shard_map;
    the ring address space is the axis' LOGICAL device ids, so the axis
    must span the whole mesh (checked by `parallel/dear.py`)."""
    world = lax.axis_size(axis_name)
    n = shard.shape[0]
    if world == 1:
        return shard
    tr = _telemetry.get_tracer()
    if tr.enabled:
        tr.count("kernel.ring_ag_builds")
        tr.event("kernel.ring_ag_build", elements=n, world=world)
    out = pl.pallas_call(
        functools.partial(_ag_kernel, world=world, axis_name=axis_name),
        out_shape=jax.ShapeDtypeStruct((world, n), shard.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=_ring_scratch((n,), shard.dtype),
        interpret=_interpret(),
    )(shard)
    return out.reshape(world * n)


# ---------------------------------------------------------------------------
# fused reduce-scatter + optimizer-update epilogue
# ---------------------------------------------------------------------------
#
# Ring reduce-scatter with the partial sums traveling in fp32; device i's
# partial starts as its LOCAL copy of chunk (i-1) mod W, and after the
# receive at step t holds chunk (i-1-t) mod W, to which it adds its local
# copy.  At t = W-1 the received partial is chunk i itself, covering every
# other device — the final local add plus the optimizer update run in the
# same kernel invocation (the epilogue).  The optimizer math is the traced
# `ShardOptimizer.update`: elementwise by contract, so applying it to the
# shard equals the unfused full-buffer update exactly.


def _flatten_opt_state(opt_state, shard_size: int):
    """(vector_leaves, scalar_leaves, treedef, is_vector_mask).

    Vector leaves are shard-shaped 1-D arrays (momentum, exp_avg, ...);
    scalar leaves are 0-d (adam step count, momentum 'initialized' flag).
    Anything else means the optimizer cannot be fused — raise with the
    reason rather than mis-updating."""
    leaves, treedef = jax.tree_util.tree_flatten(opt_state)
    vecs, scalars, mask = [], [], []
    for leaf in leaves:
        nd = getattr(leaf, "ndim", None)
        if nd == 1 and leaf.shape[0] == shard_size:
            vecs.append(leaf)
            mask.append(True)
        elif nd == 0:
            scalars.append(leaf)
            mask.append(False)
        else:
            raise ValueError(
                "dear-fused can only fuse optimizers whose state leaves "
                "are shard-shaped vectors or scalars; got a leaf of shape "
                f"{getattr(leaf, 'shape', None)} (shard size {shard_size})."
                " LayerwiseShardOptimizer (LAMB) needs cross-shard psums "
                "and cannot run inside the epilogue kernel — use "
                "mode='dear'."
            )
    return vecs, scalars, treedef, mask


def _scalar_wire(x):
    """Scalars travel as (1, 1) SMEM refs; bools as int32 (SMEM dtypes)."""
    v = jnp.asarray(x)
    if v.dtype == jnp.bool_:
        v = v.astype(jnp.int32)
    return v.reshape(1, 1)


def _rs_update_kernel(*refs, world: int, mean_world: int, optimizer,
                      treedef, mask, scalar_dtypes, n_vec: int,
                      n_scalar: int, has_step: bool, axis_name):
    """refs layout:
    in : g(any, (world, ss)), p(vmem (1, ss)), vec_state... (vmem),
         scalar_state... (smem (1,1)), [step (smem)]
    out: new_p, new_vec..., new_scalar...
    scratch: comm (2, ss) f32 + ring semaphores (`_ring_scratch`),
             work (2, ss) g-dtype (double-buffered local-chunk prefetch)
    """
    n_in = 2 + n_vec + n_scalar + (1 if has_step else 0)
    n_out = 1 + n_vec + n_scalar
    ins, outs = refs[:n_in], refs[n_in:n_in + n_out]
    comm, send_sem, recv_sem, cap_sem, copy_sem, work = refs[n_in + n_out:]
    g_ref, p_ref = ins[0], ins[1]
    vec_refs = ins[2:2 + n_vec]
    scalar_refs = ins[2 + n_vec:2 + n_vec + n_scalar]
    step_ref = ins[-1] if has_step else None

    my = lax.axis_index(axis_name)
    # round r accumulates my local copy of chunk (my - 1 - r) mod world
    loads = {}

    def chunk_load(r, wslot):
        j = lax.rem(my + 2 * world - 1 - r, world)
        cp = pltpu.make_async_copy(g_ref.at[j], work.at[wslot], copy_sem)
        cp.start()
        return cp

    def fill0():
        chunk_load(0, 0).wait()
        comm[0] = work[0].astype(jnp.float32)

    def prepare(r):
        # prefetch round r's local chunk while hop r's RDMA is in flight
        loads[r] = chunk_load(r, r % 2)

    def combine(r, s):
        loads.pop(r).wait()
        comm[s] = comm[s] + work[r % 2].astype(jnp.float32)

    _ring_rounds(axis_name, world, comm, send_sem, recv_sem, _cap(cap_sem),
                 fill0=fill0, prepare=prepare, combine=combine)

    # ---- epilogue: the fused optimizer update on the owned shard --------
    param = p_ref[0]
    grad = (comm[lax.rem(world - 1, 2)] / mean_world).astype(param.dtype)
    vec_vals = [r[0] for r in vec_refs]
    scalar_vals = []
    for r, dt in zip(scalar_refs, scalar_dtypes):
        v = r[0, 0]
        scalar_vals.append(v != 0 if dt == jnp.bool_ else v)
    leaves, vi, si = [], 0, 0
    for is_vec in mask:
        if is_vec:
            leaves.append(vec_vals[vi])
            vi += 1
        else:
            leaves.append(scalar_vals[si])
            si += 1
    state = jax.tree_util.tree_unflatten(treedef, leaves)
    kw = {"step": step_ref[0, 0]} if has_step else {}
    new_param, new_state = optimizer.update(grad, state, param, **kw)
    new_leaves = jax.tree_util.tree_flatten(new_state)[0]

    outs[0][0] = new_param
    vi, si = 0, 0
    for leaf, is_vec in zip(new_leaves, mask):
        if is_vec:
            outs[1 + vi][0] = leaf
            vi += 1
        else:
            v = jnp.asarray(leaf)
            if v.dtype == jnp.bool_:
                v = v.astype(jnp.int32)
            outs[1 + n_vec + si][0, 0] = v
            si += 1


def fused_reduce_scatter_update(
    gbuf: jax.Array,
    param_shard: jax.Array,
    opt_state,
    optimizer,
    axis_name,
    *,
    mean_world: int,
    step: Optional[jax.Array] = None,
):
    """Reduce-scatter ``gbuf`` (flat padded bucket gradient, every device's
    full copy) over ``axis_name`` AND apply ``optimizer.update`` to the
    owned shard, in one Pallas ring kernel. Returns ``(new_param_shard,
    new_opt_state)`` with exactly the unfused pytree structure.

    ``mean_world`` divides the ring sum (the gradient-averaging axis
    product, `parallel/dear.py`); ``step`` must be the replicated step
    scalar iff ``optimizer.needs_step``."""
    world = lax.axis_size(axis_name)
    ss = param_shard.shape[0]
    has_step = step is not None
    if world == 1:
        grad = (gbuf / mean_world).astype(param_shard.dtype)
        kw = {"step": step} if has_step else {}
        return optimizer.update(grad, opt_state, param_shard, **kw)
    if gbuf.shape[0] != world * ss:
        raise ValueError(
            f"gradient buffer length {gbuf.shape[0]} != world*shard "
            f"({world}x{ss}) — pass the padded bucket buffer"
        )
    vecs, scalars, treedef, mask = _flatten_opt_state(opt_state, ss)
    scalar_dtypes = [jnp.asarray(s).dtype for s in scalars]
    tr = _telemetry.get_tracer()
    if tr.enabled:
        tr.count("kernel.fused_rs_builds")
        tr.event("kernel.fused_rs_build", elements=world * ss, world=world,
                 opt_leaves=len(mask))

    kernel = functools.partial(
        _rs_update_kernel, world=world, mean_world=mean_world,
        optimizer=optimizer, treedef=treedef, mask=mask,
        scalar_dtypes=scalar_dtypes, n_vec=len(vecs), n_scalar=len(scalars),
        has_step=has_step, axis_name=axis_name,
    )
    in_specs = (
        [pl.BlockSpec(memory_space=pl.ANY),      # gbuf (chunk rows)
         pl.BlockSpec(memory_space=pltpu.VMEM)]     # param
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * len(vecs)
        + [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(scalars)
        + ([pl.BlockSpec(memory_space=pltpu.SMEM)] if has_step else [])
    )
    out_shape = (
        [jax.ShapeDtypeStruct((1, ss), param_shard.dtype)]
        + [jax.ShapeDtypeStruct((1, ss), v.dtype) for v in vecs]
        + [jax.ShapeDtypeStruct((1, 1),
                                jnp.int32 if dt == jnp.bool_ else dt)
           for dt in scalar_dtypes]
    )
    out_specs = (
        [pl.BlockSpec(memory_space=pltpu.VMEM)] * (1 + len(vecs))
        + [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(scalars)
    )
    args = (
        [gbuf.reshape(world, ss), param_shard.reshape(1, ss)]
        + [v.reshape(1, ss) for v in vecs]
        + [_scalar_wire(s) for s in scalars]
        + ([_scalar_wire(step)] if has_step else [])
    )
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=_ring_scratch((ss,), jnp.float32)
        + [pltpu.VMEM((2, ss), gbuf.dtype)],
        interpret=_interpret(),
    )(*args)
    new_param = outs[0].reshape(ss)
    new_vecs = [o.reshape(ss) for o in outs[1:1 + len(vecs)]]
    new_scalars = []
    for o, dt in zip(outs[1 + len(vecs):], scalar_dtypes):
        v = o.reshape(())
        new_scalars.append(v != 0 if dt == jnp.bool_ else v)
    leaves, vi, si = [], 0, 0
    for is_vec in mask:
        if is_vec:
            leaves.append(new_vecs[vi])
            vi += 1
        else:
            leaves.append(new_scalars[si])
            si += 1
    return new_param, jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# ring collective-matmul: y = x @ all_gather(w_shard), compute-first
# ---------------------------------------------------------------------------
#
# w is ROW-sharded over the axis (input-feature dim): w_shard = rows
# [my*kc, (my+1)*kc) of the full (K, N) weight.  The forward starts the
# MXU on the LOCAL shard while the next shard streams in:
#
#   acc  = x[:, my·kc : (my+1)·kc] @ w_local          (t = 0, no comm)
#   t:     RDMA w-chunk right; acc += x[:, j·kc:(j+1)·kc] @ chunk,
#          j = (my - t) mod W  (the chunk originated t hops left)
#
# Backward re-streams the shards for dx (dx[:, j] = dy @ w_jᵀ) and runs a
# second ring for dw that fuses the xᵀ·dy tile matmul into the
# reduce-scatter accumulation — dw_shard arrives CROSS-DEVICE REDUCED, so
# the caller's scatter into the full-weight cotangent composes exactly
# with the bucket reduce-scatter (sum over devices = full reduced grad).


def _cm_fwd_kernel(x_ref, w_ref, o_ref, comm, send_sem, recv_sem, cap_sem,
                   copy_sem, xbuf, acc, *, world: int, kc: int,
                   axis_name):
    my = lax.axis_index(axis_name)

    def xcols(j):
        cp = pltpu.make_async_copy(
            x_ref.at[:, pl.ds(j * kc, kc)], xbuf, copy_sem)
        cp.start()
        cp.wait()
        return xbuf[...].astype(jnp.float32)

    def fill0():
        comm[0] = w_ref[...]

    def consume0():
        # the MXU starts on the LOCAL shard while hop 1 streams
        acc[...] = jax.lax.dot_general(
            xcols(my), w_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    def consume(r, s):
        # chunk of owner (my - r) mod world; hop r+1 already in flight
        acc[...] = acc[...] + jax.lax.dot_general(
            xcols(lax.rem(my - r + world, world)),
            comm[s].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    _ring_rounds(axis_name, world, comm, send_sem, recv_sem, _cap(cap_sem),
                 fill0=fill0, consume0=consume0, consume=consume)
    o_ref[...] = acc[...].astype(o_ref.dtype)


def _cm_dx_kernel(dy_ref, w_ref, dx_ref, comm, send_sem, recv_sem, cap_sem,
                  copy_sem, buf, *, world: int, kc: int, axis_name):
    my = lax.axis_index(axis_name)

    def emit(j, chunk):
        buf[...] = jax.lax.dot_general(
            dy_ref[...].astype(jnp.float32), chunk.astype(jnp.float32),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ).astype(buf.dtype)
        cp = pltpu.make_async_copy(
            buf, dx_ref.at[:, pl.ds(j * kc, kc)], copy_sem)
        cp.start()
        cp.wait()

    def fill0():
        comm[0] = w_ref[...]

    def consume0():
        emit(my, w_ref[...])

    def consume(r, s):
        emit(lax.rem(my - r + world, world), comm[s])

    _ring_rounds(axis_name, world, comm, send_sem, recv_sem, _cap(cap_sem),
                 fill0=fill0, consume0=consume0, consume=consume)


def _cm_dw_kernel(x_ref, dy_ref, dw_ref, comm, send_sem, recv_sem, cap_sem,
                  copy_sem, xbuf, contrib_buf, *, world: int, kc: int,
                  axis_name):
    my = lax.axis_index(axis_name)

    def contrib(r):
        # round r's contribution is my local xᵀ·dy block for chunk
        # (my - 1 - r) mod world — independent of the incoming partial,
        # so it computes while hop r's RDMA is in flight
        j = lax.rem(my + 2 * world - 1 - r, world)
        cp = pltpu.make_async_copy(
            x_ref.at[:, pl.ds(j * kc, kc)], xbuf, copy_sem)
        cp.start()
        cp.wait()
        return jax.lax.dot_general(
            xbuf[...].astype(jnp.float32), dy_ref[...].astype(jnp.float32),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )

    def fill0():
        comm[0] = contrib(0)

    def prepare(r):
        contrib_buf[...] = contrib(r)

    def combine(r, s):
        comm[s] = comm[s] + contrib_buf[...]

    _ring_rounds(axis_name, world, comm, send_sem, recv_sem, _cap(cap_sem),
                 fill0=fill0, prepare=prepare, combine=combine)
    dw_ref[...] = comm[lax.rem(world - 1, 2)].astype(dw_ref.dtype)


def _cm_fwd_call(x, w_shard, axis_name):
    world = lax.axis_size(axis_name)
    m, k = x.shape
    kc, n = w_shard.shape
    out_dtype = jnp.result_type(x.dtype, w_shard.dtype)
    tr = _telemetry.get_tracer()
    if tr.enabled:
        tr.count("kernel.cm_builds")
        tr.event("kernel.cm_build", m=m, k=k, n=n, world=world)
    return pl.pallas_call(
        functools.partial(_cm_fwd_kernel, world=world, kc=kc,
                          axis_name=axis_name),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=_ring_scratch((kc, n), w_shard.dtype) + [
            pltpu.VMEM((m, kc), x.dtype),
            pltpu.VMEM((m, n), jnp.float32),
        ],
        interpret=_interpret(),
    )(x, w_shard)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def allgather_matmul(x: jax.Array, w_shard: jax.Array, axis_name):
    """``x @ all_gather(w_shard over rows)`` as one ring collective-matmul
    Pallas kernel: the MXU starts on the local shard while remote shards
    stream via async remote copies. ``x``: [M, K] (replicated per-device
    activations), ``w_shard``: [K/world, N] — this device's contiguous
    row block in axis order. fp32 accumulation; output dtype =
    ``result_type(x, w)``. Differentiable; call inside shard_map."""
    world = lax.axis_size(axis_name)
    if world == 1:
        return jax.lax.dot_general(
            x, w_shard, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(jnp.result_type(x.dtype, w_shard.dtype))
    return _cm_fwd_call(x, w_shard, axis_name)


def _allgather_matmul_fwd(x, w_shard, axis_name):
    return allgather_matmul(x, w_shard, axis_name), (x, w_shard)


def _allgather_matmul_bwd(axis_name, res, dy):
    x, w_shard = res
    world = lax.axis_size(axis_name)
    m, k = x.shape
    kc, n = w_shard.shape
    if world == 1:
        dx = jax.lax.dot_general(
            dy, w_shard, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(x.dtype)
        dw = jax.lax.dot_general(
            x, dy, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(w_shard.dtype)
        return dx, dw
    tr = _telemetry.get_tracer()
    if tr.enabled:
        tr.count("kernel.cm_grad_builds")
        tr.event("kernel.cm_grad_build", m=m, k=k, n=n, world=world)
    dx = pl.pallas_call(
        functools.partial(_cm_dx_kernel, world=world, kc=kc,
                          axis_name=axis_name),
        out_shape=jax.ShapeDtypeStruct((m, k), x.dtype),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=_ring_scratch((kc, n), w_shard.dtype)
        + [pltpu.VMEM((m, kc), x.dtype)],
        interpret=_interpret(),
    )(dy, w_shard)
    # dw ring fuses the xᵀ·dy tile matmuls into the reduce-scatter — the
    # returned shard cotangent is already summed across devices.
    dw = pl.pallas_call(
        functools.partial(_cm_dw_kernel, world=world, kc=kc,
                          axis_name=axis_name),
        out_shape=jax.ShapeDtypeStruct((kc, n), w_shard.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=_ring_scratch((kc, n), jnp.float32) + [
            pltpu.VMEM((m, kc), x.dtype),
            pltpu.VMEM((kc, n), jnp.float32),
        ],
        interpret=_interpret(),
    )(x, dy)
    return dx, dw


allgather_matmul.defvjp(_allgather_matmul_fwd, _allgather_matmul_bwd)


# ---------------------------------------------------------------------------
# model integration: the projection_impl hook (BERT/GPT QKV + MLP paths)
# ---------------------------------------------------------------------------


def make_ring_projection_impl(axis_name) -> Callable:
    """Model-zoo ``projection_impl`` (models/bert.py `ProjDense` contract:
    ``impl(x2d, kernel2d, bias1d, dtype)``) backed by `allgather_matmul`.

    The impl slices this device's row shard out of the (replicated) full
    kernel — a zero-copy view — and runs the ring collective-matmul, so
    the QKV / MLP projection's MXU work starts on the local shard while
    the rest streams. AD through the slice scatters the ring-reduced
    shard cotangent back into the full-weight gradient at exactly this
    device's rows; summed across devices by the bucket reduce-scatter
    that is the sum of per-device gradients — numerically the same total
    (see module docstring). Falls back to the dense matmul when the
    input-feature dim does not divide by the axis size, and outside any
    bound ``axis_name`` (model.init, an unmapped eval) where there is no
    ring to drive — the impl IS dense there, which is what lets
    `serving.engine.DecodeEngine` build its cache template from the same
    model object it later shard_maps.

    Two call sites ride this hook:

    - **training** (``--ring-projections``, mode="dear-fused"): forward
      AND backward rings in the fused train step — the auditor's
      fused-mode rows;
    - **serving ring-TP decode** (`serving.engine.DecodeEngine`
      ``tp_mesh=``): the forward ring only, inside the jitted decode /
      chunked-prefill ticks — decode is weight-bytes-bound, so the
      streamed operand is exactly the one that dominates
      (docs/SERVING.md "Ring-TP decode").

    Honest status: in both sites the full kernel is MATERIALIZED on every
    device (training: the bucket all-gather already gathered it; serving:
    the replica holds replicated params), so the impl adds ring transport
    rather than eliding the gather/replication — it exercises and
    measures the fused matmul in the real model graph; gather elision and
    resident weight sharding are the named next steps in
    docs/KERNELS.md."""
    try:
        from flax.linen import dtypes as _fdtypes
    except ImportError:  # pragma: no cover - flax always present in repo
        _fdtypes = None

    def impl(x2, kernel2, bias1, dtype):
        if _fdtypes is not None:
            x2, kernel2, bias1 = _fdtypes.promote_dtype(
                x2, kernel2, bias1, dtype=dtype)
        try:
            world = lax.axis_size(axis_name)
        except NameError:
            # outside shard_map (model.init, eval on an unmapped fn) the
            # axis is unbound and there is no ring — the impl IS dense
            world = 1
        k = kernel2.shape[0]
        if world == 1 or k % world:
            y = jax.lax.dot_general(
                x2, kernel2, (((1,), (0,)), ((), ())))
        else:
            kc = k // world
            idx = lax.axis_index(axis_name)
            w_shard = lax.dynamic_slice_in_dim(kernel2, idx * kc, kc, 0)
            y = allgather_matmul(x2, w_shard, axis_name).astype(x2.dtype)
        return y + bias1[None, :] if bias1 is not None else y

    return impl
