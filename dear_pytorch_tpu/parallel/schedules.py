"""The table of schedules: how one bucket's legs are issued, per ``mode``.

One class per entry of `dear.MODES`. A schedule hides an algorithm: how a
bucket's parameters reach the forward pass (`gather`), how its gradient
becomes the update's input (`reduce`), how the update runs (`update`), and
which build options it cannot serve (`check`). `build_train_step` picks
``SCHEDULES[mode]`` once and assembles the program around the legs
(`parallel/dear.py`). All of it is trace-time code. To add or change a
schedule, edit its class here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from dear_pytorch_tpu.comm import collectives as C
from dear_pytorch_tpu.ops import collective_matmul as CM
from dear_pytorch_tpu.ops import compression as Z
from dear_pytorch_tpu.ops import fusion as F
from dear_pytorch_tpu.ops.fused_sgd import LayerwiseShardOptimizer


class Schedule:
    """What the schedules share. The replicated family ('allreduce', 'rsag',
    'rb', 'bytescheduler': full params and optimizer state everywhere, no
    gather leg) overrides `transport` alone."""

    name: str
    #: buffers and optimizer state sharded along dim 0 (else replicated)
    sharded = False
    #: the gradient leg can run compressed (`compressed_reduce`)
    compressible = False
    #: the legs travel as ``[n / 128, 128]`` over span-padded buckets
    #: (`Dear.lane_dense`)
    lane_dense = False

    def __init__(self, **build):
        # the build's resolved options, all a leg may read: mesh, axes,
        # axis_name (one name, or the tuple ``axes``), world, mean_world,
        # plan, optimizer, comm_dtype, gather_dtype, compressor (a name of
        # `ops.compression.compressors`), density, gtopk,
        # momentum_correction, partition_mb, clip_norm, remat, dcn
        self.__dict__.update(build)

    @property
    def comp(self) -> Z.Compressor:
        # resolved on use: an unknown name raises where the lattice always
        # raised it, after the schedule's own earlier complaints
        return Z.get_compressor(self.compressor)

    @property
    def compressed(self) -> bool:
        return self.comp.name != "none"

    def check(self) -> None:
        """Raise for the build options this schedule cannot serve."""
        if self.gather_dtype is not None and not self.sharded:
            raise ValueError("gather_dtype applies to the sharded "
                             "('dear'/'fsdp') schedules only")
        if self.compressed and not self.compressible:
            raise ValueError(
                "gradient compression is supported on the 'allreduce' "
                "(WFBP-family, reference parity) and 'dear' (decoupled "
                f"RS+AG) schedules; got mode={self.name!r}")

    def differentiated(self, buffers, gather_unpack, loss):
        """``(w0, fn)``: what the loss is differentiated with respect to,
        and the function of it. ``gather_unpack(buffers)`` is every bucket's
        gather leg plus the unpack; remat='full' recomputes the forward
        during backward instead of saving activations."""
        fn = jax.checkpoint(loss) if self.remat == "full" else loss
        return gather_unpack(buffers), fn

    def grad_buffers(self, grads):
        """The per-bucket buffers `reduce` consumes, from ``fn``'s grads."""
        return F.pack_all(grads, self.plan, dtype=self.comm_dtype)

    def transport(self, bucket, gbuf):
        """The dense reduction of one bucket's gradient buffer."""
        raise NotImplementedError

    def _mean(self, reduced, state, g):
        return reduced.astype(state.buffers[g].dtype) / self.mean_world

    def reduce(self, g, bucket, gbuf, state, idx):
        """Bucket ``g``'s gradient buffer -> ``(the gradient this device
        updates with, its new compressor state)``."""
        return self._mean(self.transport(bucket, gbuf), state, g), ()

    def compressed_reduce(self, g, bucket, gbuf, state):
        """The compressed gradient leg of 'dear' and 'allreduce': every
        device compresses its bucket (error-feedback residuals and, under
        DGC momentum correction, a local velocity stay per device in
        ``state.comp_state[g]``), the payloads are exchanged, and every
        device reconstructs the same DENSE mean. Returns ``(dense mean, new
        compressor state)``."""
        axis_name, n = self.axis_name, bucket.padded_size
        comp, mc = self.comp, self.momentum_correction
        pdtype, centry = state.buffers[g].dtype, state.comp_state[g]
        if mc:
            res_entry, vel_entry = centry["res"], centry["vel"]
        else:
            res_entry, vel_entry = centry, None
        stateless = isinstance(res_entry, tuple)
        res = () if stateless else res_entry.reshape(res_entry.shape[1:])
        gin = gbuf.astype(pdtype)
        if mc:
            # local velocity accumulates momentum BEFORE sparsification
            # (wfbp/dopt.py:769-775)
            vel = mc * vel_entry.reshape(vel_entry.shape[1:]) + gin
            gin = vel
        payload, new_res = comp.compress(gin, res, self.density)
        if comp.name in Z.SIGN:
            grad = Z.sign_majority_vote_allreduce(
                payload, n, pdtype, axis_name)
        elif self.gtopk:
            grad, kept_idx = Z.gtopk_sparse_allreduce(
                payload, n, pdtype, axis_name, Z._k_of(n, self.density))
            if not stateless:
                # Error feedback under gTop-k: coordinates this device SENT
                # (zeroed out of its residual) but the global top-k
                # REJECTED would otherwise lose their gradient mass
                # permanently. Re-add them to the residual (reference
                # wfbp/dopt.py:726-728).
                kept_mask = jnp.zeros((n,), jnp.bool_).at[kept_idx].set(True)
                sent_idx = payload["indices"]
                rejected = jnp.where(
                    kept_mask[sent_idx],
                    jnp.zeros_like(payload["values"]),
                    payload["values"],
                )
                new_res = new_res.at[sent_idx].add(
                    rejected.astype(new_res.dtype))
        elif comp.name in Z.QUANT:
            grad = Z.int8_allreduce(payload, n, pdtype, axis_name)
        else:
            grad = Z.sparse_allreduce(payload, n, pdtype, axis_name)
        new_centry = () if stateless else new_res[None, :]
        if mc:
            # clear velocity at SENT coordinates (the reference's post-step
            # `buf *= zero_condition`, wfbp/dopt.py:946-951 with
            # compression.py:42-48)
            vel = vel.at[payload["indices"]].set(0.0)
            new_centry = {"res": new_centry, "vel": vel[None, :]}
        return grad, new_centry

    def update(self, g, grad, state, step_kw):
        """The optimizer's update of bucket ``g`` -> ``(params, opt)``."""
        if not isinstance(self.optimizer, LayerwiseShardOptimizer):
            return self.optimizer.update(
                grad, state.opt_state[g], state.buffers[g], **step_kw)
        # per-parameter segment metadata for exact cross-shard reductions
        # (LAMB trust ratios): this device's slice of the bucket's
        # element->parameter map, plus the psum completing shard-local
        # segment sums (identity when replicated). Computed from the TINY
        # per-bucket offsets array via searchsorted — materializing
        # FusionPlan.segment_ids here would bake an int32[padded_size]
        # constant (~1/4 of the parameter bytes) into the program on every
        # device.
        b = self.plan.buckets[g]
        starts = jnp.asarray(b.offsets, jnp.int32)
        if self.sharded:
            idx = lax.axis_index(self.axis_name)
            pos = idx * b.shard_size + jnp.arange(
                b.shard_size, dtype=jnp.int32)
            psum = lambda x: lax.psum(x, self.axis_name)  # noqa: E731
        else:
            pos = jnp.arange(b.padded_size, dtype=jnp.int32)
            psum = lambda x: x  # noqa: E731
        seg = jnp.searchsorted(starts, pos, side="right").astype(
            jnp.int32) - 1
        seg = jnp.where(pos < b.size, seg, len(b.leaf_ids))
        return self.optimizer.update(
            grad, state.opt_state[g], state.buffers[g],
            seg, len(b.leaf_ids) + 1, psum, **step_kw,
        )


    def count_launches(self, tr) -> None:
        """Per-step kernel-launch counters, beside the static leg bytes."""


class Allreduce(Schedule):
    """'allreduce' — per-bucket fused all-reduce after backward
    (MG-WFBP/DDP/Horovod shape; mgwfbp/dopt.py:690,
    pytorch-ddp/imagenet_benchmark.py:65)."""

    name = "allreduce"
    compressible = True

    def transport(self, bucket, gbuf):
        return C.all_reduce(gbuf, self.axis_name)

    def reduce(self, g, bucket, gbuf, state, idx):
        if self.compressed:
            return self.compressed_reduce(g, bucket, gbuf, state)
        return super().reduce(g, bucket, gbuf, state, idx)


class Rsag(Schedule):
    """'rsag' — per-bucket all-reduce decomposed as RS+AG inline (WFBP's
    allReduceRSAG, wfbp/dopt.py:675-701)."""

    name = "rsag"

    def transport(self, bucket, gbuf):
        return C.all_reduce_rsag(gbuf, self.axis_name)


class Rb(Schedule):
    """'rb' — per-bucket reduce-to-root + broadcast (dear/dopt_rb.py)."""

    name = "rb"

    def transport(self, bucket, gbuf):
        reduced = C.reduce(gbuf, 0, self.axis_name)
        return C.broadcast(reduced, 0, self.axis_name)


class ByteScheduler(Schedule):
    """'bytescheduler' — allreduce with tensor PARTITIONING (ByteScheduler,
    SOSP'19; reference bytescheduler/imagenet_benchmark.py:73-82,
    --partition :37-38): each bucket's gradient splits into
    ``partition_mb``-sized chunks, one independent reduction each, in layer
    order, so XLA's scheduler is free to run early-layer chunks first (the
    reference's credit scheduler, as dependency shape). Transport is RS+AG,
    not all-reduce: XLA's all-reduce combiner would re-fuse small
    neighboring all-reduces and silently undo the partitioning."""

    name = "bytescheduler"

    def transport(self, bucket, gbuf):
        return jnp.concatenate([
            C.all_reduce_rsag(gbuf[lo:hi], self.axis_name)
            for lo, hi in F.chunk_bounds(
                bucket.padded_size, gbuf.dtype.itemsize, self.partition_mb)
        ])


class Dear(Schedule):
    """'dear' — the decoupled schedule: per-bucket reduce-scatter fed by the
    backward pass, the update on the owned shard, per-bucket all-gather
    feeding the next forward (`parallel/dear.py`). Compressed, the gradient
    leg keeps this device's slice of the dense mean every device
    reconstructs; the parameter all-gather stays dense."""

    name = "dear"
    sharded = True
    compressible = True

    @property
    def wire_dtype(self):
        """The dtype the reduce-scatter carries."""
        return self.comm_dtype

    @property
    def lane_dense(self) -> bool:
        """Both legs travel as ``[n / 128, 128]`` (`C.lanes`) and
        `build_train_step` pads the plan's buckets to XLA:TPU's spans
        (`F.spans_apply`), so the compiler keeps one reduce-scatter and one
        all-gather a bucket, as asked. Only where the rule was read: the
        dense legs over a bf16 wire, one level (no ``dcn``); compressed
        payloads and an f32 wire keep the flat form."""
        return (self.dcn is None and not self.compressed
                and self.wire_dtype is not None
                and jnp.dtype(self.wire_dtype) == jnp.bfloat16
                and F.spans_apply(self.mesh.devices.flat[0].platform,
                                  self.world))

    def gather(self, g, bucket, shard):
        """Bucket ``g``'s (cast) shard -> its full buffer."""
        if self.lane_dense:
            return C.all_gather(C.lanes(shard), self.axis_name).reshape(-1)
        return C.all_gather(shard, self.axis_name)

    def transport(self, bucket, gbuf):
        if self.lane_dense:
            return C.reduce_scatter(C.lanes(gbuf), self.axis_name).reshape(-1)
        return C.reduce_scatter(gbuf, self.axis_name)

    def reduce(self, g, bucket, gbuf, state, idx):
        if not self.compressed:
            return super().reduce(g, bucket, gbuf, state, idx)
        grad, centry = self.compressed_reduce(g, bucket, gbuf, state)
        return lax.dynamic_slice_in_dim(
            grad, idx * bucket.shard_size, bucket.shard_size), centry


class DearFused(Dear):
    """'dear-fused' — 'dear' with BOTH legs executed by Pallas ring kernels
    (`ops/collective_matmul.py`): the all-gather is a ring of async remote
    copies (bit-identical to `lax.all_gather`), and the reduce-scatter is
    FUSED with the optimizer update — each ring step RDMAs the partial-sum
    tile to the neighbor, accumulates in fp32, and the final step applies
    the traced `ShardOptimizer.update` to the owned shard in the same
    kernel (FLUX / T3 ported to TPU). Matches 'dear' at dtype tolerance
    (ring reduction order differs). The models' QKV/MLP projections can ride
    the rings too (`ops.collective_matmul.make_ring_projection_impl`)."""

    name = "dear-fused"
    #: the ring kernels carry the flat buffers
    lane_dense = False

    def check(self) -> None:
        if self.dcn is not None:
            # BEFORE the mesh guards: the caller asked for a ring spanning
            # the DCN boundary, and that — not the nested mesh shape it
            # implies — is the actionable error
            raise ValueError(
                "multislice (dcn=) cannot ride mode='dear-fused': the "
                "Pallas ring kernels address devices by single-mesh axis "
                "index and a ring spanning the DCN boundary would issue "
                "remote copies to devices outside this slice's ICI mesh "
                "— use mode='dear' (hierarchical RS+AG over ICI + host "
                "DCN exchange)")
        if len(self.axes) != 1:
            raise ValueError(
                "dear-fused rings address devices by LOGICAL mesh id and "
                "currently support a single data-parallel axis; got "
                f"{self.axes}")
        if self.mesh.size != self.world:
            raise ValueError(
                "dear-fused rings require the reduction axis to span the "
                f"whole mesh (axis size {self.world} vs mesh size "
                f"{self.mesh.size}): "
                "the kernels' remote-copy device ids are the axis indices")
        if self.clip_norm is not None:
            raise ValueError(
                "dear-fused applies the optimizer inside the per-bucket "
                "reduce-scatter kernel; the cross-bucket global-norm clip "
                "needs every bucket's reduced gradient first — use "
                "mode='dear' with clip_norm")
        if isinstance(self.optimizer, LayerwiseShardOptimizer):
            raise ValueError(
                "dear-fused cannot fuse LayerwiseShardOptimizer (LAMB) "
                "into the epilogue kernel: trust ratios need cross-shard "
                "psums — use mode='dear'")
        if self.compressed:
            # rejecting here (loudly) beats a silent dense fallback that
            # would report compressed-trial timings for a schedule that
            # never compressed anything
            raise ValueError(
                "gradient compression cannot ride mode='dear-fused': the "
                "Pallas ring kernels execute the reduce-scatter leg (fused "
                "with the optimizer epilogue) on dense fp tiles and cannot "
                "exchange sparse/sign/int8-packed payloads — use mode='dear' "
                "(compressed decoupled schedule) or mode='allreduce'")

    def gather(self, g, bucket, shard):
        # chunk t+1 streams over the ICI while chunk t lands
        return CM.ring_all_gather(shard, self.axis_name)

    def reduce(self, g, bucket, gbuf, state, idx):
        # the reduce-scatter happens INSIDE the fused update kernel; carry
        # the raw comm buffer
        return gbuf, ()

    def update(self, g, grad, state, step_kw):
        # one Pallas kernel: ring reduce-scatter of the bucket's comm buffer
        # + the optimizer update on the owned shard in the final ring step
        return CM.fused_reduce_scatter_update(
            grad, state.buffers[g], state.opt_state[g], self.optimizer,
            self.axis_name, mean_world=self.mean_world, **step_kw,
        )


    def count_launches(self, tr) -> None:
        # one fused RS+update and one ring all-gather per bucket per step —
        # the overlap auditor joins these with the static leg bytes
        if not tr.enabled:
            return
        tr.count("kernel.fused_rs_launches", self.plan.num_buckets)
        tr.count("kernel.ring_ag_launches", self.plan.num_buckets)


#: the gather, and the cheap view/cast prims that alias a gathered bucket
_FSDP_UNSAVEABLE = frozenset({
    "all_gather", "reshape", "dynamic_slice", "convert_element_type",
    "transpose", "squeeze", "broadcast_in_dim", "concatenate", "pad",
})


def _fsdp_policy(prim, *_, **params):
    if prim.name == "name":
        return params["name"] != "dear_gathered"
    return prim.name not in _FSDP_UNSAVEABLE


def _named(x):
    return checkpoint_name(x, "dear_gathered")


class Fsdp(Dear):
    """'fsdp' — ZeRO-3 beyond the reference (which stops at ZeRO-1 via
    ZeroRedundancyOptimizer, pytorch-ddp/imagenet_benchmark.py:10,67-68):
    the loss is differentiated with respect to the SHARDS, so the per-bucket
    reduce-scatter is literally the AD transpose of the per-bucket
    all-gather, and a rematerialization policy re-gathers each bucket in
    the backward pass instead of keeping full parameters live across
    forward→backward. Numerics are identical to 'dear'; peak memory drops
    by ~one full parameter set on multi-bucket models."""

    name = "fsdp"
    compressible = False

    def check(self) -> None:
        if self.comm_dtype is not None:
            raise ValueError(
                "'fsdp' communicates both legs in gather_dtype (the "
                "reduce-scatter is the all-gather's AD transpose); comm_dtype "
                "must be None")
        super().check()
        if self.remat == "full":
            raise ValueError(
                "'fsdp' owns its rematerialization policy (the re-gather-in-"
                "backward checkpoint); remat applies to the other schedules")

    @property
    def wire_dtype(self):
        return self.gather_dtype

    def gather(self, g, bucket, shard):
        return _named(super().gather(g, bucket, shard))

    def differentiated(self, buffers, gather_unpack, loss):
        def shard_loss(bufs, mstate, b, extra):
            # Gather + unpack with EVERY intermediate named (wrap=): the
            # policy excludes named values from the residual set; one
            # unnamed alias anywhere between gather and consumption (a
            # slice, reshape, or cast) would be saveable and let AD keep
            # full parameters alive fwd→bwd, silently reverting to 'dear'
            # memory behavior. (A model that re-casts params internally
            # still creates such an alias — pass gather_dtype matching the
            # model's compute dtype so that cast is the identity.)
            return loss(gather_unpack(bufs, wrap=_named), mstate, b, extra)

        # Save activations but NOT the gathered buckets: backward re-gathers
        # each bucket right where its grads are needed.
        # ``save_anything_except_these_names`` alone cannot force that: it
        # lets AD save the named value's unnamed PRODUCER (the gather or a
        # view of it) instead. `_fsdp_policy` denies the gather and all
        # cheap view/cast prims too; then the only saveable values are
        # genuine compute outputs (activations), and the cheapest path back
        # to the weights in backward is re-gathering the shard (which
        # jax.checkpoint wraps in an optimization barrier — prevent_cse —
        # so XLA cannot fold the two gathers back into one and silently
        # restore 'dear'-mode param liveness).
        return tuple(buffers), jax.checkpoint(shard_loss, policy=_fsdp_policy)

    def grad_buffers(self, grads):
        # the grads ARE the per-bucket shards already: AD transposed the
        # gathers into reduce-scatters
        return grads

    def reduce(self, g, bucket, gbuf, state, idx):
        return self._mean(gbuf, state, g), ()


SCHEDULES = {c.name: c for c in (
    Dear, DearFused, Allreduce, Rsag, Rb, ByteScheduler, Fsdp)}
