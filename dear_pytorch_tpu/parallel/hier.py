"""The hierarchical (multi-slice) step: two compiled programs and a host leg.

``build_train_step(dcn=...)`` on a nested mesh runs the 'dear' legs over the
intra-slice axes (ICI) inside two jitted programs — backward (`_fwd_bwd`, up
to the intra-slice-reduced bucket gradients) and update (`_apply`) — and
averages the partials across slices between them on the host, through the
`comm.dcn.DcnExchanger`. Neither program sees the slice count, so an elastic
slice loss/rejoin renormalizes via ``dcn.set_slices`` with no recompile.
`parallel/dear.py` builds the two halves and the specs and passes them in.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dear_pytorch_tpu.observability import dtrace as _dtrace
from dear_pytorch_tpu.observability import tracer as _telemetry

_annotate = jax.profiler.TraceAnnotation


def check(*, mode, compressed, clip_norm, has_model_state, has_aux,
          mean_axes, axes, mesh, dcn, dcn_slice_axis):
    """The multi-slice build guards, each row a rejected combination and
    why: loud at plan-build rather than a silent single-level schedule
    ('dear-fused' is rejected by its own schedule, before these)."""
    n_local = len(dcn.local_slices)
    for bad, why in (
        (mode != "dear",
         "the hierarchical (dcn=) schedule is the two-level "
         f"decoupled 'dear' mode; got mode={mode!r}"),
        (compressed,
         "gradient compression on the hierarchical schedule is "
         "unsupported: the cross-slice leg averages DENSE reduced "
         "partials on the host — compress-on-DCN is a named "
         "follow-up, not a silent fallback"),
        (clip_norm is not None,
         "clip_norm needs the GLOBAL gradient norm, which crosses "
         "the slice boundary inside the step — unsupported with "
         "dcn= (the host leg averages per-bucket partials only)"),
        (has_model_state,
         "model_state (BatchNorm stats etc.) syncs over the "
         "intra-slice axes only and would silently diverge across "
         "slices — unsupported with dcn="),
        (has_aux,
         "has_aux is unsupported with dcn=: only the loss travels "
         "the cross-slice scalar path"),
        (mean_axes != axes,
         "mean_axes != axis_name is unsupported with dcn=: the "
         "intra-slice legs average over every local axis and the "
         "host leg averages over slices"),
        (dcn_slice_axis in axes,
         f"dcn_slice_axis {dcn_slice_axis!r} must not be a "
         "reduction axis: the cross-slice exchange owns it"),
        (mesh.shape.get(dcn_slice_axis) != n_local,
         f"the nested mesh needs axis {dcn_slice_axis!r} of size "
         f"{n_local} (one row per LOCAL slice "
         f"{dcn.local_slices}); mesh has {dict(mesh.shape)}"),
    ):
        if bad:
            raise ValueError(why)


def global_device_index(dcn, dcn_slice_axis, world, idx):
    """A GLOBALLY unique device index for the dropout key: devices at the
    same ICI position on different slices must not share streams."""
    return (jnp.asarray(dcn.local_slices, jnp.int32)[
        lax.axis_index(dcn_slice_axis)] * world + idx)


def build_step(fwd_bwd, apply, *, mesh, plan, axes, axis_name, state_specs,
               batch_spec_fn, dcn, dcn_slice_axis, partition_mb, donate,
               count_step):
    """``(_step, lower, multi_step)`` of the hierarchical schedule, shaped
    as `build_train_step`'s own. ``fwd_bwd`` / ``apply``: the two per-device
    halves; ``state_specs``: a state's specs; ``count_step(tracer)``: the
    per-step telemetry accounting."""
    slice_axes = (dcn_slice_axis,) + axes
    _compiled_hg: dict = {}
    _compiled_ha: dict = {}

    def batch_specs(batch):
        if batch_spec_fn is not None:
            return batch_spec_fn(batch)
        # nested mesh: the global batch shards over local slices AND the
        # intra-slice axis jointly (each slice sees its data shard; each
        # ICI device its sub-shard)
        return jax.tree.map(lambda _: jax.P(slice_axes), batch)

    def _hier_device_grads(state, batch):
        bucket_grads, loss, _aux, _nms, _ncomp = fwd_bwd(state, batch)
        # aux / model state / compressor state are inert here — `check`
        # rejected every combination that would produce them
        with jax.named_scope("dear/metrics"):
            loss_sl = lax.pmean(loss, axis_name).reshape(1)
        return tuple(bucket_grads), loss_sl

    def _hier_grads_jitted(state, batch):
        key = jax.tree.structure((state, batch))
        fn = _compiled_hg.get(key)
        if fn is None:
            mapped = jax.shard_map(
                _hier_device_grads,
                mesh=mesh,
                in_specs=(state_specs(state), batch_specs(batch)),
                out_specs=(
                    tuple(jax.P(slice_axes) for _ in plan.buckets),
                    jax.P(dcn_slice_axis),
                ),
                check_vma=False,
            )
            fn = jax.jit(mapped)
            _compiled_hg[key] = fn
        return fn

    def _hier_device_apply(state, reduced, loss_g):
        grads = [r.astype(state.buffers[g].dtype)
                 for g, r in enumerate(reduced)]
        metrics = {"loss": loss_g}
        return apply(state, grads, metrics, state.model_state,
                     state.comp_state)

    def _hier_apply_jitted(state, reduced, loss_g):
        key = jax.tree.structure((state, reduced))
        fn = _compiled_ha.get(key)
        if fn is None:
            specs = state_specs(state)
            mapped = jax.shard_map(
                _hier_device_apply,
                mesh=mesh,
                in_specs=(
                    specs,
                    tuple(jax.P(axis_name) for _ in plan.buckets),
                    jax.P(),
                ),
                out_specs=(specs, jax.P()),
                check_vma=False,
            )
            fn = jax.jit(mapped, donate_argnums=(0,) if donate else ())
            _compiled_ha[key] = fn
        return fn

    def _hier_step(state, batch):
        padded = [b.padded_size for b in plan.buckets]
        # step number read from the INPUT state (ready before dispatch):
        # it keys both the exchange and the cross-iteration prefetch
        step_no = int(np.asarray(jax.device_get(state.step)))
        ds = _dtrace.get_stream()
        t_bwd = time.monotonic() if ds.enabled else 0.0
        with _annotate("dear.backward"):
            grads_g, loss_sl = _hier_grads_jitted(state, batch)(state, batch)
            # bounded-stale mode only (no-op otherwise): start pulling the
            # peers' partials for THIS step while our backward is still
            # running on device — a peer up to one round ahead has already
            # published, so its wire time hides under the compute
            dcn.prefetch(step_no)
            # the host leg is the synchronization point of this schedule:
            # the step number keys the exchange and the partials are its
            # payload, so these transfers are the leg itself, not a stray
            # sync
            host = [np.asarray(jax.device_get(g)) for g in grads_g]
            losses = np.asarray(jax.device_get(loss_sl),
                                np.float64).reshape(-1)
        if ds.enabled:
            # the device_get above IS the backward program's wall time
            # (the host leg synchronizes on it) — a compute span on the
            # step trace, so the critical-path analysis attributes the
            # DCN round's exposure against real backward overlap
            ds.emit("dear.backward", t0=t_bwd,
                    dur_s=time.monotonic() - t_bwd, cat="compute",
                    trace=_dtrace.step_trace(dcn.epoch, step_no),
                    step=step_no, mem_epoch=dcn.epoch)
        per_slice = {
            sid: [host[g][k * padded[g]:(k + 1) * padded[g]]
                  for g in range(len(padded))]
            for k, sid in enumerate(dcn.local_slices)
        }
        scalars = {sid: float(losses[k])
                   for k, sid in enumerate(dcn.local_slices)}
        with _annotate("dear.dcn_exchange"):
            means, loss_mean = dcn.exchange(step_no, per_slice, scalars,
                                            partition_mb=partition_mb)
        sh = jax.sharding.NamedSharding(mesh, jax.P(axis_name))
        reduced = tuple(jax.device_put(m, sh) for m in means)
        loss_dev = jnp.float32(loss_mean)
        t_apply = time.monotonic() if ds.enabled else 0.0
        with _annotate("dear.apply"):
            out = _hier_apply_jitted(state, reduced, loss_dev)(
                state, reduced, loss_dev)
        if ds.enabled:
            # update-program dispatch (async: the device work may drain
            # into the NEXT step's backward; the span records the host
            # cost, which is what this schedule's critical path sees)
            ds.emit("dear.apply", t0=t_apply,
                    dur_s=time.monotonic() - t_apply, cat="compute",
                    trace=_dtrace.step_trace(dcn.epoch, step_no),
                    step=step_no, mem_epoch=dcn.epoch)
        return out

    def _step(state, batch):
        tr = _telemetry.get_tracer()
        count_step(tr)
        # no covering stream span here: the DCN leg is genuinely exposed
        # comm, and a wrapping compute span would mark it hidden in the
        # critical-path analysis (_hier_step emits backward/apply itself)
        with tr.span("dear.step", mode="dear"):
            return _hier_step(state, batch)

    def lower(state, batch):
        # the backward program is the schedule's compute body (the update
        # program is a per-bucket elementwise epilogue); MFU accounting
        # and HLO audits read this one
        return _hier_grads_jitted(state, batch).lower(state, batch)

    def multi_step(n: int):
        raise ValueError(
            "multi_step is unavailable on the hierarchical (dcn=) "
            "schedule: the cross-slice exchange is a host-level leg "
            "and cannot ride inside a compiled lax.scan")

    return _step, lower, multi_step
