"""The DeAR schedule: decoupled reduce-scatter + all-gather data parallelism.

The TPU-native heart of the framework, in place of the reference's
``_DistributedOptimizer`` (dear/dear_dopt.py:56-378), which wires the
schedule out of eager-mode hooks: backward hooks launch an async
reduce-scatter when a bucket fills (:242-272), ``step()`` syncs them and
kicks the first all-gather (:348-372), and the NEXT iteration's forward
pre-hooks sync the gather, apply a fused SGD and prefetch the next (:274-308).

Functional redesign. Master parameters and optimizer state live as *shards*:
each device owns 1/world of every fusion buffer (the reduce-scatter's output,
so ZeRO-1 sharding is inherent). One jitted train step:

    per bucket g:  full_g   = all_gather(param_shard_g)        # feeds fwd
    params         = unpack(full_0..G)
    loss, grads    = value_and_grad(loss_fn)(params, batch)
    per bucket g:  grad_shard_g = reduce_scatter(grads_g) / N  # fed by bwd
    per bucket g:  param_shard_g, opt_g = update(grad_shard_g, ...)

The data dependencies reproduce DeAR's overlap by construction: bucket g's
all-gather is needed only by layer-group g's forward, so XLA's latency-hiding
scheduler runs gather g+1 while layer-group g computes; each bucket's
reduce-scatter depends only on that bucket's grads, so it overlaps the rest
of the backward. The cross-iteration pipelining is carried functionally:
shards updated at the end of step i are gathered at the top of step i+1 —
and step 0 trains on correctly-reduced gradients, where the reference trains
it on unreduced local ones (dear_dopt.py:278,367-371).

The map. `build_train_step` is four boxes, in this order:

  validate  option values, here; what one schedule cannot serve, in its
            `check` (`parallel/schedules.py`); the multi-slice guards, in
            `parallel/hier.py:check`
  plan      `ops/fusion.py:make_plan` — which leaves share a bucket
  legs      `parallel/schedules.py` — one class per entry of `MODES`, with
            one bucket's `gather`, `reduce` and `update`; ``mode`` is read
            once, to pick the class
  assembly  here — `_fwd_bwd` (gather legs, loss and gradients, reduce
            legs) and `_apply` (clip, update legs, fingerprint) become one
            jitted `device_step` under `shard_map`, or, with ``dcn=``, the
            two programs of `parallel/hier.py` around the host's exchange

To add or change a schedule, edit its class in `schedules.py`: the legs are
all it has to say, and `_fwd_bwd` / `_apply` read nothing of a schedule but
`sharded`. Every `jax.named_scope` of the step is set here, around the leg it
names (the hierarchical programs' ``dear/metrics`` in `hier.py`; the
``bucket<g>`` under ``dear/pack`` and ``dear/unpack`` where the loop over
buckets runs, `ops/fusion.py:pack_all` / `unpack_all`).
"""

from __future__ import annotations

import math
import time
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from dear_pytorch_tpu.comm import backend
from dear_pytorch_tpu.comm.backend import DP_AXIS
from dear_pytorch_tpu.observability import counters as _tel_counters
from dear_pytorch_tpu.observability import dtrace as _dtrace
from dear_pytorch_tpu.observability import tracer as _telemetry
from dear_pytorch_tpu.ops import compression as Z
from dear_pytorch_tpu.ops import fusion as F
from dear_pytorch_tpu.ops.fused_sgd import ShardOptimizer, fused_sgd
from dear_pytorch_tpu.parallel import hier as H
from dear_pytorch_tpu.parallel import schedules as S

MODES = ("dear", "dear-fused", "allreduce", "rsag", "rb", "bytescheduler",
         "fsdp")

#: Host spans on the profiler's own clock (`dear.step`, and the three
#: stretches of the hierarchical step). With no `jax.profiler` session
#: active, entering one costs well under a microsecond
#: (scripts/check_telemetry_overhead.py gates it).
_annotate = jax.profiler.TraceAnnotation


class DearState(NamedTuple):
    """Carried training state.

    ``buffers[g]`` is bucket g's flat padded master-param buffer. In 'dear'
    mode its global array is sharded along dim 0 (each device owns its
    reduce-scatter slice); in baseline modes it is replicated. ``opt_state``
    mirrors that layout. ``step`` is a replicated scalar. ``model_state``
    holds non-trained model collections (BatchNorm running stats etc.),
    replicated; float leaves are cross-replica averaged each step (the
    reference, like DDP, keeps BN stats replica-local and divergent — here
    they stay consistent, which also makes them trivially checkpointable).
    """

    buffers: tuple
    opt_state: tuple
    step: jax.Array
    model_state: Any = ()
    #: per-bucket compressor residual/error-feedback state; per-device by
    #: construction (global shape (world, padded), sharded on the dp axis)
    comp_state: tuple = ()


class TrainStep(NamedTuple):
    """What `build_train_step` returns."""

    init: Callable[..., DearState]  # (params, model_state=None) -> DearState
    step: Callable[[DearState, Any], tuple[DearState, dict]]
    gather_params: Callable[[DearState], Any]
    plan: F.FusionPlan
    mesh: jax.sharding.Mesh
    #: AOT access to the jitted step: ``lower(state, batch)`` returns the
    #: `jax.stages.Lowered` (``.compile().as_text()`` = optimized HLO;
    #: ``.compile().cost_analysis()`` = FLOPs for MFU accounting). Same cache
    #: as ``step`` — no double compile.
    lower: Callable[[DearState, Any], Any] = None
    #: ``multi_step(n)`` -> jitted ``(state, batch) -> (state, metrics)``
    #: running n steps as ONE compiled `lax.scan` program: one dispatch per
    #: n steps, and XLA sees step i+1's all-gathers after step i's update —
    #: the cross-iteration AG-under-forward pipelining DeAR promises
    #: materializes inside a single program instead of across dispatches.
    multi_step: Callable[[int], Callable] = None
    #: the `comm.dcn.DcnExchanger` of a hierarchical (multi-slice) step —
    #: None on single-level schedules. Elastic transitions renormalize the
    #: cross-slice leg through it (``dcn.set_slices``).
    dcn: Any = None
    #: the schedule's static account of itself, built once:
    #: `observability.counters.CommAccounting`, one row per bucket and leg
    #: with the payload and ring-estimate wire bytes the step asks the
    #: interconnect to move (the ``dear.<leg>_bytes`` counters add its
    #: per-leg sums every step under ``DEAR_TELEMETRY``)
    comm: Any = None


def _opt_bucket_specs(axis_name: str, bucket_padded: int, opt_state_leaf):
    """Spec for one bucket's optimizer-state leaf: leaves shaped exactly like
    the bucket's flat buffer hold per-element state and shard with it;
    anything else (momentum 'initialized' flag, adam count) is replicated.
    """
    if (getattr(opt_state_leaf, "ndim", None) == 1
            and opt_state_leaf.shape[0] == bucket_padded):
        return jax.P(axis_name)
    return jax.P()


def build_train_step(
    loss_fn: Callable,
    params_template,
    *,
    optimizer: Optional[ShardOptimizer] = None,
    mesh: Optional[jax.sharding.Mesh] = None,
    axis_name: str = DP_AXIS,
    mode: str = "dear",
    threshold_mb: Optional[float] = 25.0,
    nearby_layers: Optional[int] = None,
    flags: Optional[Sequence[int]] = None,
    plan: Optional[F.FusionPlan] = None,
    comm_dtype=None,
    has_aux: bool = False,
    donate: bool = True,
    model_state_template=None,
    rng_seed: Optional[int] = None,
    compressor: Optional[str] = None,
    density: float = 1.0,
    gtopk: bool = False,
    momentum_correction: float = 0.0,
    batch_spec_fn: Optional[Callable[[Any], Any]] = None,
    mean_axes: Optional[Sequence[str]] = None,
    partition_mb: float = 4.0,
    accum_steps: int = 1,
    gather_dtype=None,
    clip_norm: Optional[float] = None,
    remat: Optional[str] = None,
    dcn=None,
    dcn_slice_axis: str = "slice",
) -> TrainStep:
    """Build the jitted DeAR (or baseline) data-parallel train step.

    Args:
      loss_fn: ``loss_fn(params, batch) -> loss`` (or ``(loss, aux)`` with
        ``has_aux=True``); computed per device on its local batch shard.
      params_template: pytree giving shapes/dtypes (actual values are used by
        `init`).
      optimizer: a `ShardOptimizer`; defaults to fused SGD lr=0.01 (the
        reference benchmarks' default, dear/imagenet_benchmark.py).
      mode: one of `MODES` (`parallel/schedules.py` describes each).
      threshold_mb / nearby_layers / flags / plan: bucketing controls
        (defaults mirror THRESHOLD=25 MB, dear/dear_dopt.py:42-44).
      comm_dtype: cast gradients to this dtype for communication (e.g.
        jnp.bfloat16); update math stays in the param dtype.
      model_state_template: pytree of non-trained model collections (e.g.
        flax ``batch_stats``). When given, ``loss_fn`` is called as
        ``loss_fn(params, model_state, batch)`` and must return
        ``(loss, new_model_state)`` (with ``has_aux=True``:
        ``(loss, (new_model_state, aux))``). Float leaves of the returned
        state are averaged across replicas; integer/bool leaves are maxed
        (deterministic consensus). Other leaves must already be replicated —
        divergence there is NOT detected (``check_vma=False``).
      rng_seed: when given, ``loss_fn`` receives a per-step, per-device PRNG
        key as its last positional argument (folded from seed, step counter,
        and device index) — use for dropout. Without it, stochastic layers
        need a key closed over by ``loss_fn`` (constant across steps).
      compressor / density / gtopk: gradient compression on the 'allreduce'
        schedule (where the reference applies it, dear/dear_dopt.py:381-398)
        or on 'dear' (beyond reference: `schedules.Schedule.
        compressed_reduce`; the parameter all-gather stays dense);
        error-feedback residuals stay per-device in
        ``DearState.comp_state``. ``compressor`` is a name from
        `ops.compression.compressors` ('qint8' = the int8-packed wire
        format); ``density`` the kept fraction for the top-k family;
        ``gtopk=True`` uses the recursive-halving gTop-k reduction
        (wfbp/dopt.py:50-107) instead of allgather-accumulate. Sign
        compressors perform majority vote; their "gradient" is ±1 (signSGD —
        scale lives in the lr).
      remat: None (default) or 'full' — wrap the differentiated loss in
        `jax.checkpoint`, trading recompute for activation memory (a
        searched axis of the plan-space autotuner). 'fsdp' owns its own
        policy and rejects this knob.
      momentum_correction: DGC-style momentum correction for SPARSE
        compressed training (Lin et al. 2018; reference wfbp/dopt.py:769-775,
        :946-951). When > 0, a LOCAL velocity ``u = mc·u + g`` is sparsified
        instead of the raw gradient, and ``u`` is cleared at the coordinates
        actually sent — momentum for rarely-sent coordinates keeps
        accumulating locally instead of being lost to sparsification. The
        optimizer should then be momentum-free, as the reference bypasses
        its SGD momentum buffer (wfbp/dopt.py:934-942).
      axis_name: one mesh axis name, or a TUPLE of axis names — e.g.
        ``('dp', 'sp')`` for combined data + sequence parallelism. Gradients
        reduce-scatter over every listed axis (the ZeRO shard degree is the
        product), and ``loss_fn`` may itself use collectives over an
        individual axis (e.g. ring attention over 'sp').
      batch_spec_fn: ``batch -> PartitionSpec pytree`` overriding the
        default "shard every leaf's dim 0 over axis_name" input layout —
        required for dp×sp, where the batch dim shards over 'dp' and the
        sequence dim over 'sp'.
      partition_mb: the per-level bucket partition (MB of the comm dtype).
        In 'bytescheduler' mode, the chunk size of the in-program
        partitioned reductions (the reference's ``--partition`` /
        ``BYTESCHEDULER_PARTITION``). With ``dcn=``, the CROSS-SLICE message
        size (`ops.fusion.chunk_bounds`), independent of the intra-slice
        bucket threshold — a `tuning.planspace.PlanSpace` searched axis.
        Ignored by other modes.
      accum_steps: gradient accumulation. The per-device batch splits into
        ``accum_steps`` microbatches along every leaf's leading axis
        (scanned sequentially), gradients average across microbatches, and
        the collectives + optimizer update run ONCE per step — the large
        effective batch sizes the reference reaches only by adding GPUs.
        Model state (BN stats) threads through the microbatches; with
        ``rng_seed`` each microbatch gets a distinct dropout key. Loss and
        ``aux`` are MEANS over microbatches (matching the cross-device
        `lax.pmean` convention) — aux must be a mean-like statistic, not a
        count/sum, for its value to be independent of ``accum_steps``.
      gather_dtype: cast master shards to this dtype BEFORE the per-bucket
        all-gather ('dear'/'fsdp' modes) — e.g. ``jnp.bfloat16`` halves the
        gather bytes when the model computes in bf16 anyway (the cast the
        model would apply per-layer happens once, pre-communication).
        Updates still read the f32 masters. In 'fsdp' mode this also sets
        the reduce-scatter dtype (the RS is the gather's AD transpose), so
        ``comm_dtype`` must be None there.
      clip_norm: clip gradients to this GLOBAL L2 norm before the update.
        Exact under sharding: shard-local square-norms psum across the
        axes, so the scale equals the full-tree norm clip a single device
        would compute — the cross-parameter reduction `from_optax`
        explicitly cannot express on shards. Applied to the reduced
        (averaged) gradient; the per-step norm ships in
        ``metrics['grad_norm']``. Not supported with compression (the
        sparse payloads are already a lossy transform of the gradient).
      mean_axes: the axes over which per-device losses are independent
        equal-weight samples (gradients are AVERAGED over these; summed over
        the rest). Defaults to all of ``axis_name``. For dp×sp pass
        ``('dp',)``: the sp group jointly computes ONE loss (each device
        holding partial gradients that must sum), while dp replicas hold
        different samples (gradients average).
      donate: donate the state argument so buffers are updated in place.
      dcn: a `comm.dcn.DcnExchanger` — turns ``mode='dear'`` into the
        HIERARCHICAL two-level schedule on a nested mesh (`parallel/hier.py`):
        the per-bucket reduce-scatter / all-gather run over the intra-slice
        ``axis_name`` (ICI) inside two compiled programs, backward and
        update, and the cross-slice averaging of the reduced partials runs
        between them on the host, over the exchanger's DCN transport
        (chunked at ``partition_mb``). The mesh must carry a
        ``dcn_slice_axis`` axis of size ``len(dcn.local_slices)`` (1 on a
        one-slice-per-process fleet; >1 when one process emulates several
        slices); the ZeRO shard degree is the INTRA-slice world. Rejected
        loudly at build (`hier.check`): every mode but 'dear', gradient
        compression, ``clip_norm``, ``model_state_template``, ``has_aux``,
        and ``mean_axes != axis_name``. ``multi_step`` is unavailable (the
        host leg cannot ride a scan).
      dcn_slice_axis: mesh axis name enumerating this host's LOCAL slices
        (only with ``dcn``).
    """
    # ---- validate ----------------------------------------------------------
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    mesh = mesh or backend.global_mesh()
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    axis_name = axes if len(axes) > 1 else axes[0]
    world = math.prod(mesh.shape[a] for a in axes)
    mean_axes = tuple(mean_axes) if mean_axes is not None else axes
    if not set(mean_axes) <= set(axes):
        raise ValueError(f"mean_axes {mean_axes} not a subset of {axes}")
    mean_world = math.prod(mesh.shape[a] for a in mean_axes)
    optimizer = optimizer or fused_sgd(lr=0.01)
    # ---- plan, and the schedule whose legs the program is assembled from ---
    if plan is None:
        plan = F.make_plan(params_template, world, threshold_mb=threshold_mb,
                           nearby_layers=nearby_layers, flags=flags)
    if plan.world != world:
        raise ValueError(
            f"plan was built for world={plan.world} but mesh axis "
            f"{axis_name!r} has size {world}")
    schedule = S.SCHEDULES[mode](
        mesh=mesh, axes=axes, axis_name=axis_name, world=world,
        mean_world=mean_world, plan=plan, optimizer=optimizer,
        comm_dtype=comm_dtype, gather_dtype=gather_dtype,
        compressor=compressor, density=density, gtopk=gtopk,
        momentum_correction=momentum_correction, partition_mb=partition_mb,
        clip_norm=clip_norm, remat=remat, dcn=dcn)
    # ---- validate, continued: this schedule's limits, then option values ---
    schedule.check()
    # buckets padded for this program's collectives (`F.bucket_length`),
    # wherever the plan was made
    plan = schedule.plan = F.rescale_plan(
        plan, world, platform=(mesh.devices.flat[0].platform
                               if schedule.lane_dense else None))
    # SDC sentinel: the per-bucket fingerprint is baked into the program
    # only when armed — resolved once here at build time, so the disabled
    # path carries zero extra ops and no per-step branch
    from dear_pytorch_tpu.resilience import sdc as _sdc
    sdc_fp = _sdc.sdc_enabled()
    has_model_state = model_state_template is not None
    comp, compressed = schedule.comp, schedule.compressed
    if remat not in (None, "none", "full"):
        raise ValueError(
            f"remat must be None, 'none' or 'full', got {remat!r}")
    if compressed and mean_axes != axes:
        raise ValueError(
            "compressed reductions divide by the full axis product and do "
            "not support mean_axes != axis_name (e.g. sequence-parallel "
            "partial-gradient sums); use dense schedules on multi-axis "
            "meshes with mean_axes")
    if gtopk and comp.name not in Z.SPARSE:
        raise ValueError("gtopk requires a top-k-family compressor")
    if int(accum_steps) != accum_steps or accum_steps < 1:
        raise ValueError(f"accum_steps must be a positive int, got {accum_steps}")
    accum_steps = int(accum_steps)
    if clip_norm is not None:
        if compressed:
            raise ValueError(
                "clip_norm with compression is unsupported: the sparse "
                "payloads are already a lossy gradient transform")
        if clip_norm <= 0:
            raise ValueError(f"clip_norm must be positive, got {clip_norm}")
    if momentum_correction and comp.name not in Z.SPARSE:
        raise ValueError(
            "momentum_correction requires a sparse (top-k-family) "
            "compressor (reference wfbp/dopt.py:769: mc applies on the "
            "sparse path only)")
    if dcn is not None:
        H.check(mode=mode, compressed=compressed, clip_norm=clip_norm,
                has_model_state=has_model_state, has_aux=has_aux,
                mean_axes=mean_axes, axes=axes, mesh=mesh, dcn=dcn,
                dcn_slice_axis=dcn_slice_axis)

    # ---- per-device step body (runs inside shard_map) ----------------------
    # Two halves: the single-program schedules compose them into one jitted
    # step (`device_step`), the hierarchical schedule jits them as SEPARATE
    # programs with the host's cross-slice exchange in between. `_fwd_bwd`
    # ends at the reduced bucket gradients, `_apply` starts at the update.

    def _fwd_bwd(state: DearState, batch):
        idx = lax.axis_index(axis_name)

        def gather_unpack(bufs, wrap=None):
            """Every bucket's gather leg, then the parameter tree."""
            if not schedule.sharded:
                with jax.named_scope("dear/unpack"):
                    return F.unpack_all(list(bufs), plan)
            full_bufs = []
            for g, (b, s) in enumerate(zip(plan.buckets, bufs)):
                with jax.named_scope(f"dear/bucket{g}/gather"):
                    if gather_dtype is not None:
                        s = s.astype(gather_dtype)
                    full_bufs.append(schedule.gather(g, b, s))
            # With gather_dtype, leaves STAY in gather_dtype: the model's
            # own cast is then the identity, and the sharded schedules see
            # the same numerics.
            with jax.named_scope("dear/unpack"):
                return F.unpack_all(full_bufs, plan, wrap=wrap,
                                    cast=gather_dtype is None)

        # Canonicalize every loss_fn variant to (loss, (model_state, aux)).
        def canonical_loss(p, mstate, b, extra):
            if has_model_state:
                loss, out = loss_fn(p, mstate, b, *extra)
                ms, aux = out if has_aux else (out, None)
                return loss, (ms, aux)
            if has_aux:
                loss, aux = loss_fn(p, b, *extra)
                return loss, ((), aux)
            return loss_fn(p, b, *extra), ((), None)

        w0, diff_fn = schedule.differentiated(
            state.buffers, gather_unpack, canonical_loss)
        extra_args: tuple = ()
        if rng_seed is not None:
            rng_idx = idx if dcn is None else H.global_device_index(
                dcn, dcn_slice_axis, world, idx)
            with jax.named_scope("dear/rng"):
                step_rng = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(rng_seed),
                                       state.step),
                    rng_idx,
                )
            extra_args = (step_rng,)

        vg = jax.value_and_grad(diff_fn, has_aux=True)
        if accum_steps == 1:
            (loss, (new_model_state, aux)), grads = vg(
                w0, state.model_state, batch, extra_args)
        else:
            # Microbatch scan: grads SUM across microbatches (divided once at
            # the end), model state threads through, per-microbatch rng keys.
            def _split(x):
                if x.shape[0] % accum_steps:
                    raise ValueError(
                        f"batch leaf leading axis {x.shape[0]} is not "
                        f"divisible by accum_steps={accum_steps} (note: this "
                        "is the PER-DEVICE shard size)")
                return x.reshape(
                    (accum_steps, x.shape[0] // accum_steps) + x.shape[1:])

            mb_batch = jax.tree.map(_split, batch)

            def mb_body(carry, xs):
                ms, gacc = carry
                b_i, i = xs
                extra = ((jax.random.fold_in(extra_args[0], i),)
                         if extra_args else ())
                (loss_i, (ms_i, aux_i)), g_i = vg(w0, ms, b_i, extra)
                gacc = jax.tree.map(jnp.add, gacc, g_i)
                return (ms_i, gacc), (loss_i, aux_i)

            (new_model_state, gsum), (mb_losses, mb_auxs) = lax.scan(
                mb_body,
                (state.model_state, jax.tree.map(jnp.zeros_like, w0)),
                (mb_batch, jnp.arange(accum_steps)),
            )
            grads = jax.tree.map(lambda g: g / accum_steps, gsum)
            loss = jnp.mean(mb_losses)
            aux = (None if mb_auxs is None else
                   jax.tree.map(lambda a: jnp.mean(a, axis=0), mb_auxs))
        if has_model_state:
            # Keep replicated state consistent across replicas (each saw a
            # different batch shard): average float stats, max-consensus
            # integer/bool counters.
            def _sync_leaf(x):
                dt = jnp.result_type(x)
                if jnp.issubdtype(dt, jnp.floating):
                    return lax.pmean(x, axis_name)
                if jnp.issubdtype(dt, jnp.integer) or dt == jnp.bool_:
                    return lax.pmax(x, axis_name)
                return x

            new_model_state = jax.tree.map(_sync_leaf, new_model_state)
        else:
            new_model_state = state.model_state

        with jax.named_scope("dear/pack"):
            grad_bufs = schedule.grad_buffers(grads)
        bucket_grads, new_comp = [], []
        for g, b in enumerate(plan.buckets):
            with jax.named_scope(f"dear/bucket{g}/reduce"):
                grad, centry = schedule.reduce(g, b, grad_bufs[g], state, idx)
            bucket_grads.append(grad)
            new_comp.append(centry)
        return (bucket_grads, loss, aux, new_model_state,
                tuple(new_comp) if compressed else state.comp_state)

    def _apply(state: DearState, bucket_grads, metrics, new_model_state,
               new_comp):
        if clip_norm is not None:
            with jax.named_scope("dear/clip"):
                sumsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                            for g in bucket_grads)
                if schedule.sharded:
                    # each device holds a DISTINCT shard: psum completes
                    # the global square-norm. (Replicated modes hold
                    # identical full gradients — their local sum already
                    # IS the global one.)
                    sumsq = lax.psum(sumsq, axis_name)
                gnorm = jnp.sqrt(sumsq)
                scale = jnp.minimum(
                    1.0, clip_norm / jnp.maximum(gnorm, 1e-12))
                bucket_grads = [g * scale.astype(g.dtype)
                                for g in bucket_grads]
            metrics["grad_norm"] = gnorm

        # lr-schedule optimizers evaluate lr(step) on device from the
        # replicated global counter — exact under multi_step/lax.scan
        step_kw = ({"step": state.step}
                   if getattr(optimizer, "needs_step", False) else {})
        new_buffers, new_opt = [], []
        for g, grad in enumerate(bucket_grads):
            with jax.named_scope(f"dear/bucket{g}/update"):
                new_p, new_o = schedule.update(g, grad, state, step_kw)
            new_buffers.append(new_p)
            new_opt.append(new_o)
        if sdc_fp:
            # uint32 wraparound checksum per bucket over the post-update
            # bucket bytes: bitcast + integer sum is exact and
            # order-independent, so replica-identical state implies
            # identical fingerprints and any divergence is a silent
            # corruption. psum completes the checksum across shards
            # without leaving the program; the guard fetches the value
            # only at check cadence.
            with jax.named_scope("dear/sdc_fp"):
                fps = []
                for buf in new_buffers:
                    words = lax.bitcast_convert_type(
                        buf.astype(jnp.float32), jnp.uint32)
                    s = jnp.sum(words, dtype=jnp.uint32)
                    if schedule.sharded:
                        s = lax.psum(s, axis_name)
                    fps.append(s)
                metrics["sdc_fp"] = jnp.stack(fps)
        return DearState(tuple(new_buffers), tuple(new_opt), state.step + 1,
                         new_model_state, new_comp), metrics

    def device_step(state: DearState, batch):
        bucket_grads, loss, aux, new_model_state, new_comp = _fwd_bwd(
            state, batch)
        with jax.named_scope("dear/metrics"):
            metrics = {"loss": lax.pmean(loss, axis_name)}
            if aux is not None:
                metrics["aux"] = lax.pmean(aux, axis_name)
        return _apply(state, bucket_grads, metrics, new_model_state,
                      new_comp)

    # ---- shard_map wiring --------------------------------------------------

    buf_spec = jax.P(axis_name) if schedule.sharded else jax.P()

    def _opt_specs(opt_state):
        if not schedule.sharded:
            return jax.tree.map(lambda _: jax.P(), opt_state)
        return tuple(
            jax.tree.map(
                lambda l, p=b.padded_size: _opt_bucket_specs(axis_name, p, l),
                bucket_state,
            )
            for b, bucket_state in zip(plan.buckets, opt_state)
        )

    def _state_specs(state: DearState) -> DearState:
        return DearState(
            buffers=tuple(buf_spec for _ in state.buffers),
            opt_state=_opt_specs(state.opt_state),
            step=jax.P(),
            model_state=jax.tree.map(lambda _: jax.P(), state.model_state),
            comp_state=jax.tree.map(lambda _: jax.P(axis_name),
                                    state.comp_state),
        )

    def _batch_specs(batch):
        if batch_spec_fn is not None:
            return batch_spec_fn(batch)
        return jax.tree.map(lambda _: jax.P(axis_name), batch)

    def init(params, model_state=None) -> DearState:
        if model_state is not None and not has_model_state:
            raise ValueError(
                "init() got model_state but build_train_step was called "
                "without model_state_template — the loss_fn would never "
                "see it")
        if has_model_state and model_state is None:
            model_state = model_state_template
        if has_model_state and donate:
            # Deep-copy on device: model_state would otherwise alias the
            # CALLER's arrays and the donated step would delete them out
            # from under the caller on the first step. (device_put
            # may_alias=False does not reliably unlink donation on all
            # backends.)
            model_state = jax.tree.map(jnp.copy, model_state)
        bufs = tuple(F.pack_all(params, plan))
        if donate:
            # pack_all can hand back a CALLER array unchanged (single-leaf
            # 1-D bucket with zero pad: reshape(-1) and a 1-element concat
            # are both identity) — same donation hazard as model_state.
            bufs = tuple(jnp.copy(b) for b in bufs)
        opt = tuple(optimizer.init(b) for b in bufs)
        step0 = jnp.zeros((), jnp.int32)
        if compressed:
            stateful = not isinstance(comp.init(1, jnp.float32), tuple)

            def centry(b, buf):
                res = (
                    jnp.zeros((world, b.padded_size), buf.dtype)
                    if stateful else ()
                )
                if momentum_correction:
                    return {
                        "res": res,
                        "vel": jnp.zeros((world, b.padded_size), buf.dtype),
                    }
                return res

            comp_state = tuple(
                centry(b, buf) for b, buf in zip(plan.buckets, bufs)
            )
        else:
            comp_state = ()
        state = DearState(bufs, opt, step0,
                          model_state if has_model_state else (), comp_state)
        specs = _state_specs(state)
        return jax.tree.map(
            lambda x, s: jax.device_put(x, jax.sharding.NamedSharding(mesh, s)),
            state, specs)

    # ---- the schedule's account of itself, and telemetry -------------------
    # Static per-step communication accounting for this (plan, mode),
    # returned as `TrainStep.comm`. The hot path pays two dict adds + one
    # span per step when telemetry is ON and a single attribute check when
    # it is off (the contract scripts/check_telemetry_overhead.py measures).
    _leaf_itemsize = (
        jnp.dtype(plan.leaves[0].dtype).itemsize if plan.leaves else 4
    )
    comm = _tel_counters.plan_comm_accounting(
        plan, mode=mode,
        comm_itemsize=(jnp.dtype(comm_dtype).itemsize
                       if comm_dtype is not None else _leaf_itemsize),
        # None: the shard is gathered as it is stored (`gather_unpack`)
        gather_itemsize=(jnp.dtype(gather_dtype).itemsize
                         if gather_dtype is not None else None),
        compressor=comp.name if compressed else None,
        density=density,
        # hierarchical: account the cross-slice host leg at the BUILD
        # slice count (elastic renorms change the live set at runtime;
        # the static accounting states the full-membership schedule)
        num_slices=(dcn.num_slices if dcn is not None else 1),
        dcn_partition_mb=(partition_mb if dcn is not None else None),
    )
    _tr = _telemetry.get_tracer()
    if _tr.enabled:
        _tr.count("dear.plan_builds")
        _tr.event(
            "dear.plan_built", mode=mode, world=world,
            buckets=plan.num_buckets, total_elements=plan.total_size,
            payload_bytes_per_step=comm.payload_bytes_per_step,
        )

    def _count_step(tr):
        if not tr.enabled:
            return
        tr.count("dear.steps")
        for leg, nbytes in comm.payload_bytes_by_leg.items():
            tr.count(f"dear.{leg}_bytes", nbytes)
        schedule.count_launches(tr)

    # ---- program assembly --------------------------------------------------

    _compiled: dict = {}

    def _mapped(state: DearState, batch):
        """The shard_map-wrapped device step — single construction point
        shared by the per-step and scanned-multi-step programs."""
        state_specs = _state_specs(state)
        return jax.shard_map(
            device_step,
            mesh=mesh,
            in_specs=(state_specs, _batch_specs(batch)),
            out_specs=(state_specs, jax.P()),
            check_vma=False,
        )

    def _jitted(state: DearState, batch):
        key = jax.tree.structure((state, batch))
        fn = _compiled.get(key)
        if fn is None:
            tr = _telemetry.get_tracer()
            if tr.enabled:
                # a jit-cache miss: a fresh trace+compile will run on the
                # first call of the returned fn
                tr.count("dear.compiles")
                tr.event("dear.compile", mode=mode,
                         cached_programs=len(_compiled))
            fn = jax.jit(
                _mapped(state, batch),
                donate_argnums=(0,) if donate else (),
            )
            _compiled[key] = fn
        return fn

    def step(state: DearState, batch):
        # the one host span on the profiler's clock: any `jax.profiler`
        # session shows the dispatch beside the device's lines (the
        # DEAR_TELEMETRY span below keeps a clock of its own)
        with _annotate("dear.step"):
            return _step(state, batch)

    def _step(state: DearState, batch):
        tr = _telemetry.get_tracer()
        ds = _dtrace.get_stream()
        if not tr.enabled and not ds.enabled:
            return _jitted(state, batch)(state, batch)
        _count_step(tr)
        with tr.span("dear.step", mode=mode):
            if not ds.enabled:
                return _jitted(state, batch)(state, batch)
            t0 = time.monotonic()
            out = _jitted(state, batch)(state, batch)
            # single-program schedule: in-graph RS/AG overlaps inside
            # this one dispatch, so the whole step is the compute row
            ds.emit("dear.step", t0=t0, dur_s=time.monotonic() - t0,
                    cat="compute", mode=mode)
            return out

    def lower(state: DearState, batch):
        return _jitted(state, batch).lower(state, batch)

    _multi_compiled: dict = {}

    def multi_step(n: int):
        """One jitted program running ``n`` steps on the same batch (the
        benchmark protocol) via `lax.scan`; returns the final state and the
        LAST step's metrics. Amortizes dispatch and exposes cross-step
        overlap to the scheduler. The jitted fn is cached per ``n`` so a
        training loop calling ``ts.multi_step(8)(state, batch)`` repeatedly
        does not retrace."""
        cached = _multi_compiled.get(n)
        if cached is not None:
            return cached
        tr = _telemetry.get_tracer()
        if tr.enabled:
            tr.event("dear.multi_step_compile", mode=mode, n=n)

        def fn(state: DearState, batch):
            mapped = _mapped(state, batch)

            def body(s, _):
                s, m = mapped(s, batch)
                return s, m

            final, ms = jax.lax.scan(body, state, None, length=n)
            return final, jax.tree.map(lambda x: x[-1], ms)

        jitted = jax.jit(fn, donate_argnums=(0,) if donate else ())
        _multi_compiled[n] = jitted
        return jitted

    def gather_params(state: DearState):
        """Materialize the full parameter pytree (for eval / checkpointing).
        Equivalent to the reference reading back `model.parameters()` after
        the lazy per-module updates have run. In 'dear' mode the buffers are
        sharded global arrays; XLA inserts the gather automatically."""
        return F.unpack_all(list(state.buffers), plan)

    if dcn is not None:
        # backward program -> host DCN exchange -> update program
        _step, lower, multi_step = H.build_step(
            _fwd_bwd, _apply, mesh=mesh, plan=plan, axes=axes,
            axis_name=axis_name, state_specs=_state_specs,
            batch_spec_fn=batch_spec_fn, dcn=dcn,
            dcn_slice_axis=dcn_slice_axis, partition_mb=partition_mb,
            donate=donate, count_step=_count_step)
    return TrainStep(init=init, step=step, gather_params=gather_params,
                     plan=plan, mesh=mesh, lower=lower,
                     multi_step=multi_step, dcn=dcn, comm=comm)
