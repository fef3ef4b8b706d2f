"""The DeAR schedule: decoupled reduce-scatter + all-gather data parallelism.

This is the TPU-native heart of the framework, replacing the reference's
``_DistributedOptimizer`` (dear/dear_dopt.py:56-378), which wires the
schedule out of eager-mode machinery: per-param backward hooks launch an
async reduce-scatter when a fusion bucket fills (:242-272), ``step()`` syncs
reduce-scatters and kicks the first all-gather (:348-372), and per-module
forward *pre*-hooks of the NEXT iteration sync the gather, apply a fused SGD
just-in-time, and prefetch the next bucket's gather (:274-308).

Functional redesign. Master parameters and optimizer state live as
*shards* — each device owns 1/world of every fusion buffer (which is exactly
the reduce-scatter output, and makes ZeRO-1 sharding inherent rather than an
option). One jitted train step:

    per bucket g:  full_g   = all_gather(param_shard_g)        # feeds fwd
    params         = unpack(full_0..G)
    loss, grads    = value_and_grad(loss_fn)(params, batch)
    per bucket g:  grad_shard_g = reduce_scatter(grads_g) / N  # fed by bwd
    per bucket g:  param_shard_g, opt_g = update(grad_shard_g, ...)

The data dependencies reproduce DeAR's overlap by construction: bucket g's
all-gather is needed only by layer-group g's forward, so XLA's latency-hiding
scheduler runs gather g+1 while layer-group g computes (the reference's
"prefetch next bucket" hook, dear_dopt.py:283-287); each bucket's
reduce-scatter depends only on that bucket's grads, so it overlaps the rest
of the backward (the reference's backward-hook launches). The cross-iteration
pipelining (reference applies updates of step i-1 during step i's forward) is
carried functionally: shards updated at the end of step i are gathered at the
top of step i+1 — same pipeline, but step 0 trains on correctly-reduced
gradients, fixing the reference's documented quirk of training iteration 0 on
unreduced local gradients (dear_dopt.py:278,367-371).

Baseline schedules (same builder, ``mode=``):
  'allreduce' — per-bucket fused all-reduce after backward, full params and
                replicated optimizer everywhere (MG-WFBP/DDP/Horovod shape;
                mgwfbp/dopt.py:690, pytorch-ddp/imagenet_benchmark.py:65)
  'rsag'      — per-bucket all-reduce decomposed as RS+AG inline
                (WFBP's allReduceRSAG, wfbp/dopt.py:675-701)
  'rb'        — per-bucket reduce-to-root + broadcast (dear/dopt_rb.py)
  'bytescheduler' — allreduce with tensor PARTITIONING + priority-shaped
                dependencies (ByteScheduler, SOSP'19; reference
                bytescheduler/imagenet_benchmark.py:73-82, --partition
                :37-38). Each bucket's flat gradient splits into
                ``partition_mb``-sized chunks; every chunk is an
                INDEPENDENT reduction (as an RS+AG pair — XLA's
                all-reduce combiner would re-fuse small all-reduces
                and undo the partitioning). The reference enforces
                priority with a credit-based userspace scheduler over
                async NCCL ops; here priority is carried by dependency
                shape — chunk order follows layer order, chunks never
                depend on each other, so XLA's scheduler is free to
                run early-layer chunks first and overlap the rest with
                compute. (The reference's cross-iteration preemption
                has no analog inside one jitted step; the dear mode's
                gather-next-step pipelining is the XLA-native way to
                get that effect.)
  'dear-fused'— the dear schedule with BOTH collective legs executed by
                Pallas ring kernels (`ops/collective_matmul.py`) instead
                of XLA collectives: the per-bucket all-gather is a ring of
                async remote copies, and the per-bucket reduce-scatter is
                FUSED with the optimizer-update epilogue — each ring step
                RDMAs the partial-sum tile to the neighbor, accumulates
                the incoming tile in fp32, and the final step applies the
                traced `ShardOptimizer.update` to the owned shard inside
                the same kernel (sub-XLA, tile-granular overlap; FLUX /
                T3 ported to TPU). Numerics match 'dear' at dtype
                tolerance (ring reduction order differs from
                psum_scatter; the gather leg and the update math are
                exact). Constraints: a single dp axis spanning the whole
                mesh, elementwise optimizers only (no LAMB), no
                clip_norm. The models' QKV/MLP projections can
                additionally route through the ring collective-matmul via
                their ``projection_impl`` hook (see
                `ops.collective_matmul.make_ring_projection_impl`).
  'fsdp'      — ZeRO-3 beyond the reference (which stops at ZeRO-1 via
                ZeroRedundancyOptimizer, pytorch-ddp/imagenet_benchmark.py:
                10,67-68): the loss is differentiated with respect to the
                SHARDS, so the per-bucket reduce-scatter is literally the
                AD transpose of the per-bucket all-gather, and a custom
                rematerialization policy (`checkpoint_name` on every
                gather/unpack intermediate + a policy denying those names
                AND the cheap view/cast prims that alias them) re-gathers
                each bucket in the backward pass instead of keeping full
                parameters live across forward→backward. Numerics are
                identical to 'dear'; peak memory drops by ~one full
                parameter set on multi-bucket models. (XLA's CSE can in
                principle re-merge the two identical gathers, reverting
                memory — but not correctness — to 'dear' behavior; the
                offload/remat machinery in current XLA preserves them.)
"""

from __future__ import annotations

import time
from typing import Any, Callable, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from dear_pytorch_tpu.comm import backend
from dear_pytorch_tpu.comm import collectives as C
from dear_pytorch_tpu.comm.backend import DP_AXIS
from dear_pytorch_tpu.observability import counters as _tel_counters
from dear_pytorch_tpu.observability import dtrace as _dtrace
from dear_pytorch_tpu.observability import tracer as _telemetry
from dear_pytorch_tpu.ops import collective_matmul as CM
from dear_pytorch_tpu.ops import compression as Z
from dear_pytorch_tpu.ops import fusion as F
from dear_pytorch_tpu.ops.fused_sgd import (
    LayerwiseShardOptimizer,
    ShardOptimizer,
    fused_sgd,
)

MODES = ("dear", "dear-fused", "allreduce", "rsag", "rb", "bytescheduler",
         "fsdp")
#: Ablation switches (reference `exclude_parts`, dear/dear_dopt.py:75-76,
#: dear/batch.sh:18-43). Time-breakdown instruments — numerics are garbage
#: when a phase is excluded, exactly as in the reference.
EXCLUDABLE = ("reducescatter", "allgather")

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


def _opt_bucket_specs(axis_name: str, bucket_padded: int, opt_state_leaf):
    """Spec for one bucket's optimizer-state leaf: leaves shaped exactly like
    the bucket's flat buffer hold per-element state and shard with it;
    anything else (momentum 'initialized' flag, adam count) is replicated.

    Limitation (documented): a genuinely replicated 1-D leaf whose length
    coincides with this bucket's padded size is indistinguishable by shape
    and would be sharded; pass ``opt_spec_fn`` to `build_train_step` to
    override for such optimizers.
    """
    if (
        getattr(opt_state_leaf, "ndim", None) == 1
        and opt_state_leaf.shape[0] == bucket_padded
    ):
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
    exclude_parts: Sequence[str] = (),
    comm_dtype=None,
    has_aux: bool = False,
    donate: bool = True,
    opt_spec_fn: Optional[Callable[[int, Any], Any]] = None,
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
      mode: 'dear' | 'allreduce' | 'rsag' | 'rb' | 'bytescheduler' | 'fsdp'
        (see the module docstring for each schedule).
      threshold_mb / nearby_layers / flags / plan: bucketing controls
        (defaults mirror THRESHOLD=25 MB, dear/dear_dopt.py:42-44).
      exclude_parts: subset of {'reducescatter','allgather'} — skip that
        collective for time-breakdown ablations ('dear' mode only).
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
        (WFBP-family) schedule — the reference applies compression only
        there (dear/dear_dopt.py:381-398) — OR on the 'dear' schedule
        (beyond reference): the bucket's gradient leg becomes a compressed
        reduction (every device reconstructs the dense mean from the
        gathered payloads and keeps its reduce-scatter slice), while the
        parameter all-gather leg stays dense; error-feedback residuals
        stay per-device in ``DearState.comp_state`` exactly as on the
        allreduce path. 'dear-fused' rejects compression at build time
        (the ring kernels exchange dense fp tiles only). ``compressor``
        is a name from `ops.compression.compressors` ('qint8' = the
        int8-packed wire format); ``density`` the kept fraction for the
        top-k family; ``gtopk=True`` uses the recursive-halving gTop-k
        reduction (wfbp/dopt.py:50-107) instead of allgather-accumulate.
        Sign compressors perform majority vote; their "gradient" is ±1
        (signSGD — scale lives in the lr).
      remat: None (default) or 'full' — wrap the differentiated loss in
        `jax.checkpoint`, trading recompute for activation memory (a
        searched axis of the plan-space autotuner). 'fsdp' owns its own
        policy and rejects this knob.
      momentum_correction: DGC-style momentum correction for SPARSE
        compressed training (Lin et al. 2018; reference wfbp/dopt.py:769-775
        local velocity accumulation, :946-951 post-step mask). When > 0, a
        LOCAL velocity ``u = mc·u + g`` is sparsified instead of the raw
        gradient, and ``u`` is cleared at the coordinates actually sent —
        momentum for rarely-sent coordinates keeps accumulating locally
        instead of being lost to sparsification. The optimizer should then
        be momentum-free (the velocity already carries it); the reference
        likewise bypasses its SGD momentum buffer when correction is on
        (wfbp/dopt.py:934-942).
      axis_name: one mesh axis name, or a TUPLE of axis names — e.g.
        ``('dp', 'sp')`` for combined data + sequence parallelism. Gradients
        reduce-scatter over every listed axis (the ZeRO shard degree is the
        product), and ``loss_fn`` may itself use collectives over an
        individual axis (e.g. ring attention over 'sp').
      batch_spec_fn: ``batch -> PartitionSpec pytree`` overriding the
        default "shard every leaf's dim 0 over axis_name" input layout —
        required for dp×sp, where the batch dim shards over 'dp' and the
        sequence dim over 'sp'.
      partition_mb: the per-level bucket partition. In 'bytescheduler'
        mode, the chunk size of the in-program partitioned reductions
        (MB of the comm dtype; the reference's ``--partition`` /
        ``BYTESCHEDULER_PARTITION``). On the hierarchical schedule
        (``dcn=``), the CROSS-SLICE message size: each bucket's reduced
        partial crosses the DCN in chunks of this many MB
        (`ops.fusion.chunk_bounds`), independent of the intra-slice
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
      opt_spec_fn: optional ``(bucket_index, state_leaf) -> PartitionSpec``
        override for optimizer-state sharding (see `_opt_bucket_specs`).
      dcn: a `comm.dcn.DcnExchanger` — turns ``mode='dear'`` into the
        HIERARCHICAL two-level schedule on a nested mesh: the per-bucket
        reduce-scatter / all-gather run over the intra-slice ``axis_name``
        (ICI) inside the jitted programs, and the cross-slice averaging of
        the reduced partials runs between them on the host, over the
        exchanger's DCN transport (chunked at ``partition_mb``, the
        per-level bucket partition). The step becomes two compiled
        programs — backward (grads per slice) and update — with the DCN
        leg in between; neither program depends on the slice count, so an
        elastic slice loss/rejoin renormalizes via
        ``dcn.set_slices(...)`` with NO recompilation. The mesh must
        carry a ``dcn_slice_axis`` axis of size ``len(dcn.local_slices)``
        (1 on a one-slice-per-process fleet; >1 when one process emulates
        several slices); the ZeRO shard degree is the INTRA-slice world.
        Rejected combinations (loudly, at build): every mode but 'dear'
        ('dear-fused' rings would span the DCN boundary — their
        remote-copy device ids are single-mesh axis indices), gradient
        compression, ``clip_norm`` (a global norm needs a cross-slice
        reduction inside the step), ``model_state_template`` (BN stats
        would sync intra-slice only and silently diverge across slices),
        ``has_aux``, ``exclude_parts``, and ``mean_axes != axis_name``.
        ``multi_step`` is unavailable (the host leg cannot ride a scan).
      dcn_slice_axis: mesh axis name enumerating this host's LOCAL slices
        (only with ``dcn``).
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    for e in exclude_parts:
        if e not in EXCLUDABLE:
            raise ValueError(f"exclude_parts entries must be in {EXCLUDABLE}")
    if exclude_parts and mode != "dear":
        raise ValueError("exclude_parts is a 'dear'-mode ablation")
    mesh = mesh or backend.global_mesh()
    axes = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    axis_name = axes if len(axes) > 1 else axes[0]
    world = 1
    for a in axes:
        world *= mesh.shape[a]
    mean_axes = tuple(mean_axes) if mean_axes is not None else axes
    if not set(mean_axes) <= set(axes):
        raise ValueError(f"mean_axes {mean_axes} not a subset of {axes}")
    mean_world = 1
    for a in mean_axes:
        mean_world *= mesh.shape[a]
    optimizer = optimizer or fused_sgd(lr=0.01)
    if plan is None:
        plan = F.make_plan(
            params_template,
            world,
            threshold_mb=threshold_mb,
            nearby_layers=nearby_layers,
            flags=flags,
        )
    if plan.world != world:
        raise ValueError(
            f"plan was built for world={plan.world} but mesh axis "
            f"{axis_name!r} has size {world}"
        )
    sharded = mode in ("dear", "dear-fused", "fsdp")
    fused = mode == "dear-fused"
    excl = frozenset(exclude_parts)
    # SDC sentinel: the per-bucket fingerprint is baked into the program
    # only when armed — resolved once here at build time, so the disabled
    # path carries zero extra ops and no per-step branch
    from dear_pytorch_tpu.resilience import sdc as _sdc
    sdc_fp = _sdc.sdc_enabled()
    if dcn is not None and fused:
        # checked BEFORE the generic dear-fused mesh guards: the caller
        # asked for a ring spanning the DCN boundary, and that — not the
        # nested mesh shape it implies — is the actionable error
        raise ValueError(
            "multislice (dcn=) cannot ride mode='dear-fused': the "
            "Pallas ring kernels address devices by single-mesh axis "
            "index and a ring spanning the DCN boundary would issue "
            "remote copies to devices outside this slice's ICI mesh "
            "— use mode='dear' (hierarchical RS+AG over ICI + host "
            "DCN exchange)"
        )
    if fused:
        if len(axes) != 1:
            raise ValueError(
                "dear-fused rings address devices by LOGICAL mesh id and "
                "currently support a single data-parallel axis; got "
                f"{axes}"
            )
        if mesh.size != world:
            raise ValueError(
                "dear-fused rings require the reduction axis to span the "
                f"whole mesh (axis size {world} vs mesh size {mesh.size}): "
                "the kernels' remote-copy device ids are the axis indices"
            )
        if clip_norm is not None:
            raise ValueError(
                "dear-fused applies the optimizer inside the per-bucket "
                "reduce-scatter kernel; the cross-bucket global-norm clip "
                "needs every bucket's reduced gradient first — use "
                "mode='dear' with clip_norm"
            )
        if isinstance(optimizer, LayerwiseShardOptimizer):
            raise ValueError(
                "dear-fused cannot fuse LayerwiseShardOptimizer (LAMB) "
                "into the epilogue kernel: trust ratios need cross-shard "
                "psums — use mode='dear'"
            )
    if gather_dtype is not None and not sharded:
        raise ValueError("gather_dtype applies to the sharded ('dear'/'fsdp') "
                         "schedules only")
    if mode == "fsdp" and comm_dtype is not None:
        raise ValueError(
            "'fsdp' communicates both legs in gather_dtype (the "
            "reduce-scatter is the all-gather's AD transpose); comm_dtype "
            "must be None"
        )
    has_model_state = model_state_template is not None
    comp = Z.get_compressor(compressor)
    compressed = comp.name != "none"
    if compressed and mode == "dear-fused":
        # plan-build-time guard, mirroring the dear-fused constraints
        # above: rejecting here (loudly) beats a silent dense fallback
        # that would report compressed-trial timings for a schedule that
        # never compressed anything
        raise ValueError(
            "gradient compression cannot ride mode='dear-fused': the "
            "Pallas ring kernels execute the reduce-scatter leg (fused "
            "with the optimizer epilogue) on dense fp tiles and cannot "
            "exchange sparse/sign/int8-packed payloads — use mode='dear' "
            "(compressed decoupled schedule) or mode='allreduce'"
        )
    if compressed and mode not in ("allreduce", "dear"):
        raise ValueError(
            "gradient compression is supported on the 'allreduce' "
            "(WFBP-family, reference parity) and 'dear' (decoupled "
            f"RS+AG) schedules; got mode={mode!r}"
        )
    if compressed and exclude_parts:
        raise ValueError(
            "exclude_parts ablations assume dense collectives; the "
            "compressed gradient leg has no reduce-scatter to exclude"
        )
    if remat not in (None, "none", "full"):
        raise ValueError(
            f"remat must be None, 'none' or 'full', got {remat!r}")
    remat = None if remat in (None, "none") else remat
    if remat is not None and mode == "fsdp":
        raise ValueError(
            "'fsdp' owns its rematerialization policy (the re-gather-in-"
            "backward checkpoint); remat applies to the other schedules"
        )
    if compressed and mean_axes != axes:
        raise ValueError(
            "compressed reductions divide by the full axis product and do "
            "not support mean_axes != axis_name (e.g. sequence-parallel "
            "partial-gradient sums); use dense schedules on multi-axis "
            "meshes with mean_axes"
        )
    if gtopk and comp.name not in Z.SPARSE:
        raise ValueError("gtopk requires a top-k-family compressor")
    if int(accum_steps) != accum_steps or accum_steps < 1:
        raise ValueError(f"accum_steps must be a positive int, got {accum_steps}")
    accum_steps = int(accum_steps)
    if clip_norm is not None:
        if compressed:
            raise ValueError(
                "clip_norm with compression is unsupported: the sparse "
                "payloads are already a lossy gradient transform"
            )
        if clip_norm <= 0:
            raise ValueError(f"clip_norm must be positive, got {clip_norm}")
    if momentum_correction and comp.name not in Z.SPARSE:
        raise ValueError(
            "momentum_correction requires a sparse (top-k-family) "
            "compressor (reference wfbp/dopt.py:769: mc applies on the "
            "sparse path only)"
        )
    if dcn is not None:
        # the remaining multi-slice guards, PR-8 style: reject loudly at
        # plan-build rather than silently degrading to a single-level
        # schedule (dear-fused was rejected above, pre-mesh-shape checks)
        if mode != "dear":
            raise ValueError(
                "the hierarchical (dcn=) schedule is the two-level "
                f"decoupled 'dear' mode; got mode={mode!r}"
            )
        if compressed:
            raise ValueError(
                "gradient compression on the hierarchical schedule is "
                "unsupported: the cross-slice leg averages DENSE reduced "
                "partials on the host — compress-on-DCN is a named "
                "follow-up, not a silent fallback"
            )
        if clip_norm is not None:
            raise ValueError(
                "clip_norm needs the GLOBAL gradient norm, which crosses "
                "the slice boundary inside the step — unsupported with "
                "dcn= (the host leg averages per-bucket partials only)"
            )
        if has_model_state:
            raise ValueError(
                "model_state (BatchNorm stats etc.) syncs over the "
                "intra-slice axes only and would silently diverge across "
                "slices — unsupported with dcn="
            )
        if has_aux:
            raise ValueError(
                "has_aux is unsupported with dcn=: only the loss travels "
                "the cross-slice scalar path"
            )
        if exclude_parts:
            raise ValueError(
                "exclude_parts ablations assume the single-level "
                "schedule; unsupported with dcn="
            )
        if mean_axes != axes:
            raise ValueError(
                "mean_axes != axis_name is unsupported with dcn=: the "
                "intra-slice legs average over every local axis and the "
                "host leg averages over slices"
            )
        if dcn_slice_axis in axes:
            raise ValueError(
                f"dcn_slice_axis {dcn_slice_axis!r} must not be a "
                "reduction axis: the cross-slice exchange owns it"
            )
        n_local = len(dcn.local_slices)
        if (dcn_slice_axis not in mesh.shape
                or mesh.shape[dcn_slice_axis] != n_local):
            raise ValueError(
                f"the nested mesh needs axis {dcn_slice_axis!r} of size "
                f"{n_local} (one row per LOCAL slice "
                f"{dcn.local_slices}); mesh has {dict(mesh.shape)}"
            )

    # ---- per-device step body (runs inside shard_map) ----------------------
    # Split into two halves so the single-program schedules compose them
    # into one jitted step (`device_step`, graph unchanged) while the
    # hierarchical schedule jits them as SEPARATE programs with the
    # host-level cross-slice exchange in between: `_fwd_bwd` ends at the
    # intra-slice-reduced bucket gradients, `_apply` starts at the
    # optimizer update.

    def _fwd_bwd(state: DearState, batch):
        idx = lax.axis_index(axis_name)

        def cast_shard(s):
            return s.astype(gather_dtype) if gather_dtype is not None else s

        if mode == "fsdp":
            params = None  # gathered inside the differentiated fn
        elif sharded:
            full_bufs = []
            for g, (b, s) in enumerate(zip(plan.buckets, state.buffers)):
                with jax.named_scope(f"dear/bucket{g}/gather"):
                    if "allgather" in excl:
                        # ablation: fake the gather with zeros
                        full = lax.dynamic_update_slice_in_dim(
                            jnp.zeros((b.padded_size,), cast_shard(s).dtype),
                            cast_shard(s),
                            idx * b.shard_size,
                            axis=0,
                        )
                    elif fused:
                        # Pallas ring all-gather: chunk t+1 streams over
                        # the ICI while chunk t lands (bit-identical to
                        # lax.all_gather)
                        full = CM.ring_all_gather(cast_shard(s), axis_name)
                    else:
                        full = C.all_gather(cast_shard(s), axis_name)
                full_bufs.append(full)
            # With gather_dtype, leaves STAY in gather_dtype (identical to
            # the fsdp path): the model's own cast is then the identity,
            # and the two sharded schedules see the same numerics.
            with jax.named_scope("dear/unpack"):
                params = F.unpack_all(full_bufs, plan,
                                      cast=gather_dtype is None)
        else:
            with jax.named_scope("dear/unpack"):
                params = F.unpack_all(list(state.buffers), plan)
        if rng_seed is not None:
            if dcn is not None:
                # fold a GLOBALLY unique device index: devices at the
                # same ICI position on different slices must not share
                # dropout streams
                rng_idx = (
                    jnp.asarray(dcn.local_slices, jnp.int32)[
                        lax.axis_index(dcn_slice_axis)] * world + idx)
            else:
                rng_idx = idx
            with jax.named_scope("dear/rng"):
                step_rng = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(rng_seed),
                                       state.step),
                    rng_idx,
                )
            extra_args: tuple = (step_rng,)
        else:
            extra_args = ()
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

        if mode == "fsdp":
            from jax.ad_checkpoint import checkpoint_name

            def _named(x):
                return checkpoint_name(x, "dear_gathered")

            def _named_unpack(bufs):
                """Gather + unpack with EVERY intermediate named (wrap=):
                the policy below excludes named values from the residual
                set; one unnamed alias anywhere between gather and
                consumption (a slice, reshape, or cast) would be saveable
                and let AD keep full parameters alive fwd→bwd, silently
                reverting to 'dear' memory behavior. (A model that re-casts
                params internally still creates such an alias — pass
                gather_dtype matching the model's compute dtype so that
                cast is the identity.)"""
                full = []
                for g, s in enumerate(bufs):
                    with jax.named_scope(f"dear/bucket{g}/gather"):
                        full.append(
                            _named(C.all_gather(cast_shard(s), axis_name)))
                with jax.named_scope("dear/unpack"):
                    return F.unpack_all(full, plan, wrap=_named,
                                        cast=gather_dtype is None)

            def shard_loss(bufs, mstate, b, extra):
                return canonical_loss(_named_unpack(bufs), mstate, b, extra)

            # Save activations but NOT the gathered buckets: backward
            # re-gathers each bucket right where its grads are needed.
            # ``save_anything_except_these_names`` alone cannot force that:
            # it lets AD save the named value's unnamed PRODUCER (the gather
            # or a view of it) instead — every eqn that isn't a `name` is
            # saveable under it, so nothing is ever recomputed. Deny the
            # gather and all cheap view/cast prims too; then the only
            # saveable values are genuine compute outputs (activations), and
            # the cheapest path back to the weights in backward is
            # re-gathering the shard (which jax.checkpoint wraps in an
            # optimization barrier — prevent_cse — so XLA cannot fold the
            # two gathers back into one and silently restore 'dear'-mode
            # param liveness).
            unsaveable = frozenset({
                "all_gather", "reshape", "dynamic_slice",
                "convert_element_type", "transpose", "squeeze",
                "broadcast_in_dim", "concatenate", "pad",
            })

            def _fsdp_policy(prim, *_, **params):
                if prim.name == "name":
                    return params["name"] != "dear_gathered"
                return prim.name not in unsaveable

            diff_fn = jax.checkpoint(shard_loss, policy=_fsdp_policy)
            w0 = tuple(state.buffers)
        else:
            # remat='full': recompute the forward during backward instead
            # of saving activations — a memory/recompute trade the plan-
            # space autotuner searches as a categorical axis
            diff_fn = (jax.checkpoint(canonical_loss) if remat == "full"
                       else canonical_loss)
            w0 = params

        vg = jax.value_and_grad(diff_fn, has_aux=True)
        if accum_steps == 1:
            (loss, (new_model_state, aux)), grads = vg(
                w0, state.model_state, batch, extra_args
            )
        else:
            # Microbatch scan: grads SUM across microbatches (divided once at
            # the end), model state threads through, per-microbatch rng keys.
            def _split(x):
                if x.shape[0] % accum_steps:
                    raise ValueError(
                        f"batch leaf leading axis {x.shape[0]} is not "
                        f"divisible by accum_steps={accum_steps} (note: this "
                        "is the PER-DEVICE shard size)"
                    )
                return x.reshape(
                    (accum_steps, x.shape[0] // accum_steps) + x.shape[1:]
                )

            mb_batch = jax.tree.map(_split, batch)

            def mb_body(carry, xs):
                ms, gacc = carry
                b_i, i = xs
                extra = (
                    (jax.random.fold_in(extra_args[0], i),)
                    if extra_args else ()
                )
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
            aux = (
                None if mb_auxs is None
                else jax.tree.map(lambda a: jnp.mean(a, axis=0), mb_auxs)
            )
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

        # fsdp: grads ARE the per-bucket shards already (AD transposed the
        # gathers into reduce-scatters); others: pack the param-tree grads.
        if mode == "fsdp":
            grad_bufs = None
        else:
            with jax.named_scope("dear/pack"):
                grad_bufs = F.pack_all(grads, plan, dtype=comm_dtype)

        new_comp = []

        def _reduce_bucket(g, b):
            """Bucket ``g``'s packed gradient buffer -> the gradient this
            device updates with (traced under ``dear/bucket<g>/reduce``)."""
            gbuf = None if mode == "fsdp" else grad_bufs[g]
            if mode == "fsdp":
                grad = grads[g].astype(state.buffers[g].dtype) / mean_world
            elif fused:
                # the reduce-scatter happens INSIDE the fused update kernel
                # (ring RS + optimizer epilogue); carry the raw comm buffer
                grad = gbuf
            elif compressed:
                pdtype = state.buffers[g].dtype
                centry = state.comp_state[g]
                if momentum_correction:
                    res_entry, vel_entry = centry["res"], centry["vel"]
                else:
                    res_entry, vel_entry = centry, None
                stateless = isinstance(res_entry, tuple)
                res = () if stateless else res_entry.reshape(
                    res_entry.shape[1:]
                )
                gin = gbuf.astype(pdtype)
                if momentum_correction:
                    # local velocity accumulates momentum BEFORE
                    # sparsification (wfbp/dopt.py:769-775)
                    vel = (
                        momentum_correction
                        * vel_entry.reshape(vel_entry.shape[1:])
                        + gin
                    )
                    gin = vel
                payload, new_res = comp.compress(gin, res, density)
                if comp.name in Z.SIGN:
                    grad = Z.sign_majority_vote_allreduce(
                        payload, b.padded_size, pdtype, axis_name
                    )
                elif gtopk:
                    grad, kept_idx = Z.gtopk_sparse_allreduce(
                        payload, b.padded_size, pdtype, axis_name,
                        Z._k_of(b.padded_size, density),
                    )
                    if not stateless:
                        # Error feedback under gTop-k: coordinates this
                        # device SENT (zeroed out of its residual) but the
                        # global top-k REJECTED would otherwise lose their
                        # gradient mass permanently. Re-add them to the
                        # residual (reference wfbp/dopt.py:726-728).
                        kept_mask = (
                            jnp.zeros((b.padded_size,), jnp.bool_)
                            .at[kept_idx].set(True)
                        )
                        sent_idx = payload["indices"]
                        rejected = jnp.where(
                            kept_mask[sent_idx],
                            jnp.zeros_like(payload["values"]),
                            payload["values"],
                        )
                        new_res = new_res.at[sent_idx].add(
                            rejected.astype(new_res.dtype)
                        )
                elif comp.name in Z.QUANT:
                    grad = Z.int8_allreduce(
                        payload, b.padded_size, pdtype, axis_name
                    )
                else:
                    grad = Z.sparse_allreduce(
                        payload, b.padded_size, pdtype, axis_name
                    )
                new_centry = () if stateless else new_res[None, :]
                if momentum_correction:
                    # clear velocity at SENT coordinates (the reference's
                    # post-step `buf *= zero_condition`, wfbp/dopt.py:946-951
                    # with compression.py:42-48)
                    vel = vel.at[payload["indices"]].set(0.0)
                    new_centry = {"res": new_centry, "vel": vel[None, :]}
                new_comp.append(new_centry)
                if sharded:
                    # 'dear': every device just reconstructed the same
                    # dense mean; keep this device's reduce-scatter slice
                    # (the update below runs on shards, and the dense
                    # all-gather of the UPDATED params next step is the
                    # unchanged AG leg)
                    grad = lax.dynamic_slice_in_dim(
                        grad, idx * b.shard_size, b.shard_size
                    )
            elif sharded:
                if "reducescatter" in excl:  # ablation: local slice, no comm
                    gshard = lax.dynamic_slice_in_dim(
                        gbuf, idx * b.shard_size, b.shard_size
                    )
                else:
                    gshard = C.reduce_scatter(gbuf, axis_name)
                grad = gshard.astype(state.buffers[g].dtype) / mean_world
            elif mode == "allreduce":
                grad = C.all_reduce(gbuf, axis_name).astype(
                    state.buffers[g].dtype
                ) / mean_world
            elif mode == "bytescheduler":
                # Fixed-size partitions, one independent reduction each;
                # chunk order == layer order == priority order. Transport is
                # the RS+AG decomposition, not plain all-reduce: XLA's
                # all-reduce combiner re-fuses small neighboring all-reduces
                # into one op (the compiler has its own bucketer), which
                # would silently undo the partitioning — RS/AG pairs are not
                # combined, so the per-chunk schedule survives compilation.
                pieces = [
                    C.all_reduce_rsag(gbuf[lo:hi], axis_name)
                    for lo, hi in F.chunk_bounds(
                        b.padded_size, gbuf.dtype.itemsize, partition_mb)
                ]
                grad = jnp.concatenate(pieces).astype(
                    state.buffers[g].dtype
                ) / mean_world
            elif mode == "rsag":
                grad = C.all_reduce_rsag(gbuf, axis_name).astype(
                    state.buffers[g].dtype
                ) / mean_world
            else:  # 'rb': two-phase reduce-to-root + broadcast (dopt_rb.py)
                reduced = C.reduce(gbuf, 0, axis_name)
                grad = C.broadcast(reduced, 0, axis_name).astype(
                    state.buffers[g].dtype
                ) / mean_world
            return grad

        bucket_grads = []
        for g, b in enumerate(plan.buckets):
            with jax.named_scope(f"dear/bucket{g}/reduce"):
                bucket_grads.append(_reduce_bucket(g, b))

        return (bucket_grads, loss, aux, new_model_state,
                tuple(new_comp) if compressed else state.comp_state)

    def _apply(state: DearState, bucket_grads, metrics, new_model_state,
               new_comp):
        if clip_norm is not None:
            with jax.named_scope("dear/clip"):
                sumsq = sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)))
                    for g in bucket_grads
                )
                if sharded:
                    # each device holds a DISTINCT shard: psum completes
                    # the global square-norm. (Replicated modes hold
                    # identical full gradients — their local sum already
                    # IS the global one.)
                    sumsq = lax.psum(sumsq, axis_name)
                gnorm = jnp.sqrt(sumsq)
                scale = jnp.minimum(
                    1.0, clip_norm / jnp.maximum(gnorm, 1e-12))
                bucket_grads = [
                    g * scale.astype(g.dtype) for g in bucket_grads
                ]
            metrics["grad_norm"] = gnorm

        layerwise = isinstance(optimizer, LayerwiseShardOptimizer)
        # lr-schedule optimizers evaluate lr(step) on device from the
        # replicated global counter — exact under multi_step/lax.scan
        step_kw = (
            {"step": state.step}
            if getattr(optimizer, "needs_step", False) else {}
        )
        def _update_bucket(g, grad):
            """The optimizer's update of bucket ``g`` (traced under
            ``dear/bucket<g>/update``, whichever optimizer was passed)."""
            if fused:
                # one Pallas kernel: ring reduce-scatter of the bucket's
                # comm buffer + the optimizer update on the owned shard in
                # the final ring step (the fused epilogue)
                new_p, new_o = CM.fused_reduce_scatter_update(
                    grad, state.buffers[g], state.opt_state[g], optimizer,
                    axis_name, mean_world=mean_world, **step_kw,
                )
            elif layerwise:
                # per-parameter segment metadata for exact cross-shard
                # reductions (LAMB trust ratios): this device's slice of the
                # bucket's element->parameter map, plus the psum completing
                # shard-local segment sums (identity when replicated).
                # Computed from the TINY per-bucket offsets array via
                # searchsorted — materializing FusionPlan.segment_ids here
                # would bake an int32[padded_size] constant (~1/4 of the
                # parameter bytes) into the program on every device.
                b = plan.buckets[g]
                starts = jnp.asarray(b.offsets, jnp.int32)
                if sharded:
                    idx = lax.axis_index(axis_name)
                    pos = idx * b.shard_size + jnp.arange(
                        b.shard_size, dtype=jnp.int32
                    )
                    psum = lambda x: lax.psum(x, axis_name)  # noqa: E731
                else:
                    pos = jnp.arange(b.padded_size, dtype=jnp.int32)
                    psum = lambda x: x  # noqa: E731
                seg = (
                    jnp.searchsorted(starts, pos, side="right")
                    .astype(jnp.int32) - 1
                )
                seg = jnp.where(pos < b.size, seg, len(b.leaf_ids))
                new_p, new_o = optimizer.update(
                    grad, state.opt_state[g], state.buffers[g],
                    seg, len(b.leaf_ids) + 1, psum, **step_kw,
                )
            else:
                new_p, new_o = optimizer.update(
                    grad, state.opt_state[g], state.buffers[g], **step_kw
                )
            return new_p, new_o

        new_buffers, new_opt = [], []
        for g, grad in enumerate(bucket_grads):
            with jax.named_scope(f"dear/bucket{g}/update"):
                new_p, new_o = _update_bucket(g, grad)
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
                    if sharded:
                        s = lax.psum(s, axis_name)
                    fps.append(s)
                metrics["sdc_fp"] = jnp.stack(fps)
        next_state = DearState(
            tuple(new_buffers), tuple(new_opt), state.step + 1,
            new_model_state, new_comp,
        )
        return next_state, metrics

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

    buf_spec = jax.P(axis_name) if sharded else jax.P()

    def _opt_specs(opt_state):
        if not sharded:
            return jax.tree.map(lambda _: jax.P(), opt_state)
        out = []
        for b, bucket_state in zip(plan.buckets, opt_state):
            if opt_spec_fn is not None:
                out.append(
                    jax.tree.map(lambda l, i=b.index: opt_spec_fn(i, l), bucket_state)
                )
            else:
                out.append(
                    jax.tree.map(
                        lambda l, p=b.padded_size: _opt_bucket_specs(axis_name, p, l),
                        bucket_state,
                    )
                )
        return tuple(out)

    def _state_specs(state: DearState) -> DearState:
        return DearState(
            buffers=tuple(buf_spec for _ in state.buffers),
            opt_state=_opt_specs(state.opt_state),
            step=jax.P(),
            model_state=jax.tree.map(lambda _: jax.P(), state.model_state),
            comp_state=jax.tree.map(
                lambda _: jax.P(axis_name), state.comp_state
            ),
        )

    def _batch_specs(batch):
        if batch_spec_fn is not None:
            return batch_spec_fn(batch)
        if dcn is not None:
            # nested mesh: the global batch shards over local slices AND
            # the intra-slice axis jointly (each slice sees its data
            # shard; each ICI device its sub-shard)
            return jax.tree.map(
                lambda _: jax.P((dcn_slice_axis,) + axes), batch)
        return jax.tree.map(lambda _: jax.P(axis_name), batch)

    def init(params, model_state=None) -> DearState:
        if model_state is not None and not has_model_state:
            raise ValueError(
                "init() got model_state but build_train_step was called "
                "without model_state_template — the loss_fn would never "
                "see it"
            )
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
            state,
            specs,
        )

    # ---- telemetry ---------------------------------------------------------
    # Static per-step communication accounting for this (plan, mode). The
    # hot path pays two dict adds + one span per step when telemetry is ON
    # and a single attribute check when it is off (the contract
    # scripts/check_telemetry_overhead.py measures).
    _leaf_itemsize = (
        jnp.dtype(plan.leaves[0].dtype).itemsize if plan.leaves else 4
    )
    _acct = _tel_counters.plan_comm_accounting(
        plan, mode=mode,
        comm_itemsize=(jnp.dtype(comm_dtype).itemsize
                       if comm_dtype is not None else _leaf_itemsize),
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
    _leg_bytes = {
        leg: _acct.leg_bytes_per_step(leg)
        for leg in sorted({r.leg for r in _acct.rows})
    }
    _tr = _telemetry.get_tracer()
    if _tr.enabled:
        _tr.count("dear.plan_builds")
        _tr.event(
            "dear.plan_built", mode=mode, world=world,
            buckets=plan.num_buckets, total_elements=plan.total_size,
            payload_bytes_per_step=_acct.payload_bytes_per_step,
        )

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

    # ---- hierarchical (multi-slice) two-program step -----------------------
    # Backward program -> host DCN exchange -> update program. The jitted
    # halves never see the slice count, so elastic slice transitions
    # renormalize via `dcn.set_slices` with no recompile.

    _compiled_hg: dict = {}
    _compiled_ha: dict = {}

    def _hier_device_grads(state: DearState, batch):
        bucket_grads, loss, _aux, _nms, _ncomp = _fwd_bwd(state, batch)
        # aux / model state / compressor state are inert here — the dcn
        # build guards rejected every combination that would produce them
        with jax.named_scope("dear/metrics"):
            loss_sl = lax.pmean(loss, axis_name).reshape(1)
        return tuple(bucket_grads), loss_sl

    def _hier_grads_jitted(state: DearState, batch):
        key = jax.tree.structure((state, batch))
        fn = _compiled_hg.get(key)
        if fn is None:
            state_specs = _state_specs(state)
            mapped = jax.shard_map(
                _hier_device_grads,
                mesh=mesh,
                in_specs=(state_specs, _batch_specs(batch)),
                out_specs=(
                    tuple(jax.P((dcn_slice_axis,) + axes)
                          for _ in plan.buckets),
                    jax.P(dcn_slice_axis),
                ),
                check_vma=False,
            )
            fn = jax.jit(mapped)
            _compiled_hg[key] = fn
        return fn

    def _hier_device_apply(state: DearState, reduced, loss_g):
        grads = [r.astype(state.buffers[g].dtype)
                 for g, r in enumerate(reduced)]
        metrics = {"loss": loss_g}
        return _apply(state, grads, metrics, state.model_state,
                      state.comp_state)

    def _hier_apply_jitted(state: DearState, reduced, loss_g):
        key = jax.tree.structure((state, reduced))
        fn = _compiled_ha.get(key)
        if fn is None:
            state_specs = _state_specs(state)
            mapped = jax.shard_map(
                _hier_device_apply,
                mesh=mesh,
                in_specs=(
                    state_specs,
                    tuple(jax.P(axis_name) for _ in plan.buckets),
                    jax.P(),
                ),
                out_specs=(state_specs, jax.P()),
                check_vma=False,
            )
            fn = jax.jit(mapped, donate_argnums=(0,) if donate else ())
            _compiled_ha[key] = fn
        return fn

    def _hier_step(state: DearState, batch):
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
            if dcn is not None:
                return _hier_step(state, batch)
            return _jitted(state, batch)(state, batch)
        if tr.enabled:
            tr.count("dear.steps")
            for leg, nbytes in _leg_bytes.items():
                tr.count(f"dear.{leg}_bytes", nbytes)
            if fused:
                # per-step Pallas ring-kernel launch accounting (one fused
                # RS+update and one ring all-gather per bucket per step) —
                # the overlap auditor joins these with the static leg
                # bytes above
                tr.count("kernel.fused_rs_launches", plan.num_buckets)
                tr.count("kernel.ring_ag_launches", plan.num_buckets)
        with tr.span("dear.step", mode=mode):
            if dcn is not None:
                # no covering stream span here: the hierarchical step's
                # DCN leg is genuinely exposed comm, and a wrapping
                # compute span would mark it hidden in the critical-path
                # analysis (_hier_step emits backward/apply itself)
                return _hier_step(state, batch)
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
        if dcn is not None:
            # the backward program is the schedule's compute body (the
            # update program is a per-bucket elementwise epilogue); MFU
            # accounting and HLO audits read this one
            return _hier_grads_jitted(state, batch).lower(state, batch)
        return _jitted(state, batch).lower(state, batch)

    _multi_compiled: dict = {}

    def multi_step(n: int):
        """One jitted program running ``n`` steps on the same batch (the
        benchmark protocol) via `lax.scan`; returns the final state and the
        LAST step's metrics. Amortizes dispatch and exposes cross-step
        overlap to the scheduler. The jitted fn is cached per ``n`` so a
        training loop calling ``ts.multi_step(8)(state, batch)`` repeatedly
        does not retrace."""
        if dcn is not None:
            raise ValueError(
                "multi_step is unavailable on the hierarchical (dcn=) "
                "schedule: the cross-slice exchange is a host-level leg "
                "and cannot ride inside a compiled lax.scan")
        cached = _multi_compiled.get(n)
        if cached is not None:
            return cached
        tr = _telemetry.get_tracer()
        if tr.enabled:
            tr.count("dear.multi_step_compiles")
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

    return TrainStep(init=init, step=step, gather_params=gather_params,
                     plan=plan, mesh=mesh, lower=lower,
                     multi_step=multi_step, dcn=dcn)
