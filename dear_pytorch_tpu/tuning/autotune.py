"""Live re-bucketing: drive a training loop while a tuner changes the fusion
plan under it.

Reference flow (dear/dopt_rsag_bo.py): every tuner interval the BO tuner
proposes a new threshold; rank 0's choice is broadcast for consistency
(dopt_rsag_bo.py:153, via mpi4py), fusion buffers are freed and regenerated
(:163-171), and training continues — momentum state survives because torch
keeps it per-parameter.

Here a plan change means a re-jit (bucket shapes are trace-time constants).
`AutoTuner` rebuilds the train step with the proposed plan and *repacks* the
carried state: master buffers and any per-element optimizer-state leaves are
unpacked to parameter granularity under the old plan and repacked under the
new one, so SGD momentum (etc.) survives re-bucketing exactly as it does in
the reference. Rank consistency is free: the tuner runs on deterministic
timing input per process and the plan is host metadata compiled into the
SPMD program (single-controller; no broadcast needed on one host, and on
multi-host the measured time of rank 0 can be fed to `Tuner` directly).

Compilation cost accounting matches the reference's protocol: the first
measurement window after each rebuild is discarded as warmup
(tuner.py:62-64 via `Tuner.notify_rebuild`).
"""

from __future__ import annotations

import logging
import math
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from dear_pytorch_tpu.observability import tracer as _telemetry
from dear_pytorch_tpu.ops import fusion as F
from dear_pytorch_tpu.parallel import dear as D
from dear_pytorch_tpu.tuning.bo import Tuner
from dear_pytorch_tpu.tuning.wait_time import (
    estimate_layer_backward_times,
    wait_time_flags,
)

logger = logging.getLogger("dear_pytorch_tpu")


def _repack_bucket_states(old_states, old_plan, new_plan):
    """Repack per-bucket optimizer-state pytrees across plans.

    Leaves whose shape is ``(old_padded_size,)`` are treated as per-element
    state: unpacked to parameter granularity and repacked per the new plan.
    Any other leaf (scalars like momentum's 'initialized' flag, adam counts)
    is carried from old bucket 0 into every new bucket — valid when such
    leaves are bucket-independent, which holds for step-count/flag style
    state (documented limitation).
    """
    if not old_states:
        return ()
    treedef = jax.tree.structure(old_states[0])
    per_bucket_flat = [jax.tree.leaves(s) for s in old_states]
    n_leaves = len(per_bucket_flat[0])

    new_flat_per_bucket = [[] for _ in new_plan.buckets]
    for li in range(n_leaves):
        elementwise = all(
            getattr(per_bucket_flat[bi][li], "shape", None)
            == (old_plan.buckets[bi].padded_size,)
            for bi in range(len(old_plan.buckets))
        )
        if elementwise:
            pieces = {}
            for bi, b in enumerate(old_plan.buckets):
                unpacked = F.unpack_bucket(per_bucket_flat[bi][li], old_plan, bi)
                pieces.update(unpacked)
            leaves_list = [pieces[i] for i in range(len(old_plan.leaves))]
            for nbi, nb in enumerate(new_plan.buckets):
                new_flat_per_bucket[nbi].append(
                    F.pack_bucket(leaves_list, new_plan, nbi)
                )
        else:
            for nbi in range(len(new_plan.buckets)):
                # the same array object lands in every bucket here — safe
                # only because `repack_state` deep-copies every leaf at
                # its boundary before the state meets a donating step
                # (see the copy note there)
                new_flat_per_bucket[nbi].append(per_bucket_flat[0][li])
    return tuple(
        jax.tree.unflatten(treedef, flat) for flat in new_flat_per_bucket
    )


def _repack_comp_state(old_comp, fresh_comp, old_plan, new_plan):
    """Carry per-bucket compressor error-feedback state across a plan
    change. Each stateful leaf is a global ``(world, padded)`` array (one
    residual row per device); rows are unpacked to parameter granularity
    under the old plan and repacked under the new one. Across a WORLD
    change (elastic rescale) the rows cannot map 1:1, so the unsent mass
    is redistributed mass-preservingly: every new row carries the mean of
    the old rows, keeping the residuals' total contribution to the mean
    gradient (``sum(rows)/world``) exactly invariant. Every stateful
    compressor here keeps an ADDITIVE residual in gradient units, so the
    carry is valid even when the compressor axis changes between plans
    (a plan-tuner trial switching eftopk -> qint8 keeps the unsent mass);
    a STRUCTURAL mismatch (stateless compressor, momentum-correction
    velocity appearing/disappearing) resets to the fresh zeros instead of
    guessing. Callers pass HOST (numpy) state — see `repack_state`'s
    staging note."""
    old_entries = list(old_comp)
    fresh_entries = list(fresh_comp)
    if not old_entries or not fresh_entries:
        return tuple(fresh_entries)
    old_leaves = [jax.tree.leaves(e) for e in old_entries]
    fresh_leaves = [jax.tree.leaves(e) for e in fresh_entries]
    n_leaf = len(fresh_leaves[0])
    if len(old_leaves[0]) != n_leaf:
        logger.warning(
            "autotune: compressor state structure changed across plans "
            "(%d vs %d leaves per bucket); error-feedback residuals reset",
            len(old_leaves[0]), n_leaf)
        return tuple(fresh_entries)
    if n_leaf == 0:          # stateless compressor: nothing to carry
        return tuple(fresh_entries)
    if any(getattr(old_leaves[bi][li], "shape", None)
           != (old_plan.world, old_plan.buckets[bi].padded_size)
           for bi in range(len(old_plan.buckets))
           for li in range(n_leaf)):
        logger.warning(
            "autotune: compressor state leaves are not (world, padded) "
            "shaped; error-feedback residuals reset")
        return tuple(fresh_entries)

    out_leaves = [[] for _ in new_plan.buckets]
    for li in range(n_leaf):
        per_bucket = [jnp.asarray(old_leaves[bi][li])
                      for bi in range(len(old_plan.buckets))]
        new_rows = [[] for _ in new_plan.buckets]
        for r in range(old_plan.world):
            pieces = {}
            for bi in range(len(old_plan.buckets)):
                pieces.update(
                    F.unpack_bucket(per_bucket[bi][r], old_plan, bi))
            leaves_list = [pieces[i] for i in range(len(old_plan.leaves))]
            for nbi in range(new_plan.num_buckets):
                new_rows[nbi].append(
                    F.pack_bucket(leaves_list, new_plan, nbi))
        for nbi in range(new_plan.num_buckets):
            stacked = jnp.stack(new_rows[nbi])      # (old_world, padded)
            if new_plan.world != old_plan.world:
                mean = jnp.mean(stacked, axis=0, keepdims=True)
                stacked = jnp.broadcast_to(
                    mean, (new_plan.world, stacked.shape[1]))
            out_leaves[nbi].append(stacked)
    treedef = jax.tree.structure(fresh_entries[0])
    return tuple(jax.tree.unflatten(treedef, leaves)
                 for leaves in out_leaves)


def repack_state(
    state: D.DearState, old_ts: D.TrainStep, new_ts: D.TrainStep
) -> D.DearState:
    """Carry a `DearState` across a plan change: buffers, optimizer state,
    step, model state, AND compressor error-feedback residuals
    (`_repack_comp_state` — the reference reset its buffers on
    regeneration, which silently dropped the unsent gradient mass; here
    the residual algebra survives re-bucketing, checkpoint re-packs, and
    elastic world changes)."""
    # Stage the source state to HOST numpy first. Two reasons: (1) eager
    # unpack/pack on live SHARDED arrays compiles per-op SPMD programs
    # whose cross-device rendezvous can stall for minutes under CPU
    # oversubscription (observed: a repack's gather wedged a tuner trial
    # past the driver timeout at BERT scale) — host staging makes every
    # intermediate single-device; (2) no intermediate can alias a live
    # donated device buffer (see the copy note at the bottom).
    state = jax.tree.map(
        lambda x: np.asarray(jax.device_get(x))
        if hasattr(x, "sharding") else x,
        state,
    )
    params = F.unpack_all(list(state.buffers), old_ts.plan)
    fresh = new_ts.init(params, *(
        (state.model_state,) if state.model_state != () else ()
    ))
    new_opt = _repack_bucket_states(
        list(state.opt_state), old_ts.plan, new_ts.plan
    )
    new_comp = _repack_comp_state(
        state.comp_state, fresh.comp_state, old_ts.plan, new_ts.plan
    )
    # install repacked values with the fresh state's shardings — matched by
    # LEAF ORDER, not structure: a checkpoint-restored state's containers
    # may be dict-form images of the live tuples (utils.checkpoint.
    # elastic_restore), while the leaf order is identical
    fresh_flat, fresh_def = jax.tree_util.tree_flatten(fresh.opt_state)
    new_flat = jax.tree_util.tree_leaves(new_opt)
    if len(new_flat) != len(fresh_flat):
        raise ValueError(
            f"optimizer state leaf count changed across plans: "
            f"{len(new_flat)} vs {len(fresh_flat)} — was the step rebuilt "
            "with a different optimizer?"
        )
    new_opt = jax.tree_util.tree_unflatten(
        fresh_def,
        [jax.device_put(v, ref.sharding)
         for v, ref in zip(new_flat, fresh_flat)],
    )
    # compressor state installs on the fresh shardings by leaf order too
    # (same dict-image tolerance as opt_state above)
    comp_flat = jax.tree_util.tree_leaves(new_comp)
    fresh_comp_flat, fresh_comp_def = jax.tree_util.tree_flatten(
        fresh.comp_state)
    if len(comp_flat) == len(fresh_comp_flat):
        new_comp = jax.tree_util.tree_unflatten(
            fresh_comp_def,
            [jax.device_put(v, ref.sharding)
             for v, ref in zip(comp_flat, fresh_comp_flat)],
        )
    else:
        new_comp = fresh.comp_state
    step = jax.device_put(state.step, fresh.step.sharding)
    out = D.DearState(fresh.buffers, new_opt, step, fresh.model_state,
                      new_comp)
    # Deep-copy EVERY leaf before handing the state to a donating train
    # step. The repack pipeline is built from eager slices/reshapes/
    # device_puts of the live state, and those can alias their sources —
    # `device_put` onto an identical sharding returns the same underlying
    # buffer (the carried ``step`` scalar), identity-shaped unpack/pack
    # round trips short-circuit, and XLA:CPU eager slicing can hand back
    # buffer VIEWS into the parent allocation. Donation then frees memory
    # that other live arrays (or a parent allocation) still own —
    # observed as "Attempt to donate the same buffer twice" and heap
    # corruption ("double free or corruption") on the very next jitted
    # step. `jnp.copy` materializes compact private buffers with the
    # same shardings; rebuilds are rare (tuner trials, elastic
    # transitions), so one state-size copy is noise.
    return jax.tree.map(jnp.copy, out)


class AutoTuner:
    """Training-loop driver with runtime plan tuning.

    strategy='bo': Bayesian optimization over the MB threshold
      (reference dopt_rsag_bo.py; bound (1, 256) MB, 10 trials).
    strategy='wait_time': start with one all-layers bucket
      (num_nearby_layers=-1, dopt_rsag_wt.py) and after ``warmup_steps``
      switch to flags derived from per-layer backward times.
    strategy='plan': the unified plan-space search
      (`tuning.planspace.PlanTuner`) over fusion threshold x compressor x
      comm/gather wire dtype x mode (dear / dear-fused) x remat, with the
      overlap auditor's α-β cost model pruning analytically-dominated
      configurations before they burn live trial steps. The searched axes
      are lifted OUT of the static build kwargs into the starting
      `PlanConfig`; every trial rides `_rebuild` + `repack_state` exactly
      like a threshold trial. Trial sandboxing is snapshot-based: the
      pre-trial train step AND a device copy of the state are held for
      the trial's measurement window, so a diverging trial (int8 wire
      overflow, pathological compression) reverts plan *and parameters*
      in-place — `mark_infeasible` fires, the loop continues on the last
      good config, and the `utils.guard.GuardedTrainer` wrapping this
      never sees a non-finite loss (zero ``guard.rollbacks`` attributed
      to the user's run). Costs one extra state copy while a trial is
      live (searching only; dropped once the tuner finishes).

    ``alpha_beta``: (α, β) seconds/bytes interconnect fit for the cost
    model; when None it is measured once at construction via
    `observability.overlap.fit_interconnect` if ``DEAR_TUNE_FIT=1``,
    otherwise analytic pruning is disabled (trials still run). ``space``
    defaults to `planspace.PlanSpace.from_env()`; ``trial_log`` (or
    ``DEAR_TUNE_LOG``) streams one JSONL record per tuner decision.
    """

    def __init__(
        self,
        loss_fn: Callable,
        params_template,
        *,
        strategy: str = "bo",
        threshold_mb: float = 25.0,
        bound: tuple[float, float] = (1.0, 256.0),
        max_trials: int = 10,
        interval: int = 5,
        cycle_time_s: float = 5e-3,
        warmup_steps: int = 5,
        layer_times: Optional[Sequence[float]] = None,
        log: Callable[[str], None] = lambda s: None,
        clock=None,
        tuner_seed: int = 0,
        space=None,
        alpha_beta: Optional[tuple[float, float]] = None,
        trial_log: Optional[str] = None,
        **build_kwargs: Any,
    ):
        if strategy not in ("bo", "wait_time", "plan"):
            raise ValueError(
                f"unknown strategy {strategy!r}: valid strategies are "
                "'bo' (Bayesian optimization over the fusion threshold), "
                "'wait_time' (layer-timing split flags) and 'plan' "
                "(unified plan-space search over fusion x compression x "
                "wire dtypes x mode x remat)"
            )
        self.strategy = strategy
        self._loss_fn = loss_fn
        self._template = params_template
        self._build_kwargs = dict(build_kwargs)
        self._build_kwargs.pop("threshold_mb", None)
        self._log = log
        self.rebuilds = 0
        self.planner = None

        if strategy == "plan":
            import os as _os

            from dear_pytorch_tpu.tuning import planspace as PS

            # the searched axes come OUT of the static build kwargs and
            # into the starting PlanConfig — the tuner owns them now
            base_mode = self._build_kwargs.pop("mode", "dear")
            if base_mode not in ("dear", "dear-fused"):
                raise ValueError(
                    "strategy='plan' searches the dear/dear-fused "
                    f"schedule family; start from one of those, not "
                    f"mode={base_mode!r}")
            _dcn = self._build_kwargs.get("dcn")
            if space is not None:
                self.space = space
            else:
                # a non-default bo bound (cfg.bo_bound / DEAR_BO_BOUND)
                # narrows the threshold axis; DEAR_TUNE_BOUND still wins
                # when the caller kept the default
                ov = ({"threshold_bound": tuple(bound)}
                      if tuple(bound) != (1.0, 256.0) else {})
                if _dcn is not None:
                    # hierarchical build: the space searches the
                    # per-level bucket partition too, and multislice-
                    # illegal combos become infeasible arms
                    ov["num_slices"] = _dcn.num_slices
                self.space = PS.PlanSpace.from_env(**ov)
            base_comp = self._build_kwargs.pop("compressor", None)
            base_density = self._build_kwargs.pop("density", 1.0)
            base = PS.PlanConfig(
                threshold_mb=float(threshold_mb or 25.0),
                mode=base_mode,
                compressor=base_comp,
                density=(float(base_density) if base_comp
                         else self.space.density),
                comm_dtype=PS.dtype_token(
                    self._build_kwargs.pop("comm_dtype", None)),
                gather_dtype=PS.dtype_token(
                    self._build_kwargs.pop("gather_dtype", None)),
                remat=self._build_kwargs.pop("remat", None),
                partition_mb=(self._build_kwargs.pop("partition_mb", None)
                              if _dcn is not None else None),
            )
            kw = {} if clock is None else {"clock": clock}
            self.planner = PS.PlanTuner(
                self.space, x=base, max_trials=max_trials,
                interval=interval, log=log, seed=tuner_seed,
                trial_log=trial_log, **kw,
            )
            self.tuner = self.planner  # shared notify_* driver hooks
            self.ts = D.build_train_step(
                loss_fn, params_template, **base.build_kwargs(),
                **self._build_kwargs,
            )
            self._live_config = base
            self._last_good_config = base
            self._trial_backup = None
            self._last_finite_loss: Optional[float] = None
            if alpha_beta is None and _os.environ.get(
                    "DEAR_TUNE_FIT", "").strip().lower() in (
                        "1", "true", "yes", "on"):
                from dear_pytorch_tpu.observability import overlap as OV

                try:
                    alpha_beta = OV.fit_interconnect(self.ts.mesh)
                    self._log(
                        f"autotune: interconnect fit alpha="
                        f"{alpha_beta[0]:.3e}s beta={alpha_beta[1]:.3e}s/B")
                except Exception as exc:
                    logger.error(
                        "autotune: interconnect fit failed (%s); analytic "
                        "pruning disabled", exc)
            self._alpha_beta = alpha_beta
            self._install_cost_model()
            self._host_step = 0
            return

        if strategy == "bo":
            kw = {} if clock is None else {"clock": clock}
            self.tuner: Optional[Tuner] = Tuner(
                x=threshold_mb, bound=bound, max_num_steps=max_trials,
                interval=interval, log=log, seed=tuner_seed, **kw,
            )
            self.ts = D.build_train_step(
                loss_fn, params_template, threshold_mb=threshold_mb,
                **self._build_kwargs,
            )
            # trial sandboxing bookkeeping: the threshold compiled into the
            # live plan, and the last one that produced a finite loss (the
            # revert target when a trial fails or diverges)
            self._live_threshold = float(threshold_mb)
            self._last_good_threshold = float(threshold_mb)
        else:
            self.tuner = None
            self._cycle = cycle_time_s
            self._warmup_steps = warmup_steps
            self._layer_times = layer_times
            self._switched = False
            # all layers in one bucket to start (nearby_layers=-1)
            self.ts = D.build_train_step(
                loss_fn, params_template, nearby_layers=-1,
                **self._build_kwargs,
            )
        self._host_step = 0

    def init(self, params, model_state=None):
        args = (params,) if model_state is None else (params, model_state)
        return self.ts.init(*args)

    @property
    def plan(self):
        """The LIVE train step's fusion plan — lets a
        `utils.guard.GuardedTrainer` wrap the tuner directly (its
        checkpoint path reads ``ts.plan``)."""
        return self.ts.plan

    def _install_cost_model(self) -> None:
        """(Re)build the planner's analytic cost model for the CURRENT
        world — called at construction and after every elastic rescale
        (the α-β fit survives; the plans must be rebuilt for the new
        shard sizes). On hierarchical builds the model is LINK-AWARE:
        the cross-slice 'dcn' rows are priced with their own fit —
        ``DEAR_TUNE_FIT_DCN="alpha,beta"`` explicit, or
        ``DEAR_TUNE_FIT_DCN=1`` to least-squares it from the live
        exchanger's per-fetch timing samples (`overlap.fit_dcn`)."""
        if self.planner is None or self._alpha_beta is None:
            return
        import os as _os

        from dear_pytorch_tpu.tuning import planspace as PS

        world = self.ts.plan.world
        template = self._template
        kw = {}
        dcn = self._build_kwargs.get("dcn")
        if dcn is not None:
            kw["num_slices"] = dcn.num_slices
            ab = getattr(self, "_dcn_alpha_beta", None)
            if ab is None:
                raw = _os.environ.get("DEAR_TUNE_FIT_DCN", "").strip()
                if "," in raw:
                    a, b = raw.split(",")
                    ab = (float(a), float(b))
                elif raw.lower() in ("1", "true", "yes", "on"):
                    from dear_pytorch_tpu.observability import (
                        overlap as OV,
                    )

                    try:
                        ab = OV.fit_dcn(dcn.samples())
                        self._log(
                            f"autotune: DCN link fit alpha={ab[0]:.3e}s "
                            f"beta={ab[1]:.3e}s/B "
                            f"({len(dcn.samples())} samples)")
                    except ValueError as exc:
                        logger.warning(
                            "autotune: DCN link fit unavailable (%s); "
                            "dcn rows priced at the ICI fit", exc)
                self._dcn_alpha_beta = ab
            if ab is not None:
                kw["dcn_alpha"], kw["dcn_beta"] = ab
        self.planner.cost_model = PS.CostModel(
            lambda thr: F.make_plan(template, world, threshold_mb=thr),
            *self._alpha_beta, **kw,
        )

    def _rebuild(self, state, *, force: bool = False, **plan_kwargs):
        from dear_pytorch_tpu.utils.checkpoint import plan_fingerprint

        tr = _telemetry.get_tracer()
        old_ts = self.ts
        new_ts = D.build_train_step(
            self._loss_fn, self._template, **plan_kwargs,
            **self._build_kwargs,
        )
        if not force and \
                plan_fingerprint(new_ts.plan) == plan_fingerprint(old_ts.plan):
            # a different threshold that bucketizes identically: skip the
            # repack/re-jit AND keep the current (still valid) measurement
            # window
            if tr.enabled:
                tr.event("autotune.plan_unchanged",
                         kwargs=repr(plan_kwargs)[:120])
            self._log(f"autotune: plan unchanged by {plan_kwargs}; no rebuild")
            return state
        with tr.span("autotune.rebuild", strategy=self.strategy,
                     buckets=new_ts.plan.num_buckets):
            state = repack_state(state, old_ts, new_ts)
        _dcn = self._build_kwargs.get("dcn")
        if _dcn is not None and hasattr(_dcn, "repack_residual"):
            # the degraded-DCN error-feedback residual lives in bucket
            # rows of the OLD plan: carry it across the re-bucketing with
            # the same mass-preserving algebra as the compressor state
            _dcn.repack_residual(old_ts.plan, new_ts.plan)
        self.ts = new_ts
        self.rebuilds += 1
        if tr.enabled:
            tr.count("autotune.rebuilds")
            tr.event("autotune.rebuilt", strategy=self.strategy,
                     buckets=new_ts.plan.num_buckets,
                     kwargs=repr(plan_kwargs)[:120])
        if self.tuner is not None:
            self.tuner.notify_rebuild()
        self._log(
            f"autotune: re-bucketed to {new_ts.plan.num_buckets} buckets "
            f"({plan_kwargs})"
        )
        return state

    def _trial_infeasible(self, state, bad_threshold: float, why: str):
        """Sandbox a failed/diverged BO trial: record it as infeasible
        (dominated observation, consumed trial) and revert the live plan
        to the last known-good threshold — the tuning run survives.
        Returns the (possibly reverted) state."""
        tr = _telemetry.get_tracer()
        if tr.enabled:
            tr.count("autotune.trial_failures")
            tr.event("autotune.trial_infeasible",
                     threshold_mb=float(bad_threshold), why=why[:120])
        self._log(
            f"autotune: trial threshold {bad_threshold:.4f} MB infeasible "
            f"({why}); reverting to {self._last_good_threshold:.4f} MB"
        )
        self.tuner.mark_infeasible(
            float(bad_threshold), revert_to=self._last_good_threshold
        )
        if self._live_threshold != self._last_good_threshold:
            try:
                state = self._rebuild(
                    state, threshold_mb=self._last_good_threshold
                )
                self._live_threshold = self._last_good_threshold
            except Exception as exc:  # revert itself failed: keep running
                logger.error(
                    "autotune: revert rebuild to %.4f MB failed (%s); "
                    "continuing on the trial plan",
                    self._last_good_threshold, exc,
                )
        return state

    def rescale(self, view, *, mesh: Optional[jax.sharding.Mesh] = None,
                state: Optional[D.DearState] = None):
        """Rebuild the train step for a NEW replica count after an elastic
        membership transition (`utils.guard.GuardedTrainer`'s
        ``on_membership_change`` hook calls this with the committed
        `resilience.membership.MembershipView`). The bucket grouping is
        preserved (`F.rescale_plan`) — only the per-bucket padding/shard
        sizes change — and the membership epoch is stamped into the plan,
        so `utils.checkpoint.plan_fingerprint` distinguishes the rescaled
        plan even when the world size coincides with an earlier epoch.

        ``mesh`` defaults to a 1-D dp mesh over the first ``view.world``
        global devices (single-controller CPU emulation; a real pod passes
        the re-initialized post-shrink mesh). ``state`` is optional
        because the guard restores from checkpoint AFTER this hook (the
        elastic re-pack lands directly in the new plan); pass a live state
        to carry it across the resize in-process (`repack_state`).

        Sandboxed like a BO trial: the rebuild is functional — on any
        failure the previous train step stays installed and the exception
        propagates (counted as ``autotune.rescale_failures``), so the
        caller can fall back to crash-for-relaunch without a half-swapped
        plan.
        """
        world = int(getattr(view, "world", view))
        epoch = int(getattr(view, "epoch", 0) or 0)
        old_ts = self.ts
        dcn = self._build_kwargs.get("dcn")
        if dcn is not None:
            # hierarchical schedule: the ICI axis is not elastic — the
            # plan world (intra-slice shard degree) and the mesh are
            # FIXED. A slice-granular membership transition renormalizes
            # the cross-slice leg (no recompile) and restamps the plan
            # epoch so checkpoint fingerprints stay coherent.
            world = old_ts.plan.world
            if mesh is None:
                mesh = old_ts.mesh
            slices = tuple(getattr(view, "slices", ()) or ())
            if slices:
                dcn.set_slices(slices, epoch=epoch)
        if world == old_ts.plan.world and epoch == old_ts.plan.epoch:
            return state
        tr = _telemetry.get_tracer()
        if mesh is None:
            devs = jax.devices()
            if world > len(devs):
                raise ValueError(
                    f"rescale to world={world} needs {world} devices; "
                    f"only {len(devs)} visible (pass an explicit mesh)")
            mesh = jax.sharding.Mesh(
                np.asarray(devs[:world]), (D.DP_AXIS,))
        plan = F.rescale_plan(old_ts.plan, world, epoch=epoch)
        kw = dict(self._build_kwargs)
        kw["mesh"] = mesh
        if self.strategy == "plan":
            # the searched axes live in the current config, not in the
            # static build kwargs — the rescaled step keeps the live
            # (tuned) configuration
            ckw = self._live_config.build_kwargs()
            ckw.pop("threshold_mb", None)  # the rescaled plan wins
            kw.update(ckw)
        try:
            with tr.span("autotune.rescale", world=world, epoch=epoch,
                         buckets=plan.num_buckets):
                new_ts = D.build_train_step(
                    self._loss_fn, self._template, plan=plan, **kw)
                if state is not None:
                    state = repack_state(state, old_ts, new_ts)
        except Exception as exc:
            if tr.enabled:
                tr.count("autotune.rescale_failures")
                tr.event("autotune.rescale_failed", world=world,
                         epoch=epoch, why=f"{type(exc).__name__}: {exc}"[:120])
            logger.error(
                "autotune: rescale to world=%d (epoch %d) failed (%s: %s); "
                "previous plan still installed",
                world, epoch, type(exc).__name__, exc)
            raise
        self.ts = new_ts
        self.rebuilds += 1
        if tr.enabled:
            tr.count("autotune.rescales")
            tr.event("autotune.rescaled", world=world, epoch=epoch,
                     buckets=new_ts.plan.num_buckets)
        if self.tuner is not None:
            # a rescale is a CONTEXT change, not just a re-jit: timings
            # measured on the old world are not comparable — shelve the
            # observation history so the search cannot exploit stale
            # posteriors (next window is warmup via the same call)
            self.tuner.notify_context(world=world, epoch=epoch)
        if self.strategy == "plan":
            self._trial_backup = None  # snapshot predates the new world
            self._install_cost_model()
        self._log(
            f"autotune: rescaled plan to world={world} "
            f"(membership epoch {epoch}, {new_ts.plan.num_buckets} buckets)"
        )
        return state

    def _revert_trial(self, state, metrics, why: str):
        """A live plan-space trial diverged: restore the pre-trial train
        step AND state from the snapshot, record the trial infeasible, and
        hand back a FINITE loss (the last one the reverted state actually
        produced) so a wrapping `GuardedTrainer` does not book a rollback
        for a failure the tuner already recovered from. The few steps run
        under the trial plan are discarded with it (the step counter
        rewinds to the snapshot's)."""
        old_ts, old_state, old_loss = self._trial_backup
        bad = self._live_config
        tr = _telemetry.get_tracer()
        if tr.enabled:
            tr.count("autotune.trial_failures")
            tr.event("autotune.trial_infeasible",
                     config=bad.describe(), why=why[:120])
        self.planner.mark_infeasible(
            bad, revert_to=self._last_good_config, why=why)
        self.ts = old_ts
        self._live_config = self._last_good_config
        self._trial_backup = None
        self._log(
            f"autotune: trial {bad.describe()} infeasible ({why}); "
            f"reverted plan AND state to {self._last_good_config.describe()}"
        )
        out = dict(metrics)
        out["trial_loss"] = out.get("loss")
        if old_loss is not None:
            out["loss"] = old_loss
        out["tuner_reverted"] = True
        return old_state, out

    def _plan_step(self, state, metrics):
        """Per-step plan-space tuning work (strategy='plan')."""
        import math as _math

        pt = self.planner
        if not pt.finished:
            # drain the async pipeline before the tuner samples its clock
            # (same scalar-fetch protocol as the bo path) — the fetch also
            # feeds divergence detection for the live trial
            loss = float(metrics["loss"])
            if not _math.isfinite(loss):
                if self._trial_backup is not None:
                    return self._revert_trial(state, metrics,
                                              "non-finite loss")
                # no live trial to blame: a genuine divergence — the
                # guard's recovery machinery owns it
                return state, metrics
            self._last_finite_loss = loss
        proposal = pt.step()
        if proposal is not None:
            # a NEW proposal means the live config survived a full
            # measurement window of finite losses: it becomes the revert
            # target and its snapshot is dropped
            self._trial_backup = None
            self._last_good_config = self._live_config
            tr = _telemetry.get_tracer()
            if tr.enabled:
                tr.count("autotune.trials")
                tr.event("autotune.proposal", config=proposal.describe())
            backup = (self.ts,
                      jax.tree.map(jnp.copy, state),
                      self._last_finite_loss)
            try:
                state = self._rebuild(
                    state,
                    force=proposal.key() != self._live_config.key(),
                    **proposal.build_kwargs(),
                )
            except Exception as exc:
                # a combo the surrounding build kwargs cannot express
                # (LAMB x dear-fused, clip_norm x compression, ...) is
                # structurally dead — retire the arm; anything else only
                # penalizes this threshold
                fatal = isinstance(exc, (ValueError, TypeError))
                logger.error(
                    "autotune: rebuild for trial %s raised %s: %s",
                    proposal.describe(), type(exc).__name__, exc,
                )
                if _telemetry.get_tracer().enabled:
                    _telemetry.get_tracer().count("autotune.trial_failures")
                pt.mark_infeasible(
                    proposal, revert_to=self._last_good_config,
                    fatal=fatal,
                    why=f"rebuild raised {type(exc).__name__}: {exc}",
                )
            else:
                self._live_config = proposal
                self._trial_backup = backup
        if pt.finished:
            # the adopted config is not a trial: free the snapshot (it
            # would otherwise pin a full state copy for the rest of the
            # run) and stop treating divergence as the tuner's incident
            self._trial_backup = None
            self._last_good_config = self._live_config
        return state, metrics

    def step(self, state, batch):
        state, metrics = self.ts.step(state, batch)
        self._host_step += 1
        if self.strategy == "plan":
            return self._plan_step(state, metrics)
        if self.strategy == "bo":
            if not self.tuner.finished:
                # drain the async pipeline before the tuner samples its
                # clock: otherwise it would time host dispatch, not the
                # device step
                loss = float(metrics["loss"])
                if not math.isfinite(loss) \
                        and self._live_threshold != self._last_good_threshold:
                    # the active trial diverged: plan repacks are
                    # numerically exact, so this usually means a pathological
                    # bucketization (memory/compile trouble) — record the
                    # trial infeasible and fall back; parameter recovery is
                    # the guard's job, not the tuner's
                    state = self._trial_infeasible(
                        state, self._live_threshold, "non-finite loss"
                    )
                    return state, metrics
            proposal = self.tuner.step()
            if proposal is not None:
                # a NEW proposal means the live threshold survived a full
                # measurement window of finite losses: only now does it
                # become the revert target (a trial that diverges on its
                # second step must still have a known-good plan to fall
                # back to)
                self._last_good_threshold = self._live_threshold
                tr = _telemetry.get_tracer()
                if tr.enabled:
                    tr.count("autotune.trials")
                    tr.event("autotune.proposal",
                             threshold_mb=float(proposal))
                try:
                    state = self._rebuild(state, threshold_mb=float(proposal))
                except Exception as exc:
                    # a bad proposal must not kill the tuning run: the
                    # rebuild never installed (repack_state is functional —
                    # `state` is unchanged on a raise)
                    logger.error(
                        "autotune: rebuild for trial %.4f MB raised %s: %s",
                        float(proposal), type(exc).__name__, exc,
                    )
                    state = self._trial_infeasible(
                        state, float(proposal),
                        f"rebuild raised {type(exc).__name__}",
                    )
                else:
                    self._live_threshold = float(proposal)
        elif not self._switched and self._host_step >= self._warmup_steps:
            times = (
                self._layer_times
                if self._layer_times is not None
                else estimate_layer_backward_times(self.ts.plan)
            )
            flags = wait_time_flags(times, self._cycle)
            self._switched = True
            tr = _telemetry.get_tracer()
            if tr.enabled:
                tr.count("autotune.trials")
                tr.event("autotune.wait_time_decision",
                         buckets=int(sum(flags)), cycle_time_s=self._cycle)
            if sum(flags) > 1:  # one bucket already == current plan
                try:
                    state = self._rebuild(state, flags=flags)
                except Exception as exc:
                    # stay on the (feasible) single-bucket plan
                    if tr.enabled:
                        tr.count("autotune.trial_failures")
                        tr.event("autotune.trial_infeasible",
                                 strategy="wait_time",
                                 why=type(exc).__name__)
                    logger.error(
                        "autotune: wait_time split rebuild failed (%s: %s); "
                        "keeping the all-layers bucket",
                        type(exc).__name__, exc,
                    )
        return state, metrics
