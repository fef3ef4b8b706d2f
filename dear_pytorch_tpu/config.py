"""One typed configuration for the whole framework.

The reference scatters its knobs across three uncoordinated layers —
module-level constants edited in source (THRESHOLD / NUM_NEARBY_LAYERS /
NSTREAMS / CYCLE_TIME, reference dear/dopt_rsag.py:37-40), per-benchmark
argparse, and launcher env vars (dear/horovod_mpi_cj.sh:2-12) — and selects
the communication backend by editing an import line
(dear/imagenet_benchmark.py:14-16). `DearConfig` is the single source of
truth replacing all three: constructible in code, from env vars
(``DEAR_<FIELD>``), or from the benchmark CLIs, and consumed by
`build_train_step` via `.build_kwargs()`.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Optional, Sequence

import jax.numpy as jnp

_COMM_DTYPES = {
    "": None, "none": None,
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "f32": jnp.float32, "float32": jnp.float32,
    "f16": jnp.float16, "float16": jnp.float16,
}


@dataclasses.dataclass
class DearConfig:
    """Every train-step knob in one place (defaults = the reference's)."""

    # schedule (replaces the reference's one-directory-per-method layout)
    mode: str = "dear"    # dear | dear-fused | allreduce | rsag | rb |
    #                       bytescheduler | fsdp
    partition_mb: float = 4.0               # bytescheduler chunk size (MB)

    # tensor fusion (dear/dopt_rsag.py:37-40)
    threshold_mb: Optional[float] = 25.0
    nearby_layers: Optional[int] = None
    flags: Optional[Sequence[int]] = None

    # auto-tuning ('plan' = the unified plan-space search, docs/TUNING.md)
    autotune: Optional[str] = None          # None | 'bo' | 'wait_time' | 'plan'
    bo_bound: tuple = (1.0, 256.0)          # dopt_rsag_bo.py:101
    bo_trials: int = 10                     # tuner.py:9
    bo_interval: int = 5                    # tuner.py:34
    cycle_time_s: float = 5e-3              # dopt_rsag_wt.py CYCLE_TIME

    # compression (dear/compression.py registry; allreduce-schedule only)
    compressor: Optional[str] = None
    density: float = 1.0
    gtopk: bool = False
    momentum_correction: float = 0.0        # DGC mc coefficient (sparse only)

    # optimizer
    optimizer_name: str = "sgd"     # sgd | adamw | lamb (fused, shard-safe)
    lr: float = 0.01
    momentum: float = 0.9
    weight_decay: float = 0.0
    nesterov: bool = False
    adam_betas: tuple = (0.9, 0.999)        # torch.optim.AdamW defaults
    adam_eps: float = 1e-8
    clip_norm: Optional[float] = None       # global-L2 gradient clipping

    # lr schedule (ops/schedules.py; None = fixed lr)
    lr_schedule: Optional[str] = None       # 'linear' | 'cosine' | 'multistep'
    warmup_steps: int = 0
    total_steps: Optional[int] = None       # required by linear/cosine
    end_lr: float = 0.0                     # decay floor (min_lr for cosine)
    lr_milestones: tuple = ()               # multistep boundaries (steps)
    lr_gamma: float = 0.1                   # multistep decay factor

    # precision
    comm_dtype: Any = None                  # e.g. jnp.bfloat16
    gather_dtype: Any = None                # pre-gather cast (dear/fsdp)
    compute_bf16: bool = False

    # rematerialization (None | 'full'; a plan-space autotuner axis)
    remat: Optional[str] = None

    # misc
    rng_seed: Optional[int] = None
    donate: bool = True
    accum_steps: int = 1                    # gradient accumulation microbatches

    def __post_init__(self):
        if self.mode not in ("dear", "dear-fused", "allreduce", "rsag",
                             "rb", "bytescheduler", "fsdp"):
            raise ValueError(f"bad mode {self.mode!r}")
        if self.autotune not in (None, "bo", "wait_time", "plan"):
            raise ValueError(f"bad autotune {self.autotune!r}")
        if self.remat not in (None, "none", "full"):
            raise ValueError(f"bad remat {self.remat!r}")
        if not 0.0 < self.density <= 1.0:
            raise ValueError(f"density must be in (0, 1], got {self.density}")

    # -- construction --------------------------------------------------------

    _ENV_PREFIX = "DEAR_"

    @classmethod
    def from_env(cls, **overrides) -> "DearConfig":
        """Read ``DEAR_<FIELD>`` env vars (the launcher-facing layer;
        replaces configs/envs.conf + shell exports)."""
        kwargs: dict = {}
        for f in dataclasses.fields(cls):
            env = os.environ.get(cls._ENV_PREFIX + f.name.upper())
            if env is None:
                continue
            kwargs[f.name] = cls._parse(f.name, env)
        kwargs.update(overrides)
        return cls(**kwargs)

    @staticmethod
    def _parse(name: str, raw: str):
        raw = raw.strip()
        if name in ("threshold_mb", "clip_norm"):
            return None if raw.lower() in ("none", "") else float(raw)
        if name in ("nearby_layers", "bo_trials", "bo_interval"):
            return None if raw.lower() in ("none", "") else int(raw)
        if name == "accum_steps":  # None is never legal here
            try:
                v = int(raw)
            except ValueError:
                v = 0
            if v < 1:
                raise ValueError(
                    f"DEAR_ACCUM_STEPS must be a positive int, got {raw!r}"
                )
            return v
        if name in ("lr", "momentum", "weight_decay", "density",
                    "cycle_time_s", "partition_mb", "momentum_correction",
                    "adam_eps", "end_lr", "lr_gamma"):
            return float(raw)
        if name == "warmup_steps":
            return int(raw)
        if name == "total_steps":
            return None if raw.lower() in ("none", "") else int(raw)
        if name == "lr_milestones":
            return tuple(int(x) for x in raw.split(",") if x)
        if name == "lr_schedule":
            return None if raw.lower() in ("none", "") else raw
        if name == "adam_betas":
            b1, b2 = raw.split(",")
            return (float(b1), float(b2))
        if name in ("gtopk", "nesterov", "donate", "compute_bf16"):
            return raw.lower() in ("1", "true", "yes")
        if name in ("comm_dtype", "gather_dtype"):
            return _COMM_DTYPES[raw.lower()]
        if name == "flags":
            return [int(x) for x in raw.split(",")]
        if name == "bo_bound":
            lo, hi = raw.split(",")
            return (float(lo), float(hi))
        if name in ("autotune", "compressor", "mode", "remat"):
            return None if raw.lower() in ("none", "") else raw
        return raw

    # -- consumption ---------------------------------------------------------

    def optimizer(self):
        from dear_pytorch_tpu.ops import schedules
        from dear_pytorch_tpu.ops.fused_sgd import (
            fused_adamw,
            fused_lamb,
            fused_sgd,
        )

        lr = schedules.from_config(self)  # float, or step->lr callable
        if self.optimizer_name == "adamw":
            return fused_adamw(
                lr=lr, betas=self.adam_betas, eps=self.adam_eps,
                weight_decay=self.weight_decay,
            )
        if self.optimizer_name == "lamb":
            return fused_lamb(
                lr=lr, betas=self.adam_betas, eps=self.adam_eps,
                weight_decay=self.weight_decay,
            )
        if self.optimizer_name != "sgd":
            raise ValueError(
                f"optimizer_name must be 'sgd', 'adamw' or 'lamb', "
                f"got {self.optimizer_name!r}"
            )
        # with momentum correction the LOCAL pre-sparsification velocity
        # carries the momentum; the reference's step likewise bypasses its
        # SGD momentum buffer (wfbp/dopt.py:934-942)
        momentum = 0.0 if self.momentum_correction > 0 else self.momentum
        return fused_sgd(
            lr=lr, momentum=momentum,
            weight_decay=self.weight_decay, nesterov=self.nesterov,
        )

    def build_kwargs(self) -> dict:
        """kwargs for `parallel.build_train_step` (fusion plan args are
        separate because the autotuner owns them when enabled)."""
        return dict(
            mode=self.mode,
            optimizer=self.optimizer(),
            comm_dtype=self.comm_dtype,
            gather_dtype=self.gather_dtype,
            compressor=self.compressor,
            density=self.density,
            gtopk=self.gtopk,
            momentum_correction=self.momentum_correction,
            rng_seed=self.rng_seed,
            donate=self.donate,
            partition_mb=self.partition_mb,
            accum_steps=self.accum_steps,
            clip_norm=self.clip_norm,
            remat=None if self.remat in (None, "none") else self.remat,
        )

    def describe(self) -> str:
        pairs = dataclasses.asdict(self)
        return "DearConfig(" + ", ".join(
            f"{k}={v!r}" for k, v in pairs.items()
        ) + ")"
