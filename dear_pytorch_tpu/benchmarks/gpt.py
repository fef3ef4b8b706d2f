"""Synthetic GPT causal-LM pre-training benchmark — a model family beyond
the reference zoo (its benchmarks stop at CNNs + BERT,
dear/bert_benchmark.py), measured with the same harness/output format so
the sweep driver's scraper works unchanged.

Example:
  python -m dear_pytorch_tpu.benchmarks.gpt \
      --model gpt2 --batch-size 8 --sequence-len 1024 --fp16 \
      --flash-attention
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from dear_pytorch_tpu import models
from dear_pytorch_tpu.benchmarks import runner
from dear_pytorch_tpu.comm import backend
from dear_pytorch_tpu.comm.backend import DP_AXIS, SP_AXIS
from dear_pytorch_tpu.models import data
from dear_pytorch_tpu.models.gpt import flash_causal_attention_impl


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="TPU Synthetic GPT Benchmark",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--model", type=str, default="gpt2",
                   help=f"one of {models.gpt_names()}")
    p.add_argument("--sequence-len", type=int, default=1024)
    p.add_argument("--num-hidden-layers", type=int, default=None,
                   help="override depth (scaling studies / smoke tests)")
    p.add_argument("--num-experts", type=int, default=0,
                   help="> 0 swaps every block's MLP for the top-1 switch "
                        "MoE (experts replicated under the dp schedules "
                        "here; shard them over an 'ep' axis via "
                        "parallel.tp + EP_RULES)")
    p.add_argument("--ring-projections", action="store_true", default=False,
                   help="route the QKV/MLP projections through the ring "
                        "collective-matmul (ops/collective_matmul.py "
                        "projection_impl hook; requires --mode dear-fused "
                        "on a pure dp mesh, hidden %% world == 0)")
    p.add_argument("--dropout0", action="store_true", default=False,
                   help="zero every dropout prob (the modern pretraining "
                        "default and the r5 headline config: attention "
                        "dropout alone halves S=1024 throughput — PERF.md)")
    p.add_argument("--remat", action="store_true", default=False,
                   help="rematerialize blocks in backward (cfg.remat): "
                        "the enabler for 350M+ dense-attention configs")
    p.add_argument("--flash-attention", action="store_true", default=False,
                   help="causal Pallas flash kernel instead of the dense "
                        "triangle-masked attention")
    p.add_argument("--sp-degree", type=int, default=1,
                   help="sequence-parallel degree: dp x sp mesh, causal "
                        "ring attention (or ring-flash with "
                        "--flash-attention) over global positions — "
                        "long-context autoregressive pretraining")
    p.add_argument("--sp-attention", type=str, default=None,
                   choices=["ring", "ring_flash", "ulysses", "zigzag"],
                   help="sequence-parallel attention scheme (default: ring, "
                        "or ring_flash with --flash-attention; zigzag = "
                        "load-balanced causal ring flash over a striped "
                        "shard layout)")
    runner.add_common_args(p)
    p.set_defaults(batch_size=8, base_lr=1e-4, momentum=0.0)
    return p


def main(argv=None) -> runner.BenchResult:
    args = build_parser().parse_args(argv)
    runner.apply_platform_env()
    scan_steps = runner.validate_scan_steps(args)
    sp = max(int(args.sp_degree), 1)
    if args.sp_attention and sp == 1:
        raise SystemExit("--sp-attention requires --sp-degree > 1")
    if (args.flash_attention and args.sp_attention
            and args.sp_attention != "ring_flash"):
        raise SystemExit("--flash-attention conflicts with "
                         f"--sp-attention {args.sp_attention}; pass one")
    if args.sp_attention == "zigzag" and args.sequence_len % (2 * sp):
        raise SystemExit(
            f"--sp-attention zigzag needs --sequence-len divisible by "
            f"2*sp-degree ({2 * sp}), got {args.sequence_len}"
        )
    if sp > 1:
        mesh = runner.build_sp_mesh(sp, args.sequence_len, args.pipeline,
                                    seq_flag="--sequence-len")
    else:
        mesh = backend.init()
    world = backend.dp_size(mesh)

    dtype = jnp.bfloat16 if args.fp16 else jnp.float32
    model = models.get_model(args.model, dtype=dtype)
    cfg = model.config
    if args.num_hidden_layers is not None:
        cfg = dataclasses.replace(
            cfg, num_hidden_layers=args.num_hidden_layers
        )
    if args.num_experts > 0:
        cfg = dataclasses.replace(cfg, num_experts=args.num_experts)
    if args.dropout0:
        cfg = models.dropout_free(cfg)
    if args.remat:
        cfg = dataclasses.replace(cfg, remat=True)
    if args.sequence_len > cfg.max_position_embeddings:
        raise SystemExit(f"--sequence-len {args.sequence_len} exceeds "
                         f"max_position_embeddings "
                         f"{cfg.max_position_embeddings}")
    attention_impl = None
    # the one-chip flash kernel drops probabilities itself; the
    # sequence-parallel engines have no dropout path
    kernel_attn = sp > 1 and (args.flash_attention
                              or args.sp_attention in ("ring_flash",
                                                       "ulysses", "zigzag"))
    if kernel_attn and cfg.attention_probs_dropout_prob:
        runner.log("kernel attention: attention_probs_dropout_prob "
                   f"{cfg.attention_probs_dropout_prob} -> 0.0 "
                   "(no prob-dropout path in the requested impl)")
        cfg = dataclasses.replace(cfg, attention_probs_dropout_prob=0.0)
    if args.flash_attention and sp == 1:
        attention_impl = flash_causal_attention_impl()
    projection_impl = None
    if args.ring_projections:
        if args.mode != "dear-fused" or sp > 1:
            raise SystemExit("--ring-projections requires --mode dear-fused "
                             "on a pure dp mesh (no --sp-degree)")
        from dear_pytorch_tpu.ops.collective_matmul import (
            make_ring_projection_impl,
        )

        projection_impl = make_ring_projection_impl(DP_AXIS)
    if sp == 1 and (cfg is not model.config or attention_impl is not None
                    or projection_impl is not None):
        model = models.GptLmHeadModel(cfg, attention_impl=attention_impl,
                                      projection_impl=projection_impl)

    global_bs = args.batch_size * world
    batch = data.synthetic_gpt_batch(
        jax.random.PRNGKey(0), global_bs, seq_len=args.sequence_len,
        vocab_size=cfg.vocab_size,
    )

    extra_build = {}
    if sp > 1:
        from dear_pytorch_tpu.parallel import sp as SP

        sp_model = SP.sp_gpt_model(cfg, flash=args.flash_attention,
                                   attention=args.sp_attention)
        zigzag = args.sp_attention == "zigzag"
        if zigzag:
            from dear_pytorch_tpu.parallel.ring_attention import (
                zigzag_permutation,
            )

            perm = zigzag_permutation(args.sequence_len, sp)
            batch = {"input_ids": batch["input_ids"][:, perm]}
        shardings = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s),
            SP.bert_sp_batch_specs(batch),
        )
        batch = jax.tree.map(
            lambda x, sh: runner.stage_global(x, sh), batch, shardings
        )
        params = models.GptLmHeadModel(cfg).init(
            {"params": jax.random.PRNGKey(0)}, batch["input_ids"],
            train=False,
        )["params"]
        loss_fn = SP.make_sp_gpt_loss_fn(
            sp_model, vocab_size=cfg.vocab_size, train=True, zigzag=zigzag
        )
        extra_build = dict(
            axis_name=(DP_AXIS, SP_AXIS),
            mean_axes=(DP_AXIS,),
            batch_spec_fn=SP.bert_sp_batch_specs,
        )
    else:
        sharding = jax.sharding.NamedSharding(mesh, jax.P(DP_AXIS))
        batch = runner.stage_global(batch, sharding)

        params = model.init(
            {"params": jax.random.PRNGKey(0)}, batch["input_ids"],
            train=False,
        )["params"]

        def loss_fn(p, b, rng):
            logits = model.apply(
                {"params": p}, b["input_ids"], train=True,
                rngs={"dropout": rng},
            )
            return models.gpt_lm_loss(logits, b["input_ids"],
                                      vocab_size=cfg.vocab_size)

    dear_cfg = runner.config_from_args(args, world=backend.dp_size(mesh))
    ts, stepper = runner.build_stepper(
        dear_cfg, loss_fn, params, mesh, mgwfbp=args.mgwfbp, **extra_build,
    )
    state = ts.init(params)

    runner.log(f"{args.model} causal-LM pretraining, "
               f"sequence len: {args.sequence_len}")
    runner.log(f"Batch size: {args.batch_size} (per dp rank), "
               f"{global_bs} global "
               f"({global_bs * args.sequence_len} tokens/step)")
    runner.log(f"Number of {runner.device_name()}s: "
               f"{backend.device_count()}"
               + (f" (dp {world} x sp {sp})" if sp > 1 else ""))
    runner.log(f"Schedule: {args.mode}; "
               f"fusion: {ts.plan.num_buckets} bucket(s)")

    if sp > 1:
        # --pipeline none enforced by build_sp_mesh: constant-batch source
        next_batch, close = runner.make_batch_source(args, None, None, batch)
    else:
        from dear_pytorch_tpu.runtime import pipeline as RP

        spec = RP.gpt_spec(global_bs, args.sequence_len,
                           vocab=cfg.vocab_size)
        next_batch, close = runner.make_batch_source(
            args, spec, sharding, batch
        )

    holder = {"state": state, "metrics": None, "batch": batch}
    step_fn, timed_kwargs = runner.make_step_source(
        args, scan_steps, ts, stepper, holder, next_batch
    )
    runner.run_pretune(args, stepper, holder, next_batch)
    # sequences per CHIP per step: with sp, each sequence spans sp chips
    timed_kwargs["batch_size"] = timed_kwargs["batch_size"] / sp

    def sync():
        if holder["metrics"] is not None:
            float(holder["metrics"]["loss"])

    metrics_log = runner.metrics_from_args(args)
    # with --mfu, one AOT cost analysis BEFORE timing: the run-health
    # monitor watches live per-iteration MFU, log_mfu reuses the flops
    flops = (runner.step_flops(getattr(stepper, "ts", ts), holder["state"], batch)
             if args.mfu else None)
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    try:
        result = runner.run_timed(
            step_fn, unit="sen", sync=sync, metrics=metrics_log,
            flops_per_step=flops,
            **timed_kwargs,
        )
    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()
        if metrics_log is not None:
            metrics_log.close()
        close()
    runner.log(f"Tokens/sec on {result.world} {runner.device_name()}(s): "
               f"{result.total_mean * args.sequence_len:.0f}")
    if args.mfu:
        runner.log_mfu(getattr(stepper, "ts", ts), holder["state"], batch,
                       result, flops=flops)
    return result


if __name__ == "__main__":
    main()
