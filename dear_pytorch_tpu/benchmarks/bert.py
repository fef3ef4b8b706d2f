"""Synthetic BERT pre-training benchmark (reference dear/bert_benchmark.py).

Trains ``BertForPreTraining`` (Base or Large, the reference's JSON configs)
on random token batches with the MLM+NSP criterion and prints sentences/sec
in the reference's format.

Example:
  python -m dear_pytorch_tpu.benchmarks.bert \
      --model bert --batch-size 32 --sentence-len 64 --fp16
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from dear_pytorch_tpu import models
from dear_pytorch_tpu.benchmarks import runner
from dear_pytorch_tpu.comm import backend
from dear_pytorch_tpu.comm.backend import DP_AXIS, SP_AXIS
from dear_pytorch_tpu.models import data


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="TPU Synthetic BERT Benchmark",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--model", type=str, default="bert",
                   help=f"one of {models.bert_names()} "
                        "('bert' = BERT-Large, reference naming)")
    p.add_argument("--sentence-len", type=int, default=128,
                   help="input sentence length (the reference launcher "
                        "uses 64, dear/horovod_mpi_cj.sh:6)")
    p.add_argument("--num-hidden-layers", type=int, default=None,
                   help="override encoder depth (scaling studies / smoke "
                        "tests); default = the model's config")
    p.add_argument("--flash-attention", action="store_true", default=False,
                   help="use the Pallas flash-attention kernel "
                        "(ops/flash_attention.py); falls back to dense "
                        "attention wherever attention dropout is active")
    p.add_argument("--sp-degree", type=int, default=1,
                   help="sequence-parallel degree: dp x sp mesh with the "
                        "sequence dim sharded over 'sp' and ring attention "
                        "(or ring-flash with --flash-attention) inside the "
                        "model; DeAR gradients reduce over both axes")
    p.add_argument("--sp-attention", type=str, default=None,
                   choices=["ring", "ring_flash", "ulysses"],
                   help="sequence-parallel attention scheme (default: "
                        "ring, or ring_flash with --flash-attention)")
    p.add_argument("--ring-projections", action="store_true", default=False,
                   help="route the QKV/MLP projections through the ring "
                        "collective-matmul (ops/collective_matmul.py "
                        "projection_impl hook; requires --mode dear-fused "
                        "on a pure dp mesh, hidden %% world == 0)")
    p.add_argument("--dropout0", action="store_true", default=False,
                   help="zero every dropout prob (the modern pretraining "
                        "default; the r5 on-chip A/B reads +29%% BERT / "
                        "+81%% GPT throughput vs the reference's "
                        "train-mode dropout — see PERF.md)")
    runner.add_common_args(p)
    p.set_defaults(batch_size=8, base_lr=2e-5, momentum=0.0)
    return p


def main(argv=None) -> runner.BenchResult:
    args = build_parser().parse_args(argv)
    runner.apply_platform_env()
    scan_steps = runner.validate_scan_steps(args)  # before any resources
    sp = max(int(args.sp_degree), 1)
    if args.sp_attention and sp == 1:
        raise SystemExit("--sp-attention requires --sp-degree > 1")
    if (args.flash_attention and args.sp_attention
            and args.sp_attention != "ring_flash"):
        raise SystemExit("--flash-attention conflicts with "
                         f"--sp-attention {args.sp_attention}; pass one")
    if sp > 1:
        mesh = runner.build_sp_mesh(sp, args.sentence_len, args.pipeline)
    else:
        mesh = backend.init()
    world = backend.dp_size(mesh)  # data-parallel degree (sentences)

    dtype = jnp.bfloat16 if args.fp16 else jnp.float32
    model = models.get_model(args.model, dtype=dtype)
    attention_impl = None
    if args.flash_attention and sp == 1:
        from dear_pytorch_tpu.ops import make_flash_attention_impl

        attention_impl = make_flash_attention_impl()
    projection_impl = None
    if args.ring_projections:
        if args.mode != "dear-fused" or sp > 1:
            raise SystemExit("--ring-projections requires --mode dear-fused "
                             "on a pure dp mesh (no --sp-degree)")
        from dear_pytorch_tpu.ops.collective_matmul import (
            make_ring_projection_impl,
        )

        projection_impl = make_ring_projection_impl(DP_AXIS)
    cfg_over = model.config
    # impls with no attention-prob-dropout path (the sequence-parallel
    # engines; the one-chip flash kernel drops probabilities itself):
    # dropout>0 would silently measure their dense/ring FALLBACK instead of
    # the requested kernel
    kernel_attn = sp > 1 and (args.flash_attention
                              or args.sp_attention in ("ring_flash",
                                                       "ulysses"))
    if args.num_hidden_layers is not None or kernel_attn or args.dropout0:
        import dataclasses

        if args.num_hidden_layers is not None:
            cfg_over = dataclasses.replace(
                cfg_over, num_hidden_layers=args.num_hidden_layers
            )
        if args.dropout0:
            cfg_over = models.dropout_free(cfg_over)
        if kernel_attn and cfg_over.attention_probs_dropout_prob:
            # benchmarking the kernel requires disabling it, and silently
            # measuring the fallback would be worse than changing the config
            runner.log("kernel attention: attention_probs_dropout_prob "
                       f"{cfg_over.attention_probs_dropout_prob} -> 0.0 "
                       "(no prob-dropout path in the requested impl)")
            cfg_over = dataclasses.replace(
                cfg_over, attention_probs_dropout_prob=0.0
            )
    if sp == 1 and (cfg_over is not model.config
                    or attention_impl is not None
                    or projection_impl is not None):
        model = models.BertForPreTraining(
            cfg_over, attention_impl=attention_impl,
            projection_impl=projection_impl,
        )
    cfg = cfg_over  # == model.config whenever the model was (re)built

    global_bs = args.batch_size * world
    batch = data.synthetic_bert_batch(
        jax.random.PRNGKey(0), global_bs, seq_len=args.sentence_len,
        vocab_size=cfg.vocab_size,
    )

    extra_build = {}
    if sp > 1:
        from dear_pytorch_tpu.parallel import sp as SP

        sp_model = SP.sp_bert_model(cfg, flash=args.flash_attention,
                                    attention=args.sp_attention)
        # stage per-leaf: [B, S] leaves shard (dp, sp); [B] leaves (dp,)
        shardings = jax.tree.map(
            lambda s: jax.sharding.NamedSharding(mesh, s),
            SP.bert_sp_batch_specs(batch),
        )
        batch = jax.tree.map(
            lambda x, sh: runner.stage_global(x, sh), batch, shardings
        )
        # init with the dense twin (identical params; the ring model only
        # traces inside shard_map where 'sp' is bound)
        params = models.BertForPreTraining(cfg).init(
            {"params": jax.random.PRNGKey(0)}, batch["input_ids"],
            train=False,
        )["params"]
        loss_fn = SP.make_sp_bert_loss_fn(sp_model, train=True)
        extra_build = dict(
            axis_name=(DP_AXIS, SP_AXIS),
            mean_axes=(DP_AXIS,),
            batch_spec_fn=SP.bert_sp_batch_specs,
        )
    else:
        sharding = jax.sharding.NamedSharding(mesh, jax.P(DP_AXIS))
        batch = runner.stage_global(batch, sharding)  # multi-host safe

        params = model.init(
            {"params": jax.random.PRNGKey(0)}, batch["input_ids"],
            train=False,
        )["params"]

        def loss_fn(p, b, rng):
            logits, nsp = model.apply(
                {"params": p}, b["input_ids"], b["token_type_ids"],
                b["attention_mask"], train=True, rngs={"dropout": rng},
            )
            return models.bert_pretraining_loss(
                logits.astype(jnp.float32), nsp.astype(jnp.float32),
                b["masked_lm_labels"], b["next_sentence_labels"],
            )

    dear_cfg = runner.config_from_args(args, world=backend.dp_size(mesh))
    ts, stepper = runner.build_stepper(
        dear_cfg, loss_fn, params, mesh, mgwfbp=args.mgwfbp, **extra_build,
    )
    state = ts.init(params)

    name = {"bert": "BERT Large", "bert_large": "BERT Large",
            "bert_base": "BERT Base"}[args.model.lower()]
    runner.log(f"{name} Pretraining, Sentence len: {args.sentence_len}")
    runner.log(f"Batch size: {args.batch_size} (per dp rank), "
               f"{global_bs} global")
    runner.log(f"Number of {runner.device_name()}s: "
               f"{backend.device_count()}"
               + (f" (dp {world} x sp {sp})" if sp > 1 else ""))
    runner.log(f"Schedule: {args.mode}; "
               f"fusion: {ts.plan.num_buckets} bucket(s)")

    if sp > 1:
        # --pipeline none enforced above: the constant-batch source
        next_batch, close = runner.make_batch_source(args, None, None, batch)
    else:
        from dear_pytorch_tpu.runtime import pipeline as RP

        spec = RP.bert_spec(global_bs, args.sentence_len,
                            vocab=cfg.vocab_size)
        next_batch, close = runner.make_batch_source(
            args, spec, sharding, batch
        )

    holder = {"state": state, "metrics": None, "batch": batch}
    step_fn, timed_kwargs = runner.make_step_source(
        args, scan_steps, ts, stepper, holder, next_batch
    )
    runner.run_pretune(args, stepper, holder, next_batch)
    # sentences per CHIP per step: with sp, each sentence spans sp chips
    timed_kwargs["batch_size"] = timed_kwargs["batch_size"] / sp

    def sync():
        # One device->host scalar fetch drains the in-order pipeline.
        if holder["metrics"] is not None:  # warmup may be zero steps
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
            step_fn,
            unit="sen",
            sync=sync,
            metrics=metrics_log,
            flops_per_step=flops,
            **timed_kwargs,
        )
    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()
        if metrics_log is not None:
            metrics_log.close()
        close()
    if args.mfu:
        # the autotuner may have re-bucketed: use its CURRENT step (the
        # precomputed flops short-circuits the recompile when present)
        runner.log_mfu(getattr(stepper, "ts", ts), holder["state"], batch,
                       result, flops=flops)
    return result


if __name__ == "__main__":
    main()
