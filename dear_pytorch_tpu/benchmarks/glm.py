"""Synthetic causal-LM pre-training benchmark for the decoders beyond GPT:
the sparse latent-attention one (`models/glm_moe.py`: GLM-4.x / DeepSeek-V3
family), the sparse short-convolution hybrid (`models/lfm2_moe.py`: LFM2-MoE
family), the dense Mamba-2 hybrid (`models/granite_hybrid.py`: Granite-4.0-H
family) and the sparse delta-rule hybrid (`models/ling_hybrid.py`: Ling-3.0
family), the family picked by ``--model``; measured with the same harness and
output format as `benchmarks/gpt.py`.

One chip runs its share of a deployment: ``--num-layers`` of the published
depth (the hybrids: from ``--first-layer`` on), ``--experts-held`` of the
routed experts from ``--expert-offset`` on (the router keeps its width),
``--vocab-size`` ids of the vocabulary. Examples, the benchmark cells' shares
(BENCHMARK.json, ``glm-4.7-flash-ep8.s4096``, ``lfm2-8b-a1b-ep4.s8192``,
``granite-4.0-h-micro-vp4.s4096x1`` and ``ling-3.0-flash-ep64.s8192x1``):

  python -m dear_pytorch_tpu.benchmarks.glm --model glm47_flash \\
      --num-layers 5 --experts-held 8 --vocab-size 19360 \\
      --sequence-len 4096 --batch-size 2 --fp16 --momentum 0.9
  python -m dear_pytorch_tpu.benchmarks.glm --model lfm2_8b_a1b \\
      --first-layer 1 --num-layers 5 --experts-held 8 --vocab-size 16384 \\
      --sequence-len 8192 --batch-size 1 --fp16 --momentum 0.9
  python -m dear_pytorch_tpu.benchmarks.glm --model granite_4_0_h_micro \\
      --num-layers 10 --vocab-size 25088 --remat \\
      --sequence-len 4096 --batch-size 1 --fp16 --momentum 0.9
  python -m dear_pytorch_tpu.benchmarks.glm --model ling_3_0_flash \\
      --first-layer 1 --num-layers 7 --experts-held 8 --vocab-size 19648 \\
      --remat --sequence-len 8192 --batch-size 1 --fp16 --momentum 0.9
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from dear_pytorch_tpu import models
from dear_pytorch_tpu.benchmarks import runner
from dear_pytorch_tpu.comm import backend
from dear_pytorch_tpu.comm.backend import DP_AXIS
from dear_pytorch_tpu.models import data
from dear_pytorch_tpu.observability import tracer as T


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="TPU Synthetic decoder (GLM-MoE, LFM2-MoE, "
                    "Granite-4.0-H, Ling-3.0) Benchmark",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    p.add_argument("--model", type=str, default="glm47_flash",
                   help="one of " + str(
                       models.glm_names() + models.lfm2_names()
                       + models.granite_names() + models.ling_names()))
    p.add_argument("--sequence-len", type=int, default=4096)
    p.add_argument("--num-layers", type=int, default=None,
                   help="blocks run here (the leading dense layer first)")
    p.add_argument("--first-layer", type=int, default=0,
                   help="the hybrids: the published layer the blocks start "
                        "at (its layer_types and dense layers follow)")
    p.add_argument("--experts-held", type=int, default=None,
                   help="routed experts this chip holds (default: all); the "
                        "router scores all of the model's either way")
    p.add_argument("--expert-offset", type=int, default=0,
                   help="first held expert")
    p.add_argument("--vocab-size", type=int, default=None,
                   help="ids of the vocabulary slice held here")
    p.add_argument("--no-mtp", action="store_true", default=False,
                   help="leave the multi-token-prediction module out")
    p.add_argument("--remat", action="store_true", default=False,
                   help="rematerialize blocks in backward (cfg.remat)")
    runner.add_common_args(p)
    p.set_defaults(batch_size=2, base_lr=1e-4, momentum=0.0)
    return p


def config_from_args(args, dtype):
    """The model's config (`GlmMoeConfig`, `Lfm2MoeConfig`,
    `GraniteHybridConfig` or `LingHybridConfig`) cut to this chip's
    share."""
    cfg = models.get_model(args.model, dtype=dtype).config
    if isinstance(cfg, models.GraniteHybridConfig):
        if args.experts_held is not None or args.expert_offset:
            raise ValueError("the Granite hybrid has no routed experts")
        return dataclasses.replace(
            cfg, remat=args.remat, vocab_size=args.vocab_size
            or cfg.vocab_size,
            layer_types=cfg.layer_types[args.first_layer:][:args.num_layers])
    given = {"experts_held": args.experts_held,
             "vocab_size": args.vocab_size}
    first = args.first_layer
    if isinstance(cfg, models.Lfm2MoeConfig):
        kinds = cfg.layer_types[first:][:args.num_layers]
        given.update(layer_types=kinds, num_hidden_layers=len(kinds),
                     num_dense_layers=max(cfg.num_dense_layers - first, 0))
    elif isinstance(cfg, models.LingHybridConfig):
        given.update(layer_types=cfg.layer_types[first:][:args.num_layers],
                     first_layer=cfg.first_layer + first,
                     first_k_dense_replace=max(
                         cfg.first_k_dense_replace - first, 0))
        if args.no_mtp:
            given["num_nextn_predict_layers"] = 0
    elif first:
        raise ValueError("--first-layer is the hybrid families'")
    else:
        given["num_layers"] = args.num_layers
        if args.no_mtp:
            given["num_nextn_predict_layers"] = 0
    return dataclasses.replace(
        cfg, expert_offset=args.expert_offset, remat=args.remat,
        **{k: v for k, v in given.items() if v is not None})


def loss_of(cfg, outputs, input_ids):
    """The family's training loss of its model's outputs."""
    if isinstance(cfg, models.Lfm2MoeConfig):
        return models.lfm2_moe_lm_loss(outputs, input_ids)
    if isinstance(cfg, models.GraniteHybridConfig):
        return models.granite_hybrid_lm_loss(outputs, input_ids)
    return models.glm_moe_lm_loss(outputs, input_ids,
                                  mtp_loss_weight=cfg.mtp_loss_weight)


def main(argv=None) -> runner.BenchResult:
    args = build_parser().parse_args(argv)
    runner.apply_platform_env()
    scan_steps = runner.validate_scan_steps(args)
    mesh = backend.init()
    world = backend.dp_size(mesh)

    cfg = config_from_args(args, jnp.bfloat16 if args.fp16 else jnp.float32)
    hybrid = isinstance(cfg, (models.Lfm2MoeConfig,
                             models.LingHybridConfig))
    dense = isinstance(cfg, models.GraniteHybridConfig)
    model = type(models.get_model(args.model))(cfg)
    global_bs = args.batch_size * world
    batch = data.synthetic_gpt_batch(
        jax.random.PRNGKey(0), global_bs, seq_len=args.sequence_len,
        vocab_size=cfg.vocab_size,
    )
    sharding = jax.sharding.NamedSharding(mesh, jax.P(DP_AXIS))
    batch = runner.stage_global(batch, sharding)
    params = jax.jit(lambda key: model.init(
        {"params": key}, jnp.zeros((1, 8), jnp.int32))["params"])(
            jax.random.PRNGKey(0))

    def loss_fn(p, b, rng):
        # aux (the sparse decoders): the routing counter, assignments per
        # (expert layer, held expert), averaged over the workers by the step
        del rng
        if dense:
            return loss_of(cfg, model.apply({"params": p}, b["input_ids"]),
                           b["input_ids"])
        outputs, collections = model.apply(
            {"params": p}, b["input_ids"], mutable=["intermediates"])
        return (loss_of(cfg, outputs, b["input_ids"]),
                models.expert_assignments(cfg, collections["intermediates"]))

    dear_cfg = runner.config_from_args(args, world=world)
    ts, stepper = runner.build_stepper(dear_cfg, loss_fn, params, mesh,
                                       mgwfbp=args.mgwfbp, has_aux=not dense)
    state = ts.init(params)
    del params

    depth = (f"layers {', '.join(cfg.layer_types)}" if hybrid or dense else
             f"{cfg.num_layers} layer(s), {cfg.num_nextn_predict_layers} "
             "prediction module(s)")
    experts = "no routed experts"
    if not dense:
        scored = cfg.num_experts if hybrid else cfg.n_routed_experts
        held = cfg.experts_held or scored
        experts = (f"experts [{cfg.expert_offset}, "
                   f"{cfg.expert_offset + held}) of {scored}")
    runner.log(f"{args.model} causal-LM pretraining, sequence len: "
               f"{args.sequence_len}; {depth}, {experts}, "
               f"{cfg.vocab_size} ids")
    runner.log(f"Batch size: {args.batch_size} (per dp rank), "
               f"{global_bs} global "
               f"({global_bs * args.sequence_len} tokens/step)")
    runner.log(f"Number of {runner.device_name()}s: "
               f"{backend.device_count()}")
    runner.log(f"Schedule: {args.mode}; "
               f"fusion: {ts.plan.num_buckets} bucket(s)")

    from dear_pytorch_tpu.runtime import pipeline as RP

    spec = RP.gpt_spec(global_bs, args.sequence_len, vocab=cfg.vocab_size)
    next_batch, close = runner.make_batch_source(args, spec, sharding, batch)
    holder = {"state": state, "metrics": None, "batch": batch}
    step_fn, timed_kwargs = runner.make_step_source(
        args, scan_steps, ts, stepper, holder, next_batch
    )
    runner.run_pretune(args, stepper, holder, next_batch)

    def sync():
        if holder["metrics"] is not None:
            float(holder["metrics"]["loss"])

    metrics_log = runner.metrics_from_args(args)
    try:
        result = runner.run_timed(
            step_fn, unit="sen", sync=sync, metrics=metrics_log,
            **timed_kwargs,
        )
    finally:
        if metrics_log is not None:
            metrics_log.close()
        close()
    runner.log(f"Tokens/sec on {result.world} {runner.device_name()}(s): "
               f"{result.total_mean * args.sequence_len:.0f}")
    log_expert_load(holder["metrics"])
    return result


def log_expert_load(metrics) -> None:
    """The last step's routing counter (``metrics["aux"]``: assignments per
    expert layer and held expert, the workers' mean): logged with each
    layer's max over mean, and added to the tracer's
    ``moe.layer<i>.expert<e>.assignments`` counters."""
    if not metrics or metrics.get("aux") is None:
        return
    counts = jax.device_get(metrics["aux"])
    counts = counts.reshape(-1, *counts.shape[-2:])[-1]   # a scan: its last
    tr = T.get_tracer()
    for i, row in enumerate(counts):
        if tr.enabled:
            for e, n in enumerate(row):
                tr.count(f"moe.layer{i}.expert{e}.assignments", float(n))
        runner.log(f"Expert layer {i}: assignments per held expert "
                   f"{[int(n) for n in row]}, max/mean "
                   f"{float(row.max() / max(row.mean(), 1e-9)):.2f}")


if __name__ == "__main__":
    main()
