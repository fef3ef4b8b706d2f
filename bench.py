"""Headline benchmarks: ResNet-50 and BERT-Base end-to-end training
throughput per chip, with MFU accounting.

Follows the reference's measurement shape (dear/imagenet_benchmark.py:
151-172, dear/bert_benchmark.py:160-175): warmup batches, then a timed
window of NUM_ITERS x NUM_BATCHES_PER_ITER training steps. Unlike the
reference (which averages per-run rates with a sync per run), the timed
window here is ONE contiguous dispatch queue with a single end-of-window
device->host fetch of a scalar that depends on the last step. Runs the
full DeAR train step (pack → reduce-scatter → fused-SGD → all-gather
schedule; trivial collectives at world=1) with bf16 compute / f32 master
params — the TPU-first configuration.

Prints ONE JSON line (the driver contract), primary metric first:
  {"metric": "resnet50_bs64_train_img_sec_per_chip", "value": N,
   "unit": "img/s", "vs_baseline": N, "mfu": F,
   "extra_metrics": [{"metric": "bert_base_sen_sec_per_chip", ...}]}
A phase that raises ends the run with a traceback and a non-zero exit
code: there is no partial result.

``vs_baseline`` is relative to BASELINE_IMG_SEC, this framework's own
round-4 capture on a single TPU v5e chip under the same single-fetch
protocol this file implements (the reference publishes no numbers of its
own, BASELINE.md); the emitted ``baseline_protocol`` tag names the pin's
protocol so JSON consumers can tell re-bases apart.
``mfu`` = achieved FLOP/s
(XLA cost analysis of the compiled step) over the chip's bf16 peak.
"""

from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp

# Round-4 pin: ResNet-50 bs=64 bf16 train step, TPU v5 lite (1 chip),
# 2304.13 img/s measured under the SINGLE-FETCH protocol this file
# implements (perf/onchip_r04/bench.json). Re-based in round 5 from the
# round-1 pin of 1910.0 img/s, which was captured with a per-iter-fetch
# loop (one device->host sync per 10-step window). With pin and capture
# under the same protocol, vs_baseline measures the device, not the
# harness. The emitted "baseline_protocol" tag lets JSON consumers tell
# the pins apart.
BASELINE_IMG_SEC = 2304.13
BASELINE_PROTOCOL = "single-fetch-r04"
# BERT pin: pinned automatically to the FIRST successful driver capture
# found in BENCH_r*.json history (pin-on-first-capture — no manual edit
# needed when the first on-chip BERT number lands). None until then.
BASELINE_BERT_SEN_SEC = None
# GPT pin: the metric joined the driver contract in round 5, so there is
# no BENCH_r*.json history yet; until one exists, the pin is the round-4
# on-chip headline measured under the SAME single-fetch scanned protocol
# (48,121 tok/s at S=1024 — the pre-optimization configuration the
# round-5 sweep started from).
BASELINE_GPT_TOK_SEC = 48121.0
# deliberately its own literal, not an alias of BASELINE_PROTOCOL: this
# tags the GPT pin's capture protocol, which stays r04-single-fetch even
# if the ResNet pin is later re-based under a different protocol
BASELINE_GPT_PROTOCOL = "single-fetch-r04"
# The fallback GPT pin was captured under a DIFFERENT training config than
# bench_gpt now measures, so vs_baseline against it mixes config changes
# with framework/device speedup (PERF.md documents the split). Emitted as
# "baseline_config" so JSON consumers see the delta without reading docs;
# self-heals to 'pinned-from-history' once pin-on-first-capture resolves.
BASELINE_GPT_CONFIG = ("r04 config: bs8, dropout on, naive LM loss "
                       "(measured config is bs16, dropout 0, streamed loss)")

PRIMARY_METRIC = "resnet50_bs64_train_img_sec_per_chip"


def _history_baseline(metric: str, fallback=None):
    """(value, protocol) of the first captured ``metric`` from
    BENCH_r*.json history, else (fallback, None) — pin-on-first-capture
    without manual edits. The driver stores each round as {"n", "cmd",
    "rc", "tail", "parsed"} where "parsed" is our contract line
    (extra_metrics carries the secondary entries). The protocol tag is
    derived from the resolved round (rounds >= 4 measured single-fetch;
    earlier rounds synced once per timed window), not hardcoded, so a
    backfilled early round can't mislabel the pin."""
    import glob
    import re

    here = os.path.dirname(os.path.abspath(__file__))
    rounds = []
    for p in glob.glob(os.path.join(here, "BENCH_r*.json")):
        m = re.fullmatch(r"BENCH_r(\d+)\.json", os.path.basename(p))
        if m:
            rounds.append((int(m.group(1)), p))
    for n, path in sorted(rounds):
        try:
            with open(path) as f:
                record = json.load(f)
            parsed = record.get("parsed") if isinstance(record, dict) else None
            if not isinstance(parsed, dict):
                continue
            candidates = [parsed] + list(parsed.get("extra_metrics") or [])
            for m in candidates:
                if (
                    isinstance(m, dict)
                    and m.get("metric") == metric
                    and isinstance(m.get("value"), (int, float))
                    and m["value"] > 0
                ):
                    protocol = (f"single-fetch-r{n:02d}" if n >= 4
                                else f"per-iter-fetch-r{n:02d}")
                    return float(m["value"]), protocol
        except Exception:
            continue
    return fallback, None


def _bert_baseline():
    return _history_baseline("bert_base_sen_sec_per_chip",
                             BASELINE_BERT_SEN_SEC)


SMOKE = bool(os.environ.get("DEAR_BENCH_SMOKE"))  # tiny shapes, CPU-safe


def _env_enabled(name: str) -> bool:
    """Opt-out env flag: on unless set to a falsy marker."""
    return os.environ.get(name, "1").strip().lower() not in (
        "", "0", "false", "no")


def _gather_dtype(world: int):
    """Cast master shards to bf16 before the per-bucket all-gather ONLY
    when there is gather traffic to halve (world > 1: half the AG bytes on
    ICI). At world=1 the gather is a local copy and the pre-cast is pure
    overhead — the 2026-07-31 on-chip A/B measured f32 gathers at +4.5%
    BERT-Base throughput and parity on ResNet (1225.37 sen/s in
    perf/onchip_r04/bench_gather_f32.json vs 1170.92 with bf16 gathers),
    so the choice follows the mesh.
    Override with DEAR_BENCH_GATHER_DTYPE=bf16|f32."""
    v = os.environ.get("DEAR_BENCH_GATHER_DTYPE", "").strip().lower()
    if v in ("f32", "fp32", "float32", "none"):
        return None
    if v in ("bf16", "bfloat16"):
        return jnp.bfloat16
    if v:
        raise SystemExit(
            f"DEAR_BENCH_GATHER_DTYPE={v!r}: use 'bf16' or 'f32'"
        )
    return jnp.bfloat16 if world > 1 else None

WARMUP_BATCHES = 2 if SMOKE else 10
# 10 iters x 10 scanned steps per timed window, one end-of-window fetch
NUM_ITERS = 2 if SMOKE else 10
NUM_BATCHES_PER_ITER = 2 if SMOKE else 10


def _compile_once(ts, state, batch):
    """(iter_fn, flops_per_step, peak_hbm_bytes): ONE AOT compilation of the
    scanned NUM_BATCHES_PER_ITER-step program. One program per timed
    iteration: dispatch cost amortizes over the scan, and XLA schedules step
    i+1's all-gathers under step i's tail (DeAR's cross-iteration
    pipelining, inside one executable)."""
    from dear_pytorch_tpu.utils import perf_model

    runner = ts.multi_step(NUM_BATCHES_PER_ITER)
    compiled = runner.lower(state, batch).compile()
    # XLA cost analysis counts a scan (while-loop) BODY once, so the
    # scanned program already reports one step's flops — no division.
    flops = float(compiled.cost_analysis().get("flops", 0.0))
    return compiled, flops, perf_model.peak_hbm_bytes(compiled)


def _place(mesh, batch):
    """Commit the global batch to the mesh ONCE, split over 'dp' (what the
    benchmark CLIs do) — an uncommitted `jax.random` array would be moved
    by `jit` on every step."""
    from dear_pytorch_tpu.benchmarks import runner
    from dear_pytorch_tpu.comm.backend import DP_AXIS

    return runner.stage_global(
        batch, jax.sharding.NamedSharding(mesh, jax.P(DP_AXIS)))


def _timed(iter_fn, state, batch, items_per_batch: int):
    """(value items/s, secs/step, state); each ``iter_fn`` call runs
    NUM_BATCHES_PER_ITER steps as one program.

    All NUM_ITERS programs are dispatched back-to-back (state threads
    through, so the device runs them as one contiguous queue) and ONE
    scalar that depends on the final step is fetched — exactly the
    protocol the module docstring promises."""
    n_warm_iters = max(WARMUP_BATCHES // NUM_BATCHES_PER_ITER, 1)
    metrics = None
    for _ in range(n_warm_iters):
        state, metrics = iter_fn(state, batch)
    float(metrics["loss"])  # drain the pipeline once before timing
    t0 = time.perf_counter()
    for _ in range(NUM_ITERS):
        state, metrics = iter_fn(state, batch)
    float(metrics["loss"])  # ONE device->host fetch for the whole window
    total = time.perf_counter() - t0
    steps = NUM_ITERS * NUM_BATCHES_PER_ITER
    secs_per_step = total / steps
    return items_per_batch / secs_per_step, secs_per_step, state


def bench_resnet(mesh):
    from dear_pytorch_tpu import models
    from dear_pytorch_tpu.models import data
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import dear as D

    batch_size = 8 if SMOKE else 64
    model = models.get_model(
        "resnet18" if SMOKE else "resnet50", dtype=jnp.bfloat16
    )
    batch = _place(mesh, data.synthetic_image_batch(
        jax.random.PRNGKey(0), batch_size,
        image_size=64 if SMOKE else 224, dtype=jnp.bfloat16,
    ))
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, batch["image"], train=False
    )
    params = variables["params"]
    model_state = {"batch_stats": variables["batch_stats"]}

    def loss_fn(p, mstate, b):
        logits, new_state = model.apply(
            {"params": p, **mstate}, b["image"], train=True,
            mutable=["batch_stats"],
        )
        return data.softmax_xent(logits, b["label"]), new_state

    ts = D.build_train_step(
        loss_fn,
        params,
        mesh=mesh,
        mode="dear",
        threshold_mb=25.0,
        optimizer=fused_sgd(lr=0.01, momentum=0.9),
        comm_dtype=jnp.bfloat16,
        gather_dtype=_gather_dtype(mesh.size),
        model_state_template=model_state,
    )
    state = ts.init(params, model_state)
    step_fn, flops, hbm = _compile_once(ts, state, batch)
    value, secs_per_step, _ = _timed(step_fn, state, batch, batch_size)
    out = {
        "metric": "resnet50_bs64_train_img_sec_per_chip",
        "value": round(value, 2),
        "unit": "img/s",
        "vs_baseline": round(value / BASELINE_IMG_SEC, 3),
        "baseline_protocol": BASELINE_PROTOCOL,
        "mfu": _mfu(flops, secs_per_step),
    }
    if hbm:
        out["peak_hbm_gb"] = round(hbm / 2**30, 3)
    return out


def bench_vit(mesh):
    """ViT-B/16 bs64 bf16 — the GEMM-dominated vision headline (beyond the
    reference zoo). Demonstrates the framework's MFU ceiling is set by the
    model's op mix, not the schedule: on-chip 2026-07-31 it ran 59.0% MFU
    under this protocol (53.1% via the CLI's per-iter-fetch protocol)
    vs ResNet-50's conv-bound ~28%."""
    from dear_pytorch_tpu import models
    from dear_pytorch_tpu.models import data
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import dear as D

    batch_size = 8 if SMOKE else 64
    model = models.get_model(
        "vit_s16" if SMOKE else "vit_b16", dtype=jnp.bfloat16,
        **({"num_layers": 2} if SMOKE else {}),
    )
    batch = _place(mesh, data.synthetic_image_batch(
        jax.random.PRNGKey(0), batch_size,
        image_size=32 if SMOKE else 224, dtype=jnp.bfloat16,
    ))
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, batch["image"], train=False
    )["params"]

    def loss_fn(p, b):
        logits = model.apply({"params": p}, b["image"], train=False)
        return data.softmax_xent(logits, b["label"])

    ts = D.build_train_step(
        loss_fn,
        params,
        mesh=mesh,
        mode="dear",
        threshold_mb=25.0,
        optimizer=fused_sgd(lr=0.01, momentum=0.9),
        comm_dtype=jnp.bfloat16,
        gather_dtype=_gather_dtype(mesh.size),
    )
    state = ts.init(params)
    step_fn, flops, hbm = _compile_once(ts, state, batch)
    value, secs_per_step, _ = _timed(step_fn, state, batch, batch_size)
    out = {
        "metric": "vit_b16_bs64_train_img_sec_per_chip",
        "value": round(value, 2),
        "unit": "img/s",
        "mfu": _mfu(flops, secs_per_step),
    }
    if hbm:
        out["peak_hbm_gb"] = round(hbm / 2**30, 3)
    return out


def bench_bert(mesh, variant: str = "bert_base"):
    """BERT pretraining throughput (the reference's second headline,
    dear/bert_benchmark.py:160-175; sentence length from the launcher,
    horovod_mpi_cj.sh:6). ``variant`` may be 'bert' (= BERT-Large, the
    reference's flagship config) — measured by default; skip with
    DEAR_BENCH_BERT_LARGE=0."""
    from dear_pytorch_tpu import models
    from dear_pytorch_tpu.models import data
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import dear as D

    large = variant != "bert_base"
    batch_size = 4 if SMOKE else (16 if large else 32)
    seq_len = 32 if SMOKE else 64
    model = models.get_model(variant, dtype=jnp.bfloat16)
    if SMOKE:
        import dataclasses

        model = models.BertForPreTraining(
            dataclasses.replace(model.config, num_hidden_layers=2)
        )
    cfg = model.config
    batch = _place(mesh, data.synthetic_bert_batch(
        jax.random.PRNGKey(0), batch_size, seq_len=seq_len,
        vocab_size=cfg.vocab_size,
    ))
    params = model.init(
        {"params": jax.random.PRNGKey(0)}, batch["input_ids"], train=False
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

    ts = D.build_train_step(
        loss_fn,
        params,
        mesh=mesh,
        mode="dear",
        threshold_mb=25.0,
        optimizer=fused_sgd(lr=2e-5, momentum=0.0),
        comm_dtype=jnp.bfloat16,
        gather_dtype=_gather_dtype(mesh.size),
        rng_seed=42,
    )
    state = ts.init(params)
    step_fn, flops, hbm = _compile_once(ts, state, batch)
    value, secs_per_step, _ = _timed(step_fn, state, batch, batch_size)
    name = "bert_large" if large else "bert_base"
    out = {
        "metric": f"{name}_sen_sec_per_chip",
        "value": round(value, 2),
        "unit": "sen/s",
        "mfu": _mfu(flops, secs_per_step),
    }
    if hbm:
        out["peak_hbm_gb"] = round(hbm / 2**30, 3)
    baseline, protocol = (None, None) if large else _bert_baseline()
    if baseline:
        out["vs_baseline"] = round(value / baseline, 3)
        if protocol:
            # the protocol of whatever record pin-on-first-capture resolved
            # to — so both vs_baseline fields carry their own pin's protocol
            out["baseline_protocol"] = protocol
    return out


def bench_gpt(mesh):
    """GPT-2 (124M) S=1024 causal-LM pretraining throughput — the
    transformer-decoder headline (beyond the reference zoo; harness analog
    of dear/bert_benchmark.py:160-175). Round-5 configuration from the
    on-chip sweep (perf/onchip_r05/gpt_sweep/): batch 16, dropout 0 (the
    modern pretraining default — attention-probs dropout alone draws a
    [B,12,1024,1024] random mask per layer and halves throughput),
    streamed logsumexp LM loss, default %8 vocab padding (the %128
    lane-width A/B was a null result — GptConfig.vocab_pad_multiple).
    38.9% MFU on-chip vs the r04 headline's 22.9%."""
    import dataclasses

    from dear_pytorch_tpu import models
    from dear_pytorch_tpu.models import data
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import dear as D

    batch_size = 2 if SMOKE else 16
    seq_len = 32 if SMOKE else 1024
    model = models.get_model("gpt2", dtype=jnp.bfloat16)
    cfg = models.dropout_free(model.config)
    if SMOKE:
        cfg = dataclasses.replace(
            cfg, num_hidden_layers=2, hidden_size=64,
            num_attention_heads=4, intermediate_size=128,
            vocab_size=128, max_position_embeddings=seq_len)
    model = models.GptLmHeadModel(cfg)
    batch = _place(mesh, data.synthetic_gpt_batch(
        jax.random.PRNGKey(0), batch_size, seq_len=seq_len,
        vocab_size=cfg.vocab_size,
    ))
    params = model.init({"params": jax.random.PRNGKey(0)},
                        batch["input_ids"], train=False)["params"]

    def loss_fn(p, b, rng):
        del rng  # dropout-free config
        logits = model.apply({"params": p}, b["input_ids"], train=True)
        return models.gpt_lm_loss(logits, b["input_ids"],
                                  vocab_size=cfg.vocab_size)

    ts = D.build_train_step(
        loss_fn, params, mesh=mesh, mode="dear", threshold_mb=25.0,
        optimizer=fused_sgd(lr=0.01, momentum=0.9),
        comm_dtype=jnp.bfloat16, gather_dtype=_gather_dtype(mesh.size),
        rng_seed=7,
    )
    state = ts.init(params)
    step_fn, flops, hbm = _compile_once(ts, state, batch)
    value, secs_per_step, _ = _timed(step_fn, state, batch,
                                     batch_size * seq_len)
    out = {
        "metric": "gpt2_s1024_tok_sec_per_chip",
        "value": round(value, 1),
        "unit": "tok/s",
        "mfu": _mfu(flops, secs_per_step),
    }
    if hbm:
        out["peak_hbm_gb"] = round(hbm / 2**30, 3)
    baseline, protocol = _history_baseline(
        "gpt2_s1024_tok_sec_per_chip", BASELINE_GPT_TOK_SEC)
    if baseline:
        out["vs_baseline"] = round(value / baseline, 3)
        out["baseline_protocol"] = protocol or BASELINE_GPT_PROTOCOL
        # the config delta behind vs_baseline, machine-readable: history
        # pins were captured by this same bench_gpt configuration; the
        # fallback literal was not (ADVICE.md)
        out["baseline_config"] = (
            "pinned-from-history (same bench_gpt config)" if protocol
            else BASELINE_GPT_CONFIG)
    return out


def _mfu(flops: float, secs_per_step: float):
    from dear_pytorch_tpu.utils import perf_model

    value = perf_model.mfu(flops, secs_per_step, jax.devices()[0])
    return round(value, 4) if value else None


def main() -> None:
    from dear_pytorch_tpu.benchmarks import runner
    from dear_pytorch_tpu.comm import backend

    runner.apply_platform_env()
    from dear_pytorch_tpu import observability

    if os.environ.get(observability.tracer.TELEMETRY_ENV) is None:
        # default-on, counters only (memory=False: no span records — the
        # timed loops must accumulate nothing) so the emitted JSON always
        # carries a telemetry block; an explicit DEAR_TELEMETRY value —
        # including an explicit disable — is honored as-is
        observability.configure(memory=False)
    mesh = backend.init()
    out = bench_resnet(mesh)
    extras = [bench_bert(mesh)]
    if _env_enabled("DEAR_BENCH_BERT_LARGE"):
        # the reference's flagship BERT config (dear/bert_config.json:
        # 1024h/24L) — BASELINE.md's second headline target. On by
        # default; set DEAR_BENCH_BERT_LARGE=0 to skip (it roughly
        # doubles the bench wall time).
        extras.append(bench_bert(mesh, "bert"))
    if _env_enabled("DEAR_BENCH_VIT"):
        # GEMM-dominated vision headline; DEAR_BENCH_VIT=0 skips
        extras.append(bench_vit(mesh))
    if _env_enabled("DEAR_BENCH_GPT"):
        # decoder headline (round-5 sweep config); DEAR_BENCH_GPT=0 skips
        extras.append(bench_gpt(mesh))
    out["extra_metrics"] = extras
    # counters + span aggregates from the run (plan builds, program
    # compiles, per-mode comm accounting when instrumented code ran)
    out["telemetry"] = observability.snapshot()
    # feed any DEAR_TELEMETRY prom:/stream: run-health sinks one final
    # snapshot (throughput + MFU as gauges), so a scraper sees the bench
    # round without parsing the contract line
    from dear_pytorch_tpu.observability import export as _export

    gauges = {}
    for m in [out] + extras:
        gauges[m["metric"]] = m["value"]
        if isinstance(m.get("mfu"), (int, float)):
            gauges[f"{m['metric']}_mfu"] = m["mfu"]
    _export.write_streams(out["telemetry"], gauges)  # never raises
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
