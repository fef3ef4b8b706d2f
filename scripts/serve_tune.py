"""Serving plan-space tuner + A/B fixture: search `ServeSpace` against a
closed-loop storm harness with p99 request latency as the objective.

The training-side autotuner (docs/TUNING.md) optimizes step time; a
serving fleet's contract is a latency SLO. This script drives the SAME
`PlanTuner` machinery (`tuning.planspace.ServeTuner`) over the serving
knobs — prefill chunk C x batch slots x KV-cache dtype x flash decode x
ring-TP decode — where one trial is one closed-loop EPISODE: staggered
synthetic requests through a real `serving.engine.DecodeEngine`, measured
per-request from arrival to verified completion, scored at p99. Arms are
pruned by the α-β `ServeCostModel` (ceil(P/C)+D ticks per request; ring
transport priced for tp arms) before they burn a live episode.

Outputs (``--out``, default perf/serving_r08):

  - ``trials.jsonl``    one record per tuner decision (DEAR_TUNE_LOG shape)
  - ``summary.json``    bench-contract line: requests_per_s +
                        p50/p99_latency_ms extra metrics + the tuner
                        summary + the honest CPU-emulation caveat —
                        gate with ``bench_gate.py --slo``
  - ``ab_reports.json`` driver-``reports.json``-shaped A/B fixture
                        (requests/s cells): METHOD rows ``token`` (C=1)
                        vs ``chunked`` (tuned C) and ``dense`` vs ``tp``
                        — gate with ``bench_gate.py --ab-methods
                        chunked:token``
  - ``ab_reports_p99.json`` the same methods' p99 cells (lower is
                        better) — gate with ``--ab-methods ...
                        --ab-objective latency``

Honest caveat (same rule as every perf/ artifact): CPU-emulated numbers
are dispatch-dominated and interpret-mode Pallas makes tp arms slow —
functional evidence and RELATIVE chunking wins only; on-chip runs own the
real latency numbers.

Tier-1 drives a miniature budget (tests/test_serving.py); the archived
perf/serving_r08 run used the defaults.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _model(kv_cache_len: int, model_kwargs: dict):
    """The harness's tiny causal LM (chaos_check.py's storm model, with
    the ServeConfig's cache knobs applied)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dear_pytorch_tpu.models.gpt import GptConfig, GptLmHeadModel

    cfg = GptConfig(
        vocab_size=61, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=64, kv_cache_len=kv_cache_len,
        embd_dropout_prob=0.0, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0,
    )
    cfg = dataclasses.replace(cfg, **model_kwargs)
    model = GptLmHeadModel(cfg)
    params = model.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, 4), jnp.int32), train=False)["params"]
    return model, params


def build_engine(config, *, kv_cache_len: int, mesh):
    from dear_pytorch_tpu.serving.engine import DecodeEngine

    model, params = _model(kv_cache_len, config.model_kwargs())
    return DecodeEngine(
        model, params,
        tp_mesh=(mesh if config.tp_decode else None),
        **config.engine_kwargs())


def episode(engine, *, requests: int, max_new: int = 4,
            arrival_gap_s: float = 0.0, seed: int = 7) -> dict:
    """One closed-loop episode: ``requests`` synthetic prompts of mixed
    lengths arrive on a staggered schedule, queue for a free slot, and
    are measured ARRIVAL -> completion (queue wait included — the slots
    axis must be able to matter). Deterministic prompts; wall-clock
    measured around real jitted engine ticks."""
    import numpy as np

    rs = np.random.RandomState(seed)
    pending = [(i, list(rs.randint(0, 61, int(4 + (i * 5) % 13))))
               for i in range(requests)]
    pending.reverse()                      # pop() serves in arrival order
    t0 = time.monotonic()
    arrivals, latencies = {}, []
    done = 0
    ticks = 0
    while done < requests:
        now = time.monotonic() - t0
        while pending and (arrival_gap_s <= 0.0
                           or len(arrivals) * arrival_gap_s <= now):
            rid, prompt = pending[-1]
            arrivals.setdefault(rid, time.monotonic())
            if engine.free == 0:
                break                      # arrived, waiting for a slot
            pending.pop()
            engine.submit(prompt, max_new, request_id=rid)
        if engine.active == 0:
            time.sleep(0.001)
            continue
        for fin in engine.tick():
            latencies.append(time.monotonic() - arrivals[fin.request_id])
            done += 1
        ticks += 1
    from dear_pytorch_tpu.observability.export import sorted_quantile

    lats = sorted(latencies)

    def pct(p):
        return sorted_quantile(lats, p)

    wall = time.monotonic() - t0
    return {
        "requests": requests,
        "ticks": ticks,
        "wall_s": round(wall, 4),
        "requests_per_s": round(requests / max(wall, 1e-9), 3),
        "p50_s": round(pct(0.50), 5),
        "p99_s": round(pct(0.99), 5),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="tune the serving plan space at p99 latency and emit "
                    "the serving A/B fixture")
    ap.add_argument("--out", default=os.path.join(REPO, "perf",
                                                  "serving_r08"))
    ap.add_argument("--trials", type=int, default=8)
    ap.add_argument("--requests", type=int, default=24,
                    help="requests per episode")
    ap.add_argument("--kv-cache-len", type=int, default=16)
    ap.add_argument("--slots", default="2,4")
    ap.add_argument("--chunk-bound", default="1,8")
    ap.add_argument("--tp-decode", action="store_true",
                    help="include ring-TP decode arms (interpret-mode "
                         "Pallas on CPU emulation: slow, honest)")
    ap.add_argument("--no-flash", action="store_true",
                    help="exclude decode_use_flash arms")
    ap.add_argument("--emulate", type=int, default=8,
                    help="emulated CPU device count (the tp mesh)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    os.environ.setdefault("DEAR_DISABLE_DISTRIBUTED", "1")
    import jax

    jax.config.update("jax_num_cpu_devices", args.emulate)

    from dear_pytorch_tpu.comm import backend
    from dear_pytorch_tpu.tuning.planspace import (
        ServeCostModel, ServeSpace, ServeTuner,
    )

    mesh = backend.init()
    world = int(mesh.shape["dp"])
    os.makedirs(args.out, exist_ok=True)

    lo, hi = (float(x) for x in args.chunk_bound.split(","))
    space = ServeSpace(
        chunk_bound=(lo, min(hi, float(args.kv_cache_len))),
        slots=tuple(int(s) for s in args.slots.split(",")),
        kv_dtypes=(None, "bf16"),
        flash=((False,) if args.no_flash else (False, True)),
        tp=((False, True) if args.tp_decode else (False,)),
        world=world, ring_len=args.kv_cache_len,
    )
    # mean request shape of the episode workload (prompt lengths cycle
    # 4..16); weight bytes per ring projection = the QKV/MLP kernels
    prompt_mean = 4 + 6.0
    hidden = 32
    cost = ServeCostModel(
        prompt_tokens=prompt_mean, decode_tokens=4, world=world,
        alpha=1e-5, beta=1e-9,
        weight_bytes=hidden * hidden * 4 / max(world, 1),
        n_projections=4 * 2,   # QKV + MLP-in x 2 layers
    )
    tuner = ServeTuner(
        space, max_trials=args.trials, cost_model=cost, seed=args.seed,
        trial_log=os.path.join(args.out, "trials.jsonl"))

    episodes = {}

    def measure(config) -> dict:
        key = (config.chunk,) + config.key()
        if key in episodes:
            return episodes[key]
        engine = build_engine(config, kv_cache_len=args.kv_cache_len,
                              mesh=mesh)
        # one warmup pass compiles the step programs outside the episode
        episode(engine, requests=2)
        res = episode(engine, requests=args.requests, seed=args.seed + 7)
        episodes[key] = res
        return res

    while not tuner.finished:
        cfg = tuner.current
        try:
            res = measure(cfg)
        except Exception as exc:  # noqa: BLE001 — a build failure retires
            # the arm; ServeTuner.mark_infeasible moves `current` off the
            # failing config (or finishes a fully-dead space), so this
            # loop cannot spin on a deterministic build failure
            tuner.mark_infeasible(cfg, fatal=True,
                                  why=f"{type(exc).__name__}: {exc}")
            continue
        print(f"serve_tune episode {cfg.describe()}: "
              f"p99 {res['p99_s'] * 1e3:.1f} ms, "
              f"{res['requests_per_s']:.2f} req/s", flush=True)
        tuner.observe(res["p99_s"])

    best = tuner.best_config or tuner.current
    if tuner.best_config is None:
        print(json.dumps({"ok": False,
                          "error": "no feasible episode completed; "
                                   "nothing to archive"}))
        return 2
    best_res = measure(best)

    # -- the A/B fixture: chunked vs token-at-a-time, tp vs dense ---------
    import dataclasses as _dc

    ab_pairs = {
        "token": _dc.replace(best, prefill_chunk=1.0, tp_decode=False),
        "chunked": _dc.replace(best, tp_decode=False),
    }
    if args.tp_decode and world > 1:
        ab_pairs["dense"] = _dc.replace(best, tp_decode=False)
        ab_pairs["tp"] = _dc.replace(best, tp_decode=True)
    ab_rps, ab_p99 = {}, {}
    for name, cfg in ab_pairs.items():
        res = measure(cfg)
        ab_rps[name] = {str(world): [res["requests_per_s"], 0.0]}
        ab_p99[name] = {str(world): [res["p99_s"] * 1e3, 0.0]}
    # two fixtures, one objective each — a single reports file mixing
    # higher-is-better and lower-is-better cells would gate both under
    # whatever one --ab-objective the caller picked
    with open(os.path.join(args.out, "ab_reports.json"), "w") as f:
        json.dump({"serve_gpt_tiny": ab_rps}, f, indent=1, sort_keys=True)
    with open(os.path.join(args.out, "ab_reports_p99.json"), "w") as f:
        json.dump({"serve_gpt_tiny_p99_ms": ab_p99}, f, indent=1,
                  sort_keys=True)

    summary = {
        "metric": "requests_per_s",
        "value": best_res["requests_per_s"],
        "extra_metrics": [
            {"metric": "p99_latency_ms",
             "value": round(best_res["p99_s"] * 1e3, 2)},
            {"metric": "p50_latency_ms",
             "value": round(best_res["p50_s"] * 1e3, 2)},
            {"metric": "prefill_ticks_per_13tok_prompt",
             "value": -(-13 // best.chunk)},
        ],
        "best": best.to_dict(),
        "tuner": tuner.summary(),
        "episodes": {"/".join(str(p) for p in k): v
                     for k, v in sorted(episodes.items(),
                                        key=lambda kv: str(kv[0]))},
        "world": world,
        "caveat": (
            "CPU-emulated closed-loop numbers: dispatch-dominated ticks, "
            "interpret-mode Pallas for flash/tp arms — functional + "
            "relative-chunking evidence only, NOT on-chip latency. The "
            "tp vs dense cells measure emulation overhead, not ring "
            "transport wins."),
    }
    with open(os.path.join(args.out, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1, sort_keys=True)
    print(json.dumps({"metric": summary["metric"],
                      "value": summary["value"],
                      "extra_metrics": summary["extra_metrics"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
