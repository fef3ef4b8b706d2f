"""GPT-2 (S=1024) training throughput under the bench protocol: scanned
k-step program, one contiguous dispatch queue, ONE end-of-window fetch —
the same measurement discipline as bench.py (the gpt CLI syncs once per
iteration window instead). The CLIs' per-iter fetch is what makes their
live progress lines truthful."""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from dear_pytorch_tpu.benchmarks import runner

runner.apply_platform_env()

from dear_pytorch_tpu import models                      # noqa: E402
from dear_pytorch_tpu.comm import backend                # noqa: E402
from dear_pytorch_tpu.models import data                 # noqa: E402
from dear_pytorch_tpu.ops.fused_sgd import fused_sgd     # noqa: E402
from dear_pytorch_tpu.parallel import dear as D          # noqa: E402
from dear_pytorch_tpu.utils import perf_model            # noqa: E402

BS, SEQ, K, ITERS = 8, 1024, 4, 10

mesh = backend.init()
model = models.get_model("gpt2", dtype=jnp.bfloat16)
cfg = model.config
batch = data.synthetic_gpt_batch(jax.random.PRNGKey(0), BS, seq_len=SEQ,
                                 vocab_size=cfg.vocab_size)

params = model.init({"params": jax.random.PRNGKey(0)}, batch["input_ids"],
                    train=False)["params"]

def loss_fn(p, b, rng):
    logits = model.apply({"params": p}, b["input_ids"], train=True,
                         rngs={"dropout": rng})
    return models.gpt_lm_loss(logits, b["input_ids"],
                              vocab_size=cfg.vocab_size)

ts = D.build_train_step(loss_fn, params, mesh=mesh, mode="dear",
                        threshold_mb=25.0,
                        optimizer=fused_sgd(lr=0.01, momentum=0.9),
                        comm_dtype=jnp.bfloat16, rng_seed=7)
state = ts.init(params)
step = ts.multi_step(K)
compiled = step.lower(state, batch).compile()
try:
    flops = float(compiled.cost_analysis().get("flops", 0.0))
except Exception:  # best-effort, as in bench.py — never sink the timing
    flops = 0.0

state, m = compiled(state, batch)
state, m = compiled(state, batch)
float(m["loss"])  # drain
t0 = time.perf_counter()
for _ in range(ITERS):
    state, m = compiled(state, batch)
float(m["loss"])
dt = (time.perf_counter() - t0) / (ITERS * K)
mfu = perf_model.mfu(flops, dt, jax.devices()[0]) if flops else None
print(f"gpt2 S={SEQ} bs={BS}: {BS * SEQ / dt:.0f} tok/s  "
      f"{dt * 1e3:.1f} ms/step"
      + (f"  MFU {100 * mfu:.1f}%" if mfu else ""), flush=True)
# scrape-compatible line (onchip_session summary.json / driver scrapers)
print(f"Total sen/sec on 1 TPU(s): {BS / dt:.1f}", flush=True)
