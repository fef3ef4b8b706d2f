"""One-command flash-kernel vs XLA-attention A/B on a TPU (ROADMAP A2).

`chip_smoke.py` shows the kernels compile and agree with dense attention
on the chip and prints one smoke timing; this sweep is the timing tool.
Refuses to run without a TPU — a CPU run would time the Pallas
interpreter.

    python scripts/flash_ab.py            # full sweep, prints a table
    python scripts/flash_ab.py --causal   # the GPT shape

Measures fwd and fwd+bwd for both implementations over (batch, heads,
S, D) shapes with the single-fetch protocol, and prints per-shape
speedups. No framework setup needed beyond PYTHONPATH.
"""

from __future__ import annotations

import argparse
import functools
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SHAPES = [  # (batch, seq, heads, head_dim) — flash_attention's [B,S,H,D]
    (4, 512, 12, 64),
    (4, 1024, 12, 64),
    (4, 2048, 12, 64),
    (2, 4096, 8, 64),
]


def xla_attention(q, k, v, causal):
    """Plain composed attention over [B, S, H, D] (what the model zoo
    runs when attention_impl is None)."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (d ** 0.5)
    if causal:
        S = q.shape[1]
        tri = jnp.tril(jnp.ones((S, S), jnp.bool_))
        s = jnp.where(tri[None, None], s, jnp.asarray(-1e9, s.dtype))
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _timed(fn, args, iters):
    import jax

    out = fn(*args)  # compile + warm
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)  # ONE sync for the window
    return (time.perf_counter() - t0) / iters


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--causal", action="store_true")
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--shapes", help="override, e.g. '4x512x12x64,2x1024x8x64'")
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from dear_pytorch_tpu.ops.flash_attention import flash_attention

    shapes = SHAPES
    if args.shapes:
        shapes = [tuple(int(x) for x in s.split("x"))
                  for s in args.shapes.split(",")]

    dtype = jnp.dtype(args.dtype)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"flash_ab.py times TPU kernels; found {dev.platform}")
    print(f"device: {dev.device_kind}  causal={args.causal}  "
          f"dtype={dtype.name}  iters={args.iters}")
    print(f"{'shape':>18} | {'xla fwd':>9} {'flash fwd':>9} {'x':>5} | "
          f"{'xla f+b':>9} {'flash f+b':>9} {'x':>5}")

    for b, s, h, d in shapes:
        key = jax.random.PRNGKey(0)
        kq, kk, kv = jax.random.split(key, 3)
        q = jax.random.normal(kq, (b, s, h, d)).astype(dtype)
        k = jax.random.normal(kk, (b, s, h, d)).astype(dtype)
        v = jax.random.normal(kv, (b, s, h, d)).astype(dtype)

        flash = jax.jit(functools.partial(flash_attention,
                                          causal=args.causal))
        xla = jax.jit(functools.partial(xla_attention, causal=args.causal))

        def loss(fn):
            return jax.jit(jax.grad(
                lambda q_, k_, v_: fn(q_, k_, v_).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            ))

        tf_f = _timed(flash, (q, k, v), args.iters)
        tx_f = _timed(xla, (q, k, v), args.iters)
        tf_b = _timed(loss(flash), (q, k, v), args.iters)
        tx_b = _timed(loss(xla), (q, k, v), args.iters)
        print(f"({b:>2},{s:>5},{h:>3},{d:>3}) | "
              f"{tx_f * 1e3:8.2f}ms {tf_f * 1e3:8.2f}ms "
              f"{tx_f / tf_f:4.2f}x | "
              f"{tx_b * 1e3:8.2f}ms {tf_b * 1e3:8.2f}ms "
              f"{tx_b / tf_b:4.2f}x")
    print("(x > 1 means the flash kernel is faster)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
