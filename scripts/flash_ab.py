"""One-command flash-kernel vs XLA-attention A/B on a TPU (ROADMAP A2).

`chip_smoke.py` shows the kernels compile and agree with dense attention
on the chip; this sweep is the timing tool. Refuses to run without a TPU —
a CPU run would time the Pallas interpreter.

    python scripts/flash_ab.py            # full sweep, prints a table
    python scripts/flash_ab.py --causal   # the GPT shapes
    python scripts/flash_ab.py --causal --shapes 16x1024x12x64 --blocks 512 --strips 128

Three columns over (batch, seq, heads, head_dim) shapes, all taking and
returning the model's ``[B, S, H, D]``: XLA's dense program (what the model
zoo runs without a kernel), this repo's kernel (`ops.flash_attention`), and
the Pallas kernel JAX ships (`jax.experimental.pallas.ops.tpu.
flash_attention`, at 512-row blocks, with the transposes its ``[B, H, S,
D]`` layout costs a model) as the yardstick. Forward and forward+backward,
milliseconds a call (one layer's attention core), and the share of the
matmul floor: model FLOPs of the full S×S square (4·B·H·S²·D forward, 12·
forward+backward; the causal half is not discounted, as in the
benchmark's `flops_per_token`) at the chip's bf16 peak. ``--blocks`` and
``--strips`` time our kernel at other sequence blocks and strip heights than
its own (a tuning aid: both are constants of the code, not options of the
program).
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
    (16, 1024, 12, 64),   # gpt2-124m.s1024, the benchmark's cell
    (4, 256, 12, 64),
    (4, 512, 12, 64),
    (4, 1024, 12, 64),
    (4, 2048, 12, 64),
    (2, 4096, 8, 64),
]

#: bf16 peak of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
PEAK_FLOPS = {"TPU v5 lite": 197e12}


def xla_attention(q, k, v, causal):
    """Plain composed attention over [B, S, H, D] (what the model zoo
    runs when no kernel applies)."""
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


def upstream_attention(q, k, v, causal):
    """JAX's own Pallas TPU flash attention over the model's layout."""
    from jax.experimental.pallas.ops.tpu import flash_attention as up

    s = q.shape[1]
    b = min(512, s)
    blocks = up.BlockSizes(
        block_q=b, block_k_major=b, block_k=b, block_b=1,
        block_q_major_dkv=b, block_k_major_dkv=b, block_k_dkv=b,
        block_q_dkv=b, block_k_major_dq=b, block_k_dq=b, block_q_dq=b)
    t = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    o = up.flash_attention(t(q), t(k), t(v), causal=causal,
                           sm_scale=q.shape[-1] ** -0.5, block_sizes=blocks)
    return t(o)


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
    ap.add_argument("--blocks", help="also time our kernel at these "
                    "sequence blocks, e.g. '256,512'")
    ap.add_argument("--strips", help="... and at these strip heights "
                    "(at its own block), e.g. '128,512'")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    import dear_pytorch_tpu.ops  # noqa: F401  (the package shadows the module)
    fa = sys.modules["dear_pytorch_tpu.ops.flash_attention"]

    shapes = SHAPES
    if args.shapes:
        shapes = [tuple(int(x) for x in s.split("x"))
                  for s in args.shapes.split(",")]
    # (constant of ops/flash_attention.py, value) our kernel is also timed at
    variants = [None] + [
        (const, int(x)) for const, given in (("_BLOCK", args.blocks),
                                             ("_STRIP", args.strips))
        for x in (given or "").split(",") if x]

    dtype = jnp.dtype(args.dtype)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"flash_ab.py times TPU kernels; found {dev.platform}")
    peak = PEAK_FLOPS[dev.device_kind]
    print(f"device: {dev.device_kind}  causal={args.causal}  "
          f"dtype={dtype.name}  iters={args.iters}  "
          f"floor: full-square model FLOPs at {peak / 1e12:.0f} TFLOP/s")
    print(f"{'shape':>18} {'impl':>14} | {'fwd ms':>8} {'floor%':>6} | "
          f"{'f+b ms':>8} {'floor%':>6} {'vs xla':>6} | max abs err vs "
          "dense f32 (out dq dk dv)")

    def grads(fn):
        return jax.jit(jax.grad(
            lambda q_, k_, v_: fn(q_, k_, v_).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))

    for b, s, h, d in shapes:
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (b, s, h, d)).astype(dtype)
        k = jax.random.normal(kk, (b, s, h, d)).astype(dtype)
        v = jax.random.normal(kv, (b, s, h, d)).astype(dtype)
        floor_f = 4 * b * h * s * s * d / peak
        impls = [("xla dense", functools.partial(xla_attention,
                                                 causal=args.causal), None),
                 ("jax pallas", functools.partial(upstream_attention,
                                                  causal=args.causal), None)]
        impls += [("ours" + (f" {var[0][1:].lower()} {var[1]}" if var else ""),
                   functools.partial(fa.flash_attention, causal=args.causal),
                   var) for var in variants]
        with jax.default_matmul_precision("highest"):
            f32 = [x.astype(jnp.float32) for x in (q, k, v)]
            want = [xla_attention(*f32, args.causal)] + list(
                grads(functools.partial(xla_attention,
                                        causal=args.causal))(*f32))
            want = [np.asarray(w) for w in want]
        base = None
        for name, fn, var in impls:
            if var:
                default = getattr(fa, var[0])
                setattr(fa, var[0], var[1])
                jax.clear_caches()
            try:
                fwd, bwd = jax.jit(fn), grads(fn)
                t_f = _timed(fwd, (q, k, v), args.iters)
                t_b = _timed(bwd, (q, k, v), args.iters)
                got = [fwd(q, k, v)] + list(bwd(q, k, v))
                errs = " ".join(
                    f"{np.max(np.abs(np.asarray(g, np.float32) - w)):.1e}"
                    for g, w in zip(got, want))
            except Exception as e:  # the compiler's refusal is the result
                print(f"({b:>2},{s:>5},{h:>3},{d:>3}) {name:>14} | REFUSED: "
                      f"{str(e).splitlines()[0][:200]}")
                continue
            finally:
                if var:
                    setattr(fa, var[0], default)
                    jax.clear_caches()
            base = base or t_b
            print(f"({b:>2},{s:>5},{h:>3},{d:>3}) {name:>14} | "
                  f"{t_f * 1e3:8.3f} {100 * floor_f / t_f:6.1f} | "
                  f"{t_b * 1e3:8.3f} {100 * 3 * floor_f / t_b:6.1f} "
                  f"{base / t_b:5.2f}x | {errs}", flush=True)
    print("(f+b is one call of grad wrt q, k, v: forward and backward; "
          "'vs xla' > 1 means faster than XLA's dense program)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
