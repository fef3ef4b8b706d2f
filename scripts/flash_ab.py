"""One-command flash-kernel vs XLA-attention A/B on a TPU (ROADMAP A2).

`chip_smoke.py` shows the kernels compile and agree with dense attention
on the chip; this sweep is the timing tool. Refuses to run without a TPU —
a CPU run would time the Pallas interpreter.

    python scripts/flash_ab.py            # full sweep, prints a table
    python scripts/flash_ab.py --causal   # the GPT shapes
    python scripts/flash_ab.py --causal --shapes 16x1024x12x64 --blocks 512 --strips 128
    python scripts/flash_ab.py --dropout 0.1 --shapes 16x512x16x64 --hw-prng
    python scripts/flash_ab.py --causal --shapes 1x8192x32x64 --kv-heads 8

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
program). ``--dropout RATE`` times attention-probabilities dropout: XLA's
dense program with the threefry mask the models' dense cores draw against
our kernel with its own mask (`ops.flash_attention.dropout_keep_mask`),
whose errors are then against dense f32 under that same mask; JAX's kernel
has no dropout and is left out. ``--hw-prng`` adds our kernel with the
chip's own generator in place of the hash (timing only: that mask cannot be
reproduced off the chip, so it is no path of the program). ``--kv-heads N``
gives k and v ``N`` heads (grouped-query attention): ours is then the
grouped kernels, and one more row times our equal-heads kernel behind a
`jnp.repeat` of k and v (what the grouped kernels replace).
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


def xla_attention(q, k, v, causal, keep=None, rate=0.0, kv_mask=None):
    """Plain composed attention over [B, S, H, D] (what the model zoo
    runs when no kernel applies). ``keep``: a dropout key (the threefry
    mask the models' dense cores draw) or a ready ``[B, H, S, S]`` mask."""
    import jax
    import jax.numpy as jnp

    d = q.shape[-1]
    k, v = repeat_kv(q, k, v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (d ** 0.5)
    if causal:
        S = q.shape[1]
        tri = jnp.tril(jnp.ones((S, S), jnp.bool_))
        s = jnp.where(tri[None, None], s, jnp.asarray(-1e9, s.dtype))
    if kv_mask is not None:     # the models' additive [B, 1, 1, S] form
        s = s + jnp.where(kv_mask, 0.0, -1e9).astype(s.dtype)[:, None, None]
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    if keep is not None:
        if keep.dtype != jnp.bool_:
            keep = jax.random.bernoulli(keep, 1.0 - rate, p.shape)
        p = p * keep / (1.0 - rate)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)


def repeat_kv(q, k, v):
    """k and v with each K/V head repeated for its group of Q heads (what
    stands in front of a kernel that needs equal head counts)."""
    import jax.numpy as jnp

    group = q.shape[2] // k.shape[2]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)


def hw_prng_keep(seed_ref, batch, head, row0, col0, shape, rate):
    """Stand-in for `ops.flash_attention._keep_strip` drawing the strip's
    bits from the chip's generator, seeded by the strip's coordinates."""
    import jax.numpy as jnp
    from jax.experimental.pallas import tpu as pltpu

    i32 = lambda x: jnp.asarray(x).astype(jnp.int32)  # noqa: E731
    # the generator takes two seed values: fold the coordinates into them
    pltpu.prng_seed(i32(seed_ref[0]) ^ (i32(batch) * 4099 + i32(head)),
                    i32(seed_ref[1]) ^ (i32(row0) * 65599 + i32(col0)))
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    return bits < jnp.uint32(round((1.0 - rate) * 2 ** 32))


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
    k, v = repeat_kv(q, k, v)
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
    ap.add_argument("--dropout", type=float, default=0.0, metavar="RATE",
                    help="attention-probabilities dropout at this rate")
    ap.add_argument("--kv-mask", action="store_true", help="hand every impl "
                    "but JAX's an all-valid key mask (BERT's packed batch)")
    ap.add_argument("--hw-prng", action="store_true", help="with --dropout: "
                    "also time our kernel on the chip's own generator")
    ap.add_argument("--kv-heads", type=int, default=None, help="grouped-query "
                    "attention: k and v hold this many heads; adds our "
                    "equal-heads kernel behind a repeat of k and v")
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
    rate = args.dropout
    if args.hw_prng and rate:
        variants.append(("_keep_strip", hw_prng_keep))
    rng = jax.random.PRNGKey(42) if rate else None

    dtype = jnp.dtype(args.dtype)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"flash_ab.py times TPU kernels; found {dev.platform}")
    peak = PEAK_FLOPS[dev.device_kind]
    print(f"device: {dev.device_kind}  causal={args.causal}  "
          f"dropout={rate}  dtype={dtype.name}  iters={args.iters}  "
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
        k = jax.random.normal(kk, (b, s, args.kv_heads or h, d)).astype(dtype)
        v = jax.random.normal(kv, (b, s, args.kv_heads or h, d)).astype(dtype)
        floor_f = 4 * b * h * s * s * d / peak
        kv_mask = jnp.ones((b, s), jnp.bool_) if args.kv_mask else None
        impls = [("xla dense", functools.partial(
            xla_attention, causal=args.causal, keep=rng, rate=rate,
            kv_mask=kv_mask), None)]
        if not rate:
            impls.append(("jax pallas", functools.partial(
                upstream_attention, causal=args.causal), None))
        impls += [("ours" + ((" hw prng" if callable(var[1]) else
                              f" {var[0][1:].lower()} {var[1]}")
                             if var else ""),
                   functools.partial(fa.flash_attention, causal=args.causal,
                                     kv_mask=kv_mask, dropout_rng=rng,
                                     dropout_rate=rate),
                   var) for var in variants]
        if args.kv_heads:
            ours = impls[-len(variants)][1]
            impls.append(("ours repeated",
                          lambda q_, k_, v_: ours(q_, *repeat_kv(q_, k_, v_)),
                          None))
        # the yardstick: dense f32, under our kernel's own mask if any
        keep = fa.dropout_keep_mask(rng, b, h, s, s, rate) if rate else None
        dense = functools.partial(xla_attention, causal=args.causal,
                                  keep=keep, rate=rate)
        try:
            with jax.default_matmul_precision("highest"):
                f32 = [x.astype(jnp.float32) for x in (q, k, v)]
                want = [np.asarray(w)
                        for w in [dense(*f32)] + list(grads(dense)(*f32))]
        except Exception as e:  # f32 scores of 32 heads at S=8192: 8.6 GB
            print(f"dense f32 yardstick REFUSED ({str(e).splitlines()[0][:80]}"
                  "): errors not compared; chip_smoke.py checks the values")
            want = None
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
                errs = "not compared" if want is None else " ".join(
                    f"{np.max(np.abs(np.asarray(g, np.float32) - w)):.1e}"
                    for g, w in zip(got, want))
                if rate and (name == "xla dense" or name.endswith("hw prng")):
                    errs = "another mask: not compared"
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
