"""The gated short convolution's filter on a TPU, two forms, one command.

    python scripts/short_conv_ab.py [--shape 1x8192x2048] [--iters 20]

``C * conv(B * x~)`` over ``[B, S, 3H]`` gates with ``[3, H]`` taps
(`models.lfm2_moe.short_conv_filter`): the program's form, three shifted
multiply-adds over a left-padded sequence, against
`lax.conv_general_dilated` with ``feature_group_count = H`` (a depthwise
convolution as XLA:TPU's convolution emitter sees it). Forward and forward +
backward, milliseconds a call, and the bytes a single pass would move (read
the gates, write the result; back: read gates and cotangent, write the
gates' cotangent) at the chip's HBM peak. Refuses to run without a TPU.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

#: HBM peak of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
PEAK_BYTES = {"TPU v5 lite": 819e9}


def conv_filter(gates, taps):
    """The same function through XLA's convolution."""
    import jax.numpy as jnp
    from jax import lax

    b, c, x = jnp.split(gates.astype(jnp.float32), 3, axis=-1)
    h, lag = x.shape[-1], taps.shape[0] - 1
    conv = lax.conv_general_dilated(
        b * x, taps[:, None, :], window_strides=(1,), padding=[(lag, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=h)
    return (c * conv).astype(gates.dtype)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="1x8192x2048", help="BxSxH")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dear_pytorch_tpu.models.lfm2_moe import short_conv_filter

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"short_conv_ab.py times a TPU; found {dev.platform}")
    b, s, h = (int(x) for x in args.shape.split("x"))
    kg, kt = jax.random.split(jax.random.PRNGKey(0))
    gates = jax.random.normal(kg, (b, s, 3 * h)).astype(jnp.bfloat16)
    taps = jax.random.normal(kt, (3, h), jnp.float32)
    one_pass = 2 * b * s * 4 * h            # bf16: 3H in, H out
    back_pass = 2 * b * s * 7 * h           # 3H + H in, 3H out
    peak = PEAK_BYTES[dev.device_kind]
    print(f"device: {dev.device_kind}  gates {gates.shape} bf16  floor: one "
          f"pass at {peak / 1e9:.0f} GB/s")
    want = None
    for name, fn in (("three shifts", short_conv_filter),
                     ("lax.conv depthwise", conv_filter)):
        fwd = jax.jit(fn)
        bwd = jax.jit(jax.grad(
            lambda g, t: fn(g, t).astype(jnp.float32).sum(), argnums=(0, 1)))
        times = []
        for f in (fwd, bwd):
            jax.block_until_ready(f(gates, taps))
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out = f(gates, taps)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) / args.iters)
        got = np.asarray(fwd(gates, taps), np.float32)
        want = got if want is None else want
        print(f"{name:>20} | fwd {times[0] * 1e3:7.3f} ms "
              f"({100 * one_pass / peak / times[0]:5.1f}% of one pass) | "
              f"f+b {times[1] * 1e3:7.3f} ms "
              f"({100 * (one_pass + back_pass) / peak / times[1]:5.1f}%) | "
              f"max abs diff vs three shifts {np.abs(got - want).max():.1e}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
