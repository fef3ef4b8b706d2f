"""The Mamba-2 scan (`ops.ssd.ssd_chunked_scan`) on a TPU, one command.

    python scripts/ssd_ab.py [--shape 1x4096x64x64] [--chunks 64,128,256]

Times the scan alone at one Granite-4.0-H-Micro state-space layer's shape in
the benchmark cell (``x [1, 4096, 64, 64]``, one B/C group, state 128, bf16
operands), forward and forward + backward (one `jax.vjp` call whose output
and cotangents are all returned), at the model's chunk and at the others
given, against the floor the benchmark reads it by (the family's
`ssd_scan_flops` / `ssd_scan_bytes` at the chip's peaks:
`ssd_scan_roofline_pct`), and checks its values against the one-equation
form in float32 (`perfbench/families/granite_hybrid.py` `reference_scan`).
Refuses to run without a TPU: a CPU run would time XLA:CPU.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

GROUPS, STATE = 1, 128


def _timed(fn, args, iters):
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)  # ONE sync for the window
    return (time.perf_counter() - t0) / iters


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="1x4096x64x64", help="B x S x h x p")
    ap.add_argument("--chunks", default="256", help="chunk sizes, e.g. "
                    "'64,128,256' (the model's is 256)")
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dear_pytorch_tpu.ops.ssd import ssd_chunked_scan
    from perfbench import cell as cells

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"ssd_ab.py times a TPU; found {dev.platform}")
    fam = cells.load_py(cells.HERE / "families" / "granite_hybrid.py")
    peaks = cells.peaks(dev.device_kind)
    b, s, h, p = (int(x) for x in args.shape.split("x"))
    keys = jax.random.split(jax.random.PRNGKey(0), 7)
    x, dy = (jax.random.normal(k, (b, s, h, p)).astype(jnp.bfloat16)
             for k in keys[:2])
    bm, cm = (jax.random.normal(k, (b, s, GROUPS, STATE)).astype(jnp.bfloat16)
              for k in keys[2:4])
    # the model's own ranges at initialisation: dt log-uniform in
    # [0.001, 0.1], A in -[1, 16], D = 1
    dt = jnp.exp(jax.random.uniform(keys[4], (b, s, h), jnp.float32,
                                    np.log(1e-3), np.log(0.1)))
    a = -jax.random.uniform(keys[5], (h,), jnp.float32, 1.0, 16.0)
    d = jnp.ones((h,), jnp.float32)
    operands = (x, dt, a, bm, cm, d)
    print(f"device: {dev.device_kind}  x {x.shape} bf16, {GROUPS} group(s), "
          f"state {STATE}  iters={args.iters}")

    with jax.default_matmul_precision("highest"):
        f32 = [t.astype(jnp.float32) for t in operands]
        want = jax.jit(lambda x, dt, a, bm, cm, d: fam.reference_scan(
            x, dt, a, bm, cm) + d[:, None] * x)(*f32)
    want = np.asarray(want)
    for chunk in (int(c) for c in args.chunks.split(",")):
        model = {"layer_types": ["mamba"], "mamba_chunk_size": chunk,
                 "mamba_n_groups": GROUPS, "mamba_d_state": STATE,
                 "mamba_n_heads": h, "mamba_d_head": p}
        flops = fam.ssd_scan_flops(model, b * s)
        nbytes = fam.ssd_scan_bytes(model, b * s)
        floor = max(flops / peaks["bf16_flops_per_s"],
                    nbytes / peaks["hbm_bytes_per_s"])

        def fn(*t, chunk=chunk):
            return ssd_chunked_scan(*t, chunk)

        def both(*t):
            out, vjp = jax.vjp(fn, *t)
            return (out, *vjp(dy))

        try:
            t_f = _timed(jax.jit(fn), operands, args.iters)
            t_b = _timed(jax.jit(both), operands, args.iters)
        except Exception as e:  # the compiler's refusal is the result
            print(f"chunk {chunk:4d} | REFUSED: "
                  f"{str(e).splitlines()[0][:200]}")
            continue
        got = np.asarray(jax.jit(fn)(*operands), np.float32)
        err = np.abs(got - want).max() / np.abs(want).max()
        print(f"chunk {chunk:4d} | fwd {t_f * 1e3:8.3f} ms | f+b "
              f"{t_b * 1e3:8.3f} ms | work f+b {flops / 1e9:.1f} GFLOP, "
              f"{nbytes / 1e6:.1f} MB: floor {floor * 1e3:.3f} ms -> "
              f"{100 * floor / t_b:5.2f}% of the roofline | max abs err vs "
              f"the f32 one-equation form / max |y| {err:.2e}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
