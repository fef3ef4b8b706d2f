"""The KDA scan (`ops.kda.kda_chunked`) on a TPU, one command.

    python scripts/kda_ab.py [--shape 1x8192x32x128] [--iters 10]
                             [--check-seq 2048]

Times the scan alone at one Ling-3.0-flash KDA layer's shape in the
benchmark cell (``q, k, v [1, 8192, 32, 128]`` bf16, chunk 64, decays in
``[-5, 0]``), forward and forward + backward (one `jax.vjp` call whose
output and cotangents are all returned), on the Pallas kernels and on XLA's
chunked form, against the floor the benchmark reads it by (the family's
`kda_scan_flops` / `kda_scan_bytes` for one layer at the chip's peaks:
`kda_scan_roofline_pct`). Then, at ``--check-seq`` positions, the kernels'
values and the five input gradients against XLA's form in f32 (matmul
precision "highest") and in bf16, and both f32 forms' values against the
position-by-position recurrence (`perfbench/families/ling_hybrid.py`
`reference_delta_rule`). Refuses to run without a TPU: a CPU run would time
the Pallas interpreter.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CHUNK, BOUND = 64, -5.0


def _timed(fn, args, iters):
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)  # ONE sync for the window
    return (time.perf_counter() - t0) / iters


def _inputs(shape, dtype, seed=0):
    import jax
    import jax.numpy as jnp

    keys = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    q, k = (unit(jax.random.normal(key, shape)) for key in keys[:2])
    v = jax.random.normal(keys[2], shape)
    g = jax.random.uniform(keys[3], shape, minval=BOUND, maxval=0.0)
    beta = jax.random.uniform(keys[4], shape[:3])
    do = jax.random.normal(keys[5], shape).astype(dtype)
    return (*(x.astype(dtype) for x in (q, k, v)), g, beta), do


def _paths(kda):
    """The scan on the kernels and on XLA's form, chosen by hand."""
    kernel_applies = kda.kernel_applies

    def on(kernels):
        def fn(q, k, v, g, beta):
            kda.kernel_applies = (kernel_applies if kernels
                                  else lambda *a, **kw: False)
            try:
                return kda.kda_chunked(q, k, v, g, beta, CHUNK,
                                       lower_bound=BOUND)
            finally:
                kda.kernel_applies = kernel_applies
        return fn

    return {"kernels": on(True), "xla": on(False)}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="1x8192x32x128", help="B x S x H x K")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--check-seq", type=int, default=2048)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dear_pytorch_tpu.ops import kda
    from perfbench import cell as cells

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"kda_ab.py times a TPU; found {dev.platform}")
    fam = cells.load_py(cells.HERE / "families" / "ling_hybrid.py")
    peaks = cells.peaks(dev.device_kind)
    b, s, h, d = (int(x) for x in args.shape.split("x"))
    model = {"layer_types": ["kda"], "first_k_dense_replace": 1,
             "num_attention_heads": h, "head_dim": d,
             "kda_chunk_size": CHUNK}
    floor = max(fam.kda_scan_flops(model, b * s) / peaks["bf16_flops_per_s"],
                fam.kda_scan_bytes(model, b * s) / peaks["hbm_bytes_per_s"])
    print(f"device: {dev.device_kind}  q {(b, s, h, d)} bf16, chunk {CHUNK}  "
          f"iters={args.iters}  kernel_applies="
          f"{kda.kernel_applies((b, s, h, d), CHUNK, jnp.bfloat16)}")
    operands, do = _inputs((b, s, h, d), jnp.bfloat16)
    for name, fn in _paths(kda).items():
        def both(*t, fn=fn):
            out, vjp = jax.vjp(fn, *t)
            return (out, *vjp(do))

        t_f = _timed(jax.jit(fn), operands, args.iters)
        t_b = _timed(jax.jit(both), operands, args.iters)
        print(f"{name:8s} | fwd {t_f * 1e3:8.3f} ms | f+b {t_b * 1e3:8.3f} ms"
              f" | floor {floor * 1e3:.3f} ms -> {100 * floor / t_b:5.2f}% "
              f"of the roofline", flush=True)

    shape = (1, args.check_seq, h, d)
    for dtype in (jnp.float32, jnp.bfloat16):
        operands, do = _inputs(shape, dtype, seed=1)
        grads = {}
        with jax.default_matmul_precision("highest"):
            for name, fn in _paths(kda).items():
                out, vjp = jax.jit(lambda *t, fn=fn: jax.vjp(fn, *t))(
                    *operands)
                grads[name] = [np.asarray(x, np.float32)
                               for x in (out, *vjp(do))]
            if dtype == jnp.float32:
                want = np.asarray(jax.jit(fam.reference_delta_rule)(
                    *operands))
                for name in grads:
                    err = (np.abs(grads[name][0] - want).max()
                           / np.abs(want).max())
                    print(f"f32 {name} vs the recurrence: {err:.2e} of max "
                          f"|o|", flush=True)
        errs = ", ".join(
            f"{n} {np.abs(a - w).max() / np.abs(w).max():.2e}"
            for n, a, w in zip(("o", "dq", "dk", "dv", "dlog_alpha",
                                "dbeta"), grads["kernels"], grads["xla"]))
        print(f"{jnp.dtype(dtype).name} kernels vs xla, max abs err / max "
              f"|xla|: {errs}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
