"""Per-grid-step / per-ring-hop overhead probes for the Pallas kernels.

Two sections:

1. ``elementwise`` — the original probe: identical total work at two grid
   granularities isolates the per-grid-step custom-call block-I/O cost.
2. ``kernels`` — the fused computation-collective kernels
   (ops/collective_matmul.py): `ring_all_gather` and
   `fused_reduce_scatter_update` at two chunk granularities over the SAME
   total bytes, reported as us per ring hop. On the CPU-emulated mesh it
   measures interpret-mode dispatch only (plumbing validation, NOT kernel
   speed — state that in any analysis; perf/kernels_r06/ holds such a
   run). On the chip the ring kernels do not compile yet
   (docs/KERNELS.md status table).

Usage:
  python scripts/pallas_overhead_probe.py [--section elementwise|kernels|all]
"""
import argparse
import os
import sys
import time

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def kern(x_ref, o_ref):
    o_ref[...] = x_ref[...] * 2.0 + 1.0


def run(nblocks, rows_per_block):
    x = jnp.ones((nblocks * rows_per_block, 512), jnp.float32)
    f = jax.jit(lambda x: pl.pallas_call(
        kern,
        grid=(nblocks,),
        in_specs=[pl.BlockSpec((rows_per_block, 512), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows_per_block, 512), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
    )(x))
    o = f(x); jax.block_until_ready(o)
    iters = 20
    t0 = time.perf_counter()
    for _ in range(iters):
        o = f(x)
    float(o[0, 0])
    dt = (time.perf_counter() - t0) / iters
    print(f"grid={nblocks:5d} x ({rows_per_block},512): {dt*1e3:8.3f} ms "
          f"-> {dt/nblocks*1e6:8.2f} us/grid-step", flush=True)


def elementwise_section():
    # identical total work (2M rows of 512), different grid granularity
    run(16,   1024)   # 16 big blocks
    run(2048,    8)   # 2048 tiny blocks


def kernel_section():
    """Ring-kernel per-hop cost at two chunk sizes, same total bytes."""
    from dear_pytorch_tpu.comm import backend
    from dear_pytorch_tpu.comm.backend import DP_AXIS
    from dear_pytorch_tpu.ops import collective_matmul as CM
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd

    mesh = backend.init()
    world = mesh.shape[DP_AXIS]
    if world < 2:
        print("kernel section needs a multi-device mesh; skipping",
              flush=True)
        return
    backend_name = jax.default_backend()
    print(f"ring-kernel probes on {world}-device {backend_name} mesh "
          f"(interpret={backend_name != 'tpu'}; interpret timings are "
          "dispatch overhead, not kernel speed)", flush=True)
    opt = fused_sgd(lr=0.01, momentum=0.9)

    def timeit(fn, *args, iters=5):
        out = fn(*args)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    for ss in (1 << 16, 1 << 10):   # big vs tiny shards, per device
        shards = jnp.ones((world, ss), jnp.float32)
        gstack = jnp.ones((world, world * ss), jnp.float32)
        pstack = jnp.ones((world, ss), jnp.float32)
        mstack = jnp.zeros((world, ss), jnp.float32)
        istack = jnp.zeros((world, 1), jnp.int32)

        ag = jax.jit(jax.shard_map(
            lambda s: CM.ring_all_gather(s[0], DP_AXIS)[None],
            mesh=mesh, in_specs=jax.P(DP_AXIS), out_specs=jax.P(DP_AXIS),
            check_vma=False))
        dt = timeit(ag, shards)
        hops = world - 1
        print(f"ring_all_gather  shard={ss:7d} f32: {dt*1e3:8.3f} ms "
              f"-> {dt/hops*1e6:8.2f} us/hop "
              f"({ss*4*hops/max(dt,1e-12)/2**30:6.2f} GiB/s/device wire)",
              flush=True)

        def rs(g, p, m, i):
            new_p, (new_m, new_i) = CM.fused_reduce_scatter_update(
                g[0], p[0], (m[0], i[0, 0] != 0), opt, DP_AXIS,
                mean_world=world)
            return new_p[None], new_m[None]

        rs_j = jax.jit(jax.shard_map(
            rs, mesh=mesh, in_specs=(jax.P(DP_AXIS),) * 4,
            out_specs=(jax.P(DP_AXIS),) * 2, check_vma=False))
        dt = timeit(rs_j, gstack, pstack, mstack, istack)
        print(f"fused_rs_update  shard={ss:7d} f32: {dt*1e3:8.3f} ms "
              f"-> {dt/hops*1e6:8.2f} us/hop (incl. SGD-momentum epilogue)",
              flush=True)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default="all",
                    choices=["elementwise", "kernels", "all"])
    args = ap.parse_args(argv)
    from dear_pytorch_tpu.benchmarks import runner
    runner.apply_platform_env()
    if args.section in ("elementwise", "all"):
        elementwise_section()
    if args.section in ("kernels", "all"):
        kernel_section()
    return 0


if __name__ == "__main__":
    sys.exit(main())
