"""One-command A/B of the routed experts' row movement on a TPU (ROADMAP A9a).

XLA's gather / gather-and-sum (what `parallel.ep.RoutedExperts` runs off the
TPU, and ran everywhere before PR 36) against the Pallas kernels of
`ops.moe_rows`, at one chip's share of an expert-parallel group: ``T`` tokens
choose ``k`` of ``8 / share`` experts uniformly and this chip holds 8 of
them, so ``share`` of the ``T*k`` sorted rows are live. Refuses to run without
a TPU: a CPU run would time the Pallas interpreter.

    python scripts/moe_rows_ab.py                     # shares 0, 1/8, 1/4, 1
    python scripts/moe_rows_ab.py --rows 64,256 --tokens 128,512
    python scripts/moe_rows_ab.py --layer             # a whole expert layer
    python scripts/moe_rows_ab.py --experts           # the feed-forward alone

Two rows a share: the spread (``xs[i] = x[order[i] // k]``; backward: the sum
of a token's held slots) and the combine (``sum_j w[t, j] * ys[inverse[t*k +
j]]`` over held slots in f32; backward: ``w * g`` by row and the row-wise
``<g, ys>``). Forward and forward + backward (one `jax.vjp` call whose
output and cotangents are both returned, so neither pass is dead code),
milliseconds a call, and GB/s on the rows that had to move: ``count
* H * itemsize``, read and written, once forward and twice with the backward
pass. ``--rows`` / ``--tokens`` / ``--depth`` time the kernels at other block
constants than their own (a tuning aid: constants of the code, not options of
the program). ``--layer`` times `RoutedExperts` whole, value and gradient,
at GLM-4.7-Flash's and LFM2-8B-A1B's expert widths, kernels against gathers.

``--experts`` (ROADMAP A9b) times the feed-forward between the two row
movements alone, on ``T*k`` sorted rows of which the two cells' shares are
held, GLM's with its skew (the largest expert 2.7x the mean): XLA's
`lax.ragged_dot` with the mask and the SwiGLU as fusions around it (what
`RoutedExperts` runs off the TPU), JAX's own Pallas grouped matmuls
(`jax.experimental.pallas.ops.tpu.megablox`) with the same fusions around
them, and the kernels of `ops.grouped_matmul`; value, and value with the
gradients for ``xs``, ``wi``, ``wo``; ms a call and the share of the chip's
197 TFLOP/s that ``6 * count * H * F`` (three times that with the backward)
FLOPs are. ``--tile-rows`` / ``--grad-rows`` / ``--budget-mb`` time the
kernels at other tile constants than their own, ``--each`` the six kernels
one by one.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SHARES = (0.0, 0.125, 0.25, 1.0)    # 0: no row live, the kernels' fixed cost
HELD = 8


def _timed(fn, args, iters):
    import jax

    jax.block_until_ready(fn(*args))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)  # ONE sync for the window
    return (time.perf_counter() - t0) / iters


def routing(key, tokens, k, share):
    """(group [T, k] with HELD for an absent expert, order, inverse, sizes,
    weights [T, k]) of ``k`` distinct uniform choices among HELD / share."""
    import jax
    import jax.numpy as jnp

    k_idx, k_w = jax.random.split(key)
    width = round(HELD / share) if share else 2 * HELD
    idx = jnp.argsort(jax.random.uniform(k_idx, (tokens, width)))[:, :k]
    if not share:
        idx = idx + HELD
    group = jnp.where(idx < HELD, idx, HELD).astype(jnp.int32)
    order = jnp.argsort(group.reshape(-1), stable=True)
    sizes = jnp.sum(group.reshape(-1, 1) == jnp.arange(HELD), axis=0,
                    dtype=jnp.int32)
    weights = jax.random.uniform(k_w, (tokens, k), jnp.float32, 0.1, 1.0)
    return group, order, jnp.argsort(order), sizes, weights


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="8192x4x2048", help="T x k x H")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--rows", help="also time the spread kernel at these "
                    "rows a grid step, e.g. '64,256'")
    ap.add_argument("--tokens", help="... the combine kernel at these tokens "
                    "a grid step, e.g. '128,512'")
    ap.add_argument("--depth", help="... and at these contraction depths")
    ap.add_argument("--layer", action="store_true", help="time a whole "
                    "RoutedExperts layer, kernels against gathers")
    ap.add_argument("--experts", action="store_true", help="time the "
                    "experts' feed-forward alone: XLA, megablox, ours")
    ap.add_argument("--tile-rows", help="with --experts: also at these rows "
                    "a tile of the matmul kernels, e.g. '128,512'")
    ap.add_argument("--grad-rows", help="... of the weight-gradient kernel")
    ap.add_argument("--budget-mb", help="... and at these VMEM budgets")
    ap.add_argument("--megablox-tiling", default="512,1024,1024")
    ap.add_argument("--each", action="store_true", help="with --experts: "
                    "the six kernels one by one")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dear_pytorch_tpu.ops import moe_rows
    from dear_pytorch_tpu.parallel import ep

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"moe_rows_ab.py times TPU kernels; found {dev.platform}")
    T, k, H = (int(x) for x in args.shape.split("x"))
    dtype = jnp.dtype(args.dtype)
    print(f"device: {dev.device_kind}  (T, k, H) = ({T}, {k}, {H})  "
          f"dtype={dtype.name}  iters={args.iters}")
    if args.layer:
        return layer_ab(args, T, H, dtype)
    if args.experts:
        return experts_ab(args, T * k, H, dtype)

    variants = [None] + [
        (const, int(x)) for const, given in (("_ROWS", args.rows),
                                             ("_TOKENS", args.tokens),
                                             ("_DEPTH", args.depth))
        for x in (given or "").split(",") if x]
    print(f"{'share':>6} {'count':>6} {'op':>8} {'impl':>18} | {'fwd ms':>8} "
          f"{'GB/s':>6} | {'f+b ms':>8} {'GB/s':>6} {'vs xla':>6} | "
          "max abs err vs xla (out, grads)")

    for share in SHARES:
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        group, order, inverse, sizes, weights = routing(keys[0], T, k, share)
        count = int(jnp.sum(sizes))
        live = (jnp.arange(T * k) < count)[:, None]
        held = group < HELD
        x = jax.random.normal(keys[1], (T, H)).astype(dtype)
        ys = jax.random.normal(keys[2], (T * k, H)).astype(dtype)
        ct_sorted = jax.random.normal(keys[3], (T * k, H)).astype(dtype)
        ct_token = x[::-1]
        moved_bytes = 2 * count * H * dtype.itemsize

        def xla_spread(x):
            return ep._spread(x, order, inverse, live[:, 0])

        def xla_combine(ys, w):
            back = ep._unpermute(ys, order, inverse).reshape(T, k, H)
            back = jnp.where(held[..., None], back.astype(jnp.float32), 0.0)
            return jnp.sum(back * w[..., None], axis=1).astype(dtype)

        def both(fn, ct):   # forward and backward in one call, both kept
            def run(*a):
                out, vjp = jax.vjp(fn, *a)
                return (out, *vjp(ct))
            return run

        def masked(out):    # rows of absent experts hold anything
            return [np.asarray(jnp.where(live, o, 0) if o.shape[0] == T * k
                               else o, np.float32) for o in out]

        def moved():    # the kernels' plan is part of what they cost
            return moe_rows.dispatch(group, order, inverse, sizes)

        ops = {
            "spread": (xla_spread, lambda x: moe_rows.spread(x, moved()),
                       (x,), ct_sorted),
            "combine": (xla_combine,
                        lambda ys, w: moe_rows.combine(ys, w, moved(), dtype),
                        (ys, weights), ct_token),
        }
        for op, (xla, ours, operands, ct) in ops.items():
            want = base = None
            for var in variants:
                if var and (var[0] == "_ROWS") != (op == "spread"):
                    continue
                impls = [("kernels" + (f" {var[0][1:].lower()} {var[1]}"
                                       if var else ""), ours)]
                if not var:
                    impls.insert(0, ("xla gather", xla))
                for name, fn in impls:
                    if var:
                        default = getattr(moe_rows, var[0])
                        setattr(moe_rows, var[0], var[1])
                    try:
                        fwd, bwd = jax.jit(fn), jax.jit(both(fn, ct))
                        t_f = _timed(fwd, operands, args.iters)
                        t_b = _timed(bwd, operands, args.iters)
                        got = masked(bwd(*operands))
                    except Exception as e:  # the compiler's refusal
                        print(f"{share:6.3f} {count:6d} {op:>8} {name:>18} | "
                              f"REFUSED: {str(e).splitlines()[0][:160]}")
                        continue
                    finally:
                        if var:
                            setattr(moe_rows, var[0], default)
                    want = want or got
                    base = base or t_b
                    errs = " ".join(f"{np.max(np.abs(g - w)):.1e}"
                                    for g, w in zip(got, want))
                    print(f"{share:6.3f} {count:6d} {op:>8} {name:>18} | "
                          f"{t_f * 1e3:8.3f} {moved_bytes / t_f / 1e9:6.0f} | "
                          f"{t_b * 1e3:8.3f} "
                          f"{2 * moved_bytes / t_b / 1e9:6.0f} "
                          f"{base / t_b:5.2f}x | {errs}", flush=True)
    print("(f+b is one call of jax.vjp, output and cotangents kept; 'vs xla' > 1 "
          "means faster than XLA's gathers; GB/s counts the live rows only, "
          "read and written)")
    return 0


def layer_ab(args, T, H, dtype) -> int:
    """A whole `RoutedExperts` layer, value and gradient for the parameters
    and the input, at the two cells' expert widths."""
    import jax
    import jax.numpy as jnp

    from dear_pytorch_tpu.ops import moe_rows
    from dear_pytorch_tpu.parallel import ep

    applies = moe_rows.applies
    for name, width, mlp in (("glm-4.7-flash", 64, 1536),
                             ("lfm2-8b-a1b", 32, 1792)):
        layer = ep.RoutedExperts(router_width=width, experts_held=HELD,
                                 top_k=4, mlp_dim=mlp, dtype=dtype)
        x = jax.random.normal(jax.random.PRNGKey(1), (T, H)).astype(dtype)
        params = jax.jit(layer.init)(jax.random.PRNGKey(0), x)["params"]

        def loss(p, x):
            y = layer.apply({"params": p}, x)
            return jnp.sum(jnp.square(y.astype(jnp.float32)))

        times = {}
        for impl, rule in (("gathers", lambda *a: False),
                           ("kernels", applies)):
            moe_rows.applies = rule
            try:
                step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
                times[impl] = _timed(step, (params, x), args.iters)
                value = float(step(params, x)[0])
            finally:
                moe_rows.applies = applies
            print(f"{name:>14} 8 of {width} held, F={mlp}: {impl} "
                  f"{times[impl] * 1e3:8.3f} ms a layer (value and gradient), "
                  f"loss {value:.6g}", flush=True)
        print(f"{name:>14} kernels {times['gathers'] / times['kernels']:.2f}x "
              f"the gathers, {1e3 * (times['gathers'] - times['kernels']):.3f}"
              " ms a layer saved")
    return 0


#: (name, F, held share of the rows, each held expert's rows over the mean)
EXPERT_SHAPES = (
    ("glm-4.7-flash", 1536, 0.126, (2.7, 1.5, 1.1, 0.9, 0.7, 0.5, 0.35, 0.25)),
    ("lfm2-8b-a1b", 1792, 0.251, (1.15, 1.1, 1.05, 1.0, 1.0, 0.95, 0.9, 0.85)),
)
PEAK = 197e12   # bf16 FLOP/s of one v5e chip (perfbench/peaks.json)


def experts_ab(args, N, H, dtype) -> int:
    """The experts' feed-forward alone on ``N`` sorted rows: three
    implementations, value and gradients."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.experimental.pallas.ops.tpu import megablox

    from dear_pytorch_tpu.ops import grouped_matmul as gm

    tiling = tuple(int(x) for x in args.megablox_tiling.split(","))

    def around(grouped):    # `RoutedExperts`' program around a grouped dot
        def ffn(xs, wi, wo, sizes):
            F = wo.shape[1]
            valid = jnp.arange(N) < jnp.sum(sizes)
            gate_up = grouped(xs, wi.astype(dtype), sizes)
            gate_up = jnp.where(valid[:, None], gate_up, 0)
            act = jax.nn.silu(gate_up[:, :F]) * gate_up[:, F:]
            return grouped(act, wo.astype(dtype), sizes)
        return ffn

    def ours(xs, wi, wo, sizes):
        return gm.feed_forward(xs, wi.astype(dtype), wo.astype(dtype), sizes)

    impls = [
        ("xla ragged_dot", around(lax.ragged_dot), {}),
        (f"megablox {args.megablox_tiling}", around(
            lambda a, b, sizes: megablox.gmm(a, b, sizes, dtype, tiling)), {}),
        ("kernels", ours, {}),
    ] + [(f"kernels {const[1:].lower()} {x}", ours, {const: int(x) * scale})
         for const, given, scale in (("_ROWS", args.tile_rows, 1),
                                     ("_ROWS_T", args.grad_rows, 1),
                                     ("_VMEM_BUDGET", args.budget_mb, 2**20))
         for x in (given or "").split(",") if x]
    print(f"{'shape':>14} {'count':>6} {'impl':>26} | {'fwd ms':>8} "
          f"{'peak':>6} | {'f+b ms':>8} {'peak':>6} {'vs xla':>6} | max abs "
          "err vs xla (ys, d_xs, d_wi, d_wo)")
    for name, F, share, skew in EXPERT_SHAPES:
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        sizes = jnp.asarray([round(share * N / HELD * s) for s in skew],
                            jnp.int32)
        count = int(jnp.sum(sizes))
        live = (jnp.arange(N) < count)[:, None]
        xs = jax.random.normal(keys[0], (N, H)).astype(dtype)
        wi = jax.random.normal(keys[1], (HELD, H, 2 * F)) * H ** -0.5
        wo = jax.random.normal(keys[2], (HELD, F, H)) * F ** -0.5
        ct = jax.random.normal(keys[3], (N, H)).astype(dtype)
        flops = 6 * count * H * F
        want = base = None
        for impl, fn, consts in impls:
            saved = {c: getattr(gm, c) for c in consts}
            for c, value in consts.items():
                setattr(gm, c, value)
            try:
                def both(xs, wi, wo, sizes, fn=fn):
                    out, vjp = jax.vjp(lambda *a: fn(*a, sizes), xs, wi, wo)
                    return (out, *vjp(ct))
                fwd, bwd = jax.jit(fn), jax.jit(both)
                t_f = _timed(fwd, (xs, wi, wo, sizes), args.iters)
                t_b = _timed(bwd, (xs, wi, wo, sizes), args.iters)
                got = [np.asarray(jnp.where(live, o, 0) if o.shape[0] == N
                                  else o, np.float32)
                       for o in bwd(xs, wi, wo, sizes)]
                if args.each and impl.startswith("kernels"):
                    each_kernel(args, gm, xs, wi.astype(dtype),
                                wo.astype(dtype), sizes, ct, count)
            except Exception as e:  # the compiler's refusal
                print(f"{name:>14} {count:6d} {impl:>26} | REFUSED: "
                      f"{str(e).splitlines()[0][:160]}")
                continue
            finally:
                for c, value in saved.items():
                    setattr(gm, c, value)
                if consts:  # the inner jits' keys do not hold the constants
                    jax.clear_caches()
            want, base = want or got, base or t_b
            errs = " ".join(f"{np.max(np.abs(g - w)):.1e}"
                            for g, w in zip(got, want))
            print(f"{name:>14} {count:6d} {impl:>26} | {t_f * 1e3:8.3f} "
                  f"{100 * flops / t_f / PEAK:5.1f}% | {t_b * 1e3:8.3f} "
                  f"{100 * 3 * flops / t_b / PEAK:5.1f}% {base / t_b:5.2f}x | "
                  f"{errs}", flush=True)
    print("(sizes: " + "; ".join(
        f"{n} {[round(share * N / HELD * s) for s in skew]}"
        for n, _, share, skew in EXPERT_SHAPES) + "; 'peak' is 6 * count * H "
        "* F FLOPs, 18 with the backward, over the time and 197 TFLOP/s)")
    return 0


def each_kernel(args, gm, xs, wi, wo, sizes, ct, count):
    """The six kernels of one feed-forward, each alone, at the module's own
    tiles: ms and the share of the peak its own matmul FLOPs are."""
    import jax

    (N, H), F = xs.shape, wo.shape[1]
    tiles, rows, rows_t = gm._plan(xs, wi)
    walk, walk_t = gm.visits(sizes, N, rows), gm.visits(sizes, N, rows_t)
    how = dict(rows=rows, interpret=gm._interpret())
    how_t = dict(rows=rows_t, interpret=gm._interpret())
    gu, act = gm._gate_up(xs, wi, walk, cols=tiles.gate_up, **how)
    d_gu, _ = gm._act_grad(ct, wo, gu, walk, cols=tiles.gate_up, **how)
    print(f"    tiles {tiles}, rows {rows} / {rows_t}, visits "
          f"{int(walk.total[0])} / {int(walk_t.total[0])}")
    for what, flops, fn, operands in (
        ("gate_up", 4, lambda *a: gm._gate_up(
            *a, walk, cols=tiles.gate_up, **how), (xs, wi)),
        ("ys", 2, lambda *a: gm._matmul(
            *a, walk, transposed=False, cols=tiles.out, **how),
         (act[None], wo)),
        ("act_grad", 2, lambda *a: gm._act_grad(
            *a, walk, cols=tiles.gate_up, **how), (ct, wo, gu)),
        ("d_xs", 4, lambda *a: gm._matmul(
            *a, walk, transposed=True, cols=tiles.back, **how), (d_gu, wi)),
        ("d_wi", 4, lambda *a: gm._weight_grad(
            *a, walk_t, cols=tiles.d_wi, **how_t), (xs, d_gu)),
        ("d_wo", 2, lambda *a: gm._weight_grad(
            *a, walk_t, cols=tiles.d_wo, **how_t), (act, ct[None])),
    ):
        t = _timed(jax.jit(fn), operands, args.iters)
        print(f"    {what:>9} {t * 1e3:8.3f} ms "
              f"{100 * flops * count * H * F / t / PEAK:5.1f}%", flush=True)


if __name__ == "__main__":
    sys.exit(main())
