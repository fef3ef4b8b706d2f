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


if __name__ == "__main__":
    sys.exit(main())
