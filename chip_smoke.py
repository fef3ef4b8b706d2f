"""Smallest end-to-end proof that the DeAR train step runs on the TPU.

    python chip_smoke.py             # one chip: train, reference, flash, dropout
    python chip_smoke.py --chips 4   # four chips: dear vs allreduce, only

One process, GPT-2 124M at its published widths (12 layers, hidden 768,
12 heads, MLP 3072, vocab 50257, S=1024, bf16 compute over f32 masters,
dropout-free), random weights from ``--seed``, entered the way
`benchmarks/gpt.py` enters it: `backend.init` -> `build_train_step` ->
`ts.init` -> `ts.step` on a batch placed with `runner.stage_global`.

The phases are plain functions of (mesh, config, batch size, steps), so
tests/test_chip_smoke.py runs the same code at tiny sizes on the CPU mesh.
`main()` owns the platform check and the real sizes: it refuses to run
unless JAX reports a TPU, any failed check raises (non-zero exit, no result
line), and on success the LAST stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.
Every time printed here is a smoke timing of a few steps, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from dear_pytorch_tpu import models
from dear_pytorch_tpu.benchmarks import runner
from dear_pytorch_tpu.comm import backend
from dear_pytorch_tpu.comm.backend import DP_AXIS
from dear_pytorch_tpu.models import data
from dear_pytorch_tpu.models.gpt import (
    causal_dot_product_attention,
    flash_causal_attention_impl,
)
from dear_pytorch_tpu.ops.flash_attention import (
    dropout_keep_mask,
    flash_attention,
)
from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
from dear_pytorch_tpu.parallel import build_train_step
from dear_pytorch_tpu.utils import perf_model

# bench.py's bench_gpt optimizer: a decade and a half inside the stable
# side of the lr sweep recorded for examples/char_gpt.py (CHANGES.md, PR 24)
LR, MOMENTUM = 0.01, 0.9
THRESHOLD_MB = 25.0
#: dear vs the plain f32 loop, per-step |loss difference|. Both run the
#: same f32 math at the chip's default matmul precision; only XLA's fusion
#: choices differ. The first chip run read 6.2e-5 over 10 steps on losses
#: near 10.9 (PERF.md, PR 24); the bound leaves that a factor of 16.
REFERENCE_ATOL = 1e-3
#: dear vs allreduce across chips: same math, bf16 gradients reduced in a
#: different order. The first four-chip run read 4.2e-5 over 10 steps
#: (PERF.md, PR 24); the bound leaves that a factor of 24.
DP_ATOL = 1e-3
#: flash kernel vs dense f32 attention on the same bf16 inputs — the
#: tolerance tests/test_flash_attention.py uses for bf16.
FLASH_TOL = 5e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def gpt2_config(dtype, num_layers: int | None = None):
    """GPT-2 124M, dropout-free (bench.py's bench_gpt config); only depth
    may be cut."""
    cfg = models.dropout_free(models.get_model("gpt2", dtype=dtype).config)
    if num_layers is not None:
        cfg = dataclasses.replace(cfg, num_hidden_layers=num_layers)
    return cfg


def make_loss(cfg, attention_impl=None):
    """(model, loss_fn): the causal-LM loss of `benchmarks/gpt.py`."""
    model = models.GptLmHeadModel(cfg, attention_impl=attention_impl)

    def loss_fn(params, batch):
        logits = model.apply({"params": params}, batch["input_ids"],
                             train=True)
        return models.gpt_lm_loss(logits, batch["input_ids"],
                                  vocab_size=cfg.vocab_size)

    return model, loss_fn


def make_model(cfg, seq_len: int, seed: int, attention_impl=None):
    """(loss_fn, params): `make_loss` and seeded random weights (the
    attention impl adds no parameters, so every variant of one config
    starts from identical weights)."""
    model, loss_fn = make_loss(cfg, attention_impl)
    ids = jnp.zeros((1, seq_len), jnp.int32)
    params = jax.jit(
        lambda key: model.init({"params": key}, ids, train=False)["params"]
    )(jax.random.PRNGKey(seed))
    return loss_fn, params


def make_batch(mesh, cfg, batch_size: int, seq_len: int, seed: int):
    """One fixed seeded global batch, committed to the mesh once with the
    helper the benchmark CLIs use (split over 'dp')."""
    batch = data.synthetic_gpt_batch(
        jax.random.PRNGKey(seed + 1), batch_size, seq_len=seq_len,
        vocab_size=cfg.vocab_size)
    return runner.stage_global(
        batch, jax.sharding.NamedSharding(mesh, jax.P(DP_AXIS)))


def compile_step(ts, state, batch, label: str):
    """AOT-compile the step program (same cache entry `ts.step` then runs);
    prints the compile seconds and the program's device memory by XLA's
    own analysis, and returns (the program as lowered, the program as the
    compiler optimized it)."""
    lowered = ts.lower(state, batch)
    t0 = time.perf_counter()
    compiled = lowered.compile()
    log(f"[{label}] compile: {time.perf_counter() - t0:.1f} s; program "
        f"memory (memory_analysis): "
        f"{perf_model.peak_hbm_bytes(compiled) / 2**30:.2f} GiB")
    return lowered, compiled


def count_collectives(compiled_text: str) -> dict:
    """Collective instructions in optimized HLO text, by opcode (async
    ``-start`` forms counted under their base name)."""
    ops = re.findall(
        r" (all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(", compiled_text)
    return {op: ops.count(op) for op in sorted(set(ops))}


def run_steps(ts, state, batch, steps: int, label: str):
    """``steps`` calls of ``ts.step`` on one batch -> (state, losses,
    warm seconds per step). The first step is timed apart (it still pays
    dispatch set-up); the rest are dispatched back to back and end in one
    `block_until_ready`."""
    t0 = time.perf_counter()
    state, metrics = ts.step(state, batch)
    losses = [float(metrics["loss"])]
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    pending = []
    for _ in range(steps - 1):
        state, metrics = ts.step(state, batch)
        pending.append(metrics["loss"])
    jax.block_until_ready((state, pending))
    warm = (time.perf_counter() - t0) / max(steps - 1, 1)
    losses += [float(x) for x in pending]
    log(f"[{label}] first step {first:.2f} s, then {warm * 1e3:.1f} ms/step "
        f"over {steps - 1} steps (smoke timing, not a benchmark)")
    log(f"[{label}] losses: " + " ".join(f"{x:.4f}" for x in losses))
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"{label}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{label}: loss did not fall: {losses}")
    return state, losses, warm


def memory_stat(device, key: str):
    """One of the allocator's counters, or None where the backend reports
    none (CPU). On the v5e they count live arrays, not a running program's
    temporaries (PERF.md, PR 24) — `compile_step` prints those."""
    stats = device.memory_stats()
    return None if stats is None else stats[key]


def phase_train(mesh, cfg, batch_size: int, seq_len: int, steps: int,
                seed: int, attention_impl=None, label: str = "train"):
    """The main path: bf16-compute GPT under ``mode='dear'`` + `fused_sgd`
    with bf16 gradient communication. Returns losses, warm step seconds and
    the compiled program's text."""
    loss_fn, params = make_model(cfg, seq_len, seed, attention_impl)
    batch = make_batch(mesh, cfg, batch_size, seq_len, seed)
    ts = build_train_step(
        loss_fn, params, mesh=mesh, mode="dear", threshold_mb=THRESHOLD_MB,
        optimizer=fused_sgd(lr=LR, momentum=MOMENTUM),
        comm_dtype=jnp.bfloat16)
    state = ts.init(params)
    del params
    nparams = sum(b.size for b in ts.plan.buckets)
    log(f"[{label}] {cfg.num_hidden_layers} layers, hidden "
        f"{cfg.hidden_size}, S={seq_len}, batch {batch_size}, "
        f"{nparams / 1e6:.1f}M parameters in {ts.plan.num_buckets} bucket(s)")
    text = compile_step(ts, state, batch, label)[1].as_text()
    state, losses, warm = run_steps(ts, state, batch, steps, label)
    peak = memory_stat(mesh.devices.flat[0], "peak_bytes_in_use")
    log(f"[{label}] peak_bytes_in_use: {peak}")
    return {"losses": losses, "warm_step_s": warm, "text": text,
            "peak_bytes": peak}


def phase_reference(mesh, cfg, batch_size: int, seq_len: int, steps: int,
                    seed: int, atol: float = REFERENCE_ATOL):
    """Delayed update == SGD, on the device: ``mode='dear'`` in f32 against
    a plain loop written here — `jax.value_and_grad` of the same loss and
    torch-semantics momentum SGD, no framework code — step for step."""
    loss_fn, params = make_model(cfg, seq_len, seed)
    batch = make_batch(mesh, cfg, batch_size, seq_len, seed)

    @jax.jit
    def plain_step(p, buf, batch, first):
        loss, grads = jax.value_and_grad(loss_fn)(p, batch)
        buf = jax.tree.map(
            lambda b, g: jnp.where(first, g, MOMENTUM * b + g), buf, grads)
        return jax.tree.map(lambda w, b: w - LR * b, p, buf), buf, loss

    t0 = time.perf_counter()
    p, buf = params, jax.tree.map(jnp.zeros_like, params)
    plain = []
    for i in range(steps):
        p, buf, loss = plain_step(p, buf, batch, i == 0)
        plain.append(float(loss))
        if i == 0:
            log("[reference] plain loop compile + first step: "
                f"{time.perf_counter() - t0:.1f} s")
    del p, buf
    log("[reference] plain losses: " + " ".join(f"{x:.4f}" for x in plain))

    ts = build_train_step(
        loss_fn, params, mesh=mesh, mode="dear", threshold_mb=THRESHOLD_MB,
        optimizer=fused_sgd(lr=LR, momentum=MOMENTUM))
    state = ts.init(params)
    del params
    compile_step(ts, state, batch, "reference/dear-f32")
    _, dear, _ = run_steps(ts, state, batch, steps, "reference/dear-f32")
    diff = float(np.max(np.abs(np.asarray(dear) - np.asarray(plain))))
    log(f"[reference] max |dear - plain| over {steps} steps: {diff:.2e} "
        f"(tolerance {atol:.0e})")
    if not diff <= atol:
        raise AssertionError(
            f"dear diverged from plain SGD: {diff} > {atol}\n"
            f"dear  {dear}\nplain {plain}")
    return {"dear": dear, "plain": plain, "max_diff": diff}


#: LFM2-8B-A1B's attention layer in the benchmark cell: 32 Q heads over 8
#: K/V heads of 64 at S=8192, the grouped kernels
GROUPED_SHAPE = ((1, 8192, 32, 64), 8)


def _with_grads(attend, q, k, v, do):
    """(out, dq, dk, dv) of ``attend(q, k, v)`` under the cotangent ``do``."""
    out, vjp = jax.vjp(attend, q, k, v)
    return (out,) + vjp(do.astype(out.dtype))


def _max_abs_errors(got, want, tol: float, what: str) -> dict:
    """Max abs error of (out, dq, dk, dv) ``got`` against ``want``, each
    asserted within ``tol``."""
    errs = {}
    for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w)
        errs[name] = float(np.max(np.abs(g - w)))
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol,
                                   err_msg=f"{what} {name}")
    return errs


def check_flash_kernel(shape, seed: int, tol: float = FLASH_TOL,
                       kv_heads=None):
    """Causal flash forward + q/k/v gradients at ``(B, S, H, D)`` in bf16
    against dense attention computed in f32 (highest matmul precision) on
    the same bf16-rounded inputs. ``kv_heads`` fewer than ``H``: the grouped
    kernels, k and v ``[B, S, kv_heads, D]``; the dense program (which
    repeats them) then runs one K/V head and its Q heads at a time (the f32
    scores of 32 heads at S=8192 are 8.6 GB). Returns the max abs error per
    tensor and whether the compiled program carries a Mosaic kernel."""
    b, s, h, d = shape
    groups = kv_heads or 1
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, do = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys[::3])
    k, v = (jax.random.normal(kk, (b, s, kv_heads or h, d), jnp.bfloat16)
            for kk in keys[1:3])

    flash = jax.jit(functools.partial(
        _with_grads, functools.partial(flash_attention, causal=True)))
    dense = jax.jit(functools.partial(
        _with_grads, lambda q, k, v: causal_dot_product_attention(
            q, k, v, None, dtype=jnp.float32)))
    t0 = time.perf_counter()
    compiled = flash.lower(q, k, v, do).compile()
    log(f"[flash] kernel fwd+bwd compile at {shape}"
        + (f" over {kv_heads} K/V heads" if kv_heads else "")
        + f": {time.perf_counter() - t0:.1f} s")
    got = compiled(q, k, v, do)
    splits = [np.array_split(x.astype(jnp.float32), groups, axis=2)
              for x in (q, k, v, do)]
    with jax.default_matmul_precision("highest"):
        parts = [dense(*(split[j] for split in splits))
                 for j in range(groups)]
    want = [np.concatenate(t, axis=2) for t in zip(*parts)]
    errs = _max_abs_errors(got, want, tol, "flash vs dense:")
    log(f"[flash] max abs error vs dense f32 at {shape} (bf16, tolerance "
        f"{tol}): " + ", ".join(f"{n} {e:.2e}" for n, e in errs.items()))
    return errs, "tpu_custom_call" in compiled.as_text()


def phase_flash(mesh, cfg, batch_size: int, seq_len: int, steps: int,
                seed: int, dense: dict, grouped=GROUPED_SHAPE):
    """The flash path (what ``--flash-attention`` selects): the kernel
    against dense attention at the model's attention shape and, with fewer
    K/V heads, at ``grouped`` (``((B, S, H, D), kv_heads)``: the grouped
    kernels, values of the forward pass and of all three gradients), then
    the same train step as `phase_train` with `flash_causal_attention_impl`.
    ``dense`` is `phase_train`'s result on the same config, batch and seed:
    the two must start at the same loss."""
    heads = cfg.num_attention_heads
    shape = (batch_size, seq_len, heads, cfg.hidden_size // heads)
    errs, kernel_alone = check_flash_kernel(shape, seed)
    grouped_errs, grouped_kernel = check_flash_kernel(
        grouped[0], seed, kv_heads=grouped[1])
    kernel_alone = kernel_alone and grouped_kernel
    res = phase_train(mesh, cfg, batch_size, seq_len, steps, seed,
                      attention_impl=flash_causal_attention_impl(),
                      label="flash")
    res["kernel_errors"] = errs
    res["grouped_kernel_errors"] = grouped_errs
    res["kernel_in_program"] = (kernel_alone
                                and "tpu_custom_call" in res["text"])
    log(f"[flash] tpu_custom_call in the compiled train step: "
        f"{res['kernel_in_program']}")
    gap = abs(res["losses"][0] - dense["losses"][0])
    log(f"[flash] first-step loss flash {res['losses'][0]:.4f} vs dense "
        f"{dense['losses'][0]:.4f}")
    if not gap <= FLASH_TOL:
        raise AssertionError(f"flash and dense models disagree: {gap}")
    log(f"[flash] step time flash {res['warm_step_s'] * 1e3:.1f} ms vs dense "
        f"{dense['warm_step_s'] * 1e3:.1f} ms (smoke timings, not a benchmark)")
    return res


#: the kernel against dense f32 attention on f32 inputs, both at the
#: highest matmul precision: what is left is summation order and the exp
DROPOUT_F32_TOL = 1e-3
DROPOUT_RATE = 0.1


def dense_dropped_attention(q, k, v, kv_mask, keep, rate):
    """Dense f32 attention over ``[B, S, H, D]`` whose probabilities are
    dropped by the ready mask ``keep`` ``[B, H, S, S]``."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (q.shape[-1] ** -0.5)
    s = jnp.where(kv_mask[:, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1) * keep / (1.0 - rate)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def check_dropout_kernel(shape, dtype, seed: int, tol: float):
    """The kernels with attention-probabilities dropout live (non-causal,
    a key-padding mask: BERT's call) at ``(B, S, H, D)`` against the dense
    f32 program under `dropout_keep_mask`'s mask for the same key: output
    and the three gradients. Returns the max abs errors."""
    b, s, h, _ = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v, do = (jax.random.normal(kk, shape, dtype) for kk in keys[:4])
    rng = keys[4]
    kv_mask = jnp.arange(s)[None, :] < (s - 5 * jnp.arange(b)[:, None])
    keep = dropout_keep_mask(rng, b, h, s, s, DROPOUT_RATE)

    flash = jax.jit(functools.partial(
        _with_grads, functools.partial(
            flash_attention, kv_mask=kv_mask, dropout_rng=rng,
            dropout_rate=DROPOUT_RATE)))
    dense = jax.jit(functools.partial(
        _with_grads, functools.partial(
            dense_dropped_attention, kv_mask=kv_mask, keep=keep,
            rate=DROPOUT_RATE)))
    got = flash(q, k, v, do)
    with jax.default_matmul_precision("highest"):
        want = dense(*(x.astype(jnp.float32) for x in (q, k, v, do)))
    errs = _max_abs_errors(
        got, want, tol, "dropout kernel vs dense under the same mask:")
    log(f"[dropout] max abs error vs dense f32 under the same mask at "
        f"{shape} ({jnp.dtype(dtype).name}, tolerance {tol}): "
        + ", ".join(f"{n} {e:.2e}" for n, e in errs.items()))
    return errs


def applied_keep_counts(shape, seed: int):
    """How many probabilities of each row the compiled kernel kept: with
    ``q`` = 0 every probability is 1/S, and with ``v`` = 1 each output row
    reads kept / ((1 - rate) S), so f32 gives the count back exactly.
    Returns (the kernel's counts ``[B, S, H]``, the dense mask's)."""
    b, s, h, _ = shape
    rng = jax.random.PRNGKey(seed)
    zeros, ones = jnp.zeros(shape, jnp.float32), jnp.ones(shape, jnp.float32)
    out = jax.jit(functools.partial(
        flash_attention, dropout_rate=DROPOUT_RATE))(zeros, zeros, ones,
                                                     dropout_rng=rng)
    got = np.rint(np.asarray(out[..., 0], np.float64)
                  * (1.0 - DROPOUT_RATE) * s).astype(np.int64)
    keep = dropout_keep_mask(rng, b, h, s, s, DROPOUT_RATE)
    return got, np.asarray(keep.sum(-1)).transpose(0, 2, 1)


def phase_dropout(shape, seed: int):
    """Attention-probabilities dropout inside the flash kernels, as BERT's
    default core calls them: values and gradients in bf16 and f32 against
    the dense program under the same mask, and the mask the compiled
    kernel really applied (per-row keep counts, and their share)."""
    errs = {jnp.dtype(dtype).name: check_dropout_kernel(shape, dtype, seed,
                                                        tol)
            for dtype, tol in ((jnp.bfloat16, FLASH_TOL),
                               (jnp.float32, DROPOUT_F32_TOL))}
    got, want = applied_keep_counts(shape, seed)
    share = got.sum() / (got.size * shape[1])
    log(f"[dropout] keep share the kernel applied at {shape}: {share:.6f} "
        f"of {got.size * shape[1]} scores (the dense mask: "
        f"{want.sum() / (want.size * shape[1]):.6f}; nominal "
        f"{1.0 - DROPOUT_RATE}); rows whose count differs from the dense "
        f"mask's: {int((got != want).sum())} of {got.size}")
    if not np.array_equal(got, want):
        raise AssertionError(
            "the compiled kernel kept other probabilities than "
            "dropout_keep_mask says")
    return {"errors": errs, "keep_share": float(share)}


def _check_spread(tree, mesh, what: str) -> None:
    for path, x in jax.tree_util.tree_leaves_with_path(tree):
        if len(x.sharding.device_set) != mesh.size:
            raise AssertionError(
                f"{what}{jax.tree_util.keystr(path)} lives on "
                f"{len(x.sharding.device_set)} of {mesh.size} devices")


def phase_dp(mesh, cfg, global_batch: int, seq_len: int, steps: int,
             seed: int, atol: float = DP_ATOL):
    """The data-parallel schedule across the mesh: ``mode='dear'`` and
    ``mode='allreduce'`` from the same weights and batch. Losses must agree
    step for step and fall; the batch and every state buffer must span all
    devices, each device holding 1/world of the dear state. The dear step
    as lowered must ask for reduce-scatter + all-gather where allreduce
    asks for all-reduce only; what the compiler made of them is counted
    and printed (XLA:TPU may rewrite a reduce-scatter as all-reduce +
    slice), and must still hold the parameter all-gather and a gradient
    reduction."""
    world = mesh.size
    loss_fn, params = make_model(cfg, seq_len, seed)
    params = jax.device_get(params)  # host copy: no device holds a full set
    batch = make_batch(mesh, cfg, global_batch, seq_len, seed)
    _check_spread(batch, mesh, "batch")
    out = {}
    for mode in ("dear", "allreduce"):
        ts = build_train_step(
            loss_fn, params, mesh=mesh, mode=mode, threshold_mb=THRESHOLD_MB,
            optimizer=fused_sgd(lr=LR, momentum=MOMENTUM),
            comm_dtype=jnp.bfloat16)
        state = ts.init(params)
        _check_spread((state.buffers, state.opt_state), mesh, f"{mode} state")
        if mode == "dear":
            sharded = [x for x in jax.tree.leaves(
                (state.buffers, state.opt_state)) if x.ndim == 1]
            for x in sharded:
                sizes = {s.data.size for s in x.addressable_shards}
                if sizes != {x.size // world}:
                    raise AssertionError(
                        f"dear buffer of {x.size} elements is held in "
                        f"shards of {sizes}, not 1/{world} per device")
            share = sum(x.nbytes for x in sharded) // world
            in_use = [memory_stat(d, "bytes_in_use")
                      for d in mesh.devices.flat]
            log(f"[dp] dear state: {len(sharded)} buffers, {share} bytes "
                f"per device; bytes_in_use per device: {in_use}")
            if None not in in_use and not all(
                    share <= b < share * world for b in in_use):
                raise AssertionError(
                    f"device memory does not show a 1/{world} share of "
                    f"{share} bytes each: {in_use}")
        lowered, compiled = compile_step(ts, state, batch, f"dp/{mode}")
        asked = lowered.as_text()
        asks = {op: f"stablehlo.{op}" in asked
                for op in ("reduce_scatter", "all_gather", "all_reduce")}
        kept = count_collectives(compiled.as_text())
        log(f"[dp/{mode}] lowered step asks for: "
            f"{[op for op, there in asks.items() if there]}; "
            f"compiled step holds: {kept}")
        decoupled = asks["reduce_scatter"] and asks["all_gather"]
        reduces = kept.get("reduce-scatter", 0) + kept.get("all-reduce", 0)
        if mode == "dear" and not (
                decoupled and kept.get("all-gather") and reduces):
            raise AssertionError(f"dear step lost a leg: {asks} -> {kept}")
        if mode == "allreduce" and (
                decoupled or not asks["all_reduce"]
                or not kept.get("all-reduce")):
            raise AssertionError(f"allreduce step is not one: {asks} -> {kept}")
        _, out[mode], _ = run_steps(ts, state, batch, steps, f"dp/{mode}")
        del state
    diff = float(np.max(np.abs(
        np.asarray(out["dear"]) - np.asarray(out["allreduce"]))))
    log(f"[dp] max |dear - allreduce| over {steps} steps on {world} "
        f"devices: {diff:.2e} (tolerance {atol:.0e})")
    if not diff <= atol:
        raise AssertionError(f"dear and allreduce disagree: {diff} > {atol}")
    out["max_diff"] = diff
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py needs a TPU; JAX reports {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} but JAX reports {len(devices)} device(s)",
              file=sys.stderr)
        return 2
    log(f"device: {dev.device_kind} x{len(devices)}, jax {jax.__version__}")
    t_start = time.perf_counter()
    mesh = backend.init(devices=devices[:args.chips])
    log(f"compilation cache: {jax.config.jax_compilation_cache_dir} "
        f"(enabled: {jax.config.jax_enable_compilation_cache})")
    cfg = gpt2_config(jnp.bfloat16)
    if args.chips == 4:
        phase_dp(mesh, cfg, global_batch=32, seq_len=1024, steps=10,
                 seed=args.seed)
    else:
        train = phase_train(mesh, cfg, batch_size=8, seq_len=1024, steps=10,
                            seed=args.seed)
        if "tpu_custom_call" not in train["text"]:
            raise AssertionError(
                "the default train step compiled without the flash kernel "
                "(models.gpt.flash_core_applies should hold at S=1024)")
        phase_reference(mesh, gpt2_config(jnp.float32, num_layers=2),
                        batch_size=8, seq_len=1024, steps=10, seed=args.seed)
        # the default core is the kernel on a TPU: name the dense program
        dense = phase_train(mesh, cfg, batch_size=8, seq_len=1024, steps=5,
                            seed=args.seed, label="dense",
                            attention_impl=causal_dot_product_attention)
        flash = phase_flash(mesh, cfg, batch_size=8, seq_len=1024, steps=5,
                            seed=args.seed, dense=dense)
        if not flash["kernel_in_program"]:
            raise AssertionError(
                "the flash train step compiled without a tpu_custom_call")
        # BERT-Large's attention at the benchmark cell's shape
        phase_dropout((16, 512, 16, 64), seed=args.seed)
    log(f"total: {time.perf_counter() - t_start:.0f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
