"""Build a cell's train step through the program's public entry points, check
it against the plain reference, and measure it. Called by `run.py` on the
chip and by perfbench/tests at tiny sizes on the CPU.

From the program the benchmark takes only the system under test:
`backend.init`, `build_train_step`, `fused_sgd`, `runner.stage_global`, and
the model and loss named by the family file. Timing, traffic, FLOPs, peaks,
the trace reduction and the comparison that decides ``correct`` are here.
"""

from __future__ import annotations

import math
import re
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import steploop, xplane
from perfbench.cell import Cell

#: Per-step |loss difference| allowed between ``mode="dear"`` through the
#: program's model and the plain loop through the plain reference model.
#: Both sides are float32 under `jax.default_matmul_precision("highest")`,
#: so only summation order differs: the chip read 6.2e-5 at default
#: precision on losses near 10.9 (PERF.md, PR 24). bf16 compute moves these
#: losses by 1e-2 and more, so 1e-3 still fails a lower precision than stated.
REFERENCE_ATOL = 1e-3
#: The first loss of a freshly initialised model at its stated precision
#: lies this close to the family's `initial_loss` (ln vocab): a gross error
#: in the timed bf16 program, which the f32 reference check does not run.
INITIAL_LOSS_BAND = 0.5
#: host spans the traced run writes, and `xplane.load` keeps
HOST_SPANS = ("dispatch", "wait")

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32, None: None}


def log(msg: str) -> None:
    print(msg, flush=True)


def seed_key(seed: int, stream: int):
    """A key from any whole-number seed (also past 2**31) and a stream
    number: weights and the batch draw from different streams."""
    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                             seed >> 31)
    return jax.random.fold_in(key, stream)


def train_step(cell: Cell, loss_fn, params, mesh, *, dropout_seed,
                comm_dtype):
    from dear_pytorch_tpu.ops.fused_sgd import fused_sgd
    from dear_pytorch_tpu.parallel import build_train_step

    train = cell.config["train"]
    if train["optimizer"] != "fused_sgd":
        raise ValueError(f"unknown optimizer {train['optimizer']!r}")
    return build_train_step(
        loss_fn, params, mesh=mesh, mode=cell.traffic["mode"],
        threshold_mb=train["threshold_mb"],
        optimizer=fused_sgd(lr=train["lr"], momentum=train["momentum"]),
        comm_dtype=comm_dtype,
        rng_seed=dropout_seed)


def _seeded_batch(fam, model: dict, seed: int, batch_size: int, seq: int):
    """The batch, made on the device in one jitted call from the seed."""
    return jax.jit(lambda key: fam.make_batch(model, key, batch_size, seq))(
        seed_key(seed, 1))


def _place(batch, mesh):
    from dear_pytorch_tpu.benchmarks import runner
    from dear_pytorch_tpu.comm.backend import DP_AXIS

    return runner.stage_global(
        batch, jax.sharding.NamedSharding(mesh, jax.P(DP_AXIS)))


def peak_hbm_bytes(compiled) -> float:
    """The compiled step's static allocation on one device, from XLA's
    memory analysis: argument + output + temp + generated code, less aliased
    bytes (the formula of `utils/perf_model.peak_hbm_bytes`, copied). On
    this runtime `memory_stats()["peak_bytes_in_use"]` leaves a running
    program's temporaries out (PERF.md, PR 24), so this is the peak."""
    m = compiled.memory_analysis()
    return float(m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes + m.generated_code_size_in_bytes
                 - m.alias_size_in_bytes)


def count_collectives(compiled_text: str) -> dict:
    """Collective instructions in optimized HLO text, by opcode (async
    ``-start`` forms counted under their base name; copied from
    chip_smoke.py)."""
    ops = re.findall(
        r" (all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(", compiled_text)
    return {op: ops.count(op) for op in sorted(set(ops))}


# -- correctness -------------------------------------------------------------

def reference_check(cell: Cell, mesh, seed: int,
                    atol: float = REFERENCE_ATOL) -> dict:
    """Delayed update == SGD, and the program's model == the published
    mathematics: the cell's ``mode`` through the program's model against a
    plain loop (`jax.value_and_grad` of the family's plain reference loss,
    torch-semantics momentum SGD, no framework code), from the same seeded
    weights and batch, at the configuration's full widths and the traffic
    file's reference depth, float32, dropout zeroed, matmul precision
    "highest" on both sides, step for step."""
    ref = cell.traffic["reference"]
    fam, model = cell.family, cell.config["model"]
    train = cell.config["train"]
    lr, momentum = train["lr"], train["momentum"]
    seq = cell.traffic["seq_len"]
    batch_size = ref["batch_per_chip"] * mesh.size
    steps = ref["steps"]
    cfg = fam.model_config(model, jnp.float32, num_layers=ref["layers"],
                           dropout=False)
    init_fn, loss_fn = fam.make_loss(cfg, with_rng=False)
    shard_loss = fam.reference_loss(model, ref["layers"])
    world = mesh.size

    def plain_loss(p, batch):
        # what a data-parallel job computes: the mean over the workers of
        # each worker's loss on its equal slice of the global batch (for a
        # loss normalised by a count that varies by slice, as BERT's MLM
        # term is, that is not the loss of the whole batch)
        per = batch_size // world
        return sum(shard_loss(p, jax.tree.map(
            lambda x: x[i * per:(i + 1) * per], batch))
            for i in range(world)) / world

    with jax.default_matmul_precision("highest"):
        params = jax.jit(init_fn, static_argnums=1)(seed_key(seed, 0), seq)
        batch = _seeded_batch(fam, model, seed, batch_size, seq)

        @jax.jit
        def plain_step(p, buf, batch, first):
            loss, grads = jax.value_and_grad(plain_loss)(p, batch)
            buf = jax.tree.map(
                lambda b, g: jnp.where(first, g, momentum * b + g),
                buf, grads)
            return jax.tree.map(lambda w, b: w - lr * b, p, buf), buf, loss

        p, buf = params, jax.tree.map(jnp.zeros_like, params)
        plain = []
        for i in range(steps):
            p, buf, loss = plain_step(p, buf, batch, i == 0)
            plain.append(float(loss))
        del p, buf

        ts = train_step(cell, loss_fn, params, mesh, dropout_seed=None,
                        comm_dtype=None)
        state = ts.init(params)
        del params
        placed = _place(batch, mesh)
        system = []
        for _ in range(steps):
            state, metrics = ts.step(state, placed)
            system.append(float(metrics["loss"]))
        del state
    diff = float(np.max(np.abs(np.asarray(system) - np.asarray(plain))))
    ok = bool(diff <= atol) and all(map(math.isfinite, system + plain))
    log(f"[reference] {ref['layers']} layers f32, batch {batch_size}, "
        f"S={seq}: plain  " + " ".join(f"{x:.5f}" for x in plain))
    log(f"[reference] {cell.traffic['mode']} on {mesh.size} device(s):"
        "        " + " ".join(f"{x:.5f}" for x in system))
    log(f"[reference] max |system - plain| over {steps} steps: {diff:.2e} "
        f"(tolerance {atol:.0e}) -> {'ok' if ok else 'FAILED'}")
    return {"ok": ok, "max_diff": diff, "plain": plain, "system": system}


# -- the measured program ----------------------------------------------------

def build(cell: Cell, mesh, seed: int) -> dict:
    """Weights and batch on the device from the seed, the train step, its
    state, and the step compiled ahead of time (the same cache entry
    ``ts.step`` then runs)."""
    fam, model = cell.family, cell.config["model"]
    train = cell.config["train"]
    seq = cell.traffic["seq_len"]
    batch_size = cell.traffic["batch_per_chip"] * mesh.size
    # a constant of the step program (see the config's `assumed`), so it
    # cannot follow --seed without a compile in every run
    dropout_seed = train["dropout_seed"]
    spans = {}

    t = time.perf_counter()
    cfg = fam.model_config(model, DTYPES[train["compute_dtype"]])
    init_fn, loss_fn = fam.make_loss(cfg, with_rng=dropout_seed is not None)
    params = jax.jit(init_fn, static_argnums=1)(seed_key(seed, 0), seq)
    batch = _seeded_batch(fam, model, seed, batch_size, seq)
    batch = _place(batch, mesh)
    jax.block_until_ready((params, batch))
    spans["weights_and_batch_s"] = time.perf_counter() - t

    t = time.perf_counter()
    ts = train_step(cell, loss_fn, params, mesh, dropout_seed=dropout_seed,
                    comm_dtype=DTYPES[train["comm_dtype"]])
    state = ts.init(params)
    del params
    jax.block_until_ready(state)
    spans["build_and_init_state_s"] = time.perf_counter() - t

    t = time.perf_counter()
    lowered = ts.lower(state, batch)
    spans["lower_s"] = time.perf_counter() - t
    t = time.perf_counter()
    compiled = lowered.compile()
    spans["compile_s"] = time.perf_counter() - t
    nparams = sum(b.size for b in ts.plan.buckets)
    log(f"[build] {cell.config_name}: {nparams / 1e6:.1f}M parameters in "
        f"{ts.plan.num_buckets} bucket(s), S={seq}, global batch "
        f"{batch_size} on {mesh.size} device(s), mode "
        f"{cell.traffic['mode']}")
    log("[build] " + ", ".join(f"{k} {v:.2f}" for k, v in spans.items()))
    tokens = fam.tokens_per_step(batch_size, seq)
    return {
        "ts": ts, "state": state, "batch": batch, "compiled": compiled,
        "compiled_text": compiled.as_text(), "spans": spans,
        "tokens_per_step": tokens,
        "flops_per_step": fam.flops_per_token(model, seq) * tokens,
        "peak_hbm_bytes": peak_hbm_bytes(compiled),
        "initial_loss": fam.initial_loss(model),
    }


def _wait(loss_handle) -> float:
    return float(jax.block_until_ready(loss_handle))


def _step_of(ts):
    def step(state, batch):
        state, metrics = ts.step(state, batch)
        return state, metrics["loss"]
    return step


def warm_up(built: dict, steps: int) -> list:
    """Run ``steps`` steps outside the window, each loss fetched."""
    step, losses = _step_of(built["ts"]), []
    for _ in range(steps):
        built["state"], handle = step(built["state"], built["batch"])
        losses.append(_wait(handle))
    log("[warm-up] losses: " + " ".join(f"{x:.4f}" for x in losses))
    return losses


def timed_window(built: dict, seconds: float) -> dict:
    """The measured window: steps for ``seconds`` seconds, one in flight."""
    built["state"], rec = steploop.one_in_flight(
        _step_of(built["ts"]), _wait, built["state"], built["batch"],
        stop=lambda n, now, t0: now - t0 >= seconds and n >= 3)
    return rec


def traced_stretch(built: dict, steps: int, trace_dir=None) -> tuple:
    """A short stretch under `jax.profiler`, the loop's dispatch and wait
    wrapped in `TraceAnnotation`s; returns ``(the loop's record, the reduced
    trace)``. The raw trace goes to ``trace_dir`` and stays there, or to a
    temporary directory that is removed."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # the harness's own spans are enough
    opts.host_tracer_level = 2
    keep = trace_dir is not None
    trace_dir = trace_dir or tempfile.mkdtemp(prefix="perfbench_trace_")
    try:
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            built["state"], rec = steploop.one_in_flight(
                _step_of(built["ts"]), _wait, built["state"], built["batch"],
                stop=lambda n, now, t0: n >= steps,
                span=jax.profiler.TraceAnnotation)
        finally:
            jax.profiler.stop_trace()
        trace = xplane.load(xplane.find_xplane(trace_dir), HOST_SPANS)
    finally:
        if not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return rec, trace


def losses_ok(built: dict, warm: list, window: list) -> bool:
    """Every loss finite, and the first within `INITIAL_LOSS_BAND` of the
    family's initial loss."""
    finite = all(map(math.isfinite, warm + window))
    near = abs(warm[0] - built["initial_loss"]) <= INITIAL_LOSS_BAND
    if not near:
        log(f"[correct] first loss {warm[0]:.4f} is not within "
            f"{INITIAL_LOSS_BAND} of {built['initial_loss']:.4f}")
    return finite and near
