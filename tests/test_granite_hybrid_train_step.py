"""The Mamba-2 hybrid through the program's normal path at a tiny size on
the CPU mesh: `build_train_step(mode="dear")` + `FusionPlan` + `fused_sgd`
at world 1 and world 4, with and without the blocks' recomputation, against
the hand-written momentum-SGD loop over the plain reference
(`perfbench.harness.reference_check`), and the command-line driver the
decoders share (`benchmarks/glm.py`)."""

import dataclasses
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import cell as cells
from perfbench import harness
from test_granite_hybrid import TINY

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = "granite-4.0-h-micro-vp4"
FAMILY = ROOT / "perfbench/families/granite_hybrid.py"


def tiny_cell(chips: int, remat: bool) -> cells.Cell:
    config = cells.load_json(ROOT / "perfbench/configs" / f"{CONFIG}.json")
    config["model"] = {**config["model"], **TINY, "remat": remat}
    # the reference depth of the cell: one Mamba block and the attention one
    traffic = {"seq_len": 16, "batch_per_chip": 2, "chips": chips,
               "mode": "dear", "warmup_steps": 2, "trace_steps": 3,
               "reference": {"layers": 2, "batch_per_chip": 2, "steps": 4}}
    return cells.Cell(
        name=f"{CONFIG}.tiny", chips=chips, config_name=CONFIG,
        config=config, family=cells.load_py(FAMILY),
        traffic=traffic, end_to_end=(), per_layer=())


@pytest.fixture(params=[1, 4], ids=["1dev", "4dev"])
def dp_mesh(request):
    from dear_pytorch_tpu.comm import backend

    backend.shutdown()
    yield backend.init(devices=jax.devices()[:request.param])
    backend.shutdown()


@pytest.mark.parametrize("remat", [False, True], ids=["stored", "remat"])
def test_dear_step_equals_the_plain_sgd_loop(dp_mesh, remat):
    cell = tiny_cell(dp_mesh.size, remat)
    reference = harness.reference_check(cell, dp_mesh, seed=2**31 + 23,
                                        atol=1e-4)
    assert reference["ok"], reference
    assert reference["max_diff"] < 1e-4
    # the loss moves: the comparison is of four different numbers
    assert len({round(x, 4) for x in reference["plain"]}) == 4


def test_recomputation_changes_no_loss_and_keeps_less():
    """The recomputed blocks give the stored blocks' losses and gradients
    (the same operations, run twice: float32 rounding apart), and their
    backward pass keeps fewer bytes: no `[chunk, chunk]` matrix either
    way, and with recomputation nothing elementwise."""
    fam = cells.load_py(FAMILY)
    grads, kept = {}, {}
    for remat in (False, True):
        cfg = fam.model_config({**TINY, "remat": remat}, jnp.float32)
        init_fn, loss_fn = fam.make_loss(cfg, False)
        params = init_fn(jax.random.PRNGKey(0), 16)
        batch = fam.make_batch(TINY, jax.random.PRNGKey(1), 2, 16)
        grads[remat] = jax.jit(jax.value_and_grad(loss_fn))(params, batch)
        _, vjp = jax.vjp(lambda p: loss_fn(p, batch), params)
        # (the chunk matrices and the carried states have six dimensions)
        assert all(r.ndim <= 4 for r in jax.tree.leaves(vjp))
        kept[remat] = sum(r.size for r in jax.tree.leaves(vjp))
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        a, b, rtol=0, atol=1e-5 * float(jnp.abs(b).max())),
        grads[False], grads[True])
    assert kept[True] < 0.6 * kept[False]


@pytest.mark.parametrize("remat", [False, True], ids=["stored", "remat"])
def test_cell_path_at_tiny_size(dp_mesh, remat):
    cell = tiny_cell(dp_mesh.size, remat)
    built = harness.build(cell, dp_mesh, seed=3_000_000_019)
    assert built["tokens_per_step"] == 2 * dp_mesh.size * 16
    assert built["peak_hbm_bytes"] > 0 and built["flops_per_step"] > 0
    warm = harness.warm_up(built, 2)
    rec = harness.timed_window(built, seconds=0.2)
    # (the first loss sits near the family's `initial_loss` at the published
    # widths only: the band is checked on the chip)
    assert all(x == x and abs(x) < 1e3 for x in warm + rec["losses"])
    assert rec["attempted"] == len(rec["done"]) >= 3
    counts = harness.count_collectives(built["compiled_text"])
    if dp_mesh.size > 1:
        assert counts.get("all-gather") and (
            counts.get("reduce-scatter") or counts.get("all-reduce")), counts
    # the named scopes the three readers join on are in the compiled step
    text = built["compiled_text"]
    for scope in ("mamba/in_proj", "mamba/conv1d", "mamba/ssd",
                  "mamba/gate_norm", "mamba/out_proj"):
        assert re.search(rf'op_name="[^"]*jvp\([^"]*{scope}', text), scope
        assert re.search(rf'op_name="[^"]*transpose\(jvp\([^"]*{scope}',
                         text), scope


def test_the_shared_cli_runs_the_hybrid(mesh, capsys):
    from dear_pytorch_tpu.benchmarks import glm as glm_cli

    res = glm_cli.main(
        ["--model", "granite_hybrid_tiny", "--sequence-len", "32",
         "--batch-size", "2", "--remat", "--num-warmup-batches", "1",
         "--num-batches-per-iter", "2", "--num-iters", "2"])
    out = capsys.readouterr().out
    assert re.search(r"Total sen/sec on 8 \w+\(s\): [\d.]+ \+-[\d.]+", out), out
    assert ("layers mamba, attention, mamba, no routed experts, 96 ids"
            in out)
    assert "Expert layer" not in out
    assert res.total_mean > 0


def test_the_cli_flags_cut_the_benchmark_cells_share():
    from dear_pytorch_tpu import models
    from dear_pytorch_tpu.benchmarks import glm as glm_cli

    parse = glm_cli.build_parser().parse_args
    args = parse(["--model", "granite_4_0_h_micro", "--num-layers", "10",
                  "--vocab-size", "25088", "--remat"])
    cfg = glm_cli.config_from_args(args, jnp.bfloat16)
    fam = cells.load_py(FAMILY)
    model = cells.load_json(
        ROOT / "perfbench/configs" / f"{CONFIG}.json")["model"]
    # the command line reaches the configuration the benchmark runs
    assert cfg == fam.model_config(model, jnp.bfloat16)
    assert cfg.remat and cfg.vocab_size == 25088
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",) + (
        "mamba",) * 4
    whole = glm_cli.config_from_args(
        parse(["--model", "granite_4_0_h_micro"]), jnp.float32)
    assert whole == models.GRANITE_4_0_H_MICRO
    # the next pipeline stage holds the same ten kinds in the same order
    second = glm_cli.config_from_args(
        parse(["--model", "granite_4_0_h_micro", "--first-layer", "10",
               "--num-layers", "10"]), jnp.float32)
    assert second.layer_types == cfg.layer_types
    assert dataclasses.replace(second, vocab_size=25088, remat=True,
                               dtype=jnp.bfloat16) == cfg
    with pytest.raises(ValueError, match="no routed experts"):
        glm_cli.config_from_args(
            parse(["--model", "granite_4_0_h_micro", "--experts-held", "8"]),
            jnp.float32)
    with pytest.raises(ValueError, match="hybrid families'"):
        glm_cli.config_from_args(parse(["--first-layer", "1"]), jnp.float32)
