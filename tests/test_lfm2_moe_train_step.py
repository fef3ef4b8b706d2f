"""The short-convolution hybrid through the program's normal path at a tiny
size on the CPU mesh: `build_train_step(mode="dear")` + `FusionPlan` +
`fused_sgd` at world 1 and world 4 against the plain SGD loop over the plain
reference (`perfbench.harness.reference_check`), and the one command-line
driver both sparse decoders share (`benchmarks/glm.py`)."""

import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from perfbench import cell as cells
from perfbench import harness
from test_lfm2_moe import TINY

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = "lfm2-8b-a1b-ep4"


def tiny_cell(chips: int) -> cells.Cell:
    config = cells.load_json(ROOT / "perfbench/configs" / f"{CONFIG}.json")
    config["model"] = {**config["model"], **TINY}
    # the reference depth of the cell: every kind of layer once
    traffic = {"seq_len": 16, "batch_per_chip": 2, "chips": chips,
               "mode": "dear", "warmup_steps": 2, "trace_steps": 3,
               "reference": {"layers": 3, "batch_per_chip": 2, "steps": 4}}
    return cells.Cell(
        name=f"{CONFIG}.tiny", chips=chips, config_name=CONFIG,
        config=config,
        family=cells.load_py(ROOT / "perfbench/families/lfm2_moe.py"),
        traffic=traffic, end_to_end=(), per_layer=())


@pytest.fixture(params=[1, 4], ids=["1dev", "4dev"])
def dp_mesh(request):
    from dear_pytorch_tpu.comm import backend

    backend.shutdown()
    yield backend.init(devices=jax.devices()[:request.param])
    backend.shutdown()


def test_dear_step_equals_the_plain_sgd_loop(dp_mesh):
    cell = tiny_cell(dp_mesh.size)
    reference = harness.reference_check(cell, dp_mesh, seed=2**31 + 23,
                                        atol=1e-4)
    assert reference["ok"], reference
    assert reference["max_diff"] < 1e-4
    # the loss moves: the comparison is of four different numbers
    assert len({round(x, 4) for x in reference["plain"]}) == 4


def test_cell_path_at_tiny_size(dp_mesh):
    cell = tiny_cell(dp_mesh.size)
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


def test_the_shared_cli_runs_the_hybrid(mesh, capsys):
    from dear_pytorch_tpu.benchmarks import glm as glm_cli

    res = glm_cli.main(
        ["--model", "lfm2_moe_tiny", "--sequence-len", "32", "--batch-size",
         "2", "--experts-held", "4", "--expert-offset", "8",
         "--num-warmup-batches", "1", "--num-batches-per-iter", "2",
         "--num-iters", "2"])
    out = capsys.readouterr().out
    assert re.search(r"Total sen/sec on 8 \w+\(s\): [\d.]+ \+-[\d.]+", out), out
    assert ("layers conv, full_attention, conv, conv, conv, experts [8, 12) "
            "of 16, 96 ids") in out
    # four expert layers (layer 0 is dense), four held experts each
    assert len(re.findall(r"Expert layer \d: assignments per held expert "
                          r"\[\d+, \d+, \d+, \d+\]", out)) == 4
    assert res.total_mean > 0


def test_the_cli_flags_cut_the_benchmark_cells_share():
    from dear_pytorch_tpu import models
    from dear_pytorch_tpu.benchmarks import glm as glm_cli

    args = glm_cli.build_parser().parse_args(
        ["--model", "lfm2_8b_a1b", "--first-layer", "1", "--num-layers", "5",
         "--experts-held", "8", "--vocab-size", "16384", "--remat"])
    cfg = glm_cli.config_from_args(args, jnp.bfloat16)
    fam = cells.load_py(ROOT / "perfbench/families/lfm2_moe.py")
    model = cells.load_json(
        ROOT / "perfbench/configs" / f"{CONFIG}.json")["model"]
    # the command line reaches the configuration the benchmark runs
    import dataclasses
    assert cfg == dataclasses.replace(fam.model_config(model, jnp.bfloat16),
                                      remat=True)
    assert cfg.layer_types == ("conv", "full_attention", "conv", "conv",
                               "conv") and cfg.num_dense_layers == 1
    whole = glm_cli.config_from_args(glm_cli.build_parser().parse_args(
        ["--model", "lfm2_8b_a1b"]), jnp.float32)
    assert whole == models.LFM2_8B_A1B
    # layers 6-8: past the dense layers
    late = glm_cli.config_from_args(glm_cli.build_parser().parse_args(
        ["--model", "lfm2_8b_a1b", "--first-layer", "6", "--num-layers",
         "3"]), jnp.float32)
    assert late.layer_types == ("full_attention", "conv", "conv")
    assert late.num_dense_layers == 0 and late.num_hidden_layers == 3
    with pytest.raises(ValueError, match="hybrid families'"):
        glm_cli.config_from_args(glm_cli.build_parser().parse_args(
            ["--first-layer", "1"]), jnp.float32)
