"""The delta-rule hybrid through the program's normal path at a tiny size on
the CPU mesh: `build_train_step(mode="dear")` + `FusionPlan` + `fused_sgd`,
the blocks recomputed as in the cell, against the hand-written
momentum-SGD loop over the plain reference
(`perfbench.harness.reference_check`), and the command-line driver the
decoders share (`benchmarks/glm.py`)."""

import dataclasses
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest

from perfbench import cell as cells
from perfbench import harness
from test_ling_hybrid import TINY

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = "ling-3.0-flash-ep64"
FAMILY = ROOT / "perfbench/families/ling_hybrid.py"


def tiny_cell(layers: int) -> cells.Cell:
    config = cells.load_json(ROOT / "perfbench/configs" / f"{CONFIG}.json")
    config["model"] = {**config["model"], **TINY, "remat": True}
    traffic = {"seq_len": 16, "batch_per_chip": 2, "chips": 1,
               "mode": "dear", "warmup_steps": 2, "trace_steps": 3,
               "reference": {"layers": layers, "batch_per_chip": 2,
                             "steps": 4}}
    return cells.Cell(
        name=f"{CONFIG}.tiny", chips=1, config_name=CONFIG,
        config=config, family=cells.load_py(FAMILY),
        traffic=traffic, end_to_end=(), per_layer=())


@pytest.fixture
def one_device():
    from dear_pytorch_tpu.comm import backend

    backend.shutdown()
    yield backend.init(devices=jax.devices()[:1])
    backend.shutdown()


@pytest.mark.parametrize("layers", [3, 1],
                         ids=["whole", "the_cells_reference_depth"])
def test_dear_step_equals_the_plain_sgd_loop(one_device, layers):
    """The whole tiny model (both mixers, the experts, the prediction
    module), and the cell's one-layer reference depth (a KDA expert
    layer: the router and the held experts)."""
    cell = tiny_cell(layers)
    reference = harness.reference_check(cell, one_device, seed=2**31 + 43,
                                        atol=1e-4)
    assert reference["ok"], reference
    assert reference["max_diff"] < 1e-4
    # the loss moves: the comparison is of four different numbers
    assert len({round(x, 4) for x in reference["plain"]}) == 4


def test_cell_path_at_tiny_size(one_device):
    cell = tiny_cell(3)
    built = harness.build(cell, one_device, seed=3_000_000_043)
    assert built["tokens_per_step"] == 32
    assert built["peak_hbm_bytes"] > 0 and built["flops_per_step"] > 0
    warm = harness.warm_up(built, 2)
    rec = harness.timed_window(built, seconds=0.2)
    assert all(x == x and abs(x) < 1e3 for x in warm + rec["losses"])
    assert rec["attempted"] == len(rec["done"]) >= 3
    # the named scopes the three readers and the accepted ones join on are
    # in the compiled step, forward and backward
    text = built["compiled_text"]
    for scope in ("kda/in_proj", "kda/conv", "kda/gate", "kda/scan",
                  "kda/out_norm_gate", "kda/out_proj", "moe/route",
                  "moe/experts", "mtp/"):
        assert re.search(rf'op_name="[^"]*jvp\([^"]*{scope}', text), scope
        assert re.search(rf'op_name="[^"]*transpose\(jvp\([^"]*{scope}',
                         text), scope


def test_the_shared_cli_runs_the_hybrid(mesh, capsys):
    from dear_pytorch_tpu.benchmarks import glm as glm_cli

    res = glm_cli.main(
        ["--model", "ling_hybrid_tiny", "--sequence-len", "32",
         "--batch-size", "2", "--remat", "--num-warmup-batches", "1",
         "--num-batches-per-iter", "2", "--num-iters", "2"])
    out = capsys.readouterr().out
    assert re.search(r"Total sen/sec on 8 \w+\(s\): [\d.]+ \+-[\d.]+", out), out
    assert "layers kda, kda, mla, experts [0, 16) of 16, 96 ids" in out
    assert out.count("Expert layer") == 3
    assert res.total_mean > 0


def test_the_cli_flags_cut_the_benchmark_cells_share():
    from dear_pytorch_tpu import models
    from dear_pytorch_tpu.benchmarks import glm as glm_cli

    parse = glm_cli.build_parser().parse_args
    args = parse(["--model", "ling_3_0_flash", "--first-layer", "1",
                  "--num-layers", "7", "--experts-held", "8",
                  "--vocab-size", "19648", "--remat"])
    cfg = glm_cli.config_from_args(args, jnp.bfloat16)
    fam = cells.load_py(FAMILY)
    model = cells.load_json(
        ROOT / "perfbench/configs" / f"{CONFIG}.json")["model"]
    # the command line reaches the configuration the benchmark runs
    assert cfg == fam.model_config(model, jnp.bfloat16)
    assert cfg.layer_types == ("kda",) * 4 + ("mla",) + ("kda",) * 2
    assert (cfg.first_layer, cfg.first_k_dense_replace) == (1, 1)
    whole = glm_cli.config_from_args(parse(["--model", "ling_3_0_flash"]),
                                     jnp.float32)
    assert whole == models.LING_3_0_FLASH
    # a stage of the last layers holds the clamped SwiGLUs: refused
    last = glm_cli.config_from_args(
        parse(["--model", "ling_3_0_flash", "--first-layer", "36"]),
        jnp.float32)
    assert last.first_layer == 36 and len(last.layer_types) == 6
    with pytest.raises(ValueError, match="clamp"):
        last.refuse_clamps()
    no_mtp = glm_cli.config_from_args(
        parse(["--model", "ling_3_0_flash", "--no-mtp"]), jnp.float32)
    assert no_mtp == dataclasses.replace(models.LING_3_0_FLASH,
                                         num_nextn_predict_layers=0)
