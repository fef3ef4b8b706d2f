"""The sparse decoder through the program's normal path at a tiny size on
the CPU mesh: `build_train_step(mode="dear")` + `FusionPlan` + `fused_sgd` at
world 1 and world 4 against the plain SGD loop over the plain reference
(`perfbench.harness.reference_check`, perfbench/tests/test_cells_tiny.py's
pattern), and the `benchmarks/glm.py` command-line driver."""

import pathlib
import re

import jax
import pytest

from perfbench import cell as cells
from perfbench import harness
from test_glm_moe import TINY

ROOT = pathlib.Path(__file__).resolve().parents[1]
CONFIG = "glm-4.7-flash-ep8"


def tiny_cell(chips: int) -> cells.Cell:
    config = cells.load_json(ROOT / "perfbench/configs" / f"{CONFIG}.json")
    config["model"] = {**config["model"], **TINY}
    traffic = {"seq_len": 16, "batch_per_chip": 2, "chips": chips,
               "mode": "dear", "warmup_steps": 2, "trace_steps": 3,
               "reference": {"layers": 2, "batch_per_chip": 2, "steps": 4}}
    return cells.Cell(
        name=f"{CONFIG}.tiny", chips=chips, config_name=CONFIG,
        config=config,
        family=cells.load_py(ROOT / "perfbench/families/glm_moe.py"),
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


def test_glm_cli_output_contract(mesh, capsys):
    from dear_pytorch_tpu.benchmarks import glm as glm_cli
    from dear_pytorch_tpu.observability import tracer as T

    T.configure()
    try:
        res = glm_cli.main(
            ["--model", "glm_moe_tiny", "--sequence-len", "32",
             "--batch-size", "2", "--experts-held", "4", "--expert-offset",
             "8", "--num-warmup-batches", "1", "--num-batches-per-iter",
             "2", "--num-iters", "2"])
        counters = T.get_tracer().counters()
    finally:
        T.disable()
    out = capsys.readouterr().out
    assert re.search(r"Total sen/sec on 8 \w+\(s\): [\d.]+ \+-[\d.]+", out), out
    assert "experts [8, 12) of 16" in out
    assert re.search(r"Expert layer 1: assignments per held expert \[.*\], "
                     r"max/mean [\d.]+", out), out
    assert res.total_mean > 0
    # the routing counter reached the tracer: one expert layer + the module
    mine = {k: v for k, v in counters.items() if k.startswith("moe.layer")}
    assert len(mine) == 2 * 4 and sum(mine.values()) > 0


def test_glm_cli_flags_shape_the_config():
    import jax.numpy as jnp

    from dear_pytorch_tpu.benchmarks import glm as glm_cli

    args = glm_cli.build_parser().parse_args(
        ["--num-layers", "5", "--experts-held", "8", "--vocab-size", "19360",
         "--remat", "--no-mtp"])
    cfg = glm_cli.config_from_args(args, jnp.bfloat16)
    assert (cfg.num_layers, cfg.experts_held, cfg.vocab_size) == (5, 8, 19360)
    assert cfg.n_routed_experts == 64 and cfg.hidden_size == 2048
    assert cfg.remat and cfg.num_nextn_predict_layers == 0
    assert cfg.dtype == jnp.bfloat16
    default = glm_cli.config_from_args(
        glm_cli.build_parser().parse_args([]), jnp.float32)
    assert default.num_layers == 47 and default.experts_held is None
