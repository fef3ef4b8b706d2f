"""Every cell's code path at a tiny size on 1 and 4 virtual CPU devices:
resolve the names as `run.py` does, shrink the sizes (tests only: no cell
runs below its published widths), build, check against the plain reference,
warm up and run a window. Rehearsal, not measurement."""

import json
import math
import pathlib

import jax
import pytest

from perfbench import cell as cells
from perfbench import harness, steploop

ROOT = pathlib.Path(__file__).resolve().parents[2]
FILES = sorted((ROOT / "perfbench").glob("configs/*.json"))

TINY = {
    "gpt": {"vocab_size": 203, "n_positions": 32, "n_embd": 32, "n_layer": 2,
            "n_head": 4, "n_inner": 64},
    "bert": {"vocab_size": 203, "max_position_embeddings": 32,
             "hidden_size": 32, "num_hidden_layers": 2,
             "num_attention_heads": 4, "intermediate_size": 64},
}


def tiny_cell(config_name: str, chips: int) -> cells.Cell:
    config = cells.load_json(
        ROOT / "perfbench" / "configs" / f"{config_name}.json")
    config["model"] = {**config["model"], **TINY[config["family"]]}
    traffic = {"seq_len": 16, "batch_per_chip": 2, "chips": chips,
               "mode": "dear", "warmup_steps": 2, "trace_steps": 3,
               "reference": {"layers": 2, "batch_per_chip": 2, "steps": 4}}
    return cells.Cell(
        name=f"{config_name}.tiny", chips=chips, config_name=config_name,
        config=config,
        family=cells.load_py(
            ROOT / "perfbench" / "families" / f"{config['family']}.py"),
        traffic=traffic, end_to_end=(), per_layer=())


@pytest.fixture(params=[1, 4], ids=["1dev", "4dev"])
def mesh(request):
    from dear_pytorch_tpu.comm import backend

    backend.shutdown()
    yield backend.init(devices=jax.devices()[:request.param])
    backend.shutdown()


@pytest.mark.parametrize("config_name", [f.stem for f in FILES])
def test_cell_path_at_tiny_size(config_name, mesh):
    cell = tiny_cell(config_name, mesh.size)
    built = harness.build(cell, mesh, seed=2**31 + 17)
    assert built["tokens_per_step"] == 2 * mesh.size * 16
    assert built["peak_hbm_bytes"] > 0
    reference = harness.reference_check(cell, mesh, seed=2**31 + 17,
                                        atol=1e-4)
    assert reference["ok"], reference
    # the plain model and the program's model agree from the first loss on
    assert reference["max_diff"] < 1e-4
    warm = harness.warm_up(built, 2)
    rec = harness.timed_window(built, seconds=0.2)
    assert harness.losses_ok(built, warm, rec["losses"])
    assert rec["attempted"] == len(rec["done"]) >= 3
    stats = steploop.window_metrics(rec["done"], built["tokens_per_step"],
                                    mesh.size)
    assert stats["tokens_per_s_per_chip"] > 0
    assert stats["step_ms_p95"] >= stats["step_ms_median"] > 0
    counts = harness.count_collectives(built["compiled_text"])
    if mesh.size > 1:
        assert counts.get("all-gather") and (
            counts.get("reduce-scatter") or counts.get("all-reduce")), counts


def test_the_same_seed_gives_the_same_inputs_and_another_seed_others():
    from dear_pytorch_tpu.comm import backend

    backend.shutdown()
    mesh = backend.init(devices=jax.devices()[:1])
    cell = tiny_cell("gpt2-124m", 1)
    a = harness.build(cell, mesh, seed=3_000_000_019)
    b = harness.build(cell, mesh, seed=3_000_000_019)
    c = harness.build(cell, mesh, seed=3_000_000_020)
    same = jax.tree.map(lambda x, y: bool((x == y).all()),
                        (a["batch"], a["state"].buffers),
                        (b["batch"], b["state"].buffers))
    assert all(jax.tree.leaves(same))
    assert not bool((a["batch"]["input_ids"]
                     == c["batch"]["input_ids"]).all())
    backend.shutdown()


# -- the FLOPs functions against hand numbers ---------------------------------

def _model(name):
    return cells.load_json(
        ROOT / "perfbench" / "configs" / f"{name}.json")["model"]


def test_gpt2_124m_flops_per_token_by_hand():
    fam = cells.load_py(ROOT / "perfbench/families/gpt.py")
    got = fam.flops_per_token(_model("gpt2-124m"), 1024)
    by_hand = (6 * 12 * 12 * 768 ** 2          # 12 H^2 per layer, 12 layers
               + 12 * 12 * 1024 * 768           # QK^T and AV
               + 6 * 50257 * 768)               # tied head
    assert got == by_hand
    assert got / 1e9 == pytest.approx(0.854, abs=0.001)
    # round 5's 88,764 tok/s then reads 38.5% of 197 TFLOP/s
    assert 100 * 88764 * got / 197e12 == pytest.approx(38.5, abs=0.1)


def test_bert_large_flops_per_token_by_hand():
    fam = cells.load_py(ROOT / "perfbench/families/bert.py")
    got = fam.flops_per_token(_model("bert-large"), 512)
    by_hand = (6 * 24 * 12 * 1024 ** 2 + 12 * 24 * 512 * 1024
               + 6 * 1024 ** 2 + 6 * 30522 * 1024)
    assert got == by_hand
    assert got / 1e9 == pytest.approx(2.157, abs=0.001)


# -- names resolve to files, and nothing else is needed -----------------------

def test_every_name_in_benchmark_json_resolves_to_a_file():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = cells.resolve(w["name"], bench)
        assert cell.chips == cell.traffic["chips"] == w["chips"]
        for m in cell.per_layer:
            assert callable(cells.layer_reader(m["name"]))
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert all(m["moves"] in names for m in cell.per_layer)
    for c in bench["configs"]:
        on_disk = cells.load_json(ROOT / c["file"])
        assert on_disk["reduced"] == c["reduced"]
        assert set(on_disk["changed"]) == set(c["reduced"])


def test_an_unknown_device_is_an_error():
    assert cells.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        cells.peaks("cpu")


def test_seed_key_takes_seeds_past_32_signed_bits():
    a, b = harness.seed_key(2**31 + 5, 0), harness.seed_key(5, 0)
    assert not bool((jax.random.key_data(a) == jax.random.key_data(b)).all())
    assert math.isfinite(float(jax.random.uniform(a)))
