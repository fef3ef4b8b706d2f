"""The Mamba-2 hybrid's named scopes as `perfbench/scopes.py` (unedited)
books them, the family's work functions against hand counts, each new
per-layer reader on a made-up run, the configuration file against the
catalog's row, and the benchmark's new entries as `perfbench/cell.py`
resolves them."""

import json
import pathlib

import jax
import jax.numpy as jnp
import pytest

from dear_pytorch_tpu import models
from perfbench import cell as cells
from perfbench import moe_scopes, scopes, xplane

ROOT = pathlib.Path(__file__).resolve().parents[1]
FAM = cells.load_py(ROOT / "perfbench" / "families" / "granite_hybrid.py")
CONFIG = "granite-4.0-h-micro-vp4"
CELL = f"{CONFIG}.s4096x1"
F = "jit(device_step)/shard_map/jvp(GraniteHybridLmHeadModel)/"
B = "jit(device_step)/shard_map/transpose(jvp(GraniteHybridLmHeadModel))/"
R = B + "h_4/checkpoint/rematted_computation/"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("op_name,want", [
    (F + "h_0/ln_1/mul", ("forward", "layernorm")),
    (F + "h_5/query/q_proj/dot_general", ("forward", "projections")),
    (F + "h_5/query/mul", ("forward", "projections")),
    (B + "h_5/key/k_proj/dot_general", ("backward", "projections")),
    (B + "h_5/value/v_proj/dot_general", ("backward", "projections")),
    (F + "h_5/output/dot_general", ("forward", "projections")),
    (F + "h_5/attention/pallas_call", ("forward", "attention")),
    (B + "h_5/attention/pallas_call", ("backward", "attention")),
    (F + "h_0/mlp/mlp_gate/dot_general", ("forward", "mlp")),
    (B + "h_0/mlp/mlp_down/dot_general", ("backward", "mlp")),
    (F + "ln_f/mul", ("forward", "layernorm")),
    (F + "loss/wte.attend/dot_general", ("forward", "loss")),
    (B.replace("GraniteHybridLmHeadModel", "loss") + "reduce_sum",
     ("backward", "loss")),
    (F + "wte/take", ("forward", "embedding")),
    # `_PARTS` has no part for the state-space mixer: it is booked as
    # unattributed (PERF.md section 7 asks a later PR for the part); a
    # recomputed block's forward work runs in the backward pass
    (F + "h_1/mamba/in_proj/dot_general", ("forward", scopes.UNATTRIBUTED)),
    (R + "mamba/conv1d/mul", ("backward", scopes.UNATTRIBUTED)),
    (B + "h_1/mamba/ssd/checkpoint/rematted_computation/exp",
     ("backward", scopes.UNATTRIBUTED)),
    (B + "h_1/mamba/out_proj/dot_general", ("backward", scopes.UNATTRIBUTED)),
])
def test_the_unedited_scope_table_books_the_new_program(op_name, want):
    assert scopes.classify(op_name) == want


READERS = {name: cells.layer_reader(name) for name in (
    "mamba_mixer_ms", "ssd_scan_ms", "ssd_scan_roofline_pct")}
MAMBA = cells.load_py(ROOT / "perfbench/layer_metrics/mamba_mixer_ms.py").MAMBA
SSD = cells.load_py(ROOT / "perfbench/layer_metrics/ssd_scan_ms.py").SSD
CONV = cells.load_py(ROOT / "perfbench/layer_metrics/short_conv_ms.py").CONV


@pytest.mark.parametrize("pattern,op_name,want", [
    (MAMBA, F + "h_1/mamba/in_proj/dot_general", True),
    (MAMBA, B + "h_4/mamba/ssd/checkpoint/rematted_computation/exp", True),
    (MAMBA, R + "mamba/gate_norm/mul", True),
    (MAMBA, "jit(s)/transpose(jvp(mamba))/out_proj/transpose", True),
    (MAMBA, F + "h_5/query/q_proj/dot_general", False),
    (MAMBA, F + "h_1/mamba2/ssd/exp", False),
    (SSD, F + "h_1/mamba/ssd/checkpoint/dot_general", True),
    (SSD, B + "h_1/mamba/ssd/checkpoint/while/body/mul", True),
    (SSD, F + "h_1/mamba/conv1d/mul", False),
    (SSD, F + "h_1/ssd/exp", False),
    # the Mamba convolution is NOT LFM2's `conv` scope: `short_conv_ms`
    # reads nothing of this program
    (CONV, F + "h_1/mamba/conv1d/pad", False),
    (CONV, R + "mamba/conv1d/mul", False),
])
def test_the_mamba_scope_patterns(pattern, op_name, want):
    assert bool(pattern.search(op_name)) == want


def _line(name, op_name):
    return (f'  %{name} = f32[8]{{0}} custom-call(%p), metadata='
            f'{{op_name="{op_name}"}}\n')


OPS = {   # instruction: (op_name, ns in each of the two steps)
    "in.1": (F + "h_1/mamba/in_proj/dot_general", 100),
    "conv.1": (F + "h_1/mamba/conv1d/mul", 20),
    "ssd.1": (F + "h_1/mamba/ssd/checkpoint/dot_general", 400),
    "gate.1": (F + "h_1/mamba/gate_norm/mul", 10),
    "out.1": (F + "h_1/mamba/out_proj/dot_general", 40),
    "conv.2": (R + "mamba/conv1d/mul", 25),
    "ssd.2": (B + "h_4/mamba/ssd/checkpoint/rematted_computation/exp", 500),
    "ssd.3": (B + "h_4/mamba/ssd/checkpoint/while/body/mul", 700),
    "in.2": (B + "h_4/mamba/in_proj/transpose", 150),
    "flash.1": (F + "h_5/attention/pallas_call", 300),
    "q.1": (F + "h_5/query/q_proj/dot_general", 50),
    "up.1": (F + "h_0/mlp/mlp_up/dot_general", 60),
}
TEXT = ("HloModule jit_device_step\n\nENTRY %main.1 (p: f32[8]) -> f32[8] {\n"
        "  %p = f32[8]{0} parameter(0)\n"
        + "".join(_line(n, op) for n, (op, _) in OPS.items()) + "}\n")
MIXER_NS = 100 + 20 + 400 + 10 + 40 + 25 + 500 + 700 + 150
SSD_NS = 400 + 500 + 700


def _run(text=TEXT, family=FAM):
    ops, at = [], 0
    for step in range(2):
        for name, (_, ns) in OPS.items():
            ops.append(xplane.Op(name, f"%{name} = f32[] op()", at, at + ns))
            at += ns
    runs = tuple(xplane.Op("jit_step", "%jit_step = f32[] op()", lo, hi)
                 for lo, hi in ((0, at // 2), (at // 2, at)))
    config = cells.load_json(ROOT / "perfbench/configs" / f"{CONFIG}.json")
    traffic = cells.load_json(ROOT / "perfbench/traffic/s4096x1.json")
    cell = cells.Cell(name="x", chips=1, config_name=CONFIG, config=config,
                      family=family, traffic=traffic, end_to_end=(),
                      per_layer=())
    return {"trace": xplane.Trace((xplane.Device(0, runs, tuple(ops), ()),),
                                  ()),
            "built": {"compiled_text": text}, "cell": cell, "peaks": PEAKS}


@pytest.mark.parametrize("name,want_ns", [
    ("mamba_mixer_ms", MIXER_NS), ("ssd_scan_ms", SSD_NS)])
def test_a_time_reader_on_a_made_up_run(name, want_ns, capsys):
    assert READERS[name](_run()) == pytest.approx(want_ns * 1e-6)
    if name == "mamba_mixer_ms":
        out = capsys.readouterr().out
        assert "[mamba] ms a step (device operations) by inner scope:" in out
        assert "ssd backward 0.001 (2)" in out
        assert "ssd forward 0.000 (1)" in out
        # the recomputed convolution is booked to the backward pass
        assert "conv1d backward 0.000 (1)" in out
        assert "in_proj backward" in out and "conv1d forward" in out


def test_a_fusion_named_after_its_members_is_still_the_mixers():
    """XLA fuses the RMSNorm into the ``in_proj`` matmul that follows it;
    `scopes.instruction_scopes` then names that fusion after its members
    (``mamba`` is no part of its table). The readers go by the root."""
    ln = F + "h_1/ln_1/mul"
    fused = ("%fused.1 (a: f32[8]) -> f32[8] {\n" + _line("m.1", ln)
             + _line("m.2", ln) + _line("m.3", ln) + "}\n\n")
    call = ('  %in.1 = f32[8]{0} fusion(%p), kind=kOutput, calls=%fused.1, '
            f'metadata={{op_name="{OPS["in.1"][0]}"}}\n')
    text = fused + TEXT.replace(_line("in.1", OPS["in.1"][0]), call)
    assert scopes.instruction_scopes(text)["in.1"] == ln     # the repair
    assert READERS["mamba_mixer_ms"](_run(text=text)) == pytest.approx(
        MIXER_NS * 1e-6)
    assert moe_scopes.ms_under(_run(text=text), MAMBA) == pytest.approx(
        (MIXER_NS - 100) * 1e-6)      # what the shared join would read


def test_the_scans_work_by_hand():
    """One token of one layer going forward: C B^T of a chunk, the decayed
    scores against x, the chunk's state and its read; the bytes the scan
    cannot avoid; nine layers at 4096 tokens."""
    model = _run()["cell"].config["model"]
    scan = 2 * 256 * 1 * 128 + 2 * 256 * 4096 + 4 * 4096 * 128
    assert scan == 4_259_840
    assert FAM.ssd_scan_flops(model, 4096) == 3 * 9 * 4096 * scan
    assert FAM.ssd_scan_flops(model, 4096) / 1e12 == pytest.approx(
        0.4711, abs=1e-4)
    forward = 2 * (4096 + 256 + 4096) + 4 * 64
    backward = forward + 2 * (4096 + 256) + 4 * 64
    assert (forward, backward) == (17_152, 26_112)
    assert FAM.ssd_scan_bytes(model, 4096) == 9 * 4096 * (forward + backward)
    # the matmuls bound the scan on a v5e at this chunk: 2.39 ms of FLOPs a
    # step (0.266 ms a layer), 1.95 of bytes
    assert FAM.ssd_scan_flops(model, 4096) / 197e12 == pytest.approx(
        2.391e-3, rel=1e-3)
    assert FAM.ssd_scan_bytes(model, 4096) / 819e9 == pytest.approx(
        1.947e-3, rel=1e-3)
    # functions of the model and the token count only: twice the tokens,
    # twice the work; no layer, no work
    assert FAM.ssd_scan_flops(model, 8192) == 2 * FAM.ssd_scan_flops(model,
                                                                     4096)
    none = {**model, "layer_types": ["attention"]}
    assert FAM.ssd_scan_flops(none, 4096) == FAM.ssd_scan_bytes(none,
                                                                4096) == 0


def test_model_flops_by_hand():
    model = _run()["cell"].config["model"]
    p = FAM.matmul_params_per_token(model)
    assert p["mamba"] == 2048 * 8512 + 4096 * 2048 == 25_821_184
    assert p["attention"] == 2 * 2048 * 2048 + 2 * 2048 * 512 == 10_485_760
    assert p["mlp"] == 3 * 2048 * 8192 == 50_331_648
    assert p["head"] == 25088 * 2048 == 51_380_224
    scan = 4_259_840
    by_hand = (6 * (9 * 25_821_184 + 10_485_760 + 10 * 50_331_648
                    + 51_380_224)
               + 12 * 4096 * 2048 + 3 * 9 * scan)
    assert FAM.flops_per_token(model, 4096) == by_hand
    assert by_hand / 1e9 == pytest.approx(5.001, abs=0.001)
    assert by_hand * 4096 / 1e12 == pytest.approx(20.48, abs=0.01)
    assert FAM.attention_core_flops(model, 1, 4096) == 6 * 32 * 4096 ** 2 * 64
    assert FAM.initial_loss(model) == pytest.approx(10.1365, abs=1e-3)
    assert FAM.tokens_per_step(1, 4096) == 4096


def test_the_scans_share_of_its_roofline_on_a_made_up_run():
    model = _run()["cell"].config["model"]
    flops, nbytes = (FAM.ssd_scan_flops(model, 4096),
                     FAM.ssd_scan_bytes(model, 4096))
    floor = max(flops / 197e12, nbytes / 819e9)
    assert floor == flops / 197e12
    got = READERS["ssd_scan_roofline_pct"](_run())
    assert got == pytest.approx(100 * floor / (SSD_NS * 1e-9))
    # it cannot pass 100 unless the nine scans ran under 2.39 ms a step; at
    # the ~120 ms an unfused XLA scan was planned at it reads 2
    assert 100 * floor / 120e-3 == pytest.approx(2.0, abs=0.1)
    floor_s = cells.load_py(ROOT / "perfbench/layer_metrics/"
                            "ssd_scan_roofline_pct.py").floor_s
    slow_hbm = {**PEAKS, "hbm_bytes_per_s": 1e9}
    assert floor_s(FAM, model, 4096, slow_hbm) == nbytes / 1e9


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_scopes_reads_nothing(name):
    """The parent's program has no such scope: the reader returns None and
    does not raise."""
    text = TEXT.replace("/mamba/", "/mixer/")
    assert READERS[name](_run(text=text)) is None


def test_a_family_without_the_work_functions_reads_nothing():
    lfm2 = cells.load_py(ROOT / "perfbench/families/lfm2_moe.py")
    assert not hasattr(lfm2, "ssd_scan_flops")
    assert READERS["ssd_scan_roofline_pct"](_run(family=lfm2)) is None


def test_the_accepted_readers_serve_the_new_family_on_a_made_up_run():
    """`gqa_attention_flops_util_pct` asks the family for its work: one
    attention layer's triangle at (1, 4096, 32, 64);
    `attention_kernel_calls_per_step` counts the kernels under the bare
    ``attention`` scope."""
    flops = 6 * 1 * 32 * 4096 ** 2 * 64
    got = cells.layer_reader("gqa_attention_flops_util_pct")(_run())
    assert got == pytest.approx(100 * flops / (300e-9 * 197e12))
    kernel = ('  %k = f32[8]{0} custom-call(%p), custom_call_target='
              '"tpu_custom_call", metadata={op_name="' + F
              + 'h_5/attention/pallas_call"}\n')
    run = _run(text=TEXT[:-2] + kernel + kernel + "}\n")
    assert cells.layer_reader("attention_kernel_calls_per_step")(run) == 2.0
    assert cells.layer_reader("attention_kernel_calls_per_step")(_run()) \
        is None


# -- the configuration file, the census, the benchmark's entries -------------

def _file():
    return cells.load_json(ROOT / "perfbench/configs" / f"{CONFIG}.json")


def _size(tree):
    return sum(x.size for x in jax.tree.leaves(tree))


def _shapes(cfg):
    return jax.eval_shape(
        lambda k: models.GraniteHybridLmHeadModel(cfg).init(
            {"params": k}, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))


def test_the_configuration_file_keeps_every_published_number():
    """Every key of the source's config.json (the model-configs catalog's
    entry) is in the file under its own name at the top level, unchanged
    but for those in ``reduced``, and in ``model`` (there with this chip's
    ten ``layer_types``)."""
    catalog = pathlib.Path("/opt/skills/guides/model-configs/"
                           "architectures.jsonl")
    config = _file()
    if catalog.is_file():
        row = next(r for r in map(json.loads, catalog.open())
                   if r["name"] == "granite-4.0-h-micro")
        assert config["source"] == row["source_url"]
        published = row["config"]
    else:
        published = {k: config[k] for k in config["model"] if k in config}
        published.update(num_hidden_layers=40, vocab_size=100352)
    share = {"num_hidden_layers": 10, "vocab_size": 25088}
    assert (config["reduced"] == ["num_hidden_layers", "vocab_size"]
            == list(config["changed"]))
    assert len(published) == 33
    for key, value in published.items():
        assert config[key] == share.get(key, value), key
        if key != "layer_types":
            assert config["model"][key] == share.get(key, value), key
    bench = cells.load_json(ROOT / "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    model = config["model"]
    kinds = config["layer_types"]
    assert len(kinds) == 40 and kinds.count("attention") == 4
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [
        5, 15, 25, 35]
    # published layers 0-9: a whole period, and the same ten as 10-19, ...
    assert model["layer_types"] == kinds[:10] == kinds[10:20] == kinds[30:]
    assert model["reference_layer_types"] == ["mamba", "attention"]
    assert model["vocab_size_published"] == 100352 == 4 * model["vocab_size"]
    assert model["vocab_size"] == 196 * 128
    assert model["num_hidden_layers_published"] == 40
    assert model["remat"] is True
    for text in ("assumed", "changed", "deployment", "recomputation"):
        assert config[text], text
    for key in ("initializer_range", "head_dim", "A_log", "dt_bias", "D",
                "seq_len", "weights layout", "initial loss"):
        assert key in config["assumed"], key
    assert config["train"] == cells.load_json(
        ROOT / "perfbench/configs/lfm2-8b-a1b-ep4.json")["train"]
    # the program's preset is the published model
    preset = models.GRANITE_4_0_H_MICRO
    for key, value in published.items():
        if hasattr(preset, key) and key != "layer_types":
            assert getattr(preset, key) == value, key
    assert list(preset.layer_types) == kinds
    cfg = FAM.model_config(model, jnp.bfloat16)
    assert cfg.layer_types == tuple(kinds[:10]) and cfg.remat
    assert cfg.mamba_inner == 4096 and cfg.conv_dim == 4352


def test_this_chips_share_is_798_million_parameters():
    model = _file()["model"]
    shapes = _shapes(FAM.model_config(model, jnp.bfloat16))
    mamba, attention = 76_182_976, 60_821_504
    assert [_size(shapes[f"h_{i}"]) for i in range(10)] == [
        attention if kind == "attention" else mamba
        for kind in model["layer_types"]]
    m = shapes["h_0"]["mamba"]
    assert m["in_proj"]["kernel"].shape == (2048, 8512)
    assert m["conv_kernel"].shape == (4, 4352)
    assert m["conv_bias"].shape == (4352,)
    assert m["A_log"].shape == m["D"].shape == m["dt_bias"].shape == (64,)
    assert m["gate_norm"].shape == (4096,)
    assert m["out_proj"]["kernel"].shape == (4096, 2048)
    assert _size(m) == 2048 * 8512 + 5 * 4352 + 3 * 64 + 4096 + 4096 * 2048
    assert shapes["wte"]["embedding"].shape == (25088, 2048)
    # nine Mamba blocks, the attention block, the slice's embedding (tied:
    # once), the final norm
    assert _size(shapes) == 9 * mamba + attention + 25088 * 2048 + 2048 \
        == 797_850_560
    # the reference check's two layers: 188.4M
    two = _shapes(FAM.model_config(model, jnp.float32, num_layers=2))
    assert _size(two) == mamba + attention + 25088 * 2048 + 2048 \
        == 188_386_752
    # the published model whole: 3.19B
    whole = _shapes(models.GRANITE_4_0_H_MICRO)
    assert _size(whole) == 36 * mamba + 4 * attention + 100352 * 2048 + 2048 \
        == 3_191_396_096


def test_the_benchmarks_entries_resolve():
    """`perfbench/cell.py` finds the cell's configuration, family, traffic
    and every per-layer reader by name."""
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert cell.family.__file__.endswith("families/granite_hybrid.py")
    assert cell.traffic == cells.load_json(
        ROOT / "perfbench/traffic/s4096x1.json")
    assert (cell.traffic["seq_len"], cell.traffic["batch_per_chip"],
            cell.traffic["mode"]) == (4096, 1, "dear")
    assert cell.traffic["reference"] == {"layers": 2, "batch_per_chip": 1,
                                         "steps": 4}
    names = [m["name"] for m in cell.per_layer]
    for name in ("mamba_mixer_ms", "ssd_scan_ms", "ssd_scan_roofline_pct",
                 "attention_kernel_calls_per_step",
                 "gqa_attention_flops_util_pct", "kernel_flops_util_pct",
                 "forward_ms", "backward_ms", "loss_ms"):
        assert name in names, name
        assert callable(cells.layer_reader(name))
    for name in ("moe_routed_ms", "short_conv_ms", "dropout_ms",
                 "collectives_per_step"):
        assert name not in names, name
    assert [m["name"] for m in cell.end_to_end] == [
        "tokens_per_s_per_chip", "step_ms_p95", "peak_hbm_gb", "setup_s"]
    bench = cells.load_json(ROOT / "BENCHMARK.json")
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == ["mamba_mixer_ms", "ssd_scan_ms",
                                        "ssd_scan_roofline_pct"]
    assert all(m["layer"] == "models and kernels" and m["moves"]
               == "tokens_per_s_per_chip" and m["source"] == "device_trace"
               for m in new)
    # the sixth cell and fifth configuration, later ones appended after them
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 5
    assert [c["name"] for c in bench["configs"]].index(CONFIG) == 4
    assert all(len(e["why"]) <= 200 for e in bench["workloads"]
               + bench["configs"])
