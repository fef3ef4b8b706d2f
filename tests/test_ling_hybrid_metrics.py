"""The delta-rule hybrid's named scopes as `perfbench/scopes.py` (unedited)
books them, the family's work functions against hand counts, each new
per-layer reader on a made-up run, the configuration file against the
catalog's row, and the benchmark's new entries as `perfbench/cell.py`
resolves them."""

import pathlib

import jax
import jax.numpy as jnp
import pytest

from dear_pytorch_tpu import models
from perfbench import cell as cells
from perfbench import scopes, xplane

ROOT = pathlib.Path(__file__).resolve().parents[1]
FAM = cells.load_py(ROOT / "perfbench" / "families" / "ling_hybrid.py")
CONFIG = "ling-3.0-flash-ep64"
CELL = f"{CONFIG}.s8192x1"
F = "jit(device_step)/jvp(LingHybridLmHeadModel)/"
B = "jit(device_step)/transpose(jvp(LingHybridLmHeadModel))/"
R = B + "jvp(LingHybridLmHeadModel)/checkpoint/rematted_computation/"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.mark.parametrize("op_name,want", [
    (F + "h_4/query/q_proj/dot_general", ("forward", "projections")),
    (F + "h_4/key/kv_a_proj/dot_general", ("forward", "projections")),
    (B + "h_4/value/v_up/dot_general", ("backward", "projections")),
    (F + "h_4/output/gate_proj/dot_general", ("forward", "projections")),
    (F + "h_4/attention/pallas_call", ("forward", "attention")),
    (F + "h_1/mlp/moe/route/dot_general", ("forward", "mlp")),
    (F + "mtp/mtp_block/attention/pallas_call", ("forward", "attention")),
    (F + "loss/lm_head/dot_general", ("forward", "loss")),
    # `_PARTS` has no part for the KDA mixer: it is booked as unattributed,
    # as Granite's `mamba` is (PERF.md section 7 asks a later PR for the
    # part); a recomputed block's forward work runs in the backward pass
    (F + "h_1/kda/in_proj/q_proj/dot_general",
     ("forward", scopes.UNATTRIBUTED)),
    (R + "h_1/kda/conv/mul", ("backward", scopes.UNATTRIBUTED)),
    (B + "h_1/kda/scan/checkpoint/triangular_solve",
     ("backward", scopes.UNATTRIBUTED)),
    (B + "h_1/kda/out_proj/dot_general", ("backward", scopes.UNATTRIBUTED)),
])
def test_the_unedited_scope_table_books_the_new_program(op_name, want):
    assert scopes.classify(op_name) == want


READERS = {name: cells.layer_reader(name) for name in (
    "kda_mixer_ms", "kda_scan_ms", "kda_scan_roofline_pct")}
KDA = cells.load_py(ROOT / "perfbench/layer_metrics/kda_mixer_ms.py").KDA
SCAN = cells.load_py(ROOT / "perfbench/layer_metrics/kda_scan_ms.py").SCAN


@pytest.mark.parametrize("pattern,op_name,want", [
    (KDA, F + "h_1/kda/in_proj/q_proj/dot_general", True),
    (KDA, B + "h_1/kda/scan/checkpoint/while/body/dot_general", True),
    (KDA, R + "h_2/kda/out_norm_gate/mul", True),
    (KDA, F + "h_4/query/q_proj/dot_general", False),
    (KDA, F + "h_1/kda_out/name", False),
    (SCAN, F + "h_1/kda/scan/checkpoint/triangular_solve", True),
    (SCAN, B + "h_1/kda/scan/checkpoint/while/body/mul", True),
    (SCAN, F + "h_1/kda/gate/logistic", False),
    (SCAN, F + "h_1/scan/exp", False),
])
def test_the_kda_scope_patterns(pattern, op_name, want):
    assert bool(pattern.search(op_name)) == want


def _line(name, op_name):
    return (f'  %{name} = f32[8]{{0}} custom-call(%p), metadata='
            f'{{op_name="{op_name}"}}\n')


OPS = {   # instruction: (op_name, ns in each of the two steps)
    "in.1": (F + "h_1/kda/in_proj/q_proj/dot_general", 100),
    "conv.1": (F + "h_1/kda/conv/mul", 20),
    "gate.1": (F + "h_1/kda/gate/logistic", 10),
    "scan.1": (F + "h_1/kda/scan/checkpoint/triangular_solve", 400),
    "norm.1": (F + "h_1/kda/out_norm_gate/mul", 10),
    "out.1": (F + "h_1/kda/out_proj/dot_general", 40),
    "conv.2": (R + "h_2/kda/conv/mul", 25),
    "scan.2": (B + "h_2/kda/scan/checkpoint/rematted_computation/exp", 500),
    "scan.3": (B + "h_2/kda/scan/checkpoint/while/body/mul", 700),
    "in.2": (B + "h_2/kda/in_proj/f_proj/transpose", 150),
    "flash.1": (F + "h_4/attention/pallas_call", 300),
    "q.1": (F + "h_4/query/q_proj/dot_general", 50),
    "up.1": (F + "h_0/mlp/mlp_up/dot_general", 60),
}
TEXT = ("HloModule jit_device_step\n\nENTRY %main.1 (p: f32[8]) -> f32[8] {\n"
        "  %p = f32[8]{0} parameter(0)\n"
        + "".join(_line(n, op) for n, (op, _) in OPS.items()) + "}\n")
MIXER_NS = 100 + 20 + 10 + 400 + 10 + 40 + 25 + 500 + 700 + 150
SCAN_NS = 400 + 500 + 700


def _run(text=TEXT, family=FAM):
    ops, at = [], 0
    for step in range(2):
        for name, (_, ns) in OPS.items():
            ops.append(xplane.Op(name, f"%{name} = f32[] op()", at, at + ns))
            at += ns
    runs = tuple(xplane.Op("jit_step", "%jit_step = f32[] op()", lo, hi)
                 for lo, hi in ((0, at // 2), (at // 2, at)))
    cell = cells.Cell(name="x", chips=1, config_name=CONFIG, config=_file(),
                      family=family,
                      traffic=cells.load_json(
                          ROOT / "perfbench/traffic/s8192x1.json"),
                      end_to_end=(), per_layer=())
    return {"trace": xplane.Trace((xplane.Device(0, runs, tuple(ops), ()),),
                                  ()),
            "built": {"compiled_text": text}, "cell": cell, "peaks": PEAKS}


@pytest.mark.parametrize("name,want_ns", [
    ("kda_mixer_ms", MIXER_NS), ("kda_scan_ms", SCAN_NS)])
def test_a_time_reader_on_a_made_up_run(name, want_ns, capsys):
    assert READERS[name](_run()) == pytest.approx(want_ns * 1e-6)
    if name == "kda_mixer_ms":
        out = capsys.readouterr().out
        assert "[kda] ms a step (device operations) by inner scope:" in out
        assert "scan backward 0.001 (2)" in out
        assert "scan forward 0.000 (1)" in out
        # the recomputed convolution is booked to the backward pass
        assert "conv backward 0.000 (1)" in out
        for inner in ("in_proj", "gate", "out_norm_gate", "out_proj"):
            assert f"{inner} forward" in out, inner


def test_the_scans_work_by_hand():
    """One token and head of one layer going forward at chunk 64, keys and
    values of 128: the factorised A and P, the triangular solve, the chunk's
    correction and addition to the state, the carry, the reads of the
    entering state and of U; the bytes the scan cannot avoid; six layers,
    32 heads, 8192 tokens."""
    model = _file()["model"]
    c, k = 64, 128
    scan = (2 * c * k * 2 + c * 2 * k + 2 * k * k + 2 * k * k
            + 2 * k * k * k // c + 2 * k * k * 2 + 2 * c * k)
    assert scan == 262_144
    assert FAM.kda_scan_flops(model, 8192) == 3 * 6 * 32 * 8192 * scan
    inputs = 2 * 3 * 128 + 4 * 128 + 4
    forward, backward = inputs + 2 * 128, inputs + 2 * 128 + inputs
    assert (forward, backward) == (1540, 2824)
    assert FAM.kda_scan_bytes(model, 8192) == 6 * 32 * 8192 * (forward
                                                               + backward)
    # the bytes bound the scan on a v5e: 8.38 ms a step against 6.28 of
    # matmuls
    assert FAM.kda_scan_bytes(model, 8192) / 819e9 == pytest.approx(
        8.381e-3, rel=1e-3)
    assert FAM.kda_scan_flops(model, 8192) / 197e12 == pytest.approx(
        6.279e-3, rel=1e-3)
    # functions of the model and the token count only; no KDA layer, no work
    assert FAM.kda_scan_flops(model, 4096) * 2 == FAM.kda_scan_flops(model,
                                                                     8192)
    none = {**model, "layer_types": ["mla"]}
    assert FAM.kda_scan_flops(none, 8192) == FAM.kda_scan_bytes(none,
                                                                8192) == 0


def test_model_flops_by_hand():
    model = _file()["model"]
    p = FAM.matmul_params_per_token(model)
    kda = 6 * 2560 * 4096 + 2560 * 32
    mla = (2560 * 32 * 192 + 2560 * 576 + 512 * 32 * 256 + 2560 * 32
           + 32 * 128 * 2560)
    assert (p["kda"], p["mla"]) == (kda, mla) == (62_996_480, 31_965_184)
    assert p["routed"] == 8 * 8 / 512 * 3 * 2560 * 768 == 737_280
    expert = 3 * 2560 * 768 + 2560 * 512 + 737_280
    by_hand = (6 * (6 * kda + 2 * mla + 3 * 2560 * 6144 + 7 * expert
                    + 2 * 2560 * 2560 + 2 * 19648 * 2560)
               + 2 * 6 * 8192 * 32 * (192 + 128)
               + 3 * 6 * 32 * 262_144)
    assert FAM.flops_per_token(model, 8192) == by_hand
    assert by_hand / 1e9 == pytest.approx(5.108, abs=0.001)
    assert FAM.initial_loss(model) == pytest.approx(13.517, abs=1e-3)
    assert FAM.tokens_per_step(1, 8192) == 8192
    assert FAM.expert_matmul_flops(model, 1.0) == 6 * 3 * 2560 * 768


def test_the_scans_share_of_its_roofline_on_a_made_up_run():
    model = _file()["model"]
    floor = max(FAM.kda_scan_flops(model, 8192) / 197e12,
                FAM.kda_scan_bytes(model, 8192) / 819e9)
    assert floor == FAM.kda_scan_bytes(model, 8192) / 819e9
    got = READERS["kda_scan_roofline_pct"](_run())
    assert got == pytest.approx(100 * floor / (SCAN_NS * 1e-9))
    floor_s = cells.load_py(ROOT / "perfbench/layer_metrics/"
                            "kda_scan_roofline_pct.py").floor_s
    slow = {**PEAKS, "bf16_flops_per_s": 1e12}
    assert floor_s(FAM, model, 8192, slow) == FAM.kda_scan_flops(
        model, 8192) / 1e12


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_program_without_the_scopes_reads_nothing(name):
    """The parent's program has no such scope: the reader returns None and
    does not raise."""
    text = TEXT.replace("/kda/", "/mixer/")
    assert READERS[name](_run(text=text)) is None


def test_a_family_without_the_work_functions_reads_nothing():
    granite = cells.load_py(ROOT / "perfbench/families/granite_hybrid.py")
    assert not hasattr(granite, "kda_scan_flops")
    assert READERS["kda_scan_roofline_pct"](_run(family=granite)) is None


# -- the configuration file, the census, the benchmark's entries -------------

def _file():
    return cells.load_json(ROOT / "perfbench/configs" / f"{CONFIG}.json")


def _size(tree):
    return sum(x.size for x in jax.tree.leaves(tree))


def _shapes(cfg):
    return jax.eval_shape(
        lambda k: models.LingHybridLmHeadModel(cfg).init(
            {"params": k}, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0))


CALL = ('  %{name}.{n} = bf16[1,8192,4096]{{2,1,0}} custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", metadata={{op_name="{op}"}}')


@pytest.mark.parametrize("lines,want", [
    ([CALL.format(name="kda_fwd", n=1,
                  op=F + "h_1/kda/scan/kda_fwd/pallas_call")], 1.0),
    ([CALL.format(name="kda_bwd", n=2, op=B + "jvp(LingHybridLmHeadModel)/"
                  "checkpoint/h_1/kda/scan/kda_bwd/pallas_call")], 1.0),
    # a kernel without its own op_name, and one under another scope
    (['  %kda_fwd.3 = bf16[1,8192,4096]{2,1,0} custom-call(%a), '
      'custom_call_target="tpu_custom_call"',
      CALL.format(name="flash_fwd", n=4,
                  op=F + "h_4/attention/flash_fwd/pallas_call")], None),
    # six KDA layers, forward and backward, among the other kernels
    ([CALL.format(name=f"kda_{side}", n=10 * i + n,
                  op=(F if side == "fwd" else B) + f"h_{i}/kda/scan/"
                  f"kda_{side}/pallas_call")
      for i in (0, 1, 2, 3, 5, 6) for n, side in enumerate(("fwd", "bwd"))]
     + [CALL.format(name="flash_fwd", n=4,
                    op=F + "h_4/attention/flash_fwd/pallas_call"),
        '  %fusion.5 = f32[1,8192,4096]{2,1,0} fusion(%x), kind=kLoop, '
        'metadata={op_name="' + F + 'h_1/kda/scan/mul"}'], 12.0),
], ids=["forward", "backward", "none", "a step"])
def test_the_kernel_counter_on_compiled_lines(lines, want):
    """`kda_scan_kernel_calls_per_step` counts the ``tpu_custom_call``s
    whose own ``op_name`` lies under ``kda/scan``, forward or under
    ``transpose(jvp(...))``; a scopeless one, another scope's kernel and
    XLA's fusions under the scope are not counted."""
    read = cells.layer_reader("kda_scan_kernel_calls_per_step")
    assert read({"built": {"compiled_text": "\n".join(lines)}}) == want


def test_the_configuration_file_keeps_every_published_number():
    """Every key of the source's config.json is in the file under its own
    name at the top level and in ``model``, unchanged but for those in
    ``reduced``, whose published counts stand beside them."""
    config = _file()
    assert config["source"] == ("https://huggingface.co/inclusionAI/"
                                "Ling-3.0-flash/blob/main/config.json")
    share = {"num_hidden_layers": 7, "first_k_dense_replace": 1,
             "num_experts": 8, "vocab_size": 19648}
    published = {k: config[k] for k in config["model"] if k in config}
    published.update(num_hidden_layers=42, first_k_dense_replace=2,
                     num_experts=512, vocab_size=157184)
    assert config["reduced"] == list(share) == list(config["changed"])
    assert len(published) == 62
    for key, value in published.items():
        assert config[key] == share.get(key, value), key
        assert config["model"][key] == share.get(key, value), key
    model = config["model"]
    assert (model["num_hidden_layers_published"],
            model["first_k_dense_replace_published"],
            model["num_experts_published"], model["vocab_size_published"]) \
        == (42, 2, 512, 157184)
    assert model["vocab_size"] * 8 == 157184
    assert model["layer_types"] == list(
        models.LING_3_0_FLASH.layer_types[1:8]) == [
            "kda", "kda", "kda", "kda", "mla", "kda", "kda"]
    assert model["first_layer"] == 1 and model["remat"] is True
    assert model["reference"] == {"layer_types": ["kda"], "first_layer": 2,
                                  "first_k_dense_replace": 0,
                                  "num_nextn_predict_layers": 0}
    for key in ("kda gate", "layer kinds", "qk norm", "rotary pairing",
                "mtp_loss_weight", "mtp input order", "initializer_range",
                "router_bias", "seq_len"):
        assert key in config["assumed"], key
    for text in ("changed", "deployment", "recomputation",
                 "reference check"):
        assert config[text], text
    assert config["train"] == cells.load_json(
        ROOT / "perfbench/configs/granite-4.0-h-micro-vp4.json")["train"]
    cfg = FAM.model_config(model, jnp.bfloat16)
    assert (cfg.num_experts, cfg.experts_held, cfg.n_group, cfg.topk_group,
            cfg.num_experts_per_tok) == (512, 8, 8, 4, 8)
    assert cfg.remat and cfg.first_layer == 1 and cfg.mtp_loss_weight == 0.3
    cfg.refuse_clamps()
    # the program's preset is the published model
    preset = models.LING_3_0_FLASH
    for key in ("hidden_size", "num_attention_heads", "head_dim",
                "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
                "v_head_dim", "intermediate_size", "moe_intermediate_size",
                "num_experts", "num_experts_per_tok", "n_group",
                "topk_group", "routed_scaling_factor", "rms_norm_eps",
                "vocab_size", "kda_lower_bound", "short_conv_kernel_size"):
        assert getattr(preset, key) == published[key], key
    assert preset.rope_theta == published["rope_theta"]
    assert list(preset.expert_swiglu_limits) == published[
        "expert_swiglu_limit_list"]
    assert list(preset.shared_swiglu_limits) == published[
        "share_expert_swiglu_limit_list"]


def test_this_chips_share_is_984_million_parameters():
    model = _file()["model"]
    shapes = _shapes(FAM.model_config(model, jnp.bfloat16))
    kda_dense, kda_expert, mla_expert = 110_240_928, 117_450_400, 86_366_592
    assert [_size(shapes[f"h_{i}"]) for i in range(7)] == [
        kda_dense] + [kda_expert] * 3 + [mla_expert] + [kda_expert] * 2
    m = shapes["h_1"]["kda"]
    for n in "qkvfg":
        assert m[f"{n}_proj"]["kernel"].shape == (2560, 4096)
    assert m["b_proj"]["kernel"].shape == (2560, 32)
    assert m["q_conv"].shape == (4, 4096) and m["A_log"].shape == (32,)
    assert m["dt_bias"].shape == (4096,) and m["o_norm"].shape == (128,)
    assert m["out_proj"]["kernel"].shape == (4096, 2560)
    a = shapes["h_4"]
    assert a["q_proj"]["kernel"].shape == (2560, 32, 192)
    assert a["kv_a_proj"]["kernel"].shape == (2560, 576)
    assert a["k_up"]["kernel"].shape == a["v_up"]["kernel"].shape == (
        512, 32, 128)
    assert a["gate_proj"]["kernel"].shape == (2560, 32)
    assert a["output"]["kernel"].shape == (32, 128, 2560)
    e = shapes["h_1"]["moe"]
    assert e["wi"].shape == (8, 2560, 1536) and e["router"].shape == (2560,
                                                                       512)
    assert _size(shapes["mtp_block"]) == mla_expert
    assert _size(shapes) == (kda_dense + 5 * kda_expert + 2 * mla_expert
                             + 2560 * 2560 * 2 + 2 * 19648 * 2560
                             + 4 * 2560) == 983_941_312
    # the reference check's model: published layer 2, a KDA expert layer
    # (the router, the held experts, the shared one), no prediction module
    one = _shapes(FAM.model_config(model, jnp.float32, num_layers=1))
    assert set(one) == {"wte", "h_0", "ln_f", "lm_head"}
    assert set(one["h_0"]["moe"]) == {"router", "router_bias", "wi", "wo"}
    assert _size(one) == kda_expert + 2 * 19648 * 2560 + 2560 == 218_050_720
    cfg = FAM.model_config(model, jnp.float32, num_layers=1)
    assert (cfg.first_layer, cfg.first_k_dense_replace,
            cfg.num_nextn_predict_layers) == (2, 0, 0)


def test_the_benchmarks_entries_resolve():
    """`perfbench/cell.py` finds the cell's configuration, family, traffic
    and every per-layer reader by name."""
    cell = cells.resolve(CELL)
    assert cell.chips == 1 and cell.config_name == CONFIG
    assert cell.family.__file__.endswith("families/ling_hybrid.py")
    assert (cell.traffic["seq_len"], cell.traffic["batch_per_chip"],
            cell.traffic["mode"], cell.traffic["warmup_steps"],
            cell.traffic["trace_steps"]) == (8192, 1, "dear", 3, 10)
    assert cell.traffic["reference"] == {"layers": 1, "batch_per_chip": 1,
                                         "steps": 4}
    names = [m["name"] for m in cell.per_layer]
    for name in ("kda_mixer_ms", "kda_scan_ms", "kda_scan_roofline_pct",
                 "attention_kernel_calls_per_step", "moe_routed_ms",
                 "moe_dispatch_ms", "expert_matmul_flops_util_pct",
                 "expert_load_max_over_mean", "moe_held_row_share_pct",
                 "mtp_ms", "kernel_flops_util_pct", "forward_ms", "loss_ms",
                 "moe_row_kernel_calls_per_step",
                 "expert_matmul_kernel_calls_per_step",
                 "latent_projection_ms", "kda_scan_kernel_calls_per_step"):
        assert name in names, name
        assert callable(cells.layer_reader(name))
    for name in ("mamba_mixer_ms", "short_conv_ms", "dropout_ms",
                 "collectives_per_step"):
        assert name not in names, name
    assert [m["name"] for m in cell.end_to_end] == [
        "tokens_per_s_per_chip", "step_ms_p95", "peak_hbm_gb", "setup_s"]
    bench = cells.load_json(ROOT / "BENCHMARK.json")
    new = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in new] == [
        "kda_mixer_ms", "kda_scan_ms", "kda_scan_roofline_pct",
        "kda_scan_kernel_calls_per_step"]
    assert all(m["layer"] == "models and kernels" and m["moves"]
               == "tokens_per_s_per_chip" for m in new)
    assert [m["source"] for m in new] == ["device_trace"] * 3 + [
        "program_counter"]
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 6
    assert [c["name"] for c in bench["configs"]].index(CONFIG) == 5
    assert all(len(e["why"]) <= 200 for e in bench["workloads"]
               + bench["configs"])
