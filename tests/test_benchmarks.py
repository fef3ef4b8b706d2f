"""Benchmark harness tests: CLI surface, protocol, and the scrape-able
output contract (reference benchmarks.py:119-128 greps the
``Total ... <DEV>(s): N +-C`` line)."""

import re

import pytest

from dear_pytorch_tpu.benchmarks import bert as bert_bench
from dear_pytorch_tpu.benchmarks import imagenet as imagenet_bench


TINY = ["--num-warmup-batches", "1", "--num-batches-per-iter", "2",
        "--num-iters", "2"]


def test_imagenet_cli_output_contract(mesh, capsys):
    res = imagenet_bench.main(
        ["--model", "mnistnet", "--batch-size", "4"] + TINY
    )
    out = capsys.readouterr().out
    m = re.search(r"Total img/sec on (\d+) \w+\(s\): ([\d.]+) \+-([\d.]+)",
                  out)
    assert m, out
    assert int(m.group(1)) == 8
    assert abs(float(m.group(2)) - res.total_mean) < 0.1
    assert "Running warmup..." in out and "Running benchmark..." in out
    # per-device x world == total
    assert res.total_mean == pytest.approx(8 * res.per_device_mean)


def test_imagenet_scanned_protocol(mesh, capsys):
    """--scan-steps k: one lax.scan program per dispatch; reported
    throughput stays in the same ballpark as per-step dispatch and the
    scrape line shape is unchanged."""
    base = imagenet_bench.main(
        ["--model", "mnistnet", "--batch-size", "4"] + TINY
    )
    scanned = imagenet_bench.main(
        ["--model", "mnistnet", "--batch-size", "4", "--scan-steps", "2",
         "--num-warmup-batches", "2", "--num-batches-per-iter", "4",
         "--num-iters", "2"]
    )
    out = capsys.readouterr().out
    assert "Scanned protocol: 2 steps per dispatch" in out
    # accounting invariant: throughput x per-REAL-step time = per-device
    # batch items, under BOTH protocols. Means of reciprocal quantities are
    # Jensen-biased upward under timing variance, so the tolerance is
    # generous — this checks the scan_steps bookkeeping (a factor-2 error
    # would blow straight through it), not machine speed.
    for res in (base, scanned):
        assert res.per_device_mean * res.iter_time_mean == pytest.approx(
            4.0, rel=0.35
        )
    assert scanned.per_device_mean > 0
    with pytest.raises(SystemExit, match="pipeline"):
        imagenet_bench.main(
            ["--model", "mnistnet", "--batch-size", "4", "--scan-steps",
             "2", "--pipeline", "numpy"] + TINY
        )
    with pytest.raises(SystemExit, match="autotune"):
        imagenet_bench.main(
            ["--model", "mnistnet", "--batch-size", "4", "--scan-steps",
             "2", "--autotune", "bo"] + TINY
        )


def test_imagenet_modes_and_ablations(mesh):
    # baseline schedules parse & run; the reference's exclude-parts
    # ablation is gone with its flag (the device trace gives the breakdown)
    imagenet_bench.main(
        ["--model", "mnistnet", "--batch-size", "4", "--mode", "allreduce"]
        + TINY
    )
    imagenet_bench.main(
        ["--model", "mnistnet", "--batch-size", "4", "--mode", "rb"] + TINY
    )
    for bad in (["--exclude-parts", "allgather"], ["--mode", "bogus"]):
        with pytest.raises(SystemExit):
            imagenet_bench.main(["--model", "mnistnet"] + bad + TINY)


def test_bert_cli_output_contract(mesh, capsys):
    res = bert_bench.main(
        ["--model", "bert_base", "--num-hidden-layers", "1",
         "--sentence-len", "16", "--batch-size", "2"] + TINY
    )
    out = capsys.readouterr().out
    assert re.search(r"Total sen/sec on 8 \w+\(s\): ", out), out
    assert "BERT Base Pretraining, Sentence len: 16" in out
    assert res.unit == "sen"


def test_imagenet_autotune_bo(mesh):
    # BO autotune drives the live re-bucketing machinery from the CLI
    imagenet_bench.main(
        ["--model", "mnistnet", "--batch-size", "4", "--autotune", "bo",
         "--num-warmup-batches", "6", "--num-batches-per-iter", "6",
         "--num-iters", "2"]
    )


def test_imagenet_compressed_allreduce(mesh):
    imagenet_bench.main(
        ["--model", "mnistnet", "--batch-size", "4", "--mode", "allreduce",
         "--compressor", "eftopk", "--density", "0.1"] + TINY
    )


@pytest.mark.parametrize("pl", ["native", "numpy"])
def test_imagenet_streaming_pipeline(mesh, pl):
    """--pipeline native|numpy feeds the timed loop fresh ring-buffer
    batches instead of one re-fed array; throughput must stay in the same
    regime as batch re-feed (catches a stalled producer or a host-side
    serialization)."""
    base = imagenet_bench.main(
        ["--model", "mnistnet", "--batch-size", "4"] + TINY
    )
    res = imagenet_bench.main(
        ["--model", "mnistnet", "--batch-size", "4", "--pipeline", pl]
        + TINY
    )
    assert res.total_mean > 0
    assert res.total_mean > base.total_mean / 5, (res, base)


@pytest.mark.parametrize("flash", [False, True])
def test_bert_sequence_parallel_cli(mesh, capsys, flash):
    """--sp-degree k: dp x sp mesh, ring(-flash) attention inside the
    model, sentences/sec accounted per CHIP (a sentence spans sp chips)."""
    argv = ["--model", "bert_base", "--num-hidden-layers", "1",
            "--sentence-len", "32", "--batch-size", "2",
            "--sp-degree", "4"] + TINY
    if flash:
        argv.append("--flash-attention")
    res = bert_bench.main(argv)
    out = capsys.readouterr().out
    assert "(dp 2 x sp 4)" in out
    assert re.search(r"Total sen/sec on 8 \w+\(s\): ", out), out
    # 4 sentences/step globally: total throughput = 4 / step_time
    assert res.total_mean * res.iter_time_mean == pytest.approx(4.0,
                                                               rel=0.35)
    with pytest.raises(SystemExit, match="divide"):
        bert_bench.main(["--model", "bert_base", "--sp-degree", "3"] + TINY)
    with pytest.raises(SystemExit, match="sentence-len"):
        bert_bench.main(["--model", "bert_base", "--sentence-len", "30",
                         "--sp-degree", "4"] + TINY)
    with pytest.raises(SystemExit, match="sp-degree"):
        bert_bench.main(["--model", "bert_base",
                         "--sp-attention", "ulysses"] + TINY)
    with pytest.raises(SystemExit, match="conflicts"):
        bert_bench.main(["--model", "bert_base", "--sp-degree", "4",
                         "--flash-attention",
                         "--sp-attention", "ulysses"] + TINY)


def test_bert_streaming_pipeline(mesh):
    res = bert_bench.main(
        ["--model", "bert_base", "--num-hidden-layers", "1",
         "--sentence-len", "16", "--batch-size", "2",
         "--pipeline", "native"] + TINY
    )
    assert res.unit == "sen" and res.total_mean > 0


def test_dropout0_and_remat_flags_shape_the_config():
    """--dropout0 / --remat must actually reach the model config (the r5
    perf decomposition depends on them; a silently-ignored flag would
    re-measure the dropout-on model and report it as dropout-0). The
    override logic is the shared models.dropout_free helper — assert it
    zeroes EVERY dropout field of both config families, and that the
    parsers accept the flags."""
    import dataclasses

    from dear_pytorch_tpu import models
    from dear_pytorch_tpu.benchmarks import bert as bert_cli
    from dear_pytorch_tpu.benchmarks import gpt as gpt_cli

    for cfg in (models.get_model("gpt2").config,
                models.get_model("bert_base").config):
        free = models.dropout_free(cfg)
        dropout_fields = [f.name for f in dataclasses.fields(free)
                          if "dropout" in f.name]
        assert dropout_fields  # the helper must actually find some
        assert all(getattr(free, n) == 0.0 for n in dropout_fields), free
        # non-dropout fields untouched
        assert free.hidden_size == cfg.hidden_size

    g = gpt_cli.build_parser().parse_args(
        ["--dropout0", "--remat", "--batch-size", "2"])
    assert g.dropout0 and g.remat
    b = bert_cli.build_parser().parse_args(["--dropout0"])
    assert b.dropout0


def test_bert_dear_fused_ring_projections_cli(mesh, capsys):
    """--mode dear-fused end-to-end through the BERT CLI, with the QKV/MLP
    projections routed through the ring collective-matmul
    (--ring-projections): the scrape-able contract line still appears."""
    res = bert_bench.main(
        ["--model", "bert_base", "--num-hidden-layers", "1",
         "--sentence-len", "16", "--batch-size", "2",
         "--mode", "dear-fused", "--ring-projections", "--dropout0"]
        + TINY
    )
    out = capsys.readouterr().out
    assert re.search(r"Total sen/sec on 8 \w+\(s\): ", out), out
    assert "Schedule: dear-fused" in out
    assert res.unit == "sen"


def test_ring_projections_flag_requires_dear_fused(mesh):
    with pytest.raises(SystemExit, match="ring-projections"):
        bert_bench.main(
            ["--model", "bert_base", "--num-hidden-layers", "1",
             "--sentence-len", "16", "--batch-size", "2",
             "--ring-projections"] + TINY
        )
