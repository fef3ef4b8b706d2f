"""bench.py is the driver contract (ONE JSON line, primary metric first);
these tests pin its helper logic and the contract itself so a regression
is caught in CI rather than in the driver's end-of-round capture."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def bench():
    return _load_bench()


def test_env_enabled(bench, monkeypatch):
    monkeypatch.delenv("DEAR_BENCH_VIT", raising=False)
    assert bench._env_enabled("DEAR_BENCH_VIT")
    for off in ("0", "false", "no", ""):
        monkeypatch.setenv("DEAR_BENCH_VIT", off)
        assert not bench._env_enabled("DEAR_BENCH_VIT")
    monkeypatch.setenv("DEAR_BENCH_VIT", "1")
    assert bench._env_enabled("DEAR_BENCH_VIT")


def test_gather_dtype_world_gating(bench, monkeypatch):
    import jax.numpy as jnp

    monkeypatch.delenv("DEAR_BENCH_GATHER_DTYPE", raising=False)
    assert bench._gather_dtype(1) is None          # no gather traffic
    assert bench._gather_dtype(8) is jnp.bfloat16  # halve AG bytes on ICI
    monkeypatch.setenv("DEAR_BENCH_GATHER_DTYPE", "bf16")
    assert bench._gather_dtype(1) is jnp.bfloat16  # explicit override wins
    monkeypatch.setenv("DEAR_BENCH_GATHER_DTYPE", "fp32")
    assert bench._gather_dtype(8) is None
    monkeypatch.setenv("DEAR_BENCH_GATHER_DTYPE", "bogus")
    with pytest.raises(SystemExit, match="bogus"):
        bench._gather_dtype(1)


def test_bert_baseline_pin_on_first_capture(bench, monkeypatch, tmp_path):
    """The BERT pin must come from the EARLIEST BENCH_r*.json that carries
    a bert_base value (pin-on-first-capture), tolerating malformed files."""
    (tmp_path / "BENCH_r01.json").write_text("not json")
    (tmp_path / "BENCH_r02.json").write_text(json.dumps({
        "rc": 1, "parsed": None}))
    (tmp_path / "BENCH_r03.json").write_text(json.dumps({
        "parsed": {"metric": "resnet50_bs64_train_img_sec_per_chip",
                   "value": 2000.0,
                   "extra_metrics": [
                       {"metric": "bert_base_sen_sec_per_chip",
                        "value": 1111.0}]}}))
    (tmp_path / "BENCH_r04.json").write_text(json.dumps({
        "parsed": {"metric": "resnet50_bs64_train_img_sec_per_chip",
                   "value": 2300.0,
                   "extra_metrics": [
                       {"metric": "bert_base_sen_sec_per_chip",
                        "value": 2222.0}]}}))
    # _bert_baseline derives its directory from the module's __file__ —
    # patch that, not the process-global os.path.dirname
    monkeypatch.setattr(bench, "__file__", str(tmp_path / "bench.py"))
    # protocol tag follows the RESOLVED record's round, not a constant
    assert bench._bert_baseline() == (1111.0, "per-iter-fetch-r03")


def test_failed_phase_fails_the_run(bench, mesh, monkeypatch, capsys):
    """A phase that raises must end the run (traceback, non-zero exit from
    the interpreter) with NO contract line — the pre-PR-24 loop caught
    every phase after the first and still exited 0."""
    monkeypatch.setenv("DEAR_TELEMETRY", "0")  # leave the global tracer be
    monkeypatch.setattr(bench, "bench_resnet", lambda mesh: {
        "metric": bench.PRIMARY_METRIC, "value": 1.0, "unit": "img/s"})

    def boom(mesh, variant="bert_base"):
        raise RuntimeError("compile refused")

    monkeypatch.setattr(bench, "bench_bert", boom)
    with pytest.raises(RuntimeError, match="compile refused"):
        bench.main()
    assert capsys.readouterr().out.strip() == ""


def test_smoke_contract_one_json_line():
    """End-to-end: the smoke bench must emit EXACTLY one stdout line and it
    must parse as the contract object, primary metric first."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("DEAR_")}  # ambient knobs must not leak in
    env.update(
        JAX_PLATFORMS="cpu", DEAR_BENCH_SMOKE="1",
        DEAR_BENCH_BERT_LARGE="0", DEAR_BENCH_VIT="0",
        DEAR_DISABLE_DISTRIBUTED="1",
        # no cache entries into the checkout (see tests/conftest)
        JAX_ENABLE_COMPILATION_CACHE="0",
        PYTHONPATH=REPO + os.pathsep + env.get("PYTHONPATH", ""),
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        env=env, capture_output=True, text=True, timeout=480,
    )
    assert proc.returncode == 0, proc.stderr[-800:]
    lines = [l for l in proc.stdout.splitlines() if l.strip()]
    assert len(lines) == 1, lines
    out = json.loads(lines[0])
    assert out["metric"] == "resnet50_bs64_train_img_sec_per_chip"
    assert out["value"] > 0 and out["unit"] == "img/s"
    assert {m["metric"] for m in out["extra_metrics"]} == {
        "bert_base_sen_sec_per_chip", "gpt2_s1024_tok_sec_per_chip"}
    for m in out["extra_metrics"]:
        assert "error" not in m and m["value"] > 0, m
