"""`chip_smoke.py`'s phases at tiny sizes on the CPU mesh (rehearsals 1 and
2 of the on-chip-measurement guide), its refusal to run without a TPU, where
the compilation cache lands, and that the parents which spawn benchmark
children never hold a device themselves."""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from dear_pytorch_tpu.comm import backend

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny(dtype, layers=2):
    return dataclasses.replace(
        chip_smoke.gpt2_config(dtype, layers), hidden_size=64,
        num_attention_heads=4, intermediate_size=128, vocab_size=128,
        max_position_embeddings=64)


def _mesh(n):
    return jax.sharding.Mesh(np.array(jax.devices()[:n]), ("dp",))


@pytest.fixture(scope="module")
def dense():
    return chip_smoke.phase_train(_mesh(1), _tiny(jnp.bfloat16),
                                  batch_size=4, seq_len=64, steps=6, seed=0)


def test_train_phase_learns_on_one_device(dense):
    losses = dense["losses"]
    assert len(losses) == 6 and np.all(np.isfinite(losses))
    assert losses[-1] < losses[0]
    assert dense["peak_bytes"] is None  # the CPU backend reports no stats


def test_reference_phase_dear_equals_plain_sgd():
    res = chip_smoke.phase_reference(_mesh(1), _tiny(jnp.float32),
                                     batch_size=4, seq_len=64, steps=6,
                                     seed=0)
    assert res["max_diff"] <= 1e-5  # f32 on the CPU: agreement is near exact
    with pytest.raises(AssertionError, match="diverged from plain SGD"):
        chip_smoke.phase_reference(_mesh(1), _tiny(jnp.float32),
                                   batch_size=4, seq_len=64, steps=3,
                                   seed=0, atol=-1.0)


def test_flash_phase_agrees_with_dense_and_reports_the_kernel(dense):
    res = chip_smoke.phase_flash(_mesh(1), _tiny(jnp.bfloat16),
                                 batch_size=4, seq_len=64, steps=4, seed=0,
                                 dense=dense, grouped=((1, 256, 8, 64), 2))
    assert max(res["kernel_errors"].values()) < chip_smoke.FLASH_TOL
    # the grouped kernels (8 Q heads over 2 K/V heads), against the dense
    # program one K/V head at a time
    assert set(res["grouped_kernel_errors"]) == {"out", "dq", "dk", "dv"}
    assert max(res["grouped_kernel_errors"].values()) < chip_smoke.FLASH_TOL
    assert chip_smoke.GROUPED_SHAPE == ((1, 8192, 32, 64), 8)
    assert abs(res["losses"][0] - dense["losses"][0]) < 1e-2
    # interpret mode here: the fact main() insists on is reported, as False
    assert res["kernel_in_program"] is False


def test_dropout_phase_checks_values_and_the_applied_mask(monkeypatch):
    """The dropout phase at a tiny shape (two strips a tile): kernel and
    dense program agree under the same mask in bf16 and f32, the applied
    keep counts are the dense mask's, and a kernel that kept everything
    would be caught."""
    res = chip_smoke.phase_dropout((2, 128, 2, 64), seed=0)
    assert max(res["errors"]["bfloat16"].values()) < chip_smoke.FLASH_TOL
    assert max(res["errors"]["float32"].values()) < 1e-4
    assert abs(res["keep_share"] - 0.9) < 0.01
    fa = sys.modules["dear_pytorch_tpu.ops.flash_attention"]
    monkeypatch.setattr(
        fa, "_keep_strip",
        lambda seed, b, h, r, c, shape, rate: jnp.ones(shape, jnp.bool_))
    jax.clear_caches()
    got, want = chip_smoke.applied_keep_counts((1, 128, 2, 64), seed=0)
    assert (got == 128).all() and (want < 128).any()
    with pytest.raises(AssertionError, match="under the same mask"):
        chip_smoke.phase_dropout((1, 128, 2, 64), seed=0)
    jax.clear_caches()


def test_dp_phase_spreads_the_work_over_four_devices():
    res = chip_smoke.phase_dp(_mesh(4), _tiny(jnp.bfloat16), global_batch=8,
                              seq_len=64, steps=6, seed=0)
    assert res["max_diff"] <= chip_smoke.DP_ATOL
    assert res["dear"][-1] < res["dear"][0]
    assert res["allreduce"][-1] < res["allreduce"][0]


def test_dp_phase_refuses_an_unspread_buffer():
    one, four = _mesh(1), _mesh(4)
    x = jax.device_put(jnp.zeros(8), jax.sharding.NamedSharding(one, jax.P()))
    with pytest.raises(AssertionError, match="lives on 1 of 4 devices"):
        chip_smoke._check_spread({"x": x}, four, "batch")


def test_count_collectives_reads_optimized_hlo():
    text = """
  %ag = f32[4,8]{1,0} all-gather(%p), channel_id=1
  %ags = (f32[8], f32[32]) all-gather-start(%p), channel_id=2
  %agd = f32[32] all-gather-done(%ags)
  %ar = (bf16[8], bf16[8]) all-reduce(%a, %b), to_apply=%add
  %rs = f32[2] reduce-scatter(%g), dimensions={0}
  %x = f32[] add(%reduce_scatter.1, %reduce_scatter.2)
"""
    assert chip_smoke.count_collectives(text) == {
        "all-gather": 2, "all-reduce": 1, "reduce-scatter": 1}


def test_smoke_refuses_to_run_without_a_tpu():
    """Under JAX_PLATFORMS=cpu the script exits non-zero and prints no
    result line — before building any model, so this is cheap."""
    proc = subprocess.run([sys.executable, os.path.join(REPO,
                                                        "chip_smoke.py")],
                          capture_output=True, text=True, timeout=300,
                          cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("env_dir", ["/x", None])
def test_compilation_cache_placement(monkeypatch, restore_cache_dir, env_dir):
    """JAX_COMPILATION_CACHE_DIR set: the code sets no directory at all
    (JAX reads the variable itself). Unset: one fixed in-checkout path."""
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    monkeypatch.delenv("DEAR_NUM_CPU_DEVICES", raising=False)
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    backend._apply_platform_env()
    want = (os.path.join(REPO, ".jax_cache") if env_dir is None
            else "sentinel")
    assert jax.config.jax_compilation_cache_dir == want


def test_cache_entries_land_where_the_environment_says(tmp_path):
    code = (
        "import os, jax, jax.numpy as jnp\n"
        "from dear_pytorch_tpu.comm import backend\n"
        "backend.init()\n"
        "assert jax.config.jax_compilation_cache_dir == "
        "os.environ['JAX_COMPILATION_CACHE_DIR']\n"
        "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64)))"
        ".block_until_ready()\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "x"),
               JAX_ENABLE_COMPILATION_CACHE="1",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=300, cwd=str(tmp_path))
    assert any("jit__lambda" in f for f in os.listdir(tmp_path / "x"))


# A chip belongs to one process: a parent that initialised a backend would
# hold it, and the children it spawns would fail or hang. Each parent below
# runs its real spawn path with the process launch replaced by a probe.
_PARENTS = r"""
import os, subprocess, sys
from jax._src import xla_bridge

tmp = sys.argv[1]
spawned = []


class Done:
    returncode, stdout, stderr, pid = 0, "", "", 1

    def poll(self):
        return 0


def probe(cmd, *args, **kwargs):
    spawned.append((os.path.basename(str(cmd[1])),
                    xla_bridge.backends_are_initialized()))
    return Done()


subprocess.run = subprocess.Popen = probe
sys.path[:0] = [os.path.join(os.getcwd(), d) for d in ("scripts", "launch")]

from dear_pytorch_tpu.benchmarks import driver
driver.main(["--logdir", os.path.join(tmp, "logs"), "--tasks", "mnistnet:4",
             "--methods", "dear", "--emulate", "--nworkers", "2"])

import sweep_common
sweep_common.run_sweep("gpt_sweep.py", ["base"],
                       os.path.join(tmp, "sweep", "out.json"), timeout=5)

import conv_sweep
sys.argv = ["conv_sweep.py", "--configs", "base",
            "--out", os.path.join(tmp, "conv", "out.json")]
conv_sweep.main()

import supervisor
supervisor.ElasticSupervisor(
    2, [sys.executable, "worker.py"], elastic_dir=os.path.join(tmp, "el"),
    env=dict(os.environ, DEAR_SDC="1"), log=lambda s: None).start()

assert len(spawned) == 5, spawned
assert not any(live for _, live in spawned), spawned
assert not xla_bridge.backends_are_initialized()
print("PARENTS_OK", [name for name, _ in spawned])
"""


def test_spawning_parents_hold_no_device(tmp_path):
    proc = subprocess.run(
        [sys.executable, "-c", _PARENTS, str(tmp_path)], cwd=REPO,
        capture_output=True, text=True, timeout=300,
        env=dict(os.environ, PYTHONPATH=REPO + os.pathsep
                 + os.environ.get("PYTHONPATH", "")))
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "PARENTS_OK" in proc.stdout
