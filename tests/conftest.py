"""Test configuration: emulate an 8-device TPU slice on the CPU backend.

The reference had no fake-cluster story — multi-node behavior was only
testable on a real 16×4-GPU cluster under mpirun (SURVEY.md §4). The XLA CPU
backend gives us a true multi-device world on one host: real ReduceScatter /
AllGather / AllReduce semantics, deterministic, CI-friendly.

Must run before any `import jax` in the test process.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DEAR_DISABLE_DISTRIBUTED"] = "1"  # the suite is single-process
# No persistent compilation cache in the suite (JAX's own switch, inherited
# by every subprocess a test spawns): its default home is inside the
# checkout, and the chip tool copies the tree as it stands on disk — CPU
# entries written by tests would ride along on every chip call.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

import jax  # noqa: E402

# A config update, not XLA_FLAGS: it stays out of os.environ, so
# subprocess-spawning tests (bench smoke, examples, multiprocess clusters)
# do not inherit an 8-device world they never asked for.
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh():
    """Global 1-D data-parallel mesh over the 8 emulated devices."""
    from dear_pytorch_tpu.comm import backend

    m = backend.init()
    yield m


@pytest.fixture(scope="session")
def world(mesh):
    return mesh.shape["dp"]


@pytest.fixture
def rng():
    import numpy as np

    return np.random.default_rng(10)  # seed mirrors test_comm.py:6
