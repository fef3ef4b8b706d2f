"""perfbench's own tests: run by hand (`python -m pytest perfbench/tests -q
-p no:cacheprovider`), not part of tier-1. They rehearse the harness on the
CPU at tiny sizes; no number they produce is a device number."""

import os
import pathlib
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["DEAR_DISABLE_DISTRIBUTED"] = "1"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"  # no CPU entries for the chip

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[2]))

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)
