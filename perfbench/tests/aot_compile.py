"""Compile a cell's real step for a described (not attached) v5e, by hand:

    JAX_PLATFORMS=cpu python perfbench/tests/aot_compile.py <workload> [batch_per_chip ...]

Prints, for each per-chip batch (default: the traffic file's), what the TPU
compiler makes of the step: whether it fits, `memory_analysis()` on one
device, the collectives kept, the compile seconds here. The workload's
config and traffic are resolved as `run.py` resolves them; the workload may
also be given as ``<config>:<traffic>`` for a cell not yet in
BENCHMARK.json. A compile that passes is a compile, never a run.
"""

import os
import pathlib
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402

from perfbench import cell as cells  # noqa: E402
from perfbench import harness  # noqa: E402


def describe(cell, batch_per_chip, topo):
    from dear_pytorch_tpu.parallel.dear import DearState

    mesh = Mesh(np.array(topo.devices[:cell.chips]), ("dp",))
    fam, model, train = cell.family, cell.config["model"], cell.config["train"]
    seq = cell.traffic["seq_len"]
    cfg = fam.model_config(model, harness.DTYPES[train["compute_dtype"]])
    dropout_seed = train["dropout_seed"]
    init_fn, loss_fn = fam.make_loss(cfg, with_rng=dropout_seed is not None)
    params = jax.eval_shape(lambda k: init_fn(k, seq), jax.random.PRNGKey(0))
    ts = harness.train_step(cell, loss_fn, params, mesh,
                            dropout_seed=dropout_seed,
                            comm_dtype=harness.DTYPES[train["comm_dtype"]])

    def on(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    sizes = [b.padded_size for b in ts.plan.buckets]
    momentum = train["momentum"] != 0.0
    state = DearState(
        buffers=tuple(on((n,), jnp.float32, jax.P("dp")) for n in sizes),
        opt_state=tuple(
            (on((n,), jnp.float32, jax.P("dp")), on((), jnp.bool_, jax.P()))
            if momentum else () for n in sizes),
        step=on((), jnp.int32, jax.P()))
    batch = {k: on(shape, dtype, jax.P("dp")) for k, (shape, dtype) in
             fam.batch_shapes(model, batch_per_chip * cell.chips,
                              seq).items()}
    t = time.perf_counter()
    compiled = ts.lower(state, batch).compile()
    secs = time.perf_counter() - t
    m = compiled.memory_analysis()
    print(f"{cell.name} batch/chip {batch_per_chip}: compiled in {secs:.0f} s"
          f" (here); program {harness.peak_hbm_bytes(compiled) / 1e9:.3f} GB"
          f" = arguments {m.argument_size_in_bytes / 1e9:.3f} + output "
          f"{m.output_size_in_bytes / 1e9:.3f} + temp "
          f"{m.temp_size_in_bytes / 1e9:.3f} + code "
          f"{m.generated_code_size_in_bytes / 1e9:.3f} - alias "
          f"{m.alias_size_in_bytes / 1e9:.3f}; collectives "
          f"{harness.count_collectives(compiled.as_text())}", flush=True)


def main(argv):
    from jax.experimental import topologies

    name, batches = argv[0], [int(b) for b in argv[1:]]
    if ":" in name:
        config, traffic = name.split(":")
        bench = {"workloads": [{"name": name, "config": config,
                                "traffic": traffic,
                                "chips": cells.load_json(
                                    cells.HERE / "traffic" /
                                    f"{traffic}.json")["chips"]}],
                 "end_to_end": [], "per_layer": []}
        cell = cells.resolve(name, bench)
    else:
        cell = cells.resolve(name)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for b in batches or [cell.traffic["batch_per_chip"]]:
        try:
            describe(cell, b, topo)
        except Exception as e:  # the compiler's refusal is the result
            print(f"{cell.name} batch/chip {b}: REFUSED: "
                  f"{str(e).splitlines()[0][:300]}", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
