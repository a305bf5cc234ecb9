"""Compile every cell's step or round at full size for the chip, without
the chip.

    JAX_PLATFORMS=cpu python benchmark/rehearse.py [--workload NAME ...]
                                                   [--batch N] [--chips N]

libtpu is installed here, and it compiles for a chip that is described and
not attached (``v5e:2x2``).  For each cell of ``BENCHMARK.json`` this
builds the system under test on the CPU exactly as the cell's driver does,
then lowers its jitted train step (``Solver._step``) or its round
(``DistributedTrainer._round``, rebuilt over a mesh of the described
devices) with shapes in place of arrays, and runs the TPU compiler.  What
the compiler refuses here costs no chip time.  It prints the compile
seconds, the bytes the program needs on a device (arguments, outputs and
temporaries: what ``memory_peak_bytes`` will be near), the Pallas kernels
by name and the collectives in the compiled program.

Nothing runs, so it says nothing about results or times, and a compile
that passes is not a chip run.  ``--batch`` tries another batch a chip
than the configuration states, for sizing a cell against the memory floor.
"""

from __future__ import annotations

import argparse
import collections
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def report(name: str, lowered, t0: float) -> None:
    compiled = lowered.compile()
    took = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    text = compiled.as_text()
    kernels = collections.Counter(
        re.findall(r'kernel_name = "(\w+)"', lowered.as_text()))
    collectives = collections.Counter(re.findall(
        r"= \S+ (all-reduce|reduce-scatter|all-gather|all-to-all|"
        r"collective-permute)(?:-start)?\(", text))
    print(f"{name}: compiled for the chip in {took:.1f} s; per device "
          f"{need / 1e9:.2f} GB (arguments "
          f"{mem.argument_size_in_bytes / 1e9:.2f}, outputs "
          f"{mem.output_size_in_bytes / 1e9:.2f}, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f}, aliased "
          f"{mem.alias_size_in_bytes / 1e9:.2f}); kernels "
          f"{dict(kernels)}; collectives {dict(collectives)}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append")
    ap.add_argument("--batch", type=int)
    ap.add_argument("--chips", type=int, help="another number of chips "
                    "than the cell's: 1 is the round that x4_scaling_eff "
                    "compares with")
    args = ap.parse_args()

    import jax
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    # no cache: a compile for a described chip is written but cannot be
    # read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    # trace-time checks of the backend must take the chip's branch
    jax.default_backend = lambda: "tpu"

    from benchmark.lib import harness

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    spec = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    names = args.workload or [w["name"] for w in spec["workloads"]]

    def struct(tree, sharding):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                           sharding=sharding), tree)

    for name in names:
        cell = harness.resolve_cell(spec, name, seed=0)
        if args.batch:
            cell.config["batch"][cell.mix["compute_dtype"]] = args.batch
        if args.chips:
            cell.chips = args.chips
        driver = harness.load_driver(cell.mix).Driver(cell)
        raw = driver.raw_shape()
        t0 = time.perf_counter()
        if cell.mix["driver"] == "solver_steps":
            one = SingleDeviceSharding(topo.devices[0])
            solver = driver.make_solver()
            b = driver.batch
            batch = {"data": jax.ShapeDtypeStruct((1, b, *raw), np.uint8,
                                                  sharding=one),
                     "label": jax.ShapeDtypeStruct((1, b), np.float32,
                                                   sharding=one)}
            lowered = solver._step.lower(
                struct(solver.params, one), struct(solver.state, one), 0,
                batch, struct(jax.random.PRNGKey(0), one))
        elif cell.mix["driver"] == "trainer_rounds":
            n = cell.chips
            trainer, _ = driver.make_trainer(n)
            mesh = Mesh(np.asarray(topo.devices[:n]).reshape(n, 1),
                        trainer.mesh.axis_names)
            rep = NamedSharding(mesh, P())
            stacked = NamedSharding(mesh, trainer._state_tier()[1])
            feed = NamedSharding(mesh, trainer.input_sharding.spec)
            params, state = trainer.params, trainer.state
            # the round closes over these; as host arrays they become
            # constants of the program and not arrays on CPU devices
            trainer._lr_mults = jax.tree_util.tree_map(
                np.asarray, trainer._lr_mults)
            trainer._decay_mults = jax.tree_util.tree_map(
                np.asarray, trainer._decay_mults)
            trainer.mesh = mesh
            rounds = trainer._build_round()
            gb, rows = driver.batch * n, driver.tau * trainer.sp.iter_size
            batches = {
                "data": jax.ShapeDtypeStruct((rows, gb, *raw), np.uint8,
                                             sharding=feed),
                "label": jax.ShapeDtypeStruct((rows, gb), np.float32,
                                              sharding=feed)}
            lowered = rounds.lower(
                struct(params, rep), struct(state, stacked),
                jax.ShapeDtypeStruct((), np.int32, sharding=rep), batches,
                struct(jax.random.PRNGKey(0), rep),
                jax.ShapeDtypeStruct((), np.float32, sharding=rep))
        else:
            print(f"{name}: driver {cell.mix['driver']!r} has no rehearsal "
                  f"here", flush=True)
            continue
        report(name, lowered, t0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
