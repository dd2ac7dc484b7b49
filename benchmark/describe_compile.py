"""Compile a cell's device programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 benchmark/describe_compile.py --workload <name>

For each program the cell runs (the registry widening and the chain's
h2c / prep / pair stages at every shape its traffic forms) it prints the
compile seconds and ``memory_analysis()``: what the chip's compiler would
refuse, at no chip time. Nothing runs, so nothing here is a device time.
"""

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def shapes(config: dict) -> list[tuple[int, int]]:
    """(n_pad, k_pad) of every batch the cell's traffic forms."""
    from lighthouse_tpu.bls.tpu_backend import bucket

    keys = config["validators"] // (config["slots_per_epoch"]
                                    * config["committees_per_slot"])
    return [(bucket(config["sets_per_batch"]), bucket(keys))]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--widen", action="store_true",
                    help="also compile the registry widening")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[0] = ROOT

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import lighthouse_tpu  # noqa: F401 — x64
    from benchmark import harness, keys
    from lighthouse_tpu.bls import tpu_backend as tb

    jax.config.update("jax_enable_compilation_cache", False)
    _, _, config, _ = harness.cell_files(args.workload)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    m = int(config["validators"] ** 0.5)
    base = jax.ShapeDtypeStruct((m, 3, 25), jnp.uint64, sharding=one_chip)
    def programs():
        if args.widen:
            yield "widen", jax.jit(keys.widen).lower(base, base)
        for n_pad, k_pad in shapes(config):
            for name, lowered in tb.stage_lowerings(
                    n_pad, k_pad, config["validators"], sharding=one_chip):
                yield f"{name} n_pad={n_pad} k_pad={k_pad}", lowered

    for name, lowered in programs():
        t0 = time.perf_counter()
        ma = lowered.compile().memory_analysis()
        print(f"{name}: {time.perf_counter() - t0:.1f} s; bytes: args "
              f"{ma.argument_size_in_bytes}, out {ma.output_size_in_bytes}, temp "
              f"{ma.temp_size_in_bytes}, code {ma.generated_code_size_in_bytes}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
