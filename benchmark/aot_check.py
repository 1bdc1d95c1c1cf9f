"""Rehearsal without a chip: compile a configuration's device programs for a
described TPU v5e at the configuration's real length and batch, and print
what the compiler says they need.

    JAX_PLATFORMS=cpu python benchmark/aot_check.py --config resnet50-f32m6

Compiles what one round of a cell runs on the device: the two planar folds
the start-up race tries, the two packed folds production stages into, and
the unmask subtract. A program the v5e compiler refuses (layout, HBM, a
Mosaic lowering) raises here, in seconds, at no chip time. Nothing runs, so
this says nothing about results or times, and is never reported as a chip run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--batch", type=int, default=None, help="override batch_size")
    args = ap.parse_args(argv)

    from benchmark.harness import sizing
    from benchmark.harness.data import load_config

    cfg = load_config(args.config)
    k = args.batch or cfg["batch_size"]
    n, n_limbs, bpn = cfg["model_length"], cfg["n_limbs"], cfg["bytes_per_number"]

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, SingleDeviceSharding

    from xaynet_tpu.core.mask.config import BoundType, DataType, GroupType, MaskConfig, ModelType
    from xaynet_tpu.parallel import aggregator as agg_mod
    from xaynet_tpu.parallel.mesh import MODEL_AXIS

    jax.config.update("jax_enable_compilation_cache", False)
    m = cfg["mask"]
    mask = MaskConfig(GroupType[m["group_type"].upper()], DataType[m["data_type"].upper()],
                      BoundType[m["bound_type"].upper()], ModelType[m["model_type"].upper()])
    assert mask.bytes_per_number == bpn, (mask.bytes_per_number, bpn)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = topo.devices[0]
    agg = object.__new__(agg_mod.ShardedAggregator)
    agg.config, agg.order, agg.n_limbs = mask, mask.order, n_limbs
    agg.mesh = Mesh(np.asarray([dev]), (MODEL_AXIS,))
    agg.packed_width = bpn
    s = SingleDeviceSharding(dev)
    acc = jax.ShapeDtypeStruct((n_limbs, n), jnp.uint32, sharding=s)
    planar = jax.ShapeDtypeStruct((k, n_limbs, n), jnp.uint32, sharding=s)
    packed = jax.ShapeDtypeStruct((k, bpn, n), jnp.uint8, sharding=s)
    programs = [
        ("fold planar xla (race)", agg._make_fold_fn("xla"), (acc, planar)),
        ("fold planar pallas (race)", agg._make_fold_fn("pallas"), (acc, planar)),
        ("fold packed xla", agg._make_packed_fold_fn("xla"), (acc, packed)),
        ("fold packed pallas", agg._make_packed_fold_fn("pallas"), (acc, packed)),
        ("unmask", lambda a, b: agg_mod._unmask_kernel(a, b, mask.order), (acc, acc)),
    ]
    report = {"config": cfg["name"], "model_length": n, "batch_size": k,
              "device_kind": dev.device_kind, "programs": {}}
    for name, fn, specs in programs:
        mem = jax.jit(fn).lower(*specs).compile().memory_analysis()
        report["programs"][name] = {
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
        }
        print(f"{name}: args {mem.argument_size_in_bytes / 1e9:.2f} GB, "
              f"temp {mem.temp_size_in_bytes / 1e9:.2f} GB, "
              f"out {mem.output_size_in_bytes / 1e9:.2f} GB", flush=True)
    report["footprint"] = sizing.footprint(n, n_limbs, bpn, k)
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
