#!/usr/bin/env python3
"""Compile both step twins of every cell's configuration at the real sizes
for a described v5e chip, with no chip attached (on-chip-measurement guide,
section 2, rehearsal 3), and print what each needs in device memory.

    JAX_PLATFORMS=cpu python benchmark/tests/aot_sizes.py [cell ...]

A compile and not a chip run: it says the chip's compiler accepts the
program and what one program needs, never what else the process holds.
Run by hand before a chip call (a few minutes); not a tier-1 test.
"""

import glob
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))

GIB = 1024**3


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    from dlrover_tpu.accel.dry_runner import _build
    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.models.config import TransformerConfig
    from dlrover_tpu.models.train import batch_sharding
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.trainer.elastic.trainer import build_optimizer

    fa = importlib.import_module("dlrover_tpu.ops.flash_attention")
    # code that asks the backend sees the CPU here: steer it, in this
    # script and not through an option of the program
    fa._interpret_default = lambda: False
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    names = argv or sorted(
        os.path.basename(p)[:-5]
        for p in glob.glob(os.path.join(BENCH, "cells", "*.json"))
    )
    seen = set()
    for name in names:
        with open(os.path.join(BENCH, "cells", f"{name}.json")) as f:
            cell = json.load(f)
        key = (cell["config"], cell["batch"], cell["seq"])
        if key in seen:
            continue
        seen.add(key)
        with open(
            os.path.join(BENCH, "configs", f"{cell['config']}.json")
        ) as f:
            config = json.load(f)
        strat = config["strategy"]
        strategy = Strategy(
            mesh=MeshConfig(**strat.get("mesh", {})),
            **{k: v for k, v in strat.items() if k != "mesh"},
        )
        opt = dict(config["optimizer"])
        tx = build_optimizer(opt.pop("name"), **opt)
        for donate in (True, False):
            _, mesh, step_fn, _, _, abstract_state = _build(
                strategy, TransformerConfig(**config["model"]), tx,
                topo.devices[: cell.get("chips", 1)],
                donate=donate, donate_inputs=donate,
            )
            x = jax.ShapeDtypeStruct(
                (cell["batch"], cell["seq"]), jnp.int32,
                sharding=batch_sharding(mesh),
            )
            lowered = step_fn.lower(abstract_state(), x, x)
            assert "tpu_custom_call" in lowered.as_text(), "kernel is out"
            ma = lowered.compile().memory_analysis()
            need = (
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes
            )
            print(json.dumps({
                "config": cell["config"], "batch": cell["batch"],
                "seq": cell["seq"],
                "step": "donating" if donate else "safe (non-donating)",
                "needs_gib": round(need / GIB, 2),
                "arguments_gib": round(ma.argument_size_in_bytes / GIB, 2),
                "temp_gib": round(ma.temp_size_in_bytes / GIB, 2),
            }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
