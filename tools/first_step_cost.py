"""What the first call of a benchmark cell's donating train step costs
before the chip runs it, leg by leg: trace (Python), lower (Python and
MLIR), and compile-or-load (XLA's compile on a cold cache, or the
persistent cache's entry read, deserialised and loaded onto the chip on a
warm one), with the bytes of the entry the step left in the compile cache.

The step is built as the trainer builds it (``auto_accelerate`` with the
configuration's strategy and optimizer at the cell's batch) and given
abstract arguments: nothing is allocated and nothing runs, so what is left
of a cell's ``startup.first_step_s`` beside these legs is the step's own
run. Run it twice in one call on the chip, with one cache directory: the
first process compiles and writes the entry, the second finds it.

    PYTHONPATH=. python tools/first_step_cost.py \\
        nemotron3-nano-30b-a3b-d9.steady /tmp/cache

One JSON line: ``trace_s``, ``lower_s``, ``compile_or_load_s``,
``cache_hits`` / ``cache_misses`` of the compile, ``entry_bytes`` (the
largest file the compile wrote, or on a warm start the largest in the
directory), ``q8_kernel_calls`` and ``q8_kernel_functions`` (call sites of
the one-pass int8-AdamW kernel in the lowered text, and the functions they
share), ``mosaic_calls`` (every ``tpu_custom_call`` of the text).
"""

import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        return json.load(f)


def _files(directory):
    out = {}
    for name in os.listdir(directory) if os.path.isdir(directory) else []:
        path = os.path.join(directory, name)
        if os.path.isfile(path):
            out[name] = os.path.getsize(path)
    return out


def main(argv):
    cell_name, cache_dir = argv
    cell = _json("cells", f"{cell_name}.json")
    config = _json("configs", f"{cell['config']}.json")
    os.makedirs(cache_dir, exist_ok=True)

    import jax
    import jax.numpy as jnp

    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from dlrover_tpu.accel.accelerate import auto_accelerate
    from dlrover_tpu.accel.profiler import compile_meter
    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.models.config import TransformerConfig
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.trainer.elastic.optimizer import build_optimizer

    meter = compile_meter()
    meter.install()
    opt = dict(config["optimizer"])
    strat = config["strategy"]
    batch, seq = int(cell["batch"]), int(cell["seq"])
    accel = auto_accelerate(
        TransformerConfig(**config["model"]),
        build_optimizer(opt.pop("name"), **opt),
        batch=batch, seq=seq, devices=jax.devices()[:1],
        strategy=Strategy(
            mesh=MeshConfig(**strat.get("mesh", {})),
            **{k: v for k, v in strat.items() if k != "mesh"},
        ),
        donate=False,
    )
    state = jax.eval_shape(accel.init_fn, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    held = _files(cache_dir)
    before = dict(meter.totals)
    t0 = time.perf_counter()
    traced = accel.donating_step_fn.trace(state, x, x)
    t1 = time.perf_counter()
    lowered = traced.lower()
    t2 = time.perf_counter()
    lowered.compile()
    t3 = time.perf_counter()
    text = lowered.as_text()
    wrote = {
        name: size for name, size in _files(cache_dir).items()
        if name not in held
    }
    dev = jax.devices()[0]
    print(json.dumps({
        "cell": cell_name,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "trace_s": round(t1 - t0, 3),
        "lower_s": round(t2 - t1, 3),
        "compile_or_load_s": round(t3 - t2, 3),
        "cache_hits": meter.totals["cache_hits"] - before["cache_hits"],
        "cache_misses": (
            meter.totals["cache_misses"] - before["cache_misses"]
        ),
        "entry_bytes": max((wrote or held or {"": 0}).values()),
        "q8_kernel_calls": len(re.findall(r"call @_q8_adam_step", text)),
        "q8_kernel_functions": len(
            re.findall(r"func\.func private @_q8_adam_step", text)
        ),
        "mosaic_calls": text.count("tpu_custom_call"),
        "lowered_text_bytes": len(text),
    }))


if __name__ == "__main__":
    main(sys.argv[1:])
