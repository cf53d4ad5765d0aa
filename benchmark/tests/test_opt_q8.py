"""The benchmark's test of the reader PR 28 brought,
``opt.q8_tiles_share``, run by hand beside ``test_benchmark.py`` (which
holds ``BENCHMARK.json`` and every reader in agreement, this one too):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

The reader on hand-made runs, the cells it belongs to, and the counter as
a real trainer sets it at a toy size. Nothing here is a speed.
"""

import dataclasses
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402

NAME = "opt.q8_tiles_share"


def _run(pipeline):
    return SimpleNamespace(window={"pipeline_open": {}, "pipeline": pipeline})


@pytest.mark.parametrize("pipeline,want", [
    ({"opt_q8_tiles_elems": 2090000000, "opt_q8_blocks_elems": 0}, 100.0),
    ({"opt_q8_tiles_elems": 3 * 4096, "opt_q8_blocks_elems": 4096}, 75.0),
    ({"opt_q8_tiles_elems": 0, "opt_q8_blocks_elems": 8192}, 0.0),
    # fp32 moments, a program without the counters (the parent), no window
    ({"opt_q8_tiles_elems": 0, "opt_q8_blocks_elems": 0}, None),
    ({"steps_ahead": 7}, None),
    ({}, None),
    (None, None),
])
def test_reader_on_hand_made_runs(pipeline, want):
    read = harness.load_layer_metrics()[NAME].read
    assert read(_run(pipeline)) == want


def test_its_cells_are_the_ones_with_int8_moments():
    mod = harness.load_layer_metrics()[NAME]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": mod.UNIT, "better": "higher",
        "source": "program_counter", "layer": mod.LAYER,
        "moves": mod.MOVES, "workloads": ["olmoe-1b-7b-d2.steady"],
    }
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        int8 = harness.load_config(cell["config"])["optimizer"][
            "name"].startswith("adamw_8bit")
        assert mod.CELLS(cell) is int8
        assert int8 is (w["name"] in entry["workloads"])
    # a rehearsal's cell names a configuration of its own data
    # directory: left to read()
    assert mod.CELLS({"config": "toy-olmoe"}) is True


@pytest.mark.parametrize("optimizer", ["adamw_8bit", "adamw"])
def test_the_counter_as_a_trainer_sets_it(optimizer):
    """``worker.py`` writes ``dataclasses.asdict(PipelineStats)`` as the
    window's ``pipeline``; a toy trainer's, with and without int8
    moments, through the reader. The toy's attention leaves are
    ``[128, 2, 64]``, not whole (8, 128) tiles: they stay in blocks."""
    import jax
    import numpy as np

    from dlrover_tpu.accel.strategy import Strategy
    from dlrover_tpu.models.config import TransformerConfig
    from dlrover_tpu.parallel.mesh import MeshConfig
    from dlrover_tpu.trainer.elastic.trainer import (
        ElasticTrainer,
        TrainerConfig,
        build_optimizer,
    )

    class Toks:
        def __len__(self):
            return 64

        def __getitem__(self, i):
            t = np.full((17,), i % 50, np.int32)
            return t[:-1], t[1:]

    trainer = ElasticTrainer(
        model_cfg=TransformerConfig(
            vocab_size=256, num_layers=1, model_dim=128, num_heads=2,
            mlp_dim=256, max_seq_len=16,
        ),
        tx=build_optimizer(
            optimizer, lr=1e-3, **(
                {"min_quantized_size": 4096, "use_pallas": False}
                if optimizer == "adamw_8bit" else {}
            )
        ),
        dataset=Toks(),
        trainer_cfg=TrainerConfig(
            batch_size=8, seq_len=16, report_metrics=False
        ),
        # every device the process has: one here, eight under tests/'s
        # conftest
        strategy=Strategy(
            mesh=MeshConfig(dp=jax.device_count()), dtype="float32"
        ),
    )
    pipeline = dataclasses.asdict(trainer.pipeline_stats)
    read = harness.load_layer_metrics()[NAME].read
    if optimizer == "adamw":
        assert read(_run(pipeline)) is None
        return
    tiles = blocks = 0
    for leaf in jax.tree.leaves(trainer.state.params):
        if leaf.size < 4096:
            continue  # fp32 moments
        whole = leaf.ndim >= 2 and not (
            leaf.shape[-1] % 128 or leaf.shape[-2] % 8
        )
        tiles += 2 * leaf.size * whole  # both moments
        blocks += 2 * leaf.size * (not whole)
    assert tiles and blocks
    assert pipeline["opt_q8_tiles_elems"] == tiles
    assert pipeline["opt_q8_blocks_elems"] == blocks
    assert read(_run(pipeline)) == 100.0 * tiles / (tiles + blocks)
